"""Every name a configuration, a mix or a metric's reader under ``bench/``
holds resolves to a file: a family, a tokenizer kind, a warm-up recipe, a
reference, a check, a generator, a reducer; and every cell, configuration and
per-layer metric of ``BENCHMARK.json`` to its files.  The tier-1 copy of
``bench/tests/test_names_resolve.py`` (PERF.md, PR 26's question 16a): a
benchmark file added without what it names fails here, on every PR, and
nothing here touches jax or the benchmark's own modules.

``per_layer`` says each thing once (PR 48), and since PR 49 tier-1 holds that
a case an (entry, cell) PAIR, as the benchmark's own copy does: a merge of two
copies into one entry with both cells in its ``workloads`` keeps every pair and
so costs no pass (PERF.md, question 34).  The per-entry and per-file cases
stay."""

import glob
import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def files(directory):
    return sorted(glob.glob(os.path.join(BENCH, directory, "*.json")))


def load(path):
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def assert_file(directory, name, suffix=".py"):
    assert NAME.match(name), name
    path = os.path.join(BENCH, directory, name + suffix)
    assert os.path.isfile(path), f"bench/{directory}/{name}{suffix}"


@pytest.mark.parametrize("path", files("configs"), ids=os.path.basename)
def test_a_configuration_names_files(path):
    config = load(path)
    assert_file("families", config["family"])
    assert_file("tokenizers", config["tokenizer"]["kind"])
    assert_file("references", config["reference"])
    assert_file("checks", config["check"]["name"])
    serve = config["serve"]
    assert {"weights_env", "vocab_env", "param_dtype"} <= set(serve)
    if "warmup" in serve:
        assert_file("warmups", serve["warmup"])
    assert set(config["dry_run"]["sizes"]) <= set(config)


@pytest.mark.parametrize("path", files("traffic"), ids=os.path.basename)
def test_a_mix_names_its_generator(path):
    mix = load(path)
    assert_file("generators", mix["generator"])
    assert "warm_groups" in mix and mix["loop"] in ("open", "closed")


@pytest.mark.parametrize("path", files("layer_metrics"), ids=os.path.basename)
def test_a_metric_names_its_reader(path):
    read = load(path)["read"]
    assert read["from"] in ("metrics", "trace", "window")
    if read["from"] == "trace":
        assert_file("reducers", read["reducer"])


BENCHMARK = load(os.path.join(ROOT, "BENCHMARK.json"))


@pytest.mark.parametrize("cell", BENCHMARK["workloads"], ids=lambda c: c["name"])
def test_a_cell_names_its_configuration_and_mix(cell):
    config = next(c for c in BENCHMARK["configs"] if c["name"] == cell["config"])
    assert os.path.isfile(os.path.join(ROOT, config["file"]))
    assert load(os.path.join(ROOT, config["file"]))["reduced"] == config["reduced"]
    assert_file("traffic", cell["traffic"], ".json")
    assert cell["chips"] in (1, 4) and len(cell["why"]) <= 200
    reported = [
        m["name"] for m in BENCHMARK["end_to_end"]
        if "workloads" not in m or cell["name"] in m["workloads"]
    ]
    assert "setup_s" in reported and len(reported) >= 2
    assert any(cell["name"] in m.get("workloads", [cell["name"]]) for m in BENCHMARK["per_layer"])


@pytest.mark.parametrize("metric", BENCHMARK["per_layer"], ids=lambda m: m["name"])
def test_a_per_layer_metric_has_its_file_and_its_cells(metric):
    assert_file("layer_metrics", metric["name"], ".json")
    cells = {c["name"] for c in BENCHMARK["workloads"]}
    moved = next(m for m in BENCHMARK["end_to_end"] if m["name"] == metric["moves"])
    for name in metric.get("workloads", []):
        assert name in cells
        assert "workloads" not in moved or name in moved["workloads"]


PER_LAYER = BENCHMARK["per_layer"]
CELLS = [cell["name"] for cell in BENCHMARK["workloads"]]
# copies PR 48 found and could not merge yet (PERF.md, question 34): a name
# leaves this set with its twin, and none may enter it
COPIES_LEFT = {
    *(f"experts.held_pairs_share.{s}" for s in ("qnext", "glm5", "dots3", "trinity")),
    *(f"kernel.expert_products_roofline.{s}" for s in ("qnext", "glm5", "dots3", "trinity")),
    *(f"dispatch.device_ms.{s}" for s in ("judge", "qnext", "glm5", "dots3", "trinity")),
    *(f"window.band_share.{s}" for s in ("dots3", "trinity", "phi4flash")),
    *(f"{name}.{s}" for s in ("glm5", "dots3") for name in (
        "index.selected_share", "kernel.index_scores_roofline",
        "kernel.index_select_roofline", "kernel.selected_attention_roofline")),
    "forward.mfu.closed", "forward.mfu.judge",
}


def cells_of(metric):
    return metric.get("workloads", CELLS)


RECIPES = {
    metric["name"]: json.dumps(
        load(os.path.join(BENCH, "layer_metrics", metric["name"] + ".json"))["read"],
        sort_keys=True,
    )
    for metric in PER_LAYER
}


def recipe(metric):
    return RECIPES[metric["name"]]


@pytest.mark.parametrize(
    "metric, cell",
    [(metric, cell) for metric in PER_LAYER for cell in cells_of(metric)],
    ids=lambda v: v["name"] if isinstance(v, dict) else v,
)
def test_a_cell_reads_a_recipe_under_one_name(metric, cell):
    """The cell exists and reports the end-to-end metric the entry moves, and
    no other entry that lists the cell holds the same recipe."""
    assert cell in CELLS
    moved = next(m for m in BENCHMARK["end_to_end"] if m["name"] == metric["moves"])
    assert cell in cells_of(moved)
    twice = [
        other["name"] for other in PER_LAYER
        if other is not metric and cell in cells_of(other) and recipe(other) == recipe(metric)
    ]
    assert not twice, f"{cell} reads {recipe(metric)} as {metric['name']} and as {twice}"


@pytest.mark.parametrize("metric", PER_LAYER, ids=lambda m: m["name"])
def test_a_recipe_with_one_moves_is_one_entry(metric):
    """The guard that sends the next configuration's cell to an entry's
    ``workloads`` list and not to a copy of its file under a suffix."""
    twins = [
        other["name"] for other in PER_LAYER
        if other is not metric and other["moves"] == metric["moves"]
        and recipe(other) == recipe(metric)
    ]
    if metric["name"] in COPIES_LEFT:
        assert twins, f"{metric['name']} has no twin any more: take it out of COPIES_LEFT"
        assert set(twins) <= COPIES_LEFT
    else:
        assert not twins, f"add the cell to {twins}' `workloads` instead"


def test_the_list_holds_no_more_than_it_may():
    assert len(PER_LAYER) <= 128
    assert len({metric["name"] for metric in PER_LAYER}) == len(PER_LAYER)
    assert COPIES_LEFT <= {metric["name"] for metric in PER_LAYER}
