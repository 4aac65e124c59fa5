"""Every name a configuration, a mix or a metric's reader holds resolves to a
file: a family, a tokenizer kind, a warm-up recipe, a reference, a check, a
generator, a reducer.  A case per file under ``bench/configs``,
``bench/traffic`` and ``bench/layer_metrics``; and ``BENCHMARK.json``'s
``per_layer`` says each thing once (PR 48): a case for every (entry, cell)
pair and a case an entry.  Nothing here touches jax."""

import glob
import json
import os

import pytest

import byname

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def files(directory):
    return sorted(glob.glob(os.path.join(BENCH, directory, "*.json")))


def load(path):
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def assert_file(directory, name):
    assert byname.NAME.match(name), name
    assert os.path.isfile(byname.path_of(directory, name)), f"bench/{directory}/{name}.py"


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
    assert_file("generators", load(path)["generator"])


@pytest.mark.parametrize("path", files("layer_metrics"), ids=os.path.basename)
def test_a_trace_metric_names_its_reducer(path):
    read = load(path)["read"]
    if read["from"] == "trace":
        assert_file("reducers", read["reducer"])


BENCHMARK = load(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json"))
PER_LAYER = BENCHMARK["per_layer"]
CELLS = [cell["name"] for cell in BENCHMARK["workloads"]]
# copies PR 48 found and could not merge yet (PERF.md, question 34: tier-1
# counts a case an entry, so a benchmark PR can shorten the list by nine): a
# name leaves this set with its twin, and none may enter it
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


def test_a_name_that_is_no_file_is_refused():
    with pytest.raises(byname.BenchError, match="no file bench/families/"):
        byname.module("families", "never-met")
    with pytest.raises(byname.BenchError, match="not a name"):
        byname.module("families", "../run")


def test_a_generator_gives_what_the_harness_takes():
    for path in files("traffic"):
        gen = byname.module("generators", load(path)["generator"])
        for name in ("PATH", "KEEP", "generate", "warm_sample", "request_tokens",
                     "render_body", "well_formed"):
            assert hasattr(gen, name), name
        assert gen.PATH.startswith("/") and all(isinstance(k, str) for k in gen.KEEP)
