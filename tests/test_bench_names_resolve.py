"""Every name a configuration, a mix or a metric's reader under ``bench/``
holds resolves to a file: a family, a tokenizer kind, a warm-up recipe, a
reference, a check, a generator, a reducer; and every cell, configuration and
per-layer metric of ``BENCHMARK.json`` to its files.  The tier-1 copy of
``bench/tests/test_names_resolve.py`` (PERF.md, PR 26's question 16a): a
benchmark file added without what it names fails here, on every PR, and
nothing here touches jax or the benchmark's own modules."""

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
