"""Every name a configuration, a mix or a metric's reader holds resolves to a
file: a family, a tokenizer kind, a warm-up recipe, a reference, a check, a
generator, a reducer.  A case per file under ``bench/configs``,
``bench/traffic`` and ``bench/layer_metrics``; nothing here touches jax."""

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
