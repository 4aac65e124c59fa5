"""A configuration of a family, a tokenizer kind, a role and a scorer that no
file of ``bench/`` has met comes as NEW files and edits none.

``bench/`` is copied to a temporary checkout; a family, a tokenizer, a
generator that keeps two fields of an answer and judges them itself, a check,
a mix, a configuration and a ``BENCHMARK.json`` are ADDED, all under names
that no file of ``bench/`` holds; ``run.py --dry-run`` goes through them to
``correct: true``, and every file that was copied still has its hash.  The
program's tiny BERT preset has to serve the stranger on the CPU, so its
configuration names the embedder's variables as its own role keys, and its
family's tensors are BERT's under another standard deviation.

And the shards: a checkpoint written in pieces reads back, through the lazy
mapping, tensor for tensor what the single file holds."""

import hashlib
import json
import os
import shutil
import subprocess
import sys

import numpy as np

import checkpoints

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)

FAMILY = '''"""A family of its own: BERT's tensors, another initializer."""
import byname

INIT_STD = 0.03
_bert = byname.module("families", "bert")
tensors = _bert.tensors
forward_flops = _bert.forward_flops
'''

TOKENIZER = '''"""Whole words, one a line, under a file name of its own."""
FILE = "quux-words.vocab"


def write(path, vocab_size):
    words = ["[PAD]", "[UNK]", "[CLS]", "[SEP]"]
    words += [f"w{i}" for i in range(vocab_size - 4)]
    with open(path, "w", encoding="utf-8") as f:
        f.write("\\n".join(words) + "\\n")
'''

GENERATOR = '''"""A generator that keeps two fields of an answer and judges both."""
import byname

_votes = byname.module("generators", "consensus")
PATH = _votes.PATH
KEEP = ("confidence", "usage")
generate = _votes.generate
warm_sample = _votes.warm_sample
render_text = _votes.render_text
render_body = _votes.render_body
blocker = _votes.blocker
request_tokens = _votes.request_tokens


def well_formed(kept, req):
    usage = kept.get("usage")
    return (
        set(kept) == set(KEEP)
        and _votes.well_formed({"confidence": kept["confidence"]}, req)
        and isinstance(usage, dict)
        and usage.get("total_tokens", 0) >= req["n"]
    )
'''

CHECK = '''"""The stranger's check: the vote against the reference, and the second
kept field read where the check runs."""
import byname

_votes = byname.module("checks", "consensus_logit")
sample = _votes.sample
collect = _votes.collect


def run(picked, **rest):
    verdict = _votes.run(picked=picked, **rest)
    short = sum(
        kept["usage"]["total_tokens"] < sum(len(w) for w in req["words"])
        for req, kept in picked
    )
    verdict["numbers"].append({"name": "usage_short", "value": short, "limit": 0})
    verdict["usage_read"] = len(picked)
    return verdict
'''

SIZES = {
    "hidden_size": 64,
    "num_hidden_layers": 2,
    "num_attention_heads": 4,
    "intermediate_size": 128,
    "vocab_size": 512,
    "max_position_embeddings": 64,
    "type_vocab_size": 2,
    "max_tokens": 64,
}

CONFIG = {
    "source": "bench/tests/test_stranger.py",
    **SIZES,
    "precision": "bfloat16",
    "family": "quux-lm",
    "role": "quux-judge",
    "serve": {
        "weights_env": "EMBEDDER_WEIGHTS",
        "vocab_env": "EMBEDDER_VOCAB",
        "param_dtype": "param_dtype",
        "warmup": "nxs_groups",
    },
    "tokenizer": {
        "kind": "quux-words", "specials": 4, "overhead": 2,
        "pad": 0, "unk": 1, "cls": 2, "sep": 3, "first_word": 4,
    },
    "server_env": {"EMBEDDER_MODEL": "test-tiny", "EMBEDDER_MAX_TOKENS": "64"},
    "reference": "bert_cls_cosine",
    "check": {
        "name": "quux_check", "requests": 8, "temperature": 0.05,
        "logit_rms_limit": 2e-07, "embedding_limit": 1e-06,
    },
    "dry_run": {"sizes": {}},
}

MIX = {
    "generator": "quux_ballot",
    "loop": "closed",
    "callers": 4,
    "pool_per_s": 16.0,
    "n": {"values": [8]},
    "words": {"kind": "fixed", "value": 26},
    "changed": 0.1,
    "warm_groups": [2, 3, 4],
    "warm_rounds": 1,
    "warm_blockers": 3,
}

BENCHMARK = {
    "command": ["python3", "bench/run.py"],
    "paths": ["bench"],
    "run_seconds": 2,
    "configs": [
        {"name": "quux-tiny", "source": "bench/tests/test_stranger.py",
         "file": "bench/configs/quux-tiny.json", "reduced": [], "why": "a stranger"}
    ],
    "workloads": [
        {"name": "quux-tiny.quux.closed4", "config": "quux-tiny",
         "traffic": "quux.closed4", "chips": 1, "why": "a stranger's cell"}
    ],
    "end_to_end": [
        {"name": "answers_per_s", "unit": "answers/s", "better": "higher",
         "bound": 0.01, "source": "host_clock"},
        {"name": "setup_s", "unit": "s", "better": "lower", "bound": 0.1,
         "source": "host_clock"},
    ],
    "per_layer": [
        {"name": "batcher.items_per_dispatch", "unit": "items", "better": "higher",
         "source": "program_counter", "layer": "Batcher", "moves": "answers_per_s"}
    ],
}

ADDED = {
    "bench/families/quux-lm.py": FAMILY,
    "bench/tokenizers/quux-words.py": TOKENIZER,
    "bench/generators/quux_ballot.py": GENERATOR,
    "bench/checks/quux_check.py": CHECK,
    "bench/configs/quux-tiny.json": json.dumps(CONFIG, indent=1),
    "bench/traffic/quux.closed4.json": json.dumps(MIX, indent=1),
    "BENCHMARK.json": json.dumps(BENCHMARK, indent=1),
}


def hashes(root):
    out = {}
    for folder, _, names in os.walk(root):
        if "__pycache__" in folder:
            continue
        for name in names:
            path = os.path.join(folder, name)
            with open(path, "rb") as f:
                out[os.path.relpath(path, root)] = hashlib.sha256(f.read()).hexdigest()
    return out


def test_a_stranger_comes_as_files_and_edits_none(tmp_path):
    checkout = tmp_path / "checkout"
    shutil.copytree(
        BENCH, checkout / "bench", ignore=shutil.ignore_patterns("__pycache__", "*.pyc")
    )
    before = hashes(checkout)
    for rel in before:  # a name that no file of bench/ holds, but this one
        assert "quux" not in rel
        if not rel.endswith("test_stranger.py"):
            assert b"quux" not in (checkout / rel).read_bytes(), rel
    for rel, text in ADDED.items():
        assert rel not in before
        (checkout / rel).write_text(text, encoding="utf-8")
    env = {k: v for k, v in os.environ.items() if k != "BENCH_RUN"}
    env["PYTHONPATH"] = ROOT  # the program; the copy holds the benchmark alone
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [
            sys.executable, str(checkout / "bench" / "run.py"),
            "--workload", "quux-tiny.quux.closed4", "--seed", str(2**31 + 1234),
            "--dry-run",
        ],
        cwd=checkout, env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0
    assert result["counts"]["served"] > 0
    # its own numbers were compared, the second kept field among them
    assert set(result["check"]) == {
        "malformed_answers", "logit_rms", "embedding_1_minus_cos", "usage_short",
    }
    assert list(result)[-1] == "check"
    # what it was handed came from the stranger's own files
    work = checkout / ".bench_work" / "quux-tiny.quux.closed4"
    assert (work / "ckpt" / "quux-words.vocab").exists()
    results = [json.loads(line) for line in (work / "results.jsonl").read_text().splitlines()]
    assert all(set(r["kept"]) == {"confidence", "usage"} for r in results if r["status"] == 200)
    assert {rel: d for rel, d in hashes(checkout).items() if rel in before} == before


BERT = {
    "hidden_size": 64, "num_hidden_layers": 3, "intermediate_size": 128,
    "vocab_size": 512, "max_position_embeddings": 64, "type_vocab_size": 2,
}


def test_shards_read_back_what_the_single_file_holds(tmp_path):
    single = checkpoints.write_checkpoint(str(tmp_path / "one"), "bert", BERT, 2**31 + 5)
    assert os.path.basename(single) == "model.safetensors"
    assert os.listdir(tmp_path / "one") == ["model.safetensors"]
    index = checkpoints.write_checkpoint(
        str(tmp_path / "many"), "bert", BERT, 2**31 + 5, shard_bytes=70_000
    )
    assert os.path.basename(index) == "model.safetensors.index.json"
    files = sorted(os.listdir(tmp_path / "many"))
    shards = [name for name in files if name.endswith(".safetensors")]
    assert len(shards) > 2 and files[-1] == "model.safetensors.index.json"
    assert shards[0] == f"model-00001-of-{len(shards):05d}.safetensors"
    assert all(os.path.getsize(tmp_path / "many" / s) < 70_000 + 4096 for s in shards[1:])
    with open(index, encoding="utf-8") as f:
        doc = json.load(f)
    one = checkpoints.read_checkpoint(str(tmp_path / "one"))
    many = checkpoints.read_checkpoint(str(tmp_path / "many"))
    assert list(doc["weight_map"]) == [name for name, _, _ in checkpoints.specs_of("bert", BERT)[0]]
    assert set(one) == set(many) == set(doc["weight_map"]) and len(one) == len(many)
    total = 0
    for name in one:
        a, b = one[name], many[name]
        assert a.dtype == b.dtype == checkpoints.BF16 and a.shape == b.shape
        assert np.array_equal(a.view(np.uint16), b.view(np.uint16)), name
        total += a.nbytes
    assert doc["metadata"]["total_size"] == total
    # the whole state, made in memory, is the same numbers again
    state = checkpoints.make_state("bert", BERT, 2**31 + 5)
    assert all(np.array_equal(state[k].view(np.uint16), many[k].view(np.uint16)) for k in state)


def test_a_work_directory_that_changes_layout_keeps_no_stale_file(tmp_path):
    where = str(tmp_path / "ckpt")
    checkpoints.write_checkpoint(where, "bert", BERT, 3, shard_bytes=70_000)
    checkpoints.write_checkpoint(where, "bert", BERT, 3)
    assert os.listdir(where) == ["model.safetensors"]
