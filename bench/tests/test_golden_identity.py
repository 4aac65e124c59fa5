"""What a run hands the program and the load generator is, for a given
``--seed``, byte for byte what the parent of PR 26 handed them.

``data/golden_identity.json`` was recorded with the PARENT's ``bench/`` code
(commit bacaff0, before the registries of families, tokenizers, roles and
routes became directories), through the steps ``run.run`` takes before the
server starts, for every cell of ``BENCHMARK.json`` and of
``data/BENCHMARK.with-deberta.json``, at two seeds, at the dry sizes (the
checkpoint too) and at the full sizes (the schedule of a whole 50 s window,
the environment, the vocabulary).  It is data and is not made again: a change
under ``bench/`` that moves one of these hashes has changed what the accepted
cells measure.

``data/golden_moved.json`` lists what a later ``benchmark`` change moved on
purpose: a mix's parameters as the parent had them, and the fields of the
record that follow from the new ones.  With the parent's parameters put back
the code still reproduces the parent's record whole; with the file as it is
it reproduces the record with the moved fields in place."""

import hashlib
import json
import os

import pytest

import byname
import run as bench_run

HERE = os.path.dirname(os.path.abspath(__file__))
BENCHMARKS = {
    "BENCHMARK.json": os.path.join(bench_run.ROOT, "BENCHMARK.json"),
    "BENCHMARK.with-deberta.json": os.path.join(
        HERE, "data", "BENCHMARK.with-deberta.json"
    ),
}
with open(os.path.join(HERE, "data", "golden_identity.json"), encoding="utf-8") as f:
    GOLDEN = json.load(f)["cells"]
with open(os.path.join(HERE, "data", "golden_moved.json"), encoding="utf-8") as f:
    MOVED = json.load(f)


def merged(base: dict, over: dict) -> dict:
    """``base`` with ``over`` laid on it, nested groups key by key."""
    out = dict(base)
    for key, value in over.items():
        both = isinstance(value, dict) and isinstance(out.get(key), dict)
        out[key] = merged(out[key], value) if both else value
    return out


def sha(path):
    digest = hashlib.sha256()
    with open(path, "rb") as f:
        for block in iter(lambda: f.read(1 << 20), b""):
            digest.update(block)
    return digest.hexdigest()


def sha_json(obj):
    return hashlib.sha256(json.dumps(obj, sort_keys=True).encode()).hexdigest()


def record(bench_path, workload, seed, dry, seconds, work, as_parent=False):
    _, cell, config, cfg, mix, gen = bench_run.load_cell(workload, dry, bench_path)
    if as_parent and not dry:
        mix = merged(mix, MOVED["parent_mix"].get(cell["traffic"], {}))
    tok = config["tokenizer"]
    vocab_words = cfg["vocab_size"] - tok["specials"]
    requests = gen.generate(mix, seed, seconds, vocab_words)
    warm = gen.warm_sample(mix, seed, vocab_words)
    shapes = bench_run.warm_shapes(
        gen, requests + warm, tok["overhead"], int(cfg["max_tokens"])
    )
    out = {"requests": len(requests), "shapes": [list(s) for s in shapes]}
    if dry:
        files = bench_run.prepare_files(work, config, cfg, seed)
        assert sorted(os.listdir(files["ckpt"])) == sorted(
            ["model.safetensors", os.path.basename(files["vocab"])]
        )
        out["checkpoint_sha256"] = sha(os.path.join(files["ckpt"], "model.safetensors"))
    else:  # the vocabulary alone: a full-size checkpoint takes a minute
        ckpt = os.path.join(work, "ckpt")
        os.makedirs(ckpt)
        tokenizer = byname.module("tokenizers", tok["kind"])
        files = {"ckpt": ckpt, "vocab": os.path.join(ckpt, tokenizer.FILE)}
        tokenizer.write(files["vocab"], cfg["vocab_size"])
    out["vocab_file"] = os.path.basename(files["vocab"])
    out["vocab_sha256"] = sha(files["vocab"])
    env = bench_run.server_env(config, files, shapes, work, dry)
    out["env"] = sorted([k, v.replace(work, "<work>")] for k, v in env.items())
    schedule = os.path.join(work, "schedule.jsonl")
    bench_run.write_schedule(schedule, gen, requests)
    out["schedule_sha256"] = sha(schedule)
    bodies = bench_run.warm_bodies(gen, requests + warm, tok["overhead"])
    out["warm_bodies"] = len(bodies)
    out["warm_bodies_sha256"] = sha_json(bodies)
    out["warm_blockers_sha256"] = sha_json([gen.blocker(b) for b in bodies])
    groups = mix["warm_groups"]
    out["warm_plan"] = {
        "groups": list(groups),
        "rounds": int(mix.get("warm_rounds", bench_run.WARM_ROUNDS)) if groups else 0,
        "blockers": int(mix.get("warm_blockers", 6)),
    }
    return out


@pytest.mark.parametrize("key", sorted(GOLDEN))
def test_a_run_hands_over_what_the_parent_handed_over(key, tmp_path):
    label, workload, seed, sizes = key.split("|")
    bench_path = BENCHMARKS[label]
    dry = sizes == "dry"
    with open(bench_path, encoding="utf-8") as f:
        seconds = 2.0 if dry else float(json.load(f)["run_seconds"])
    got = record(
        bench_path, workload, int(seed), dry, seconds, str(tmp_path), as_parent=True
    )
    assert got == GOLDEN[key]


@pytest.mark.parametrize("key", sorted(MOVED["moved"]))
def test_a_moved_mix_moves_only_what_the_record_says(key, tmp_path):
    label, workload, seed, _ = key.split("|")
    bench_path = BENCHMARKS[label]
    with open(bench_path, encoding="utf-8") as f:
        seconds = float(json.load(f)["run_seconds"])
    got = record(bench_path, workload, int(seed), False, seconds, str(tmp_path))
    assert got == {**GOLDEN[key], **MOVED["moved"][key]}


def test_every_cell_is_in_the_record():
    for label, path in BENCHMARKS.items():
        with open(path, encoding="utf-8") as f:
            for cell in json.load(f)["workloads"]:
                mine = [k for k in GOLDEN if k.startswith(f"{label}|{cell['name']}|")]
                assert len(mine) == 4, (label, cell["name"], mine)
