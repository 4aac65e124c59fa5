"""The whole command on the CPU at the tiny sizes (``--dry-run`` skips only
the look for a chip): a sound run comes out correct, and a run whose timed
path is broken underneath comes out NOT correct.

The break: the server is handed a checkpoint whose last layer's output
projection is zeroed, so every answer is altered where it is produced, while
the reference keeps the checkpoint the seed defines."""

import argparse
import json
import os

import numpy as np
import pytest

import checkpoints
import run as bench_run


WITH_DEBERTA = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "data", "BENCHMARK.with-deberta.json"
)


def args(workload, seed):
    # the deberta cell is not in the shipped BENCHMARK.json (PERF.md, Open
    # questions): its entries live in a copy beside these tests
    return argparse.Namespace(
        workload=workload, seed=seed, seconds=2.0, trace=0, dry_run=True,
        control=False, benchmark=WITH_DEBERTA,
    )


def last_line(capsys):
    lines = [
        line for line in capsys.readouterr().out.splitlines() if line.startswith("{")
    ]
    return json.loads(lines[-1])


CELLS = ["bge-large-en.n64-s512.steady", "deberta-v3-base.rerank-n16.closed8"]


@pytest.mark.parametrize("cell", CELLS)
def test_a_sound_run_is_correct(cell, capsys):
    assert bench_run.run(args(cell, 2**31 + 99)) == 0
    result = last_line(capsys)
    assert result["correct"] is True and result["failed"] == 0
    assert result["dry_run"] is True and "metrics" not in result


@pytest.mark.parametrize("cell", CELLS)
def test_a_broken_timed_path_is_not_correct(cell, capsys, monkeypatch):
    sound = bench_run.prepare_files

    def broken(work, config, cfg, seed):
        files = sound(work, config, cfg, seed)
        good = os.path.join(work, "reference_ckpt")
        checkpoints.write_checkpoint(good, config["family"], cfg, seed)
        state = dict(checkpoints.read_checkpoint(files["ckpt"]))
        last = cfg["num_hidden_layers"] - 1
        name = next(
            k for k in state if k.endswith(f"layer.{last}.output.dense.weight")
        )
        state[name] = np.zeros_like(state[name])
        from safetensors.numpy import save_file

        save_file(state, os.path.join(files["ckpt"], "model.safetensors"))
        return {**files, "reference_ckpt": good}

    monkeypatch.setattr(bench_run, "prepare_files", broken)
    assert bench_run.run(args(cell, 2**31 + 99)) == 1
    result = last_line(capsys)
    assert result["correct"] is False and result["failed"] == 0
