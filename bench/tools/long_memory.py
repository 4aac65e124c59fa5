#!/usr/bin/env python3
"""The program against the plain reference with a recurrent state that LIVES.

    python3 bench/tools/long_memory.py --config qwen3-next-80b-a3b --seed N \
        [--memory 0.01] [--calls 3] [--dry-run]

The benchmark's seeded checkpoint draws every tensor N(0, 0.02)
(``checkpoints._draw`` knows ``normal`` and ``ln_scale``), so a linear
layer's ``A_log`` and ``dt_bias`` give a log-decay of about -0.69 a token: the
state forgets within a few tokens, and the cell's check cannot see a wrong
carry between the chunks of the delta rule (PERF.md, open questions).  This
tool makes the same seeded checkpoint, OVERWRITES those two tensors so that a
head forgets about ``--memory`` of its state a token (|g| ~ 0.01: what a
chunk wrote is most of the state a thousand positions later), and compares,
at the configuration's own widths on the device the process finds:

  the program   ``models/judge.py``'s ``judge_panel`` over ``--calls`` calls
                of seeded tokens, right-padded to the configuration's bucket
                (prefill through the chunked kernel, one decoded letter
                through both caches), loaded from the checkpoint by its HF
                names like a served one;
  the reference ``bench/references/<reference>.py`` (float32, the RECURRENT
                form), one forward over T + 1 positions a call.

Printed, a JSON line a call and one for all: the root mean square of (served
log-probabilities, centred over the letters read) - (reference logits,
centred), a read at a time, as the cell's check takes it
(``checks/judge_ballot_logit.py``).  Nothing here is timed; no result of the
benchmark rests on it.  It runs the program in THIS process (no server), so
nothing else may hold the chip.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
from collections.abc import Mapping

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

import byname  # noqa: E402
import checkpoints  # noqa: E402


class Overwritten(Mapping):
    """A checkpoint with some tensors replaced."""

    def __init__(self, state, replaced: dict):
        self._state, self._replaced = state, replaced

    def __getitem__(self, name):
        return self._replaced[name] if name in self._replaced else self._state[name]

    def __contains__(self, name):
        return name in self._state

    def __iter__(self):
        return iter(self._state)

    def __len__(self):
        return len(self._state)


def long_memory(state, memory: float, seed: int) -> dict:
    """``A_log`` and ``dt_bias`` of every linear layer: -exp(A_log) ·
    softplus(a + dt_bias) is about -memory where a is near 0."""
    rng = np.random.default_rng(np.random.SeedSequence([seed, 31]))
    out = {}
    for name in state:
        if name.endswith(".A_log"):
            noise = rng.standard_normal(np.asarray(state[name]).shape) * 0.02
            out[name] = (math.log(memory / math.log(2.0)) + noise).astype(checkpoints.BF16)
    return out


def main() -> int:
    parser = argparse.ArgumentParser("bench/tools/long_memory.py")
    parser.add_argument("--config", default="qwen3-next-80b-a3b")
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--memory", type=float, default=0.01)
    parser.add_argument("--calls", type=int, default=3)
    parser.add_argument("--dry-run", action="store_true")
    args = parser.parse_args()

    with open(os.path.join(HERE, "configs", args.config + ".json"), encoding="utf-8") as f:
        config = json.load(f)
    cfg = {**config, **config["dry_run"]["sizes"]} if args.dry_run else config
    if args.dry_run:
        os.environ["JAX_PLATFORMS"] = "cpu"
    work = os.path.join(ROOT, ".bench_work", "long_memory", "ckpt")
    checkpoints.write_checkpoint(work, config["family"], cfg, args.seed)
    seeded = checkpoints.read_checkpoint(work)
    state = Overwritten(seeded, long_memory(seeded, args.memory, args.seed))

    import jax
    import jax.numpy as jnp

    from llm_weighted_consensus_tpu.models import judge

    preset_name = (config["dry_run"] if args.dry_run else config)["server_env"]["JUDGE_MODEL"]
    preset = judge.JUDGE_PRESETS[preset_name]
    decoder = judge.decoder_of(preset)
    dtype = jnp.float32 if args.dry_run else jnp.bfloat16
    params, served = decoder.from_hf_weights(state, preset, dtype=dtype)

    tok, slots = config["tokenizer"], int(cfg["max_tokens"])
    rng = np.random.default_rng(np.random.SeedSequence([args.seed, 32]))
    lens = np.asarray([slots - slots // 12 - 7 * i for i in range(args.calls)], np.int32)
    ids = np.full((args.calls, slots), tok["pad"], np.int32)
    for row, n in enumerate(lens):
        ids[row, :n] = rng.integers(tok["first_word"], cfg["vocab_size"], size=n)
        ids[row, 0], ids[row, n - 1] = tok["bos"], tok["tick_open"]
    letters = np.arange(tok["letter_first"], tok["letter_first"] + 20, dtype=np.int32)
    first = np.zeros((args.calls, 20), bool)
    first[:, :4] = True
    second = np.zeros((args.calls, 20, 20), bool)
    second[:, :4, :16] = True
    out = judge.judge_panel(
        params, jnp.asarray(ids), jnp.asarray(lens), jnp.asarray(letters),
        jnp.asarray(first), jnp.asarray(second), decoder=decoder, config=served, depth=2,
    )
    got_first = np.asarray(out["first_logprobs"], np.float64)
    got_second = np.asarray(out["second_logprobs"], np.float64)
    chosen = np.asarray(out["chosen"])
    device = jax.devices()[0]
    del params, out
    jax.clear_caches()

    reference = byname.module("references", config["reference"])
    calls = [
        (ids[row, :n].tolist() + [int(letters[chosen[row]])], [int(n) - 1, int(n)])
        for row, n in enumerate(lens)
    ]
    reads = reference.read_logits(state, cfg, calls, letters.tolist())

    def rms(got, want):
        got, want = got - got.mean(), want - want.mean()
        return float(math.sqrt(np.mean((got - want) ** 2)))

    all_reads = []
    for row, read in enumerate(reads):
        line = {
            "call": row, "tokens": int(lens[row]),
            "first_rms": rms(got_first[row, :4], read[0][:4]),
            "second_rms": rms(got_second[row, :16], read[1][:16]),
            "logit_spread": float(np.std(read[1][:16])),
        }
        all_reads += [line["first_rms"], line["second_rms"]]
        print(json.dumps(line), flush=True)
    print(json.dumps({
        "memory": args.memory, "seed": args.seed, "calls": args.calls,
        "device": {"platform": device.platform, "kind": device.device_kind},
        "dtype": jnp.dtype(dtype).name, "read_rms_median": float(np.median(all_reads)),
        "read_rms_max": float(max(all_reads)),
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
