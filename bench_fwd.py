#!/usr/bin/env python
"""Full-forward breakdown: where the bge-large N=64/s=128 milliseconds go.

Times ``bert.embed`` with each cost candidate swapped out
(monkeypatched) so the device-only budget is attributable: attention
impl (einsum vs tiled Pallas), GELU (exact erf vs tanh vs identity),
layernorm (real vs identity).  Runs in bf16 on the devices JAX gives it
and names them in the record.
"""

from __future__ import annotations

import argparse
import sys
import functools
import json
import time

import jax
import jax.numpy as jnp
import numpy as np


def timed_ms(fn, args_, reps_hi=51, trials=3):
    @functools.partial(jax.jit, static_argnames=("k",))
    def rep(args, k):
        def body(i, acc):
            eps = (acc * 1e-20).astype(jnp.int32)
            out = fn(args[0], args[1] + eps, *args[2:])
            return acc + jnp.sum(out.astype(jnp.float32))

        return jax.lax.fori_loop(0, k, body, 0.0)

    float(rep(args_, 1))
    float(rep(args_, reps_hi))
    samples = []
    for _ in range(trials):
        t0 = time.perf_counter()
        float(rep(args_, 1))
        t1 = time.perf_counter() - t0
        t0 = time.perf_counter()
        float(rep(args_, reps_hi))
        thi = time.perf_counter() - t0
        samples.append(max((thi - t1) / (reps_hi - 1) * 1e3, 1e-3))
    samples.sort()
    return round(samples[len(samples) // 2], 3)


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--model", default="bge-large-en")
    p.add_argument("--b", type=int, default=64)
    p.add_argument("--seq", type=int, default=128)
    args = p.parse_args()

    import dataclasses

    from llm_weighted_consensus_tpu.utils import device_summary

    from llm_weighted_consensus_tpu.models import bert
    from llm_weighted_consensus_tpu.models.configs import PRESETS

    config = PRESETS[args.model]
    dtype = jnp.bfloat16
    params = bert.init_params(jax.random.PRNGKey(0), config, dtype=dtype)
    rng = np.random.default_rng(0)
    ids = jnp.asarray(
        rng.integers(0, config.vocab_size, (args.b, args.seq)), jnp.int32
    )
    mask = jnp.ones((args.b, args.seq), jnp.int32)

    real_gelu = bert._gelu_erf
    real_ln = bert._layer_norm

    def run(cfg):
        return timed_ms(
            lambda p_, i_, m_: bert.embed.__wrapped__(
                p_, i_, m_, cfg, pooling="cls", normalize=True
            ),
            (params, ids, mask),
        )

    out = {}
    for impl in ("einsum", "fused"):
        cfg = dataclasses.replace(config, attention_impl=impl)
        out[f"attn={impl}"] = run(cfg)

    cfg = dataclasses.replace(config, attention_impl="einsum")
    bert._gelu_erf = lambda x: jax.nn.gelu(x, approximate=True)
    out["gelu=tanh"] = run(cfg)
    bert._gelu_erf = lambda x: x
    out["gelu=identity"] = run(cfg)
    bert._gelu_erf = real_gelu

    bert._layer_norm = lambda x, p_, eps: x
    out["ln=identity"] = run(cfg)
    bert._layer_norm = real_ln

    out.update(device_summary())
    print(json.dumps(out))


if __name__ == "__main__":
    sys.exit(main())
