#!/usr/bin/env python
"""Attention-tiling microbenchmark (VERDICT r3 item 2 groundwork).

Times, on the real chip, one encoder-shaped attention op under the
candidate tilings so the crossover table in ``ops/attention.py`` is
measured, not argued:

* ``einsum``   — XLA batched einsum attention
* ``fusedKh``  — re-tiled Pallas kernel, K flat (batch, head) tiles/step

Timing uses k-rep fori_loop differencing (median of trials) so the
host<->device round trip and its jitter cancel out.  The attention
policy is decided on the IN-CONTEXT numbers from bench_fwd.py, not
these.

``--long-seq`` switches to the ring-vs-dense crossover scenario
(PR 14 long-context serving): one attention op per sequence length,
dense full-softmax vs ``parallel.ring.ring_attention`` sharded over an
``sp`` mesh axis, reporting p50 per-call ms AND the compiled
executable's per-device memory (argument+output+temp bytes from XLA
``memory_analysis`` — the O(s^2) score materialization is the term the
ring divides by sp^2).  The committed record is ``BENCH_attn.json``;
the crossover sequence length is where the ring first wins on p50
while its per-device peak stays flat.  Needs ``--sp`` devices and
fails without them (for a CPU run, set
``XLA_FLAGS=--xla_force_host_platform_device_count=<sp>`` yourself).

Runs in bf16 on the devices JAX gives it and names them in every record.
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


def einsum_attention(q, k, v, bias, scale):
    logits = (
        jnp.einsum("bqnd,bknd->bnqk", q, k, preferred_element_type=jnp.float32)
        * scale
    )
    logits = logits + bias[:, None, None, :]
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.einsum(
        "bnqk,bknd->bqnd", probs, v, preferred_element_type=jnp.float32
    ).astype(q.dtype)


def timed_ms(fn, params, reps_hi=201, trials=3):
    """Amortized per-call ms via k=1 vs k=reps_hi fori_loop difference
    (median of ``trials`` so host jitter cannot swamp sub-ms kernels)."""

    @functools.partial(jax.jit, static_argnames=("k",))
    def rep(args, k):
        def body(i, acc):
            # chain acc into the input so XLA can neither hoist the body
            # out of the loop nor run iterations concurrently (acc*1e-20
            # is not foldable: x*0 != 0 for floats)
            eps = (acc * 1e-20).astype(args[0].dtype)
            out = fn(args[0] + eps, *args[1:])
            return acc + jnp.sum(out.astype(jnp.float32))

        return jax.lax.fori_loop(0, k, body, 0.0)

    float(rep(params, 1))
    float(rep(params, reps_hi))
    samples = []
    for _ in range(trials):
        t0 = time.perf_counter()
        float(rep(params, 1))
        t1 = time.perf_counter() - t0
        t0 = time.perf_counter()
        float(rep(params, reps_hi))
        thi = time.perf_counter() - t0
        samples.append(max((thi - t1) / (reps_hi - 1) * 1e3, 1e-3))
    samples.sort()
    return samples[len(samples) // 2]


def ring_vs_dense_crossover(seqs, sp, b, nh, hd, reps_hi=5, trials=3):
    """One attention op per sequence length, dense vs ring-over-sp:
    p50 per-call ms (k-rep differencing, fewer reps — long sequences
    are slow everywhere) and per-device compiled memory.  Returns the
    per-seq table plus the first sequence length where ring wins."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from llm_weighted_consensus_tpu.parallel.ring import ring_attention

    mesh = Mesh(np.asarray(jax.devices()[:sp]), ("sp",))
    qkv_spec = P(None, "sp", None, None)
    bias_spec = P(None, "sp")
    ring_fn = jax.jit(
        jax.shard_map(
            lambda q, k, v, bias, scale: ring_attention(
                q, k, v, bias, scale, "sp"
            ),
            mesh=mesh,
            in_specs=(qkv_spec, qkv_spec, qkv_spec, bias_spec, P()),
            out_specs=qkv_spec,
            check_vma=False,
        )
    )

    def compiled_bytes(fn, *xs):
        mem = jax.jit(fn).lower(*xs).compile().memory_analysis()
        return {
            "peak_bytes": int(
                mem.argument_size_in_bytes
                + mem.output_size_in_bytes
                + mem.temp_size_in_bytes
            ),
            "temp_bytes": int(mem.temp_size_in_bytes),
        }

    dtype = jnp.bfloat16
    rng = np.random.default_rng(0)
    scale = 1.0 / float(hd) ** 0.5
    rows = {}
    crossover = None
    for s in seqs:
        if s % sp:
            continue
        shape = (b, s, nh, hd)
        q = jnp.asarray(rng.standard_normal(shape), dtype)
        k = jnp.asarray(rng.standard_normal(shape), dtype)
        v = jnp.asarray(rng.standard_normal(shape), dtype)
        bias = jnp.zeros((b, s), jnp.float32)

        dense = compiled_bytes(
            lambda q, k, v: einsum_attention(q, k, v, bias, scale), q, k, v
        )
        dense["p50_ms"] = timed_ms(
            lambda q, k, v: einsum_attention(q, k, v, bias, scale),
            (q, k, v), reps_hi=reps_hi, trials=trials,
        )

        sharding = NamedSharding(mesh, qkv_spec)
        qs, ks, vs = (jax.device_put(x, sharding) for x in (q, k, v))
        bs = jax.device_put(bias, NamedSharding(mesh, bias_spec))
        scale_arr = jnp.float32(scale)
        ref = np.asarray(
            einsum_attention(q, k, v, bias, scale), np.float32
        )
        out = np.asarray(ring_fn(qs, ks, vs, bs, scale_arr), np.float32)
        np.testing.assert_allclose(out, ref, atol=3e-2, rtol=3e-2)
        # memory_analysis on the sharded executable is PER-DEVICE —
        # exactly the "does the score tile fit one chip" question
        ring = compiled_bytes(
            lambda q, k, v, t: ring_fn(q, k, v, bs, t),
            qs, ks, vs, scale_arr,
        )
        ring["p50_ms"] = timed_ms(
            lambda q, k, v, t: ring_fn(q, k, v, bs, t),
            (qs, ks, vs, scale_arr), reps_hi=reps_hi, trials=trials,
        )
        rows[f"s={s}"] = {"dense": dense, f"ring_sp{sp}": ring}
        if crossover is None and ring["p50_ms"] < dense["p50_ms"]:
            crossover = s
        print(json.dumps({f"s={s}": rows[f"s={s}"]}), flush=True)
    return rows, crossover


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--b", type=int, default=64)
    p.add_argument("--nh", type=int, default=16)
    p.add_argument("--hd", type=int, default=64)
    p.add_argument("--seqs", default="128,256,512")
    p.add_argument("--ks", default="8,16,32")
    p.add_argument(
        "--long-seq",
        action="store_true",
        help="ring-vs-dense long-context crossover instead of the "
        "tiling sweep: p50 + per-device compiled memory per sequence "
        "length (--long-seqs), ring sharded over --sp devices",
    )
    p.add_argument("--long-seqs", default="256,512,1024,2048")
    p.add_argument("--sp", type=int, default=4)
    p.add_argument("--long-b", type=int, default=1)
    p.add_argument("--long-nh", type=int, default=4)
    args = p.parse_args()
    from llm_weighted_consensus_tpu.utils import device_summary

    if args.long_seq and jax.device_count() < args.sp:
        sys.exit(
            f"--long-seq needs --sp={args.sp} devices, JAX has "
            f"{jax.device_count()}"
        )

    if args.long_seq:
        seqs = [int(x) for x in args.long_seqs.split(",")]
        rows, crossover = ring_vs_dense_crossover(
            seqs, args.sp, args.long_b, args.long_nh, args.hd
        )
        print(json.dumps({
            "metric": "ring-vs-dense attention crossover "
            "(p50 ms + per-device peak bytes per seq length)",
            **device_summary(),
            "sp": args.sp,
            "b": args.long_b,
            "nh": args.long_nh,
            "hd": args.hd,
            "crossover_seq": crossover,
            "results": rows,
        }))
        return

    from llm_weighted_consensus_tpu.ops.attention import fused_attention_tiled

    dtype = jnp.bfloat16
    rng = np.random.default_rng(0)
    results = {}
    for s in [int(x) for x in args.seqs.split(",")]:
        shape = (args.b, s, args.nh, args.hd)
        q = jnp.asarray(rng.standard_normal(shape), dtype)
        k = jnp.asarray(rng.standard_normal(shape), dtype)
        v = jnp.asarray(rng.standard_normal(shape), dtype)
        bias = jnp.zeros((args.b, s), jnp.float32)
        scale = 1.0 / float(args.hd) ** 0.5

        row = {}
        ref = einsum_attention(q, k, v, bias, scale)
        row["einsum"] = timed_ms(
            lambda q, k, v: einsum_attention(q, k, v, bias, scale), (q, k, v)
        )
        for kk in [int(x) for x in args.ks.split(",")]:
            if (args.b * args.nh) % kk:
                continue
            try:
                # the kernel works on the encoder's [b, s, h]
                flat = tuple(t.reshape(args.b, s, -1) for t in (q, k, v))
                out = fused_attention_tiled(
                    *flat, bias, scale, args.nh, heads_per_step=kk
                )
                np.testing.assert_allclose(
                    np.asarray(out.reshape(shape), np.float32),
                    np.asarray(ref, np.float32),
                    atol=3e-2, rtol=3e-2,
                )
                row[f"fused{kk}h"] = timed_ms(
                    lambda q, k, v, kk=kk: fused_attention_tiled(
                        q, k, v, bias, scale, args.nh, heads_per_step=kk
                    ),
                    flat,
                )
            except Exception as e:  # noqa: BLE001 - report and move on
                row[f"fused{kk}h"] = f"ERROR: {type(e).__name__}: {e}"[:200]
        results[f"s={s}"] = row
        print(json.dumps({f"s={s}": row}), flush=True)

    print(json.dumps({**device_summary(), "results": results}))


if __name__ == "__main__":
    sys.exit(main())
