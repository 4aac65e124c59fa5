#!/usr/bin/env python3
"""The fourth judge's attention forms, each ALONE on the chip (PR 39).

    chiprun -- python3 scripts/time_dots3_forms.py        (host clock around
    ``block_until_ready``, bf16, a panel of 3 x 8192 slots; one JSON line a
    form on stdout and in ``chiprun_out/dots3_forms.jsonl``)

A FULL layer's 128 heads of 128 | 64 = 192 lanes against the keys, values of 128:
  (i)  laid in 256 lanes (zero lanes between nope and rope), the projections'
       own [b, s, heads * 256] layout, with the selection's tile and without;
       and the ``q_b`` product at 256 lanes a head against 192;
  (ii) 192 lanes walked as they are: a block's last dimension must be whole
       128-lane columns or the whole of the array's, so each head lies alone,
       [b * heads, s, 192] (heads = 1 to the kernel; no selection: its tile is a
       call's, not a head's), and the transposes that layout costs, q, k, v in
       and the context out.
A SLIDING layer's 64 heads of 256 over a window of 513, values of 128, at blocks
of 256 / 512 / 1024 and at today's 2048 (``ops/causal_attention.py::
work_over_window`` says what each multiplies over the band).
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import jax
import jax.numpy as jnp

from llm_weighted_consensus_tpu.ops import causal_attention as ca

B, S, DT = 3, 8192, jnp.bfloat16
OUT = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "chiprun_out")
ONLY = set(sys.argv[1:])


def rand(i, *shape, dtype=DT):
    return jax.random.normal(jax.random.PRNGKey(i), shape, jnp.float32).astype(dtype)


def timed(name, fn, *args, repeat=5, **note):
    if ONLY and name.split(":")[0] not in ONLY:
        return
    f = jax.jit(fn)
    t0 = time.perf_counter()
    jax.block_until_ready(f(*args))
    compile_s = time.perf_counter() - t0
    jax.block_until_ready(f(*args))
    times = []
    for _ in range(repeat):
        t0 = time.perf_counter()
        jax.block_until_ready(f(*args))
        times.append((time.perf_counter() - t0) * 1e3)
    row = {"form": name, "ms_min": min(times), "ms_median": sorted(times)[len(times) // 2],
           "compile_s": round(compile_s, 1), "device": jax.devices()[0].device_kind, **note}
    print(json.dumps(row), flush=True)
    os.makedirs(OUT, exist_ok=True)
    with open(os.path.join(OUT, "dots3_forms.jsonl"), "a", encoding="utf-8") as f:
        f.write(json.dumps(row) + "\n")


def full_layer():
    heads, dv, scale = 128, 128, 192**-0.5
    q, k = rand(1, B, S, heads * 256), rand(2, B, S, heads * 256)
    v = rand(3, B, S, heads * dv)
    keep = (jax.random.uniform(jax.random.PRNGKey(4), (B, S, S)) < 0.44)
    keep = (keep & jnp.tril(jnp.ones((S, S), bool))).astype(jnp.int8)
    timed("full:laid256+selection", lambda q, k, v, m: ca.causal_attention_blockwise(
        q, k, v, m, heads=heads, scale=scale), q, k, v, keep)
    timed("full:laid256", lambda q, k, v: ca.causal_attention_blockwise(
        q, k, v, heads=heads, scale=scale), q, k, v)
    del keep
    # (ii): a head alone, 192 lanes the whole of the last dimension
    q4, k4 = q[..., : heads * 192].reshape(B, S, heads, 192), k[..., : heads * 192].reshape(B, S, heads, 192)
    to_heads = lambda x: jnp.swapaxes(x, 1, 2).reshape(B * heads, S, x.shape[-1])  # noqa: E731
    qh, kh, vh = to_heads(q4), to_heads(k4), to_heads(v.reshape(B, S, heads, dv))
    del q, k
    timed("full:walked192", lambda q, k, v: ca.causal_attention_blockwise(
        q, k, v, heads=1, scale=scale), qh, kh, vh)
    timed("full:walked192_transposes", lambda q, k, v, o: (
        to_heads(q), to_heads(k), to_heads(v),
        jnp.swapaxes(o.reshape(B, heads, S, dv), 1, 2).reshape(B, S, heads * dv)),
        q4, k4, v.reshape(B, S, heads, dv), vh)
    del q4, k4, qh, kh, vh, v
    cq = rand(5, B, S, 1024)
    for lanes in (256, 192):
        w = rand(6, 1024, heads * lanes)
        timed(f"full:q_b_{lanes}", lambda x, w: jnp.einsum(
            "bsi,io->bso", x, w, preferred_element_type=jnp.float32).astype(DT), cq, w)


def sliding_layer():
    heads, dv, scale, window = 64, 128, 256**-0.5, 513
    q, k = rand(7, B, S, heads * 256), rand(8, B, S, heads * 256)
    v = rand(9, B, S, heads * dv)
    for block in (256, 512, 1024, 2048):
        timed(f"sliding:window_block{block}", lambda q, k, v, block=block: ca.window_attention_blockwise(
            q, k, v, heads=heads, scale=scale, window=window, block_q=block, block_k=block),
            q, k, v, work_over_band=round(ca.work_over_window(S, block, block, window), 3),
            steps=len(ca._steps(S, block, block, window)[0]))
    timed("sliding:causal_block2048", lambda q, k, v: ca.causal_attention_blockwise(
        q, k, v, heads=heads, scale=scale), q, k, v)


if __name__ == "__main__":
    if jax.default_backend() != "tpu":
        sys.exit("a time comes only from the chip: run through the chip tool")
    full_layer()
    sliding_layer()
