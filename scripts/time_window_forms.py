#!/usr/bin/env python3
"""The window kernel ALONE on the chip at both served geometries (PR 42).

    chiprun -- python3 scripts/time_window_forms.py [--tree DIR] [form ...]

(host clock around ``block_until_ready``, bf16; one JSON line a form on stdout
and in ``chiprun_out/window_forms.jsonl``).  ``--tree`` imports the package
from another checkout (the parent's, unpacked beside this one), so that one
call times both sides on one chip, a process a side.

- the fifth judge's sliding layer: 3 x 16,384 slots, 48 query heads on 8 key
  heads of 128 lanes, a window of 4096, at the blocks the window gives (2048)
  and at 1024; the causal kernel at the same heads beside it;
- the fourth judge's: 3 x 8192 slots, 64 heads of 256 against the keys and 128
  against the values, a window of 513, at its blocks of 512, with stripes of
  ``_STRIPE`` as served and of 128;
- each kernel's context against ``causal_attention_einsum`` on the same chip
  (``against_einsum``: six query heads on a key head at 16,384 slots, two heads
  of 256 | 128 at 8192), so that two trees' errors can be laid side by side.

``work_over_band`` is ``ops/causal_attention.py::work_over_window`` of the
tree that ran: what the form multiplies over the band it keeps.
"""
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ARGS = sys.argv[1:]
TREE = os.path.abspath(ARGS.pop(ARGS.index("--tree") + 1)) if "--tree" in ARGS else HERE
ONLY = {a for a in ARGS if a != "--tree"}
sys.path.insert(0, TREE)
import jax
import jax.numpy as jnp

from llm_weighted_consensus_tpu.ops import causal_attention as ca

DT = jnp.bfloat16
OUT = os.path.join(HERE, "chiprun_out")


def rand(i, *shape):
    return jax.random.normal(jax.random.PRNGKey(i), shape, jnp.float32).astype(DT)


def emit(name, **numbers):
    row = {"form": name, "tree": os.path.relpath(TREE, HERE),
           "device": jax.devices()[0].device_kind, **numbers}
    print(json.dumps(row), flush=True)
    os.makedirs(OUT, exist_ok=True)
    with open(os.path.join(OUT, "window_forms.jsonl"), "a", encoding="utf-8") as f:
        f.write(json.dumps(row) + "\n")


def timed(name, fn, *args, repeat=7, **note):
    if ONLY and name not in ONLY:
        return
    jax.clear_caches()  # a form may differ from the last by a module constant alone
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
    emit(name, ms_min=min(times), ms_median=sorted(times)[len(times) // 2],
         compile_s=round(compile_s, 1), **note)


def window_form(name, q, k, v, *, block=0, stripe=0, **kw):
    s, window = q.shape[1], kw["window"]
    served = ca._STRIPE
    ca._STRIPE = stripe or served
    try:
        bq = block or ca.window_block(s, window)
        timed(name, lambda q, k, v: ca.window_attention_blockwise(
            q, k, v, block_q=block, block_k=block, **kw), q, k, v,
            block=bq, stripe=ca._STRIPE, steps=len(ca._steps(s, bq, bq, window)[0]),
            work_over_band=round(ca.work_over_window(s, bq, bq, window), 4))
    finally:
        ca._STRIPE = served


def trinity_sliding_layer():
    b, s, heads, kv, hd = 3, 16384, 48, 8, 128
    q, k, v = rand(1, b, s, heads * hd), rand(2, b, s, kv * hd), rand(3, b, s, kv * hd)
    kw = dict(heads=heads, kv_heads=kv, scale=hd**-0.5)
    window_form("trinity:window4096", q, k, v, window=4096, **kw)
    window_form("trinity:window4096_block1024", q, k, v, block=1024, window=4096, **kw)
    timed("trinity:causal", lambda q, k, v: ca.causal_attention_blockwise(q, k, v, **kw), q, k, v)


def dots3_sliding_layer():
    b, s, heads = 3, 8192, 64
    q, k, v = rand(4, b, s, heads * 256), rand(5, b, s, heads * 256), rand(6, b, s, heads * 128)
    kw = dict(heads=heads, scale=256**-0.5, window=513)
    window_form("dots3:window513", q, k, v, **kw)
    window_form("dots3:window513_stripe128", q, k, v, stripe=128, **kw)


def against_einsum(name, *, s, heads, kv, hd, dv, window):
    """The kernel's context against the plain twin's on the SAME device: bf16
    in, the twin a query head at a time (whole [s, s] scores in float32)."""
    if ONLY and name not in ONLY:
        return
    q, k, v = rand(11, 1, s, heads * hd), rand(12, 1, s, kv * hd), rand(13, 1, s, kv * dv)
    kw = dict(scale=hd**-0.5, window=window)
    got = ca.window_attention_blockwise(q, k, v, heads=heads, kv_heads=kv, **kw).astype(jnp.float32)
    group, worst, square = heads // kv, 0.0, 0.0
    for h in range(heads):
        cut = lambda x, i, w: x[..., i * w:(i + 1) * w]  # noqa: E731
        want = ca.causal_attention_einsum(
            cut(q, h, hd), cut(k, h // group, hd), cut(v, h // group, dv), heads=1, **kw)
        diff = cut(got, h, dv) - want.astype(jnp.float32)
        worst, square = max(worst, float(jnp.abs(diff).max())), square + float((diff**2).mean())
    emit(name, max_abs=worst, rms=(square / heads) ** 0.5)


if __name__ == "__main__":
    if jax.default_backend() != "tpu":
        sys.exit("a time comes only from the chip: run through the chip tool")
    trinity_sliding_layer()
    dots3_sliding_layer()
    against_einsum("trinity:against_einsum", s=16384, heads=6, kv=1, hd=128, dv=128, window=4096)
    against_einsum("dots3:against_einsum", s=8192, heads=2, kv=2, hd=256, dv=128, window=513)
