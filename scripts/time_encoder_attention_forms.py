#!/usr/bin/env python3
"""The encoder's attention kernel ALONE on the chip, form by form (PR 46).

    chiprun -- python3 scripts/time_encoder_attention_forms.py [--rows N] [form ...]

(host clock around ``block_until_ready`` of 24 calls in one program, over 24;
bf16, one bge-large layer's attention at the two cells' shapes,
[64, 512, 1024] and [512, 512, 1024], every fourth row padded from 480; one
JSON line a form and shape on stdout and in
``chiprun_out/encoder_attention_forms.jsonl``, with the form's largest
distance from the einsum path on the same chip).  The stack's FIRST call took
18 s at 64 rows and 130 s at 512 on the chip's machine, whatever the form
(``compile_s``; not understood), so a dozen forms at both shapes are 30
chip-minutes: ``--rows 64`` times one shape.

A form is ``ops/attention.py::_attn_kernel`` with one thing changed, named by
``+``-joined words (``served`` is the module's own body, whatever it is):

  parent        PR 25's body: scale, bias, max, subtract, ``exp``, sum and a
                division over the whole [s, s] tile
  ctx           normalise after the second product, on [s, hd]
  scores        ``exp2((t - max t) * c)`` on raw scores, c = scale * log2(e),
                the bias scaled to match
  q             c on q's [s, hd] tile before the first product (q rounded to
                the storage dtype once more: NOT the einsum path's numerics)
  mxusum        the row's sum from the second product's idle lanes:
                ``e @ [v_head | 1]`` over the 128-lane tile (implies ctx)
  mxubias       the key bias as one more contraction row of the first
                product: ``[q | 1] . [k | bias]`` over the 128-lane tile
  rows1 ... rows8   rows an iteration of the kernel's loop
  step1 ... step8   ``MAX_ROWS_PER_STEP``

``--check`` runs every form once on whatever backend is there at a tiny shape
against the einsum path (the CPU's interpreter: counts nothing, times
nothing).  ``--sched DIR form`` compiles ONE form at 64 x 512 for a described
v5e with the scheduler's report dumped to DIR (``LIBTPU_INIT_ARGS``; the
process aborts after the files are written): a static count, never a time.
"""
import functools
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ARGS = sys.argv[1:]
CHECK = "--check" in ARGS
SCHED = ARGS[ARGS.index("--sched") + 1] if "--sched" in ARGS else None
ROWS = [int(ARGS[ARGS.index("--rows") + 1])] if "--rows" in ARGS else [64, 512]
FORMS = [a for a in ARGS if not a.startswith("--") and a not in (SCHED, str(ROWS[0]))]
if SCHED:
    os.makedirs(SCHED, exist_ok=True)
    os.environ["LIBTPU_INIT_ARGS"] = f"--xla_jf_dump_to={SCHED} --xla_jf_dump_llo_text=true"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, HERE)
import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

from llm_weighted_consensus_tpu.ops import attention

OUT = os.path.join(HERE, "chiprun_out")
LOG2E = 1.4426950408889634
LANES = attention.LANES
DEFAULT = [
    "parent", "ctx", "scores", "ctx+scores", "ctx+q", "ctx+scores+mxusum",
    "ctx+scores+mxusum+mxubias", "ctx+scores+mxusum+rows1", "ctx+scores+mxusum+rows4",
    "ctx+scores+mxusum+step8+rows8", "ctx+scores+mxusum+step2+rows2", "served",
]


def body(words):
    """``_attn_kernel`` with the form's words applied."""
    ctx_norm = "ctx" in words or "mxusum" in words
    fold = "scores" if "scores" in words else "q" if "q" in words else ""
    unroll = next((int(w[4:]) for w in words if w.startswith("rows")), 2)

    def kernel(q_ref, k_ref, v_ref, row_ref, out_ref, *, scale, hd):
        bb, s, width = q_ref.shape
        c = scale * LOG2E
        idle = width % LANES == 0 and hd < LANES  # a head's tile holds another's lanes
        lane = jax.lax.broadcasted_iota(jnp.int32, (s, LANES), 1)

        def product(e, v):
            return jax.lax.dot_general(e.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                                       preferred_element_type=jnp.float32)

        def weights(r, j, row, col):
            lanes = slice(j * hd, (j + 1) * hd)
            if "mxubias" in words and idle:
                at = j * hd % LANES
                tile = slice(j * hd // LANES * LANES, (j * hd // LANES + 1) * LANES)
                mine = (lane >= at) & (lane < at + hd)
                one = lane == (at + hd) % LANES
                q = jnp.where(mine, q_ref[r, :, tile], one.astype(q_ref.dtype))
                k = jnp.where(mine, k_ref[r, :, tile], jnp.where(one, col, 0).astype(k_ref.dtype))
            else:
                q, k = q_ref[r, :, lanes], k_ref[r, :, lanes]
            if fold == "q":
                q = (q.astype(jnp.float32) * c).astype(q.dtype)
            t = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            if not fold:
                t = t * scale
            if not ("mxubias" in words and idle):
                t = t + row
            m = jnp.max(t, axis=-1, keepdims=True)
            if fold == "scores":
                return jnp.exp2((t - m) * c)
            return jnp.exp2(t - m) if fold else jnp.exp(t - m)

        def one_row(r):
            row = row_ref[r]  # [1, s]
            if fold:
                row = row * (1.0 / scale if fold == "scores" else LOG2E)
            col = None
            if "mxubias" in words and idle:
                col = jnp.broadcast_to(row, (LANES, s)).T  # [s, LANES]: a key's bias on its sublane
            if "mxusum" in words and idle:
                for t0 in range(0, width, LANES):
                    tile = slice(t0, t0 + LANES)
                    v = v_ref[r, :, tile]
                    num = den = None
                    for j in range(t0 // hd, (t0 + LANES) // hd):
                        mine = (lane >= j * hd - t0) & (lane < (j + 1) * hd - t0)
                        o = product(weights(r, j, row, col), jnp.where(mine, v, jnp.ones_like(v)))
                        total = pltpu.roll(o, hd, 1)  # the neighbour's lanes hold the sums
                        num = o if num is None else jnp.where(mine, o, num)
                        den = total if den is None else jnp.where(mine, total, den)
                    out_ref[r, :, tile] = (num * (1.0 / den)).astype(out_ref.dtype)
                return
            for j in range(width // hd):
                lanes = slice(j * hd, (j + 1) * hd)
                v = v_ref[r, :, lanes]
                e = weights(r, j, row, col)
                total = jnp.sum(e, axis=-1, keepdims=True)
                ctx = product(e, v) * (1.0 / total) if ctx_norm else product(e / total, v)
                out_ref[r, :, lanes] = ctx.astype(out_ref.dtype)

        pair = unroll if bb % unroll == 0 else 1

        def rows(i, carry):
            for u in range(pair):
                one_row(i * pair + u)
            return carry

        jax.lax.fori_loop(0, bb // pair, rows, 0)

    return kernel


SERVED = (attention._attn_kernel, attention.MAX_ROWS_PER_STEP)


def install(form):
    words = set(form.split("+"))
    attention._attn_kernel, attention.MAX_ROWS_PER_STEP = SERVED
    if words - {"served"} - {w for w in words if w.startswith("step")}:
        if "served" in words:
            raise SystemExit(f"{form}: 'served' takes only stepN beside it, the body is the module's")
        attention._attn_kernel = body(words)
    if "parent" in words:
        attention.MAX_ROWS_PER_STEP = 4  # PR 25's
    for w in words:
        if w.startswith("step"):
            attention.MAX_ROWS_PER_STEP = int(w[4:])
    jax.clear_caches()


def case(b, s, nh, hd, dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q, k, v = (jax.random.normal(x, (b, s, nh * hd), jnp.float32).astype(dtype) for x in ks)
    real = jnp.arange(s)[None, :] < jnp.where(jnp.arange(b) % 4 == 0, s - s // 16, s)[:, None]
    return q, k, v, jnp.where(real, 0.0, -1e9).astype(jnp.float32)


def einsum_path(q, k, v, bias, scale, nh):
    """``models/bert.py::_attention``'s einsum branch."""
    b, s, h = q.shape
    q, k, v = (x.reshape(b, s, nh, h // nh) for x in (q, k, v))
    t = jnp.einsum("bqnd,bknd->bnqk", q, k, preferred_element_type=q.dtype) * scale
    t = t + bias[:, None, None, :].astype(q.dtype)
    p = jax.nn.softmax(t.astype(jnp.float32), axis=-1).astype(q.dtype)
    ctx = jnp.einsum("bnqk,bknd->bqnd", p, v, preferred_element_type=jnp.float32)
    return ctx.astype(q.dtype).reshape(b, s, h)


def run(form, b, s, nh, hd, dtype):
    install(form)
    kk = attention.best_heads_per_step(b, s, nh, hd, jnp.dtype(dtype).itemsize)
    scale = float(hd) ** -0.5
    return functools.partial(attention.fused_attention_tiled, scale=scale, nh=nh,
                             heads_per_step=kk), kk


def emit(**row):
    print(json.dumps(row), flush=True)
    os.makedirs(OUT, exist_ok=True)
    with open(os.path.join(OUT, "encoder_attention_forms.jsonl"), "a", encoding="utf-8") as f:
        f.write(json.dumps(row) + "\n")


LAYERS = 24  # calls in one program: bge-large's depth


def timed(form, b, s=512, nh=16, hd=64, dtype=jnp.bfloat16, repeat=10):
    f, kk = run(form, b, s, nh, hd, dtype)
    q, k, v, bias = case(b, s, nh, hd, dtype)
    # One dispatch costs the host about 0.55 ms, more than half of the kernel
    # at 64 x 512, so a layer's time is a stack's over its depth.  The loop
    # carries one number: a carried context would be copied every trip (1.65
    # ms at 512 x 512), and the bias takes the trip's index so that the call
    # cannot be moved out of the loop.
    def layer(i, carry):
        out = f(q, k, v, bias + (i // LAYERS).astype(bias.dtype))
        return carry + out[0, 0, :LANES].astype(jnp.float32).sum()

    stack = jax.jit(lambda q, k, v, bias: jax.lax.fori_loop(0, LAYERS, layer, 0.0))
    t0 = time.perf_counter()
    jax.block_until_ready(stack(q, k, v, bias))
    compile_s = time.perf_counter() - t0
    times = []
    for _ in range(repeat):
        t0 = time.perf_counter()
        jax.block_until_ready(stack(q, k, v, bias))
        times.append((time.perf_counter() - t0) * 1e3 / LAYERS)
    got = f(q, k, v, bias)
    want = jax.jit(einsum_path, static_argnums=(4, 5))(q, k, v, bias, float(hd) ** -0.5, nh)
    diff = jnp.abs(got.astype(jnp.float32) - want.astype(jnp.float32))
    emit(form=form, rows=b, s=s, heads=nh, hd=hd, heads_per_step=kk,
         ms_a_layer_min=min(times), ms_a_layer_median=sorted(times)[len(times) // 2],
         max_abs_vs_einsum=float(diff.max()), rms_vs_einsum=float((diff**2).mean() ** 0.5),
         compile_s=round(compile_s, 1), device=jax.devices()[0].device_kind)


def check(form):
    for nh, hd, dtype in ((4, 64, jnp.float32), (4, 32, jnp.bfloat16), (2, 128, jnp.float32)):
        f, _ = run(form, 4, 128, nh, hd, dtype)
        args = case(4, 128, nh, hd, dtype)
        want = einsum_path(*args, float(hd) ** -0.5, nh)
        diff = jnp.abs(f(*args).astype(jnp.float32) - want.astype(jnp.float32))
        print(form, f"hd={hd}", jnp.dtype(dtype).name, "max_abs", float(diff.max()), flush=True)


def sched(form):
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    attention._interpret = lambda: False
    f, _ = run(form, 64, 512, 16, 64, jnp.bfloat16)
    x = jax.ShapeDtypeStruct((64, 512, 1024), jnp.bfloat16, sharding=chip)
    bias = jax.ShapeDtypeStruct((64, 512), jnp.float32, sharding=chip)
    jax.jit(f).lower(x, x, x, bias).compile()


if __name__ == "__main__":
    forms = FORMS or DEFAULT
    if SCHED:
        sched(forms[0])
    elif CHECK:
        for form in forms:
            check(form)
    else:
        if jax.default_backend() != "tpu":
            sys.exit("a time comes only from the chip: run through the chip tool")
        for form in forms:
            for b in ROWS:
                try:
                    timed(form, b)
                except Exception as e:  # a form Mosaic refuses is a finding, not the run's end
                    emit(form=form, rows=b, refused=f"{type(e).__name__}: {str(e)[:300]}")
