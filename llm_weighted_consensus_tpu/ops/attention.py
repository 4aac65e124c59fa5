"""Fused multi-head attention Pallas kernel for the encoder hot path.

The einsum attention in ``models/bert.py`` materializes the [b, nh, s, s]
logits and probs tensors in HBM between XLA ops.  For encoder sequence
lengths (<=1024) a block of flat (batch, head) tiles — q/k/v [k, s, hd]
plus the [k, s, s] score tile — fits in VMEM, so the whole
QK^T -> bias -> softmax -> PV chain runs as ONE kernel with f32
accumulation on the MXU and no HBM round-trips for the intermediates
(SURVEY §3.5; VERDICT r1 item 2, r3 item 2).

Layout: grid (b*nh // heads_per_step,); each step processes
``heads_per_step`` flat (batch, head) tiles.  The additive padding bias
[b, s] (0 for real tokens, -1e9 for padding) is pre-expanded to one row
per flat tile so a step may straddle batch elements — any power-of-two
divisor of b*nh inside the VMEM budget works (``best_heads_per_step``).

Which path is faster, and from which sequence length, is not measured
on this toolchain.  The kernel pays the [b, s, nh, hd] -> [b*nh, s, hd]
transposes as HBM passes that XLA fuses into the einsum path's
projection matmuls; the serving policy (``models/bert.py``
``_use_fused_attention``: the kernel from s=512) comes from a builder's
round-4 timings on another toolchain and is to be re-measured (ROADMAP
S2).  A native-layout variant (BlockSpec carving [1, s, kh, hd] tiles
straight out of the encoder layout, no transposes) hit a Mosaic INTERNAL
error on batched dot_general with a middle batch axis on that toolchain;
not retried on this one.

The kernel is a single-device program: under a GSPMD-partitioned jit
Mosaic refuses it (parallel/sharding.py ``gspmd_config``).

On non-TPU backends the kernel runs in interpret mode (same code path,
same numerics) so the CPU test mesh exercises it; parity with the einsum
reference is asserted in tests/test_models.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# One (s, s) f32 score tile + 3 (s, hd) operand tiles must fit VMEM.
MAX_FUSED_SEQ = 1024


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _attn_kernel_tiled(
    q_ref, k_ref, v_ref, bias_ref, out_ref, *, scale: float
):
    # q/k/v blocks: [k, s, hd] (k flat (batch, head) tiles); bias block:
    # [k, 1, s] (pre-expanded per head, so a step may straddle batch
    # elements).  Matmul inputs stay in the storage dtype (bf16 feeds the
    # MXU natively with f32 accumulation); softmax is f32 — same numerics
    # as the einsum path.
    q = q_ref[:]  # [k, s, hd]
    k = k_ref[:]
    v = v_ref[:]
    logits = (
        jax.lax.dot_general(
            q,
            k,
            # batch over heads, contract over hd
            dimension_numbers=(((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )
        * scale
    )  # [k, s, s] f32
    logits = logits + bias_ref[:, 0, :][:, None, :]  # key-side padding bias
    mx = jnp.max(logits, axis=-1, keepdims=True)
    e = jnp.exp(logits - mx)
    probs = (e / jnp.sum(e, axis=-1, keepdims=True)).astype(v.dtype)
    ctx = jax.lax.dot_general(
        probs,
        v,
        dimension_numbers=(((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    )  # [k, s, hd] f32
    out_ref[:] = ctx.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "heads_per_step"))
def fused_attention_tiled(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    bias: jax.Array,
    scale: float,
    heads_per_step: int = 8,
) -> jax.Array:
    """q/k/v[b, s, nh, hd], bias[b, s] additive key padding -> ctx[b, s, nh, hd].

    Softmax(QK^T * scale + bias) V fused over ``heads_per_step`` flat
    (batch, head) tiles per grid step, amortizing per-step grid/DMA
    overhead (the r3 kernel's 1-head steps were overhead-bound at s=128,
    0.56 vs 0.08 ms isolated).  ``heads_per_step`` may be any divisor of
    b*nh within the VMEM budget; ``best_heads_per_step`` picks one.
    """
    b, s, nh, hd = q.shape
    kk = heads_per_step
    if (b * nh) % kk:
        raise ValueError(f"heads_per_step={kk} must divide b*nh={b * nh}")
    grid = (b * nh // kk,)

    def to_heads(t):
        return t.transpose(0, 2, 1, 3).reshape(b * nh, s, hd)

    flat_bias = jnp.broadcast_to(bias[:, None, :], (b, nh, s)).reshape(
        b * nh, 1, s
    )
    qkv_spec = pl.BlockSpec(
        (kk, s, hd), lambda i: (i, 0, 0), memory_space=pltpu.VMEM
    )
    bias_spec = pl.BlockSpec(
        (kk, 1, s), lambda i: (i, 0, 0), memory_space=pltpu.VMEM
    )
    out = pl.pallas_call(
        functools.partial(_attn_kernel_tiled, scale=scale),
        grid=grid,
        in_specs=[qkv_spec, qkv_spec, qkv_spec, bias_spec],
        out_specs=qkv_spec,
        out_shape=jax.ShapeDtypeStruct((b * nh, s, hd), q.dtype),
        # independent grid steps: lets Mosaic double-buffer the block DMAs
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)
        ),
        interpret=_interpret(),
    )(to_heads(q), to_heads(k), to_heads(v), flat_bias)
    return out.reshape(b, nh, s, hd).transpose(0, 2, 1, 3)


def _attn_kernel_tiled_seg(
    q_ref, k_ref, v_ref, seg_ref, out_ref, *, scale: float
):
    # Segment-masked variant for the packed (continuous-batching) layout:
    # instead of a per-key additive padding bias, the block carries the
    # int32 segment-ids row [k, 1, s] and the mask is computed IN VMEM —
    # query i attends key j iff seg[i] == seg[j] and seg[j] > 0 (0 marks
    # pad slots).  Building the [s, s] mask here costs one compare per
    # logit and keeps the HBM traffic identical to the padded kernel
    # (a pre-materialized [b*nh, s, s] bias would triple it at s=512).
    q = q_ref[:]  # [k, s, hd]
    k = k_ref[:]
    v = v_ref[:]
    logits = (
        jax.lax.dot_general(
            q,
            k,
            dimension_numbers=(((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )
        * scale
    )  # [k, s, s] f32
    seg = seg_ref[:, 0, :]  # [k, s] int32
    same = (seg[:, :, None] == seg[:, None, :]) & (seg[:, None, :] > 0)
    logits = jnp.where(same, logits, -1e9)
    # pad-slot query rows are fully masked: every logit is the same -1e9,
    # so the softmax is uniform (never 0/0) and the garbage rows are
    # dropped by segment pooling downstream
    mx = jnp.max(logits, axis=-1, keepdims=True)
    e = jnp.exp(logits - mx)
    probs = (e / jnp.sum(e, axis=-1, keepdims=True)).astype(v.dtype)
    ctx = jax.lax.dot_general(
        probs,
        v,
        dimension_numbers=(((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    )  # [k, s, hd] f32
    out_ref[:] = ctx.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "heads_per_step"))
def fused_attention_tiled_seg(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    segment_ids: jax.Array,
    scale: float,
    heads_per_step: int = 8,
) -> jax.Array:
    """q/k/v[b, s, nh, hd], segment_ids[b, s] int32 (0 = pad slot) ->
    ctx[b, s, nh, hd] with attention confined to same-segment tokens.

    The packed-serving twin of ``fused_attention_tiled``: same grid/tile
    layout and numerics, but the key-side padding bias is replaced by an
    in-kernel segment equality mask so one dense row can carry many
    independent sequences (serve/packing.py builds the layout).  VMEM
    cost matches the padded kernel (the int32 seg row replaces the f32
    bias row), so ``best_heads_per_step`` applies unchanged.
    """
    b, s, nh, hd = q.shape
    kk = heads_per_step
    if (b * nh) % kk:
        raise ValueError(f"heads_per_step={kk} must divide b*nh={b * nh}")
    grid = (b * nh // kk,)

    def to_heads(t):
        return t.transpose(0, 2, 1, 3).reshape(b * nh, s, hd)

    flat_seg = jnp.broadcast_to(
        segment_ids.astype(jnp.int32)[:, None, :], (b, nh, s)
    ).reshape(b * nh, 1, s)
    qkv_spec = pl.BlockSpec(
        (kk, s, hd), lambda i: (i, 0, 0), memory_space=pltpu.VMEM
    )
    seg_spec = pl.BlockSpec(
        (kk, 1, s), lambda i: (i, 0, 0), memory_space=pltpu.VMEM
    )
    out = pl.pallas_call(
        functools.partial(_attn_kernel_tiled_seg, scale=scale),
        grid=grid,
        in_specs=[qkv_spec, qkv_spec, qkv_spec, seg_spec],
        out_specs=qkv_spec,
        out_shape=jax.ShapeDtypeStruct((b * nh, s, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)
        ),
        interpret=_interpret(),
    )(to_heads(q), to_heads(k), to_heads(v), flat_seg)
    return out.reshape(b, nh, s, hd).transpose(0, 2, 1, 3)


def best_heads_per_step(
    b: int,
    s: int,
    nh: int,
    hd: int,
    itemsize: int = 2,
    score_itemsize: int = 4,
    bias_itemsize: int = 4,
) -> int:
    """Largest power-of-two divisor of b*nh whose block set fits VMEM,
    or 0 if not even a 1-tile step fits (callers fall back to einsum).

    Per step the kernel holds 4 [k, s, hd] operand/output blocks in the
    storage dtype (``itemsize`` bytes/element, x2 for double-buffering),
    the [k, s, s] score/prob tiles (``score_itemsize``, f32 today), and
    the bias row (``bias_itemsize``; the packed variant's int32 segment
    row has the same width).  The per-dtype byte widths are parameters
    — not baked-in 4s — so a narrower score accumulator or bias layout
    reuses this one fit model, mirroring ``w8a8_shape_fits``'s
    ``w_bytes``.  11 MB of the ~16 MB VMEM admits the measured-best
    tiles (bf16: kk=32 @ s=128: 8.4 MB; kk=4 @ s=512: 10.5 MB) and
    rejects the ones Mosaic refuses or that regress from double-buffer
    pressure (kk=64 @ s=128: 16.8 MB).
    """
    budget = 11 * 1024 * 1024
    best = 0
    kk = 1
    while kk <= b * nh:
        if (b * nh) % kk == 0:
            need = kk * (
                8 * s * hd * itemsize
                + 2 * s * s * score_itemsize
                + s * bias_itemsize
            )
            if need <= budget:
                best = kk
        kk *= 2
    return best


def attention_fits(s: int, hd: int) -> bool:
    """Coarse shape gate for the fused kernel; the binding per-dtype fit
    decision is ``best_heads_per_step(...) > 0``."""
    return s <= MAX_FUSED_SEQ and hd <= 256
