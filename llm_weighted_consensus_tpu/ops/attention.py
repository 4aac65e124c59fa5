"""Fused multi-head attention Pallas kernel for the encoder hot path.

The einsum attention in ``models/bert.py`` materializes the [b, nh, s, s]
logits and probs tensors in HBM between XLA ops.  For encoder sequence
lengths (<=1024) the q/k/v blocks of a few batch rows plus one [s, s]
score tile fit in VMEM, so the whole QK^T -> bias -> softmax -> PV chain
runs as ONE kernel with f32 accumulation on the MXU and no HBM
round-trips for the intermediates (SURVEY §3.5; VERDICT r1 item 2, r3
item 2).

Layout: the encoder's own.  q, k and v are the projections' [b, s, h]
outputs and the context is the [b, s, h] array ``attn_out`` reads: no
head transpose, no reshape to an hd-minor array (at hd 64 such an array
fills half of every 128-lane tile, so each relayout copy wrote twice the
bytes it read).  ``BlockSpec``s carve (rows, s, g*hd) column blocks, g
heads to a whole number of 128-lane tiles (``heads_per_block``: 2 heads
at hd 64, 4 at hd 32, all of them where h < 128); grid
(b // rows, nh // g), both parallel.  Inside a step the kernel goes row
by row and head by head with plain 2-D products on static lane slices of
the block.  The additive padding bias [b, s] (0 for real tokens, -1e9
for padding) goes in as [b, 1, s], one row per batch row, shared by its
heads.

What the chip says (TPU v5e, bf16, one bge-large layer's attention, my
chip runs, PR 25): at 64 x 512 the kernel takes 0.94 ms where the
transposing kernel it replaces took 2.21 ms with its copies (0.9 ms of
that the kernel), at 512 x 512 7.45 ms against 24.4 ms, the einsum path
3.06 and 28.2 ms.  Two other forms of the same block were timed and not
kept: products on the whole 128-lane block with the other head's lanes
zeroed (1.12 ms at 64 x 512; faster below s=512), and every row of a
block unrolled (no faster than two).  The
serving policy (``models/bert.py`` ``_use_fused_attention``: the kernel
from s=512) is older than this layout; PERF.md's open questions hold
what the chip says under 512.

The kernel is a single-device program: under a GSPMD-partitioned jit
Mosaic refuses it (parallel/sharding.py ``gspmd_config``).

On non-TPU backends the kernel runs in interpret mode (same code path,
same numerics) so the CPU test mesh exercises it; parity with the einsum
reference is asserted in tests/test_models.py, and
tests/test_tpu_compile.py compiles it for a described v5e.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .kernels import _round_up

# One (s, s) f32 score tile + the operand blocks must fit VMEM.
MAX_FUSED_SEQ = 1024
LANES = 128
# Rows of one grid step.  From 1 to 8 rows the chip times the kernel the
# same (1.00 ms a layer at 64 x 512 either way: the step's arithmetic, not
# its overhead, is what costs), so the block stays small: its first DMA is
# the one the pipeline cannot hide.
MAX_ROWS_PER_STEP = 4
# Of the ~16 MB/core VMEM, what one grid step's blocks and score tiles may
# take by ``best_heads_per_step``'s reckoning; Mosaic's own temporaries
# need the rest (bf16 at s=1024 reckons 10 MB for one row and compiles).
VMEM_BUDGET = 11 * 1024 * 1024


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def heads_per_block(nh: int, hd: int) -> int:
    """Heads to one column block of [b, s, h]: the fewest whose lanes fill
    whole 128-lane tiles (2 at hd 64, 4 at hd 32), every head where the
    whole of h is under one tile, 0 where h cannot be carved."""
    for g in range(1, nh + 1):
        if nh % g == 0 and (g * hd) % LANES == 0:
            return g
    return nh if nh * hd < LANES else 0


def _attn_kernel(
    q_ref, k_ref, v_ref, row_ref, out_ref, *, scale: float, hd: int
):
    # q/k/v/out blocks: [bb, s, g*hd], bb batch rows by the g heads of one
    # column block of the encoder's [b, s, h]; row block: [bb, 1, s], the
    # f32 key-padding bias, one row per batch row and shared by its
    # heads.  One (row, head) tile at a time: plain 2-D products,
    # matmul inputs in the storage dtype (bf16
    # feeds the MXU natively with f32 accumulation), softmax in f32 — the
    # einsum path's numerics.
    bb, _, width = q_ref.shape

    def one_row(r):
        row = row_ref[r]  # [1, s]
        for j in range(width // hd):
            lanes = slice(j * hd, (j + 1) * hd)
            q = q_ref[r, :, lanes]  # [s, hd]
            k = k_ref[r, :, lanes]
            v = v_ref[r, :, lanes]
            logits = (
                jax.lax.dot_general(
                    q, k, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                * scale
            )  # [s, s] f32
            logits = logits + row  # key-side padding bias
            mx = jnp.max(logits, axis=-1, keepdims=True)
            e = jnp.exp(logits - mx)
            probs = (e / jnp.sum(e, axis=-1, keepdims=True)).astype(v.dtype)
            ctx = jax.lax.dot_general(
                probs, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [s, hd] f32
            out_ref[r, :, lanes] = ctx.astype(out_ref.dtype)

    # A loop, not an unrolled block, so Mosaic's compile time does not
    # grow with the block; two rows an iteration let it overlap one row's
    # softmax with the next one's products (0.94 against 1.00 ms a layer
    # at 64 x 512, 7.45 against 7.93 at 512 x 512: chip, PR 25).
    pair = 2 if bb % 2 == 0 else 1

    def rows(i, carry):
        for u in range(pair):
            one_row(i * pair + u)
        return carry

    jax.lax.fori_loop(0, bb // pair, rows, 0)


# The kernel's device events are named after the jit that holds the
# pallas_call, and the benchmark reads them by this name
# (bench/reducers/attention_roofline.py).


@functools.partial(jax.jit, static_argnames=("scale", "nh", "heads_per_step"))
def fused_attention_tiled(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    bias: jax.Array,
    scale: float,
    nh: int,
    heads_per_step: int = 8,
) -> jax.Array:
    """q/k/v[b, s, h] as the projections leave them, bias[b, s] additive
    key padding -> ctx[b, s, h] as ``attn_out`` reads it.

    Softmax(QK^T * scale + bias) V fused, ``heads_per_step`` (batch row,
    head) tiles per grid step: ``heads_per_step // g`` rows of one
    g-head column block, which amortizes per-step grid/DMA overhead.
    ``best_heads_per_step`` picks one within the VMEM budget.
    """
    b, s, h = q.shape
    hd = h // nh
    g = heads_per_block(nh, hd)
    if g < 1:
        raise ValueError(
            f"hidden {h} = {nh} heads x {hd} cannot be carved into "
            f"{LANES}-lane column blocks"
        )
    bb = max(heads_per_step // g, 1)
    if b % bb:
        raise ValueError(
            f"heads_per_step={heads_per_step} ({bb} rows x {g} heads) "
            f"must divide b={b} rows"
        )
    qkv_spec = pl.BlockSpec(
        (bb, s, g * hd), lambda i, j: (i, 0, j), memory_space=pltpu.VMEM
    )
    row_spec = pl.BlockSpec(
        (bb, 1, s), lambda i, j: (i, 0, 0), memory_space=pltpu.VMEM
    )
    return pl.pallas_call(
        functools.partial(_attn_kernel, scale=scale, hd=hd),
        grid=(b // bb, nh // g),
        in_specs=[qkv_spec, qkv_spec, qkv_spec, row_spec],
        out_specs=qkv_spec,
        out_shape=jax.ShapeDtypeStruct((b, s, h), q.dtype),
        # independent grid steps: lets Mosaic double-buffer the block DMAs
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")
        ),
        interpret=_interpret(),
    )(q, k, v, bias.astype(jnp.float32)[:, None, :])


def best_heads_per_step(
    b: int,
    s: int,
    nh: int,
    hd: int,
    itemsize: int = 2,
    score_itemsize: int = 4,
    bias_itemsize: int = 4,
) -> int:
    """(Batch row, head) tiles per grid step: the g heads of one column
    block (``heads_per_block``) times the largest power-of-two number of
    rows that divides b and whose block set fits VMEM.  0 if h cannot be
    carved or not even a one-row step fits (callers fall back to einsum).

    Per step the kernel holds 4 [bb, s, g*hd] operand/output blocks in the
    storage dtype (``itemsize`` bytes/element, x2 for double-buffering,
    lanes padded to whole tiles), the bias rows (``bias_itemsize``, 8
    sublanes a row, x2), and the [s, s] score/prob tiles of the ONE head
    in work (``score_itemsize``, f32 today).  The per-dtype byte widths are
    parameters — not baked-in 4s — so a narrower score accumulator or
    bias layout reuses this one fit model, mirroring ``w8a8_shape_fits``'s
    ``w_bytes``.  A function of shapes and item sizes only.
    """
    g = heads_per_block(nh, hd)
    if g < 1:
        return 0
    width = _round_up(g * hd, LANES)
    scores = 2 * s * s * score_itemsize
    best = 0
    bb = 1
    while bb <= min(b, MAX_ROWS_PER_STEP):
        if b % bb == 0:
            blocks = bb * (8 * s * width * itemsize + 16 * s * bias_itemsize)
            if blocks + scores <= VMEM_BUDGET:
                best = bb
        bb *= 2
    return best * g


def attention_fits(s: int, hd: int) -> bool:
    """Coarse shape gate for the fused kernel; the binding per-dtype fit
    decision is ``best_heads_per_step(...) > 0``."""
    return s <= MAX_FUSED_SEQ and hd <= 256
