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

The softmax stays off the [s, s] tile wherever the mathematics lets it
(PR 46).  A head's two products are 256 pushes of 16 rows, 16 cycles each on
one of four MXUs: 1,024 cycles a (row, head) tile at s=512, the 50% of the
bf16 peak that heads of 64 allow (q k^T contracts over 64 of the array's 128
rows, probs v fills 64 of its 128 columns), 0.70 ms a layer at 64 x 512 and
5.6 at 512 x 512.  The tile is 256 f32 vregs, four register files, so every
pass over it is a load and a store beside its arithmetic.  What is left on
it: the key bias (scaled into raw scores' units once a row), the row's
maximum, and ``exp2((t - max t) * c)`` with c = scale * log2(e): no pass for
the scale, none for ``exp``'s own constant.  The weights go to the second
product unnormalised, in (0, 1] with the row's largest exactly 1, and the
division is done on its [s, 128] result.  Where heads share a 128-lane tile
(hd 32, 64) the second product's idle columns return the row's sums:
``e @ [v_head | 1]`` puts the head's context into its own lanes and the sum of
the very weights that multiplied v into every other lane, one lane turn
brings it home, and a tile's heads are divided and stored together, 128
lanes at a time.  A head that fills its tile (hd 128), or a hidden under one
tile, has no idle column and sums its weights on the vector unit.  Nothing
is approximated: f32 statistics, an exact division, the bias kept.  (With
float32 activations ON the chip, which nothing serves, Mosaic's default
precision multiplies in one bf16 pass as XLA's einsum does, and this body
hands it the unnormalised weights where the einsum path rounds the
normalised ones: 2.9e-3 and 2.1e-3 from it at heads of 64 and 128, s=128,
where PR 25's body read 7e-5 and 0; in bf16 both bodies read 0.0117 at
heads of 32 and 0.00036 in root mean square at the cells' shapes.)

What the chip says (TPU v5e, bf16, one bge-large layer's attention at
64 x 512 / 512 x 512, ms a layer; the kernel alone on the host's clock, 24
calls in one program over 24, my chip runs, PR 46,
``scripts/time_encoder_attention_forms.py``; "sched" is the chip's compiler
run for a described v5e, scheduled bundles of four tiles where the MXUs
need 4,096: a count, not a time).  Each step alone on PR 25's body (two
rows an iteration of a loop, four rows a grid step):

  PR 25's body                                   0.986 / 7.392   sched 4,914
  the division after the second product          0.891 / 6.674         4,581
  exp2 on raw scores, the constant folded        0.976 / 7.356         4,884
  both                                           0.887 / 6.622         4,537
  both, the constant on q's tile instead         0.936 / 6.592         4,520
    (not kept: q is rounded to bf16 once more, 0.000378 from the einsum
    path's context in root mean square for 0.000357, and no faster)
  both + the row's sum from the idle lanes       0.832 / 6.266         4,225
  + the bias as a contraction row, [q | 1] . [k | bias]
                                                 0.912 / 6.853         4,423
    (not kept: two selects on [s, 128] a head and the turn of the bias
    onto sublanes cost more than the add over [s, s] they replace)

Then the loop, on the kept arithmetic: one row an iteration 0.895 / 6.671,
two 0.832 / 6.266, all four of a step unrolled **0.801 / 6.027** (kept: the
scheduler's count says 0.7%, the chip 4%: a trip's boundary drains the
MXUs), eight rows a step all unrolled 0.795 / 5.960 (not kept: 1% for twice
the code and the compile), two rows a step 0.828 / 6.338.  As served
0.809 / 6.035; in the served programs' traces 0.776 / 5.99 ms a layer for
PR 25's 0.953 / 7.35.  Counted and not timed: the score tile worked in chunks
of 256 or 128 query rows (4,843 and 6,138 bundles: the keys are pushed
again a chunk), the [v | 1] select on packed words (4,260).  What is left
over the MXU's 0.70 / 5.6 is a grid step's own 390 bundles, the drain at
its end, and 3% of schedule; under it there is only fewer pushes.

PR 25's readings, on the arithmetic it had: the transposing kernel this
layout replaced 2.21 ms with its copies (0.9 of that the kernel) and 24.4,
the einsum path 3.06 and 28.2; products on the whole 128-lane block with
the other head's lanes zeroed 1.12 ms at 64 x 512 (faster below s=512),
not kept.  The serving policy (``models/bert.py``
``_use_fused_attention``: the kernel from s=512) is older than this layout;
PERF.md's open questions hold what the chip says under 512.

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
# Of the ~16 MB/core VMEM, what one grid step's blocks and tiles may take
# by ``best_heads_per_step``'s reckoning; Mosaic's own temporaries need the
# rest (bf16 at s=1024 reckons 12.4 MB for one row and compiles).
VMEM_BUDGET = 13 * 1024 * 1024


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


def _sum_rides(hd: int, width: int) -> bool:
    """Whole heads share each 128-lane tile of a ``width``-lane block: the
    second product has idle columns to return the row's sums in."""
    return hd < LANES and LANES % hd == 0 and width % LANES == 0


_LOG2E = 1.4426950408889634


def _attn_kernel(
    q_ref, k_ref, v_ref, row_ref, out_ref, *, scale: float, hd: int
):
    # q/k/v/out blocks: [bb, s, g*hd], bb batch rows by the g heads of one
    # column block of the encoder's [b, s, h]; row block: [bb, 1, s], the
    # f32 key-padding bias, one row per batch row and shared by its
    # heads.  One (row, head) tile at a time: plain 2-D products, matmul
    # inputs in the storage dtype (bf16 feeds the MXU natively with f32
    # accumulation), softmax in f32 -- the einsum path's numerics.  A
    # score tile is [s, s] f32, four register files at s=512, so every
    # pass over it is a load and a store: it gets the bias, the maximum,
    # one subtract, one multiply and exp2, and nothing else.
    bb, s, width = q_ref.shape
    c = scale * _LOG2E  # exp(scale * x) = exp2(c * x): no pass for the scale
    # Heads that share a 128-lane tile leave the second product idle
    # columns, and [v_head | 1] returns the row's sums in them for nothing.
    ride = _sum_rides(hd, width)

    def weights(r, lanes, bias):
        """exp2 of one head's scores less their row's maximum: [s, s] f32
        in (0, 1], the row's largest exactly 1."""
        t = jax.lax.dot_general(
            q_ref[r, :, lanes], k_ref[r, :, lanes], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # raw scores
        t = t + bias  # key-side padding bias
        return jnp.exp2((t - jnp.max(t, axis=-1, keepdims=True)) * c)

    def product(e, v):
        return jax.lax.dot_general(
            e.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    def one_row(r):
        bias = row_ref[r] * (1.0 / scale)  # [1, s], in raw scores' units
        if not ride:
            for j in range(width // hd):
                lanes = slice(j * hd, (j + 1) * hd)
                e = weights(r, lanes, bias)
                ctx = product(e, v_ref[r, :, lanes])  # [s, hd] f32
                total = jnp.sum(e, axis=-1, keepdims=True)
                out_ref[r, :, lanes] = (ctx / total).astype(out_ref.dtype)
            return
        lane = jax.lax.broadcasted_iota(jnp.int32, (s, LANES), 1)
        for t0 in range(0, width, LANES):
            tile = slice(t0, t0 + LANES)
            v = v_ref[r, :, tile]
            num = den = None
            for at in range(0, LANES, hd):
                mine = (lane >= at) & (lane < at + hd)
                e = weights(r, slice(t0 + at, t0 + at + hd), bias)
                # the head's lanes: its context; every other lane: e's sum
                o = product(e, jnp.where(mine, v, jnp.ones_like(v)))
                # a neighbour's lanes turned onto the head's own
                total = pltpu.roll(o, hd, 1)
                num = o if num is None else jnp.where(mine, o, num)
                den = total if den is None else jnp.where(mine, total, den)
            out_ref[r, :, tile] = (num / den).astype(out_ref.dtype)

    # Every row of the step unrolled: the scheduler overlaps one tile's
    # softmax with the next one's products across rows, and a loop's trip
    # boundary, where the MXUs drain, costs the chip about 0.3 us.  As a
    # loop that Mosaic unrolls, so that the row is traced once.
    jax.lax.fori_loop(0, bb, lambda r, _: one_row(r), None, unroll=True)


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
    sublanes a row, x2), the [s, s] score and weight tiles of the ONE head
    in work (``score_itemsize``, f32 today) and the second product's
    [s, 128] f32 tiles: its result, and where the row's sum rides it
    (heads that share a tile) the tile's numerators and denominators and
    the [v | 1] operand beside them.  The per-dtype byte widths are
    parameters -- not baked-in 4s -- so a narrower score accumulator or
    bias layout reuses this one fit model, mirroring ``w8a8_shape_fits``'s
    ``w_bytes``.  A function of shapes and item sizes only.
    """
    g = heads_per_block(nh, hd)
    if g < 1:
        return 0
    width = _round_up(g * hd, LANES)
    scores = 2 * s * s * score_itemsize
    ride = _sum_rides(hd, g * hd)
    products = s * LANES * (3 * 4 + itemsize if ride else 4)
    best = 0
    bb = 1
    while bb <= min(b, MAX_ROWS_PER_STEP):
        if b % bb == 0:
            blocks = bb * (8 * s * width * itemsize + 16 * s * bias_itemsize)
            if blocks + scores + products <= VMEM_BUDGET:
                best = bb
        bb *= 2
    return best * g


def attention_fits(s: int, hd: int) -> bool:
    """Coarse shape gate for the fused kernel; the binding per-dtype fit
    decision is ``best_heads_per_step(...) > 0``."""
    return s <= MAX_FUSED_SEQ and hd <= 256
