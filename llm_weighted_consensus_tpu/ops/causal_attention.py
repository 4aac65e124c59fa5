"""Causal blockwise attention for a decoder's prefill (Pallas).

``ops/attention.py``'s kernel is the encoder's: bidirectional, one whole
[s, s] score tile in VMEM, heads of 32 or 64.  A judge's prefill is 8k
tokens under a causal mask with heads of 256: 20 x 8192 x 8192 float32
scores are 5.4 GB a call, so neither that kernel nor a plain ``einsum`` can
serve it.  This kernel walks the keys in blocks and keeps a running maximum,
a running sum and an unnormalised accumulator per query block (the online
softmax), so that nothing of size s x s ever exists.

Layout: the projections' own.  q and k are [b, s, heads * hd], v is
[b, s, heads * dv] and the context is the [b, s, heads * dv] array the output
projection reads; a head is one column block, ``hd`` wide against the keys and
``dv`` wide against the values (each a multiple of 128 lanes, or the whole of
the last dimension), so there is no head transpose around the kernel.  ``hd``
is what q and k SHARE and nothing else: the accumulator and the context take
the value width, which may be narrower (128 against 192 or 256).  A key head
that is not whole columns (128 | 64 = 192) cannot be a block: the loader lays
it in whole columns with zero lanes (``models/glm_moe.py``), which leaves
every q.k what it was and costs the MXU, which contracts in 128s, nothing.
Keys and values may have fewer heads than the queries (``kv_heads``): a
query head's key block is found by ``head // group`` in the index map, and
with a key head a query head the index map is the one it always was.  The
values may have fewer heads still (``value_heads``: a differential pair's two
key heads weigh ONE value head of twice the width, ``models/sambay.py``); a
query head's value block is found the same way, by its own group.

Two jitted names over one body.  ``causal_attention_blockwise`` attends every
key at or before the query (or a selection among them);
``window_attention_blockwise`` the ``window`` keys up to and with the query.
What differs for a window is the table of steps (only the blocks the band
touches), a second masked edge, and the blocks, which follow the window.

The schedule follows from (s, block_q, block_k, hd) and nothing else:

- Grid (b, heads, steps): one step a (query block, key block) pair of the
  lower triangle, a query block's key blocks in order, through two
  scalar-prefetch tables built at trace time (``_steps``).  A pair above the
  diagonal is no step at all.
- A block below the diagonal is walked in bands of ``_BAND`` query rows, each
  a whole online-softmax update of its own rows: straight-line code, so one
  band's softmax runs under the next band's q.k.
- The block the diagonal crosses, where it is square and splits
  (``stripe_for``), is walked in stripes of ``_STRIPE`` rows; a stripe
  multiplies against the keys up to its own end only, and only its last
  square chunk is masked.  Any other shape (block_q != block_k, a block of
  one stripe) stays one whole masked tile.  ``work_over_causal`` is what
  that leaves: 1.031 of the causal pairs at 8192, from 1.125 by whole
  tiles of 1024.
- With a window, a query block's steps begin at the block that holds the
  first key of its first query (``_steps``), the running state is reset
  there, and the block the band's OLD edge crosses is walked as the
  diagonal's is, in the same stripes mirrored: a stripe of query rows skips
  the keys before the chunk that holds its first row's first key, masks the
  one or two chunks the edge passes through (both edges in one mask, by
  position) and multiplies the chunks behind them unmasked
  (``_edge_parts``).  Where that edge lies in the tile follows from the
  shapes alone: with square blocks the distance qi - ki says which block a
  step meets, and one or two distances are the old edge's
  (``_edge_offsets``).  Any other shape (a block that does not split, a
  window narrower than the block) stays one whole masked tile.  A row may
  see NO key of that block (the last of a query block where the window is
  whole blocks): its maximum stays ``_NEG``, ``exp2(0)`` puts ones into its
  sums, and the next block's first real key wipes them (alpha = 0); every
  row meets its own key in a later step.  The blocks follow the window
  (``window_block``: the largest inside its reach; 512 for 513 keys, where a
  query block meets exactly two key blocks), because today's 2048 would
  multiply 7.2 times the band.  ``work_over_window`` is the count beside
  ``work_over_causal``: pairs multiplied over the ``band_pairs`` kept.  At
  16,384 slots and 4096 keys 1.0625 at blocks of 2048 (1.25 with the old
  edge's block whole); at 8192 slots and 513 keys 1.50 at blocks of 512
  (1.74 with 15 whole edge tiles), 1.50 at 256 (whole tiles of one stripe),
  3.86 at 1024.
- A score costs a subtract, a multiply and an ``exp2``: the maximum is
  taken over raw scores (scale > 0) and scale * log2(e) is one constant.
  Where the shapes allow, the running maximum and sum are kept once a lane
  ([block_q, 128]): no column is broadcast across lanes, and the sum's
  lanes are added up once, when the query block is written.

What the chip says (TPU v5e, bf16, 3 x 8192 tokens, 20 heads of 256: one
layer's attention of a judge panel; my chip runs, PR 30; the kernel alone on
the host's clock, which reads 0.75 ms over the trace's events).  PR 27's
kernel (grid (b, heads, 8, 8), whole tiles of 1024, ``exp``, [block_q, 1]
columns) 16.93 ms, for 10.46 at the bf16 peak.  Each step alone: the
triangle's grid 15.30 (an empty step costs 0.97 us, 1,680 of them a layer);
the diagonal in stripes of 256 16.02 (128: 16.49, 512: 15.93 with twice the
waste); ``exp2`` with the folded constant 16.26; maximum and sum a lane
16.16 (the maximum alone 16.82); together 12.87, the sum of the four.
Bands of 256-512 rows below the diagonal 12.68-12.74 (alone, on the old
kernel, they cost: 16.9-19.6).  Blocks of 2048: 12.13-12.19 (600 steps a
layer for 2,160, half the rescales; 4096: 13.9-14.7).  The bands as a
``fori_loop``: 12.66, two a trip 12.42, four 12.27, so they are unrolled;
q.k of the next band issued by hand before the softmax: 12.45, the
scheduler does better alone.  Compiling: 6.2 s here against 2.2 (the
program's seven calls compile once).  In the served program the kernel's
events are 11.51 ms a layer, 91% of its roofline.  jax's own kernels at
the same size in their [b, h, s, d] layout, the transposes left out:
``flash_attention`` 16.09 ms at blocks 1024 / 1024 / 1024 (16.5-17.3 at
others), ``splash_attention`` 15.21 at 1024 / 1024 / 512 (15.9-16.4).

Matmul inputs stay in the storage dtype (bf16 feeds the MXU natively),
scores, softmax and the accumulator are float32.  On a backend without a
TPU the kernel runs in interpret mode, the same code path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -1e30  # a masked score: finite, so that exp2((_NEG - m) * c) is an exact 0
_LOG2E = 1.4426950408889634
_BLOCKS = (2048, 1024, 512, 256, 128, 64, 32, 16, 8)
_STRIPE = 256  # query rows of a stripe of the block the diagonal crosses
_BAND = 512  # query rows of a stripe of a block below the diagonal
_LANES = 128
_VMEM_LIMIT = 48 << 20  # of a v5e core's 128 MiB; blocks of 2048 pass the default 16


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def block_for(seq: int, cap: int = 2048) -> int:
    """The largest block of ``_BLOCKS`` under ``cap`` that divides ``seq``."""
    for block in _BLOCKS:
        if block <= cap and seq % block == 0:
            return block
    raise ValueError(f"sequence length {seq} is not a multiple of 8")


def stripe_for(block_q: int, block_k: int) -> int:
    """Query rows of a stripe of the block the diagonal crosses; 0 where the
    block is not square or too small to split, and stays one masked tile."""
    square = block_q == block_k and block_q % _STRIPE == 0
    return _STRIPE if square and block_q > _STRIPE else 0


@functools.lru_cache(maxsize=None)
def _steps(seq: int, block_q: int, block_k: int, window: int = 0):
    """(query block, key block) of every grid step: the lower triangle's
    pairs, a query block's key blocks in order; with a ``window`` (keys a
    query, its own among them) only the pairs the band touches, from the
    block that holds the first key of the block's first query."""
    pairs = [
        (qi, ki)
        for qi in range(seq // block_q)
        for ki in range(
            max(0, qi * block_q - window + 1) // block_k if window else 0,
            (qi * block_q + block_q - 1) // block_k + 1,
        )
    ]
    return tuple(np.asarray(column, np.int32) for column in zip(*pairs))


def window_block(seq: int, window: int) -> int:
    """The block a windowed layer takes: the largest that divides ``seq`` and
    lies inside the window's reach, so that a query block meets the fewest
    key blocks the band allows (window 513: blocks of 512, two a query
    block; today's 2048 would multiply seven times the band)."""
    return block_for(seq, cap=max(window - 1, _BLOCKS[-1]))


def band_pairs(seq: int, window: int) -> int:
    """(query, key) pairs with query - window < key <= query: what a windowed
    layer attends over ``seq`` slots."""
    w = min(window, seq)
    return w * (w + 1) // 2 + (seq - w) * w


def _edge_offsets(seq: int, block_q: int, block_k: int, window: int) -> tuple[int, ...]:
    """``qi - ki`` of the steps whose key block the band's OLD edge crosses
    (wholly below the diagonal, not wholly inside the band).  With square
    blocks that distance alone says which block a step meets, and there are
    one or two such distances, fixed by the shapes."""
    qi, ki = _steps(seq, block_q, block_k, window)
    below = ki * block_k + block_k - 1 <= qi * block_q
    inside = ki * block_k > qi * block_q + block_q - 1 - window
    return tuple(np.unique((qi - ki)[below & ~inside]).tolist())


def _edge_parts(r0: int, stripe: int, block_k: int, d: int) -> list:
    """Column ranges of the old edge's key block that the stripe of query rows
    [r0, r0 + stripe) meets, as ``update`` takes them; a row sees the columns
    ``col > row + d``.  The chunks before the one that holds the first row's
    first key are skipped, the one or two the edge passes through are masked,
    the rest are one unmasked range; none where no row sees a key."""
    skipped = max(r0 + d + 1, 0) // stripe * stripe
    whole = min(max(-(-(r0 + stripe + d) // stripe) * stripe, 0), block_k)
    parts = [(c0, stripe, "tile") for c0 in range(skipped, whole, stripe)]
    return parts + ([(whole, block_k - whole, None)] if whole < block_k else [])


def work_over_window(seq: int, block_q: int, block_k: int, window: int) -> float:
    """Score pairs the kernel multiplies over the ``band_pairs`` the window
    keeps.  Where ``stripe_for`` splits the block and the window covers it,
    the block the diagonal crosses is walked as ``work_over_causal`` says and
    the block the old edge crosses in the same stripes mirrored
    (``_edge_parts``); every other step is a whole tile."""
    qi, ki = _steps(seq, block_q, block_k, window)
    stripe = stripe_for(block_q, block_k) if block_q <= window else 0
    tile = block_q * block_k
    if not stripe:
        return len(qi) * tile / band_pairs(seq, window)
    n = block_q // stripe
    by_offset = {0: stripe * stripe * n * (n + 1) // 2}  # the diagonal's block
    for delta in _edge_offsets(seq, block_q, block_k, window):
        by_offset[delta] = stripe * sum(
            cols
            for r0 in range(0, block_q, stripe)
            for _, cols, _ in _edge_parts(r0, stripe, block_k, delta * block_q - window)
        )
    pairs = sum(by_offset.get(delta, tile) for delta in (qi - ki).tolist())
    return pairs / band_pairs(seq, window)


def work_over_causal(
    seq: int, block_q: int, block_k: int, stripe: int | None = None
) -> float:
    """Score pairs the kernel multiplies over the seq * (seq + 1) / 2 the
    causal mask keeps; ``stripe`` as ``stripe_for`` gives it unless given
    (0: whole tiles)."""
    if stripe is None:
        stripe = stripe_for(block_q, block_k)
    qi, ki = _steps(seq, block_q, block_k)
    crossed = int((ki * block_k + block_k - 1 > qi * block_q).sum())
    pairs = (len(qi) - crossed) * block_q * block_k
    if stripe:  # stripe r against the keys up to its own end
        n = block_q // stripe
        pairs += crossed * stripe * stripe * n * (n + 1) // 2
    else:
        pairs += crossed * block_q * block_k
    return pairs / (seq * (seq + 1) // 2)


def _kernel(
    qi_ref, ki_ref, q_ref, k_ref, v_ref, *rest, scale, bq, bk, stripe, band, window=0,
    edges=(),
):
    # with a selection, its tile comes behind v: 1 where the query may meet the key
    keep_ref = rest[0] if len(rest) == 5 else None
    o_ref, m_ref, l_ref, acc_ref = rest[-4:]
    step = pl.program_id(2)
    qi, ki = qi_ref[step], ki_ref[step]
    last = (qi * bq + bq - 1) // bk  # the key block that holds the diagonal's end
    # ... and the one that holds the first key of the block's first query
    first = jnp.maximum(qi * bq - (window - 1), 0) // bk if window else 0
    lanes, hd = m_ref.shape[1], acc_ref.shape[1]  # hd: a VALUE head's lanes
    c = scale * _LOG2E  # exp(scale * x) = exp2(c * x): one multiply a score

    def across(x, n):
        """[rows, lanes] against n columns: the same registers again."""
        return x if lanes == 1 or n == lanes else pltpu.repeat(x, n // lanes, axis=1)

    def row_sums(p):
        """[rows, lanes]: lane j holds the sum of columns j, j + lanes, ...;
        the lanes are summed once, when the query block is written."""
        if lanes == 1:
            return jnp.sum(p, axis=1, keepdims=True)
        chunks = [p[:, j:j + lanes] for j in range(0, p.shape[1], lanes)]
        return functools.reduce(jnp.add, chunks)

    @pl.when(ki == first)
    def _():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def update(r0, rows, parts):
        """Query rows [r0, r0 + rows) of the block against the key block's
        column ranges ``parts``: (first column, columns, mask), the mask
        None, "triangle" (the square chunk on the diagonal) or "tile" (a
        whole tile by its positions in the sequence)."""
        rs = slice(r0, r0 + rows)
        q = q_ref[rs, :]
        scores = []
        for c0, cols, mask in parts:
            raw = jax.lax.dot_general(
                q, k_ref[c0:c0 + cols, :], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [rows, cols], not yet scaled: scale > 0 keeps the maximum
            if keep_ref is not None:  # the selection is causal already
                chosen = keep_ref[rs, c0:c0 + cols].astype(jnp.int32) != 0
                raw = jnp.where(chosen, raw, _NEG)
            elif mask:
                row = jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 0)
                col = jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 1)
                if mask == "tile":
                    row, col = qi * bq + r0 + row, ki * bk + c0 + col
                seen = col <= row
                if window and mask == "tile":  # the band's old edge as well
                    seen = seen & (col > row - window)
                raw = jnp.where(seen, raw, _NEG)
            scores.append(raw)
        m_prev = m_ref[rs, :]
        m_new = functools.reduce(
            jnp.maximum, [jnp.max(raw, axis=1, keepdims=True) for raw in scores], m_prev
        )
        alpha = jnp.exp2((m_prev - m_new) * c)
        l_new, acc_new = alpha * l_ref[rs, :], across(alpha, hd) * acc_ref[rs, :]
        for raw, (c0, cols, _) in zip(scores, parts):
            p = jnp.exp2((raw - across(m_new, cols)) * c)
            l_new += row_sums(p)
            acc_new += jax.lax.dot_general(
                p.astype(v_ref.dtype), v_ref[c0:c0 + cols, :], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
        m_ref[rs, :], l_ref[rs, :], acc_ref[rs, :] = m_new, l_new, acc_new

    # a key block lies wholly below the diagonal when its last column is at
    # or before the query block's first row
    below = ki * bk + bk - 1 <= qi * bq
    inside = below
    if window:
        # ... and wholly inside the band when its first column is within the
        # window of the query block's last row; the block the band's old edge
        # crosses goes as the diagonal's does: in stripes where it splits
        # (``edges``: the distances qi - ki of such blocks), else one masked tile
        inside = below & (ki * bk > qi * bq + bq - 1 - window)

        if not edges:

            @pl.when(below & jnp.logical_not(inside))
            def _():
                update(0, bq, [(0, bk, "tile")])

        for delta in edges:  # the diagonal's stripes mirrored: a row sees col > row + d

            @pl.when(qi - ki == delta)
            def _(d=delta * bq - window):
                for r0 in range(0, bq, stripe):
                    parts = _edge_parts(r0, stripe, bk, d)
                    if parts:
                        update(r0, stripe, parts)

    @pl.when(inside)
    def _():
        for r0 in range(0, bq, band):
            update(r0, band, [(0, bk, None)])

    @pl.when(jnp.logical_not(below))
    def _():
        if not stripe:
            update(0, bq, [(0, bk, "tile")])
            return
        for r0 in range(0, bq, stripe):
            # the keys before the stripe's own chunk need no mask
            before = [(0, r0, None)] if r0 else []
            update(r0, stripe, before + [(r0, stripe, "triangle")])

    @pl.when(ki == last)
    def _():
        norm = 1.0 / jnp.sum(l_ref[...], axis=1, keepdims=True)
        o_ref[...] = (acc_ref[...] * norm).astype(o_ref.dtype)


def _attend(
    q, k, v, keep, *, heads, scale, kv_heads, block_q, block_k, interpret, window=0,
    value_heads=0,
):
    """The pallas_call behind both jitted names below."""
    b, s, width = q.shape
    hd = width // heads
    group = heads // (kv_heads or heads)
    # a value head may be narrower than a key head, or wider and shared by several
    value_group = heads // value_heads if value_heads else group
    dv = v.shape[-1] * value_group // heads
    if interpret is None:
        interpret = _interpret()
    if hd * heads != width:
        raise ValueError(f"heads of {hd} lanes cannot be carved from {width}")
    if not interpret and heads > 1 and (hd % _LANES or dv % _LANES):
        which = "key" if hd % _LANES else "value"
        raise ValueError(
            f"a {which} head of {dv if which == 'value' else hd} lanes is not whole "
            f"{_LANES}-lane columns (key heads {hd}, value heads {dv}): a block is one "
            "head's columns of the projections' own layout.  The loader can lay such a "
            "head in whole columns with zero lanes (zero rows of the query and key "
            "products: every q.k stays what it was; zero value lanes need zero rows of "
            "the output product)"
        )
    if group * (kv_heads or heads) != heads or k.shape[-1] * group != width:
        raise ValueError(f"{heads} query heads on keys of width {k.shape[-1]}")
    if dv * heads != v.shape[-1] * value_group:
        raise ValueError(f"{heads} query heads on values of width {v.shape[-1]}")
    if window and keep is not None:
        raise ValueError("a windowed layer attends its band, not a selection")
    block = window_block(s, window) if window else block_for(s)
    bq, bk = block_q or block, block_k or block
    # the running maximum and sum stand once a lane where the shapes allow
    lanes = _LANES if bk % _LANES == 0 and hd % _LANES == 0 and dv % _LANES == 0 else 1
    band = _BAND if bq % _BAND == 0 else bq
    qi_of_step, ki_of_step = _steps(s, bq, bk, window)
    # the diagonal's block goes in stripes where the window covers the block
    stripe = stripe_for(bq, bk) if not window or bq <= window else 0
    windowed = {"window": window} if window else {}
    if window and stripe:  # ... and so does the block the old edge crosses
        windowed["edges"] = _edge_offsets(s, bq, bk, window)

    def q_index(bi, h, step, qi_of_step, ki_of_step):
        return bi, qi_of_step[step], h

    def kv_index(bi, h, step, qi_of_step, ki_of_step):
        return bi, ki_of_step[step], h if group == 1 else h // group

    def value_index(bi, h, step, qi_of_step, ki_of_step):
        return bi, ki_of_step[step], h // value_group

    def keep_index(bi, h, step, qi_of_step, ki_of_step):
        return bi, qi_of_step[step], ki_of_step[step]

    selection = [] if keep is None else [keep]
    return pl.pallas_call(
        functools.partial(
            _kernel, scale=scale, bq=bq, bk=bk, stripe=stripe, band=band, **windowed
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, heads, len(qi_of_step)),
            in_specs=[
                pl.BlockSpec((None, bq, hd), q_index),
                pl.BlockSpec((None, bk, hd), kv_index),
                pl.BlockSpec((None, bk, dv), value_index if value_heads else kv_index),
                *[pl.BlockSpec((None, bq, bk), keep_index) for _ in selection],
            ],
            out_specs=pl.BlockSpec((None, bq, dv), q_index),
            scratch_shapes=[
                pltpu.VMEM((bq, lanes), jnp.float32),
                pltpu.VMEM((bq, lanes), jnp.float32),
                pltpu.VMEM((bq, dv), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, s, heads * dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=interpret,
    )(jnp.asarray(qi_of_step), jnp.asarray(ki_of_step), q, k, v, *selection)


@functools.partial(
    jax.jit,
    static_argnames=("heads", "scale", "kv_heads", "block_q", "block_k", "interpret"),
)
def causal_attention_blockwise(
    q, k, v, keep=None, *, heads: int, scale: float, kv_heads: int = 0,
    block_q: int = 0, block_k: int = 0, interpret: bool | None = None,
):
    """q: [b, s, heads * hd], k: [b, s, kv_heads * hd], v: [b, s, kv_heads *
    dv] (``kv_heads`` 0: a key head a query head) -> context [b, s, heads *
    dv]; position i attends positions <= i.  ``hd`` is what q and k share; a
    value head may be of another width ``dv`` (the accumulator's and the
    context's), and on a TPU both are whole 128-lane columns unless there is
    one head.  With fewer key heads, query head h reads key
    head ``h // (heads / kv_heads)`` through the block's index: no key is
    repeated in memory.  With ``keep`` [b, s, s] int8 (a learned sparse
    selection, ``ops/sparse_index.py``; 0 wherever key > query) a query meets
    only the keys its row marks: every pair of the lower triangle's blocks is
    still multiplied, and a pair not chosen is masked before the softmax in
    place of the causal mask; a head reads the tile again.  The jitted
    function's name is the kernel's name in a device trace."""
    return _attend(
        q, k, v, keep, heads=heads, scale=scale, kv_heads=kv_heads, block_q=block_q,
        block_k=block_k, interpret=interpret,
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "heads", "scale", "window", "kv_heads", "value_heads", "block_q", "block_k",
        "interpret",
    ),
)
def window_attention_blockwise(
    q, k, v, *, heads: int, scale: float, window: int, kv_heads: int = 0,
    value_heads: int = 0, block_q: int = 0, block_k: int = 0, interpret: bool | None = None,
):
    """``causal_attention_blockwise`` over a WINDOW: position i attends the
    ``window`` positions i - window < j <= i (its own among them).  The same
    kernel body; what differs is the table of steps (``_steps``: only the
    pairs of blocks the band touches; ``work_over_window`` says what that
    multiplies over the band), a second masked edge (the block the band's
    old edge crosses goes in the diagonal's stripes mirrored where the block
    splits and the window covers it, else as one whole masked tile, as an
    unsplit diagonal block does) and the blocks, which follow the window
    (``window_block``).  With ``value_heads`` v is [b, s, value_heads * dv]
    and query head h weighs value head ``h // (heads / value_heads)``.  Under
    its own jitted name, so that a device trace tells the two apart."""
    return _attend(
        q, k, v, None, heads=heads, scale=scale, kv_heads=kv_heads, block_q=block_q,
        block_k=block_k, interpret=interpret, window=window, value_heads=value_heads,
    )


def causal_attention_einsum(
    q, k, v, keep=None, *, heads: int, scale: float, kv_heads: int = 0, window: int = 0,
    value_heads: int = 0,
):
    """The kernels' plain twin: whole [s, s] scores (tests, tiny sizes)."""
    b, s, width = q.shape
    hd = width // heads
    qh, kh = (x.reshape(b, s, -1, hd) for x in (q, k))
    vh = v.reshape(b, s, value_heads or kh.shape[2], -1)
    kh, vh = (jnp.repeat(x, heads // x.shape[2], axis=2) for x in (kh, vh))
    scores = jnp.einsum(
        "bqhd,bkhd->bhqk", qh, kh, preferred_element_type=jnp.float32
    ) * scale
    seen = jnp.tril(jnp.ones((s, s), bool))
    if window:
        seen = seen & ~jnp.tril(jnp.ones((s, s), bool), -window)
    if keep is not None:
        seen = seen & (keep != 0)[:, None]
    probs = jax.nn.softmax(jnp.where(seen, scores, _NEG), axis=-1)
    out = jnp.einsum(
        "bhqk,bkhd->bqhd", probs.astype(v.dtype), vh,
        preferred_element_type=jnp.float32,
    )
    return out.reshape(b, s, -1).astype(q.dtype)
