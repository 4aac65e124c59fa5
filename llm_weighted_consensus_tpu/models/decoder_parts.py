"""What the judges' decoders share (``models/glm_moe.py``, ``models/qwen3_next.py``,
``models/afmoe.py``): RMSNorm, the dense product with its W8A8 twin, SwiGLU, the
half-swapped rotary turn (and the turn of some lanes of every head where they lie),
the elementwise gate on an attention's output, one decoded row against cached keys of
fewer heads, the sigmoid router, the int8 walk over a parameter tree, and the routed
experts.

Routed experts run over the tokens sent to each, as TWO kernels over the
(token, choice) pairs sorted by expert (``ops/grouped_matmul.py``): gate and
up as one product with SwiGLU on its float32 accumulators, then down with the
router's weight of each row on its accumulator.  Around them three stages,
each a ``jax.named_scope``: ``experts_layout`` (the pairs sorted by expert
into padded row tiles, and the rows of ``h`` gathered into that order from
column chunks that stay in VMEM), ``experts_swiglu`` (the kernels),
``experts_combine`` (each token's k rows gathered back, a gather a choice,
and summed; nothing is multiplied there).  A decode step's handful of tokens
goes through the same path in tiles of 16 rows (tiles that hold no row fetch
and compute nothing).

A layer that holds experts 0..held-1 of a wider router (``held`` given) lays
out only the pairs whose expert is here, drops none of them, and returns the
partial sum: what the experts elsewhere would add is their chip's to add.
Its way back WALKS the pairs held (``ops/grouped_matmul.py::held_rows_sum``,
a kernel of that name under ``experts_combine``): one row fetched a held
pair, where k gathers a token would fetch three rows in four to mask them.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..ops import grouped_matmul as _gmm
from ..ops import rotary as _rotary


def rms(x, weight, eps: float):
    """x / sqrt(mean(x^2) + eps) · weight over the last dimension, in float32."""
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * weight.astype(jnp.float32)).astype(x.dtype)


def dense(x, p: dict):
    """x[..., in] @ kernel[in, out]; the W8A8 twin where the loader
    quantized this product (``JUDGE_QUANTIZE=int8``)."""
    if "kernel_q" in p:
        from .quant import dense_int8

        return dense_int8(x, p, impl="xla")
    return jnp.einsum(
        "...i,io->...o", x, p["kernel"], preferred_element_type=jnp.float32
    ).astype(x.dtype)


def swiglu(x, p: dict):
    gate = dense(x, p["gate"]).astype(jnp.float32)
    up = dense(x, p["up"]).astype(jnp.float32)
    return dense((jax.nn.silu(gate) * up).astype(x.dtype), p["down"])


def rope_angles(positions, dim: int, theta: float):
    """positions [...] -> (cos, sin) [..., dim / 2], float32."""
    inv = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    angle = positions.astype(jnp.float32)[..., None] * inv
    return jnp.cos(angle), jnp.sin(angle)


def rope(x, cos, sin):
    """x [..., d] with pairs (i, i + d/2); cos, sin broadcast to [..., d/2]."""
    half = x.shape[-1] // 2
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    ).astype(x.dtype)


def turn_heads(x, cos, sin, heads: int, first: int):
    """x [..., heads * hd] -> the same, lanes [first, first + dims) of every
    head turned; cos, sin [..., dims / 2], a position's.  Heads of whole
    128-lane columns over [b, s, width] are turned where they lie
    (``ops/rotary.py``); any other shape (a tiny preset, a decode step's rows)
    is cut apart, turned and put together again."""
    dims = 2 * cos.shape[-1]
    if cos.shape[:-1] == x.shape[1:-1] and _rotary.fits(x.shape, heads, first, dims):
        return _rotary.turn_lanes(x, cos, sin, heads=heads, first=first)
    xh = x.reshape(*x.shape[:-1], heads, -1)
    turned = rope(xh[..., first:first + dims], cos[..., None, :], sin[..., None, :])
    return jnp.concatenate(
        [xh[..., :first], turned, xh[..., first + dims:]], axis=-1
    ).reshape(x.shape)


def gated(ctx, gate):
    """The elementwise gate: ctx · sigmoid(gate), lane for lane, in float32."""
    return (ctx.astype(jnp.float32) * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(ctx.dtype)


def attend_cached(q, k_all, v_all, lens, kv_heads: int, window: bool = False):
    """One decoded row a call against its cached keys and its own: q [b, heads
    * hd] at position ``lens[b]``, k_all and v_all [b, slots, kv_heads * hd]
    (a key head serving ``heads / kv_heads`` query heads), the row's own key
    and value in the LAST slot -> context [b, kv_heads, heads / kv_heads, hd].
    The slots before it hold positions 0 .. slots - 2, of which those >=
    ``lens[b]`` are padding; or, with ``window``, the ``slots - 1`` positions
    before ``lens[b]`` (slot j is position ``lens[b] - (slots - 1) + j``; those
    before position 0 are padding).  Scores and softmax in float32."""
    b, slots = k_all.shape[:2]
    hd = k_all.shape[-1] // kv_heads
    q = q.reshape(b, kv_heads, -1, hd)
    scores = jnp.einsum(
        "bgrd,btgd->bgrt", q, k_all.reshape(b, slots, kv_heads, hd),
        preferred_element_type=jnp.float32,
    )
    t = jnp.arange(slots)[None, :]
    if window:  # the slots at or past position 0, and itself
        seen = t >= slots - 1 - lens[:, None]
    else:
        seen = (t < lens[:, None]) | (t == slots - 1)  # the cache, and itself
    scores = jnp.where(seen[:, None, None, :], scores / math.sqrt(hd), -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum(
        "bgrt,btgd->bgrd", probs, v_all.reshape(b, slots, kv_heads, hd),
        preferred_element_type=jnp.float32,
    ).astype(q.dtype)


def route_sigmoid(h, p: dict, k: int, scale: float):
    """h [t, hidden] -> (experts [t, k] int32, weights [t, k] float32): every
    expert scored ``sigmoid(W_r · h)`` in float32, the top k of score +
    ``p["bias"]`` (the bias chooses, it does not weigh), the chosen weighed by
    their unbiased scores over their sum, times ``scale``."""
    logits = jnp.dot(
        h.astype(jnp.float32), p["router"], precision=jax.lax.Precision.HIGHEST
    )
    score = jax.nn.sigmoid(logits)
    _, chosen = jax.lax.top_k(score + p["bias"], k)
    weight = jnp.take_along_axis(score, chosen, axis=1)
    weight = weight / jnp.sum(weight, axis=1, keepdims=True)
    return chosen.astype(jnp.int32), weight * scale


def quantize_dense(params: dict) -> dict:
    """``JUDGE_QUANTIZE=int8``: every ``{"kernel"}`` product of a decoder's
    parameter tree (the token mixers' projections, a dense layer's MLP, the
    shared expert) becomes ``quant.dense_int8``'s ``{"kernel_q", "scale",
    "bias"}``.  Router, routed experts, arrays held bare, embedding and head
    stay as they are."""
    from .quant import quantize_weight

    def walk(node):
        if isinstance(node, dict):
            if set(node) == {"kernel"}:
                q, scale = quantize_weight(node["kernel"])
                bias = jnp.zeros((q.shape[-1],), node["kernel"].dtype)
                return {"kernel_q": q, "scale": scale, "bias": bias}
            return {k: walk(v) for k, v in node.items()}
        if isinstance(node, (list, tuple)):
            return type(node)(walk(v) for v in node)
        return node

    return walk(params)


# rows of the padded layout a share's usual routing fits, at most (the first
# judge's whole bound: tables of that many rows stay in VMEM for XLA's gathers)
USUAL_ROWS = 114_688


def usual_rows(pairs: int, experts: int, held: int, tile: int) -> int:
    """Rows of the padded layout that a share's usual routing fits: four
    times the load the held experts see where the router spreads its pairs
    evenly, in whole tiles, and ``USUAL_ROWS`` at most (a quarter of a router
    held: ``USUAL_ROWS``; a sixteenth of one: a quarter of the pairs)."""
    return min(USUAL_ROWS, -(-4 * pairs * held // (experts * tile)) * tile)


def experts_grouped(h, chosen, weight, p: dict, experts: int, held: int | None = None):
    """h [t, hidden], chosen and weight [t, k], ``p`` with ``w_gate``,
    ``w_up`` [E, hidden, width] and ``w_down`` [E, width, hidden] -> (the
    chosen experts' weighted sum [t, hidden], counts).  ``experts`` is the
    router's width (it sizes the tiles: a group's mean load); with ``held``
    the weights are those of experts 0..held-1 and counts is [held + 1], the
    last entry the pairs routed elsewhere; else counts is [experts].

    A share's layout is sized for every pair being held here (none may be
    dropped), many times its usual load: where that bound passes
    ``USUAL_ROWS`` and the tiles in use fit ``usual_rows`` the rows past them
    are neither gathered nor laid out (one ``lax.cond`` on the layout's own
    count; the other branch is the same code over the whole bound).

    The way back, by what the layer holds.  Every expert held (``held`` None):
    a gather a choice from column chunks of the down product that stay in
    VMEM, each read a row that is summed; at a row every 2-3 ns that is at par
    with a copy a row out of HBM, so it stays as it is.  A share: three pairs
    in four are elsewhere and the same gathers would fetch a row for each to
    mask it, so the down product leaves a row a slab and ``held_rows_sum``
    fetches one row a pair held here (the pairs elsewhere are sorted out of
    its lists before it runs); the sum is the same float32 sum in the order
    of the choices, bit for bit.
    The walk serves every width whose row is whole sublanes of words and at
    least one whole (8, 128) tile of them, the slab padded up to whole tiles
    where the row does not fill them (5120 x bfloat16: 20 sublanes in a slab
    of 24).  A row under one whole tile of words, or not whole sublanes
    (``row_slabs`` (0, 0): the tiny presets, hidden 64-512), keeps the masked
    gathers: its slab would be mostly padding."""
    t, k = chosen.shape
    tile = _gmm.tile_for(t * k, experts)
    with jax.named_scope("experts_layout"):
        if held is None:
            tables = _gmm.route_layout_weighted(
                chosen.reshape(-1), weight.reshape(-1), experts, tile
            )
            here = None
        else:
            tables, here = _gmm.route_layout_held(
                chosen.reshape(-1), weight.reshape(-1), held, tile
            )
        pair_of_row, row_of_pair, tile_expert, used, counts, row_weight = tables
        # a share's rows are walked where a row is a whole tile of words or more
        walk = here is not None and _gmm.row_slabs(h.shape[1], h.dtype)[0] > 0
        if here is None:
            rows_of = row_of_pair.reshape(t, k)
        elif walk:  # a pair elsewhere is skipped: a row no table has
            rows_of = jnp.where(here, row_of_pair, -1)
        else:  # a pair elsewhere reads a row that exists and counts as nothing
            here = here.reshape(t, k)
            rows_of = jnp.where(here, row_of_pair.reshape(t, k), 0)
        parts = jnp.split(h, _gmm.column_chunks(*h.shape, h.dtype.itemsize), axis=1)

    def over(rows: int):
        """The layer over the first ``rows`` rows of the layout."""
        with jax.named_scope("experts_layout"):
            # rows gathered from column chunks small enough to stay in VMEM
            x = tuple(part[pair_of_row[:rows] // k] for part in parts)
        with jax.named_scope("experts_swiglu"):
            product = partial(
                _gmm.grouped_expert_product, tile_expert=tile_expert[: rows // tile],
                tiles_used=used, tile=tile,
            )
            y = product(
                product(x, p["w_gate"], w_up=p["w_up"]), p["w_down"],
                row_weight=row_weight[:rows], slabs=walk,
                out_chunks=None if walk else _gmm.column_chunks(
                    rows, h.shape[1], h.dtype.itemsize
                ),
            )
        with jax.named_scope("experts_combine"):
            if walk:
                return _gmm.held_rows_sum(y, rows_of, k=k, width=h.shape[1])
            # a gather a choice, so that no [t, k, hidden] is laid out between
            if here is None:
                take = lambda part, j: part[rows_of[:, j]].astype(jnp.float32)  # noqa: E731
            else:
                take = lambda part, j: jnp.where(  # noqa: E731
                    here[:, j, None], part[rows_of[:, j]].astype(jnp.float32), 0.0
                )
            routed = [sum(take(part, j) for j in range(k)) for part in y]
            return jnp.concatenate(routed, axis=1).astype(h.dtype)

    whole = pair_of_row.shape[0]
    if held is None or whole <= USUAL_ROWS:
        return over(whole), counts
    usual = usual_rows(t * k, experts, held, tile)
    fits = used[0] * tile <= usual
    return jax.lax.cond(fits, lambda: over(usual), lambda: over(whole)), counts


def _tiles(load, experts: int, share: bool):
    """On the host, from the counts a dispatch brought back (``load`` [layers,
    held + 1], a share's ``counts`` a sparse layer; [layers, experts] where
    every expert is held and ``share`` is false): a layer's row tiles that
    hold a pair, and the tiles of the layout's two sizes as
    ``experts_grouped`` reckons them, the usual load's and the whole bound's
    (the same where the layout has one)."""
    pairs, groups = int(load[0].sum()), load.shape[1]
    held = groups - 1 if share else groups
    tile = _gmm.tile_for(pairs, experts)
    in_use = (-(-load[:, :held] // tile)).sum(axis=1)
    whole = _gmm.padded_rows(pairs, groups, tile)
    usual = whole
    if share and whole > USUAL_ROWS:
        usual = usual_rows(pairs, experts, held, tile)
    return in_use, usual // tile, whole // tile


def layers_past_usual(load, experts: int) -> int:
    """The layers of a share's dispatch whose tiles in use passed
    ``usual_rows``, so that ``experts_grouped``'s ``lax.cond`` ran them over
    the whole bound."""
    load = np.asarray(load)
    if not load.size:
        return 0
    in_use, usual, _ = _tiles(load, experts, True)
    return int((in_use > usual).sum())


def tiles_laid_and_in_use(load, experts: int, share: bool = True) -> tuple[int, int]:
    """The row tiles of a dispatch's sparse layers: those the layouts laid,
    every one a grid step a column block of ``grouped_expert_product`` (the
    usual load's or the whole bound's, as the ``lax.cond`` chose), and those
    of them that hold a pair.  The rest are steps that run no product and,
    since ISSUE 43, move no block."""
    load = np.asarray(load)
    if not load.size:
        return 0, 0
    in_use, usual, whole = _tiles(load, experts, share)
    return int(np.where(in_use > usual, whole, usual).sum()), int(in_use.sum())
