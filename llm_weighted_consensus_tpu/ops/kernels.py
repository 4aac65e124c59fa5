"""Fused Pallas TPU kernels for the consensus hot path.

The fusion that matters for serving latency (keeping intermediates in
VMEM instead of round-tripping HBM between XLA ops):

* ``fused_cosine_vote``  — l2-normalize + pairwise cosine + mean-off-diag +
  masked softmax in one pass (the whole self-consistency scorer); the
  serving hot path's scorer (models/embedder.py, clients/multichat.py).
* ``w8a8_matmul``        — fused W8A8 quantized dense: per-row dynamic
  activation int8 quant + int8 x int8 -> int32 MXU matmul + dequant/bias
  (+ optional GELU) epilogue in one kernel, so neither the quantized
  activations nor the int32 accumulator nor a dequantized bf16 weight
  copy ever materializes in HBM (models/quant.py's fast path).

(A fused tally kernel existed but was removed: the live tally is host
Decimal by product contract and batched re-scoring uses
``consensus.tally_batch`` — a device twin with no caller is dead weight.)

On non-TPU backends the kernels run in interpret mode (same code path, same
results) so the CPU test mesh exercises them; beyond the single-block VMEM
budget the jnp compositions in ``consensus``/``similarity`` are the
fallback.  Guide: /opt/skills/guides/pallas_guide.md.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# The vote kernel is one ungridded block under Mosaic's 16 MiB scoped-VMEM
# default.  At 1024 x 2048 it asks for 41.4 MiB and fails to compile on a
# v5e; these caps — the service's own: MAX_CONSENSUS_CANDIDATES rows of
# the widest preset's hidden size — compile and match the reference there
# (chip runs, PR 21).  Anything larger takes the jnp composition.
MAX_FUSED_CHOICES = 256
MAX_FUSED_DIM = 1024


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _pad_to(x: jax.Array, axis: int, multiple: int) -> jax.Array:
    size = x.shape[axis]
    target = -(-size // multiple) * multiple
    if target == size:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, target - size)
    return jnp.pad(x, pad)


# ---------------------------------------------------------------------------
# Fused cosine self-consistency vote
# ---------------------------------------------------------------------------


def _cosine_vote_kernel(x_ref, out_ref, *, n_valid: int, temperature: float):
    x = x_ref[:].astype(jnp.float32)  # [Np, Dp], padding rows are zero
    norm = jax.lax.rsqrt(
        jnp.sum(x * x, axis=-1, keepdims=True) + 1e-12
    )
    nx = x * norm
    sims = jnp.dot(nx, nx.T, preferred_element_type=jnp.float32,
                   precision=jax.lax.Precision.HIGHEST)  # [Np, Np]
    np_ = sims.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, (np_, np_), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (np_, np_), 1)
    valid = (col < n_valid) & (row != col)
    mean_sim = jnp.sum(jnp.where(valid, sims, 0.0), axis=-1) / max(
        n_valid - 1, 1
    )  # [Np]
    logits = mean_sim / temperature
    row_valid = jax.lax.iota(jnp.int32, np_) < n_valid
    logits = jnp.where(row_valid, logits, -jnp.inf)
    # masked softmax over the valid candidates
    mx = jnp.max(logits)
    e = jnp.where(row_valid, jnp.exp(logits - mx), 0.0)
    out_ref[:] = (e / jnp.sum(e))[None, :]


@functools.partial(jax.jit, static_argnames=("temperature",))
def fused_cosine_vote(
    embeddings: jax.Array, temperature: float = 0.05
) -> jax.Array:
    """embeddings[N, D] -> confidence[N]: the whole self-consistency scorer
    (normalize + cosine + mean + softmax) fused into one kernel."""
    n, d = embeddings.shape
    if n > MAX_FUSED_CHOICES or d > MAX_FUSED_DIM:
        from .similarity import cosine_consensus_vote

        return cosine_consensus_vote(embeddings, temperature=temperature)
    x = _pad_to(_pad_to(embeddings.astype(jnp.float32), 0, 8), 1, 128)
    np_ = x.shape[0]
    out = pl.pallas_call(
        functools.partial(
            _cosine_vote_kernel, n_valid=n, temperature=temperature
        ),
        out_shape=jax.ShapeDtypeStruct((1, np_), jnp.float32),
        interpret=_interpret(),
    )(x)
    return out[0, :n]


# ---------------------------------------------------------------------------
# Fused W8A8 quantized matmul (the int8 serving path's dense op)
# ---------------------------------------------------------------------------

# Grid over M row tiles with the whole [K, N] int8 weight block resident
# in VMEM: the weight BlockSpec's index map is constant, so the Mosaic
# pipeline DMAs it once and revisits it across grid steps — activations
# stream through while the weights stay put (the opposite split would
# re-fetch the big operand per tile).
W8A8_TILE_M = 128
# weight block + double-buffered x/out tiles must fit comfortably under
# the ~16 MB/core VMEM; beyond this the caller's dot_general fallback
# (models/quant.py) takes over
_W8A8_VMEM_BUDGET = 12 * 1024 * 1024


def _round_up(n: int, multiple: int) -> int:
    return -(-n // multiple) * multiple


def w8a8_shape_fits(
    m: int, k: int, n: int, x_bytes: int, w_bytes: float = 1.0
) -> bool:
    """Whether the single-weight-block tiling fits the VMEM budget.

    Every preset's encoder matmul fits (bge-large mlp_in, the largest:
    1 MB x-tile + 4 MB int8 weights + 4 MB f32 out-tile, double-buffered
    tiles well under 12 MB); the gate exists for hypothetical huge
    projections, which fall back to the XLA int8 dot_general.

    ``w_bytes`` is the resident weight block's bytes per element — 1 for
    the int8 kernel, 0.5 for the packed-int4 kernel (two weights per
    uint8 byte) — so both quantized paths share this one gate instead of
    diverging copies."""
    kp = _round_up(k, 128)
    np_ = _round_up(n, 128)
    tm = min(W8A8_TILE_M, _round_up(m, 8))
    weight = int(kp * np_ * w_bytes)  # int8: 1 byte; packed int4: 1/2
    tiles = 2 * tm * kp * x_bytes + 2 * tm * np_ * x_bytes  # double-buffered
    scale_bias = 2 * np_ * 4
    return weight + tiles + scale_bias <= _W8A8_VMEM_BUDGET


def _w8a8_kernel(x_ref, wq_ref, sw_ref, b_ref, o_ref, *, gelu, approx_gelu):
    # lazy: ops must not import models at module load (models/__init__
    # imports embedder, which imports this module)
    from ..models.layers import gelu_f32

    x = x_ref[:].astype(jnp.float32)  # [TM, Kp]; pad rows/cols are zero
    # per-row dynamic activation quant, fused: the int8 activations never
    # leave VMEM (the whole point — a separate XLA quant pass would write
    # xq + scale to HBM and read them back)
    sx = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 127.0  # [TM, 1]
    sx = jnp.maximum(sx, 1e-12)
    xq = jnp.clip(jnp.round(x / sx), -127.0, 127.0).astype(jnp.int8)
    acc = jax.lax.dot_general(
        xq,
        wq_ref[:],
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )  # [TM, Np] int32 on the MXU, exact
    # epilogue: rank-1 dequant (sx x sw) + bias (+ GELU), all in f32 in
    # registers, single cast on the way out
    out = acc.astype(jnp.float32) * sx * sw_ref[:] + b_ref[:]
    if gelu:
        out = gelu_f32(out, approx=approx_gelu)
    o_ref[:] = out.astype(o_ref.dtype)


def w8a8_matmul(
    x: jax.Array,
    wq: jax.Array,
    sw: jax.Array,
    bias: jax.Array,
    *,
    gelu: bool = False,
    interpret=None,
) -> jax.Array:
    """Fused W8A8 dense: ``x[..., K] @ wq[K, N] -> [..., N]`` in x.dtype.

    ``wq`` is the per-output-channel int8 kernel and ``sw`` its f32 scale
    (models/quant.py:quantize_weight); activations are quantized per row
    INSIDE the kernel.  ``gelu=True`` folds the exact-profile GELU into
    the epilogue (erf for f32 activations, the A&S 7.1.26 form for bf16 —
    the same dtype split as layers.gelu_erf, so the fused MLP matches the
    unfused composition's numerics).  Non-TPU backends run in interpret
    mode; ``interpret`` overrides for tests."""
    k = x.shape[-1]
    n = wq.shape[-1]
    x2 = x.reshape(-1, k)
    m = x2.shape[0]
    tm = min(W8A8_TILE_M, _round_up(m, 8))
    xp = _pad_to(_pad_to(x2, 0, tm), 1, 128)
    wqp = _pad_to(_pad_to(wq, 0, 128), 1, 128)
    swp = _pad_to(sw.astype(jnp.float32).reshape(1, n), 1, 128)
    bp = _pad_to(bias.astype(jnp.float32).reshape(1, n), 1, 128)
    mp, kp = xp.shape
    np_ = wqp.shape[1]
    out = pl.pallas_call(
        functools.partial(
            _w8a8_kernel,
            gelu=gelu,
            approx_gelu=x.dtype == jnp.bfloat16,
        ),
        grid=(mp // tm,),
        in_specs=[
            pl.BlockSpec((tm, kp), lambda i: (i, 0)),
            pl.BlockSpec((kp, np_), lambda i: (0, 0)),
            pl.BlockSpec((1, np_), lambda i: (0, 0)),
            pl.BlockSpec((1, np_), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((tm, np_), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), x.dtype),
        interpret=_interpret() if interpret is None else interpret,
    )(xp, wqp, swp, bp)
    return out[:m, :n].reshape(*x.shape[:-1], n)


# ---------------------------------------------------------------------------
# Fused W4A8 quantized matmul (packed int4 weights, the long-context path)
# ---------------------------------------------------------------------------

# Packed layout contract (shared with models/quant.py): an int4 kernel
# [K, N] is stored as ONE uint8 array [Kp/2, N] where Kp = K rounded up
# to a multiple of 2*128 (so each nibble half stays lane-aligned).  The
# LOW nibble of row kk holds weight row kk; the HIGH nibble holds weight
# row kk + Kp/2 (split-K halves, not interleaved pairs — the kernel then
# needs no gather/concat, just two lane-aligned dot_generals).  Nibbles
# are stored biased by +8: quantized values live in [-7, 7], stored as
# [1, 15], and the zero-pad nibble is 8 (unbiases to exactly 0, so K
# padding contributes nothing to the accumulator).
W4A8_PACK_K = 256


def pack_int4_weights(wq: jax.Array) -> jax.Array:
    """Pack a per-channel int4-quantized kernel ``wq[..., K, N]`` (int8
    values in [-7, 7]) into the biased-nibble uint8 layout above along
    the K axis (leading dims — e.g. a stacked per-layer kernel — ride
    through untouched)."""
    k = wq.shape[-2]
    kp = _round_up(k, W4A8_PACK_K)
    half = kp // 2
    biased = (wq.astype(jnp.int32) + 8).astype(jnp.uint8)
    pad = [(0, 0)] * wq.ndim
    pad[-2] = (0, kp - k)
    biased = jnp.pad(biased, pad, constant_values=8)
    lo = biased[..., :half, :]
    hi = biased[..., half:, :]
    return (lo | (hi << 4)).astype(jnp.uint8)


def _w4a8_kernel(x_ref, wq_ref, sw_ref, b_ref, o_ref, *, gelu, approx_gelu):
    # lazy: ops must not import models at module load (models/__init__
    # imports embedder, which imports this module)
    from ..models.layers import gelu_f32

    x = x_ref[:].astype(jnp.float32)  # [TM, Kp]; pad rows/cols are zero
    # the SAME per-row dynamic activation quant as the W8A8 kernel — the
    # two paths differ only in how the weight block decodes
    sx = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 127.0  # [TM, 1]
    sx = jnp.maximum(sx, 1e-12)
    xq = jnp.clip(jnp.round(x / sx), -127.0, 127.0).astype(jnp.int8)
    # unpack via int32 (Mosaic's comfortable width for bit ops), then
    # narrow to int8 for the MXU
    packed = wq_ref[:].astype(jnp.int32)  # [Kp/2, Np], biased nibbles
    lo = ((packed & 0xF) - 8).astype(jnp.int8)  # weight rows [0, Kp/2)
    hi = ((packed >> 4) - 8).astype(jnp.int8)  # weight rows [Kp/2, Kp)
    half = packed.shape[0]
    acc = jax.lax.dot_general(
        xq[:, :half],
        lo,
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    ) + jax.lax.dot_general(
        xq[:, half:],
        hi,
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )  # [TM, Np] int32 on the MXU, exact
    # identical epilogue to _w8a8_kernel: rank-1 dequant + bias (+ GELU)
    out = acc.astype(jnp.float32) * sx * sw_ref[:] + b_ref[:]
    if gelu:
        out = gelu_f32(out, approx=approx_gelu)
    o_ref[:] = out.astype(o_ref.dtype)


def w4a8_matmul(
    x: jax.Array,
    wq4: jax.Array,
    sw: jax.Array,
    bias: jax.Array,
    *,
    gelu: bool = False,
    interpret=None,
) -> jax.Array:
    """Fused W4A8 dense: ``x[..., K] @ unpack(wq4)[K, N] -> [..., N]``.

    ``wq4`` is the packed biased-nibble uint8 kernel from
    ``pack_int4_weights`` and ``sw`` its per-output-channel f32 scale
    (max|W|/7); activations are quantized to int8 per row INSIDE the
    kernel.  Reuses the W8A8 grid and epilogue; the weight block is half
    the VMEM of int8, which is what buys long-context activations room
    next to the weights.  Non-TPU backends run in interpret mode."""
    k = x.shape[-1]
    n = wq4.shape[-1]
    kp = 2 * wq4.shape[0]  # pack-time K padding (multiple of 256)
    x2 = x.reshape(-1, k)
    m = x2.shape[0]
    tm = min(W8A8_TILE_M, _round_up(m, 8))
    xp = jnp.pad(_pad_to(x2, 0, tm), ((0, 0), (0, kp - k)))
    wq4p = _pad_to(wq4, 1, 128)
    swp = _pad_to(sw.astype(jnp.float32).reshape(1, n), 1, 128)
    bp = _pad_to(bias.astype(jnp.float32).reshape(1, n), 1, 128)
    mp = xp.shape[0]
    np_ = wq4p.shape[1]
    out = pl.pallas_call(
        functools.partial(
            _w4a8_kernel,
            gelu=gelu,
            approx_gelu=x.dtype == jnp.bfloat16,
        ),
        grid=(mp // tm,),
        in_specs=[
            pl.BlockSpec((tm, kp), lambda i: (i, 0)),
            pl.BlockSpec((kp // 2, np_), lambda i: (0, 0)),
            pl.BlockSpec((1, np_), lambda i: (0, 0)),
            pl.BlockSpec((1, np_), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((tm, np_), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), x.dtype),
        interpret=_interpret() if interpret is None else interpret,
    )(xp, wq4p, swp, bp)
    return out[:m, :n].reshape(*x.shape[:-1], n)
