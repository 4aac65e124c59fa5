"""Causal blockwise attention for a decoder's prefill (Pallas).

``ops/attention.py``'s kernel is the encoder's: bidirectional, one whole
[s, s] score tile in VMEM, heads of 32 or 64.  A judge's prefill is 8k
tokens under a causal mask with heads of 256: 20 x 8192 x 8192 float32
scores are 5.4 GB a call, so neither that kernel nor a plain ``einsum`` can
serve it.  This kernel walks the keys in blocks and keeps a running maximum,
a running sum and an unnormalised accumulator per query block (the online
softmax), so that nothing of size s x s ever exists.

Layout: the projections' own.  q, k and v are [b, s, heads * hd] and the
context is the [b, s, heads * hd] array the output projection reads; a head
is one hd-wide column block (hd a multiple of 128 lanes, or the whole of
the last dimension), so there is no head transpose around the kernel.

Grid (b, heads, s / block_q, s / block_k), the key blocks innermost and
sequential.  Key blocks wholly above the diagonal do no work, and their
index map names the diagonal block again, so nothing is fetched for them
either.  Blocks wholly below the diagonal skip the mask; only blocks the
diagonal crosses pay for the comparison.

What the chip says (TPU v5e, bf16, 3 x 8192 tokens, 20 heads of 256: one
layer's attention of a judge panel; my chip run, PR 27): blocks of 1024 x 1024
16.4 ms (126 TFLOP/s of the causal half's operations, 64% of the bf16 peak),
1024 x 512 17.4, 512 x 1024 18.1, 512 x 512 19.3, 256 x 512 26.1, 512 x 256
31.3 ms.  So the largest block that divides the sequence, up to 1024.

Matmul inputs stay in the storage dtype (bf16 feeds the MXU natively),
scores, softmax and the accumulator are float32.  On a backend without a
TPU the kernel runs in interpret mode, the same code path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -1e30  # a masked score: finite, so that exp(_NEG - m) is an exact 0
_BLOCKS = (1024, 512, 256, 128, 64, 32, 16, 8)


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def block_for(seq: int, cap: int = 1024) -> int:
    """The largest block of ``_BLOCKS`` under ``cap`` that divides ``seq``."""
    for block in _BLOCKS:
        if block <= cap and seq % block == 0:
            return block
    raise ValueError(f"sequence length {seq} is not a multiple of 8")


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *, scale, bq, bk):
    qi, ki = pl.program_id(2), pl.program_id(3)
    last = (qi * bq + bq - 1) // bk  # the key block that holds the diagonal's end

    @pl.when(ki == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def step(masked: bool):
        scores = jax.lax.dot_general(
            q_ref[...], k_ref[...], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [bq, bk]
        if masked:
            row = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            col = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            scores = jnp.where(col <= row, scores, _NEG)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(scores - m_new)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[...], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = m_new

    # a key block lies wholly below the diagonal when its last column is at
    # or before the query block's first row
    below = ki * bk + bk - 1 <= qi * bq

    @pl.when(below)
    def _():
        step(masked=False)

    @pl.when(jnp.logical_and(jnp.logical_not(below), ki <= last))
    def _():
        step(masked=True)

    @pl.when(ki == last)
    def _():
        o_ref[...] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("heads", "scale", "block_q", "block_k", "interpret")
)
def causal_attention_blockwise(
    q, k, v, *, heads: int, scale: float, block_q: int = 0, block_k: int = 0,
    interpret: bool | None = None,
):
    """q, k, v: [b, s, heads * hd] -> context [b, s, heads * hd]; position i
    attends positions <= i.  The jitted function's name is the kernel's name
    in a device trace."""
    b, s, width = q.shape
    hd = width // heads
    if interpret is None:
        interpret = _interpret()
    if hd * heads != width or (not interpret and heads > 1 and hd % 128):
        raise ValueError(f"heads of {hd} lanes cannot be carved from {width}")
    bq = block_q or block_for(s)
    bk = block_k or block_for(s)

    def kv_index(bi, h, qi, ki):
        return bi, jnp.minimum(ki, (qi * bq + bq - 1) // bk), h

    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, bq=bq, bk=bk),
        grid=(b, heads, s // bq, s // bk),
        in_specs=[
            pl.BlockSpec((None, bq, hd), lambda bi, h, qi, ki: (bi, qi, h)),
            pl.BlockSpec((None, bk, hd), kv_index),
            pl.BlockSpec((None, bk, hd), kv_index),
        ],
        out_specs=pl.BlockSpec((None, bq, hd), lambda bi, h, qi, ki: (bi, qi, h)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)


def causal_attention_einsum(q, k, v, *, heads: int, scale: float):
    """The kernel's plain twin: whole [s, s] scores (tests, tiny sizes)."""
    b, s, width = q.shape
    hd = width // heads
    qh, kh, vh = (x.reshape(b, s, heads, hd) for x in (q, k, v))
    scores = jnp.einsum(
        "bqhd,bkhd->bhqk", qh, kh, preferred_element_type=jnp.float32
    ) * scale
    keep = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(keep, scores, _NEG), axis=-1)
    out = jnp.einsum(
        "bhqk,bkhd->bqhd", probs.astype(v.dtype), vh,
        preferred_element_type=jnp.float32,
    )
    return out.reshape(b, s, width).astype(q.dtype)
