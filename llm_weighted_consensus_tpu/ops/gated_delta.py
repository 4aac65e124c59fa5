"""The gated delta rule in chunks, for a linear-attention layer's prefill (Pallas).

A head keeps a state ``S`` [dk, dv] and per token t, with a key k_t and a
query q_t (both taken to unit length here, eps 1e-6, as the published kernels
do, and the query then scaled by dk^-0.5), a value v_t, a write strength
beta_t in (0, 1) and a log-decay g_t <= 0:

    S   <- S * exp(g_t)
    d_t  = (v_t - S^T k_t) * beta_t          what the state does not yet hold
    S   <- S + k_t d_t^T
    o_t  = S^T q_t                           (/ rms(o_t) where ``norm_eps`` is given:
                                              the layer's gated norm's first half)

``gated_delta_rule`` serves a prefill; ``gated_delta_recurrent`` is that loop, a ``lax.scan`` over the positions in
float32: the twin the tests compare with, and one step of it
(``gated_delta_step``) is a judge's decoded token.  A prefill of 8192
positions cannot serve as a chain of 8192 rank-one updates, so the kernel
works a chunk of C positions at a time.  With G_i the running sum of g inside
the chunk and D_ij = exp(G_i - G_j) for i >= j:

    A    = strictly lower (beta_i (k_i . k_j) D_ij)
    T    = (I + A)^-1
    W    = T (beta exp(G) k),   U = T (beta v)
    V'   = U - W S                            the chunk's d_t, all at once
    O    = (q exp(G)) S + lower (q k^T D) V'
    S   <- S exp(G_C) + (k exp(G_C - G))^T V'

``T`` is found by merging blocks: the inverse of a unit lower triangular
[[L11, 0], [L21, L22]] is [[X11, 0], [-X22 L21 X11, X22]], so from blocks of
two (whose inverse is I - A) every doubling is ``T <- T - T A_off T`` with
``A_off`` the blocks below the doubled diagonal: 2 log2(C) - 2 products of
[C, C], each exact block substitution (a product of (I + A^(2^i)), the other
logarithmic form, passes through powers of A whose entries grow as binomials
before they cancel: with a long memory, D near 1, float32 loses digits there).

Layout: the projections' own.  q and k are [b, s, key heads * dk], v and the
output [b, s, value heads * dv]; a value head reads key head ``h // (value
heads / key heads)`` through the block's index, nothing is repeated in
memory.  g and beta are [b, s, value heads] float32.  Positions past a
call's length come with beta 0 and g 0 and leave the state as it was.

Grid (b, value heads / heads a step, chunks), the chunks innermost and in
order: the state is the resident output block [heads a step, dk, dv] float32.
The running sum of g, its exponentials, the two normalisations and the state
are float32 whatever the inputs; the products' operands stay in the storage
dtype (bf16 feeds the MXU natively) and accumulate in float32 (a float32
operand at Mosaic's default precision is one bf16 pass too: the same numbers
and the same milliseconds, my chip runs, PR 31).  The normalisations are in
the kernel because a head's 128 lanes are a row's reduction there and a
relayout to [.., heads, 128] in XLA (22 + 14 ms a program, my chip runs,
PR 31).  Several heads a step are unrolled
in one body: their chains are independent, so one head's products run under
another's exponentials.  The jitted function's name is the kernel's name in
a device trace.  What the chip says of the forms tried: PERF.md, PR 31.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CHUNK = 128  # positions of a chunk: one pass of the 128 x 128 MXU a product
HEADS_PER_STEP = 4  # value heads unrolled in one grid step
_VMEM_LIMIT = 48 << 20
_L2_EPS = 1e-6


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _dot(a, b, dims, dtype):
    return jax.lax.dot_general(
        a.astype(dtype), b.astype(dtype), (dims, ((), ())),
        preferred_element_type=jnp.float32,
    )


_NN = ((1,), (0,))  # a @ b
_NT = ((1,), (1,))  # a @ b.T
_TN = ((0,), (0,))  # a.T @ b


def _unit(x, scale: float = 1.0):
    """Rows of x [.., d] float32 at length ``scale``."""
    return x * (jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + _L2_EPS) * scale)


def _kernel(
    q_ref, k_ref, v_ref, g_ref, beta_ref, o_ref, s_ref,
    *, heads, per_key, dk, dv, chunk, norm_eps,
):
    mxu = q_ref.dtype
    row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    eye, lower, strict = row == col, row >= col, row > col
    # the blocks each doubling brings in: below the diagonal of the doubled
    # block, outside the blocks already inverted
    merges, size = [], 2
    while size < chunk:
        merges.append((row // (2 * size) == col // (2 * size)) & (row // size != col // size))
        size *= 2
    pairs = (row // 2 == col // 2) & strict

    @pl.when(pl.program_id(2) == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    def column(x_row):
        """[1, C] along the lanes -> [C, 1] down the sublanes."""
        across = jnp.broadcast_to(x_row, (chunk, chunk))
        return jnp.sum(jnp.where(eye, across, 0.0), axis=1, keepdims=True)

    for j in range(heads):
        kh = j // per_key
        if j % per_key == 0:  # a key head serves ``per_key`` value heads
            q = _unit(q_ref[:, kh * dk:(kh + 1) * dk].astype(jnp.float32), dk ** -0.5)
            k32 = _unit(k_ref[:, kh * dk:(kh + 1) * dk].astype(jnp.float32))
            k = k32.astype(mxu)
        v = v_ref[:, j * dv:(j + 1) * dv]
        g_row, beta_row = g_ref[j:j + 1, :], beta_ref[j:j + 1, :]
        g_col, beta_col = column(g_row), column(beta_row)
        g_end = g_row[:, chunk - 1:chunk]  # [1, 1]: the chunk's whole decay
        decay = jnp.exp(jnp.where(lower, g_col - g_row, -jnp.inf))  # D_ij, 0 above
        a = jnp.where(strict, _dot(k, k, _NT, mxu) * decay * beta_col, 0.0)
        t = jnp.where(eye, 1.0, 0.0) - jnp.where(pairs, a, 0.0)
        for mask in merges:
            t = t - _dot(_dot(t, jnp.where(mask, a, 0.0), _NN, mxu), t, _NN, mxu)
        w = _dot(t, k32 * (beta_col * jnp.exp(g_col)), _NN, mxu)
        u = _dot(t, v.astype(jnp.float32) * beta_col, _NN, mxu)
        state = s_ref[j]
        fresh = u - _dot(w, state, _NN, mxu)  # the chunk's (v - S^T k) beta
        within = jnp.where(lower, _dot(q, k, _NT, mxu) * decay, 0.0)
        out = _dot(q * jnp.exp(g_col), state, _NN, mxu)
        out = out + _dot(within, fresh, _NN, mxu)
        if norm_eps is not None:
            out = out * jax.lax.rsqrt(jnp.mean(out * out, axis=1, keepdims=True) + norm_eps)
        o_ref[:, j * dv:(j + 1) * dv] = out.astype(o_ref.dtype)
        carried = k32 * jnp.exp(g_end - g_col)
        # [1, 1] goes along the lanes first: Mosaic broadcasts one way at a time
        kept = jnp.exp(jnp.broadcast_to(g_end, (1, dv)))
        s_ref[j] = state * kept + _dot(carried, fresh, _TN, mxu)


def _heads_a_step(hv: int, per_key: int, heads_per_step: int) -> int:
    """Value heads of a grid step: whole key heads, a divisor of ``hv``."""
    heads = max(heads_per_step // per_key, 1) * per_key
    while hv % heads:
        heads -= per_key
    return heads


@functools.partial(jax.jit, static_argnames=("key_heads", "norm_eps", "interpret"))
def gated_delta_chunked(
    q, k, v, g_rows, beta_rows, *, key_heads: int, norm_eps: float | None = None,
    interpret: bool,
):
    """The kernel alone, under the name a device trace calls it by: whole
    chunks, and g (summed within its chunk) and beta as ``gated_delta_rule``
    lays them out, [b, chunks, steps, heads a step, C] float32."""
    b, s, _ = q.shape
    _, n, steps, heads, chunk = g_rows.shape
    hv = steps * heads
    dk, dv = q.shape[-1] // key_heads, v.shape[-1] // hv
    per_key = hv // key_heads
    by_chunk = lambda width: pl.BlockSpec(  # noqa: E731
        (None, chunk, width), lambda bi, hg, ci: (bi, ci, hg)
    )
    by_head = pl.BlockSpec(
        (None, None, None, heads, chunk), lambda bi, hg, ci: (bi, ci, hg, 0, 0)
    )
    return pl.pallas_call(
        functools.partial(
            _kernel, heads=heads, per_key=per_key, dk=dk, dv=dv, chunk=chunk,
            norm_eps=norm_eps,
        ),
        grid=(b, steps, n),
        in_specs=[
            by_chunk(heads // per_key * dk), by_chunk(heads // per_key * dk),
            by_chunk(heads * dv), by_head, by_head,
        ],
        out_specs=[
            by_chunk(heads * dv),
            pl.BlockSpec((None, heads, dk, dv), lambda bi, hg, ci: (bi, hg, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(v.shape, v.dtype),
            jax.ShapeDtypeStruct((b, hv, dk, dv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=interpret,
    )(q, k, v, g_rows, beta_rows)


def gated_delta_rule(
    q, k, v, g, beta, *, key_heads: int, norm_eps: float | None = None,
    chunk: int = CHUNK, heads_per_step: int = HEADS_PER_STEP,
    interpret: bool | None = None,
):
    """q, k [b, s, key heads * dk] (of any length: the rule takes a head's to
    unit length and scales the query by dk^-0.5), v [b, s, value heads * dv],
    g and beta [b, s, value heads] float32 -> (o [b, s, value heads * dv] in
    v's dtype, divided a head by its root mean square where ``norm_eps`` is
    given; the state after the last position [b, value heads, dk, dv]
    float32).  A length that is no whole number of chunks is padded with
    positions that change nothing."""
    b, s, _ = q.shape
    hv = g.shape[-1]
    dk, dv = q.shape[-1] // key_heads, v.shape[-1] // hv
    per_key = hv // key_heads
    if interpret is None:
        interpret = _interpret()
    if per_key * key_heads != hv or (not interpret and (dk % 128 or dv % 128)):
        raise ValueError(f"{hv} value heads of {dv} on {key_heads} key heads of {dk}")
    heads = _heads_a_step(hv, per_key, heads_per_step)
    n = -(-s // chunk)
    if n * chunk != s:
        grow = ((0, 0), (0, n * chunk - s), (0, 0))
        q, k, v, g, beta = (jnp.pad(x, grow) for x in (q, k, v, g, beta))

    def rows(x):  # [b, chunks, C, hv] -> [b, chunks, steps, heads a step, C], a head a row
        return jnp.transpose(x.reshape(b, n, chunk, hv // heads, heads), (0, 1, 3, 4, 2))

    g = g.astype(jnp.float32).reshape(b, n, chunk, hv)
    beta = beta.astype(jnp.float32).reshape(b, n, chunk, hv)
    out, state = gated_delta_chunked(
        q, k, v, rows(jnp.cumsum(g, axis=2)), rows(beta), key_heads=key_heads,
        norm_eps=norm_eps, interpret=interpret,
    )
    return out[:, :s], state


def gated_delta_step(state, q, k, v, g, beta):
    """One position of the rule: state [..., dk, dv] float32, q and k
    [..., dk] of any length, v [..., dv], g and beta [...] -> (o [..., dv],
    the new state), float32."""
    v = v.astype(jnp.float32)
    q = _unit(q.astype(jnp.float32), q.shape[-1] ** -0.5)
    k = _unit(k.astype(jnp.float32))
    state = state * jnp.exp(g.astype(jnp.float32))[..., None, None]
    held = jnp.einsum("...kv,...k->...v", state, k)
    delta = (v - held) * beta.astype(jnp.float32)[..., None]
    state = state + k[..., :, None] * delta[..., None, :]
    return jnp.einsum("...kv,...k->...v", state, q), state


def gated_delta_recurrent(q, k, v, g, beta, *, key_heads: int, norm_eps: float | None = None):
    """The kernel's plain twin, a position at a time (tests, tiny sizes):
    the same arguments and results as ``gated_delta_rule``."""
    b, s, _ = q.shape
    hv = g.shape[-1]
    dk, dv = q.shape[-1] // key_heads, v.shape[-1] // hv

    def heads(x, d):  # [b, s, heads * d] -> [s, b, value heads, d]
        x = x.reshape(b, s, -1, d)
        return jnp.moveaxis(jnp.repeat(x, hv // x.shape[2], axis=2), 1, 0)

    def step(state, xs):
        out, state = gated_delta_step(state, *xs)
        return state, out

    xs = (heads(q, dk), heads(k, dk), heads(v, dv), jnp.moveaxis(g, 1, 0), jnp.moveaxis(beta, 1, 0))
    state, out = jax.lax.scan(step, jnp.zeros((b, hv, dk, dv), jnp.float32), xs)
    if norm_eps is not None:
        out = out * jax.lax.rsqrt(jnp.mean(out * out, axis=-1, keepdims=True) + norm_eps)
    return jnp.moveaxis(out, 0, 1).reshape(b, s, hv * dv).astype(v.dtype), state
