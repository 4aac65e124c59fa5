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

    A'   = strictly lower ((k_i . k_j) D_ij)
    T    = (I + A' diag(beta))^-1
    V'   = diag(beta) T (v - exp(G) (k S))    the chunk's d_t, all at once
    O    = exp(G) (q S) + lower (q k^T D) V'
    S   <- S exp(G_C) + k^T (exp(G_C - G) V')

(the chunked WY form: (I + diag(beta) A')^-1 diag(beta) = diag(beta) T, so
beta scales the COLUMNS of A', a row's broadcast, and T (beta e^G k) S is
T applied to e^G (k S); every scaling by a position is a row's broadcast or
one multiplication of a product's float32 result, none of an operand.)

``T`` is found by merging blocks: the inverse of a unit lower triangular
[[L11, 0], [L21, L22]] is [[X11, 0], [-X22 L21 X11, X22]], so from blocks of
two (whose inverse is I - A) every doubling from blocks of ``s`` is
``T <- T - mask(T A) T``, the mask keeping the blocks below the doubled
diagonal.  Only the rows of each pair's second block change; from s = 8 on
those are whole sublane groups, so the C / 2 rows alone go through both
products (the levels of 2 and 4 stay dense): 2 log2(C) - 2 products, four
of C rows and eight of C / 2 at C = 128, each exact block substitution (a
product of (I + A^(2^i)), the other logarithmic form, passes through powers
of A whose entries grow as binomials before they cancel: with a long memory,
D near 1, float32 loses digits there).

Products a chunk and value head at C = 128, in [128, 128] weight tiles by
rows streamed: [k; q] k^T 256 rows once a KEY head; the solve 4 x 128 +
8 x 64; [k; q] S 256; T (v - ..) 128; lower(q k^T D beta) V' 128; k^T (..)
128: 1,792 rows where the first form (PR 31) streamed 2,560.

Layout: the projections' own.  q and k are [b, s, key heads * dk], v and the
output [b, s, value heads * dv]; a value head reads key head ``h // (value
heads / key heads)`` through the block's index, nothing is repeated in
memory.  g and beta are [b, s, value heads] float32 and reach the kernel a
head a row, [heads a step, C] a chunk.  The kernel sums g along the chunk
itself (one product of the tile with a triangle of ones at HIGHEST
precision: 1.06 ms a layer as ``jnp.cumsum`` beside the kernel, my chip
runs, PR 36) and turns the two tiles once a step for the heads' columns.
Positions past a call's length come with beta 0 and g 0 and leave the state
as it was.

Grid (b, value heads / heads a step, chunks), the chunks innermost and in
order: the state is the resident output block [heads a step, dk, dv] float32.
The running sum of g, its exponentials, the two normalisations and the state
are float32 whatever the inputs; the products' operands stay in the storage
dtype (bf16 feeds the MXU natively) and accumulate in float32 (a float32
operand at Mosaic's default precision is one bf16 pass too: the same numbers
and the same milliseconds, my chip runs, PR 31).  The normalisations are in
the kernel because a head's 128 lanes are a row's reduction there and a
relayout to [.., heads, 128] in XLA (22 + 14 ms a program, my chip runs,
PR 31).

The heads of a step are worked STAGE BY STAGE, every stage written over all
of them before the next.  As the compiler's schedule reads, the four MXUs
take the products in turn in program order and each works its own in that
order, so a head's chain of dependent products runs alone unless another
head's stands beside it in the program: PR 31's body, a head after a head,
kept ONE of the four MXUs busy (the scheduler's own report, dumped for a
described v5e: 10,083 cycles a step of four heads, 10.32 ms a layer at
1.5 GHz; the trace read 10.3).  The body is written for EIGHT heads a step,
two chains an MXU: 5,381 cycles a step, the MXUs 94% busy through the solve,
2.84 ms a layer on the chip.  ``HEADS_PER_STEP`` is 2, a key head's pair,
7.64 ms: with eight the second judge's program takes 239 ms for 288, and
its benchmark cell's pool of 200 requests a window is spent before the
window ends, which fails the run (PERF.md section 5 and question 30).  The
constant is the one line to change once the pool is larger.  The jitted
function's name is the kernel's name in a device trace.

What the chip said of the forms (my chip runs, PR 36, a layer's rule at
[3, 8192], 32 value heads on 16 key heads of 128, bf16, host clock; PERF.md
section 5 has the table and the scheduler's cycles beside each): PR 31's
kernel 10.66 ms and 1.06 more for the running sum in XLA; its solve
replaced by I - A 3.35, its masks dropped 10.44, its columns read from
memory 10.75, its normalisations dropped 10.30, two / eight heads a step
12.15 / 9.81; the same arithmetic stage by stage over two heads 6.45, over
four 4.46, over eight 3.51; with the products that share an operand made one
and half the rows in the late levels 3.14; the scalings moved off the
operands (this form) 2.85 with columns from memory, and 2.84 WITH the
running sum inside and the columns turned in the kernel; this form at four
heads a step 4.29, at two 7.64.
Refused: ``[within; carried^T] fresh`` as one product (4.47 against 4.46:
the explicit turn costs what the shared weights save); the columns as
[C, heads] arrays from outside (a minor dimension of 8 is padded to 128
lanes in HBM, sixteen times the bytes, for 0.1 ms); the running sum as
seven shifted additions (exact, but 5,957 cycles for 5,381: its latency
heads the critical path); two groups of eight heads a phase apart in one
step (2.76 ms for twice the body); masks as a constant input (5,620 cycles
against 5,650: building them hides under the first loads).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CHUNK = 128  # positions of a chunk: one pass of the 128 x 128 MXU a product
HEADS_PER_STEP = 2  # value heads of a grid step, worked stage by stage (why not 8: the docstring)
_VMEM_LIMIT = 48 << 20
_L2_EPS = 1e-6

_NN = ((1,), (0,))  # a @ b
_NT = ((1,), (1,))  # a @ b.T
_TN = ((0,), (0,))  # a.T @ b


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _dot(a, b, dims=_NN, precision=None):
    return jax.lax.dot_general(
        a, b, (dims, ((), ())), precision=precision, preferred_element_type=jnp.float32
    )


def _unit(x, scale: float = 1.0):
    """Rows of x [.., d] float32 at length ``scale``."""
    return x * (jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + _L2_EPS) * scale)


def _second(x, size: int):
    """The rows of every pair's second block of ``size``: [C, ..] -> [C / 2, ..]."""
    return jnp.concatenate([x[i:i + size] for i in range(size, x.shape[0], 2 * size)], axis=0)


def _weave(x, new, size: int):
    """``x`` with the rows of every pair's second block of ``size`` replaced by ``new`` [C / 2, ..]."""
    parts = []
    for n, i in enumerate(range(0, x.shape[0], 2 * size)):
        parts += [x[i:i + size], new[n * size:(n + 1) * size]]
    return jnp.concatenate(parts, axis=0)


def _kernel(
    q_ref, k_ref, v_ref, g_ref, beta_ref, o_ref, s_ref,
    *, heads, per_key, dk, dv, chunk, norm_eps,
):
    mxu = q_ref.dtype
    row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    apart = row ^ col  # under 2 s: the same block of 2 s; from s on: other blocks of s
    lower, strict = row >= col, row > col
    pairs = (apart == 1) & strict
    eye = jnp.where(apart == 0, 1.0, 0.0)

    @pl.when(pl.program_id(2) == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    def cast(x):
        return x.astype(mxu)

    # Every stage over all the heads before the next stage: the compiler hands
    # the products to the MXUs in program order and each MXU works in that
    # order, so the heads' chains run side by side only if they stand so here.
    js, khs = range(heads), range(heads // per_key)
    # g summed along the chunk: the tile times a triangle of ones, float32 kept
    g_rows = _dot(g_ref[...], jnp.where(row <= col, 1.0, 0.0), precision=jax.lax.Precision.HIGHEST)
    # a head's column [C, 1] beside its row [1, C]: one turn of the [heads, C]
    # block a step serves every head (a row's broadcast down the sublanes and
    # a column's along the lanes are then both plain)
    g_cols, beta_cols = g_rows.T, beta_ref[...].T
    g_row = [g_rows[j:j + 1, :] for j in js]
    g_col = [g_cols[:, j:j + 1] for j in js]
    # D_ij beta_j, 0 above the diagonal: beta scales COLUMNS, a row's broadcast, for
    # (I + diag(beta) A')^-1 diag(beta) = diag(beta) (I + A' diag(beta))^-1
    scaled = [
        jnp.exp(jnp.where(lower, g_col[j] - g_row[j], -jnp.inf)) * beta_ref[j:j + 1, :] for j in js
    ]
    kb = [cast(_unit(k_ref[:, i * dk:(i + 1) * dk].astype(jnp.float32))) for i in khs]
    qb = [cast(_unit(q_ref[:, i * dk:(i + 1) * dk].astype(jnp.float32), dk ** -0.5)) for i in khs]
    kq = [jnp.concatenate([kb[i], qb[i]], axis=0) for i in khs]  # [k; q], twice an operand
    both = [_dot(kq[i], kb[i], _NT) for i in khs]  # [k; q] k^T, once a key head
    kk = [jnp.where(strict, x[:chunk], 0.0) for x in both]
    qk = [x[chunk:] for x in both]
    a = [kk[j // per_key] * scaled[j] for j in js]
    ab = [cast(x) for x in a]
    within = [cast(qk[j // per_key] * scaled[j]) for j in js]
    t = [jnp.where(pairs, -x, eye) for x in a]  # blocks of two: I - A
    size = 2
    while size < chunk:
        # the blocks a doubling brings in lie below the diagonal of the doubled
        # block, outside the blocks of ``size`` already inverted; only those
        # rows change, and from 8 on they are whole sublane groups: half the
        # rows go through both products
        mask = (apart >> (size.bit_length() - 1)) == 1
        if size >= 8:
            mask = _second(mask, size)
            rows = [_second(x, size) for x in t]
            x = [cast(jnp.where(mask, _dot(cast(rows[j]), ab[j]), 0.0)) for j in js]
            y = [_dot(x[j], cast(t[j])) for j in js]
            t = [_weave(t[j], rows[j] - y[j], size) for j in js]
        else:
            tb = [cast(x) for x in t]
            x = [cast(jnp.where(mask, _dot(tb[j], ab[j]), 0.0)) for j in js]
            t = [t[j] - _dot(x[j], tb[j]) for j in js]
        size *= 2
    state = [s_ref[j] for j in js]
    held = [_dot(kq[j // per_key], cast(state[j])) for j in js]  # [k; q] S
    grown = [jnp.exp(g_col[j]) for j in js]
    missing = [
        cast(v_ref[:, j * dv:(j + 1) * dv].astype(jnp.float32) - held[j][:chunk] * grown[j]) for j in js
    ]
    fresh = [_dot(cast(t[j]), missing[j]) for j in js]  # the chunk's (v - S^T k), before beta
    out = [held[j][chunk:] * grown[j] + _dot(within[j], cast(fresh[j])) for j in js]
    g_end = [x[:, chunk - 1:chunk] for x in g_row]  # [1, 1]: the chunk's whole decay
    carried = [
        cast(fresh[j] * (jnp.exp(g_end[j] - g_col[j]) * beta_cols[:, j:j + 1])) for j in js
    ]
    add = [_dot(kb[j // per_key], carried[j], _TN) for j in js]
    for j in js:
        o = out[j]
        if norm_eps is not None:
            o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=1, keepdims=True) + norm_eps)
        o_ref[:, j * dv:(j + 1) * dv] = o.astype(o_ref.dtype)
        # [1, 1] goes along the lanes first: Mosaic broadcasts one way at a time
        kept = jnp.exp(jnp.broadcast_to(g_end[j], (1, dv)))
        s_ref[j] = state[j] * kept + add[j]


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
    chunks, and g (a position's own: the kernel sums it within its chunk) and
    beta as ``gated_delta_rule`` lays them out, [b, chunks, steps, heads a
    step, C] float32."""
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
        q, k, v, rows(g), rows(beta), key_heads=key_heads,
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
