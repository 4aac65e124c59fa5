"""A learned sparse selection in front of attention: the indexer's scores and
the choice of each query's keys (Pallas).

A decoder with an indexer (``model_type`` ``glm_moe_dsa``, the DeepSeek-V3.2
``Indexer``) lets a query attend only the ``index_topk`` keys a second, small
attention scores highest:

  score[t, s] = sum_j w[t, j] * ReLU(q_I[t, j] . k_I[s])       s <= t, float32
  S_t         = the min(index_topk, t + 1) positions of largest score, the
                lower index first where scores tie

with H index heads j of D dims and ONE index key a position.  The per-head
scores of a judge panel are [3, 32, 8192, 8192], 12.9 GB in bf16, and never
exist: ``index_scores`` walks the lower triangle's (query block, key block)
pairs like ``ops/causal_attention.py`` does, multiplies a head at a time on
the MXU (bf16 in, float32 out), and adds ``w * ReLU`` into one float32 tile,
which is all it writes.

``index_select`` turns a row of scores into the row's choice WITHOUT sorting
it.  A float32's bits, the negative ones flipped, order as the numbers do, so
the k-th largest of a row is found a bit at a time: 32 passes of "how many
keys are at or over this candidate" over a row block that stays in VMEM,
each a compare and an add a key.  Keys over the threshold are chosen; of the
keys AT it, the lowest indices, as many as are still needed, by 14 passes
more over the positions (``lax.top_k``'s rule for equals; -0.0 orders under
0.0, as its total order has it).  A key past the
query (s > t) orders below every number and is cut from the result, so a
query before position k chooses every key it may see and a padded slot is
never chosen by a real query.  The choice leaves as one int8 a (query, key)
pair, [b, s, s]: the attention kernel reads its tile a head, 0.2 GB a call
against 0.8 as float32.

``select_topk_dense`` is the same rule in ``jax.numpy`` through
``lax.top_k``: the decoded token's one row, and the kernels' twin in the
tests.  ``index_scores_einsum`` is the plain twin of the scores.

The jitted functions' names, ``index_scores`` and ``index_select``, are the
kernels' names in a device trace.  On a backend without a TPU the kernels run
in interpret mode, the same code path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .causal_attention import _steps, block_for

_LANES = 128
_INT_MIN = -(1 << 31)
_VMEM_LIMIT = 64 << 20  # of a v5e core's 128 MiB; the default scope is 16
_SCORE_BLOCK = 512  # query rows and keys of one tile of scores
_SELECT_ROWS = 128  # queries whose rows of scores one step of the choice holds
_GROUP = 4  # key chunks a trip of the counting loops


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


# -- the scores ---------------------------------------------------------------------


def _scores_kernel(qi_ref, ki_ref, q_ref, k_ref, w_ref, o_ref, *, heads: int):
    dim = k_ref.shape[1]
    k = k_ref[...]
    acc = jnp.zeros(o_ref.shape, jnp.float32)
    for j in range(heads):
        raw = jax.lax.dot_general(
            q_ref[:, j * dim:(j + 1) * dim], k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc += w_ref[:, j:j + 1] * jnp.maximum(raw, 0.0)
    o_ref[...] = acc


@functools.partial(jax.jit, static_argnames=("heads", "block", "interpret"))
def index_scores(q, k, w, *, heads: int, block: int = 0, interpret: bool | None = None):
    """q [b, s, heads * d], k [b, s, d], w [b, s, heads] float32 -> scores
    [b, s, s] float32, ``sum_j w[t, j] * ReLU(q[t, j] . k[s])``, written for
    the blocks of the lower triangle only: what lies in a block wholly above
    the diagonal is never written (``index_select`` reads no key past its
    query)."""
    b, s, _ = q.shape
    block = block or block_for(s, _SCORE_BLOCK)
    qi_of_step, ki_of_step = _steps(s, block, block)

    def by_query(bi, step, qi, ki):
        return bi, qi[step], 0

    return pl.pallas_call(
        functools.partial(_scores_kernel, heads=heads),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, len(qi_of_step)),
            in_specs=[
                pl.BlockSpec((None, block, q.shape[2]), by_query),
                pl.BlockSpec((None, block, k.shape[2]), lambda bi, step, qi, ki: (bi, ki[step], 0)),
                pl.BlockSpec((None, block, heads), by_query),
            ],
            out_specs=pl.BlockSpec(
                (None, block, block), lambda bi, step, qi, ki: (bi, qi[step], ki[step])
            ),
        ),
        out_shape=jax.ShapeDtypeStruct((b, s, s), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"), vmem_limit_bytes=_VMEM_LIMIT
        ),
        interpret=_interpret() if interpret is None else interpret,
    )(jnp.asarray(qi_of_step), jnp.asarray(ki_of_step), q, k, w.astype(jnp.float32))


def index_scores_einsum(q, k, w, *, heads: int):
    """The kernel's plain twin: every [b, heads, s, s] score at once (tests,
    a decoded token's one row)."""
    b, t, _ = q.shape
    qh = q.reshape(b, t, heads, -1)
    raw = jnp.einsum("bthd,bsd->bths", qh, k, preferred_element_type=jnp.float32)
    return jnp.sum(w.astype(jnp.float32)[..., None] * jnp.maximum(raw, 0.0), axis=2)


# -- the choice ---------------------------------------------------------------------


def _ordered(x):
    """float32 -> int32 that orders as the numbers do, -0.0 under 0.0 (the
    total order ``lax.top_k`` sorts by)."""
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    return jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)


def _select_kernel(s_ref, o_ref, key_ref, *, k: int):
    rows, width = s_ref.shape
    chunks = width // _LANES
    r0 = pl.program_id(1) * rows
    row = r0 + jax.lax.broadcasted_iota(jnp.int32, (rows, _LANES), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, _LANES), 1)

    def seen(c):
        """Keys of chunk c at or before each row's query."""
        return c * _LANES + lane <= row

    # every query of the block is before position k: each chooses all it sees
    @pl.when(r0 + rows <= k)
    def _():
        for c in range(chunks):
            o_ref[:, c * _LANES:(c + 1) * _LANES] = seen(c).astype(o_ref.dtype)

    @pl.when(r0 + rows > k)
    def _():
        low = jnp.int32(_INT_MIN)
        for c in range(chunks):
            key = _ordered(s_ref[:, c * _LANES:(c + 1) * _LANES])
            key_ref[c] = jnp.where(seen(c), key, low)
        # chunks that hold a key some query of the block sees, in whole trips
        trips = ((r0 + rows + _LANES - 1) // _LANES + _GROUP - 1) // _GROUP

        def count(hit):
            """[rows, lanes], every lane the row's count of keys that ``hit(key
            chunk, chunk index)`` marks."""

            def trip(i, acc):
                for u in range(_GROUP):
                    c = i * _GROUP + u
                    acc = acc + hit(key_ref[c], c).astype(jnp.int32)
                return acc

            acc = jax.lax.fori_loop(0, trips, trip, jnp.zeros((rows, _LANES), jnp.int32))
            return jnp.broadcast_to(jnp.sum(acc, axis=1, keepdims=True), acc.shape)

        # the k-th largest key of a row, from the sign down: the largest
        # threshold that k keys or more reach
        zero = jnp.zeros((rows, _LANES), jnp.int32)
        at = jnp.where(count(lambda key, c: key >= zero) >= k, zero, low)

        def lower_bit(i, at):
            cand = at | jnp.left_shift(jnp.int32(1), 30 - i)
            return jnp.where(count(lambda key, c: key >= cand) >= k, cand, at)

        at = jax.lax.fori_loop(0, 31, lower_bit, at)
        # of the keys AT the threshold, the lowest positions, as many as the
        # keys over it leave room for: the largest bound that many lie under
        room = k - count(lambda key, c: key > at)

        def under(bound):
            return lambda key, c: (key == at) & (c * _LANES + lane < bound)

        def bound_bit(i, bound):
            cand = bound | jnp.left_shift(jnp.int32(1), width.bit_length() - 1 - i)
            return jnp.where(count(under(cand)) <= room, cand, bound)

        bound = jax.lax.fori_loop(0, width.bit_length(), bound_bit, zero)
        for c in range(chunks):
            key = key_ref[c]
            chosen = (key > at) | ((key == at) & (c * _LANES + lane < bound))
            o_ref[:, c * _LANES:(c + 1) * _LANES] = (chosen & seen(c)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def index_select(scores, *, k: int, interpret: bool | None = None):
    """scores [b, s, s] float32 (``index_scores``) -> keep [b, s, s] int8: 1
    where key s is one of query t's min(k, t + 1) highest-scored keys at or
    before t, the lower index first among equals; 0 elsewhere."""
    b, s, width = scores.shape
    rows = block_for(s, _SELECT_ROWS)
    if width % (_LANES * _GROUP):  # a tiny preset: whole trips of chunks
        pad = -width % (_LANES * _GROUP)
        scores = jnp.pad(scores, ((0, 0), (0, 0), (0, pad)))
    padded = scores.shape[2]
    keep = pl.pallas_call(
        functools.partial(_select_kernel, k=k),
        grid=(b, s // rows),
        in_specs=[pl.BlockSpec((None, rows, padded), lambda bi, i: (bi, i, 0))],
        out_specs=pl.BlockSpec((None, rows, padded), lambda bi, i: (bi, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, s, padded), jnp.int8),
        scratch_shapes=[pltpu.VMEM((padded // _LANES, rows, _LANES), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"), vmem_limit_bytes=_VMEM_LIMIT
        ),
        interpret=_interpret() if interpret is None else interpret,
    )(scores)
    return keep[:, :, :width] if padded != width else keep


def select_topk_dense(scores, seen, k: int):
    """The same rule over whole rows in ``jax.numpy``: scores [..., n]
    float32, ``seen`` [..., n] bool (the keys a row may choose from) -> bool
    [..., n], the min(k, keys seen) highest-scored of them, the lower index
    first among equals."""
    k = min(k, scores.shape[-1])
    masked = jnp.where(seen, _ordered(scores), _INT_MIN)
    at = jax.lax.top_k(masked, k)[0][..., -1:]
    over, equal = masked > at, masked == at
    room = k - jnp.sum(over, axis=-1, keepdims=True)
    return (over | (equal & (jnp.cumsum(equal, axis=-1) <= room))) & seen
