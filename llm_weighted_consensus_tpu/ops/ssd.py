"""The state-space dual (SSD) form of Mamba-2 in chunks, for a prefill (Pallas).

A head keeps a state ``S`` [P, N] (P the head's width, N the states) and per
position t, with the head's input x_t [P], a step dt_t > 0 and ONE rate a < 0 a
head (Mamba-1, ``ops/selective_scan.py``, has a rate for every channel and
state), and B_t, C_t [N] that the heads of a GROUP share as query heads share a
key head:

    S_t = exp(dt_t a) S_{t-1} + dt_t x_t (x) B_t
    y_t = S_t C_t + d x_t

``ssd_recurrent`` is that loop, a ``lax.scan`` over the positions in float32:
the twin the tests compare with, and one step of it (``ssd_step``) is a judge's
decoded token.  One decay a head and a position makes a chunk's inside matrix
products: with g_t = dt_t a, G_t its running sum inside a chunk of C positions
and G_C the chunk's whole decay,

    Y    = lower((C B^T) . exp(G_t - G_s) dt_s) X      the chunk's own positions
         + exp(G_t) (C S^T)                            what the state carried in
         + d X
    S   <- exp(G_C) S + (X . exp(G_C - G_s) dt_s)^T B

so only the chunk-to-chunk state is a recurrence: the shape
``ops/gated_delta.py`` has for another rule.  C B^T is taken ONCE a group and a
chunk and every head of the step masks it with its own decays.

Layout: the projections' own.  xs and y are [b, s, heads * P], b and c [b, s,
groups * N]; head j reads group ``j // (heads / groups)`` through the block's
index, nothing is repeated in memory.  dt is [b, s, heads] float32 (the
softplus taken) and reaches the kernel a head a row, [heads a step, C] a chunk;
the kernel sums g along the chunk itself (one product with a triangle of ones
at HIGHEST precision, as the delta rule's does) and turns the tile once a step
for the heads' columns.  A position at or past ``lens`` has its step set to 0
INSIDE the kernel (``lens`` is prefetched): it leaves the state as it was, and
a chunk that starts past ``lens`` runs no product at all.

Grid (b, heads / heads a step, chunks), the chunks innermost and in order: the
state is the resident output block [heads a step, P, N] float32.  The running
sums, their differences and exponentials and the state are float32 whatever
the inputs; the products' operands stay in the storage dtype (bf16 feeds the
MXU natively) and accumulate in float32.  The heads of a step are worked STAGE
BY STAGE (``ops/gated_delta.py``: the compiler hands the products to the MXUs
in program order, so the heads' chains run side by side only if they stand so
in the program).  The jitted function's name is the kernel's name in a device
trace.

What the chip said (TPU v5e, my chip runs, PR 49; a layer's scan at [3, 8192],
32 heads of 128 on 2 groups of 256 states, bf16, ``lens`` 7525 / 8192 / 4000,
``scripts/time_ssd_forms.py``).  Under the PUBLISHED long-memory initialisation
the compiled kernel reads 0.19% of the float32 recurrence's output and 0.17%
of its state (root mean square of the difference over root mean square), the
same in every form.  Before the chip, the chip's compiler run for a described
v5e had counted 5,850 scheduled bundles a grid step at sixteen heads a step
(384 steps a layer: 1.5 ms at 1.5 GHz) and 3,460 at eight (768 steps: 1.8 ms);
in the seventh judge's program the kernel's own events are 1.34 ms a layer.
Alone, host clock around the jitted function, the steps' two layouts and a
dispatch's 0.55 ms included, ms: sixteen heads a step 2.22 (as served: B and C
read once a group), eight 2.31, four 2.79; chunks of 256 positions 1.97 and
2.06 (a tenth less for twice the masks' work: not taken, 0.2 ms a layer of a
program of 825).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CHUNK = 128  # positions of a chunk: one pass of the 128 x 128 MXU a product
HEADS_PER_STEP = 16  # heads of a grid step, of one group (at 16: B and C once a group), worked stage by stage
_VMEM_LIMIT = 48 << 20

_NN = ((1,), (0,))  # a @ b
_NT = ((1,), (1,))  # a @ b.T
_TN = ((0,), (0,))  # a.T @ b


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _dot(a, b, dims=_NN, precision=None):
    return jax.lax.dot_general(
        a, b, (dims, ((), ())), precision=precision, preferred_element_type=jnp.float32
    )


def _kernel(
    lens_ref, x_ref, b_ref, c_ref, dt_ref, g_ref, d_ref, y_ref, s_ref,
    *, heads, p, n, chunk,
):
    start = pl.program_id(2) * chunk
    length = lens_ref[pl.program_id(0)]

    @pl.when(pl.program_id(2) == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    @pl.when(start >= length)
    def _():  # every position is padding: the state stays, nothing is multiplied
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(start < length)
    def _():
        mxu = x_ref.dtype
        row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
        lower = row >= col
        live = start + jax.lax.broadcasted_iota(jnp.int32, (1, chunk), 1) < length
        js = range(heads)
        # a head a row: the steps, and g = dt a summed along the chunk (the tile
        # times a triangle of ones, float32 kept)
        dt_rows = jnp.where(live, dt_ref[...], 0.0)
        g_rows = _dot(
            jnp.where(live, g_ref[...], 0.0), jnp.where(row <= col, 1.0, 0.0),
            precision=jax.lax.Precision.HIGHEST,
        )
        g_end = g_rows[:, chunk - 1:chunk]  # [heads, 1]: the chunk's whole decay
        # what a position still weighs in the state the chunk hands on
        w_rows = jnp.exp(g_end - g_rows) * dt_rows
        # a head's column [C, 1] beside its row [1, C]: one turn a tile
        g_cols, w_cols, d_cols = g_rows.T, w_rows.T, d_ref[...].T
        bm, cm = b_ref[...], c_ref[...]
        scores = _dot(cm, bm, _NT)  # C B^T, once a group
        x = [x_ref[:, j * p:(j + 1) * p] for j in js]
        x32 = [v.astype(jnp.float32) for v in x]
        state = [s_ref[j] for j in js]
        # lower(exp(G_t - G_s)) dt_s: the decays are a head's, dt scales COLUMNS
        masked = [
            (
                scores
                * jnp.exp(jnp.where(lower, g_cols[:, j:j + 1] - g_rows[j:j + 1, :], -jnp.inf))
                * dt_rows[j:j + 1, :]
            ).astype(mxu)
            for j in js
        ]
        within = [_dot(masked[j], x[j]) for j in js]
        carried = [_dot(cm, state[j].astype(mxu), _NT) for j in js]  # C S^T
        weighed = [(x32[j] * w_cols[:, j:j + 1]).astype(mxu) for j in js]
        add = [_dot(weighed[j], bm, _TN) for j in js]  # (X . w)^T B
        for j in js:
            y = (
                within[j] + carried[j] * jnp.exp(g_cols[:, j:j + 1])
                + x32[j] * d_cols[:, j:j + 1]
            )
            y_ref[:, j * p:(j + 1) * p] = y.astype(y_ref.dtype)
            # [1, 1] goes along the lanes first: Mosaic broadcasts one way at a time
            kept = jnp.exp(jnp.broadcast_to(g_end[j:j + 1, :], (1, n)))
            s_ref[j] = state[j] * kept + add[j]


def _heads_a_step(per_group: int, heads_per_step: int) -> int:
    """Heads of a grid step: a divisor of a group's heads."""
    heads = max(min(heads_per_step, per_group), 1)
    while per_group % heads:
        heads -= 1
    return heads


@functools.partial(
    jax.jit, static_argnames=("groups", "chunk", "heads_per_step", "interpret")
)
def ssd_chunked(
    xs, dt, a, b, c, d, lens, *, groups: int, chunk: int = CHUNK,
    heads_per_step: int = HEADS_PER_STEP, interpret: bool | None = None,
):
    """xs [b, s, heads * P], dt [b, s, heads] float32 (> 0: the softplus
    taken), a [heads] (< 0), b and c [b, s, groups * N], d [heads], lens [b]
    -> (y [b, s, heads * P] in xs' dtype, the state after position ``lens -
    1`` [b, heads, P, N] float32).  Positions at or past ``lens`` move no
    state (their y is not meant to be read).  A length that is no whole number
    of chunks is padded with positions past every ``lens``."""
    bsz, s, width = xs.shape
    hv = dt.shape[-1]
    p, n = width // hv, b.shape[-1] // groups
    per_group = hv // groups
    if interpret is None:
        interpret = _interpret()
    if per_group * groups != hv or p * hv != width or (not interpret and (p % 128 or n % 128)):
        raise ValueError(f"{hv} heads of {p} on {groups} groups of {n} states")
    heads = _heads_a_step(per_group, heads_per_step)
    steps = hv // heads
    chunks = -(-s // chunk)
    lens = jnp.minimum(lens.astype(jnp.int32), s)
    if chunks * chunk != s:
        grow = ((0, 0), (0, chunks * chunk - s), (0, 0))
        xs, dt, b, c = (jnp.pad(v, grow) for v in (xs, dt, b, c))

    def rows(v):  # [b, s, hv] -> [b, chunks, steps, heads a step, C], a head a row
        v = v.astype(jnp.float32).reshape(bsz, chunks, chunk, steps, heads)
        return jnp.transpose(v, (0, 1, 3, 4, 2))

    a32 = a.astype(jnp.float32)
    d_rows = jnp.broadcast_to(d.astype(jnp.float32).reshape(steps, heads, 1), (steps, heads, chunk))
    by_chunk = lambda width, of: pl.BlockSpec(  # noqa: E731
        (None, chunk, width), lambda bi, hg, ci, lens: (bi, ci, of(hg))
    )
    by_head = pl.BlockSpec(
        (None, None, None, heads, chunk), lambda bi, hg, ci, lens: (bi, ci, hg, 0, 0)
    )
    own, group = (lambda hg: hg), (lambda hg: hg // (per_group // heads))
    y, state = pl.pallas_call(
        functools.partial(_kernel, heads=heads, p=p, n=n, chunk=chunk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bsz, steps, chunks),
            in_specs=[
                by_chunk(heads * p, own), by_chunk(n, group), by_chunk(n, group),
                by_head, by_head,
                pl.BlockSpec((None, heads, chunk), lambda bi, hg, ci, lens: (hg, 0, 0)),
            ],
            out_specs=[
                by_chunk(heads * p, own),
                pl.BlockSpec((None, heads, p, n), lambda bi, hg, ci, lens: (bi, hg, 0, 0)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(xs.shape, xs.dtype),
            jax.ShapeDtypeStruct((bsz, hv, p, n), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=interpret,
    )(lens, xs, b, c, rows(dt), rows(dt * a32), d_rows)
    return y[:, :s], state


def ssd_step(state, x, dt, a, b, c, d):
    """One position of the recurrence: state [b, heads, P, N] float32, x [b,
    heads * P], dt [b, heads] (> 0), a and d [heads], b and c [b, groups * N]
    -> (y [b, heads * P] float32, the new state)."""
    bsz, hv, p, n = state.shape
    f32 = lambda v: v.astype(jnp.float32)  # noqa: E731
    x, dt = f32(x).reshape(bsz, hv, p), f32(dt)

    def heads(v):  # [b, groups * N] -> [b, heads, N]: head j reads group j // (heads / groups)
        v = f32(v).reshape(bsz, -1, n)
        return jnp.repeat(v, hv // v.shape[1], axis=1)

    decay = jnp.exp(dt * f32(a))[..., None, None]
    state = state * decay + (dt[..., None] * x)[..., None] * heads(b)[:, :, None, :]
    y = jnp.einsum("bhpn,bhn->bhp", state, heads(c)) + f32(d)[None, :, None] * x
    return y.reshape(bsz, hv * p), state


def ssd_recurrent(xs, dt, a, b, c, d, lens, *, groups: int):
    """The kernel's plain twin, a position at a time (tests, tiny sizes): the
    same arguments and results as ``ssd_chunked``."""
    bsz, s, width = xs.shape
    hv = dt.shape[-1]
    n = b.shape[-1] // groups
    live = jnp.arange(s)[None, :] < lens[:, None]
    dt = jnp.where(live[..., None], dt.astype(jnp.float32), 0.0)

    def step(state, at):
        y, state = ssd_step(state, *at[:2], a, *at[2:], d)
        return state, y

    along = lambda v: jnp.moveaxis(v, 1, 0)  # noqa: E731
    state, y = jax.lax.scan(
        step, jnp.zeros((bsz, hv, width // hv, n), jnp.float32),
        (along(xs), along(dt), along(b), along(c)),
    )
    return along(y).astype(xs.dtype), state
