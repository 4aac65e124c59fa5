"""The selective state-space scan of a Mamba layer's prefill (Pallas).

A channel c keeps a state of ``n`` numbers and per token t, with an input
x[t, c], a step dt[t, c] > 0, the channel's own rates A[c, :] < 0 and the
token's B[t, :] and C[t, :] (shared by every channel):

    dt[t]   = softplus(dt_raw[t] + dt_bias)
    h[t]    = exp(dt[t, c] · A[c, :]) ⊙ h[t - 1] + dt[t, c] · x[t, c] · B[t, :]
    y[t, c] = h[t] · C[t, :] + D[c] · x[t, c]

The decay differs a channel AND a state, so no matrix form serves it (the
delta rule's chunked kernel multiplies; this one cannot): it is a chain of
elementwise updates, the vector unit's work and none of the MXU's.
``selective_scan_recurrent`` is that chain as a ``lax.scan`` over the
positions in float32, the twin the tests compare with, and one step of it
(``selective_scan_step``) is a judge's decoded token.  A prefill cannot be
served by either: the state at every position is [s, channels, n] float32
(8 GB a layer at 3 x 8192 x 5120 x 16) and a ``lax.scan`` is a launch a
position.  The kernel keeps the state in VMEM and only y leaves.

Layout: the projections' own, channels on the lanes.  x and dt_raw are
[b, s, channels] as the convolution and the dt product wrote them, y the
same; the state stands [n, channels]: its n numbers down the SUBLANES (two
vector registers of float32 a 128 channels at n = 16), so a position's update
is whole-register arithmetic and y[t] is one reduction over sublanes.  B[t]
and C[t] then have to be columns spread across the lanes: they arrive
[b, s, n, lanes] (``_across_lanes``: XLA writes each number a lane group
wide, 0.1 GB an array at the judges' shapes, which the kernel reads ONCE a
chunk because a grid step holds every channel; spreading a row's 16 numbers
down the sublanes inside the kernel would be a relayout a position).

Grid (b, channel blocks, chunks of positions), the chunks innermost and in
order; the state is the second OUTPUT's block, which stays in VMEM from chunk
to chunk and goes to HBM when the channel block changes.  A chunk's step
walks its channels ``group`` at a time, three loops a group, so that nothing
wider than a group is ever one value (PR 45 took softplus of the whole
[chunk, channels] tile: 5,300 spill loads and stores a step).  The groups
are a loop too, and a group's lane groups ONE value [lane groups, n, lanes],
so that a position is a dozen traced equations: as five of Python's groups
of eight of Python's lane groups the same schedule took 2.8 s to trace and
lower for 0.2, which the sixth judge's server paid at every start (its panel
4.4 s for the parent's 2.3 on the chip's machine, cache or no cache):

1. rows, ``_ROWS_IN`` a trip: softplus, the mask of the positions at and past
   the call's length (dt = 0 there: exp(0) = 1 and nothing is added, so the
   state stays as it stood after ``lens - 1``) and dt * x, written a lane
   group apart ([lane groups, chunk, lanes]: a row of ONE lane group is what a
   load can spread down the sublanes for nothing, where a row of four cost a
   ``vperm.slane`` a lane group and position);
2. positions, ``_UNROLL`` a trip, the group's states the loop's carry
   (registers): ``h = exp2(dt * A log2 e) * h + dt x * B``, then
   ``h * C`` halved down to ONE register of eight sublanes.  The sum over
   those eight goes THROUGH VMEM: position j of eight stores its register to
   rows j, j + 8, ... j + 56 (one strided store), so that tile r of the 64
   rows holds sublane r of all eight positions, a position a row, and seven
   whole-register adds of the eight tiles are eight finished rows in position
   order.  No rotate, no select, no masked row (PR 45: three rotates and adds
   and a masked one-row store a position, at the END of every trip where
   nothing overlapped them);
3. rows again, a packed tile of y a trip: D * x added (x re-read: it is not
   held across the loops), the storage dtype, the store.

dt * A is exponentiated a position, in the kernel: an [s, channels, n] array
never exists.  ``d_state`` is whole groups of eight sublanes (8, 16, 32 ...:
halved down to eight before the store); another is refused.

The forms, PR 47: bundles a grid step of [128, 5120] by the scheduler's own
report for a described v5e (a STATIC count) beside the kernel ALONE on a
v5 lite at [3, 8192, 5120] bf16, n = 16 (``scripts/time_scan_forms.py``, ms a
layer; nine calls a program, the least of ten):

    form                                              bundles   ms
    PR 45's: 2 positions a trip, exp, one-row sums     50,751   6.75
    8 positions a trip                                 41,174   5.38
    ... exp2, log2 e in A                              38,717   5.09
    ... the eight sums by a butterfly of seven folds   32,825   4.31
    ... the first and last step in row groups of 8     31,472   4.14
    states a lane group, sums through VMEM, summed
      in the last loop (8 strided loads a register)    36,730   not timed
    ... stored strided, summed in the last loop        31,256   4.13
    ... summed in the trip, 8 positions a trip         30,004   4.10
    ... 16 positions a trip                            27,572   3.79
    ... 32 rows a trip of the first loop               26,102   3.61
    ... B and C unpacked once a chunk (not kept)       25,613   3.63
    ... groups of 256 (not kept)                       31,433   4.47
    ... groups of 1024                                 23,499   3.31
    ... 32 positions a trip (not kept: 8 s to compile) 22,935   3.23
    groups of 1024, 16 positions, the groups a loop    23,731   3.33
    ... a group's lane groups one value: SERVED        23,940   3.44

The count told the order and, to 5%, the time (1.44 GHz a bundle) of every
form but one: unpacking B and C once a chunk counted 2% under and timed level
(the trip's loads, one a bundle, were then its bound).  The last two rows
buy no time and cost 4%: they are there for the trace (above).  The served
trip is 491 bundles for 16 positions x 8 lane groups, 3.8 a position and lane
group where the vector slots' own 1,867 operations fill 3.6: what is left of
the kernel is its arithmetic (8 multiplies, 4 adds, 2 ``vpow2`` a position,
lane group and n = 16).

On a backend without a TPU the kernel runs in interpret mode, the same code
path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
_CHUNK = 128  # positions a grid step
# channels whose states the position loop carries in registers: 16 of the 64
# at 1024 and n = 16, beside A's 16.  Timed alone on the chip (ms a layer):
# 256 4.47, 512 3.61, 1024 3.31; 1280 counted level with 1024 and spills
_GROUP = 1024
# positions a trip of that loop (Mosaic unrolls a loop wholly or not at all):
# at 8 the trip ends on its own sums (4.6 bundles a position and lane group),
# at 16 the first eight's overlap the second eight's updates (4.2: 3.79 ms
# for 4.10 at groups of 512); 32 times 2% under 16 and compiles twice as long
_UNROLL = 16
# rows a trip of the loop before it: softplus is one long chain a register,
# so 16 rows leave the slots idle (3.79 -> 3.61 ms from 16 to 32 at 512)
_ROWS_IN = 32
_LOG2E = 1.4426950408889634
_VMEM_LIMIT = 64 << 20  # of a v5e core's 128 MiB


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _softplus(x):
    return jnp.maximum(x, 0.0) + jnp.log(1.0 + jnp.exp(-jnp.abs(x)))


def _across_lanes(rows, lanes: int):
    """[b, s, n] -> [b, s, n, lanes]: each number a lane group wide."""
    return jnp.broadcast_to(rows[..., None], (*rows.shape, lanes))


def _kernel(
    lens_ref, x_ref, dt_ref, bias_ref, a_ref, b_ref, c_ref, d_ref, y_ref, state_ref,
    dt_s, dtx_s, y_s, part_s, *, chunk: int, group: int, lanes: int,
):
    bi, ci = pl.program_id(0), pl.program_id(2)
    n, channels = a_ref.shape
    tiles = group // lanes  # lane groups whose states a trip walks side by side
    trip = _UNROLL if chunk % _UNROLL == 0 else 8  # positions, whole groups of eight
    rows = 16 if chunk % 16 == 0 else 8  # the last loop's: a packed tile of the storage dtype
    rows_in = _ROWS_IN if chunk % _ROWS_IN == 0 else rows

    @pl.when(ci == 0)
    def _():
        state_ref[...] = jnp.zeros_like(state_ref)

    def one_group(ri, carry):
        c0 = pl.multiple_of(ri * group, group)
        cs = pl.ds(c0, group)
        tile = lambda g: pl.ds(pl.multiple_of(c0 + g * lanes, lanes), lanes)  # noqa: E731

        def rows_before(i, carry):
            at = pl.ds(pl.multiple_of(i * rows_in, rows_in), rows_in)
            position = ci * chunk + i * rows_in + jax.lax.broadcasted_iota(
                jnp.int32, (rows_in, group), 0
            )
            dt = _softplus(dt_ref[at, cs].astype(jnp.float32) + bias_ref[:, cs])
            dt = jnp.where(position < lens_ref[bi], dt, 0.0)
            dtx = dt * x_ref[at, cs].astype(jnp.float32)
            for g in range(tiles):
                dt_s[g, at, :] = dt[:, g * lanes:(g + 1) * lanes]
                dtx_s[g, at, :] = dtx[:, g * lanes:(g + 1) * lanes]
            return carry

        jax.lax.fori_loop(0, chunk // rows_in, rows_before, 0)
        # the group's lane groups side by side, [tiles, n, lanes]; exp(x) = exp2(x log2 e)
        rates = jnp.stack([a_ref[:, tile(g)] for g in range(tiles)]) * _LOG2E

        def positions(i, h):
            for e in range(trip // 8):
                for j in range(8):  # eight positions, in order
                    t = i * trip + e * 8 + j
                    # a row of one lane group: the load spreads it down the sublanes
                    dt, dtx = dt_s[:, pl.ds(t, 1), :], dtx_s[:, pl.ds(t, 1), :]
                    h = jnp.exp2(dt * rates) * h + dtx * b_ref[t].astype(jnp.float32)
                    hc = h * c_ref[t].astype(jnp.float32)
                    part = hc[:, :8]
                    for k in range(8, n, 8):
                        part = part + hc[:, k:k + 8]
                    # sublane r to row 8 r + j: tile r is sublane r of the eight positions
                    part_s[e, :, pl.ds(j, 8, stride=8), :] = part
                total = part_s[e, :, pl.ds(0, 8), :]  # ... and the tiles' sum their finished rows
                for r in range(1, 8):
                    total = total + part_s[e, :, pl.ds(r * 8, 8), :]
                y_s[:, pl.ds(pl.multiple_of(i * trip + e * 8, 8), 8), :] = total
            return h

        h = jax.lax.fori_loop(
            0, chunk // trip, positions, jnp.stack([state_ref[:, tile(g)] for g in range(tiles)])
        )
        for g in range(tiles):
            state_ref[:, tile(g)] = h[g]

        def rows_behind(i, carry):
            at = pl.ds(pl.multiple_of(i * rows, rows), rows)
            sums = jnp.concatenate([y_s[g, at, :] for g in range(tiles)], axis=1)
            dx = d_ref[:, cs] * x_ref[at, cs].astype(jnp.float32)
            y_ref[at, cs] = (sums + dx).astype(y_ref.dtype)
            return carry

        jax.lax.fori_loop(0, chunk // rows, rows_behind, 0)
        return carry

    jax.lax.fori_loop(0, channels // group, one_group, 0)


@functools.partial(jax.jit, static_argnames=("chunk", "block", "interpret"))
def selective_scan_chunked(
    x, dt_raw, dt_bias, a, b_wide, c_wide, d, lens, *, chunk: int, block: int,
    interpret: bool,
):
    """The kernel alone, under the name a device trace calls it by: whole
    chunks of ``chunk`` positions, whole blocks of ``block`` channels, A as
    [n, channels] float32, B and C as ``_across_lanes`` lays them out."""
    bsz, s, channels = x.shape
    n, lanes = b_wide.shape[2:]
    if n % 8:
        raise ValueError(f"d_state {n}: the kernel sums whole groups of eight sublanes")
    group = next(g for g in (_GROUP, 512, 256, lanes) if block % g == 0 and g % lanes == 0)
    by_chunk = pl.BlockSpec((None, chunk, block), lambda bi, hi, ci, lens: (bi, ci, hi))
    by_channel = lambda rows: pl.BlockSpec(  # noqa: E731
        (rows, block), lambda bi, hi, ci, lens: (0, hi)
    )
    by_position = pl.BlockSpec(
        (None, chunk, n, lanes), lambda bi, hi, ci, lens: (bi, ci, 0, 0)
    )
    tile = pltpu.VMEM((group // lanes, chunk, lanes), jnp.float32)  # dt, dt x, the sums
    parts = pltpu.VMEM((_UNROLL // 8, group // lanes, 64, lanes), jnp.float32)
    return pl.pallas_call(
        functools.partial(_kernel, chunk=chunk, group=group, lanes=lanes),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bsz, channels // block, s // chunk),
            in_specs=[
                by_chunk, by_chunk, by_channel(1), by_channel(n), by_position, by_position,
                by_channel(1),
            ],
            out_specs=[
                by_chunk,
                pl.BlockSpec((None, n, block), lambda bi, hi, ci, lens: (bi, 0, hi)),
            ],
            scratch_shapes=[tile, tile, tile, parts],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            jax.ShapeDtypeStruct((bsz, n, channels), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=interpret,
    )(
        lens.astype(jnp.int32), x, dt_raw, dt_bias.astype(jnp.float32)[None, :],
        a.astype(jnp.float32), b_wide, c_wide, d.astype(jnp.float32)[None, :],
    )


def selective_scan(
    x, dt_raw, dt_bias, a, b, c, d, lens, *, chunk: int = _CHUNK, block: int = 0,
    interpret: bool | None = None,
):
    """x and dt_raw [b, s, channels], dt_bias and D [channels], A [channels,
    n] (negative), B and C [b, s, n], ``lens`` [b] -> (y [b, s, channels] in
    x's dtype, the state [b, channels, n] float32 as it stands after position
    ``lens - 1``).  Positions at and past ``lens`` leave the state alone
    (their y is no token's).  A length that is no whole chunk is padded
    behind."""
    s, channels = x.shape[1:]
    if interpret is None:
        interpret = _interpret()
    chunk = min(chunk, -(-s // 8) * 8)  # whole sublanes, so whole trips too
    pad = -s % chunk
    if pad:
        behind = lambda v: jnp.pad(v, ((0, 0), (0, pad), (0, 0)))  # noqa: E731
        x, dt_raw, b, c = behind(x), behind(dt_raw), behind(b), behind(c)
        lens = jnp.minimum(lens, s)
    lanes = _LANES if channels % _LANES == 0 else channels
    y, state = selective_scan_chunked(
        x, dt_raw, dt_bias, a.T, _across_lanes(b, lanes), _across_lanes(c, lanes), d, lens,
        chunk=chunk, block=block or channels, interpret=interpret,
    )
    return y[:, :s], jnp.swapaxes(state, 1, 2)


def selective_scan_step(state, x, dt_raw, dt_bias, a, b, c, d):
    """One position: state [b, channels, n] float32, x and dt_raw [b,
    channels], B and C [b, n] -> (y [b, channels] float32, the new state)."""
    f32 = lambda v: v.astype(jnp.float32)  # noqa: E731
    x = f32(x)
    dt = jax.nn.softplus(f32(dt_raw) + f32(dt_bias))
    state = (
        jnp.exp(dt[..., None] * f32(a)) * state
        + (dt * x)[..., None] * f32(b)[:, None, :]
    )
    return jnp.einsum("bcn,bn->bc", state, f32(c)) + f32(d) * x, state


def selective_scan_recurrent(x, dt_raw, dt_bias, a, b, c, d, lens):
    """The recurrence as written, a ``lax.scan`` over the positions in
    float32: ``selective_scan``'s twin for the tests."""
    bsz, s, channels = x.shape
    real = jnp.arange(s)[None, :] < lens[:, None]

    def step(state, at):
        x_t, dt_t, b_t, c_t, real_t = at
        y, new = selective_scan_step(state, x_t, dt_t, dt_bias, a, b_t, c_t, d)
        return jnp.where(real_t[:, None, None], new, state), y

    state0 = jnp.zeros((bsz, channels, a.shape[1]), jnp.float32)
    over = lambda v: jnp.swapaxes(v, 0, 1)  # noqa: E731
    state, ys = jax.lax.scan(step, state0, (over(x), over(dt_raw), over(b), over(c), real.T))
    return over(ys).astype(x.dtype), state
