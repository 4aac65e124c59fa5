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
first takes softplus, the mask of the positions at and past the call's length
(dt = 0 there: exp(0) = 1 and nothing is added, so the state stays as it
stood after ``lens - 1``) and dt · x for the whole [chunk, channels] tile,
then walks the positions ``group`` channels at a time with the state of those
channels as the loop's carry (registers), and last adds D · x and writes the
tile.  dt · A is exponentiated a position, in the kernel: an [s, channels, n]
array never exists.

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
_GROUP = 512  # channels whose state the position loop carries in registers
_UNROLL = 2  # positions a trip of that loop (Mosaic unrolls a loop wholly or not at all)
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
    dt_s, dtx_s, y_s, *, chunk: int, group: int, lanes: int,
):
    bi, ci = pl.program_id(0), pl.program_id(2)

    @pl.when(ci == 0)
    def _():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[...].astype(jnp.float32)  # [chunk, channels]
    position = ci * chunk + jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    dt = _softplus(dt_ref[...].astype(jnp.float32) + bias_ref[...])
    dt = jnp.where(position < lens_ref[bi], dt, 0.0)
    dt_s[...] = dt
    dtx_s[...] = dt * x

    def across(column):
        """[n, lanes], every lane the same -> [n, group]: the registers again."""
        if group == lanes:
            return column
        return pltpu.repeat(column, group // lanes, axis=1)

    for c0 in range(0, x.shape[1], group):
        cs = slice(c0, c0 + group)
        a = a_ref[:, cs]  # [n, group]

        def steps(i, h, cs=cs, a=a):
            for j in range(_UNROLL):  # positions a trip, in order
                t = i * _UNROLL + j
                row = pl.ds(t, 1)
                decay = jnp.exp(dt_s[row, cs] * a)
                h = decay * h + dtx_s[row, cs] * across(b_ref[t].astype(jnp.float32))
                y_s[row, cs] = jnp.sum(
                    h * across(c_ref[t].astype(jnp.float32)), axis=0, keepdims=True
                )
            return h

        state_ref[:, cs] = jax.lax.fori_loop(0, chunk // _UNROLL, steps, state_ref[:, cs])
    y_ref[...] = (y_s[...] + d_ref[...] * x).astype(y_ref.dtype)


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
    group = next(g for g in (_GROUP, 256, lanes) if block % g == 0 and g % lanes == 0)
    by_chunk = pl.BlockSpec((None, chunk, block), lambda bi, hi, ci, lens: (bi, ci, hi))
    by_channel = lambda rows: pl.BlockSpec(  # noqa: E731
        (rows, block), lambda bi, hi, ci, lens: (0, hi)
    )
    by_position = pl.BlockSpec(
        (None, chunk, n, lanes), lambda bi, hi, ci, lens: (bi, ci, 0, 0)
    )
    tile = pltpu.VMEM((chunk, block), jnp.float32)
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
            scratch_shapes=[tile, tile, tile],
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
