"""Every head of a projection normalised, and turned, where the product left
it (Pallas).

A grouped-query decoder with head norms takes ``rms`` over each head's 128
dims of q [b, s, heads * 128] before the rotary turn.  Written plainly that is
a reshape to [b, s, heads, 128], and on the chip the reshape is no bitcast: the
tiles hold 16 positions of one head's lanes, the reduction wants them a head a
row, so XLA lays the array out again in float32, broadcasts the scale and lays
it back (the chip's compiler in the sandbox, PR 41: for 0.6 GB of queries a
layer, a copy, a broadcast and a reshape of 1.2 GB each).  Here the array
stays as the product wrote it and the kernel's block is ONE head's 128-lane
column for a block of rows, aliased in to out, as ``ops/rotary.py``'s is: a
row's mean square is a sum along the lanes, and the turn, where the layer
turns, is done on the float32 values before they are rounded, once.

  y   = x / sqrt(mean(x^2) + eps) · weight          ``decoder_parts.rms``, a head
  out = y · C + partner(y) · S                       ``decoder_parts.rope``, pairs
                                                     (i, i + 64); only with angles

``fits`` says which shapes the kernel serves: heads of exactly one 128-lane
column over [b, s, width].  Anything else (a tiny preset, a decode step's
rows) is the caller's to do the plain way.  On a backend without a TPU the
kernel runs in interpret mode, the same code path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .causal_attention import block_for

_LANES = 128
_ROWS = 2048  # rows of a block, as ``ops/rotary.py``'s
_CHUNK = 512  # rows the body works at a time


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def fits(shape, head_dim: int) -> bool:
    """Whether x [b, s, heads * head_dim] is a shape the kernel serves."""
    return len(shape) == 3 and shape[1] % 8 == 0 and head_dim == _LANES


def _kernel(x_ref, w_ref, *rest, eps: float, chunk: int):
    o_ref = rest[-1]
    tables = rest[:-1]  # (C, S) where the heads turn

    def one(i, carry):
        rows = pl.ds(pl.multiple_of(i * chunk, chunk), chunk)
        x = x_ref[rows, :].astype(jnp.float32)
        y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=1, keepdims=True) + eps) * w_ref[...]
        if tables:
            c_ref, s_ref = tables
            y = y * c_ref[rows, :] + pltpu.roll(y, _LANES // 2, axis=1) * s_ref[rows, :]
        o_ref[rows, :] = y.astype(o_ref.dtype)
        return carry

    jax.lax.fori_loop(0, x_ref.shape[0] // chunk, one, 0)


@functools.partial(jax.jit, static_argnames=("eps", "interpret"))
def head_norm_turn(x, weight, cos=None, sin=None, *, eps: float, interpret: bool | None = None):
    """x [b, s, heads * 128], ``weight`` [128] -> every head normalised over
    its 128 lanes; with ``cos`` and ``sin`` [s, 64] float32 (a slot's angles,
    the same for every call) each head then turned, pairs (i, i + 64);
    ``fits`` must hold.  The jitted function's name is the kernel's name in a
    device trace."""
    b, s, width = x.shape
    rows = block_for(s, _ROWS)
    w = weight.astype(jnp.float32).reshape(1, _LANES)
    tables = []
    if cos is not None:
        tables = [jnp.concatenate([cos, cos], axis=1), jnp.concatenate([-sin, sin], axis=1)]

    def x_index(i, bi, h):
        return bi, i, h

    return pl.pallas_call(
        functools.partial(_kernel, eps=eps, chunk=block_for(rows, _CHUNK)),
        grid=(s // rows, b, width // _LANES),
        in_specs=[
            pl.BlockSpec((None, rows, _LANES), x_index),
            pl.BlockSpec((1, _LANES), lambda i, bi, h: (0, 0)),
            # a block of the tables serves every call and head
            *[pl.BlockSpec((rows, _LANES), lambda i, bi, h: (i, 0)) for _ in tables],
        ],
        out_specs=pl.BlockSpec((None, rows, _LANES), x_index),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        input_output_aliases={0: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")
        ),
        interpret=_interpret() if interpret is None else interpret,
    )(x, w, *tables)
