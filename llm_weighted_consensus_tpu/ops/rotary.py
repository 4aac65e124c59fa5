"""A head's rotary lanes turned where the projection left them (Pallas).

A latent-attention head is ``nope`` dims that never turn beside ``rope`` dims
that do (192 | 64 of 256 lanes).  Slicing the heads apart, turning the narrow
part and concatenating them again makes XLA pass over the whole query array
some nine times (it moves the sequence onto the lanes to cut at lane 192, and
back).  Here the array stays [b, s, heads * hd] as the product wrote it, and
the kernel's block is the ONE 128-lane column of a head that holds its rotary
lanes, for a block of rows, aliased in to out: the columns it does not name
are never read or written.

The turn: out = x * C + partner * S in float32, rounded once, where C is cos
over the rotary lanes and 1 elsewhere, S is -sin over the first half of them,
+sin over the second and 0 elsewhere, and partner is the lane half a turn away
(two lane rolls and a select): ``decoder_parts.rope``'s arithmetic, pairs
(i, i + dims / 2), to the last bit.

``fits`` says which shapes the kernel serves: heads of whole 128-lane columns
with the rotary lanes inside one of them, over [b, s, width].  Anything else
(a tiny preset, a decode step's rows) is the caller's to turn the plain way.
On a backend without a TPU the kernel runs in interpret mode, the same code
path.

What the chip says (TPU v5e, bf16, [3, 8192, heads x 256] behind its product,
host clock over 20 calls; my chip runs, PR 38): at 64 heads the product alone
8.53 ms, sliced and concatenated 18.50, turned here 9.97 (1.44 for 0.98 at the
HBM's peak over the one column read and written); at 20 heads 1.05, 3.20,
1.51.  Blocks of 1024 rows cost 0.3 ms more at 64 heads, 4096 and 8192 the
same as 2048; 64 rows at a time in the body 1.0 more, 128 0.3, 256 0.1.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .causal_attention import block_for

_LANES = 128
_ROWS = 2048  # rows of a block: with both tables and two buffers each 6 MiB of VMEM
_CHUNK = 512  # rows the body works at a time


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def fits(shape, heads: int, first: int, dims: int) -> bool:
    """Whether x [b, s, heads * hd], each head's lanes [first, first + dims)
    to turn, is a shape the kernel serves."""
    if len(shape) != 3 or shape[1] % 8 or shape[2] % (heads * _LANES):
        return False
    return first % _LANES + dims <= _LANES


def _kernel(x_ref, c_ref, s_ref, o_ref, *, split: int, half: int, chunk: int):
    lane = jax.lax.broadcasted_iota(jnp.int32, (chunk, _LANES), 1)
    ahead = lane < split  # the first half's partner lies half a turn ahead

    def turn(i, carry):
        rows = pl.ds(pl.multiple_of(i * chunk, chunk), chunk)
        x = x_ref[rows, :].astype(jnp.float32)
        partner = jnp.where(
            ahead, pltpu.roll(x, _LANES - half, axis=1), pltpu.roll(x, half, axis=1)
        )
        o_ref[rows, :] = (x * c_ref[rows, :] + partner * s_ref[rows, :]).astype(o_ref.dtype)
        return carry

    jax.lax.fori_loop(0, x_ref.shape[0] // chunk, turn, 0)


@functools.partial(jax.jit, static_argnames=("heads", "first", "interpret"))
def turn_lanes(x, cos, sin, *, heads: int, first: int, interpret: bool | None = None):
    """x [b, s, heads * hd], cos and sin [s, dims / 2] float32 (a slot's
    angles, the same for every call) -> x with lanes [first, first + dims) of
    every head turned, pairs (i, i + dims / 2); ``fits`` must hold.  The
    jitted function's name is the kernel's name in a device trace."""
    b, s, width = x.shape
    half, off = cos.shape[-1], first % _LANES
    columns, column = width // heads // _LANES, first // _LANES
    rows = block_for(s, _ROWS)
    beside = ((0, 0), (off, _LANES - off - 2 * half))
    c = jnp.pad(jnp.concatenate([cos, cos], axis=1), beside, constant_values=1.0)
    sg = jnp.pad(jnp.concatenate([-sin, sin], axis=1), beside)

    def x_index(i, bi, h):
        return bi, i, h * columns + column

    def table_index(i, bi, h):  # a block of the tables serves every call and head
        return i, 0

    return pl.pallas_call(
        functools.partial(
            _kernel, split=off + half, half=half, chunk=block_for(rows, _CHUNK)
        ),
        grid=(s // rows, b, heads),
        in_specs=[
            pl.BlockSpec((None, rows, _LANES), x_index),
            pl.BlockSpec((rows, _LANES), table_index),
            pl.BlockSpec((rows, _LANES), table_index),
        ],
        out_specs=pl.BlockSpec((None, rows, _LANES), x_index),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        input_output_aliases={0: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")
        ),
        interpret=_interpret() if interpret is None else interpret,
    )(x, c, sg)
