"""Products over the experts held, by the tokens routed to each (Pallas).

A sparse-expert layer sends every token to a few of E experts, so an
expert's product runs over a ragged group of rows whose size is known only
on the device.  Shapes stay static like this: the (token, choice) pairs are
sorted by expert, and each expert's group is padded up to a whole number of
row tiles of ``tile`` rows (``route_layout``).  Every row tile then belongs
to ONE expert, whose index a scalar-prefetched table gives to the weight
block's index map: the kernel is a plain tiled product whose weight operand
is picked per row tile.  ``M + E * tile`` rows bound the padded total,
whatever the routing; tiles past the rows in use do no work.

Grid (N / tile_n, row tiles), the row tiles innermost: consecutive row tiles
of one expert name the same weight block, which is then fetched once per
expert and column block, and the activations stream past it.  The whole of K
is one block (2048 and 1536 here), so there is no accumulator.

``grouped_product_ragged`` is the same product through
``jax.lax.ragged_dot`` over the sorted, unpadded rows: the twin the tests
compare with.  What the chip says (TPU v5e, bf16, 98,304 pairs over 64
experts, one 2048 x 1536 product; my chip run, PR 27, the sort and the gather
of the rows taken off each): this kernel 4.3 ms at tiles of 256 x 512 (144
TFLOP/s), 4.05 ms at 256 x 768 (152 TFLOP/s, 77% of the bf16 peak; 512-row
tiles the same, a whole 1536-wide block does not fit VMEM);
``jax.lax.ragged_dot`` 7.7 ms (80 TFLOP/s); jax's own
``pallas.ops.tpu.megablox.gmm`` at tiling (512, 1024, 768) 4.8 ms (128
TFLOP/s).  So the kernel here serves, at 256 x 768.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TILE = 256  # rows of one tile on the chip: half a tile of padding an expert


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def tile_for(pairs: int, experts: int) -> int:
    """Rows of a tile for ``pairs`` rows over ``experts`` groups: ``TILE``
    where a group sees that many rows, else the power of two under a
    group's mean, down to 16 (a bf16 tile's sublanes; a decode step)."""
    mean = max(pairs // experts, 1)
    return min(TILE, max(16, 1 << (mean.bit_length() - 1)))


def padded_rows(pairs: int, experts: int, tile: int) -> int:
    """Static bound on the padded row count: every group rounded up."""
    return (-(-pairs // tile) + experts) * tile


def route_layout(expert_of_pair, experts: int, tile: int):
    """``expert_of_pair`` [M] int32, the expert of each (token, choice) pair
    in token-major order.  Returns

      ``pair_of_row`` [M_pad]  the pair a padded row holds (pair 0 for padding),
      ``row_of_pair`` [M]      where each pair's row lies,
      ``tile_expert`` [tiles]  the expert of each row tile,
      ``tiles_used``  [1]      row tiles that hold at least one real row,
      ``counts``      [E]      pairs routed to each expert.
    """
    m = expert_of_pair.shape[0]
    rows = padded_rows(m, experts, tile)
    counts = jnp.zeros((experts,), jnp.int32).at[expert_of_pair].add(1)
    padded = -(-counts // tile) * tile
    padded_end = jnp.cumsum(padded)
    padded_start = padded_end - padded
    start = jnp.cumsum(counts) - counts
    order = jnp.argsort(expert_of_pair, stable=True)
    sorted_expert = expert_of_pair[order]
    rank = jnp.arange(m, dtype=jnp.int32) - start[sorted_expert]
    row_sorted = padded_start[sorted_expert] + rank
    row_of_pair = jnp.zeros((m,), jnp.int32).at[order].set(row_sorted)
    pair_of_row = jnp.zeros((rows,), jnp.int32).at[row_sorted].set(order)
    first_rows = jnp.arange(rows // tile, dtype=jnp.int32) * tile
    tile_expert = jnp.minimum(
        jnp.searchsorted(padded_end, first_rows, side="right"), experts - 1
    ).astype(jnp.int32)
    tiles_used = (padded_end[-1:] // tile).astype(jnp.int32)
    return pair_of_row, row_of_pair, tile_expert, tiles_used, counts


def _kernel(tile_expert_ref, tiles_used_ref, x_ref, w_ref, o_ref):
    del tile_expert_ref

    @pl.when(pl.program_id(1) < tiles_used_ref[0])
    def _():
        o_ref[...] = jax.lax.dot_general(
            x_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tile", "tile_n", "interpret"))
def grouped_expert_product(
    x, w, tile_expert, tiles_used, *, tile: int, tile_n: int = 768,
    interpret: bool | None = None,
):
    """x [M_pad, K] rows in ``route_layout``'s order, w [E, K, N] ->
    [M_pad, N]; row tile i is multiplied by ``w[tile_expert[i]]``.  Rows of
    tiles past ``tiles_used`` are left unwritten (nothing reads them).  The
    jitted function's name is the kernel's name in a device trace."""
    rows, k = x.shape
    n = w.shape[2]
    tile_n = next(t for t in (tile_n, 512, 256, 128, n) if t <= n and n % t == 0)
    if rows % tile:
        raise ValueError(f"{rows} x {n} is not whole tiles of {tile} x {tile_n}")
    if interpret is None:
        interpret = _interpret()
    return pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n // tile_n, rows // tile),
            in_specs=[
                pl.BlockSpec((tile, k), lambda j, i, te, used: (i, 0)),
                pl.BlockSpec((None, k, tile_n), lambda j, i, te, used: (te[i], 0, j)),
            ],
            out_specs=pl.BlockSpec((tile, tile_n), lambda j, i, te, used: (i, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((rows, n), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(tile_expert, tiles_used, x, w)


def grouped_product_ragged(x_sorted, w, counts):
    """The same product over sorted, unpadded rows: x_sorted [M, K] (rows of
    expert 0 first), counts [E] -> [M, N]."""
    return jax.lax.ragged_dot(
        x_sorted, w, counts.astype(jnp.int32),
        preferred_element_type=jnp.float32,
    ).astype(x_sorted.dtype)
