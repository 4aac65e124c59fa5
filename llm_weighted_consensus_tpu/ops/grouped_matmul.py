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
is one block (2048 and 1536 here), so there is no accumulator to carry.

ONE kernel body, three forms by what it is handed (all under the jitted name
``grouped_expert_product``, which is what a device trace calls them):

  gate-up   ``w_up`` given: ``silu(x @ w[e]) * (x @ w_up[e])``, both products
            over one x block in VMEM, SwiGLU on the float32 accumulators, one
            bf16 write;
  down      ``row_weight`` given: the router's weight of each row on the
            float32 accumulator before the cast (0 for padding rows);
  plain     neither: the product alone (the tests' twin of ``ragged_dot``).

x may come as column chunks and the result may leave as column chunks
(``column_chunks``): the rows are gathered into and out of the padded layout
by XLA, and XLA gathers rows from a table in VMEM at 1.5 ms a layer where it
takes 3.8 from HBM (one DMA descriptor of 33 ns a row).  Its memory
assignment keeps a table of 24-28 MiB in VMEM and not one of 48 or 96.

``route_layout`` makes its tables with three sorts and one-hot sums: a gather
or scatter of SCALARS runs an element at a time on the chip, and the six of
the first version were 3.0 of a layer's 3.9 ms of layout.

``grouped_product_ragged`` is the same product through
``jax.lax.ragged_dot`` over the sorted, unpadded rows: the twin the tests
compare with.  What the chip says (TPU v5e, bf16, 98,304 pairs over 64
experts; my chip runs, PR 27 and PR 28, a layer at a time outside the
server).  One 2048 x 1536 product: this kernel 4.3 ms at tiles of 256 x 512
(144 TFLOP/s), 4.05-4.2 at 256 x 768; ``jax.lax.ragged_dot`` 7.7 ms; jax's
own ``pallas.ops.tpu.megablox.gmm`` at (512, 1024, 768) 4.8 ms.  Gate-up
fused, column blocks of 384 / 512 / 768 / 1536: 8.13 / 7.89 / 7.64 / 7.36 ms
(against 2 x 4.2 unfused plus 1.6 of XLA's SwiGLU pass); down with the row
weights at 512 / 1024 / 2048: 4.66 / 4.27 / 4.03 (plain at 512: 4.62).  So
both serve at the WHOLE width, one column block, which takes
``vmem_limit_bytes`` over the default 16 MiB (two 6.3 MB weight blocks,
double-buffered).  Rows gathered INSIDE the gate-up kernel (scalar-prefetched
token table, one row copy a padded row from ``h`` packed two bf16 to a word,
since Mosaic refuses a one-row slice of a bf16 array in HBM): 11.8 ms issued
in a loop, 10.6 issued in straight-line code between the products, against
11.2 for XLA's gather from HBM plus the fused kernel and 9.1 for XLA's gather
from VMEM chunks plus the fused kernel: the descriptors' issue (28 ns a row)
does not overlap the products, so XLA's gather serves.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TILE = 256  # rows of one tile on the chip: half a tile of padding an expert
VMEM_LIMIT = 64 << 20  # of a v5e core's 128 MiB; the default scope is 16
GATHER_TABLE_BYTES = 32 << 20  # a table XLA's memory assignment keeps in VMEM


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


def _lookup(table, index):
    """``table[index]`` for a table of a few entries, as a one-hot sum: a
    gather or scatter of scalars runs an element at a time on the TPU (7 ns
    each: six of them were 3 ms of a layer's layout, my chip run, PR 28)."""
    hit = index[:, None] == jnp.arange(table.shape[0], dtype=index.dtype)[None, :]
    return jnp.sum(jnp.where(hit, table[None, :], 0), axis=1)


def route_layout_weighted(expert_of_pair, pair_weight, experts: int, tile: int):
    """``route_layout`` and, carried through the same sorts, ``row_weight``
    [M_pad] float32: ``pair_weight`` [M] of the pair a row holds, 0 for
    padding.  Three sorts and sums over one-hot comparisons; no scalar is
    gathered or scattered."""
    m = expert_of_pair.shape[0]
    rows = padded_rows(m, experts, tile)
    int32 = functools.partial(jnp.arange, dtype=jnp.int32)
    counts = jnp.sum(
        expert_of_pair[None, :] == int32(experts)[:, None], axis=1, dtype=jnp.int32
    )
    padded = -(-counts // tile) * tile
    padded_end = jnp.cumsum(padded)
    sorted_expert, order, weight_sorted = jax.lax.sort(
        (expert_of_pair, int32(m), pair_weight.astype(jnp.float32)), num_keys=1
    )
    # a group's rows start where its padded predecessors end
    shift = (padded_end - padded) - (jnp.cumsum(counts) - counts)
    row_sorted = int32(m) + _lookup(shift, sorted_expert)
    _, row_of_pair = jax.lax.sort((order, row_sorted), num_keys=1)
    # the rows no pair fills, in order: each group's tail, then the tiles
    # past the last group (which follow the last group's formula)
    unfilled_end = jnp.cumsum(padded - counts)
    j = int32(rows - m)
    group = jnp.sum(unfilled_end[None, :] <= j[:, None], axis=1, dtype=jnp.int32)
    unfilled = j + _lookup(padded_end - unfilled_end, jnp.minimum(group, experts - 1))
    nobody = jnp.zeros((rows - m,), jnp.int32)
    _, pair_of_row, row_weight = jax.lax.sort(
        (
            jnp.concatenate([row_sorted, unfilled]),
            jnp.concatenate([order, nobody]),
            jnp.concatenate([weight_sorted, nobody.astype(jnp.float32)]),
        ),
        num_keys=1,
    )
    first_rows = int32(rows // tile) * tile
    tile_expert = jnp.minimum(
        jnp.sum(padded_end[None, :] <= first_rows[:, None], axis=1, dtype=jnp.int32),
        experts - 1,
    )
    tiles_used = (padded_end[-1:] // tile).astype(jnp.int32)
    return pair_of_row, row_of_pair, tile_expert, tiles_used, counts, row_weight


def route_layout(expert_of_pair, experts: int, tile: int):
    """``expert_of_pair`` [M] int32, the expert of each (token, choice) pair
    in token-major order.  Returns

      ``pair_of_row`` [M_pad]  the pair a padded row holds (pair 0 for padding),
      ``row_of_pair`` [M]      where each pair's row lies,
      ``tile_expert`` [tiles]  the expert of each row tile,
      ``tiles_used``  [1]      row tiles that hold at least one real row,
      ``counts``      [E]      pairs routed to each expert.
    """
    nothing = jnp.zeros(expert_of_pair.shape, jnp.float32)
    return route_layout_weighted(expert_of_pair, nothing, experts, tile)[:5]


def route_layout_held(expert_of_pair, pair_weight, held: int, tile: int):
    """``route_layout_weighted`` for a layer that holds experts 0..held-1 of
    a wider router: a pair whose expert lies elsewhere gets no tile that is
    ever multiplied.  The pairs elsewhere are one more group behind the held
    ones, past ``tiles_used``, so every table keeps its static size (``M +
    (held + 1) * tile`` rows: every pair may be held here, and none that is
    held is dropped, whatever the routing).  Returns the six tables (``counts``
    [held + 1], the last entry the pairs elsewhere; ``row_of_pair`` of a pair
    elsewhere points at a row no kernel writes: mask it by ``here``) and
    ``here`` [M] bool."""
    here = expert_of_pair < held
    pair_of_row, row_of_pair, tile_expert, _, counts, row_weight = route_layout_weighted(
        jnp.minimum(expert_of_pair, held), pair_weight, held + 1, tile
    )
    tiles_used = jnp.sum(-(-counts[:held] // tile), keepdims=True).astype(jnp.int32)
    tile_expert = jnp.minimum(tile_expert, held - 1)  # a weight block that exists
    return (pair_of_row, row_of_pair, tile_expert, tiles_used, counts, row_weight), here


def _kernel(_, tiles_used_ref, *refs, x_chunks: int, swiglu: bool, weighted: bool):
    """One row tile times its expert's weight block, float32 accumulation;
    the epilogue on the accumulator, before the one cast: ``silu(x @ w) *
    (x @ w_up)`` where a second weight came, ``* row_weight`` where a
    per-row weight did.  x and the output may each come as column chunks."""
    refs = list(refs)
    x_refs = [refs.pop(0) for _ in range(x_chunks)]
    w_ref = refs.pop(0)
    up_ref = refs.pop(0) if swiglu else None
    weight_ref = refs.pop(0) if weighted else None
    o_refs = refs
    dims = (((1,), (0,)), ((), ()))

    @pl.when(pl.program_id(1) < tiles_used_ref[0])
    def _():
        x = jnp.concatenate([ref[...] for ref in x_refs], axis=1)
        acc = jax.lax.dot_general(x, w_ref[...], dims, preferred_element_type=jnp.float32)
        if swiglu:
            up = jax.lax.dot_general(x, up_ref[...], dims, preferred_element_type=jnp.float32)
            acc = acc * jax.nn.sigmoid(acc) * up
        if weighted:
            acc = acc * weight_ref[...]
        width = acc.shape[1] // len(o_refs)
        for c, o_ref in enumerate(o_refs):
            o_ref[...] = acc[:, c * width:(c + 1) * width].astype(o_ref.dtype)


def column_chunks(rows: int, width: int, itemsize: int = 2) -> int:
    """Into how many column chunks a [rows, width] table goes where XLA is to
    gather rows from it: the least power of two that leaves a chunk within
    ``GATHER_TABLE_BYTES`` (and whole lane tiles wide); one where even a
    lane tile's width of it is larger, since a chunk that stays in HBM costs
    a descriptor a row like the whole row does."""
    chunks, size = 1, rows * width * itemsize
    while size > chunks * GATHER_TABLE_BYTES and width % (256 * chunks) == 0:
        chunks *= 2
    return chunks if size <= chunks * GATHER_TABLE_BYTES else 1


@functools.partial(
    jax.jit, static_argnames=("tile", "tile_n", "out_chunks", "interpret")
)
def grouped_expert_product(
    x, w, tile_expert, tiles_used, *, w_up=None, row_weight=None, tile: int,
    tile_n: int | None = None, out_chunks: int | None = None,
    interpret: bool | None = None,
):
    """x [M_pad, K] rows in ``route_layout``'s order (or its column chunks,
    a tuple), w [E, K, N] -> [M_pad, N] (with ``out_chunks`` a tuple of that
    many column chunks, carved from one block of the whole width); row tile
    i is multiplied by ``w[tile_expert[i]]``.  With ``w_up`` [E, K, N] the result is ``silu(x @
    w[e]) * (x @ w_up[e])`` (gate and up in one pass over x); with
    ``row_weight`` [M_pad] float32 each row of the product is scaled by its
    weight.  Both act on the float32 accumulator.  Rows of tiles past
    ``tiles_used`` are left unwritten (nothing reads them).  The jitted
    function's name is the kernel's name in a device trace, whichever form
    runs."""
    xs = tuple(x) if isinstance(x, (tuple, list)) else (x,)
    rows, k = xs[0].shape[0], sum(part.shape[1] for part in xs)
    n = w.shape[2]
    tile_n = tile_n or n
    pieces = out_chunks or 1
    if rows % tile or n % tile_n or tile_n % pieces or (out_chunks and tile_n != n):
        raise ValueError(f"{rows} x {n} is not whole tiles of {tile} x {tile_n}")
    if interpret is None:
        interpret = _interpret()
    weight_spec = pl.BlockSpec((None, k, tile_n), lambda j, i, te, used: (te[i], 0, j))
    by_row = lambda width: pl.BlockSpec((tile, width), lambda j, i, te, used: (i, 0))  # noqa: E731
    operands = [*xs, w]
    in_specs = [*(by_row(part.shape[1]) for part in xs), weight_spec]
    if w_up is not None:
        operands.append(w_up)
        in_specs.append(weight_spec)
    if row_weight is not None:
        operands.append(row_weight.astype(jnp.float32).reshape(rows, 1))
        in_specs.append(by_row(1))
    out = pl.pallas_call(
        functools.partial(
            _kernel, x_chunks=len(xs), swiglu=w_up is not None,
            weighted=row_weight is not None,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n // tile_n, rows // tile),
            in_specs=in_specs,
            out_specs=[
                pl.BlockSpec((tile, tile_n // pieces), lambda j, i, te, used: (i, j))
            ] * pieces,
        ),
        out_shape=[jax.ShapeDtypeStruct((rows, n // pieces), xs[0].dtype)] * pieces,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT,
        ),
        interpret=interpret,
    )(tile_expert, tiles_used, *operands)
    return tuple(out) if out_chunks else out[0]


def grouped_product_ragged(x_sorted, w, counts):
    """The same product over sorted, unpadded rows: x_sorted [M, K] (rows of
    expert 0 first), counts [E] -> [M, N]."""
    return jax.lax.ragged_dot(
        x_sorted, w, counts.astype(jnp.int32),
        preferred_element_type=jnp.float32,
    ).astype(x_sorted.dtype)
