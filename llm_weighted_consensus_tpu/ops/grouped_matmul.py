"""Products over the experts held, by the tokens routed to each (Pallas).

A sparse-expert layer sends every token to a few of E experts, so an
expert's product runs over a ragged group of rows whose size is known only
on the device.  Shapes stay static like this: the (token, choice) pairs are
sorted by expert, and each expert's group is padded up to a whole number of
row tiles of ``tile`` rows (``route_layout``).  Every row tile then belongs
to ONE expert, whose index a scalar-prefetched table gives to the weight
block's index map: the kernel is a plain tiled product whose weight operand
is picked per row tile.  ``M + E * tile`` rows bound the padded total,
whatever the routing.  A tile past the rows in use runs no product (its step's
body stands under ``pl.when``), and since PR 43 its step moves no block
either: every block indexed by the row tile names ``_row_block``'s, the last
tile in use again on every step past it, and a step that names the block the
step before it named fetches nothing and writes nothing back.  Until then such
a step fetched its x block and wrote its (never written) out block back, and
with no product to hide behind that traffic was the step's whole time (see
"The tiles no pair fills" below).

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

The way back for a SHARE of a wider router's experts (``held_rows_sum``; my
chip runs, PR 32, a layer alone at the second judge's cell: 24,576 tokens, 10
choices, 128 experts held of 512, 2048 wide, 61,461 of 245,760 pairs held,
the down product over 114,688 rows).  The gathers above fetch k rows a token
from every column chunk and mask three in four: 9.1 ms a layer inside the
program (the trace, PR 31; alone, where XLA leaves the sixteen tables in HBM,
51 ms).  The walk fetches one row a pair held.  Mosaic refuses a one-row slice
of a tiled table in HBM whatever the word (bf16, and uint32 too: "must be
aligned to tiling (8)"; (4) for a bf16 table read as words), so the down
kernel leaves y a row a SLAB, [rows x 8, 128] words, one (8, 128) tile of 4 KB
a row (a bf16 row two columns a word, c and c + 1024), by eight
sublane-strided stores a row tile: 1.79 ms against 1.80 for the sixteen column
chunks, the epilogue is free.  The forms of the walk, each bit for bit the
gathers' sums: a scalar test a routed pair inside the kernel 5.42 ms (5.02
with NO pair held: 20 ns a test and its branch; a copy itself 6.6 ns; steps of
64, 128 or 256 tokens the same); each step's held pairs sorted to the front of
a list of codes before the kernel, one copy a trip of the scalar loops 2.78 ms
(31 ns a held pair; the table built from [t, k] arrays, whose reshapes to
[steps, 1, 1280] XLA does as relayouts, 0.9 ms, and whose sort in that layout
takes 0.42); 8 copies a trip 15 ns a pair, 16 the same; the table built from
the layout's FLAT row_of_pair and sorted as [steps, 1280] (0.13 ms) **1.82 ms**,
which serves: the kernel 1.64 (0.69 with no pair held: zeroing, sums, the
turn back to rows), the sort 0.13.  A spare slot for a list's filler copies
made each half of the buffer 1281 slots and is not needed: a list's last trip
repeats its first copy.

A width that is no whole tile of words (``row_slabs``; my chip run, PR 40, a
sparse layer of the fourth judge's cell alone: 24,576 tokens, 8 choices, 16
experts held of 256, 5120 wide, 12,337 of 196,608 pairs held, the down product
over 49,152 rows).  A bf16 row of 5120 columns is 2560 words, 20 sublanes: two
and a half (8, 128) tiles, and Mosaic copies whole tiles only.  The slab is
PADDED: a row's stride in the table is 24 sublanes, of which the down kernel
writes 20 (twenty stores at a stride of 24) and leaves four holding whatever
was there; ``held_rows_sum`` copies all 24 a held pair, the unit Mosaic allows,
lets the pad ride the float32 sums in the third vreg of the real sublanes (a
pad word meets pad words only) and turns 20 back into a row.  12 KB copied for
10 of data.  The forms, each bit for bit the gathers' sums: the eight masked
gathers alone 20.22 ms (ONE table of 503 MB in HBM: no chunk of it fits VMEM),
21.98 with the down product in front; the padded slab 2.95 ms for the down
kernel (2.71 with one column chunk out: +0.24) and 2.61 for the walk (2.34
with no pair held: at one pair in sixteen held the zeroing and the sums are
the walk), 4.81 together; the down product 6144 wide (1024 columns of zeros
behind ``w_down``), the unpadded slab of 24 walked and the sum cut back to
5120 columns 5.85.  The padded slab serves.  A row under one whole tile of
words (the tiny presets) keeps the gathers: its slab would be mostly padding.

The tiles no pair fills (my chip run, PR 43, each kernel alone at a share's
shapes, min of 7, parent and change in one call:
``scripts/time_expert_tiles.py``).  The fifth judge's sparse layer: a layout of
98,304 rows = 384 row tiles as ``decoder_parts.usual_rows`` sizes it, four
times the even load, of which the seeded router fills 112; 32 experts held,
3072 x 3072; gate-up in four column blocks of 768, the down product whole, a
row a slab of 16 sublanes.  With every step naming its own row tile's blocks:
gate-up 10.09 ms over the layout as laid against 6.84 over the layout CUT to
its 112 tiles, the down product 5.32 against 3.82: the 272 tiles that hold
nothing cost 3.25 + 1.50 ms a layer, 7.9 + 3.7 MB moved a tile (the x block of
1.57 MB once a column block and four out blocks of 0.39; 1.57 in and a slab
block of 2.10 out) at 660 GB/s.  With ``_row_block``: 6.91 against 6.78 and
3.86 against 3.88, 0.14 + 0.0 ms: the empty steps themselves, 0.13 us each.
The third judge's (192 tiles, 60 in use, 16 held, 6144 x 2048, column blocks
of 512, a slab of 24): 2.46 + 0.94 ms before, 0.03 + 0.04 after.  With NO tile
in use the kernels still take 2.30 and 1.55 ms (6.63 and 3.65 before): a step
fetches its weight block whether or not it multiplies, 1.2 and 0.6 GB of them
a kernel, which the products hide where there are products.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TILE = 256  # rows of one tile on the chip: half a tile of padding an expert
VMEM_LIMIT = 64 << 20  # of a v5e core's 128 MiB; the default scope is 16
WEIGHT_WINDOW_BYTES = 32 << 20  # the weight blocks of a step, double-buffered
GATHER_TABLE_BYTES = 32 << 20  # a table XLA's memory assignment keeps in VMEM
LANES = 128  # words of one sublane
WALK_TOKENS = 128  # tokens of one step of ``held_rows_sum``
WALK_UNROLL = 8  # copies issued (and awaited) a trip of its scalar loops


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


def _bf16_bits(x):
    """x float32 -> the bits of its bfloat16 rounding, in a word's upper half."""
    rounded = x.astype(jnp.bfloat16).astype(jnp.float32)
    return jax.lax.bitcast_convert_type(rounded, jnp.uint32)


def _kernel(
    _, tiles_used_ref, *refs, x_chunks: int, swiglu: bool, weighted: bool, slabs: bool
):
    """One row tile times its expert's weight block, float32 accumulation;
    the epilogue on the accumulator, before the one cast: ``silu(x @ w) *
    (x @ w_up)`` where a second weight came, ``* row_weight`` where a
    per-row weight did.  x and the output may each come as column chunks;
    with ``slabs`` the output is laid a row a slab (``row_slabs``)."""
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
        if slabs:
            (o_ref,) = o_refs
            if o_ref.dtype == jnp.uint32:  # bf16: columns c and c + n / 2 share a word
                half = acc.shape[1] // 2
                acc = (_bf16_bits(acc[:, :half]) >> 16) | _bf16_bits(acc[:, half:])
            # a row's c-th lane tile to sublane c of its slab; a slab's pad
            # (its stride past the sublanes a row fills) is never written
            stride = o_ref.shape[0] // acc.shape[0]
            for c in range(acc.shape[1] // LANES):
                o_ref[pl.ds(c, acc.shape[0], stride=stride), :] = acc[
                    :, c * LANES:(c + 1) * LANES
                ]
            return
        width = acc.shape[1] // len(o_refs)
        for c, o_ref in enumerate(o_refs):
            o_ref[...] = acc[:, c * width:(c + 1) * width].astype(o_ref.dtype)


def _row_block(i, tiles_used):
    """The block of rows that row tile ``i``'s step names: its own up to the
    last tile in use, and that one again on every step past it (block 0
    where no tile is in use).  A step that names the block the step before it
    named fetches nothing and writes nothing back, which is how the weight
    block has been fetched once an expert since PR 27; its body runs no
    product either, so the last tile in use stays in VMEM through the steps
    past it and goes back once, as it was."""
    return jnp.minimum(i, jnp.maximum(tiles_used[0] - 1, 0))


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


def row_slabs(width: int, dtype) -> tuple[int, int]:
    """(slab, filled) in sublanes, for one row of a [rows, width] table laid
    a row a slab, [rows * slab, LANES] words (a bfloat16 row two columns a
    word, c and c + width / 2).  ``filled`` is what the row's words take,
    ``words / LANES``; ``slab`` is the row's stride in the table, ``filled``
    padded up to whole (8, 128) tiles, which is what Mosaic lets a kernel copy
    out of HBM by a row's index (a one-row slice of a tiled table it refuses,
    whatever the word).  8 and 8 at 2048 x bfloat16, 24 and 24 at 6144, 24 and
    20 at 5120: a padded slab's last sublanes are never written and never
    read past the copy.  What the pad costs on the chip (PR 40, the fourth
    judge's program): 12 KB copied a held pair for 10 of data,
    ``held_rows_sum`` 1.86 ms a layer at 5120 where the unpadded 24 of 6144
    take 1.9, the down kernel's 20 stores at a stride of 24 +0.19 ms a layer
    over its column chunk.  (0, 0) where the row is not whole sublanes of
    words or is under one whole tile of them (the tiny presets: a slab mostly
    padding, and the tier-1 suite's CPU time), or the dtype has no
    packing here: such a table is not walked."""
    row_bytes = width * jnp.dtype(dtype).itemsize
    if dtype not in (jnp.bfloat16, jnp.float32) or row_bytes % (4 * LANES):
        return 0, 0
    filled = row_bytes // (4 * LANES)
    return (-(-filled // 8) * 8, filled) if filled >= 8 else (0, 0)


@functools.partial(
    jax.jit, static_argnames=("tile", "tile_n", "out_chunks", "slabs", "interpret")
)
def grouped_expert_product(
    x, w, tile_expert, tiles_used, *, w_up=None, row_weight=None, tile: int,
    tile_n: int | None = None, out_chunks: int | None = None, slabs: bool = False,
    interpret: bool | None = None,
):
    """x [M_pad, K] rows in ``route_layout``'s order (or its column chunks,
    a tuple), w [E, K, N] -> [M_pad, N] (with ``out_chunks`` a tuple of that
    many column chunks, carved from one block of the whole width); row tile
    i is multiplied by ``w[tile_expert[i]]``.  With ``w_up`` [E, K, N] the result is ``silu(x @
    w[e]) * (x @ w_up[e])`` (gate and up in one pass over x); with
    ``row_weight`` [M_pad] float32 each row of the product is scaled by its
    weight.  Both act on the float32 accumulator.  With ``slabs`` the result
    leaves laid a row a slab, [M_pad * slab, LANES] words (``row_slabs``), the
    same roundings in another place, a slab's pad left unwritten
    (``held_rows_sum`` reads it).  Rows of tiles
    past ``tiles_used`` are left unwritten (nothing reads them).  The jitted
    function's name is the kernel's name in a device trace, whichever form
    runs."""
    xs = tuple(x) if isinstance(x, (tuple, list)) else (x,)
    rows, k = xs[0].shape[0], sum(part.shape[1] for part in xs)
    n = w.shape[2]
    # the whole width is one column block where its weight windows fit
    # (2048 x 1536 twice, double-buffered: 25 MB); a wider expert's (6144 x
    # 2048) go in halves until they do, and a slab, which needs its row
    # whole, takes the room over ``VMEM_LIMIT`` instead
    windows = 2 * (2 if w_up is not None else 1) * k * n * w.dtype.itemsize
    vmem_limit = VMEM_LIMIT
    if tile_n is None and not (slabs or out_chunks):
        tile_n = n
        while windows > WEIGHT_WINDOW_BYTES and tile_n % (2 * LANES) == 0:
            tile_n, windows = tile_n // 2, windows // 2
    elif windows > WEIGHT_WINDOW_BYTES and tile_n in (None, n):
        vmem_limit += windows - WEIGHT_WINDOW_BYTES
    tile_n = tile_n or n
    pieces = out_chunks or 1
    if rows % tile or n % tile_n or tile_n % pieces or (out_chunks and tile_n != n):
        raise ValueError(f"{rows} x {n} is not whole tiles of {tile} x {tile_n}")
    dtype = xs[0].dtype
    if slabs:
        slab, _ = row_slabs(n, dtype)
        if not slab or out_chunks or tile_n != n:
            raise ValueError(f"a row of {n} x {dtype} is not laid a row a slab")
        out_specs = [
            pl.BlockSpec((tile * slab, LANES), lambda j, i, te, used: (_row_block(i, used), 0))
        ]
        words = jnp.uint32 if dtype == jnp.bfloat16 else dtype
        out_shape = [jax.ShapeDtypeStruct((rows * slab, LANES), words)]
    else:
        out_specs = [
            pl.BlockSpec(
                (tile, tile_n // pieces), lambda j, i, te, used: (_row_block(i, used), j)
            )
        ] * pieces
        out_shape = [jax.ShapeDtypeStruct((rows, n // pieces), dtype)] * pieces
    if interpret is None:
        interpret = _interpret()
    weight_spec = pl.BlockSpec((None, k, tile_n), lambda j, i, te, used: (te[i], 0, j))
    by_row = lambda width: pl.BlockSpec(  # noqa: E731
        (tile, width), lambda j, i, te, used: (_row_block(i, used), 0)
    )
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
            weighted=row_weight is not None, slabs=slabs,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n // tile_n, rows // tile),
            in_specs=in_specs,
            out_specs=out_specs,
        ),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=vmem_limit,
        ),
        interpret=interpret,
    )(tile_expert, tiles_used, *operands)
    return tuple(out) if out_chunks else out[0]


def _walk_kernel(
    trips_ref, codes_ref, ahead_ref, y_ref, o_ref, buf, sems, *sums,
    k: int, slab: int, filled: int, bits: int,
):
    """One step sums the rows of ``tokens`` tokens.  Its held pairs come as a
    compacted list of codes (row << bits | slot), so the scalar core spends
    nothing on a pair elsewhere: one copy a code out of HBM into the slot of
    its (choice, token), issued a step AHEAD into the other half of ``buf``
    (zeroed first: a pair elsewhere adds the 0.0 it added masked); then the k
    slots of a token added in float32, choice 0 first, and a token's slab
    turned back into a row."""
    step, steps = pl.program_id(0), pl.num_programs(0)
    tokens = o_ref.shape[0]
    slot_rows = tokens * slab

    def copy(row, slot, half):
        return pltpu.make_async_copy(
            y_ref.at[pl.ds(pl.multiple_of(row * slab, slab), slab), :],
            buf.at[half, pl.ds(pl.multiple_of(slot * slab, slab), slab), :],
            sems.at[half],
        )

    def fetch(codes, of_step, half):
        buf[half] = jnp.zeros(buf.shape[1:], buf.dtype)

        def issue(trip, carry):  # several a trip: the table's reads overlap
            for u in range(WALK_UNROLL):
                code = codes[0, trip * WALK_UNROLL + u]
                copy(code >> bits, code & ((1 << bits) - 1), half).start()
            return carry

        jax.lax.fori_loop(0, trips_ref[of_step], issue, 0)

    @pl.when(step == 0)
    def _():
        fetch(codes_ref, 0, 0)

    @pl.when(step + 1 < steps)
    def _():
        fetch(ahead_ref, step + 1, (step + 1) % 2)

    half = step % 2

    def landed(_, carry):  # every copy is one slab: any of them counts one down
        for _ in range(WALK_UNROLL):
            copy(0, 0, half).wait()
        return carry

    jax.lax.fori_loop(0, trips_ref[step], landed, 0)
    packed = buf.dtype == jnp.uint32
    low = high = jnp.zeros((slot_rows, LANES), jnp.float32)
    for j in range(k):
        slot = buf[half, j * slot_rows:(j + 1) * slot_rows, :]
        if packed:  # a bfloat16 is the upper half of its float32
            low = low + jax.lax.bitcast_convert_type(slot << 16, jnp.float32)
            high = high + jax.lax.bitcast_convert_type(
                slot & jnp.uint32(0xFFFF0000), jnp.float32
            )
        else:
            low = low + slot
    for part, (ref, value) in enumerate(zip(sums, (low, high))):
        ref[...] = value
        for c in range(filled):  # sublane c of every token's slab: a lane tile of rows
            at = (part * filled + c) * LANES
            o_ref[:, at:at + LANES] = ref[pl.ds(c, tokens, stride=slab), :].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("k", "width", "interpret"))
def held_rows_sum(y, row_of_pair, *, k: int, width: int, interpret: bool | None = None):
    """y [M_pad * slab, LANES] words, the down product laid a row a slab
    (``grouped_expert_product(slabs=True)``); row_of_pair [t * k] int32, the
    row of each (token, choice) pair in token-major order, negative where the
    pair's expert is not held here -> [t, width]: each token's held rows
    summed in float32 in the order of its choices, bit for bit what k masked
    gathers and sums give.  ONE row is fetched a held pair, by a copy out of
    HBM; the pairs elsewhere are sorted out of each step's list before the
    kernel, which never meets them.  A kernel of its own name in a device
    trace."""
    t = row_of_pair.shape[0] // k
    dtype = jnp.bfloat16 if y.dtype == jnp.uint32 else y.dtype
    slab, filled = row_slabs(width, dtype)
    tokens = WALK_TOKENS if t >= WALK_TOKENS else -(-t // 8) * 8
    steps, pairs = -(-t // tokens), tokens * k
    bits = (pairs - 1).bit_length()
    if (y.shape[0] // slab) << bits >= 1 << 31:
        raise ValueError(f"{y.shape[0] // slab} rows and {pairs} slots pass one int32")
    rows = jnp.pad(row_of_pair, (0, steps * pairs - t * k), constant_values=-1)
    rows = rows.reshape(steps, pairs)
    # a step's slots lie choice-major, so that a choice's rows are one block
    pair = jnp.arange(pairs, dtype=jnp.int32)
    slot = (pair % k) * tokens + pair // k
    nobody = jnp.iinfo(jnp.int32).max
    codes = jnp.sort(jnp.where(rows >= 0, (rows << bits) | slot, nobody), axis=1)
    # the scalar loops go WALK_UNROLL copies a trip: a list's last trip is
    # filled up with its first copy again (the same row into the same slot)
    codes = jnp.where(codes == nobody, codes[:, :1], codes).reshape(steps, 1, pairs)
    trips = -(-jnp.sum(rows >= 0, axis=1, dtype=jnp.int32) // WALK_UNROLL)
    by_step = lambda ahead: pl.BlockSpec(  # noqa: E731
        (None, 1, pairs), lambda i, trips: (jnp.minimum(i + ahead, steps - 1), 0, 0),
        memory_space=pltpu.SMEM,
    )
    out = pl.pallas_call(
        functools.partial(_walk_kernel, k=k, slab=slab, filled=filled, bits=bits),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(steps,),
            in_specs=[by_step(0), by_step(1), pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((tokens, width), lambda i, trips: (i, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, k * tokens * slab, LANES), y.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                # the sums: a bfloat16 word's two columns apart
                *[pltpu.VMEM((tokens * slab, LANES), jnp.float32)] * (width // (filled * LANES)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((steps * tokens, width), dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=VMEM_LIMIT
        ),
        interpret=_interpret() if interpret is None else interpret,
    )(trips, codes, codes, y)
    return out[:t]


def grouped_product_ragged(x_sorted, w, counts):
    """The same product over sorted, unpadded rows: x_sorted [M, K] (rows of
    expert 0 first), counts [E] -> [M, N]."""
    return jax.lax.ragged_dot(
        x_sorted, w, counts.astype(jnp.int32),
        preferred_element_type=jnp.float32,
    ).astype(x_sorted.dtype)
