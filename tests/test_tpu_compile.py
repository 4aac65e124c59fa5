"""The fused attention kernel compiled for a described TPU v5e, no chip
attached: what interpret mode cannot show (Mosaic's own refusals, the
VMEM the served blocks take) and what the benchmark reads (the kernel's
name, and that no relayout copy stands around it).

Nothing runs, so nothing here is a time.  Keep every such compile in this
one file: only the worker that is handed it loads the TPU's library.
"""

import functools
import os
import re
from dataclasses import replace

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp

from llm_weighted_consensus_tpu.models import bert, configs
from llm_weighted_consensus_tpu.ops import attention


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compiled_kernels(monkeypatch):
    # off a TPU the kernel would take its interpret branch; the compiler
    # under test is the chip's
    monkeypatch.setattr(attention, "_interpret", lambda: False)


def instructions(hlo_text):
    """(name, opcode, rest of the line) of every instruction of an
    optimized HLO module."""
    return re.findall(
        r"^\s*(?:ROOT )?%?([\w.\-]+) = \S+ ([\w\-]+)\((.*)$", hlo_text, re.M
    )


def mosaic_kernels(found):
    """Names of the Mosaic kernels among ``instructions``: the compiler's
    own custom calls (a weight's prefetch into fast memory) are not the
    program's."""
    return [
        name.split(".")[0]
        for name, op, rest in found
        if op == "custom-call" and '"tpu_custom_call"' in rest
    ]


@pytest.mark.parametrize("b", [64, 512], ids=["solo", "group-of-8"])
def test_bge_large_attention_block_compiles_without_copies(
    one_chip, compiled_kernels, b
):
    """bert._attention at the benchmark's bucket (bge-large, 512 tokens,
    one request and a full group), bf16: Mosaic takes the served block,
    the kernel keeps the name the benchmark reads, and between the
    projections, the kernel and attn_out the compiler places no copy and
    no transpose."""
    cfg = replace(configs.BGE_LARGE, attention_impl="fused")
    s, h = 512, cfg.hidden_size
    dt = jnp.bfloat16

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    layer = jax.tree_util.tree_map(
        lambda a: arg(a.shape[1:], dt),
        jax.eval_shape(
            lambda: bert.init_params(jax.random.PRNGKey(0), cfg, dtype=dt)
        )["layers"],
    )
    x = arg((b, s, h), dt)
    bias = arg((b, 1, 1, s), jnp.float32)
    compiled = (
        jax.jit(lambda x, p, bias: bert._attention(x, p, bias, cfg))
        .lower(x, layer, bias)
        .compile()
    )
    found = instructions(compiled.as_text())
    names = [name for name, op, _ in found]
    assert mosaic_kernels(found) == ["fused_attention_tiled"], names
    # "copy-start"/"copy-done" are prefetches, not relayouts
    assert not [n for n, op, _ in found if op in ("copy", "transpose")], names


@pytest.mark.parametrize(
    "s, nh, hd, rows",
    [
        (512, 12, 32, 4),  # bge-small: four heads a tile
        (512, 8, 128, 4),  # a head a tile: no idle lane for the sum
        (1024, 16, 64, 1),  # the longest bucket: one row a step within VMEM
        (1024, 8, 128, 2),
    ],
    ids=["hd32", "hd128", "hd64-s1024", "hd128-s1024"],
)
def test_attention_kernel_compiles_at_every_head_width(
    one_chip, compiled_kernels, s, nh, hd, rows
):
    """What the interpreter cannot refuse: the lane turn and the selects of
    the form that takes the row's sum from the second product's idle lanes
    (heads of 32 and 64), the plain form beside it (heads of 128), each at
    the rows a step ``best_heads_per_step`` reckons to fit."""
    kk = attention.best_heads_per_step(64, s, nh, hd, 2)
    assert kk == rows * attention.heads_per_block(nh, hd)
    x = jax.ShapeDtypeStruct((64, s, nh * hd), jnp.bfloat16, sharding=one_chip)
    bias = jax.ShapeDtypeStruct((64, s), jnp.float32, sharding=one_chip)
    kernel = functools.partial(
        attention.fused_attention_tiled,
        scale=hd**-0.5, nh=nh, heads_per_step=kk,
    )
    compiled = jax.jit(kernel).lower(x, x, x, bias).compile()
    found = instructions(compiled.as_text())
    assert mosaic_kernels(found) == ["fused_attention_tiled"], found


# -- the judge's kernels at the configuration's widths (ISSUE 27) -------------


def test_causal_attention_kernel_compiles_at_the_judge_s_shape(one_chip):
    """A panel's attention: 3 calls x 8192 tokens, 20 heads of 256, bf16,
    blocks of 2048 x 2048 in stripes of 256 (what ``block_for`` and
    ``stripe_for`` pick there).  The jit holds the kernel under the name the
    benchmark reads and nothing else: no pass of attention stands outside
    the kernel's own events."""
    from llm_weighted_consensus_tpu.ops import causal_attention as ca

    assert ca.block_for(8192) == 2048 and ca.stripe_for(2048, 2048) == 256
    x = jax.ShapeDtypeStruct((3, 8192, 20 * 256), jnp.bfloat16, sharding=one_chip)
    compiled = jax.jit(
        lambda q, k, v: ca.causal_attention_blockwise(
            q, k, v, heads=20, scale=1 / 16, interpret=False
        )
    ).lower(x, x, x).compile()
    found = instructions(compiled.as_text())
    calls = [name for name, op, _ in found if op == "custom-call"]
    assert len(calls) == 1 and calls[0].startswith("causal_attention_blockwise"), calls
    beside = [(name, op) for name, op, _ in found if op in ("copy", "transpose", "fusion")]
    assert not beside, beside


@pytest.mark.parametrize(
    "pairs,fused",
    [(3 * 8192 * 4, "gate-up"), (3 * 8192 * 4, "down"), (12, "gate-up"), (12, "down")],
    ids=["prefill-gate-up", "prefill-down", "decode", "decode-down"],
)
def test_grouped_expert_product_compiles_at_the_judge_s_shapes(one_chip, pairs, fused):
    """64 experts; a prefill's 98,304 pairs in tiles of 256 rows, a decode
    step's 12 in tiles of 16; the kernels as served: gate and up fused with
    SwiGLU (K 2048, N 1536), down with the rows' weights (K 1536, N 2048)."""
    from llm_weighted_consensus_tpu.ops import grouped_matmul as gm

    tile = gm.tile_for(pairs, 64)
    assert tile == (gm.TILE if pairs > 1000 else 16)
    rows = gm.padded_rows(pairs, 64, tile)
    k, n = (2048, 1536) if fused == "gate-up" else (1536, 2048)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def served(x, w, extra, te, used):
        epilogue = {"w_up": extra} if fused == "gate-up" else {"row_weight": extra}
        return gm.grouped_expert_product(
            x, w, te, used, tile=tile, interpret=False, **epilogue
        )

    weights = arg((64, k, n), jnp.bfloat16)
    compiled = jax.jit(served).lower(
        arg((rows, k), jnp.bfloat16), weights,
        weights if fused == "gate-up" else arg((rows,), jnp.float32),
        arg((rows // tile,), jnp.int32), arg((1,), jnp.int32),
    ).compile()
    names = [name for name, op, _ in instructions(compiled.as_text()) if op == "custom-call"]
    assert any(name.startswith("grouped_expert_product") for name in names), names


# -- the second judge's kernels at its configuration's widths (ISSUE 31) -------


@pytest.mark.parametrize("heads_per_step", [None, 8], ids=["as-served", "eight-heads"])
def test_gated_delta_kernel_compiles_at_the_judge_s_shape(one_chip, heads_per_step):
    """A linear layer's rule over a panel: 3 calls x 8192 positions, 32 value
    heads on 16 key heads of 128, bf16, chunks of 128, as ``gated_delta_rule``
    lays them out, the head's normalisation on as the judge calls it, at the
    heads a step the judge runs and at eight (what the kernel's body is
    written for, PERF.md section 5).  The jit holds ONE kernel under the name
    the benchmark reads and nothing else."""
    from llm_weighted_consensus_tpu.ops import gated_delta as gd

    b, s, hk, hv, d = 3, 8192, 16, 32, 128
    heads = gd._heads_a_step(hv, hv // hk, heads_per_step or gd.HEADS_PER_STEP)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    rows = arg((b, s // gd.CHUNK, hv // heads, heads, gd.CHUNK), jnp.float32)
    compiled = jax.jit(
        lambda q, k, v, g, beta: gd.gated_delta_chunked(
            q, k, v, g, beta, key_heads=hk, norm_eps=1e-6, interpret=False
        )
    ).lower(
        arg((b, s, hk * d), jnp.bfloat16), arg((b, s, hk * d), jnp.bfloat16),
        arg((b, s, hv * d), jnp.bfloat16), rows, rows,
    ).compile()
    # the kernel returns two arrays: its line's type is a tuple, which
    # ``instructions`` does not read
    text = compiled.as_text()
    calls = re.findall(r"%([\w.]+) = \([^=]*\) custom-call\(", text)
    assert len(calls) == 1 and calls[0].startswith("gated_delta_chunked"), calls
    assert not re.findall(r" (?:copy|transpose|fusion)\(", text)


def test_causal_attention_kernel_compiles_with_16_query_heads_on_2_key_heads(one_chip):
    """A full-attention layer of the second judge: a key head's block found
    by ``head // 8``, nothing repeated in memory."""
    from llm_weighted_consensus_tpu.ops import causal_attention as ca

    q = jax.ShapeDtypeStruct((3, 8192, 16 * 256), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((3, 8192, 2 * 256), jnp.bfloat16, sharding=one_chip)
    compiled = jax.jit(
        lambda q, k, v: ca.causal_attention_blockwise(
            q, k, v, heads=16, kv_heads=2, scale=1 / 16, interpret=False
        )
    ).lower(q, kv, kv).compile()
    found = instructions(compiled.as_text())
    calls = [name for name, op, _ in found if op == "custom-call"]
    assert len(calls) == 1 and calls[0].startswith("causal_attention_blockwise"), calls
    assert not [(n, op) for n, op, _ in found if op in ("copy", "transpose", "fusion")]


# (tokens, choices, the router's width, experts held, hidden, an expert's width)
SECOND_JUDGE_S_SHARE = (3 * 8192, 10, 512, 128, 2048, 512)
FIFTH_JUDGE_S_SHARE = (3 * 16384, 4, 256, 32, 3072, 3072)


@pytest.mark.parametrize("fused", ["gate-up", "down"])
@pytest.mark.parametrize(
    "share,rows", [(SECOND_JUDGE_S_SHARE, None), (FIFTH_JUDGE_S_SHARE, 98_304)],
    ids=["second-judge-whole-bound", "fifth-judge-usual-load"],
)
def test_grouped_expert_product_compiles_at_the_held_share_s_shapes(one_chip, fused, share, rows):
    """128 experts held of a router 512 wide, 10 a token: the static bound is
    every pair of a panel (245,760) and one more group, tiles of 256; gate and
    up fused (K 2048, N 512), down with the rows' weights (K 512, N 2048) in
    ONE column chunk (the table is too large for chunks that stay in VMEM).
    And the fifth judge's (ISSUE 43: the size its claim rests on): 32 held of
    256, 4 a token of 49,152, square experts of 3072 x 3072 in column blocks
    of 768 (gate-up) and whole (down), over the usual load's 384 row tiles of
    which about 112 hold a pair: every block indexed by the row tile names
    ``_row_block``'s, a scalar read from the prefetched ``tiles_used`` inside
    the index map, which Mosaic lowers at these sizes."""
    from llm_weighted_consensus_tpu.models import decoder_parts
    from llm_weighted_consensus_tpu.ops import grouped_matmul as gm

    tokens, choices, router, held, hidden, width = share
    pairs = tokens * choices
    tile = gm.tile_for(pairs, router)
    whole = gm.padded_rows(pairs, held + 1, tile)
    if rows:
        assert decoder_parts.usual_rows(pairs, router, held, tile) == rows == 384 * tile < whole
    rows = rows or whole
    assert tile == gm.TILE and gm.column_chunks(rows, hidden) == 1
    if share == SECOND_JUDGE_S_SHARE:
        assert gm.column_chunks(tokens, hidden) == 4
    k, n = (hidden, width) if fused == "gate-up" else (width, hidden)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def served(x, w, extra, te, used):
        epilogue = {"w_up": extra} if fused == "gate-up" else {"row_weight": extra}
        return gm.grouped_expert_product(x, w, te, used, tile=tile, interpret=False, **epilogue)

    weights = arg((held, k, n), jnp.bfloat16)
    compiled = jax.jit(served).lower(
        arg((rows, k), jnp.bfloat16), weights,
        weights if fused == "gate-up" else arg((rows,), jnp.float32),
        arg((rows // tile,), jnp.int32), arg((1,), jnp.int32),
    ).compile()
    names = [name for name, op, _ in instructions(compiled.as_text()) if op == "custom-call"]
    assert any(name.startswith("grouped_expert_product") for name in names), names


@pytest.mark.parametrize(
    "share,tokens,rows",
    [
        (SECOND_JUDGE_S_SHARE, 3 * 8192, 114_688), (SECOND_JUDGE_S_SHARE, 3 * 8192, None),
        (SECOND_JUDGE_S_SHARE, 3, None), (FIFTH_JUDGE_S_SHARE, 3 * 16384, 98_304),
    ],
    ids=["prefill-usual-load", "prefill-whole-bound", "decode", "fifth-judge-usual-load"],
)
def test_a_share_s_walk_compiles_at_the_held_share_s_shapes(one_chip, share, tokens, rows):
    """The way back for a share (ISSUE 32): the down product leaves a row a
    slab of one (8, 128) tile of words, and ``held_rows_sum`` copies a tile a
    held pair out of HBM (a one-row slice of a tiled table Mosaic refuses,
    which only this compile shows); over the usual load's rows, over the whole
    bound, and for a decode step's three tokens in tiles of 16.  The fifth
    judge's (ISSUE 43): a row of 3072 x bf16 is 12 sublanes in a slab of 16,
    a row tile's block of the slab 4096 sublanes, named by ``_row_block``."""
    from llm_weighted_consensus_tpu.ops import grouped_matmul as gm

    _, k, router, held, hidden, width = share
    tile = gm.tile_for(tokens * k, router)
    rows = rows or gm.padded_rows(tokens * k, held + 1, tile)
    slabs = (8, 8) if share == SECOND_JUDGE_S_SHARE else (16, 12)
    assert gm.row_slabs(hidden, jnp.bfloat16) == slabs and rows % tile == 0

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def served(x, w, weight, te, used, rows_of):
        y = gm.grouped_expert_product(
            x, w, te, used, row_weight=weight, tile=tile, slabs=True, interpret=False
        )
        return gm.held_rows_sum(y, rows_of, k=k, width=hidden, interpret=False)

    compiled = jax.jit(served).lower(
        arg((rows, width), jnp.bfloat16), arg((held, width, hidden), jnp.bfloat16),
        arg((rows,), jnp.float32), arg((rows // tile,), jnp.int32), arg((1,), jnp.int32),
        arg((tokens * k,), jnp.int32),
    ).compile()
    names = [name for name, op, _ in instructions(compiled.as_text()) if op == "custom-call"]
    assert any(name.startswith("held_rows_sum") for name in names), names
    assert any(name.startswith("grouped_expert_product") for name in names), names


# -- the third judge's kernels at its configuration's widths (ISSUE 33) --------


def test_the_indexer_s_two_kernels_compile_at_the_judge_s_shape(one_chip):
    """A panel's selection on a layer that owns an indexer: 3 calls x 8192
    slots, 32 index heads of 128 against one key a position, float32 scores
    for the lower triangle's blocks of 512, then the choice of 2048 keys a
    query over row blocks of 128 with the whole row of keys in VMEM, an int8
    a pair out.  Each jit holds its kernel under the name the benchmark reads
    and no pass of its own (XLA may stage the small operands, the index keys
    and the heads' weights, in VMEM ahead of the kernel: copies, no work)."""
    from llm_weighted_consensus_tpu.ops import sparse_index as si

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    scores = jax.jit(
        lambda q, k, w: si.index_scores(q, k, w, heads=32, interpret=False)
    ).lower(
        arg((3, 8192, 32 * 128), jnp.bfloat16), arg((3, 8192, 128), jnp.bfloat16),
        arg((3, 8192, 32), jnp.float32),
    ).compile()
    choice = jax.jit(lambda x: si.index_select(x, k=2048, interpret=False)).lower(
        arg((3, 8192, 8192), jnp.float32)
    ).compile()
    for compiled, name in ((scores, "index_scores"), (choice, "index_select")):
        found = instructions(compiled.as_text())
        calls = [n for n, op, _ in found if op == "custom-call" and n.startswith(name)]
        assert len(calls) == 1, found
        assert not [(n, op) for n, op, _ in found if op in ("transpose", "fusion")]


def test_causal_attention_kernel_compiles_with_a_selection_at_64_heads(one_chip):
    """The third judge's attention: 64 heads of 256 over 3 x 8192 slots, the
    selection's int8 tile of 2048 x 2048 beside q, k and v a step."""
    from llm_weighted_consensus_tpu.ops import causal_attention as ca

    x = jax.ShapeDtypeStruct((3, 8192, 64 * 256), jnp.bfloat16, sharding=one_chip)
    keep = jax.ShapeDtypeStruct((3, 8192, 8192), jnp.int8, sharding=one_chip)
    compiled = jax.jit(
        lambda q, k, v, keep: ca.causal_attention_blockwise(
            q, k, v, keep, heads=64, scale=1 / 16, interpret=False
        )
    ).lower(x, x, x, keep).compile()
    found = instructions(compiled.as_text())
    calls = [name for name, op, _ in found if op == "custom-call"]
    assert len(calls) == 1 and calls[0].startswith("causal_attention_blockwise"), calls
    assert not [(n, op) for n, op, _ in found if op in ("copy", "transpose", "fusion")]


@pytest.mark.parametrize(
    "hidden,width,slabs", [(6144, 2048, (24, 24)), (5120, 1536, (24, 20))],
    ids=["third-judge-6144", "fourth-judge-5120"],
)
@pytest.mark.parametrize(
    "tokens,rows", [(3 * 8192, 49_152), (3 * 8192, None), (3, None)],
    ids=["prefill-usual-load", "prefill-whole-bound", "decode"],
)
def test_a_sixteenth_s_experts_compile_at_their_widths(
    one_chip, tokens, rows, hidden, width, slabs
):
    """16 experts held of a router 256 wide, 8 a token.  Rows 6144 wide and
    experts 2048 (the third judge): gate and up fused go in column blocks (two
    weight windows of the whole width would be 100 MB), the down product
    leaves a row a slab of three (8, 128) tiles of words with its whole weight
    in VMEM (the limit is raised by what the window takes over the usual), and
    ``held_rows_sum`` walks slabs of 24 sublanes.  Rows 5120 wide and experts
    1536 (the fourth judge, ISSUE 40): a bf16 row is 20 sublanes of words, no
    whole tile, so the slab is PADDED to 24, the down product stores 20 at a
    stride of 24 and the walk copies all 24 a held pair and turns 20 back
    (that Mosaic takes a block of whole tiles partly written only this
    compile shows)."""
    from llm_weighted_consensus_tpu.models import decoder_parts
    from llm_weighted_consensus_tpu.ops import grouped_matmul as gm

    k, held = 8, 16
    tile = gm.tile_for(tokens * k, 256)
    whole = gm.padded_rows(tokens * k, held + 1, tile)
    if rows:
        assert decoder_parts.usual_rows(tokens * k, 256, held, tile) == rows < whole
    rows = rows or whole
    assert gm.row_slabs(hidden, jnp.bfloat16) == slabs and rows % tile == 0

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def served(x, w_gate, w_up, w_down, weight, te, used, rows_of):
        product = functools.partial(
            gm.grouped_expert_product, tile_expert=te, tiles_used=used, tile=tile,
            interpret=False,
        )
        y = product(product(x, w_gate, w_up=w_up), w_down, row_weight=weight, slabs=True)
        assert y.shape == (rows * slabs[0], gm.LANES)
        return gm.held_rows_sum(y, rows_of, k=k, width=hidden, interpret=False)

    up = arg((held, hidden, width), jnp.bfloat16)
    compiled = jax.jit(served).lower(
        arg((rows, hidden), jnp.bfloat16), up, up, arg((held, width, hidden), jnp.bfloat16),
        arg((rows,), jnp.float32), arg((rows // tile,), jnp.int32), arg((1,), jnp.int32),
        arg((tokens * k,), jnp.int32),
    ).compile()
    names = [name for name, op, _ in instructions(compiled.as_text()) if op == "custom-call"]
    assert sum(name.startswith("grouped_expert_product") for name in names) == 2, names
    assert any(name.startswith("held_rows_sum") for name in names), names


# -- the latent-attention judges' rotary turn where the heads lie (ISSUE 38) --


@pytest.mark.parametrize(
    "heads,latent", [(20, 768), (64, 2048)], ids=["first-judge", "third-judge"]
)
def test_the_rotary_turn_compiles_in_place_behind_the_query_product(
    one_chip, monkeypatch, heads, latent
):
    """``glm_moe._queries``' second half at a panel's shape: the query product
    [3 x 8192, heads x 256] and the turn of each head's lanes 192-255.  Mosaic
    takes the block (2048 rows of one 128-lane column, aliased in to out), the
    kernel keeps its name, and beside the product and the kernel nothing of
    the queries' size is made: no copy, no slice, no pad, no second fusion."""
    from llm_weighted_consensus_tpu.models import glm_moe
    from llm_weighted_consensus_tpu.ops import rotary

    monkeypatch.setattr(rotary, "_interpret", lambda: False)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def turned(cq, w, cos, sin):
        q = jnp.einsum("bsi,io->bso", cq, w, preferred_element_type=jnp.float32)
        return glm_moe._turn_heads(q.astype(cq.dtype), cos, sin, heads, 192)

    angles = arg((8192, 32), jnp.float32)
    compiled = jax.jit(turned).lower(
        arg((3, 8192, latent), jnp.bfloat16), arg((latent, heads * 256), jnp.bfloat16),
        angles, angles,
    ).compile()
    text = compiled.as_text()
    calls = [n for n, op, _ in instructions(text) if op == "custom-call"]
    assert len([n for n in calls if n.startswith("turn_lanes")]) == 1, calls
    # what writes an array of the queries' size, the product's own steps left out
    wide = re.findall(rf"= bf16\[3,8192,{heads * 256}\]\S* ([\w\-]+)\(", text)
    wide = sorted(op for op in wide if op not in ("convolution", "convert", "parameter"))
    assert wide == ["custom-call", "fusion"], wide
    assert compiled.memory_analysis().temp_size_in_bytes < 16 << 20  # the two tables


def test_the_window_kernel_compiles_at_the_fourth_judge_s_shape(one_chip):
    """A sliding layer's attention: 64 heads of 256 lanes against the keys,
    VALUES OF 128, over 3 x 8192 slots under a window of 513 at the blocks the
    window gives (512: a query block meets two key blocks).  Mosaic takes the
    second masked edge and the narrower accumulator; the kernel runs under its
    own name, and nothing stands around it."""
    from llm_weighted_consensus_tpu.ops import causal_attention as ca

    qk = jax.ShapeDtypeStruct((3, 8192, 64 * 256), jnp.bfloat16, sharding=one_chip)
    v = jax.ShapeDtypeStruct((3, 8192, 64 * 128), jnp.bfloat16, sharding=one_chip)
    compiled = jax.jit(
        lambda q, k, v: ca.window_attention_blockwise(
            q, k, v, heads=64, scale=1 / 16, window=513, interpret=False
        )
    ).lower(qk, qk, v).compile()
    assert ca.window_block(8192, 513) == 512 and len(ca._steps(8192, 512, 512, 513)[0]) == 31
    found = instructions(compiled.as_text())
    calls = [name for name, op, _ in found if op == "custom-call"]
    assert len(calls) == 1 and calls[0].startswith("window_attention_blockwise"), calls
    assert not [(n, op) for n, op, _ in found if op in ("copy", "transpose", "fusion")]


def test_a_full_layer_s_kernels_compile_at_128_heads_laid_in_256_lanes(one_chip):
    """The fourth judge's full layer: 128 heads of 128 | 64 laid in 256 lanes,
    values of 128, the selection's tile beside them; and its indexer at 64
    heads (the third judge's has 32)."""
    from llm_weighted_consensus_tpu.ops import causal_attention as ca
    from llm_weighted_consensus_tpu.ops import sparse_index as si

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    qk, v = arg((3, 8192, 128 * 256), jnp.bfloat16), arg((3, 8192, 128 * 128), jnp.bfloat16)
    compiled = jax.jit(
        lambda q, k, v, keep: ca.causal_attention_blockwise(
            q, k, v, keep, heads=128, scale=192**-0.5, interpret=False
        )
    ).lower(qk, qk, v, arg((3, 8192, 8192), jnp.int8)).compile()
    found = instructions(compiled.as_text())
    calls = [name for name, op, _ in found if op == "custom-call"]
    assert len(calls) == 1 and calls[0].startswith("causal_attention_blockwise"), calls
    assert not [(n, op) for n, op, _ in found if op in ("copy", "transpose", "fusion")]
    scores = jax.jit(
        lambda q, k, w: si.index_scores(q, k, w, heads=64, interpret=False)
    ).lower(
        arg((3, 8192, 64 * 128), jnp.bfloat16), arg((3, 8192, 128), jnp.bfloat16),
        arg((3, 8192, 64), jnp.float32),
    ).compile()
    # (XLA may stage the small operands in VMEM ahead of the kernel: a call of its own)
    found = instructions(scores.as_text())
    calls = [n for n, op, _ in found if op == "custom-call" and n.startswith("index_scores")]
    assert len(calls) == 1, found
    # a head of 192 lanes as published is refused before Mosaic is asked
    with pytest.raises(ValueError, match="a key head of 192 lanes"):
        jax.jit(
            lambda q, k, v: ca.causal_attention_blockwise(
                q, k, v, heads=128, scale=1.0, interpret=False
            )
        ).lower(arg((3, 8192, 128 * 192), jnp.bfloat16), arg((3, 8192, 128 * 192), jnp.bfloat16), v)


def test_the_fourth_judge_s_panel_compiles_and_fits_the_chip(one_chip, monkeypatch):
    """``judge_panel`` for ``dots3-note-prev`` as its cell cuts it (published
    layers 0-4, 16 of 256 experts, an eighth of the vocabulary, bf16) over a
    panel of 3 x 8192 slots at depth 2: every kernel of both kinds of layer is
    taken by the chip's compiler in ONE program, and its count of the device's
    memory (arguments + temporaries) is under the issue's 14.0 GB, the rule
    that chose 16 experts over 32 (32: 8.28 + 6.32 = 14.60 GB, PERF.md).  A
    count of the compiler's, not a reading of the chip."""
    from llm_weighted_consensus_tpu.models import glm_moe, judge
    from llm_weighted_consensus_tpu.ops import (
        causal_attention, grouped_matmul, rotary, sparse_index,
    )

    for module in (causal_attention, grouped_matmul, rotary, sparse_index):
        monkeypatch.setattr(module, "_interpret", lambda: False)
    preset = configs.DOTS3_NOTE_PREV
    cut = replace(preset, num_layers=5, vocab_size=19008, layer_types=preset.layer_types[:5])
    shapes = jax.eval_shape(
        lambda: glm_moe.init_params(jax.random.PRNGKey(0), cut, dtype=jnp.bfloat16, held=16)
    )

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(lambda a: arg(a.shape, a.dtype), shapes)
    b, s, letters = 3, 8192, 20
    compiled = judge.judge_panel.lower(
        params, arg((b, s), jnp.int32), arg((b,), jnp.int32), arg((letters,), jnp.int32),
        arg((b, letters), jnp.bool_), arg((b, letters, letters), jnp.bool_),
        decoder=glm_moe, config=cut, depth=2,
    ).compile()
    kernels = [n for n, op, _ in instructions(compiled.as_text()) if op == "custom-call"]
    for name, count in (
        ("window_attention_blockwise", 3), ("causal_attention_blockwise", 2),
        ("index_scores", 2), ("index_select", 2),
    ):
        assert sum(k.startswith(name) for k in kernels) == count, (name, kernels)
    assert any(k.startswith("grouped_expert_product") for k in kernels)
    assert any(k.startswith("turn_lanes") for k in kernels)  # 256-lane heads turn in place
    memory = compiled.memory_analysis()
    weights = sum(a.size * a.dtype.itemsize for a in jax.tree_util.tree_leaves(shapes))
    assert 5.2e9 < weights < 5.3e9 and memory.argument_size_in_bytes >= weights
    held = memory.argument_size_in_bytes + memory.temp_size_in_bytes
    assert 0.25 * 16e9 < held < 14.0e9, held


# -- the fifth judge: grouped-query attention of two kinds at 16k slots (ISSUE 41) --


def test_the_window_kernel_compiles_at_the_fifth_judge_s_shape(one_chip):
    """A sliding layer's attention: 48 query heads on 8 key heads of 128 lanes
    over 3 x 16,384 slots under a window of 4096, at the blocks the window
    gives (2048: a query block meets three key blocks, the old edge's a whole
    masked tile, the diagonal's in stripes).  Mosaic takes the group of six,
    both edges and the blocks of 2048 inside the kernel's VMEM limit; the
    kernel runs under its own name, and nothing stands around it.  The full
    layer's causal kernel at the same heads likewise."""
    from llm_weighted_consensus_tpu.ops import causal_attention as ca

    q = jax.ShapeDtypeStruct((3, 16384, 48 * 128), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((3, 16384, 8 * 128), jnp.bfloat16, sharding=one_chip)
    assert ca.window_block(16384, 4096) == 2048 and len(ca._steps(16384, 2048, 2048, 4096)[0]) == 21
    for name, call in (
        ("window_attention_blockwise", lambda q, k, v: ca.window_attention_blockwise(
            q, k, v, heads=48, kv_heads=8, scale=128**-0.5, window=4096, interpret=False)),
        ("causal_attention_blockwise", lambda q, k, v: ca.causal_attention_blockwise(
            q, k, v, heads=48, kv_heads=8, scale=128**-0.5, interpret=False)),
    ):
        compiled = jax.jit(call).lower(q, kv, kv).compile()
        found = instructions(compiled.as_text())
        calls = [n for n, op, _ in found if op == "custom-call"]
        assert len(calls) == 1 and calls[0].startswith(name), calls
        assert not [(n, op) for n, op, _ in found if op in ("copy", "transpose", "fusion")]


@pytest.mark.parametrize("turn", [False, True], ids=["full", "sliding"])
def test_the_head_norm_compiles_in_place_behind_the_query_product(one_chip, monkeypatch, turn):
    """``afmoe._heads`` behind the query product at a panel's shape, [3 x
    16384, 48 x 128]: Mosaic takes the block (2048 rows of one head's column,
    a lane reduction a row, aliased in to out), the kernel keeps its name, and
    beside the product and the kernel nothing of the queries' size is made
    (written as a reshape to [b, s, heads, 128] the norm made XLA lay out three
    float32 arrays of that size)."""
    from llm_weighted_consensus_tpu.models import afmoe
    from llm_weighted_consensus_tpu.ops import head_norm

    monkeypatch.setattr(head_norm, "_interpret", lambda: False)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def normed(h, w, scale):
        q = jnp.einsum("bsi,io->bso", h, w, preferred_element_type=jnp.float32).astype(h.dtype)
        return afmoe._heads(q, scale, jnp.arange(16384), configs.TRINITY_LARGE_PREVIEW, turn)

    compiled = jax.jit(normed).lower(
        arg((3, 16384, 3072), jnp.bfloat16), arg((3072, 6144), jnp.bfloat16),
        arg((128,), jnp.bfloat16),
    ).compile()
    text = compiled.as_text()
    calls = [n for n, op, _ in instructions(text) if op == "custom-call"]
    assert len([n for n in calls if n.startswith("head_norm_turn")]) == 1, calls
    wide = re.findall(r"= (?:bf16|f32)\[3,16384,(?:6144|48,128)\]\S* ([\w\-]+)\(", text)
    wide = sorted(op for op in wide if op not in ("convolution", "convert", "parameter"))
    assert wide == ["custom-call", "fusion"], wide
    assert compiled.memory_analysis().temp_size_in_bytes < 32 << 20  # the two tables


def test_the_fifth_judge_s_panel_compiles_and_fits_the_chip(one_chip, monkeypatch):
    """``judge_panel`` for ``trinity-large-preview`` as its cell cuts it
    (published layers 5-9, 32 of 256 experts, an eighth of the vocabulary, bf16)
    over a panel of 3 x 16,384 slots at depth 2: every kernel of both kinds of
    layer is taken by the chip's compiler in ONE program, and its count of the
    device's memory (arguments + temporaries) is under the issue's 14.0 GB, so
    the rule that would have held 16 experts did not fire (8.65 + 4.19 = 12.84
    GB, PERF.md).  A count of the compiler's, not a reading of the chip."""
    from llm_weighted_consensus_tpu.models import afmoe, judge
    from llm_weighted_consensus_tpu.ops import causal_attention, grouped_matmul, head_norm

    for module in (causal_attention, grouped_matmul, head_norm):
        monkeypatch.setattr(module, "_interpret", lambda: False)
    preset = configs.TRINITY_LARGE_PREVIEW
    cut = replace(
        preset, num_layers=5, num_dense_layers=1, vocab_size=25024,
        layer_types=preset.layer_types[5:10],
    )
    shapes = jax.eval_shape(
        lambda: afmoe.init_params(jax.random.PRNGKey(0), cut, dtype=jnp.bfloat16, held=32)
    )

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(lambda a: arg(a.shape, a.dtype), shapes)
    b, s, letters = 3, 16384, 20
    compiled = judge.judge_panel.lower(
        params, arg((b, s), jnp.int32), arg((b,), jnp.int32), arg((letters,), jnp.int32),
        arg((b, letters), jnp.bool_), arg((b, letters, letters), jnp.bool_),
        decoder=afmoe, config=cut, depth=2,
    ).compile()
    kernels = [n for n, op, _ in instructions(compiled.as_text()) if op == "custom-call"]
    for name, count in (
        ("window_attention_blockwise", 4), ("causal_attention_blockwise", 1),
        ("head_norm_turn", 10),
    ):
        assert sum(k.startswith(name) for k in kernels) == count, (name, kernels)
    assert any(k.startswith("grouped_expert_product") for k in kernels)
    assert any(k.startswith("held_rows_sum") for k in kernels)  # 3072 x bf16: 12 sublanes in a slab of 16
    memory = compiled.memory_analysis()
    weights = sum(a.size * a.dtype.itemsize for a in jax.tree_util.tree_leaves(shapes))
    assert weights == 8_650_101_248 and memory.argument_size_in_bytes >= weights
    held = memory.argument_size_in_bytes + memory.temp_size_in_bytes
    assert 0.25 * 16e9 < held < 14.0e9, held


def test_the_sixth_judge_s_panel_compiles_whole_and_fits_the_chip(one_chip, monkeypatch):
    """``judge_panel`` for ``phi-4-mini-flash-reasoning`` UNCUT (32 layers, the
    whole vocabulary, bf16) over a panel of 3 x 8192 slots at depth 2: Mosaic
    takes the selective scan at 5120 channels (every channel a grid step, the
    state in VMEM), the window kernel at heads of 64 laid in 128 lanes with a
    value head two key heads, and the pairs' norm, all in ONE program; the
    full layer has no kernel of its own (it attends at the row read).  The
    compiler's count of the device's memory is over the benchmark's floor and
    well under the chip: a count of the compiler's, not a reading of the chip."""
    from llm_weighted_consensus_tpu.models import judge, sambay
    from llm_weighted_consensus_tpu.ops import causal_attention, head_norm, selective_scan

    for module in (causal_attention, head_norm, selective_scan):
        monkeypatch.setattr(module, "_interpret", lambda: False)
    # a kernel's jit traced at these very shapes off the chip (interpreted) must not be met again
    jax.clear_caches()
    preset = configs.PHI_4_MINI_FLASH_REASONING
    shapes = jax.eval_shape(
        lambda: sambay.init_params(jax.random.PRNGKey(0), preset, dtype=jnp.bfloat16)
    )

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(lambda a: arg(a.shape, a.dtype), shapes)
    b, s, letters = 3, 8192, 20
    compiled = judge.judge_panel.lower(
        params, arg((b, s), jnp.int32), arg((b,), jnp.int32), arg((letters,), jnp.int32),
        arg((b, letters), jnp.bool_), arg((b, letters, letters), jnp.bool_),
        decoder=sambay, config=preset, depth=2,
    ).compile()
    text = compiled.as_text()
    for name, count in (
        ("selective_scan_chunked", 9), ("window_attention_blockwise", 8), ("head_norm_turn", 8),
        ("causal_attention_blockwise", 0),
    ):
        calls = re.findall(rf"^\s*(?:ROOT )?%?{name}[\w.]* = .*custom-call\(", text, re.M)
        assert len(calls) == count, (name, len(calls))
    memory = compiled.memory_analysis()
    weights = sum(a.size * a.dtype.itemsize for a in jax.tree_util.tree_leaves(shapes))
    # the checkpoint's 7,705,125,888 and the zero lanes of the heads laid in columns
    assert weights == 7_975_411_200 and memory.argument_size_in_bytes >= weights
    held = memory.argument_size_in_bytes + memory.temp_size_in_bytes
    assert 0.25 * 16e9 < held < 12.0e9, held


def test_the_seventh_judge_s_panel_compiles_at_six_layers_and_fits_the_chip(one_chip, monkeypatch):
    """``judge_panel`` for ``falcon-h1-34b-instruct`` as the benchmark serves it
    (published layers 0..5 of 72, the whole vocabulary of 261,120 rows and the
    untied head, NO width cut, bf16) over a panel of 3 x 8192 slots at depth 2:
    Mosaic takes the chunked state-space dual kernel at 32 heads of 128 on 2
    groups of 256 states (sixteen heads a grid step, the state in VMEM) and the
    causal kernel at five query heads a key head, both in ONE program, six of
    each.  THE RULE OF THE CUT (ISSUE 49): six layers ship if the compiler's
    count of the device's memory (arguments and temporaries) is at most 14.0
    GB, else five, else four; the vocabulary is not sliced to buy depth.  A
    count of the compiler's, not a reading of the chip."""
    from llm_weighted_consensus_tpu.models import falcon_h1, judge
    from llm_weighted_consensus_tpu.ops import causal_attention, rotary, ssd

    for module in (causal_attention, rotary, ssd):
        monkeypatch.setattr(module, "_interpret", lambda: False)
    # a kernel's jit traced at these very shapes off the chip (interpreted) must not be met again
    jax.clear_caches()
    preset = replace(configs.FALCON_H1_34B_INSTRUCT, num_layers=6)
    shapes = jax.eval_shape(
        lambda: falcon_h1.init_params(jax.random.PRNGKey(0), preset, dtype=jnp.bfloat16)
    )

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(lambda a: arg(a.shape, a.dtype), shapes)
    b, s, letters = 3, 8192, 20
    compiled = judge.judge_panel.lower(
        params, arg((b, s), jnp.int32), arg((b,), jnp.int32), arg((letters,), jnp.int32),
        arg((b, letters), jnp.bool_), arg((b, letters, letters), jnp.bool_),
        decoder=falcon_h1, config=preset, depth=2,
    ).compile()
    text = compiled.as_text()
    for name, count in (("ssd_chunked", 6), ("causal_attention_blockwise", 6)):
        calls = re.findall(rf"^\s*(?:ROOT )?%?{name}[\w.]* = .*custom-call\(", text, re.M)
        assert len(calls) == count, (name, len(calls))
    memory = compiled.memory_analysis()
    weights = sum(a.size * a.dtype.itemsize for a in jax.tree_util.tree_leaves(shapes))
    # the checkpoint's 10,509,188,224, A_log in float32 (32 a layer, two bytes more each)
    assert weights == 10_509_188_224 + 6 * 32 * 2 and memory.argument_size_in_bytes >= weights
    held = memory.argument_size_in_bytes + memory.temp_size_in_bytes
    # 10,509,338,112 of arguments + 2,134,012,928 of temporaries = 12.64 GB when this was written
    assert 0.25 * 16e9 < held <= 14.0e9, held
