"""The sixth judge (``models/sambay.py``, ``model_type`` ``phi4flash``): a
decoder that feeds a decoder.  Mamba layers and differential attention over a
window, one full layer whose keys and values every cross layer reads, gated
memory units on the last Mamba layer's scan; a prefill whose second half runs
at the row the panel reads and nowhere else; the selective-scan kernel
(``ops/selective_scan.py``) behind ``POST /consensus`` ``scorer: judge``.

The oracle is the benchmark's own plain reference,
``bench/references/phi4flash_judge.py`` (float32 ``jax.numpy`` at ``highest``,
a ``lax.scan`` over positions, ALL layers at EVERY position, whole mask rows,
nothing of the program), loaded by its path; the checkpoint is drawn here from
the family's tensor list (``bench/families/phi4flash.py``), on the CPU at the
tiny preset: eight layers that keep every kind (Mamba, sliding, Mamba,
sliding, the memory's Mamba, the full layer, a memory unit, a cross layer), a
window (8) shorter than most sequences below.

Tolerances.  Program and reference are both float32 here and differ in the
order of their sums only (a blockwise online softmax against whole rows, a
chunked scan against a scan a position): centred logits agree to 2e-5 (they
read 2e-7 to 4e-7).

THE CARRY.  The benchmark's checkpoints draw ``A_log`` and ``dt_proj.bias``
N(0, 0.02): a state that halves every token, under which a scan that lost its
state between chunks would still read right (PERF.md, question 23).  The tests
at the end hold the kernel to the recurrence with the PUBLISHED rates (A in
-(1..16) x 1e-2 here, dt in 0.001..0.1: a memory of hundreds of tokens) over
several chunks, a length that is no whole chunk, padding behind ``lens`` and a
decoded token continued from the state.
"""

import dataclasses
import importlib.util
import json
import math
import os

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from test_judge import candidates, tiny_tokenizer  # noqa: E402
from llm_weighted_consensus_tpu.models import judge as judge_module  # noqa: E402
from llm_weighted_consensus_tpu.models import sambay  # noqa: E402
from llm_weighted_consensus_tpu.models.configs import (  # noqa: E402
    PHI4FLASH_TEST_TINY, PHI_4_MINI_FLASH_REASONING, Phi4FlashConfig,
)
from llm_weighted_consensus_tpu.models.judge import JUDGE_PRESETS, TpuJudge  # noqa: E402
from llm_weighted_consensus_tpu.ops import causal_attention as attn  # noqa: E402
from llm_weighted_consensus_tpu.ops import selective_scan as scan  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
C = PHI4FLASH_TEST_TINY
SEQ = 96
TOL = 2e-5
KINDS = ["mamba", "sliding", "mamba", "sliding", "mamba", "full", "memory", "cross"]


def bench_file(directory, name):
    path = os.path.join(ROOT, "bench", directory, name + ".py")
    spec = importlib.util.spec_from_file_location(f"tier1_{directory}_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


reference = bench_file("references", "phi4flash_judge")
family = bench_file("families", "phi4flash")


def hf_config(config=C, **changed) -> dict:
    """The configuration as ``config.json`` keys it, and the Mamba sizes under
    the keys the benchmark's file gives them."""
    out = {
        "vocab_size": config.vocab_size,
        "hidden_size": config.hidden_size,
        "num_hidden_layers": config.num_layers,
        "num_attention_heads": config.num_heads,
        "num_key_value_heads": config.num_kv_heads,
        "intermediate_size": config.intermediate_size,
        "sliding_window": config.sliding_window,
        "mb_per_layer": config.mb_per_layer,
        "layer_norm_eps": config.layer_norm_eps,
        "mamba_d_state": config.d_state,
        "mamba_d_conv": config.d_conv,
        "mamba_expand": config.expand,
        "mamba_dt_rank": config.dt_rank,
    }
    return {**out, **changed}


def random_state(cfg: dict, seed: int) -> dict:
    """The family's tensors, N(0, 0.02) and 1 + N(0, 0.02), float32."""
    rng = np.random.default_rng(seed)
    out = {}
    for name, shape, kind in family.tensors(cfg):
        x = rng.standard_normal(shape).astype(np.float32) * 0.02
        out[name] = x + 1.0 if kind == "ln_scale" else x
    return out


@pytest.fixture(scope="module")
def state():
    return random_state(hf_config(), seed=3)


@pytest.fixture(scope="module")
def loaded(state):
    return sambay.from_hf_weights(state, C)


@pytest.fixture(scope="module")
def prompts():
    """Rows of DIFFERENT lengths in one right-padded bucket, on both sides of
    the window (8) and of the convolution's reach (4): far above both and off
    every block (90), the window's own reach (7: the gathered cache is filled
    exactly), one past it (8), under the convolution's taps (2) and one short
    of the bucket (a decoded token still fits the reference's block)."""
    rng = np.random.default_rng(1)
    lens = np.array([90, 13, 7, 8, 2, SEQ - 1], np.int32)
    ids = np.zeros((len(lens), SEQ), np.int32)
    for row, n in enumerate(lens):
        ids[row, :n] = rng.integers(4, C.vocab_size, size=n)
    return ids, lens


def centred(x):
    x = np.asarray(x, np.float64)
    return x - x.mean(axis=-1, keepdims=True)


EVERY = list(range(C.vocab_size))


# -- the decoder against the plain reference -----------------------------------------------


def test_the_tiny_preset_keeps_every_kind_of_layer(loaded):
    _, config = loaded
    assert [config.kind(i) for i in range(config.num_layers)] == KINDS and config.kv_layer == 5
    big = PHI_4_MINI_FLASH_REASONING
    kinds = [big.kind(i) for i in range(32)]
    assert big.kv_layer == 17 and kinds[17] == "full" and kinds[16] == "mamba"
    assert [kinds.count(k) for k in ("mamba", "sliding", "full", "memory", "cross")] == [9, 8, 1, 7, 7]
    assert kinds[:17:2] == ["mamba"] * 9 and kinds[1:16:2] == ["sliding"] * 8
    assert kinds[18::2] == ["memory"] * 7 and kinds[19::2] == ["cross"] * 7
    assert [kinds[i] for i in range(32)] == [family.kind_of({"num_hidden_layers": 32, "mb_per_layer": 2}, i) for i in range(32)]


def test_prefill_reads_the_reference_s_logits_at_rows_of_different_lengths(state, loaded, prompts):
    """The program ran layers 6 and 7 (and layer 5's own attention) at ONE row
    a call; the reference ran every layer at every position."""
    params, config = loaded
    ids, lens = prompts
    hidden, _, loads = sambay.prefill(params, jnp.asarray(ids), config, lens=jnp.asarray(lens))
    assert hidden.shape == (len(lens), 1, C.hidden_size) and loads == []
    got = sambay.head_logprobs(params, hidden[:, 0], config)
    calls = [(ids[row, :n].tolist(), [int(n) - 1]) for row, n in enumerate(lens)]
    for row, want in enumerate(reference.read_logits(state, hf_config(), calls, EVERY)):
        assert np.abs(centred(got[row]) - centred(want[0])).max() < TOL, row


def test_the_second_decoder_at_the_row_read_is_the_all_positions_forward_taken_there(
    state, loaded, prompts
):
    """One call read at EVERY length: a bucket whose rows are the same tokens
    under lengths 1 .. 40, against ONE reference forward over 40 positions."""
    params, config = loaded
    ids, _ = prompts
    n = 40
    lens = np.arange(1, n + 1, dtype=np.int32)
    rows = np.repeat(ids[:1], n, axis=0)
    hidden, _, _ = sambay.prefill(params, jnp.asarray(rows), config, lens=jnp.asarray(lens))
    got = sambay.head_logprobs(params, hidden[:, 0], config)
    (want,) = reference.read_logits(state, hf_config(), [(ids[0, :n].tolist(), list(range(n)))], EVERY)
    assert np.abs(centred(got) - centred(want)).max() < TOL


def test_decode_through_the_three_caches_matches_the_full_forward(state, loaded, prompts):
    """The decoded token takes one step of each recurrence from the cached
    state and tail, attends the window's cached keys on the sliding layers and
    layer 5's every cached key, its own appended once, on the full layer and the
    cross layer; the head reads what ONE forward over T + 1 tokens reads at T."""
    params, config = loaded
    ids, lens = prompts
    token = np.array([11, 200, 57, 300, 9, 77], np.int32)
    _, caches, _ = sambay.prefill(params, jnp.asarray(ids), config, lens=jnp.asarray(lens))
    step = sambay.decode_step(params, jnp.asarray(token), jnp.asarray(lens), caches, config)
    got = sambay.head_logprobs(params, step, config)
    calls = [(ids[row, :n].tolist() + [int(token[row])], [int(n)]) for row, n in enumerate(lens)]
    for row, want in enumerate(reference.read_logits(state, hf_config(), calls, EVERY)):
        assert np.abs(centred(got[row]) - centred(want[0])).max() < TOL, row


def test_the_caches_are_of_three_kinds_and_layer_k_s_is_one_array(loaded, prompts):
    """A Mamba layer keeps a convolution tail and a float32 state; a sliding
    layer the ``window - 1`` keys and values before a call's length; layer 5
    its keys and values at every slot, ONCE: the cross layer's entry is the
    same pair of arrays (identity, not equality); a memory unit nothing."""
    params, config = loaded
    ids, lens = prompts
    _, caches, _ = sambay.prefill(params, jnp.asarray(ids), config, lens=jnp.asarray(lens))
    lanes, narrow = sambay.head_lanes(C), C.num_kv_heads * C.head_dim
    back, b = C.sliding_window - 1, len(lens)
    for i in (0, 2, 4):
        tail, scanned = caches[i]
        assert tail.shape == (b, C.d_conv - 1, C.d_inner)
        assert scanned.shape == (b, C.d_inner, C.d_state) and scanned.dtype == jnp.float32
    for i in (1, 3):
        assert [x.shape for x in caches[i]] == [(b, back, C.num_kv_heads * lanes), (b, back, narrow)]
    assert [x.shape for x in caches[5]] == [(b, SEQ, C.num_kv_heads * lanes), (b, SEQ, narrow)]
    assert caches[6] == ()
    assert caches[7] is caches[5] and caches[7][0] is caches[5][0] and caches[7][1] is caches[5][1]


def test_a_padded_slot_moves_neither_the_row_read_nor_a_cache(loaded, prompts):
    """Whatever stands at and past ``lens``: the state is the state after
    ``lens - 1`` (dt is zero there), the tail and the window end at ``lens``,
    and the row read sees the first ``lens`` keys of layer 5's cache."""
    params, config = loaded
    ids, lens = prompts
    other = ids.copy()
    for row, n in enumerate(lens):
        other[row, n:] = 7 + row
    a, ca, _ = sambay.prefill(params, jnp.asarray(ids), config, lens=jnp.asarray(lens))
    b, cb, _ = sambay.prefill(params, jnp.asarray(other), config, lens=jnp.asarray(lens))
    assert np.array_equal(np.asarray(a), np.asarray(b))
    for i in range(5):
        for x, y in zip(ca[i], cb[i]):
            assert np.array_equal(np.asarray(x), np.asarray(y)), i
    for row, n in enumerate(lens):
        for x, y in zip(ca[5], cb[5]):
            assert np.array_equal(np.asarray(x[row, :n]), np.asarray(y[row, :n]))


def test_a_short_call_s_tail_and_window_hold_nothing_before_position_zero(loaded, prompts):
    params, config = loaded
    ids, lens = prompts
    _, caches, _ = sambay.prefill(params, jnp.asarray(ids), config, lens=jnp.asarray(lens))
    row = 4  # two tokens: one tap of three, two keys of seven
    assert int(lens[row]) == 2
    assert not np.asarray(caches[0][0][row, :1]).any() and np.asarray(caches[0][0][row, 1:]).all()


# -- differential attention ------------------------------------------------------------


def test_lambda_init_a_layer_and_the_scale_behind_the_norm():
    for layer in (1, 5, 17, 31):
        assert C.lambda_init(layer) == pytest.approx(0.8 - 0.6 * math.exp(-0.3 * layer), abs=1e-12)
        assert C.lambda_init(layer) == pytest.approx(reference.lambda_init(layer), abs=1e-12)
    assert PHI_4_MINI_FLASH_REASONING.lambda_init(17) == pytest.approx(0.79634, abs=1e-5)
    # the norm's output is scaled by (1 - lambda_init): all else equal, two
    # layers' outputs stand in that ratio
    rng = np.random.default_rng(0)
    ctx = jnp.asarray(rng.standard_normal((3, C.num_heads * 2 * C.head_dim)), jnp.float32)
    p = {name: jnp.zeros((C.head_dim,)) for name in ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2")}
    p["subln"] = jnp.ones((2 * C.head_dim,))
    pair = 2 * C.head_dim
    # with a2 = 0 the lambda itself falls out and only the scale is left
    halves = np.asarray(ctx).reshape(3, C.num_kv_heads // 2, 2, -1).copy()
    halves[:, :, 1] = 0.0
    ctx = jnp.asarray(halves.reshape(3, -1))
    a, b = (np.asarray(sambay._diff_norm(ctx, p, C, layer)) for layer in (1, 5))
    assert a.shape == (3, C.num_heads // 2 * pair)
    assert np.allclose(a / (1 - C.lambda_init(1)), b / (1 - C.lambda_init(5)), atol=1e-6)
    assert np.allclose(np.sqrt((a.reshape(3, -1, pair) ** 2).mean(-1)), 1 - C.lambda_init(1), atol=1e-4)


def test_the_laid_order_puts_a_pair_s_softmaxes_in_aligned_halves():
    """``laid_heads`` is its own inverse, ``p // G`` is the published key head
    and ``p // 2G`` the value head; ``_diff_norm`` over a context laid so gives
    the pairs in the published order, by hand."""
    for config in (C, PHI_4_MINI_FLASH_REASONING):
        order = sambay.laid_heads(config)
        g = config.num_heads // config.num_kv_heads
        assert sorted(order) == list(range(config.num_heads))
        assert [order[h] for h in order] == list(range(config.num_heads))
        for p, h in enumerate(order):
            j, r = h // 2, h % 2  # softmax r of pair j
            assert p // g == 2 * (j // g) + r and p // (2 * g) == j // g
    rng = np.random.default_rng(1)
    dv, layer = 2 * C.head_dim, 3
    published = rng.standard_normal((5, C.num_heads, dv)).astype(np.float32)  # a head's context
    laid = published[:, sambay.laid_heads(C)].reshape(5, -1)
    p = {name: jnp.asarray(rng.standard_normal(C.head_dim) * 0.3, jnp.float32)
         for name in ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2")}
    p["subln"] = jnp.asarray(1 + rng.standard_normal(dv) * 0.1, jnp.float32)
    lam = (
        math.exp(float(jnp.sum(p["lambda_q1"] * p["lambda_k1"])))
        - math.exp(float(jnp.sum(p["lambda_q2"] * p["lambda_k2"]))) + C.lambda_init(layer)
    )
    diff = published[:, 0::2] - lam * published[:, 1::2]  # pair j: heads 2j, 2j + 1
    want = diff / np.sqrt((diff**2).mean(-1, keepdims=True) + 1e-5) * np.asarray(p["subln"])
    want = want * (1 - C.lambda_init(layer))
    got = sambay._diff_norm(jnp.asarray(laid), p, C, layer)
    assert np.abs(np.asarray(got) - want.reshape(5, -1)).max() < 1e-5


def test_a_prefill_s_pairs_of_one_column_take_the_head_norm_kernel():
    """At the published head width a pair's context is one 128-lane column and
    a prefill's [b, s, width] goes through ``ops/head_norm.py``; the same values
    as a decode step's rows, which are cut into heads the plain way."""
    wide = dataclasses.replace(C, hidden_size=512, num_heads=8, num_kv_heads=4)
    assert wide.head_dim == 64 and sambay.head_lanes(wide) == 128
    rng = np.random.default_rng(2)
    ctx = jnp.asarray(rng.standard_normal((2, 16, wide.num_heads * 128)), jnp.float32)
    p = {name: jnp.asarray(rng.standard_normal(64) * 0.1, jnp.float32)
         for name in ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2")}
    p["subln"] = jnp.asarray(1 + rng.standard_normal(128) * 0.1, jnp.float32)
    text = jax.jit(lambda c: sambay._diff_norm(c, p, wide, 1)).lower(ctx).as_text(debug_info=True)
    assert "head_norm_turn" in text
    whole = sambay._diff_norm(ctx, p, wide, 1)
    rows = sambay._diff_norm(ctx.reshape(32, -1), p, wide, 1)
    assert np.abs(np.asarray(whole).reshape(32, -1) - np.asarray(rows)).max() < 1e-5


def test_the_window_s_edge_a_key_a_window_back_is_unseen():
    """Position t sees t - window < s <= t: what stands ``window`` back moves
    nothing at t, what stands ``window - 1`` back does (the published 512: the
    511 before it and itself)."""
    rng = np.random.default_rng(4)
    params = sambay.init_params(jax.random.PRNGKey(5), C)
    p, w, t = params["layers"][1]["attn"], C.sliding_window, 40
    h = jnp.asarray(rng.standard_normal((1, 64, C.hidden_size)), jnp.float32)
    lens = jnp.asarray([64], jnp.int32)
    base, _ = sambay._sliding_prefill(h, p, lens, C, 1)

    def moved(back):
        other, _ = sambay._sliding_prefill(h.at[0, t - back].add(1.0), p, lens, C, 1)
        return float(jnp.abs(other[0, t] - base[0, t]).max())

    assert moved(w) == 0.0 and moved(w + 3) == 0.0
    assert moved(w - 1) > 1e-4 and moved(1) > 1e-4
    # and the decoded token at position t: its cache is the w - 1 keys before it
    _, cache = sambay._sliding_prefill(h, p, jnp.asarray([t], jnp.int32), C, 1)
    assert cache[0].shape[1] == w - 1
    _, far = sambay._sliding_prefill(h.at[0, t - w].add(1.0), p, jnp.asarray([t], jnp.int32), C, 1)
    _, near = sambay._sliding_prefill(h.at[0, t - w + 1].add(1.0), p, jnp.asarray([t], jnp.int32), C, 1)
    assert np.array_equal(np.asarray(far[0]), np.asarray(cache[0]))
    assert not np.array_equal(np.asarray(near[0]), np.asarray(cache[0]))


@pytest.mark.parametrize(
    "s, window, block, heads, kv, hd",
    [
        (64, 8, 8, 8, 4, 8),  # the tiny preset: blocks as wide as the window
        (128, 32, 32, 8, 4, 16),  # two key blocks a query block, the old edge's a strict triangle
        (2048, 512, 512, 4, 2, 64),  # the published window and head, stripes of 256
        (96, 24, 0, 12, 6, 8),  # the kernel's own blocks (the largest under the window)
    ],
)
def test_window_attention_with_a_value_head_two_key_heads_is_the_einsum(s, window, block, heads, kv, hd):
    """Query head h on key head ``h // G`` and value head ``h // 2G`` of twice
    the key head's width, through the blocks' index maps: no key and no value
    repeated in memory."""
    rng = np.random.default_rng(s + heads)
    q = jnp.asarray(rng.standard_normal((2, s, heads * hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((2, s, kv * hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((2, s, kv * hd)), jnp.float32)
    kw = dict(heads=heads, kv_heads=kv, value_heads=kv // 2, scale=hd**-0.5, window=window)
    want = attn.causal_attention_einsum(q, k, v, **kw)
    got = attn.window_attention_blockwise(q, k, v, block_q=block, block_k=block, **kw)
    assert got.shape == (2, s, heads * 2 * hd)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 2e-6
    # by hand, one head: laid head 3 of G = 2 reads key head 1 and value pair 0
    g, h, t = heads // kv, 3, s - 1
    lo = max(0, t - window + 1)
    scores = np.asarray(k[0, lo:t + 1, (h // g) * hd:(h // g + 1) * hd]) @ np.asarray(
        q[0, t, h * hd:(h + 1) * hd]
    ) * hd**-0.5
    probs = np.exp(scores - scores.max())
    pair = h // (2 * g)
    by_hand = (probs / probs.sum()) @ np.asarray(v[0, lo:t + 1, pair * 2 * hd:(pair + 1) * 2 * hd])
    assert np.abs(np.asarray(got[0, t, h * 2 * hd:(h + 1) * 2 * hd]) - by_hand).max() < 1e-5


def test_the_callers_that_pass_no_value_heads_get_the_kernel_they_had():
    """``value_heads`` 0: the values go by the keys' index map, the one
    function object the five accepted judges' layers trace."""
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((1, 64, 6 * 16)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 64, 2 * 16)), jnp.float32)
    kw = dict(heads=6, kv_heads=2, scale=0.25, window=24)
    a = attn.window_attention_blockwise(q, k, k, **kw)
    b = attn.window_attention_blockwise(q, k, k, value_heads=2, **kw)
    assert np.array_equal(np.asarray(a), np.asarray(b))
    with pytest.raises(ValueError, match="values of width"):
        attn.window_attention_blockwise(q, k, k, value_heads=4, **kw)


# -- the loader -------------------------------------------------------------------------


def test_from_hf_weights_lays_the_family_s_names(state, loaded):
    params, config = loaded
    assert (config.num_layers, config.vocab_size) == (8, C.vocab_size)
    hd, lanes = C.head_dim, sambay.head_lanes(C)
    assert lanes == 128
    for i, kind in enumerate(KINDS):
        layer = params["layers"][i]
        assert ("mamba" in layer, "memory" in layer, "attn" in layer) == (
            kind == "mamba", kind == "memory", kind in ("sliding", "full", "cross")
        )
        assert layer["mlp"]["gate"]["kernel"].shape == (C.hidden_size, C.intermediate_size)
    # fc1 is [gate | up], gate first
    fc1 = state["model.layers.0.mlp.fc1.weight"]
    assert np.array_equal(np.asarray(params["layers"][0]["mlp"]["gate"]["kernel"]), fc1[: C.intermediate_size].T)
    assert np.array_equal(np.asarray(params["layers"][0]["mlp"]["up"]["kernel"]), fc1[C.intermediate_size:].T)
    # in_proj is [xs | z]; the convolution's taps lie [tap, channel]
    mamba = params["layers"][0]["mamba"]
    w = state["model.layers.0.attn.in_proj.weight"]
    assert np.array_equal(np.asarray(mamba["in_x"]["kernel"]), w[: C.d_inner].T)
    assert np.array_equal(np.asarray(mamba["in_z"]["kernel"]), w[C.d_inner:].T)
    assert np.array_equal(np.asarray(mamba["conv"]), state["model.layers.0.attn.conv1d.weight"][:, 0, :].T)
    assert mamba["a_log"].dtype == jnp.float32 and mamba["x"]["kernel"].shape == (C.d_inner, C.dt_rank + 32)
    # the fused Wqkv cut in three; a head in whole columns with zero lanes
    # behind it; the query heads in the kernel's order
    fused = state["model.layers.1.attn.Wqkv.weight"]
    a = params["layers"][1]["attn"]
    q = np.asarray(a["q"]["kernel"]).reshape(C.hidden_size, C.num_heads, lanes)
    assert not q[:, :, hd:].any()
    for p, h in enumerate(sambay.laid_heads(C)):
        assert np.array_equal(q[:, p, :hd], fused[h * hd:(h + 1) * hd].T)
    wide, narrow = C.num_heads * hd, C.num_kv_heads * hd
    k = np.asarray(a["k"]["kernel"]).reshape(C.hidden_size, C.num_kv_heads, lanes)
    assert not k[:, :, hd:].any()
    assert np.array_equal(k[:, :, :hd].reshape(C.hidden_size, -1), fused[wide:wide + narrow].T)
    assert np.array_equal(np.asarray(a["v"]["kernel"]), fused[wide + narrow:].T)
    bias = state["model.layers.1.attn.Wqkv.bias"]
    assert np.array_equal(np.asarray(a["v_bias"]), bias[wide + narrow:])
    assert np.array_equal(np.asarray(a["k_bias"]).reshape(-1, lanes)[:, :hd].reshape(-1), bias[wide:wide + narrow])
    # a cross layer has a query and an output product and no key of its own
    cross = params["layers"][7]["attn"]
    assert "k" not in cross and "v" not in cross and cross["q"]["kernel"].shape == (C.hidden_size, C.num_heads * lanes)
    assert cross["lambda_q1"].dtype == jnp.float32
    assert "lm_head" not in params  # the head is the embedding


def test_a_checkpoint_whose_shapes_are_not_the_preset_s_is_refused(state):
    with pytest.raises(ValueError, match="Wqkv is"):
        sambay.from_hf_weights(state, dataclasses.replace(C, num_kv_heads=2))
    broken = dict(state)
    broken["model.layers.0.attn.in_proj.weight"] = np.zeros((C.d_inner, C.hidden_size), np.float32)
    with pytest.raises(ValueError, match="in_proj is"):
        sambay.from_hf_weights(broken, C)
    with pytest.raises(ValueError, match="names no layer"):
        sambay.from_hf_weights({"model.embed_tokens.weight": state["model.embed_tokens.weight"]}, C)


def test_a_checkpoint_on_disk_is_served_as_it_names(tmp_path):
    from safetensors.numpy import save_file

    from llm_weighted_consensus_tpu.models.judge import load_judge_params

    cfg = hf_config(vocab_size=128)
    save_file(random_state(cfg, seed=4), str(tmp_path / "model.safetensors"))
    params, config = load_judge_params(str(tmp_path), C, dtype=jnp.float32)
    assert (config.num_layers, config.vocab_size, config.kv_layer) == (8, 128, 5)
    assert sambay.experts_held(params, config) == 0
    assert sambay.whole_bound_layers(np.zeros((0, 1)), config) == 0
    assert sambay.expert_tiles(np.zeros((0, 1)), config) == (0, 0)


def test_presets_name_the_sixth_decoder():
    assert judge_module.decoder_of(JUDGE_PRESETS["phi-4-mini-flash-reasoning"]) is sambay
    assert JUDGE_PRESETS["phi4flash-test-tiny"] is C and isinstance(C, Phi4FlashConfig)
    p = JUDGE_PRESETS["phi-4-mini-flash-reasoning"]
    assert p is PHI_4_MINI_FLASH_REASONING
    path = os.path.join(ROOT, "bench", "configs", "phi-4-mini-flash-reasoning.json")
    with open(path, encoding="utf-8") as f:
        published = json.load(f)
    for field, key in (
        ("hidden_size", "hidden_size"), ("num_heads", "num_attention_heads"),
        ("num_kv_heads", "num_key_value_heads"), ("intermediate_size", "intermediate_size"),
        ("num_layers", "num_hidden_layers"), ("vocab_size", "vocab_size"),
        ("sliding_window", "sliding_window"), ("mb_per_layer", "mb_per_layer"),
        ("layer_norm_eps", "layer_norm_eps"), ("d_state", "mamba_d_state"),
        ("d_conv", "mamba_d_conv"), ("expand", "mamba_expand"), ("dt_rank", "mamba_dt_rank"),
    ):
        assert getattr(p, field) == published[key], field
    assert published["reduced"] == [] and published["tie_word_embeddings"] is True
    assert (p.head_dim, p.d_inner, p.dt_rank, p.kv_layer) == (64, 5120, 160, 17)
    sizes = published["dry_run"]["sizes"]
    assert sizes["num_hidden_layers"] == C.num_layers and sizes["sliding_window"] == C.sliding_window
    assert sizes["mamba_dt_rank"] == C.dt_rank


# -- the panel, the counters, the service ---------------------------------------------------


@pytest.fixture(scope="module")
def judge():
    # a bucket of its own: the dispatch label's count is the process's
    return TpuJudge("phi4flash-test-tiny", tokenizer=tiny_tokenizer(), max_tokens=424, seed=2)


def test_judge_counts_the_band_and_the_positions_run(judge):
    before = judge.stats()
    confidence, _, ballots = judge.judge(
        candidates(24, np.random.default_rng(3)), "w7 w8 w9", [(5, 3.0), (6, 2.0), (7, 1.0)]
    )
    assert len(confidence) == 24 and abs(confidence.sum() - 1.0) < 1e-6 and len(ballots) == 3
    stats = judge.stats()
    s, w = judge.max_tokens, C.sliding_window
    grew = lambda key: stats[key] - before[key]  # noqa: E731
    assert grew("window_keys_causal") == 2 * 3 * family.causal_pairs(s)  # the two sliding layers
    assert grew("window_keys_band") == 2 * 3 * family.band_pairs(hf_config(), s)
    assert family.band_pairs(hf_config(), s) == w * (w + 1) // 2 + (s - w) * w == attn.band_pairs(s, w)
    # layers 0..5 at every slot, layers 6 and 7 at one: 3 calls
    assert grew("layer_positions_run") == 3 * (6 * s + 2)
    assert grew("layer_positions_whole") == 3 * 8 * s
    assert grew("index_keys_causal") == 0 and grew("expert_pairs_routed") == 0
    assert stats["expert_tokens"] == [] and stats["layers"] == 8


def test_the_cell_s_counters_read_what_the_issue_reckoned():
    """3 calls of 8192 slots through 32 layers: 18 x 8192 + 14 positions a
    call of 32 x 8192 (56.3%), 4,063,488 of 33,558,528 pairs a sliding layer
    (12.1%); counts of shapes, no device work."""
    big = PHI_4_MINI_FLASH_REASONING
    tallies: dict = {}
    shapes = jax.eval_shape(lambda: sambay.init_params(jax.random.PRNGKey(0), big, dtype=jnp.bfloat16))
    ids = jax.ShapeDtypeStruct((1, 8192), jnp.int32)  # shapes only; one call, not the cell's three

    def counted(params, ids):
        sambay.prefill(params, ids, big, tallies=tallies)
        return dict(tallies)

    out = jax.eval_shape(counted, shapes, ids)
    assert set(out) == {"window_keys", "layer_positions"} and out["layer_positions"].shape == (2,)
    run, whole = 3 * (18 * 8192 + 14), 3 * 32 * 8192
    assert 100 * run / whole == pytest.approx(56.3, abs=0.05)
    assert attn.band_pairs(8192, 512) == 4_063_488 and 8192 * 8193 // 2 == 33_558_528
    assert 100 * 4_063_488 / 33_558_528 == pytest.approx(12.1, abs=0.05)
    small = {}
    sambay.prefill(sambay.init_params(jax.random.PRNGKey(0), C), jnp.zeros((3, 64), jnp.int32), C, tallies=small)
    assert np.asarray(small["layer_positions"]).tolist() == [3 * (6 * 64 + 2), 3 * 8 * 64]


def test_the_other_judges_programs_name_none_of_this_decoder_s_own_scopes():
    """``selective_scan`` is how the benchmark's sixth scope table knows this
    decoder's programs (``bench/phi4flash_scopes.py``): no other judge names
    it, and the five keep the whole prefill's hidden states for the panel."""
    from llm_weighted_consensus_tpu.models import afmoe, glm_moe, qwen3_next
    from llm_weighted_consensus_tpu.models.configs import (
        AFMOE_TEST_TINY, DOTS3_TEST_TINY, QWEN3_NEXT_TEST_TINY,
    )

    def text(module, config):
        params = module.init_params(jax.random.PRNGKey(0), config)
        ids = jnp.zeros((1, 32), jnp.int32)
        return jax.jit(lambda p, i: module.prefill(p, i, config)[0]).lower(params, ids).as_text(
            debug_info=True
        )

    mine = text(sambay, C)
    for scope in ("selective_scan", "mamba_conv", "diff_norm", "cross_decoder", "memory_unit",
                  "cross_attention", "window_attention", "mlp"):
        assert f"/{scope}/" in mine, scope
    for module, config in ((glm_moe, DOTS3_TEST_TINY), (qwen3_next, QWEN3_NEXT_TEST_TINY), (afmoe, AFMOE_TEST_TINY)):
        other = text(module, config)  # a scope on an operation's path, not a file's name
        assert "/selective_scan/" not in other and "/cross_decoder/" not in other
        hidden = jax.eval_shape(
            lambda p, i: module.prefill(p, i, config)[0],
            module.init_params(jax.random.PRNGKey(0), config), jnp.zeros((2, 32), jnp.int32),
        )
        assert hidden.shape[1] == 32  # every slot: the panel gathers its row


def test_int8_control_reaches_every_dense_product_and_moves_the_reads():
    """``JUDGE_QUANTIZE=int8`` is the cell's control: every dense product of
    every kind of layer through ``quant.dense_int8``, and the reads move by
    far more than the dry run's limit (1e-6: float32 round-off)."""
    low = TpuJudge("phi4flash-test-tiny", tokenizer=tiny_tokenizer(), max_tokens=SEQ, seed=2, quantize="int8")
    base = TpuJudge("phi4flash-test-tiny", tokenizer=tiny_tokenizer(), max_tokens=SEQ, seed=2)
    layers = low.params["layers"]
    assert all("kernel_q" in layers[0]["mamba"][k] for k in ("in_x", "in_z", "x", "dt", "out"))
    assert all("kernel_q" in layers[1]["attn"][k] for k in ("q", "k", "v", "o"))
    assert all("kernel_q" in layers[5]["attn"][k] for k in ("q", "k", "v", "o"))
    assert all("kernel_q" in layers[6]["memory"][k] for k in ("in", "out"))
    assert all("kernel_q" in layers[7]["attn"][k] for k in ("q", "o")) and "k" not in layers[7]["attn"]
    assert all("kernel_q" in layers[3]["mlp"][k] for k in ("gate", "up", "down"))
    assert layers[0]["mamba"]["a_log"].dtype == jnp.float32  # the scan's own parameters stay
    assert low.params["token_embed"].dtype == base.params["token_embed"].dtype  # and the tied head
    texts = candidates(8, np.random.default_rng(0))
    a, _, ba = base.judge(texts, "w5", [(1, 1.0)])
    b, _, bb = low.judge(texts, "w5", [(1, 1.0)])
    assert abs(b.sum() - 1.0) < 1e-6 and set(ba[0]["siblings"]) == set(bb[0]["siblings"])
    reads = lambda ballots: centred([e["logprob"] for _, e in sorted(ballots[0]["siblings"].items())])  # noqa: E731
    assert math.sqrt(np.mean((reads(ba) - reads(bb)) ** 2)) > 100 * 1e-6


def test_consensus_judge_through_gateway_and_batcher(judge):
    from fakes import FakeTransport
    from test_gateway import go, post_json, with_client

    from llm_weighted_consensus_tpu import archive, registry
    from llm_weighted_consensus_tpu.clients.chat import ApiBase, DefaultChatClient
    from llm_weighted_consensus_tpu.clients.multichat import MultichatClient
    from llm_weighted_consensus_tpu.clients.score import ScoreClient
    from llm_weighted_consensus_tpu.serve import build_app

    chat = DefaultChatClient(FakeTransport([]), [ApiBase("https://up.example", "k")])
    reg = registry.InMemoryModelRegistry()
    store = archive.InMemoryArchive()
    score = ScoreClient(chat, reg, archive_fetcher=store)
    app = build_app(chat, score, MultichatClient(chat, reg, archive_fetcher=store), judge=judge)
    texts = candidates(21, np.random.default_rng(4))

    async def drive(client):
        dispatched = judge.stats()["dispatches"]
        resp = await post_json(
            client, "/consensus",
            {"input": texts, "scorer": "judge", "prompt": "w1 w2",
             "panel": [{"seed": 7, "weight": 2}, {"seed": 8}]},
        )
        assert resp.status == 200, await resp.text()
        body = await resp.json()
        assert body["scorer"] == "judge" and body["model"] == "phi4flash-test-tiny"
        assert len(body["confidence"]) == 21
        assert sum(body["confidence"]) == pytest.approx(1.0, abs=1e-6)
        assert [b["seed"] for b in body["ballots"]] == [7, 8]
        metrics = await (await client.get("/metrics")).json()
        assert metrics["roofline"]["buckets"]["judge(n=2,s=424)"]["count"] >= 1
        assert metrics["judge"]["dispatches"] == dispatched + 1
        assert 0 < metrics["judge"]["window_keys_band"] < metrics["judge"]["window_keys_causal"]
        assert 0 < metrics["judge"]["layer_positions_run"] < metrics["judge"]["layer_positions_whole"]

    go(with_client(app, drive))


def test_build_judge_knows_the_presets(monkeypatch):
    from llm_weighted_consensus_tpu.serve import Config
    from llm_weighted_consensus_tpu.serve.__main__ import build_judge

    monkeypatch.delenv("LWC_ALLOW_RANDOM_PARAMS", raising=False)
    config = Config.from_env({"JUDGE_MODEL": "phi4flash-test-tiny", "JUDGE_MAX_TOKENS": "64"})
    with pytest.raises(ValueError, match="JUDGE_WEIGHTS"):
        build_judge(config)
    built = build_judge(config, allow_synthetic=True)
    assert built.max_tokens == 64 and built.decoder is sambay
    assert built.config.sliding_window == 8 and built.config.num_layers == 8
    with pytest.raises(ValueError, match="phi-4-mini-flash-reasoning"):
        build_judge(Config.from_env({"JUDGE_MODEL": "phi-4"}))


# -- the family's counts (the benchmark's yardstick) ---------------------------------------


def test_the_family_counts_the_work_the_answer_needs():
    path = os.path.join(ROOT, "bench", "configs", "phi-4-mini-flash-reasoning.json")
    with open(path, encoding="utf-8") as f:
        cfg = json.load(f)
    rows, s = 3, 8192
    assert [family.layers_of(cfg, k) for k in ("mamba", "sliding", "full", "memory", "cross")] == [9, 8, 1, 7, 7]
    assert family.band_pairs(cfg, s) == 4_063_488 and family.causal_pairs(s) == 33_558_528
    assert family.mlp_weights(cfg) == 78_643_200 and family.memory_weights(cfg) == 26_214_400
    assert family.mamba_weights(cfg) == 26_214_400 + 983_040 + 819_200 + 13_107_200
    # by hand: the band's pairs x 40 heads x (64 + 128) x 2, eight layers
    assert family.window_attention_flops(cfg, rows, s) == 8 * 3 * 4_063_488 * 40 * 192 * 2
    assert family.window_attention_bytes(cfg, rows, s) == 8 * 3 * s * (3 * 2560 + 2 * 1280) * 2
    assert family.selective_scan_flops(cfg, rows, s) == 9 * 3 * s * 5120 * 16 * 6
    assert family.selective_scan_bytes(cfg, rows, s) == 9 * 3 * s * (3 * 5120 + 32) * 2
    # a slot's products over layers 0..16 and layer 17's keys and values: 1,870.9M
    # parameters (the issue's 3.925 GFLOP counted layer 17's query, output and
    # MLP at every slot too; the answer needs them at two)
    assert family.self_decoder_token_flops(cfg) == 3_741_777_920
    assert family.self_decoder_token_flops(cfg) == 2 * (
        9 * 41_123_840 + 8 * (13_107_200 + 6_553_600) + 17 * 78_643_200 + 6_553_600
    )
    total = family.forward_flops(cfg, rows, s)
    assert total == pytest.approx(93.6e12, rel=0.002)
    # all 32 layers at every slot would count 1.75 times as much: an MFU over 100
    whole = rows * s * (family.self_decoder_token_flops(cfg) + family.cross_decoder_row_flops(cfg, 0))
    assert whole / total == pytest.approx(1.75, abs=0.01)
    sizes = sum(int(np.prod(shape)) for _, shape, _ in family.tensors(cfg))
    assert 2 * sizes == cfg["bytes"]["checkpoint"] == 7_705_125_888


# -- THE CARRY: the kernel against the recurrence with a long memory -------------------------


def long_memory(b, s, channels, n=16, seed=0):
    """Inputs under the PUBLISHED rates' shape: A in -(1..16) x 1e-2, dt in
    0.001..0.1 (handed over as what softplus takes to it), so a state remembers
    for hundreds of tokens."""
    rng = np.random.default_rng(seed)
    f = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.float32)  # noqa: E731
    dt = jnp.asarray(rng.uniform(0.001, 0.1, (b, s, channels)), jnp.float32)
    a = -jnp.asarray(np.tile(np.arange(1, n + 1), (channels, 1)) * 1e-2, jnp.float32)
    return dict(
        x=f(b, s, channels), dt_raw=jnp.log(jnp.expm1(dt)), dt_bias=jnp.zeros((channels,)),
        a=a, b=f(b, s, n), c=f(b, s, n), d=f(channels),
    )


@pytest.mark.parametrize(
    "s, chunk, block, lens, channels, n",
    [
        (256, 64, 0, [256, 137], 256, 16),  # four chunks; a call that ends inside the third
        (300, 64, 0, [300, 65], 256, 16),  # a length that is no whole chunk: padded behind
        (192, 32, 128, [100, 192], 256, 16),  # two channel blocks, six chunks
        (40, 128, 0, [40, 9], 256, 16),  # shorter than one chunk
        # a call that ends INSIDE a trip's eight positions, on its last and on the next trip's first
        (256, 64, 0, [71, 135], 256, 16),
        (256, 64, 0, [72, 136], 256, 16),
        (256, 64, 0, [73, 129], 256, 16),
        (256, 64, 0, [64, 128], 256, 16),  # ... and on a chunk's edge
        (77, 32, 0, [77, 50], 256, 16),  # no multiple of eight: three chunks, the last padded
        (9, 128, 0, [9, 3], 256, 16),  # one chunk of sixteen, no packed tile of rows
        (96, 32, 0, [0, 1], 256, 16),  # a call of nothing leaves zeros; a call of one token
        (96, 32, 0, [96, 41], 2048, 16),  # ``_GROUP`` whole, twice: the groups' loop goes round
        (96, 32, 0, [96, 41], 768, 16),  # no whole ``_GROUP`` nor 512: 256 at a time
        (96, 32, 0, [96, 41], 128, 16),  # one lane group
        (96, 32, 0, [96, 41], 384, 16),  # three lane groups: one at a time
        (96, 32, 0, [96, 41], 72, 16),  # a width that is no multiple of 128: the lanes are the channels
        (96, 32, 0, [96, 41], 256, 8),  # a state of one group of eight sublanes
        (96, 32, 0, [96, 41], 256, 32),  # ... and of four: halved down to eight first
    ],
)
def test_the_scan_kernel_carries_its_state_from_chunk_to_chunk(s, chunk, block, lens, channels, n):
    args = long_memory(2, s, channels, n=n, seed=s)
    lens = jnp.asarray(lens, jnp.int32)
    got, state = scan.selective_scan(**args, lens=lens, chunk=chunk, block=block)
    want, state_want = scan.selective_scan_recurrent(**args, lens=lens)
    assert got.shape == (2, s, channels) and state.shape == (2, channels, n)
    assert state.dtype == jnp.float32
    for row, real in enumerate(np.asarray(lens)):
        assert np.abs(np.asarray(got[row, :real]) - np.asarray(want[row, :real])).max(initial=0) < 5e-5, row
    # the state after ``lens - 1``, whatever stands behind it
    assert np.abs(np.asarray(state) - np.asarray(state_want)).max() < 5e-5
    if min(np.asarray(lens)) > 8:
        assert float(jnp.abs(state_want).max()) > 1.0  # a state worth carrying
    else:
        assert not np.asarray(state[np.asarray(lens) == 0]).any()


def test_a_state_that_is_no_whole_group_of_sublanes_is_refused():
    args = long_memory(1, 16, 128, n=12)
    with pytest.raises(ValueError, match="d_state 12"):
        scan.selective_scan(**args, lens=jnp.asarray([16], jnp.int32))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_the_rows_of_a_trip_come_out_in_position_order(dtype):
    """A state that forgets at once (exp(dt A) = 0), B = C = 1 and D = 0 leave
    y[t, c] = n dt x[t, c]: with x[t] = t + 1 every position of a trip's eight
    reads its own number, so a part stored to another position's rows, a tile
    summed from the wrong sublanes or rows written in another order show as
    another row's number and not as round-off."""
    s, channels, n = 24, 256, 16
    x = jnp.broadcast_to(jnp.arange(1.0, s + 1)[None, :, None], (1, s, channels))
    x = x * (1.0 + jnp.arange(channels) % 3)[None, None, :]  # ... and every lane group's own
    one = jnp.ones((1, s, n), dtype)
    got, _ = scan.selective_scan(
        x.astype(dtype), jnp.full((1, s, channels), jnp.log(jnp.expm1(1.0)), dtype),
        jnp.zeros((channels,)), jnp.full((channels, n), -200.0), one, one, jnp.zeros((channels,)),
        jnp.asarray([s], jnp.int32),
    )
    raw = np.asarray(jnp.log(jnp.expm1(1.0)).astype(dtype).astype(jnp.float32))
    dt = np.log1p(np.exp(raw))  # softplus of what the storage dtype kept
    want = n * dt * np.asarray(x[0])
    assert np.abs(np.asarray(got[0], np.float32) / want - 1.0).max() < (1e-5 if dtype == jnp.float32 else 5e-3)


def test_a_scan_that_lost_its_state_between_chunks_would_read_wrong():
    """What the test above would catch: each chunk scanned from zero differs
    from the recurrence by the carried state's size, not by round-off."""
    args = long_memory(1, 128, 128, seed=7)
    lens = jnp.asarray([128], jnp.int32)
    whole, _ = scan.selective_scan_recurrent(**args, lens=lens)
    cut = {k: (v[:, 64:] if v.ndim == 3 else v) for k, v in args.items()}
    second, _ = scan.selective_scan_recurrent(**cut, lens=jnp.asarray([64], jnp.int32))
    assert float(jnp.abs(second - whole[:, 64:]).max()) > 0.1


def test_padding_behind_lens_leaves_the_state_and_a_decoded_token_goes_on_from_it():
    """The state handed to a decode step is the state after ``lens - 1`` with
    garbage behind it; one step from it is the recurrence run one token
    further."""
    args = long_memory(2, 160, 128, seed=3)
    lens = jnp.asarray([97, 160], jnp.int32)
    _, state = scan.selective_scan(**args, lens=lens, chunk=32)
    garbage = dict(args, x=args["x"].at[0, 97:].set(50.0), dt_raw=args["dt_raw"].at[0, 97:].set(3.0))
    _, same = scan.selective_scan(**garbage, lens=lens, chunk=32)
    assert np.array_equal(np.asarray(state), np.asarray(same))
    # the token at position 97 of call 0, through one step from the cached state
    at = lambda v: v[:1, 97]  # noqa: E731
    y, after = scan.selective_scan_step(
        state[:1], at(args["x"]), at(args["dt_raw"]), args["dt_bias"], args["a"],
        at(args["b"]), at(args["c"]), args["d"],
    )
    further, state_further = scan.selective_scan_recurrent(
        **{k: (v[:1] if v.ndim == 3 else v) for k, v in args.items()}, lens=jnp.asarray([98], jnp.int32)
    )
    assert np.abs(np.asarray(y[0]) - np.asarray(further[0, 97])).max() < 5e-5
    assert np.abs(np.asarray(after) - np.asarray(state_further)).max() < 5e-5


@pytest.mark.parametrize("s, channels, lens", [(128, 128, 128), (75, 640, 37)])
def test_the_scan_in_bfloat16_keeps_its_state_in_float32(s, channels, lens):
    args = long_memory(1, s, channels, seed=5)
    low = {k: (v.astype(jnp.bfloat16) if k in ("x", "dt_raw", "b", "c") else v) for k, v in args.items()}
    lens = jnp.asarray([lens], jnp.int32)
    got, state = scan.selective_scan(**low, lens=lens, chunk=32)
    want, state_want = scan.selective_scan_recurrent(
        **{k: v.astype(jnp.float32) for k, v in low.items()}, lens=lens
    )
    real = int(lens[0])
    assert got.dtype == jnp.bfloat16 and state.dtype == jnp.float32
    assert np.abs(np.asarray(state) - np.asarray(state_want)).max() < 5e-5
    # y's own rounding
    assert np.abs(np.asarray(got[:, :real], np.float32) - np.asarray(want[:, :real])).max() < 0.1
