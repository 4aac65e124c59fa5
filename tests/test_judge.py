"""The local judge panel (ISSUE 27): a causal sparse-expert latent-attention
decoder behind ``POST /consensus`` ``scorer: judge``.

Tiny sizes (hidden 64, 1 dense + 2 sparse layers, 8 experts, 2 a token, 4
heads of 24 + 8 / 32, vocabulary 512, 96 tokens), float32, the kernels in
interpret mode.  The plain reference is ``glm4_moe_lite_reference.py`` beside
this file: numpy float64 from the equations, HF names, nothing of the program.
"""

import dataclasses
import json
import random
import types
from decimal import Decimal

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

import glm4_moe_lite_reference as reference  # noqa: E402
from llm_weighted_consensus_tpu.ballot import PrefixTree, extract_vote  # noqa: E402
from llm_weighted_consensus_tpu.ballot.tree import ALPHABET  # noqa: E402
from llm_weighted_consensus_tpu.models import glm_moe  # noqa: E402
from llm_weighted_consensus_tpu.models.configs import GLM_TEST_TINY  # noqa: E402
from llm_weighted_consensus_tpu.models import judge as judge_module  # noqa: E402
from llm_weighted_consensus_tpu.models.judge import TpuJudge  # noqa: E402
from llm_weighted_consensus_tpu.models.spm import UnigramTokenizer  # noqa: E402
from llm_weighted_consensus_tpu.ops import causal_attention as attn  # noqa: E402
from llm_weighted_consensus_tpu.ops import grouped_matmul as gmm  # noqa: E402

C = GLM_TEST_TINY
SEQ = 96


def hf_config(config=C) -> dict:
    return {
        "vocab_size": config.vocab_size,
        "hidden_size": config.hidden_size,
        "num_hidden_layers": config.num_layers,
        "num_attention_heads": config.num_heads,
        "q_lora_rank": config.q_lora_rank,
        "kv_lora_rank": config.kv_lora_rank,
        "qk_nope_head_dim": config.qk_nope_head_dim,
        "qk_rope_head_dim": config.qk_rope_head_dim,
        "v_head_dim": config.v_head_dim,
        "intermediate_size": config.intermediate_size,
        "moe_intermediate_size": config.moe_intermediate_size,
        "n_routed_experts": config.n_routed_experts,
        "num_experts_per_tok": config.num_experts_per_tok,
        "n_shared_experts": config.n_shared_experts,
        "routed_scaling_factor": config.routed_scaling_factor,
        "first_k_dense_replace": config.first_k_dense_replace,
        "rope_theta": config.rope_theta,
        "rms_norm_eps": config.rms_norm_eps,
    }


@pytest.fixture(scope="module")
def state():
    return reference.random_state(hf_config(), seed=3)


@pytest.fixture(scope="module")
def loaded(state):
    return glm_moe.from_hf_weights(state, C)


@pytest.fixture(scope="module")
def prompts():
    rng = np.random.default_rng(1)
    lens = np.array([90, 77, SEQ], np.int32)
    ids = np.zeros((3, SEQ), np.int32)
    for row, n in enumerate(lens):
        ids[row, :n] = rng.integers(4, C.vocab_size, size=n)
    return ids, lens


def log_softmax(x):
    return x - np.log(np.exp(x - x.max(-1, keepdims=True)).sum(-1, keepdims=True)) - x.max(
        -1, keepdims=True
    )


# -- the decoder against the plain reference ---------------------------------


def test_prefill_logits_match_the_reference(state, loaded, prompts):
    params, config = loaded
    ids, lens = prompts
    hidden, caches, loads = glm_moe.prefill(params, jnp.asarray(ids), config)
    assert len(caches) == config.num_layers and len(loads) == 2
    for row, n in enumerate(lens):
        want = log_softmax(reference.logits(state, hf_config(), ids[row, :n]))
        got = np.asarray(glm_moe.head_logprobs(params, hidden[row, :n], config))
        assert np.abs(got - want).max() < 2e-5
    # every real and padded token went to exactly k experts
    assert (np.asarray(loads).sum(axis=1) == 3 * SEQ * config.num_experts_per_tok).all()


def panel_masks(first_letters=4, siblings=16):
    first = np.zeros((3, 20), bool)
    first[:, :first_letters] = True
    second = np.zeros((3, 20, 20), bool)
    second[:, :first_letters, :siblings] = True
    return first, second


def test_decode_through_the_cache_matches_the_full_forward(state, loaded, prompts):
    """Prefill, one decoded letter through the latent cache on the absorbed
    path, the second read: against the reference's one forward over T + 1."""
    params, config = loaded
    ids, lens = prompts
    letters = jnp.arange(10, 30, dtype=jnp.int32)
    first, second = panel_masks()
    out = judge_module.judge_panel(
        params, jnp.asarray(ids), jnp.asarray(lens), letters,
        jnp.asarray(first), jnp.asarray(second), decoder=glm_moe, config=config, depth=2,
    )
    for row, n in enumerate(lens):
        token = int(letters[out["chosen"][row]])
        full = log_softmax(
            reference.logits(state, hf_config(), np.append(ids[row, :n], token))
        )
        got_first = np.asarray(out["first_logprobs"][row])
        got_second = np.asarray(out["second_logprobs"][row])
        assert np.abs(got_first[:4] - full[n - 1, 10:14]).max() < 2e-5
        assert np.abs(got_second[:16] - full[n, 10:26]).max() < 2e-5
        assert np.isneginf(got_first[4:]).all() and np.isneginf(got_second[16:]).all()
        assert int(out["chosen"][row]) == int(np.argmax(full[n - 1, 10:14]))
    votes = np.asarray(out["votes"])
    assert np.allclose(votes.sum(axis=1), 1.0, atol=1e-6)
    assert (votes[:, 16:] == 0).all()


def test_absorbed_path_matches_the_prefill_path(loaded, prompts):
    """The program against itself: position T through the cache (W_kvb folded
    into the query and the output) and as the last row of a prefill over
    T + 1 (keys and values rebuilt from the latent)."""
    params, config = loaded
    ids, lens = prompts
    ids, lens = ids[:2], lens[:2]  # rows with room for one more token
    token = jnp.asarray([17, 23], jnp.int32)
    _, caches, _ = glm_moe.prefill(params, jnp.asarray(ids), config)
    step = glm_moe.decode_step(params, token, jnp.asarray(lens), caches, config)
    longer = ids.copy()
    longer[np.arange(2), lens] = np.asarray(token)
    hidden, _, _ = glm_moe.prefill(params, jnp.asarray(longer), config)
    want = np.asarray(hidden)[np.arange(2), lens]
    assert np.abs(np.asarray(step) - want).max() < 2e-5


def test_router_matches_numpy_top_k_with_the_bias_used_for_choice_only():
    rng = np.random.default_rng(5)
    config = dataclasses.replace(C, n_routed_experts=64, num_experts_per_tok=4)
    h = rng.standard_normal((50, config.hidden_size)).astype(np.float32)
    weight = (rng.standard_normal((config.hidden_size, 64)) * 0.3).astype(np.float32)
    bias = rng.standard_normal(64).astype(np.float32)  # large: it reorders
    chosen, w = glm_moe.route(
        jnp.asarray(h), {"router": jnp.asarray(weight), "bias": jnp.asarray(bias)}, config
    )
    score = 1 / (1 + np.exp(-(h.astype(np.float64) @ weight)))
    want = np.argsort(-(score + bias), axis=1, kind="stable")[:, :4]
    assert (np.sort(np.asarray(chosen), 1) == np.sort(want, 1)).all()
    assert (want != np.argsort(-score, axis=1)[:, :4]).any()  # the bias chose
    picked = np.take_along_axis(score, np.asarray(chosen), 1)
    assert np.allclose(
        np.asarray(w), picked / picked.sum(1, keepdims=True) * 1.8, atol=1e-5
    )


# -- the kernels against their einsum twins -----------------------------------


def _qkv(seed, b, s, heads, hd, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    return tuple(
        jnp.asarray(rng.standard_normal((b, s, heads * hd)), dtype) for _ in range(3)
    )


# (b, s, heads, hd, block_q, block_k, dtype, tolerance): every branch of the
# kernel's schedule at a size the interpreter runs
CAUSAL_CASES = {
    "whole-tiles-32": (2, SEQ, 4, 32, 32, 32, jnp.float32, 2e-6),
    "bq-over-bk-16": (2, SEQ, 4, 32, 32, 16, jnp.float32, 2e-6),
    "bk-over-bq-16": (2, SEQ, 4, 32, 16, 32, jnp.float32, 2e-6),
    "one-small-block": (2, SEQ, 4, 32, 96, 96, jnp.float32, 2e-6),
    "two-stripes": (1, 1024, 2, 32, 512, 512, jnp.float32, 2e-6),
    "four-stripes": (1, 2048, 1, 32, 1024, 1024, jnp.float32, 2e-6),
    "two-stripes-a-lane": (2, 1024, 2, 128, 512, 512, jnp.float32, 2e-6),
    "one-block-in-stripes": (1, 512, 2, 128, 512, 512, jnp.float32, 2e-6),
    "bq-over-bk-a-lane": (1, 512, 2, 128, 256, 128, jnp.float32, 2e-6),
    "bk-over-bq-a-lane": (1, 512, 2, 128, 128, 256, jnp.float32, 2e-6),
    "bands-below-the-diagonal": (1, 2048, 1, 128, 1024, 1024, jnp.float32, 2e-6),
    "the-block-it-picks": (2, 768, 2, 32, 0, 0, jnp.float32, 2e-6),
    "bfloat16": (2, 1024, 2, 128, 512, 512, jnp.bfloat16, 2e-2),
}


@pytest.mark.parametrize("case", CAUSAL_CASES)
def test_causal_kernel_matches_einsum(case):
    b, s, heads, hd, block_q, block_k, dtype, tolerance = CAUSAL_CASES[case]
    q, k, v = _qkv(block_q + block_k + s, b, s, heads, hd, dtype)
    scale = 1.6 / hd**0.5
    got = attn.causal_attention_blockwise(
        q, k, v, heads=heads, scale=scale, block_q=block_q, block_k=block_k
    )
    want = attn.causal_attention_einsum(q, k, v, heads=heads, scale=scale)
    assert got.dtype == dtype and got.shape == q.shape
    err = jnp.abs(got.astype(jnp.float32) - want.astype(jnp.float32)).max()
    assert float(err) < tolerance


@pytest.mark.parametrize("block,at", [(512, 300), (512, 767), (32, 40)])
def test_causal_kernel_ignores_the_future(block, at):
    """Keys and values after position ``at`` changed: rows <= at are the
    same bit for bit (a stripe that read past its own end would differ)."""
    s = 2 * block
    q, k, v = _qkv(at, 1, s, 2, 32)
    later = (jnp.arange(s) > at)[None, :, None]

    def run(k, v):
        return attn.causal_attention_blockwise(
            q, k, v, heads=2, scale=0.3, block_q=block, block_k=block
        )

    got = run(jnp.where(later, 7.5 - k, k), jnp.where(later, v * -3.0 + 1.0, v))
    want = run(k, v)
    assert (np.asarray(got)[:, : at + 1] == np.asarray(want)[:, : at + 1]).all()
    assert (np.asarray(got)[:, at + 1 :] != np.asarray(want)[:, at + 1 :]).any()


def test_work_over_causal_hand_counts():
    # 36 tiles of 1024 x 1024 for the 8192 * 8193 / 2 pairs the mask keeps
    assert attn.work_over_causal(8192, 1024, 1024, stripe=0) == pytest.approx(
        36 * 1024 * 1024 / (8192 * 8193 // 2)
    )
    assert round(attn.work_over_causal(8192, 1024, 1024, stripe=0), 3) == 1.125
    # the 8 tiles on the diagonal in stripes of 256: 10 of 16 chunks each
    assert attn.stripe_for(1024, 1024) == 256
    assert attn.work_over_causal(8192, 1024, 1024) == pytest.approx(
        (28 * 16 + 8 * 10) * 65536 / (8192 * 8193 // 2)
    )
    assert round(attn.work_over_causal(8192, 1024, 1024), 3) == 1.031
    # the waste is the stripes', whatever the block: what the judge runs
    assert attn.block_for(8192) == 2048
    assert round(attn.work_over_causal(8192, 2048, 2048), 3) == 1.031
    # no split: a block of one stripe, a block that is not square
    assert attn.stripe_for(256, 256) == 0 and attn.stripe_for(1024, 512) == 0
    assert attn.work_over_causal(1024, 256, 256) == pytest.approx(
        10 * 65536 / (1024 * 1025 // 2)
    )
    assert attn.work_over_causal(64, 32, 16) == pytest.approx(
        (3 * 32 * 16 + 4 * 32 * 16 - 32 * 16) / (64 * 65 // 2)
    )


@pytest.mark.parametrize("pairs,tile", [(37, 8), (200, 16), (64, 32)])
def test_grouped_product_matches_einsum_at_ragged_sizes(pairs, tile):
    rng = np.random.default_rng(pairs)
    experts, k, n = 8, 64, 48
    expert = rng.integers(0, experts, size=pairs).astype(np.int32)
    expert[expert == 3] = 4  # an expert with no token
    x = jnp.asarray(rng.standard_normal((pairs, k)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((experts, k, n)), jnp.float32)
    pair_of_row, row_of_pair, tile_expert, used, counts = gmm.route_layout(
        jnp.asarray(expert), experts, tile
    )
    assert int(counts[3]) == 0 and int(counts.sum()) == pairs
    assert int(used[0]) == sum(-(-int(c) // tile) for c in counts)
    out = gmm.grouped_expert_product(
        x[pair_of_row], w, tile_expert, used, tile=tile, tile_n=16
    )
    want = jnp.einsum("mk,mkn->mn", x, w[expert])
    assert float(jnp.abs(out[row_of_pair] - want).max()) < 1e-4
    order = np.argsort(expert, kind="stable")
    ragged = gmm.grouped_product_ragged(x[order], w, counts)
    assert float(jnp.abs(ragged - want[order]).max()) < 1e-4


def ragged_case(pairs, tile, k, experts=8):
    """``pairs`` rows over ``experts`` groups, expert 3 with no token, in
    ``route_layout``'s padded order."""
    rng = np.random.default_rng(pairs)
    expert = rng.integers(0, experts, size=pairs).astype(np.int32)
    expert[expert == 3] = 4
    x = jnp.asarray(rng.standard_normal((pairs, k)), jnp.float32)
    layout = gmm.route_layout(jnp.asarray(expert), experts, tile)
    assert int(layout[4][3]) == 0
    return rng, expert, x, layout


@pytest.mark.parametrize("pairs,tile,chunks", [(37, 8, 1), (200, 16, 2), (64, 32, 4)])
def test_fused_gate_up_matches_swiglu_at_ragged_sizes(pairs, tile, chunks):
    """Gate and up in one kernel, SwiGLU on the float32 accumulators; the
    rows as one array and as column chunks."""
    experts, k, n = 8, 64, 48
    rng, expert, x, (pair_of_row, row_of_pair, tile_expert, used, _) = ragged_case(
        pairs, tile, k
    )
    w_gate = jnp.asarray(rng.standard_normal((experts, k, n)) * 0.2, jnp.float32)
    w_up = jnp.asarray(rng.standard_normal((experts, k, n)), jnp.float32)
    rows = x[pair_of_row]
    out = gmm.grouped_expert_product(
        rows if chunks == 1 else tuple(jnp.split(rows, chunks, axis=1)),
        w_gate, tile_expert, used, w_up=w_up, tile=tile, tile_n=16,
    )
    gate = jnp.einsum("mk,mkn->mn", x, w_gate[expert])
    want = jax.nn.silu(gate) * jnp.einsum("mk,mkn->mn", x, w_up[expert])
    assert float(jnp.abs(out[row_of_pair] - want).max()) < 1e-4


@pytest.mark.parametrize("pairs,tile,chunks", [(36, 8, 1), (200, 16, 2), (64, 32, 4)])
def test_weighted_down_and_combine_match_the_weighted_sum(pairs, tile, chunks):
    """The router's weight on the down product's accumulator, then the way
    back: rows gathered per token and summed over its k choices; the
    product as one array and as column chunks."""
    experts, k, n, choices = 8, 48, 64, 4
    rng, expert, x, (pair_of_row, row_of_pair, tile_expert, used, _) = ragged_case(
        pairs, tile, k
    )
    w_down = jnp.asarray(rng.standard_normal((experts, k, n)), jnp.float32)
    weight = jnp.asarray(rng.random(pairs) + 0.1, jnp.float32)
    y = gmm.grouped_expert_product(
        x[pair_of_row], w_down, tile_expert, used, row_weight=weight[pair_of_row],
        tile=tile, **({"tile_n": 16} if chunks == 1 else {"out_chunks": chunks}),
    )
    if chunks > 1:
        assert [part.shape for part in y] == [(x[pair_of_row].shape[0], n // chunks)] * chunks
        y = jnp.concatenate(y, axis=1)
    got = jnp.sum(y[row_of_pair].reshape(pairs // choices, choices, n), axis=1)
    pair = jnp.einsum("mk,mkn->mn", x, w_down[expert]) * weight[:, None]
    want = jnp.sum(pair.reshape(pairs // choices, choices, n), axis=1)
    assert float(jnp.abs(got - want).max()) < 1e-4


# -- the row tiles no pair fills move no block (ISSUE 43) -----------------------


def test_a_step_past_the_tiles_in_use_names_the_last_block_in_use():
    def block(i, used):
        return int(gmm._row_block(jnp.int32(i), jnp.asarray([used], jnp.int32)))

    # under, at and past ``tiles_used``: a tile in use names its own block
    assert [block(i, 3) for i in (0, 1, 2, 3, 4, 11)] == [0, 1, 2, 2, 2, 2]
    assert [block(i, 12) for i in (0, 11)] == [0, 11]  # every tile in use
    assert [block(i, 1) for i in (0, 1, 11)] == [0, 0, 0]
    assert [block(i, 0) for i in (0, 1, 11)] == [0, 0, 0]  # no pair held: a block that exists


def every_step_its_own_block(monkeypatch):
    """``grouped_expert_product`` as it stood before ISSUE 43 (every grid step
    names its own row tile's blocks, in use or not), outside its jit so that
    no compiled form of the served one answers for it."""
    monkeypatch.setattr(gmm, "_row_block", lambda i, used: i)
    return gmm.grouped_expert_product.__wrapped__


@pytest.mark.parametrize("in_use", ["a-third", "one-tile", "none"])
@pytest.mark.parametrize("form", ["gate-up", "down-chunks", "down-slabs"])
def test_tiles_past_those_in_use_change_no_row_of_a_tile_in_use(monkeypatch, form, in_use):
    """4 experts held of a router's 16, most pairs elsewhere: a grid of 17 row
    tiles of which six, one or none hold a pair, three column blocks a row
    tile in the gate-up form.  Every row of a tile in use has the bits it had
    when every step named its own block; the rows past them nothing reads."""
    pairs, tile, held, router, k = 96, 8, 4, 16, 48
    rng = np.random.default_rng(7)
    expert = rng.integers(held, router, size=pairs).astype(np.int32)
    here = {"a-third": 34, "one-tile": 5, "none": 0}[in_use]
    expert[:here] = 2 if in_use == "one-tile" else rng.integers(0, held, size=here)
    weight = jnp.asarray(rng.random(pairs) + 0.1, jnp.float32)
    (pair_of_row, _, tile_expert, used, _, row_weight), _ = gmm.route_layout_held(
        jnp.asarray(expert), weight, held, tile
    )
    tiles = pair_of_row.shape[0] // tile
    assert tiles == 17 and int(used[0]) == {"a-third": 6, "one-tile": 1, "none": 0}[in_use]
    dtype, n = (jnp.bfloat16, 2048) if form == "down-slabs" else (jnp.float32, 48)
    x = jnp.asarray(rng.standard_normal((pairs, k)), dtype)[pair_of_row]
    w, w_up = (jnp.asarray(rng.standard_normal((held, k, n)) * 0.2, dtype) for _ in range(2))
    how = {
        "gate-up": dict(w_up=w_up, tile_n=16),
        "down-chunks": dict(row_weight=row_weight, out_chunks=2),
        "down-slabs": dict(row_weight=row_weight, slabs=True),
    }[form]
    got = gmm.grouped_expert_product(x, w, tile_expert, used, tile=tile, **how)
    want = every_step_its_own_block(monkeypatch)(x, w, tile_expert, used, tile=tile, **how)
    if form == "down-chunks":
        got, want = jnp.concatenate(got, axis=1), jnp.concatenate(want, axis=1)
    per_tile = tile * (gmm.row_slabs(n, dtype)[0] if form == "down-slabs" else 1)
    assert got.shape == want.shape and got.shape[0] == tiles * per_tile
    rows = int(used[0]) * per_tile
    got, want = np.asarray(got[:rows]), np.asarray(want[:rows])
    assert np.array_equal(got, want) and got.any() == (in_use != "none")


def moe_case(tokens, dtype, seed=0, hidden=C.hidden_size):
    """A sparse layer of 64 experts, 4 a token, at the tiny widths."""
    config = dataclasses.replace(
        C, n_routed_experts=64, num_experts_per_tok=4, hidden_size=hidden
    )
    params = glm_moe.init_params(jax.random.PRNGKey(seed), config, dtype=jnp.float32)
    p = params["layers"][config.first_k_dense_replace]["moe"]
    p = {**p, "router": p["router"] * 40.0}  # scores that differ: top-4 is stable
    rng = np.random.default_rng(tokens)
    h = jnp.asarray(rng.standard_normal((tokens, config.hidden_size)), jnp.float32)
    weights = {k: v for k, v in p.items() if k not in ("router", "bias")}
    p = {**p, **jax.tree_util.tree_map(lambda a: a.astype(dtype), weights)}
    return config, p, h.astype(dtype)


def moe_by_token(h, p, config, round_to=None):
    """The layer one token at a time over its chosen experts, float32; with
    ``round_to`` the parent's unfused formula: gate, up, their product and
    the down product each rounded to it before the weighted sum."""

    def f64(a):
        return np.asarray(a.astype(jnp.float32), np.float64)

    def rounded(a):
        return a if round_to is None else f64(jnp.asarray(a, jnp.float32).astype(round_to))

    chosen, weight = glm_moe.route(h, p, config)
    x, gate, up, down = f64(h), f64(p["w_gate"]), f64(p["w_up"]), f64(p["w_down"])
    out = np.zeros((h.shape[0], config.hidden_size))
    for t in range(h.shape[0]):
        for e, w in zip(np.asarray(chosen[t]), np.asarray(weight[t], np.float64)):
            g, u = rounded(x[t] @ gate[e]), rounded(x[t] @ up[e])
            act = rounded(g / (1 + np.exp(-g)) * u)
            out[t] += w * rounded(act @ down[e])
    return out + f64(glm_moe._swiglu(h, p["shared"])), chosen


@pytest.mark.parametrize(
    "tokens,tile,hidden,chunks",
    [(400, 16, C.hidden_size, 1), (3, 16, C.hidden_size, 1), (400, 16, 512, 2)],
    ids=["prefill-tiles-of-16", "decode-12-pairs", "prefill-in-column-chunks"],
)
def test_moe_matches_a_loop_over_each_token_s_experts_in_float32(
    tokens, tile, hidden, chunks, monkeypatch
):
    """The last case with tables too large for one gather (by a limit set
    low): the rows go in, and the products come back, in column chunks."""
    config, p, h = moe_case(tokens, jnp.float32, hidden=hidden)
    if chunks > 1:
        monkeypatch.setattr(gmm, "GATHER_TABLE_BYTES", 1 << 19)
    assert gmm.column_chunks(tokens, hidden, 4) == chunks
    assert gmm.tile_for(tokens * 4, 64) == tile
    got, counts = glm_moe._moe(h, p, config)
    want, chosen = moe_by_token(h, p, config)
    assert np.abs(np.asarray(got, np.float64) - want).max() < 1e-4
    assert (np.asarray(counts) == np.bincount(np.asarray(chosen).ravel(), minlength=64)).all()


@pytest.mark.parametrize(
    "tokens", [400, 3], ids=["prefill-tiles-of-16", "decode-12-pairs"]
)
def test_moe_in_bfloat16_is_within_rounding_of_the_unfused_formula(tokens):
    """bf16 operands, float32 accumulation: against the parent's formula
    (every product rounded to bf16) the fused layer differs by bf16's
    rounding, and from the float32 loop by no more than that formula does."""
    config, p, h = moe_case(tokens, jnp.bfloat16)
    got = np.asarray(glm_moe._moe(h, p, config)[0].astype(jnp.float32), np.float64)
    unfused, _ = moe_by_token(h, p, config, round_to=jnp.bfloat16)
    exact, _ = moe_by_token(h, p, config)
    size = np.abs(exact).max()
    assert np.abs(got - unfused).max() < 2.0**-7 * size
    assert np.abs(got - exact).max() <= np.abs(unfused - exact).max() + 2.0**-8 * size


# -- the judge: ballots, votes, the tally --------------------------------------


def tiny_tokenizer(vocab_size=C.vocab_size):
    """Whole-word pieces, the key letters and the backtick among them."""
    pieces = [("[PAD]", 0.0, 3), ("[CLS]", 0.0, 3), ("[SEP]", 0.0, 3), ("[UNK]", 0.0, 2)]
    marks = list(ALPHABET) + ["▁`", "``", "`:", "`", ":", "▁Select", "▁the", "▁response:"]
    pieces += [(m, -10.0, 1) for m in marks]
    pieces += [(f"▁w{k}", -10.0, 1) for k in range(vocab_size - len(pieces))]
    return UnigramTokenizer(pieces, scheme="deberta")


@pytest.fixture(scope="module")
def judge():
    return TpuJudge("glm-test-tiny", tokenizer=tiny_tokenizer(), max_tokens=448, seed=2)


def candidates(n, rng):
    """n one-word candidates."""
    return [f"w{rng.integers(0, 400)}" for _ in range(n)]


def test_a_call_s_prompt_is_one_token_a_word(judge):
    prepared = judge.prepare(["w1 w2 w3", "w4"], "w9 w8", [(5, 1.0)])
    ids = prepared.ids[0, : prepared.lens[0]].tolist()
    tok = judge.tokenizer
    assert tok.unk_id not in ids
    # BOS, 2 conversation words, 3 header words, two entries of key (3
    # pieces at depth 1) + text, the opening backtick
    assert len(ids) == 1 + 2 + 3 + (3 + 3) + (3 + 1) + 1
    assert ids[0] == tok.cls_id and ids[-1] == judge.encode("`")[0]
    assert len(set(judge.letter_ids.tolist())) == 20


@pytest.mark.parametrize("n", [3, 20, 21, 64])
def test_vote_matches_extract_vote_fed_the_same_letters(judge, n):
    """The device's masked reads, handed to ``ballot.vote.extract_vote`` as
    an upstream judge's answer (content = the key, ``top_logprobs`` at its
    last letter = the siblings' log-probabilities), give the call's vote."""
    rng = np.random.default_rng(n)
    texts = candidates(n, rng)
    panel = [(11, 3.0), (12, 2.0), (13, 1.0)]
    confidence, tokens, ballots = judge.judge(texts, "w7 w8 w9", panel)
    assert len(confidence) == n and abs(confidence.sum() - 1.0) < 1e-6
    assert tokens > 0 and len(ballots) == 3
    tally = np.zeros(n)
    for (seed, weight), ballot in zip(panel, ballots):
        tree_rng = random.Random(seed)
        tree = PrefixTree.build(tree_rng, n, 20)
        keys = [k for k, _ in tree.key_indices(tree_rng)]
        assert ballot["key"] in keys and ballot["seed"] == seed
        alternatives = [
            types.SimpleNamespace(token=letter, logprob=entry["logprob"])
            for letter, entry in ballot["siblings"].items()
        ]
        letters = [c for c in ballot["key"] if c in ALPHABET]
        stream = []
        for depth, letter in enumerate(letters):
            stream.append(types.SimpleNamespace(token="`", top_logprobs=[]))
            last = depth == len(letters) - 1
            stream.append(
                types.SimpleNamespace(token=letter, top_logprobs=alternatives if last else [])
            )
            stream.append(types.SimpleNamespace(token="`", top_logprobs=[]))
        vote = extract_vote(
            tree, *PrefixTree.regex_patterns(keys), n, ballot["key"], stream
        )
        assert sum(vote) == pytest.approx(Decimal(1), abs=Decimal("1e-20"))
        for letter, entry in ballot["siblings"].items():
            assert tree.walk(ballot["key"])[letter] == entry["candidate"]
        if tree.depth == 2:
            assert set(ballot["first"]) == set(tree.root)
            assert ballot["key"][1] == max(ballot["first"], key=ballot["first"].get)
        tally += np.array([float(v) for v in vote]) * weight
    assert np.abs(tally / 6.0 - confidence).max() < 1e-6


def test_a_call_too_long_for_the_bucket_is_refused(judge):
    with pytest.raises(ValueError, match="JUDGE_MAX_TOKENS"):
        judge.prepare(["w1 " * 300, "w2 " * 300], None, None)


def test_int8_control_moves_the_reads_and_keeps_the_protocol():
    base = TpuJudge("glm-test-tiny", tokenizer=tiny_tokenizer(), max_tokens=SEQ, seed=2)
    low = TpuJudge(
        "glm-test-tiny", tokenizer=tiny_tokenizer(), max_tokens=SEQ, seed=2, quantize="int8"
    )
    assert low.config.quantize == "int8"
    assert "kernel_q" in low.params["layers"][0]["attn"]["q_a"]
    assert "kernel_q" not in low.params["layers"][1]["moe"]
    texts = candidates(8, np.random.default_rng(0))
    a, _, ba = base.judge(texts, "w5", [(1, 1.0)])
    b, _, bb = low.judge(texts, "w5", [(1, 1.0)])
    assert abs(b.sum() - 1.0) < 1e-6 and set(ba[0]["siblings"]) == set(bb[0]["siblings"])
    assert np.abs(a - b).max() > 0


# -- /consensus scorer judge through the gateway and DeviceBatcher ------------


def test_consensus_judge_through_gateway_and_batcher(judge):
    from test_gateway import (
        _tiny_embedder, _tiny_reranker, go, post_json, with_client,
    )
    from llm_weighted_consensus_tpu.serve import build_app

    from llm_weighted_consensus_tpu import archive, registry
    from llm_weighted_consensus_tpu.clients.chat import ApiBase, DefaultChatClient
    from llm_weighted_consensus_tpu.clients.multichat import MultichatClient
    from llm_weighted_consensus_tpu.clients.score import ScoreClient
    from fakes import FakeTransport

    def build(**device):
        chat = DefaultChatClient(FakeTransport([]), [ApiBase("https://up.example", "k")])
        reg = registry.InMemoryModelRegistry()
        store = archive.InMemoryArchive()
        score = ScoreClient(chat, reg, archive_fetcher=store)
        return build_app(chat, score, MultichatClient(chat, reg, archive_fetcher=store), **device)

    texts = candidates(21, np.random.default_rng(4))
    cosine = {"input": ["the answer is 42", "the answer is 42!", "cabbage"]}
    rm = {**cosine, "scorer": "rm", "prompt": "what is the answer?"}

    async def with_judge(client):
        dispatched = judge.stats()["dispatches"]
        resp = await post_json(
            client, "/consensus",
            {"input": texts, "scorer": "judge", "prompt": "w1 w2",
             "panel": [{"seed": 7, "weight": 2}, {"seed": 8}]},
        )
        assert resp.status == 200, await resp.text()
        body = await resp.json()
        assert body["scorer"] == "judge" and body["model"] == "glm-test-tiny"
        assert len(body["confidence"]) == 21
        assert sum(body["confidence"]) == pytest.approx(1.0, abs=1e-6)
        assert [b["seed"] for b in body["ballots"]] == [7, 8]
        assert [b["weight"] for b in body["ballots"]] == [2.0, 1.0]
        for ballot in body["ballots"]:
            assert set(ballot) == {"seed", "weight", "first", "key", "siblings"}
            for entry in ballot["siblings"].values():
                assert entry["logprob"] < 0 and 0 <= entry["candidate"] < 21
        assert body["usage"]["prompt_tokens"] == body["usage"]["total_tokens"] > 2 * 21
        # the default panel: three calls, weights 1
        resp = await post_json(client, "/consensus", {"input": texts[:3], "scorer": "judge"})
        default = await resp.json()
        assert [b["seed"] for b in default["ballots"]] == [0, 1, 2]
        assert "first" not in default["ballots"][0]  # depth 1
        for bad in ({"panel": []}, {"panel": [{"seed": "x"}]}, {"panel": [{"seed": 1, "weight": 0}]}):
            resp = await post_json(
                client, "/consensus", {"input": texts[:3], "scorer": "judge", **bad}
            )
            assert resp.status == 400
        metrics = await (await client.get("/metrics")).json()
        assert metrics["device_batcher"]["dispatches"] >= 2
        assert metrics["roofline"]["buckets"]["judge(n=2,s=448)"]["count"] == 1
        assert metrics["phases"]["tokenize"]["count"] >= 2
        assert metrics["judge"]["dispatches"] == dispatched + 2
        return (
            await (await post_json(client, "/consensus", cosine)).read(),
            await (await post_json(client, "/consensus", rm)).read(),
        )

    async def without_judge(client):
        resp = await post_json(client, "/consensus", {"input": texts[:3], "scorer": "judge"})
        assert resp.status == 400
        assert "JUDGE_MODEL" in (await resp.json())["message"]
        return (
            await (await post_json(client, "/consensus", cosine)).read(),
            await (await post_json(client, "/consensus", rm)).read(),
        )

    embedder, reranker = _tiny_embedder(), _tiny_reranker()
    got = go(with_client(build(embedder=embedder, reranker=reranker, judge=judge), with_judge))
    want = go(with_client(build(embedder=embedder, reranker=reranker), without_judge))
    assert got == want  # cosine and rm byte for byte as before
    assert set(json.loads(want[0])) == {"model", "scorer", "confidence", "usage"}

    # a server with a judge and no embedder serves
    async def judge_only(client):
        resp = await post_json(client, "/consensus", {"input": texts[:3], "scorer": "judge"})
        assert resp.status == 200
        resp = await post_json(client, "/consensus", cosine)
        assert resp.status == 400 and "EMBEDDER_MODEL" in (await resp.json())["message"]

    go(with_client(build(judge=judge), judge_only))
    stats = judge.stats()
    assert stats["calls"] >= 2 + 3 + 3 and stats["prefill_tokens"] > 0
    assert stats["padded_tokens"] > 0 and sum(stats["expert_tokens"]) > 0
    # every dispatch ran the one bucket, whose schedule multiplies no less
    # than the mask keeps: the counters say the one, the function the other
    # (a number of the bucket; ``/metrics`` holds no mean of it)
    assert (
        stats["prefill_tokens"] + stats["padded_tokens"]
        == stats["calls"] * judge.max_tokens
    )
    block = attn.block_for(judge.max_tokens)
    assert attn.work_over_causal(judge.max_tokens, block, block) >= 1.0
    assert "attention_work_over_causal" not in stats


def test_padded_counters_of_a_judge_panel(judge):
    """What the benchmark's ``packing.padding_share`` reads in a judge's
    cell: a panel's real tokens against calls x JUDGE_MAX_TOKENS slots."""
    import asyncio

    from llm_weighted_consensus_tpu.serve.batcher import DeviceBatcher

    batcher = DeviceBatcher(None, None, judge=judge)
    texts = candidates(21, np.random.default_rng(4))
    panel = [(7, 2.0), (8, 1.0)]
    asyncio.new_event_loop().run_until_complete(
        batcher.judge(texts, "w1 w2", panel)
    )
    counters = batcher.utilization()["padded"]
    real = judge.prepare(texts, "w1 w2", panel).tokens
    assert 0 < real < 2 * judge.max_tokens
    assert counters["real_tokens"] == real
    assert counters["slot_tokens"] == 2 * judge.max_tokens
    assert counters["padding_waste"] == round(1.0 - real / (2 * 448), 4)


def test_build_judge_gate_and_presets(monkeypatch):
    from llm_weighted_consensus_tpu.serve import Config
    from llm_weighted_consensus_tpu.serve.__main__ import build_judge

    monkeypatch.delenv("LWC_ALLOW_RANDOM_PARAMS", raising=False)
    config = Config.from_env({"JUDGE_MODEL": "glm-test-tiny", "JUDGE_MAX_TOKENS": "64"})
    with pytest.raises(ValueError, match="JUDGE_WEIGHTS"):
        build_judge(config)
    built = build_judge(config, allow_synthetic=True)
    assert built.max_tokens == 64 and built.jit_stats()["judge_panel"] >= 1
    with pytest.raises(ValueError, match="not a known preset"):
        build_judge(Config.from_env({"JUDGE_MODEL": "glm-enormous"}))
    assert build_judge(Config.from_env({})) is None


# -- HF-named sharded loading ---------------------------------------------------


def test_sharded_checkpoint_loads_like_the_single_file(tmp_path, state):
    from safetensors.numpy import save_file

    from llm_weighted_consensus_tpu.models.judge import load_judge_params

    single = tmp_path / "single"
    sharded = tmp_path / "sharded"
    single.mkdir()
    sharded.mkdir()
    save_file(state, str(single / "model.safetensors"))
    names = sorted(state)
    cut = [names[i::3] for i in range(3)]
    weight_map = {}
    for i, part in enumerate(cut, start=1):
        name = f"model-{i:05d}-of-00003.safetensors"
        save_file({k: state[k] for k in part}, str(sharded / name))
        weight_map.update({k: name for k in part})
    (sharded / "model.safetensors.index.json").write_text(
        json.dumps({"metadata": {}, "weight_map": weight_map})
    )
    # a checkpoint that names fewer layers than the preset is served at its depth
    deep = dataclasses.replace(C, num_layers=47)
    a, config_a = load_judge_params(str(single), deep, dtype=jnp.float32)
    b, config_b = load_judge_params(str(sharded), deep, dtype=jnp.float32)
    c, _ = load_judge_params(
        str(sharded / "model.safetensors.index.json"), deep, dtype=jnp.float32
    )
    assert config_a.num_layers == config_b.num_layers == C.num_layers
    for x, y, z in zip(*(jax.tree_util.tree_leaves(t) for t in (a, b, c))):
        assert x.shape == y.shape and bool((x == y).all()) and bool((x == z).all())
