"""The second judge (``models/qwen3_next.py``): gated delta-rule layers three
to one with gated full attention, a share of a wider router's experts held,
behind ``POST /consensus`` ``scorer: judge``.

Against the plain reference ``tests/qwen3_next_reference.py`` (numpy float64,
the recurrent form, nothing of the program) on the CPU at the tiny preset,
seeded weights.
"""

import dataclasses
import functools
import json
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(__file__))

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

import qwen3_next_reference as reference  # noqa: E402
from test_judge import candidates, log_softmax, panel_masks, tiny_tokenizer  # noqa: E402
from llm_weighted_consensus_tpu.models import decoder_parts, qwen3_next  # noqa: E402
from llm_weighted_consensus_tpu.models import judge as judge_module  # noqa: E402
from llm_weighted_consensus_tpu.models.configs import QWEN3_NEXT_TEST_TINY  # noqa: E402
from llm_weighted_consensus_tpu.models.judge import JUDGE_PRESETS, TpuJudge  # noqa: E402
from llm_weighted_consensus_tpu.ops import causal_attention as attn  # noqa: E402
from llm_weighted_consensus_tpu.ops import gated_delta  # noqa: E402
from llm_weighted_consensus_tpu.ops import grouped_matmul as gmm  # noqa: E402

C = QWEN3_NEXT_TEST_TINY
SEQ = 96
MEMORY = 0.01  # |g| a token: a state that lives across every chunk of a call


def hf_config(config=C, **changed) -> dict:
    return {
        "vocab_size": config.vocab_size,
        "hidden_size": config.hidden_size,
        "num_hidden_layers": config.num_layers,
        "full_attention_interval": config.full_attention_interval,
        "num_attention_heads": config.num_heads,
        "num_key_value_heads": config.num_kv_heads,
        "head_dim": config.head_dim,
        "partial_rotary_factor": config.partial_rotary_factor,
        "linear_num_key_heads": config.linear_num_key_heads,
        "linear_num_value_heads": config.linear_num_value_heads,
        "linear_key_head_dim": config.linear_key_head_dim,
        "linear_value_head_dim": config.linear_value_head_dim,
        "linear_conv_kernel_dim": config.linear_conv_kernel_dim,
        "moe_intermediate_size": config.moe_intermediate_size,
        "shared_expert_intermediate_size": config.shared_expert_intermediate_size,
        "num_experts": config.num_experts,
        "num_experts_per_tok": config.num_experts_per_tok,
        "rope_theta": config.rope_theta,
        "rms_norm_eps": config.rms_norm_eps,
        **changed,
    }


@pytest.fixture(scope="module")
def state():
    """Every expert of the router held, and a memory that lasts."""
    return reference.random_state(hf_config(), seed=3, memory=MEMORY)


@pytest.fixture(scope="module")
def loaded(state):
    return qwen3_next.from_hf_weights(state, C)


@pytest.fixture(scope="module")
def prompts():
    rng = np.random.default_rng(1)
    lens = np.array([90, 77, SEQ], np.int32)
    ids = np.zeros((3, SEQ), np.int32)
    for row, n in enumerate(lens):
        ids[row, :n] = rng.integers(4, C.vocab_size, size=n)
    return ids, lens


# -- the chunked kernel against the recurrence -----------------------------------


def rule_inputs(b, s, hk, hv, dk, dv, seed=0, memory=MEMORY):
    rng = np.random.default_rng(seed)
    q = rng.standard_normal((b, s, hk, dk)) + 0.3  # of any length: the rule
    k = rng.standard_normal((b, s, hk, dk)) + 0.3  # takes them to unit length
    v = rng.standard_normal((b, s, hv, dv))
    g = -memory * (0.5 + rng.random((b, s, hv)))
    beta = 0.2 + 0.6 * rng.random((b, s, hv))
    return q, k, v, g, beta


def recurrent(q, k, v, g, beta):
    """The reference's loop, a call at a time, over unit keys and queries."""
    per = v.shape[2] // q.shape[2]
    q, k = reference.l2(q) * q.shape[-1] ** -0.5, reference.l2(k)
    outs, states = zip(
        *(
            reference.delta_rule(
                np.repeat(q[i], per, axis=1), np.repeat(k[i], per, axis=1), v[i], g[i], beta[i]
            )
            for i in range(q.shape[0])
        )
    )
    return np.stack(outs), np.stack(states)


def chunked(q, k, v, g, beta, **kwargs):
    b, s = q.shape[:2]
    out, state = gated_delta.gated_delta_rule(
        *(jnp.asarray(x.reshape(b, s, -1), jnp.float32) for x in (q, k, v)),
        jnp.asarray(g, jnp.float32), jnp.asarray(beta, jnp.float32),
        key_heads=q.shape[2], **kwargs,
    )
    return np.asarray(out, np.float64).reshape(v.shape), np.asarray(state, np.float64)


@pytest.mark.parametrize(
    "s,chunk,heads_per_step,hk,hv,d",
    [
        (64, 16, 2, 2, 4, 16), (70, 16, 4, 2, 4, 16), (96, 32, 2, 2, 4, 16), (100, 32, 1, 2, 4, 16),
        (130, 64, 4, 2, 4, 16), (128, 128, 4, 2, 4, 16), (200, 128, 2, 2, 4, 16),
        # the cell's shape class: two value heads a key head, heads of 128,
        # chunks of 128, two and a half of them
        (320, 128, 8, 2, 4, 128),
        (70, 16, 4, 4, 4, 16),  # a value head a key head: [k; q] k^T serves one
        (36, 8, 2, 2, 4, 16),  # a chunk of 8: no level moves whole sublane groups
        (200, 32, 8, 4, 8, 16),  # eight heads a step, as the cell
    ],
)
def test_chunked_rule_carries_a_long_memory_across_chunks(s, chunk, heads_per_step, hk, hv, d):
    """|g| about 0.01 a token: what the first chunk wrote is still most of
    the state at the last, so a wrong carry between chunks cannot pass."""
    inputs = rule_inputs(2, s, hk, hv, d, d, seed=s)
    want, want_state = recurrent(*inputs)
    got, got_state = chunked(*inputs, chunk=chunk, heads_per_step=heads_per_step)
    assert np.abs(got - want).max() < 2e-5
    assert np.abs(got_state - want_state).max() < 2e-5
    # the memory does live: the last chunk alone, from an empty state, is
    # far from the last chunk of the whole call
    last = (-(-s // chunk) - 1) * chunk
    if last:
        alone, _ = recurrent(*(x[:, last:] for x in inputs))
        assert np.abs(alone - want[:, last:]).max() > 0.05


def test_chunked_rule_forgets_with_the_seeded_kind_of_decay():
    """A_log and dt_bias drawn N(0, 0.02) give |g| about 0.69 a token: the
    rule still agrees, and here a lost carry WOULD pass (why the test above
    draws a long memory)."""
    inputs = rule_inputs(1, 64, 2, 4, 16, 16, seed=5, memory=0.69)
    want, _ = recurrent(*inputs)
    got, _ = chunked(*inputs, chunk=16)
    assert np.abs(got - want).max() < 2e-5
    alone, _ = recurrent(*(x[:, 48:] for x in inputs))
    assert np.abs(alone[:, 8:] - want[:, 56:]).max() < 1e-2


@pytest.mark.parametrize("memory", [0.01, 0.69])
def test_chunked_rule_in_bf16_stays_at_the_levels_the_chip_read(memory):
    """Operands in bf16 as the cell's (the products' operands in the storage
    dtype, everything summed or exponentiated in float32) against the
    float32 recurrence over the same bf16 inputs, two key heads of 128 with
    two value heads each, five chunks of 128, a long memory and the seeded
    kind: under 0.6% of root mean square, where PR 31's chip runs read
    0.58% and 0.34% (PERF.md, section 5)."""
    b, s, hk, hv, d = 1, 640, 2, 4, 128
    q, k, v, g, beta = rule_inputs(b, s, hk, hv, d, d, seed=11, memory=memory)
    q, k, v = (jnp.asarray(x.reshape(b, s, -1), jnp.bfloat16) for x in (q, k, v))
    g, beta = jnp.asarray(g, jnp.float32), jnp.asarray(beta, jnp.float32)
    want, want_state = gated_delta.gated_delta_recurrent(
        *(x.astype(jnp.float32) for x in (q, k, v)), g, beta, key_heads=hk, norm_eps=1e-6
    )
    got, got_state = gated_delta.gated_delta_rule(q, k, v, g, beta, key_heads=hk, norm_eps=1e-6)
    assert got.dtype == jnp.bfloat16

    def rms(x):
        return float(np.sqrt(np.mean(np.square(np.asarray(x, np.float64)))))

    assert rms(got.astype(jnp.float32) - want) < 0.006 * rms(want)
    assert rms(got_state - want_state) < 0.006 * rms(want_state)


def test_positions_with_beta_0_and_g_0_leave_the_state_as_it_was():
    q, k, v, g, beta = rule_inputs(1, 48, 2, 4, 16, 16, seed=9)
    g[:, 37:], beta[:, 37:] = 0.0, 0.0
    _, padded = chunked(q, k, v, g, beta, chunk=16)
    _, exact = recurrent(*(x[:, :37] for x in (q, k, v, g, beta)))
    assert np.abs(padded - exact).max() < 2e-5


def test_the_rule_divides_a_head_s_output_by_its_root_mean_square_where_asked():
    inputs = rule_inputs(1, 40, 2, 4, 16, 16, seed=4)
    want, _ = recurrent(*inputs)
    want = want / np.sqrt(np.mean(want * want, axis=-1, keepdims=True) + 1e-6)
    got, _ = chunked(*inputs, chunk=16, norm_eps=1e-6)
    assert np.abs(got - want).max() < 2e-5
    b, s = inputs[0].shape[:2]
    twin, _ = gated_delta.gated_delta_recurrent(
        *(jnp.asarray(x.reshape(b, s, -1), jnp.float32) for x in inputs[:3]),
        jnp.asarray(inputs[3], jnp.float32), jnp.asarray(inputs[4], jnp.float32),
        key_heads=2, norm_eps=1e-6,
    )
    assert np.abs(np.asarray(twin).reshape(want.shape) - want).max() < 2e-5


def test_one_step_is_the_recurrence():
    q, k, v, g, beta = rule_inputs(2, 9, 4, 4, 16, 16, seed=2)
    _, state = recurrent(*(x[:, :8] for x in (q, k, v, g, beta)))
    want, want_state = recurrent(q, k, v, g, beta)
    out, new = gated_delta.gated_delta_step(
        jnp.asarray(state, jnp.float32), *(jnp.asarray(x[:, 8], jnp.float32) for x in (q, k, v, g, beta))
    )
    assert np.abs(np.asarray(out) - want[:, 8]).max() < 2e-5
    assert np.abs(np.asarray(new) - want_state).max() < 2e-5


# -- the causal kernel with fewer key heads ----------------------------------------


def test_causal_kernel_16_query_heads_on_2_key_heads():
    rng = np.random.default_rng(0)
    b, s, heads, kv, hd = 2, 64, 16, 2, 8
    q = jnp.asarray(rng.standard_normal((b, s, heads * hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, s, kv * hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, kv * hd)), jnp.float32)
    want = attn.causal_attention_einsum(q, k, v, heads=heads, kv_heads=kv, scale=hd ** -0.5)
    for block in (16, 32, 64):
        got = attn.causal_attention_blockwise(
            q, k, v, heads=heads, kv_heads=kv, scale=hd ** -0.5, block_q=block, block_k=block
        )
        assert np.abs(np.asarray(got - want)).max() < 2e-5
    # by hand: query head 9 reads key head 1
    scores = np.einsum("qd,kd->qk", np.asarray(q[0, :, 72:80]), np.asarray(k[0, :, 8:16]))
    scores = np.where(np.tril(np.ones((s, s), bool)), scores * hd ** -0.5, -np.inf)
    probs = np.exp(scores - scores.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    assert np.abs(probs @ np.asarray(v[0, :, 8:16]) - np.asarray(want[0, :, 72:80])).max() < 2e-5


def test_a_key_head_a_query_head_is_the_program_it_was():
    """20 heads on 20 key heads: the index map divides by nothing and the
    traced program is the same whether ``kv_heads`` is named or not."""
    q = jax.ShapeDtypeStruct((1, 64, 20 * 8), jnp.float32)
    plain = jax.make_jaxpr(
        lambda q, k, v: attn.causal_attention_blockwise(q, k, v, heads=20, scale=0.25)
    )(q, q, q)
    named = jax.make_jaxpr(
        lambda q, k, v: attn.causal_attention_blockwise(q, k, v, heads=20, kv_heads=20, scale=0.25)
    )(q, q, q)
    grouped = jax.make_jaxpr(
        lambda q, k, v: attn.causal_attention_blockwise(q, k, v, heads=20, kv_heads=4, scale=0.25)
    )(q, jax.ShapeDtypeStruct((1, 64, 4 * 8), jnp.float32), jax.ShapeDtypeStruct((1, 64, 4 * 8), jnp.float32))
    assert str(plain) == str(named)
    assert str(grouped) != str(plain)
    with pytest.raises(ValueError, match="query heads on keys"):
        attn.causal_attention_blockwise(
            jnp.zeros((1, 64, 160)), jnp.zeros((1, 64, 24)), jnp.zeros((1, 64, 24)),
            heads=20, kv_heads=4, scale=0.25,
        )


# -- the decoder against the plain reference -------------------------------------------


def test_prefill_logits_match_the_reference(state, loaded, prompts):
    params, config = loaded
    ids, lens = prompts
    hidden, caches, loads = qwen3_next.prefill(
        params, jnp.asarray(ids), config, lens=jnp.asarray(lens)
    )
    assert len(caches) == config.num_layers == len(loads) == 4
    for row, n in enumerate(lens):
        want = log_softmax(reference.logits(state, hf_config(), ids[row, :n]))
        got = np.asarray(qwen3_next.head_logprobs(params, hidden[row, :n], config))
        assert np.abs(got - want).max() < 3e-5
    # every real and padded token went to exactly k experts, all held here
    loads = np.asarray(loads)
    assert loads.shape == (4, C.num_experts + 1) and (loads[:, -1] == 0).all()
    assert (loads.sum(axis=1) == 3 * SEQ * config.num_experts_per_tok).all()


def test_right_padded_calls_leave_state_and_tail_at_their_own_length(state, loaded, prompts):
    """Three calls of different ``lens`` in one batch: each linear layer's
    recurrent state and convolution tail equal the unpadded call's."""
    params, config = loaded
    ids, lens = prompts
    _, caches, _ = qwen3_next.prefill(params, jnp.asarray(ids), config, lens=jnp.asarray(lens))
    for row, n in enumerate(lens):
        want = []
        reference.hidden(state, hf_config(), ids[row, :n], caches=want)
        linear = [c for i, c in enumerate(caches) if not config.is_full_attention(i)]
        assert len(want) == len(linear) == 3
        for (tail, rule), (want_tail, want_rule) in zip(linear, want):
            size = np.abs(want_rule).max()
            assert size > 1e-3  # a state worth comparing
            assert np.abs(np.asarray(tail[row]) - want_tail).max() < 1e-6
            assert np.abs(np.asarray(rule[row]) - want_rule).max() < 1e-4 * size
    # and the same call in a narrower bucket, alone, gives the same state
    alone = qwen3_next.prefill(
        params, jnp.asarray(ids[1:2, :80]), config, lens=jnp.asarray(lens[1:2])
    )[1]
    assert np.abs(np.asarray(alone[0][1][0] - caches[0][1][1])).max() < 1e-7


def test_a_call_shorter_than_the_convolution_has_zeros_in_its_tail(loaded):
    params, config = loaded
    ids = jnp.asarray(np.full((1, 16), 7, np.int32))
    _, caches, _ = qwen3_next.prefill(params, ids, config, lens=jnp.asarray([2], jnp.int32))
    tail = np.asarray(caches[0][0][0])
    assert (tail[0] == 0).all() and np.abs(tail[1:]).max() > 0


def test_decode_through_both_caches_matches_the_full_forward(state, loaded, prompts):
    """Prefill, one decoded letter (one recurrent step through the linear
    layers, one row against the cached keys in the full layer), the second
    read: against the reference's one forward over T + 1."""
    params, config = loaded
    ids, lens = prompts
    letters = jnp.arange(10, 30, dtype=jnp.int32)
    first, second = panel_masks()
    out = judge_module.judge_panel(
        params, jnp.asarray(ids), jnp.asarray(lens), letters,
        jnp.asarray(first), jnp.asarray(second), decoder=qwen3_next, config=config, depth=2,
    )
    for row, n in enumerate(lens):
        token = int(letters[out["chosen"][row]])
        full = log_softmax(reference.logits(state, hf_config(), np.append(ids[row, :n], token)))
        got_first = np.asarray(out["first_logprobs"][row])
        got_second = np.asarray(out["second_logprobs"][row])
        assert np.abs(got_first[:4] - full[n - 1, 10:14]).max() < 3e-5
        assert np.abs(got_second[:16] - full[n, 10:26]).max() < 3e-5
        assert np.isneginf(got_first[4:]).all() and np.isneginf(got_second[16:]).all()
        assert int(out["chosen"][row]) == int(np.argmax(full[n - 1, 10:14]))
    votes = np.asarray(out["votes"])
    assert np.allclose(votes.sum(axis=1), 1.0, atol=1e-6) and (votes[:, 16:] == 0).all()


def test_router_is_softmax_top_k_normalised(state, loaded):
    params, config = loaded
    h = np.random.default_rng(2).standard_normal((50, C.hidden_size)).astype(np.float32)
    gate = np.asarray(state["model.layers.1.mlp.gate.weight"], np.float64)
    want_chosen, want_weight = reference.route(h.astype(np.float64), gate, C.num_experts_per_tok)
    chosen, weight = qwen3_next.route(jnp.asarray(h), params["layers"][1]["moe"], config)
    assert (np.asarray(chosen) == want_chosen).all()
    assert np.abs(np.asarray(weight) - want_weight).max() < 1e-6
    assert np.allclose(np.asarray(weight).sum(axis=1), 1.0, atol=1e-6)


# -- the share, tied to the model ----------------------------------------------------------


def share_of(state, experts):
    """The checkpoint a chip holding ``experts`` (renumbered from 0) would be
    given: everything but the other chips' experts."""
    out = {k: v for k, v in state.items() if ".mlp.experts." not in k}
    for name, value in state.items():
        if ".mlp.experts." in name:
            head, rest = name.split(".mlp.experts.")
            e, kind = rest.split(".", 1)
            if int(e) in experts:
                out[f"{head}.mlp.experts.{experts.index(int(e))}.{kind}"] = value
    return out


def test_four_shares_add_up_to_the_uncut_layer(state):
    """The router is 16 wide and 4 a token; four chips hold 4 experts each.
    Chip c's layer gives Σ over the pairs whose expert it holds; the four
    partial sums and the gated shared expert, counted once, are the
    reference's whole layer.  (A chip holds experts 0..3 of ITS numbering:
    the router's rows are permuted so that its experts come first.)"""
    cfg = hf_config()
    rng = np.random.default_rng(7)
    h = (rng.standard_normal((40, C.hidden_size)) * 0.5).astype(np.float32)
    base = "model.layers.0"

    def get(name):
        return np.asarray(state[name], np.float64)

    whole = reference.sparse_half(h.astype(np.float64), state, get, cfg, base)
    total = np.zeros_like(whole)
    pairs_here = 0
    for chip in range(4):
        mine = list(range(4 * chip, 4 * chip + 4))
        order = mine + [e for e in range(16) if e not in mine]
        held = share_of(state, mine)
        held[f"{base}.mlp.gate.weight"] = state[f"{base}.mlp.gate.weight"][order]
        for i in range(1, 4):
            held[f"model.layers.{i}.mlp.gate.weight"] = state[f"model.layers.{i}.mlp.gate.weight"][order]
        params, config = qwen3_next.from_hf_weights(held, C)
        assert qwen3_next.experts_held(params, config) == 4
        moe = params["layers"][0]["moe"]
        got, counts = qwen3_next._moe(jnp.asarray(h), moe, config)
        # the reference's partial sum over the same experts, the shared
        # expert in it once
        want = reference.sparse_half(
            h.astype(np.float64), state, get, cfg, base, experts=mine, shared=True
        )
        assert np.abs(np.asarray(got, np.float64) - want).max() < 2e-6
        shared = reference.sparse_half(h.astype(np.float64), state, get, cfg, base, experts=[])
        total += np.asarray(got, np.float64) - (shared if chip else 0.0)
        counts = np.asarray(counts)
        assert counts.shape == (5,) and counts.sum() == 40 * C.num_experts_per_tok
        pairs_here += counts[:4].sum()
    assert pairs_here == 40 * C.num_experts_per_tok  # every pair held somewhere, once
    assert np.abs(total - whole).max() < 5e-6


@pytest.mark.parametrize("held,tokens,k", [(4, 300, 1), (4, 64, 4), (2, 40, 3)])
def test_no_pair_held_here_is_dropped_at_the_most_uneven_routing(held, tokens, k):
    """Every token to ONE held expert (and its other choices elsewhere): the
    layout holds them all, the kernels multiply them all."""
    rng = np.random.default_rng(tokens)
    hidden, width, router = 32, 16, 16
    h = jnp.asarray(rng.standard_normal((tokens, hidden)), jnp.float32)
    p = {
        "w_gate": jnp.asarray(rng.standard_normal((held, hidden, width)) * 0.1, jnp.float32),
        "w_up": jnp.asarray(rng.standard_normal((held, hidden, width)) * 0.1, jnp.float32),
        "w_down": jnp.asarray(rng.standard_normal((held, width, hidden)) * 0.1, jnp.float32),
    }
    chosen = np.full((tokens, k), 1, np.int32)  # the one held expert everyone wants
    chosen[:, 1:] = held + 1 + np.arange(k - 1)  # the rest elsewhere
    weight = rng.random((tokens, k)).astype(np.float32)
    got, counts = decoder_parts.experts_grouped(
        h, jnp.asarray(chosen), jnp.asarray(weight), p, router, held=held
    )
    counts = np.asarray(counts)
    assert counts[1] == tokens and counts[-1] == tokens * (k - 1) and counts.sum() == tokens * k
    x = np.asarray(h, np.float64)
    gate = x @ np.asarray(p["w_gate"][1], np.float64)
    up = x @ np.asarray(p["w_up"][1], np.float64)
    want = (reference.silu(gate) * up) @ np.asarray(p["w_down"][1], np.float64)
    assert np.abs(np.asarray(got, np.float64) - want * weight[:, :1]).max() < 1e-5


def test_held_layout_puts_the_pairs_elsewhere_past_the_tiles_used():
    expert = jnp.asarray([0, 5, 1, 7, 1, 6, 0, 9], jnp.int32)
    weight = jnp.arange(8, dtype=jnp.float32) + 1
    (pair_of_row, row_of_pair, tile_expert, used, counts, row_weight), here = gmm.route_layout_held(
        expert, weight, 2, 16
    )
    assert np.asarray(here).tolist() == [True, False, True, False, True, False, True, False]
    assert np.asarray(counts).tolist() == [2, 2, 4] and int(used[0]) == 2
    assert int(np.asarray(tile_expert).max()) == 1  # a weight block that exists
    rows = np.asarray(row_of_pair)
    assert sorted(rows[[0, 6]]) == [0, 1] and sorted(rows[[2, 4]]) == [16, 17]
    assert (rows[[1, 3, 5, 7]] >= 32).all()
    assert np.asarray(row_weight)[rows[[0, 6, 2, 4]]].tolist() == [1.0, 7.0, 3.0, 5.0]
    assert np.asarray(pair_of_row)[rows].tolist() == list(range(8))


def test_a_checkpoint_naming_128_of_512_experts_and_8_of_48_layers_loads_at_that_share():
    """The checkpoint carries the share: no variable says it."""
    wide = dataclasses.replace(
        C, num_layers=48, num_experts=512, num_experts_per_tok=10, moe_intermediate_size=8,
        shared_expert_intermediate_size=8, vocab_size=64,
    )
    cfg = hf_config(wide, num_hidden_layers=8)
    state = reference.random_state(cfg, seed=1, held=128)
    params, served = qwen3_next.from_hf_weights(state, wide)
    assert served.num_layers == 8 and served.num_experts == 512
    assert [("attn" in layer) for layer in params["layers"]] == [False, False, False, True] * 2
    moe = params["layers"][0]["moe"]
    assert moe["router"].shape == (C.hidden_size, 512) and moe["w_gate"].shape[0] == 128
    assert qwen3_next.experts_held(params, served) == 128
    ids = np.random.default_rng(0).integers(4, 64, size=(1, 24)).astype(np.int32)
    hidden, _, loads = qwen3_next.prefill(params, jnp.asarray(ids), served)
    want = log_softmax(reference.logits(state, cfg, ids[0]))
    got = np.asarray(qwen3_next.head_logprobs(params, hidden[0], served))
    assert np.abs(got - want).max() < 3e-5
    loads = np.asarray(loads)
    assert loads.shape == (8, 129) and (loads.sum(axis=1) == 240).all()
    assert 0 < loads[:, :128].sum() < loads.sum()  # some here, some elsewhere
    with pytest.raises(ValueError, match="names no expert"):
        qwen3_next.from_hf_weights(share_of(state, []), wide)


# -- the judge: prompts, ballots, counters -------------------------------------------------


@pytest.fixture(scope="module")
def judge():
    # a bucket of its own: the dispatch label's count is the process's, and the
    # first judge's tests pin theirs
    return TpuJudge("qwen3-next-test-tiny", tokenizer=tiny_tokenizer(), max_tokens=440, seed=2)


def test_presets_name_both_decoders():
    from llm_weighted_consensus_tpu.models import glm_moe

    assert judge_module.decoder_of(JUDGE_PRESETS["qwen3-next-80b-a3b"]) is qwen3_next
    assert judge_module.decoder_of(JUDGE_PRESETS["qwen3-next-test-tiny"]) is qwen3_next
    assert judge_module.decoder_of(JUDGE_PRESETS["glm-4.7-flash"]) is glm_moe
    published = JUDGE_PRESETS["qwen3-next-80b-a3b"]
    assert (published.num_layers, published.num_experts, published.num_experts_per_tok) == (48, 512, 10)
    assert qwen3_next.recurrent_layers(published) == 36 and published.rotary_dim == 64


def test_judge_counts_the_pairs_held_and_the_padding(judge):
    before = judge.stats()
    confidence, tokens, ballots = judge.judge(
        candidates(24, np.random.default_rng(3)), "w7 w8 w9", [(5, 3.0), (6, 2.0), (7, 1.0)]
    )
    assert len(confidence) == 24 and abs(confidence.sum() - 1.0) < 1e-6 and len(ballots) == 3
    stats = judge.stats()
    slots = 3 * judge.max_tokens
    pairs = slots * C.num_experts_per_tok * C.num_layers
    assert stats["expert_pairs_here"] - before["expert_pairs_here"] == pairs
    assert stats["expert_pairs_elsewhere"] == 0  # random init holds every expert
    assert len(stats["expert_tokens"]) == C.num_experts
    assert sum(stats["expert_tokens"]) == stats["expert_pairs_here"]
    # the recurrence's padding share is the section's own two counters
    padded = stats["padded_tokens"] - before["padded_tokens"]
    prefill = stats["prefill_tokens"] - before["prefill_tokens"]
    assert padded / (prefill + padded) == pytest.approx(1.0 - tokens / slots)
    assert judge.jit_stats()["judge_panel"] >= 1


def test_a_judge_holding_a_share_counts_the_pairs_elsewhere():
    params = qwen3_next.init_params(jax.random.PRNGKey(0), C, held=4)
    held = TpuJudge(
        "qwen3-next-test-tiny", params=params, tokenizer=tiny_tokenizer(), max_tokens=96
    )
    held.judge(candidates(6, np.random.default_rng(1)), "w1", [(1, 1.0)])
    stats = held.stats()
    assert len(stats["expert_tokens"]) == 4
    total = 96 * C.num_experts_per_tok * C.num_layers
    assert stats["expert_pairs_here"] + stats["expert_pairs_elsewhere"] == total
    assert 0 < stats["expert_pairs_here"] < total
    assert sum(stats["expert_tokens"]) == stats["expert_pairs_here"]


def test_int8_control_moves_the_reads_and_keeps_the_protocol():
    base = TpuJudge("qwen3-next-test-tiny", tokenizer=tiny_tokenizer(), max_tokens=SEQ, seed=2)
    low = TpuJudge(
        "qwen3-next-test-tiny", tokenizer=tiny_tokenizer(), max_tokens=SEQ, seed=2, quantize="int8"
    )
    assert low.config.quantize == "int8"
    assert "kernel_q" in low.params["layers"][0]["linear"]["in_qkv"]
    assert "kernel_q" in low.params["layers"][3]["attn"]["q"]
    assert "kernel_q" in low.params["layers"][0]["moe"]["shared"]["up"]
    assert "kernel_q" not in low.params["layers"][0]["moe"]
    texts = candidates(8, np.random.default_rng(0))
    a, _, ba = base.judge(texts, "w5", [(1, 1.0)])
    b, _, bb = low.judge(texts, "w5", [(1, 1.0)])
    assert abs(b.sum() - 1.0) < 1e-6 and set(ba[0]["siblings"]) == set(bb[0]["siblings"])
    assert np.abs(a - b).max() > 0


# -- /consensus scorer judge through the gateway and DeviceBatcher ---------------------------


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
        # the label's count is the process's (another judge's tests share it)
        was = (await (await client.get("/metrics")).json()).get("roofline", {}).get("buckets", {})
        was = was.get("judge(n=2,s=440)", {}).get("count", 0)
        resp = await post_json(
            client, "/consensus",
            {"input": texts, "scorer": "judge", "prompt": "w1 w2",
             "panel": [{"seed": 7, "weight": 2}, {"seed": 8}]},
        )
        assert resp.status == 200, await resp.text()
        body = await resp.json()
        assert body["scorer"] == "judge" and body["model"] == "qwen3-next-test-tiny"
        assert len(body["confidence"]) == 21
        assert sum(body["confidence"]) == pytest.approx(1.0, abs=1e-6)
        assert [b["seed"] for b in body["ballots"]] == [7, 8]
        for ballot in body["ballots"]:
            assert set(ballot) == {"seed", "weight", "first", "key", "siblings"}
        resp = await post_json(client, "/consensus", {"input": texts[:3], "scorer": "judge"})
        assert [b["seed"] for b in (await resp.json())["ballots"]] == [0, 1, 2]
        metrics = await (await client.get("/metrics")).json()
        assert metrics["roofline"]["buckets"]["judge(n=2,s=440)"]["count"] == was + 1
        assert metrics["judge"]["dispatches"] == dispatched + 2
        assert metrics["judge"]["model"] == "qwen3-next-test-tiny"
        for key in ("expert_pairs_here", "expert_pairs_elsewhere", "padded_tokens"):
            assert key in metrics["judge"]

    go(with_client(app, drive))


def test_build_judge_knows_the_presets(monkeypatch):
    from llm_weighted_consensus_tpu.serve import Config
    from llm_weighted_consensus_tpu.serve.__main__ import build_judge

    monkeypatch.delenv("LWC_ALLOW_RANDOM_PARAMS", raising=False)
    config = Config.from_env({"JUDGE_MODEL": "qwen3-next-test-tiny", "JUDGE_MAX_TOKENS": "64"})
    with pytest.raises(ValueError, match="JUDGE_WEIGHTS"):
        build_judge(config)
    built = build_judge(config, allow_synthetic=True)
    assert built.max_tokens == 64 and built.decoder is qwen3_next
    with pytest.raises(ValueError, match="qwen3-next-80b-a3b"):
        build_judge(Config.from_env({"JUDGE_MODEL": "qwen3-next"}))


def test_a_checkpoint_on_disk_is_served_at_its_share(tmp_path):
    from safetensors.numpy import save_file

    from llm_weighted_consensus_tpu.models.judge import load_judge_params

    cfg = hf_config(num_hidden_layers=4)
    state = reference.random_state(cfg, seed=4, held=8)
    save_file(state, str(tmp_path / "model.safetensors"))
    (tmp_path / "ignored.json").write_text(json.dumps({}))
    deep = dataclasses.replace(C, num_layers=48)
    params, config = load_judge_params(str(tmp_path), deep, dtype=jnp.float32)
    assert config.num_layers == 4 and qwen3_next.experts_held(params, config) == 8


def test_a_share_s_usual_load_and_its_whole_bound_are_one_answer(monkeypatch):
    """The layout's rows past the usual load are skipped where the tiles in
    use fit; where they do not, the whole bound runs: the same sums."""
    rng = np.random.default_rng(3)
    tokens, k, hidden, width, held, router = 200, 4, 32, 16, 4, 16
    h = jnp.asarray(rng.standard_normal((tokens, hidden)), jnp.float32)
    p = {
        "w_gate": jnp.asarray(rng.standard_normal((held, hidden, width)) * 0.1, jnp.float32),
        "w_up": jnp.asarray(rng.standard_normal((held, hidden, width)) * 0.1, jnp.float32),
        "w_down": jnp.asarray(rng.standard_normal((held, width, hidden)) * 0.1, jnp.float32),
    }
    chosen = np.stack([rng.permutation(router)[:k] for _ in range(tokens)]).astype(np.int32)
    weight = rng.random((tokens, k)).astype(np.float32)
    answers = []
    for usual in (10**9, 512, 64):  # one path; the usual load fits; it does not
        monkeypatch.setattr(decoder_parts, "USUAL_ROWS", usual)
        got, counts = decoder_parts.experts_grouped(
            h, jnp.asarray(chosen), jnp.asarray(weight), p, router, held=held
        )
        answers.append(np.asarray(got))
        assert int(np.asarray(counts)[:held].sum()) == int((chosen < held).sum())
    assert np.abs(answers[0] - answers[1]).max() < 1e-6
    assert np.abs(answers[0] - answers[2]).max() < 1e-6
    x = np.asarray(h, np.float64)
    want = np.zeros_like(x)
    for e in range(held):
        y = (reference.silu(x @ np.asarray(p["w_gate"][e], np.float64)) * (
            x @ np.asarray(p["w_up"][e], np.float64))) @ np.asarray(p["w_down"][e], np.float64)
        want += y * (weight * (chosen == e)).sum(axis=1, keepdims=True)
    assert np.abs(answers[0] - want).max() < 1e-5


# -- a share's way back from the padded layout: the walk (ISSUE 32) --------------------------


def gather_twin(h, chosen, weight, p, experts, held, rows=None):
    """The way back as it was before the walk, kept here as the plain twin:
    the same layout and kernels, the down product as column chunks, then a
    masked gather a choice and a float32 sum in the order of the choices."""
    t, k = chosen.shape
    tile = gmm.tile_for(t * k, experts)
    tables, here = gmm.route_layout_held(chosen.reshape(-1), weight.reshape(-1), held, tile)
    pair_of_row, row_of_pair, tile_expert, used, _, row_weight = tables
    here = here.reshape(t, k)
    rows = rows or pair_of_row.shape[0]
    rows_of = jnp.where(here, row_of_pair.reshape(t, k), 0)
    kernels = dict(tile_expert=tile_expert[: rows // tile], tiles_used=used, tile=tile)
    x = h[pair_of_row[:rows] // k]
    y = gmm.grouped_expert_product(
        gmm.grouped_expert_product(x, p["w_gate"], w_up=p["w_up"], **kernels), p["w_down"],
        row_weight=row_weight[:rows], out_chunks=2, **kernels,
    )
    return masked_sums(y, rows_of, here).astype(h.dtype)


def masked_sums(chunks, rows_of, here):
    """Column chunks of a down product, rows_of and here [t, k] -> [t, width]
    float32: a masked gather a choice, summed in the order of the choices."""
    take = lambda part, j: jnp.where(  # noqa: E731
        here[:, j, None], part[rows_of[:, j]].astype(jnp.float32), 0.0
    )
    return jnp.concatenate(
        [sum(take(part, j) for j in range(here.shape[1])) for part in chunks], axis=1
    )


def share_case(tokens, k, dtype, routing, seed=0):
    """A layer's inputs at a width whose rows are whole tiles of words, 12 of
    a router's 32 experts held.  ``mixed``: a token in four has none of its
    choices held, one in four all of them, the rest as they fall; ``one``:
    every token to ONE held expert and elsewhere with its other choices."""
    rng = np.random.default_rng(seed)
    hidden = 2048 if dtype == jnp.bfloat16 else 1024
    width, held, router = 16, 12, 32
    h = jnp.asarray(rng.standard_normal((tokens, hidden)), dtype)
    p = {
        name: jnp.asarray(rng.standard_normal(shape) * 0.1, dtype)
        for name, shape in (
            ("w_gate", (held, hidden, width)), ("w_up", (held, hidden, width)),
            ("w_down", (held, width, hidden)),
        )
    }
    chosen = np.stack([rng.permutation(router)[:k] for _ in range(tokens)]).astype(np.int32)
    if routing == "mixed":
        chosen[0::4] = held + np.stack([rng.permutation(router - held)[:k] for _ in chosen[0::4]])
        chosen[1::4] = np.stack([rng.permutation(held)[:k] for _ in chosen[1::4]])
    else:
        chosen[:, 0] = 1
        chosen[:, 1:] = held + 1 + np.arange(k - 1)
    weight = rng.random((tokens, k)).astype(np.float32)
    return h, jnp.asarray(chosen), jnp.asarray(weight), p, router, held


@pytest.mark.parametrize(
    "tokens,k,dtype,routing,bound",
    [
        (128, 4, jnp.float32, "mixed", "one"),
        (200, 4, jnp.float32, "mixed", "fits"),
        (200, 4, jnp.float32, "mixed", "whole"),
        (300, 1, jnp.float32, "one", "one"),
        (130, 10, jnp.float32, "one", "fits"),
        (256, 10, jnp.float32, "mixed", "whole"),
        (300, 10, jnp.bfloat16, "mixed", "one"),
        (256, 4, jnp.bfloat16, "mixed", "fits"),
        (136, 1, jnp.bfloat16, "mixed", "whole"),
        (3, 10, jnp.bfloat16, "mixed", "one"),
        (3, 4, jnp.float32, "one", "one"),
    ],
    ids=lambda v: getattr(v, "__name__", str(v)),
)
def test_a_share_s_walk_is_the_gather_form_bit_for_bit(
    monkeypatch, tokens, k, dtype, routing, bound
):
    """One row fetched a held pair and a token's rows summed in float32 in
    the order of its choices: the same bits as k masked gathers, for tokens
    none of whose choices is held and tokens all of whose are, whole steps of
    tokens and not, a decode step's three, either branch of the ``lax.cond``."""
    h, chosen, weight, p, router, held = share_case(tokens, k, dtype, routing)
    assert gmm.row_slabs(h.shape[1], dtype) == (8, 8)
    here = np.asarray(chosen) < held
    if routing == "mixed" and tokens > 8:
        assert not here[0::4].any() and here[1::4].all()
    tile = gmm.tile_for(tokens * k, router)
    counts = np.bincount(np.asarray(chosen)[here], minlength=held)
    used = int((-(-counts // tile)).sum()) * tile
    whole = gmm.padded_rows(tokens * k, held + 1, tile)
    usual = {"one": whole, "fits": used, "whole": used - tile}[bound]
    assert bound == "one" or usual < whole
    monkeypatch.setattr(decoder_parts, "USUAL_ROWS", usual)
    got, load = decoder_parts.experts_grouped(h, chosen, weight, p, router, held=held)
    assert np.asarray(load)[:held].tolist() == counts.tolist()
    assert decoder_parts.layers_past_usual(np.asarray(load)[None], router) == (bound == "whole")
    want = gather_twin(h, chosen, weight, p, router, held)
    assert got.dtype == want.dtype == dtype and got.shape == (tokens, h.shape[1])
    assert np.array_equal(np.asarray(got, np.float32), np.asarray(want, np.float32))
    assert np.abs(np.asarray(want, np.float32)).max() > 0.1  # sums of something
    assert not np.asarray(got, np.float32)[~here.any(axis=1)].any()


@pytest.mark.parametrize(
    "width,dtype,slabs",
    [(2048, jnp.bfloat16, (8, 8)), (1024, jnp.float32, (8, 8)), (2048, jnp.float32, (16, 16)),
     (4096, jnp.bfloat16, (16, 16)), (1024, jnp.bfloat16, (0, 0)), (64, jnp.float32, (0, 0)),
     (2048, jnp.float16, (0, 0)), (2048, jnp.int8, (0, 0)),
     # since ISSUE 40: (the slab's stride, the sublanes a row fills)
     (6144, jnp.bfloat16, (24, 24)), (5120, jnp.bfloat16, (24, 20)),
     (2560, jnp.bfloat16, (16, 10)), (1280, jnp.float32, (16, 10)),
     (2304, jnp.bfloat16, (16, 9)), (512, jnp.float32, (0, 0)), (512, jnp.bfloat16, (0, 0)),
     (2112, jnp.bfloat16, (0, 0)), (2049, jnp.bfloat16, (0, 0))],
    ids=lambda v: getattr(v, "__name__", str(v)),
)
def test_rows_are_walked_only_where_a_row_is_whole_tiles_of_words(width, dtype, slabs):
    """A row of at least one whole tile of words is walked, its slab padded up
    to whole (8, 128) tiles; a row under one (the tiny presets), one that is
    not whole sublanes of words, a dtype with no packing: not walked."""
    assert gmm.row_slabs(width, dtype) == slabs


# -- the walk at a width that is no whole tile of words: a padded slab (ISSUE 40) ----------


def padded_case(dtype, held_share, tokens=200, k=4, seed=3):
    """A down product's inputs at a width whose row pads its slab (10 of 16
    sublanes: a scaled-down 5120), 12 of a router's 32 experts named held, the
    pairs ``mixed`` over them, all ``elsewhere`` or all ``here``."""
    rng = np.random.default_rng(seed)
    hidden = 2560 if dtype == jnp.bfloat16 else 1280
    width, held, router = 16, 12, 32
    choose = {"mixed": router, "elsewhere": router - held, "here": held}[held_share]
    chosen = np.stack([rng.permutation(choose)[:k] for _ in range(tokens)]).astype(np.int32)
    chosen += held if held_share == "elsewhere" else 0
    weight = jnp.asarray(rng.random((tokens, k)), jnp.float32)
    tile = gmm.tile_for(tokens * k, router)
    tables, here = gmm.route_layout_held(
        jnp.asarray(chosen).reshape(-1), weight.reshape(-1), held, tile
    )
    rows = tables[0].shape[0]
    x = jnp.asarray(rng.standard_normal((rows, width)), dtype)
    w = jnp.asarray(rng.standard_normal((held, width, hidden)) * 0.1, dtype)
    return x, w, tables, here, tile, (tokens, k, hidden)


@pytest.mark.parametrize("held_share", ["mixed", "elsewhere", "here"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=lambda v: v.__name__)
def test_a_padded_slab_s_walk_is_the_gather_form_bit_for_bit(dtype, held_share):
    """``grouped_expert_product(slabs=True)`` then ``held_rows_sum`` at a width
    whose slab is padded, against the column chunks and k masked gathers: the
    same bits, with every pad sublane of y holding NaN bits beforehand (the
    kernel leaves the pad unwritten: on the chip it holds whatever was there),
    with no pair held and with every pair held."""
    x, w, tables, here, tile, (tokens, k, hidden) = padded_case(dtype, held_share)
    _, row_of_pair, tile_expert, used, _, row_weight = tables
    slab, filled = gmm.row_slabs(hidden, dtype)
    assert (slab, filled) == (16, 10)
    here_np = np.asarray(here).reshape(tokens, k)
    assert {"mixed": 0 < here_np.mean() < 1, "elsewhere": not here_np.any(),
            "here": here_np.all()}[held_share]
    product = functools.partial(
        gmm.grouped_expert_product, x, w, tile_expert, used, row_weight=row_weight, tile=tile
    )
    y = product(slabs=True)
    assert y.shape == (x.shape[0] * slab, gmm.LANES)
    poison = np.asarray(y).copy().reshape(x.shape[0], slab, gmm.LANES)
    nan = np.uint32(0x7FC07FC0).view(poison.dtype)  # as a float32 and as either bfloat16
    poison[:, filled:] = nan
    poison[x.shape[0] - 1] = nan  # a row no pair holds
    got = gmm.held_rows_sum(
        jnp.asarray(poison.reshape(y.shape)), jnp.where(here, row_of_pair, -1), k=k, width=hidden
    )
    rows_of = jnp.where(here, row_of_pair, 0).reshape(tokens, k)
    want = masked_sums(product(out_chunks=2), rows_of, here.reshape(tokens, k)).astype(dtype)
    assert got.dtype == want.dtype == dtype and got.shape == (tokens, hidden)
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.isfinite(got).all() and np.array_equal(got, want)
    assert (np.abs(want).max() > 0.1) == (held_share != "elsewhere")


# -- the row tiles no pair fills move no block (ISSUE 43) -------------------------------------


@pytest.mark.parametrize(
    "tokens,k,dtype,routing",
    [(200, 4, jnp.float32, "mixed"), (256, 10, jnp.bfloat16, "mixed"),
     (130, 10, jnp.float32, "one"), (128, 4, jnp.bfloat16, "none")],
    ids=lambda v: getattr(v, "__name__", str(v)),
)
def test_a_share_s_sum_is_what_it_was_when_every_step_named_its_own_block(
    monkeypatch, tokens, k, dtype, routing
):
    """``experts_grouped`` with 12 of 32 experts held, its layout many times
    its tiles in use (a third of it, one tile, none): the sum, bit for bit,
    of the kernels whose every grid step fetched and wrote its own row tile's
    blocks (``grouped_expert_product`` before ISSUE 43)."""
    h, chosen, weight, p, router, held = share_case(tokens, k, dtype, routing.replace("none", "one"))
    if routing == "none":
        chosen = jnp.maximum(chosen, held)  # the one held choice goes elsewhere too
    got, load = decoder_parts.experts_grouped(h, chosen, weight, p, router, held=held)
    laid, in_use = decoder_parts.tiles_laid_and_in_use(np.asarray(load)[None], router)
    tile = gmm.tile_for(tokens * k, router)
    assert laid == gmm.padded_rows(tokens * k, held + 1, tile) // tile
    assert 0 <= in_use < laid / 2
    if routing != "mixed":  # every token's one held choice to ONE expert, or to none
        assert in_use == (-(-tokens // tile) if routing == "one" else 0)
    monkeypatch.setattr(gmm, "_row_block", lambda i, used: i)
    monkeypatch.setattr(gmm, "grouped_expert_product", gmm.grouped_expert_product.__wrapped__)
    want, _ = decoder_parts.experts_grouped(h, chosen, weight, p, router, held=held)
    assert got.dtype == want.dtype == dtype
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.array_equal(got, want) and (np.abs(want).max() > 0.1) == (routing != "none")


@pytest.mark.parametrize(
    "loads,experts,share,usual,expected",
    [
        # a quarter of 16 held, tiles of 32: 17 tiles the whole bound, 8 the usual load's
        ([[30, 30, 30, 30, 392]] * 3, 16, True, 256, (3 * 8, 3 * 4)),
        ([[128, 128, 128, 128, 0]] * 3, 16, True, 256, (3 * 21, 3 * 16)),  # past the usual
        ([[30, 30, 30, 30, 392], [200, 100, 100, 100, 12], [0, 0, 0, 33, 479]], 16, True, 256,
         (8 + 21 + 8, 4 + 19 + 2)),
        ([[30, 30, 30, 30, 392]] * 3, 16, True, 10**6, (3 * 21, 3 * 4)),  # one bound
        ([[0, 0, 0, 0, 512]], 16, True, 256, (8, 0)),  # no pair held
        # every expert of 8 held, tiles of 64: one bound of 8 + 8 tiles whatever the usual
        ([[100, 28, 0, 0, 384, 0, 0, 0]], 8, False, 256, (16, 2 + 1 + 6)),
        ([], 16, True, 256, (0, 0)),
    ],
    ids=["even", "all-held", "one-layer", "one-bound", "none-held", "no-share", "no-sparse-layer"],
)
def test_row_tiles_laid_and_in_use_are_counted_from_the_pairs_routed(
    monkeypatch, loads, experts, share, usual, expected
):
    monkeypatch.setattr(decoder_parts, "USUAL_ROWS", usual)
    load = np.asarray(loads, np.int32).reshape(len(loads), 5 if share else experts)
    assert decoder_parts.tiles_laid_and_in_use(load, experts, share) == expected


def primitives(jaxpr, scope=""):
    """(primitive name, the name stack it stands under) of every equation, the
    jaxprs of calls, branches and loops gone into."""
    for eqn in jaxpr.eqns:
        stack = scope + "/" + str(eqn.source_info.name_stack)
        # a jitted function called inside goes by its own name
        name = eqn.params.get("name") if "jaxpr" in eqn.params else None
        yield (name if isinstance(name, str) else eqn.primitive.name), stack
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from primitives(inner, stack)


@pytest.mark.parametrize("held", [None, 12], ids=["every-expert-held", "a-share"])
def test_only_a_share_is_walked_and_every_expert_held_keeps_the_gathers(held):
    """The first judge's way back is untouched: with every expert held the
    jaxpr holds k gathers a column chunk under ``experts_combine`` and no walk,
    at a width a share WOULD walk; a share holds the walk there and no gather."""
    h, chosen, weight, p, router, _ = share_case(64, 4, jnp.float32, "mixed")
    if held is None:
        p = {name: jnp.concatenate([w, w, w[:8]]) for name, w in p.items()}  # all 32 held
    traced = jax.make_jaxpr(
        lambda h, chosen, weight, p: decoder_parts.experts_grouped(
            h, chosen, weight, p, router, held=held
        )
    )(h, chosen, weight, p)
    found = list(primitives(traced.jaxpr))
    combine = [name for name, stack in found if "experts_combine" in stack]
    chunks = gmm.column_chunks(gmm.padded_rows(64 * 4, router, gmm.tile_for(64 * 4, router)), 1024, 4)
    walks = [name for name, _ in found if name == "held_rows_sum"]
    kernels = [name for name, _ in found if name == "grouped_expert_product"]
    assert len(kernels) == 2
    if held is None:
        assert combine.count("gather") == 4 * chunks == 4 and not walks
    else:
        assert "gather" not in combine and walks == ["held_rows_sum"]
        assert combine.count("held_rows_sum") == 1


@pytest.mark.parametrize(
    "loads,usual,expected",
    [
        ([[30, 30, 30, 30, 392]] * 3, 256, 0),  # even routing, a quarter held
        ([[128, 128, 128, 128, 0]] * 3, 256, 3),  # every pair to a held expert
        ([[30, 30, 30, 30, 392], [200, 100, 100, 100, 12], [0, 0, 0, 256, 256]], 256, 1),
        ([[128, 128, 128, 128, 0]] * 3, 10**6, 0),  # a layout with one bound
        ([], 256, 0),
    ],
    ids=["even", "all-held", "one-layer", "one-bound", "no-sparse-layer"],
)
def test_layers_past_the_usual_load_are_counted_from_the_pairs_routed(
    monkeypatch, loads, usual, expected
):
    monkeypatch.setattr(decoder_parts, "USUAL_ROWS", usual)
    load = np.asarray(loads, np.int32).reshape(len(loads), 5)
    assert decoder_parts.layers_past_usual(load, 16) == expected


@pytest.mark.parametrize("held", [4, 16], ids=["a-quarter-held", "every-pair-held"])
def test_judge_counts_the_layers_that_ran_over_the_whole_bound(monkeypatch, held):
    """``/metrics`` ``judge.expert_layers_whole_bound``: 0 where the routing
    is even over a router of which a quarter is held, every sparse layer of
    the dispatch where every pair goes to a held expert."""
    monkeypatch.setattr(decoder_parts, "USUAL_ROWS", 256)
    params = qwen3_next.init_params(jax.random.PRNGKey(0), C, held=held)
    judge = TpuJudge(
        "qwen3-next-test-tiny", params=params, tokenizer=tiny_tokenizer(), max_tokens=88
    )
    assert judge.stats()["expert_layers_whole_bound"] == 0
    judge.judge(candidates(6, np.random.default_rng(1)), "w1", [(1, 1.0)])
    stats = judge.stats()
    assert stats["expert_pairs_routed"] == 88 * C.num_experts_per_tok * C.num_layers
    assert stats["expert_layers_whole_bound"] == (C.num_layers if held == 16 else 0)
    # 88 tokens x 4 choices in tiles of 16: the usual load's 16 or the whole bound's 39
    assert stats["expert_tiles_laid"] == C.num_layers * (39 if held == 16 else 16)
    assert 0 < stats["expert_tiles_in_use"] <= stats["expert_tiles_laid"]
