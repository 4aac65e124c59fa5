"""The third judge (``models/glm_moe.py`` under a configuration with an indexer,
``model_type`` ``glm_moe_dsa``): a learned sparse selection (the indexer's
scores, the top ``index_topk`` keys a query, shared over the layers behind a
``full`` one) in front of latent attention, a share of a wider router's
experts held, behind ``POST /consensus`` ``scorer: judge``.

The oracle is the benchmark's own plain reference,
``bench/references/glm_moe_dsa_judge.py`` (float32 ``jax.numpy`` at
``highest``, ``lax.top_k`` for the selection, nothing of the program), loaded
by its path; the checkpoint is drawn here from the family's tensor list
(``bench/families/glm_moe_dsa.py``), on the CPU at the tiny preset, whose
``index_topk`` (32) is smaller than every sequence below.
"""

import dataclasses
import importlib.util
import json
import os

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from test_judge import candidates, tiny_tokenizer  # noqa: E402
from llm_weighted_consensus_tpu.models import decoder_parts, glm_moe  # noqa: E402
from llm_weighted_consensus_tpu.models import judge as judge_module  # noqa: E402
from llm_weighted_consensus_tpu.models.configs import (  # noqa: E402
    GLM_5_2, GLM_DSA_TEST_TINY, GLM_TEST_TINY,
)
from llm_weighted_consensus_tpu.models.judge import JUDGE_PRESETS, TpuJudge  # noqa: E402
from llm_weighted_consensus_tpu.ops import causal_attention as attn  # noqa: E402
from llm_weighted_consensus_tpu.ops import sparse_index as si  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
C = GLM_DSA_TEST_TINY
SEQ = 96
KINDS = {"indexer_types": list(C.indexer_types), "mlp_layer_types": ["dense"] + ["sparse"] * 4}


def bench_file(directory, name):
    path = os.path.join(ROOT, "bench", directory, name + ".py")
    spec = importlib.util.spec_from_file_location(f"tier1_{directory}_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


reference = bench_file("references", "glm_moe_dsa_judge")
family = bench_file("families", "glm_moe_dsa")


def hf_config(config=C, held=None, **changed) -> dict:
    layers = changed.get("num_hidden_layers", config.num_layers)
    return {
        "vocab_size": config.vocab_size,
        "hidden_size": config.hidden_size,
        "num_hidden_layers": layers,
        "num_attention_heads": config.num_heads,
        "q_lora_rank": config.q_lora_rank,
        "kv_lora_rank": config.kv_lora_rank,
        "qk_nope_head_dim": config.qk_nope_head_dim,
        "qk_rope_head_dim": config.qk_rope_head_dim,
        "v_head_dim": config.v_head_dim,
        "intermediate_size": config.intermediate_size,
        "moe_intermediate_size": config.moe_intermediate_size,
        "n_routed_experts": held or config.n_routed_experts,
        "n_routed_experts_routed": config.n_routed_experts,
        "num_experts_per_tok": config.num_experts_per_tok,
        "n_shared_experts": config.n_shared_experts,
        "routed_scaling_factor": config.routed_scaling_factor,
        "rope_parameters": {"rope_theta": config.rope_theta, "rope_type": "default"},
        "rms_norm_eps": config.rms_norm_eps,
        "index_n_heads": config.index_n_heads,
        "index_head_dim": config.index_head_dim,
        "index_topk": config.index_topk,
        "layers_served": list(range(layers)),
        **KINDS,
        **changed,
    }


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
    """Experts 0..7 of a router 16 wide: a share."""
    return random_state(hf_config(held=8), seed=3)


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


def centred(x):
    x = np.asarray(x, np.float64)
    return x - x.mean(axis=-1, keepdims=True)


def top_k_rows(scores, k):
    """The rule by ``lax.top_k`` a row: [.., s, s] bool."""
    scores = np.asarray(scores)
    out = np.zeros(scores.shape, bool)
    for index in np.ndindex(scores.shape[:-1]):
        t = index[-1]
        _, chosen = jax.lax.top_k(jnp.asarray(scores[index][: t + 1]), min(k, t + 1))
        out[index][np.asarray(chosen)] = True
    return out


# -- the decoder against the plain reference -----------------------------------------------


def test_prefill_logits_match_the_reference(state, loaded, prompts):
    params, config = loaded
    ids, lens = prompts
    assert config.indexer_types == C.indexer_types and config.first_k_dense_replace == 1
    assert [len(c) for c in glm_moe.prefill(params, jnp.asarray(ids), config)[1]] == [3, 2, 2, 2, 3]
    hidden, _, loads = glm_moe.prefill(params, jnp.asarray(ids), config, lens=jnp.asarray(lens))
    cfg = hf_config(held=8)
    every = list(range(C.vocab_size))
    calls = [(ids[row, :n].tolist(), [n - 1, n // 2, 40]) for row, n in enumerate(lens)]
    for row, want in enumerate(reference.read_logits(state, cfg, calls, every)):
        at = jnp.asarray(calls[row][1])
        got = glm_moe.head_logprobs(params, hidden[row][at], config)
        assert np.abs(centred(got) - centred(want)).max() < 2e-5
    loads = np.asarray(loads)
    assert loads.shape == (4, 9) and (loads.sum(axis=1) == 3 * SEQ * C.num_experts_per_tok).all()


def test_decode_through_the_three_caches_matches_the_full_forward(state, loaded, prompts):
    """Prefill leaves (latent, rotary key) a layer and the index keys on the
    layers that own an indexer; the decoded token chooses ``index_topk`` of
    the positions it sees from them, the layers behind attend its choice, and
    the head reads what ONE forward over T + 1 tokens reads at position T."""
    params, config = loaded
    ids, lens = prompts
    token = np.array([11, 200, 57], np.int32)
    _, caches, _ = glm_moe.prefill(params, jnp.asarray(ids), config, lens=jnp.asarray(lens))
    assert caches[0][2].shape == (3, SEQ, C.index_head_dim) and caches[4][2].shape == caches[0][2].shape
    step = glm_moe.decode_step(params, jnp.asarray(token), jnp.asarray(lens), caches, config)
    got = glm_moe.head_logprobs(params, step, config)
    cfg = hf_config(held=8)
    calls = [(ids[row, :n].tolist() + [int(token[row])], [int(n)]) for row, n in enumerate(lens)]
    for row, want in enumerate(reference.read_logits(state, cfg, calls, list(range(C.vocab_size)))):
        assert np.abs(centred(got[row]) - centred(want[0])).max() < 2e-5


def test_a_padded_slot_moves_no_real_query(loaded, prompts):
    params, config = loaded
    ids, lens = prompts
    other = ids.copy()
    for row, n in enumerate(lens):
        other[row, n:] = 7 + row
    a, _, _ = glm_moe.prefill(params, jnp.asarray(ids), config)
    b, _, _ = glm_moe.prefill(params, jnp.asarray(other), config)
    for row, n in enumerate(lens):
        assert np.array_equal(np.asarray(a[row, :n]), np.asarray(b[row, :n]))


def test_a_shared_layer_attends_over_exactly_its_full_layer_s_set(state, loaded, prompts, monkeypatch):
    """Layers 1, 2 and 3 are handed the very selection layer 0 made, layer 4
    makes its own, and each is the reference's set for that layer."""
    params, config = loaded
    ids, _ = prompts
    seen = []
    kernel = glm_moe.causal_attention_blockwise

    def spy(q, k, v, keep=None, **kw):
        seen.append(keep)
        return kernel(q, k, v, keep, **kw)

    monkeypatch.setattr(glm_moe, "causal_attention_blockwise", spy)
    glm_moe.prefill(params, jnp.asarray(ids[2:]), config)
    assert len(seen) == 5 and all(keep is not None and keep.dtype == jnp.int8 for keep in seen)
    assert seen[1] is seen[0] and seen[2] is seen[0] and seen[3] is seen[0]
    assert seen[4] is not seen[0] and not np.array_equal(np.asarray(seen[4]), np.asarray(seen[0]))
    selections = []
    reference.hidden_states(state, hf_config(held=8), [ids[2].tolist()], selections)
    for layer, keep in enumerate(seen):
        want = selections[layer][0][:SEQ, :SEQ]
        assert np.array_equal(np.asarray(keep[0]) != 0, want), layer
        assert want.sum(axis=1).tolist() == [min(C.index_topk, t + 1) for t in range(SEQ)]


def test_a_decoder_without_an_indexer_is_handed_no_selection(monkeypatch):
    seen = []
    kernel = glm_moe.causal_attention_blockwise
    monkeypatch.setattr(
        glm_moe, "causal_attention_blockwise",
        lambda q, k, v, keep=None, **kw: seen.append(keep) or kernel(q, k, v, keep, **kw),
    )
    params = glm_moe.init_params(jax.random.PRNGKey(0), GLM_TEST_TINY)
    tallies = {}
    glm_moe.prefill(params, jnp.zeros((1, 32), jnp.int32), GLM_TEST_TINY, tallies=tallies)
    assert seen == [None] * GLM_TEST_TINY.num_layers and tallies == {}
    assert all("indexer" not in layer["attn"] for layer in params["layers"])


# -- the selection against lax.top_k -------------------------------------------------------


def quantised(rng, shape, step):
    """Scores on a coarse grid: equals everywhere, the boundary among them."""
    return np.round(rng.standard_normal(shape) / step).astype(np.float32) * step


@pytest.mark.parametrize(
    "s,k,step",
    [
        (128, 128, 0),  # every query before position k: t < k and t = k - 1
        (256, 64, 0),  # whole blocks
        (256, 64, 0.5),  # crafted ties at the boundary
        (200, 40, 0.25),  # not whole lane tiles: the keys are padded
        (448, 100, 0),  # the dry run's bucket: blocks of 64 rows
        (448, 447, 1.0),  # k one short of the sequence
        (96, 200, 0.5),  # k past the sequence
    ],
)
def test_the_choice_is_lax_top_k_s(s, k, step):
    rng = np.random.default_rng(s + k)
    scores = quantised(rng, (2, s, s), step) if step else rng.standard_normal((2, s, s)).astype(np.float32)
    want = top_k_rows(scores, k)
    keep = np.asarray(si.index_select(jnp.asarray(scores), k=k))
    assert keep.dtype == np.int8 and keep.shape == (2, s, s)
    assert np.array_equal(keep != 0, want)
    assert (keep.sum(axis=2) == np.minimum(k, np.arange(s) + 1)).all()
    tri = np.tril(np.ones((s, s), bool))
    assert not (keep != 0)[:, ~tri].any()  # a key past its query, a padded slot: never
    dense = si.select_topk_dense(jnp.asarray(scores), jnp.asarray(tri)[None], k)
    assert np.array_equal(np.asarray(dense), want)


def test_equals_at_the_boundary_go_to_the_lower_index():
    s, k = 128, 4
    scores = np.full((1, s, s), -1.0, np.float32)
    scores[0, :, 5] = 3.0  # over the boundary
    scores[0, :, [9, 20, 21, 40, 90]] = 2.0  # five equals for three places
    keep = np.asarray(si.index_select(jnp.asarray(scores), k=k))[0] != 0
    assert np.where(keep[127])[0].tolist() == [5, 9, 20, 21]
    assert np.where(keep[20])[0].tolist() == [0, 5, 9, 20]  # -1.0 at 0 before -1.0 at 1
    assert np.where(keep[3])[0].tolist() == [0, 1, 2, 3]
    assert np.array_equal(keep, top_k_rows(scores, k)[0])


def test_what_lies_past_the_query_is_never_read():
    """``index_scores`` writes the lower triangle's blocks only: whatever a
    slot past the query holds, NaN or an infinity, the choice is the same."""
    rng = np.random.default_rng(0)
    s, k = 256, 32
    scores = rng.standard_normal((1, s, s)).astype(np.float32)
    spoiled = scores.copy()
    upper = ~np.tril(np.ones((s, s), bool))
    spoiled[0][upper] = np.where(rng.random(upper.sum()) < 0.5, np.nan, np.inf)
    a = np.asarray(si.index_select(jnp.asarray(scores), k=k))
    b = np.asarray(si.index_select(jnp.asarray(spoiled), k=k))
    assert np.array_equal(a, b)


def test_a_decoded_token_chooses_among_the_positions_it_sees():
    rng = np.random.default_rng(2)
    scores = quantised(rng, (3, 97), 0.5)
    lens = np.array([96, 40, 10])
    t = np.arange(97)[None, :]
    seen = (t < lens[:, None]) | (t == 96)
    got = np.asarray(si.select_topk_dense(jnp.asarray(scores), jnp.asarray(seen), 32))
    assert got.sum(axis=1).tolist() == [32, 32, 11] and not got[~seen].any()
    for row in range(3):
        at = np.where(seen[row])[0]
        _, best = jax.lax.top_k(jnp.asarray(scores[row, at]), min(32, len(at)))
        assert sorted(at[np.asarray(best)].tolist()) == np.where(got[row])[0].tolist()


# -- the new kernels in interpret mode against their jnp forms ---------------------------------


@pytest.mark.parametrize(
    "b,s,heads,dim,block", [(2, 128, 4, 16, 0), (1, 448, 2, 32, 0), (1, 256, 3, 16, 64)]
)
def test_index_scores_kernel_is_its_einsum_on_the_lower_triangle(b, s, heads, dim, block):
    rng = np.random.default_rng(s)
    q = jnp.asarray(rng.standard_normal((b, s, heads * dim)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, s, dim)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((b, s, heads)), jnp.float32)
    got = np.asarray(si.index_scores(q, k, w, heads=heads, block=block))
    want = np.asarray(si.index_scores_einsum(q, k, w, heads=heads))
    tri = np.tril(np.ones((s, s), bool))
    assert np.abs(np.where(tri, got - want, 0.0)).max() < 1e-4
    per_head = np.einsum("bthd,bsd->bths", np.asarray(q).reshape(b, s, heads, dim), np.asarray(k))
    plain = (np.asarray(w)[..., None] * np.maximum(per_head, 0.0)).sum(axis=2)
    assert np.abs(want - plain).max() < 1e-4


@pytest.mark.parametrize(
    "s,block,heads,hd",
    [(128, 0, 2, 32), (448, 0, 4, 32), (512, 512, 1, 128), (512, 128, 2, 128)],
    ids=["one-tile", "blocks-of-64", "stripes", "lanes"],
)
def test_attention_over_a_selection_is_its_einsum(s, block, heads, hd):
    rng = np.random.default_rng(s + hd)
    q, k, v = (jnp.asarray(rng.standard_normal((2, s, heads * hd)), jnp.float32) for _ in range(3))
    keep = jnp.asarray(top_k_rows(rng.standard_normal((2, s, s)), 48).astype(np.int8))
    kw = dict(heads=heads, scale=hd**-0.5)
    got = attn.causal_attention_blockwise(q, k, v, keep, block_q=block, block_k=block, **kw)
    want = attn.causal_attention_einsum(q, k, v, keep, **kw)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 2e-5
    causal = attn.causal_attention_einsum(q, k, v, **kw)
    assert np.abs(np.asarray(want) - np.asarray(causal))[:, 48:].max() > 1e-3  # it chose


def test_a_query_whose_first_key_block_holds_none_of_its_keys():
    """The running maximum starts below every score: a block of a row's keys
    with nothing chosen in it leaves nothing behind once a chosen key comes."""
    s, hd = 256, 32
    rng = np.random.default_rng(5)
    q, k, v = (jnp.asarray(rng.standard_normal((1, s, hd)), jnp.float32) for _ in range(3))
    keep = np.tril(np.ones((s, s), np.int8))
    keep[128:, :128] = 0  # the late queries chose no early key
    keep = jnp.asarray(keep[None])
    got = attn.causal_attention_blockwise(q, k, v, keep, heads=1, scale=0.2, block_q=64, block_k=64)
    want = attn.causal_attention_einsum(q, k, v, keep, heads=1, scale=0.2)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 2e-5


# -- the share of the experts ---------------------------------------------------------------


def share_of(state: dict, experts: list, order: list) -> dict:
    """A checkpoint that names ``experts`` (renumbered from 0) of ``state``'s,
    its routers' rows in ``order`` (the held ones first)."""
    out = {}
    for name, value in state.items():
        if ".mlp.gate." in name:
            value = value[order]
        if ".mlp.experts." in name:
            head, rest = name.split(".mlp.experts.")
            e, kind = rest.split(".", 1)
            if int(e) not in experts:
                continue
            name = f"{head}.mlp.experts.{experts.index(int(e))}.{kind}"
        out[name] = value
    return out


@pytest.mark.parametrize("chips", [2, 4])
def test_the_shares_add_up_to_the_uncut_reference_layer(chips):
    """The router is 16 wide and takes 2 a token; ``chips`` chips hold 16 /
    chips experts each.  Chip c's layer gives the sum over the pairs whose
    expert it holds plus the shared expert; the partial sums, the shared
    expert counted once, are the reference's whole layer with every expert
    held.  (A chip holds experts 0..E-1 of ITS numbering: the router's rows
    are permuted so that its experts come first.)"""
    cfg = hf_config()  # every expert held: the uncut layer
    whole_state = random_state(cfg, seed=9)
    rng = np.random.default_rng(7)
    h = (rng.standard_normal((64, C.hidden_size)) * 0.5).astype(np.float32)
    _, _, sparse = reference.functions(cfg)
    with jax.default_matmul_precision("highest"):
        whole = np.asarray(sparse(jnp.asarray(h), reference.layer_weights(whole_state, cfg, 1)[1]))
    each = 16 // chips
    total, pairs_here = np.zeros_like(whole), 0
    for chip in range(chips):
        mine = list(range(each * chip, each * chip + each))
        order = mine + [e for e in range(16) if e not in mine]
        params, config = glm_moe.from_hf_weights(share_of(whole_state, mine, order), C)
        assert glm_moe.experts_held(params, config) == each
        moe = params["layers"][1]["moe"]
        got, counts = glm_moe._moe(jnp.asarray(h), moe, config)
        shared = np.asarray(decoder_parts.swiglu(jnp.asarray(h), moe["shared"]))
        total += np.asarray(got) - (shared if chip else 0.0)
        counts = np.asarray(counts)
        assert counts.shape == (each + 1,) and counts.sum() == 64 * C.num_experts_per_tok
        pairs_here += counts[:each].sum()
        # the reference given the same share says the same
        part = hf_config(held=each)
        with jax.default_matmul_precision("highest"):
            want = sparse_of(part)(
                jnp.asarray(h), reference.layer_weights(share_of(whole_state, mine, order), part, 1)[1]
            )
        assert np.abs(np.asarray(got) - np.asarray(want)).max() < 2e-6
    assert pairs_here == 64 * C.num_experts_per_tok  # every pair held somewhere, once
    assert np.abs(total - whole).max() < 5e-6


def sparse_of(cfg):
    return reference.functions(cfg)[2]


def test_a_checkpoint_names_its_stage_its_share_and_its_slice_of_the_vocabulary():
    """No variable says any of it: five layers of 78 named from 0 (the kinds
    read off the names), experts 0..7 of 16, 256 rows of the vocabulary."""
    published = dataclasses.replace(
        C, num_layers=78, first_k_dense_replace=3, vocab_size=4096,
        indexer_types=GLM_5_2.indexer_types,
    )
    cfg = hf_config(held=8, vocab_size=256)
    state = random_state(cfg, seed=1)
    params, served = glm_moe.from_hf_weights(state, published)
    assert (served.num_layers, served.first_k_dense_replace, served.vocab_size) == (5, 1, 256)
    assert served.indexer_types == ("full", "shared", "shared", "shared", "full")
    assert served.n_routed_experts == 16 and glm_moe.experts_held(params, served) == 8
    assert ["indexer" in layer["attn"] for layer in params["layers"]] == [True, False, False, False, True]
    assert ["mlp" in layer for layer in params["layers"]] == [True, False, False, False, False]
    assert params["token_embed"].shape == (256, C.hidden_size)
    assert params["layers"][1]["moe"]["router"].shape == (C.hidden_size, 16)
    ids = np.random.default_rng(0).integers(4, 256, size=(1, 64)).astype(np.int32)
    hidden, _, loads = glm_moe.prefill(params, jnp.asarray(ids), served)
    want = reference.read_logits(state, cfg, [(ids[0].tolist(), [63])], list(range(256)))[0]
    got = glm_moe.head_logprobs(params, hidden[:, 63], served)
    assert np.abs(centred(got) - centred(want)).max() < 2e-5
    loads = np.asarray(loads)
    assert 0 < loads[:, :8].sum() < loads.sum()  # some here, some elsewhere


def test_a_stage_whose_first_layer_owns_no_indexer_is_refused(state):
    headless = {k: v for k, v in state.items() if "layers.0.self_attn.indexer" not in k}
    with pytest.raises(ValueError, match="owns no indexer"):
        glm_moe.from_hf_weights(headless, C)


def test_the_first_judge_s_checkpoint_loads_as_it_did():
    first = bench_file("families", "glm4_moe_lite")
    cfg = {
        **hf_config(GLM_TEST_TINY, num_hidden_layers=2), "first_k_dense_replace": 1,
        "n_routed_experts": GLM_TEST_TINY.n_routed_experts,
    }
    rng = np.random.default_rng(0)
    state = {n: rng.standard_normal(s).astype(np.float32) * 0.02 for n, s, _ in first.tensors(cfg)}
    params, served = glm_moe.from_hf_weights(state, GLM_TEST_TINY)
    assert served == dataclasses.replace(GLM_TEST_TINY, num_layers=2)
    assert glm_moe._held(params["layers"][1]["moe"], served) is None
    assert glm_moe.whole_bound_layers(np.zeros((1, 8), np.int32), served) == 0


@pytest.mark.parametrize(
    "pairs,experts,held,want",
    [
        (3 * 8192 * 10, 512, 128, 114_688),  # the second judge: the cap, as it was
        (3 * 8192 * 8, 256, 16, 49_152),  # a sixteenth held: four times its even load
        (3 * 8192 * 8, 256, 256, 114_688),
    ],
)
def test_a_share_s_usual_rows_follow_its_share(pairs, experts, held, want):
    assert decoder_parts.usual_rows(pairs, experts, held, 256) == want
    assert want % 256 == 0


# -- the judge: presets, counters, the gateway ---------------------------------------------


def test_presets_name_the_third_decoder():
    assert judge_module.decoder_of(JUDGE_PRESETS["glm-5.2"]) is glm_moe
    assert JUDGE_PRESETS["glm-dsa-test-tiny"] is C
    p = JUDGE_PRESETS["glm-5.2"]
    assert (p.hidden_size, p.num_heads, p.q_lora_rank, p.kv_lora_rank) == (6144, 64, 2048, 512)
    assert (p.qk_nope_head_dim, p.qk_rope_head_dim, p.v_head_dim) == (192, 64, 256)
    assert (p.intermediate_size, p.moe_intermediate_size) == (12288, 2048)
    assert (p.n_routed_experts, p.num_experts_per_tok, p.routed_scaling_factor) == (256, 8, 2.5)
    assert (p.index_n_heads, p.index_head_dim, p.index_topk) == (32, 128, 2048)
    assert (p.num_layers, p.first_k_dense_replace, p.rope_theta) == (78, 3, 8e6)
    with open(os.path.join(ROOT, "bench", "configs", "glm-5.2.json"), encoding="utf-8") as f:
        published = json.load(f)
    assert list(p.indexer_types) == published["indexer_types"]
    assert [published["indexer_types"][i] for i in published["layers_served"]] == list(C.indexer_types)
    assert JUDGE_PRESETS["glm-4.7-flash"].index_topk == 0


@pytest.fixture(scope="module")
def judge():
    # a bucket of its own: the dispatch label's count is the process's
    return TpuJudge("glm-dsa-test-tiny", tokenizer=tiny_tokenizer(), max_tokens=432, seed=2)


def test_judge_counts_the_keys_seen_and_chosen(judge):
    before = judge.stats()
    confidence, tokens, ballots = judge.judge(
        candidates(24, np.random.default_rng(3)), "w7 w8 w9", [(5, 3.0), (6, 2.0), (7, 1.0)]
    )
    assert len(confidence) == 24 and abs(confidence.sum() - 1.0) < 1e-6 and len(ballots) == 3
    stats = judge.stats()
    s, k, owners = judge.max_tokens, C.index_topk, 2
    causal = owners * 3 * s * (s + 1) // 2
    chosen = owners * 3 * (k * (k + 1) // 2 + (s - k) * k)
    assert stats["index_keys_causal"] - before["index_keys_causal"] == causal
    assert stats["index_keys_selected"] - before["index_keys_selected"] == chosen
    cfg = hf_config()
    assert chosen == owners * 3 * family.selected_pairs(cfg, s)
    assert causal == owners * 3 * family.causal_pairs(s)
    assert len(stats["expert_tokens"]) == 16 and stats["expert_pairs_elsewhere"] == 0


def test_a_judge_holding_a_share_counts_the_pairs_elsewhere():
    params = glm_moe.init_params(jax.random.PRNGKey(0), C, held=4)
    held = TpuJudge("glm-dsa-test-tiny", params=params, tokenizer=tiny_tokenizer(), max_tokens=96)
    held.judge(candidates(6, np.random.default_rng(1)), "w1", [(1, 1.0)])
    stats = held.stats()
    total = 96 * C.num_experts_per_tok * 4
    assert len(stats["expert_tokens"]) == 4
    assert stats["expert_pairs_here"] + stats["expert_pairs_elsewhere"] == total
    assert 0 < stats["expert_pairs_here"] < total
    assert sum(stats["expert_tokens"]) == stats["expert_pairs_here"]
    assert stats["expert_layers_whole_bound"] == 0
    assert 0 < stats["expert_tiles_in_use"] < stats["expert_tiles_laid"]


def test_the_first_judge_keeps_no_selection_counters_running():
    first = TpuJudge("glm-test-tiny", tokenizer=tiny_tokenizer(), max_tokens=64)
    first.judge(candidates(4, np.random.default_rng(1)), "w1", [(1, 1.0)])
    stats = first.stats()
    assert stats["index_keys_causal"] == 0 and stats["index_keys_selected"] == 0


def test_int8_control_reaches_the_indexer_s_products():
    base = TpuJudge("glm-dsa-test-tiny", tokenizer=tiny_tokenizer(), max_tokens=SEQ, seed=2)
    low = TpuJudge(
        "glm-dsa-test-tiny", tokenizer=tiny_tokenizer(), max_tokens=SEQ, seed=2, quantize="int8"
    )
    indexer = low.params["layers"][0]["attn"]["indexer"]
    assert "kernel_q" in indexer["q"] and "kernel_q" in indexer["k"]
    assert "kernel_q" in low.params["layers"][0]["attn"]["q_b"]
    assert "kernel_q" in low.params["layers"][1]["moe"]["shared"]["up"]
    assert indexer["w"].dtype == jnp.float32 and "kernel_q" not in low.params["layers"][1]["moe"]
    texts = candidates(8, np.random.default_rng(0))
    a, _, ba = base.judge(texts, "w5", [(1, 1.0)])
    b, _, bb = low.judge(texts, "w5", [(1, 1.0)])
    assert abs(b.sum() - 1.0) < 1e-6 and set(ba[0]["siblings"]) == set(bb[0]["siblings"])
    assert np.abs(a - b).max() > 0


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
        assert body["scorer"] == "judge" and body["model"] == "glm-dsa-test-tiny"
        assert len(body["confidence"]) == 21
        assert sum(body["confidence"]) == pytest.approx(1.0, abs=1e-6)
        assert [b["seed"] for b in body["ballots"]] == [7, 8]
        for ballot in body["ballots"]:
            assert set(ballot) == {"seed", "weight", "first", "key", "siblings"}
        metrics = await (await client.get("/metrics")).json()
        assert metrics["roofline"]["buckets"]["judge(n=2,s=432)"]["count"] >= 1
        assert metrics["judge"]["dispatches"] == dispatched + 1
        assert metrics["judge"]["model"] == "glm-dsa-test-tiny"
        chosen, causal = metrics["judge"]["index_keys_selected"], metrics["judge"]["index_keys_causal"]
        assert 0 < chosen < causal
        for key in ("expert_pairs_routed", "expert_pairs_here"):
            assert key in metrics["judge"]

    go(with_client(app, drive))


def test_build_judge_knows_the_presets(monkeypatch):
    from llm_weighted_consensus_tpu.serve import Config
    from llm_weighted_consensus_tpu.serve.__main__ import build_judge

    monkeypatch.delenv("LWC_ALLOW_RANDOM_PARAMS", raising=False)
    config = Config.from_env({"JUDGE_MODEL": "glm-dsa-test-tiny", "JUDGE_MAX_TOKENS": "64"})
    with pytest.raises(ValueError, match="JUDGE_WEIGHTS"):
        build_judge(config)
    built = build_judge(config, allow_synthetic=True)
    assert built.max_tokens == 64 and built.decoder is glm_moe and built.config.index_topk == 32
    with pytest.raises(ValueError, match="glm-5.2"):
        build_judge(Config.from_env({"JUDGE_MODEL": "glm-5"}))


def test_a_checkpoint_on_disk_is_served_as_it_names(tmp_path):
    from safetensors.numpy import save_file

    from llm_weighted_consensus_tpu.models.judge import load_judge_params

    cfg = hf_config(held=8, vocab_size=128)
    save_file(random_state(cfg, seed=4), str(tmp_path / "model.safetensors"))
    params, config = load_judge_params(str(tmp_path), GLM_5_2_TINY_WIDE, dtype=jnp.float32)
    assert (config.num_layers, config.vocab_size) == (5, 128)
    assert glm_moe.experts_held(params, config) == 8


GLM_5_2_TINY_WIDE = dataclasses.replace(
    C, num_layers=78, first_k_dense_replace=3, indexer_types=GLM_5_2.indexer_types
)


# -- the family's counts (the benchmark's yardstick) ---------------------------------------


def test_the_family_counts_the_pairs_of_the_cell():
    with open(os.path.join(ROOT, "bench", "configs", "glm-5.2.json"), encoding="utf-8") as f:
        cfg = json.load(f)
    assert family.selected_pairs(cfg, 8192) == 14_681_088
    assert family.causal_pairs(8192) == 33_558_528
    assert 100 * 14_681_088 / 33_558_528 == pytest.approx(43.75, abs=0.01)
    names = [name for name, _, _ in family.tensors(cfg)]
    assert sum(".indexer.wq_b" in n for n in names) == 2 and sum(".mlp.gate.weight" in n for n in names) == 4
    assert sum(".mlp.experts." in n for n in names) == 4 * 16 * 3
    params = sum(int(np.prod(shape)) for _, shape, _ in family.tensors(cfg))
    assert 7.7e9 < 2 * params < 7.8e9  # 7.76 GB of bf16
