"""The fifth judge (``models/afmoe.py``, ``model_type`` ``afmoe``):
grouped-query attention of TWO kinds in one stack (sliding layers that turn
their heads over a window, full layers that turn nothing), six query heads a
key head, an elementwise sigmoid gate, four norms a layer (the two behind the
branches BEFORE the sums), a sigmoid router with a bias that chooses and does
not weigh, a share of a wider router's experts held, behind ``POST
/consensus`` ``scorer: judge``.

The oracle is the benchmark's own plain reference,
``bench/references/afmoe_judge.py`` (float32 ``jax.numpy`` at ``highest``,
whole mask rows, nothing of the program), loaded by its path; the checkpoint is
drawn here from the family's tensor list (``bench/families/afmoe.py``), on the
CPU at the tiny preset: the cell's order of layers (sliding dense; sliding,
full, sliding, sliding sparse) under their PUBLISHED numbers 5 to 9 of a
pattern of twelve, a window (24) shorter than most sequences below and no
multiple of a block.

Tolerances.  Program and reference are both float32 here and differ in the
order of their sums only (a blockwise online softmax against whole rows, a
grouped product against ``ragged_dot``): centred logits agree to 2e-5 (they
read 2e-6 to 6e-6).  The same parameters rounded to bfloat16 read 1e-2 and
more, so bfloat16 in place of float32 fails every comparison below
(``test_bfloat16_in_place_of_float32_fails_the_tolerance``).
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
from llm_weighted_consensus_tpu.models import afmoe, decoder_parts  # noqa: E402
from llm_weighted_consensus_tpu.models import judge as judge_module  # noqa: E402
from llm_weighted_consensus_tpu.models.configs import (  # noqa: E402
    AFMOE_TEST_TINY, TRINITY_LARGE_PREVIEW, AfmoeConfig,
)
from llm_weighted_consensus_tpu.models.judge import JUDGE_PRESETS, TpuJudge  # noqa: E402
from llm_weighted_consensus_tpu.ops import causal_attention as attn  # noqa: E402
from llm_weighted_consensus_tpu.ops import head_norm  # noqa: E402
from llm_weighted_consensus_tpu.ops import grouped_matmul as gmm  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
C = AFMOE_TEST_TINY
SEQ = 96
TOL = 2e-5
FULL, SLIDING = "full_attention", "sliding_attention"
# a published pattern of twelve layers, six leading dense ones, of which the
# stage serves 5 to 9 (the cell's cut in small): S S S F | S [S S F S S] S F
PATTERN = tuple(FULL if n % 4 == 3 else SLIDING for n in range(12))
SERVED = [5, 6, 7, 8, 9]
WIDE = dataclasses.replace(C, num_layers=12, num_dense_layers=6, layer_types=PATTERN)


def bench_file(directory, name):
    path = os.path.join(ROOT, "bench", directory, name + ".py")
    spec = importlib.util.spec_from_file_location(f"tier1_{directory}_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


reference = bench_file("references", "afmoe_judge")
family = bench_file("families", "afmoe")


def hf_config(config=C, held=None, **changed) -> dict:
    """The configuration as ``config.json`` keys it: the stage's five layers
    under their published numbers, the published pattern whole."""
    out = {
        "vocab_size": config.vocab_size,
        "hidden_size": config.hidden_size,
        "num_hidden_layers": len(SERVED),
        "num_attention_heads": config.num_heads,
        "num_key_value_heads": config.num_kv_heads,
        "head_dim": config.head_dim,
        "intermediate_size": config.intermediate_size,
        "moe_intermediate_size": config.moe_intermediate_size,
        "num_dense_layers": 1,
        "num_experts": held or config.num_experts,
        "num_experts_routed": config.num_experts,
        "num_experts_per_tok": config.num_experts_per_tok,
        "num_shared_experts": config.num_shared_experts,
        "route_scale": config.route_scale,
        "rms_norm_eps": config.rms_norm_eps,
        "rope_theta": config.rope_theta,
        "sliding_window": config.sliding_window,
        "mup_enabled": config.mup_enabled,
        "layer_types": list(PATTERN),
        "layers_served": list(SERVED),
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


@pytest.fixture(scope="module", params=[8, None], ids=["a-share", "every-expert"])
def held(request):
    return request.param


@pytest.fixture(scope="module")
def state(held):
    """Experts 0..7 of a router 16 wide (a share), or all sixteen."""
    return random_state(hf_config(held=held), seed=3)


@pytest.fixture(scope="module")
def loaded(state):
    return afmoe.from_hf_weights(state, WIDE)


@pytest.fixture(scope="module")
def prompts():
    """Lengths on both sides of the window (24): far above it and off every
    block (90), below it (13), the window's own reach (23: the gathered cache
    is filled exactly, from position 0), one past it (24) and the bucket."""
    rng = np.random.default_rng(1)
    lens = np.array([90, 13, 23, 24, SEQ], np.int32)
    ids = np.zeros((len(lens), SEQ), np.int32)
    for row, n in enumerate(lens):
        ids[row, :n] = rng.integers(4, C.vocab_size, size=n)
    return ids, lens


def centred(x):
    x = np.asarray(x, np.float64)
    return x - x.mean(axis=-1, keepdims=True)


EVERY = list(range(C.vocab_size))


def prefill_error(params, config, state, cfg, prompts) -> float:
    """The largest |centred program logits - centred reference logits| over a
    few real positions of every call."""
    ids, lens = prompts
    hidden, _, _ = afmoe.prefill(params, jnp.asarray(ids), config, lens=jnp.asarray(lens))
    calls = [
        (ids[row, :n].tolist(), sorted({n - 1, n // 2, min(40, n - 1), 0}))
        for row, n in enumerate(lens)
    ]
    worst = 0.0
    for row, want in enumerate(reference.read_logits(state, cfg, calls, EVERY)):
        got = afmoe.head_logprobs(params, hidden[row][jnp.asarray(calls[row][1])], config)
        worst = max(worst, float(np.abs(centred(got) - centred(want)).max()))
    return worst


# -- the decoder against the plain reference -----------------------------------------------


def test_prefill_logits_match_the_reference(state, loaded, prompts, held):
    params, config = loaded
    assert config.layer_types == tuple(PATTERN[n] for n in SERVED)
    assert (config.num_layers, config.num_dense_layers) == (5, 1)
    assert prefill_error(params, config, state, hf_config(held=held), prompts) < TOL
    ids, lens = prompts
    _, _, loads = afmoe.prefill(params, jnp.asarray(ids), config, lens=jnp.asarray(lens))
    loads = np.asarray(loads)
    assert loads.shape == (4, 9 if held else 16)
    assert (loads.sum(axis=1) == len(lens) * SEQ * C.num_experts_per_tok).all()


def test_prefill_at_every_real_position_of_one_call(state, loaded, prompts, held):
    params, config = loaded
    ids, lens = prompts
    hidden, _, _ = afmoe.prefill(params, jnp.asarray(ids), config, lens=jnp.asarray(lens))
    n = int(lens[0])
    (want,) = reference.read_logits(
        state, hf_config(held=held), [(ids[0, :n].tolist(), list(range(n)))], EVERY
    )
    got = afmoe.head_logprobs(params, hidden[0, :n], config)
    assert np.abs(centred(got) - centred(want)).max() < TOL


def test_the_cache_has_two_kinds_and_two_lengths(loaded, prompts):
    """A full layer keeps every slot's keys and values; a sliding layer the
    ``window - 1`` positions before a call's length, and nothing else."""
    params, config = loaded
    ids, lens = prompts
    _, caches, _ = afmoe.prefill(params, jnp.asarray(ids), config, lens=jnp.asarray(lens))
    narrow, back = C.num_kv_heads * C.head_dim, C.sliding_window - 1
    assert [tuple(x.shape[1:] for x in cache) for cache in caches] == [
        ((back, narrow),) * 2, ((back, narrow),) * 2, ((SEQ, narrow),) * 2,
        ((back, narrow),) * 2, ((back, narrow),) * 2,
    ]


def test_decode_through_both_caches_matches_the_full_forward(state, loaded, prompts, held):
    """The decoded token attends the window's cached, TURNED keys on the four
    sliding layers and every cached, unturned key on the full one; the head
    reads what ONE forward over T + 1 tokens reads at position T, for T short
    of the window, exactly its reach, one past it, far past it and the bucket."""
    params, config = loaded
    ids, lens = prompts
    token = np.array([11, 200, 57, 300, 9], np.int32)
    _, caches, _ = afmoe.prefill(params, jnp.asarray(ids), config, lens=jnp.asarray(lens))
    step = afmoe.decode_step(params, jnp.asarray(token), jnp.asarray(lens), caches, config)
    got = afmoe.head_logprobs(params, step, config)
    calls = [(ids[row, :n].tolist() + [int(token[row])], [int(n)]) for row, n in enumerate(lens)]
    for row, want in enumerate(reference.read_logits(state, hf_config(held=held), calls, EVERY)):
        assert np.abs(centred(got[row]) - centred(want[0])).max() < TOL


def test_a_padded_slot_moves_no_real_query(loaded, prompts):
    params, config = loaded
    ids, lens = prompts
    other = ids.copy()
    for row, n in enumerate(lens):
        other[row, n:] = 7 + row
    a, _, _ = afmoe.prefill(params, jnp.asarray(ids), config, lens=jnp.asarray(lens))
    b, _, _ = afmoe.prefill(params, jnp.asarray(other), config, lens=jnp.asarray(lens))
    for row, n in enumerate(lens):
        assert np.array_equal(np.asarray(a[row, :n]), np.asarray(b[row, :n]))


def test_a_full_layer_s_keys_are_unturned_and_a_sliding_layer_s_turned(loaded):
    """What each kind caches, over ONE token repeated at every slot (every
    layer's input is then the same at every position: attention over equal
    values is that value): the full layer's keys hold no position at all, a
    sliding layer's are the same product turned by their position."""
    params, config = loaded
    same = jnp.full((1, SEQ), 17, jnp.int32)
    _, caches, _ = afmoe.prefill(params, same, config)
    full = np.asarray(caches[2][0])
    assert np.abs(full - full[:, :1]).max() < 1e-5  # unturned: the same key everywhere
    h = decoder_parts.rms(
        afmoe._embed(params, same, config), params["layers"][0]["input_norm"], C.rms_norm_eps
    )
    att, at = params["layers"][0]["attn"], jnp.arange(SEQ)
    k = np.asarray(afmoe._qkv(h, att, at, config, True)[1])
    plain = np.asarray(afmoe._qkv(h, att, at, config, False)[1])
    assert np.abs(plain - plain[:, :1]).max() < 1e-5
    assert np.abs(k[:, 0] - plain[:, 0]).max() < 1e-6  # position 0 turns by nothing
    assert np.abs(k[:, 5] - plain[:, 5]).max() > 1e-2  # any other does
    back = C.sliding_window - 1
    assert np.allclose(np.asarray(caches[0][0])[0], k[0, SEQ - back:], atol=1e-6)
    slid = np.asarray(caches[1][0])[0]
    assert np.abs(slid - slid[:1]).max() > 1e-2  # a sliding layer's cache holds positions


def test_the_kinds_swapped_is_another_model(state, loaded, prompts, held):
    """The reference told that the full layer slides and a sliding layer is
    full disagrees with the program (and with itself) far over the tolerance;
    so does the program handed the swapped kinds."""
    params, config = loaded
    swapped = list(PATTERN)
    swapped[7], swapped[8] = SLIDING, FULL
    cfg = hf_config(held=held, layer_types=swapped)
    assert prefill_error(params, config, state, cfg, prompts) > 50 * TOL
    other = dataclasses.replace(config, layer_types=tuple(swapped[n] for n in SERVED))
    assert prefill_error(params, other, state, cfg, prompts) < TOL
    assert prefill_error(params, other, state, hf_config(held=held), prompts) > 50 * TOL


def test_bfloat16_in_place_of_float32_fails_the_tolerance(state, loaded, prompts, held):
    params, config = loaded
    low = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16) if a.dtype == jnp.float32 and a.ndim > 1 else a, params
    )
    for layer in low["layers"]:  # the routers stay float32, as served
        if "moe" in layer:
            layer["moe"]["router"] = layer["moe"]["router"].astype(jnp.float32)
    assert prefill_error(low, config, state, hf_config(held=held), prompts) > 20 * TOL


@pytest.mark.parametrize("part", ["gate", "post_attn_norm", "post_mlp_norm", "mup", "head_norm"])
def test_a_part_left_out_changes_the_output(state, loaded, prompts, held, part, monkeypatch):
    """Each form the configuration has no key for matters to the logits: the
    elementwise gate, the norm behind each branch, the embedding's scale, the
    head norms."""
    params, config = loaded
    if part == "gate":
        monkeypatch.setattr(afmoe, "gated", lambda ctx, gate: ctx)
    elif part == "mup":
        config = dataclasses.replace(config, mup_enabled=False)
    elif part == "head_norm":
        params = {**params, "layers": [
            {**layer, "attn": {**layer["attn"], "q_norm": 2.0 * layer["attn"]["q_norm"]}}
            for layer in params["layers"]
        ]}
    else:  # the norm BEFORE the sum: its scale is the branch's size in the stream
        params = {**params, "layers": [
            {**layer, part: 3.0 * layer[part]} for layer in params["layers"]
        ]}
    assert prefill_error(params, config, state, hf_config(held=held), prompts) > 50 * TOL


# -- the router -------------------------------------------------------------------------


def test_the_bias_chooses_and_does_not_weigh():
    rng = np.random.default_rng(5)
    h = jnp.asarray(rng.standard_normal((32, C.hidden_size)), jnp.float32)
    router = jnp.asarray(rng.standard_normal((C.hidden_size, C.num_experts)) * 0.3, jnp.float32)
    plain = {"router": router, "bias": jnp.zeros((C.num_experts,), jnp.float32)}
    pushed = {"router": router, "bias": plain["bias"].at[3].set(10.0)}  # chosen by everyone
    chosen, weight = afmoe.route(h, pushed, C)
    assert (np.asarray(chosen) == 3).any(axis=1).all()
    score = np.asarray(jax.nn.sigmoid(h @ router))
    picked = np.take_along_axis(score, np.asarray(chosen), axis=1)
    want = picked / picked.sum(axis=1, keepdims=True) * C.route_scale
    assert np.abs(np.asarray(weight) - want).max() < 1e-6  # unbiased scores, normalised, scaled
    assert np.allclose(np.asarray(weight).sum(axis=1), C.route_scale, atol=1e-5)
    base, _ = afmoe.route(h, plain, C)
    assert not (np.asarray(base) == 3).any(axis=1).all()


def test_the_router_is_the_latent_attention_judges_to_the_letter():
    """``glm_moe.route`` and ``afmoe.route`` are one function
    (``decoder_parts.route_sigmoid``); the 1e-20 this family adds beside the
    sum is nothing a float32 sum of sigmoids can see."""
    from llm_weighted_consensus_tpu.models import glm_moe
    from llm_weighted_consensus_tpu.models.configs import GLM_TEST_TINY as G

    rng = np.random.default_rng(2)
    h = jnp.asarray(rng.standard_normal((16, 64)), jnp.float32)
    p = {
        "router": jnp.asarray(rng.standard_normal((64, 8)), jnp.float32),
        "bias": jnp.asarray(rng.standard_normal((8,)) * 0.02, jnp.float32),
    }
    a = glm_moe.route(h, p, G)
    same = dataclasses.replace(
        C, num_experts_per_tok=G.num_experts_per_tok, route_scale=G.routed_scaling_factor
    )
    b = afmoe.route(h, p, same)
    assert np.array_equal(np.asarray(a[0]), np.asarray(b[0]))
    assert np.array_equal(np.asarray(a[1]), np.asarray(b[1]))
    total = jnp.sum(b[1] / G.routed_scaling_factor, axis=1)
    assert np.array_equal(np.asarray(total), np.asarray(total + 1e-20))


# -- the share ---------------------------------------------------------------------------


def share_of(whole: dict, mine: list, order: list) -> dict:
    """The whole checkpoint as ONE chip of a group sees it: its experts
    ``mine`` named 0.., the router's rows and the bias in ``order`` (its own
    experts first), so that the experts it names are the router's first."""
    out = {}
    for name, value in whole.items():
        if ".mlp.experts." in name:
            e = int(name.split(".mlp.experts.")[1].split(".")[0])
            if e in mine:
                out[name.replace(f".experts.{e}.", f".experts.{mine.index(e)}.")] = value
        elif name.endswith("mlp.router.gate.weight") or name.endswith("mlp.expert_bias"):
            out[name] = value[order]
        else:
            out[name] = value
    return out


def test_the_eight_shares_add_up_to_the_uncut_reference_layer():
    """The guide's share test at the cell's share: the router is 16 wide and
    takes 2 a token; EIGHT chips hold 2 experts each.  The partial sums of all
    eight, the shared expert counted once, are the reference's whole layer
    with every expert held."""
    cfg = hf_config()  # every expert held: the uncut layer
    whole_state = random_state(cfg, seed=9)
    rng = np.random.default_rng(7)
    h = (rng.standard_normal((64, C.hidden_size)) * 0.5).astype(np.float32)
    with jax.default_matmul_precision("highest"):
        whole = np.asarray(
            reference.functions(cfg)[2](jnp.asarray(h), reference.layer_weights(whole_state, cfg, 1)[1])
        )
    total, pairs_here = np.zeros_like(whole), 0
    for chip in range(8):
        mine = [2 * chip, 2 * chip + 1]
        order = mine + [e for e in range(16) if e not in mine]
        part_state = share_of(whole_state, mine, order)
        params, config = afmoe.from_hf_weights(part_state, WIDE)
        assert afmoe.experts_held(params, config) == 2
        moe = params["layers"][1]["moe"]
        got, counts = afmoe._moe(jnp.asarray(h), moe, config)
        shared = np.asarray(decoder_parts.swiglu(jnp.asarray(h), moe["shared"]))
        total += np.asarray(got) - (shared if chip else 0.0)
        counts = np.asarray(counts)
        assert counts.shape == (3,) and counts.sum() == 64 * C.num_experts_per_tok
        pairs_here += counts[:2].sum()
        part = hf_config(held=2)  # the reference given the same share says the same
        with jax.default_matmul_precision("highest"):
            want = reference.functions(part)[2](
                jnp.asarray(h), reference.layer_weights(part_state, part, 1)[1]
            )
        assert np.abs(np.asarray(got) - np.asarray(want)).max() < 2e-6
    assert pairs_here == 64 * C.num_experts_per_tok  # every pair held somewhere, once
    assert np.abs(total - whole).max() < 5e-6


# -- the kernels at this decoder's geometry ------------------------------------------------


@pytest.mark.parametrize(
    "s,window,block,heads,kv,hd",
    [
        (128, 32, 16, 12, 2, 16),  # a window of two blocks, three key blocks a query block
        (256, 64, 32, 6, 1, 32),  # one key head serving all six
        (96, 32, 16, 12, 2, 16),  # the last query block's band ends at the bucket
        (192, 64, 32, 48, 8, 8),  # the published heads, narrow
    ],
)
def test_window_attention_with_six_query_heads_a_key_head_is_the_einsum(s, window, block, heads, kv, hd):
    """The cell's geometry scaled down in proportion: the window is TWO blocks
    (``window_block`` gives the largest under it), so a query block meets
    three key blocks: the old edge's (one whole masked tile at blocks this
    small), one wholly inside the band, the diagonal's; a group of SIX query
    heads a key head."""
    assert attn.window_block(s, window) == block
    qi, ki = attn._steps(s, block, block, window)
    assert max(np.bincount(qi)) == 3 and (qi - ki).max() == 2
    rng = np.random.default_rng(s + heads)
    q = jnp.asarray(rng.standard_normal((2, s, heads * hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((2, s, kv * hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((2, s, kv * hd)), jnp.float32)
    want = attn.causal_attention_einsum(q, k, v, heads=heads, kv_heads=kv, scale=hd**-0.5, window=window)
    got = attn.window_attention_blockwise(q, k, v, heads=heads, kv_heads=kv, scale=hd**-0.5, window=window)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 2e-6
    full = attn.causal_attention_blockwise(q, k, v, heads=heads, kv_heads=kv, scale=hd**-0.5)
    whole = attn.causal_attention_einsum(q, k, v, heads=heads, kv_heads=kv, scale=hd**-0.5)
    assert np.abs(np.asarray(full) - np.asarray(whole)).max() < 2e-6
    assert np.abs(np.asarray(full) - np.asarray(got)).max() > 1e-3  # the window matters


def test_the_diagonal_s_stripes_hold_under_a_window_wider_than_the_block():
    """At the cell's blocks the diagonal's block is walked in stripes (the
    window, 4096, covers the block of 2048); in small: blocks of 512 under a
    window of 1024, stripes of 256."""
    s, window, heads, kv, hd = 2048, 1024, 6, 1, 8
    assert attn.window_block(s, window) == 512 and attn.stripe_for(512, 512) == 256
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((1, s, heads * hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, s, kv * hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, s, kv * hd)), jnp.float32)
    want = attn.causal_attention_einsum(q, k, v, heads=heads, kv_heads=kv, scale=hd**-0.5, window=window)
    got = attn.window_attention_blockwise(q, k, v, heads=heads, kv_heads=kv, scale=hd**-0.5, window=window)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 2e-6


@pytest.mark.parametrize(
    "s,window,block,heads,kv,hd,edges",
    [
        (2048, 1024, 512, 6, 1, 8, (2,)),  # the cell in small: a window of exactly two blocks
        (1536, 513, 512, 2, 1, 8, (1,)),  # a window of a block and a key (the fourth judge's)
        (2048, 700, 512, 2, 1, 8, (1, 2)),  # no multiple of the stripe: the edge through two chunks
        (2048, 512, 512, 1, 1, 16, (1,)),  # a window of one block: the edge block's diagonal masked
        (1536, 300, 512, 2, 1, 8, ()),  # a window narrower than the block: one whole masked tile
        (1024, 513, 256, 2, 1, 8, ()),  # blocks of one stripe: one whole masked tile
    ],
)
def test_the_old_edge_s_stripes_are_the_einsum(s, window, block, heads, kv, hd, edges):
    """The block the band's old edge crosses, walked in the diagonal's stripes
    mirrored wherever the block splits and the window covers it (``edges``:
    the distances qi - ki of such blocks), and one whole masked tile
    elsewhere: the same context either way."""
    stripe = attn.stripe_for(block, block) if block <= window else 0
    assert (attn._edge_offsets(s, block, block, window) if stripe else ()) == edges
    kw = dict(heads=heads, kv_heads=kv, scale=hd**-0.5, window=window)
    rng = np.random.default_rng(s + window)
    q = jnp.asarray(rng.standard_normal((1, s, heads * hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, s, kv * hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, s, kv * hd)), jnp.float32)
    text = str(jax.make_jaxpr(
        lambda *a: attn.window_attention_blockwise(*a, block_q=block, block_k=block, **kw)
    )(q, k, v))
    assert (f"bool[{block},{block}]" in text) == (not edges)  # no whole tile of scores is masked
    want = attn.causal_attention_einsum(q, k, v, **kw)
    got = attn.window_attention_blockwise(q, k, v, block_q=block, block_k=block, **kw)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 2e-6


def test_a_row_that_meets_no_key_of_the_edge_block_is_wiped_by_the_next():
    """Where the window is whole blocks, the last row of a query block sees no
    key of its old-edge block: its running maximum stays masked there and the
    softmax of nothing puts ones into its sums, which the next block's first
    real key wipes (alpha = 0).  Values of 1e4 in the edge block would show in
    that row if anything of them were left."""
    s, window, block, hd = 1536, 1024, 512, 8
    rng = np.random.default_rng(3)
    q, k, v = (jnp.asarray(rng.standard_normal((1, s, hd)), jnp.float32) for _ in range(3))
    v = v.at[:, :block].set(1e4)
    kw = dict(heads=1, scale=hd**-0.5, window=window)
    assert attn._edge_parts(block - 256, 256, block, 0) == [(block - 256, 256, "tile")]
    got = np.asarray(attn.window_attention_blockwise(q, k, v, **kw))
    want = np.asarray(attn.causal_attention_einsum(q, k, v, **kw))
    assert np.abs(want[0, s - 1]).max() < 1 < np.abs(want[0, s - 2]).max()  # its oldest key is 512
    assert np.abs(got[0, s - 1] - want[0, s - 1]).max() < 2e-6
    assert np.abs(got - want).max() < 2e-6 * 1e4


def _pairs_multiplied_by_branch(jaxpr):
    """Of the window kernel's traced body: the q.k score pairs each
    ``pl.when`` branch multiplies, in the body's order."""
    def dots(jaxpr):
        found = 0
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "dot_general":
                (contract_l, contract_r), _ = eqn.params["dimension_numbers"]
                if (tuple(contract_l), tuple(contract_r)) == ((1,), (1,)):  # q.k, not p.v
                    found += int(np.prod(eqn.outvars[0].aval.shape))
            for value in eqn.params.values():
                for sub in value if isinstance(value, (tuple, list)) else (value,):
                    inner = getattr(sub, "jaxpr", sub)
                    if hasattr(inner, "eqns"):
                        found += dots(inner)
        return found

    (call,) = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
    return [dots(e.params["branches"][1].jaxpr) for e in call.params["jaxpr"].eqns if e.primitive.name == "cond"]


@pytest.mark.parametrize(
    "s,window,block,edge_steps",
    [(16384, 4096, 2048, {2: 6}), (8192, 513, 512, {1: 15}), (2048, 700, 512, {1: 3, 2: 2})],
)
def test_work_over_window_is_what_the_traced_kernel_multiplies(s, window, block, edge_steps):
    """The count read off the kernel's own trace: the shapes of the q.k
    products under each ``pl.when``, times the steps of the table that take
    that branch, is ``work_over_window`` x ``band_pairs``.  At the fifth
    judge's shape 62,390,272 pairs for the band's 58,722,304 (1.0625)."""
    x = jax.ShapeDtypeStruct((1, s, 8), jnp.float32)
    closed = jax.make_jaxpr(
        lambda q, k, v: attn.window_attention_blockwise.__wrapped__(q, k, v, heads=1, scale=1.0, window=window)
    )(x, x, x)
    by_branch = [n for n in _pairs_multiplied_by_branch(closed.jaxpr) if n]
    qi, ki = attn._steps(s, block, block, window)
    steps = np.bincount(qi - ki)
    assert {d: int(steps[d]) for d in attn._edge_offsets(s, block, block, window)} == edge_steps
    inside = [d for d in range(1, len(steps)) if d not in edge_steps]
    # the body's order: the old edge's block by its distance, a block inside the band, the diagonal's
    assert len(by_branch) == len(edge_steps) + 2
    total = sum(by_branch[i] * n for i, n in enumerate(edge_steps.values()))
    total += by_branch[-2] * int(sum(steps[d] for d in inside)) + by_branch[-1] * int(steps[0])
    assert by_branch[-2] == block * block and by_branch[-1] < block * block
    assert total == round(attn.work_over_window(s, block, block, window) * attn.band_pairs(s, window))
    if s == 16384:
        assert total == 62_390_272 and by_branch[0] == by_branch[-1] == 36 * 256 * 256


def test_the_cell_s_blocks_steps_and_work_are_pinned():
    """16,384 slots under a window of 4096: blocks of 2048, three key blocks a
    query block (21 steps a head for the causal kernel's 36), 1.0625 times the
    band's pairs multiplied (the old edge's block and the diagonal's both in
    stripes of 256: 36 chunks of 64; 1.25 with the edge's block whole), the
    band 43.7% of the causal pairs."""
    assert attn.window_block(16384, 4096) == 2048
    qi, ki = attn._steps(16384, 2048, 2048, 4096)
    assert len(qi) == 21 and len(attn._steps(16384, 2048, 2048)[0]) == 36
    assert np.bincount(qi).tolist() == [1, 2, 3, 3, 3, 3, 3, 3]
    assert attn.work_over_window(16384, 2048, 2048, 4096) == pytest.approx(62_390_272 / 58_722_304, rel=1e-9)
    assert attn.work_over_causal(16384, 2048, 2048) == pytest.approx(1.01556, abs=1e-5)
    assert attn.band_pairs(16384, 4096) == 58_722_304
    assert attn.band_pairs(16384, 4096) / (16384 * 16385 // 2) == pytest.approx(0.43749, abs=1e-5)
    assert attn.window_block(8192, 513) == 512  # the fourth judge's blocks are what they were
    assert attn.work_over_window(8192, 512, 512, 513) == pytest.approx(1.49708, abs=1e-5)


@pytest.mark.parametrize("turn", [False, True], ids=["full", "sliding"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=lambda v: v.__name__)
def test_the_head_norm_kernel_is_the_plain_norm_and_turn(turn, dtype):
    """``ops/head_norm.py`` at the published head width against
    ``decoder_parts.rms`` a head and ``decoder_parts.rope``: the same float32
    arithmetic; in bfloat16 the kernel rounds once where the plain way rounds
    after the norm and again after the turn."""
    rng = np.random.default_rng(0)
    b, s, heads = 2, 64, 3
    x = jnp.asarray(rng.standard_normal((b, s, heads * 128)), dtype)
    w = jnp.asarray(1 + 0.1 * rng.standard_normal(128), jnp.float32)
    assert head_norm.fits(x.shape, 128) and not head_norm.fits(x.shape, 64)
    assert not head_norm.fits((b, heads * 128), 128)  # a decode step's rows
    angles = decoder_parts.rope_angles(jnp.arange(s), 128, 1e4) if turn else ()
    want = decoder_parts.rms(x.reshape(b, s, heads, 128), w, 1e-5).reshape(x.shape)
    if turn:
        want = decoder_parts.turn_heads(want, *angles, heads, 0)
    got = head_norm.head_norm_turn(x, w, *angles, eps=1e-5)
    assert got.dtype == dtype
    limit = 1e-6 if dtype == jnp.float32 else 0.04
    assert np.abs(np.asarray(got, np.float32) - np.asarray(want, np.float32)).max() < limit


def test_a_prefill_at_the_published_head_width_takes_the_kernel(monkeypatch):
    """Heads of one 128-lane column go through ``head_norm_turn`` (a prefill,
    both kinds of layer); a decode step's rows and the tiny presets' heads are
    cut into heads the plain way; both give the same numbers."""
    config = dataclasses.replace(C, head_dim=128, num_heads=6, num_kv_heads=1, hidden_size=64)
    p = afmoe.init_params(jax.random.PRNGKey(1), dataclasses.replace(config, num_layers=1))
    att = p["layers"][0]["attn"]
    h = jax.random.normal(jax.random.PRNGKey(2), (2, 16, 64), jnp.float32)
    calls = []
    real = head_norm.head_norm_turn
    monkeypatch.setattr(
        head_norm, "head_norm_turn", lambda *a, **k: calls.append(len(a)) or real(*a, **k)
    )
    q, k, _, _ = afmoe._qkv(h, att, jnp.arange(16), config, True)
    assert calls == [4, 4]
    qf, kf, _, _ = afmoe._qkv(h, att, jnp.arange(16), config, False)
    assert calls == [4, 4, 2, 2]
    monkeypatch.setattr(head_norm, "fits", lambda *a: False)
    q2, k2, _, _ = afmoe._qkv(h, att, jnp.arange(16), config, True)
    assert np.abs(np.asarray(q) - np.asarray(q2)).max() < 1e-6
    assert np.abs(np.asarray(k) - np.asarray(k2)).max() < 1e-6
    monkeypatch.undo()
    # a decode step: rows [b, width] at positions [b]
    row = afmoe._qkv(h[:, 5], att, jnp.full((2,), 5), config, True)[0]
    assert np.abs(np.asarray(row) - np.asarray(q[:, 5])).max() < 1e-6


# -- what a checkpoint names ----------------------------------------------------------------


def test_a_checkpoint_names_its_stage_its_share_and_its_slice():
    cfg = hf_config(held=4, vocab_size=128)
    state = random_state(cfg, seed=4)
    assert "model.layers.5.mlp.gate_proj.weight" in state  # the dense one, by its published number
    assert "model.layers.7.mlp.experts.3.up_proj.weight" in state and "model.layers.0.input_layernorm.weight" not in state
    params, config = afmoe.from_hf_weights(state, WIDE)
    assert (config.num_layers, config.num_dense_layers, config.vocab_size) == (5, 1, 128)
    assert config.layer_types == (SLIDING, SLIDING, FULL, SLIDING, SLIDING)
    assert afmoe.experts_held(params, config) == 4 and config.num_experts == 16
    assert "mlp" in params["layers"][0] and all("moe" in layer for layer in params["layers"][1:])
    assert params["layers"][1]["moe"]["router"].dtype == jnp.float32
    assert params["layers"][1]["moe"]["router"].shape == (C.hidden_size, 16)
    # the whole published model names every layer from 0: the kinds are the pattern's
    whole = {**hf_config(), "num_hidden_layers": 12, "num_dense_layers": 6, "layers_served": list(range(12))}
    params, config = afmoe.from_hf_weights(random_state(whole, seed=5), WIDE)
    assert config.layer_types == PATTERN and config.num_dense_layers == 6
    assert afmoe.experts_held(params, config) == 16


@pytest.mark.parametrize(
    "drop,match",
    [
        ("model.layers.7.", "no run of the published"),
        ("model.layers.", "names no layer"),
        ("model.layers.6.mlp.experts.", "names no expert"),
    ],
)
def test_a_checkpoint_that_is_no_stage_is_refused(drop, match):
    state = {k: v for k, v in random_state(hf_config(held=2), seed=6).items() if not k.startswith(drop)}
    with pytest.raises(ValueError, match=match):
        afmoe.from_hf_weights(state, WIDE)


def test_a_layer_whose_shapes_are_not_the_preset_s_is_refused():
    state = random_state(hf_config(held=2), seed=6)
    with pytest.raises(ValueError, match="q_proj is"):
        afmoe.from_hf_weights(state, dataclasses.replace(WIDE, num_heads=6))


# -- the judge: presets, counters, the gateway ---------------------------------------------


def test_presets_name_the_fifth_decoder():
    assert judge_module.decoder_of(JUDGE_PRESETS["trinity-large-preview"]) is afmoe
    assert JUDGE_PRESETS["afmoe-test-tiny"] is C and isinstance(C, AfmoeConfig)
    p = JUDGE_PRESETS["trinity-large-preview"]
    assert p is TRINITY_LARGE_PREVIEW
    with open(os.path.join(ROOT, "bench", "configs", "trinity-large-preview.json"), encoding="utf-8") as f:
        published = json.load(f)
    assert list(p.layer_types) == published["layer_types"] and len(p.layer_types) == 60
    assert sum(t == FULL for t in p.layer_types) == 15
    assert [published["layer_types"][n] for n in published["layers_served"]] == list(C.layer_types)
    for field, key in (
        ("hidden_size", "hidden_size"), ("num_heads", "num_attention_heads"),
        ("num_kv_heads", "num_key_value_heads"), ("head_dim", "head_dim"),
        ("intermediate_size", "intermediate_size"),
        ("moe_intermediate_size", "moe_intermediate_size"),
        ("num_experts_per_tok", "num_experts_per_tok"),
        ("num_shared_experts", "num_shared_experts"), ("route_scale", "route_scale"),
        ("sliding_window", "sliding_window"), ("rope_theta", "rope_theta"),
        ("rms_norm_eps", "rms_norm_eps"), ("mup_enabled", "mup_enabled"),
    ):
        assert getattr(p, field) == published[key], field
    for field, key in (
        ("num_layers", "num_hidden_layers"), ("num_dense_layers", "num_dense_layers"),
        ("num_experts", "num_experts"), ("vocab_size", "vocab_size"),
    ):
        assert getattr(p, field) == published["published"][key], field
    assert p.num_heads // p.num_kv_heads == 6 == C.num_heads // C.num_kv_heads
    assert p.slides(5) and not p.slides(7) and p.is_dense(5) and not p.is_dense(6)


@pytest.fixture(scope="module")
def judge():
    # a bucket of its own: the dispatch label's count is the process's
    return TpuJudge("afmoe-test-tiny", tokenizer=tiny_tokenizer(), max_tokens=432, seed=2)


def test_judge_counts_the_band_over_four_sliding_layers(judge):
    before = judge.stats()
    confidence, _, ballots = judge.judge(
        candidates(24, np.random.default_rng(3)), "w7 w8 w9", [(5, 3.0), (6, 2.0), (7, 1.0)]
    )
    assert len(confidence) == 24 and abs(confidence.sum() - 1.0) < 1e-6 and len(ballots) == 3
    stats = judge.stats()
    s, w = judge.max_tokens, C.sliding_window
    grew = lambda key: stats[key] - before[key]  # noqa: E731
    assert grew("window_keys_causal") == 4 * 3 * family.causal_pairs(s)  # the four sliding layers
    assert grew("window_keys_band") == 4 * 3 * family.band_pairs(hf_config(), s)
    assert family.band_pairs(hf_config(), s) == w * (w + 1) // 2 + (s - w) * w == attn.band_pairs(s, w)
    assert grew("index_keys_causal") == 0  # no indexer anywhere
    assert grew("expert_pairs_routed") == 4 * 3 * s * C.num_experts_per_tok
    assert grew("expert_pairs_elsewhere") == 0  # the preset's own parameters hold every expert
    # every expert held: one bound of row tiles a sparse layer, most of them in use
    pairs = 3 * s * C.num_experts_per_tok
    tile = gmm.tile_for(pairs, C.num_experts)
    assert grew("expert_tiles_laid") == 4 * gmm.padded_rows(pairs, C.num_experts, tile) // tile
    assert grew("expert_tiles_laid") / 2 < grew("expert_tiles_in_use") <= grew("expert_tiles_laid")


def test_a_share_counts_the_pairs_here_and_elsewhere():
    params, config = afmoe.from_hf_weights(random_state(hf_config(held=4), seed=8), WIDE)
    share = TpuJudge(
        "afmoe-test-tiny", params=params, config=config, tokenizer=tiny_tokenizer(), max_tokens=SEQ
    )
    share.judge(candidates(6, np.random.default_rng(1)), "w1 w2", [(1, 1.0), (2, 1.0)])
    stats = share.stats()
    assert len(stats["expert_tokens"]) == 4 and stats["layers"] == 5
    assert stats["expert_pairs_here"] + stats["expert_pairs_elsewhere"] == stats["expert_pairs_routed"]
    assert stats["expert_pairs_routed"] == 4 * 2 * SEQ * C.num_experts_per_tok
    assert 0 < stats["expert_pairs_here"] < stats["expert_pairs_elsewhere"]
    assert stats["expert_layers_whole_bound"] == 0
    # four experts and the pairs elsewhere: five groups' bound a sparse layer
    pairs = 2 * SEQ * C.num_experts_per_tok
    tile = gmm.tile_for(pairs, C.num_experts)
    assert stats["expert_tiles_laid"] == 4 * gmm.padded_rows(pairs, 5, tile) // tile
    assert 0 < stats["expert_tiles_in_use"] < stats["expert_tiles_laid"] / 2


def test_the_other_judges_programs_name_none_of_this_decoder_s_own_scopes():
    """``mlp_norm`` is how the benchmark's fifth scope table knows this
    decoder's programs (``bench/trinity_scopes.py``): no other judge names it."""
    from llm_weighted_consensus_tpu.models import glm_moe, qwen3_next
    from llm_weighted_consensus_tpu.models.configs import DOTS3_TEST_TINY, QWEN3_NEXT_TEST_TINY

    def text(module, config):
        params = module.init_params(jax.random.PRNGKey(0), config)
        ids = jnp.zeros((1, 32), jnp.int32)
        return jax.jit(lambda p, i: module.prefill(p, i, config)[0]).lower(params, ids).as_text(
            debug_info=True
        )

    assert "mlp_norm" in text(afmoe, C) and "attn_gate" in text(afmoe, C)
    assert "mlp_norm" not in text(glm_moe, DOTS3_TEST_TINY)
    assert "mlp_norm" not in text(qwen3_next, QWEN3_NEXT_TEST_TINY)


def test_int8_control_reaches_the_gate_and_both_kinds():
    low = TpuJudge("afmoe-test-tiny", tokenizer=tiny_tokenizer(), max_tokens=SEQ, seed=2, quantize="int8")
    base = TpuJudge("afmoe-test-tiny", tokenizer=tiny_tokenizer(), max_tokens=SEQ, seed=2)
    for layer in (1, 2):  # a sliding layer and the full one
        a = low.params["layers"][layer]["attn"]
        assert all("kernel_q" in a[k] for k in ("q", "k", "v", "gate", "o"))
        assert "kernel_q" in low.params["layers"][layer]["moe"]["shared"]["up"]
        assert low.params["layers"][layer]["moe"]["w_up"].dtype == jnp.float32  # routed experts stay
    assert "kernel_q" in low.params["layers"][0]["mlp"]["down"]
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
        assert body["scorer"] == "judge" and body["model"] == "afmoe-test-tiny"
        assert len(body["confidence"]) == 21
        assert sum(body["confidence"]) == pytest.approx(1.0, abs=1e-6)
        assert [b["seed"] for b in body["ballots"]] == [7, 8]
        metrics = await (await client.get("/metrics")).json()
        assert metrics["roofline"]["buckets"]["judge(n=2,s=432)"]["count"] >= 1
        assert metrics["judge"]["dispatches"] == dispatched + 1
        assert 0 < metrics["judge"]["window_keys_band"] < metrics["judge"]["window_keys_causal"]
        assert metrics["judge"]["index_keys_causal"] == 0

    go(with_client(app, drive))


def test_build_judge_knows_the_presets(monkeypatch):
    from llm_weighted_consensus_tpu.serve import Config
    from llm_weighted_consensus_tpu.serve.__main__ import build_judge

    monkeypatch.delenv("LWC_ALLOW_RANDOM_PARAMS", raising=False)
    config = Config.from_env({"JUDGE_MODEL": "afmoe-test-tiny", "JUDGE_MAX_TOKENS": "64"})
    with pytest.raises(ValueError, match="JUDGE_WEIGHTS"):
        build_judge(config)
    built = build_judge(config, allow_synthetic=True)
    assert built.max_tokens == 64 and built.decoder is afmoe
    assert built.config.sliding_window == 24 and built.config.num_heads == 12
    with pytest.raises(ValueError, match="trinity-large-preview"):
        build_judge(Config.from_env({"JUDGE_MODEL": "trinity"}))


def test_a_checkpoint_on_disk_is_served_as_it_names(tmp_path):
    from safetensors.numpy import save_file

    from llm_weighted_consensus_tpu.models.judge import load_judge_params

    cfg = hf_config(held=8, vocab_size=128)
    save_file(random_state(cfg, seed=4), str(tmp_path / "model.safetensors"))
    params, config = load_judge_params(str(tmp_path), WIDE, dtype=jnp.float32)
    assert (config.num_layers, config.vocab_size, config.num_dense_layers) == (5, 128, 1)
    assert config.layer_types == C.layer_types and afmoe.experts_held(params, config) == 8


# -- the family's counts (the benchmark's yardstick) ---------------------------------------


def test_the_family_counts_the_pairs_and_the_operations_of_the_cell():
    with open(os.path.join(ROOT, "bench", "configs", "trinity-large-preview.json"), encoding="utf-8") as f:
        cfg = json.load(f)
    rows, s = 3, 16384
    assert family.layers_of(cfg, SLIDING) == 4 and family.layers_of(cfg, FULL) == 1
    assert family.band_pairs(cfg, s) == 58_722_304 and family.causal_pairs(s) == 134_225_920
    assert family.attention_weights(cfg) == 62_914_560
    # by hand: the band's pairs x 48 heads x (128 + 128) x 2, four layers
    assert family.window_attention_flops(cfg, rows, s) == 4 * 3 * 58_722_304 * 48 * 256 * 2
    assert family.causal_attention_flops(cfg, rows, s) == 3 * 134_225_920 * 48 * 256 * 2
    assert family.window_attention_bytes(cfg, rows, s) == 4 * 3 * s * (2 * 6144 + 2 * 1024) * 2
    assert family.head_norm_bytes(cfg, rows, s) == 5 * 3 * s * 7168 * 2 * 2
    assert family.expected_held_pairs(cfg, rows, s) == 4 * 24_576
    assert family.expert_products_flops(cfg, rows, s, held_pairs=1000) == 1000 * 6 * 3072 * 3072
    total = family.forward_flops(cfg, rows, s)
    assert total == pytest.approx(86.3e12, rel=0.01)
    attention = family.window_attention_flops(cfg, rows, s) + family.causal_attention_flops(cfg, rows, s)
    assert attention / total == pytest.approx(0.316, abs=0.005)  # the issue's 27 of 86
    sizes = sum(int(np.prod(shape)) for _, shape, _ in family.tensors(cfg))
    assert 2 * sizes == cfg["bytes"]["checkpoint"] == 8_643_807_744
