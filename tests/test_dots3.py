"""The fourth judge (``models/glm_moe.py`` under a configuration whose layers
are of two kinds, ``model_type`` ``dots3_note``): full layers of latent
attention each behind an indexer of its own, sliding layers of ANOTHER
geometry (heads, latent ranks, head widths, rotary base) over a window, a
sigmoid gate a head, rescaled latents, value heads narrower than key heads, a
share of a wider router's experts held, behind ``POST /consensus`` ``scorer:
judge``.

The oracle is the benchmark's own plain reference,
``bench/references/dots3_note_judge.py`` (float32 ``jax.numpy`` at
``highest``, whole mask rows, nothing of the program), loaded by its path; the
checkpoint is drawn here from the family's tensor list
(``bench/families/dots3_note.py``), on the CPU at the tiny preset: two full
and three sliding layers in the cut's order, a window (17) far shorter than
every sequence below and no multiple of a block.
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
    DOTS3_NOTE_PREV, DOTS3_TEST_TINY, GLM_5_2, GLM_DSA_TEST_TINY, GLM_TEST_TINY,
)
from llm_weighted_consensus_tpu.models.judge import JUDGE_PRESETS, TpuJudge  # noqa: E402
from llm_weighted_consensus_tpu.ops import causal_attention as attn  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
C = DOTS3_TEST_TINY
SEQ = 96
FULL, SLIDING = "full_attention", "sliding_attention"


def bench_file(directory, name):
    path = os.path.join(ROOT, "bench", directory, name + ".py")
    spec = importlib.util.spec_from_file_location(f"tier1_{directory}_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


reference = bench_file("references", "dots3_note_judge")
family = bench_file("families", "dots3_note")


def hf_config(config=C, held=None, **changed) -> dict:
    """The configuration as ``config.json`` keys it (a sliding layer's under
    ``swa_``), the served layers the published ones from 0."""
    layers = changed.get("num_hidden_layers", config.num_layers)
    out = {
        "vocab_size": config.vocab_size,
        "hidden_size": config.hidden_size,
        "num_hidden_layers": layers,
        "intermediate_size": config.intermediate_size,
        "moe_intermediate_size": config.moe_intermediate_size,
        "n_routed_experts": held or config.n_routed_experts,
        "n_routed_experts_routed": config.n_routed_experts,
        "num_experts_per_tok": config.num_experts_per_tok,
        "n_shared_experts": config.n_shared_experts,
        "routed_scaling_factor": config.routed_scaling_factor,
        "first_k_dense_replace": config.first_k_dense_replace,
        "rms_norm_eps": config.rms_norm_eps,
        "index_n_heads": config.index_n_heads,
        "index_head_dim": config.index_head_dim,
        "index_topk": config.index_topk,
        "sliding_window_size": config.sliding_window,
        "layer_types": list(config.layer_types),
        "layers_served": list(range(layers)),
    }
    for swa in ("", "swa_"):
        out.update({
            swa + "num_attention_heads": getattr(config, swa + "num_heads"),
            swa + "q_lora_rank": getattr(config, swa + "q_lora_rank"),
            swa + "kv_lora_rank": getattr(config, swa + "kv_lora_rank"),
            swa + "qk_nope_head_dim": getattr(config, swa + "qk_nope_head_dim"),
            swa + "qk_rope_head_dim": getattr(config, swa + "qk_rope_head_dim"),
            swa + "v_head_dim": getattr(config, swa + "v_head_dim"),
            swa + "rope_theta": getattr(config, swa + "rope_theta"),
        })
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
    """Experts 0..7 of a router 16 wide: a share."""
    return random_state(hf_config(held=8), seed=3)


@pytest.fixture(scope="module")
def loaded(state):
    return glm_moe.from_hf_weights(state, C)


@pytest.fixture(scope="module")
def prompts():
    """Lengths above the window and off every block (90), below it (13) and
    the whole bucket."""
    rng = np.random.default_rng(1)
    lens = np.array([90, 13, SEQ], np.int32)
    ids = np.zeros((3, SEQ), np.int32)
    for row, n in enumerate(lens):
        ids[row, :n] = rng.integers(4, C.vocab_size, size=n)
    return ids, lens


def centred(x):
    x = np.asarray(x, np.float64)
    return x - x.mean(axis=-1, keepdims=True)


EVERY = list(range(C.vocab_size))


# -- the decoder against the plain reference -----------------------------------------------


def test_prefill_logits_match_the_reference(state, loaded, prompts):
    params, config = loaded
    ids, lens = prompts
    assert config.layer_types == C.layer_types and config.first_k_dense_replace == 1
    hidden, caches, loads = glm_moe.prefill(params, jnp.asarray(ids), config, lens=jnp.asarray(lens))
    cfg = hf_config(held=8)
    calls = [(ids[row, :n].tolist(), [n - 1, n // 2, min(40, n - 1)]) for row, n in enumerate(lens)]
    for row, want in enumerate(reference.read_logits(state, cfg, calls, EVERY)):
        got = glm_moe.head_logprobs(params, hidden[row][jnp.asarray(calls[row][1])], config)
        assert np.abs(centred(got) - centred(want)).max() < 2e-5
    loads = np.asarray(loads)
    assert loads.shape == (4, 9) and (loads.sum(axis=1) == 3 * SEQ * C.num_experts_per_tok).all()


def test_the_cache_has_four_kinds(loaded, prompts):
    """A full layer keeps every position's latent, rotary key and index key; a
    sliding layer the latent (of ITS rank) and rotary key of the ``window -
    1`` positions before a call's length, and nothing else."""
    params, config = loaded
    ids, lens = prompts
    _, caches, _ = glm_moe.prefill(params, jnp.asarray(ids), config, lens=jnp.asarray(lens))
    back = C.sliding_window - 1
    assert [tuple(x.shape[1:] for x in cache) for cache in caches] == [
        ((SEQ, C.kv_lora_rank), (SEQ, C.qk_rope_head_dim), (SEQ, C.index_head_dim)),
    ] * 2 + [((back, C.swa_kv_lora_rank), (back, C.swa_qk_rope_head_dim))] * 3
    whole = glm_moe._attention_prefill(
        glm_moe._rms(jnp.take(params["token_embed"], jnp.asarray(ids), axis=0),
                     params["layers"][0]["input_norm"], C.rms_norm_eps),
        params["layers"][0]["attn"], config, geo=config.geometry(0),
    )[1]
    assert whole[0].shape == (3, SEQ, C.kv_lora_rank)


def test_decode_through_the_four_caches_matches_the_full_forward(state, loaded, prompts):
    """The decoded token chooses ``index_topk`` of the positions it sees on
    the two full layers and attends the window's on the three sliding ones,
    all on the absorbed path; the head reads what ONE forward over T + 1
    tokens reads at position T (T above the window, below it, the bucket)."""
    params, config = loaded
    ids, lens = prompts
    token = np.array([11, 200, 57], np.int32)
    _, caches, _ = glm_moe.prefill(params, jnp.asarray(ids), config, lens=jnp.asarray(lens))
    step = glm_moe.decode_step(params, jnp.asarray(token), jnp.asarray(lens), caches, config)
    got = glm_moe.head_logprobs(params, step, config)
    cfg = hf_config(held=8)
    calls = [(ids[row, :n].tolist() + [int(token[row])], [int(n)]) for row, n in enumerate(lens)]
    for row, want in enumerate(reference.read_logits(state, cfg, calls, EVERY)):
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


def test_each_kind_of_layer_attends_its_own_keys(state, loaded, prompts, monkeypatch):
    """A full layer hands its kernel a selection of ITS OWN (no layer shares
    one), a sliding layer hands the window kernel none; each is the
    reference's mask for that layer."""
    params, config = loaded
    ids, _ = prompts
    seen = []
    full, window = glm_moe.causal_attention_blockwise, glm_moe.window_attention_blockwise
    monkeypatch.setattr(
        glm_moe, "causal_attention_blockwise",
        lambda q, k, v, keep=None, **kw: seen.append(keep) or full(q, k, v, keep, **kw),
    )
    monkeypatch.setattr(
        glm_moe, "window_attention_blockwise",
        lambda q, k, v, **kw: seen.append(kw["window"]) or window(q, k, v, **kw),
    )
    glm_moe.prefill(params, jnp.asarray(ids[2:]), config)
    assert seen[2:] == [C.sliding_window] * 3 and seen[0].dtype == jnp.int8
    assert not np.array_equal(np.asarray(seen[0]), np.asarray(seen[1]))
    masks = []
    reference.hidden_states(state, hf_config(held=8), [ids[2].tolist()], masks)
    for layer in (0, 1):
        want = masks[layer][0][:SEQ, :SEQ]
        assert np.array_equal(np.asarray(seen[layer][0]) != 0, want), layer
        assert want.sum(axis=1).tolist() == [min(C.index_topk, t + 1) for t in range(SEQ)]
    for layer in (2, 3, 4):
        assert masks[layer][0][:SEQ, :SEQ].sum(axis=1).tolist() == [
            min(C.sliding_window, t + 1) for t in range(SEQ)
        ]


@pytest.mark.parametrize("part", ["gate", "rescale", "window"])
def test_a_part_left_out_changes_the_output(state, loaded, prompts, part):
    """The program agrees with the whole reference (above) and NOT with the
    reference that leaves the part out: none of the three is a no-op at these
    sizes, so none can be dropped inside the tolerance."""
    params, config = loaded
    ids, lens = prompts
    hidden, _, _ = glm_moe.prefill(params, jnp.asarray(ids[:1]), config, lens=jnp.asarray(lens[:1]))
    at = int(lens[0]) - 1
    got = glm_moe.head_logprobs(params, hidden[:, at], config)
    without = reference.read_logits(
        state, hf_config(held=8), [(ids[0, : at + 1].tolist(), [at])], EVERY, **{part: False}
    )[0]
    assert np.abs(centred(got) - centred(without)).max() > 1e-3
    # ... and the program without it agrees with that reference
    off = {"gate": "attention_gate", "rescale": "lora_rescale"}.get(part)
    if off:
        bare = dataclasses.replace(config, **{off: False})
        if part == "gate":
            params = {**params, "layers": [
                {**layer, "attn": {k: v for k, v in layer["attn"].items() if k != "gate"}}
                for layer in params["layers"]
            ]}
    else:
        bare = dataclasses.replace(config, sliding_window=SEQ)
    hidden, _, _ = glm_moe.prefill(params, jnp.asarray(ids[:1]), bare, lens=jnp.asarray(lens[:1]))
    got = glm_moe.head_logprobs(params, hidden[:, at], bare)
    assert np.abs(centred(got) - centred(without)).max() < 2e-5


def test_the_rescale_does_not_move_the_indexer_s_choice(state, loaded, prompts):
    """The indexer reads the rescaled query latent: a positive scale of a
    query's index heads scales all its ReLU scores alike, so every query
    chooses the same keys with the rescale and without."""
    params, config = loaded
    ids, _ = prompts
    x = jnp.take(params["token_embed"], jnp.asarray(ids[2:]), axis=0)
    layer = params["layers"][0]
    h = glm_moe._rms(x, layer["input_norm"], C.rms_norm_eps)
    keeps = []
    for rescale in (True, False):
        cfg = dataclasses.replace(config, lora_rescale=rescale)
        geo = cfg.geometry(0)
        assert (geo.q_scale != 1.0) == rescale
        keeps.append(np.asarray(glm_moe._attention_prefill(h, layer["attn"], cfg, geo=geo)[2]))
    assert keeps[0].sum() > 0 and np.array_equal(*keeps)


# -- the window kernel ---------------------------------------------------------------------


@pytest.mark.parametrize(
    "s,window,block,heads,hd,dv",
    [
        (96, 17, 16, 2, 24, 16),  # the tiny preset's sliding layer
        (96, 17, 8, 2, 24, 16),  # three key blocks a query block
        (96, 17, 32, 2, 24, 16),  # blocks wider than the window: one masked tile
        (128, 33, 32, 1, 32, 32),  # window - 1 a block: exactly two key blocks
        (128, 200, 64, 2, 16, 8),  # a window wider than the sequence: causal
        (64, 1, 16, 1, 16, 16),  # a query sees itself alone
        (1024, 513, 512, 1, 128, 128),  # the cell's window and block, stripes on the diagonal
        (1024, 513, 256, 1, 128, 128),
    ],
)
def test_window_attention_is_the_einsum_under_a_band_mask(s, window, block, heads, hd, dv):
    rng = np.random.default_rng(s + window + block)
    q, k = (jnp.asarray(rng.standard_normal((2, s, heads * hd)), jnp.float32) for _ in range(2))
    v = jnp.asarray(rng.standard_normal((2, s, heads * dv)), jnp.float32)
    got = attn.window_attention_blockwise(
        q, k, v, heads=heads, scale=0.2, window=window, block_q=block, block_k=block
    )
    want = attn.causal_attention_einsum(q, k, v, heads=heads, scale=0.2, window=window)
    assert got.shape == (2, s, heads * dv)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 5e-6
    # the band mask itself, by hand: query t sees t - window < key <= t
    rows, cols = np.arange(s)[:, None], np.arange(s)[None, :]
    seen = (cols <= rows) & (cols > rows - window)
    assert seen.sum() == attn.band_pairs(s, window)
    scores = np.einsum("bqhd,bkhd->bhqk", *(np.asarray(x).reshape(2, s, heads, hd) for x in (q, k)))
    scores = np.where(seen, scores * 0.2, -np.inf)
    probs = np.exp(scores - scores.max(axis=-1, keepdims=True))
    probs /= probs.sum(axis=-1, keepdims=True)
    by_hand = np.einsum("bhqk,bkhd->bqhd", probs, np.asarray(v).reshape(2, s, heads, dv))
    assert np.abs(np.asarray(got) - by_hand.reshape(2, s, -1)).max() < 5e-6


@pytest.mark.parametrize(
    "s,window,block,steps,work",
    [
        # 16 diagonal blocks in two stripes each (3 chunks of 256 x 256) + 15 old-edge
        # blocks in the same stripes mirrored (a window of a block and a key: 3 chunks)
        (8192, 513, 512, 31, (16 * 3 + 15 * 3) * 256 * 256 / 4_071_168),
        # the fifth judge's: 8 diagonal and 6 old-edge blocks of 36 chunks for 64, 7 whole
        (16384, 4096, 2048, 21, 62_390_272 / 58_722_304),
        (2048, 1024, 512, 9, (4 * 3 + 2 * 3 + 3 * 4) * 256 * 256 / 1_573_376),  # that cell in small
        # a window that is no multiple of the stripe: two old-edge blocks a query block,
        # the far one's first stripe alone sees keys (1 chunk), the near one's 2 + 2 chunks
        (2048, 700, 512, 9, (4 * 3 + 2 * 1 + 3 * 4) * 256 * 256 / 1_188_950),
        (8192, 513, 256, 93, 93 * 256 * 256 / 4_071_168),  # a block of one stripe: whole tiles
        (8192, 513, 1024, 15, 15 * 1024 * 1024 / 4_071_168),  # a block past the window: whole tiles
        (96, 17, 16, 11, 11 * 256 / 1496),
    ],
)
def test_the_step_table_follows_the_band(s, window, block, steps, work):
    """Every (query block, key block) pair the band touches is a step, in a
    query block's key order, and no other; ``work_over_window`` is what the
    steps multiply over the band's pairs."""
    qi, ki = attn._steps(s, block, block, window)
    rows, cols = np.arange(s, dtype=np.int32)[:, None], np.arange(s, dtype=np.int32)[None, :]
    seen = cols <= rows
    seen &= cols > rows - window
    touched = seen.reshape(s // block, block, s // block, block).any(axis=(1, 3))
    assert sorted(zip(qi.tolist(), ki.tolist())) == [tuple(p) for p in np.argwhere(touched).tolist()]
    assert list(zip(qi.tolist(), ki.tolist())) == sorted(zip(qi.tolist(), ki.tolist()))
    assert len(qi) == steps and attn.band_pairs(s, window) == int(seen.sum())
    assert attn.work_over_window(s, block, block, window) == pytest.approx(work, rel=1e-9)
    assert work >= 1.0


def test_a_window_layer_s_blocks_follow_its_window():
    assert attn.window_block(8192, 513) == 512 and attn.window_block(96, 17) == 16
    assert attn.window_block(8192, 4096) == 2048 and attn.block_for(8192) == 2048
    # with today's blocks the band would cost six times its pairs
    assert attn.work_over_window(8192, 2048, 2048, 513) > 5.5
    assert attn.work_over_window(8192, 512, 512, 513) <= 1.5


@pytest.mark.parametrize(
    "preset,heads,kv_heads,hd,selected",
    [
        ("glm-test-tiny", 4, 0, 32, False),
        ("glm-dsa-test-tiny", 4, 0, 32, True),
        ("qwen3-next-test-tiny", 4, 2, 32, False),
    ],
)
def test_an_accepted_preset_s_attention_call_is_what_it_was(preset, heads, kv_heads, hd, selected):
    """Without a window: the step table is the lower triangle's, as it always
    was; the kernel's parameters name no window; and a window that covers the
    whole sequence, which masks nothing more, gives the same bits."""
    config = JUDGE_PRESETS[preset]
    if hasattr(config, "geometry"):
        geo = config.geometry(0)
        assert (geo.heads, geo.laid, geo.v, geo.window, geo.gate) == (heads, hd, hd, 0, False)
        assert (geo.q_scale, geo.kv_scale) == (1.0, 1.0)
    s, block = 64, 16
    qi, ki = attn._steps(s, block, block)
    assert list(zip(qi.tolist(), ki.tolist())) == [(a, b) for a in range(4) for b in range(a + 1)]
    assert attn._steps(s, block, block) is attn._steps(s, block, block)
    rng = np.random.default_rng(5)
    q = jnp.asarray(rng.standard_normal((1, s, heads * hd)), jnp.float32)
    k, v = (
        jnp.asarray(rng.standard_normal((1, s, (kv_heads or heads) * hd)), jnp.float32)
        for _ in range(2)
    )
    keep = jnp.asarray(np.tril(rng.random((1, s, s)) < 0.5) | np.eye(s, dtype=bool), jnp.int8)
    args = (q, k, v, keep) if selected else (q, k, v)
    kw = dict(heads=heads, scale=0.25, kv_heads=kv_heads, block_q=block, block_k=block)
    text = str(jax.make_jaxpr(lambda *a: attn.causal_attention_blockwise(*a, **kw))(*args))
    assert "window" not in text
    got = attn.causal_attention_blockwise(*args, **kw)
    want = attn.causal_attention_einsum(*args, heads=heads, scale=0.25, kv_heads=kv_heads)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 5e-6
    if not selected:
        covered = attn.window_attention_blockwise(q, k, v, window=s, **kw)
        assert np.array_equal(np.asarray(got), np.asarray(covered))


@pytest.mark.parametrize(
    "keep,kv_heads,digest",
    [
        (False, 0, "3c3555bc2cb7e626"),
        (False, 2, "527b75d69bbcedd1"),
        (True, 0, "82d0b75938a6bf84"),
        (True, 2, "37adbcd79f28e812"),
    ],
)
def test_the_causal_kernel_s_trace_is_the_one_written_down(keep, kv_heads, digest):
    """``causal_attention_blockwise`` shares its body with the window kernel:
    a change for the window must leave the CAUSAL kernel's traced program (its
    jaxpr, so its Mosaic program) byte for byte what it was.  The digests are
    of the jaxpr's text at PR 41's commit (blocks of 512 over 1024 slots, so
    the diagonal's stripes, the bands and the selection's tile are all in it);
    a change that means to alter the causal kernel writes new ones down."""
    import hashlib

    s, block, heads, hd = 1024, 512, 4, 8
    q = jnp.zeros((1, s, heads * hd), jnp.float32)
    k = jnp.zeros((1, s, (kv_heads or heads) * hd), jnp.float32)
    args = (q, k, k) + ((jnp.zeros((1, s, s), jnp.int8),) if keep else ())
    kw = dict(heads=heads, scale=0.25, kv_heads=kv_heads, block_q=block, block_k=block)
    text = str(jax.make_jaxpr(lambda *a: attn.causal_attention_blockwise(*a, **kw))(*args))
    assert "window" not in text and "edges" not in text
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == digest


def test_a_head_that_is_not_whole_columns_is_refused_by_name():
    """On a TPU a block is one head's columns: the error says WHICH width
    failed and what the loader can do."""
    q = jnp.zeros((1, 256, 2 * 192), jnp.bfloat16)
    v = jnp.zeros((1, 256, 2 * 128), jnp.bfloat16)
    with pytest.raises(ValueError, match=r"a key head of 192 lanes.*value heads 128.*zero lanes"):
        attn.causal_attention_blockwise(q, q, v, heads=2, scale=1.0, interpret=False)
    q = jnp.zeros((1, 256, 2 * 256), jnp.bfloat16)
    v = jnp.zeros((1, 256, 2 * 96), jnp.bfloat16)
    with pytest.raises(ValueError, match=r"a value head of 96 lanes.*key heads 256"):
        attn.causal_attention_blockwise(q, q, v, heads=2, scale=1.0, interpret=False)
    with pytest.raises(ValueError, match="not a selection"):
        attn._attend(
            q, q, q, jnp.zeros((1, 256, 256), jnp.int8), heads=2, scale=1.0, kv_heads=0,
            block_q=0, block_k=0, interpret=True, window=5,
        )


# -- heads laid in whole columns -----------------------------------------------------------

WIDE = dataclasses.replace(
    C, num_layers=2, layer_types=(FULL, SLIDING), num_heads=2, qk_nope_head_dim=128,
    qk_rope_head_dim=64, v_head_dim=128, swa_qk_rope_head_dim=64, swa_qk_nope_head_dim=24,
    index_topk=16, index_head_dim=64,
)


def test_a_192_lane_head_is_laid_in_256_and_every_product_stays_what_it_was():
    """The published full layer's 128 | 64 head: the loader lays it in two
    whole columns with zero rows in ``q_b`` and zero lanes in ``w_k``, the
    rotary lanes last; prefill and the decoded token agree with the reference,
    which knows no padding."""
    geo = WIDE.geometry(0)
    assert (geo.head_dim, geo.laid) == (192, 256) and WIDE.geometry(1).laid == 88
    assert DOTS3_NOTE_PREV.geometry(0).laid == 256 == DOTS3_NOTE_PREV.geometry(2).laid
    cfg = hf_config(WIDE, num_hidden_layers=2)
    state = random_state(cfg, seed=5)
    params, config = glm_moe.from_hf_weights(state, WIDE)
    attn_p = params["layers"][0]["attn"]
    assert attn_p["q_b"]["kernel"].shape == (C.q_lora_rank, 2 * 256)
    assert attn_p["w_k"].shape == (C.kv_lora_rank, 2, 256)
    assert not np.asarray(attn_p["q_b"]["kernel"]).reshape(-1, 2, 256)[:, :, 128:192].any()
    assert not np.asarray(attn_p["w_k"])[:, :, 128:].any()
    drawn = glm_moe.init_params(jax.random.PRNGKey(0), WIDE)["layers"][0]["attn"]
    assert not np.asarray(drawn["q_b"]["kernel"]).reshape(-1, 2, 256)[:, :, 128:192].any()
    ids = np.random.default_rng(2).integers(4, C.vocab_size, size=(1, 48)).astype(np.int32)
    lens = jnp.asarray([47], jnp.int32)
    hidden, caches, _ = glm_moe.prefill(params, jnp.asarray(ids), config, lens=lens)
    want = reference.read_logits(state, cfg, [(ids[0, :47].tolist(), [46, 20])], EVERY)[0]
    got = glm_moe.head_logprobs(params, hidden[0, jnp.asarray([46, 20])], config)
    assert np.abs(centred(got) - centred(want)).max() < 2e-5
    step = glm_moe.decode_step(params, jnp.asarray(ids[:, 47]), lens, caches, config)
    want = reference.read_logits(state, cfg, [(ids[0].tolist(), [47])], EVERY)[0]
    assert np.abs(centred(glm_moe.head_logprobs(params, step, config)) - centred(want)).max() < 2e-5


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
        params, config = glm_moe.from_hf_weights(part_state, C)
        assert glm_moe.experts_held(params, config) == 2
        moe = params["layers"][1]["moe"]
        got, counts = glm_moe._moe(jnp.asarray(h), moe, config)
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


# -- the held experts' way back at the published width (ISSUE 40) ----------------------------


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=lambda v: v.__name__)
def test_the_published_width_takes_the_walk_and_gives_the_fall_back_s_bits(monkeypatch, dtype):
    """Hidden 5120 is 20 sublanes of bf16 words (40 of float32), no whole
    (8, 128) tile: a small layer at the configuration's own width and routing
    (8 of a router 256 wide, 16 held) lowers ``held_rows_sum`` over a padded
    slab and no gather on its way back, and its sum is bit for bit what the
    masked gathers, the path of every width until this issue, give."""
    from test_qwen3_next import primitives
    from llm_weighted_consensus_tpu.ops import grouped_matmul as gmm

    call_names = lambda fn: [name for name, _ in primitives(jax.make_jaxpr(fn)(h, p).jaxpr)]  # noqa: E731
    cfg = DOTS3_NOTE_PREV
    hidden, k, router, held = cfg.hidden_size, cfg.num_experts_per_tok, cfg.n_routed_experts, 16
    slab, filled = gmm.row_slabs(hidden, dtype)
    assert (slab, filled) == ((24, 20) if dtype == jnp.bfloat16 else (40, 40))
    rng = np.random.default_rng(11)
    tokens, width = 40, 16
    h = jnp.asarray(rng.standard_normal((tokens, hidden)), dtype)
    p = {
        name: jnp.asarray(rng.standard_normal(shape) * 0.1, dtype)
        for name, shape in (
            ("w_gate", (held, hidden, width)), ("w_up", (held, hidden, width)),
            ("w_down", (held, width, hidden)),
        )
    }
    # a token in four with every choice held, one in four with none, the rest as they fall
    chosen = np.stack([rng.permutation(router)[:k] for _ in range(tokens)]).astype(np.int32)
    chosen[0::4] = np.stack([rng.permutation(held)[:k] for _ in chosen[0::4]])
    chosen[1::4] = held + np.stack([rng.permutation(router - held)[:k] for _ in chosen[1::4]])
    chosen, weight = jnp.asarray(chosen), jnp.asarray(rng.random((tokens, k)), jnp.float32)
    layer = lambda h, p: decoder_parts.experts_grouped(h, chosen, weight, p, router, held=held)  # noqa: E731
    names = call_names(layer)
    assert names.count("held_rows_sum") == 1 and "gather" not in names[names.index("held_rows_sum"):]
    got, counts = layer(h, p)
    monkeypatch.setattr(gmm, "row_slabs", lambda width, dtype: (0, 0))  # the parent's answer
    masked = lambda h, p: layer(h, p)  # noqa: E731  (a trace of its own, not the cached one)
    fall_back = call_names(masked)
    assert "held_rows_sum" not in fall_back and fall_back.count("gather") >= k
    want, _ = masked(h, p)
    assert got.dtype == want.dtype == dtype and got.shape == (tokens, hidden)
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.array_equal(got, want) and np.abs(want).max() > 0.1
    assert not got[1::4].any() and int(np.asarray(counts)[:held].sum()) == int((chosen < held).sum())


# -- the loader ----------------------------------------------------------------------------


def test_a_checkpoint_names_its_stage_its_kinds_its_share_and_its_slice():
    """No variable says any of it: five layers of 46 named from 0, their kinds
    read off what each names (an indexer: full; none: sliding, at the sliding
    geometry's shapes), experts 0..7 of 16, 256 rows of the vocabulary."""
    published = dataclasses.replace(
        C, num_layers=46, vocab_size=4096, layer_types=DOTS3_NOTE_PREV.layer_types
    )
    cfg = hf_config(held=8, vocab_size=256)
    state = random_state(cfg, seed=1)
    params, served = glm_moe.from_hf_weights(state, published)
    assert (served.num_layers, served.first_k_dense_replace, served.vocab_size) == (5, 1, 256)
    assert served.layer_types == (FULL, FULL, SLIDING, SLIDING, SLIDING) == C.layer_types
    assert served.indexer_types == () and glm_moe.experts_held(params, served) == 8
    assert ["indexer" in layer["attn"] for layer in params["layers"]] == [True, True, False, False, False]
    assert all("gate" in layer["attn"] for layer in params["layers"])
    assert ["mlp" in layer for layer in params["layers"]] == [True, False, False, False, False]
    full, slid = params["layers"][1]["attn"], params["layers"][2]["attn"]
    assert full["gate"]["kernel"].shape == (C.hidden_size, C.num_heads)
    assert slid["gate"]["kernel"].shape == (C.hidden_size, C.swa_num_heads)
    assert slid["w_k"].shape == (C.swa_kv_lora_rank, C.swa_num_heads, 32)
    assert full["w_v"].shape == (C.kv_lora_rank, C.num_heads * C.v_head_dim)
    ids = np.random.default_rng(0).integers(4, 256, size=(1, 64)).astype(np.int32)
    hidden, _, loads = glm_moe.prefill(params, jnp.asarray(ids), served)
    want = reference.read_logits(state, cfg, [(ids[0].tolist(), [63])], list(range(256)))[0]
    got = glm_moe.head_logprobs(params, hidden[:, 63], served)
    assert np.abs(centred(got) - centred(want)).max() < 2e-5
    loads = np.asarray(loads)
    assert 0 < loads[:, :8].sum() < loads.sum()  # some here, some elsewhere
    # a stage that starts inside the pattern: sliding, sliding, full (published 3, 4, 5)
    later = hf_config(held=8, num_hidden_layers=3, layers_served=[3, 4, 5], first_k_dense_replace=0)
    later["layer_types"] = list(DOTS3_NOTE_PREV.layer_types)
    _, stage = glm_moe.from_hf_weights(random_state(later, seed=2), published)
    assert stage.layer_types == (SLIDING, SLIDING, FULL) and stage.first_k_dense_replace == 0


def test_a_layer_whose_shapes_are_not_its_kind_s_is_refused(state):
    """A sliding layer's tensors under an indexer's name: the kind read off
    the names and the shapes found disagree, and the error says where."""
    wrong = dict(state)
    for name in list(state):
        if "layers.0.self_attn.indexer" in name:
            wrong[name.replace("layers.0.", "layers.2.")] = state[name]
    with pytest.raises(ValueError, match=r"layer 2 \(full by what it names\): q_b_proj is"):
        glm_moe.from_hf_weights(wrong, C)


def test_the_third_judge_s_checkpoint_loads_as_it_did():
    third = bench_file("families", "glm_moe_dsa")
    c = GLM_DSA_TEST_TINY
    cfg = {
        "vocab_size": 128, "hidden_size": c.hidden_size, "num_hidden_layers": 5,
        "num_attention_heads": c.num_heads, "q_lora_rank": c.q_lora_rank,
        "kv_lora_rank": c.kv_lora_rank, "qk_nope_head_dim": c.qk_nope_head_dim,
        "qk_rope_head_dim": c.qk_rope_head_dim, "v_head_dim": c.v_head_dim,
        "intermediate_size": c.intermediate_size, "moe_intermediate_size": c.moe_intermediate_size,
        "n_routed_experts": 8, "n_routed_experts_routed": 16, "n_shared_experts": 1,
        "index_n_heads": c.index_n_heads, "index_head_dim": c.index_head_dim,
        "indexer_types": list(c.indexer_types), "layers_served": list(range(5)),
        "mlp_layer_types": ["dense"] + ["sparse"] * 4,
    }
    rng = np.random.default_rng(0)
    state = {n: rng.standard_normal(s).astype(np.float32) * 0.02 for n, s, _ in third.tensors(cfg)}
    params, served = glm_moe.from_hf_weights(state, c)
    assert served == dataclasses.replace(c, vocab_size=128) and served.layer_types == ()
    assert all("gate" not in layer["attn"] for layer in params["layers"])
    assert [len(cache) for cache in glm_moe.prefill(params, jnp.zeros((1, 32), jnp.int32), served)[1]] == [
        3, 2, 2, 2, 3
    ]


# -- the judge: presets, counters, the gateway ---------------------------------------------


def test_presets_name_the_fourth_decoder():
    assert judge_module.decoder_of(JUDGE_PRESETS["dots3-note-prev"]) is glm_moe
    assert JUDGE_PRESETS["dots3-test-tiny"] is C
    p = JUDGE_PRESETS["dots3-note-prev"]
    with open(os.path.join(ROOT, "bench", "configs", "dots3-note-prev.json"), encoding="utf-8") as f:
        published = json.load(f)
    assert list(p.layer_types) == published["layer_types"] and len(p.layer_types) == 46
    assert [published["layer_types"][i] for i in published["layers_served"]] == list(C.layer_types)
    full, slid = p.geometry(0), p.geometry(2)
    for geo, swa in ((full, ""), (slid, "swa_")):
        assert geo.heads == published[swa + "num_attention_heads"]
        assert (geo.q_lora_rank, geo.kv_lora_rank) == (published[swa + "q_lora_rank"], published[swa + "kv_lora_rank"])
        assert (geo.nope, geo.rope, geo.v) == tuple(
            published[swa + key] for key in ("qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim")
        )
        assert geo.theta == published[swa + "rope_theta"] and geo.gate
    assert (full.heads, full.head_dim, slid.heads, slid.head_dim) == (128, 192, 64, 256)
    assert (full.window, slid.window) == (0, 513) == (0, published["sliding_window_size"])
    assert full.q_scale == pytest.approx(5**0.5) and full.kv_scale == pytest.approx(10**0.5)
    assert slid.kv_scale == pytest.approx(5**0.5)
    assert (p.hidden_size, p.intermediate_size, p.moe_intermediate_size) == (5120, 13824, 1536)
    assert (p.n_routed_experts, p.num_experts_per_tok, p.routed_scaling_factor) == (256, 8, 1.0)
    assert (p.index_n_heads, p.index_head_dim, p.index_topk) == (64, 128, 2048)
    assert JUDGE_PRESETS["glm-5.2"].layer_types == () and GLM_5_2.geometry(7).window == 0


@pytest.fixture(scope="module")
def judge():
    # a bucket of its own: the dispatch label's count is the process's
    return TpuJudge("dots3-test-tiny", tokenizer=tiny_tokenizer(), max_tokens=440, seed=2)


def test_judge_counts_the_band_and_the_selection(judge):
    before = judge.stats()
    confidence, _, ballots = judge.judge(
        candidates(24, np.random.default_rng(3)), "w7 w8 w9", [(5, 3.0), (6, 2.0), (7, 1.0)]
    )
    assert len(confidence) == 24 and abs(confidence.sum() - 1.0) < 1e-6 and len(ballots) == 3
    stats = judge.stats()
    s, k, w = judge.max_tokens, C.index_topk, C.sliding_window
    cfg = hf_config()
    grew = lambda key: stats[key] - before[key]  # noqa: E731
    assert grew("index_keys_causal") == 2 * 3 * family.causal_pairs(s)  # the two full layers only
    assert grew("index_keys_selected") == 2 * 3 * family.selected_pairs(cfg, s)
    assert grew("window_keys_causal") == 3 * 3 * family.causal_pairs(s)  # the three sliding ones
    assert grew("window_keys_band") == 3 * 3 * family.band_pairs(cfg, s)
    assert family.band_pairs(cfg, s) == w * (w + 1) // 2 + (s - w) * w == attn.band_pairs(s, w)
    assert family.selected_pairs(cfg, s) == k * (k + 1) // 2 + (s - k) * k


def test_the_other_judges_keep_no_window_counter_running():
    for preset in ("glm-test-tiny", "glm-dsa-test-tiny"):
        other = TpuJudge(preset, tokenizer=tiny_tokenizer(), max_tokens=64)
        other.judge(candidates(4, np.random.default_rng(1)), "w1", [(1, 1.0)])
        stats = other.stats()
        assert stats["window_keys_causal"] == 0 and stats["window_keys_band"] == 0


def test_int8_control_reaches_the_gate_and_both_kinds(judge):
    low = TpuJudge("dots3-test-tiny", tokenizer=tiny_tokenizer(), max_tokens=SEQ, seed=2, quantize="int8")
    base = TpuJudge("dots3-test-tiny", tokenizer=tiny_tokenizer(), max_tokens=SEQ, seed=2)
    for layer in (1, 3):
        a = low.params["layers"][layer]["attn"]
        assert all("kernel_q" in a[k] for k in ("q_a", "q_b", "kv_a", "o", "gate"))
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
        assert body["scorer"] == "judge" and body["model"] == "dots3-test-tiny"
        assert len(body["confidence"]) == 21
        assert sum(body["confidence"]) == pytest.approx(1.0, abs=1e-6)
        assert [b["seed"] for b in body["ballots"]] == [7, 8]
        metrics = await (await client.get("/metrics")).json()
        assert metrics["roofline"]["buckets"]["judge(n=2,s=440)"]["count"] >= 1
        assert metrics["judge"]["dispatches"] == dispatched + 1
        assert 0 < metrics["judge"]["window_keys_band"] < metrics["judge"]["window_keys_causal"]
        assert 0 < metrics["judge"]["index_keys_selected"] < metrics["judge"]["index_keys_causal"]

    go(with_client(app, drive))


def test_build_judge_knows_the_presets(monkeypatch):
    from llm_weighted_consensus_tpu.serve import Config
    from llm_weighted_consensus_tpu.serve.__main__ import build_judge

    monkeypatch.delenv("LWC_ALLOW_RANDOM_PARAMS", raising=False)
    config = Config.from_env({"JUDGE_MODEL": "dots3-test-tiny", "JUDGE_MAX_TOKENS": "64"})
    with pytest.raises(ValueError, match="JUDGE_WEIGHTS"):
        build_judge(config)
    built = build_judge(config, allow_synthetic=True)
    assert built.max_tokens == 64 and built.decoder is glm_moe
    assert built.config.sliding_window == 17 and built.config.geometry(3).heads == 2
    with pytest.raises(ValueError, match="dots3-note-prev"):
        build_judge(Config.from_env({"JUDGE_MODEL": "dots3"}))


def test_a_checkpoint_on_disk_is_served_as_it_names(tmp_path):
    from safetensors.numpy import save_file

    from llm_weighted_consensus_tpu.models.judge import load_judge_params

    cfg = hf_config(held=8, vocab_size=128)
    save_file(random_state(cfg, seed=4), str(tmp_path / "model.safetensors"))
    wide = dataclasses.replace(C, num_layers=46, layer_types=DOTS3_NOTE_PREV.layer_types)
    params, config = load_judge_params(str(tmp_path), wide, dtype=jnp.float32)
    assert (config.num_layers, config.vocab_size) == (5, 128)
    assert config.layer_types == C.layer_types and glm_moe.experts_held(params, config) == 8


# -- the family's counts (the benchmark's yardstick) ---------------------------------------


def test_the_family_counts_the_pairs_and_the_bytes_of_the_cell():
    with open(os.path.join(ROOT, "bench", "configs", "dots3-note-prev.json"), encoding="utf-8") as f:
        cfg = json.load(f)
    assert family.band_pairs(cfg, 8192) == 4_071_168 == attn.band_pairs(8192, 513)
    assert family.selected_pairs(cfg, 8192) == 14_681_088
    assert 100 * 4_071_168 / family.causal_pairs(8192) == pytest.approx(12.13, abs=0.01)
    assert (family.layers_of(cfg, FULL), family.layers_of(cfg, SLIDING)) == (2, 3)
    names = [name for name, _, _ in family.tensors(cfg)]
    assert sum(".indexer.wq_b" in n for n in names) == 2 and sum(".g_proj" in n for n in names) == 5
    assert sum(".mlp.gate.weight" in n for n in names) == 4
    assert sum(".mlp.experts." in n for n in names) == 4 * cfg["n_routed_experts"] * 3
    shapes = {name: shape for name, shape, _ in family.tensors(cfg)}
    assert shapes["model.layers.1.self_attn.q_b_proj.weight"] == (128 * 192, 1024)
    assert shapes["model.layers.2.self_attn.q_b_proj.weight"] == (64 * 256, 1024)
    assert shapes["model.layers.2.self_attn.kv_a_proj_with_mqa.weight"] == (1024 + 64, 5120)
    assert shapes["model.layers.2.self_attn.kv_b_proj.weight"] == (64 * (192 + 128), 1024)
    # the window kernel's count: band pairs x 64 heads x (256 + 128) x 2, three layers, three calls
    assert family.window_attention_flops(cfg, 3, 8192) == 3 * 3 * 4_071_168 * 64 * 384 * 2
    # a full layer's: selected pairs x 128 heads x (192 + 128) x 2 at the PUBLISHED head width
    assert family.selected_attention_flops(cfg, 3, 8192) == 2 * 3 * 14_681_088 * 128 * 320 * 2
    params = sum(int(np.prod(shape)) for _, shape, _ in family.tensors(cfg))
    held = cfg["n_routed_experts"]
    experts = 4 * held * 3 * 5120 * 1536
    assert params - experts == pytest.approx(1.065e9, rel=0.01)  # everything but the experts held
    assert 2 * params == pytest.approx(2.13e9 + 2 * experts, rel=0.01)
