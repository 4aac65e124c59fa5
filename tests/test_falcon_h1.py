"""The seventh judge (``models/falcon_h1.py``, ``model_type`` ``falcon_h1``): a
Mamba-2 (SSD) mixer and grouped-query attention side by side in every block,
every product behind a published µP multiplier, behind ``POST /consensus``
``scorer: judge``.

TWO ORACLES.  The benchmark's own plain reference,
``bench/references/falcon_h1_judge.py`` (float32 ``jax.numpy`` at ``highest``,
the scan unfused and chunked, whole mask rows, every multiplier where the
family's code applies it, nothing of the program), loaded by its path; and the
family's PUBLISHED implementation, ``transformers``' ``FalconH1ForCausalLM``
(its ``torch_forward`` path, float32, CPU), to which the reference is held
where ``transformers`` has the family.  The checkpoint is drawn here from the
family's tensor list (``bench/families/falcon_h1.py``) at the tiny preset: two
layers, three query heads a key head and three mixer heads a group (neither a
power of two), every multiplier a value of its own.

Tolerances.  Program and reference are both float32 here and differ in the
order of their sums only (a blockwise online softmax against whole rows, one
chunking of the scan against another, a multiplier on a product's result
against the same multiplier on its input): centred logits of size 1 to 3 agree
to 5e-5 (they read 1e-6 to 1e-5).  Reference and ``transformers`` differ in
float32 round-off of two frameworks: 1e-4 on logits of size 5 (they read 6e-6).
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
from llm_weighted_consensus_tpu.models import falcon_h1  # noqa: E402
from llm_weighted_consensus_tpu.models import judge as judge_module  # noqa: E402
from llm_weighted_consensus_tpu.models.configs import (  # noqa: E402
    FALCON_H1_34B_INSTRUCT, FALCON_H1_TEST_TINY, FalconH1Config,
)
from llm_weighted_consensus_tpu.models.judge import JUDGE_PRESETS, TpuJudge  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
C = FALCON_H1_TEST_TINY
SEQ = 96
TOL = 5e-5
# the configuration's keys for the preset's fields
KEYS = (
    ("vocab_size", "vocab_size"), ("hidden_size", "hidden_size"),
    ("num_layers", "num_hidden_layers"), ("num_heads", "num_attention_heads"),
    ("num_kv_heads", "num_key_value_heads"), ("head_dim", "head_dim"),
    ("intermediate_size", "intermediate_size"), ("rope_theta", "rope_theta"),
    ("rms_norm_eps", "rms_norm_eps"), ("d_ssm", "mamba_d_ssm"), ("d_state", "mamba_d_state"),
    ("d_conv", "mamba_d_conv"), ("ssm_heads", "mamba_n_heads"), ("ssm_groups", "mamba_n_groups"),
    ("ssm_head_dim", "mamba_d_head"),
)
MULTIPLIERS = (
    "embedding_multiplier", "lm_head_multiplier", "attention_in_multiplier",
    "attention_out_multiplier", "key_multiplier", "ssm_in_multiplier", "ssm_out_multiplier",
    "ssm_multipliers", "mlp_multipliers",
)


def bench_file(directory, name):
    path = os.path.join(ROOT, "bench", directory, name + ".py")
    spec = importlib.util.spec_from_file_location(f"tier1_{directory}_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


reference = bench_file("references", "falcon_h1_judge")
family = bench_file("families", "falcon_h1")


def hf_config(config=C, **changed) -> dict:
    """The configuration as ``config.json`` keys it."""
    out = {key: getattr(config, field) for field, key in KEYS}
    out.update({name: getattr(config, name) for name in MULTIPLIERS})
    out.update(mamba_chunk_size=128, mamba_expand=2, mamba_rms_norm=True, mamba_norm_before_gate=False)
    out["ssm_multipliers"], out["mlp_multipliers"] = list(out["ssm_multipliers"]), list(out["mlp_multipliers"])
    return {**out, **changed}


def random_state(cfg: dict, seed: int, std: float = 0.1) -> dict:
    """The family's tensors, N(0, std) and 1 + N(0, std), float32."""
    rng = np.random.default_rng(seed)
    out = {}
    for name, shape, kind in family.tensors(cfg):
        x = rng.standard_normal(shape).astype(np.float32) * std
        out[name] = x + 1.0 if kind == "ln_scale" else x
    return out


@pytest.fixture(scope="module")
def state():
    return random_state(hf_config(), seed=3)


@pytest.fixture(scope="module")
def loaded(state):
    return falcon_h1.from_hf_weights(state, C)


@pytest.fixture(scope="module")
def prompts():
    """Rows of DIFFERENT lengths in one right-padded bucket: far off every
    block (90), under the convolution's taps (2), at them (3, 4), a middling
    one and one short of the bucket (a decoded token still fits)."""
    rng = np.random.default_rng(1)
    lens = np.array([90, 13, 3, 4, 2, SEQ - 1], np.int32)
    ids = np.zeros((len(lens), SEQ), np.int32)
    for row, n in enumerate(lens):
        ids[row, :n] = rng.integers(4, C.vocab_size, size=n)
    return ids, lens


def centred(x):
    x = np.asarray(x, np.float64)
    return x - x.mean(axis=-1, keepdims=True)


EVERY = list(range(C.vocab_size))


def program_logits(params, config, ids, lens):
    hidden, caches, loads = falcon_h1.prefill(params, jnp.asarray(ids), config, lens=jnp.asarray(lens))
    assert loads == []
    last = jnp.take_along_axis(hidden, (jnp.asarray(lens) - 1)[:, None, None], axis=1)[:, 0]
    return falcon_h1.head_logprobs(params, last, config), caches


# -- the decoder against the plain reference -----------------------------------------------


def test_prefill_reads_the_reference_s_logits_at_rows_of_different_lengths(state, loaded, prompts):
    params, config = loaded
    ids, lens = prompts
    got, _ = program_logits(params, config, ids, lens)
    calls = [(ids[row, :n].tolist(), [int(n) - 1]) for row, n in enumerate(lens)]
    for row, want in enumerate(reference.read_logits(state, hf_config(), calls, EVERY)):
        assert np.abs(centred(got[row]) - centred(want[0])).max() < TOL, row
        assert np.abs(centred(want[0])).max() > 0.3  # logits that say something


def test_decode_through_both_kinds_of_cache_matches_the_full_forward(state, loaded, prompts):
    """The decoded token takes one step of each layer's recurrence from the
    cached state and convolution tail AND one row against the same layer's
    cached keys, its own appended; the head reads what ONE forward over T + 1
    tokens reads at T."""
    params, config = loaded
    ids, lens = prompts
    token = np.array([11, 200, 57, 300, 9, 77], np.int32)
    _, caches = program_logits(params, config, ids, lens)
    step = falcon_h1.decode_step(params, jnp.asarray(token), jnp.asarray(lens), caches, config)
    got = falcon_h1.head_logprobs(params, step, config)
    calls = [(ids[row, :n].tolist() + [int(token[row])], [int(n)]) for row, n in enumerate(lens)]
    for row, want in enumerate(reference.read_logits(state, hf_config(), calls, EVERY)):
        assert np.abs(centred(got[row]) - centred(want[0])).max() < TOL, row


def test_every_layer_keeps_two_kinds_of_cache(loaded, prompts):
    params, config = loaded
    ids, lens = prompts
    _, caches = program_logits(params, config, ids, lens)
    assert len(caches) == config.num_layers == 2
    for (keys, values), (tail, carried) in caches:
        width = config.num_kv_heads * config.head_dim
        assert keys.shape == values.shape == (len(lens), SEQ, width)
        assert tail.shape == (len(lens), config.d_conv - 1, config.conv_dim)
        assert carried.shape == (len(lens), config.ssm_heads, config.ssm_head_dim, config.d_state)
        assert carried.dtype == jnp.float32
        # a call of 2 tokens has one row of nothing before position 0
        assert not np.asarray(tail[4, 0]).any() and np.asarray(tail[4, 1]).any()


def test_a_padded_slot_moves_neither_the_row_read_nor_a_cache(loaded, prompts):
    params, config = loaded
    ids, lens = prompts
    got, caches = program_logits(params, config, ids, lens)
    noisy = ids.copy()
    for row, n in enumerate(lens):
        noisy[row, n:] = 7 + row
    again, caches_again = program_logits(params, config, noisy, lens)
    assert np.abs(np.asarray(got) - np.asarray(again)).max() < 1e-6
    for ((k, v), (tail, carried)), ((k2, v2), (tail2, carried2)) in zip(caches, caches_again):
        assert np.array_equal(np.asarray(tail), np.asarray(tail2))
        assert np.abs(np.asarray(carried) - np.asarray(carried2)).max() < 1e-6
        for row, n in enumerate(lens):
            assert np.abs(np.asarray(k[row, :n]) - np.asarray(k2[row, :n])).max() < 1e-6
            assert np.abs(np.asarray(v[row, :n]) - np.asarray(v2[row, :n])).max() < 1e-6


# -- the multipliers -------------------------------------------------------------------------


def _changed(value, index):
    if isinstance(value, tuple):
        return tuple(v * 1.37 if i == index else v for i, v in enumerate(value))
    return value * 1.37


@pytest.mark.parametrize(
    "name, index",
    [(n, None) for n in MULTIPLIERS[:7]]
    + [("ssm_multipliers", i) for i in range(5)] + [("mlp_multipliers", i) for i in range(2)],
    ids=lambda v: str(v),
)
def test_every_multiplier_stands_where_it_is_published(state, loaded, prompts, name, index):
    """One multiplier changed ON BOTH SIDES: the program still reads the
    reference's logits (it is applied where the family applies it, or where
    the arithmetic is the same), and the logits are not the unchanged ones (it
    is not dropped).  The tiny preset gives every multiplier a value of its
    own, so two of them swapped fail the first tests of this file."""
    params, config = loaded
    ids, lens = prompts[0][1:3], prompts[1][1:3]
    changed = dataclasses.replace(config, **{name: _changed(getattr(config, name), index)})
    value = getattr(changed, name)
    cfg = hf_config(changed, **{name: list(value) if isinstance(value, tuple) else value})
    got, caches = program_logits(params, changed, ids, lens)
    base, _ = program_logits(params, config, ids, lens)
    token = np.array([200, 57], np.int32)
    step = falcon_h1.decode_step(params, jnp.asarray(token), jnp.asarray(lens), caches, changed)
    got_step = falcon_h1.head_logprobs(params, step, changed)
    calls = [
        (ids[row, :n].tolist() + [int(token[row])], [int(n) - 1, int(n)]) for row, n in enumerate(lens)
    ]
    for row, want in enumerate(reference.read_logits(state, cfg, calls, EVERY)):
        assert np.abs(centred(got[row]) - centred(want[0])).max() < TOL, (name, row)
        assert np.abs(centred(got_step[row]) - centred(want[1])).max() < TOL, (name, row)
    # the step's multiplier moves the logits least (the step's bias outweighs its product): 3e-3
    assert np.abs(centred(got) - centred(base)).max() > 20 * TOL, name
    values = [getattr(C, n) for n in MULTIPLIERS[:7]] + list(C.ssm_multipliers) + list(C.mlp_multipliers)
    assert len(set(values)) == len(values) == 14  # each a value of its own


# -- the reference against the published implementation -------------------------------------


def _published(cfg: dict):
    transformers = pytest.importorskip("transformers")
    if not hasattr(transformers, "FalconH1ForCausalLM"):
        pytest.skip("this transformers has no falcon_h1")
    import torch

    config = transformers.FalconH1Config(
        **cfg, tie_word_embeddings=False, attention_bias=False, mlp_bias=False, mamba_conv_bias=True,
        mamba_proj_bias=False, projectors_bias=False, max_position_embeddings=512,
        attn_implementation="eager",
    )
    return torch, transformers, transformers.FalconH1ForCausalLM(config).float().eval()


def test_the_reference_is_the_published_implementation(state):
    """The same state dict through ``FalconH1ForCausalLM`` (float32, CPU, its
    ``torch_forward`` path: no fused kernel is installed) and through the
    reference: a whole forward's logits at every position, and ONE step
    through the published cache (keys, convolution state, scan state) against
    the reference's forward one token longer."""
    cfg = hf_config(mamba_chunk_size=8)  # several of ITS chunks in 21 tokens
    torch, _, model = _published(cfg)
    from transformers.models.falcon_h1.modeling_falcon_h1 import (
        FalconHybridMambaAttentionDynamicCache,
    )

    model.load_state_dict({name: torch.tensor(value) for name, value in state.items()}, strict=True)
    ids = np.random.default_rng(0).integers(0, C.vocab_size, size=(1, 22))
    with torch.no_grad():
        whole = model(torch.tensor(ids)).logits[0].numpy()
        cache = FalconHybridMambaAttentionDynamicCache(
            model.config, 1, torch.float32, devices=["cpu"] * C.num_layers
        )
        model(torch.tensor(ids[:, :21]), past_key_values=cache, use_cache=True)
        stepped = model(
            torch.tensor(ids[:, 21:]), past_key_values=cache, use_cache=True,
            cache_position=torch.tensor([21]),
        ).logits[0, -1].numpy()
    (got,) = reference.read_logits(state, cfg, [(ids[0].tolist(), list(range(22)))], EVERY)
    assert np.abs(whole).max() > 1.0
    assert np.abs(got - whole).max() < 1e-4
    assert np.abs(got[21] - stepped).max() < 1e-4


def test_the_family_s_names_are_the_published_ones(state):
    _, _, model = _published(hf_config())
    assert set(model.state_dict()) == set(state)
    for name, tensor in model.state_dict().items():
        assert tuple(tensor.shape) == state[name].shape, name


# -- the loader ------------------------------------------------------------------------------


def test_from_hf_weights_lays_the_family_s_names(state, loaded):
    params, config = loaded
    assert (config.num_layers, config.vocab_size) == (2, C.vocab_size)
    inner, conv = C.d_ssm, C.conv_dim
    for n, layer in enumerate(params["layers"]):
        base = f"model.layers.{n}"
        fused = state[f"{base}.mamba.in_proj.weight"]
        assert fused.shape == (inner + conv + C.ssm_heads, C.hidden_size)
        mixer = layer["mamba"]
        assert np.array_equal(mixer["in_z"]["kernel"], fused[:inner].T)
        assert np.array_equal(mixer["in_xbc"]["kernel"], fused[inner:inner + conv].T)
        assert np.array_equal(mixer["in_dt"]["kernel"], fused[inner + conv:].T)
        assert np.array_equal(mixer["conv"], state[f"{base}.mamba.conv1d.weight"][:, 0, :].T)
        for ours, theirs in (
            ("conv_bias", "conv1d.bias"), ("a_log", "A_log"), ("d", "D"), ("dt_bias", "dt_bias"),
            ("norm", "norm.weight"),
        ):
            assert np.array_equal(mixer[ours], state[f"{base}.mamba.{theirs}"]), ours
        assert np.array_equal(mixer["out"]["kernel"], state[f"{base}.mamba.out_proj.weight"].T)
        for which in "qkvo":
            want = state[f"{base}.self_attn.{which}_proj.weight"].T
            assert np.array_equal(layer["attn"][which]["kernel"], want), which
        for which in ("gate", "up", "down"):
            want = state[f"{base}.feed_forward.{which}_proj.weight"].T
            assert np.array_equal(layer["mlp"][which]["kernel"], want), which
        assert np.array_equal(layer["input_norm"], state[f"{base}.input_layernorm.weight"])
        assert np.array_equal(layer["pre_ff_norm"], state[f"{base}.pre_ff_layernorm.weight"])
    assert np.array_equal(params["token_embed"], state["model.embed_tokens.weight"])
    assert np.array_equal(params["lm_head"], state["lm_head.weight"].T)  # untied
    assert np.array_equal(params["final_norm"], state["model.final_layernorm.weight"])
    # the served layout is ``init_params``' layout
    drawn = falcon_h1.init_params(jax.random.PRNGKey(0), C)
    assert jax.tree_util.tree_structure(drawn) == jax.tree_util.tree_structure(params)
    same = jax.tree_util.tree_map(lambda a, b: a.shape == b.shape, drawn, params)
    assert all(jax.tree_util.tree_leaves(same))


def test_a_checkpoint_whose_shapes_are_not_the_preset_s_is_refused(state):
    with pytest.raises(ValueError, match="in_proj is"):
        falcon_h1.from_hf_weights(state, dataclasses.replace(C, ssm_heads=3))
    with pytest.raises(ValueError, match="names no layer"):
        falcon_h1.from_hf_weights({"model.embed_tokens.weight": state["model.embed_tokens.weight"]}, C)


def test_a_checkpoint_on_disk_is_served_as_it_names(tmp_path):
    from safetensors.numpy import save_file

    from llm_weighted_consensus_tpu.models.judge import load_judge_params

    cfg = hf_config(vocab_size=128, num_hidden_layers=1)
    save_file(random_state(cfg, seed=4), str(tmp_path / "model.safetensors"))
    params, config = load_judge_params(str(tmp_path), C, dtype=jnp.float32)
    assert (config.num_layers, config.vocab_size) == (1, 128)  # a stage of one layer, a slice of rows
    assert falcon_h1.experts_held(params, config) == 0
    assert falcon_h1.whole_bound_layers(np.zeros((0, 1)), config) == 0
    assert falcon_h1.expert_tiles(np.zeros((0, 1)), config) == (0, 0)


def test_presets_name_the_seventh_decoder():
    assert judge_module.decoder_of(JUDGE_PRESETS["falcon-h1-34b-instruct"]) is falcon_h1
    assert JUDGE_PRESETS["falcon-h1-test-tiny"] is C and isinstance(C, FalconH1Config)
    p = JUDGE_PRESETS["falcon-h1-34b-instruct"]
    assert p is FALCON_H1_34B_INSTRUCT and len(JUDGE_PRESETS) == 14
    path = os.path.join(ROOT, "bench", "configs", "falcon-h1-34b-instruct.json")
    with open(path, encoding="utf-8") as f:
        published = json.load(f)
    for field, key in KEYS:
        want = published["published"][key] if key == "num_hidden_layers" else published[key]
        assert getattr(p, field) == want, field
    for name in MULTIPLIERS:
        value = getattr(p, name)
        assert (list(value) if isinstance(value, tuple) else value) == published[name], name
    assert published["reduced"] == ["num_hidden_layers"] and published["tie_word_embeddings"] is False
    assert (p.ssm_head_dim, p.conv_dim, p.num_heads // p.num_kv_heads) == (128, 5120, 5)
    sizes = published["dry_run"]["sizes"]
    for field, key in KEYS:
        if key in sizes:
            assert getattr(C, field) == sizes[key], key
    for name in MULTIPLIERS:
        value = getattr(C, name)
        assert (list(value) if isinstance(value, tuple) else value) == sizes[name], name


# -- the panel, the counters, the service ---------------------------------------------------


@pytest.fixture(scope="module")
def judge():
    # a bucket of its own: the dispatch label's count is the process's
    return TpuJudge("falcon-h1-test-tiny", tokenizer=tiny_tokenizer(), max_tokens=432, seed=2)


def test_judge_counts_the_positions_that_moved_a_state(judge):
    before = judge.stats()
    confidence, tokens, ballots = judge.judge(
        candidates(24, np.random.default_rng(3)), "w7 w8 w9", [(5, 3.0), (6, 2.0), (7, 1.0)]
    )
    assert len(confidence) == 24 and abs(confidence.sum() - 1.0) < 1e-6 and len(ballots) == 3
    stats = judge.stats()
    grew = lambda key: stats[key] - before[key]  # noqa: E731
    # every real token of the three calls moved both layers' states; no padded slot did
    assert grew("state_positions_moved") == 2 * tokens == 2 * grew("prefill_tokens")
    assert grew("state_positions_whole") == 2 * 3 * judge.max_tokens
    assert grew("window_keys_causal") == 0 and grew("layer_positions_whole") == 0
    assert grew("index_keys_causal") == 0 and grew("expert_pairs_routed") == 0
    assert stats["expert_tokens"] == [] and stats["layers"] == 2


def test_the_program_names_its_scopes_and_no_other_judge_names_the_scan():
    """``ssd_scan`` is how the benchmark's seventh scope table knows this
    decoder's programs (``bench/falconh1_scopes.py``): no other judge names
    it."""
    from llm_weighted_consensus_tpu.models import afmoe, glm_moe, qwen3_next, sambay
    from llm_weighted_consensus_tpu.models.configs import (
        AFMOE_TEST_TINY, DOTS3_TEST_TINY, PHI4FLASH_TEST_TINY, QWEN3_NEXT_TEST_TINY,
    )

    def text(module, config):
        params = module.init_params(jax.random.PRNGKey(0), config)
        ids = jnp.zeros((1, 32), jnp.int32)

        def both(p, i):
            hidden, caches, _ = module.prefill(p, i, config)
            step = module.decode_step(p, i[:, 0], jnp.full((1,), 31, jnp.int32), caches, config)
            return module.head_logprobs(p, step, config), hidden

        return jax.jit(both).lower(params, ids).as_text(debug_info=True)

    mine = text(falcon_h1, C)
    for scope in (
        "embed_tokens", "ssm_in", "ssm_conv", "ssd_scan", "ssm_norm", "ssm_out", "attn_qkv",
        "causal_attention", "attn_out", "mlp", "head_read",
    ):
        assert f"/{scope}/" in mine, scope
    for module, config in (
        (glm_moe, DOTS3_TEST_TINY), (qwen3_next, QWEN3_NEXT_TEST_TINY), (afmoe, AFMOE_TEST_TINY),
        (sambay, PHI4FLASH_TEST_TINY),
    ):
        assert "/ssd_scan/" not in text(module, config)


def test_int8_control_reaches_every_dense_product_and_moves_the_reads():
    """``JUDGE_QUANTIZE=int8`` is the cell's control: every dense product of
    the module through ``quant.dense_int8``, and the reads move by far more
    than the dry run's limit (float32 round-off)."""
    low = TpuJudge("falcon-h1-test-tiny", tokenizer=tiny_tokenizer(), max_tokens=SEQ, seed=2, quantize="int8")
    base = TpuJudge("falcon-h1-test-tiny", tokenizer=tiny_tokenizer(), max_tokens=SEQ, seed=2)
    for layer in low.params["layers"]:
        assert all("kernel_q" in layer["mamba"][k] for k in ("in_z", "in_xbc", "in_dt", "out"))
        assert all("kernel_q" in layer["attn"][k] for k in "qkvo")
        assert all("kernel_q" in layer["mlp"][k] for k in ("gate", "up", "down"))
        assert layer["mamba"]["a_log"].dtype == jnp.float32  # the scan's own parameters stay
    # every ``kernel`` of the tree is quantized: none is left for a product to take
    assert not [p for p, _ in jax.tree_util.tree_leaves_with_path(low.params) if "'kernel'" in str(p)]
    assert sum("'kernel'" in str(p) for p, _ in jax.tree_util.tree_leaves_with_path(base.params)) == 2 * 11
    assert low.params["token_embed"].dtype == base.params["token_embed"].dtype  # and the embedding
    assert low.params["lm_head"].dtype == base.params["lm_head"].dtype  # and the head
    assert low.config.quantize == "int8"
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
        assert body["scorer"] == "judge" and body["model"] == "falcon-h1-test-tiny"
        assert len(body["confidence"]) == 21
        assert sum(body["confidence"]) == pytest.approx(1.0, abs=1e-6)
        assert [b["seed"] for b in body["ballots"]] == [7, 8]
        metrics = await (await client.get("/metrics")).json()
        assert metrics["roofline"]["buckets"]["judge(n=2,s=432)"]["count"] >= 1
        assert metrics["judge"]["dispatches"] == dispatched + 1
        assert 0 < metrics["judge"]["state_positions_moved"] < metrics["judge"]["state_positions_whole"]

    go(with_client(app, drive))


def test_build_judge_knows_the_presets(monkeypatch):
    from llm_weighted_consensus_tpu.serve import Config
    from llm_weighted_consensus_tpu.serve.__main__ import build_judge

    monkeypatch.delenv("LWC_ALLOW_RANDOM_PARAMS", raising=False)
    config = Config.from_env({"JUDGE_MODEL": "falcon-h1-test-tiny", "JUDGE_MAX_TOKENS": "64"})
    with pytest.raises(ValueError, match="JUDGE_WEIGHTS"):
        build_judge(config)
    built = build_judge(config, allow_synthetic=True)
    assert built.max_tokens == 64 and built.decoder is falcon_h1
    assert built.config.num_layers == 2 and built.config.ssm_groups == 2
    with pytest.raises(ValueError, match="falcon-h1-34b-instruct"):
        build_judge(Config.from_env({"JUDGE_MODEL": "falcon-h1"}))


# -- the family's counts (the benchmark's yardstick) ---------------------------------------


def test_the_family_counts_the_published_algorithm():
    path = os.path.join(ROOT, "bench", "configs", "falcon-h1-34b-instruct.json")
    with open(path, encoding="utf-8") as f:
        cfg = json.load(f)
    rows, s, layers = 3, 8192, cfg["num_hidden_layers"]
    assert (family.ssm_width(cfg), family.conv_width(cfg), family.in_proj_width(cfg)) == (4096, 5120, 9248)
    assert family.mlp_weights(cfg) == 330_301_440
    assert family.ssm_weights(cfg) == 5120 * 9248 + 4096 * 5120 == 68_321_280
    assert family.attention_weights(cfg) == 2 * 5120 * 2560 + 2 * 5120 * 512 == 31_457_280
    # by hand, a slot and layer: C B^T a group, the masked scores times x, the
    # chunk's state and the state's output
    assert family.ssd_slot_flops(cfg) == 2 * 128 * 256 * 2 + 2 * 128 * 4096 + 2 * (2 * 256 * 4096)
    assert family.ssd_flops(cfg, rows, s) == layers * 3 * s * 5_373_952
    assert family.ssd_bytes(cfg, rows, s) == layers * 3 * s * ((2 * 4096 + 2 * 512) * 2 + 4 * 32)
    assert family.causal_attention_flops(cfg, rows, s) == layers * 3 * (s * (s + 1) // 2) * 20 * 128 * 4
    assert family.causal_attention_bytes(cfg, rows, s) == layers * 3 * s * 2 * 24 * 128 * 2
    total = family.forward_flops(cfg, rows, s)
    products = 2 * layers * 3 * s * family.layer_weights(cfg)
    assert products == pytest.approx(layers * 21.14e12, rel=0.002)  # 21.1 TFLOP a layer
    # the MLP is 77% of the products, the mixers' projections 23%
    assert family.mlp_weights(cfg) / family.layer_weights(cfg) == pytest.approx(0.768, abs=0.001)
    assert products < total < 1.08 * products
    sizes = sum(int(np.prod(shape)) for _, shape, _ in family.tensors(cfg))
    assert 2 * sizes == cfg["bytes"]["checkpoint"]
    assert family.INIT_STD == cfg["assumed"]["init_std"]
