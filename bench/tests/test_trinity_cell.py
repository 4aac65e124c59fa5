"""The fifth judge cell's own files (PR 41), on the CPU at the configuration's
``dry_run`` sizes: every published number against the catalog, the file's byte
arithmetic, the traffic's tokens against the bucket, the reference against the
program in float32, the whole command sound and broken (the kinds swapped; the
gate left out), the int8 control, the family's counts against hand arithmetic
at the cell's shapes, the scope table against the scopes the decoder names, and
the new reducers on a made-up trace."""

import argparse
import json
import os
import re

import numpy as np
import pytest

import byname
import checkpoints
import run as bench_run
import trinity_scopes
from test_judge_cell import broken_judge_env, last_line

CELL = "trinity-large-preview.n64-c16k.closed4"
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PUBLISHED = json.load(open(os.path.join(BENCH, "configs", "trinity-large-preview.json")))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
FULL, SLIDING = "full_attention", "sliding_attention"


def load_cell():
    return bench_run.load_cell(CELL, dry=True)


def args(seed, control=False):
    return argparse.Namespace(
        workload=CELL, seed=seed, seconds=3.0, trace=0, dry_run=True,
        control=control, benchmark=None,
    )


# -- the configuration against its source ----------------------------------------


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog beside the guide")
def test_every_published_number_stands_unless_reduced():
    with open(CATALOG, encoding="utf-8") as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Trinity-Large-Preview")
    assert PUBLISHED["source"] == row["source_url"]
    assert PUBLISHED["reduced"] == [
        "num_hidden_layers", "num_dense_layers", "num_experts", "vocab_size"
    ]
    for key, value in row["config"].items():
        if key in PUBLISHED["reduced"]:
            assert PUBLISHED["published"][key] == value and PUBLISHED[key] != value
        else:
            assert PUBLISHED[key] == value, key
    assert PUBLISHED["num_experts_routed"] == row["config"]["num_experts"]
    assert PUBLISHED["layers_served"] == [5, 6, 7, 8, 9]
    kinds = [PUBLISHED["layer_types"][n] for n in PUBLISHED["layers_served"]]
    assert kinds == [SLIDING, SLIDING, FULL, SLIDING, SLIDING]  # one whole period behind layer 5
    assert PUBLISHED["layers_served"][0] == row["config"]["num_dense_layers"] - 1  # the last dense one
    assert PUBLISHED["vocab_size"] * 8 == row["config"]["vocab_size"]  # the guide's floor
    assert PUBLISHED["num_experts"] * 8 == row["config"]["num_experts"]
    assert set(PUBLISHED["reduced"]) == set(PUBLISHED["reduced_from"])
    assert PUBLISHED["max_tokens"] == 16384 == int(PUBLISHED["server_env"]["JUDGE_MAX_TOKENS"])


def test_the_file_s_byte_arithmetic_adds_up():
    b = PUBLISHED["bytes"]
    h, heads, kv, hd = 3072, 48, 8, 128
    assert b["attention_a_layer"] == 2 * (2 * h * heads * hd + 2 * h * kv * hd + heads * hd * h)
    assert b["norms_a_layer"] == 2 * (4 * h + 2 * hd)
    assert b["dense_mlp"] == 2 * 3 * h * 12288 and b["expert"] == b["shared_expert"] == 2 * 3 * h * h
    assert b["experts_held_a_layer"] == 32 * b["expert"]
    assert b["dense_layer"] == b["attention_a_layer"] + b["norms_a_layer"] + b["dense_mlp"]
    assert b["sparse_layer"] == (
        b["attention_a_layer"] + b["norms_a_layer"] + b["router_and_bias"]
        + b["experts_held_a_layer"] + b["shared_expert"]
    )
    assert b["embedding_and_head"] == 2 * 2 * 25024 * h
    assert b["checkpoint"] == (
        b["dense_layer"] + 4 * b["sparse_layer"] + b["embedding_and_head"] + b["final_norm"]
    )
    assert round(b["checkpoint"] / 1e9, 2) == 8.64 and 0.5 < b["checkpoint"] / 16e9 < 0.6
    assert round(b["sparse_layer"] / 1e9, 2) == 2.00 and round(b["dense_layer"] / 1e9, 2) == 0.35
    # the published layer: 256 experts are 14.5 GB, a whole sparse layer 14.7
    assert round((b["sparse_layer"] + 224 * b["expert"]) / 1e9, 1) == 14.7


def test_the_checkpoint_is_the_cut_s_tensors_under_their_published_numbers():
    family = byname.module("families", "afmoe")
    specs = family.tensors(PUBLISHED)
    assert len({name for name, _, _ in specs}) == len(specs)
    assert 2 * sum(int(np.prod(shape)) for _, shape, _ in specs) == PUBLISHED["bytes"]["checkpoint"]
    assert len(checkpoints.plan_shards(specs, checkpoints.SHARD_BYTES)) == 2
    layers = sorted({int(m.group(1)) for name, _, _ in specs if (m := re.match(r"model\.layers\.(\d+)\.", name))})
    assert layers == [5, 6, 7, 8, 9]
    assert ("model.layers.5.mlp.gate_proj.weight", (12288, 3072), "normal") in specs
    assert ("model.layers.7.self_attn.gate_proj.weight", (6144, 3072), "normal") in specs
    assert ("model.layers.7.self_attn.k_proj.weight", (1024, 3072), "normal") in specs
    assert ("model.layers.6.mlp.router.gate.weight", (256, 3072), "normal") in specs
    assert ("model.layers.9.mlp.experts.31.down_proj.weight", (3072, 3072), "normal") in specs
    assert ("model.layers.8.post_mlp_layernorm.weight", (3072,), "ln_scale") in specs
    assert not any(".experts.32." in name for name, _, _ in specs)
    assert [family.kind_of(PUBLISHED, i) for i in range(5)] == [SLIDING, SLIDING, FULL, SLIDING, SLIDING]
    assert [family.is_dense(PUBLISHED, i) for i in range(5)] == [True] + [False] * 4


# -- the traffic ------------------------------------------------------------------


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 5, 3141592653])
def test_every_call_fits_the_bucket(seed):
    mix = json.load(open(os.path.join(BENCH, "traffic", "n64-c16k.closed4.json")))
    gen = byname.module("generators", mix["generator"])
    tok = PUBLISHED["tokenizer"]
    requests = gen.generate(mix, seed, 50.0, PUBLISHED["vocab_size"] - tok["specials"])
    assert len(requests) == 152  # 3.0 a second of window, in whole rounds of 4 callers
    tokens = {gen.request_tokens(r, tok["overhead"]) for r in requests}
    assert tokens == {15125}  # 2 + 2000 + 3 + 64 x 5 + 64 x 200: the same work a request
    assert max(tokens) <= PUBLISHED["max_tokens"] and max(tokens) > 0.9 * PUBLISHED["max_tokens"]
    words = sorted(len(w) for w in requests[0]["words"])
    assert (words[0], words[-1]) == (141, 259) and sum(words) == 64 * 200
    assert max(int(w.max()) for r in requests[:4] for w in r["words"]) < 25024 - tok["specials"]


# -- the reference against the program ------------------------------------------


def test_the_reference_is_the_programs_forward_in_float32():
    """The seeded dry checkpoint names 8 experts of a router 16 wide and five
    layers (sliding dense; sliding, full, sliding, sliding): both sides serve
    that, the program through its kernels and its two kinds of cache, the
    reference through whole mask rows and one forward."""
    import jax.numpy as jnp

    from llm_weighted_consensus_tpu.models import afmoe
    from llm_weighted_consensus_tpu.models.configs import AFMOE_TEST_TINY

    _, _, config, cfg, _, _ = load_cell()
    ref = byname.module("references", config["reference"])
    state = checkpoints.make_state(config["family"], cfg, 2**31 + 9)
    f32 = {k: np.asarray(v).astype(np.float32) for k, v in state.items()}
    params, served = afmoe.from_hf_weights(f32, AFMOE_TEST_TINY, dtype=jnp.float32)
    assert afmoe.experts_held(params, served) == 8 and served.num_experts == 16
    assert served.layer_types == AFMOE_TEST_TINY.layer_types
    rng = np.random.default_rng(2)
    lens = [150, 9]  # above the window of 24 and off every block; below it
    ids = np.zeros((2, 160), np.int32)
    for row, n in enumerate(lens):
        ids[row, :n] = rng.integers(32, cfg["vocab_size"], size=n)
    letters = list(range(4, 24))
    token = np.array([5, 17], np.int32)
    hidden, caches, loads = afmoe.prefill(
        params, jnp.asarray(ids), served, lens=jnp.asarray(lens, jnp.int32)
    )
    assert [cache[0].shape[1] for cache in caches] == [23, 23, 160, 23, 23]
    step = afmoe.decode_step(params, jnp.asarray(token), jnp.asarray(lens, jnp.int32), caches, served)
    loads = np.asarray(loads)
    assert 0 < loads[:, :8].sum() < loads.sum()  # some pairs here, some elsewhere
    calls = [
        (ids[row, :n].tolist() + [int(token[row])], [n - 1, n // 2, n])
        for row, n in enumerate(lens)
    ]
    reads = ref.read_logits(f32, cfg, calls, letters)
    centred = lambda x: x - x.mean(axis=1, keepdims=True)  # noqa: E731
    for row, n in enumerate(lens):
        got = np.asarray(afmoe.head_logprobs(params, hidden[row, [n - 1, n // 2]], served))
        last = np.asarray(afmoe.head_logprobs(params, step[row][None], served))
        got = np.concatenate([got, last])[:, letters]
        assert np.abs(centred(got) - centred(reads[row])).max() < 5e-6


# -- the whole command ------------------------------------------------------------


def test_a_sound_run_is_correct(capsys):
    assert bench_run.run(args(2**31 + 99)) == 0
    result = last_line(capsys)
    assert result["correct"] is True and result["failed"] == 0
    assert result["check"]["ballot_logit_rms"]["value"] < 2e-6
    assert result["check"]["ballot_mismatches"]["value"] == 0
    assert result["check"]["confidence_abs_err"]["value"] < 1e-6


KINDS_SWAPPED = """
import llm_weighted_consensus_tpu.models.configs as configs
# "every layer turns and slides: the full layer is one in five"
configs.AfmoeConfig.slides = lambda self, layer: True
"""

NO_GATE = """
import llm_weighted_consensus_tpu.models.afmoe as afmoe
# the elementwise gate left out: one product and a sigmoid a layer saved
afmoe.gated = lambda ctx, gate: ctx
"""

NORM_AFTER_THE_SUM = """
import llm_weighted_consensus_tpu.models.afmoe as afmoe
_rms = afmoe.rms
def _attn_out(ctx, gate, p, norm, eps):
    # the output product un-normed: "the stream's next norm will do"
    return afmoe.dense(afmoe.gated(ctx, gate), p["o"])
afmoe._attn_out = _attn_out
"""


@pytest.mark.parametrize(
    "patch", [KINDS_SWAPPED, NO_GATE, NORM_AFTER_THE_SUM],
    ids=["the_full_layer_slides", "the_gate_is_left_out", "a_branch_is_not_normed"],
)
def test_a_broken_timed_path_is_not_correct(patch, capsys, monkeypatch):
    broken_judge_env(monkeypatch, patch)
    assert bench_run.run(args(2**31 + 99)) == 1
    result = last_line(capsys)
    assert result["correct"] is False and result["failed"] == 0
    number = result["check"]["ballot_read_rms_median"]
    assert number["value"] > number["limit"]


def test_the_int8_control_is_not_correct_at_dry_size(capsys):
    assert bench_run.run(args(2**31 + 99, control=True)) == 1
    result = last_line(capsys)
    assert result["correct"] is False
    assert result["check"]["ballot_logit_rms"]["value"] > 2e-6
    assert result["check"]["confidence_abs_err"]["value"] < 1e-6  # the tally is exact


# -- counts ------------------------------------------------------------------------


def test_operations_against_hand_arithmetic_at_the_cells_shapes():
    family = byname.module("families", "afmoe")
    rows, seq = 3, 16384
    assert family.band_pairs(PUBLISHED, seq) == 4096 * 4097 // 2 + (seq - 4096) * 4096 == 58_722_304
    assert family.causal_pairs(seq) == 134_225_920
    assert family.band_pairs(PUBLISHED, 4000) == family.causal_pairs(4000)  # a call inside one window
    assert family.band_pairs(PUBLISHED, seq) / family.causal_pairs(seq) == pytest.approx(0.43749, abs=1e-5)
    # at the shared 8k file the band would be 75% of the causal pairs: why the cell is at 16k
    assert family.band_pairs(PUBLISHED, 8192) / family.causal_pairs(8192) == pytest.approx(0.75, abs=0.001)
    window = family.window_attention_flops(PUBLISHED, rows, seq)
    assert window == 4 * rows * 58_722_304 * 48 * (128 + 128) * 2
    assert 4.32e12 < window / 4 < 4.34e12  # the issue: 4.3 TFLOP a sliding layer
    causal = family.causal_attention_flops(PUBLISHED, rows, seq)
    assert causal == rows * 134_225_920 * 48 * 256 * 2 and 9.89e12 < causal < 9.91e12
    assert causal / (window / 4) == pytest.approx(2.286, abs=0.001)  # a full layer's 2.3 times a sliding one's
    moved = family.window_attention_bytes(PUBLISHED, rows, seq) / 4
    assert moved == rows * seq * (2 * 6144 + 2 * 1024) * 2
    assert window / 4 / 197e12 > 10 * moved / 819e9  # compute-bound, far
    assert family.head_norm_flops(PUBLISHED, rows, seq) == 0
    assert family.head_norm_bytes(PUBLISHED, rows, seq) == 5 * rows * seq * (6144 + 1024) * 2 * 2
    # projections 6.2 TFLOP a layer, the shared expert 2.8, the dense MLP 11.1
    assert 2 * rows * seq * family.attention_weights(PUBLISHED) == pytest.approx(6.18e12, rel=0.005)
    assert 2 * rows * seq * 3 * 3072 * 3072 == pytest.approx(2.78e12, rel=0.005)
    assert 2 * rows * seq * 3 * 3072 * 12288 == pytest.approx(11.1e12, rel=0.005)
    # the experts: from the pairs counted, not from 4 a token
    pair = 2 * 3 * 3072 * 3072
    assert family.expert_products_flops(PUBLISHED, rows, seq, held_pairs=1000) == 1000 * pair
    expected = family.expected_held_pairs(PUBLISHED, rows, seq)
    assert expected == 4 * rows * seq * 4 * 32 / 256 == 4 * 24_576
    assert expected / 4 / 32 == 768  # rows an expert held; the eight chips' batches would bring 6,144
    assert expected * pair / 4 == pytest.approx(1.39e12, rel=0.005)  # the issue: 1.4 a layer
    weights = 4 * 32 * 3 * 3072 * 3072
    assert family.expert_products_bytes(PUBLISHED, rows, seq, held_pairs=0) == 2 * weights
    whole = family.forward_flops(PUBLISHED, rows, seq)
    assert 86.0e12 < whole < 86.6e12  # the issue's 86
    assert (window + causal) / whole == pytest.approx(0.316, abs=0.004)  # its 31%
    more = family.forward_flops(PUBLISHED, rows, seq, held_pairs=2 * expected)
    assert more - whole == pytest.approx(expected * pair)
    by_hand = (
        rows * seq * 2 * (5 * 62_914_560 + 3 * 3072 * 12288 + 4 * (256 * 3072 + 3 * 3072 * 3072))
        + window + causal + expected * pair
    )
    decode_and_reads = whole - by_hand
    assert 0 < decode_and_reads < 0.01e12  # one token through 4.3 G parameters and two caches, two reads


# -- the scope table and the decoder's own names -------------------------------------------


def test_the_scope_table_covers_every_scope_the_decoder_names():
    root = os.path.dirname(BENCH)
    named = set()
    for module in ("afmoe", "judge"):
        source = open(os.path.join(root, "llm_weighted_consensus_tpu", "models", module + ".py")).read()
        named |= set(re.findall(r'named_scope\(\s*"(\w+)"', source))
        named |= {n for pair in re.findall(r'named_scope\("(\w+)" if \w+ else "(\w+)"\)', source) for n in pair}
    inner = {"experts_layout", "experts_swiglu", "experts_combine"}  # decoder_parts', under experts_routed
    assert named == trinity_scopes.SCOPES
    grouped = {s for group in trinity_scopes.GROUPS.values() for s in group}
    assert grouped - {"unscoped"} <= trinity_scopes.SCOPES and not inner & trinity_scopes.SCOPES
    # what no share holds is PERF.md's table by scope
    assert trinity_scopes.SCOPES - grouped == {"embed_tokens", "head_read", "ballot_vote"}


def made_up_trace():
    """Three judge programs of 100 us; the middle one (the one kept) holds the
    four kernels, scoped fusions of the gate, the projections, the norm behind
    the MLP, a decode-step fusion, a path-less copy that the router's fusion
    alone reads, and the held experts' ``cond``, which spans its branch's
    kernel (a container: counted, it would hold the kernel's time twice)."""
    def ins(name, tf_op, operands=()):
        return {"name": name, "program": "1", "tf_op": tf_op, "category": None,
                "operands": list(operands)}

    base = "jit(judge_panel)/jit(main)/"
    instructions = [
        ins("head_norm_turn.3", base + "attn_qkv/jit(head_norm_turn)/pallas_call"),
        ins("window_attention_blockwise.4", base + "window_attention/jit(window_attention_blockwise)/pallas_call"),
        ins("causal_attention_blockwise.1", base + "causal_attention/jit(causal_attention_blockwise)/pallas_call"),
        ins("grouped_expert_product.7", base + "experts_routed/experts_swiglu/jit(grouped_expert_product)/pallas_call"),
        ins("fusion.1", base + "attn_gate/mul"),
        ins("fusion.2", base + "attn_qkv/dot_general"),
        ins("fusion.3", base + "dense_mlp/dot_general"),
        ins("fusion.4", base + "decode_step/window_attention/dot_general"),
        ins("copy.9", None, ()),
        ins("fusion.5", base + "router/dot_general", ("copy.9",)),
        ins("fusion.6", base + "mlp_norm/add"),
        ins("fusion.7", base + "convert_element_type"),
        ins("cond.14", base + "cond"),
    ]
    durations = [5_000, 20_000, 10_000, 10_000, 5_000, 15_000, 10_000, 5_000, 2_000, 8_000, 6_000, 4_000]
    ops = []
    for program in range(3):
        t = program * 200_000
        for index, dur in enumerate(durations):
            if instructions[index]["name"].startswith("grouped_expert_product"):
                ops.append([len(durations), t, dur])  # the cond over its branch
            ops.append([index, t, dur])
            t += dur
    modules = [["jit_judge_panel(123)", p * 200_000, 100_000] for p in range(3)]
    return {"modules": modules, "instructions": instructions, "ops": ops, "spans": []}


def ctx_for(trace, judge_before=None, judge_after=None, family="afmoe"):
    label = "judge(n=3,s=16384)"
    before = {"roofline": {"buckets": {label: {"count": 5}}}}
    after = {"roofline": {"buckets": {label: {"count": 8}}}}
    if judge_after is not None:
        before["judge"], after["judge"] = judge_before, judge_after
    return {
        "scoped": trace,
        "config": {"trace_modules": ["jit_judge_panel"], "family": family},
        "cfg": PUBLISHED,
        "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        "profile": {"before": before, "after": after},
    }


BAND, CAUSAL = 4 * 3 * 58_722_304, 4 * 3 * 134_225_920
COUNTED = (
    {"dispatches": 5, "expert_pairs_here": 100_000, "expert_pairs_routed": 800_000,
     "window_keys_band": BAND, "window_keys_causal": CAUSAL,
     "expert_load_max_over_mean_sum": 10.0,
     "expert_tiles_laid": 5 * 384, "expert_tiles_in_use": 5 * 120},
    {"dispatches": 8, "expert_pairs_here": 400_000, "expert_pairs_routed": 3_159_296,
     "window_keys_band": 4 * BAND, "window_keys_causal": 4 * CAUSAL,
     "expert_load_max_over_mean_sum": 19.0,
     "expert_tiles_laid": 8 * 384, "expert_tiles_in_use": 5 * 120 + 3 * 124},
)


def test_the_scopes_and_shares():
    assert trinity_scopes.scope_of("a/decode_step/window_attention/x") == "decode_step"
    assert trinity_scopes.scope_of("a/window_attention/jit(window_attention_blockwise)/x") == "window_attention"
    assert trinity_scopes.scope_of("a/attn_qkv/jit(head_norm_turn)/x") == "attn_qkv"
    assert trinity_scopes.scope_of("a/mlp_norm/x") == "mlp_norm"
    assert trinity_scopes.scope_of("a/latent_q/x") == "unscoped"  # the first judge's
    ctx = ctx_for(made_up_trace())
    share = {g: byname.module("reducers", f"trinity_share_{g}").reduce(ctx)
             for g in trinity_scopes.GROUPS}
    assert share == {
        "window_attention": 20.0, "full_attention": 10.0,
        "projections": 25.0,  # the head-norm kernel and the gate's multiply among them
        "experts": 36.0,  # the kernel, the dense MLP, the router's fusion and the copy it alone reads, the norm
        "decode": 5.0, "unscoped": 4.0,
    }
    assert sum(share.values()) == 100.0
    assert trinity_scopes.share({**ctx, "scoped": None}, "experts") is None


def test_the_rooflines_read_the_kernels_own_events_and_the_counted_pairs():
    family = byname.module("families", "afmoe")
    ctx = ctx_for(made_up_trace(), *COUNTED)
    got = byname.module("reducers", "trinity_window_attention_roofline").reduce(ctx)
    assert got == pytest.approx(100 * family.window_attention_flops(PUBLISHED, 3, 16384) / 197e12 / 20e-6)
    got = byname.module("reducers", "trinity_causal_attention_roofline").reduce(ctx)
    assert got == pytest.approx(100 * family.causal_attention_flops(PUBLISHED, 3, 16384) / 197e12 / 10e-6)
    got = byname.module("reducers", "trinity_head_norm_roofline").reduce(ctx)
    assert got == pytest.approx(100 * family.head_norm_bytes(PUBLISHED, 3, 16384) / 819e9 / 5e-6)
    pairs = 300_000 / 3
    got = byname.module("reducers", "expert_products_roofline_held").reduce(ctx)
    least = max(
        family.expert_products_flops(PUBLISHED, 3, 16384, pairs) / 197e12,
        family.expert_products_bytes(PUBLISHED, 3, 16384, pairs) / 819e9,
    )
    assert got == pytest.approx(100 * least / 10e-6)
    got = byname.module("reducers", "trinity_forward_mfu").reduce(ctx)
    assert got == pytest.approx(
        100 * family.forward_flops(PUBLISHED, 3, 16384, pairs) / (100e-6 * 197e12)
    )


def test_the_counters_give_the_shares():
    import layers

    def read(name):
        spec = json.load(open(os.path.join(BENCH, "layer_metrics", name + ".json")))
        return layers.read_metrics(spec["read"], {"judge": COUNTED[0]}, {"judge": COUNTED[1]})

    assert read("window.band_share.trinity") == pytest.approx(43.749, abs=1e-3)
    assert read("experts.held_pairs_share.trinity") == pytest.approx(100 * 300_000 / 2_359_296)
    assert read("experts.load_max_over_mean") == pytest.approx(3.0)
    # of the 384 row tiles a dispatch's four sparse layers lay, those that hold a pair
    assert read("experts.tiles_in_use_share") == pytest.approx(100 * 124 / 384)


def test_another_judge_s_program_gives_nothing_to_read():
    """The parent commit cannot run the cell at all; another judge's program
    (the fourth names ``window_attention`` and ``attn_gate`` too, but no
    ``mlp_norm``) gives every reader of this table nothing, and another family
    counts no head norm."""
    bare = made_up_trace()
    bare["instructions"] = [
        dict(i, tf_op=(i["tf_op"] or "").replace("mlp_norm", "expert_shared") or None)
        for i in bare["instructions"]
    ]
    ctx = ctx_for(bare, *COUNTED)
    for group in trinity_scopes.GROUPS:
        assert byname.module("reducers", f"trinity_share_{group}").reduce(ctx) is None
    for name in ("trinity_forward_mfu", "trinity_window_attention_roofline",
                 "trinity_causal_attention_roofline", "trinity_head_norm_roofline"):
        assert byname.module("reducers", name).reduce(ctx) is None
    other = ctx_for(made_up_trace(), *COUNTED, family="dots3_note")
    assert byname.module("reducers", "trinity_head_norm_roofline").reduce(other) is None
    assert byname.module("reducers", "trinity_forward_mfu").reduce(ctx_for(made_up_trace())) is None
