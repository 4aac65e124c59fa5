"""The fourth judge cell's own files (PR 39), on the CPU at the configuration's
``dry_run`` sizes: the reference against the program in float32, the whole
command sound and broken (a window ignored; a gate left out), the int8
control, the family's counts against hand arithmetic at the cell's shapes, and
the new scope table and reducers on a made-up trace."""

import argparse
import json
import os

import numpy as np
import pytest

import byname
import checkpoints
import dots3_scopes
import run as bench_run
from test_judge_cell import broken_judge_env, last_line

CELL = "dots3-note-prev.n64-c8k.closed4"
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PUBLISHED = json.load(open(os.path.join(BENCH, "configs", "dots3-note-prev.json")))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


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
        row = next(r for r in map(json.loads, f) if r["name"] == "dots3-note-prev")
    assert PUBLISHED["source"] == row["source_url"]
    assert PUBLISHED["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    for key, value in row["config"].items():
        if key in PUBLISHED["reduced"]:
            assert PUBLISHED["published"][key] == value and PUBLISHED[key] != value
        else:
            assert PUBLISHED[key] == value, key
    assert PUBLISHED["n_routed_experts_routed"] == row["config"]["n_routed_experts"]
    assert PUBLISHED["layers_served"] == [0, 1, 2, 3, 4]
    kinds = [PUBLISHED["layer_types"][i] for i in PUBLISHED["layers_served"]]
    assert kinds == ["full_attention"] * 2 + ["sliding_attention"] * 3  # one whole period behind layer 0
    assert PUBLISHED["vocab_size"] * 8 == row["config"]["vocab_size"]  # the guide's floor
    assert PUBLISHED["n_routed_experts"] >= 8 and PUBLISHED["num_hidden_layers"] >= 5


# -- the reference against the program ------------------------------------------


def test_the_reference_is_the_programs_forward_in_float32():
    """The seeded dry checkpoint names 8 experts of a router 16 wide and five
    layers (full, full, sliding x 3): both sides serve that, the program
    through its kernels and its four kinds of cache, the reference through
    whole mask rows and one forward."""
    import jax.numpy as jnp

    from llm_weighted_consensus_tpu.models import glm_moe
    from llm_weighted_consensus_tpu.models.configs import DOTS3_TEST_TINY

    _, _, config, cfg, _, _ = load_cell()
    ref = byname.module("references", config["reference"])
    state = checkpoints.make_state(config["family"], cfg, 2**31 + 9)
    f32 = {k: np.asarray(v).astype(np.float32) for k, v in state.items()}
    params, served = glm_moe.from_hf_weights(f32, DOTS3_TEST_TINY, dtype=jnp.float32)
    assert glm_moe.experts_held(params, served) == 8 and served.n_routed_experts == 16
    assert served.layer_types == DOTS3_TEST_TINY.layer_types
    rng = np.random.default_rng(2)
    lens = [150, 9]  # above the window of 17 and off every block; below it
    ids = np.zeros((2, 160), np.int32)
    for row, n in enumerate(lens):
        ids[row, :n] = rng.integers(32, cfg["vocab_size"], size=n)
    letters = list(range(4, 24))
    token = np.array([5, 17], np.int32)
    hidden, caches, loads = glm_moe.prefill(
        params, jnp.asarray(ids), served, lens=jnp.asarray(lens, jnp.int32)
    )
    assert [len(cache) for cache in caches] == [3, 3, 2, 2, 2]
    assert caches[2][0].shape == (2, 16, 32)  # the window's 16 positions before a call's end
    step = glm_moe.decode_step(params, jnp.asarray(token), jnp.asarray(lens, jnp.int32), caches, served)
    loads = np.asarray(loads)
    assert 0 < loads[:, :8].sum() < loads.sum()  # some pairs here, some elsewhere
    calls = [
        (ids[row, :n].tolist() + [int(token[row])], [n - 1, n // 2, n])
        for row, n in enumerate(lens)
    ]
    reads = ref.read_logits(f32, cfg, calls, letters)
    centred = lambda x: x - x.mean(axis=1, keepdims=True)  # noqa: E731
    for row, n in enumerate(lens):
        got = np.asarray(glm_moe.head_logprobs(params, hidden[row, [n - 1, n // 2]], served))
        last = np.asarray(glm_moe.head_logprobs(params, step[row][None], served))
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


EVERY_CAUSAL_KEY = """
import llm_weighted_consensus_tpu.models.glm_moe as glm_moe
_sound = glm_moe.causal_attention_blockwise
def _broken(q, k, v, *, heads, scale, window):
    # a sliding layer attends every causal key "because the band holds most of the mass"
    return _sound(q, k, v, heads=heads, scale=scale)
glm_moe.window_attention_blockwise = _broken
"""

NO_GATE = """
import llm_weighted_consensus_tpu.models.glm_moe as glm_moe
# the head gate left out: one product and a sigmoid a layer saved
glm_moe._gated = lambda ctx, h, p, heads: ctx
"""


@pytest.mark.parametrize(
    "patch", [EVERY_CAUSAL_KEY, NO_GATE],
    ids=["the_window_is_ignored", "the_gate_is_left_out"],
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


def test_the_checkpoint_is_the_cut_s_five_gigabytes():
    family = byname.module("families", "dots3_note")
    specs = family.tensors(PUBLISHED)
    assert len({name for name, _, _ in specs}) == len(specs)
    total = sum(int(np.prod(shape)) for _, shape, _ in specs)
    h = 5120
    full = h * 1024 + 1024 * 128 * 192 + h * 576 + 512 * 128 * 256 + 128 * 128 * h + 128 * h
    slid = h * 1024 + 1024 * 64 * 256 + h * 1088 + 1024 * 64 * 320 + 64 * 128 * h + 64 * h
    assert round(full / 1e6, 1) == 134.7 and round(slid / 1e6, 1) == 90.8  # the issue's counts
    indexer = 1024 * 64 * 128 + h * 128 + 128 + 128 + h * 64
    assert round(indexer / 1e6, 1) == 9.4
    expert = 3 * h * 1536
    assert round(expert / 1e6, 1) == 23.6 and round(3 * h * 13824 / 1e6, 1) == 212.3
    held = PUBLISHED["n_routed_experts"]
    sparse = 256 * h + 256 + held * expert + expert
    norms = lambda q, kv: 2 * h + q + kv  # noqa: E731
    want = (
        full + indexer + norms(1024, 512) + 3 * h * 13824
        + full + indexer + norms(1024, 512) + sparse
        + 3 * (slid + norms(1024, 1024) + sparse)
        + 2 * 19008 * h + h
    )
    assert total == want
    assert held == 16 and 5.14e9 < 2 * total < 5.17e9  # 5.15 GB of bf16: the rule's sixteen
    assert 8.16e9 < 2 * (total + 4 * 16 * expert) < 8.19e9  # the issue's 8.17 GB at 32 held
    assert len(checkpoints.plan_shards(specs, checkpoints.SHARD_BYTES)) == 2
    assert [family.kind_of(PUBLISHED, i) for i in range(5)] == ["full_attention"] * 2 + ["sliding_attention"] * 3
    assert [family.is_dense(PUBLISHED, i) for i in range(5)] == [True] + [False] * 4
    assert ("model.layers.1.self_attn.indexer.wq_b.weight", (8192, 1024), "normal") in specs
    assert not any("layers.2.self_attn.indexer" in name for name, _, _ in specs)
    assert ("model.layers.3.self_attn.g_proj.weight", (64, h), "normal") in specs
    assert ("model.layers.0.self_attn.g_proj.weight", (128, h), "normal") in specs
    assert ("model.layers.1.mlp.gate.weight", (256, h), "normal") in specs
    assert not any(f".experts.{held}." in name for name, _, _ in specs)


def test_operations_against_hand_arithmetic_at_the_cells_shapes():
    family = byname.module("families", "dots3_note")
    rows, seq = 3, 8192
    assert family.band_pairs(PUBLISHED, seq) == 513 * 514 // 2 + (seq - 513) * 513 == 4_071_168
    assert family.selected_pairs(PUBLISHED, seq) == 14_681_088
    assert family.causal_pairs(seq) == 33_558_528
    assert family.band_pairs(PUBLISHED, 400) == family.causal_pairs(400)  # a call inside one window
    # the new kernel: band pairs x 64 heads x (256 + 128) x 2, the three sliding layers
    window = family.window_attention_flops(PUBLISHED, rows, seq)
    assert window == 3 * rows * 4_071_168 * 64 * 384 * 2
    assert 0.59e12 < window / 3 < 0.61e12  # the issue: 0.6 TFLOP a layer
    moved = family.window_attention_bytes(PUBLISHED, rows, seq) / 3
    assert moved == rows * seq * 64 * (2 * 256 + 2 * 128) * 2
    assert window / 3 / 197e12 > moved / 819e9  # compute-bound, by a little
    # a full layer: selected pairs x 128 heads x (192 + 128) x 2 at the PUBLISHED head width
    attention = family.selected_attention_flops(PUBLISHED, rows, seq)
    assert attention == 2 * rows * 14_681_088 * 128 * 320 * 2
    laid = 2 * rows * 33_558_528 * 128 * (256 + 128) * 2  # every causal pair at 256 lanes
    assert attention / laid == pytest.approx(0.4375 * 320 / 384, abs=1e-4)  # what the masked, laid form reads at most
    # the indexer: causal pairs x 64 heads x 128 dims x 2, the two full layers
    assert family.index_scores_flops(PUBLISHED, rows, seq) == 2 * rows * 33_558_528 * 64 * 128 * 2
    assert family.index_select_bytes(PUBLISHED, rows, seq) == 2 * rows * 33_558_528 * 5
    assert family.index_select_flops(PUBLISHED, rows, seq) == 0
    # the experts: from the pairs counted, not from 8 a token
    pair = 2 * 3 * 5120 * 1536
    assert family.expert_products_flops(PUBLISHED, rows, seq, held_pairs=1000) == 1000 * pair
    expected = family.expected_held_pairs(PUBLISHED, rows, seq)
    assert expected == 4 * rows * seq * 8 * PUBLISHED["n_routed_experts"] / 256
    weights = 4 * PUBLISHED["n_routed_experts"] * 3 * 5120 * 1536
    assert family.expert_products_bytes(PUBLISHED, rows, seq, held_pairs=0) == 2 * weights
    whole = family.forward_flops(PUBLISHED, rows, seq)
    assert 56e12 < whole < 59e12
    more = family.forward_flops(PUBLISHED, rows, seq, held_pairs=2 * expected)
    assert more - whole == pytest.approx(expected * pair)
    # the sliding layers' attention is a small part of their layer: the projections carry it
    assert window / whole < 0.04 and attention / whole > 0.12


# -- the reducers on a made-up trace ---------------------------------------------------


def made_up_trace():
    """Three judge programs of 100 us; the middle one (the one kept) holds the
    five kernels, scoped fusions of the gate, the indexer and the projections,
    a decode-step fusion and a path-less copy that the router's fusion alone
    reads."""
    def ins(name, tf_op, operands=()):
        return {"name": name, "program": "1", "tf_op": tf_op, "category": None,
                "operands": list(operands)}

    base = "jit(judge_panel)/jit(main)/"
    instructions = [
        ins("index_scores.3", base + "index_scores/jit(index_scores)/pallas_call"),
        ins("index_select.2", base + "index_select/jit(index_select)/pallas_call"),
        ins("causal_attention_blockwise.2", base + "selected_attention/jit(causal_attention_blockwise)/pallas_call"),
        ins("window_attention_blockwise.4", base + "window_attention/jit(window_attention_blockwise)/pallas_call"),
        ins("grouped_expert_product.7", base + "experts_routed/experts_swiglu/jit(grouped_expert_product)/pallas_call"),
        ins("fusion.1", base + "attn_gate/dot_general"),
        ins("fusion.2", base + "latent_q/dot_general"),
        ins("fusion.3", base + "dense_mlp/dot_general"),
        ins("fusion.4", base + "decode_step/window_attention/dot_general"),
        ins("copy.9", None, ()),
        ins("fusion.5", base + "router/dot_general", ("copy.9",)),
    ]
    durations = [10_000, 5_000, 20_000, 10_000, 10_000, 5_000, 15_000, 10_000, 5_000, 2_000, 8_000]
    ops = []
    for program in range(3):
        t = program * 200_000
        for index, dur in enumerate(durations):
            ops.append([index, t, dur])
            t += dur
    modules = [["jit_judge_panel(123)", p * 200_000, 100_000] for p in range(3)]
    return {"modules": modules, "instructions": instructions, "ops": ops, "spans": []}


def ctx_for(trace, judge_before=None, judge_after=None, family="dots3_note"):
    label = "judge(n=3,s=8192)"
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


BAND, CAUSAL, CHOSEN = 3 * 3 * 4_071_168, 3 * 33_558_528, 2 * 3 * 14_681_088
COUNTED = (
    {"dispatches": 5, "expert_pairs_here": 100_000, "expert_pairs_routed": 1_600_000,
     "index_keys_selected": CHOSEN, "index_keys_causal": 2 * CAUSAL,
     "window_keys_band": BAND, "window_keys_causal": 3 * CAUSAL},
    {"dispatches": 8, "expert_pairs_here": 250_000, "expert_pairs_routed": 3_959_296,
     "index_keys_selected": 4 * CHOSEN, "index_keys_causal": 8 * CAUSAL,
     "window_keys_band": 4 * BAND, "window_keys_causal": 12 * CAUSAL},
)


def test_the_scopes_and_shares():
    assert dots3_scopes.scope_of("a/decode_step/window_attention/x") == "decode_step"
    assert dots3_scopes.scope_of("a/window_attention/jit(window_attention_blockwise)/x") == "window_attention"
    assert dots3_scopes.scope_of("a/attn_gate/x") == "attn_gate"
    assert dots3_scopes.scope_of("a/selected_attention/x") == "selected_attention"
    assert dots3_scopes.scope_of("a/delta_rule/x") == "unscoped"  # the second judge's
    ctx = ctx_for(made_up_trace())
    share = {g: byname.module("reducers", f"dots3_share_{g}").reduce(ctx)
             for g in dots3_scopes.GROUPS}
    assert share == {
        "window_attention": 10.0, "selected_attention": 20.0, "indexer": 15.0,
        "projections": 20.0,  # the gate's product among them
        "experts": 30.0,  # the kernel, the dense MLP, the router's fusion and the copy it alone reads
        "decode": 5.0, "unscoped": 0.0,
    }
    assert dots3_scopes.share({**ctx, "scoped": None}, "experts") is None


def test_the_rooflines_read_the_kernels_own_events_and_the_counted_pairs():
    family = byname.module("families", "dots3_note")
    ctx = ctx_for(made_up_trace(), *COUNTED)
    got = byname.module("reducers", "window_attention_roofline").reduce(ctx)
    assert got == pytest.approx(100 * family.window_attention_flops(PUBLISHED, 3, 8192) / 197e12 / 10e-6)
    got = byname.module("reducers", "selected_attention_roofline").reduce(ctx)
    assert got == pytest.approx(100 * family.selected_attention_flops(PUBLISHED, 3, 8192) / 197e12 / 20e-6)
    got = byname.module("reducers", "index_scores_roofline").reduce(ctx)
    assert got == pytest.approx(100 * family.index_scores_flops(PUBLISHED, 3, 8192) / 197e12 / 10e-6)
    got = byname.module("reducers", "index_select_roofline").reduce(ctx)
    assert got == pytest.approx(100 * family.index_select_bytes(PUBLISHED, 3, 8192) / 819e9 / 5e-6)
    pairs = 150_000 / 3
    got = byname.module("reducers", "expert_products_roofline_held").reduce(ctx)
    least = max(
        family.expert_products_flops(PUBLISHED, 3, 8192, pairs) / 197e12,
        family.expert_products_bytes(PUBLISHED, 3, 8192, pairs) / 819e9,
    )
    assert got == pytest.approx(100 * least / 10e-6)
    got = byname.module("reducers", "dots3_forward_mfu").reduce(ctx)
    assert got == pytest.approx(
        100 * family.forward_flops(PUBLISHED, 3, 8192, pairs) / (100e-6 * 197e12)
    )


def test_the_counters_give_the_shares():
    import layers

    def read(name):
        spec = json.load(open(os.path.join(BENCH, "layer_metrics", name + ".json")))
        return layers.read_metrics(spec["read"], {"judge": COUNTED[0]}, {"judge": COUNTED[1]})

    assert read("window.band_share.dots3") == pytest.approx(100 * 4_071_168 / 33_558_528)
    assert read("window.band_share.dots3") == pytest.approx(12.13, abs=0.01)
    assert read("index.selected_share.dots3") == pytest.approx(43.747, abs=1e-3)
    assert read("experts.held_pairs_share.dots3") == pytest.approx(100 * 150_000 / 2_359_296)


def test_a_program_without_a_window_gives_nothing_to_read():
    """The parent commit, or another judge (the third names every scope but the
    window's and the gate's): no ``window_attention`` scope, no
    ``judge.window_keys_*``, no such kernel; another family counts none."""
    import layers

    bare = made_up_trace()
    bare["instructions"] = [
        dict(i, name=i["name"].replace("window_", "causal_"),
             tf_op=(i["tf_op"] or "").replace("window_", "causal_").replace("attn_gate", "attn_out") or None)
        for i in bare["instructions"]
    ]
    ctx = ctx_for(bare, *COUNTED)
    for group in dots3_scopes.GROUPS:
        assert byname.module("reducers", f"dots3_share_{group}").reduce(ctx) is None
    assert byname.module("reducers", "dots3_forward_mfu").reduce(ctx) is None
    assert byname.module("reducers", "window_attention_roofline").reduce(ctx) is None
    other = ctx_for(made_up_trace(), *COUNTED, family="glm_moe_dsa")
    assert byname.module("reducers", "window_attention_roofline").reduce(other) is None
    assert byname.module("reducers", "dots3_forward_mfu").reduce(ctx_for(made_up_trace())) is None
    spec = json.load(open(os.path.join(BENCH, "layer_metrics", "window.band_share.dots3.json")))
    old = {"judge": {"dispatches": 3}}
    assert layers.read_metrics(spec["read"], old, {"judge": {"dispatches": 9}}) is None
    # ... and the third judge's own readers still read this decoder's selection
    assert byname.module("reducers", "glm5_share_indexer").reduce(ctx_for(made_up_trace())) == 15.0
