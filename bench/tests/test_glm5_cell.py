"""The third judge cell's own files (PR 33), on the CPU at the configuration's
``dry_run`` sizes: the reference against the program in float32, the whole
command sound and broken (a selection ignored; the decoded token choosing
nothing), the int8 control, the family's counts against hand arithmetic at
the cell's shapes, and the new scope table and reducers on a made-up trace."""

import argparse
import json
import os

import numpy as np
import pytest

import byname
import checkpoints
import glm5_scopes
import run as bench_run
from test_judge_cell import broken_judge_env, last_line

CELL = "glm-5.2.n64-c8k.closed4"
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PUBLISHED = json.load(open(os.path.join(BENCH, "configs", "glm-5.2.json")))


def load_cell():
    return bench_run.load_cell(CELL, dry=True)


def args(seed, control=False):
    return argparse.Namespace(
        workload=CELL, seed=seed, seconds=3.0, trace=0, dry_run=True,
        control=control, benchmark=None,
    )


# -- the reference against the program ------------------------------------------


def test_the_reference_is_the_programs_forward_in_float32():
    """The seeded dry checkpoint names 8 experts of a router 16 wide and five
    layers (full, shared x 3, full): both sides serve that, the program
    through its kernels and its three caches, the reference through
    ``lax.top_k`` and one forward."""
    import jax.numpy as jnp

    from llm_weighted_consensus_tpu.models import glm_moe
    from llm_weighted_consensus_tpu.models.configs import GLM_DSA_TEST_TINY

    _, _, config, cfg, _, _ = load_cell()
    ref = byname.module("references", config["reference"])
    state = checkpoints.make_state(config["family"], cfg, 2**31 + 9)
    f32 = {k: np.asarray(v).astype(np.float32) for k, v in state.items()}
    params, served = glm_moe.from_hf_weights(f32, GLM_DSA_TEST_TINY, dtype=jnp.float32)
    assert glm_moe.experts_held(params, served) == 8 and served.n_routed_experts == 16
    assert served.indexer_types == ("full", "shared", "shared", "shared", "full")
    rng = np.random.default_rng(2)
    lens = [150, 97]
    ids = np.zeros((2, 160), np.int32)
    for row, n in enumerate(lens):
        ids[row, :n] = rng.integers(32, cfg["vocab_size"], size=n)
    letters = list(range(4, 24))
    token = np.array([5, 17], np.int32)
    hidden, caches, loads = glm_moe.prefill(
        params, jnp.asarray(ids), served, lens=jnp.asarray(lens, jnp.int32)
    )
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
def _broken(q, k, v, keep=None, **kw):
    # attends every causal key "because the result stays inside the tolerance"
    return _sound(q, k, v, None, **kw)
glm_moe.causal_attention_blockwise = _broken
"""

DECODED_TOKEN_SEES_EVERYTHING = """
import llm_weighted_consensus_tpu.models.glm_moe as glm_moe
# the decoded token chooses nothing: it attends every cached position
glm_moe.select_topk_dense = lambda scores, seen, k: seen
"""


@pytest.mark.parametrize(
    "patch", [EVERY_CAUSAL_KEY, DECODED_TOKEN_SEES_EVERYTHING],
    ids=["the_prefill_ignores_the_selection", "the_decoded_token_chooses_nothing"],
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


def test_the_checkpoint_is_the_issues_seven_and_three_quarter_gigabytes():
    family = byname.module("families", "glm_moe_dsa")
    specs = family.tensors(PUBLISHED)
    assert len({name for name, _, _ in specs}) == len(specs)
    total = sum(int(np.prod(shape)) for _, shape, _ in specs)
    h = 6144
    attention = h * 2048 + 2048 * 64 * 256 + h * 576 + 512 * 64 * 448 + 64 * 256 * h
    norms = 2 * h + 2048 + 512
    indexer = 2048 * 32 * 128 + h * 128 + 128 + 128 + h * 32
    expert = 3 * h * 2048
    dense = attention + norms + indexer + 3 * h * 12288
    sparse = attention + norms + 256 * h + 256 + 16 * expert + expert
    assert attention == 165_019_648 and expert == 37_748_736  # the issue: 165.0M, 37.7M
    assert total == dense + 3 * sparse + (sparse + indexer) + 2 * 19360 * h + h
    assert 7.75e9 < 2 * total < 7.78e9  # the issue: 7.76 GB, 49% of the chip
    assert len(checkpoints.plan_shards(specs, checkpoints.SHARD_BYTES)) == 2
    # published layers 2..6: the last dense layer and layer 6 own an indexer
    owners = [i for i in range(5) if family.owns_indexer(PUBLISHED, i)]
    assert owners == [0, 4] and [family.is_dense(PUBLISHED, i) for i in range(5)] == [True] + [False] * 4
    assert ("model.layers.0.self_attn.indexer.wq_b.weight", (4096, 2048), "normal") in specs
    assert ("model.layers.4.self_attn.indexer.k_norm.weight", (128,), "ln_scale") in specs
    assert not any("layers.1.self_attn.indexer" in name for name, _, _ in specs)
    # experts 0..15 of a router 256 wide
    assert ("model.layers.1.mlp.gate.weight", (256, h), "normal") in specs
    assert any(name == "model.layers.4.mlp.experts.15.down_proj.weight" for name, _, _ in specs)
    assert not any(".experts.16." in name for name, _, _ in specs)


def test_operations_against_hand_arithmetic_at_the_cells_shapes():
    family = byname.module("families", "glm_moe_dsa")
    rows, seq = 3, 8192
    assert family.selected_pairs(PUBLISHED, seq) == 14_681_088
    assert family.causal_pairs(seq) == 33_558_528
    assert family.selected_pairs(PUBLISHED, 1000) == family.causal_pairs(1000)  # nobody chooses
    # the indexer: causal pairs x 32 heads x 128 dims x 2, the two layers that own one
    scores = family.index_scores_flops(PUBLISHED, rows, seq)
    assert scores == 2 * rows * 33_558_528 * 32 * 128 * 2
    assert 0.82e12 < scores / 2 < 0.83e12  # the issue: 0.82 TFLOP a layer
    moved = family.index_scores_bytes(PUBLISHED, rows, seq) / 2
    assert moved == rows * (seq * ((4096 + 128) * 2 + 32 * 4) + 33_558_528 * 4)
    assert scores / 2 / 197e12 > moved / 819e9  # compute-bound
    # the choice: a memory roofline
    assert family.index_select_flops(PUBLISHED, rows, seq) == 0
    assert family.index_select_bytes(PUBLISHED, rows, seq) == 2 * rows * 33_558_528 * 5
    # attention: the selected pairs x 64 heads x 512 dims x 2, all five layers
    attention = family.selected_attention_flops(PUBLISHED, rows, seq)
    assert attention == 5 * rows * 14_681_088 * 64 * 512 * 2
    assert 2.88e12 < attention / 5 < 2.90e12  # the issue: 2.89 TFLOP a layer
    every = 5 * rows * 33_558_528 * 64 * 512 * 2
    assert attention / every == pytest.approx(0.4375, abs=1e-4)  # what a masked form reads at most
    assert attention / 5 / 197e12 > family.selected_attention_bytes(PUBLISHED, rows, seq) / 5 / 819e9
    # the experts: from the pairs counted, not from 8 a token
    pair = 2 * 3 * 6144 * 2048
    assert family.expert_products_flops(PUBLISHED, rows, seq, held_pairs=1000) == 1000 * pair
    expected = family.expected_held_pairs(PUBLISHED, rows, seq)
    assert expected == 4 * rows * seq * 8 / 16
    assert family.expert_products_flops(PUBLISHED, rows, seq) == expected * pair
    weights = 4 * 16 * 3 * 6144 * 2048
    assert family.expert_products_bytes(PUBLISHED, rows, seq, held_pairs=0) == 2 * weights
    # one dispatch: the issue's 80 TFLOP, the attention path 72% of it
    whole = family.forward_flops(PUBLISHED, rows, seq)
    assert 79e12 < whole < 81.5e12
    projections = 5 * 2 * 165_019_648 * rows * seq
    path = projections + scores + attention + 2 * 2 * 9_379_840 * rows * seq
    assert 0.70 < path / whole < 0.74
    more = family.forward_flops(PUBLISHED, rows, seq, held_pairs=2 * expected)
    assert more - whole == pytest.approx(expected * pair)


# -- the reducers on a made-up trace ---------------------------------------------------


def made_up_trace():
    """Three judge programs of 100 us; the middle one (the one kept) holds the
    four kernels, scoped fusions of the indexer and the projections, a
    decode-step fusion and a path-less copy that the router's fusion alone
    reads."""
    def ins(name, tf_op, operands=()):
        return {"name": name, "program": "1", "tf_op": tf_op, "category": None,
                "operands": list(operands)}

    base = "jit(judge_panel)/jit(main)/"
    instructions = [
        ins("index_scores.3", base + "index_scores/jit(index_scores)/pallas_call"),
        ins("index_select.2", base + "index_select/jit(index_select)/pallas_call"),
        ins("causal_attention_blockwise.2", base + "selected_attention/jit(causal_attention_blockwise)/pallas_call"),
        ins("grouped_expert_product.7", base + "experts_routed/experts_swiglu/jit(grouped_expert_product)/pallas_call"),
        ins("fusion.1", base + "index_q/dot_general"),
        ins("fusion.2", base + "latent_q/dot_general"),
        ins("fusion.3", base + "dense_mlp/dot_general"),
        ins("fusion.4", base + "decode_step/index_select/top_k"),
        ins("copy.9", None, ()),
        ins("fusion.5", base + "router/dot_general", ("copy.9",)),
    ]
    durations = [10_000, 5_000, 25_000, 10_000, 5_000, 20_000, 10_000, 5_000, 2_000, 8_000]
    ops = []
    for program in range(3):
        t = program * 200_000
        for index, dur in enumerate(durations):
            ops.append([index, t, dur])
            t += dur
    modules = [["jit_judge_panel(123)", p * 200_000, 100_000] for p in range(3)]
    return {"modules": modules, "instructions": instructions, "ops": ops, "spans": []}


def ctx_for(trace, judge_before=None, judge_after=None, family="glm_moe_dsa"):
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


COUNTED = (
    {"dispatches": 5, "expert_pairs_here": 100_000, "expert_pairs_routed": 1_600_000,
     "index_keys_selected": 88_086_528, "index_keys_causal": 201_351_168},
    {"dispatches": 8, "expert_pairs_here": 250_000, "expert_pairs_routed": 3_959_296,
     "index_keys_selected": 3 * 88_086_528, "index_keys_causal": 3 * 201_351_168},
)


def test_the_scopes_and_shares():
    assert glm5_scopes.scope_of("a/decode_step/index_select/x") == "decode_step"
    assert glm5_scopes.scope_of("a/experts_routed/experts_combine/x") == "experts_routed"
    assert glm5_scopes.scope_of("a/selected_attention/x") == "selected_attention"
    assert glm5_scopes.scope_of("a/delta_rule/x") == "unscoped"  # the second judge's
    ctx = ctx_for(made_up_trace())
    share = {g: byname.module("reducers", f"glm5_share_{g}").reduce(ctx)
             for g in glm5_scopes.GROUPS}
    assert share == {
        "indexer": 20.0, "selected_attention": 25.0, "projections": 20.0,
        "experts": 30.0,  # the kernel, the dense MLP, the router's fusion and the copy it alone reads
        "decode": 5.0, "unscoped": 0.0,
    }
    assert glm5_scopes.share({**ctx, "scoped": None}, "experts") is None


def test_the_rooflines_read_the_kernels_own_events_and_the_counted_pairs():
    family = byname.module("families", "glm_moe_dsa")
    ctx = ctx_for(made_up_trace(), *COUNTED)
    got = byname.module("reducers", "index_scores_roofline").reduce(ctx)
    assert got == pytest.approx(100 * family.index_scores_flops(PUBLISHED, 3, 8192) / 197e12 / 10e-6)
    got = byname.module("reducers", "index_select_roofline").reduce(ctx)
    assert got == pytest.approx(100 * family.index_select_bytes(PUBLISHED, 3, 8192) / 819e9 / 5e-6)
    got = byname.module("reducers", "selected_attention_roofline").reduce(ctx)
    assert got == pytest.approx(100 * family.selected_attention_flops(PUBLISHED, 3, 8192) / 197e12 / 25e-6)
    pairs = 150_000 / 3
    got = byname.module("reducers", "expert_products_roofline_held").reduce(ctx)
    least = max(
        family.expert_products_flops(PUBLISHED, 3, 8192, pairs) / 197e12,
        family.expert_products_bytes(PUBLISHED, 3, 8192, pairs) / 819e9,
    )
    assert got == pytest.approx(100 * least / 10e-6)
    got = byname.module("reducers", "glm5_forward_mfu").reduce(ctx)
    assert got == pytest.approx(
        100 * family.forward_flops(PUBLISHED, 3, 8192, pairs) / (100e-6 * 197e12)
    )


def test_the_counters_give_the_shares():
    import layers

    def read(name):
        spec = json.load(open(os.path.join(BENCH, "layer_metrics", name + ".json")))
        return layers.read_metrics(spec["read"], {"judge": COUNTED[0]}, {"judge": COUNTED[1]})

    assert read("index.selected_share.glm5") == pytest.approx(43.747, abs=1e-3)
    assert read("experts.held_pairs_share.glm5") == pytest.approx(100 * 150_000 / 2_359_296)


def test_a_program_without_the_selection_gives_nothing_to_read():
    """The parent commit, or another judge: no ``index_*`` scope, no
    ``judge.index_keys_*``, no such kernel; another family counts none."""
    import layers

    bare = made_up_trace()
    bare["instructions"] = [
        dict(i, name="fusion.0", tf_op=(i["tf_op"] or "").replace("index_", "x_").replace("selected_", "causal_") or None)
        for i in bare["instructions"]
    ]
    ctx = ctx_for(bare, *COUNTED)
    for group in glm5_scopes.GROUPS:
        assert byname.module("reducers", f"glm5_share_{group}").reduce(ctx) is None
    assert byname.module("reducers", "glm5_forward_mfu").reduce(ctx) is None
    for name in ("index_scores_roofline", "index_select_roofline", "selected_attention_roofline"):
        assert byname.module("reducers", name).reduce(ctx) is None
        other = ctx_for(made_up_trace(), *COUNTED, family="glm4_moe_lite")
        assert byname.module("reducers", name).reduce(other) is None
    assert byname.module("reducers", "glm5_forward_mfu").reduce(ctx_for(made_up_trace())) is None
    spec = json.load(open(os.path.join(BENCH, "layer_metrics", "index.selected_share.glm5.json")))
    old = {"judge": {"dispatches": 3}}
    assert layers.read_metrics(spec["read"], old, {"judge": {"dispatches": 9}}) is None
