"""The second judge cell's own files (PR 31), on the CPU at the configuration's
``dry_run`` sizes: the reference against the program in float32, the whole
command sound and broken, the int8 control, the family's counts against hand
counts, and the new reducers on a made-up trace."""

import argparse
import json
import os

import numpy as np
import pytest

import byname
import checkpoints
import qnext_scopes
import run as bench_run
from test_judge_cell import broken_judge_env, last_line

CELL = "qwen3-next-80b-a3b.n64-c8k.closed4"
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PUBLISHED = json.load(open(os.path.join(BENCH, "configs", "qwen3-next-80b-a3b.json")))


def load_cell():
    return bench_run.load_cell(CELL, dry=True)


def args(seed, control=False):
    return argparse.Namespace(
        workload=CELL, seed=seed, seconds=3.0, trace=0, dry_run=True,
        control=control, benchmark=None,
    )


# -- the reference against the program ------------------------------------------


def test_the_reference_is_the_programs_forward_in_float32():
    """The seeded dry checkpoint names 8 experts of a router 16 wide: both
    sides serve that share, the program through its chunked rule and its
    held-experts layout, the reference through the recurrence."""
    import jax.numpy as jnp

    from llm_weighted_consensus_tpu.models import qwen3_next
    from llm_weighted_consensus_tpu.models.configs import QWEN3_NEXT_TEST_TINY

    _, _, config, cfg, _, _ = load_cell()
    ref = byname.module("references", config["reference"])
    state = checkpoints.make_state(config["family"], cfg, 2**31 + 9)
    f32 = {k: np.asarray(v).astype(np.float32) for k, v in state.items()}
    params, served = qwen3_next.from_hf_weights(f32, QWEN3_NEXT_TEST_TINY, dtype=jnp.float32)
    assert qwen3_next.experts_held(params, served) == 8 and served.num_experts == 16
    rng = np.random.default_rng(2)
    lens = [150, 97]
    ids = np.zeros((2, 160), np.int32)
    for row, n in enumerate(lens):
        ids[row, :n] = rng.integers(32, cfg["vocab_size"], size=n)
    letters = list(range(4, 24))
    hidden, _, loads = qwen3_next.prefill(
        params, jnp.asarray(ids), served, lens=jnp.asarray(lens, jnp.int32)
    )
    loads = np.asarray(loads)
    assert 0 < loads[:, :8].sum() < loads.sum()  # some pairs here, some elsewhere
    calls = [(ids[row, :n].tolist(), [n - 1, n // 2]) for row, n in enumerate(lens)]
    reads = ref.read_logits(f32, cfg, calls, letters)
    for row, n in enumerate(lens):
        got = np.asarray(qwen3_next.head_logprobs(params, hidden[row, [n - 1, n // 2]], served))
        got = got[:, letters]
        centred = lambda x: x - x.mean(axis=1, keepdims=True)  # noqa: E731
        assert np.abs(centred(got) - centred(reads[row])).max() < 5e-6


# -- the whole command ------------------------------------------------------------


def test_a_sound_run_is_correct(capsys):
    assert bench_run.run(args(2**31 + 99)) == 0
    result = last_line(capsys)
    assert result["correct"] is True and result["failed"] == 0
    assert result["check"]["ballot_logit_rms"]["value"] < 2e-6
    assert result["check"]["ballot_mismatches"]["value"] == 0
    assert result["check"]["confidence_abs_err"]["value"] < 1e-6


STATE_NOT_CARRIED = """
import jax.numpy as jnp
import llm_weighted_consensus_tpu.models.qwen3_next as qwen3_next
_sound = qwen3_next._linear_decode
def _broken(h, p, cache, config):
    # the decoded token starts from an empty recurrent state
    return _sound(h, p, (cache[0], jnp.zeros_like(cache[1])), config)
qwen3_next._linear_decode = _broken
"""

PADDING_IN_THE_STATE = """
import jax.numpy as jnp
import llm_weighted_consensus_tpu.models.qwen3_next as qwen3_next
_sound = qwen3_next._linear_prefill
def _broken(h, p, lens, config):
    # the padded slots run through the recurrence like tokens
    return _sound(h, p, jnp.full_like(lens, h.shape[1]), config)
qwen3_next._linear_prefill = _broken
"""


@pytest.mark.parametrize(
    "patch", [STATE_NOT_CARRIED, PADDING_IN_THE_STATE],
    ids=["the_decode_step_loses_the_state", "padding_runs_through_the_recurrence"],
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


def test_the_checkpoint_is_the_issues_eight_gigabytes():
    family = byname.module("families", "qwen3_next")
    specs = family.tensors(PUBLISHED)
    assert len({name for name, _, _ in specs}) == len(specs)
    total = sum(int(np.prod(shape)) for _, shape, _ in specs)
    expert = 3 * 2048 * 512
    half = 512 * 2048 + 128 * expert + expert + 2048  # router, held, shared and its gate
    linear = 12288 * 2048 + 64 * 2048 + 8192 * 4 + 32 + 32 + 128 + 2048 * 4096
    full = 8192 * 2048 + 2 * 512 * 2048 + 2048 * 4096 + 2 * 256
    assert total == 6 * linear + 2 * full + 8 * (half + 2 * 2048) + 2 * 151936 * 2048 + 2048
    assert 8.26e9 < 2 * total < 8.29e9  # the issue: 8.27 GB, 52% of the chip
    assert len(checkpoints.plan_shards(specs, checkpoints.SHARD_BYTES)) == 2
    kinds = {kind for name, _, kind in specs if name.endswith("linear_attn.norm.weight")}
    assert kinds == {"ln_scale"}
    assert {kind for name, _, kind in specs if "layernorm" in name or name.endswith(".A_log")} == {"normal"}
    # experts 0..127 of a router 512 wide
    assert ("model.layers.0.mlp.gate.weight", (512, 2048), "normal") in specs
    assert any(name == "model.layers.7.mlp.experts.127.down_proj.weight" for name, _, _ in specs)
    assert not any(".experts.128." in name for name, _, _ in specs)


def test_operations_against_hand_counts():
    family = byname.module("families", "qwen3_next")
    rows, seq = 3, 8192
    # the recurrence: three products of 128 x 128 a position and value head
    rule = family.gated_delta_flops(PUBLISHED, rows, seq) / 6
    assert rule == rows * seq * 32 * 3 * 2 * 128 * 128
    assert 0.077e12 < rule < 0.078e12  # under the issue's 0.14 for a chunked form
    moved = family.gated_delta_bytes(PUBLISHED, rows, seq) / 6
    assert moved == rows * (seq * ((2048 + 2048 + 4096 + 4096) * 2 + 64 * 4) + 32 * 128 * 128 * 4)
    assert moved / 819e9 > rule / 197e12  # memory bounds it
    # attention: the two full layers only, a key head read once
    attention = family.causal_attention_flops(PUBLISHED, rows, seq) / 2
    assert attention == rows * 2 * 16 * 512 * seq * (seq + 1) // 2
    assert 8.3e-3 < attention / 197e12 < 8.5e-3  # the issue: 8.4 ms a layer at the peak
    assert family.causal_attention_bytes(PUBLISHED, 1, seq) == 2 * seq * (16 + 2) * 512 * 2
    # the experts: from the pairs counted, not from 10 a token
    pair = 2 * 3 * 2048 * 512
    assert family.expert_products_flops(PUBLISHED, rows, seq, held_pairs=1000) == 1000 * pair
    expected = family.expected_held_pairs(PUBLISHED, rows, seq)
    assert expected == 8 * rows * seq * 10 / 4
    assert family.expert_products_flops(PUBLISHED, rows, seq) == expected * pair
    assert 3.0e12 < expected * pair < 3.2e12  # the issue: 3.1 TFLOP, a quarter of the pairs held
    weights = 8 * 128 * 3 * 2048 * 512
    assert family.expert_products_bytes(PUBLISHED, rows, seq, held_pairs=0) == 2 * weights
    # one dispatch: the issue's 12.6 TFLOP of mixer products, and 1.65 of
    # router and shared expert
    whole = family.forward_flops(PUBLISHED, rows, seq)
    dense = whole - family.gated_delta_flops(PUBLISHED, rows, seq) - attention * 2 - expected * pair
    assert 14.1e12 < dense < 14.4e12
    more = family.forward_flops(PUBLISHED, rows, seq, held_pairs=2 * expected)
    assert more - whole == pytest.approx(expected * pair)


# -- the reducers on a made-up trace ---------------------------------------------------


def made_up_trace():
    """Three judge programs of 100 us; the middle one (the one kept) holds the
    three kernels, scoped fusions of both token mixers, a decode-step fusion
    and a path-less copy that the router's fusion alone reads."""
    def ins(name, tf_op, operands=()):
        return {"name": name, "program": "1", "tf_op": tf_op, "category": None,
                "operands": list(operands)}

    base = "jit(judge_panel)/jit(main)/"
    instructions = [
        ins("gated_delta_chunked.3", base + "delta_rule/jit(gated_delta_chunked)/pallas_call"),
        ins("causal_attention_blockwise.2", base + "causal_attention/jit(causal_attention_blockwise)/pallas_call"),
        ins("grouped_expert_product.7", base + "experts_routed/experts_swiglu/jit(grouped_expert_product)/pallas_call"),
        ins("fusion.1", base + "linear_in/dot_general"),
        ins("fusion.2", base + "linear_conv/mul"),
        ins("fusion.3", base + "attn_qkv/dot_general"),
        ins("fusion.4", base + "decode_step/delta_rule/mul"),
        ins("copy.9", None, ()),
        ins("fusion.5", base + "router/dot_general", ("copy.9",)),
    ]
    durations = [20_000, 10_000, 10_000, 20_000, 10_000, 5_000, 5_000, 5_000, 15_000]
    ops = []
    for program in range(3):
        t = program * 200_000
        for index, dur in enumerate(durations):
            ops.append([index, t, dur])
            t += dur
    modules = [["jit_judge_panel(123)", p * 200_000, 100_000] for p in range(3)]
    return {"modules": modules, "instructions": instructions, "ops": ops, "spans": []}


def ctx_for(trace, judge_before=None, judge_after=None):
    label = "judge(n=3,s=8192)"
    before = {"roofline": {"buckets": {label: {"count": 5}}}}
    after = {"roofline": {"buckets": {label: {"count": 8}}}}
    if judge_after is not None:
        before["judge"], after["judge"] = judge_before, judge_after
    return {
        "scoped": trace,
        "config": {"trace_modules": ["jit_judge_panel"], "family": "qwen3_next"},
        "cfg": PUBLISHED,
        "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        "profile": {"before": before, "after": after},
    }


COUNTED = (
    {"dispatches": 5, "expert_pairs_here": 1_000_000, "expert_pairs_routed": 4_000_000},
    {"dispatches": 8, "expert_pairs_here": 2_500_000, "expert_pairs_routed": 9_898_240},
)


def test_the_scopes_and_shares():
    assert qnext_scopes.scope_of("a/decode_step/delta_rule/x") == "decode_step"
    assert qnext_scopes.scope_of("a/experts_routed/experts_combine/x") == "experts_routed"
    assert qnext_scopes.scope_of("a/latent_q/x") == "unscoped"  # the other judge's
    ctx = ctx_for(made_up_trace())
    share = {g: byname.module("reducers", f"qnext_share_{g}").reduce(ctx)
             for g in qnext_scopes.GROUPS}
    assert share == {
        "linear_attention": 30.0, "attention": 10.0,
        "experts": 30.0,  # the kernel, the router's fusion and the copy it alone reads
        "projections": 25.0, "decode": 5.0, "unscoped": 0.0,
    }
    assert qnext_scopes.share({**ctx, "scoped": None}, "experts") is None


def test_the_rooflines_read_the_kernels_own_events_and_the_counted_pairs():
    family = byname.module("families", "qwen3_next")
    ctx = ctx_for(made_up_trace(), *COUNTED)
    got = byname.module("reducers", "gated_delta_roofline").reduce(ctx)
    least = family.gated_delta_bytes(PUBLISHED, 3, 8192) / 819e9  # memory bounds it
    assert got == pytest.approx(100 * least / 20e-6)
    got = byname.module("reducers", "causal_attention_roofline").reduce(ctx)
    assert got == pytest.approx(100 * family.causal_attention_flops(PUBLISHED, 3, 8192) / 197e12 / 10e-6)
    pairs = 1_500_000 / 3
    assert qnext_scopes.held_pairs(ctx) == pairs
    got = byname.module("reducers", "expert_products_roofline_held").reduce(ctx)
    least = max(
        family.expert_products_flops(PUBLISHED, 3, 8192, pairs) / 197e12,
        family.expert_products_bytes(PUBLISHED, 3, 8192, pairs) / 819e9,
    )
    assert got == pytest.approx(100 * least / 10e-6)
    got = byname.module("reducers", "qnext_forward_mfu").reduce(ctx)
    assert got == pytest.approx(
        100 * family.forward_flops(PUBLISHED, 3, 8192, pairs) / (100e-6 * 197e12)
    )


def test_a_program_without_the_counters_or_the_kernel_gives_nothing_to_read():
    """The parent commit: no ``judge.expert_pairs_here``, no such kernel."""
    import layers

    ctx = ctx_for(made_up_trace())
    assert qnext_scopes.held_pairs(ctx) is None
    assert byname.module("reducers", "expert_products_roofline_held").reduce(ctx) is None
    assert byname.module("reducers", "qnext_forward_mfu").reduce(ctx) is None
    bare = made_up_trace()
    bare["instructions"] = [dict(i, name="fusion.0") for i in bare["instructions"]]
    assert byname.module("reducers", "gated_delta_roofline").reduce(ctx_for(bare)) is None
    glm = ctx_for(made_up_trace())
    glm["config"]["family"] = "glm4_moe_lite"
    assert byname.module("reducers", "gated_delta_roofline").reduce(glm) is None
    spec = json.load(open(os.path.join(BENCH, "layer_metrics", "experts.held_pairs_share.qnext.json")))
    old = {"judge": {"dispatches": 3}}
    assert layers.read_metrics(spec["read"], old, {"judge": {"dispatches": 9}}) is None
    assert layers.read_metrics(spec["read"], {"judge": COUNTED[0]}, {"judge": COUNTED[1]}) == (
        pytest.approx(100 * 1_500_000 / 5_898_240)
    )
