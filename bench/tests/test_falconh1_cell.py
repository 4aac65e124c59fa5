"""The seventh judge cell's own files (PR 49), on the CPU at the configuration's
``dry_run`` sizes: every published number against the catalog with ONE key
reduced, the file's byte count against the tensor list, the traffic's tokens
against the bucket, the reference against the program in float32, the whole
command sound and broken (a head that reads the other group's B and C; a
multiplier dropped; a decoded token without its scan state), the int8 control,
the family's counts by hand, the scope table against the scopes the decoder
names, and the new reducers on a made-up trace."""

import argparse
import json
import os
import re

import numpy as np
import pytest

import byname
import checkpoints
import falconh1_scopes
import run as bench_run
from test_judge_cell import broken_judge_env, last_line

CELL = "falcon-h1-34b-instruct.n64-c8k.closed4"
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PUBLISHED = json.load(open(os.path.join(BENCH, "configs", "falcon-h1-34b-instruct.json")))
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
def test_every_published_number_stands_but_the_depth():
    with open(CATALOG, encoding="utf-8") as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Falcon-H1-34B-Instruct")
    assert PUBLISHED["source"] == row["source_url"]
    assert PUBLISHED["reduced"] == ["num_hidden_layers"]
    assert PUBLISHED["reduced_from"] == {"num_hidden_layers": row["config"]["num_hidden_layers"]}
    for key, value in row["config"].items():
        if key not in PUBLISHED["reduced"]:
            assert PUBLISHED[key] == value, key
    assert PUBLISHED["num_hidden_layers"] == 6 and row["config"]["num_hidden_layers"] == 72
    for key, value in PUBLISHED["published"].items():
        if key in row["config"]:
            assert value == row["config"][key], key
    assert PUBLISHED["max_tokens"] == 8192 == int(PUBLISHED["server_env"]["JUDGE_MAX_TOKENS"])
    bench = json.load(open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")))
    entry = next(c for c in bench["configs"] if c["name"] == "falcon-h1-34b-instruct")
    assert entry["reduced"] == ["num_hidden_layers"] and entry["source"] == row["source_url"]
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["traffic"], cell["chips"]) == ("n64-c8k.closed4", 1)
    # no width is cut: the keys a cut may never name stand as published
    for key in ("hidden_size", "intermediate_size", "head_dim", "mamba_d_ssm", "mamba_d_state",
                "mamba_d_head", "mamba_n_heads", "num_attention_heads", "num_key_value_heads",
                "vocab_size"):
        assert PUBLISHED[key] == row["config"][key], key


def test_the_file_s_byte_count_is_the_tensor_list_s():
    family = byname.module("families", "falcon_h1")
    specs = family.tensors(PUBLISHED)
    assert len({name for name, _, _ in specs}) == len(specs)
    sizes = {name: 2 * int(np.prod(shape)) for name, shape, _ in specs}
    b = PUBLISHED["bytes"]

    def under(prefix):
        return sum(v for k, v in sizes.items() if k.startswith(prefix))

    assert b["checkpoint"] == sum(sizes.values()) == 10_509_188_224
    assert 0.65 < b["checkpoint"] / 16e9 < 0.66
    assert b["mixer_a_layer"] == under("model.layers.0.mamba.") == 2 * (
        5120 * 9248 + 5120 * 4 + 5120 + 3 * 32 + 4096 + 4096 * 5120
    )
    assert b["attention_a_layer"] == under("model.layers.0.self_attn.") == 2 * (
        2 * 5120 * 2560 + 2 * 5120 * 512
    )
    assert b["mlp_a_layer"] == under("model.layers.0.feed_forward.") == 2 * 3 * 5120 * 21504
    assert b["norms_a_layer"] == 2 * 2 * 5120
    assert b["a_layer"] == under("model.layers.3.") == 860_240_064  # 430.1M parameters, 0.860 GB
    assert b["six_layers"] == 6 * b["a_layer"] == sum(under(f"model.layers.{i}.") for i in range(6))
    assert b["embedding"] == b["untied_head"] == 2 * 261_120 * 5120 and b["final_norm"] == 2 * 5120
    assert b["checkpoint"] == b["six_layers"] + b["embedding"] + b["untied_head"] + b["final_norm"]
    assert round(b["six_layers"] / 1e9, 3) == 5.161 and round((b["embedding"] + b["untied_head"]) / 1e9, 3) == 5.348
    assert len(checkpoints.plan_shards(specs, checkpoints.SHARD_BYTES)) == 3
    assert checkpoints.specs_of("falcon_h1", PUBLISHED)[1] == 0.1 == PUBLISHED["assumed"]["init_std"]


def test_the_checkpoint_names_the_published_tensors():
    family = byname.module("families", "falcon_h1")
    specs = family.tensors(PUBLISHED)
    for spec in (
        ("model.layers.5.mamba.in_proj.weight", (9248, 5120), "normal"),
        ("model.layers.0.mamba.conv1d.weight", (5120, 1, 4), "normal"),
        ("model.layers.0.mamba.conv1d.bias", (5120,), "normal"),
        ("model.layers.2.mamba.A_log", (32,), "normal"),
        ("model.layers.2.mamba.D", (32,), "ln_scale"),
        ("model.layers.2.mamba.dt_bias", (32,), "normal"),
        ("model.layers.2.mamba.norm.weight", (4096,), "ln_scale"),
        ("model.layers.4.mamba.out_proj.weight", (5120, 4096), "normal"),
        ("model.layers.1.self_attn.q_proj.weight", (2560, 5120), "normal"),
        ("model.layers.1.self_attn.k_proj.weight", (512, 5120), "normal"),
        ("model.layers.1.self_attn.o_proj.weight", (5120, 2560), "normal"),
        ("model.layers.3.feed_forward.gate_proj.weight", (21504, 5120), "normal"),
        ("model.layers.3.feed_forward.down_proj.weight", (5120, 21504), "normal"),
        ("model.layers.0.pre_ff_layernorm.weight", (5120,), "ln_scale"),
        ("model.final_layernorm.weight", (5120,), "ln_scale"),
        ("lm_head.weight", (261120, 5120), "normal"),  # untied
    ):
        assert spec in specs, spec[0]
    names = {name for name, _, _ in specs}
    assert "model.layers.6.input_layernorm.weight" not in names  # six layers, 0..5
    assert not any("bias" in n and "conv1d" not in n and "dt_bias" not in n for n in names)


# -- the traffic ------------------------------------------------------------------


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 5, 3141592653])
def test_every_call_fits_the_bucket(seed):
    mix = json.load(open(os.path.join(BENCH, "traffic", "n64-c8k.closed4.json")))
    gen = byname.module("generators", mix["generator"])
    tok = PUBLISHED["tokenizer"]
    requests = gen.generate(mix, seed, 50.0, PUBLISHED["vocab_size"] - tok["specials"])
    assert len(requests) == 300  # 6.0 a second of window: far over what a window answers
    tokens = {gen.request_tokens(r, tok["overhead"]) for r in requests}
    assert tokens == {7525}  # 2 + 800 + 3 + 64 x 5 + 64 x 100: the same work a request
    assert max(tokens) <= PUBLISHED["max_tokens"] and max(tokens) > 0.9 * PUBLISHED["max_tokens"]
    # words from the WHOLE vocabulary: the embedding's every row may be read
    assert max(int(w.max()) for r in requests[:8] for w in r["words"]) > 200_000
    assert max(int(w.max()) for r in requests[:8] for w in r["words"]) < 261_120 - tok["specials"]


# -- the reference against the program ------------------------------------------


def test_the_reference_is_the_programs_forward_in_float32():
    """The seeded dry checkpoint names two layers: both sides serve that, the
    program through its kernels and a layer's two kinds of cache, the
    reference through the unfused chunked scan, whole mask rows and one forward
    over T + 1 positions."""
    import jax.numpy as jnp

    from llm_weighted_consensus_tpu.models import falcon_h1
    from llm_weighted_consensus_tpu.models.configs import FALCON_H1_TEST_TINY

    _, _, config, cfg, _, _ = load_cell()
    ref = byname.module("references", config["reference"])
    state = checkpoints.make_state(config["family"], cfg, 2**31 + 9)
    f32 = {k: np.asarray(v).astype(np.float32) for k, v in state.items()}
    params, served = falcon_h1.from_hf_weights(f32, FALCON_H1_TEST_TINY, dtype=jnp.float32)
    assert served == FALCON_H1_TEST_TINY
    rng = np.random.default_rng(2)
    lens = [150, 2]  # over a chunk of the kernel and off every block; under the convolution's taps
    ids = np.zeros((2, 160), np.int32)
    for row, n in enumerate(lens):
        ids[row, :n] = rng.integers(32, cfg["vocab_size"], size=n)
    letters = list(range(4, 24))
    token = np.array([5, 17], np.int32)
    at = jnp.asarray(lens, jnp.int32)
    hidden, caches, loads = falcon_h1.prefill(params, jnp.asarray(ids), served, lens=at)
    assert hidden.shape == (2, 160, 64) and loads == [] and len(caches) == 2
    last = jnp.take_along_axis(hidden, (at - 1)[:, None, None], axis=1)[:, 0]
    step = falcon_h1.decode_step(params, jnp.asarray(token), at, caches, served)
    calls = [(ids[row, :n].tolist() + [int(token[row])], [n - 1, n]) for row, n in enumerate(lens)]
    reads = ref.read_logits(f32, cfg, calls, letters)
    centred = lambda x: x - x.mean(axis=1, keepdims=True)  # noqa: E731
    for row in range(2):
        got = np.concatenate([
            np.asarray(falcon_h1.head_logprobs(params, last[row][None], served)),
            np.asarray(falcon_h1.head_logprobs(params, step[row][None], served)),
        ])[:, letters]
        assert np.abs(centred(got) - centred(reads[row])).max() < 2e-5


# -- the whole command ------------------------------------------------------------


def test_a_sound_run_is_correct(capsys):
    assert bench_run.run(args(2**31 + 99)) == 0
    result = last_line(capsys)
    assert result["correct"] is True and result["failed"] == 0
    assert result["check"]["ballot_logit_rms"]["value"] < 2e-6
    assert result["check"]["ballot_mismatches"]["value"] == 0
    assert result["check"]["confidence_abs_err"]["value"] < 1e-6


OTHER_GROUP = """
import llm_weighted_consensus_tpu.models.falcon_h1 as falcon_h1
_sound = falcon_h1._split
def _broken(xbc, config):
    xs, b, c = _sound(xbc, config)
    n = config.d_state  # every head reads the OTHER group's B and C (two groups: a roll swaps them)
    return xs, falcon_h1.jnp.roll(b, n, -1), falcon_h1.jnp.roll(c, n, -1)
falcon_h1._split = _broken
"""

MULTIPLIER_DROPPED = """
import dataclasses
import llm_weighted_consensus_tpu.models.judge as judge
# the attention's output multiplier left at 1
tiny = judge.JUDGE_PRESETS["falcon-h1-test-tiny"]
judge.JUDGE_PRESETS["falcon-h1-test-tiny"] = dataclasses.replace(tiny, attention_out_multiplier=1.0)
"""

STATE_FORGOTTEN = """
import llm_weighted_consensus_tpu.models.falcon_h1 as falcon_h1
_sound = falcon_h1._mixer_decode
def _broken(h, p, cache, config):
    # the decoded token's recurrence starts from nothing
    return _sound(h, p, (cache[0], cache[1] * 0), config)
falcon_h1._mixer_decode = _broken
"""


@pytest.mark.parametrize(
    "patch", [OTHER_GROUP, MULTIPLIER_DROPPED, STATE_FORGOTTEN],
    ids=["heads_that_read_the_other_group", "a_multiplier_dropped",
         "a_decoded_token_without_its_state"],
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
    assert result["check"]["ballot_logit_rms"]["value"] > 4e-6
    assert result["check"]["confidence_abs_err"]["value"] < 1e-6  # the tally is exact


# -- counts ------------------------------------------------------------------------


def test_the_ssd_count_against_a_hand_count():
    """The published algorithm at the published chunk of 128, a slot and
    layer, product by product; and the whole dispatch at the cell's shapes."""
    family = byname.module("families", "falcon_h1")
    chunk, n, groups, inner = 128, 256, 2, 4096
    scores = 2 * chunk * n * groups  # C B^T: a position's row of `chunk` scores over N states, a group
    within = 2 * chunk * inner  # the masked scores times x: `chunk` terms a channel
    chunk_state = 2 * n * inner  # the chunk's state: a position's x (x) B, every channel and state
    state_out = 2 * n * inner  # the state times C
    assert family.ssd_slot_flops(PUBLISHED) == scores + within + chunk_state + state_out == 5_373_952
    rows, seq = 3, 8192
    assert family.ssd_flops(PUBLISHED, rows, seq) == 6 * rows * seq * 5_373_952
    assert family.ssd_flops(PUBLISHED, rows, seq) / 6 == pytest.approx(0.132e12, rel=0.005)
    moved = family.ssd_bytes(PUBLISHED, rows, seq) / 6
    assert moved == rows * seq * (2 * 4096 * 2 + 2 * 512 * 2 + 32 * 4) == pytest.approx(0.456e9, rel=0.005)
    # compute-bound as counted, narrowly: 0.67 ms of arithmetic over 0.56 ms of memory a layer
    assert (0.132e12 / 197e12) / (moved / 819e9) == pytest.approx(1.2, abs=0.05)
    # the attention kernel: about 1.03 TFLOP a layer and program
    assert family.causal_attention_flops(PUBLISHED, rows, seq) / 6 == pytest.approx(1.03e12, rel=0.005)
    whole = family.forward_flops(PUBLISHED, rows, seq)
    products = 2 * 6 * rows * seq * family.layer_weights(PUBLISHED)
    assert products / 6 == pytest.approx(21.14e12, rel=0.002)
    assert whole == pytest.approx(133.8e12, rel=0.002)
    # the MLP 77%, the mixers' projections 23% of the dense products
    assert family.mlp_weights(PUBLISHED) / family.layer_weights(PUBLISHED) == pytest.approx(0.768, abs=0.001)
    rest = whole - products - family.causal_attention_flops(PUBLISHED, rows, seq) - family.ssd_flops(PUBLISHED, rows, seq)
    # the decoded token and two reads of the whole vocabulary: weights read, next to no arithmetic
    assert 0 < rest < 0.06e12


def test_the_counts_at_a_tiny_size_from_the_tensor_shapes():
    family = byname.module("families", "falcon_h1")
    _, _, _, cfg, _, _ = load_cell()
    shapes = {name: shape for name, shape, _ in family.tensors(cfg)}
    two_d = sum(
        2 * shape[0] * shape[1] for name, shape in shapes.items()
        if name.startswith("model.layers.0.") and len(shape) == 2
    )
    assert 2 * family.layer_weights(cfg) == two_d
    rows, seq, layers = 2, 21, cfg["num_hidden_layers"]
    heads, hd = cfg["num_attention_heads"], cfg["head_dim"]
    attention = layers * rows * sum(t + 1 for t in range(seq)) * heads * 2 * hd * 2
    assert family.causal_attention_flops(cfg, rows, seq) == attention
    step = 3 * 2 * cfg["mamba_d_ssm"] * cfg["mamba_d_state"]
    want = (
        rows * (seq + 1) * layers * two_d + attention + family.ssd_flops(cfg, rows, seq)
        + rows * layers * (step + (seq + 1) * heads * 2 * hd * 2)
        + 2 * rows * 2 * cfg["hidden_size"] * cfg["vocab_size"]
    )
    assert family.forward_flops(cfg, rows, seq) == want


# -- the scope table and the decoder's own names -------------------------------------------


def test_the_scope_table_covers_every_scope_the_decoder_names():
    root = os.path.dirname(BENCH)
    named = set()
    for module in ("falcon_h1", "judge"):
        source = open(os.path.join(root, "llm_weighted_consensus_tpu", "models", module + ".py")).read()
        named |= set(re.findall(r'named_scope\(\s*"(\w+)"', source))
    assert named == falconh1_scopes.SCOPES
    grouped = {s for group in falconh1_scopes.GROUPS.values() for s in group}
    assert grouped - {"unscoped"} <= falconh1_scopes.SCOPES
    # what no share holds is PERF.md's table by scope
    assert falconh1_scopes.SCOPES - grouped == {"embed_tokens", "head_read", "ballot_vote"}


def made_up_trace():
    """Three judge programs of 100 us; the middle one (the one kept) holds the
    scan's and the attention's kernels, the rotary turn under the attention's
    scope, scoped fusions of the mixer's products, the convolution (and a
    path-less copy that it alone reads), the gated norm, the MLP, a decode-step
    fusion whose path holds ``ssd_scan`` too, and one operation under no
    scope."""
    def ins(name, tf_op, operands=()):
        return {"name": name, "program": "1", "tf_op": tf_op, "category": None,
                "operands": list(operands)}

    base = "jit(judge_panel)/jit(main)/"
    instructions = [
        ins("ssd_chunked.3", base + "ssd_scan/jit(ssd_chunked)/pallas_call"),
        ins("causal_attention_blockwise.4", base + "causal_attention/jit(causal_attention_blockwise)/pallas_call"),
        ins("turn_lanes.2", base + "causal_attention/jit(turn_lanes)/pallas_call"),
        ins("fusion.1", base + "ssm_in/dot_general"),
        ins("copy.9", None, ()),
        ins("fusion.2", base + "ssm_conv/mul", ("copy.9",)),
        ins("fusion.3", base + "ssm_norm/mul"),
        ins("fusion.4", base + "attn_qkv/dot_general"),
        ins("fusion.5", base + "ssm_out/dot_general"),
        ins("fusion.6", base + "attn_out/dot_general"),
        ins("fusion.7", base + "mlp/dot_general"),
        ins("fusion.8", base + "decode_step/ssd_scan/mul"),
        ins("fusion.9", base + "convert_element_type"),
    ]
    durations = [4_000, 6_000, 1_000, 8_000, 1_000, 2_000, 3_000, 4_000, 3_000, 2_000, 60_000, 4_000, 2_000]
    ops = []
    for program in range(3):
        t = program * 200_000
        for index, dur in enumerate(durations):
            ops.append([index, t, dur])
            t += dur
    modules = [["jit_judge_panel(123)", p * 200_000, 100_000] for p in range(3)]
    return {"modules": modules, "instructions": instructions, "ops": ops, "spans": []}


def ctx_for(trace, family="falcon_h1"):
    label = "judge(n=3,s=8192)"
    before = {"roofline": {"buckets": {label: {"count": 5}}}, "judge": {"dispatches": 5, "expert_pairs_here": 0}}
    after = {"roofline": {"buckets": {label: {"count": 8}}}, "judge": {"dispatches": 8, "expert_pairs_here": 0}}
    return {
        "scoped": trace,
        "config": {"trace_modules": ["jit_judge_panel"], "family": family},
        "cfg": PUBLISHED,
        "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        "profile": {"before": before, "after": after},
    }


def test_the_scopes_and_shares_add_up_to_the_program():
    assert falconh1_scopes.scope_of("a/decode_step/ssd_scan/x") == "decode_step"
    assert falconh1_scopes.scope_of("a/ssd_scan/jit(ssd_chunked)/x") == "ssd_scan"
    assert falconh1_scopes.scope_of("a/causal_attention/jit(turn_lanes)/x") == "causal_attention"
    assert falconh1_scopes.scope_of("a/mlp/x") == "mlp"
    assert falconh1_scopes.scope_of("a/selective_scan/x") == "unscoped"  # the sixth judge's
    ctx = ctx_for(made_up_trace())
    share = {g: byname.module("reducers", f"falconh1_share_{g}").reduce(ctx)
             for g in falconh1_scopes.GROUPS}
    assert share == {
        "state_space": 10.0,  # the kernel, the convolution and the copy it alone reads, the norm
        "attention": 7.0,  # the kernel and the rotary turn
        "projections": 17.0,  # ssm_in, ssm_out, attn_qkv, attn_out
        "mlp": 60.0,
        "decode": 4.0,
        "unscoped": 2.0,
    }
    assert sum(share.values()) == 100.0
    assert falconh1_scopes.share({**ctx, "scoped": None}, "mlp") is None


def test_the_rooflines_and_the_mfu_read_the_kernels_own_events():
    family = byname.module("families", "falcon_h1")
    ctx = ctx_for(made_up_trace())
    got = byname.module("reducers", "falconh1_ssd_roofline").reduce(ctx)
    assert got == pytest.approx(100 * family.ssd_flops(PUBLISHED, 3, 8192) / 197e12 / 4e-6)
    got = byname.module("reducers", "causal_attention_roofline").reduce(ctx)
    assert got == pytest.approx(100 * family.causal_attention_flops(PUBLISHED, 3, 8192) / 197e12 / 6e-6)
    got = byname.module("reducers", "falconh1_forward_mfu").reduce(ctx)
    assert got == pytest.approx(100 * family.forward_flops(PUBLISHED, 3, 8192) / (100e-6 * 197e12))
    # at the chip's best the six scans are 4.0 ms of arithmetic a program and the
    # six attention kernels 31 ms: neither share can pass 100
    assert family.ssd_flops(PUBLISHED, 3, 8192) / 197e12 == pytest.approx(4.0e-3, rel=0.02)
    assert family.causal_attention_flops(PUBLISHED, 3, 8192) / 197e12 == pytest.approx(31.4e-3, rel=0.01)


def test_another_judge_s_program_gives_nothing_to_read():
    """The parent commit cannot run the cell at all; another judge's program
    (several name ``causal_attention``, ``attn_qkv`` and ``mlp`` too, but no
    ``ssd_scan``) gives every reader of this table nothing, and another family
    counts no state-space dual."""
    bare = made_up_trace()
    bare["instructions"] = [
        dict(i, tf_op=(i["tf_op"] or "").replace("ssd_scan", "selective_scan") or None)
        for i in bare["instructions"]
    ]
    ctx = ctx_for(bare)
    for group in falconh1_scopes.GROUPS:
        assert byname.module("reducers", f"falconh1_share_{group}").reduce(ctx) is None
    for name in ("falconh1_forward_mfu", "falconh1_ssd_roofline"):
        assert byname.module("reducers", name).reduce(ctx) is None
    other = ctx_for(made_up_trace(), family="afmoe")
    assert byname.module("reducers", "falconh1_ssd_roofline").reduce(other) is None


def test_the_cell_s_names_resolve():
    bench = json.load(open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")))
    mine = [m for m in bench["per_layer"] if CELL in m.get("workloads", [])]
    assert len(bench["per_layer"]) == 128 and len(mine) == 17  # eight of its own, nine joined
    own = [m["name"] for m in mine if m["name"].endswith(".falconh1")]
    assert own == [
        "forward.mfu.falconh1", "forward.share.state_space.falconh1",
        "forward.share.attention.falconh1", "forward.share.projections.falconh1",
        "forward.share.mlp.falconh1", "forward.share.decode.falconh1",
        "forward.share.unscoped.falconh1", "kernel.ssd_roofline.falconh1",
    ]
    assert [m["name"] for m in bench["per_layer"][-8:]] == own  # appended, at the end
    for metric in mine:
        assert metric["moves"] in ("answers_per_s", "setup_s")
        spec = json.load(open(os.path.join(BENCH, "layer_metrics", metric["name"] + ".json")))["read"]
        if spec["from"] == "trace":
            assert hasattr(byname.module("reducers", spec["reducer"]), "reduce")
    moved = next(m for m in bench["end_to_end"] if m["name"] == "answers_per_s")
    assert moved["workloads"][-1] == CELL
    for name in ("family", "reference"):
        byname.module({"family": "families", "reference": "references"}[name], PUBLISHED[name])
