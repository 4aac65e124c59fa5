"""The sixth judge cell's own files (PR 45), on the CPU at the configuration's
``dry_run`` sizes: every published number against the catalog with NOTHING
reduced, the file's byte count against the tensor list, the traffic's tokens
against the bucket, the reference against the program in float32, the whole
command sound and broken (one lambda for every layer; a memory unit that
forgets its gate; a decoded token without its convolution's tail), the int8
control, every count of the family against a brute-force count at a tiny size,
the scope table against the scopes the decoder names, and the new reducers on a
made-up trace."""

import argparse
import json
import math
import os
import re

import numpy as np
import pytest

import byname
import checkpoints
import phi4flash_scopes
import run as bench_run
from test_judge_cell import broken_judge_env, last_line

CELL = "phi-4-mini-flash-reasoning.n64-c8k.closed4"
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PUBLISHED = json.load(open(os.path.join(BENCH, "configs", "phi-4-mini-flash-reasoning.json")))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
KINDS = ["mamba", "sliding"] * 8 + ["mamba", "full"] + ["memory", "cross"] * 7


def load_cell():
    return bench_run.load_cell(CELL, dry=True)


def args(seed, control=False):
    return argparse.Namespace(
        workload=CELL, seed=seed, seconds=3.0, trace=0, dry_run=True,
        control=control, benchmark=None,
    )


# -- the configuration against its source ----------------------------------------


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog beside the guide")
def test_every_published_number_stands_and_nothing_is_reduced():
    with open(CATALOG, encoding="utf-8") as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Phi-4-mini-flash-reasoning")
    assert PUBLISHED["source"] == row["source_url"]
    assert PUBLISHED["reduced"] == [] and PUBLISHED["reduced_from"] == {}
    for key, value in row["config"].items():
        assert PUBLISHED[key] == value, key
    for key in ("num_hidden_layers", "vocab_size", "hidden_size", "intermediate_size",
                "num_attention_heads", "num_key_value_heads", "sliding_window"):
        assert PUBLISHED["published"][key] == PUBLISHED[key] == row["config"][key], key
    assert PUBLISHED["max_tokens"] == 8192 == int(PUBLISHED["server_env"]["JUDGE_MAX_TOKENS"])
    assert PUBLISHED["mamba_dt_rank"] == math.ceil(PUBLISHED["hidden_size"] / 16) == 160
    bench = json.load(open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")))
    entry = next(c for c in bench["configs"] if c["name"] == "phi-4-mini-flash-reasoning")
    assert entry["reduced"] == [] and entry["source"] == row["source_url"]
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["traffic"], cell["chips"]) == ("n64-c8k.closed4", 1)


def test_the_file_s_byte_count_is_the_tensor_list_s():
    family = byname.module("families", "phi4flash")
    specs = family.tensors(PUBLISHED)
    assert len({name for name, _, _ in specs}) == len(specs)
    sizes = {name: 2 * int(np.prod(shape)) for name, shape, _ in specs}
    b = PUBLISHED["bytes"]

    def under(prefix):
        return sum(v for k, v in sizes.items() if k.startswith(prefix))

    assert b["checkpoint"] == sum(sizes.values()) == 7_705_125_888
    assert b["checkpoint"] == 2 * 3_852_562_944 and 0.48 < b["checkpoint"] / 16e9 < 0.49
    assert b["mlp_a_layer"] == under("model.layers.0.mlp.") == 2 * 3 * 2560 * 10240
    assert b["mamba_mixer"] == under("model.layers.0.attn.") == 2 * (
        2560 * 10240 + 5120 * 4 + 5120 + 5120 * 192 + 160 * 5120 + 5120 + 5120 * 16 + 5120
        + 5120 * 2560
    )
    assert b["self_attention_mixer"] == under("model.layers.1.attn.") == 2 * (
        2560 * 5120 + 5120 + 2560 * 2560 + 2560 + 4 * 64 + 128
    )
    assert b["cross_attention_mixer"] == under("model.layers.19.attn.") == 2 * (
        2 * (2560 * 2560 + 2560) + 4 * 64 + 128
    )
    assert b["memory_unit_mixer"] == under("model.layers.18.attn.") == 2 * 2 * 2560 * 5120
    assert b["norms_a_layer"] == 2 * 4 * 2560
    assert b["self_decoder_layers_0_to_17"] == sum(under(f"model.layers.{i}.") for i in range(18))
    assert b["cross_decoder_layers_18_to_31"] == sum(under(f"model.layers.{i}.") for i in range(18, 32))
    assert round(b["self_decoder_layers_0_to_17"] / 2e6, 2) == 1963.96  # the issue's count
    assert round(b["cross_decoder_layers_18_to_31"] / 2e6, 2) == 1376.44
    assert b["embedding_tied_head"] == 2 * 200_064 * 2560 and b["final_norm"] == 2 * 2 * 2560
    assert b["checkpoint"] == (
        b["self_decoder_layers_0_to_17"] + b["cross_decoder_layers_18_to_31"]
        + b["embedding_tied_head"] + b["final_norm"]
    )
    assert len(checkpoints.plan_shards(specs, checkpoints.SHARD_BYTES)) == 2


def test_the_checkpoint_names_every_kind_of_layer_and_no_head():
    family = byname.module("families", "phi4flash")
    specs = family.tensors(PUBLISHED)
    assert [family.kind_of(PUBLISHED, i) for i in range(32)] == KINDS
    assert ("model.layers.16.attn.A_log", (5120, 16), "normal") in specs
    assert ("model.layers.16.attn.D", (5120,), "ln_scale") in specs
    assert ("model.layers.0.attn.conv1d.weight", (5120, 1, 4), "normal") in specs
    assert ("model.layers.2.attn.x_proj.weight", (192, 5120), "normal") in specs
    assert ("model.layers.17.attn.Wqkv.weight", (5120, 2560), "normal") in specs
    assert ("model.layers.19.attn.Wq.weight", (2560, 2560), "normal") in specs
    assert ("model.layers.31.attn.inner_cross_attn.subln.weight", (128,), "ln_scale") in specs
    assert ("model.layers.18.attn.in_proj.weight", (5120, 2560), "normal") in specs
    assert ("model.layers.5.mlp.fc1.weight", (20480, 2560), "normal") in specs
    assert ("model.layers.30.input_layernorm.bias", (2560,), "normal") in specs
    names = {name for name, _, _ in specs}
    assert "lm_head.weight" not in names and "model.layers.19.attn.Wqkv.weight" not in names
    assert not any(".18.attn.A_log" in n or ".18.attn.conv1d" in n for n in names)
    # the dry sizes keep every kind in eight layers
    _, _, _, cfg, _, _ = load_cell()
    assert [family.kind_of(cfg, i) for i in range(8)] == [
        "mamba", "sliding", "mamba", "sliding", "mamba", "full", "memory", "cross"
    ]
    assert cfg["num_attention_heads"] % 2 == 0 and cfg["num_key_value_heads"] % 2 == 0


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
    assert max(int(w.max()) for r in requests[:8] for w in r["words"]) > 150_000
    assert max(int(w.max()) for r in requests[:8] for w in r["words"]) < 200_064 - tok["specials"]


# -- the reference against the program ------------------------------------------


def test_the_reference_is_the_programs_forward_in_float32():
    """The seeded dry checkpoint names eight layers of every kind: both sides
    serve that, the program through its kernels, its split and its three kinds
    of cache, the reference through a scan a position, whole mask rows and one
    forward of every layer at every position."""
    import jax.numpy as jnp

    from llm_weighted_consensus_tpu.models import sambay
    from llm_weighted_consensus_tpu.models.configs import PHI4FLASH_TEST_TINY

    _, _, config, cfg, _, _ = load_cell()
    ref = byname.module("references", config["reference"])
    state = checkpoints.make_state(config["family"], cfg, 2**31 + 9)
    f32 = {k: np.asarray(v).astype(np.float32) for k, v in state.items()}
    params, served = sambay.from_hf_weights(f32, PHI4FLASH_TEST_TINY, dtype=jnp.float32)
    assert served == PHI4FLASH_TEST_TINY
    rng = np.random.default_rng(2)
    lens = [150, 5]  # above the window of 8 and off every block; below it
    ids = np.zeros((2, 160), np.int32)
    for row, n in enumerate(lens):
        ids[row, :n] = rng.integers(32, cfg["vocab_size"], size=n)
    letters = list(range(4, 24))
    token = np.array([5, 17], np.int32)
    hidden, caches, loads = sambay.prefill(
        params, jnp.asarray(ids), served, lens=jnp.asarray(lens, jnp.int32)
    )
    assert hidden.shape == (2, 1, 64) and loads == [] and caches[7] is caches[5]
    assert [c[0].shape[1] for c in caches[:6]] == [3, 7, 3, 7, 3, 160]
    step = sambay.decode_step(params, jnp.asarray(token), jnp.asarray(lens, jnp.int32), caches, served)
    calls = [(ids[row, :n].tolist() + [int(token[row])], [n - 1, n]) for row, n in enumerate(lens)]
    reads = ref.read_logits(f32, cfg, calls, letters)
    centred = lambda x: x - x.mean(axis=1, keepdims=True)  # noqa: E731
    for row in range(2):
        got = np.concatenate([
            np.asarray(sambay.head_logprobs(params, hidden[row], served)),
            np.asarray(sambay.head_logprobs(params, step[row][None], served)),
        ])[:, letters]
        assert np.abs(centred(got) - centred(reads[row])).max() < 5e-6


# -- the whole command ------------------------------------------------------------


def test_a_sound_run_is_correct(capsys):
    assert bench_run.run(args(2**31 + 99)) == 0
    result = last_line(capsys)
    assert result["correct"] is True and result["failed"] == 0
    assert result["check"]["ballot_logit_rms"]["value"] < 2e-6
    assert result["check"]["ballot_mismatches"]["value"] == 0
    assert result["check"]["confidence_abs_err"]["value"] < 1e-6


ONE_LAMBDA = """
import llm_weighted_consensus_tpu.models.configs as configs
# "lambda_init is 0.8, its limit": every layer's differential weight and scale alike
configs.Phi4FlashConfig.lambda_init = lambda self, layer: 0.8
"""

MEMORY_UNGATED = """
import llm_weighted_consensus_tpu.models.sambay as sambay
# the memory unit without its gate: the other layer's scan straight through out_proj
sambay._memory_unit = lambda h, m, p: sambay.dense(m, p["out"])
"""

TAIL_FORGOTTEN = """
import llm_weighted_consensus_tpu.models.sambay as sambay
_sound = sambay._mamba_decode
def _broken(h, p, cache, config):
    # the decoded token's convolution sees no token before it
    return _sound(h, p, (cache[0] * 0, cache[1]), config)
sambay._mamba_decode = _broken
"""


@pytest.mark.parametrize(
    "patch", [ONE_LAMBDA, MEMORY_UNGATED, TAIL_FORGOTTEN],
    ids=["one_lambda_for_every_layer", "a_memory_unit_without_its_gate",
         "a_decoded_token_without_its_tail"],
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


def brute_force(cfg: dict, rows: int, seq: int) -> dict:
    """The work the answer needs, counted a call, a position and a layer from
    the checkpoint's own tensor shapes: every 2-D weight a token meets is two
    operations an entry; a sliding layer's query meets min(window, t + 1)
    keys, 3 hd lanes a key and head; the scan six operations a channel and
    state.  Layers 0 .. K - 1 and layer K's key and value rows at every slot
    and at the decoded token's; layer K's query, scores and output, every layer
    behind it and the head at the two positions read."""
    family = byname.module("families", "phi4flash")
    shapes = {name: shape for name, shape, _ in family.tensors(cfg)}
    h, heads, kv = cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, window, n_layers = h // heads, cfg["sliding_window"], cfg["num_hidden_layers"]
    inner, states = cfg["mamba_expand"] * h, cfg["mamba_d_state"]
    top = n_layers // 2 + 1

    def product(name, rows_of=None):
        out_rows, in_cols = shapes[name]
        return 2 * (rows_of if rows_of is not None else out_rows) * in_cols

    def mlp(i):
        return product(f"model.layers.{i}.mlp.fc1.weight") + product(f"model.layers.{i}.mlp.fc2.weight")

    count = {"window": 0, "scan": 0, "products": 0, "behind": 0, "heads": 0}
    for _ in range(rows):
        for t in range(seq + 1):  # the slots, and the decoded token at position seq
            for i in range(top):
                mix = f"model.layers.{i}.attn"
                if family.kind_of(cfg, i) == "mamba":
                    count["products"] += sum(
                        product(f"{mix}.{name}.weight")
                        for name in ("in_proj", "x_proj", "dt_proj", "out_proj")
                    )
                    count["scan"] += 6 * inner * states
                else:
                    count["products"] += product(f"{mix}.Wqkv.weight") + product(f"{mix}.out_proj.weight")
                    count["window"] += min(window, t + 1) * heads * 3 * hd * 2
                count["products"] += mlp(i)
            count["products"] += product(f"model.layers.{top}.attn.Wqkv.weight", rows_of=2 * kv * hd)
        for keys in (seq, seq + 1):  # the row read, and the decoded token
            for i in range(top, n_layers):
                mix = f"model.layers.{i}.attn"
                kind = family.kind_of(cfg, i)
                if kind == "memory":
                    count["behind"] += product(f"{mix}.in_proj.weight") + product(f"{mix}.out_proj.weight")
                else:
                    fused = "Wq" if kind == "cross" else "Wqkv"
                    count["behind"] += product(f"{mix}.{fused}.weight", rows_of=heads * hd)
                    count["behind"] += product(f"{mix}.out_proj.weight") + keys * heads * 3 * hd * 2
                count["behind"] += mlp(i)
            count["heads"] += product("model.embed_tokens.weight")
    return count


def test_every_count_of_the_family_against_a_brute_force_count_at_a_tiny_size():
    family = byname.module("families", "phi4flash")
    _, _, _, cfg, _, _ = load_cell()
    rows, seq = 2, 21
    brute = brute_force(cfg, rows, seq)
    assert family.forward_flops(cfg, rows, seq) == sum(brute.values())
    # the parts: the prefill's band and scans alone (the decoded token's taken off)
    window, hd = cfg["sliding_window"], cfg["hidden_size"] // cfg["num_attention_heads"]
    pair = cfg["num_attention_heads"] * 3 * hd * 2
    token_window = family.layers_of(cfg, "sliding") * min(window, seq + 1) * pair
    assert family.window_attention_flops(cfg, rows, seq) == brute["window"] - rows * token_window
    assert family.selective_scan_flops(cfg, rows, seq) == brute["scan"] * seq // (seq + 1)
    assert rows * (seq + 1) * family.self_decoder_token_flops(cfg) == brute["products"]
    assert rows * (
        family.cross_decoder_row_flops(cfg, seq) + family.cross_decoder_row_flops(cfg, seq + 1)
    ) == brute["behind"]
    band = sum(min(window, t + 1) for t in range(seq))
    assert family.band_pairs(cfg, seq) == band and family.causal_pairs(seq) == seq * (seq + 1) // 2
    assert family.band_pairs(cfg, 5) == family.causal_pairs(5)  # a call inside one window
    # bytes: each array once, at the mathematics' width
    h, inner = cfg["hidden_size"], 2 * cfg["hidden_size"]
    kv_width = cfg["num_key_value_heads"] * hd
    assert family.window_attention_bytes(cfg, rows, seq) == 2 * rows * seq * (h + 2 * kv_width + 2 * h) * 2
    assert family.selective_scan_bytes(cfg, rows, seq) == 3 * rows * seq * (3 * inner + 2 * 16) * 2


def test_operations_against_hand_arithmetic_at_the_cells_shapes():
    family = byname.module("families", "phi4flash")
    rows, seq = 3, 8192
    assert family.band_pairs(PUBLISHED, seq) == 512 * 513 // 2 + (seq - 512) * 512 == 4_063_488
    assert family.band_pairs(PUBLISHED, seq) / family.causal_pairs(seq) == pytest.approx(0.12109, abs=1e-5)
    window = family.window_attention_flops(PUBLISHED, rows, seq)
    assert window == 8 * rows * 4_063_488 * 40 * (64 + 128) * 2
    assert window == pytest.approx(1.5e12, rel=0.01)  # the issue: eight windows 1.5 TFLOP
    scans = family.selective_scan_flops(PUBLISHED, rows, seq)
    assert scans / 9 / 6 == rows * seq * 5120 * 16 == 2_013_265_920  # 2.0 G state updates a layer
    moved = family.selective_scan_bytes(PUBLISHED, rows, seq) / 9
    assert moved == pytest.approx(0.756e9, rel=0.005)  # the issue's 0.76 GB
    assert moved / 819e9 > 10 * (scans / 9) / 197e12  # memory-bound as counted, far
    assert family.window_attention_bytes(PUBLISHED, rows, seq) / 8 == pytest.approx(0.503e9, rel=0.005)
    whole = family.forward_flops(PUBLISHED, rows, seq)
    assert whole == pytest.approx(93.6e12, rel=0.002)
    products = rows * seq * family.self_decoder_token_flops(PUBLISHED)
    assert products == pytest.approx(91.96e12, rel=0.002)
    rest = whole - products - window - scans
    # the second decoder at two positions, the decoded token's first half, two
    # reads of the whole vocabulary: weights read, next to no arithmetic
    assert 0 < rest < 0.06e12
    behind = family.cross_decoder_row_flops(PUBLISHED, seq)
    assert behind == pytest.approx(2 * (8 * 13_107_200 + 7 * 26_214_400 + 15 * 78_643_200) + 8 * seq * 40 * 192 * 2)
    # the split decides 41% of the layer parameters' work
    assert 1376.44 / (1963.96 + 1376.44) == pytest.approx(0.412, abs=0.001)


# -- the scope table and the decoder's own names -------------------------------------------


def test_the_scope_table_covers_every_scope_the_decoder_names():
    root = os.path.dirname(BENCH)
    named = set()
    for module in ("sambay", "judge"):
        source = open(os.path.join(root, "llm_weighted_consensus_tpu", "models", module + ".py")).read()
        named |= set(re.findall(r'named_scope\(\s*"(\w+)"', source))
        named |= {n for pair in re.findall(r'scope = "(\w+)" if .* else "(\w+)"', source) for n in pair}
    inner = {"causal_attention", "memory_unit", "cross_attention"}  # beneath cross_decoder
    assert inner <= named and named - inner == phi4flash_scopes.SCOPES
    grouped = {s for group in phi4flash_scopes.GROUPS.values() for s in group}
    assert grouped - {"unscoped"} <= phi4flash_scopes.SCOPES
    # what no share holds is PERF.md's table by scope
    assert phi4flash_scopes.SCOPES - grouped == {"embed_tokens", "head_read", "ballot_vote"}


def made_up_trace():
    """Three judge programs of 100 us; the middle one (the one kept) holds the
    scan's and the window's kernels, the pairs' norm kernel, scoped fusions of
    the Mamba products, the attention's, the MLP, the second decoder's row
    (whose inner scopes are NOT shares: an ``mlp`` and a ``diff_norm`` beneath
    ``cross_decoder`` are the second decoder's), a decode-step fusion whose
    path holds ``cross_decoder`` too, a path-less copy that the convolution's
    fusion alone reads, and one operation under no scope."""
    def ins(name, tf_op, operands=()):
        return {"name": name, "program": "1", "tf_op": tf_op, "category": None,
                "operands": list(operands)}

    base = "jit(judge_panel)/jit(main)/"
    instructions = [
        ins("selective_scan_chunked.3", base + "selective_scan/jit(selective_scan_chunked)/pallas_call"),
        ins("window_attention_blockwise.4", base + "window_attention/jit(window_attention_blockwise)/pallas_call"),
        ins("head_norm_turn.2", base + "diff_norm/jit(head_norm_turn)/pallas_call"),
        ins("fusion.1", base + "mamba_in/dot_general"),
        ins("copy.9", None, ()),
        ins("fusion.2", base + "mamba_conv/mul", ("copy.9",)),
        ins("fusion.3", base + "attn_qkv/dot_general"),
        ins("fusion.4", base + "mlp/dot_general"),
        ins("fusion.5", base + "cross_decoder/mlp/dot_general"),
        ins("fusion.6", base + "cross_decoder/cross_attention/diff_norm/mul"),
        ins("fusion.7", base + "decode_step/cross_decoder/memory_unit/dot_general"),
        ins("fusion.8", base + "convert_element_type"),
    ]
    durations = [12_000, 10_000, 2_000, 14_000, 1_000, 3_000, 6_000, 40_000, 3_000, 2_000, 4_000, 3_000]
    ops = []
    for program in range(3):
        t = program * 200_000
        for index, dur in enumerate(durations):
            ops.append([index, t, dur])
            t += dur
    modules = [["jit_judge_panel(123)", p * 200_000, 100_000] for p in range(3)]
    return {"modules": modules, "instructions": instructions, "ops": ops, "spans": []}


COUNTED = (
    {"dispatches": 5, "expert_pairs_here": 0, "window_keys_band": 8 * 3 * 4_063_488,
     "window_keys_causal": 8 * 3 * 33_558_528, "layer_positions_run": 3 * (18 * 8192 + 14),
     "layer_positions_whole": 3 * 32 * 8192},
    {"dispatches": 8, "expert_pairs_here": 0, "window_keys_band": 4 * 8 * 3 * 4_063_488,
     "window_keys_causal": 4 * 8 * 3 * 33_558_528, "layer_positions_run": 4 * 3 * (18 * 8192 + 14),
     "layer_positions_whole": 4 * 3 * 32 * 8192},
)


def ctx_for(trace, family="phi4flash", counted=COUNTED):
    label = "judge(n=3,s=8192)"
    before = {"roofline": {"buckets": {label: {"count": 5}}}}
    after = {"roofline": {"buckets": {label: {"count": 8}}}}
    if counted is not None:
        before["judge"], after["judge"] = counted
    return {
        "scoped": trace,
        "config": {"trace_modules": ["jit_judge_panel"], "family": family},
        "cfg": PUBLISHED,
        "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        "profile": {"before": before, "after": after},
    }


def test_the_scopes_and_shares_add_up_to_the_program():
    assert phi4flash_scopes.scope_of("a/decode_step/cross_decoder/memory_unit/x") == "decode_step"
    assert phi4flash_scopes.scope_of("a/cross_decoder/mlp/x") == "cross_decoder"
    assert phi4flash_scopes.scope_of("a/cross_decoder/causal_attention/x") == "cross_decoder"
    assert phi4flash_scopes.scope_of("a/mlp/x") == "mlp"
    assert phi4flash_scopes.scope_of("a/selective_scan/jit(selective_scan_chunked)/x") == "selective_scan"
    assert phi4flash_scopes.scope_of("a/diff_norm/jit(head_norm_turn)/x") == "diff_norm"
    assert phi4flash_scopes.scope_of("a/delta_rule/x") == "unscoped"  # the second judge's
    ctx = ctx_for(made_up_trace())
    share = {g: byname.module("reducers", f"phi4flash_share_{g}").reduce(ctx)
             for g in phi4flash_scopes.GROUPS}
    assert share == {
        "state_space": 30.0,  # the kernel, in_proj, the convolution and the copy it alone reads
        "attention": 18.0,  # the window kernel, the pairs' norm kernel, the projections
        "mlp": 40.0,
        "cross_decoder": 5.0,  # its MLP and its norm are its own, not the first half's
        "decode": 4.0,
        "unscoped": 3.0,
    }
    assert sum(share.values()) == 100.0
    assert phi4flash_scopes.share({**ctx, "scoped": None}, "mlp") is None


def test_the_rooflines_and_the_mfu_read_the_kernels_own_events():
    family = byname.module("families", "phi4flash")
    ctx = ctx_for(made_up_trace())
    got = byname.module("reducers", "phi4flash_selective_scan_roofline").reduce(ctx)
    assert got == pytest.approx(100 * family.selective_scan_bytes(PUBLISHED, 3, 8192) / 819e9 / 12e-6)
    got = byname.module("reducers", "phi4flash_window_attention_roofline").reduce(ctx)
    assert got == pytest.approx(100 * family.window_attention_flops(PUBLISHED, 3, 8192) / 197e12 / 10e-6)
    got = byname.module("reducers", "phi4flash_forward_mfu").reduce(ctx)
    assert got == pytest.approx(100 * family.forward_flops(PUBLISHED, 3, 8192) / (100e-6 * 197e12))
    # at the chip's best the scan's nine layers are 8.3 ms of memory and the
    # windows 7.6 ms of arithmetic a program: neither share can pass 100
    assert family.selective_scan_bytes(PUBLISHED, 3, 8192) / 819e9 == pytest.approx(8.3e-3, rel=0.01)
    assert family.window_attention_flops(PUBLISHED, 3, 8192) / 197e12 == pytest.approx(7.6e-3, rel=0.01)


def test_the_counters_give_the_shares():
    import layers

    def read(name):
        spec = json.load(open(os.path.join(BENCH, "layer_metrics", name + ".json")))
        return layers.read_metrics(spec["read"], {"judge": COUNTED[0]}, {"judge": COUNTED[1]})

    assert read("window.band_share.phi4flash") == pytest.approx(12.109, abs=1e-3)
    assert read("yoco.positions_run_share.phi4flash") == pytest.approx(56.255, abs=1e-3)
    assert round(read("yoco.positions_run_share.phi4flash"), 1) == 56.3
    # a program that keeps no such counter (the parent's, another judge's) gives nothing
    bare = ({"dispatches": 5}, {"dispatches": 8})
    spec = json.load(open(os.path.join(BENCH, "layer_metrics", "yoco.positions_run_share.phi4flash.json")))
    assert layers.read_metrics(spec["read"], {"judge": bare[0]}, {"judge": bare[1]}) is None


def test_another_judge_s_program_gives_nothing_to_read():
    """The parent commit cannot run the cell at all; another judge's program
    (the fourth and the fifth name ``window_attention`` and ``attn_qkv`` too,
    but no ``selective_scan``) gives every reader of this table nothing, and
    another family counts no selective scan."""
    bare = made_up_trace()
    bare["instructions"] = [
        dict(i, tf_op=(i["tf_op"] or "").replace("selective_scan", "delta_rule") or None)
        for i in bare["instructions"]
    ]
    ctx = ctx_for(bare)
    for group in phi4flash_scopes.GROUPS:
        assert byname.module("reducers", f"phi4flash_share_{group}").reduce(ctx) is None
    for name in ("phi4flash_forward_mfu", "phi4flash_selective_scan_roofline",
                 "phi4flash_window_attention_roofline"):
        assert byname.module("reducers", name).reduce(ctx) is None
    other = ctx_for(made_up_trace(), family="afmoe")
    assert byname.module("reducers", "phi4flash_selective_scan_roofline").reduce(other) is None
