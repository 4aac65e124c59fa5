"""The judge cell's own files (PR 27), on the CPU at the configuration's
``dry_run`` sizes: the reference against the program, the whole command sound
and broken, the int8 control, the family's counts against hand counts, and
the new reducers on made-up traces."""

import argparse
import json
import os
import random

import numpy as np
import pytest

import byname
import checkpoints
import judge_scopes
import run as bench_run

CELL = "glm-4.7-flash.n64-c8k.closed4"
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_cell():
    return bench_run.load_cell(CELL, dry=True)


def args(seed, control=False):
    return argparse.Namespace(
        workload=CELL, seed=seed, seconds=3.0, trace=0, dry_run=True,
        control=control, benchmark=None,
    )


def last_line(capsys):
    lines = [l for l in capsys.readouterr().out.splitlines() if l.startswith("{")]
    return json.loads(lines[-1])


# -- the reference against the program ------------------------------------------


def test_the_reference_builds_the_programs_ballot():
    from llm_weighted_consensus_tpu.ballot.tree import PrefixTree

    ref = byname.module("references", "glm4_moe_lite_judge")
    for seed, n in ((3, 3), (2**31 - 5, 20), (11, 21), (12, 64), (13, 400)):
        rng = random.Random(seed)
        tree = PrefixTree.build(rng, n, 20)
        pairs = tree.key_indices(rng)
        root, depth, mine = ref.ballot(seed, n)
        assert (root, depth, mine) == (tree.root, tree.depth, pairs)


def test_the_reference_tokenizes_like_the_program(tmp_path):
    from llm_weighted_consensus_tpu.models.judge import TpuJudge
    from llm_weighted_consensus_tpu.models.tokenizer import load_tokenizer

    _, _, config, cfg, mix, gen = load_cell()
    ref = byname.module("references", config["reference"])
    writer = byname.module("tokenizers", config["tokenizer"]["kind"])
    vocab = str(tmp_path / writer.FILE)
    writer.write(vocab, cfg["vocab_size"])
    judge = TpuJudge(
        "glm-test-tiny", tokenizer=load_tokenizer(vocab, scheme="deberta"),
        max_tokens=cfg["max_tokens"],
    )
    tok = config["tokenizer"]
    assert judge.letter_ids.tolist() == [ref.letter_id(c, tok) for c in ref.ALPHABET]
    for req in gen.generate(mix, 7, 2.0, cfg["vocab_size"] - tok["specials"])[:2]:
        body = gen.render_body(req)
        panel = [(c["seed"], c["weight"]) for c in body["panel"]]
        prepared = judge.prepare(body["input"], body["prompt"], panel)
        for row, (seed, _) in enumerate(panel):
            _, _, pairs = ref.ballot(seed, req["n"])
            want = ref.call_ids(req, pairs, tok)
            assert prepared.ids[row, : prepared.lens[row]].tolist() == want
            assert len(want) == gen.request_tokens(req, tok["overhead"])
            assert tok["unk"] not in want


def test_the_reference_is_the_programs_forward_in_float32():
    import jax.numpy as jnp

    from llm_weighted_consensus_tpu.models import glm_moe
    from llm_weighted_consensus_tpu.models.configs import GLM_TEST_TINY

    _, _, config, cfg, _, _ = load_cell()
    ref = byname.module("references", config["reference"])
    state = checkpoints.make_state(config["family"], cfg, 2**31 + 9)
    f32 = {k: np.asarray(v).astype(np.float32) for k, v in state.items()}
    params, served = glm_moe.from_hf_weights(f32, GLM_TEST_TINY, dtype=jnp.float32)
    rng = np.random.default_rng(2)
    lens = [150, 97]
    ids = np.zeros((2, 160), np.int32)
    for row, n in enumerate(lens):
        ids[row, :n] = rng.integers(32, cfg["vocab_size"], size=n)
    letters = list(range(4, 24))
    hidden, _, _ = glm_moe.prefill(params, jnp.asarray(ids), served)
    calls = [(ids[row, :n].tolist(), [n - 1, n // 2]) for row, n in enumerate(lens)]
    reads = ref.read_logits(f32, cfg, calls, letters)
    for row, n in enumerate(lens):
        got = np.asarray(glm_moe.head_logprobs(params, hidden[row, [n - 1, n // 2]], served))
        got = got[:, letters]
        want = reads[row]
        centred = lambda x: x - x.mean(axis=1, keepdims=True)  # noqa: E731
        assert np.abs(centred(got) - centred(want)).max() < 5e-6


def test_the_reference_says_how_firmly_it_routed_each_read():
    """With the router's weights nought every score is a half, so the bias
    alone ranks the experts: a read's margin is then the least, over the
    sparse layers, of the k-th largest bias less the next."""
    _, _, config, cfg, _, _ = load_cell()
    ref = byname.module("references", config["reference"])
    state = checkpoints.make_state(config["family"], cfg, 2**31 + 11)
    f32 = {k: np.asarray(v).astype(np.float32) for k, v in state.items()}
    rng = np.random.default_rng(3)
    calls = [
        (rng.integers(32, cfg["vocab_size"], size=n).tolist(), [n - 1, n // 3])
        for n in (90, 41)
    ]
    letters = list(range(4, 24))
    reads, margins = ref.read_logits_and_margins(f32, cfg, calls, letters)
    again = ref.read_logits(f32, cfg, calls, letters)
    for read, same, margin, (_, rows) in zip(reads, again, margins, calls):
        assert np.array_equal(read, same)
        assert margin.shape == (len(rows),) and np.all(margin >= 0) and np.all(margin < 1)
    assert len({float(m) for margin in margins for m in margin}) == 4  # a row's own
    k, want = cfg["num_experts_per_tok"], np.inf
    for name in f32:
        if name.endswith("mlp.gate.weight"):
            f32[name] = np.zeros_like(f32[name])
        if name.endswith("e_score_correction_bias"):
            ranked = np.sort(f32[name].astype(np.float64))[::-1]
            want = min(want, ranked[k - 1] - ranked[k])
    _, margins = ref.read_logits_and_margins(f32, cfg, calls, letters)
    for margin in margins:
        assert np.allclose(margin, want, rtol=0, atol=1e-7)


def test_reads_the_reference_routed_by_rounding_are_left_out_of_the_median():
    check = byname.module("checks", "judge_ballot_logit")
    by_level = {"first": [0.3, 0.3, 0.3, 0.01], "last": [0.01, 0.02, 0.4, 0.03]}
    margins = {"first": [1e-5, 2e-5, 3e-5, 0.01], "last": [0.01, 0.02, 1e-5, 0.03]}
    firm, medians = check.firm_medians(by_level, margins, 0.0, 1)
    assert firm == by_level and medians == {"first": 0.3, "last": 0.025}
    firm, medians = check.firm_medians(by_level, margins, 1e-4, 1)
    assert firm == {"first": [0.01], "last": [0.01, 0.02, 0.03]}
    assert medians == {"first": 0.01, "last": 0.02}
    # a level that keeps too few is read over all of its reads
    firm, medians = check.firm_medians(by_level, margins, 1e-4, 2)
    assert firm["first"] == by_level["first"] and medians == {"first": 0.3, "last": 0.02}
    # and under a reference that gives no margin every read counts
    assert check.firm_medians(by_level, {}, 1e-4, 2)[0] == by_level


# -- the whole command ------------------------------------------------------------


def test_a_sound_run_is_correct(capsys):
    assert bench_run.run(args(2**31 + 99)) == 0
    result = last_line(capsys)
    assert result["correct"] is True and result["failed"] == 0
    assert result["check"]["ballot_logit_rms"]["value"] < 2e-6
    assert result["check"]["ballot_mismatches"]["value"] == 0
    assert result["check"]["confidence_abs_err"]["value"] < 1e-6


def broken_judge_env(monkeypatch, patch: str):
    """The server child imports ``sitecustomize`` from a directory put first
    on its PYTHONPATH: the timed path is broken inside the server, and the
    reference knows nothing of it."""
    import tempfile

    directory = tempfile.mkdtemp()
    with open(os.path.join(directory, "sitecustomize.py"), "w", encoding="utf-8") as f:
        f.write(patch)
    monkeypatch.setenv(
        "PYTHONPATH", directory + os.pathsep + os.environ.get("PYTHONPATH", "")
    )


SEED_IGNORED = """
import llm_weighted_consensus_tpu.models.judge as judge
_sound = judge._Call.__init__
def _broken(self, seed, weight, n):
    _sound(self, 0, weight, n)   # every call's ballot from seed 0
    self.seed = seed
judge._Call.__init__ = _broken
"""

CACHE_OFF_BY_ONE = """
import jax.numpy as jnp
import llm_weighted_consensus_tpu.models.glm_moe as glm_moe
_sound = glm_moe._attention_decode
def _broken(h, p, lens, cache, config):
    # the decoded token does not see the cache's last position
    return _sound(h, p, lens, (cache[0].at[jnp.arange(lens.shape[0]), lens - 1].set(0),
                               cache[1].at[jnp.arange(lens.shape[0]), lens - 1].set(0)), config)
glm_moe._attention_decode = _broken
"""


@pytest.mark.parametrize(
    "patch,number",
    [(SEED_IGNORED, "ballot_mismatches"), (CACHE_OFF_BY_ONE, "ballot_logit_rms")],
    ids=["a_ballots_seed_ignored", "the_caches_last_position_lost"],
)
def test_a_broken_timed_path_is_not_correct(patch, number, capsys, monkeypatch):
    broken_judge_env(monkeypatch, patch)
    assert bench_run.run(args(2**31 + 99)) == 1
    result = last_line(capsys)
    assert result["correct"] is False and result["failed"] == 0
    assert result["check"][number]["value"] > result["check"][number]["limit"]


def test_the_int8_control_is_not_correct_at_dry_size(capsys):
    assert bench_run.run(args(2**31 + 99, control=True)) == 1
    result = last_line(capsys)
    assert result["correct"] is False
    assert result["check"]["ballot_logit_rms"]["value"] > 2e-6
    assert result["check"]["confidence_abs_err"]["value"] < 1e-6  # the tally is exact


# -- counts ------------------------------------------------------------------------

PUBLISHED = json.load(open(os.path.join(BENCH, "configs", "glm-4.7-flash.json")))


def test_the_checkpoint_is_the_issues_nine_gigabytes():
    family = byname.module("families", "glm4_moe_lite")
    specs = family.tensors(PUBLISHED)
    assert len({name for name, _, _ in specs}) == len(specs)
    total = sum(int(np.prod(shape)) for _, shape, _ in specs)
    attention = 2048 * 768 + 768 * 5120 + 2048 * 576 + 512 * 8960 + 5120 * 2048
    assert attention == 21_757_952
    expert = 3 * 2048 * 1536
    sparse = attention + 65 * expert + 64 * 2048 + 64 + 2 * 2048 + 768 + 512
    dense = attention + 3 * 2048 * 10240 + 2 * 2048 + 768 + 512
    assert total == dense + 6 * sparse + 2 * 154880 * 2048 + 2048
    assert 9.05e9 < 2 * total < 9.08e9
    shards = checkpoints.plan_shards(specs, checkpoints.SHARD_BYTES)
    assert len(shards) == 2


def test_forward_flops_against_hand_counts():
    family = byname.module("families", "glm4_moe_lite")
    rows, seq = 3, 8192
    # the issue's reckoning, a call: attention 0.69 a layer, routed experts
    # 0.62 a sparse layer, latent projections 0.36 a layer
    attention = family.causal_attention_flops(PUBLISHED, 1, seq) / 7
    assert attention == 2 * 20 * (256 + 256) * seq * (seq + 1) // 2
    assert 0.68e12 < attention < 0.69e12
    experts = family.expert_products_flops(PUBLISHED, 1, seq) / 6
    assert experts == 2 * seq * 4 * 3 * 2048 * 1536
    assert 2 * seq * family._attention_weights(PUBLISHED) == 2 * seq * 21_757_952
    call = family.forward_flops(PUBLISHED, 1, seq)
    assert 12.9e12 < call < 13.1e12  # the issue: 13.0 TFLOP a call
    assert family.forward_flops(PUBLISHED, rows, seq) == rows * call
    # 4 experts a token and the shared one are counted, not the 64 held
    all_held = dict(PUBLISHED, num_experts_per_tok=64)
    assert family.forward_flops(all_held, 1, seq) > 3 * call
    # bytes: q, k, v and the context once; every expert's weights once
    assert family.causal_attention_bytes(PUBLISHED, 1, seq) == 7 * seq * 20 * 1024 * 2
    moved = family.expert_products_bytes(PUBLISHED, rows, seq) / 6
    assert moved == 2 * (64 * 3 * 2048 * 1536 + rows * seq * 4 * (2 * 3584 + 3584))


# -- the reducers on made-up traces ---------------------------------------------------


def made_up_trace():
    """Three judge programs of 100 us; the middle one (the one kept) holds a
    kernel, a scoped fusion, a decode-step fusion and a path-less copy."""
    def ins(name, tf_op, operands=()):
        return {"name": name, "program": "1", "tf_op": tf_op, "category": None,
                "operands": list(operands)}

    base = "jit(judge_panel)/jit(main)/"
    instructions = [
        ins("causal_attention_blockwise.3", base + "causal_attention/jit(causal_attention_blockwise)/pallas_call"),
        ins("grouped_expert_product.7", base + "experts_routed/jit(grouped_expert_product)/pallas_call"),
        ins("fusion.1", base + "latent_q/dot_general"),
        ins("fusion.2", base + "decode_step/latent_q/dot_general"),
        ins("copy.9", None, ()),
        ins("fusion.5", base + "router/dot_general", ("copy.9",)),
    ]
    ops = []
    for program in range(3):
        t0 = program * 200_000
        for index, (offset, dur) in enumerate(
            [(0, 40_000), (40_000, 20_000), (60_000, 10_000), (70_000, 5_000),
             (75_000, 5_000), (80_000, 20_000)]
        ):
            ops.append([index, t0 + offset, dur])
    modules = [["jit_judge_panel(123)", p * 200_000, 100_000] for p in range(3)]
    return {"modules": modules, "instructions": instructions, "ops": ops, "spans": []}


def ctx_for(trace):
    label = "judge(n=3,s=8192)"
    return {
        "scoped": trace,
        "config": {"trace_modules": ["jit_judge_panel"], "family": "glm4_moe_lite"},
        "cfg": PUBLISHED,
        "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        "profile": {
            "before": {"roofline": {"buckets": {label: {"count": 5}}}},
            "after": {"roofline": {"buckets": {label: {"count": 8}}}},
        },
    }


def test_the_judges_scopes_and_shares():
    assert judge_scopes.scope_of("a/decode_step/latent_q/x") == "decode_step"
    assert judge_scopes.scope_of("a/latent_kv/x") == "latent_kv"
    assert judge_scopes.scope_of("a/encoder_layers/mlp/x") == "unscoped"
    ctx = ctx_for(made_up_trace())
    share = {g: byname.module("reducers", f"judge_share_{g}").reduce(ctx)
             for g in judge_scopes.GROUPS}
    assert share == {
        "latent_attention": 40.0, "projections": 10.0,
        "experts": 45.0,  # the kernel, the router's fusion and the copy it alone reads
        "decode": 5.0, "unscoped": 0.0,
    }
    assert judge_scopes.share({**ctx, "scoped": None}, "experts") is None


def test_the_kernels_rooflines_are_counted_for_the_kept_programs():
    family = byname.module("families", "glm4_moe_lite")
    ctx = ctx_for(made_up_trace())
    got = byname.module("reducers", "causal_attention_roofline").reduce(ctx)
    least = family.causal_attention_flops(PUBLISHED, 3, 8192) / 197e12
    assert got == pytest.approx(100 * least / 40e-6)
    got = byname.module("reducers", "expert_products_roofline").reduce(ctx)
    least = family.expert_products_flops(PUBLISHED, 3, 8192) / 197e12
    assert got == pytest.approx(100 * least / 20e-6)
    # a program that lacks the kernels (the parent's): nothing to read
    bare = made_up_trace()
    bare["instructions"] = [dict(i, name="fusion.0") for i in bare["instructions"]]
    assert byname.module("reducers", "causal_attention_roofline").reduce(ctx_for(bare)) is None
    # a label that cannot be read: no guess
    odd = ctx_for(made_up_trace())
    odd["profile"]["after"]["roofline"]["buckets"]["judge(calls=3)"] = {"count": 1}
    assert byname.module("reducers", "expert_products_roofline").reduce(odd) is None


def test_the_new_metrics_read_nothing_from_a_program_without_a_judge():
    """What the parent commit's /metrics holds: no ``judge`` section."""
    import layers

    before = {"phases": {"tokenize": {"sum_ms": 1.0, "count": 1}}}
    after = {"phases": {"tokenize": {"sum_ms": 5.0, "count": 3}}}
    spec = json.load(open(os.path.join(BENCH, "layer_metrics", "experts.load_max_over_mean.json")))
    assert layers.read_metrics(spec["read"], before, after) is None
    spec = json.load(open(os.path.join(BENCH, "layer_metrics", "batcher.tokenize_ms.closed.json")))
    assert layers.read_metrics(spec["read"], before, after) == 2.0


def test_the_new_reducers_on_a_recorded_trace():
    """Three judge programs of a chip run (my chip run, PR 27: 3 calls x
    8192 slots, 7 layers, random bf16 weights, blocks of 512; the middle
    program's operations of 5 us and more kept, 6.5 of its 448.6 ms dropped
    with the rest, paths shortened): the shares add up with the
    scopes that are no metric, the kernels are found by name and read under
    their rooflines."""
    path = os.path.join(BENCH, "tests", "data", "trace_scoped_judge.json")
    with open(path, encoding="utf-8") as f:
        ctx = ctx_for(json.load(f))
    table, program_ns = judge_scopes.by_scope(ctx["scoped"], ["jit_judge_panel"])
    assert 440e6 < program_ns < 460e6  # one program of 448.6 ms
    kinds = {kind for _, kind in table}
    assert {"causal_attention_blockwise", "grouped_expert_product"} <= kinds
    share = {g: judge_scopes.share(ctx, g) for g in judge_scopes.GROUPS}
    assert 29 < share["latent_attention"] < 31 and 45 < share["experts"] < 48
    assert 13 < share["projections"] < 15 and 5 < share["decode"] < 8
    assert share["unscoped"] < 3
    rest = sum(ns for (s, _), ns in table.items()
               if s in ("embed_tokens", "head_read", "ballot_vote")) / program_ns
    assert sum(share.values()) + 100 * rest == pytest.approx(100 * (1 - 6.54 / 448.6), abs=0.1)
    attention = byname.module("reducers", "causal_attention_roofline").reduce(ctx)
    experts = byname.module("reducers", "expert_products_roofline").reduce(ctx)
    assert 50 < attention < 60 and 65 < experts < 80
