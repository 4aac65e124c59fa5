"""Consensus-quality observability (ISSUE 12): per-judge scorecards
(agreement, entropy, hedging, top-1 calibration/ECE, exact weight
share), pairwise Cohen's kappa, windowed drift detection, the
JUDGE_BIAS_PLAN drill seam, the persistent outcome ledger, the
all-judges-failed forced-trace regression, the ledger -> training
round trip, and the seeded end-to-end bias drill over the gateway."""

import asyncio
import json
import math
import random
from decimal import Decimal

import numpy as np
import pytest
from aiohttp.test_utils import TestClient, TestServer

from llm_weighted_consensus_tpu import archive, obs, registry
from llm_weighted_consensus_tpu.clients.chat import (
    ApiBase,
    BackoffPolicy,
    DefaultChatClient,
)
from llm_weighted_consensus_tpu.clients.multichat import MultichatClient
from llm_weighted_consensus_tpu.clients.score import (
    AllVotesFailed,
    ScoreClient,
)
from llm_weighted_consensus_tpu.identity.model import ModelBase
from llm_weighted_consensus_tpu.obs import (
    LEDGER_SCHEMA,
    JudgeBallot,
    Outcome,
    OutcomeLedger,
    QualityAggregator,
)
from llm_weighted_consensus_tpu.obs.quality import N_CALIBRATION_BINS
from llm_weighted_consensus_tpu.resilience import JudgeBiasPlan
from llm_weighted_consensus_tpu.serve import Config, build_app
from llm_weighted_consensus_tpu.serve.metrics import (
    KNOWN_PROM_FAMILIES,
    KNOWN_SECTIONS,
    Metrics,
    register_quality,
    render_prometheus,
)
from llm_weighted_consensus_tpu.types.score_request import (
    ChatCompletionCreateParams as ScoreParams,
)
from llm_weighted_consensus_tpu.utils import jsonutil
from llm_weighted_consensus_tpu.weights.training_table import (
    TrainingTableStore,
)

from fakes import FakeTransport, Script, chunk_obj

SEED = 42
NO_RETRY = BackoffPolicy(max_elapsed_ms=0)
AB = [ApiBase("https://a.example", "key-a")]
TEXTS = ["answer alpha", "answer beta"]


def go(coro):
    return asyncio.new_event_loop().run_until_complete(coro)


@pytest.fixture(autouse=True)
def _fresh_quality():
    # the aggregator is a process-global singleton (like phases); every
    # test starts and ends from a clean, default-configured slate
    obs.reset_quality()
    obs.configure_quality(window=64, drift_threshold=0.25)
    yield
    obs.reset_quality()
    obs.configure_quality(window=64, drift_threshold=0.25)


# -- synthetic outcome helpers ------------------------------------------------


def ballot(model, vote, weight=1, error_code=None, index=0):
    return JudgeBallot(
        model=model,
        model_index=index,
        weight=Decimal(weight),
        vote=vote,
        error_code=error_code,
    )


def outcome(ballots, winner=0, margin=0.5, n=2, **kw):
    weight_sum = sum(
        (b.weight for b in ballots if b.vote is not None), Decimal(0)
    )
    flags = {
        "degraded": False,
        "quorum_degraded": False,
        "all_failed": False,
    }
    flags.update(kw)
    return Outcome(
        winner=winner,
        margin=margin,
        weight_sum=weight_sum,
        n_choices=n,
        trace_id="trace-1",
        judges=ballots,
        **flags,
    )


# -- scorecard math -----------------------------------------------------------


def test_scorecard_rates_and_exact_weight_share():
    agg = QualityAggregator()
    # a (weight 2) always agrees with the winner; b (weight 1) never
    for _ in range(3):
        agg.observe_outcome(
            outcome(
                [
                    ballot("a", [0.8, 0.2], weight=2),
                    ballot("b", [0.1, 0.9], weight=1, index=1),
                ]
            )
        )
    # b additionally abstains, errors, and is cancelled once each
    for code in (None, 500, 499):
        agg.observe_outcome(
            outcome(
                [
                    ballot("a", [0.8, 0.2], weight=2),
                    ballot("b", None, error_code=code, index=1),
                ]
            )
        )
    a = agg.scorecard("a")
    b = agg.scorecard("b")
    assert a["ballots"] == 6 and a["voted"] == 6
    assert a["agreement_rate"] == 1.0
    assert a["hedge_rate"] == 0.0
    assert b["ballots"] == 6 and b["voted"] == 3
    assert b["agreement_rate"] == 0.0
    assert b["abstain_rate"] == round(1 / 6, 4)
    assert b["error_rate"] == round(1 / 6, 4)
    assert b["cancelled_rate"] == round(1 / 6, 4)
    # weight share is Decimal-exact: a contributed 2 of each 3-weight
    # shared panel plus 2 of each 2-weight solo panel
    assert a["weight_share"] == float(
        Decimal(2 * 6) / Decimal(3 * 3 + 2 * 3)
    )
    assert b["weight_share"] == float(Decimal(3) / Decimal(9))
    assert agg.scorecard("nope") is None


def test_entropy_and_hedge_detection():
    agg = QualityAggregator()
    agg.observe_outcome(
        outcome(
            [
                # uniform vote: maximal entropy, and a hedge (top < 0.5)
                ballot("fence-sitter", [0.25, 0.25, 0.25, 0.25]),
                # one-hot vote: zero entropy, no hedge
                ballot("decisive", [1.0, 0.0, 0.0, 0.0], index=1),
            ],
            n=4,
        )
    )
    fence = agg.scorecard("fence-sitter")
    decisive = agg.scorecard("decisive")
    assert fence["entropy_mean"] == 1.0
    assert fence["hedge_rate"] == 1.0
    assert decisive["entropy_mean"] == 0.0
    assert decisive["hedge_rate"] == 0.0


def test_top1_calibration_bins_and_ece():
    agg = QualityAggregator()
    # two confident picks that win, two mild picks that lose
    for _ in range(2):
        agg.observe_outcome(outcome([ballot("j", [0.95, 0.05])], winner=0))
    for _ in range(2):
        agg.observe_outcome(outcome([ballot("j", [0.45, 0.55])], winner=0))
    cal = agg.scorecard("j")["calibration"]
    assert cal["samples"] == 4
    by_le = {row["le"]: row for row in cal["bins"]}
    assert by_le[1.0]["count"] == 2
    assert by_le[1.0]["p_avg"] == 0.95
    assert by_le[1.0]["win_rate"] == 1.0
    assert by_le[0.6]["count"] == 2
    assert by_le[0.6]["p_avg"] == 0.55
    assert by_le[0.6]["win_rate"] == 0.0
    # ECE = 0.5*|0.95-1.0| + 0.5*|0.55-0.0|
    assert cal["ece"] == round(0.5 * 0.05 + 0.5 * 0.55, 4)
    assert len(cal["bins"]) <= N_CALIBRATION_BINS


def test_pairwise_kappa_corrects_for_chance():
    agg = QualityAggregator()
    # a and b always agree, varying their pick; c always disagrees
    for pick in (0, 1, 0, 1):
        votes = {0: [0.9, 0.1], 1: [0.1, 0.9]}
        agg.observe_outcome(
            outcome(
                [
                    ballot("a", votes[pick]),
                    ballot("b", votes[pick], index=1),
                    ballot("c", votes[1 - pick], index=2),
                ],
                winner=pick,
            )
        )
    kappa = agg.snapshot()["pairwise_kappa"]
    assert kappa["a|b"]["ballots"] == 4
    assert kappa["a|b"]["kappa"] == 1.0
    # both picked both candidates half the time but never together:
    # chance predicts 0.5 agreement, observed is 0 -> perfect discord
    assert kappa["a|c"]["kappa"] == -1.0
    assert kappa["b|c"]["kappa"] == -1.0


def test_pairwise_kappa_degenerate_unanimous_panel():
    agg = QualityAggregator()
    for _ in range(3):
        agg.observe_outcome(
            outcome(
                [ballot("a", [1.0, 0.0]), ballot("b", [1.0, 0.0], index=1)]
            )
        )
    # both always pick candidate 0: chance predicts total agreement,
    # the degenerate branch reports perfect (not 0/0) kappa
    assert agg.snapshot()["pairwise_kappa"]["a|b"]["kappa"] == 1.0


# -- drift --------------------------------------------------------------------


def drifted_agg(healthy, sour, window=4, threshold=0.3):
    agg = QualityAggregator(window=window, drift_threshold=threshold)
    for _ in range(healthy):
        agg.observe_outcome(outcome([ballot("j", [1.0, 0.0])]))
    for _ in range(sour):
        agg.observe_outcome(outcome([ballot("j", [0.0, 1.0])]))
    return agg


def test_drift_needs_full_window_and_full_baseline():
    # a cold judge is never flagged, however bad the start looks
    agg = QualityAggregator(window=4, drift_threshold=0.3)
    for _ in range(6):
        agg.observe_outcome(outcome([ballot("j", [0.0, 1.0])]))
    drift = agg.scorecard("j")["drift"]
    assert drift["flagged"] is False
    assert drift["recent_agreement"] == 0.0
    # healthy history but the baseline is still inside the window
    assert drifted_agg(4, 3).scorecard("j")["drift"]["flagged"] is False


def test_drift_flags_agreement_collapse_against_baseline():
    agg = drifted_agg(8, 4)
    drift = agg.scorecard("j")["drift"]
    assert drift["flagged"] is True
    assert drift["recent_agreement"] == 0.0
    assert drift["baseline_agreement"] == 1.0
    assert drift["agreement_drop"] == 1.0
    assert agg.snapshot()["flagged"] == ["j"]


def test_drift_healthy_judge_stays_unflagged():
    agg = drifted_agg(12, 0)
    drift = agg.scorecard("j")["drift"]
    assert drift["flagged"] is False
    assert drift["agreement_drop"] == 0.0


def test_configure_rebounds_existing_windows():
    agg = drifted_agg(8, 4, window=4)
    agg.configure(window=2, drift_threshold=0.7)
    drift = agg.scorecard("j")["drift"]
    assert drift["window"] == 2 and drift["window_fill"] == 2
    # the shrunken window keeps the 2 newest (sour) ballots; baseline
    # is now 8 healthy + 2 sour = 0.8 agreement, a 0.8 drop
    assert drift["agreement_drop"] == 0.8
    assert drift["flagged"] is True


# -- outcome counters / snapshot / summary ------------------------------------


def test_outcome_counters_and_margin_histogram():
    agg = QualityAggregator()
    agg.observe_outcome(outcome([ballot("j", [1.0, 0.0])], margin=0.4))
    agg.observe_outcome(
        outcome([ballot("j", [1.0, 0.0])], margin=0.2, degraded=True)
    )
    agg.observe_outcome(
        outcome(
            [ballot("j", [1.0, 0.0])],
            margin=0.2,
            degraded=True,
            quorum_degraded=True,
        )
    )
    agg.observe_outcome(
        outcome(
            [ballot("j", None, error_code=500)],
            winner=None,
            margin=None,
            all_failed=True,
        )
    )
    snap = agg.snapshot()
    assert snap["requests"] == 4
    assert snap["outcomes"] == {
        "scored": 3,
        "degraded": 2,
        "quorum_degraded": 1,
        "all_failed": 1,
    }
    assert snap["degraded_rate"] == 0.5
    assert snap["all_failed_rate"] == 0.25
    # only real margins land in the histogram (the all-failed request
    # has no consensus to measure)
    assert snap["confidence_margin"]["count"] == 3
    assert snap["flagged"] == []


def test_prom_snapshot_is_cloned_and_flat():
    agg = QualityAggregator()
    agg.observe_outcome(outcome([ballot("j", [1.0, 0.0])], margin=0.4))
    psnap = agg.prom_snapshot()
    assert psnap["margin"].count == 1
    assert psnap["exemplar"][0] == "trace-1"
    assert psnap["agreement"] == {"j": 1.0}
    assert psnap["drift_flagged"] == {"j": 0.0}
    # the clone must not alias live state
    psnap["margin"].observe(0.1)
    assert agg.prom_snapshot()["margin"].count == 1


# -- JUDGE_BIAS_PLAN ----------------------------------------------------------


def test_bias_plan_parse_round_trip():
    plan = JudgeBiasPlan.parse("judge=2,after=16,flip=1.0,seed=7")
    assert plan.judge == 2 and plan.after == 16 and plan.seed == 7
    assert plan.probabilities["flip"] == 1.0
    scripted = JudgeBiasPlan.parse("judge=1,script=ok|flip|uniform")
    assert scripted._script == [None, "flip", "uniform"]


@pytest.mark.parametrize(
    "spec",
    [
        "bogus=1",
        "judge",
        "judge=2,script=flip|warp",
    ],
)
def test_bias_plan_parse_rejects_bad_specs(spec):
    with pytest.raises(ValueError, match="JUDGE_BIAS_PLAN"):
        JudgeBiasPlan.parse(spec)


def test_bias_plan_after_warmup_and_determinism():
    vote = [Decimal(1), Decimal(0)]

    def run():
        plan = JudgeBiasPlan(
            judge=1, seed=3, after=2, probabilities={"flip": 1.0}
        )
        out = []
        for _ in range(5):
            plan.perturb(0, list(vote))  # untargeted judge interleaved
            out.append(plan.perturb(1, list(vote)))
        return plan, out

    plan, first = run()
    _, second = run()
    # warm-up ballots pass through untouched, then the flip begins;
    # the sequence is identical across runs (seeded, ordinal-keyed)
    assert first[:2] == [vote, vote]
    assert all(v == [Decimal(0), Decimal(1)] for v in first[2:])
    assert first == second
    assert plan.injected["flip"] == 3
    assert plan.snapshot()["injected"] == {"flip": 3}
    # the untargeted judge was never perturbed
    assert plan.snapshot()["ballots"] == {0: 5, 1: 5}


def test_bias_kinds_permute_or_flatten():
    v = [Decimal("0.5"), Decimal("0.3"), Decimal("0.2")]
    flip = JudgeBiasPlan(judge=0, probabilities={"flip": 1.0})
    assert flip.perturb(0, list(v)) == [v[1], v[2], v[0]]
    invert = JudgeBiasPlan(judge=0, probabilities={"invert": 1.0})
    assert invert.perturb(0, list(v)) == list(reversed(v))
    uniform = JudgeBiasPlan(judge=0, probabilities={"uniform": 1.0})
    third = Decimal(1) / Decimal(3)
    assert uniform.perturb(0, list(v)) == [third, third, third]
    # a single-entry vote has nothing to perturb
    solo = JudgeBiasPlan(judge=0, probabilities={"flip": 1.0})
    assert solo.perturb(0, [Decimal(1)]) == [Decimal(1)]


# -- outcome ledger -----------------------------------------------------------


def test_ledger_ring_bound_and_newest_first():
    ledger = OutcomeLedger(capacity=3)
    for i in range(5):
        ledger.offer({"id": f"r{i}", "winner": i})
    snap = ledger.snapshot()
    assert snap["size"] == 3 and snap["kept"] == 5
    assert snap["schema"] == LEDGER_SCHEMA
    assert [r["id"] for r in ledger.index()] == ["r4", "r3", "r2"]
    assert [r["id"] for r in ledger.index(limit=1)] == ["r4"]
    assert ledger.get("r0") is None  # evicted
    record = ledger.get("r4")
    assert record["winner"] == 4
    assert record["schema"] == LEDGER_SCHEMA  # stamped on offer


def test_ledger_disk_jsonl(tmp_path):
    ledger = OutcomeLedger(capacity=2, disk_dir=str(tmp_path))
    for i in range(4):
        ledger.offer({"id": f"r{i}"})
    # the ring is bounded but the JSONL tier keeps everything
    lines = [
        json.loads(ln)
        for ln in open(ledger.snapshot()["disk_path"], encoding="utf-8")
    ]
    assert [r["id"] for r in lines] == ["r0", "r1", "r2", "r3"]
    assert all(r["schema"] == LEDGER_SCHEMA for r in lines)
    assert ledger.snapshot()["disk_errors"] == 0


def test_ledger_disk_error_never_raises(tmp_path):
    ledger = OutcomeLedger(capacity=2, disk_dir=str(tmp_path))
    # a directory in place of the file: every append fails with OSError,
    # which must be swallowed and counted, never raised into the tally
    ledger._disk_path = str(tmp_path)
    ledger.offer({"id": "r0"})
    assert ledger.snapshot()["disk_errors"] == 1
    assert ledger.get("r0") is not None


# -- registries ---------------------------------------------------------------


def test_quality_sections_and_families_registered():
    assert "quality" in KNOWN_SECTIONS and "ledger" in KNOWN_SECTIONS
    for family in (
        "lwc_confidence_margin",
        "lwc_consensus_outcomes",
        "lwc_judge_agreement",
        "lwc_judge_drift",
    ):
        assert family in KNOWN_PROM_FAMILIES, family
    metrics = Metrics()
    register_quality(metrics, OutcomeLedger(capacity=2))
    snap = metrics.snapshot()
    assert snap["quality"]["requests"] == 0
    assert snap["ledger"]["capacity"] == 2


# -- config knobs -------------------------------------------------------------


def test_config_quality_knobs_and_validation():
    c = Config.from_env({})
    assert c.quality_window == 64 and c.quality_drift_threshold == 0.25
    assert c.outcome_ledger() is None
    assert c.judge_bias_injection_plan() is None
    c = Config.from_env(
        {"QUALITY_WINDOW": "8", "QUALITY_DRIFT_THRESHOLD": "0.5"}
    )
    assert c.quality_window == 8 and c.quality_drift_threshold == 0.5
    with pytest.raises(ValueError, match="QUALITY_WINDOW"):
        Config.from_env({"QUALITY_WINDOW": "0"})
    for bad in ("0", "1.5", "-0.1"):
        with pytest.raises(ValueError, match="QUALITY_DRIFT_THRESHOLD"):
            Config.from_env({"QUALITY_DRIFT_THRESHOLD": bad})


def test_config_ledger_and_bias_factories(tmp_path):
    ledger = Config.from_env({"LEDGER_RING": "4"}).outcome_ledger()
    assert ledger.capacity == 4 and ledger.snapshot()["disk_path"] is None
    # LEDGER_DIR alone arms the ledger at the default ring size
    ledger = Config.from_env(
        {"LEDGER_DIR": str(tmp_path)}
    ).outcome_ledger()
    assert ledger.capacity == 256
    assert ledger.snapshot()["disk_path"].startswith(str(tmp_path))
    plan = Config.from_env(
        {"JUDGE_BIAS_PLAN": "judge=1,flip=1.0"}
    ).judge_bias_injection_plan()
    assert isinstance(plan, JudgeBiasPlan) and plan.judge == 1


# -- the tally seam (ScoreClient integration) ---------------------------------


def make_model(judges):
    return ModelBase.from_json_obj({"llms": judges}).into_model_validate()


def inline_model_json(model):
    return {"llms": [llm.base.to_json_obj() for llm in model.llms]}


def ballot_keys(n):
    from llm_weighted_consensus_tpu.ballot import PrefixTree, branch_limit

    rng = random.Random(SEED)
    tree = PrefixTree.build(rng, n, branch_limit(None))
    return {idx: key for key, idx in tree.key_indices(rng)}


def judge_script(key, **kw):
    return Script(
        [chunk_obj(f"I pick {key} as best.", finish="stop")], **kw
    )


def score_params(choices, model, **kw):
    return ScoreParams.from_json_obj(
        {
            "messages": [{"role": "user", "content": "pick the best"}],
            "model": model,
            "choices": choices,
            **kw,
        }
    )


def make_score_client(scripts, **kw):
    transport = FakeTransport(scripts)
    chat = DefaultChatClient(transport, AB, backoff=NO_RETRY)
    client = ScoreClient(
        chat,
        registry.InMemoryModelRegistry(),
        archive_fetcher=archive.InMemoryArchive(),
        rng_factory=lambda: random.Random(SEED),
        **kw,
    )
    return client, transport


async def collect(client, params):
    stream = await client.create_streaming(None, params)
    return [item async for item in stream]


def test_tally_seam_populates_scorecards_and_ledger():
    keys = ballot_keys(2)
    model = make_model([{"model": "judge-a"}, {"model": "judge-b"}])
    # scorecards are keyed by the deterministic judge id (llm.id), and
    # judges dispatch in sorted-by-id order — the transport pops
    # scripts in that order
    first, second = (llm.id for llm in model.llms)
    ledger = OutcomeLedger(capacity=8)
    client, _ = make_score_client(
        [judge_script(keys[0]), judge_script(keys[1])], ledger=ledger
    )
    go(collect(client, score_params(TEXTS, inline_model_json(model))))

    snap = obs.quality_snapshot()
    assert snap["requests"] == 1
    assert snap["outcomes"]["scored"] == 1
    assert set(snap["judges"]) == {first, second}
    # split panel: margin (top1 - top2)/weight_sum is exactly 0
    assert snap["confidence_margin"]["count"] == 1
    assert f"{min(first, second)}|{max(first, second)}" in (
        snap["pairwise_kappa"]
    )

    [record] = ledger.index()
    assert record["schema"] == LEDGER_SCHEMA
    assert record["n_choices"] == 2
    assert record["margin"] == 0.0
    assert record["weight_sum"] == 2.0
    assert len(record["confidence"]) == 2
    rows = {row["model"]: row for row in record["judges"]}
    assert rows[first]["vote"] == [1.0, 0.0]
    assert rows[second]["vote"] == [0.0, 1.0]
    # alignment is the judge's share-weighted confidence: with equal
    # weights each judge's one-hot vote aligns 0.5 with the consensus
    assert rows[first]["alignment"] == 0.5
    assert rows[second]["error"] is None


def test_all_failed_forces_trace_retention():
    # merged-4xx all-failed: the unary surface is a 4xx, below the >=500
    # middleware forcing threshold — the tally must force retention
    model = make_model([{"model": "judge-a"}, {"model": "judge-b"}])
    client, _ = make_score_client(
        [Script(status=418, body=b"{}"), Script(status=418, body=b"{}")]
    )

    async def run():
        root = obs.start_trace("test:root", sampled=False)
        token = root.activate()
        try:
            items = await collect(
                client, score_params(TEXTS, inline_model_json(model))
            )
        finally:
            obs.Span.deactivate(token)
            root.finish()
        return root.trace, items

    trace, items = go(run())
    assert isinstance(items[-1], AllVotesFailed)
    assert not trace.sampled
    assert trace.forced and trace.force_reason == "all_failed"
    snap = obs.quality_snapshot()
    assert snap["outcomes"]["all_failed"] == 1
    cards = snap["judges"]
    assert cards[model.llms[0].id]["error_rate"] == 1.0


def test_ledger_rows_train_without_transformation(tmp_path):
    # the round trip ROADMAP items 4-5 rely on: ledger vote vectors are
    # the embeddings, alignment scores the labels — no transformation
    keys = ballot_keys(2)
    model = make_model([{"model": "judge-a"}, {"model": "judge-b"}])
    ledger = OutcomeLedger(capacity=8)
    client, _ = make_score_client(
        [judge_script(keys[0]), judge_script(keys[0])] * 3, ledger=ledger
    )
    params = score_params(TEXTS, inline_model_json(model))
    for _ in range(3):
        go(collect(client, params))

    store = TrainingTableStore()
    for record in ledger.index():
        for row in record["judges"]:
            if row["vote"] is None:
                continue
            store.add_rows(
                row["model"],
                np.asarray([row["vote"]]),
                np.asarray([row["alignment"]]),
            )
    path = str(tmp_path / "tables.npz")
    store.save(path)
    loaded = TrainingTableStore.load(path)
    embeddings, scores = loaded.get(model.llms[0].id)
    assert embeddings.shape == (3, 2) and embeddings.dtype == np.float32
    # unanimous one-hot panel: full alignment with the consensus
    assert np.array_equal(embeddings, np.asarray([[1, 0]] * 3, np.float32))
    assert np.array_equal(scores, np.ones(3, np.float32))


# -- the seeded end-to-end drill ----------------------------------------------


def post_json(client, path, obj):
    return client.post(
        path,
        data=jsonutil.dumps(obj),
        headers={"content-type": "application/json"},
    )


def test_bias_drill_flags_judge_over_gateway():
    """ISSUE 12 acceptance: a JUDGE_BIAS_PLAN-miscalibrated judge is
    flagged by the drift detector within a bounded request count, the
    scorecard is visible on /v1/judges and in the quality section of
    both /metrics forms, with zero request errors."""
    n_requests = 16
    obs.configure_quality(window=4, drift_threshold=0.3)
    keys = ballot_keys(2)
    model = make_model(
        [{"model": "judge-a"}, {"model": "judge-b"}, {"model": "judge-c"}]
    )
    model_json = inline_model_json(model)
    # the target of the bias plan is a judge *index* (deterministic:
    # position in the sorted-by-id panel), exactly how the env spec
    # would name it; the drift detector sees only the opaque judge id
    biased = next(l for l in model.llms if l.base.model == "judge-c")
    honest_ids = sorted(l.id for l in model.llms if l is not biased)
    # every judge honestly picks candidate 0, every request; the plan
    # flips judge-c after 8 healthy warm-up ballots
    scripts = [judge_script(keys[0]) for _ in range(3 * n_requests)]
    transport = FakeTransport(scripts)
    chat = DefaultChatClient(transport, AB, backoff=NO_RETRY)
    ledger = OutcomeLedger(capacity=64)
    score = ScoreClient(
        chat,
        registry.InMemoryModelRegistry(),
        archive_fetcher=archive.InMemoryArchive(),
        rng_factory=lambda: random.Random(SEED),
        bias_plan=JudgeBiasPlan.parse(
            f"judge={biased.index},after=8,flip=1.0,seed=7"
        ),
        ledger=ledger,
    )
    multichat = MultichatClient(
        chat,
        registry.InMemoryModelRegistry(),
        archive_fetcher=archive.InMemoryArchive(),
    )
    app = build_app(chat, score, multichat, ledger=ledger)

    async def run(client):
        for _ in range(n_requests):
            resp = await post_json(
                client,
                "/score/completions",
                {
                    "messages": [{"role": "user", "content": "q"}],
                    "model": model_json,
                    "choices": TEXTS,
                },
            )
            assert resp.status == 200  # zero request errors
            body = await resp.json()
            assert "error" not in body

        resp = await client.get("/v1/judges")
        assert resp.status == 200
        listing = await resp.json()
        assert listing["window"] == 4
        cards = {c["model"]: c for c in listing["judges"]}
        assert set(cards) == {biased.id, *honest_ids}
        assert cards[biased.id]["drift"]["flagged"] is True
        for honest in honest_ids:
            assert cards[honest]["drift"]["flagged"] is False
            # the panel's honest members agree with every consensus
            assert cards[honest]["agreement_rate"] == 1.0
        # the biased judge's windowed agreement collapsed
        assert cards[biased.id]["drift"]["recent_agreement"] == 0.0

        resp = await client.get(f"/v1/judges/{biased.id}")
        assert resp.status == 200
        card = await resp.json()
        assert card["drift"]["flagged"] is True
        assert (await client.get("/v1/judges/nope")).status == 404

        resp = await client.get("/metrics")
        snap = await resp.json()
        assert snap["quality"]["requests"] == n_requests
        assert snap["quality"]["flagged"] == [biased.id]
        assert snap["quality"]["outcomes"]["scored"] == n_requests
        assert snap["ledger"]["kept"] == n_requests

        resp = await client.get("/metrics?format=prometheus")
        text = await resp.text()
        assert f'lwc_judge_drift{{judge="{biased.id}"}} 1' in text
        assert f'lwc_judge_drift{{judge="{honest_ids[0]}"}} 0' in text
        assert (
            f'lwc_consensus_outcomes_total{{outcome="scored"}} {n_requests}'
            in text
        )
        assert f'lwc_judge_agreement{{judge="{honest_ids[1]}"}} 1' in text
        assert "lwc_confidence_margin_bucket" in text

    async def with_client():
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            await run(client)
        finally:
            await client.close()

    go(with_client())
    # the ledger recorded the whole drill for later training
    assert ledger.snapshot()["kept"] == n_requests
    flipped = [
        row
        for record in ledger.index(limit=n_requests)
        for row in record["judges"]
        if row["model"] == biased.id and row["vote"] == [0.0, 1.0]
    ]
    assert len(flipped) == n_requests - 8
