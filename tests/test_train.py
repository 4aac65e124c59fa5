"""Offline lane + weight learner (ISSUE 20): priority-class scheduling
in the device batcher, ledger shard rotation and the shard-streaming
feed, the batched JAX judge-weight learner (miscalibrated-panel drill:
fitted weights beat the observed base weights on held-out records), the
versioned live weight table behind atomic hot-swap (`PUT /v1/weights`
mid-traffic with zero client errors, versions stamped on ledger
records), the offline rescore endpoint, and the
`weights/learning.py::populate_from_archive` scoring contracts."""

import asyncio
import json
import random
import time

import numpy as np
import pytest

pytest.importorskip("jax")

from aiohttp.test_utils import TestClient, TestServer

from llm_weighted_consensus_tpu import archive, obs, registry
from llm_weighted_consensus_tpu.clients.chat import (
    ApiBase,
    BackoffPolicy,
    DefaultChatClient,
)
from llm_weighted_consensus_tpu.clients.multichat import MultichatClient
from llm_weighted_consensus_tpu.clients.score import ScoreClient
from llm_weighted_consensus_tpu.identity.model import ModelBase
from llm_weighted_consensus_tpu.models.configs import TEST_TINY
from llm_weighted_consensus_tpu.models.embedder import TpuEmbedder
from llm_weighted_consensus_tpu.obs import JudgeBallot, OutcomeLedger
from llm_weighted_consensus_tpu.obs.ledger import (
    ledger_shard_paths,
    load_ledger_records,
    read_shard_records,
)
from llm_weighted_consensus_tpu.resilience import JudgeBiasPlan
from llm_weighted_consensus_tpu.serve import Config, build_app
from llm_weighted_consensus_tpu.serve.batcher import DeviceBatcher
from llm_weighted_consensus_tpu.serve.metrics import (
    KNOWN_PROM_FAMILIES,
    KNOWN_SECTIONS,
    Metrics,
    register_quality,
    render_prometheus,
)
from llm_weighted_consensus_tpu.train.feed import (
    LedgerFeed,
    OfflineFeed,
    archive_groups,
    candidate_texts,
    synthetic_groups,
)
from llm_weighted_consensus_tpu.train.fit import (
    build_dataset,
    fit_from_ledger,
    fit_from_records,
    fit_weights,
    holdout_split,
    tally_accuracy,
)
from llm_weighted_consensus_tpu.utils import jsonutil
from llm_weighted_consensus_tpu.weights.live import (
    BASE_VERSION,
    LiveWeightStore,
    weights_version,
)

from fakes import FakeTransport, Script, chunk_obj

SEED = 42
NO_RETRY = BackoffPolicy(max_elapsed_ms=0)
AB = [ApiBase("https://a.example", "key-a")]
TEXTS = ["answer alpha", "answer beta"]


def go(coro):
    return asyncio.new_event_loop().run_until_complete(coro)


@pytest.fixture(scope="module")
def embedder():
    return TpuEmbedder("test-tiny", config=TEST_TINY, max_tokens=32)


@pytest.fixture(autouse=True)
def _fresh_quality():
    obs.reset_quality()
    yield
    obs.reset_quality()


# -- panel helpers (the test_quality.py idioms) -------------------------------


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
    return Script([chunk_obj(f"I pick {key} as best.", finish="stop")], **kw)


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
    return client, chat


async def collect(client, params):
    stream = await client.create_streaming(None, params)
    return [item async for item in stream]


def score_params(choices, model, **kw):
    from llm_weighted_consensus_tpu.types.score_request import (
        ChatCompletionCreateParams as ScoreParams,
    )

    return ScoreParams.from_json_obj(
        {
            "messages": [{"role": "user", "content": "pick the best"}],
            "model": model,
            "choices": choices,
            **kw,
        }
    )


def post_json(client, path, obj):
    return client.post(
        path,
        data=jsonutil.dumps(obj),
        headers={"content-type": "application/json"},
    )


# -- ledger shard rotation (satellite: LEDGER_ROTATE_BYTES) -------------------


def test_ledger_rotation_seals_shards(tmp_path):
    ledger = OutcomeLedger(
        capacity=4, disk_dir=str(tmp_path), rotate_bytes=200
    )
    for i in range(10):
        ledger.offer({"id": f"r{i}", "payload": "x" * 64})
    snap = ledger.snapshot()
    assert snap["rotate_bytes"] == 200
    assert snap["rotations"] >= 2
    paths = ledger_shard_paths(str(tmp_path))
    # sealed generations (+ the active file, unless the final offer
    # itself rotated), all on the one read glob
    assert snap["rotations"] <= len(paths) <= snap["rotations"] + 1
    assert all(p.endswith(".jsonl") for p in paths)
    # no shard grew past the threshold by more than one record
    import os

    for p in paths:
        assert os.path.getsize(p) < 200 + 120
    # the multi-shard read returns every record, in offer order
    records, torn = load_ledger_records(str(tmp_path))
    assert torn == 0
    assert [r["id"] for r in records] == [f"r{i}" for i in range(10)]


def test_ledger_rotation_zero_keeps_single_shard(tmp_path):
    ledger = OutcomeLedger(capacity=4, disk_dir=str(tmp_path))
    for i in range(50):
        ledger.offer({"id": f"r{i}", "payload": "x" * 64})
    assert ledger.snapshot()["rotations"] == 0
    assert len(ledger_shard_paths(str(tmp_path))) == 1


def test_ledger_rotation_torn_tail_per_shard(tmp_path):
    ledger = OutcomeLedger(
        capacity=4, disk_dir=str(tmp_path), rotate_bytes=150
    )
    for i in range(6):
        ledger.offer({"id": f"r{i}", "payload": "y" * 48})
    paths = ledger_shard_paths(str(tmp_path))
    assert len(paths) >= 3
    # a crash mid-append tears the tail of one sealed shard AND the
    # active file: both skip-and-count, neither is fatal
    with open(paths[0], "a", encoding="utf-8") as f:
        f.write('{"id": "torn-a"')
    with open(paths[-1], "a", encoding="utf-8") as f:
        f.write('{"id": "torn-b", "partial": tru')
    records, torn = load_ledger_records(str(tmp_path))
    assert torn == 2
    assert [r["id"] for r in records] == [f"r{i}" for i in range(6)]
    # per-shard reader agrees with the composed loader
    shard_records, shard_torn = read_shard_records(paths[0])
    assert shard_torn == 1 and all(
        r["id"].startswith("r") for r in shard_records
    )


def test_ledger_feed_streams_every_shard(tmp_path):
    ledger = OutcomeLedger(
        capacity=2, disk_dir=str(tmp_path), rotate_bytes=150
    )
    for i in range(8):
        ledger.offer({"id": f"r{i}", "payload": "z" * 48})
    feed = LedgerFeed(str(tmp_path))
    ids = [r["id"] for r in feed.records()]
    assert ids == [f"r{i}" for i in range(8)]
    assert feed.shards_read == len(ledger_shard_paths(str(tmp_path)))
    assert feed.torn == 0


def test_config_threads_rotate_bytes(tmp_path):
    ledger = Config.from_env(
        {"LEDGER_DIR": str(tmp_path), "LEDGER_ROTATE_BYTES": "4096"}
    ).outcome_ledger()
    assert ledger.rotate_bytes == 4096
    assert Config.from_env({"LEDGER_RING": "4"}).outcome_ledger(
    ).rotate_bytes == 0
    with pytest.raises(ValueError, match="LEDGER_ROTATE_BYTES"):
        Config.from_env({"LEDGER_ROTATE_BYTES": "-1"})


# -- the feed -----------------------------------------------------------------


def test_synthetic_groups_deterministic():
    a = synthetic_groups(3, 4, seed=7)
    b = synthetic_groups(3, 4, seed=7)
    c = synthetic_groups(3, 4, seed=8)
    assert a == b and a != c
    assert len(a) == 3 and all(len(g) == 4 for g in a)
    # every candidate is distinct — a degenerate all-equal group would
    # make the consensus vote meaningless
    assert len({t for g in a for t in g}) == 12


class _FakeCompletion:
    def __init__(self, choices):
        self.choices = choices


class _Choice:
    def __init__(self, index, content=None, model_index=None, vote=None):
        from types import SimpleNamespace

        self.index = index
        self.model_index = model_index
        self.model = f"judge-{model_index}" if model_index is not None else None
        self.confidence = None
        self.message = SimpleNamespace(content=content, vote=vote)


def test_candidate_texts_skips_judges_and_empties():
    completion = _FakeCompletion(
        [
            _Choice(1, content="beta"),
            _Choice(0, content="alpha"),
            _Choice(2, content="judge says", model_index=0, vote=[1, 0]),
            _Choice(3, content=""),
            _Choice(4, content=None),
        ]
    )
    assert candidate_texts(completion) == ["alpha", "beta"]


def test_archive_groups_skips_unvotable():
    class _Store:
        def __init__(self, completions):
            self._c = completions

        def score_ids(self):
            return list(self._c)

        def score_completion(self, cid):
            return self._c[cid]

    store = _Store(
        {
            "ok": _FakeCompletion(
                [_Choice(0, content="a"), _Choice(1, content="b")]
            ),
            "solo": _FakeCompletion([_Choice(0, content="only")]),
            "gone": None,
        }
    )
    assert list(archive_groups(store)) == [["a", "b"]]


# -- priority classes in the batcher ------------------------------------------


def test_latency_plans_before_queued_offline(embedder):
    """Both lanes queued in the same window: every latency item
    dispatches before any offline item (the planner drains the latency
    queue first; pipeline_depth=1 serializes dispatch order)."""
    metrics = Metrics()
    batcher = DeviceBatcher(
        embedder, metrics, window_ms=60.0, pipeline_depth=1
    )
    texts = [f"candidate {i}" for i in range(4)]
    done = {}

    async def one(lane, i):
        await batcher.consensus(texts, priority=lane)
        done[(lane, i)] = time.perf_counter()

    async def run():
        offline = [
            asyncio.ensure_future(one("offline", i)) for i in range(3)
        ]
        # let the offline items enqueue first — they still must not
        # dispatch ahead of the latency lane
        await asyncio.sleep(0.01)
        latency = [
            asyncio.ensure_future(one("latency", i)) for i in range(3)
        ]
        await asyncio.gather(*offline, *latency)

    go(run())
    last_latency = max(t for (lane, _), t in done.items() if lane == "latency")
    first_offline = min(t for (lane, _), t in done.items() if lane == "offline")
    assert last_latency <= first_offline
    lanes = batcher.utilization()["lanes"]
    assert lanes["latency"]["items"] == 3
    assert lanes["offline"]["items"] == 3
    assert lanes["latency"]["dispatches"] >= 1
    assert lanes["offline"]["dispatches"] >= 1


def test_offline_exempt_from_queue_depth_shed(embedder):
    """max_queue_depth sheds latency work, never the offline feeder —
    it self-limits by awaiting its own futures."""
    from llm_weighted_consensus_tpu.errors import OverloadedError

    batcher = DeviceBatcher(
        embedder, None, window_ms=30.0, max_queue_depth=2
    )
    texts = [f"candidate {i}" for i in range(3)]

    async def run():
        offline = [
            asyncio.ensure_future(
                batcher.consensus(texts, priority="offline")
            )
            for _ in range(6)
        ]
        results = await asyncio.gather(*offline, return_exceptions=True)
        assert not any(isinstance(r, OverloadedError) for r in results)

    go(run())
    assert batcher.utilization()["lanes"]["offline"]["items"] == 6


def test_lane_occupancy_merges_pipelined_intervals(embedder):
    from llm_weighted_consensus_tpu.obs.account import DeviceAccount

    batcher = DeviceBatcher(embedder, None)
    # the one account the seam feeds, here with stamps made by hand
    account = batcher._account = DeviceAccount(clock=lambda: 0.0)
    # two overlapping dispatch intervals + one still in flight: honest
    # coverage merges them instead of summing past 100%
    first = account.enqueue(0.0, lane="offline")
    second = account.enqueue(5.0, lane="offline")
    account.ready(first, 10.0)
    account.ready(second, 15.0)
    assert batcher.lane_occupancy("offline", 0.0, until=20.0) == 0.75
    inflight = account.enqueue(15.0, lane="offline")
    assert batcher.lane_occupancy("offline", 0.0, until=20.0) == 1.0
    assert batcher.lane_occupancy("latency", 0.0, until=20.0) == 0.0
    account.ready(inflight, 15.0)
    assert batcher.lane_occupancy("offline", 16.0, until=16.0) == 0.0
    # the lanes together are the account's own union, never over 1
    assert batcher.lane_occupancy(None, 0.0, until=20.0) == 0.75


def test_offline_feed_sustains_occupancy_on_idle_mesh(embedder):
    """The acceptance gauge: with no latency traffic, the bounded-
    inflight feed keeps the device covered by offline work."""
    metrics = Metrics()
    batcher = DeviceBatcher(embedder, metrics, window_ms=1.0)
    groups = synthetic_groups(12, 4, seed=3)

    async def run():
        # warm the (N=4) consensus compilation OUTSIDE the measured
        # drive — occupancy measures serving, not jit
        await batcher.consensus(groups[0], priority="offline")
        feed = OfflineFeed(batcher, inflight=4)
        results, occupancy = await feed.drive(groups)
        return feed, results, occupancy

    feed, results, occupancy = go(run())
    assert feed.groups == 12 and feed.errors == 0
    assert all(r is not None for r in results)
    assert occupancy >= 0.5
    # per-lane counters rode the device_batcher section into /metrics
    snap = metrics.snapshot()["device_batcher"]["lanes"]
    assert snap["offline"]["items"] == 12 + 1  # drive + the warm call
    assert snap["latency"]["items"] == 0
    text = render_prometheus(metrics)
    assert 'lwc_lane_dispatches_total{lane="offline"}' in text
    assert 'lwc_lane_items_total{lane="latency"} 0' in text
    assert 'lwc_lane_busy_fraction{lane="offline"}' in text


# -- the live weight table ----------------------------------------------------


def test_weights_version_is_content_addressed():
    v1 = weights_version({"b": 2, "a": 1})
    assert v1 == weights_version({"a": 1, "b": 2})
    assert v1.startswith("wv-") and len(v1) == 15
    assert v1 != weights_version({"a": 1, "b": 3})


def test_live_store_apply_and_base_version():
    model = make_model([{"model": "judge-a"}, {"model": "judge-b"}])
    store = LiveWeightStore()
    from decimal import Decimal

    fetched = [Decimal(1), Decimal(1)]
    out, version = store.apply(model, fetched)
    assert out is fetched and version == BASE_VERSION
    target = model.llms[0]
    version = store.put({target.id: 5})
    out, applied_version = store.apply(model, fetched)
    assert applied_version == version == store.version
    assert out[target.index] == Decimal(5)
    # judges absent from the table keep their fetched weight
    other = model.llms[1]
    assert out[other.index] == Decimal(1)
    store.clear(mode="active")
    assert store.apply(model, fetched)[1] == BASE_VERSION


def test_live_store_validation_and_persistence(tmp_path):
    path = str(tmp_path / "weights.json")
    store = LiveWeightStore(path=path)
    for bad in ({"j": -1}, {"j": "nan"}, {"j": "zebra"}, {}):
        with pytest.raises(ValueError):
            store.put(bad)
    with pytest.raises(ValueError, match="mode"):
        store.put({"j": 1}, mode="canary")
    active = store.put({"j": "1.5", "k": 2})
    shadow = store.put({"j": 1}, mode="shadow")
    assert store.snapshot()["swaps"] == 2
    # a fresh process loads both tables from WEIGHTS_PATH
    reloaded = LiveWeightStore(path=path)
    assert reloaded.version == active
    assert reloaded.wire()["shadow"]["version"] == shadow
    assert reloaded.wire()["weights"] == {"j": "1.5", "k": "2"}


def test_shadow_counters_track_flips():
    from decimal import Decimal

    store = LiveWeightStore()
    ballots = [
        JudgeBallot(
            model="a",
            model_index=0,
            weight=Decimal(1),
            vote=[1.0, 0.0],
            error_code=None,
        ),
        JudgeBallot(
            model="c",
            model_index=1,
            weight=Decimal(3),
            vote=[0.0, 1.0],
            error_code=None,
        ),
    ]
    # no shadow table staged: comparison is a no-op
    store.observe_shadow(ballots, 2)
    assert store.shadow_compared == 0
    # shadow downweights c: the verdict would flip from 1 to 0
    store.put({"c": "0.5"}, mode="shadow")
    store.observe_shadow(ballots, 2)
    assert store.shadow_compared == 1
    assert store.shadow_would_flip == 1
    assert store.snapshot()["shadow_confidence_delta_sum"] > 0
    # a shadow table matching the active weights never flips
    store.put({"c": 3}, mode="shadow")
    store.observe_shadow(ballots, 2)
    assert store.shadow_compared == 2
    assert store.shadow_would_flip == 1


def test_weights_section_and_families_registered():
    assert "weights" in KNOWN_SECTIONS
    for family in (
        "lwc_lane_dispatches",
        "lwc_lane_items",
        "lwc_lane_busy_fraction",
        "lwc_weights_swaps",
        "lwc_weights_shadow",
    ):
        assert family in KNOWN_PROM_FAMILIES, family
    metrics = Metrics()
    store = LiveWeightStore()
    store.put({"j": 1})
    register_quality(metrics, live_weights=store)
    assert metrics.snapshot()["weights"]["swaps"] == 1
    text = render_prometheus(metrics)
    assert "lwc_weights_swaps_total 1" in text
    assert 'lwc_weights_shadow_total{kind="compared"} 0' in text


def test_config_live_weights_factory(tmp_path):
    assert Config.from_env({}).live_weights() is None
    assert Config.from_env({"WEIGHTS_ENABLED": "1"}).live_weights() is not None
    path = str(tmp_path / "w.json")
    store = Config.from_env({"WEIGHTS_PATH": path}).live_weights()
    assert store is not None and store.path == path
    with pytest.raises(ValueError, match="OFFLINE_INFLIGHT"):
        Config.from_env({"OFFLINE_ENABLED": "1", "OFFLINE_INFLIGHT": "0"})


# -- the learner --------------------------------------------------------------


def _synthetic_records(n=24, flip_after=8):
    """A miscalibrated panel: judge-c carries weight 3 but votes for the
    wrong candidate after ``flip_after``; a and b (weight 1) stay
    honest.  The recorded winner follows the (wrong) weighted tally."""
    records = []
    for i in range(n):
        flipped = i >= flip_after
        c_vote = [0.0, 1.0] if flipped else [1.0, 0.0]
        records.append(
            {
                "id": f"rec-{i}",
                "n_choices": 2,
                "all_failed": False,
                "winner": 1 if flipped else 0,
                "judges": [
                    {"model": "judge-a", "vote": [1.0, 0.0], "weight": 1.0},
                    {"model": "judge-b", "vote": [1.0, 0.0], "weight": 1.0},
                    {"model": "judge-c", "vote": c_vote, "weight": 3.0},
                ],
            }
        )
    return records


def test_build_dataset_skip_rules_and_label_priority():
    records = _synthetic_records(4, flip_after=99)
    records.append({"id": "failed", "n_choices": 2, "all_failed": True,
                    "winner": 0, "judges": records[0]["judges"]})
    records.append({"id": "solo", "n_choices": 1, "winner": 0,
                    "judges": records[0]["judges"]})
    records.append({"id": "mute", "n_choices": 2, "winner": 0, "judges": []})
    records.append({"id": "unlabeled", "n_choices": 2,
                    "judges": records[0]["judges"]})
    dataset = build_dataset(records)
    assert dataset.n_records == 4 and dataset.skipped == 4
    assert dataset.judge_ids == ["judge-a", "judge-b", "judge-c"]
    np.testing.assert_allclose(dataset.base_weights, [1.0, 1.0, 3.0])
    # explicit labels override the recorded winner; a record "label"
    # field outranks the winner too
    labeled = build_dataset(records[:4], labels={"rec-0": 1})
    assert labeled.labels[0] == 1 and labeled.labels[1] == 0
    records[1]["label"] = 1
    assert build_dataset(records[:4]).labels[1] == 1
    assert build_dataset([]) is None


def test_tally_accuracy_is_pure_numpy():
    dataset = build_dataset(_synthetic_records(8, flip_after=4),
                            labels={f"rec-{i}": 0 for i in range(8)})
    # base weights (c=3) lose every flipped record; uniform wins all:
    # a+b outvote c 2:1
    assert tally_accuracy(dataset, dataset.base_weights) == 0.5
    assert tally_accuracy(dataset, np.ones(3, np.float32)) == 1.0


def test_fit_downweights_the_miscalibrated_judge():
    labels = {f"rec-{i}": 0 for i in range(24)}
    report = fit_from_records(
        _synthetic_records(24, flip_after=8), labels=labels, steps=200
    )
    assert report["records"] == 24
    assert report["version"].startswith("wv-")
    # the learner drill's measurable improvement: fitted beats the
    # observed serving weights on the held-out split
    assert report["accuracy"]["fitted"] > report["accuracy"]["base"]
    assert report["accuracy"]["fitted"] == 1.0
    weights = report["weights"]
    assert weights["judge-c"] < weights["judge-a"]
    assert weights["judge-c"] < 0.5


def test_fit_weights_dp_shards_on_a_mesh():
    import jax
    from jax.sharding import Mesh

    dataset = build_dataset(
        _synthetic_records(10, flip_after=5),
        labels={f"rec-{i}": 0 for i in range(10)},
    )
    devices = np.array(jax.devices()[:4]).reshape(4)
    with Mesh(devices, ("dp",)) as mesh:
        # 10 records pad to 12 on dp=4 with zero-sample_weight rows;
        # the fit must match the unsharded result's verdicts
        fitted = fit_weights(dataset, steps=150, mesh=mesh)
    assert tally_accuracy(dataset, fitted) == 1.0
    assert fitted[2] < fitted[0]


def test_holdout_split_is_deterministic():
    dataset = build_dataset(_synthetic_records(12, flip_after=6))
    train, hold = holdout_split(dataset, every=4)
    assert hold.n_records == 3 and train.n_records == 9
    train2, hold2 = holdout_split(dataset, every=4)
    np.testing.assert_array_equal(hold.labels, hold2.labels)


# -- the learner drill: serve -> rotated ledger shards -> fit -----------------


def test_learner_drill_ledger_to_fit(tmp_path):
    """ISSUE 20 acceptance: records generated through the REAL tally
    seam under a seeded JUDGE_BIAS_PLAN (judge-c mis-votes with weight
    3), written through shard rotation, streamed back by the feed, and
    fit — held-out consensus accuracy improves over the observed base
    weights, via both the API and the CLI."""
    n_requests = 24
    keys = ballot_keys(2)
    model = make_model(
        [
            {"model": "judge-a", "weight": {"type": "static", "weight": 1}},
            {"model": "judge-b", "weight": {"type": "static", "weight": 1}},
            {"model": "judge-c", "weight": {"type": "static", "weight": 3}},
        ]
    )
    biased = next(l for l in model.llms if l.base.model == "judge-c")
    ledger = OutcomeLedger(
        capacity=64, disk_dir=str(tmp_path), rotate_bytes=2048
    )
    client, _ = make_score_client(
        [judge_script(keys[0]) for _ in range(3 * n_requests)],
        bias_plan=JudgeBiasPlan.parse(
            f"judge={biased.index},after=8,flip=1.0,seed=7"
        ),
        ledger=ledger,
    )
    params = score_params(TEXTS, inline_model_json(model))
    for _ in range(n_requests):
        go(collect(client, params))

    # rotation really sharded the drill's ledger
    assert ledger.snapshot()["rotations"] >= 2
    records, torn = load_ledger_records(str(tmp_path))
    assert len(records) == n_requests and torn == 0
    # candidate 0 was always correct; after the flip the 3-weight judge
    # drags the recorded verdict to candidate 1
    wrong = [r for r in records if r["winner"] == 1]
    assert len(wrong) == n_requests - 8
    labels = {r["id"]: 0 for r in records}

    report = fit_from_ledger(str(tmp_path), labels=labels, steps=200)
    assert report["shards"] == len(ledger_shard_paths(str(tmp_path)))
    assert report["records"] == n_requests
    assert report["accuracy"]["fitted"] > report["accuracy"]["base"]
    assert report["accuracy"]["fitted"] == 1.0
    fitted = report["weights"]
    assert fitted[biased.id] == min(fitted.values())

    # the CLI face: fit --out writes a table WEIGHTS_PATH can load
    from llm_weighted_consensus_tpu.train.__main__ import main

    labels_path = tmp_path / "labels.json"
    labels_path.write_text(json.dumps(labels))
    out_path = tmp_path / "weights.json"
    rc = main(
        [
            "fit",
            "--ledger-dir",
            str(tmp_path),
            "--labels",
            str(labels_path),
            "--steps",
            "200",
            "--out",
            str(out_path),
        ]
    )
    assert rc == 0
    loaded = LiveWeightStore(path=str(out_path))
    assert loaded.version == report["version"]


# -- the hot-swap drill over the gateway --------------------------------------


def test_weights_hot_swap_drill_over_gateway():
    """Version flips mid-traffic via PUT /v1/weights with zero client
    errors; every ledger record names the version that scored it, the
    swap changes the live verdict, and the staged shadow table feeds
    the would-have-flipped counters."""
    keys = ballot_keys(2)
    model = make_model(
        [{"model": "judge-a"}, {"model": "judge-b"}, {"model": "judge-c"}]
    )
    model_json = inline_model_json(model)
    dissenter = next(l for l in model.llms if l.base.model == "judge-c")
    # a and b pick candidate 0 every request; c dissents with candidate 1
    scripts = [
        judge_script(keys[1 if llm is dissenter else 0])
        for _ in range(12)
        for llm in model.llms
    ]
    ledger = OutcomeLedger(capacity=64)
    live = LiveWeightStore()
    transport = FakeTransport(scripts)
    chat = DefaultChatClient(transport, AB, backoff=NO_RETRY)
    score = ScoreClient(
        chat,
        registry.InMemoryModelRegistry(),
        archive_fetcher=archive.InMemoryArchive(),
        rng_factory=lambda: random.Random(SEED),
        ledger=ledger,
        live_weights=live,
    )
    multichat = MultichatClient(
        chat, registry.InMemoryModelRegistry(),
        archive_fetcher=archive.InMemoryArchive(),
    )
    app = build_app(chat, score, multichat, ledger=ledger, live_weights=live)
    body = {
        "messages": [{"role": "user", "content": "q"}],
        "model": model_json,
        "choices": TEXTS,
    }

    async def run(client):
        resp = await client.get("/v1/weights")
        assert (await resp.json())["version"] == BASE_VERSION
        for _ in range(4):
            resp = await post_json(client, "/score/completions", body)
            assert resp.status == 200
            assert "error" not in (await resp.json())
        # the hot swap: quintuple the dissenter mid-traffic
        resp = await client.put(
            "/v1/weights",
            data=jsonutil.dumps({"weights": {dissenter.id: 5}}),
            headers={"content-type": "application/json"},
        )
        assert resp.status == 200
        version = (await resp.json())["version"]
        assert version.startswith("wv-")
        for _ in range(4):
            resp = await post_json(client, "/score/completions", body)
            assert resp.status == 200  # zero client errors across the flip
            assert "error" not in (await resp.json())
        # stage a shadow table that would restore the old verdict
        resp = await client.put(
            "/v1/weights",
            data=jsonutil.dumps(
                {"weights": {dissenter.id: 1}, "mode": "shadow"}
            ),
            headers={"content-type": "application/json"},
        )
        assert resp.status == 200
        for _ in range(4):
            resp = await post_json(client, "/score/completions", body)
            assert resp.status == 200
        resp = await client.get("/v1/weights")
        wire = await resp.json()
        assert wire["version"] == version
        assert wire["shadow_compared"] == 4
        assert wire["shadow_would_flip"] == 4
        snap = await (await client.get("/metrics")).json()
        assert snap["weights"]["version"] == version
        assert snap["weights"]["swaps"] == 2
        text = await (
            await client.get("/metrics?format=prometheus")
        ).text()
        assert "lwc_weights_swaps_total 2" in text
        assert 'lwc_weights_shadow_total{kind="would_flip"} 4' in text
        # malformed swaps are 400s, and never disturb the active table
        for bad in (
            {"weights": {dissenter.id: -2}},
            {"weights": {dissenter.id: 1}, "mode": "canary"},
            {"not_weights": 1},
        ):
            resp = await client.put(
                "/v1/weights",
                data=jsonutil.dumps(bad),
                headers={"content-type": "application/json"},
            )
            assert resp.status == 400
        assert (await (await client.get("/v1/weights")).json())[
            "version"
        ] == version
        return version

    async def with_client():
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            return await run(client)
        finally:
            await client.close()

    version = go(with_client())
    records = ledger.index(limit=12)[::-1]
    assert [r["weights_version"] for r in records] == (
        [BASE_VERSION] * 4 + [version] * 8
    )
    # the swap flipped the served verdict: 2-vs-1 before, 2-vs-5 after
    assert [r["winner"] for r in records] == [0] * 4 + [1] * 8


def test_weights_endpoints_disabled_are_explicit_403():
    chat = DefaultChatClient(FakeTransport([]), AB, backoff=NO_RETRY)
    score, _ = make_score_client([])
    app = build_app(chat, score)

    async def run(client):
        assert (await client.get("/v1/weights")).status == 403
        assert (
            await client.put("/v1/weights", data=b"{}")
        ).status == 403
        assert (
            await client.post("/v1/train/rescore", data=b"{}")
        ).status == 403

    async def with_client():
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            await run(client)
        finally:
            await client.close()

    go(with_client())


def test_offline_rescore_endpoint_drives_the_lane(embedder):
    chat = DefaultChatClient(FakeTransport([]), AB, backoff=NO_RETRY)
    score, _ = make_score_client([])
    metrics = Metrics()
    app = build_app(
        chat,
        score,
        embedder=embedder,
        metrics=metrics,
        batch_window_ms=1.0,
        offline_enabled=True,
        offline_inflight=3,
    )

    async def run(client):
        resp = await post_json(
            client, "/v1/train/rescore", {"groups": 5, "n": 4, "seed": 1}
        )
        assert resp.status == 200
        stats = await resp.json()
        assert stats["groups"] == 5 and stats["errors"] == 0
        assert stats["offline_occupancy"] > 0
        assert stats["lanes"]["offline"]["items"] == 5
        # a malformed body is a 400, not a silent default drive
        resp = await post_json(client, "/v1/train/rescore", {"groups": "x"})
        assert resp.status == 400

    async def with_client():
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            await run(client)
        finally:
            await client.close()

    go(with_client())
    assert metrics.snapshot()["device_batcher"]["lanes"]["offline"][
        "dispatches"
    ] >= 1


# -- train package surface (satellite: resolve the stub) ----------------------


def test_train_package_exports():
    import llm_weighted_consensus_tpu.train as train

    assert "offline" in train.__doc__
    for name in ("contrastive_train_step", "reward_train_step",
                 "save_train_state", "load_train_state"):
        assert name in train.__all__ and hasattr(train, name)


# -- populate_from_archive scoring contracts (satellite) ----------------------


def _alignment_completion():
    """2 candidates (confidence .75/.25), 3 judges: one aligned, one
    dissenting, one errored (no stored ballot)."""
    from types import SimpleNamespace

    def cand(index, confidence):
        return SimpleNamespace(
            index=index, model_index=None, model=None,
            confidence=confidence, message=SimpleNamespace(vote=None),
        )

    def judge(index, model_index, vote):
        return SimpleNamespace(
            index=index, model_index=model_index, model=f"j{model_index}",
            confidence=None, message=SimpleNamespace(vote=vote),
        )

    return _FakeCompletion(
        [
            cand(0, 0.75),
            cand(1, 0.25),
            judge(2, 0, [1.0, 0.0]),
            judge(3, 1, [0.0, 1.0]),
            judge(4, 2, None),
        ]
    )


def test_judge_alignment_supervised_vs_self_consistency():
    from llm_weighted_consensus_tpu.weights.learning import (
        judge_alignment_scores,
    )

    completion = _alignment_completion()
    # self-consistency: vote · confidence
    scores = judge_alignment_scores(completion)
    assert scores[0] == pytest.approx(0.75)
    assert scores[1] == pytest.approx(0.25)
    # the ballot-less judge is OMITTED, never scored 0 — an errored leg
    # must not be trained as a dissenter
    assert 2 not in scores
    # supervised: vote mass on the known-correct candidate
    supervised = judge_alignment_scores(completion, label=1)
    assert supervised[0] == 0.0 and supervised[1] == 1.0
    assert 2 not in supervised
    # out-of-range labels (incl. the -1 sentinel) score 0, never index
    # from the end of the vote vector
    assert judge_alignment_scores(completion, label=-1)[0] == 0.0
    assert judge_alignment_scores(completion, label=9)[1] == 0.0
