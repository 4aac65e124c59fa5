"""Performance observability (ISSUE 11): log-bucket histograms, phase
attribution, the roofline gauge + JXA013 gate, Prometheus exposition,
and the /v1/profile capture guard.

Layers mirror the tentpole pieces:

* obs/histogram.py — bucket boundary invariants, exact merge, bounded
  quantile error vs the exact empirical quantile;
* obs/phases.py — aggregator snapshot semantics and the span-tree
  breakdown, including the acceptance bar that a served score request's
  named phases sum to within 10% of its end-to-end latency;
* serve/metrics.py — OpenMetrics text format (HELP/TYPE, histogram
  families, exemplar syntax), family-registry discipline, and the JSON
  snapshot staying shape-compatible;
* analysis/roofline.py — SoL math, mesh-suffix chip scaling, and the
  JXA013 injected regressions (missing/stale/drifted rows, bad peaks);
* gateway — /v1/profile one-shot capture, PROFILE_DIR guard, admission
  exemption.
"""

import asyncio
import json
import math
import random
import re
import time

import numpy as np
import pytest
from aiohttp.test_utils import TestClient, TestServer

from llm_weighted_consensus_tpu import archive, obs, registry
from llm_weighted_consensus_tpu.clients.chat import (
    ApiBase,
    BackoffPolicy,
    DefaultChatClient,
)
from llm_weighted_consensus_tpu.clients.score import ScoreClient
from llm_weighted_consensus_tpu.obs import TraceSink
from llm_weighted_consensus_tpu.obs.account import STARVED_KEYS, DeviceAccount
from llm_weighted_consensus_tpu.obs.histogram import (
    _BOUNDS,
    GROWTH,
    N_BUCKETS,
    Histogram,
    bucket_index,
    le_for,
)
from llm_weighted_consensus_tpu.obs.phases import (
    PHASES,
    PhaseAggregator,
    _union_ms,
)
from llm_weighted_consensus_tpu.serve import build_app
from llm_weighted_consensus_tpu.serve.metrics import (
    KNOWN_PROM_FAMILIES,
    KNOWN_SECTIONS,
    Metrics,
    register_performance,
    render_prometheus,
)
from llm_weighted_consensus_tpu.utils import jsonutil

from fakes import FakeTransport, Script, chunk_obj

SEED = 42
NO_RETRY = BackoffPolicy(max_elapsed_ms=0)
TEXTS = ["answer alpha", "answer beta", "answer gamma"]


def go(coro):
    return asyncio.new_event_loop().run_until_complete(coro)


# -- histogram ----------------------------------------------------------------


def test_bucket_boundaries_are_exclusive_above():
    """Bucket i holds (bound[i-1], bound[i]]: the bound itself lands in
    its bucket, the next float above lands in the next."""
    for i in (0, 1, 7, 40, N_BUCKETS - 2):
        bound = _BOUNDS[i]
        assert bucket_index(bound) == i, i
        assert bucket_index(math.nextafter(bound, math.inf)) == i + 1, i
    # everything at or below the base bound collapses into bucket 0
    assert bucket_index(_BOUNDS[0] / 2) == 0
    assert bucket_index(0.0) == 0
    assert bucket_index(-1.0) == 0
    # beyond the top finite bound -> overflow
    assert bucket_index(math.nextafter(_BOUNDS[-1], math.inf)) == N_BUCKETS
    assert le_for(_BOUNDS[-1] * 2) == "+Inf"


def test_observe_is_exact_on_count_and_sum():
    hist = Histogram()
    values = [0.01, 1.5, 1.5, 200.0, 1e9]
    for v in values:
        hist.observe(v)
    obj = hist.to_json_obj()
    assert obj["count"] == len(values)
    assert obj["sum_ms"] == pytest.approx(sum(values))
    cum = list(hist.cumulative())
    assert cum[-1] == ("+Inf", len(values))
    # cumulative counts are monotone
    counts = [c for _, c in cum]
    assert counts == sorted(counts)


def test_quantile_error_bounded_by_bucket_geometry():
    """Geometric-midpoint quantiles are off by at most sqrt(GROWTH)-1
    relative — the bound the ISSUE's bucket scheme is sized for."""
    rng = np.random.default_rng(SEED)
    samples = np.exp(rng.normal(loc=2.0, scale=1.2, size=20_000))
    hist = Histogram()
    for v in samples:
        hist.observe(float(v))
    bound = GROWTH**0.5 - 1
    for q in (0.5, 0.9, 0.99):
        exact = float(np.quantile(samples, q))
        approx = hist.quantile(q)
        assert abs(approx - exact) / exact <= bound + 1e-6, (q, exact, approx)


def test_merge_is_exact():
    rng = random.Random(SEED)
    a, b, both = Histogram(), Histogram(), Histogram()
    for _ in range(5_000):
        v = rng.lognormvariate(1.0, 2.0)
        (a if rng.random() < 0.5 else b).observe(v)
        both.observe(v)
    merged = Histogram().merge(a).merge(b)
    assert merged.counts == both.counts
    assert merged.count == both.count
    assert merged.sum == pytest.approx(both.sum)
    assert merged.quantile(0.99) == both.quantile(0.99)


# -- phase aggregator ---------------------------------------------------------


def test_aggregator_snapshot_orders_phases_and_feeds_the_device_table():
    agg = PhaseAggregator()
    agg.observe_phase("upstream_judge", 30.0)
    agg.observe_phase("http_respond", 1.0)
    agg.observe_phase("batcher_queue", 10.0)
    agg.observe_phase("tokenize", 5.0)
    agg.observe_device("vote1(n=8,s=16)", 60.0)  # also device_dispatch
    snap = agg.snapshot()
    assert list(snap) == [
        "tokenize", "batcher_queue", "device_dispatch", "upstream_judge",
        "http_respond",
    ]  # PHASES order, only observed phases: no gauge rides the section
    assert snap["device_dispatch"] == {
        "count": 1, "sum_ms": 60.0,
        "p50_ms": snap["device_dispatch"]["p50_ms"],
        "p99_ms": snap["device_dispatch"]["p99_ms"],
    }
    dev = agg.device_snapshot()
    assert dev["vote1(n=8,s=16)"]["count"] == 1


def test_aggregator_empty_snapshot_is_empty_and_so_is_a_fresh_account():
    assert PhaseAggregator().snapshot() == {}
    # whether the device was kept fed is the account's to say, in totals
    snap = DeviceAccount(clock=lambda: 0.0).snapshot()
    assert snap["wall_ms"] == snap["enqueued_ms"] == snap["dispatches"] == 0
    assert set(snap["starved_by"]) == set(STARVED_KEYS)


def test_interval_union_attributes_concurrent_work_once():
    assert _union_ms([(0.0, 10.0), (5.0, 15.0)]) == pytest.approx(15.0)
    assert _union_ms([(0.0, 5.0), (10.0, 12.0)]) == pytest.approx(7.0)
    assert _union_ms([]) == 0.0


# -- host<->device overlap (ISSUE 13), read from the account (ISSUE 37) -------


def test_account_windows_what_the_overlap_gauge_could_not():
    """The old gauge's own schedule: two pipelined programs tile the wall,
    then a host-side gap opens.  The account says the same in totals that
    two readings window."""
    acct = DeviceAccount(clock=lambda: 0.0)  # the stamps below are by hand
    assert acct.snapshot()["enqueued_ms"] == 0
    a = acct.enqueue(0.0)
    b = acct.enqueue(0.5)  # pipelined: enqueued before the first is ready
    acct.ready(a, 1.0)
    acct.ready(b, 1.5)
    first = acct.snapshot()
    # the union, not the sum: 1.5 s of programs in 1.5 s of wall
    assert first["enqueued_ms"] == pytest.approx(1500.0)
    assert first["wall_ms"] == pytest.approx(1500.0)
    c = acct.enqueue(2.5)  # a host-side gap opened
    acct.ready(c, 3.0)
    second = acct.snapshot()
    assert second["enqueued_ms"] - first["enqueued_ms"] == pytest.approx(500.0)
    assert second["wall_ms"] - first["wall_ms"] == pytest.approx(1500.0)
    assert second["idle_ms"] == pytest.approx(1000.0)  # no request waited
    acct.reset()
    assert acct.snapshot()["dispatches"] == 0


def test_staging_pool_reuses_buffers_per_shape():
    from llm_weighted_consensus_tpu.models.dispatch_seam import StagingPool

    pool = StagingPool(per_bucket=1)
    a = pool.acquire((4, 8), np.int32)
    pool.release(a)
    b = pool.acquire((4, 8), np.int32)
    assert b is a and pool.hits == 1
    c = pool.acquire((4, 8), np.int32)  # free list empty -> fresh
    assert c is not a and pool.misses == 2
    pool.release(b)
    pool.release(c)  # capacity 1 per bucket: the second drop is let go
    assert pool.stats()["buckets"] == 1
    d = pool.acquire((2, 8), np.int32)  # different shape, own bucket
    assert d.shape == (2, 8) and pool.misses == 3
    assert not StagingPool(per_bucket=0).enabled


def test_deferred_readiness_scopes_to_the_thread_and_nests():
    from llm_weighted_consensus_tpu.models import dispatch_seam as seam

    assert seam.active_sink() is None
    sink = seam.DispatchSink()
    with seam.deferred_readiness(sink):
        assert seam.active_sink() is sink
        with seam.deferred_readiness(None):  # inline-dispatch escape
            assert seam.active_sink() is None
        assert seam.active_sink() is sink
    assert seam.active_sink() is None


def test_drain_sink_recycles_buffers_only_on_clean_drain():
    from llm_weighted_consensus_tpu.models import dispatch_seam as seam

    released = []
    sink = seam.DispatchSink()
    sink.staged.append("buf")
    sink.add(
        seam.PendingDispatch("x", 0.0, None, wait=lambda out: None, timed=False)
    )
    seam.drain_sink(sink, release=released.append)
    assert released == ["buf"] and sink.staged == []

    sink = seam.DispatchSink()
    sink.staged.append("buf2")

    def boom(out):
        raise RuntimeError("device fault")

    sink.add(seam.PendingDispatch("x", 0.0, None, wait=boom))
    with pytest.raises(RuntimeError, match="device fault"):
        seam.drain_sink(sink, release=released.append)
    # a faulted drain drops its buffers for the GC — an async device_put
    # may still be reading them, so recycling would hand out torn memory
    assert released == ["buf"]


class _FakeDeviceArray:
    """A 'device' output handle that becomes ready ``device_sec`` after
    its dispatch: any host materialization (or the seam waiter) blocks
    until then, like a real PJRT buffer."""

    def __init__(self, value, ready_at):
        self._value = value
        self.ready_at = ready_at

    def block(self):
        now = time.perf_counter()
        if now < self.ready_at:
            time.sleep(self.ready_at - now)

    def __array__(self, dtype=None):
        self.block()
        a = np.asarray(self._value)
        return a.astype(dtype) if dtype is not None else a


def _fake_wait(out):
    out.block()


class _SlowDeviceEmbedder:
    """Batcher-facing embedder whose device takes ``device_sec`` per
    dispatch, mirroring ``TpuEmbedder._timed_dispatch``'s seam contract:
    under a deferred-readiness sink the call returns at enqueue; direct
    callers pay the inline timing bracket."""

    max_tokens = 32

    def __init__(self, device_sec, device_timing=True):
        self.device_sec = device_sec
        self.device_timing = device_timing

    def tokenize(self, texts, max_tokens=None):
        n = max(1, len(texts))
        return (
            np.ones((n, 8), np.int32),
            np.ones((n, 8), np.int32),
        )

    def embed_tokens(self, ids, mask):
        from llm_weighted_consensus_tpu.models import dispatch_seam as seam
        from llm_weighted_consensus_tpu.obs import phases as _ph

        t0 = time.perf_counter()
        out = _FakeDeviceArray(
            np.zeros((ids.shape[0], 4), np.float32),
            t0 + self.device_sec,
        )
        label = f"fake(b={ids.shape[0]})"
        sink = seam.active_sink()
        if sink is not None:
            sink.add(
                seam.PendingDispatch(
                    label, t0, out, wait=_fake_wait,
                    timed=self.device_timing,
                )
            )
            return out
        if self.device_timing:
            ticket = obs.device_account().enqueue(t0)
            _fake_wait(out)
            t1 = time.perf_counter()
            obs.device_account().ready(ticket, t1)
            _ph.observe_device(label, (t1 - t0) * 1e3)
        return out


def test_pipelined_dispatches_overlap_with_device_timing_on():
    """The ISSUE 13 acceptance drill: two pipelined groups against a
    slow fake device, METRICS_DEVICE_TIMING semantics ON — their device
    intervals must genuinely overlap and the pair must finish in well
    under 2x one group's device time.  On main the blocking bracket
    held the dispatch thread for the full device time, serializing the
    pipeline (~2x)."""
    from llm_weighted_consensus_tpu.obs import phases as ph
    from llm_weighted_consensus_tpu.serve.batcher import DeviceBatcher

    obs.reset_phases()
    T = 0.2
    fake = _SlowDeviceEmbedder(T, device_timing=True)
    batcher = DeviceBatcher(fake, None, window_ms=0.0, pipeline_depth=2)

    async def run():
        t0 = time.perf_counter()
        # different max_tokens caps -> different keys -> two groups
        await asyncio.gather(
            batcher.embed(["a"], 16), batcher.embed(["b"], 32)
        )
        return time.perf_counter() - t0

    wall = go(run())
    batcher.close()
    account = obs.device_account().snapshot()
    assert account["dispatches"] == 2
    # the second dispatch enqueued before the first became ready: the
    # union of the two is well under their sum, and the second sat
    # behind the first for most of its sojourn
    assert account["enqueued_ms"] < 1.5 * T * 1e3
    assert account["service_ms"] + account["waited_ms"] > 1.8 * T * 1e3
    assert account["waited_ms"] > 0.5 * T * 1e3
    assert wall < 1.5 * T, wall
    # device time still recorded per (bucket) label, one per group
    dev = ph.aggregator().device_snapshot()
    assert dev["fake(b=1)"]["count"] == 2
    # sojourn (the phase) is service + waited (the account), to round-off
    assert dev["fake(b=1)"]["sum_ms"] == pytest.approx(
        account["service_ms"] + account["waited_ms"], abs=1.0
    )
    # the device had a program for most of the pair's wall time
    assert account["enqueued_ms"] >= 0.8 * T * 1e3
    obs.reset_phases()


def test_waiter_and_bracket_device_times_agree():
    """Satellite (b) parity: the deferred waiter path and the inline
    bracket must report the same device time for the same work."""
    from llm_weighted_consensus_tpu.models import dispatch_seam as seam
    from llm_weighted_consensus_tpu.obs import phases as ph

    obs.reset_phases()
    T = 0.15
    fake = _SlowDeviceEmbedder(T, device_timing=True)
    # bracket mode: direct call, no sink active
    fake.embed_tokens(*fake.tokenize(["a"]))
    # deferred mode: enqueue under a sink, then drain like the waiter
    sink = seam.DispatchSink()
    with seam.deferred_readiness(sink):
        fake.embed_tokens(*fake.tokenize(["b"]))
    assert not _already_ready(sink)  # enqueue returned before readiness
    seam.drain_sink(
        sink,
        observe_device=ph.observe_device,
    )
    row = ph.aggregator().device_snapshot()["fake(b=1)"]
    assert row["count"] == 2
    # both measurements bracket the same T-second device run
    assert row["sum_ms"] / 2 == pytest.approx(T * 1e3, rel=0.5)
    obs.reset_phases()


def _already_ready(sink):
    """True if the sink's pending output already had to materialize —
    i.e. the dispatch thread blocked instead of deferring."""
    return any(
        time.perf_counter() >= rec.out.ready_at for rec in sink.pending
    )


def test_real_embedder_waiter_matches_bracket_labels():
    """Smoke the seam against the real TpuEmbedder on CPU: the deferred
    path must record the SAME bucket label as the inline bracket, with a
    positive device time."""
    from llm_weighted_consensus_tpu.models import dispatch_seam as seam
    from llm_weighted_consensus_tpu.models.configs import TEST_TINY
    from llm_weighted_consensus_tpu.models.embedder import TpuEmbedder
    from llm_weighted_consensus_tpu.obs import phases as ph

    obs.reset_phases()
    emb = TpuEmbedder("test-tiny", config=TEST_TINY, max_tokens=32)
    emb.device_timing = True
    ids, mask = emb.tokenize(["parity probe"])
    emb.embed_tokens(ids, mask)  # bracket
    bracket = set(ph.aggregator().device_snapshot())
    obs.reset_phases()
    sink = seam.DispatchSink()
    with seam.deferred_readiness(sink):
        out = emb.embed_tokens(ids, mask)
    seam.drain_sink(
        sink,
        observe_device=ph.observe_device,
    )
    deferred = ph.aggregator().device_snapshot()
    assert set(deferred) == bracket  # same (mesh-shape, bucket) labels
    assert all(row["sum_ms"] > 0 for row in deferred.values())
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(emb.embed_tokens(ids, mask)),
        rtol=1e-5, atol=1e-6,
    )
    obs.reset_phases()


# -- served request: phase sum within 10% of e2e ------------------------------


def ballot_keys(n):
    from llm_weighted_consensus_tpu.ballot import PrefixTree, branch_limit

    rng = random.Random(SEED)
    tree = PrefixTree.build(rng, n, branch_limit(None))
    return {idx: key for key, idx in tree.key_indices(rng)}


def judge_script(key, **kw):
    return Script(
        [
            chunk_obj("I pick ", model="up-model"),
            chunk_obj(f"{key} as best.", model="up-model", finish="stop"),
        ],
        **kw,
    )


def make_score_app(scripts, sink, admission=None, profile_dir=None):
    transport = FakeTransport(scripts)
    chat = DefaultChatClient(
        transport, [ApiBase("https://up.example", "k")], backoff=NO_RETRY
    )
    score = ScoreClient(
        chat,
        registry.InMemoryModelRegistry(),
        archive_fetcher=archive.InMemoryArchive(),
        rng_factory=lambda: random.Random(SEED),
    )
    return build_app(
        chat,
        score,
        trace_sink=sink,
        admission=admission,
        profile_dir=profile_dir,
    )


async def with_client(app, fn):
    client = TestClient(TestServer(app))
    await client.start_server()
    try:
        return await fn(client)
    finally:
        await client.close()


def score_body():
    return {
        "messages": [{"role": "user", "content": "pick the best"}],
        "model": {
            "llms": [
                {"model": "judge-a", "weight": {"type": "static", "weight": 2}},
                {"model": "judge-b", "weight": {"type": "static", "weight": 1}},
            ]
        },
        "choices": TEXTS,
    }


def test_served_request_phase_sum_within_10pct_of_e2e():
    """The acceptance bar: every traced request's root span carries a
    phase_breakdown whose named phases account for >= 90% of end-to-end
    latency.  Judge streams are stalled so attributable time dominates
    the fake-transport floor."""
    keys = ballot_keys(3)
    sink = TraceSink(sample_rate=1.0)
    scripts = [
        judge_script(keys[1], delays={1: 0.08}),
        judge_script(keys[1], delays={1: 0.08}),
    ]
    app = make_score_app(scripts, sink)

    async def run(client):
        resp = await client.post(
            "/score/completions",
            data=jsonutil.dumps(score_body()),
            headers={"content-type": "application/json"},
        )
        assert resp.status == 200
        await resp.read()
        trace_id = resp.headers["x-trace-id"]
        return await (await client.get(f"/v1/traces/{trace_id}")).json()

    record = go(with_client(app, run))
    root = record["spans"][0]
    breakdown = root["attributes"]["phase_breakdown"]
    assert set(PHASES) <= set(breakdown), breakdown
    assert breakdown["e2e_ms"] >= 80.0  # the injected stall is inside
    named = sum(breakdown[p] for p in PHASES)
    assert named >= 0.9 * breakdown["e2e_ms"], breakdown
    # concurrent judge streams attribute wall time once, not twice
    assert breakdown["upstream_judge"] < 2 * 0.8 * 80.0
    assert breakdown["other_ms"] == pytest.approx(
        max(0.0, breakdown["e2e_ms"] - named), abs=0.01
    )


# -- prometheus exposition ----------------------------------------------------


def _sample_family(line: str) -> str:
    name = re.split(r"[{ ]", line, 1)[0]
    for suffix in ("_total", "_bucket", "_sum", "_count"):
        if name.endswith(suffix):
            return name[: -len(suffix)]
    return name


def test_prometheus_exposition_golden_format():
    obs.reset_phases()
    metrics = Metrics()
    register_performance(metrics)
    metrics.observe("http:/v1/score", 12.5, trace_id="abcd1234ef")
    metrics.observe("http:/v1/score", 90.0, error=True)
    obs.observe_phase("upstream_judge", 40.0)
    obs.observe_device("vote1(n=8,s=16)", 7.5)
    text = render_prometheus(metrics)
    assert text.endswith("# EOF\n")
    lines = text.splitlines()

    # every HELP has a TYPE on the next line, both naming a known family
    for i, line in enumerate(lines):
        if line.startswith("# HELP "):
            family = line.split()[2]
            assert family in KNOWN_PROM_FAMILIES, family
            assert lines[i + 1].startswith(f"# TYPE {family} "), family
    # every sample belongs to a declared family (the registry LWC012
    # enforces statically, re-checked here against real output)
    for line in lines:
        if line.startswith("#") or not line:
            continue
        assert _sample_family(line) in KNOWN_PROM_FAMILIES, line

    # counters: _total samples with the series label
    assert 'lwc_series_requests_total{series="http:/v1/score"} 2' in lines
    assert 'lwc_series_errors_total{series="http:/v1/score"} 1' in lines

    # histogram family: cumulative buckets + exemplar on the bucket
    # containing the exemplar value, then _sum/_count
    bucket_lines = [
        ln for ln in lines if ln.startswith("lwc_series_latency_ms_bucket")
    ]
    assert bucket_lines[-1].startswith(
        'lwc_series_latency_ms_bucket{series="http:/v1/score",le="+Inf"} 2'
    )
    exemplar = [ln for ln in bucket_lines if "#" in ln]
    assert len(exemplar) == 1
    m = re.fullmatch(
        r'lwc_series_latency_ms_bucket\{series="http:/v1/score",'
        r'le="(?P<le>[^"]+)"\} \d+ '
        r'# \{trace_id="abcd1234ef"\} 12\.5 \d+(\.\d+)?',
        exemplar[0],
    )
    assert m, exemplar[0]
    assert m.group("le") == le_for(12.5)
    assert 'lwc_series_latency_ms_count{series="http:/v1/score"} 2' in lines

    # phase + device histograms from the global aggregator
    assert any(
        ln.startswith('lwc_phase_latency_ms_bucket{phase="upstream_judge"')
        for ln in lines
    )
    assert any(
        ln.startswith(
            'lwc_device_latency_ms_count{bucket="vote1(n=8,s=16)"} 1'
        )
        for ln in lines
    )
    obs.reset_phases()


def test_json_snapshot_stays_shape_compatible():
    """The PR 5 JSON consumers (the benchmark's readers, dashboards) read
    count / errors / p50_ms / p99_ms / trace_id per series; the histogram swap
    must not change that shape, and the new sections are registered."""
    obs.reset_phases()
    metrics = Metrics()
    register_performance(metrics)
    metrics.observe("http:/x", 10.0, trace_id="t1")
    snap = metrics.snapshot()
    row = snap["series"]["http:/x"]
    assert set(row) == {"count", "errors", "p50_ms", "p99_ms", "trace_id"}
    assert row["count"] == 1 and row["errors"] == 0
    assert row["trace_id"] == "t1"
    assert snap["uptime_sec"] >= 0
    assert "phases" in snap  # registered provider section
    assert "phases" in KNOWN_SECTIONS and "roofline" in KNOWN_SECTIONS
    obs.reset_phases()


def test_metrics_uptime_uses_monotonic_clock():
    # the satellite fix: _started must be a monotonic reading (epoch
    # seconds are ~1.7e9 and jump under NTP; monotonic starts near 0)
    metrics = Metrics()
    assert abs(metrics._started - time.monotonic()) < 60.0
    assert metrics.uptime_sec() >= 0.0


# -- roofline -----------------------------------------------------------------


from llm_weighted_consensus_tpu.analysis.roofline import (  # noqa: E402
    DEFAULT_PEAKS,
    RooflineGauge,
    compare_roofline,
    sol_ms,
    split_label,
    write_roofline,
)

_SCOPE = {"model": "test-tiny", "dp": 4, "tp": 2}
_PEAKS = {"cpu": {"flops_per_sec": 1e9, "hbm_bytes_per_sec": 1e9}}


def _roofline(buckets, scope=_SCOPE, peaks=None):
    return {
        "scope": scope,
        "tolerance": {"flops": 0.25, "bytes_accessed": 0.25},
        "peaks": {**DEFAULT_PEAKS, **(peaks or {})},
        "buckets": buckets,
    }


def test_split_label_parses_mesh_suffix():
    assert split_label("vote1(n=8,s=16)@dp4xtp2") == ("vote1(n=8,s=16)", 8)
    assert split_label("embed(b=16,s=16)") == ("embed(b=16,s=16)", 1)


def test_sol_ms_takes_the_binding_ceiling_and_scales_by_chips():
    figures = {"flops": 2e9, "bytes_accessed": 1e6}
    peaks = {"flops_per_sec": 1e9, "hbm_bytes_per_sec": 1e9}
    assert sol_ms(figures, peaks) == pytest.approx(2000.0)  # compute-bound
    assert sol_ms(figures, peaks, chips=4) == pytest.approx(500.0)
    bw_bound = {"flops": 1e3, "bytes_accessed": 5e8}
    assert sol_ms(bw_bound, peaks) == pytest.approx(500.0)
    assert sol_ms({}, peaks) is None
    assert sol_ms(figures, {"flops_per_sec": 0, "hbm_bytes_per_sec": 1}) is None


def test_roofline_gauge_scales_sol_by_mesh_chips():
    obs.reset_phases()
    figures = {"flops": 4e6, "bytes_accessed": 1e3}
    gauge = RooflineGauge(
        _roofline({"x(b=1)": figures}, peaks=_PEAKS), "cpu"
    )
    obs.observe_device("x(b=1)", 8.0)
    obs.observe_device("x(b=1)@dp2xtp2", 2.0)
    snap = gauge.snapshot()
    assert snap["device_kind"] == "cpu" and snap["known_peaks"]
    single = snap["buckets"]["x(b=1)"]
    meshed = snap["buckets"]["x(b=1)@dp2xtp2"]
    assert single["sol_ms"] == pytest.approx(4.0)  # 4e6 / 1e9 * 1e3
    assert meshed["sol_ms"] == pytest.approx(1.0)  # 4 chips
    # attainment = sol / measured p50 (p50 is the bucket midpoint, so
    # compare against the reported figure, not the raw observation)
    assert single["attainment"] == pytest.approx(
        single["sol_ms"] / single["device_p50_ms"], rel=1e-3
    )
    # an observed bucket with no committed row still reports its count
    obs.observe_device("rogue(b=1)", 1.0)
    row = gauge.snapshot()["buckets"]["rogue(b=1)"]
    assert row["count"] == 1 and "sol_ms" not in row
    obs.reset_phases()


def test_jxa013_missing_file_is_one_actionable_finding():
    findings = compare_roofline({"a": {"flops": 1, "bytes_accessed": 1}}, {})
    assert len(findings) == 1
    assert findings[0].rule == "JXA013"
    assert "--write-roofline" in findings[0].message


def test_jxa013_scope_mismatch_short_circuits():
    measured = {"a": {"flops": 1, "bytes_accessed": 1}}
    roofline = _roofline({"a": {"flops": 1, "bytes_accessed": 1}})
    findings = compare_roofline(
        measured, roofline, scope={"model": "other", "dp": 1, "tp": 1}
    )
    assert len(findings) == 1 and "scope" in findings[0].message


def test_jxa013_flags_missing_row_and_stale_row():
    measured = {"new_bucket": {"flops": 100.0, "bytes_accessed": 10.0}}
    roofline = _roofline({"gone_bucket": {"flops": 5.0, "bytes_accessed": 1.0}})
    findings = compare_roofline(measured, roofline, scope=_SCOPE)
    by_symbol = {f.symbol: f.message for f in findings}
    assert "no roofline row" in by_symbol["new_bucket"]
    assert "stale roofline row" in by_symbol["gone_bucket"]


def test_jxa013_flags_drifted_figures_both_directions():
    committed = {"b": {"flops": 1000.0, "bytes_accessed": 1000.0}}
    # +30% flops (above the 25% band), -40% bytes
    measured = {"b": {"flops": 1300.0, "bytes_accessed": 600.0}}
    findings = compare_roofline(measured, _roofline(committed), scope=_SCOPE)
    assert len(findings) == 2
    assert all("stale" in f.message and f.symbol == "b" for f in findings)
    # within the band: silent
    ok = {"b": {"flops": 1100.0, "bytes_accessed": 900.0}}
    assert compare_roofline(ok, _roofline(committed), scope=_SCOPE) == []


def test_jxa013_flags_unusable_peaks():
    roofline = _roofline({}, peaks={"cpu": {"flops_per_sec": 0}})
    findings = compare_roofline({}, roofline, scope=_SCOPE)
    assert [f.symbol for f in findings] == ["cpu"]
    assert "per-chip" in findings[0].message


def test_write_roofline_preserves_policy_and_rounds_figures(tmp_path):
    from llm_weighted_consensus_tpu.analysis.roofline import load_roofline

    path = tmp_path / "roofline.json"
    previous = _roofline({}, peaks=_PEAKS)
    previous["tolerance"] = {"flops": 0.5, "bytes_accessed": 0.5}
    write_roofline(
        path,
        {"a": {"flops": 123.456, "bytes_accessed": 7.0}},
        _SCOPE,
        previous,
    )
    reloaded = load_roofline(path)
    assert reloaded["scope"] == _SCOPE
    assert reloaded["tolerance"] == previous["tolerance"]  # survives
    assert reloaded["peaks"] == previous["peaks"]  # survives
    assert reloaded["buckets"]["a"]["flops"] == 123.5  # fresh figures
    assert compare_roofline(
        {"a": {"flops": 123.456, "bytes_accessed": 7.0}},
        reloaded,
        scope=_SCOPE,
    ) == []


def test_mesh_audit_roofline_path_env_override(monkeypatch):
    from llm_weighted_consensus_tpu.analysis.mesh_audit import _roofline_path

    monkeypatch.setenv("ANALYSIS_ROOFLINE", "/tmp/other-roofline.json")
    assert str(_roofline_path()) == "/tmp/other-roofline.json"


# -- /v1/profile --------------------------------------------------------------


def test_profile_endpoint_403_without_profile_dir():
    app = make_score_app([], sink=None, profile_dir=None)

    async def run(client):
        resp = await client.post("/v1/profile")
        assert resp.status == 403
        body = await resp.json()
        assert "PROFILE_DIR" in body["message"]

    go(with_client(app, run))


def test_profile_one_shot_capture_writes_trace(tmp_path):
    app = make_score_app([], sink=None, profile_dir=str(tmp_path))

    async def run(client):
        resp = await client.post(
            "/v1/profile", data=json.dumps({"duration_ms": 20})
        )
        assert resp.status == 200
        body = await resp.json()
        assert body["ok"] and body["duration_ms"] == 20.0

    go(with_client(app, run))
    assert any(tmp_path.iterdir())  # xprof artifacts landed


def test_profile_rides_the_admission_exemption():
    """Profiling an overload is the point: while the gate sheds every
    scoring request, /v1/profile must still reach its handler (here the
    clean 403, not a 503 shed)."""
    from llm_weighted_consensus_tpu.resilience import (
        AdmissionConfig,
        AdmissionController,
    )

    admission = AdmissionController(AdmissionConfig(max_inflight=1))
    admission.draining = True  # sheds everything non-exempt
    app = make_score_app([], sink=None, admission=admission)

    async def run(client):
        resp = await client.post(
            "/score/completions", data=jsonutil.dumps(score_body())
        )
        assert resp.status == 503  # shed at the door
        assert (await resp.json())["message"]["shed_reason"] == "draining"
        resp = await client.post("/v1/profile")
        assert resp.status == 403  # reached the handler, not the gate

    go(with_client(app, run))
