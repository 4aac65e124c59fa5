"""Host spans (ISSUE 24): one helper, one vocabulary, three readers.

* ``obs.host_span`` — feeds the phase histograms, the profiler's
  annotation and the request's span tree from one call; inert beyond the
  phase observe with no profile and no root span;
* the sites — through the real gateway and batcher on the tiny config,
  each span of the vocabulary fires once per request or once per group,
  and the ids agree across the four threads a request passes;
* the capture — ``POST /v1/profile`` on the CPU holds the ten span names
  with their attributes, both ``lwc:clock`` marks (the device's account on
  each) and no Python-tracer frame;
* the scopes — metadata only: the optimised HLO is the same with and
  without them;
* ``jit.backend_compiles`` — one more for a fresh shape, flat under
  warmed load.
"""

import asyncio
import contextlib
import json
import os
import random
import re
import threading

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
from llm_weighted_consensus_tpu.obs import hostspan
from llm_weighted_consensus_tpu.obs.phases import PHASES
from llm_weighted_consensus_tpu.obs.sink import TraceSink
from llm_weighted_consensus_tpu.obs.span import KNOWN_SPANS
from llm_weighted_consensus_tpu.serve import build_app

from fakes import FakeTransport

SEED = 24
TEXTS = [f"candidate answer number {i} with a few words" for i in range(4)]
TEN = (
    "http:arrive", "http:read", "http:parse", "host:tokenize", "batcher:idle",
    "batcher:stage", "device:wait", "host:finalize", "http:respond",
    "lwc:clock",
)


def go(coro):
    return asyncio.new_event_loop().run_until_complete(coro)


class FakeAnnotation:
    """Stands in for the profiler's annotation: records what a real one
    would write into the trace, and on which thread."""

    events: list = []

    def __init__(self, name, **attrs):
        self.row = {"name": name, "attrs": dict(attrs), "open": False}

    def __enter__(self):
        self.row["open"] = True
        self.row["thread"] = threading.current_thread().name
        return self

    def set_metadata(self, **attrs):
        self.row["attrs"].update(attrs)

    def __exit__(self, *exc):
        self.row["open"] = False
        FakeAnnotation.events.append(self.row)
        return False


@pytest.fixture
def annotations():
    FakeAnnotation.events = []
    obs.set_profiler_annotation(FakeAnnotation)
    try:
        yield FakeAnnotation.events
    finally:
        obs.set_profiler_annotation(None)


def phase_counts() -> dict:
    snap = obs.phases_snapshot()
    return {p: snap[p]["count"] for p in PHASES if p in snap}


# -- the helper ---------------------------------------------------------------


def test_vocabulary_is_declared_where_the_lint_and_metrics_look():
    for name, phase in hostspan.HOST_SPANS.items():
        assert phase is None or phase in PHASES, (name, phase)
        assert any(
            name == known or (known.endswith("*") and name.startswith(known[:-1]))
            for known in KNOWN_SPANS
        ), name


def test_one_call_feeds_phases_profile_and_span_tree(annotations):
    obs.reset_phases()
    root = obs.start_trace("gateway:POST /t", sampled=True)
    token = root.activate()
    try:
        with obs.host_span("http:parse", rid=7, bytes=12) as span:
            span.annotate(n=3)
    finally:
        obs.Span.deactivate(token)
        root.finish()
    # 1: the phase histogram
    assert phase_counts() == {"http_parse": 1}
    # 2: the profiler's annotation, attributes given at entry and inside
    assert annotations == [
        {
            "name": "http:parse",
            "attrs": {"rid": 7, "bytes": 12, "n": 3},
            "open": False,
            "thread": threading.current_thread().name,
        }
    ]
    # 3: a finished child on the ambient tree
    child = root.trace.spans[1]
    assert (child.name, child.parent_id) == ("http:parse", root.span_id)
    assert child.attributes == {"rid": 7, "bytes": 12, "n": 3}
    assert child.duration_ms() is not None


def test_explicit_parents_get_one_child_each_and_none_is_skipped(annotations):
    a = obs.start_trace("gateway:POST /a", sampled=True)
    b = obs.start_trace("gateway:POST /b", sampled=True)
    with obs.host_span("batcher:stage", parents=[a, None, b], group=5):
        pass
    assert [s.name for s in a.trace.spans] == ["gateway:POST /a", "batcher:stage"]
    assert [s.name for s in b.trace.spans] == ["gateway:POST /b", "batcher:stage"]
    assert len(annotations) == 1


def test_inert_without_profile_and_root_span():
    obs.reset_phases()
    obs.set_profiler_annotation(None)
    assert obs.current_span() is None
    with obs.host_span("host:finalize", group=1) as span:
        span.annotate(extra=1)
    assert span._ann is None and span._spans == []
    assert phase_counts() == {"finalize": 1}
    # a span with no phase observes nothing at all
    with obs.host_span("batcher:idle", parents=()):
        pass
    assert phase_counts() == {"finalize": 1}


def test_an_error_inside_marks_the_child_and_still_observes():
    obs.reset_phases()
    root = obs.start_trace("gateway:POST /t", sampled=True)
    with pytest.raises(ValueError):
        with obs.host_span("batcher:stage", parents=[root], group=1):
            raise ValueError("boom")
    assert root.trace.spans[1].status == "error"
    assert phase_counts() == {"stage": 1}


def test_arrive_mints_a_fresh_rid_or_adopts_the_trace_id(annotations):
    first = obs.arrive("/consensus", 10)
    second = obs.arrive("/consensus", 10)
    assert isinstance(first, int) and second == first + 1
    assert obs.request_id() == second  # kept for the rest of the context
    root = obs.start_trace("gateway:POST /t", sampled=True)
    token = root.activate()
    try:
        assert obs.arrive("/consensus", 10) == root.trace.trace_id
    finally:
        obs.Span.deactivate(token)
    assert [e["attrs"]["rid"] for e in annotations] == [
        first, second, root.trace.trace_id
    ]
    assert {e["attrs"]["route"] for e in annotations} == {"/consensus"}


def test_phase_breakdown_attributes_each_interval_once():
    """A request's tree with the host spans inside its batcher span: the
    named phases telescope to the batcher span's length, nothing twice."""
    from llm_weighted_consensus_tpu.obs.span import Span, Trace

    trace = Trace(None, True)
    t0 = trace.t0

    def at(name, parent, start_ms, dur_ms):
        span = Span(trace, name, parent.span_id if parent else None)
        span._start = t0 + start_ms / 1e3
        span._end = span._start + dur_ms / 1e3
        return span

    root = at("gateway:POST /consensus", None, 0, 100)
    at("http:parse", root, 1, 4)
    item = at("batcher:consensus", root, 6, 90)
    at("host:tokenize", item, 6, 20)
    device = at("device:dispatch", item, 30, 66)
    at("batcher:stage", item, 30, 6)
    at("device:wait", item, 36, 55)
    at("host:finalize", item, 91, 5)
    at("http:respond", root, 97, 2)
    assert device.duration_ms() == 66
    out = obs.phase_breakdown(trace)
    assert out["http_parse"] == 4 and out["http_respond"] == 2
    assert out["tokenize"] == 20 and out["stage"] == 6 and out["finalize"] == 5
    assert out["device_dispatch"] == 55  # the bracket minus stage and finalize
    assert out["batcher_queue"] == 4  # 26..30: waiting for a slot
    assert set(PHASES) <= set(out)
    named = sum(out[p] for p in PHASES)
    assert named == 96 and out["other_ms"] == 4


# -- the sites, through the real gateway and batcher -----------------------------


def embedder_app(sink=None, profile_dir=None, **kw):
    pytest.importorskip("jax")
    from llm_weighted_consensus_tpu.models.embedder import TpuEmbedder

    embedder = TpuEmbedder("test-tiny", max_tokens=32)
    chat = DefaultChatClient(
        FakeTransport([]),
        [ApiBase("https://up.example", "k")],
        backoff=BackoffPolicy(max_elapsed_ms=0),
    )
    score = ScoreClient(
        chat,
        registry.InMemoryModelRegistry(),
        archive_fetcher=archive.InMemoryArchive(),
        rng_factory=lambda: random.Random(SEED),
    )
    return build_app(
        chat, score, None, embedder, trace_sink=sink,
        profile_dir=profile_dir, **kw,
    )


async def with_client(app, fn):
    client = TestClient(TestServer(app))
    await client.start_server()
    try:
        return await fn(client)
    finally:
        await client.close()


async def consensus(client, texts=TEXTS):
    resp = await client.post("/consensus", data=json.dumps({"input": texts}))
    assert resp.status == 200, await resp.text()
    return resp


def by_name(events) -> dict:
    out: dict = {}
    for event in events:
        out.setdefault(event["name"], []).append(event)
    return out


def test_each_site_fires_once_per_request_with_one_rid(annotations):
    obs.reset_phases()
    app = embedder_app()

    async def run(client):
        await consensus(client)
        await asyncio.sleep(0.05)  # the flusher ends; batcher:idle opens
        await consensus(client)

    go(with_client(app, run))
    seen = by_name(annotations)
    for name in (
        "http:arrive", "http:read", "http:parse", "host:tokenize",
        "batcher:stage", "device:wait", "host:finalize", "http:respond",
    ):
        assert len(seen[name]) == 2, (name, seen.get(name))
    assert len(seen["batcher:idle"]) >= 1  # between the two requests
    rids = [e["attrs"]["rid"] for e in seen["http:arrive"]]
    assert len(set(rids)) == 2
    for i, rid in enumerate(rids):
        # the same id on the event loop, the tokenizer pool, the dispatch
        # executor (as the group's one rid) and back
        assert seen["http:read"][i]["attrs"]["rid"] == rid
        assert seen["http:parse"][i]["attrs"]["rid"] == rid
        assert seen["host:tokenize"][i]["attrs"]["rid"] == rid
        assert seen["http:respond"][i]["attrs"]["rid"] == rid
        stage = seen["batcher:stage"][i]["attrs"]
        assert stage["rids"] == str(rid)
        assert stage["label"].startswith("vote1(n=4,s=")
        group = stage["group"]
        assert seen["device:wait"][i]["attrs"]["group"] == group
        assert seen["device:wait"][i]["attrs"]["label"] == stage["label"]
        assert seen["host:finalize"][i]["attrs"]["group"] == group
    assert seen["http:parse"][0]["attrs"]["n"] == 4
    assert seen["http:respond"][0]["attrs"]["status"] == 200
    assert seen["host:tokenize"][0]["attrs"]["rows"] == 4
    assert seen["host:tokenize"][0]["attrs"]["tokens"] > 4
    threads = {n: seen[n][0]["thread"] for n in seen}
    assert threads["host:tokenize"].startswith("lwc-hosttok")
    assert threads["batcher:stage"].startswith("lwc-device")
    assert threads["device:wait"].startswith("lwc-waiter")
    assert threads["host:finalize"].startswith("lwc-waiter")
    assert threads["http:parse"] == threads["http:respond"] == threads["batcher:idle"]
    counts = phase_counts()
    for phase in (
        "http_read", "http_parse", "tokenize", "stage", "finalize",
        "http_respond",
    ):
        assert counts[phase] == 2, counts
    assert counts["batcher_queue"] == 2 and counts["device_dispatch"] == 2


def test_a_group_of_requests_is_one_stage_one_wait_one_finalize(annotations):
    app = embedder_app(batch_window_ms=60.0)

    async def run(client):
        await asyncio.gather(*(consensus(client) for _ in range(3)))

    go(with_client(app, run))
    seen = by_name(annotations)
    assert len(seen["http:arrive"]) == len(seen["host:tokenize"]) == 3
    rids = {str(e["attrs"]["rid"]) for e in seen["http:arrive"]}
    # 3 same-shape requests inside one window: one group, padded to 4
    assert len(seen["batcher:stage"]) == 1
    stage = seen["batcher:stage"][0]["attrs"]
    assert set(stage["rids"].split(" ")) == rids
    assert stage["label"].startswith("many(r=4,n=4,s=")
    assert [e["attrs"]["group"] for e in seen["device:wait"]] == [stage["group"]]
    assert [e["attrs"]["group"] for e in seen["host:finalize"]] == [stage["group"]]
    assert len(seen["http:respond"]) == 3


def test_inline_tokenization_lies_inside_stage_and_counts_as_tokenize(annotations):
    """With the tokenizer pool off the stage hop tokenizes for the group."""
    pytest.importorskip("jax")
    from llm_weighted_consensus_tpu.models.embedder import TpuEmbedder
    from llm_weighted_consensus_tpu.serve.batcher import DeviceBatcher

    obs.reset_phases()
    batcher = DeviceBatcher(
        TpuEmbedder("test-tiny", max_tokens=32), host_tokenizer_workers=0
    )

    async def run():
        try:
            return await batcher.consensus(TEXTS, 0.05)
        finally:
            batcher.close()

    conf, _ = go(run())
    assert len(conf) == 4
    names = [e["name"] for e in annotations]
    # closed (and so recorded) inside the stage that contains it
    assert names.index("host:tokenize") < names.index("batcher:stage")
    tokenize = by_name(annotations)["host:tokenize"][0]
    assert tokenize["thread"].startswith("lwc-device")
    assert tokenize["attrs"]["rids"] == by_name(annotations)["batcher:stage"][0]["attrs"]["rids"]
    counts = phase_counts()
    assert counts["tokenize"] == 1 and counts["stage"] == 1


def test_spans_hang_on_the_request_tree_when_tracing_is_on():
    sink = TraceSink(sample_rate=1.0)
    app = embedder_app(sink=sink)

    async def run(client):
        resp = await consensus(client)
        trace_id = resp.headers["x-trace-id"]
        return trace_id, await (await client.get(f"/v1/traces/{trace_id}")).json()

    trace_id, record = go(with_client(app, run))
    spans = {s["name"]: s for s in record["spans"]}
    for name in (
        "http:arrive", "http:parse", "host:tokenize", "batcher:stage",
        "device:wait", "host:finalize", "http:respond",
    ):
        assert name in spans, sorted(spans)
    # the rid IS the trace id where there is a root span
    assert spans["http:arrive"]["attributes"]["rid"] == trace_id
    assert spans["host:tokenize"]["attributes"]["rid"] == trace_id
    assert spans["batcher:stage"]["attributes"]["rids"] == trace_id
    item = spans["batcher:consensus"]["span_id"]
    for name in ("host:tokenize", "batcher:stage", "device:wait", "host:finalize"):
        assert spans[name]["parent_id"] == item, name
    breakdown = record["spans"][0]["attributes"]["phase_breakdown"]
    assert set(PHASES) <= set(breakdown)
    assert breakdown["tokenize"] > 0 and breakdown["stage"] > 0


def test_parse_errors_answer_400_inside_a_respond_span(annotations):
    app = embedder_app()

    async def run(client):
        resp = await client.post("/consensus", data="{not json")
        assert resp.status == 400
        resp = await client.post("/consensus", data=json.dumps({"input": ["one"]}))
        assert resp.status == 400
        assert "at least" in (await resp.text()) or ">= 2" in (await resp.text())

    go(with_client(app, run))
    seen = by_name(annotations)
    assert [e["attrs"]["status"] for e in seen["http:respond"]] == [400, 400]
    assert len(seen["http:arrive"]) == 2 and "host:tokenize" not in seen


# -- the capture ----------------------------------------------------------------


def test_cpu_profile_holds_the_spans_the_clock_marks_and_no_python_frames(tmp_path):
    pytest.importorskip("jax")
    from jax.profiler import ProfileData

    app = embedder_app(profile_dir=str(tmp_path))

    async def run(client):
        await consensus(client)  # compiles outside the capture

        async def traffic():
            await asyncio.sleep(0.1)
            await consensus(client)
            await asyncio.sleep(0.1)
            await consensus(client)

        resp, _ = await asyncio.gather(
            client.post("/v1/profile", data=json.dumps({"duration_ms": 600})),
            traffic(),
        )
        assert resp.status == 200

    go(with_client(app, run))
    assert hostspan._annotation is None  # handed back when the capture ends
    found = [
        os.path.join(root, name)
        for root, _, names in os.walk(tmp_path)
        for name in names
        if name.endswith(".xplane.pb")
    ]
    assert len(found) == 1
    events: dict = {}
    frames = 0
    for plane in ProfileData.from_file(found[0]).planes:
        for line in plane.lines:
            for event in line.events:
                if event.name.startswith("$"):
                    frames += 1  # the Python tracer's ``$file:line fn``
                if event.name in hostspan.HOST_SPANS:
                    events.setdefault(event.name, []).append(dict(event.stats))
    assert frames == 0
    for name in TEN:
        assert name in events, (name, sorted(events))
    assert len(events["lwc:clock"]) == 2
    for mark in events["lwc:clock"]:
        assert mark["perf_counter_ns"] > 0 and mark["epoch_ns"] > 1e18
    opened, closed = sorted(
        events["lwc:clock"], key=lambda m: m["perf_counter_ns"]
    )
    span_ms = (closed["perf_counter_ns"] - opened["perf_counter_ns"]) / 1e6
    assert 0.5e3 < span_ms < 5e3
    # the account's window between the marks is the trace's: what it
    # booked there is the marks' distance, less a starved stretch still open
    booked = sum(
        float(closed[key]) - float(opened[key])
        for key in ("enqueued_ms", "starved_ms", "idle_ms")
    )
    assert 0.0 < booked <= span_ms + 1.0
    assert booked > span_ms - 250.0
    assert len(events["http:arrive"]) == 2
    rids = {a["rid"] for a in events["http:arrive"]}
    assert {r["rid"] for r in events["http:respond"]} == rids
    assert {t["rid"] for t in events["host:tokenize"]} == rids
    assert {int(s["rids"]) for s in events["batcher:stage"]} == rids
    assert all(s["label"].startswith("vote1(") for s in events["batcher:stage"])
    assert {a["route"] for a in events["http:arrive"]} == {"/consensus"}
    assert all(p["n"] == 4 and p["bytes"] > 0 for p in events["http:parse"])
    assert {r["rid"] for r in events["http:read"]} == rids
    assert [r["bytes"] for r in events["http:read"]] == [
        p["bytes"] for p in events["http:parse"]
    ]


# -- the scopes -----------------------------------------------------------------


_METADATA = re.compile(r",? ?metadata=\{[^}]*\}")
_TABLES = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")


def _without_metadata(text: str) -> str:
    """An HLO module's text without what names where it came from: each
    instruction's ``metadata={..}`` and the module's source tables."""
    blocks = [
        block
        for block in text.split("\n\n")
        if block.lstrip("\n").split("\n", 1)[0] not in _TABLES
    ]
    return _METADATA.sub("", "\n\n".join(blocks))


@pytest.mark.parametrize("family", ["bert", "deberta"])
def test_scopes_leave_the_optimised_hlo_unchanged(family, monkeypatch):
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp

    from llm_weighted_consensus_tpu.models import bert, configs, deberta

    ids = jnp.asarray(np.arange(64).reshape(4, 16) % 50 + 3, jnp.int32)
    mask = jnp.ones((4, 16), jnp.int32)
    if family == "bert":
        cfg = configs.TEST_TINY
        params = bert.init_params(jax.random.PRNGKey(0), cfg)

        def forward(p, i, m):
            return bert.pool(bert.encode(p, i, m, cfg), m)

        expected = {"embeddings", "encoder_layers", "qkv_proj", "attn_out",
                    "attn_ln", "mlp", "mlp_ln", "pool", "einsum_attention"}
    else:
        cfg = configs.DEBERTA_TEST_TINY
        params = deberta.init_params(jax.random.PRNGKey(0), cfg)

        def forward(p, i, m):
            return deberta.reward.__wrapped__(p, i, m, cfg)

        expected = {"embeddings", "encoder_layers", "qkv_proj", "rel_bias",
                    "attention", "attn_out", "attn_ln", "mlp", "mlp_ln", "head"}
    # the persistent cache keys a program without its metadata: a hit would
    # hand the second compile the first one's text, scopes and all
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        _compare(jax, forward, (params, ids, mask), expected, monkeypatch)
    finally:
        jax.config.update("jax_enable_compilation_cache", True)


def _compare(jax, forward, args, expected, monkeypatch):
    params, ids, mask = args
    scoped_text = jax.jit(forward).lower(params, ids, mask).compile().as_text()
    for scope in expected:
        assert f"/{scope}/" in scoped_text, scope
    with_scopes = _without_metadata(scoped_text)
    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
    # a new function object: the trace of ``forward`` itself is cached
    def again(p, i, m):
        return forward(p, i, m)

    again.__name__ = again.__qualname__ = forward.__name__
    bare_text = jax.jit(again).lower(params, ids, mask).compile().as_text()
    assert "/mlp/" not in bare_text and "/qkv_proj/" not in bare_text
    assert _without_metadata(bare_text) == with_scopes


# -- the compile counter ----------------------------------------------------------


def test_backend_compiles_counts_a_fresh_shape_once_and_stays_flat_warm():
    jax = pytest.importorskip("jax")
    from llm_weighted_consensus_tpu.serve.config import CompileCacheStats

    stats = CompileCacheStats("unused")
    try:
        @jax.jit
        def double(x):
            return x * 2.0 + 1.0

        base = stats.compiles()["backend_compiles"]
        double(np.ones((3, 7), np.float32)).block_until_ready()
        first = stats.compiles()
        assert first["backend_compiles"] == base + 1
        assert first["backend_compile_s"] > 0
        for _ in range(5):
            double(np.ones((3, 7), np.float32)).block_until_ready()
        assert stats.compiles()["backend_compiles"] == base + 1
        double(np.ones((4, 7), np.float32)).block_until_ready()
        assert stats.compiles()["backend_compiles"] == base + 2
    finally:
        jax.monitoring.unregister_event_listener(stats._on_event)
        jax.monitoring.unregister_event_duration_listener(stats._on_duration)


def test_metrics_device_reports_the_reserved_region(monkeypatch):
    jax = pytest.importorskip("jax")
    from llm_weighted_consensus_tpu.serve import __main__ as main

    class Device:
        id = 0
        platform = "tpu"

        def memory_stats(self):
            return {
                "bytes_in_use": 10, "peak_bytes_in_use": 20,
                "bytes_reserved": 30, "peak_bytes_reserved": 40,
                "largest_alloc_size": 5,
            }

    monkeypatch.setattr(jax, "local_devices", lambda: [Device()])
    row = main._device_stats(None, None)["devices"][0]
    assert row == {
        "id": 0, "bytes_in_use": 10, "peak_bytes_in_use": 20,
        "bytes_reserved": 30, "peak_bytes_reserved": 40,
    }
