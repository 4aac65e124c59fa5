"""The device's account (ISSUE 37, ``obs/account.py``): the arithmetic on
schedules made by hand with a fake clock, then the feeds through the real
seam, batcher and gateway on the tiny configuration.  Nothing here is a
device time: a CPU run gives the arithmetic and the plumbing."""

import asyncio
import json
import time

import pytest

from llm_weighted_consensus_tpu import obs
from llm_weighted_consensus_tpu.models import dispatch_seam as seam
from llm_weighted_consensus_tpu.obs.account import (
    STALL_MS,
    STARVED_KEYS,
    DeviceAccount,
)


def go(coro):
    return asyncio.new_event_loop().run_until_complete(coro)


class Clock:
    def __init__(self, now=0.0):
        self.now = now

    def __call__(self):
        return self.now


def fresh(now=0.0):
    clock = Clock(now)
    return DeviceAccount(clock=clock), clock


def ms(snap, key):
    return snap[key] / 1e3  # the hand-made schedules below are in seconds


# -- the arithmetic -------------------------------------------------------------


def test_two_pipelined_dispatches_split_sojourn_and_count_the_union_once():
    acct, clock = fresh()
    a = acct.enqueue(1.0)
    b = acct.enqueue(1.2)  # behind a
    acct.ready(a, 2.0)
    acct.ready(b, 2.9)
    clock.now = 3.0
    snap = acct.snapshot()
    assert snap["dispatches"] == 2
    # a: 1.0 of service, none waited; b: started at a's ready
    assert ms(snap, "service_ms") == pytest.approx(1.0 + 0.9)
    assert ms(snap, "waited_ms") == pytest.approx(0.8)
    # service + waited is enqueue -> ready, what the device_dispatch phase sums
    assert ms(snap, "service_ms") + ms(snap, "waited_ms") == pytest.approx(
        (2.0 - 1.0) + (2.9 - 1.2)
    )
    # the union [1.0, 2.9], not the sum 2.7
    assert ms(snap, "enqueued_ms") == pytest.approx(1.9)
    assert ms(snap, "idle_ms") == pytest.approx(1.0 + 0.1)


def test_a_ready_seen_out_of_order_closes_the_programs_ahead_of_it():
    """Two waiter threads: the second program's ready is reported first.
    The stream is FIFO, so the first was ready by then too."""
    acct, clock = fresh()
    a = acct.enqueue(0.0)
    b = acct.enqueue(0.1)
    acct.ready(b, 2.0)  # b's waiter woke first
    snap = acct.snapshot()
    assert snap["dispatches"] == 2 and not a.open and not b.open
    assert ms(snap, "enqueued_ms") == pytest.approx(2.0)
    assert ms(snap, "service_ms") == pytest.approx(2.0)  # a's 2.0, b's none
    assert ms(snap, "waited_ms") == pytest.approx(1.9)
    acct.ready(a, 2.3)  # a's own late report finds nothing to do
    clock.now = 2.5
    late = acct.snapshot()
    assert late["dispatches"] == 2
    assert ms(late, "enqueued_ms") == pytest.approx(2.0)
    assert ms(late, "service_ms") == pytest.approx(2.0)


MARKS = dict(arrived=10.0, read=10.2, submitted=10.5, rows_ready=11.1, started=11.6)
ORDER = ("arrived", "read", "submitted", "rows_ready", "started")


@pytest.mark.parametrize(
    "start,want",
    [
        # S before the oldest item arrived: the answers going out before it
        (9.0, dict(finalize=1.0, read=0.2, parse=0.3, tokenize=0.6, loop=0.5, stage=0.4)),
        # S inside each slice in turn: the slices before it are empty
        (10.1, dict(finalize=0, read=0.1, parse=0.3, tokenize=0.6, loop=0.5, stage=0.4)),
        (10.4, dict(finalize=0, read=0, parse=0.1, tokenize=0.6, loop=0.5, stage=0.4)),
        (11.0, dict(finalize=0, read=0, parse=0, tokenize=0.1, loop=0.5, stage=0.4)),
        (11.5, dict(finalize=0, read=0, parse=0, tokenize=0, loop=0.1, stage=0.4)),
        (11.9, dict(finalize=0, read=0, parse=0, tokenize=0, loop=0, stage=0.1)),
    ],
)
def test_a_starved_interval_is_cut_at_the_oldest_items_timestamps(start, want):
    acct, _ = fresh()
    ahead = acct.enqueue(5.0)
    acct.request_open(8.0)  # somebody is in the server all along
    acct.ready(ahead, start)  # the device runs dry at S
    acct.ready(
        acct.enqueue(12.0, marks=tuple(MARKS[k] for k in ORDER)), 12.5
    )
    snap = acct.snapshot()
    assert ms(snap, "starved_ms") == pytest.approx(12.0 - start)
    for key in STARVED_KEYS:
        assert ms(snap["starved_by"], key) == pytest.approx(want[key]), key
    assert sum(snap["starved_by"].values()) == pytest.approx(
        snap["starved_ms"], abs=0.01
    )


def test_a_missing_or_late_timestamp_leaves_its_slice_empty():
    """No tokenizer pool (rows ready unknown) and rows that were ready only
    after ``_run_group`` began: the next slice takes the time."""
    acct, _ = fresh()
    acct.request_open(0.0)
    marks = (0.0, 0.1, 0.3, None, 0.5)  # tokenized inline, in the stage hop
    acct.ready(acct.enqueue(1.0, marks=marks), 2.0)
    by = acct.snapshot()["starved_by"]
    assert ms(by, "tokenize") == 0 and ms(by, "loop") == pytest.approx(0.2)
    assert ms(by, "stage") == pytest.approx(0.5)
    acct.request_close(2.0)
    acct.request_open(10.0)
    marks = (10.0, 10.1, 10.3, 10.9, 10.5)  # the stage hop waited for rows
    acct.ready(acct.enqueue(11.0, marks=marks), 12.0)
    by2 = acct.snapshot()["starved_by"]
    assert ms(by2, "tokenize") == pytest.approx(0.6)
    assert ms(by2, "loop") == pytest.approx(0.2)  # none added
    assert ms(by2, "stage") == pytest.approx(0.5 + 0.1)
    # an enqueue that brings no timestamps at all: the way to it is stage
    acct.request_close(12.0)
    acct.request_open(20.0)
    acct.ready(acct.enqueue(20.4), 21.0)
    assert ms(acct.snapshot()["starved_by"], "stage") == pytest.approx(1.0)


def test_enqueued_starved_and_idle_are_the_whole_wall():
    acct, clock = fresh(100.0)
    acct.request_open(101.0)  # 1.0 idle
    a = acct.enqueue(101.5)  # 0.5 starved
    acct.request_open(102.0)
    acct.ready(a, 103.0)  # 1.5 enqueued
    acct.request_close(103.25)  # starved runs on: the second is still in
    acct.request_close(103.5)  # 0.5 starved, ends with no enqueue
    clock.now = 105.0  # 1.5 idle
    snap = acct.snapshot()
    assert ms(snap, "enqueued_ms") == pytest.approx(1.5)
    assert ms(snap, "starved_ms") == pytest.approx(1.0)
    assert ms(snap, "idle_ms") == pytest.approx(2.5)
    assert ms(snap, "wall_ms") == pytest.approx(5.0)
    assert snap["wall_ms"] == pytest.approx(
        snap["enqueued_ms"] + snap["starved_ms"] + snap["idle_ms"], abs=0.005
    )
    assert snap["starved"]["count"] == 2 and snap["starved"]["max_ms"] == 500.0


def test_a_starved_interval_still_open_is_booked_when_it_ends():
    """Every identity holds at every reading: the open stretch is in none
    of the totals until its end says what held the device."""
    acct, clock = fresh()
    acct.request_open(1.0)
    clock.now = 1.4
    during = acct.snapshot()
    assert during["starved_ms"] == 0 and ms(during, "wall_ms") == pytest.approx(1.0)
    assert sum(during["starved_by"].values()) == during["starved_ms"]
    acct.ready(acct.enqueue(1.5, marks=(1.0, 1.1, 1.2, 1.3, 1.4)), 2.0)
    after = acct.snapshot()
    assert ms(after, "starved_ms") == pytest.approx(0.5)
    assert ms(after, "wall_ms") == pytest.approx(2.0)


def test_the_last_answer_of_a_burst_going_out_is_finalize():
    acct, _ = fresh()
    acct.request_open(0.0)
    acct.request_open(0.0)
    group = acct.enqueue(0.0, marks=(0.0,) * 5)
    acct.ready(group, 1.0)  # both answers are finalized and go out
    acct.request_close(1.02)
    acct.request_close(1.03)
    snap = acct.snapshot()
    assert ms(snap, "starved_ms") == pytest.approx(0.03)
    assert ms(snap["starved_by"], "finalize") == pytest.approx(0.03)
    assert snap["starved"]["count"] == 1  # one interval, whoever left in it


@pytest.mark.parametrize("gap_ms,stalls", [(STALL_MS - 1.0, 0), (STALL_MS + 1.0, 1)])
def test_a_stall_is_a_starved_interval_of_fifty_milliseconds(gap_ms, stalls):
    acct, _ = fresh()
    acct.request_open(0.0)
    acct.ready(acct.enqueue(0.0), 1.0)
    acct.ready(acct.enqueue(1.0 + gap_ms / 1e3), 2.0)
    snap = acct.snapshot()
    assert snap["stalls"] == stalls
    assert snap["starved_ms"] == pytest.approx(gap_ms)
    assert snap["starved"]["count"] == 1


def test_occupancy_is_a_union_and_needs_no_clamp():
    """Three programs in flight at once: the old gauge summed them to 3 and
    clamped; the views read the union."""
    acct, _ = fresh()
    tickets = [acct.enqueue(0.0 + i * 0.01, lane="latency") for i in range(3)]
    assert acct.occupancy(None, 0.0, 10.0) == pytest.approx(1.0, abs=1e-3)
    for i, ticket in enumerate(tickets):
        acct.ready(ticket, 4.0 + i)
    assert acct.occupancy("latency", 0.0, 10.0) == pytest.approx(0.6)
    assert acct.occupancy(None, 0.0, 6.0) == pytest.approx(1.0)
    assert acct.occupancy(None, 6.0, 10.0) == 0.0
    assert acct.occupancy("offline", 0.0, 10.0) == 0.0  # a lane never seen
    assert acct.occupancy(None, 5.0, 5.0) == 0.0  # no window


def test_a_program_given_up_on_is_closed_and_not_counted():
    acct, clock = fresh()
    acct.request_open(0.0)
    good = acct.enqueue(0.0)
    lost = acct.enqueue(0.5)
    acct.ready(lost, 1.0, served=False)  # a device fault at the waiter
    acct.ready(good, 2.0)
    clock.now = 3.0
    snap = acct.snapshot()
    assert snap["dispatches"] == 1 and ms(snap, "service_ms") == pytest.approx(2.0)
    assert ms(snap, "enqueued_ms") == pytest.approx(2.0)
    acct.request_close(3.0)
    assert ms(acct.snapshot(), "starved_ms") == pytest.approx(1.0)


def test_an_event_stamped_before_the_last_one_takes_effect_at_the_last_one():
    """Feeds come from four threads with their own stamps: time never runs
    backwards in the totals."""
    acct, _ = fresh()
    a = acct.enqueue(1.0)
    acct.request_open(0.5)  # stamped earlier, seen later
    acct.ready(a, 2.0)
    acct.request_close(1.5)  # likewise
    snap = acct.snapshot()
    assert ms(snap, "wall_ms") == pytest.approx(2.0)
    assert ms(snap, "enqueued_ms") == pytest.approx(1.0)
    assert ms(snap, "starved_ms") == 0.0


def test_what_an_enqueue_and_a_ready_cost():
    """A lock and a few additions a dispatch, at 2.4-7 dispatches a second
    (PERF.md gives the figure measured over 100,000; this holds it loosely
    enough for a shared test machine)."""
    acct = DeviceAccount()
    acct.request_open()
    n = 20_000
    marks = (0.0, 0.0, 0.0, 0.0, 0.0)
    t0 = time.perf_counter()
    for _ in range(n):
        now = time.perf_counter()
        acct.ready(acct.enqueue(now, "latency", marks), now)
    each_us = (time.perf_counter() - t0) / n * 1e6
    assert acct.snapshot()["dispatches"] == n
    assert each_us < 50.0, each_us


# -- the feeds: the seam ----------------------------------------------------------


def test_the_sink_carries_lane_and_timestamps_to_the_enqueue():
    obs.reset_phases()
    acct = obs.device_account()
    acct.request_open()
    asked = []

    def marks():
        # asked at the enqueue, not when the sink is made: the rows the
        # stage hop waits for are ready only by then
        asked.append(time.perf_counter())
        return (None,) * 5

    sink = seam.DispatchSink(lane="offline", marks=marks)
    assert not asked
    t0 = time.perf_counter()
    record = sink.add(seam.PendingDispatch("x", t0, None, wait=lambda out: None))
    assert len(asked) == 1 and asked[0] >= t0
    assert record.ticket.open and record.ticket.lane == "offline"
    time.sleep(0.002)
    assert acct.occupancy("offline", t0) > 0.9  # in flight since t0
    assert acct.occupancy("latency", t0) == 0.0
    seam.drain_sink(sink)
    assert not record.ticket.open
    snap = acct.snapshot()
    assert snap["dispatches"] == 1 and snap["starved"]["count"] == 1
    acct.request_close()
    obs.reset_phases()


def test_a_faulted_drain_leaves_no_program_open():
    obs.reset_phases()
    acct = obs.device_account()

    def boom(out):
        raise RuntimeError("device fault")

    sink = seam.DispatchSink()
    now = time.perf_counter()
    first = sink.add(seam.PendingDispatch("a", now, None, wait=lambda out: None))
    second = sink.add(seam.PendingDispatch("b", now, None, wait=boom))
    third = sink.add(seam.PendingDispatch("c", now, None, wait=lambda out: None))
    with pytest.raises(RuntimeError, match="device fault"):
        seam.drain_sink(sink)
    assert not (first.ticket.open or second.ticket.open or third.ticket.open)
    assert acct.snapshot()["dispatches"] == 1  # the one seen ready
    enqueued = acct.snapshot()["enqueued_ms"]
    time.sleep(0.01)
    assert acct.snapshot()["enqueued_ms"] == enqueued  # nothing left enqueued
    obs.reset_phases()


def test_the_inline_bracket_feeds_the_account_too():
    pytest.importorskip("jax")
    obs.reset_phases()
    assert seam.dispatch("inline(n=1)", lambda: 7) == 7  # no sink: it waits
    snap = obs.device_account().snapshot()
    assert snap["dispatches"] == 1 and snap["waited_ms"] == 0
    assert obs.phases_snapshot()["device_dispatch"]["count"] == 1
    # untimed: the account still hears of it, the phase does not
    seam.dispatch("inline(n=1)", lambda: 7, timed=False)
    assert obs.device_account().snapshot()["dispatches"] == 2
    assert obs.phases_snapshot()["device_dispatch"]["count"] == 1
    obs.reset_phases()


# -- the feeds: through the real gateway and batcher ------------------------------


def embedder_app(**kw):
    pytest.importorskip("jax")
    from test_hostspan import embedder_app as build

    return build(**kw)


async def with_client(app, fn):
    from aiohttp.test_utils import TestClient, TestServer

    client = TestClient(TestServer(app))
    await client.start_server()
    try:
        return await fn(client)
    finally:
        await client.close()


TEXTS = [f"candidate answer number {i} with a few words" for i in range(4)]


def test_a_request_alone_starves_the_device_on_its_way_in_and_out():
    obs.reset_phases()
    app = embedder_app()

    async def run(client):
        for _ in range(3):
            resp = await client.post("/consensus", data=json.dumps({"input": TEXTS}))
            assert resp.status == 200
            await asyncio.sleep(0.02)
        return await (await client.get("/metrics")).json()

    metrics = go(with_client(app, run))
    account = metrics["device_batcher"]["account"]
    assert account["dispatches"] == 3
    # a lone request: the way in ends with the enqueue, the way out with
    # its answer: two starved intervals a request
    assert account["starved"]["count"] == 6
    assert account["starved_ms"] > 0 and account["idle_ms"] > 0
    by = account["starved_by"]
    assert sum(by.values()) == pytest.approx(account["starved_ms"], abs=0.01)
    assert by["finalize"] > 0 and by["stage"] > 0 and by["tokenize"] > 0
    assert account["wall_ms"] == pytest.approx(
        account["enqueued_ms"] + account["starved_ms"] + account["idle_ms"],
        abs=0.01,
    )
    # the body's read has a phase of its own now
    assert metrics["phases"]["http_read"]["count"] == 3
    assert "overlap" not in metrics["phases"]
    batcher = metrics["device_batcher"]
    assert 0.0 <= batcher["busy_fraction"] <= 1.0
    assert 0.0 <= batcher["lanes"]["latency"]["busy_fraction"] <= 1.0
    obs.reset_phases()


def test_tokenizing_that_outlasts_the_window_is_put_down_to_tokenize(monkeypatch):
    """The stage hop begins while the rows are still being made (the
    batching window is 3 ms) and waits for them: the marks are asked at the
    enqueue, so the wait is ``tokenize``, not ``stage`` or ``loop``."""
    pytest.importorskip("jax")
    from llm_weighted_consensus_tpu.models.embedder import TpuEmbedder

    slow_s = 0.06
    tokenize = TpuEmbedder.tokenize

    def slow(self, *args, **kw):
        time.sleep(slow_s)
        return tokenize(self, *args, **kw)

    monkeypatch.setattr(TpuEmbedder, "tokenize", slow)
    obs.reset_phases()
    app = embedder_app()

    async def run(client):
        resp = await client.post("/consensus", data=json.dumps({"input": TEXTS}))
        assert resp.status == 200
        return await (await client.get("/metrics")).json()

    account = go(with_client(app, run))["device_batcher"]["account"]
    by = account["starved_by"]
    assert by["tokenize"] >= slow_s * 1e3 - 5.0, by
    assert by["loop"] == 0.0, by  # ``_run_group`` began before the rows were ready
    assert account["stalls"] == 1  # and a wait this long is a stall
    obs.reset_phases()


def test_a_request_that_never_answers_leaves_the_account():
    """A body over the cap raises above the handler (413) and a malformed
    one answers 400: neither is left in the server."""
    obs.reset_phases()
    app = embedder_app(max_body_bytes=2048)

    async def run(client):
        resp = await client.post("/consensus", data="x" * 4096)
        assert resp.status == 413
        resp = await client.post("/consensus", data="{not json")
        assert resp.status == 400
        await asyncio.sleep(0.05)

    go(with_client(app, run))
    acct = obs.device_account()
    before = acct.snapshot()
    time.sleep(0.02)
    after = acct.snapshot()
    # nobody in the server: the time since is idle, not starved
    assert after["idle_ms"] > before["idle_ms"]
    assert after["starved"]["count"] == before["starved"]["count"] == 2
    assert after["starved_by"]["finalize"] == pytest.approx(after["starved_ms"], abs=0.01)
    obs.reset_phases()


def test_the_prometheus_text_holds_the_account_as_counters():
    from llm_weighted_consensus_tpu.serve.gateway import METRICS_KEY
    from llm_weighted_consensus_tpu.serve.metrics import render_prometheus

    obs.reset_phases()
    app = embedder_app()

    async def run(client):
        resp = await client.post("/consensus", data=json.dumps({"input": TEXTS}))
        assert resp.status == 200
        return render_prometheus(app[METRICS_KEY])

    text = go(with_client(app, run))
    for state in ("enqueued", "starved", "idle"):
        assert f'lwc_device_time_ms_total{{state="{state}"}}' in text
    for part in ("service", "waited"):
        assert f'lwc_device_program_ms_total{{part="{part}"}}' in text
    assert "lwc_device_dispatches_total 1" in text
    for key in STARVED_KEYS:
        assert f'lwc_device_starved_by_ms_total{{phase="{key}"}}' in text
    assert "lwc_device_stalls_total" in text
    assert 'lwc_device_starved_interval_ms_count{device="0"} 2' in text
    assert 'lwc_lane_busy_fraction{lane="latency"}' in text
    obs.reset_phases()


# -- set-up's stopwatches -----------------------------------------------------------


def test_the_startup_section_is_shaped_for_a_value_reduction():
    from llm_weighted_consensus_tpu.serve import startup

    saved = dict(startup._SECONDS)
    startup._SECONDS.clear()
    try:
        with startup.stopwatch("weights"):
            time.sleep(0.01)
        startup.add("weights", 1.0)  # a second model's share adds up
        startup.add("warmup", 2.5)
        startup.listening()

        class Compiles:
            backend_compile_s = 4.25

        snap = startup.snapshot(Compiles())
        assert set(snap) == {"weights", "warmup", "listening", "compile"}
        assert all(set(row) == {"seconds"} for row in snap.values())
        assert 1.01 <= snap["weights"]["seconds"] < 1.5
        assert snap["warmup"]["seconds"] == 2.5 and snap["compile"]["seconds"] == 4.25
        # this process has been alive for a while, by the kernel's own stamp
        assert 0.0 < snap["listening"]["seconds"] < 24 * 3600
        assert "compile" not in startup.snapshot(None)
    finally:
        startup._SECONDS.clear()
        startup._SECONDS.update(saved)


def test_the_server_reports_its_startup_section(monkeypatch):
    """Through ``build_service``: the tiny embedder's checkpoint-less build
    and its warm-up each leave their seconds behind."""
    pytest.importorskip("jax")
    from llm_weighted_consensus_tpu.serve import Config, startup
    from llm_weighted_consensus_tpu.serve.__main__ import build_service

    saved = dict(startup._SECONDS)
    startup._SECONDS.clear()
    try:
        config = Config.from_env(
            {"EMBEDDER_MODEL": "test-tiny", "EMBEDDER_MAX_TOKENS": "32",
             "WARMUP": "4x16", "LWC_ALLOW_RANDOM_PARAMS": "1"}
        )
        monkeypatch.setenv("LWC_ALLOW_RANDOM_PARAMS", "1")
        app = build_service(config, fake_upstream=True)

        async def run(client):
            return await (await client.get("/metrics")).json()

        section = go(with_client(app, run))["startup"]
    finally:
        startup._SECONDS.clear()
        startup._SECONDS.update(saved)
    # no socket of the program's own in a test server: no ``listening``
    assert set(section) == {"weights", "warmup"}
    assert all(set(row) == {"seconds"} for row in section.values())
    assert section["weights"]["seconds"] > 0 and section["warmup"]["seconds"] > 0


# -- the benchmark's new readers find what they name --------------------------------

import glob  # noqa: E402
import os  # noqa: E402

LAYER_METRICS = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "bench", "layer_metrics",
)
NEW_METRICS = sorted(
    os.path.basename(path)[: -len(".json")]
    for pattern in ("device.st*", "dispatch.service_ms.*", "setup.*")
    for path in glob.glob(os.path.join(LAYER_METRICS, pattern + ".json"))
)


@pytest.fixture(scope="module")
def live_metrics():
    """``/metrics`` of the tiny server after one request, through
    ``build_service`` so that the ``startup`` section is there."""
    pytest.importorskip("jax")
    from llm_weighted_consensus_tpu.serve import Config, startup
    from llm_weighted_consensus_tpu.serve.__main__ import build_service

    class Compiles:
        backend_compile_s = 1.5

        def snapshot(self):
            return {"dir": "", "hits": 0, "misses": 0}

        def compiles(self):
            return {"backend_compiles": 1, "backend_compile_s": 1.5}

    saved = dict(startup._SECONDS)
    allowed = os.environ.get("LWC_ALLOW_RANDOM_PARAMS")
    os.environ["LWC_ALLOW_RANDOM_PARAMS"] = "1"
    try:
        config = Config.from_env(
            {"EMBEDDER_MODEL": "test-tiny", "EMBEDDER_MAX_TOKENS": "32",
             "WARMUP": "4x16"}
        )
        app = build_service(config, fake_upstream=True, compile_cache=Compiles())
        startup.listening()

        async def run(client):
            resp = await client.post("/consensus", data=json.dumps({"input": TEXTS}))
            assert resp.status == 200
            return await (await client.get("/metrics")).json()

        return go(with_client(app, run))
    finally:
        # as it was: conftest sets it for the session, and the files this
        # worker runs next (``--dist loadfile``) build embedders under it
        if allowed is None:
            os.environ.pop("LWC_ALLOW_RANDOM_PARAMS", None)
        else:
            os.environ["LWC_ALLOW_RANDOM_PARAMS"] = allowed
        startup._SECONDS.clear()
        startup._SECONDS.update(saved)


def dig(doc, path):
    """``bench/layers.py::dig``: one dotted path, a key a part."""
    for part in path.split("."):
        if not isinstance(doc, dict) or part not in doc:
            return None
        doc = doc[part]
    return doc


def test_the_new_metrics_are_the_ones_the_issue_names():
    assert len(NEW_METRICS) == 5 + 6 + 4, NEW_METRICS


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_new_metric_reads_a_number_off_the_live_document(name, live_metrics):
    with open(os.path.join(LAYER_METRICS, name + ".json"), encoding="utf-8") as f:
        read = json.load(f)["read"]
    assert read["from"] == "metrics"  # data only: no reducer, no code
    paths = (
        [read["path"]] if read["reduce"] in ("value", "delta")
        else [read["num"], read["den"]]
    )
    for path in paths:
        value = dig(live_metrics, path)
        assert isinstance(value, (int, float)) and value >= 0, (path, value)
    if name.startswith("device.starved_by."):
        assert read["scale"] == 100.0 and read["den"].endswith("account.starved_ms")
    if name.startswith("setup."):
        # ``value`` reads first key, last key, and what lies between as one
        first, _, rest = read["path"].partition(".")
        middle, _, last = rest.rpartition(".")
        assert live_metrics[first][middle][last] >= 0
