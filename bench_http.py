#!/usr/bin/env python
"""Gateway-level benchmark: answers/sec + p50 THROUGH the HTTP service.

Every number in bench.py / bench_all.py calls the embedder/clients
directly; this harness measures the product surface instead (VERDICT r2
item 3): real aiohttp server on a localhost TCP socket, JSON
serialization, SSE framing, executor hops, and the micro-batcher all
inside the timed path.  Three served endpoints:

1. ``/consensus`` — the device self-consistency scorer over HTTP: R
   concurrent clients each posting N=64 candidate texts.  The direct-call
   twin (embedder.consensus_confidence, same shapes — bench.py's metric)
   runs alongside, and the JSON reports the served/direct delta, which is
   the true cost of the HTTP+batcher edge.
2. ``/score/completions`` (streaming, fake upstream) — the reference's
   primary path (src/main.rs:189-232): ballot prompt injection, judge SSE
   round-trip, vote extraction, tally, SSE out with [DONE].
3. ``/multichat/completions`` (unary, ``consensus: true``) — N-generator
   fan-out + device consensus overlay (BASELINE config 2's serving form).

Prints ONE JSON line per endpoint: {"endpoint", "value", "unit",
"p50_ms", ...}, each naming the platform, device kind and device count
it ran on.  The bench runs on the devices JAX gives it and never changes
model or device by itself: --model defaults to bge-large-en everywhere
(pass ``--model test-tiny`` for a CPU run).  Flags: --model, --n,
--requests, --concurrency, --quick.

``--cache {off,cold,warm}`` replaces the endpoint trio with the consensus
result cache scenario (cache/): the SAME score request replayed K times
against a service started with SCORE_CACHE_TTL set (except ``off``),
reporting hit vs miss p50/p95 plus the served /metrics ``score_cache``
counters in the same one-JSON-line format.  ``cold`` starts the repeat
run on an empty cache (first request is the miss that fills it; the
in-flight rest collapse onto it); ``warm`` primes the entry untimed
first so every timed request is a pure hit.

``--faults [SPEC]`` replaces the trio with the resilience scenario
(resilience/): the service starts with ``FAULT_PLAN`` injecting seeded
stalls at the transport seam and ``RESILIENCE_QUORUM`` arming the
weight-quorum early exit, then a 3-judge score body is driven K times.
Reports the degraded-response rate and p50/p99 under injected stalls
plus the served /metrics ``resilience`` counters — the number that
matters is p99: with the quorum on, a stalled judge costs a ``degraded:
true`` frame instead of a stall-length tail latency.

``--overload`` replaces the trio with the admission-control scenario
(resilience/admission.py): the service starts with
``ADMISSION_MAX_INFLIGHT`` at the drive concurrency, then an OPEN-LOOP
arrival process offers ``--overload-factor`` (default 4) x the measured
closed-loop capacity.  Reports goodput, shed rate (503/504), and the
admitted-request p99 against the unloaded p99 — the acceptance bar is
admitted p99 within ~2x unloaded while the excess sheds retryably.

``--trace-overhead`` replaces the trio with the tracing-cost scenario
(obs/): the standard streaming score scenario against three fresh
services — tracing off, ``TRACE_SAMPLE_RATE=0.01``, and ``1.0`` —
reporting the p50 inflation of each traced setting over off.  The
acceptance bar is <= 2%% at 1%% sampling.

``--mesh-faults`` replaces the trio with the degraded-mesh scenario
(resilience/meshfault.py): a dp x tp mesh service with the fault
ladder armed and every rung AOT-warmed, driven through three phases —
healthy closed loop, the SAME traffic with a scripted persistent
device fault landing mid-burst (downsize + in-flight re-dispatch),
and after an explicit recovery probe upsizes back.  Reports goodput
and p99 per phase plus the served ``meshfault`` counters; the numbers
that matter are the degraded-phase goodput (~dp_rung/dp of healthy,
zero non-504 errors) and the absence of a compile stall at the
downsize (the rung executables were warmed at startup).

``--mixed-lengths`` replaces the trio with the continuous-batching
scenario (serve/packing.py): the SAME open-loop mixed-length
/consensus arrival process (short-head/long-tail lengths, mixed
candidate counts, shared conversation prefixes) driven at 1.5x the
padded service's closed-loop capacity against a bucketed-padded and a
packed (``PACKING_ENABLED=1``) service, reporting goodput for each
plus the served packing-efficiency counters (real tokens vs dispatched
slot tokens, prefix-dedup hits).

``--overlap`` replaces the trio with the host<->device overlap scenario
(models/dispatch_seam.py): the SAME closed-loop /consensus workload
against a ``METRICS_DEVICE_TIMING=1`` and a ``=0`` service, both with
``BATCH_PIPELINE=2``.  Reports the timing-on/timing-off goodput ratio
(the waiter seam means timing no longer re-serializes the pipeline;
acceptance >= 0.95) and the ``overlap`` gauge — device-busy union over
wall — read from the timing-on service over a saturated burst
(acceptance >= 0.8).

``--offline`` replaces the trio with the priority-class scenario
(serve/batcher.py two-lane scheduler + train/feed.py): one service with
``OFFLINE_ENABLED=1``, measured in three phases — an idle-mesh
``POST /v1/train/rescore`` drive (the offline lane alone; its merged
device occupancy is the near-100%-on-an-idle-mesh acceptance gauge), a
closed-loop /consensus baseline with the offline lane quiet, and the
SAME /consensus drive with a saturating rescore running concurrently.
The number that matters is the contended-vs-baseline latency p99
inflation: offline work yields at dispatch boundaries, so the latency
lane must pay at most one in-flight offline dispatch (<10%).

``--fleet`` replaces the trio with the fleet-tier scenario (fleet/):
THREE replicas on real localhost sockets sharing a static
``FLEET_PEERS`` roster and ONE counting fake upstream, driven through
three phases — cold (every fingerprint new, round-robin), warm (the
same fingerprints re-requested on a DIFFERENT replica than computed
them, so every hit crosses the peer-fetch wire), and a hot-key
stampede (one fingerprint, open fan-in across all three replicas).
Reports goodput and latency per phase plus the fake-upstream call
count per phase; the numbers that matter are warm-phase upstream
calls == 0 (peer fetch serves fleet-wide) and stampede upstream
calls == 1 (cross-replica single-flight).
"""

from __future__ import annotations

import argparse
import asyncio
import json
import statistics
import sys
import time

import numpy as np

sys.path.insert(0, ".")

from bench import (  # noqa: E402
    BASELINE_BASIS,
    BENCH_WORDS,
    bench_tokenizer,
    consensus_quality_summary,
    make_requests,
    phase_summary,
)
from llm_weighted_consensus_tpu.utils import device_summary  # noqa: E402


def emit(endpoint: str, value: float, unit: str, **extra) -> None:
    # every record carries the phase attribution of its timed window
    # (the service runs in-process, so the global aggregator — reset by
    # _drive after warmup — covers exactly the measured traffic)
    extra.setdefault("phase_breakdown", phase_summary())
    extra.setdefault("quality_summary", consensus_quality_summary())
    print(
        json.dumps(
            {
                "endpoint": endpoint,
                "value": round(value, 3),
                "unit": unit,
                "baseline_basis": BASELINE_BASIS,
                **device_summary(),
                **extra,
            }
        ),
        flush=True,
    )


def _percentiles(lat_ms: list) -> dict:
    lat = sorted(lat_ms)
    return {
        "p50_ms": round(statistics.median(lat), 2),
        "p99_ms": round(lat[min(len(lat) - 1, int(len(lat) * 0.99))], 2),
    }


def _quantile(lat_ms: list, q: float) -> float:
    lat = sorted(lat_ms)
    return round(lat[min(len(lat) - 1, int(len(lat) * q))], 2)


async def _start_service(
    model: str,
    window_ms: float,
    quantize: str = "none",
    cache_ttl_sec: float = 0.0,
    extra_env: dict = None,
):
    """The real service on real localhost TCP sockets (fake upstream
    included), exactly as ``python -m ...serve --fake-upstream`` wires it."""
    from aiohttp import web
    from aiohttp.test_utils import unused_port

    from llm_weighted_consensus_tpu.serve import Config
    from llm_weighted_consensus_tpu.serve.__main__ import (
        _fake_upstream,
        build_service,
    )

    fake_port = unused_port()

    config = Config.from_env(
        {
            "EMBEDDER_MODEL": model,
            "BATCH_WINDOW_MS": str(window_ms),
            "EMBEDDER_QUANTIZE": quantize,
            **(
                {"SCORE_CACHE_TTL": str(cache_ttl_sec)}
                if cache_ttl_sec > 0
                else {}
            ),
            **(extra_env or {}),
        }
    )
    app = build_service(
        config, fake_upstream=True, fake_upstream_port=fake_port
    )
    # the embedder in build_service used the env tokenizer path; give it
    # the bench WordPiece vocab so tokenization cost matches bench.py
    from llm_weighted_consensus_tpu.serve.gateway import BATCHER_KEY

    embedder = app[BATCHER_KEY].embedder if BATCHER_KEY in app else None
    if embedder is not None:
        embedder.tokenizer = bench_tokenizer()

    fake_app = web.Application()
    fake_app.router.add_post("/v1/chat/completions", _fake_upstream)
    fake_runner = web.AppRunner(fake_app)
    await fake_runner.setup()
    await web.TCPSite(fake_runner, "127.0.0.1", fake_port).start()

    runner = web.AppRunner(app)
    await runner.setup()
    port = unused_port()
    await web.TCPSite(runner, "127.0.0.1", port).start()
    return runner, fake_runner, port, embedder, app


async def _drive(session, url, bodies, concurrency, warmup_bursts=2):
    """Fire ``bodies`` at ``url`` with bounded concurrency; returns
    (total_seconds, per-request latencies ms).

    Warm-up: ``warmup_bursts`` full-concurrency bursts run UNTIMED first,
    so jit specializations for the batcher group sizes the burst produces
    (power-of-two buckets) compile outside the measured window — the
    same discipline bench.py applies to its shapes."""
    sem = asyncio.Semaphore(concurrency)
    lat = []

    async def one(body, record=True):
        async with sem:
            t0 = time.perf_counter()
            async with session.post(url, data=body) as resp:
                await resp.read()
                assert resp.status == 200, await resp.text()
            if record:
                lat.append((time.perf_counter() - t0) * 1e3)

    for _ in range(warmup_bursts):
        burst = (bodies * ((concurrency // len(bodies)) + 1))[:concurrency]
        await asyncio.gather(*(one(b, record=False) for b in burst))
    # scope the phase and quality aggregators to the timed window (the
    # summaries every emitted record embeds via bench.phase_summary /
    # bench.consensus_quality_summary)
    from llm_weighted_consensus_tpu.obs import reset_phases, reset_quality

    reset_phases()
    reset_quality()
    t0 = time.perf_counter()
    await asyncio.gather(*(one(b) for b in bodies))
    return time.perf_counter() - t0, lat


async def bench_consensus_endpoint(
    session, base, embedder, n, requests, concurrency, quantize="none"
):
    """Served /consensus vs the direct-call twin on identical inputs."""
    reqs = make_requests(requests, n)
    bodies = [
        json.dumps({"input": texts, "temperature": 0.05}) for texts in reqs
    ]
    # deterministic warm-up: compile every power-of-two R bucket the
    # batcher can produce under this concurrency, plus the r=1 path
    loop = asyncio.get_running_loop()
    ids, mask = embedder.tokenize(reqs[0])
    r_bucket = 1
    while True:
        r_eff = min(r_bucket, concurrency)
        rep_ids = np.tile(ids[None], (r_eff, 1, 1))
        rep_mask = np.tile(mask[None], (r_eff, 1, 1))
        await loop.run_in_executor(
            None,
            lambda ri=rep_ids, rm=rep_mask: np.asarray(
                embedder.consensus_confidence_tokens_many(ri, rm, 0.05)
            ),
        )
        if r_bucket >= concurrency:
            break
        r_bucket *= 2
    await loop.run_in_executor(
        None, lambda: np.asarray(embedder.consensus_confidence(reqs[0]))
    )

    total, lat = await _drive(
        session, base + "/consensus", bodies, concurrency
    )
    served = len(bodies) / total

    # direct-call twin (bench.py's pipelined shape): same texts, same
    # embedder, no HTTP — the delta IS the gateway overhead
    from concurrent.futures import ThreadPoolExecutor

    def direct(texts):
        return embedder.consensus_confidence(texts, temperature=0.05)

    direct(reqs[0])  # warm
    pool = ThreadPoolExecutor(8)
    t0 = time.perf_counter()
    futs = [pool.submit(np.asarray, direct(texts)) for texts in reqs]
    for f in futs:
        f.result()
    direct_rate = len(reqs) / (time.perf_counter() - t0)
    pool.shutdown()

    emit(
        "/consensus",
        served,
        "answers/sec",
        **_percentiles(lat),
        n_candidates=n,
        requests=len(bodies),
        concurrency=concurrency,
        quantize=quantize,
        direct_call_answers_per_sec=round(direct_rate, 3),
        served_vs_direct=round(served / direct_rate, 3),
        note=(
            "served = aiohttp + JSON + micro-batcher + device; "
            "direct = same shapes via embedder.consensus_confidence "
            "(bench.py's pipelined path)"
        ),
    )
    return served


async def bench_score_endpoint(session, base, requests, concurrency):
    """Streaming /score/completions against the local fake upstream."""
    rng = np.random.default_rng(3)
    bodies = []
    for i in range(requests):
        words = " ".join(rng.choice(BENCH_WORDS, size=24).tolist())
        bodies.append(
            json.dumps(
                {
                    "stream": True,
                    "messages": [{"role": "user", "content": words}],
                    "model": {"llms": [{"model": "fake-judge"}]},
                    "choices": [f"candidate a {i}", f"candidate b {i}"],
                }
            )
        )
    async with session.post(
        base + "/score/completions", data=bodies[0]
    ) as resp:
        assert resp.status == 200
        await resp.read()
    total, lat = await _drive(
        session, base + "/score/completions", bodies, concurrency
    )
    emit(
        "/score/completions",
        len(bodies) / total,
        "requests/sec",
        **_percentiles(lat),
        requests=len(bodies),
        concurrency=concurrency,
        note=(
            "streaming SSE incl. [DONE]; 1 judge via local fake upstream "
            "(ballot round-trip + vote extraction + tally per request)"
        ),
    )


async def bench_multichat_endpoint(
    session, base, embedder, requests, concurrency, generators=4
):
    """Unary /multichat/completions with the device consensus overlay."""
    if embedder is None:
        return
    bodies = []
    for i in range(requests):
        bodies.append(
            json.dumps(
                {
                    "consensus": True,
                    "messages": [
                        {"role": "user", "content": f"question {i}"}
                    ],
                    "model": {
                        "llms": [
                            {"model": f"fake-gen-{g}"}
                            for g in range(generators)
                        ]
                    },
                }
            )
        )
    async with session.post(
        base + "/multichat/completions", data=bodies[0]
    ) as resp:
        assert resp.status == 200
        body = await resp.json()
        assert "consensus" in body, "consensus overlay missing"
    total, lat = await _drive(
        session,
        base + "/multichat/completions",
        bodies,
        concurrency,
        # the consensus overlay's device shapes (n=generators) are only
        # reachable through the endpoint, so give the bursts one extra
        # pass to compile every bucket before the timed window
        warmup_bursts=3,
    )
    emit(
        "/multichat/completions",
        len(bodies) / total,
        "requests/sec",
        **_percentiles(lat),
        requests=len(bodies),
        concurrency=concurrency,
        generators=generators,
        note=(
            "unary multichat: N-generator fan-out via fake upstream + "
            "fused device consensus overlay (batched across concurrent "
            "requests)"
        ),
    )


def _score_body(content: str) -> str:
    return json.dumps(
        {
            "stream": True,
            "messages": [{"role": "user", "content": content}],
            "model": {"llms": [{"model": "fake-judge"}]},
            "choices": ["candidate a", "candidate b"],
        }
    )


async def bench_score_cache(session, base, requests, concurrency, mode):
    """Hit vs miss economics of the consensus result cache.

    Two timed samples through /score/completions: K DISTINCT bodies
    (every request a cache miss — the full ballot round-trip), then the
    SAME body K times (hits after the first fill).  ``warm`` primes the
    repeated body untimed so the hit sample is pure; ``cold`` lets the
    first timed repeat be the miss that fills the entry (concurrent
    repeats collapse onto it via single-flight); ``off`` runs the same
    traffic with the cache disabled, so "hits" cost the same as misses —
    the baseline the other two modes are read against.
    """
    rng = np.random.default_rng(17)

    def words():
        return " ".join(rng.choice(BENCH_WORDS, size=24).tolist())

    miss_bodies = [_score_body(f"miss {i}: {words()}") for i in range(requests)]
    hit_body = _score_body(f"hit: {words()}")

    # one throwaway request to pay connection/handler setup outside both
    # samples (its fingerprint differs from every timed body)
    async with session.post(
        base + "/score/completions", data=_score_body("warmup")
    ) as resp:
        assert resp.status == 200, await resp.text()
        await resp.read()

    # warmup_bursts=0 everywhere: a burst would FILL the cache with the
    # miss sample's bodies and turn the timed misses into hits
    _, miss_lat = await _drive(
        session, base + "/score/completions", miss_bodies, concurrency,
        warmup_bursts=0,
    )

    if mode == "warm":
        async with session.post(
            base + "/score/completions", data=hit_body
        ) as resp:
            assert resp.status == 200
            await resp.read()
    total, hit_lat = await _drive(
        session, base + "/score/completions", [hit_body] * requests,
        concurrency, warmup_bursts=0,
    )

    async with session.get(base + "/metrics") as resp:
        cache_stats = (await resp.json()).get("score_cache")

    emit(
        f"/score/completions?cache={mode}",
        len(hit_lat) / total,
        "requests/sec",
        cache=mode,
        requests=requests,
        concurrency=concurrency,
        miss_p50_ms=_quantile(miss_lat, 0.50),
        miss_p95_ms=_quantile(miss_lat, 0.95),
        hit_p50_ms=_quantile(hit_lat, 0.50),
        hit_p95_ms=_quantile(hit_lat, 0.95),
        score_cache=cache_stats,
        note=(
            "miss sample = K distinct score bodies (full judge "
            "round-trip); hit sample = one body x K (replayed from the "
            "consensus cache when enabled); score_cache = served "
            "/metrics counters after both samples"
        ),
    )


def _sse_objs(text: str) -> list:
    """Decode every ``data:`` frame of an SSE body (skipping [DONE])."""
    objs = []
    for frame in text.split("\n\n"):
        for line in frame.splitlines():
            if not line.startswith("data: "):
                continue
            payload = line[len("data: "):]
            if payload.strip() == "[DONE]":
                continue
            try:
                objs.append(json.loads(payload))
            except ValueError:
                pass
    return objs


async def bench_score_faults(session, base, requests, concurrency, spec):
    """Streaming /score/completions under injected stalls: the quorum
    early exit trades a stalled judge for a ``degraded: true`` frame, so
    the numbers to watch are degraded_rate and the p99 it buys."""
    body = json.dumps(
        {
            "stream": True,
            "messages": [{"role": "user", "content": "pick the best"}],
            "model": {
                "llms": [{"model": f"fake-judge-{g}"} for g in range(3)]
            },
            "choices": ["candidate a", "candidate b"],
        }
    )

    sem = asyncio.Semaphore(concurrency)
    lat = []
    degraded = 0
    errors = 0

    async def one():
        nonlocal degraded, errors
        async with sem:
            t0 = time.perf_counter()
            async with session.post(
                base + "/score/completions", data=body
            ) as resp:
                text = await resp.text()
                if resp.status != 200:
                    errors += 1
                    return
            lat.append((time.perf_counter() - t0) * 1e3)
            if any(o.get("degraded") for o in _sse_objs(text)):
                degraded += 1

    # one untimed warmup to pay handler/jit setup (it draws one slot of
    # the seeded plan; the timed sample stays deterministic given K)
    await one()
    lat.clear()
    degraded = 0
    errors = 0
    t0 = time.perf_counter()
    await asyncio.gather(*(one() for _ in range(requests)))
    total = time.perf_counter() - t0

    async with session.get(base + "/metrics") as resp:
        resilience = (await resp.json()).get("resilience")

    emit(
        "/score/completions?faults",
        len(lat) / total if total else 0.0,
        "requests/sec",
        **_percentiles(lat),
        requests=requests,
        concurrency=concurrency,
        fault_plan=spec,
        degraded_rate=round(degraded / max(1, requests), 3),
        error_rate=round(errors / max(1, requests), 3),
        resilience=resilience,
        note=(
            "3-judge streaming score under FAULT_PLAN stalls; "
            "RESILIENCE_QUORUM=0.6 cancels unflippable stragglers, so "
            "a stalled judge costs degraded:true instead of p99"
        ),
    )


async def bench_score_overload(
    session, base, requests, concurrency, factor
):
    """Open-loop overload (ISSUE PR 4 acceptance): arrivals at ``factor``
    x the measured closed-loop capacity, against a service whose
    admission gate caps in-flight work at ``concurrency``.  The numbers
    that matter: the p99 of ADMITTED requests must stay within ~2x the
    unloaded p99 (the whole point of shedding at the door), and the
    excess must come back as fast retryable 503s — goodput holds at
    capacity instead of collapsing under queueing."""
    rng = np.random.default_rng(7)

    def body(tag):
        words = " ".join(rng.choice(BENCH_WORDS, size=24).tolist())
        return _score_body(f"{tag}: {words}")

    url = base + "/score/completions"
    # phase A — idle p99: a trickle (closed loop, concurrency 2), the
    # floor nothing loaded can beat
    _, idle_lat = await _drive(
        session, url, [body(f"idle {i}") for i in range(requests)],
        2, warmup_bursts=1,
    )
    # phase B — the UNLOADED baseline: closed loop AT the admission
    # limit, offered == capacity, every request admitted.  This is the
    # service at its normal operating concurrency; the admitted set
    # under overload is held to ~2x ITS p99 (an idle-trickle baseline
    # would charge admission for ordinary concurrency queueing)
    cap_total, unloaded_lat = await _drive(
        session, url, [body(f"cap {i}") for i in range(requests)],
        concurrency, warmup_bursts=0,
    )
    capacity = len(unloaded_lat) / cap_total
    offered = capacity * factor

    # phase C — open loop at ``factor`` x capacity: arrivals fire on the
    # clock regardless of completions (the closed-loop limiter every
    # load tool defaults to would hide the overload — coordinated
    # omission), so the gateway MUST shed to protect the admitted set
    admitted_lat: list = []
    shed_503 = 0
    shed_504 = 0
    errors = 0

    async def one(b):
        nonlocal shed_503, shed_504, errors
        t0 = time.perf_counter()
        async with session.post(url, data=b) as resp:
            await resp.read()
            if resp.status == 200:
                admitted_lat.append((time.perf_counter() - t0) * 1e3)
            elif resp.status == 503:
                shed_503 += 1
            elif resp.status == 504:
                shed_504 += 1
            else:
                errors += 1

    arrivals = [body(f"overload {i}") for i in range(2 * requests)]
    interval = 1.0 / offered
    t_start = time.perf_counter()
    tasks = []
    for i, b in enumerate(arrivals):
        delay = t_start + i * interval - time.perf_counter()
        if delay > 0:
            await asyncio.sleep(delay)
        tasks.append(asyncio.ensure_future(one(b)))
    await asyncio.gather(*tasks)
    total = time.perf_counter() - t_start

    async with session.get(base + "/metrics") as resp:
        admission = (await resp.json()).get("admission")

    shed = shed_503 + shed_504
    unloaded_p99 = _quantile(unloaded_lat, 0.99)
    admitted_p99 = (
        _quantile(admitted_lat, 0.99) if admitted_lat else None
    )
    emit(
        "/score/completions?overload",
        len(admitted_lat) / total,
        "goodput requests/sec",
        requests=len(arrivals),
        concurrency=concurrency,
        overload_factor=factor,
        capacity_rps=round(capacity, 3),
        offered_rps=round(offered, 3),
        idle_p50_ms=_quantile(idle_lat, 0.50),
        idle_p99_ms=_quantile(idle_lat, 0.99),
        unloaded_p50_ms=_quantile(unloaded_lat, 0.50),
        unloaded_p99_ms=unloaded_p99,
        admitted_p50_ms=(
            _quantile(admitted_lat, 0.50) if admitted_lat else None
        ),
        admitted_p99_ms=admitted_p99,
        p99_inflation=(
            round(admitted_p99 / unloaded_p99, 3)
            if admitted_p99 and unloaded_p99
            else None
        ),
        shed_rate=round(shed / max(1, len(arrivals)), 3),
        shed_503=shed_503,
        shed_504=shed_504,
        errors=errors,
        admission=admission,
        note=(
            "open-loop arrivals at overload_factor x measured capacity "
            "vs ADMISSION_MAX_INFLIGHT=concurrency; goodput = admitted "
            "(200) completions/sec; unloaded = closed loop at the "
            "admission limit (offered == capacity); p99_inflation = "
            "admitted p99 / unloaded p99 (acceptance: <= ~2 under 4x "
            "overload)"
        ),
    )


async def bench_trace_overhead(args) -> None:
    """Tracing cost on the standard streaming score scenario (obs/):
    the SAME body set driven against three fresh services — tracing off
    (no sink: instrumentation short-circuits on one contextvar read),
    TRACE_SAMPLE_RATE=0.01 (spans built every request, 99% dropped at
    the sink), and 1.0 (every trace kept in the ring).  The acceptance
    number is p50 inflation at 1% vs off: the always-capture-the-bad-
    ones design is only free if healthy-path sampling costs <= ~2%."""
    import aiohttp
    import os

    # judge-latency floor, same reasoning as the overload scenario: with
    # a 0 ms fake upstream the whole request is event-loop CPU and the
    # "p50 inflation" degenerates into a pure CPU-ratio reading no
    # deployment ever sees; 25 ms approximates a fast real judge, so the
    # metric answers the question the knob poses — what tracing adds to
    # an end-to-end scored request
    os.environ.setdefault("FAKE_UPSTREAM_DELAY_MS", "25")
    # below saturation on purpose: at the trio's concurrency 16 this
    # in-process loop (client + service + fake upstream on one thread)
    # runs at 100% and p50 reads queue depth — every CPU microsecond
    # amplified by 1/(1-rho) — instead of request latency
    concurrency = min(args.concurrency, 4)

    settings = [("off", None), ("sampled_1pct", "0.01"), ("full", "1.0")]
    rounds = 5
    # all three services up-front, then INTERLEAVED drive rounds
    # (off, 1%, full, off, 1%, full, ...): the per-setting signal is
    # tens of microseconds per request, far below the run-to-run drift
    # of a fresh service (jit state, allocator, CPU frequency) —
    # interleaving plus a median over per-round p50s cancels the drift
    services = []
    for label, rate in settings:
        runner, fake_runner, port, _, _ = await _start_service(
            args.model,
            args.window_ms,
            args.quantize,
            extra_env=(
                {"TRACE_SAMPLE_RATE": rate} if rate is not None else None
            ),
        )
        services.append((label, rate, runner, fake_runner, port))

    # identical body set for every setting (seeded): the standard score
    # scenario from bench_score_endpoint
    rng = np.random.default_rng(3)
    bodies = []
    for i in range(args.requests):
        words = " ".join(rng.choice(BENCH_WORDS, size=24).tolist())
        bodies.append(
            json.dumps(
                {
                    "stream": True,
                    "messages": [{"role": "user", "content": words}],
                    "model": {"llms": [{"model": "fake-judge"}]},
                    "choices": [f"candidate a {i}", f"candidate b {i}"],
                }
            )
        )

    results = {}
    try:
        async with aiohttp.ClientSession(
            headers={"content-type": "application/json"}
        ) as session:
            pooled = {label: [] for label, _ in settings}
            round_p50s = {label: [] for label, _ in settings}
            totals = {label: 0.0 for label, _ in settings}
            for rnd in range(rounds):
                for label, rate, _, _, port in services:
                    total, lat = await _drive(
                        session,
                        f"http://127.0.0.1:{port}/score/completions",
                        bodies,
                        concurrency,
                        # warm each service once; later rounds are warm
                        warmup_bursts=2 if rnd == 0 else 0,
                    )
                    pooled[label].extend(lat)
                    round_p50s[label].append(_quantile(lat, 0.50))
                    totals[label] += total
            for label, rate, _, _, port in services:
                lat = pooled[label]
                entry = {
                    # headline p50: median over per-round p50s (robust
                    # to a slow round hitting one setting)
                    "p50_ms": round(
                        statistics.median(round_p50s[label]), 2
                    ),
                    "round_p50s_ms": round_p50s[label],
                    "p95_ms": _quantile(lat, 0.95),
                    "p99_ms": _quantile(lat, 0.99),
                    "requests_per_sec": round(
                        len(lat) / totals[label], 3
                    ),
                }
                if rate is not None:
                    async with session.get(
                        f"http://127.0.0.1:{port}/metrics"
                    ) as resp:
                        entry["traces"] = (await resp.json()).get("traces")
                results[label] = entry
    finally:
        for _, _, runner, fake_runner, _ in services:
            await runner.cleanup()
            await fake_runner.cleanup()

    off_p50 = results["off"]["p50_ms"]

    def inflation(label):
        if not off_p50:
            return None
        return round(
            (results[label]["p50_ms"] / off_p50 - 1.0) * 100.0, 2
        )

    emit(
        "/score/completions?trace-overhead",
        inflation("sampled_1pct") or 0.0,
        "p50_inflation_pct",
        requests=args.requests,
        concurrency=concurrency,
        rounds=rounds,
        p50_inflation_pct_full=inflation("full"),
        **{label: entry for label, entry in results.items()},
        note=(
            "streaming score scenario, one service per setting, "
            "interleaved drive rounds, p50 = median of per-round p50s; "
            "value = p50 inflation of TRACE_SAMPLE_RATE=0.01 over "
            "tracing off (acceptance <= 2%); 'traces' = served /metrics "
            "sink counters after the run"
        ),
    )


async def bench_mixed_lengths(args) -> None:
    """Continuous-batching goodput (ISSUE PR 7): the SAME open-loop
    mixed-length /consensus arrival process against two fresh services —
    the bucketed-padded path and the packed path (``PACKING_ENABLED=1``,
    serve/packing.py) — reporting goodput for each plus the served
    /metrics packing-efficiency counters.

    The workload is where padding hurts: request lengths drawn from a
    short-head/long-tail mixture (60% chat-short, 30% paragraph, 10%
    document) and candidate counts mixed per request, so the padded
    dispatch pads every row to the group seq bucket AND buckets each
    distinct (N, temperature) into its own group, while the packed path
    lays all of it end-to-end in shared rows.  Arrivals are open-loop at
    1.5x the PADDED service's measured closed-loop capacity — offered
    load the padded path cannot clear, so
    goodput separates the paths instead of both idling at the arrival
    rate.  Success (200 within deadline) counts toward goodput; the
    padding-waste ratios (real tokens / dispatched slot tokens) come
    from each service's own counters."""
    import aiohttp

    rng = np.random.default_rng(11)

    def text(words: int, tag: str) -> str:
        return f"{tag} " + " ".join(
            rng.choice(BENCH_WORDS, size=max(1, words)).tolist()
        )

    def request_texts(i: int) -> list:
        n = int(rng.choice([3, 4, 6, 8], p=[0.3, 0.3, 0.25, 0.15]))
        kind = rng.random()
        if kind < 0.6:
            words = int(rng.integers(4, 17))
        elif kind < 0.9:
            words = int(rng.integers(24, 65))
        else:
            words = int(rng.integers(96, 193))
        # shared conversation prefix + divergent answers: the realistic
        # consensus shape, and what PREFIX_DEDUP exists for
        prefix = text(words, f"ctx {i}")
        return [f"{prefix} answer {j} {text(6, 'a')}" for j in range(n)]

    bodies = [
        json.dumps({"input": request_texts(i), "temperature": 0.05})
        for i in range(args.requests)
    ]

    settings = [
        ("padded", {"PACKING_ENABLED": "0"}),
        ("packed", {"PACKING_ENABLED": "1"}),
    ]
    results = {}
    padded_capacity = None
    for label, env in settings:
        runner, fake_runner, port, _, _ = await _start_service(
            args.model, args.window_ms, args.quantize, extra_env=env
        )
        url = f"http://127.0.0.1:{port}/consensus"
        try:
            async with aiohttp.ClientSession(
                headers={"content-type": "application/json"}
            ) as session:
                # closed-loop capacity first (also the jit/AOT warmup);
                # the PADDED run's capacity sets the open-loop rate for
                # BOTH services, so they face identical offered load
                total, lat = await _drive(
                    session, url, bodies, args.concurrency
                )
                capacity = len(bodies) / total
                if padded_capacity is None:
                    padded_capacity = capacity
                offered = padded_capacity * 1.5

                ok_lat: list = []
                failures = 0

                async def one(b):
                    nonlocal failures
                    t0 = time.perf_counter()
                    try:
                        async with session.post(url, data=b) as resp:
                            await resp.read()
                            if resp.status == 200:
                                ok_lat.append(
                                    (time.perf_counter() - t0) * 1e3
                                )
                            else:
                                failures += 1
                    except Exception:
                        failures += 1

                interval = 1.0 / offered
                t_start = time.perf_counter()
                tasks = []
                for i, b in enumerate(bodies):
                    delay = (
                        t_start + i * interval - time.perf_counter()
                    )
                    if delay > 0:
                        await asyncio.sleep(delay)
                    tasks.append(asyncio.ensure_future(one(b)))
                await asyncio.gather(*tasks)
                open_total = time.perf_counter() - t_start

                async def batcher_stats():
                    async with session.get(
                        f"http://127.0.0.1:{port}/metrics"
                    ) as resp:
                        return (await resp.json()).get(
                            "device_batcher", {}
                        )

                stats = await batcher_stats()

                # saturated burst — every request in flight at once, so
                # dispatch groups (and packed calls) reach their full
                # size: the real-token/slot-token ratio HERE is the
                # packing-efficiency acceptance number (the open-loop
                # phase above under-fills calls by design: arrivals
                # trickle in at the padded path's pace)
                before = stats
                await _drive(
                    session, url, bodies, len(bodies), warmup_bursts=0
                )
                after = await batcher_stats()
                sat_key = "packing" if env["PACKING_ENABLED"] == "1" else "padded"
                d_real = (after[sat_key]["real_tokens"]
                          - before[sat_key]["real_tokens"])
                d_slot = (after[sat_key]["slot_tokens"]
                          - before[sat_key]["slot_tokens"])
            results[label] = {
                "goodput_rps": round(len(ok_lat) / open_total, 3),
                "closed_loop_rps": round(capacity, 3),
                "offered_rps": round(offered, 3),
                "failures": failures,
                **_percentiles(ok_lat or [0.0]),
                "saturated_efficiency": (
                    round(d_real / d_slot, 4) if d_slot else None
                ),
                "saturated_real_tokens": d_real,
                "saturated_slot_tokens": d_slot,
                "packing": after.get("packing"),
                "padded": after.get("padded"),
            }
        finally:
            await runner.cleanup()
            await fake_runner.cleanup()

    padded_good = results["padded"]["goodput_rps"]
    packed_good = results["packed"]["goodput_rps"]
    emit(
        "/consensus?mixed-lengths",
        packed_good,
        "goodput requests/sec",
        requests=args.requests,
        concurrency=args.concurrency,
        goodput_ratio=(
            round(packed_good / padded_good, 3) if padded_good else None
        ),
        closed_loop_ratio=(
            round(
                results["packed"]["closed_loop_rps"]
                / results["padded"]["closed_loop_rps"],
                3,
            )
            if results["padded"]["closed_loop_rps"]
            else None
        ),
        **results,
        note=(
            "open-loop mixed-length /consensus arrivals at 1.5x the "
            "PADDED service's closed-loop capacity, against "
            "bucketed-padded vs packed (PACKING_ENABLED=1) services; "
            "goodput = 200 completions/sec; saturated_efficiency = "
            "real-tokens/dispatched-slots measured from the served "
            "counters over an all-in-flight burst (full dispatch "
            "groups — the packing-efficiency acceptance number); "
            "'packing'/'padded' = each service's cumulative counters"
        ),
    )


async def bench_overlap(args) -> None:
    """Host<->device overlap (ISSUE 13): the same closed-loop /consensus
    workload against two fresh services — METRICS_DEVICE_TIMING=1 (the
    waiter-measured enqueue-to-ready timing) and =0 (no recording) —
    both with the dispatch pipeline armed.  Before the waiter seam,
    timing ON re-serialized the pipeline (the bracket held the dispatch
    thread for every timed call), so its goodput trailed timing OFF by
    the full device time; now both run the identical two-hop pipeline
    and the acceptance bar is timing-on goodput within 5% of timing-off.
    The second number is the ``overlap`` gauge (device-busy union /
    wall) read from the timing-on service's ``phases`` section over an
    all-in-flight saturated burst — >= 0.8 means the device stays busy
    while hosts stage, which is the whole point of the seam."""
    import aiohttp

    settings = [
        ("timing_off", {"METRICS_DEVICE_TIMING": "0", "BATCH_PIPELINE": "2"}),
        ("timing_on", {"METRICS_DEVICE_TIMING": "1", "BATCH_PIPELINE": "2"}),
    ]
    rounds = 3
    # both services up-front, then interleaved rounds (off, on, off,
    # on, ...) with a median over per-round goodput — same drift
    # discipline as the trace-overhead scenario: the 5% bar is below
    # fresh-service run-to-run noise
    services = []
    for label, env in settings:
        runner, fake_runner, port, _, _ = await _start_service(
            args.model, args.window_ms, args.quantize, extra_env=env
        )
        services.append((label, runner, fake_runner, port))

    bodies = [
        json.dumps({"input": texts, "temperature": 0.05})
        for texts in make_requests(args.requests, args.n)
    ]

    results = {}
    try:
        async with aiohttp.ClientSession(
            headers={"content-type": "application/json"}
        ) as session:
            round_rps = {label: [] for label, _ in settings}
            pooled = {label: [] for label, _ in settings}
            for rnd in range(rounds):
                for label, _, _, port in services:
                    total, lat = await _drive(
                        session,
                        f"http://127.0.0.1:{port}/consensus",
                        bodies,
                        args.concurrency,
                        warmup_bursts=2 if rnd == 0 else 0,
                    )
                    round_rps[label].append(round(len(lat) / total, 3))
                    pooled[label].extend(lat)
            for label, _, _, port in services:
                results[label] = {
                    "goodput_rps": round(
                        statistics.median(round_rps[label]), 3
                    ),
                    "round_rps": round_rps[label],
                    **_percentiles(pooled[label]),
                }

            # saturated burst on the timing-on service: every request in
            # flight at once, so consecutive pipelined groups keep the
            # device busy end to end — the overlap gauge HERE is the
            # acceptance number (phases reset at the drive's timed
            # window, so the gauge covers exactly this burst)
            on_port = services[1][3]
            await _drive(
                session,
                f"http://127.0.0.1:{on_port}/consensus",
                bodies,
                len(bodies),
                warmup_bursts=0,
            )
            async with session.get(
                f"http://127.0.0.1:{on_port}/metrics"
            ) as resp:
                served = await resp.json()
            phases = served.get("phases", {})
            batcher_stats = served.get("device_batcher", {})
    finally:
        for _, runner, fake_runner, _ in services:
            await runner.cleanup()
            await fake_runner.cleanup()

    on_good = results["timing_on"]["goodput_rps"]
    off_good = results["timing_off"]["goodput_rps"]
    emit(
        "/consensus?overlap",
        on_good,
        "goodput requests/sec",
        requests=len(bodies),
        concurrency=args.concurrency,
        n_candidates=args.n,
        rounds=rounds,
        goodput_ratio_on_vs_off=(
            round(on_good / off_good, 3) if off_good else None
        ),
        overlap=phases.get("overlap"),
        host_tokenizer_workers=batcher_stats.get("host_tokenizer_workers"),
        staging=batcher_stats.get("staging"),
        **results,
        note=(
            "closed-loop /consensus, METRICS_DEVICE_TIMING=1 vs =0, "
            "BATCH_PIPELINE=2, interleaved rounds with median goodput; "
            "acceptance = ratio >= 0.95 (timing on no longer "
            "re-serializes the pipeline) and overlap >= 0.8 over the "
            "all-in-flight saturated burst (device-busy union / wall "
            "from the timing-on service's phases section)"
        ),
    )


async def bench_mesh_faults(args) -> None:
    """Goodput through a device fault (resilience/meshfault.py): the
    /consensus scorer on a dp x tp mesh, driven closed-loop in three
    phases.  Phase A is the healthy baseline.  Before phase B the
    manager's DEVICE_FAULT_PLAN seam is armed with ``script=persistent``,
    so the first device dispatch of the burst dies exactly the way a
    lost chip does: the batcher classifies, downsizes one ladder rung
    (dp halves, tp survives), and re-dispatches the in-flight groups on
    the warmed rung executables — phase B's goodput and error counts ARE
    the incident behavior.  Phase C runs after an explicit recovery
    probe restores the full shape.  No open loop here on purpose: the
    question is what admitted requests experience through the shape
    change, not how the door sheds."""
    import aiohttp

    from llm_weighted_consensus_tpu.resilience.meshfault import (
        DeviceFaultPlan,
    )
    from llm_weighted_consensus_tpu.serve.gateway import (
        BATCHER_KEY,
        MESHFAULT_KEY,
    )

    dp, tp = 4, 2
    n = max(2, min(args.n, 8))
    concurrency = min(args.concurrency, 8)
    # EMBEDDER_MAX_TOKENS=32 + 96-word texts: every request tokenizes to
    # the cap, so serving traffic hits exactly the (n, 32) bucket the
    # WARMUP spec names and warm_ladder pre-compiles on every rung —
    # phase B measures the downsize, not a mid-incident compile
    extra_env = {
        "MESH_ENABLED": "1",
        "MESH_SHAPE": f"{dp}x{tp}",
        "MESH_FAULT_ENABLED": "1",
        "MESH_FAULT_TRANSIENT_RETRIES": "2",
        "EMBEDDER_MAX_TOKENS": "32",
        "WARMUP": f"{n}x32",
        "WARMUP_R": "2,4,8",
        "WARMUP_AOT": "1",
    }
    runner, fake_runner, port, embedder, app = await _start_service(
        args.model, args.window_ms, args.quantize, extra_env=extra_env
    )
    meshfault = app[MESHFAULT_KEY]
    batcher = app[BATCHER_KEY]
    base = f"http://127.0.0.1:{port}"
    url = base + "/consensus"

    bodies = [
        json.dumps({"input": texts, "temperature": 0.05})
        for texts in make_requests(args.requests, n)
    ]

    async def drive_counting(session, warmup_bursts=0):
        sem = asyncio.Semaphore(concurrency)
        lat: list = []
        shed_504 = 0
        errors = 0

        async def one(b, record=True):
            nonlocal shed_504, errors
            async with sem:
                t0 = time.perf_counter()
                async with session.post(url, data=b) as resp:
                    await resp.read()
                    if not record:
                        return
                    if resp.status == 200:
                        lat.append((time.perf_counter() - t0) * 1e3)
                    elif resp.status == 504:
                        shed_504 += 1
                    else:
                        errors += 1

        for _ in range(warmup_bursts):
            burst = (bodies * ((concurrency // len(bodies)) + 1))[
                :concurrency
            ]
            await asyncio.gather(*(one(b, record=False) for b in burst))
        t0 = time.perf_counter()
        await asyncio.gather(*(one(b) for b in bodies))
        total = time.perf_counter() - t0
        return {
            "goodput_rps": round(len(lat) / total, 3),
            **_percentiles(lat or [0.0]),
            "shed_504": shed_504,
            "errors": errors,
        }

    async def readyz(session):
        async with session.get(base + "/readyz") as resp:
            return resp.status, await resp.json()

    loop = asyncio.get_running_loop()
    try:
        async with aiohttp.ClientSession(
            headers={"content-type": "application/json"}
        ) as session:
            healthy = await drive_counting(session, warmup_bursts=2)

            # arm the seam: the next device dispatch dies persistently,
            # mid-burst, with the rest of the phase in flight behind it
            meshfault.fault_plan = DeviceFaultPlan.parse(
                "script=persistent"
            )
            degraded = await drive_counting(session)
            ready_status, ready_body = await readyz(session)

            # the recovery probe runs where downsize ran: on the
            # dispatch executor, serialized with device work
            recovered_ok = await loop.run_in_executor(
                batcher._executor, meshfault.try_recover
            )
            recovered = await drive_counting(session)
            ready_after_status, ready_after = await readyz(session)

            async with session.get(base + "/metrics") as resp:
                counters = (await resp.json()).get("meshfault")
    finally:
        await runner.cleanup()
        await fake_runner.cleanup()

    emit(
        "/consensus?mesh-faults",
        degraded["goodput_rps"],
        "goodput answers/sec",
        requests=len(bodies),
        concurrency=concurrency,
        n_candidates=n,
        mesh_shape=f"{dp}x{tp}",
        fault_plan="script=persistent",
        healthy=healthy,
        degraded=degraded,
        recovered=recovered,
        degraded_vs_healthy=(
            round(
                degraded["goodput_rps"] / healthy["goodput_rps"], 3
            )
            if healthy["goodput_rps"]
            else None
        ),
        recovered_vs_healthy=(
            round(
                recovered["goodput_rps"] / healthy["goodput_rps"], 3
            )
            if healthy["goodput_rps"]
            else None
        ),
        readyz_during=(ready_status, ready_body),
        readyz_after=(ready_after_status, ready_after),
        recovery_probe_ok=bool(recovered_ok),
        meshfault=counters,
        note=(
            "closed-loop /consensus on a dp x tp mesh through a "
            "scripted persistent device fault: value = degraded-phase "
            "goodput (one downsize rung, in-flight groups "
            "re-dispatched on warmed executables); acceptance = zero "
            "'errors' in every phase, readyz_during 200 with "
            "degraded_mesh, recovered goodput back near healthy"
        ),
    )


async def bench_offline(args) -> None:
    """Priority-class scheduling (ISSUE 20): does a saturated offline
    lane actually stay out of the latency lane's way?  One service with
    ``OFFLINE_ENABLED=1``; the rescore drives go through the REAL
    ``POST /v1/train/rescore`` endpoint so the whole seam (handler lock,
    synthetic feed, bounded-inflight drive, lane accounting) is inside
    the measured path.

    Phase A — idle occupancy: one rescore drive with no latency traffic;
    its ``offline_occupancy`` (merged busy coverage of the offline lane
    over the drive window) is the near-100%-on-an-idle-mesh acceptance
    gauge.  Phase B — the closed-loop /consensus baseline, offline lane
    quiet.  Phase C — the SAME /consensus drive with a saturating
    rescore running concurrently.  Offline work is preemptible at
    dispatch boundaries only, so an admitted latency request pays at
    most one in-flight offline dispatch: acceptance is contended p99
    within 10% of baseline while the offline lane still makes progress
    (contended-phase offline dispatches > 0)."""
    import aiohttp

    n_latency = max(2, min(args.n, 8))
    concurrency = min(args.concurrency, 8)
    offline_n = 4
    rounds = 5
    runner, fake_runner, port, embedder, _ = await _start_service(
        args.model,
        args.window_ms,
        args.quantize,
        # pipeline depth 1 makes the preemption quantum literally the
        # scheduler's contract — ONE in-flight dispatch: at depth 2 a
        # latency arrival can land behind two already-running offline
        # dispatches, and the measured inflation would charge the
        # pipeline, not the planner
        extra_env={
            "OFFLINE_ENABLED": "1",
            "OFFLINE_INFLIGHT": "4",
            "BATCH_PIPELINE": "1",
        },
    )
    base = f"http://127.0.0.1:{port}"

    reqs = make_requests(args.requests, n_latency)
    bodies = [
        json.dumps({"input": texts, "temperature": 0.05}) for texts in reqs
    ]

    # compile every latency R bucket up-front (the trio's discipline):
    # the contended phase's batching dynamics produce group sizes the
    # baseline never formed, and a mid-window jit compile would be
    # charged to the scheduler
    loop = asyncio.get_running_loop()
    ids, mask = embedder.tokenize(reqs[0])
    r_bucket = 1
    while True:
        r_eff = min(r_bucket, concurrency)
        rep_ids = np.tile(ids[None], (r_eff, 1, 1))
        rep_mask = np.tile(mask[None], (r_eff, 1, 1))
        await loop.run_in_executor(
            None,
            lambda ri=rep_ids, rm=rep_mask: np.asarray(
                embedder.consensus_confidence_tokens_many(ri, rm, 0.05)
            ),
        )
        if r_bucket >= concurrency:
            break
        r_bucket *= 2

    async def rescore(session, groups, seed, inflight=4):
        async with session.post(
            base + "/v1/train/rescore",
            data=json.dumps(
                {"groups": groups, "n": offline_n, "inflight": inflight,
                 "seed": seed},
            ),
        ) as resp:
            assert resp.status == 200, await resp.text()
            return await resp.json()

    async def lane_counters(session):
        async with session.get(base + "/metrics") as resp:
            return (await resp.json())["device_batcher"]["lanes"]

    try:
        async with aiohttp.ClientSession(
            headers={"content-type": "application/json"}
        ) as session:
            # phase A — idle-mesh occupancy.  The first drive pays the
            # offline group shape's jit compiles (inside busy intervals,
            # so occupancy stays honest either way); the second is the
            # reported steady-state gauge, with enough in-flight groups
            # (inflight=8) for back-to-back dispatches to pipeline.
            await rescore(session, max(16, args.requests // 2), seed=1)
            idle = await rescore(
                session, max(48, args.requests), seed=2, inflight=8
            )

            # phases B and C, interleaved (baseline, contended,
            # baseline, ...): the per-round signal — one in-flight
            # offline dispatch of tail latency — sits below fresh-run
            # drift, so a median over alternating rounds is the same
            # discipline the trace-overhead scenario uses
            base_p50s, base_p99s, base_lat = [], [], []
            cont_p50s, cont_p99s, cont_lat = [], [], []
            base_rps, cont_rps = [], []
            offline_dispatches_during = 0
            contended_rescore = None
            # round 0 is a full warmup pass, discarded: the first
            # CONTENDED round compiles whatever group shapes only the
            # mixed workload produces (staggered latency arrivals form
            # R buckets the quiet baseline never does), and that
            # one-time compile would otherwise be the pooled p99
            for rnd in range(rounds + 1):
                record = rnd > 0
                total, lat = await _drive(
                    session, base + "/consensus", bodies, concurrency,
                    warmup_bursts=2 if rnd == 0 else 0,
                )
                if record:
                    base_p50s.append(_quantile(lat, 0.50))
                    base_p99s.append(_quantile(lat, 0.99))
                    base_rps.append(len(lat) / total)
                    base_lat.extend(lat)

                # the offline lane saturated: a large rescore launched
                # first and still running while every timed latency
                # request flows.  inflight=2 keeps the queue non-empty
                # (each completion resubmits) while keeping the
                # preemption quantum — ONE in-flight offline dispatch,
                # the scheduler's contract — small; a deployment tunes
                # OFFLINE_INFLIGHT exactly this way
                lanes_before = await lane_counters(session)
                rescore_task = asyncio.ensure_future(
                    rescore(
                        session,
                        max(64, 4 * args.requests),
                        seed=3 + rnd,
                        inflight=2,
                    )
                )
                await asyncio.sleep(0.05)  # the drive is in flight
                total, lat = await _drive(
                    session, base + "/consensus", bodies, concurrency,
                    warmup_bursts=0,
                )
                contended_rescore = await rescore_task
                lanes_after = await lane_counters(session)
                if record:
                    cont_p50s.append(_quantile(lat, 0.50))
                    cont_p99s.append(_quantile(lat, 0.99))
                    cont_rps.append(len(lat) / total)
                    cont_lat.extend(lat)
                    offline_dispatches_during += (
                        lanes_after["offline"]["dispatches"]
                        - lanes_before["offline"]["dispatches"]
                    )
    finally:
        await runner.cleanup()
        await fake_runner.cleanup()

    # headline percentiles over the POOLED samples (rounds x requests):
    # a single round's p99 is one order statistic of ~requests samples
    # and swings +-20% between identical baseline rounds; the per-round
    # p99s ride along as the drift record
    base_p = {
        "p50_ms": statistics.median(base_p50s),
        "p99_ms": _quantile(base_lat, 0.99),
        "round_p99s_ms": base_p99s,
    }
    cont_p = {
        "p50_ms": statistics.median(cont_p50s),
        "p99_ms": _quantile(cont_lat, 0.99),
        "round_p99s_ms": cont_p99s,
    }
    emit(
        "/consensus?offline",
        (
            round(cont_p["p99_ms"] / base_p["p99_ms"], 3)
            if base_p["p99_ms"]
            else 0.0
        ),
        "contended/baseline p99 ratio",
        requests=len(bodies),
        concurrency=concurrency,
        n_candidates=n_latency,
        offline_n=offline_n,
        rounds=rounds,
        offline_occupancy_idle=idle["offline_occupancy"],
        idle_rescore=idle,
        baseline={
            "rps": round(statistics.median(base_rps), 3),
            **base_p,
        },
        contended={
            "rps": round(statistics.median(cont_rps), 3),
            **cont_p,
        },
        p99_inflation_pct=(
            round((cont_p["p99_ms"] / base_p["p99_ms"] - 1.0) * 100.0, 2)
            if base_p["p99_ms"]
            else None
        ),
        p50_inflation_pct=(
            round((cont_p["p50_ms"] / base_p["p50_ms"] - 1.0) * 100.0, 2)
            if base_p["p50_ms"]
            else None
        ),
        offline_dispatches_during_contention=offline_dispatches_during,
        contended_rescore={
            k: contended_rescore[k]
            for k in ("groups", "items", "errors", "offline_occupancy")
        },
        lanes=lanes_after,
        note=(
            "one OFFLINE_ENABLED=1 service; idle = POST /v1/train/rescore "
            "alone (offline_occupancy_idle is the near-100% idle-mesh "
            "acceptance gauge); contended = the same closed-loop "
            "/consensus drive with a saturating rescore in flight; "
            "acceptance = p99_inflation_pct < 10 (offline yields at "
            "dispatch boundaries) with "
            "offline_dispatches_during_contention > 0"
        ),
    )


async def bench_fleet(args) -> None:
    """Fleet-tier goodput (fleet/): three replicas on real localhost
    sockets, one shared counting fake upstream — cold / warm (every hit
    crosses the peer wire) / hot-key stampede (one upstream fan-out
    fleet-wide)."""
    import os

    import aiohttp
    from aiohttp import web
    from aiohttp.test_utils import unused_port

    from llm_weighted_consensus_tpu.serve import Config
    from llm_weighted_consensus_tpu.serve.__main__ import (
        _fake_upstream,
        build_service,
    )

    # judge-latency floor, same reasoning as --overload: with a 0 ms
    # upstream every request is event-loop CPU and goodput reads
    # single-core contention (client + 3 services + fake upstream share
    # one thread), not the peer protocol's cost
    os.environ.setdefault("FAKE_UPSTREAM_DELAY_MS", "25")
    concurrency = min(args.concurrency, 8)

    calls = {"n": 0}

    async def counting_upstream(request):
        calls["n"] += 1
        return await _fake_upstream(request)

    fake_port = unused_port()
    fake_app = web.Application()
    fake_app.router.add_post("/v1/chat/completions", counting_upstream)
    fake_runner = web.AppRunner(fake_app)
    await fake_runner.setup()
    await web.TCPSite(fake_runner, "127.0.0.1", fake_port).start()

    ports = [unused_port() for _ in range(3)]
    roster = ",".join(f"http://127.0.0.1:{p}" for p in ports)
    runners = [fake_runner]
    bases = []
    for port in ports:
        config = Config.from_env(
            {
                # host-only replicas: the fleet tier is a score-path
                # feature; the AOT store covers the device side
                "EMBEDDER_MODEL": "",
                "SCORE_CACHE_TTL": "600",
                "FLEET_SELF": f"http://127.0.0.1:{port}",
                "FLEET_PEERS": roster,
                "OPENAI_API_BASE": f"http://127.0.0.1:{fake_port}/v1",
                "OPENAI_API_KEY": "bench-key",
            }
        )
        runner = web.AppRunner(build_service(config))
        await runner.setup()
        await web.TCPSite(runner, "127.0.0.1", port).start()
        runners.append(runner)
        bases.append(f"http://127.0.0.1:{port}")

    rng = np.random.default_rng(3)
    bodies = []
    for i in range(args.requests):
        words = " ".join(rng.choice(BENCH_WORDS, size=24).tolist())
        bodies.append(
            json.dumps(
                {
                    "stream": True,
                    "messages": [{"role": "user", "content": words}],
                    "model": {"llms": [{"model": "fake-judge"}]},
                    "choices": [f"candidate a {i}", f"candidate b {i}"],
                }
            )
        )

    try:
        async with aiohttp.ClientSession(
            headers={"content-type": "application/json"}
        ) as session:

            async def drive(targets_and_bodies):
                sem = asyncio.Semaphore(concurrency)
                lat = []

                async def one(base, body):
                    async with sem:
                        t0 = time.perf_counter()
                        async with session.post(
                            base + "/score/completions", data=body
                        ) as resp:
                            await resp.read()
                            assert resp.status == 200, await resp.text()
                        lat.append((time.perf_counter() - t0) * 1e3)

                t0 = time.perf_counter()
                await asyncio.gather(
                    *(one(b, body) for b, body in targets_and_bodies)
                )
                return time.perf_counter() - t0, lat

            def phase(total, lat, upstream):
                return {
                    "rps": round(len(lat) / total, 2),
                    **_percentiles(lat),
                    "upstream_calls": upstream,
                }

            # cold: every fingerprint new, round-robin across replicas
            c0 = calls["n"]
            cold_total, cold_lat = await drive(
                [(bases[i % 3], b) for i, b in enumerate(bodies)]
            )
            cold = phase(cold_total, cold_lat, calls["n"] - c0)
            # let fire-and-forget publishes land on the owners
            await asyncio.sleep(0.3)

            # warm: same fingerprints on a DIFFERENT replica than
            # computed them — every hit crosses the peer-fetch wire
            c0 = calls["n"]
            warm_total, warm_lat = await drive(
                [(bases[(i + 1) % 3], b) for i, b in enumerate(bodies)]
            )
            warm = phase(warm_total, warm_lat, calls["n"] - c0)

            # hot-key stampede: ONE new fingerprint, open fan-in
            hot_body = json.dumps(
                {
                    "stream": True,
                    "messages": [
                        {"role": "user", "content": "the hot question"}
                    ],
                    "model": {"llms": [{"model": "fake-judge"}]},
                    "choices": ["candidate a", "candidate b"],
                }
            )
            c0 = calls["n"]
            hot_total, hot_lat = await drive(
                [
                    (bases[i % 3], hot_body)
                    for i in range(len(bodies))
                ]
            )
            hot = phase(hot_total, hot_lat, calls["n"] - c0)

            fleet_counters = []
            for base in bases:
                async with session.get(base + "/metrics") as resp:
                    fleet_counters.append(
                        (await resp.json()).get("fleet", {})
                    )

        emit(
            "/score/completions?fleet",
            warm["rps"],
            "requests/sec warm goodput",
            requests=len(bodies),
            concurrency=concurrency,
            replicas=3,
            cold=cold,
            warm=warm,
            hot_stampede=hot,
            peer_fetch_hits=sum(
                c.get("peer_fetch", {}).get("hits", 0)
                for c in fleet_counters
            ),
            lease_waits=sum(
                c.get("leases", {}).get("waits", 0)
                for c in fleet_counters
            ),
            note=(
                "3 replicas, one counting fake upstream; acceptance = "
                "warm upstream_calls == 0 (peer fetch serves "
                "fleet-wide) and hot_stampede upstream_calls == 1 "
                "(cross-replica single-flight)"
            ),
        )
    finally:
        for runner in runners:
            await runner.cleanup()


async def bench_fleet_partition(args) -> None:
    """Degraded-goodput under a network partition (fleet/faults.py):
    the same three-replica fleet as ``--fleet``, but a second trio is
    started with ``FLEET_FAULT_PLAN`` env specs carving a ``{a} | {b,c}``
    cut out of pure configuration (``blackhole=1.0,to=...`` per
    replica).  Cold populates, warm rotates every fingerprint onto a
    different replica — under the cut, cross-partition peer fetches
    blackhole, breakers open, quarantine re-homes the severed keys, and
    every request still answers 200 with clean frames from local
    compute.  Acceptance: zero errors and zero degraded frames in the
    partitioned warm round, warm upstream == 0 when healthy."""
    import os

    import aiohttp
    from aiohttp import web
    from aiohttp.test_utils import unused_port

    from llm_weighted_consensus_tpu.serve import Config
    from llm_weighted_consensus_tpu.serve.__main__ import (
        _fake_upstream,
        build_service,
    )

    os.environ.setdefault("FAKE_UPSTREAM_DELAY_MS", "25")
    concurrency = min(args.concurrency, 8)
    requests = min(args.requests, 60)

    calls = {"n": 0}

    async def counting_upstream(request):
        calls["n"] += 1
        return await _fake_upstream(request)

    fake_port = unused_port()
    fake_app = web.Application()
    fake_app.router.add_post("/v1/chat/completions", counting_upstream)
    fake_runner = web.AppRunner(fake_app)
    await fake_runner.setup()
    await web.TCPSite(fake_runner, "127.0.0.1", fake_port).start()

    rng = np.random.default_rng(17)
    bodies = []
    for i in range(requests):
        words = " ".join(rng.choice(BENCH_WORDS, size=24).tolist())
        bodies.append(
            json.dumps(
                {
                    "stream": True,
                    "messages": [{"role": "user", "content": words}],
                    "model": {"llms": [{"model": "fake-judge"}]},
                    "choices": [f"candidate a {i}", f"candidate b {i}"],
                }
            )
        )

    async def start_trio(fault_plan_for):
        ports = [unused_port() for _ in range(3)]
        urls = [f"http://127.0.0.1:{p}" for p in ports]
        trio_runners, bases = [], []
        for i, port in enumerate(ports):
            env = {
                "EMBEDDER_MODEL": "",
                "SCORE_CACHE_TTL": "600",
                "FLEET_SELF": urls[i],
                "FLEET_PEERS": ",".join(urls),
                # bound the blackhole burn so degraded goodput reads the
                # breaker/quarantine recovery, not a 2 s default timeout
                "FLEET_FETCH_TIMEOUT_MILLIS": "150",
                "OPENAI_API_BASE": f"http://127.0.0.1:{fake_port}/v1",
                "OPENAI_API_KEY": "bench-key",
            }
            plan = fault_plan_for(i, urls)
            if plan:
                env["FLEET_FAULT_PLAN"] = plan
            runner = web.AppRunner(build_service(Config.from_env(env)))
            await runner.setup()
            await web.TCPSite(runner, "127.0.0.1", port).start()
            trio_runners.append(runner)
            bases.append(urls[i])
        return trio_runners, bases

    async def drive(session, bases):
        """cold (populate) then warm (rotated) rounds; returns the warm
        phase dict + violation count."""
        bad = {"n": 0}

        async def round_at(offset):
            sem = asyncio.Semaphore(concurrency)
            lat = []

            async def one(i, body):
                async with sem:
                    t0 = time.perf_counter()
                    async with session.post(
                        bases[(i + offset) % 3] + "/score/completions",
                        data=body,
                    ) as resp:
                        payload = await resp.read()
                        assert resp.status == 200, payload[:200]
                        if (
                            b'"degraded":true' in payload
                            or b"corrupt" in payload
                        ):
                            bad["n"] += 1
                    lat.append((time.perf_counter() - t0) * 1e3)

            t0 = time.perf_counter()
            await asyncio.gather(
                *(one(i, b) for i, b in enumerate(bodies))
            )
            return time.perf_counter() - t0, lat

        c0 = calls["n"]
        await round_at(0)
        cold_upstream = calls["n"] - c0
        await asyncio.sleep(0.3)  # publishes land
        c0 = calls["n"]
        total, lat = await round_at(1)
        return {
            "rps": round(len(lat) / total, 2),
            **_percentiles(lat),
            "upstream_calls": calls["n"] - c0,
            "cold_upstream_calls": cold_upstream,
            "dirty_frames": bad["n"],
        }

    def healthy_plan(i, urls):
        return None

    def partition_plan(i, urls):
        # {urls[0]} | {urls[1], urls[2]}, carved from env config alone
        if i == 0:
            return f"blackhole=1.0,to={urls[1]}|{urls[2]}"
        return f"blackhole=1.0,to={urls[0]}"

    runners = [fake_runner]
    try:
        async with aiohttp.ClientSession(
            headers={"content-type": "application/json"}
        ) as session:
            trio, bases = await start_trio(healthy_plan)
            runners += trio
            healthy = await drive(session, bases)

            trio, bases = await start_trio(partition_plan)
            runners += trio
            partitioned = await drive(session, bases)

            fleet_counters = []
            for base in bases:
                async with session.get(base + "/metrics") as resp:
                    fleet_counters.append(
                        (await resp.json()).get("fleet", {})
                    )

        emit(
            "/score/completions?fleet-partition",
            partitioned["rps"],
            "requests/sec degraded goodput (warm round under partition)",
            requests=len(bodies),
            concurrency=concurrency,
            replicas=3,
            healthy_warm=healthy,
            partitioned_warm=partitioned,
            local_fallbacks=sum(
                c.get("local_fallbacks", 0) for c in fleet_counters
            ),
            peer_errors=sum(
                c.get("peer_fetch", {}).get("errors", 0)
                for c in fleet_counters
            ),
            quarantines=sum(
                c.get("health", {}).get("quarantines", 0)
                for c in fleet_counters
            ),
            note=(
                "3 replicas; partition carved via FLEET_FAULT_PLAN "
                "blackhole=1.0,to=... env specs ({a} | {b,c}); "
                "acceptance = healthy warm upstream_calls == 0, "
                "partitioned warm all-200 with dirty_frames == 0 "
                "(severed replicas recompute locally, clean)"
            ),
        )
    finally:
        for runner in runners:
            await runner.cleanup()


async def main_async(args) -> None:
    import aiohttp

    if args.fleet_partition:
        await bench_fleet_partition(args)
        return
    if args.trace_overhead:
        await bench_trace_overhead(args)
        return
    if args.mesh_faults:
        await bench_mesh_faults(args)
        return
    if args.mixed_lengths:
        await bench_mixed_lengths(args)
        return
    if args.overlap:
        await bench_overlap(args)
        return
    if args.fleet:
        await bench_fleet(args)
        return
    if args.offline:
        await bench_offline(args)
        return
    overload_env = None
    if args.overload:
        overload_env = {
            "ADMISSION_MAX_INFLIGHT": str(args.concurrency),
            "ADMISSION_MAX_QUEUE_DEPTH": str(2 * args.concurrency),
        }
        # judge-latency floor: admitted requests must HOLD their slot
        # for a realistic interval, or the scenario degenerates into
        # measuring shed-processing event-loop contention
        import os

        os.environ.setdefault("FAKE_UPSTREAM_DELAY_MS", "100")
    runner, fake_runner, port, embedder, _ = await _start_service(
        args.model,
        args.window_ms,
        args.quantize,
        cache_ttl_sec=(
            600.0 if args.cache in ("cold", "warm") else 0.0
        ),
        extra_env=(
            {"FAULT_PLAN": args.faults, "RESILIENCE_QUORUM": "0.6"}
            if args.faults is not None
            else overload_env
        ),
    )
    base = f"http://127.0.0.1:{port}"
    try:
        async with aiohttp.ClientSession(
            headers={"content-type": "application/json"}
        ) as session:
            if args.overload:
                await bench_score_overload(
                    session, base, args.requests, args.concurrency,
                    args.overload_factor,
                )
                return
            if args.faults is not None:
                await bench_score_faults(
                    session, base, args.requests, args.concurrency,
                    args.faults,
                )
                return
            if args.cache is not None:
                await bench_score_cache(
                    session, base, args.requests, args.concurrency,
                    args.cache,
                )
                return
            if embedder is not None:
                await bench_consensus_endpoint(
                    session,
                    base,
                    embedder,
                    args.n,
                    args.requests,
                    args.concurrency,
                    quantize=args.quantize,
                )
            await bench_score_endpoint(
                session, base, args.requests, args.concurrency
            )
            await bench_multichat_endpoint(
                session, base, embedder, args.requests, args.concurrency
            )
    finally:
        await runner.cleanup()
        await fake_runner.cleanup()


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--model", default="bge-large-en")
    parser.add_argument(
        "--quantize",
        choices=("none", "int8"),
        default="none",
        help="serve the embedder W8A8 (EMBEDDER_QUANTIZE passthrough)",
    )
    parser.add_argument(
        "--cache",
        choices=("off", "cold", "warm"),
        default=None,
        help="run the consensus-cache scenario instead of the endpoint "
        "trio: same score request replayed K times, hit vs miss p50/p95 "
        "(off = cache disabled baseline, cold = first repeat fills the "
        "entry inside the timed window, warm = entry primed untimed)",
    )
    parser.add_argument(
        "--faults",
        nargs="?",
        default=None,
        const="seed=42,stall_first=0.2,stall_mid=0.1,stall_ms=400",
        metavar="SPEC",
        help="run the resilience scenario instead of the endpoint trio: "
        "service started with FAULT_PLAN=SPEC (default: seeded 30%% "
        "stall mix) + RESILIENCE_QUORUM=0.6; reports degraded-response "
        "rate and p99 under the injected stalls",
    )
    parser.add_argument(
        "--overload",
        action="store_true",
        help="run the overload scenario instead of the endpoint trio: "
        "service started with ADMISSION_MAX_INFLIGHT=concurrency, then "
        "open-loop arrivals at --overload-factor x measured capacity; "
        "reports goodput, shed rate, and admitted-p99 vs unloaded-p99",
    )
    parser.add_argument("--overload-factor", type=float, default=4.0)
    parser.add_argument(
        "--trace-overhead",
        action="store_true",
        help="run the tracing-cost scenario instead of the endpoint "
        "trio: the standard streaming score scenario against three "
        "fresh services (tracing off / TRACE_SAMPLE_RATE=0.01 / 1.0); "
        "reports p50 inflation per setting vs off",
    )
    parser.add_argument(
        "--mesh-faults",
        action="store_true",
        help="run the degraded-mesh scenario instead of the endpoint "
        "trio: a 4x2 mesh service with the fault ladder armed and "
        "AOT-warmed, /consensus driven healthy -> scripted persistent "
        "device fault (downsize + in-flight re-dispatch) -> recovery; "
        "reports goodput and p99 per phase plus the served meshfault "
        "counters",
    )
    parser.add_argument(
        "--mixed-lengths",
        action="store_true",
        help="run the continuous-batching scenario instead of the "
        "endpoint trio: the same open-loop mixed-length /consensus "
        "arrival process against a bucketed-padded and a packed "
        "(PACKING_ENABLED=1) service; reports goodput for each plus "
        "the served packing-efficiency counters",
    )
    parser.add_argument(
        "--overlap",
        action="store_true",
        help="run the host<->device overlap scenario instead of the "
        "endpoint trio: the same closed-loop /consensus workload against "
        "METRICS_DEVICE_TIMING=1 vs =0 services (BATCH_PIPELINE=2); "
        "reports the goodput ratio (acceptance >= 0.95) and the overlap "
        "gauge over a saturated burst (acceptance >= 0.8)",
    )
    parser.add_argument(
        "--offline",
        action="store_true",
        help="run the priority-class scenario instead of the endpoint "
        "trio: OFFLINE_ENABLED=1 service, idle-mesh /v1/train/rescore "
        "occupancy, then closed-loop /consensus baseline vs the same "
        "drive with a saturating rescore concurrent; acceptance = "
        "contended p99 within 10%% of baseline, idle offline occupancy "
        "near 100%%",
    )
    parser.add_argument(
        "--fleet",
        action="store_true",
        help="run the fleet-tier scenario instead of the endpoint trio: "
        "3 replicas sharing a FLEET_PEERS roster + one counting fake "
        "upstream; cold / warm (peer-fetch) / hot-key-stampede goodput; "
        "acceptance = warm upstream_calls 0, stampede upstream_calls 1",
    )
    parser.add_argument(
        "--fleet-partition",
        action="store_true",
        help="run the fleet-partition scenario instead of the endpoint "
        "trio: the --fleet trio healthy vs. a second trio with a "
        "{a} | {b,c} cut carved via FLEET_FAULT_PLAN env specs; "
        "reports degraded warm goodput under the partition; acceptance "
        "= all-200 with zero degraded frames both ways",
    )
    parser.add_argument("--n", type=int, default=64)
    parser.add_argument("--requests", type=int, default=100)
    parser.add_argument("--concurrency", type=int, default=16)
    parser.add_argument("--window-ms", type=float, default=3.0)
    parser.add_argument(
        "--quick", action="store_true", help="small counts for CI/CPU"
    )
    args = parser.parse_args()
    if args.quick:
        args.requests = min(args.requests, 20)
        args.n = min(args.n, 8)
    from llm_weighted_consensus_tpu.serve.config import (
        configure_compile_cache,
    )

    configure_compile_cache()
    if args.mesh_faults:
        import jax

        if jax.device_count() < 8:
            raise SystemExit(
                f"--mesh-faults serves a 4x2 mesh and needs 8 devices; JAX "
                f"has {jax.device_count()} (for a CPU run set XLA_FLAGS="
                "--xla_force_host_platform_device_count=8)"
            )
    asyncio.run(main_async(args))


if __name__ == "__main__":
    main()
