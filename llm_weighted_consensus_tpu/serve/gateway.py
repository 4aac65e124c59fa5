"""The aiohttp application: SSE endpoints over the consensus engine.

Frame semantics (main.rs:142-232): streaming responses are SSE ``data:``
frames — chunk JSON, or ``{code, message}`` ResponseError JSON for
mid-stream errors — terminated by ``data: [DONE]``.  Pre-stream failures
and unary failures map to HTTP status + the error's message JSON.
"""

from __future__ import annotations

import asyncio
import time as _time
from typing import Optional

from aiohttp import web

from .. import obs
from ..errors import (
    OverloadedError,
    ScoreError,
    StatusError,
    to_response_error,
    with_trace_id,
)
from . import frames
from .metrics import (
    PROM_CONTENT_TYPE,
    Metrics,
    middleware,
    register_overload,
    register_performance,
    register_quality,
    register_resilience,
    render_prometheus,
)
from ..types.chat_request import ChatCompletionCreateParams as ChatParams
from ..types.embeddings import CreateEmbeddingParams
from ..types.multichat_request import (
    ChatCompletionCreateParams as MultichatParams,
)
from ..types.score_request import ChatCompletionCreateParams as ScoreParams
from ..utils import jsonutil

METRICS_KEY: web.AppKey = web.AppKey("metrics", Metrics)
# the drain/readiness state machine (serve/lifecycle.py), when wired
LIFECYCLE_KEY: web.AppKey = web.AppKey("lifecycle", object)

DONE = b"data: [DONE]\n\n"
SSE_HEADERS = {
    "content-type": "text/event-stream",
    "cache-control": "no-cache",
}

# aiohttp's own default request-body cap (client_max_size), used when
# MAX_BODY_BYTES=0 — the gateway never runs uncapped
_AIOHTTP_DEFAULT_BODY_BYTES = 1024 ** 2


def payload_cap_middleware():
    """Render aiohttp's 413 (client_max_size exceeded) as the uniform
    ``{code, message}`` envelope with a machine-readable kind, instead
    of the stock HTML error page.  The body read that trips the cap
    happens inside the handler (``await request.text()``), so this sits
    anywhere above the handlers in the middleware chain."""

    @web.middleware
    async def _mw(request, handler):
        try:
            return await handler(request)
        except web.HTTPRequestEntityTooLarge:
            obs.annotate(payload_too_large=True)
            return web.Response(
                status=413,
                text=jsonutil.dumps(
                    with_trace_id(
                        {
                            "code": 413,
                            "message": {"kind": "payload_too_large"},
                        }
                    )
                ),
                content_type="application/json",
            )

    return _mw


def _error_response(e: Exception) -> web.Response:
    if isinstance(e, OverloadedError):
        # load sheds are retryable by construction — say when (same
        # header the admission middleware sets on its 503s)
        import math

        return web.Response(
            status=503,
            headers={
                "Retry-After": str(
                    max(1, math.ceil((e.retry_after_ms or 1000.0) / 1000.0))
                )
            },
            text=jsonutil.dumps(
                with_trace_id({"code": 503, "message": e.message()})
            ),
            content_type="application/json",
        )
    if isinstance(e, StatusError):
        status, message = e.status(), e.message()
        if isinstance(message, dict):
            # dict-shaped error payloads carry the request's trace id so
            # a client-reported failure names its exact trace; string
            # payloads keep the reference's constant messages untouched
            message = with_trace_id(dict(message))
        body = jsonutil.dumps(message)
    else:
        # Uniform {code, message} envelope for unexpected failures; ONE
        # policy site — errors.to_response_error — masks the detail into
        # the server log (src/error.rs:8-13 parity, VERDICT r4 weak-7),
        # same as the mid-stream frame path in _respond_streaming.
        err = to_response_error(e)
        status = err.code
        body = jsonutil.dumps(with_trace_id(err.to_json_obj()))
    return web.Response(
        status=status, text=body, content_type="application/json"
    )


def _frame(obj) -> bytes:
    # kept as the module's one-frame helper for non-loop callers; the
    # per-chunk loop below goes through frames.FrameEncoder (LWC017)
    return frames.frame_bytes(obj)


def _note_client_disconnect(request: web.Request) -> None:
    obs.annotate(client_disconnect=True)
    metrics = request.app.get(METRICS_KEY)
    if metrics is not None:
        metrics.observe("http:client_disconnect", 0.0, error=True)


async def _respond_streaming(
    request: web.Request, stream, fastpath: bool = False
) -> web.StreamResponse:
    # a stream's ``http:respond`` is its response object built, like a
    # unary answer's; its frames go out across awaits and are in no span
    with obs.host_span("http:respond", rid=obs.request_id(), status=200):
        resp = web.StreamResponse(headers=SSE_HEADERS)
        encoder = frames.FrameEncoder(fastpath)
    await resp.prepare(request)
    try:
        async for item in stream:
            if isinstance(item, Exception):
                # a mid-stream error makes this trace worth keeping even
                # when head sampling said no (sink.py retention rule)
                obs.force_keep("stream_error")
                await resp.write(encoder.encode_error(item))
            else:
                await resp.write(encoder.encode(item))
        if encoder.fallbacks:
            # fast-lane frames that fell back to the slow path: loud in
            # the trace, invisible on the wire (bytes are identical)
            obs.annotate(fastpath_fallbacks=encoder.fallbacks)
        await resp.write(DONE)
    except (ConnectionResetError, ConnectionError):
        # the client disconnected mid-stream: nothing left to say to it,
        # but the abandoned pipeline must be torn down NOW — the finally
        # below acloses the generator chain, whose cleanup cancels the
        # upstream judge pumps and any batcher futures this request has
        # in flight (batcher._submit drops a cancelled item before its
        # group dispatches — no orphaned device work)
        _note_client_disconnect(request)
    except asyncio.CancelledError:
        # a server started with handler_cancellation=True (aiohttp's test
        # server is) cancels the handler when the client leaves, where
        # the default lets its next write fail: the same event, counted
        # the same way.  Any other cancellation (shutdown) is not one.
        if request.transport is None:
            _note_client_disconnect(request)
        raise
    finally:
        aclose = getattr(stream, "aclose", None)
        if aclose is not None:
            await aclose()
    return resp


def _parse_error_response(e: Exception) -> web.Response:
    """The parse-phase 400 policy, one definition for every endpoint.

    The EXPECTED malformed-request classes — SchemaError (path-annotated,
    types/base.py) and the json decoder's JSONDecodeError — are
    ValueErrors whose text describes the *client's input*: safe and
    useful to echo (the serde_path_to_error surface).  Anything else is a
    latent decoder bug, not client input: same masking policy as the 500
    envelope — detail to the server log only, never into the body."""
    if isinstance(e, ValueError):
        message: object = str(e)
    else:
        import logging

        from ..errors import MASKING_LOGGER

        logging.getLogger(MASKING_LOGGER).error(
            "unexpected parse-phase error", exc_info=e
        )
        message = "malformed request body"
    return web.Response(
        status=400,
        text=jsonutil.dumps(with_trace_id({"code": 400, "message": message})),
        content_type="application/json",
    )


async def _read_body(request: web.Request, rid) -> str:
    """``http:read``: the body off the socket, between ``http:arrive`` and
    ``http:parse``."""
    with obs.host_span("http:read", rid=rid) as reading:
        raw = await request.text()
        reading.annotate(bytes=len(raw))
    return raw


def _arrives(handler):
    """Around a handler that calls ``obs.arrive``: a request that leaves
    without an ``http:respond`` (its client gone and the handler cancelled,
    a 413 raised to the middleware) leaves the device's account too."""

    async def accounted(request: web.Request):
        try:
            return await handler(request)
        finally:
            obs.depart()

    return accounted


def _respond(rid, build) -> web.Response:
    """``http:respond``: from the result (or the error) in hand to the
    response object built, serialization included."""
    with obs.host_span("http:respond", rid=rid) as span:
        resp = build()
        span.annotate(status=resp.status)
    return resp


def deadline_middleware(resilience):
    """Stamp the per-request deadline on the ambient contextvar.

    The client's ``x-deadline-ms`` header wins; the policy's
    ``deadline_ms`` is the default.  Because aiohttp runs each handler in
    its own task, the activation is naturally request-scoped and every
    task the fan-out spawns under it (judge pumps, hedge attempts)
    inherits the deadline."""
    from ..resilience import Deadline

    @web.middleware
    async def _mw(request, handler):
        ms = resilience.deadline_ms
        header = request.headers.get("x-deadline-ms")
        if header:
            try:
                ms = float(header)
            except ValueError:
                pass
        if ms <= 0:
            return await handler(request)
        token = Deadline(ms / 1000.0).activate()
        try:
            return await handler(request)
        finally:
            Deadline.deactivate(token)

    return _mw


# probes and the trace read endpoints are never themselves traced — a
# poller scraping /metrics must not churn the sampling budget, and
# reading traces must not mint traces
TRACE_EXEMPT_PATHS = frozenset({"/healthz", "/livez", "/readyz", "/metrics"})


def trace_middleware(sink):
    """The gateway door of the obs/ subsystem: extract an upstream
    ``traceparent`` (external callers stitch our tree under theirs),
    flip the head-sampling coin, run the whole request — middlewares
    included, so admission sheds land inside the root span — and offer
    the finished trace to the sink, which keeps it when sampled or when
    the outcome forced retention (5xx, shed, degraded, stream error)."""

    @web.middleware
    async def _mw(request, handler):
        if request.path in TRACE_EXEMPT_PATHS or request.path.startswith(
            ("/v1/traces", "/v1/judges")
        ):
            return await handler(request)
        upstream = obs.extract(request.headers)
        if upstream is not None:
            trace_id, parent_span_id, caller_sampled = upstream
            sampled = caller_sampled or sink.sample()
        else:
            trace_id = parent_span_id = None
            sampled = sink.sample()
        root = obs.start_trace(
            f"gateway:{request.method} {request.path}",
            sampled=sampled,
            trace_id=trace_id,
            parent_span_id=parent_span_id,
        )
        token = root.activate()
        status: Optional[int] = None
        try:
            resp = await handler(request)
            status = resp.status
            if not resp.prepared:
                resp.headers["x-trace-id"] = root.trace.trace_id
            return resp
        except Exception as e:
            root.set_error(e)
            raise
        finally:
            if status is not None:
                root.annotate(http_status=status)
                if status >= 500:
                    # sheds return their 503 rather than raising — the
                    # admission middleware annotated shed_reason already
                    root.status = "error"
                    root.trace.force(f"http_{status}")
            obs.Span.deactivate(token)
            root.finish()
            try:
                # the per-request phase attribution (obs/phases.py):
                # derived from the finished span tree and stamped on the
                # root, so every retained trace explains where its
                # milliseconds went without a second tool
                root.annotate(
                    phase_breakdown=obs.phase_breakdown(root.trace)
                )
            except Exception:
                pass  # attribution must never break serving
            sink.offer(root.trace)

    return _mw


def _trace_handlers(sink):
    """GET /v1/traces (recent index) + GET /v1/traces/{trace_id}."""

    async def index(request: web.Request):
        try:
            limit = int(request.query.get("limit", 50))
        except ValueError:
            limit = 50
        return web.json_response(
            {"traces": sink.index(limit=max(1, min(limit, sink.capacity)))}
        )

    async def get_one(request: web.Request):
        record = sink.get(request.match_info["trace_id"])
        if record is None:
            return web.json_response(
                {"code": 404, "message": "unknown trace_id"}, status=404
            )
        return web.json_response(record)

    return index, get_one


def _judge_handlers():
    """GET /v1/judges (all scorecards) + GET /v1/judges/{judge_id}.

    Reads the process-global quality aggregator (obs/quality.py), so
    the scorecards exist whether or not tracing or the ledger is
    configured — same always-on contract as the ``phases`` section."""
    from ..obs import quality as _quality

    async def index(request: web.Request):
        agg = _quality.quality_aggregator()
        return web.json_response(
            {
                "window": agg.window,
                "drift_threshold": agg.drift_threshold,
                "judges": agg.scorecards(),
            }
        )

    async def get_one(request: web.Request):
        card = _quality.quality_aggregator().scorecard(
            request.match_info["judge_id"]
        )
        if card is None:
            return web.json_response(
                {"code": 404, "message": "unknown judge id"}, status=404
            )
        return web.json_response(card)

    return index, get_one


def _weights_handlers(live_weights):
    """GET /v1/weights (active table + shadow counters) + PUT /v1/weights
    (validated atomic hot-swap — ISSUE 20 tentpole piece c).

    PUT body: ``{"weights": {judge_id: number, ...}, "version"?: str,
    "mode"?: "active"|"shadow"}``.  ``mode: "shadow"`` stages the table
    for would-have-flipped comparison without changing served verdicts;
    ``"weights": {}`` with ``"mode"`` clears that slot.  The swap is one
    assignment on the event loop, so in-flight tallies finish under the
    version they captured and the next tally sees the new one — zero
    client errors across a flip is the hot-swap drill's assertion."""

    async def get_weights(request: web.Request):
        return web.json_response(live_weights.wire())

    async def put_weights(request: web.Request):
        try:
            body = jsonutil.loads(await request.text())
        except Exception:
            return web.json_response(
                {"code": 400, "message": "body must be a JSON object"},
                status=400,
            )
        if not isinstance(body, dict) or not isinstance(
            body.get("weights"), dict
        ):
            return web.json_response(
                {"code": 400, "message": 'body needs a "weights" object'},
                status=400,
            )
        mode = body.get("mode", "active")
        try:
            if not body["weights"]:
                live_weights.clear(mode=mode)
                return web.json_response({"ok": True, "cleared": mode})
            version = live_weights.put(
                body["weights"], version=body.get("version"), mode=mode
            )
        except ValueError as e:
            return web.json_response(
                {"code": 400, "message": str(e)}, status=400
            )
        return web.json_response(
            {"ok": True, "version": version, "mode": mode}
        )

    return get_weights, put_weights


async def _weights_disabled(request: web.Request) -> web.Response:
    """/v1/weights without WEIGHTS_ENABLED/WEIGHTS_PATH: explicit 403,
    same contract as the /v1/profile guard."""
    return web.json_response(
        {
            "code": 403,
            "message": "live weights disabled: set WEIGHTS_ENABLED=1 "
            "or WEIGHTS_PATH",
        },
        status=403,
    )


def _offline_rescore_handler(batcher, default_inflight: int = 4):
    """POST /v1/train/rescore: saturate the offline priority class with
    deterministic synthetic candidate groups and report the lane stats —
    the HTTP face of ``python -m ...train rescore``, to be driven
    concurrently with latency traffic.

    Body (all optional): ``{"groups": int, "n": int, "seed": int,
    "inflight": int, "temperature": float}``.  Runs the drive to
    completion in-handler and returns ``{groups, items, errors,
    offline_occupancy, lanes}`` so the caller gets the merged-interval
    occupancy gauge in the same response.  One drive at a time (409 on
    overlap) — two saturators would double-count each other's idle."""
    import asyncio

    lock = asyncio.Lock()

    async def rescore(request: web.Request):
        from ..train.feed import OfflineFeed, synthetic_groups

        if lock.locked():
            return web.json_response(
                {"code": 409, "message": "a rescore drive is already running"},
                status=409,
            )
        try:
            body = jsonutil.loads(await request.text()) if (
                request.can_read_body
            ) else {}
        except Exception:
            body = {}
        if not isinstance(body, dict):
            body = {}
        try:
            n_groups = max(1, min(int(body.get("groups", 32)), 4096))
            n = max(2, min(int(body.get("n", 8)), MAX_CONSENSUS_CANDIDATES))
            seed = int(body.get("seed", 0))
            inflight = max(1, min(int(body.get("inflight", default_inflight)), 64))
            temperature = float(body.get("temperature", 0.05))
        except (TypeError, ValueError):
            return web.json_response(
                {"code": 400, "message": "rescore params must be numeric"},
                status=400,
            )
        async with lock:
            feed = OfflineFeed(batcher, inflight=inflight)
            _results, occupancy = await feed.drive(
                synthetic_groups(n_groups, n, seed=seed),
                temperature=temperature,
            )
        return web.json_response(
            {
                "ok": True,
                "groups": feed.groups,
                "items": feed.items,
                "errors": feed.errors,
                "offline_occupancy": occupancy,
                "lanes": batcher.utilization()["lanes"],
            }
        )

    return rescore


async def _offline_rescore_disabled(request: web.Request) -> web.Response:
    """/v1/train/rescore without OFFLINE_ENABLED (or without a device
    batcher): explicit 403, same contract as the /v1/profile guard."""
    return web.json_response(
        {
            "code": 403,
            "message": "offline lane disabled: set OFFLINE_ENABLED=1 "
            "(and configure EMBED_MODEL)",
        },
        status=403,
    )


def _make_handler(params_cls, create_streaming, create_unary, fastpath=False):
    async def handler(request: web.Request):
        rid = obs.arrive(request.path, request.content_length or 0)
        try:
            raw = await _read_body(request, rid)
            with obs.host_span("http:parse", rid=rid, bytes=len(raw)):
                params = params_cls.from_json_obj(jsonutil.loads(raw))
        except web.HTTPException:
            raise  # e.g. 413 body-too-large must keep its status
        except Exception as e:  # parse phase is side-effect free: never
            # a server-state fault — 400 with the path-annotated message
            # (or masked, for non-ValueError: see _parse_error_response)
            return _respond(rid, lambda: _parse_error_response(e))
        ctx = request.headers.get("authorization")
        if params.stream:
            try:
                stream = await create_streaming(ctx, params)
            except Exception as e:
                return _respond(rid, lambda: _error_response(e))
            return await _respond_streaming(request, stream, fastpath)
        try:
            result = await create_unary(ctx, params)
        except Exception as e:
            return _respond(rid, lambda: _error_response(e))
        return _respond(
            rid,
            lambda: web.Response(
                text=result.to_json(), content_type="application/json"
            ),
        )

    return _arrives(handler)


async def _with_consensus_frames(stream, embedder, metrics=None, batcher=None):
    """Interleave live ``multichat.consensus`` frames into a multichat
    stream; embeds + revotes run off the loop — through the micro-batcher
    (shared dispatches across concurrent streams) when one is attached."""
    from ..clients.multichat import ConsensusUpdate, StreamingSelfConsistency

    sc = StreamingSelfConsistency(embedder, batcher=batcher)
    try:
        async for chunk in stream:
            yield chunk
            if isinstance(chunk, Exception) or sc is None:
                continue
            t0 = _time.perf_counter()
            try:
                update = await sc.push_chunk_async(chunk)
            except Exception:
                # consensus frames are an overlay on the multichat stream:
                # an embedder failure degrades to plain multichat (no more
                # consensus frames) rather than tearing the stream down
                if metrics is not None:
                    metrics.observe(
                        "device:consensus_update",
                        (_time.perf_counter() - t0) * 1e3,
                        error=True,
                    )
                sc = None
                continue
            if update is not None:
                if metrics is not None:
                    metrics.observe(
                        "device:consensus_update",
                        (_time.perf_counter() - t0) * 1e3,
                    )
                yield ConsensusUpdate(update)
    finally:
        # client disconnects surface here as GeneratorExit; the inner
        # stream's cleanup must still run
        aclose = getattr(stream, "aclose", None)
        if aclose is not None:
            await aclose()


def _multichat_streaming(multichat_client, embedder, metrics, batcher=None):
    async def create_streaming(ctx, params):
        stream = await multichat_client.create_streaming(ctx, params)
        if params.consensus and embedder is not None:
            return _with_consensus_frames(stream, embedder, metrics, batcher)
        return stream

    return create_streaming


def _multichat_unary(multichat_client, embedder, batcher):
    """Unary multichat with ``consensus: true``: after the fold, embed all
    finished candidates + consensus-vote in ONE fused dispatch and attach
    the confidence distribution (the unary view of the streaming
    ``multichat.consensus`` frames).  The batcher coalesces concurrent
    requests with the same candidate count into one device batch
    (``consensus_confidence_tokens_many``)."""

    async def create_unary(ctx, params):
        result = await multichat_client.create_unary(ctx, params)
        if not (params.consensus and embedder is not None and batcher):
            return result
        slots, texts = [], []
        for choice in result.choices:
            content = getattr(choice.message, "content", None)
            if choice.error is None and isinstance(content, str) and content:
                slots.append(choice.index)
                texts.append(content)
        if len(texts) >= 2:
            try:
                conf, _tokens = await batcher.consensus(texts)
            except Exception:
                # the consensus is an overlay on the multichat result: an
                # embedder failure degrades to plain multichat (no
                # `consensus` field) rather than discarding N completed
                # generations with a 5xx — mirrors the streaming path
                return result
            result.consensus = {
                str(slot): float(c) for slot, c in zip(slots, conf)
            }
        return result

    return create_unary


def _profile_handlers(profile_dir: str):
    """JAX profiler control (SURVEY §5 tracing row): traces land under
    ``profile_dir`` in xprof format.  One trace at a time; stop without
    start is a 400 rather than a crash.

    While a profile runs, every ``obs.host_span`` of the serving path is
    in it too, on the profiler's clock (the annotation class is handed to
    ``obs`` here, the one place a profile starts), and the trace opens
    and closes with a ``lwc:clock`` mark that carries ``perf_counter_ns``
    and ``epoch_ns``: any host time of this process, or of a client on
    the same machine, can be laid on the trace; and the device's account
    (``enqueued_ms``, ``starved_ms``, ``idle_ms``: obs/account.py), so the
    account's window between the marks is the trace's.  ``POST /v1/profile``
    turns the Python tracer off: the host planes then hold those spans
    and the runtime's own events instead of every Python frame, and the
    trace is a third to two thirds smaller.  ``/profile/start`` keeps the
    profiler's defaults for a look by hand."""
    # one lock serializes start/stop end-to-end: the JAX profiler is a
    # process-global singleton, so overlapping operations (a start racing
    # an in-flight stop's serialization) must queue, and a concurrent
    # duplicate gets the clean 400 once the lock frees
    state = {"active": False, "lock": asyncio.Lock()}

    def clock_mark() -> None:
        # the device's account as it stands at the mark: the trace's own
        # idle time between the two marks can be set beside it by hand
        booked = obs.device_account().snapshot()
        with obs.host_span(
            "lwc:clock",
            parents=(),
            perf_counter_ns=_time.perf_counter_ns(),
            epoch_ns=_time.time_ns(),
            **{
                key: booked[key]
                for key in ("enqueued_ms", "starved_ms", "idle_ms")
            },
        ):
            pass

    def start_profiler(python_tracer: bool) -> None:
        import jax

        options = jax.profiler.ProfileOptions()
        if not python_tracer:
            options.python_tracer_level = 0
        jax.profiler.start_trace(profile_dir, profiler_options=options)
        obs.set_profiler_annotation(jax.profiler.TraceAnnotation)
        clock_mark()

    def stop_profiler() -> None:
        import jax

        clock_mark()
        obs.set_profiler_annotation(None)
        jax.profiler.stop_trace()

    async def start(request: web.Request):
        async with state["lock"]:
            if state["active"]:
                return web.json_response(
                    {"code": 400, "message": "trace already active"},
                    status=400,
                )
            try:
                # profiler IO runs on the executor; the loop keeps serving
                await asyncio.get_running_loop().run_in_executor(
                    None, start_profiler, True
                )
            except Exception as e:
                return _error_response(e)
            state["active"] = True
        return web.json_response({"ok": True, "dir": profile_dir})

    async def stop(request: web.Request):
        async with state["lock"]:
            if not state["active"]:
                return web.json_response(
                    {"code": 400, "message": "no active trace"}, status=400
                )
            # cleared regardless of outcome so a failed serialization
            # can't wedge the endpoints; the error still surfaces
            state["active"] = False
            try:
                # trace serialization can be hundreds of MB — never on
                # the loop
                await asyncio.get_running_loop().run_in_executor(
                    None, stop_profiler
                )
            except Exception as e:
                return _error_response(e)
        return web.json_response({"ok": True, "dir": profile_dir})

    async def capture(request: web.Request):
        """POST /v1/profile: one-shot capture — start, sleep the
        requested window while live traffic runs, stop.  Bounded so a
        fat-fingered duration can't leave the profiler running; the
        admission middleware exempts this path (profiling an overload
        is the point), so the guard here is PROFILE_DIR alone."""
        try:
            body = jsonutil.loads(await request.text() or "{}")
        except Exception:
            body = {}
        duration_ms = float(body.get("duration_ms", 500.0) or 500.0)
        duration_ms = min(10_000.0, max(10.0, duration_ms))
        async with state["lock"]:
            if state["active"]:
                return web.json_response(
                    {"code": 400, "message": "trace already active"},
                    status=400,
                )
            state["active"] = True
        loop = asyncio.get_running_loop()
        try:
            await loop.run_in_executor(None, start_profiler, False)
            # capture window: the loop keeps serving, so in-flight and
            # new requests land inside the trace
            await asyncio.sleep(duration_ms / 1e3)
            await loop.run_in_executor(None, stop_profiler)
        except Exception as e:
            return _error_response(e)
        finally:
            obs.set_profiler_annotation(None)
            async with state["lock"]:
                state["active"] = False
        return web.json_response(
            {"ok": True, "dir": profile_dir, "duration_ms": duration_ms}
        )

    return start, stop, capture


async def _profile_disabled(request: web.Request) -> web.Response:
    """POST /v1/profile without PROFILE_DIR: a clear 403, not a 404 —
    the endpoint exists, the operator just hasn't enabled it."""
    return web.json_response(
        {"code": 403, "message": "profiling disabled: set PROFILE_DIR"},
        status=403,
    )


def _roofline_gauge(embedder):
    """Wire the live roofline-attainment gauge (ISSUE 11 tentpole piece
    3) when a device path exists: committed per-bucket ceilings from
    analysis/roofline.json against the live device-time histograms.
    Import-guarded — the gauge is observability, never a serving
    dependency."""
    if embedder is None:
        return None
    try:
        import jax

        from ..analysis.roofline import (
            RooflineGauge,
            default_roofline_path,
            load_roofline,
        )

        roofline = load_roofline(default_roofline_path())
        if not roofline:
            return None
        return RooflineGauge(roofline, jax.devices()[0].device_kind)
    except Exception:
        return None


def build_app(
    chat_client,
    score_client,
    multichat_client=None,
    embedder=None,
    metrics=None,
    profile_dir=None,
    batcher=None,
    batch_window_ms: float = 3.0,
    batch_max: int = 64,
    reranker=None,
    judge=None,
    embed_cache=None,
    resilience=None,
    fault_plan=None,
    admission=None,
    lifecycle=None,
    watchdog=None,
    trace_sink=None,
    ledger=None,
    fleet=None,
    host_fastpath: bool = False,
    memguard=None,
    max_body_bytes: int = 0,
    live_weights=None,
    offline_enabled: bool = False,
    offline_inflight: int = 4,
) -> web.Application:
    metrics = metrics or Metrics()
    register_resilience(metrics, resilience, fault_plan)
    register_overload(metrics, admission, watchdog, lifecycle, memguard)
    register_performance(
        metrics, _roofline_gauge(embedder if embedder is not None else judge)
    )
    register_quality(metrics, ledger, live_weights)
    if judge is not None:
        # the judge's counters: calls, prefill and padded tokens, tokens
        # routed to each expert and a dispatch's largest load over its mean
        metrics.register_provider("judge", judge.stats)
    if (embedder is not None or judge is not None) and batcher is None:
        from .batcher import DeviceBatcher

        batcher = DeviceBatcher(
            embedder,
            metrics,
            judge=judge,
            window_ms=batch_window_ms,
            max_batch=batch_max,
            embed_cache=embed_cache,
            watchdog=watchdog,
            max_queue_depth=(
                admission.config.max_queue_depth
                if admission is not None
                else 0
            ),
        )
    # consensus result cache counters (hits/misses/evictions + in-flight
    # collapses) surface as the `score_cache` section of GET /metrics;
    # the score client may arrive wrapped (_ArchivingClient delegates)
    inner_score = getattr(score_client, "_inner", score_client)
    score_cache = getattr(inner_score, "cache", None)
    if score_cache is not None:
        score_flights = getattr(inner_score, "flights", None)

        def _score_cache_stats():
            stats = score_cache.stats()
            stats["inflight_collapses"] = (
                score_flights.collapses if score_flights is not None else 0
            )
            return stats

        metrics.register_provider("score_cache", _score_cache_stats)
    middlewares = []
    if trace_sink is not None:
        # outermost: the root span brackets everything, and the metrics
        # middleware inside it observes with the ambient trace active
        # (that read is where the per-series trace_id exemplars come from)
        middlewares.append(trace_middleware(trace_sink))
        metrics.register_provider("traces", trace_sink.snapshot)
    middlewares.append(middleware(metrics))
    # inside metrics (413s are observable per route), outside admission
    # (an oversized body should not burn an admission slot's error
    # accounting on its way out)
    middlewares.append(payload_cap_middleware())
    if admission is not None:
        # inside metrics (sheds are observable per route), outside the
        # deadline stamp (shed work should not even start a budget)
        from ..resilience.admission import admission_middleware

        middlewares.append(admission_middleware(admission))
    if resilience is not None:
        middlewares.append(deadline_middleware(resilience))
    elif fleet is not None:
        # fleet peer calls forward their clamped budget as x-deadline-ms
        # (fleet/client.py); honoring it server-side needs the deadline
        # stamp even with the resilience subsystem off.  No default
        # budget — header-only, so non-fleet requests are untouched
        class _HeaderOnlyDeadline:
            deadline_ms = 0.0

        middlewares.append(deadline_middleware(_HeaderOnlyDeadline()))
    # MAX_BODY_BYTES → aiohttp's own pre-parse body cap; covers every
    # route on this app, /fleet/v1 included.  0 keeps aiohttp's default
    # rather than lifting the cap — the gateway never runs unbounded
    app = web.Application(
        middlewares=middlewares,
        client_max_size=(
            max_body_bytes if max_body_bytes > 0 else _AIOHTTP_DEFAULT_BODY_BYTES
        ),
    )
    app[METRICS_KEY] = metrics
    if fleet is not None:
        # the replica-to-replica surface (/fleet/v1/*, fleet/handlers.py)
        # plus the `fleet` metrics section (membership, leases, peer
        # fetch and handoff counters)
        from ..fleet import register_fleet_routes

        register_fleet_routes(app, fleet)
        metrics.register_provider("fleet", fleet.stats)
    if lifecycle is not None:
        app[LIFECYCLE_KEY] = lifecycle
    if batcher is not None:

        async def _close_batcher(app):
            batcher.close()

        app.on_cleanup.append(_close_batcher)
    app.router.add_post(
        "/chat/completions",
        _make_handler(
            ChatParams,
            chat_client.create_streaming,
            chat_client.create_unary,
            fastpath=host_fastpath,
        ),
    )
    app.router.add_post(
        "/score/completions",
        _make_handler(
            ScoreParams,
            score_client.create_streaming,
            score_client.create_unary,
            fastpath=host_fastpath,
        ),
    )
    if multichat_client is not None:
        app.router.add_post(
            "/multichat/completions",
            _make_handler(
                MultichatParams,
                _multichat_streaming(
                    multichat_client, embedder, metrics, batcher
                ),
                _multichat_unary(multichat_client, embedder, batcher),
                fastpath=host_fastpath,
            ),
        )
    if embedder is not None:
        app.router.add_post(
            "/embeddings", _embeddings_handler(embedder, metrics, batcher)
        )
    if embedder is not None or reranker is not None or judge is not None:
        app.router.add_post(
            "/consensus",
            _consensus_handler(embedder, metrics, batcher, reranker, judge),
        )

    async def healthz(request):
        # deprecated alias for the /livez + /readyz split: kept
        # byte-identical for pre-split probers
        return web.json_response({"ok": True})

    async def metrics_handler(request):
        # ?format=prometheus flips the same data into OpenMetrics text
        # (histogram families + exemplars); the default JSON snapshot
        # keeps its PR 5 shape for existing scrapers
        if request.query.get("format") == "prometheus":
            return web.Response(
                body=render_prometheus(metrics).encode("utf-8"),
                headers={"Content-Type": PROM_CONTENT_TYPE},
            )
        return web.json_response(metrics.snapshot())

    from .lifecycle import health_handlers

    livez, readyz = health_handlers(lifecycle)
    app.router.add_get("/healthz", healthz)
    app.router.add_get("/livez", livez)
    app.router.add_get("/readyz", readyz)
    app.router.add_get("/metrics", metrics_handler)
    if trace_sink is not None:
        traces_index, traces_get = _trace_handlers(trace_sink)
        app.router.add_get("/v1/traces", traces_index)
        app.router.add_get("/v1/traces/{trace_id}", traces_get)
    judges_index, judges_get = _judge_handlers()
    app.router.add_get("/v1/judges", judges_index)
    app.router.add_get("/v1/judges/{judge_id}", judges_get)
    if live_weights is not None:
        weights_get, weights_put = _weights_handlers(live_weights)
        app.router.add_get("/v1/weights", weights_get)
        app.router.add_put("/v1/weights", weights_put)
    else:
        # registered either way so the guard is an explicit 403, not a
        # confusable 404 (same contract as /v1/profile below)
        app.router.add_get("/v1/weights", _weights_disabled)
        app.router.add_put("/v1/weights", _weights_disabled)
    if offline_enabled and batcher is not None:
        app.router.add_post(
            "/v1/train/rescore",
            _offline_rescore_handler(batcher, default_inflight=offline_inflight),
        )
    else:
        app.router.add_post("/v1/train/rescore", _offline_rescore_disabled)
    if profile_dir:
        start, stop, capture = _profile_handlers(profile_dir)
        app.router.add_post("/profile/start", start)
        app.router.add_post("/profile/stop", stop)
        app.router.add_post("/v1/profile", capture)
    else:
        # registered either way so the guard is an explicit 403, not a
        # confusable 404
        app.router.add_post("/v1/profile", _profile_disabled)
    return app


# /consensus request-size ceiling: bounds the device batch a single
# request can demand, and — because the candidate count is a jit-static
# shape — bounds the total set of compiled specializations a client can
# force (temperature is traced, so it can never force one)
MAX_CONSENSUS_CANDIDATES = 256


def _consensus_handler(
    embedder, metrics=None, batcher=None, reranker=None, judge=None
):
    """POST /consensus: the device scorer as a direct service — N
    candidate texts in, a confidence distribution out.

    Three scorers: ``"cosine"`` (default) is the embedding self-consistency
    vote (one fused embed+vote dispatch; concurrent requests coalesce via
    the micro-batcher — the path the benchmark's encoder cells time);
    ``"rm"`` re-ranks by reward model: softmax(reward/T) over the
    candidates, each scored against the optional ``prompt``;
    ``"judge"`` is a LOCAL judge panel
    (models/judge.py): ``panel`` calls (default three, weights 1) of one
    causal decoder read the candidates under differently seeded
    prefix-tree ballots, each call's vote is the softmax over the ballot's
    sibling key letters at the decoder's own head, and ``confidence`` is
    the calls' weighted tally (Σ vote x weight / Σ weight) — the
    reference's judge protocol without the HTTP hop.  Its answer also
    carries ``ballots``, one entry a call: what an upstream judge's
    ``top_logprobs`` would have carried.  The other two have no reference
    analog (its scoring always goes through judge LLMs; SURVEY §2.6).

    Body: {"input": [texts...], "scorer"?: "cosine"|"rm"|"judge",
    "prompt"?: str, "temperature"?: float (cosine, rm),
    "panel"?: [{"seed": int, "weight": number}] (judge)}.  Response:
    {"model", "scorer", "confidence": [...], "usage": {prompt_tokens,
    total_tokens}} and, for ``judge``, "ballots": [{"seed", "weight",
    "first"?: {letter: logprob}, "key", "siblings": {letter: {"logprob",
    "candidate"}}}].
    """
    import asyncio
    import math
    from decimal import Decimal as _Decimal

    def parse_panel(raw):
        """``panel`` to [(seed, weight)], or None for the default."""
        from ..models.judge import MAX_PANEL

        if raw is None:
            return None
        if not isinstance(raw, list) or not 1 <= len(raw) <= MAX_PANEL:
            raise ValueError(
                f"`panel` must be a list of 1 to {MAX_PANEL} calls"
            )
        panel = []
        for call in raw:
            seed = call.get("seed") if isinstance(call, dict) else None
            weight = call.get("weight", 1) if isinstance(call, dict) else None
            if isinstance(seed, bool) or not isinstance(seed, int):
                raise ValueError("a panel call's `seed` must be an integer")
            if isinstance(weight, bool) or not isinstance(
                weight, (int, float, _Decimal)
            ):
                raise ValueError("a panel call's `weight` must be a number")
            weight = float(weight)
            if not math.isfinite(weight) or weight <= 0:
                raise ValueError(
                    "a panel call's `weight` must be finite and positive"
                )
            panel.append((seed, weight))
        return panel

    def parse(raw: str):
        """The request body to (texts, scorer, prompt, temperature,
        panel) — the judge has a panel and no temperature, the others the
        reverse — or the ValueError the 400 policy echoes."""
        body = jsonutil.loads(raw)
        if not isinstance(body, dict):
            raise ValueError("body must be a JSON object")
        texts = body.get("input")
        if (
            not isinstance(texts, list)
            or len(texts) < 2
            or not all(isinstance(t, str) for t in texts)
        ):
            raise ValueError(
                "`input` must be a list of >= 2 candidate strings"
            )
        if len(texts) > MAX_CONSENSUS_CANDIDATES:
            raise ValueError(
                f"`input` accepts at most {MAX_CONSENSUS_CANDIDATES} "
                "candidates per request"
            )
        scorer = body.get("scorer", "cosine")
        if scorer not in ("cosine", "rm", "judge"):
            raise ValueError("`scorer` must be 'cosine', 'rm' or 'judge'")
        if scorer == "cosine" and embedder is None:
            raise ValueError(
                "cosine scorer unavailable: no EMBEDDER_MODEL configured"
            )
        if scorer == "rm" and reranker is None:
            raise ValueError(
                "rm scorer unavailable: no RM_MODEL configured"
            )
        if scorer == "judge" and judge is None:
            raise ValueError(
                "judge scorer unavailable: no JUDGE_MODEL configured"
            )
        prompt = body.get("prompt")
        if prompt is not None and not isinstance(prompt, str):
            raise ValueError("`prompt` must be a string")
        if scorer == "judge":
            return texts, scorer, prompt, None, parse_panel(body.get("panel"))
        traw = body.get("temperature", 0.05 if scorer == "cosine" else 1.0)
        # explicit type check, not bare float(): a non-numeric value
        # must raise the ValueError the 400 policy echoes, never a
        # TypeError the policy masks as a server bug (jsonutil.loads
        # parses JSON floats as Decimal)
        if isinstance(traw, bool) or not isinstance(
            traw, (int, float, _Decimal)
        ):
            raise ValueError("`temperature` must be a number")
        temperature = float(traw)
        if not math.isfinite(temperature) or temperature <= 0:
            raise ValueError(
                "`temperature` must be a finite positive number"
            )
        return texts, scorer, prompt, temperature, None

    async def handler(request: web.Request):
        rid = obs.arrive(request.path, request.content_length or 0)
        try:
            raw = await _read_body(request, rid)
            with obs.host_span(
                "http:parse", rid=rid, bytes=len(raw)
            ) as parsing:
                texts, scorer, prompt, temperature, panel = parse(raw)
                parsing.annotate(n=len(texts))
        except web.HTTPException:
            raise  # e.g. 413 body-too-large must keep its status
        except Exception as e:  # parse phase is side-effect free
            return _respond(rid, lambda: _parse_error_response(e))
        loop = asyncio.get_running_loop()
        ballots = None
        try:
            if scorer == "judge":
                conf, tokens, ballots = await batcher.judge(
                    texts, prompt, panel
                )
                model_name = judge.model_name
            elif scorer == "rm":
                t0 = _time.perf_counter()
                conf, tokens = await loop.run_in_executor(
                    None,
                    lambda: reranker.rerank_confidence(
                        texts, prompt=prompt, temperature=temperature
                    ),
                )
                if metrics is not None:
                    metrics.observe(
                        "device:rm_vote",
                        (_time.perf_counter() - t0) * 1e3,
                    )
                model_name = reranker.model_name
            elif batcher is not None:
                conf, tokens = await batcher.consensus(texts, temperature)
                model_name = embedder.model_name
            else:

                def run():
                    ids, mask = embedder.tokenize(texts)
                    return (
                        embedder.consensus_confidence_tokens(
                            ids, mask, temperature
                        ),
                        int(mask.sum()),
                    )

                t0 = _time.perf_counter()
                conf, tokens = await loop.run_in_executor(None, run)
                if metrics is not None:
                    metrics.observe(
                        "device:consensus",
                        (_time.perf_counter() - t0) * 1e3,
                    )
                model_name = embedder.model_name
        except Exception as e:
            return _respond(rid, lambda: _error_response(e))
        import numpy as np

        return _respond(
            rid,
            lambda: web.Response(
                text=jsonutil.dumps(
                    {
                        "model": model_name,
                        "scorer": scorer,
                        "confidence": [float(c) for c in np.asarray(conf)],
                        "usage": {
                            "prompt_tokens": tokens,
                            "total_tokens": tokens,
                        },
                        **({} if ballots is None else {"ballots": ballots}),
                    }
                ),
                content_type="application/json",
            ),
        )

    return _arrives(handler)


def _embeddings_handler(embedder, metrics=None, batcher=None):
    async def handler(request: web.Request):
        rid = obs.arrive(request.path, request.content_length or 0)
        try:
            raw = await _read_body(request, rid)
            with obs.host_span("http:parse", rid=rid, bytes=len(raw)):
                params = CreateEmbeddingParams.from_json_obj(
                    jsonutil.loads(raw)
                )
        except web.HTTPException:
            raise  # e.g. 413 body-too-large must keep its status
        except Exception as e:  # parse phase is side-effect free
            return _respond(rid, lambda: _parse_error_response(e))
        if params.model and params.model != embedder.model_name:
            return _respond(
                rid,
                lambda: web.Response(
                    status=400,
                    text=jsonutil.dumps(
                        {
                            "code": 400,
                            "message": "unknown embeddings model "
                            f"{params.model!r}; this gateway serves "
                            f"{embedder.model_name!r}",
                        }
                    ),
                    content_type="application/json",
                ),
            )
        import asyncio

        try:
            if batcher is not None:
                # the micro-batcher coalesces concurrent requests' texts
                # into one tokenize + one embed_tokens dispatch; response
                # assembly (per-row tolist over possibly thousands of
                # vectors) still stays off the event loop
                emb, tokens = await batcher.embed(params.inputs())
                resp = await asyncio.get_running_loop().run_in_executor(
                    None, embedder.wire_response, emb, tokens
                )
            else:
                # the device forward blocks; keep the event loop responsive
                t0 = _time.perf_counter()
                resp = await asyncio.get_running_loop().run_in_executor(
                    None, embedder.embeddings_response, params.inputs()
                )
                if metrics is not None:
                    metrics.observe(
                        "device:embed", (_time.perf_counter() - t0) * 1e3
                    )
        except Exception as e:
            return _respond(rid, lambda: _error_response(e))
        return _respond(
            rid,
            lambda: web.Response(
                text=resp.to_json(), content_type="application/json"
            ),
        )

    return _arrives(handler)
