"""Dynamic micro-batching for the serving device path.

The reference serves concurrent work by fanning judge sub-requests out over
async streams (select_all, score client.rs:343); its "device" is an upstream
HTTP API, so concurrency composes for free.  Here the device is a TPU chip
behind one PJRT queue: K concurrent HTTP requests each dispatching their own
forward pay K host<->device round-trips for work the MXU could do in one
batch.  This module closes that gap (SURVEY §2.8 "DP over candidates" at the
serving edge): handlers submit device work items to a ``DeviceBatcher``,
which collects everything that arrives within a small window (or while a
previous dispatch holds the device) and dispatches each compatible group as
ONE batched device call.

Three work kinds are batched, and a fourth rides the same queue and
pipeline alone:

* ``embed``       — texts -> (embeddings, token count); R requests' texts are
                    tokenized together and run as one ``embed_tokens`` batch;
* ``consensus``   — N candidate texts -> confidence[N]; R same-shape requests
                    run as one ``consensus_confidence_tokens_many`` dispatch;
* ``stream``      — one streaming-consensus update (embed one candidate into a
                    device-resident buffer + masked revote); R concurrent
                    streams' updates run as one vmapped dispatch
                    (``stream_vote_update_many``);
* ``judge``       — N candidate texts + a conversation -> a local judge
                    panel's tally (models/judge.py): the panel's calls are
                    ONE device program of static shape, so requests are
                    never grouped; what it shares is the queue, the two
                    pipeline slots and the three hops.

Dispatches are PIPELINED to ``pipeline_depth`` in flight (default 2), and
the pipeline is asynchronous end to end (ISSUE 13):

* **submit time** — each item's tokenization runs in a small host
  worker pool (``HOST_TOKENIZER_WORKERS``) the moment it is submitted,
  so ``_dispatch_*`` only concatenates pre-built rows;
* **dispatch thread** — pads into reusable staging buffers, starts the
  ``device_put`` (baked batch sharding in mesh mode), and returns as
  soon as the PJRT call is ENQUEUED (models/dispatch_seam.py) — group
  k+1's staging genuinely overlaps group k's device execution, even
  with ``METRICS_DEVICE_TIMING=1``;
* **waiter thread** — blocks on the enqueued outputs, records the
  per-bucket device time, tells the device's account, recycles the
  staging buffers, and materializes per-item results.  Device faults
  surface here and feed the same meshfault triage as dispatch-thread
  ones.

XLA orders the device work on its stream, so results are unaffected;
arrivals while every slot is busy queue and ride the next group.
Utilization (queue depth, busy fraction, items-per-dispatch) is exposed
through the metrics provider hook so the window/batch knobs are tunable
from ``GET /metrics``.
"""

from __future__ import annotations

import asyncio
import functools
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Optional

import numpy as np

from ..models import dispatch_seam as _seam
from ..obs.account import device_account
from ..obs import hostspan as _hostspan


class _Item:
    __slots__ = (
        "kind", "key", "payload", "future", "deadline", "span",
        "redispatches", "submitted", "prepared", "lane", "rid",
        "request", "rows_ready", "started",
    )

    def __init__(
        self, kind, key, payload, future, deadline=None, span=None,
        lane="latency",
    ):
        self.kind = kind
        self.key = key
        self.payload = payload
        self.future = future
        # priority class (ISSUE 20): "latency" rides the request path,
        # "offline" (train/ feed work) dispatches only when the latency
        # lane has no ready group — the two queues never mix in a group
        self.lane = lane
        # enqueue timestamp: _run_group attributes (dispatch start -
        # submitted) to the ``batcher_queue`` phase per item, including
        # any fault re-queue wait (obs/phases.py)
        self.submitted = time.perf_counter()
        # the request's propagated deadline (resilience/deadline.py),
        # captured at submit so the pre-dispatch shed can drop work
        # that can no longer finish in time
        self.deadline = deadline
        # the request's batcher span (obs/), captured at submit like the
        # deadline: the flusher and _run_group run in long-lived tasks
        # whose ambient context is stale, so device timing children hang
        # off this explicit handle instead of contextvars
        self.span = span
        # the request's id on every host span (obs/hostspan.py): its trace
        # id where it has a root span, else a number of the process-wide
        # sequence; captured at submit like the span, for the same reason
        self.rid = _hostspan.request_id()
        # what the device's account cuts a starved interval at (obs/
        # account.py): the request's own arrival and body read, captured
        # like the rid; the end of ``_prepare_item``; ``_run_group``'s t0
        # (``marks``)
        self.request = _hostspan.current_request()
        self.rows_ready = None
        self.started = None
        # times this item was re-queued after a classified device fault
        # (resilience/meshfault.py) — bounded so a fault loop can never
        # recycle one item forever
        self.redispatches = 0
        # submit-time tokenization (HOST_TOKENIZER_WORKERS): a future
        # resolving to this item's pre-built rows; None when the pool is
        # off or the kind streams
        self.prepared = None

    def marks(self) -> tuple:
        """(arrived, body read, submitted, rows ready, started) as they
        stand when asked: the dispatch seam asks at the ENQUEUE, when the
        rows the stage hop waited for are there."""
        request = self.request
        return (
            request.arrived if request is not None else None,
            request.read if request is not None else None,
            self.submitted,
            self.rows_ready,
            self.started,
        )


def _rids(group: list) -> str:
    """A group's request ids as one span attribute: joined by a space (a
    comma would end the value in the profiler's encoding)."""
    return " ".join(str(item.rid) for item in group)


def _labels(sink) -> str:
    """The dispatch labels a stage hop enqueued (``many(r=8,n=64,s=512)``;
    several where one group made several device calls)."""
    return " ".join(record.label for record in sink.pending)


class _StagedGroup:
    """What the dispatch hop hands the waiter hop: the group's deferred-
    readiness sink (pending device dispatches + checked-out staging
    buffers) and the finalize closure that materializes per-item results
    after readiness."""

    __slots__ = ("sink", "finalize", "group", "spans")

    def __init__(self, sink, finalize, group=None, spans=()) -> None:
        self.sink = sink
        self.finalize = finalize
        # the dispatch group's id and its traced items' spans: the waiter
        # hop's host spans carry the one and hang on the others
        self.group = group
        self.spans = spans


class DeviceBatcher:
    """Collects concurrent device work and dispatches it in fused batches.

    ``window_ms`` bounds the extra latency a lone request pays waiting for
    company; ``max_batch`` bounds items per dispatch (oversized groups are
    chunked).  ``window_ms=0`` still batches whatever accumulates behind an
    in-flight dispatch — only the idle-arrival wait is removed.
    """

    def __init__(
        self,
        embedder,
        metrics=None,
        *,
        window_ms: float = 3.0,
        max_batch: int = 64,
        pipeline_depth: int = 2,
        max_rows: int = 512,
        embed_cache=None,
        max_queue_depth: int = 0,
        watchdog=None,
        fallback_embedder=None,
        fallback_context=None,
        meshfault=None,
        host_tokenizer_workers: int = 2,
        staging_buffers: int = 2,
        judge=None,
    ) -> None:
        self.embedder = embedder  # None where the server has only a judge
        # the local judge panel (models/judge.py TpuJudge), or None
        self.judge_model = judge
        self.metrics = metrics
        # padding accounting (/metrics ``padded``): real vs dispatched
        # token slots.  The stats lock exists because these counters
        # mutate on the dispatch executor (pipeline_depth >= 2 workers)
        # while utilization() reads them on the event loop: += on a
        # plain int is read-modify-write, and two workers interleaving
        # it drop increments (registered in
        # analysis/concurrency_model.py)
        self._stats_lock = threading.Lock()
        self._pad_real_tokens = 0
        self._pad_slot_tokens = 0
        # bounded queue (ADMISSION_MAX_QUEUE_DEPTH): arrivals beyond
        # this many pending items fail fast with OverloadedError (503)
        # instead of growing the queue without limit; 0 = unbounded
        # (the pre-change behavior)
        self.max_queue_depth = max(0, int(max_queue_depth))
        # device watchdog (resilience/watchdog.py): every dispatch is
        # bracketed begin/end so a hung PJRT call is detected
        self.watchdog = watchdog
        # CPU fallback: while the watchdog holds the device unhealthy,
        # dispatches route to this embedder instead (built against host
        # params); fallback_context() supplies the jax.default_device
        # scope so its computations stay off the wedged device
        self.fallback_embedder = fallback_embedder
        self.fallback_context = fallback_context
        self._use_fallback = False
        # mesh fault domains (resilience/meshfault.py): classifies
        # dispatch failures, injects DEVICE_FAULT_PLAN faults at the
        # _dispatch seam, and downsizes the mesh on persistent loss —
        # the batcher re-queues the failed group's live items onto the
        # new shape instead of failing them
        self.meshfault = meshfault
        self.shed_queue_full = 0
        self.shed_deadline = 0
        self.shed_redispatch_limit = 0
        self.cancelled_items = 0
        self.fallback_dispatches = 0
        # per-kind EWMA of dispatch wall time: the deadline shed drops
        # an item whose remaining budget is below the expected cost
        # (CoDel-flavored: dead-on-arrival work never reaches the MXU)
        self._ewma_ms: dict = {}
        # optional per-row embedding memoization (cache/EmbeddingCache):
        # hot rows resolve before the dispatch path, and identical rows
        # in flight collapse onto one device computation
        self.embed_cache = embed_cache
        self._embed_inflight: dict = {}
        self._embed_collapses = 0
        self.window_ms = float(window_ms)
        self.max_batch = int(max_batch)
        self.pipeline_depth = max(1, int(pipeline_depth))
        # rows (encoder batch entries) per dispatch: a synchronized burst
        # of K requests otherwise forms ONE giant group per drain round,
        # which the pipeline cannot overlap (the next round's group only
        # forms after this one's responses restart the closed loop);
        # chunking by rows turns a burst into pipeline_depth-overlappable
        # sub-dispatches sized for good MXU utilization
        self.max_rows = max(1, int(max_rows))
        # full-mesh capacity, kept so rescale_capacity is idempotent in
        # the scale (downsize 8->4->2 then recovery back to 1.0 restores
        # the configured values exactly)
        self._base_max_rows = self.max_rows
        self._base_max_batch = self.max_batch
        self._pending: list = []
        # offline priority class (ISSUE 20): a separate queue the group
        # planner only draws from when the latency queue is empty.
        # Preemption happens at dispatch boundaries for free — groups
        # are planned one at a time after each pipeline-slot acquire,
        # so a latency arrival waits behind at most the offline
        # dispatches already in flight (<= 1 extra slot wait), never
        # behind queued offline work
        self._pending_offline: list = []
        self._flusher: Optional[asyncio.Task] = None
        # ``batcher:idle`` held open while no flusher runs (_begin_idle)
        self._idle_span = None
        self._sem: Optional[asyncio.Semaphore] = None
        # set by _submit so a parked _drain starts new work immediately
        # instead of waiting out an in-flight dispatch
        self._wake: Optional[asyncio.Event] = None
        self._executor = ThreadPoolExecutor(
            max_workers=self.pipeline_depth,
            thread_name_prefix="lwc-device",
        )
        # the readiness waiters (dispatch_seam.py): one hop per in-flight
        # group blocks on its enqueued outputs OFF the dispatch thread,
        # so sizing matches the pipeline depth exactly
        self._waiters = ThreadPoolExecutor(
            max_workers=self.pipeline_depth,
            thread_name_prefix="lwc-waiter",
        )
        # submit-time tokenization pool (HOST_TOKENIZER_WORKERS; 0 =
        # tokenize on the dispatch thread, the pre-ISSUE-13 behavior)
        self.host_tokenizer_workers = max(0, int(host_tokenizer_workers))
        self._tok_pool = (
            ThreadPoolExecutor(
                max_workers=self.host_tokenizer_workers,
                thread_name_prefix="lwc-hosttok",
            )
            if self.host_tokenizer_workers > 0
            else None
        )
        # size the embedder's staging-buffer pool (STAGING_BUFFERS; the
        # waiter recycles buffers through it at readiness)
        self.staging_buffers = max(0, int(staging_buffers))
        pool = getattr(embedder, "staging_pool", None)
        if pool is not None:
            pool.per_bucket = self.staging_buffers
        # whether the device had a program, all lanes and each: views of
        # the one account the dispatch seam feeds (obs/account.py)
        self._account = device_account()
        # the groups currently in flight (``idle()``)
        self._inflight: set = set()
        self._started = time.perf_counter()
        self._dispatches = 0
        self._items = 0
        # per-lane accounting (ISSUE 20): dispatches/items counters per
        # priority class, so /metrics exposes per-class utilization.
        # Event-loop-only like the combined counters above — _observe is
        # the sole writer — so no lock (and no concurrency_model.py
        # registry row) is needed
        self._lane_dispatches = {"latency": 0, "offline": 0}
        self._lane_items = {"latency": 0, "offline": 0}
        if metrics is not None:
            metrics.register_provider("device_batcher", self.utilization)
            if embed_cache is not None:
                metrics.register_provider(
                    "embed_cache", self._embed_cache_stats
                )

    def _embed_cache_stats(self) -> dict:
        stats = self.embed_cache.stats()
        stats["inflight_collapses"] = self._embed_collapses
        return stats

    # -- public async API ----------------------------------------------------

    async def embed(
        self,
        texts: list,
        max_tokens: Optional[int] = None,
        priority: str = "latency",
    ):
        """texts -> (embeddings[N, H] f32, token_count).  Batches with every
        other embed request sharing the same ``max_tokens`` cap.

        With an ``embed_cache`` attached, rows resolve individually
        BEFORE batching: cached rows skip the device entirely, rows
        already being computed by a concurrent request are joined rather
        than recomputed, and only genuinely new rows ride a dispatch.
        The public contract is unchanged either way.

        ``priority="offline"`` routes the item through the offline
        class: it dispatches only when the latency lane has no ready
        group (train/ feed work riding an otherwise-idle device)."""
        texts = list(texts)
        if await self._route_ring(texts, max_tokens):
            # over-length request on a sequence-parallel mesh: the ring
            # dispatch serves the FULL text where the dense path would
            # truncate at max_tokens.  Bypasses the embed cache — its
            # fingerprints assume dense truncation semantics, and a
            # full-length vector under the same (text, cap) key would
            # poison dense hits (and vice versa).
            emb, row_tokens = await self._submit(
                "ring_embed",
                ("ring_embed", max_tokens),
                (texts, max_tokens),
                priority=priority,
            )
            return emb, int(np.asarray(row_tokens).sum())
        # a group is tokenized whole with one cap, so the cap is in the key
        key = ("embed", max_tokens)
        cache = self.embed_cache
        if cache is None or not cache.enabled or not texts:
            emb, row_tokens = await self._submit(
                "embed", key, (texts, max_tokens), priority=priority
            )
            return emb, int(np.asarray(row_tokens).sum())
        from ..cache.fingerprint import embed_fingerprint

        model_id = getattr(self.embedder, "model_name", "") or ""
        rows: list = [None] * len(texts)
        joins: list = []  # (row position, future) — ours or a peer's
        submit_fps: list = []
        submit_texts: list = []
        loop = asyncio.get_running_loop()
        for i, text in enumerate(texts):
            fp = embed_fingerprint(model_id, text, max_tokens)
            hit = cache.get(fp)
            if hit is not None:
                rows[i] = hit
                continue
            fut = self._embed_inflight.get(fp)
            if fut is not None:
                # identical row already being computed (by a concurrent
                # request, or earlier in THIS text list): join it
                self._embed_collapses += 1
                joins.append((i, fut))
                continue
            fut = loop.create_future()
            self._embed_inflight[fp] = fut
            submit_fps.append(fp)
            submit_texts.append(text)
            joins.append((i, fut))
        if submit_texts:
            try:
                emb, row_tokens = await self._submit(
                    "embed",
                    key,
                    (submit_texts, max_tokens),
                    priority=priority,
                )
            except BaseException as e:
                for fp in submit_fps:
                    fut = self._embed_inflight.pop(fp, None)
                    if fut is not None and not fut.done():
                        fut.set_exception(e)
                        fut.exception()  # joined peers re-raise; lone
                        # futures must not warn "never retrieved"
                raise
            row_tokens = np.asarray(row_tokens)
            for j, fp in enumerate(submit_fps):
                vec = np.asarray(emb[j])
                cache.put_row(fp, vec, int(row_tokens[j]))
                fut = self._embed_inflight.pop(fp, None)
                if fut is not None and not fut.done():
                    fut.set_result((vec, int(row_tokens[j])))
        retry: list = []
        for i, fut in joins:
            try:
                # shielded: this caller's cancellation must not poison a
                # future other requests are also joined on
                rows[i] = await asyncio.shield(fut)
            except BaseException:
                if (
                    fut.done()
                    and not fut.cancelled()
                    and fut.exception() is not None
                ):
                    retry.append(i)  # the peer's dispatch failed —
                    # recompute rather than inherit its fate
                else:
                    raise  # this caller itself was cancelled
        if retry:
            emb, row_tokens = await self._submit(
                "embed",
                key,
                ([texts[i] for i in retry], max_tokens),
                priority=priority,
            )
            row_tokens = np.asarray(row_tokens)
            for j, i in enumerate(retry):
                rows[i] = (np.asarray(emb[j]), int(row_tokens[j]))
        return (
            np.stack([r[0] for r in rows]).astype(np.float32, copy=False),
            int(sum(r[1] for r in rows)),
        )

    async def consensus(
        self,
        texts: list,
        temperature: float = 0.05,
        priority: str = "latency",
    ):
        """N candidate texts -> (confidence[N], token_count): embed +
        cosine consensus vote in one fused dispatch, with the prompt
        token count from the SAME tokenization (callers must not
        re-tokenize on the event loop for usage accounting).  Batches
        with same-N same-temperature requests via
        ``consensus_confidence_tokens_many``.

        Over-length candidate sets on a sequence-parallel mesh route to
        the ring dispatch instead (full-length scoring, no truncation)."""
        texts = list(texts)
        if await self._route_ring(texts):
            return await self._submit(
                "ring_vote",
                ("ring_vote", len(texts), float(temperature)),
                (texts, temperature),
                priority=priority,
            )
        return await self._submit(
            "consensus",
            ("consensus", len(texts), float(temperature)),
            (texts, temperature),
            priority=priority,
        )

    async def judge(
        self,
        texts: list,
        prompt: Optional[str] = None,
        panel=None,
        priority: str = "latency",
    ):
        """N candidate texts (+ the conversation they answer) ->
        (confidence[N], token_count, ballots): a local judge panel, the
        calls of ``panel`` (``[(ballot seed, weight)]``) in one device
        program.  Every item has a key of its own: a panel's program is one
        static shape, so requests share the queue and the pipeline, never a
        dispatch."""
        return await self._submit(
            "judge",
            ("judge", _hostspan.next_id()),
            (list(texts), prompt, panel),
            priority=priority,
        )

    async def _route_ring(
        self, texts: list, max_tokens: Optional[int] = None
    ) -> bool:
        """Whether this request should ride the long-context ring
        dispatch: the embedder serves a sequence-parallel mesh AND at
        least one text exceeds the dense token window.

        The gateway never sends a length cap, so routing keys off the
        ACTUAL text length.  Two tiers keep the common case free:
        ``len(text) + 2`` is an upper bound on the wordpiece token count
        (every token consumes >= 1 character, plus [CLS]/[SEP]), so any
        request under the window in characters is dense with zero extra
        work; only plausibly-long requests pay a precise tokenization,
        run OFF the event loop on the host tokenizer pool.  An explicit
        ``max_tokens`` at or under the dense window is an intentional
        truncation request and stays dense."""
        embedder = self.embedder
        if not texts or not getattr(
            embedder, "ring_available", lambda: False
        )():
            return False
        cap = embedder.max_tokens
        if max_tokens is not None and int(max_tokens) <= cap:
            return False
        if all(len(t) + 2 <= cap for t in texts):
            return False
        loop = asyncio.get_running_loop()

        def over_length() -> bool:
            _, mask = embedder.tokenize_ring(texts, max_tokens)
            return int(mask.sum(axis=1).max(initial=0)) > cap

        return await loop.run_in_executor(self._tok_pool, over_length)

    async def stream_update(
        self,
        text: str,
        buf,
        valid,
        position: int,
        temperature: float = 0.05,
        want_conf: bool = True,
    ):
        """One streaming-consensus update -> (buf, valid, confidence[CAP]).
        Batches with updates from other live streams at the same capacity
        bucket (vmapped embed + scatter + masked revote).

        ``want_conf=False`` skips the host confidence fetch (conf returns
        None): a stream folding K candidates in one burst reads only the
        LAST confidence, and K synchronous link round-trips for discarded
        intermediates would undo the batching win."""
        return await self._submit(
            "stream",
            ("stream", int(buf.shape[0]), float(temperature)),
            (text, buf, valid, position, temperature, want_conf),
        )

    def close(self) -> None:
        self._end_idle()
        self._executor.shutdown(wait=False)
        self._waiters.shutdown(wait=False)
        if self._tok_pool is not None:
            self._tok_pool.shutdown(wait=False)

    # -- overload / lifecycle hooks -------------------------------------------

    def use_fallback(self, active: bool) -> None:
        """Route dispatches to the CPU fallback embedder (watchdog
        on_trip) or back to the device (on_recover).  A bare flag read
        by the dispatch path; no-op without a fallback embedder."""
        self._use_fallback = bool(active)

    def rescale_capacity(self, scale: float) -> None:
        """Scale per-dispatch capacity to the surviving chip fraction
        (a MeshFaultManager rescale hook): a half-size mesh gets half
        the encoder rows per group, so dispatch wall time — and the
        deadline-shed EWMA feeding on it — stays roughly flat through a
        downsize.  scale=1.0 restores the configured capacity exactly."""
        scale = max(0.0, float(scale))
        self.max_rows = max(1, int(self._base_max_rows * scale))
        self.max_batch = max(1, int(self._base_max_batch * scale))

    def idle(self) -> bool:
        """No pending items (either priority class) and no dispatch in
        flight."""
        return (
            not self._pending
            and not self._pending_offline
            and not self._inflight
            and (self._flusher is None or self._flusher.done())
        )

    async def drain(self, timeout_sec: float) -> bool:
        """Wait (bounded) for every queued item to dispatch and every
        dispatch to finish; True = the queue drained clean.  The drain
        path in serve/lifecycle.py calls this after admission stops —
        nothing new arrives, so the wait is monotone."""
        deadline = time.perf_counter() + max(0.0, float(timeout_sec))
        while not self.idle():
            if time.perf_counter() >= deadline:
                return self.idle()
            await asyncio.sleep(0.005)
        return True

    # -- observability (SURVEY §5 metrics row: "device util") -----------------

    def utilization(self, window_sec: float = 60.0) -> dict:
        now = time.perf_counter()
        since = max(now - window_sec, self._started)
        # consistent counter snapshot: the dispatch workers mutate these
        # under the same lock; the staging-pool stats() call below stays
        # OUTSIDE it (the pool has its own lock — no nesting, no edge)
        with self._stats_lock:
            pad_real = self._pad_real_tokens
            pad_slot = self._pad_slot_tokens
            fallback_dispatches = self.fallback_dispatches
        return {
            "queue_depth": len(self._pending),
            # the share of the last ``window_sec`` in which the device
            # had a program enqueued (pipelined programs once)
            "busy_fraction": self._account.occupancy(None, since, now),
            "account": self._account.snapshot(),
            # per-priority-class utilization (ISSUE 20): the offline
            # lane's occupancy is the acceptance gauge for the train/
            # feed drill (>= 90% on an otherwise-idle mesh)
            "lanes": {
                lane: {
                    "queue_depth": len(
                        self._pending
                        if lane == "latency"
                        else self._pending_offline
                    ),
                    "dispatches": self._lane_dispatches[lane],
                    "items": self._lane_items[lane],
                    "busy_fraction": self._account.occupancy(
                        lane, since, now
                    ),
                }
                for lane in ("latency", "offline")
            },
            "dispatches": self._dispatches,
            "items": self._items,
            "items_per_dispatch": round(
                self._items / self._dispatches, 2
            )
            if self._dispatches
            else 0.0,
            "window_ms": self.window_ms,
            "max_batch": self.max_batch,
            # host<->device overlap machinery (ISSUE 13): submit-time
            # tokenization pool size and the embedder's staging-buffer
            # reuse counters (None when the embedder has no pool)
            "host_tokenizer_workers": self.host_tokenizer_workers,
            "staging": (
                self.embedder.staging_pool.stats()
                if getattr(self.embedder, "staging_pool", None) is not None
                else None
            ),
            "max_queue_depth": self.max_queue_depth,
            "shed_queue_full": self.shed_queue_full,
            "shed_deadline": self.shed_deadline,
            "shed_redispatch_limit": self.shed_redispatch_limit,
            "cancelled_items": self.cancelled_items,
            "fallback_active": self._use_fallback,
            "fallback_dispatches": fallback_dispatches,
            # real tokens actually embedded vs device slots dispatched:
            # what the buckets' padding costs
            "padded": {
                "real_tokens": pad_real,
                "slot_tokens": pad_slot,
                "padding_waste": round(1.0 - pad_real / pad_slot, 4)
                if pad_slot
                else 0.0,
            },
        }

    def lane_occupancy(
        self, lane: str, since: float, until: Optional[float] = None
    ) -> float:
        """Fraction of ``[since, until]`` the device had a program of
        ``lane`` enqueued, pipelined programs counted once (the acceptance
        gauge for the offline-occupancy drill): the account's view."""
        return self._account.occupancy(lane, since, until)

    # -- internals -----------------------------------------------------------

    async def _submit(self, kind, key, payload, priority="latency"):
        from .. import obs

        offline = priority == "offline"
        # enqueue -> result wall time for THIS request's item; created
        # here (the submitting task still carries the request context)
        span = obs.child_span(
            f"batcher:{kind}",
            queue_depth=len(self._pending),
            **({"lane": "offline"} if offline else {}),
        )
        # the queue-depth shed guards the LATENCY lane only: offline
        # feeders self-limit by awaiting their futures, and shedding
        # background work with a 503 would just make the drill retry it
        if (
            not offline
            and self.max_queue_depth
            and len(self._pending) >= self.max_queue_depth
        ):
            # fail fast at the door: a queue this deep means every item
            # behind it would wait out its deadline anyway (satellite
            # fix for the unbounded deque growth under overload)
            self.shed_queue_full += 1
            if self.metrics is not None:
                self.metrics.observe(
                    "device:shed:queue_full", 0.0, error=True
                )
            if span is not None:
                span.annotate(shed="queue_full")
                span.finish("error")
            from ..errors import OverloadedError

            raise OverloadedError("batcher_queue_full")
        from ..resilience.deadline import current_deadline

        loop = asyncio.get_running_loop()
        future = loop.create_future()
        item = _Item(
            kind,
            key,
            payload,
            future,
            current_deadline(),
            span,
            lane="offline" if offline else "latency",
        )
        if self._tok_pool is not None and kind in (
            "embed", "consensus", "ring_embed", "ring_vote", "judge"
        ):
            # submit-time tokenization: the item's rows build on the
            # host pool NOW, overlapping earlier groups'
            # device time; tokenizer errors park in the future and
            # re-raise on the dispatch thread, same path as before
            try:
                item.prepared = self._tok_pool.submit(
                    self._prepare_item, item
                )
            except RuntimeError:  # pool shut down mid-close
                item.prepared = None
        (self._pending_offline if offline else self._pending).append(item)
        if self._flusher is None or self._flusher.done():
            self._end_idle()
            self._flusher = loop.create_task(self._drain())
        elif self._wake is not None:
            self._wake.set()  # unpark a flusher waiting on in-flight work
        try:
            result = await future
            if span is not None:
                span.finish()
            return result
        except BaseException:
            # the caller is gone (task cancellation, or a GeneratorExit
            # thrown into a streaming generator by the client
            # disconnecting): cancel the item's future so a not-yet-
            # dispatched item is dropped from its group instead of
            # burning device time on work nobody will read
            future.cancel()
            if span is not None:
                span.finish("error")
            raise

    def _begin_idle(self) -> None:
        """No flusher runs between the last group's end and the next
        arrival: ``batcher:idle`` stays open across that stretch (begun
        where ``_drain`` ends, ended where ``_submit`` starts the next
        one, both on the event loop), so a device gap in which nothing
        was due reads as such in a profile."""
        self._idle_span = _hostspan.host_span(
            "batcher:idle", parents=()
        ).open_span()

    def _end_idle(self) -> None:
        span, self._idle_span = self._idle_span, None
        if span is not None:
            span.close_span()

    async def _drain(self) -> None:
        try:
            await self._drain_pending()
        finally:
            self._begin_idle()

    async def _drain_pending(self) -> None:
        loop = asyncio.get_running_loop()
        if self._sem is None:
            self._sem = asyncio.Semaphore(self.pipeline_depth)
            self._wake = asyncio.Event()
        if self.window_ms > 0:
            # the accumulation window: lone arrivals wait this long for
            # company; arrivals during a dispatch skip it (they already
            # waited behind the device)
            await asyncio.sleep(self.window_ms / 1000.0)
        inflight: set = set()
        while self._pending or self._pending_offline or inflight:
            if self._pending or self._pending_offline:
                # bounded pipelining: wait for a dispatch slot FIRST and
                # only then plan ONE group from whatever is pending —
                # continuous admission: items arriving while earlier
                # groups hold the device join the NEXT dispatch group
                # instead of waiting behind a plan made before they
                # existed (the old snapshot-everything drain)
                if self._sem.locked():
                    with _hostspan.host_span(
                        "batcher:slots_full",
                        parents=(),
                        pending=len(self._pending)
                        + len(self._pending_offline),
                    ):
                        await self._sem.acquire()
                else:
                    await self._sem.acquire()
                # the slot is owned here until _run_group takes it:
                # release on every non-handoff exit (shed-to-empty,
                # _shed_group raising) or the pipeline wedges one
                # depth shallower per leak
                handed_off = False
                try:
                    # shed AFTER the slot wait — that queueing delay
                    # is exactly where deadlines die under overload
                    group = self._shed_group(self._next_group())
                    if group:
                        task = loop.create_task(
                            self._run_group(loop, group)
                        )
                        inflight.add(task)
                        task.add_done_callback(inflight.discard)
                        handed_off = True
                finally:
                    if not handed_off:
                        self._sem.release()
            else:
                # park until a dispatch finishes OR a new item arrives
                # (_submit sets the wake event) — a free pipeline slot
                # must start staging new work immediately, not wait out
                # the in-flight device call
                self._wake.clear()
                waker = loop.create_task(self._wake.wait())
                try:
                    with _hostspan.host_span("batcher:idle", parents=()):
                        await asyncio.wait(
                            {waker, *inflight},
                            return_when=asyncio.FIRST_COMPLETED,
                        )
                finally:
                    waker.cancel()

    def _next_group(self) -> list:
        """Plan ONE dispatch group from the live pending queue: the head
        item's key, joined by every same-key arrival (order preserved) up
        to ``max_batch`` items and the row budget; everything else stays
        pending for the next iteration.  Planning one group at a time —
        AFTER the pipeline-slot wait — is what makes the batcher
        continuous: work that arrives during an in-flight dispatch is in
        ``self._pending`` by the time this runs, so it rides the very
        next group instead of a pre-made plan.

        Consensus groups keep the pow2-chunk policy (``_pow2_chunks``):
        the first chunk dispatches now, the remainder returns to the
        FRONT of the queue (they are the oldest same-key items) and
        dispatches next iteration — same chunk sizes as the snapshot
        drain, one slot apart.

        Priority classes (ISSUE 20): the latency queue is ALWAYS
        planned first; the offline queue contributes a group only when
        no latency item is ready.  Because this selection re-runs after
        every pipeline-slot acquire, an offline backlog yields the very
        next slot to a latency arrival — the offline class can delay
        latency work by at most the dispatch already in flight."""
        from_latency = bool(self._pending)
        pending = self._pending if from_latency else self._pending_offline
        if not pending:
            return []
        key = pending[0].key
        take: list = []
        rest: list = []
        rows = 0
        closed = False  # once one same-key item misses the budget, later
        # same-key items must not jump it (per-key FIFO is the contract)
        for item in pending:
            r = self._rows(item)
            if (
                item.key == key
                and not closed
                and len(take) < self.max_batch
                and (not take or rows + r <= self.max_rows)
            ):
                take.append(item)
                rows += r
            else:
                if item.key == key:
                    closed = True
                rest.append(item)
        if from_latency:
            self._pending = rest
        else:
            self._pending_offline = rest
        if take and take[0].kind == "consensus" and key[0] == "consensus":
            chunks = list(self._pow2_chunks(take))
            if len(chunks) > 1:
                remainder = [i for c in chunks[1:] for i in c]
                if from_latency:
                    self._pending = remainder + self._pending
                else:
                    self._pending_offline = (
                        remainder + self._pending_offline
                    )
                take = chunks[0]
        return take

    def _shed_group(self, group: list) -> list:
        """Items still worth dispatching: drops items whose caller
        already cancelled (client disconnect), and fails items whose
        propagated deadline is expired — or has less budget left than
        this kind's warm dispatch-time estimate — with 504 (CoDel-style:
        dead work is cheapest to drop the moment before it costs MXU
        time)."""
        live = []
        for item in group:
            if item.future.done():
                # cancelled by a departed caller (_submit's except path)
                self.cancelled_items += 1
                continue
            deadline = item.deadline
            if deadline is not None:
                estimate = self._ewma_ms.get(item.kind)
                doomed = deadline.expired() or (
                    estimate is not None
                    and deadline.remaining() * 1e3 < estimate
                )
                if doomed:
                    from ..errors import DeadlineExceededError

                    if item.span is not None:
                        # finished by _submit when the exception lands
                        item.span.annotate(shed="deadline")
                    item.future.set_exception(
                        DeadlineExceededError("shed before device dispatch")
                    )
                    self.shed_deadline += 1
                    if self.metrics is not None:
                        self.metrics.observe(
                            "device:shed:deadline", 0.0, error=True
                        )
                    continue
            live.append(item)
        return live

    async def _run_group(self, loop, group) -> None:
        t0 = time.perf_counter()
        token = object()
        self._inflight.add(token)
        from ..obs import phases as _phases

        for item in group:
            item.started = t0
            _phases.observe_phase(
                "batcher_queue", (t0 - item.submitted) * 1e3
            )
        # device wall-time children on each traced item's batcher span,
        # bracketing exactly what the watchdog brackets (the executor
        # hop + the PJRT call); the mesh epoch stamps which shape served
        # the dispatch, so a re-dispatched item's span tree shows one
        # child per epoch it touched — and a classified fault hands the
        # SAME stamp to downsize(), which skips the ladder step when the
        # epoch already advanced (two pipelined groups faulting on one
        # dead device must cost one rung, not two)
        epoch = self.meshfault.epoch if self.meshfault is not None else None
        extra = {"mesh_epoch": epoch} if epoch is not None else {}
        dspans = [
            item.span.child(
                "device:dispatch",
                kind=item.kind,
                batch_size=len(group),
                **extra,
            )
            for item in group
            if item.span is not None
        ]
        error = False
        wd_token = (
            self.watchdog.begin(group[0].kind)
            if self.watchdog is not None
            else None
        )
        try:
            staged = await loop.run_in_executor(
                self._executor, self._dispatch, group
            )
            # readiness moved OFF the dispatch thread (ISSUE 13): the
            # hop above returns at enqueue, freeing its executor worker
            # to stage the next group; this waiter hop blocks on the
            # enqueued outputs, records device time,
            # and materializes per-item results
            results = await loop.run_in_executor(
                self._waiters, self._finalize_group, staged
            )
        except Exception as e:
            error = True
            # device-fault triage (resilience/meshfault.py): a classified
            # fault re-queues the group's live items (after a downsize,
            # when the fault is persistent) instead of failing them;
            # ordinary application errors — and anything raised by the
            # CPU twin — keep the fail-the-group path byte-for-byte.
            # Faults now surface on EITHER hop — inject/staging errors on
            # the dispatch thread, device faults at the waiter where
            # readiness reports them — and both land here
            kind = (
                self.meshfault.classify(e)
                if self.meshfault is not None and not self._use_fallback
                else None
            )
            if kind is not None:
                await self._handle_device_fault(loop, kind, e, group, epoch)
            else:
                for item in group:
                    if not item.future.done():
                        item.future.set_exception(e)
            self._observe(group, t0, token, error=True)
        else:
            for item, result in zip(group, results):
                if not item.future.done():
                    item.future.set_result(result)
            self._observe(group, t0, token, error=False)
        finally:
            if wd_token is not None:
                self.watchdog.end(wd_token)
            for dspan in dspans:
                dspan.finish("error" if error else None)
            self._sem.release()

    # each item survives at most this many fault re-queues before it
    # inherits the device exception — a backstop above the natural bound
    # (ladder length x transient retries) so a pathological fault plan
    # can never recycle one item indefinitely
    REDISPATCH_LIMIT = 8

    async def _handle_device_fault(
        self, loop, kind, exc, group, epoch=None
    ) -> None:
        """React to a classified device fault: persistent faults walk
        the downsize ladder (off the event loop — the downsize blocks on
        the shape gate until in-flight dispatches drain, and holds the
        failed dispatch's launch epoch so concurrent faults from one
        dead device step the ladder exactly once); a spent ladder
        flips to the CPU twin — the last resort, per the
        DEVICE_WATCHDOG_CPU_FALLBACK x MESH_ENABLED precedence — and a
        spent ladder WITHOUT a twin fails the group.  Every surviving
        path re-queues the group's live items for re-dispatch on the
        new (or retried) shape."""
        if kind == "persistent":
            ok = await loop.run_in_executor(
                self._executor,
                functools.partial(
                    self.meshfault.downsize, observed_epoch=epoch
                ),
            )
            if not ok:
                if self.fallback_embedder is not None:
                    self.use_fallback(True)
                else:
                    for item in group:
                        if not item.future.done():
                            item.future.set_exception(exc)
                    return
        self._requeue(group, exc)

    def _requeue(self, group, exc) -> None:
        """Put a faulted group's items back at the FRONT of the pending
        queue (they are the oldest work), bounded by their propagated
        deadlines — an item past budget sheds 504 here exactly as the
        pre-dispatch shed does — and by REDISPATCH_LIMIT."""
        from ..errors import DeadlineExceededError

        live = []
        for item in group:
            if item.future.done():
                self.cancelled_items += 1
                continue
            if item.deadline is not None and item.deadline.expired():
                if item.span is not None:
                    item.span.annotate(shed="deadline")
                item.future.set_exception(
                    DeadlineExceededError("deadline expired during re-dispatch")
                )
                self.shed_deadline += 1
                if self.metrics is not None:
                    self.metrics.observe(
                        "device:shed:deadline", 0.0, error=True
                    )
                continue
            if item.redispatches >= self.REDISPATCH_LIMIT:
                # observable like the adjacent deadline shed: a fault
                # loop exhausting items must show up in /metrics, not
                # only as client-side errors
                if item.span is not None:
                    item.span.annotate(shed="redispatch_limit")
                item.future.set_exception(exc)
                self.shed_redispatch_limit += 1
                if self.metrics is not None:
                    self.metrics.observe(
                        "device:shed:redispatch", 0.0, error=True
                    )
                continue
            item.redispatches += 1
            live.append(item)
        if not live:
            return
        # items return to the FRONT of their own lane's queue: a faulted
        # offline group must not jump the latency class on re-dispatch
        offline = [i for i in live if i.lane == "offline"]
        latency = [i for i in live if i.lane != "offline"]
        if latency:
            self._pending[:0] = latency
        if offline:
            self._pending_offline[:0] = offline
        self.meshfault.note_redispatch(len(live))
        if self._wake is not None:
            self._wake.set()

    def _observe(self, group, t0, token, *, error: bool) -> None:
        end = time.perf_counter()
        self._inflight.discard(token)
        self._dispatches += 1
        self._items += len(group)
        lane = group[0].lane
        self._lane_dispatches[lane] += 1
        self._lane_items[lane] += len(group)
        series = group[0].kind
        if not error:
            # warm per-kind dispatch-time estimate for the deadline shed
            ms = (end - t0) * 1e3
            prev = self._ewma_ms.get(series)
            self._ewma_ms[series] = (
                ms if prev is None else 0.8 * prev + 0.2 * ms
            )
        if self.metrics is not None:
            # exemplar: the first traced item in the group links this
            # series to a concrete span tree (explicit handle — ambient
            # reads would see the flusher task's stale context)
            trace_id = next(
                (
                    item.span.trace.trace_id
                    for item in group
                    if item.span is not None
                ),
                None,
            )
            self.metrics.observe(
                f"device:batch:{series}",
                (end - t0) * 1e3,
                error=error,
                trace_id=trace_id,
            )

    @staticmethod
    def _rows(item) -> int:
        """Encoder rows one item contributes to its dispatch."""
        if item.kind in ("embed", "consensus", "ring_embed", "ring_vote"):
            return max(1, len(item.payload[0]))
        return 1  # stream: one new candidate per update

    def _group(self, batch: list):
        """Compatible-work groups, arrival order preserved, each at most
        ``max_batch`` items AND ``max_rows`` encoder rows (so one burst
        splits into pipeline-overlappable dispatches).

        Consensus groups whose pow2-bucket padding would waste more than
        a quarter of the device rows are additionally split into
        power-of-two chunks (9 -> 8+1): the consensus device path buckets
        the request dimension to the next power of two (a full-encoder
        jit specialization per bucket, so buckets must stay coarse), and
        e.g. a 9-request group padded to 16 would burn 44% of its rows
        embedding [PAD] slots.  Chunks reuse the already-compiled
        specializations and pipeline (``pipeline_depth``); mild padding
        (<=25%) is kept whole because an extra dispatch costs a pipeline
        slot — not worth a few pad rows (r4 code-review finding)."""
        groups: dict = {}
        order = []
        for item in batch:
            if item.key not in groups:
                groups[item.key] = []
                order.append(item.key)
            groups[item.key].append(item)
        for key in order:
            items = groups[key]
            group: list = []
            rows = 0
            for item in items:
                r = self._rows(item)
                if group and (
                    len(group) >= self.max_batch
                    or rows + r > self.max_rows
                ):
                    yield from self._pow2_chunks(group)
                    group, rows = [], 0
                group.append(item)
                rows += r
            if group:
                yield from self._pow2_chunks(group)

    @staticmethod
    def _pow2_chunks(group: list):
        """Split a group into pow2-sized chunks wherever the padded
        single dispatch would waste >25% of its rows; otherwise pass it
        through whole (see _group docstring for the trade).  Only the
        consensus kind benefits: embed batches pad total ROWS, not
        items, and the stream path's R bucket has a minimum of 16, so
        chunking small stream groups would strictly ADD padding and
        dispatches."""
        if group[0].kind != "consensus":
            yield group
            return
        start = 0
        remaining = len(group)
        from ..utils import next_pow2

        while remaining:
            bucket = next_pow2(remaining)
            if (bucket - remaining) * 4 <= bucket:
                # <=25% padding: one dispatch beats extra round-trips
                yield group[start:]
                return
            size = bucket // 2  # largest pow2 below remaining
            yield group[start : start + size]
            start += size
            remaining -= size

    # -- dispatch implementations (device thread) ------------------------------

    def _dispatch(self, group: list):
        """Stage-and-enqueue hop: returns a plain result list on the
        fallback paths, or a ``_StagedGroup`` whose device work is
        ENQUEUED but not awaited — ``_finalize_group`` (waiter hop)
        finishes it."""
        spans = [item.span for item in group if item.span is not None]
        gid = _hostspan.next_id()
        with _hostspan.host_span(
            "batcher:stage", parents=spans, group=gid, rids=_rids(group)
        ) as stage:
            staged = self._stage(group)
            if isinstance(staged, _StagedGroup):
                staged.group, staged.spans = gid, spans
                stage.annotate(label=_labels(staged.sink))
        return staged

    def _stage(self, group: list):
        fn = getattr(self, "_dispatch_" + group[0].kind)
        if self._use_fallback and self.fallback_embedder is not None:
            with self._stats_lock:
                self.fallback_dispatches += 1
            if self.fallback_context is not None:
                # jax.default_device scope: the fallback's computations
                # must stage on the CPU, never queue behind the wedged
                # device dispatch the watchdog tripped on.  No deferral:
                # the twin's results materialize inline, inside the scope
                with self.fallback_context():
                    return fn(group, self.fallback_embedder)()
            return fn(group, self.fallback_embedder)()
        # the lane and the oldest item's timestamps ride the sink to the
        # enqueue, where the device's account takes them
        oldest = min(group, key=lambda item: item.submitted)
        sink = _seam.DispatchSink(lane=oldest.lane, marks=oldest.marks)
        if self.meshfault is not None:
            # shared side of the shape gate: this dispatch's embedder
            # reads (params, batch_multiple, shardings) are serialized
            # against downsize/try_recover re-shards (the executor has
            # pipeline_depth workers, so "run the re-shard on the
            # executor" alone would NOT serialize them).  The gate
            # releases at ENQUEUE: the PJRT call has captured its
            # buffers by then, so a re-shard swapping ``params`` cannot
            # tear in-flight device work — faults from that work surface
            # at the waiter and classify exactly like dispatch-thread
            # ones.  The DEVICE_FAULT_PLAN seam injects here, on the
            # dispatch thread where a real staging failure would raise;
            # the CPU-twin branch above never injects (the plan models
            # the device tier)
            with self.meshfault.dispatch_guard():
                self.meshfault.maybe_inject()
                with _seam.deferred_readiness(sink):
                    finalize = fn(group, self.embedder)
        else:
            with _seam.deferred_readiness(sink):
                finalize = fn(group, self.embedder)
        return _StagedGroup(sink, finalize)

    def _finalize_group(self, staged):
        """Waiter hop (lwc-waiter thread): block on the group's enqueued
        outputs, record per-bucket device time (the seam tells the
        device's account each ready), recycle staging buffers, then run
        the finalize closure (np conversions + per-item splits).  Device
        faults raise here and ride ``_run_group``'s triage."""
        if not isinstance(staged, _StagedGroup):
            return staged  # fallback path: already final
        from ..obs import phases as _phases

        pool = getattr(self.embedder, "staging_pool", None)
        with _hostspan.host_span(
            "device:wait",
            parents=staged.spans,
            group=staged.group,
            label=_labels(staged.sink),
        ):
            _seam.drain_sink(
                staged.sink,
                observe_device=_phases.observe_device,
                release=pool.release if pool is not None else None,
            )
        with _hostspan.host_span(
            "host:finalize", parents=staged.spans, group=staged.group
        ):
            results = staged.finalize()
        if self.meshfault is not None and not self._use_fallback:
            # the success note moves with readiness: a dispatch only
            # resets the transient-fault streak once its device work
            # actually completed, not merely enqueued
            self.meshfault.note_dispatch_ok()
        return results

    def _prepare_item(self, item):
        """Submit-time host work for one item (lwc-hosttok thread):
        pre-built padded rows for embed/consensus items, a judge's
        calls for a judge item.  Always runs against the
        PRIMARY embedder's tokenizer; the dispatch falls back to inline
        tokenization when it is serving the CPU twin.  Its end is the
        item's ``rows_ready`` (the device's account, ``_Item.marks``)."""
        try:
            return self._prepare_rows(item)
        finally:
            item.rows_ready = time.perf_counter()

    def _prepare_rows(self, item):
        kind, payload = item.kind, item.payload
        if kind == "judge":
            return self._prepare_judge(item)
        ring = kind in ("ring_embed", "ring_vote")
        texts, second = payload  # the cap of an embed, a vote's temperature
        cap = (second,) if kind in ("embed", "ring_embed") else ()
        return self._tokenize(
            self.embedder.tokenize_ring if ring else self.embedder.tokenize,
            texts,
            *cap,
            parents=(item.span,),
            rid=item.rid,
        )

    @staticmethod
    def _tokenize(tokenize, texts, *cap, parents, **ids):
        """texts -> (ids, mask) under ``host:tokenize``: on the host pool
        for one item (``rid``), or inline in the stage hop for a whole
        group whose items came without prepared rows (``rids``)."""
        with _hostspan.host_span(
            "host:tokenize", parents=parents, **ids
        ) as span:
            rows = tokenize(texts, *cap)
            span.annotate(rows=len(rows[0]), tokens=int(rows[1].sum()))
        return rows

    def _group_rows(self, group: list, embedder, tokenize, *cap):
        """The group's rows: joined from its items' submit-time rows, or
        tokenized here, inside ``batcher:stage`` (pool off, CPU twin,
        mid-close)."""
        prepared = self._prepared_rows(group, embedder)
        if prepared is not None:
            return prepared
        return self._tokenize(
            tokenize,
            [t for item in group for t in item.payload[0]],
            *cap,
            parents=[item.span for item in group],
            rids=_rids(group),
        )

    def _prepared_rows(self, group: list, embedder):
        """Concatenate the group's submit-time tokenized rows into the
        batch group-level ``tokenize`` would have produced: each item's
        rows are padded from its own seq bucket out to the group's
        (fill = the tokenizer pad id, mask 0 — the exact background
        ``encode_batch`` writes), so the result is byte-identical to
        tokenizing the whole group at once.  None when any item lacks
        prepared rows (pool off, CPU twin, mid-close)."""
        if embedder is not self.embedder:
            return None
        rows = []
        for item in group:
            fut = item.prepared
            if fut is None:
                return None
            rows.append(fut.result())  # re-raises tokenizer errors
        width = max(ids.shape[1] for ids, _ in rows)
        if len(rows) == 1:
            return rows[0]
        pad_id = int(
            getattr(getattr(embedder, "tokenizer", None), "pad_id", 0) or 0
        )
        ids_parts, mask_parts = [], []
        for ids, mask in rows:
            gap = width - ids.shape[1]
            if gap:
                ids = np.pad(
                    ids, ((0, 0), (0, gap)), constant_values=pad_id
                )
                mask = np.pad(mask, ((0, 0), (0, gap)))
            ids_parts.append(ids)
            mask_parts.append(mask)
        return np.concatenate(ids_parts), np.concatenate(mask_parts)

    def _dispatch_embed(self, group: list, embedder):
        max_tokens = group[0].payload[1]
        counts = [len(item.payload[0]) for item in group]
        ids, mask = self._group_rows(
            group, embedder, embedder.tokenize, max_tokens
        )
        self._count_padded(embedder, ids, mask)
        emb = embedder.embed_tokens(ids, mask)
        tokens = mask.sum(axis=1)

        def finalize() -> list:
            # waiter hop: emb materializes AFTER readiness (under the
            # deferred scope embed_tokens handed back the device array)
            emb_np = np.asarray(emb)
            out = []
            start = 0
            for count in counts:
                # per-ROW token counts (not the summed total): embed()
                # needs row granularity for the per-row memoization path
                # and sums for the public (emb, total_tokens) contract
                out.append(
                    (
                        emb_np[start : start + count],
                        tokens[start : start + count],
                    )
                )
                start += count
            return out

        return finalize

    def _dispatch_consensus(self, group: list, embedder):
        texts0, temperature = group[0].payload
        n = len(texts0)
        ids, mask = self._group_rows(group, embedder, embedder.tokenize)
        if len(group) == 1:
            with self._stats_lock:
                self._pad_real_tokens += int(mask.sum())
                self._pad_slot_tokens += int(ids.size)
            conf = embedder.consensus_confidence_tokens(
                ids, mask, temperature
            )
            tok = int(mask.sum())

            def finalize_one() -> list:
                return [(np.asarray(conf), tok)]

            return finalize_one
        r = len(group)
        from ..utils import next_pow2

        # the grouped dispatch pads the request dim to its pow2 bucket
        with self._stats_lock:
            self._pad_real_tokens += int(mask.sum())
            self._pad_slot_tokens += int(next_pow2(r) * n * ids.shape[1])
        conf = embedder.consensus_confidence_tokens_many(
            ids.reshape(r, n, -1), mask.reshape(r, n, -1), temperature
        )
        tokens = mask.reshape(r, n, -1).sum(axis=(1, 2))

        def finalize() -> list:
            conf_np = np.asarray(conf)
            return [(conf_np[i], int(tokens[i])) for i in range(r)]

        return finalize

    # -- local judge panel --------------------------------------------------

    def _prepare_judge(self, item):
        """A judge item's host work under ``host:tokenize``: each candidate
        tokenized once, the panel's ballots and prompts built."""
        texts, prompt, panel = item.payload
        with _hostspan.host_span(
            "host:tokenize", parents=(item.span,), rid=item.rid
        ) as span:
            prepared = self.judge_model.prepare(texts, prompt, panel)
            span.annotate(rows=len(prepared.calls), tokens=prepared.tokens)
        return prepared

    def _dispatch_judge(self, group: list, embedder):
        """One item, one program: causal prefill of every call, one decoded
        key letter through the latent cache, the masked reads and the vote
        (models/judge.py ``judge_panel``)."""
        (item,) = group
        judge = self.judge_model
        prepared = (
            item.prepared.result()  # re-raises tokenizer and size errors
            if item.prepared is not None
            else self._prepare_judge(item)
        )
        with self._stats_lock:
            self._pad_real_tokens += prepared.tokens
            self._pad_slot_tokens += int(prepared.ids.size)
        out = judge.dispatch(prepared)

        def finalize() -> list:
            return [judge.finalize(prepared, out)]

        return finalize

    # -- long-context ring dispatch -------------------------------------------

    def _dispatch_ring_embed(self, group: list, embedder):
        """Over-length embed items -> full-length embeddings via the
        sequence-parallel ring dispatch (``embed_tokens_ring``).  Only
        the primary embedder carries the sp mesh; on the CPU twin the
        group falls back to the dense (truncating) dispatch — degraded
        but serving, the same contract every other kind has there."""
        if not getattr(embedder, "ring_available", lambda: False)():
            return self._dispatch_embed(group, embedder)
        max_tokens = group[0].payload[1]
        counts = [len(item.payload[0]) for item in group]
        ids, mask = self._group_rows(
            group, embedder, embedder.tokenize_ring, max_tokens
        )
        self._count_padded(embedder, ids, mask)
        emb = embedder.embed_tokens_ring(ids, mask)
        tokens = mask.sum(axis=1)

        def finalize() -> list:
            emb_np = np.asarray(emb)
            out = []
            start = 0
            for count in counts:
                out.append(
                    (
                        emb_np[start : start + count],
                        tokens[start : start + count],
                    )
                )
                start += count
            return out

        return finalize

    def _dispatch_ring_vote(self, group: list, embedder):
        """Over-length consensus items -> full-length scoring via the
        fused ring embed + vote (``consensus_confidence_tokens_ring``).
        One device dispatch PER item — there is no grouped ring vote
        (long-context groups are rare and row-heavy; the per-item
        dispatches still pipeline through the shared readiness sink) —
        with the dense (truncating) fallback on the CPU twin."""
        if not getattr(embedder, "ring_available", lambda: False)():
            return self._dispatch_consensus(group, embedder)
        staged = []
        for item in group:
            _texts, temperature = item.payload
            ids, mask = self._group_rows(
                [item], embedder, embedder.tokenize_ring
            )
            with self._stats_lock:
                self._pad_real_tokens += int(mask.sum())
                self._pad_slot_tokens += int(ids.size)
            conf = embedder.consensus_confidence_tokens_ring(
                ids, mask, temperature
            )
            staged.append((conf, int(mask.sum())))

        def finalize() -> list:
            return [(np.asarray(conf), tok) for conf, tok in staged]

        return finalize

    def _count_padded(self, embedder, ids, mask) -> None:
        """Padded-path efficiency accounting for an embed dispatch: real
        tokens vs the row-bucketed slot count ``embed_tokens`` pads to."""
        try:
            from ..models.embedder import _bucket

            pad_b = _bucket(
                ids.shape[0], getattr(embedder, "MAX_DEVICE_BATCH", 4096)
            )
            # mesh/dp embedders pad the bucket up to the dp multiple too
            pad_b += (-pad_b) % getattr(embedder, "batch_multiple", 1)
        except Exception:
            pad_b = ids.shape[0]
        with self._stats_lock:
            self._pad_real_tokens += int(mask.sum())
            self._pad_slot_tokens += int(pad_b * ids.shape[1])

    def _dispatch_stream(self, group: list, embedder):
        if len(group) == 1:
            text, buf, valid, position, temperature, want = group[0].payload
            out_buf, out_valid, conf = embedder.stream_vote_update(
                text, buf, valid, position, temperature
            )

            def finalize_one() -> list:
                # fetch here, on the waiter thread — a device-resident
                # conf would make the caller's np.asarray stall the
                # event loop for a link round-trip per update
                return [
                    (out_buf, out_valid, np.asarray(conf) if want else None)
                ]

            return finalize_one
        texts = [item.payload[0] for item in group]
        bufs = [item.payload[1] for item in group]
        valids = [item.payload[2] for item in group]
        positions = [item.payload[3] for item in group]
        temperature = group[0].payload[4]
        wants = [item.payload[5] for item in group]
        out_bufs, out_valids, confs = embedder.stream_vote_update_many(
            texts, bufs, valids, positions, temperature
        )

        def finalize() -> list:
            # fetch ALL wanted confidences in ONE transfer here, on the
            # waiter thread: every stream np.asarray's its own
            # confidence right after this returns, and R separate slice
            # fetches would re-serialize the round-trips the batching
            # just fused (R x link RTT per dispatch).  bufs / valids
            # stay device-resident — nobody reads them on host.
            confs_host = np.asarray(confs) if any(wants) else None
            return [
                (
                    out_bufs[i],
                    out_valids[i],
                    confs_host[i] if wants[i] else None,
                )
                for i in range(len(group))
            ]

        return finalize
