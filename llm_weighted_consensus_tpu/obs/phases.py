"""Phase-attributed request timing (ISSUE 11 tentpole piece 1).

How a request's time splits between host and device is a first-class,
always-on aggregate.  Every scored request is bracketed through a fixed
phase vocabulary:

* ``admission_wait``  — gateway door to admission slot held
* ``http_read``       — the request's body read off the socket (span
  ``http:read``)
* ``http_parse``      — body parsed and validated (span ``http:parse``)
* ``tokenize``        — an item's texts to padded rows, on the host
  tokenizer pool at submit or inline in the stage hop (``host:tokenize``)
* ``batcher_queue``   — item enqueued to its group taking the device
* ``stage``           — a group's rows joined, padded, put on the device
  and its program enqueued (``batcher:stage``)
* ``device_dispatch`` — a device executable's SOJOURN, enqueue to ready
  at the embedder seam (models/dispatch_seam.py: the batcher's waiter
  thread blocks; direct callers pay an inline bracket), per (mesh-shape,
  bucket): with two programs in flight it holds the wait behind the one
  ahead, which ``obs/account.py`` splits off (``service_ms`` /
  ``waited_ms``)
* ``finalize``        — results fetched, converted and split per item
  (``host:finalize``)
* ``host_tally``      — consensus tally on host
* ``upstream_judge``  — judge LLM streaming fan-out
* ``http_respond``    — result in hand to response object built
  (``http:respond``)

The phases named with a span are fed by ``obs.host_span`` (hostspan.py),
which also puts the span on the profiler's clock and on the request's tree.

Two consumers, two mechanisms:

1. **Aggregates** — instrumentation sites call ``observe_phase`` /
   ``observe_device`` directly into one process-global aggregator of
   mergeable log-bucket histograms (obs/histogram.py).  Global on
   purpose: the sites span the event loop, executor threads and the
   score client, and the phases section must work for harnesses that
   drive the batcher without a gateway.  The aggregator takes a lock
   per observe — the executor threads are real writers.
2. **Per-request breakdown** — ``phase_breakdown(trace)`` re-derives
   the same vocabulary from a finished PR 5 span tree (interval union,
   so R concurrent judge streams attribute wall time once, not R
   times).  The gateway's trace middleware annotates every root span
   with it; tests assert the phase sum lands within 10% of the
   request's end-to-end latency.

Stdlib-only, dependency-free below ``utils`` like the rest of ``obs/``.
"""

from __future__ import annotations

import threading
from typing import Dict, List, Optional, Tuple

from .account import device_account
from .histogram import Histogram

# the phase vocabulary, in request order; the /metrics ``phases``
# section renders exactly these keys
PHASES = (
    "admission_wait",
    "http_read",
    "http_parse",
    "tokenize",
    "batcher_queue",
    "stage",
    "device_dispatch",
    "finalize",
    "host_tally",
    "upstream_judge",
    "http_respond",
)


class PhaseAggregator:
    """Process-global phase + per-bucket device-time histograms.

    Lock-guarded because device timing lands from executor threads
    while HTTP phases land from the event loop; each observe is one
    O(1) histogram increment under an uncontended lock."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._phases: Dict[str, Histogram] = {}
        self._device: Dict[str, Histogram] = {}

    def observe_phase(self, phase: str, ms: float) -> None:
        with self._lock:
            hist = self._phases.get(phase)
            if hist is None:
                hist = self._phases[phase] = Histogram()
            hist.observe(ms)

    def observe_device(self, bucket: str, ms: float) -> None:
        """One device executable run at ``bucket`` (a canonical label
        like ``vote1(n=8,s=16)@dp4xtp2``): feeds both the per-bucket
        table the roofline gauge reads and the ``device_dispatch``
        phase aggregate."""
        with self._lock:
            hist = self._device.get(bucket)
            if hist is None:
                hist = self._device[bucket] = Histogram()
            hist.observe(ms)
            phist = self._phases.get("device_dispatch")
            if phist is None:
                phist = self._phases["device_dispatch"] = Histogram()
            phist.observe(ms)

    # -- read side ------------------------------------------------------------

    def snapshot(self) -> dict:
        """The /metrics ``phases`` section: per-phase histogram summary,
        in ``PHASES`` order, the observed phases only.  (Whether pipelined
        dispatches keep the device fed is the account's to say:
        ``device_batcher.account``, obs/account.py.)"""
        with self._lock:
            rows = {
                phase: hist.to_json_obj()
                for phase, hist in self._phases.items()
            }
        return {phase: rows[phase] for phase in PHASES if phase in rows}

    def device_snapshot(self) -> Dict[str, dict]:
        """Per-(mesh-shape, bucket) device-time summaries."""
        with self._lock:
            return {
                bucket: hist.to_json_obj()
                for bucket, hist in sorted(self._device.items())
            }

    def raw_histograms(self) -> Tuple[Dict[str, Histogram], Dict[str, Histogram]]:
        """Cloned (phases, device) histogram maps for the Prometheus
        renderer — clones, so rendering never races an executor-thread
        observe."""
        with self._lock:
            return (
                {k: _clone(h) for k, h in self._phases.items()},
                {k: _clone(h) for k, h in self._device.items()},
            )

    def device_quantile(self, bucket: str, q: float) -> Optional[float]:
        with self._lock:
            hist = self._device.get(bucket)
            return hist.quantile(q) if hist is not None else None

    def reset(self) -> None:
        with self._lock:
            self._phases.clear()
            self._device.clear()


def _clone(hist: Histogram) -> Histogram:
    return Histogram().merge(hist)


_AGG = PhaseAggregator()


def aggregator() -> PhaseAggregator:
    return _AGG


def observe_phase(phase: str, ms: float) -> None:
    _AGG.observe_phase(phase, ms)


def observe_device(bucket: str, ms: float) -> None:
    _AGG.observe_device(bucket, ms)


def phases_snapshot() -> dict:
    return _AGG.snapshot()


def reset_phases() -> None:
    """Tests: the aggregator and, beside it, the device's account."""
    _AGG.reset()
    device_account().reset()


# ---------------------------------------------------------------------------
# Per-request breakdown from a finished span tree
# ---------------------------------------------------------------------------


def _union_ms(intervals: List[Tuple[float, float]]) -> float:
    """Total length of the union of (start, end) intervals — concurrent
    judge streams attribute wall time once."""
    if not intervals:
        return 0.0
    intervals.sort()
    total = 0.0
    cur_start, cur_end = intervals[0]
    for start, end in intervals[1:]:
        if start > cur_end:
            total += cur_end - cur_start
            cur_start, cur_end = start, end
        else:
            cur_end = max(cur_end, end)
    return total + (cur_end - cur_start)


def phase_breakdown(trace) -> dict:
    """Attribute one finished trace's wall time to the phase vocabulary.

    Span-derived, each interval of wall time attributed once, to the
    innermost thing that names it: the host spans (``host:tokenize``,
    ``batcher:stage``, ``host:finalize``) first, then what is left of
    the ``device:dispatch`` bracket around them is device time, then what
    is left of the item's ``batcher:<kind>`` span is queue time;
    ``http:read``, ``http:parse``, ``http:respond``, ``consensus:tally`` and
    ``judge:stream`` map directly; ``admission_wait_ms`` rides a root
    annotation (the admission middleware runs before any child span
    exists).  Returns ``{phase: ms}`` plus ``e2e_ms`` and the
    unattributed ``other_ms`` remainder — the acceptance bar is that the
    named phases sum to within 10% of ``e2e_ms`` on a served request."""
    by_name: Dict[str, List[Tuple[float, float]]] = {}
    batcher: List[Tuple[float, float]] = []
    root = trace.spans[0] if trace.spans else None
    for span in trace.spans:
        dur = span.duration_ms()
        if dur is None:
            continue
        start = span.start_ms()
        name = span.name
        if name in _BREAKDOWN_SPANS:
            by_name.setdefault(name, []).append((start, start + dur))
        elif name.startswith("batcher:"):
            batcher.append((start, start + dur))

    def of(name: str) -> List[Tuple[float, float]]:
        return by_name.get(name, [])

    # each layer's share is what it adds to the union of those inside it
    covered: List[Tuple[float, float]] = []
    total = 0.0
    grown = {}
    for key, intervals in (
        ("tokenize", of("host:tokenize")),
        ("stage", of("batcher:stage")),
        ("finalize", of("host:finalize")),
        ("device_dispatch", of("device:dispatch")),
        ("batcher_queue", batcher),
    ):
        covered += intervals
        grown[key] = _union_ms(covered) - total
        total += grown[key]
    out = {
        "admission_wait": float(
            root.attributes.get("admission_wait_ms", 0.0)
        )
        if root is not None
        else 0.0,
        "http_read": _union_ms(of("http:read")),
        "http_parse": _union_ms(of("http:parse")),
        "tokenize": grown["tokenize"],
        "batcher_queue": grown["batcher_queue"],
        "stage": grown["stage"],
        "device_dispatch": grown["device_dispatch"],
        "finalize": grown["finalize"],
        "host_tally": _union_ms(of("consensus:tally")),
        "upstream_judge": _union_ms(of("judge:stream")),
        "http_respond": _union_ms(of("http:respond")),
    }
    out = {k: round(v, 3) for k, v in out.items()}
    e2e = root.duration_ms() if root is not None else None
    if e2e is not None:
        attributed = sum(out.values())
        out["e2e_ms"] = e2e
        out["other_ms"] = round(max(0.0, e2e - attributed), 3)
    return out


# the span names ``phase_breakdown`` reads by name (an item's own
# ``batcher:<kind>`` span is whatever else starts with ``batcher:``)
_BREAKDOWN_SPANS = frozenset(
    (
        "http:read",
        "http:parse",
        "host:tokenize",
        "batcher:stage",
        "device:dispatch",
        "host:finalize",
        "consensus:tally",
        "judge:stream",
        "http:respond",
    )
)
