"""Deferred-readiness dispatch seam (ISSUE 13 tentpole piece 1).

The old device-timing bracket (`jax.block_until_ready` inside
``TpuEmbedder._timed_dispatch``) held the dispatch thread for the full
device time of every timed call, so the executor thread that should
have been staging group k+1 sat parked on group k's readiness.  This
module is the replacement contract:

* the **dispatch thread** enters ``deferred_readiness(sink)``, calls
  the embedder, and returns as soon as every PJRT call is ENQUEUED —
  each timed dispatch appends a :class:`PendingDispatch` record
  (label, enqueue timestamp, output handle, waiter callable) to the
  sink instead of blocking;
* the batcher's **waiter thread** later runs :func:`drain_sink`, which
  blocks on each output, records the per-(mesh-shape, bucket) device
  time, tells the device's account (``obs/account.py``) the program is
  ready, and recycles any host staging buffers the dispatch checked out
  of the :class:`StagingPool`.

The account is fed here, at the two moments of a dispatch this module
already holds: its enqueue (``DispatchSink.add``, with the record's
``t0``, the sink's lane and its oldest item's timestamps; the inline
bracket of :func:`dispatch`) and its ready (``drain_sink``'s ``t1``, the
bracket's).

Device faults therefore surface at the waiter (readiness is where XLA
reports them), and the batcher's meshfault triage handles waiter-hop
exceptions exactly like dispatch-hop ones.

Deliberately jax-free at import time: test fakes implement a "device"
by passing their own ``wait`` callable.  :func:`wait_device_ready` is
the ONE sanctioned blocking readiness call on the dispatch path (lint
LWC013 allowlists it by symbol); everything else must defer.
"""

from __future__ import annotations

import threading
import time
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from ..obs.account import device_account as _account


def wait_device_ready(out) -> None:
    """Default readiness waiter: block until ``out``'s device buffers
    are materialized.  Runs on the batcher's waiter thread — never on
    the dispatch thread (LWC013 enforces that split statically)."""
    import jax

    jax.block_until_ready(out)


class PendingDispatch:
    """One enqueued-but-not-ready device dispatch."""

    __slots__ = ("label", "t0", "out", "wait", "timed", "ticket")

    def __init__(
        self,
        label: str,
        t0: float,
        out,
        wait: Callable = wait_device_ready,
        timed: bool = True,
    ) -> None:
        self.label = label
        self.t0 = t0
        self.out = out
        self.wait = wait
        # False when METRICS_DEVICE_TIMING=0: the waiter still blocks
        # (finalize would anyway) but records no device time
        self.timed = timed
        # the account's handle on this program (``DispatchSink.add``)
        self.ticket = None


class DispatchSink:
    """Per-group collector the dispatch thread fills under
    ``deferred_readiness``: the pending device dispatches plus the host
    staging buffers checked out for them (returned to the pool only
    after readiness — a ``device_put`` may still be reading the host
    buffer asynchronously before that).  ``lane`` and ``marks`` are the
    batcher's, for the device's account: the group's priority class and a
    call that gives its oldest item's timestamps, asked at the enqueue
    (``DeviceAccount.enqueue``)."""

    __slots__ = ("pending", "staged", "lane", "marks")

    def __init__(self, lane=None, marks=None) -> None:
        self.pending: List[PendingDispatch] = []
        self.staged: list = []
        self.lane = lane
        self.marks = marks

    def add(self, record: PendingDispatch) -> PendingDispatch:
        marks = self.marks() if self.marks is not None else None
        record.ticket = _account().enqueue(record.t0, self.lane, marks)
        self.pending.append(record)
        return record


_TLS = threading.local()


def active_sink() -> Optional[DispatchSink]:
    """The calling thread's deferred-readiness sink, or None when
    dispatches should block inline (direct callers)."""
    return getattr(_TLS, "sink", None)


class deferred_readiness:
    """Context manager scoping a :class:`DispatchSink` to the calling
    thread.  ``deferred_readiness(None)`` suspends an outer scope:
    dispatches inside it block inline."""

    __slots__ = ("sink", "_prev")

    def __init__(self, sink: Optional[DispatchSink]) -> None:
        self.sink = sink
        self._prev = None

    def __enter__(self) -> Optional[DispatchSink]:
        self._prev = getattr(_TLS, "sink", None)
        _TLS.sink = self.sink
        return self.sink

    def __exit__(self, *exc) -> bool:
        _TLS.sink = self._prev
        return False


def dispatch(label: str, fn: Callable, timed: bool = True):
    """Run one device dispatch under its label: the entry point of a model
    that is no ``TpuEmbedder`` (the judge).  Under a deferred-readiness sink
    the PJRT call is merely ENQUEUED and a :class:`PendingDispatch` hands
    (label, t0, output) to the waiter; without one (direct callers) the
    block-until-ready bracket runs inline and records the same numbers."""
    sink = active_sink()
    t0 = time.perf_counter()
    out = fn()
    if sink is not None:
        sink.add(PendingDispatch(label, t0, out, timed=timed))
        return out
    ticket = _account().enqueue(t0)
    try:
        wait_device_ready(out)
    except BaseException:
        _account().ready(ticket, time.perf_counter(), served=False)
        raise
    t1 = time.perf_counter()
    _account().ready(ticket, t1)
    if timed:
        from ..obs import phases as _phases

        _phases.observe_device(label, (t1 - t0) * 1e3)
    return out


def drain_sink(
    sink: DispatchSink,
    observe_device: Optional[Callable[[str, float], None]] = None,
    release: Optional[Callable] = None,
    clock: Callable[[], float] = time.perf_counter,
) -> None:
    """The waiter hop: block on every pending dispatch in enqueue
    order, telling the device's account each one's ready and recording
    each timed one's device ms (same label contract as the old bracket).
    Staging buffers recycle only on a clean drain — a raising ``wait``
    (device fault) propagates to the caller's triage, the buffers are
    dropped for the GC instead and the account gives up the programs not
    seen ready."""
    account = _account()
    try:
        for record in sink.pending:
            record.wait(record.out)
            t1 = clock()
            account.ready(record.ticket, t1)
            if record.timed and observe_device is not None:
                observe_device(record.label, (t1 - record.t0) * 1e3)
    except BaseException:
        t1 = clock()
        for record in sink.pending:
            account.ready(record.ticket, t1, served=False)
        raise
    if release is not None:
        for buf in sink.staged:
            release(buf)
        sink.staged = []


class StagingPool:
    """Per-(shape, dtype) reusable host staging buffers for the padded
    dispatch paths (ISSUE 13 tentpole piece 3): every padded dispatch
    used to allocate fresh ``np.pad`` copies of ids/mask per call; the
    pool hands back the previous group's buffer once its transfer is
    confirmed ready.  Device-side aliasing stays where it is legal —
    the ``_stream_vote_update`` donation of same-shape f32 state —
    because int32 ids/mask alias no f32 output (a measured no-op, see
    models/embedder.py); host-side reuse is the generalization that IS
    safe, provided recycling waits for readiness (the waiter's
    ``release``)."""

    def __init__(self, per_bucket: int = 2) -> None:
        self.per_bucket = max(0, int(per_bucket))
        self._lock = threading.Lock()
        self._free: Dict[Tuple[tuple, str], list] = {}
        self.hits = 0
        self.misses = 0

    @property
    def enabled(self) -> bool:
        return self.per_bucket > 0

    def acquire(self, shape, dtype) -> np.ndarray:
        """A writable buffer of exactly ``shape``/``dtype`` — recycled
        (contents stale: the caller overwrites every row) or fresh."""
        key = (tuple(shape), np.dtype(dtype).str)
        with self._lock:
            free = self._free.get(key)
            if free:
                self.hits += 1
                return free.pop()
            self.misses += 1
        return np.empty(shape, dtype)

    def release(self, buf: np.ndarray) -> None:
        key = (buf.shape, buf.dtype.str)
        with self._lock:
            free = self._free.setdefault(key, [])
            if len(free) < self.per_bucket:
                free.append(buf)

    def stats(self) -> dict:
        with self._lock:
            return {
                "per_bucket": self.per_bucket,
                "hits": self.hits,
                "misses": self.misses,
                "buckets": len(self._free),
            }
