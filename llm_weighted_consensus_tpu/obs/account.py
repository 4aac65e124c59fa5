"""The device's time as the host sees it: one cumulative account (ISSUE 37).

"Is my chip waiting for my host, and on what" is the first thing an operator
asks, and the three gauges that used to answer it (the ``overlap`` key of
the phases section, the batcher's clamped ``busy_fraction``, the lanes'
interval rings) were neither windowed nor right.  This is the one record
in their place.  It is fed by calls that were there already:

* a timed dispatch's ENQUEUE (``models/dispatch_seam.py``: the ``t0`` of a
  ``PendingDispatch``, with the lane and the oldest item's timestamps the
  batcher's sink carries) and its READY (``drain_sink``'s ``t1``, or the
  inline bracket's);
* a request's arrival (``obs.arrive``) and the end of its ``http:respond``.

Every field of ``snapshot()`` is a monotone total since the process
started, so two ``/metrics`` readings window it:

``enqueued_ms``
    the union of [enqueue_k, ready_k]: the device has a program.
``starved_ms``
    no program enqueued AND a request between its arrival and the end of
    its ``http:respond``.  Booked when the interval ENDS, together with its
    split, so ``starved_by`` sums to it at every reading.
``idle_ms``
    no program and no request.
``wall_ms``
    the three together (what has been booked: a starved interval still
    open is not in it yet).
``dispatches``, ``service_ms``, ``waited_ms``
    the device is one FIFO stream, so program k starts at
    max(enqueue_k, ready_{k-1}): ``service`` is ready_k less that start (the
    program's own time), ``waited`` the start less enqueue_k (the time it
    sat behind the program ahead).  Their sum is enqueue to ready, which is
    what the ``device_dispatch`` phase holds.
``starved_by``
    each starved interval [S, E] put down to the host phase that held it.
    At the enqueue that ends it the group's oldest item brings its own
    timestamps, and [S, E] is cut at them: before it ARRIVED ``finalize``
    (the only requests in the server were answers on their way out), up to
    the body READ ``read``, up to SUBMITTED to the batcher ``parse``
    (parsing, validation, the way in), up to its ROWS READY ``tokenize``, up
    to ``_run_group``'s t0 ``loop`` (the flusher, ``window_ms``, the event
    loop), up to E ``stage``.  An interval that ends with no enqueue (the
    last answer of a burst going out) is ``finalize``.
``stalls``, ``starved``
    the starved intervals of ``STALL_MS`` and more, counted, and all of
    them as one ``Histogram`` of their lengths.

What it cannot see: the host learns of a program's end when a waiter thread
runs again, so a process that stood still as a whole reads its programs as
long (``service_ms``), not its device as starved.

A ready may be seen out of order (two waiter threads): a ready of program k
proves every program enqueued before k ready by then, so they are closed
with it and their own late report finds nothing to do.

Stdlib only; ``time.perf_counter``, the clock the ``lwc:clock`` marks lay
on the profiler's.  One lock, an enqueue and a ready a dispatch.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Callable, Optional

from .histogram import Histogram

STARVED_KEYS = ("read", "parse", "tokenize", "loop", "stage", "finalize")
# a starved interval this long is a stall: three or four times the host's
# whole share of a request (16 ms; PERF.md section 5)
STALL_MS = 50.0
# the views keep this many closed stretches a lane (``occupancy``)
STRETCHES = 1024


class _Ticket:
    """One enqueued program."""

    __slots__ = ("t0", "lane", "open")

    def __init__(self, t0: float, lane) -> None:
        self.t0 = t0
        self.lane = lane
        self.open = True


class _Cover:
    """The union of one lane's [enqueue, ready] intervals: how many are
    open, since when, and the last closed stretches for the views."""

    __slots__ = ("n", "since", "stretches")

    def __init__(self) -> None:
        self.n = 0
        self.since = 0.0
        self.stretches: deque = deque(maxlen=STRETCHES)

    def enter(self, t: float) -> None:
        if self.n == 0:
            self.since = t
        self.n += 1

    def leave(self, t: float) -> None:
        self.n -= 1
        if self.n == 0 and t > self.since:
            self.stretches.append((self.since, t))

    def covered(self, since: float, until: float) -> float:
        total = 0.0
        for start, end in self.stretches:
            if end > since and start < until:
                total += min(end, until) - max(start, since)
        if self.n and self.since < until:
            total += until - max(self.since, since)
        return total


class DeviceAccount:
    def __init__(self, clock: Callable[[], float] = time.perf_counter) -> None:
        self._clock = clock
        self._lock = threading.Lock()
        self._reset()

    # caller-holds-lock: DeviceAccount._lock (reset() calls this inside its with-lock block; __init__ before publication)
    def _reset(self) -> None:
        self._last = self._clock()
        self._requests = 0
        # open tickets, in enqueue order: as many as the pipeline is deep
        self._programs: list = []
        self._covers = {None: _Cover()}  # None: every lane together
        self._last_ready = self._last
        self._starved_since: Optional[float] = None
        self._enqueued_s = 0.0
        self._idle_s = 0.0
        self._starved_s = 0.0
        self._by = dict.fromkeys(STARVED_KEYS, 0.0)
        self._service_s = 0.0
        self._waited_s = 0.0
        self._dispatches = 0
        self._stalls = 0
        self._starved_max_ms = 0.0
        self._starved = Histogram()

    def reset(self) -> None:
        """Tests only: the totals are the process's."""
        with self._lock:
            self._reset()

    # -- the four feeds ---------------------------------------------------------

    def request_open(self, t: Optional[float] = None) -> None:
        with self._lock:
            t = self._advance(self._clock() if t is None else t)
            self._requests += 1
            if self._requests == 1 and not self._programs:
                self._starved_since = t

    def request_close(self, t: Optional[float] = None) -> None:
        with self._lock:
            t = self._advance(self._clock() if t is None else t)
            self._requests = max(0, self._requests - 1)
            if self._requests == 0 and self._starved_since is not None:
                self._book_starved(t, None)

    def enqueue(self, t0: float, lane=None, marks=None) -> _Ticket:
        """A program handed to the device at ``t0``.  ``marks`` are the
        oldest item's (arrived, read, submitted, rows ready, started), any
        of them None; they split the starved interval this enqueue ends."""
        ticket = _Ticket(t0, lane)
        with self._lock:
            t = self._advance(t0)
            if self._starved_since is not None:
                self._book_starved(t, marks or ())
            self._programs.append(ticket)
            self._covers[None].enter(t)
            if lane is not None:
                cover = self._covers.get(lane)
                if cover is None:
                    cover = self._covers[lane] = _Cover()
                cover.enter(t)
        return ticket

    def ready(self, ticket: _Ticket, t1: float, served: bool = True) -> None:
        """The program's outputs are there at ``t1``.  ``served`` False for
        one given up on (a device fault at the waiter): closed, not counted."""
        with self._lock:
            if not ticket.open:
                return
            t = self._advance(t1)
            if not served:
                self._programs.remove(ticket)
                self._close(ticket, t)
                return
            while self._programs:
                head = self._programs.pop(0)
                start = max(head.t0, self._last_ready)
                self._waited_s += start - head.t0
                self._service_s += max(0.0, t1 - start)
                self._last_ready = max(self._last_ready, t1)
                self._dispatches += 1
                self._close(head, t)
                if head is ticket:
                    break

    # -- inside the lock ----------------------------------------------------------

    # caller-holds-lock: DeviceAccount._lock (the feeds and snapshot() call this inside their with-lock blocks)
    def _advance(self, t: float) -> float:
        """Book the time up to ``t`` under the state that held it; an event
        stamped before the last one takes effect at the last one."""
        if t > self._last:
            if self._programs:
                self._enqueued_s += t - self._last
            elif self._starved_since is None:
                self._idle_s += t - self._last
            self._last = t
        return self._last

    # caller-holds-lock: DeviceAccount._lock (only ready() calls this, inside its with-lock block)
    def _close(self, ticket: _Ticket, t: float) -> None:
        ticket.open = False
        self._covers[None].leave(t)
        if ticket.lane is not None:
            self._covers[ticket.lane].leave(t)
        if not self._programs and self._requests:
            self._starved_since = t

    # caller-holds-lock: DeviceAccount._lock (request_close() and enqueue() call this inside their with-lock blocks)
    def _book_starved(self, end: float, marks) -> None:
        start, self._starved_since = self._starved_since, None
        if end <= start:
            return  # a request and its program stamped as one instant
        cur = start
        if marks is not None:
            for key, mark in zip(
                ("finalize", "read", "parse", "tokenize", "loop"), marks
            ):
                if mark is not None:
                    cut = min(max(mark, cur), end)
                    self._by[key] += cut - cur
                    cur = cut
        self._by["finalize" if marks is None else "stage"] += end - cur
        self._starved_s += end - start
        ms = (end - start) * 1e3
        self._starved.observe(ms)
        self._starved_max_ms = max(self._starved_max_ms, ms)
        if ms >= STALL_MS:
            self._stalls += 1

    # -- read side ----------------------------------------------------------------

    def snapshot(self) -> dict:
        """``/metrics`` ``device_batcher.account``."""
        with self._lock:
            self._advance(self._clock())
            enqueued, starved, idle = (
                self._enqueued_s, self._starved_s, self._idle_s
            )
            return {
                "wall_ms": round((enqueued + starved + idle) * 1e3, 3),
                "enqueued_ms": round(enqueued * 1e3, 3),
                "starved_ms": round(starved * 1e3, 3),
                "idle_ms": round(idle * 1e3, 3),
                "dispatches": self._dispatches,
                "service_ms": round(self._service_s * 1e3, 3),
                "waited_ms": round(self._waited_s * 1e3, 3),
                "stalls": self._stalls,
                "starved_by": {
                    key: round(self._by[key] * 1e3, 3) for key in STARVED_KEYS
                },
                "starved": {
                    **self._starved.to_json_obj(),
                    "max_ms": round(self._starved_max_ms, 3),
                },
            }

    def starved_histogram(self) -> Histogram:
        """A clone, for the Prometheus renderer."""
        with self._lock:
            return Histogram().merge(self._starved)

    def occupancy(
        self, lane, since: float, until: Optional[float] = None
    ) -> float:
        """The share of [since, until] in which ``lane`` (None: any) had a
        program enqueued: pipelined programs once, so never over 1."""
        until = self._clock() if until is None else until
        if until <= since:
            return 0.0
        with self._lock:
            cover = self._covers.get(lane)
            covered = cover.covered(since, until) if cover is not None else 0.0
        return round(covered / (until - since), 4)


_ACCOUNT = DeviceAccount()


def device_account() -> DeviceAccount:
    return _ACCOUNT
