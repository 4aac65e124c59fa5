"""Host spans: one call at each boundary of the serving host path, three
readers (ISSUE 24).

The repo had three separate instruments: the request span tree (span.py,
``GET /v1/traces``), the always-on phase histograms (phases.py, the
``phases`` section of ``/metrics``) and the JAX profiler behind ``POST
/v1/profile``.  They shared no clock and no vocabulary.  ``host_span`` joins
them; an instrumentation site says

    with obs.host_span("batcher:stage", parents=spans, group=gid) as hs:
        ...
        hs.annotate(label=label)

and the one call

1. always observes the duration into the phase aggregator under the phase
   ``HOST_SPANS`` gives the name (none for a name mapped to None), so
   ``/metrics`` has it with the profiler off;
2. while a profile is being taken, opens the profiler's own annotation of the
   same name and attributes on the calling thread, so the span lies in the
   profiler's trace, on the profiler's clock, beside the device's operations;
3. hangs a child on the request's span tree: on ``parents`` where the site
   holds its requests' spans (a thread with no ambient context, a group of
   several requests), else on the ambient span, where there is one.

A request's own two ends feed the device's account (account.py) from here
too: ``arrive`` opens it, the end of ``http:read`` stamps its body read, the
end of ``http:respond`` closes it (``depart`` for a handler that never
answered), and the batcher keeps ``current_request()`` with the item.

With no profile and no root span it is the phase observe alone.  The
annotation class is handed in by whoever starts the profiler
(``set_profiler_annotation``): ``obs/`` imports no jax.

Every span carries ``rid`` (the request's trace id where it has a root span,
else a number from one process-wide sequence, fixed by ``arrive`` at the
handler's first line and read back by ``request_id`` wherever the request's
context is alive) or ``group`` (one number per dispatch group, with its
items' ``rids`` joined by a space: a comma would end the value in the
profiler's encoding).
"""

from __future__ import annotations

import contextvars
import itertools
import time
from typing import Iterable, Optional

from .account import device_account
from .phases import observe_phase
from .span import current_span

# span name -> the phase its duration is observed under.  The vocabulary of
# the host path, in request order; tests hold every phase here to PHASES and
# every name to KNOWN_SPANS, and lint LWC010 holds the call sites to both.
HOST_SPANS = {
    "http:arrive": None,  # an instant: the request's first line
    "http:read": "http_read",  # the body off the socket
    "http:parse": "http_parse",
    "host:tokenize": "tokenize",
    "batcher:idle": None,  # a wait, not work
    "batcher:slots_full": None,  # a wait, not work
    "batcher:stage": "stage",
    # the waiter's block on the enqueued outputs; ``drain_sink`` observes
    # ``device_dispatch`` per program inside it, unchanged
    "device:wait": None,
    "host:finalize": "finalize",
    "http:respond": "http_respond",
    "lwc:clock": None,  # the capture's two clock marks
}

_annotation = None  # the profiler's annotation class while a profile runs
_RID: contextvars.ContextVar = contextvars.ContextVar("lwc_rid", default=None)
_REQUEST: contextvars.ContextVar = contextvars.ContextVar(
    "lwc_request", default=None
)
_SEQ = itertools.count(1)


class _Request:
    """A request's own timestamps for the device's account (account.py):
    when it arrived, when its body was read, and whether the account still
    counts it in the server."""

    __slots__ = ("arrived", "read", "open")

    def __init__(self, arrived: float) -> None:
        self.arrived = arrived
        self.read = None
        self.open = True

    def close(self) -> None:
        if self.open:
            self.open = False
            device_account().request_close()


def set_profiler_annotation(factory) -> None:
    """``factory(name, **attrs)`` is a context manager that writes one host
    event into the running profile (and has ``set_metadata``); None when
    the profile stops.  Spans open at the switch are not re-opened: a span
    that began before the profile is missing from it."""
    global _annotation
    _annotation = factory


def next_id() -> int:
    """The process-wide sequence behind ``rid`` and ``group``."""
    return next(_SEQ)


def request_id():
    """The ambient request's ``rid``; minted (and kept for the rest of this
    context) where the caller never passed a handler: a harness driving the
    batcher directly gets one number per submitting task."""
    rid = _RID.get()
    if rid is None:
        span = current_span()
        rid = span.trace.trace_id if span is not None else next(_SEQ)
        _RID.set(rid)
    return rid


def arrive(route: str, nbytes: int):
    """First line of a request handler: fixes the request's ``rid`` for
    everything the handler's task does after it, and marks ``http:arrive``.
    Always a fresh id: a kept-alive connection's next request may run in the
    same context as the last."""
    _RID.set(None)
    rid = request_id()
    with host_span("http:arrive", rid=rid, route=route, bytes=nbytes):
        pass
    depart()  # a request before this one on the connection that never answered
    request = _Request(time.perf_counter())
    _REQUEST.set(request)
    device_account().request_open(request.arrived)
    return rid


def current_request():
    """The ambient request's timestamps (None for a caller that never passed
    a handler): the batcher keeps them with the item it submits."""
    return _REQUEST.get()


def depart() -> None:
    """The handler is over: a request that left without an ``http:respond``
    (its client gone, a 413 raised above the handler) leaves the account
    here.  Nothing to do where ``http:respond`` closed it."""
    request = _REQUEST.get()
    if request is not None:
        request.close()


class host_span:
    """See the module docstring.  A plain (not async) context manager: on the
    event loop it may be held across an ``await`` (``batcher:idle``,
    ``batcher:slots_full``), and then overlaps whatever else the loop runs
    meanwhile on that thread's line of the trace."""

    __slots__ = ("name", "attrs", "_parents", "_t0", "_ann", "_spans")

    def __init__(
        self, name: str, parents: Optional[Iterable] = None, **attrs
    ) -> None:
        self.name = name
        self.attrs = attrs
        self._parents = parents
        self._ann = None
        self._spans: list = []

    def __enter__(self) -> "host_span":
        return self.open_span()

    def open_span(self) -> "host_span":
        """``with`` by hand, for a span one function opens and another
        closes (``batcher:idle`` between two flushers)."""
        factory = _annotation
        if factory is not None:
            self._ann = factory(self.name, **self.attrs)
            self._ann.__enter__()
        parents = self._parents
        if parents is None:
            parents = (current_span(),)
        self._spans = [
            p.child(self.name, **self.attrs) for p in parents if p is not None
        ]
        self._t0 = time.perf_counter()
        return self

    def annotate(self, **attrs) -> None:
        """Attributes known only inside the span (a dispatch's label, the
        rows tokenized): on all three readers, like those given at entry."""
        self.attrs.update(attrs)
        if self._ann is not None:
            self._ann.set_metadata(**attrs)
        for span in self._spans:
            span.annotate(**attrs)

    def __exit__(self, exc_type, exc, tb) -> bool:
        self.close_span(error=exc is not None)
        return False

    def close_span(self, error: bool = False) -> None:
        ms = (time.perf_counter() - self._t0) * 1e3
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        for span in self._spans:
            span.finish("error" if error else None)
        phase = HOST_SPANS[self.name]
        if phase is not None:
            observe_phase(phase, ms)
        if self.name in _REQUEST_ENDS:
            request = _REQUEST.get()
            if request is not None:
                _REQUEST_ENDS[self.name](request)


def _body_read(request: _Request) -> None:
    request.read = time.perf_counter()


# the two spans whose END is one of a request's timestamps
_REQUEST_ENDS = {"http:read": _body_read, "http:respond": _Request.close}
