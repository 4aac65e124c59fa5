"""End-to-end request tracing (ISSUE 5 / Dapper-style).

``span`` — contextvar-carried span tree; ``sink`` — bounded ring +
JSONL retention with head sampling and forced capture for degraded/
shed/error requests; ``propagate`` — W3C ``traceparent`` inject at
upstream calls / extract at the gateway door.

The whole package is dependency-free below ``utils`` so any layer
(cache, clients, batcher, resilience) can instrument without cycles.
With no sink configured nothing ever activates a root span, and every
ambient helper here is a single contextvar read returning None.

``hostspan`` — the one helper behind every boundary of the serving host
path: a ``host_span`` feeds the phase histograms, the profiler's trace and
the span tree from one call (ISSUE 24).

``account`` — the device's time as the host sees it, one cumulative record
of enqueued, served, starved and idle time (ISSUE 37).
"""

from .account import DeviceAccount, device_account  # noqa: F401
from .histogram import Histogram  # noqa: F401
from .hostspan import (  # noqa: F401
    HOST_SPANS,
    arrive,
    current_request,
    depart,
    host_span,
    next_id,
    request_id,
    set_profiler_annotation,
)
from .ledger import (  # noqa: F401
    LEDGER_SCHEMA,
    OutcomeLedger,
    load_ledger_records,
)
from .phases import (  # noqa: F401
    PHASES,
    observe_device,
    observe_phase,
    phase_breakdown,
    phases_snapshot,
    reset_phases,
)
from .propagate import (  # noqa: F401
    TRACEPARENT_HEADER,
    extract,
    format_traceparent,
    inject,
    parse_traceparent,
)
from .quality import (  # noqa: F401
    JudgeBallot,
    Outcome,
    QualityAggregator,
    configure_quality,
    observe_outcome,
    quality_aggregator,
    quality_snapshot,
    reset_quality,
)
from .sink import TraceSink  # noqa: F401
from .span import (  # noqa: F401
    Span,
    Trace,
    annotate,
    child_span,
    current_span,
    current_trace_id,
    force_keep,
    span,
    start_trace,
)
