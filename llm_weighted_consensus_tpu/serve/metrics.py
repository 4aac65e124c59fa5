"""Out-of-band service metrics (SURVEY §5 metrics row: "add ordinary
service metrics (qps, p50, device util) out-of-band").

The reference keeps all observability in-band (per-choice
``completion_metadata`` + usage/cost accounting); that is preserved
bit-exact in the wire types.  This module adds the service-level view the
reference lacks: per-endpoint request counts and latency histograms plus
device dispatch timings, exposed at ``GET /metrics``.

Two expositions off one store (ISSUE 11):

* the original JSON snapshot — shape-compatible with the pre-histogram
  dashboards (``count``/``errors``/``p50_ms``/``p99_ms``/``trace_id``
  per series, provider sections keyed by ``KNOWN_SECTIONS``);
* ``GET /metrics?format=prometheus`` — OpenMetrics text with full
  ``_bucket``/``_sum``/``_count`` histogram families and trace-id
  exemplars on hot series, fed by the same mergeable log-bucket
  histograms (obs/histogram.py) that replaced the old 1024-sample
  reservoir, so percentiles no longer silently describe only the last
  1024 requests.
"""

from __future__ import annotations

import time
from typing import Dict, List, Optional, Tuple

from ..obs.histogram import Histogram, le_for

# Every provider-section name that may appear in the /metrics snapshot.
# The registry the LWC010 lint checks both ways: a `register_provider`
# call with a name not listed here fails lint (dashboards/tests grep
# these keys, so ad-hoc names silently vanish from alerting), and a
# listed name no call site registers is a stale entry to delete.
KNOWN_SECTIONS = (
    "resilience",
    "admission",
    "device_watchdog",
    "lifecycle",
    "device_batcher",
    "startup",
    "embed_cache",
    "score_cache",
    "traces",
    "device",
    "judge",
    "compile_cache",
    "jit",
    "mesh",
    "meshfault",
    "phases",
    "roofline",
    "quality",
    "ledger",
    "lock_witness",
    "fleet",
    "memguard",
    "weights",
)

# Every Prometheus family the text exposition may emit.  Same contract
# as KNOWN_SECTIONS, enforced by LWC012 both ways: a `prom_family(...)`
# call with an unlisted name fails lint, and a listed family no call
# site emits is stale.  Counter families are declared WITHOUT the
# `_total` sample suffix (OpenMetrics convention).
KNOWN_PROM_FAMILIES = (
    "lwc_uptime_seconds",
    "lwc_series_requests",
    "lwc_series_errors",
    "lwc_series_latency_ms",
    "lwc_phase_latency_ms",
    "lwc_device_latency_ms",
    "lwc_roofline_sol_ms",
    "lwc_roofline_attainment",
    "lwc_confidence_margin",
    "lwc_consensus_outcomes",
    "lwc_judge_agreement",
    "lwc_judge_drift",
    "lwc_fleet_peer_fetches",
    "lwc_fleet_leases",
    "lwc_fleet_disruptions",
    "lwc_memguard_rss_bytes",
    "lwc_memguard_level",
    "lwc_memguard_trips",
    "lwc_lane_dispatches",
    "lwc_lane_items",
    "lwc_lane_busy_fraction",
    "lwc_device_time_ms",
    "lwc_device_program_ms",
    "lwc_device_dispatches",
    "lwc_device_starved_by_ms",
    "lwc_device_stalls",
    "lwc_device_starved_interval_ms",
    "lwc_weights_swaps",
    "lwc_weights_shadow",
)


class _Series:
    __slots__ = ("count", "errors", "hist", "exemplar")

    def __init__(self) -> None:
        self.count = 0
        self.errors = 0
        self.hist = Histogram()
        # (trace_id, latency_ms, unix_ts) — enough to render an
        # OpenMetrics exemplar on the right bucket line
        self.exemplar: Optional[Tuple[str, float, float]] = None


class Metrics:
    def __init__(self) -> None:
        self._series_store: Dict[str, _Series] = {}
        self._providers: dict = {}
        # monotonic: wall-clock steps (NTP, leap smear) must not skew
        # reported uptime
        self._started = time.monotonic()

    def observe(
        self,
        series: str,
        ms: float,
        *,
        error: bool = False,
        trace_id=None,
    ) -> None:
        s = self._series_store.get(series)
        if s is None:
            s = self._series_store[series] = _Series()
        s.count += 1
        if error:
            s.errors += 1
        s.hist.observe(ms)
        if trace_id is not None:
            # trace-id exemplar: the most recent traced request on this
            # series — an aggregate that looks wrong links straight to
            # one concrete span tree.  Passed EXPLICITLY by call sites
            # that know the right trace (ambient reads here would pick
            # up stale contexts from long-lived tasks like the
            # batcher's flusher).
            s.exemplar = (trace_id, ms, time.time())

    def register_provider(self, name: str, fn) -> None:
        """Attach a live gauge section to the snapshot (e.g. the device
        batcher's queue depth / busy fraction — SURVEY §5 "device util")."""
        self._providers[name] = fn

    def snapshot(self) -> dict:
        out = {}
        for series, s in sorted(self._series_store.items()):
            entry = {"count": s.count, "errors": s.errors}
            if s.hist.count:
                entry["p50_ms"] = round(s.hist.quantile(0.5), 2)
                entry["p99_ms"] = round(s.hist.quantile(0.99), 2)
            if s.exemplar is not None:
                entry["trace_id"] = s.exemplar[0]
            out[series] = entry
        snap = {
            "uptime_sec": round(time.monotonic() - self._started, 1),
            "series": out,
        }
        for name, fn in self._providers.items():
            try:
                snap[name] = fn()
            except Exception as e:  # a broken gauge must not break /metrics
                snap[name] = {"error": str(e)}
        return snap

    # -- prometheus exposition ----------------------------------------------

    def provider_section(self, name: str):
        """One provider section by registry name (None when absent or
        broken) — the Prometheus renderer pulls ``roofline`` this way."""
        fn = self._providers.get(name)
        if fn is None:
            return None
        try:
            return fn()
        except Exception:
            return None

    def uptime_sec(self) -> float:
        return time.monotonic() - self._started

    def series_items(self) -> List[Tuple[str, "_Series"]]:
        return sorted(self._series_store.items())


PROM_CONTENT_TYPE = "application/openmetrics-text; version=1.0.0; charset=utf-8"


def prom_family(name: str, typ: str, help_text: str) -> List[str]:
    """The ``# HELP``/``# TYPE`` header for one family.  Call sites MUST
    pass the family name as a string literal drawn from
    KNOWN_PROM_FAMILIES — the LWC012 lint checks the two both ways so
    the text exposition can't drift from what dashboards scrape."""
    return [f"# HELP {name} {help_text}", f"# TYPE {name} {typ}"]


def _esc(label_value: str) -> str:
    return (
        label_value.replace("\\", "\\\\").replace('"', '\\"').replace("\n", "\\n")
    )


def _render_hist(
    name: str,
    label: str,
    value: str,
    hist: Histogram,
    exemplar: Optional[Tuple[str, float, float]] = None,
) -> List[str]:
    """One labelled histogram as ``_bucket``/``_sum``/``_count`` lines,
    with the exemplar (when given) attached to the bucket whose range
    contains the exemplar's own latency (OpenMetrics requires the
    exemplar value to lie inside its bucket)."""
    sel = f'{label}="{_esc(value)}"'
    lines = []
    ex_le = le_for(exemplar[1]) if exemplar is not None else None
    for le, cum in hist.cumulative():
        line = f'{name}_bucket{{{sel},le="{le}"}} {cum}'
        if ex_le is not None and le == ex_le:
            trace_id, ms, ts = exemplar
            line += f' # {{trace_id="{_esc(trace_id)}"}} {ms:.6g} {ts:.3f}'
            ex_le = None  # first matching line only
        lines.append(line)
    lines.append(f"{name}_sum{{{sel}}} {hist.sum:.6g}")
    lines.append(f"{name}_count{{{sel}}} {hist.count}")
    return lines


def render_prometheus(metrics: Metrics) -> str:
    """The whole process as OpenMetrics text: uptime, per-series request
    counters + latency histograms (with trace-id exemplars), the phase
    and per-bucket device-time histograms from the global phase
    aggregator, and the roofline attainment gauges when the roofline
    section is registered.  Ends with the mandatory ``# EOF``."""
    from ..obs import account as _account
    from ..obs import phases as _phases

    lines: List[str] = []
    lines += prom_family("lwc_uptime_seconds", "gauge", "Process uptime (monotonic).")
    lines.append(f"lwc_uptime_seconds {metrics.uptime_sec():.3f}")

    items = metrics.series_items()
    lines += prom_family(
        "lwc_series_requests", "counter", "Requests observed per series."
    )
    for series, s in items:
        lines.append(f'lwc_series_requests_total{{series="{_esc(series)}"}} {s.count}')
    lines += prom_family(
        "lwc_series_errors", "counter", "Errored requests per series."
    )
    for series, s in items:
        lines.append(f'lwc_series_errors_total{{series="{_esc(series)}"}} {s.errors}')
    lines += prom_family(
        "lwc_series_latency_ms",
        "histogram",
        "Per-series latency, fixed log buckets (obs/histogram.py).",
    )
    for series, s in items:
        lines += _render_hist(
            "lwc_series_latency_ms", "series", series, s.hist, s.exemplar
        )

    phase_hists, device_hists = _phases.aggregator().raw_histograms()
    lines += prom_family(
        "lwc_phase_latency_ms",
        "histogram",
        "Request time attributed per phase (admission_wait .. upstream_judge).",
    )
    for phase in _phases.PHASES:
        hist = phase_hists.get(phase)
        if hist is not None:
            lines += _render_hist("lwc_phase_latency_ms", "phase", phase, hist)
    lines += prom_family(
        "lwc_device_latency_ms",
        "histogram",
        "Enqueue-to-ready device time per (mesh-shape, bucket).",
    )
    for bucket, hist in sorted(device_hists.items()):
        lines += _render_hist("lwc_device_latency_ms", "bucket", bucket, hist)

    roofline = metrics.provider_section("roofline")
    if isinstance(roofline, dict):
        rows = roofline.get("buckets", {})
        lines += prom_family(
            "lwc_roofline_sol_ms",
            "gauge",
            "Speed-of-light time per AOT bucket from analysis/roofline.json.",
        )
        for bucket, row in sorted(rows.items()):
            sol = row.get("sol_ms")
            if sol is not None:
                lines.append(
                    f'lwc_roofline_sol_ms{{bucket="{_esc(bucket)}"}} {sol:.6g}'
                )
        lines += prom_family(
            "lwc_roofline_attainment",
            "gauge",
            "sol_ms / measured device p50 per AOT bucket (1.0 = roofline).",
        )
        for bucket, row in sorted(rows.items()):
            att = row.get("attainment")
            if att is not None:
                lines.append(
                    f'lwc_roofline_attainment{{bucket="{_esc(bucket)}"}} {att:.6g}'
                )

    from ..obs import quality as _quality

    qsnap = _quality.quality_aggregator().prom_snapshot()
    lines += prom_family(
        "lwc_confidence_margin",
        "histogram",
        "Consensus confidence margin (top1 - top2) per scored request.",
    )
    lines += _render_hist(
        "lwc_confidence_margin",
        "kind",
        "margin",
        qsnap["margin"],
        qsnap["exemplar"],
    )
    lines += prom_family(
        "lwc_consensus_outcomes",
        "counter",
        "Scored requests by consensus outcome (scored/degraded/...).",
    )
    for outcome, count in qsnap["outcomes"].items():
        lines.append(
            f'lwc_consensus_outcomes_total{{outcome="{_esc(outcome)}"}} {count}'
        )
    lines += prom_family(
        "lwc_judge_agreement",
        "gauge",
        "Per-judge agreement-with-final-consensus rate.",
    )
    for judge, rate in qsnap["agreement"].items():
        lines.append(
            f'lwc_judge_agreement{{judge="{_esc(judge)}"}} {rate:.6g}'
        )
    lines += prom_family(
        "lwc_judge_drift",
        "gauge",
        "1 when the drift detector currently flags the judge, else 0.",
    )
    for judge, flagged in qsnap["drift_flagged"].items():
        lines.append(
            f'lwc_judge_drift{{judge="{_esc(judge)}"}} {flagged:.0f}'
        )

    fleet = metrics.provider_section("fleet")
    if isinstance(fleet, dict):
        fetch = fleet.get("peer_fetch", {})
        lines += prom_family(
            "lwc_fleet_peer_fetches",
            "counter",
            "Peer cache fetches by result (hit/miss/error).",
        )
        for result in ("hits", "misses", "errors"):
            lines.append(
                f'lwc_fleet_peer_fetches_total{{result="{result}"}} '
                f"{fetch.get(result, 0)}"
            )
        leases = fleet.get("leases", {})
        lines += prom_family(
            "lwc_fleet_leases",
            "gauge",
            "Cross-replica single-flight leases active on this owner.",
        )
        lines.append(f"lwc_fleet_leases {leases.get('active', 0)}")
        health = fleet.get("health", {})
        lines += prom_family(
            "lwc_fleet_disruptions",
            "counter",
            "Fleet failure-plane events by kind (partition tolerance).",
        )
        for kind, value in (
            ("ring_divergence", fleet.get("ring_divergences", 0)),
            ("ring_reject", fleet.get("ring_rejects", 0)),
            ("early_takeover", fleet.get("early_takeovers", 0)),
            (
                "late_publish",
                leases.get("late_publishes", 0),
            ),
            ("quarantine", health.get("quarantines", 0)),
            ("readmission", health.get("readmissions", 0)),
        ):
            lines.append(
                f'lwc_fleet_disruptions_total{{kind="{kind}"}} {value}'
            )

    memguard = metrics.provider_section("memguard")
    if isinstance(memguard, dict):
        lines += prom_family(
            "lwc_memguard_rss_bytes",
            "gauge",
            "Process RSS as last sampled by the memory governor.",
        )
        if "rss_bytes" in memguard:
            lines.append(f"lwc_memguard_rss_bytes {memguard['rss_bytes']}")
        lines += prom_family(
            "lwc_memguard_level",
            "gauge",
            "Memory pressure level (0 ok, 1 soft, 2 hard).",
        )
        level_num = {"ok": 0, "soft": 1, "hard": 2}.get(
            memguard.get("level"), 0
        )
        lines.append(f"lwc_memguard_level {level_num}")
        lines += prom_family(
            "lwc_memguard_trips",
            "counter",
            "Watermark crossings by kind (soft/hard/recovery).",
        )
        for kind, key in (
            ("soft", "soft_trips"),
            ("hard", "hard_trips"),
            ("recovery", "recoveries"),
        ):
            lines.append(
                f'lwc_memguard_trips_total{{kind="{kind}"}} '
                f"{memguard.get(key, 0)}"
            )

    batcher = metrics.provider_section("device_batcher")
    if isinstance(batcher, dict) and isinstance(batcher.get("lanes"), dict):
        lanes = sorted(batcher["lanes"].items())
        lines += prom_family(
            "lwc_lane_dispatches",
            "counter",
            "Device dispatches per priority class (latency/offline).",
        )
        for lane, row in lanes:
            lines.append(
                f'lwc_lane_dispatches_total{{lane="{_esc(lane)}"}} '
                f"{row.get('dispatches', 0)}"
            )
        lines += prom_family(
            "lwc_lane_items",
            "counter",
            "Items dispatched per priority class.",
        )
        for lane, row in lanes:
            lines.append(
                f'lwc_lane_items_total{{lane="{_esc(lane)}"}} '
                f"{row.get('items', 0)}"
            )
        lines += prom_family(
            "lwc_lane_busy_fraction",
            "gauge",
            "Device busy fraction attributed per priority class.",
        )
        for lane, row in lanes:
            lines.append(
                f'lwc_lane_busy_fraction{{lane="{_esc(lane)}"}} '
                f"{row.get('busy_fraction', 0.0):.6g}"
            )

    account = batcher.get("account") if isinstance(batcher, dict) else None
    if isinstance(account, dict):
        # the device's time as the host sees it (obs/account.py): every
        # one a total since the process started
        lines += prom_family(
            "lwc_device_time_ms",
            "counter",
            "Wall time by what the device had: a program enqueued, none "
            "with a request in the server (starved), none and no request.",
        )
        for state in ("enqueued", "starved", "idle"):
            lines.append(
                f'lwc_device_time_ms_total{{state="{state}"}} '
                f"{account.get(state + '_ms', 0.0):.6g}"
            )
        lines += prom_family(
            "lwc_device_program_ms",
            "counter",
            "Enqueue to ready, split: a program's own time on the FIFO "
            "stream (service) and its wait behind the one ahead (waited).",
        )
        for part in ("service", "waited"):
            lines.append(
                f'lwc_device_program_ms_total{{part="{part}"}} '
                f"{account.get(part + '_ms', 0.0):.6g}"
            )
        lines += prom_family(
            "lwc_device_dispatches",
            "counter",
            "Programs seen ready by the device's account.",
        )
        lines.append(
            f"lwc_device_dispatches_total {account.get('dispatches', 0)}"
        )
        lines += prom_family(
            "lwc_device_starved_by_ms",
            "counter",
            "Starved time by the host phase that held the device.",
        )
        for key, ms in (account.get("starved_by") or {}).items():
            lines.append(
                f'lwc_device_starved_by_ms_total{{phase="{_esc(key)}"}} '
                f"{ms:.6g}"
            )
        lines += prom_family(
            "lwc_device_stalls",
            "counter",
            "Starved intervals of 50 ms and more.",
        )
        lines.append(f"lwc_device_stalls_total {account.get('stalls', 0)}")
        lines += prom_family(
            "lwc_device_starved_interval_ms",
            "histogram",
            "Lengths of the starved intervals, fixed log buckets.",
        )
        lines += _render_hist(
            "lwc_device_starved_interval_ms",
            "device",
            "0",
            _account.device_account().starved_histogram(),
        )

    weights = metrics.provider_section("weights")
    if isinstance(weights, dict):
        lines += prom_family(
            "lwc_weights_swaps",
            "counter",
            "Live weight-table installs (active + shadow).",
        )
        lines.append(f"lwc_weights_swaps_total {weights.get('swaps', 0)}")
        lines += prom_family(
            "lwc_weights_shadow",
            "counter",
            "Shadow-table comparisons by kind (compared/would_flip).",
        )
        for kind, key in (
            ("compared", "shadow_compared"),
            ("would_flip", "shadow_would_flip"),
        ):
            lines.append(
                f'lwc_weights_shadow_total{{kind="{kind}"}} '
                f"{weights.get(key, 0)}"
            )

    lines.append("# EOF")
    return "\n".join(lines) + "\n"


def register_resilience(metrics: Metrics, policy, fault_plan=None) -> None:
    """Surface the resilience subsystem as the ``resilience`` section of
    ``GET /metrics``: per-upstream breaker states (the breaker-state
    gauge), retry/hedge/degraded counters, the effective hedge delay, and
    — on chaos runs — the fault-injection tallies."""

    if policy is None and fault_plan is None:
        return

    def _snapshot() -> dict:
        snap = policy.snapshot() if policy is not None else {}
        if fault_plan is not None:
            snap["fault_plan"] = fault_plan.snapshot()
        return snap

    metrics.register_provider("resilience", _snapshot)


def register_overload(
    metrics: Metrics,
    admission=None,
    watchdog=None,
    lifecycle=None,
    memguard=None,
) -> None:
    """Surface the overload/lifecycle subsystem on ``GET /metrics``:
    the ``admission`` section (inflight gauge, adaptive limit, per-reason
    shed counters), ``device_watchdog`` (health, active dispatches,
    trip/recovery counters), ``lifecycle`` (state, drain outcome,
    cache flushes), and ``memguard`` (RSS, pressure level, watermark
    trip counters).  The batcher's own queue-depth gauge and shed
    counters ride its existing ``device_batcher`` provider."""
    if admission is not None:
        metrics.register_provider("admission", admission.snapshot)
    if watchdog is not None:
        metrics.register_provider("device_watchdog", watchdog.snapshot)
    if lifecycle is not None:
        metrics.register_provider("lifecycle", lifecycle.snapshot)
    if memguard is not None:
        metrics.register_provider("memguard", memguard.snapshot)


def register_performance(metrics: Metrics, roofline=None) -> None:
    """Surface the ISSUE 11 performance-observability sections: the
    ``phases`` aggregate (per-phase histograms + device-time share) and,
    when a gauge is supplied, the ``roofline`` per-bucket attainment
    table."""
    from ..obs import phases as _phases

    metrics.register_provider("phases", _phases.phases_snapshot)
    if roofline is not None:
        metrics.register_provider("roofline", roofline.snapshot)


def register_quality(metrics: Metrics, ledger=None, live_weights=None) -> None:
    """Surface the ISSUE 12 consensus-quality sections: the ``quality``
    aggregate (per-judge scorecards, pairwise kappa, drift flags,
    margin histogram, outcome rates), plus — when configured — the
    outcome ledger's ``ledger`` retention counters and the live
    weight-table's ``weights`` section (active/shadow versions, swap
    and shadow-comparison counters)."""
    from ..obs import quality as _quality

    metrics.register_provider("quality", _quality.quality_snapshot)
    if ledger is not None:
        metrics.register_provider("ledger", ledger.snapshot)
    if live_weights is not None:
        metrics.register_provider("weights", live_weights.snapshot)


def _series(request) -> str:
    """Series key = the MATCHED route, so unmatched-path probes can't mint
    unbounded series (they all bucket under ``http:unmatched``)."""
    resource = getattr(request.match_info.route, "resource", None)
    canonical = getattr(resource, "canonical", None)
    return f"http:{canonical}" if canonical else "http:unmatched"


def middleware(metrics: Metrics):
    """aiohttp middleware timing every request by matched route.  Runs
    inside the trace middleware (serve/gateway.py orders it so), hence
    the ambient trace — when one is active — becomes the series'
    exemplar."""
    from aiohttp import web

    from ..obs import current_trace_id

    @web.middleware
    async def _mw(request, handler):
        t0 = time.perf_counter()
        try:
            resp = await handler(request)
        except Exception:
            metrics.observe(
                _series(request),
                (time.perf_counter() - t0) * 1e3,
                error=True,
                trace_id=current_trace_id(),
            )
            raise
        metrics.observe(
            _series(request),
            (time.perf_counter() - t0) * 1e3,
            error=resp.status >= 400,
            trace_id=current_trace_id(),
        )
        return resp

    return _mw
