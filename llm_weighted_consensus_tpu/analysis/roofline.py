"""Per-bucket speed-of-light roofline for the serving path (JXA013).

ROADMAP item 1 asks that "fast as the hardware allows" become a checked
invariant.  ``analysis/roofline.json`` commits, next to the JXA009/010
resource budgets, everything needed to compute each AOT bucket's
speed-of-light (SoL) time on a given backend:

* ``peaks`` — PER-CHIP peak compute (``flops_per_sec``) and HBM
  bandwidth (``hbm_bytes_per_sec``) keyed by ``device_kind``, the string
  ``jax.devices()[0].device_kind`` reports.  ``TPU v5 lite`` is the v5e
  (bf16 peak 197 TFLOP/s, 819 GB/s HBM per chip, Google Cloud's "TPU
  v5e" page); a kind with no row gets no ``sol_ms``/``attainment``
  (``known_peaks: false``), never another chip's numbers.  The ``cpu``
  row is a deliberately rough laptop-class figure so the gauge stays
  testable on the CPU-simulated stack.
* ``buckets`` — per-bucket ``flops`` / ``bytes_accessed`` from XLA's
  ``cost_analysis``, the same figures the mesh audit measures for the
  budgets file.  Rows are committed mesh-shape-free: the runtime gauge
  scales peaks by the chip count parsed from the serving label's
  ``@dp{dp}xtp{tp}[xsp{sp}]`` suffix, so ONE committed row covers every
  mesh-ladder rung (dp-halving keeps per-bucket totals, splits chips)
  and every sequence-parallel ring bucket.

``sol_ms = max(flops / (peak_flops * chips),
               bytes_accessed / (peak_bw * chips)) * 1e3``

The live gauge (``RooflineGauge``, the ``roofline`` /metrics section)
divides SoL by the measured block-until-ready device p50 per
(mesh-shape, bucket) from the phase aggregator:
``attainment = sol_ms / device_p50_ms`` — 1.0 means the dispatch runs
at the hardware roofline; 0.1 means 10× headroom.

**JXA013** gates the file exactly like budgets.py gates JXA009/010:
missing file, scope mismatch, audited bucket without a row, stale row
without a bucket, or committed figures drifted beyond the tolerance
band vs fresh measurement — all fail the analyzer.  Re-baseline:
``python -m llm_weighted_consensus_tpu.analysis.mesh_audit
--write-roofline`` (peaks and tolerance survive; figures do not).

Stdlib-only (json/pathlib); the jax-touching measurement lives in
``mesh_audit.py`` and the device timings in ``obs/phases.py``.
"""

from __future__ import annotations

import json
import re
from pathlib import Path
from typing import Dict, List, Optional, Tuple

from .engine import Finding

# figures a roofline row must carry; both come from XLA cost_analysis
ROOFLINE_METRICS = ("flops", "bytes_accessed")

DEFAULT_TOLERANCE = 0.25  # same band rationale as budgets.py

# Committed starting peaks, used when --write-roofline creates the file
# from scratch.  Per chip, keyed by device_kind.  "TPU v5 lite" = v5e:
# 394 TOP/s int8 / 197 TFLOP/s bf16; we commit the bf16 figure because
# the serving matmuls are bf16/f32 with only the int8-pallas path below
# it.  cpu = rough one-core-ish figure so CPU-simulated runs report a
# stable, obviously-not-TPU attainment.
DEFAULT_PEAKS = {
    "TPU v5 lite": {"flops_per_sec": 1.97e14, "hbm_bytes_per_sec": 8.19e11},
    "cpu": {"flops_per_sec": 5.0e10, "hbm_bytes_per_sec": 2.0e10},
}

_MESH_SUFFIX = re.compile(
    r"^(?P<base>.+)@dp(?P<dp>\d+)xtp(?P<tp>\d+)(?:xsp(?P<sp>\d+))?$"
)


def default_roofline_path() -> Path:
    return Path(__file__).resolve().parent / "roofline.json"


def load_roofline(path: Optional[Path] = None) -> dict:
    path = path or default_roofline_path()
    if not path.exists():
        return {}
    return json.loads(path.read_text(encoding="utf-8"))


def split_label(label: str) -> Tuple[str, int]:
    """Runtime device-timing label -> (committed row label, chip count).

    ``vote1(n=8,s=16)@dp4xtp2`` -> (``vote1(n=8,s=16)``, 8); the
    sequence-parallel suffix multiplies too (``ring(b=16,s=64)
    @dp2xtp2xsp2`` -> 8 chips); an unsuffixed single-device label
    counts as one chip."""
    m = _MESH_SUFFIX.match(label)
    if m is None:
        return label, 1
    chips = int(m.group("dp")) * int(m.group("tp"))
    if m.group("sp"):
        chips *= int(m.group("sp"))
    return m.group("base"), chips


def sol_ms(figures: dict, peaks: dict, chips: int = 1) -> Optional[float]:
    """Speed-of-light time in ms for one bucket on ``chips`` chips of a
    backend described by ``peaks``; None when either side is unusable."""
    try:
        flops = float(figures["flops"])
        bytes_accessed = float(figures["bytes_accessed"])
        peak_flops = float(peaks["flops_per_sec"]) * max(1, chips)
        peak_bw = float(peaks["hbm_bytes_per_sec"]) * max(1, chips)
    except (KeyError, TypeError, ValueError):
        return None
    if peak_flops <= 0 or peak_bw <= 0:
        return None
    return max(flops / peak_flops, bytes_accessed / peak_bw) * 1e3


def tolerance_of(roofline: dict, metric: str) -> float:
    return float(roofline.get("tolerance", {}).get(metric, DEFAULT_TOLERANCE))


def compare_roofline(
    measured: Dict[str, Dict[str, float]],
    roofline: dict,
    scope: Optional[dict] = None,
) -> List[Finding]:
    """JXA013: every audited AOT bucket must have a live, in-band
    roofline row, both ways — the gauge is only as honest as this file."""
    findings: List[Finding] = []
    if not roofline:
        findings.append(
            Finding(
                rule="JXA013",
                path="analysis/roofline.json",
                line=0,
                message=(
                    "no committed roofline: run `python -m "
                    "llm_weighted_consensus_tpu.analysis.mesh_audit "
                    "--write-roofline` and commit the result so every AOT "
                    "bucket reports a speed-of-light attainment gauge"
                ),
            )
        )
        return findings
    if scope is not None and roofline.get("scope", {}) != scope:
        findings.append(
            Finding(
                rule="JXA013",
                path="analysis/roofline.json",
                line=0,
                message=(
                    f"committed roofline scope {roofline.get('scope', {})} "
                    f"does not match the audited configuration {scope}; "
                    "re-baseline with --write-roofline"
                ),
            )
        )
        return findings
    peaks = roofline.get("peaks", {})
    for kind in sorted(set(peaks) | set(DEFAULT_PEAKS)):
        row = peaks.get(kind, {})
        if not all(float(row.get(k, 0)) > 0 for k in (
            "flops_per_sec", "hbm_bytes_per_sec"
        )):
            findings.append(
                Finding(
                    rule="JXA013",
                    path="analysis/roofline.json",
                    line=0,
                    symbol=kind,
                    message=(
                        f"peaks entry for device kind `{kind}` is missing "
                        "or non-positive; the attainment gauge needs "
                        "per-chip flops_per_sec and hbm_bytes_per_sec"
                    ),
                )
            )
    committed = roofline.get("buckets", {})
    for label, figures in sorted(measured.items()):
        entry = committed.get(label)
        if entry is None:
            findings.append(
                Finding(
                    rule="JXA013",
                    path="analysis/roofline.json",
                    line=0,
                    symbol=label,
                    message=(
                        f"audited bucket `{label}` has no roofline row; it "
                        "would serve without an attainment gauge — "
                        "re-baseline with --write-roofline"
                    ),
                )
            )
            continue
        for metric in ROOFLINE_METRICS:
            if metric not in figures or metric not in entry:
                continue
            got, want = float(figures[metric]), float(entry[metric])
            if want <= 0:
                continue
            band = tolerance_of(roofline, metric)
            ratio = got / want
            if ratio > 1.0 + band or ratio < 1.0 - band:
                findings.append(
                    Finding(
                        rule="JXA013",
                        path="analysis/roofline.json",
                        line=0,
                        symbol=label,
                        message=(
                            f"roofline row `{label}` {metric} is stale: "
                            f"measured {got:.0f} vs committed {want:.0f} "
                            f"({ratio:.2f}x, band ±{band:.0%}) — the gauge "
                            "would report attainment against the wrong "
                            "speed of light; re-baseline with "
                            "--write-roofline"
                        ),
                    )
                )
    for label in sorted(committed):
        if label not in measured:
            findings.append(
                Finding(
                    rule="JXA013",
                    path="analysis/roofline.json",
                    line=0,
                    symbol=label,
                    message=(
                        f"stale roofline row `{label}`: the audit no longer "
                        "lowers this bucket — delete the row"
                    ),
                )
            )
    return findings


def write_roofline(
    path: Path,
    measured: Dict[str, Dict[str, float]],
    scope: dict,
    previous: dict,
) -> None:
    """Fresh cost figures under the committed policy knobs (peaks and
    tolerance survive a re-baseline; figures do not)."""
    payload = {
        "_doc": (
            "Committed per-bucket speed-of-light roofline (JXA013). "
            "peaks are PER-CHIP; the runtime gauge scales by the "
            "dp*tp parsed from the serving label. Re-baseline: python -m "
            "llm_weighted_consensus_tpu.analysis.mesh_audit "
            "--write-roofline, then review the diff. Math: DESIGN.md "
            "'Performance observability'."
        ),
        "scope": scope,
        "tolerance": previous.get(
            "tolerance", {m: DEFAULT_TOLERANCE for m in ROOFLINE_METRICS}
        ),
        "peaks": previous.get("peaks", DEFAULT_PEAKS),
        "buckets": {
            label: {
                m: round(float(figures[m]), 1)
                for m in ROOFLINE_METRICS
                if m in figures
            }
            for label, figures in sorted(measured.items())
        },
    }
    path.write_text(json.dumps(payload, indent=2) + "\n", encoding="utf-8")


class RooflineGauge:
    """The live ``roofline`` /metrics section: per observed
    (mesh-shape, bucket) device-time key, SoL time for the serving
    device kind and ``attainment = sol_ms / device_p50_ms`` — both only
    when the peaks table has a row for that kind."""

    def __init__(self, roofline: dict, device_kind: str) -> None:
        self._peaks = roofline.get("peaks", {}).get(device_kind)
        self._buckets = roofline.get("buckets", {})
        self._device_kind = device_kind

    def snapshot(self) -> dict:
        from ..obs import phases as _phases

        rows: Dict[str, dict] = {}
        for label, stats in _phases.aggregator().device_snapshot().items():
            base, chips = split_label(label)
            row = {"count": stats["count"]}
            p50 = stats.get("p50_ms")
            if p50 is not None:
                row["device_p50_ms"] = p50
            figures = self._buckets.get(base)
            if figures is not None and self._peaks is not None:
                sol = sol_ms(figures, self._peaks, chips)
                if sol is not None:
                    row["sol_ms"] = round(sol, 4)
                    if p50:
                        row["attainment"] = round(sol / p50, 4)
            rows[label] = row
        return {
            "device_kind": self._device_kind,
            "known_peaks": self._peaks is not None,
            "buckets": rows,
        }
