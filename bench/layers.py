"""Per-layer metrics of a traced run, each read by its own small file.

``bench/layer_metrics/<name>.json`` says where a metric comes from:

  {"read": {"from": "metrics", "reduce": "delta_ratio",
            "num": "phases.batcher_queue.sum_ms",
            "den": "phases.batcher_queue.count", "scale": 1.0}}

reads two /metrics documents, one taken at the window's start and one near its
end (inside the trace, before the profiler stops and writes):
``value`` is one path of the later document (a running median the program
keeps), ``delta`` is after - before of one dotted path, ``delta_ratio`` the
ratio of two deltas (``one_minus`` turns a share into its complement, ``scale``
multiplies).  Such a metric is data only.

  {"read": {"from": "trace", "reducer": "forward_mfu"}}

calls ``bench/reducers/forward_mfu.py``'s ``reduce(ctx)``.

  {"read": {"from": "window", "name": "latency_p95_ms"}}

takes a number the harness works out from the window's requests themselves
(``run.end_to_end``): one that is measured like an end-to-end metric but
cannot be held to a bound (PERF.md section 2).  A reader that finds
nothing to read returns None and the metric is left out of the line.
"""

from __future__ import annotations

import json
import os

import byname
import xplane as xtrace
from server import BenchError

HERE = os.path.dirname(os.path.abspath(__file__))


def reports(metric: dict, cell: str) -> bool:
    """Whether a BENCHMARK.json metric entry is this cell's to report."""
    return "workloads" not in metric or cell in metric["workloads"]


def dig(doc: dict, path: str):
    cur = doc
    for part in path.split("."):
        if not isinstance(cur, dict) or part not in cur:
            return None
        cur = cur[part]
    return cur


def dig_series(doc: dict, path: str):
    """Like ``dig``, for a path whose middle key holds dots or colons: the
    first part, the last part, and whatever lies between as one key."""
    first, _, rest = path.partition(".")
    middle, _, last = rest.rpartition(".")
    value = (doc.get(first) or {}).get(middle, {}).get(last)
    return None if value is None else float(value)


def read_metrics(spec: dict, before: dict, after: dict):
    def delta(path):
        a, b = dig(after, path), dig(before, path)
        if a is None:
            return None
        return float(a) - float(b or 0.0)

    kind = spec["reduce"]
    if kind == "value":
        value = dig_series(after, spec["path"])
    elif kind == "delta":
        value = delta(spec["path"])
    elif kind == "delta_ratio":
        num, den = delta(spec["num"]), delta(spec["den"])
        if num is None or not den:
            return None
        value = num / den
    else:
        raise BenchError(f"unknown reduction {kind!r}")
    if value is None:
        return None
    if spec.get("one_minus"):
        value = 1.0 - value
    return value * float(spec.get("scale", 1.0))


def reduce_all(bench, cell, config, cfg, before, profile, work, window) -> dict:
    """``before`` is /metrics at the window's start; the later reading is the
    profile's own (taken inside the trace, near the window's end)."""
    if profile.get("status") != 200 or "after" not in profile:
        raise BenchError(
            "/v1/profile failed: "
            + str({k: profile.get(k) for k in ("status", "error", "wall_s")})
        )
    after = profile["after"]
    path = xtrace.newest_xplane(os.path.join(work, "prof"))
    if path is None:
        raise BenchError("the profile wrote no .xplane.pb")
    trace = xtrace.read(path)
    with open(os.path.join(work, "trace_described.txt"), "w", encoding="utf-8") as f:
        f.write(xtrace.describe(trace))
    device = xtrace.busy(trace)
    if not device or device["busy_s"] <= 0:
        raise BenchError("no operation ran on the device in the traced window")
    with open(os.path.join(HERE, "peaks.json"), encoding="utf-8") as f:
        peaks = json.load(f)
    kind = (after.get("device") or {}).get("device_kind")
    if kind not in peaks:
        raise BenchError(f"device kind {kind!r} is not in bench/peaks.json")
    ctx = {
        "trace": trace,
        "device": device,
        "profile": profile,
        "config": config,
        "cfg": cfg,
        "peaks": peaks[kind],
    }
    metrics = {}
    for metric in bench["per_layer"]:
        if not reports(metric, cell["name"]):
            continue
        with open(
            os.path.join(HERE, "layer_metrics", metric["name"] + ".json"),
            encoding="utf-8",
        ) as f:
            spec = json.load(f)["read"]
        if spec["from"] == "metrics":
            value = read_metrics(spec, before, after)
        elif spec["from"] == "window":
            value = window.get(spec["name"])
        elif spec["from"] == "trace":
            value = byname.module("reducers", spec["reducer"]).reduce(ctx)
        else:
            raise BenchError(f"unknown source {spec['from']!r}")
        if value is not None:
            metrics[metric["name"]] = {"value": value, "unit": metric["unit"]}
    return {
        "metrics": metrics,
        "device": {"busy_s": device["busy_s"], "window_s": device["window_s"]},
        "breakdown": {
            "device_ops": device["device_ops"],
            "idle_gaps": device["idle_gaps"],
        },
    }
