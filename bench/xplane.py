"""Reduction of a profiler trace (``.xplane.pb``) to what the contract asks:
device-busy seconds, the traced window, the operations that took most device
time and the longest idle gaps.

Read with ``jax.profiler.ProfileData`` alone.  A device plane is one whose
name starts with ``/device:TPU``; on it the line ``XLA Ops`` holds one event
per executed HLO operation, ``XLA Modules`` one per executed program.  Busy
time is the UNION of the op intervals (ops on one core do not overlap, but
the union is what "an operation ran" means and stays right if they did).
"""

from __future__ import annotations

import os


def newest_xplane(directory: str):
    found = [
        os.path.join(root, name)
        for root, _, names in os.walk(directory)
        for name in names
        if name.endswith(".xplane.pb")
    ]
    return max(found, key=os.path.getmtime) if found else None


def union_seconds(intervals: list) -> float:
    """Total length of the union of (start_ns, end_ns) intervals, seconds."""
    total, cur_start, cur_end = 0.0, None, None
    for start, end in sorted(intervals):
        if cur_end is None or start > cur_end:
            if cur_end is not None:
                total += cur_end - cur_start
            cur_start, cur_end = start, end
        else:
            cur_end = max(cur_end, end)
    if cur_end is not None:
        total += cur_end - cur_start
    return total / 1e9


def gaps(intervals: list, least_ns: float = 0.0) -> list:
    """(start_ns, end_ns) of the idle stretches between merged intervals."""
    out, cur_end = [], None
    for start, end in sorted(intervals):
        if cur_end is not None and start - cur_end > least_ns:
            out.append((cur_end, start))
        cur_end = end if cur_end is None else max(cur_end, end)
    return out


def read(path: str) -> dict:
    """{"devices": [{"name", "ops": [(name, start_ns, dur_ns, stats)],
    "modules": [...]}], "host": [(line, name, start_ns, dur_ns)]}"""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU"):
            dev = {"name": plane.name, "ops": [], "modules": [], "lines": []}
            for line in plane.lines:
                events = [
                    (e.name, e.start_ns, e.duration_ns, dict(e.stats))
                    for e in line.events
                ]
                dev["lines"].append((line.name, len(events)))
                if line.name == "XLA Ops":
                    dev["ops"] = events
                elif line.name == "XLA Modules":
                    dev["modules"] = events
            devices.append(dev)
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for e in line.events:
                    if e.duration_ns > 0:
                        host.append((line.name, e.name, e.start_ns, e.duration_ns))
    return {"devices": devices, "host": host}


# control flow whose event spans the events of its own body (the layer scan
# is one ``while``; a ``lax.cond`` that XLA leaves alone keeps the name
# ``cond``): left out of the per-operation sums, or they count twice
CONTAINERS = ("while", "conditional", "call", "cond")


def _op_key(name: str) -> str:
    """``%fusion.123 = ...`` and ``fusion.123`` -> ``fusion``: ops of one kind
    add up under one name."""
    name = name.lstrip("%").split(" ")[0]
    head = name.split(".")[0]
    return head or name


def module_events(trace: dict, prefixes: list) -> list:
    """[(start_ns, dur_ns)], by start, of the executions on the first device
    plane of programs whose name starts with one of ``prefixes``
    (``jit__embed_and_vote(<fingerprint>)``)."""
    dev = next((d for d in trace["devices"] if d["modules"]), None)
    if dev is None:
        return []
    return sorted(
        (start, dur)
        for name, start, dur, *_ in dev["modules"]
        if any(name.startswith(p + "(") or name == p for p in prefixes)
    )


def busy(trace: dict) -> dict:
    """busy_s averaged over the device planes, window_s (first op start to
    last op end, the same on every plane to within a dispatch), top ops and
    longest gaps labelled by what the host was running then."""
    if not trace["devices"] or not any(d["ops"] for d in trace["devices"]):
        return {}
    busy_each, starts, ends = [], [], []
    for dev in trace["devices"]:
        spans = [(s, s + d) for _, s, d, _ in dev["ops"]]
        if not spans:
            continue
        busy_each.append(union_seconds(spans))
        starts.append(min(s for s, _ in spans))
        ends.append(max(e for _, e in spans))
    window_s = (max(ends) - min(starts)) / 1e9
    by_op: dict = {}
    dev0 = next(d for d in trace["devices"] if d["ops"])
    for name, _, dur, _ in dev0["ops"]:
        key = _op_key(name)
        if key in CONTAINERS:
            continue  # its body's operations are on the line too
        by_op[key] = by_op.get(key, 0.0) + dur / 1e9
    top = sorted(by_op.items(), key=lambda kv: -kv[1])[:10]
    spans0 = [(s, s + d) for _, s, d, _ in dev0["ops"]]
    longest = sorted(gaps(spans0), key=lambda g: g[0] - g[1])[:10]
    idle = [[_host_label(trace["host"], g), (g[1] - g[0]) / 1e9] for g in longest]
    return {
        "busy_s": sum(busy_each) / len(busy_each),
        "window_s": window_s,
        "device_ops": [[k, v] for k, v in top],
        "idle_gaps": idle,
    }


def _host_label(host: list, gap: tuple) -> str:
    """The host event that overlaps the gap most (by name), or ``idle``."""
    best, best_overlap = "no host event", 0.0
    for line, name, start, dur in host:
        overlap = min(gap[1], start + dur) - max(gap[0], start)
        if overlap > best_overlap and dur < 50 * (gap[1] - gap[0]):
            best, best_overlap = f"{line}:{name}"[:80], overlap
    return best


def describe(trace: dict, limit: int = 12) -> str:
    """A by-hand look: planes, lines, a few events with their stats."""
    out = []
    for dev in trace["devices"]:
        out.append(f"PLANE {dev['name']} lines={dev['lines']}")
        for kind in ("modules", "ops"):
            for name, start, dur, st in dev[kind][:limit]:
                short = {k: str(v)[:100] for k, v in st.items()}
                out.append(f"  {kind}: {name[:90]} start={start} dur={dur} {short}")
    out.append(f"HOST events: {len(trace['host'])}")
    lines = sorted({line for line, *_ in trace["host"]})
    out.append(f"HOST lines: {lines[:40]}")
    return "\n".join(out)
