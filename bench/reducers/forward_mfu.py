"""forward.mfu.*: model operations of the model programs that ran inside the
trace, over the device time of exactly those programs times the bf16 peak.

From the trace: the executions of the model programs (the ``XLA Modules``
line, programs named in the configuration's ``trace_modules``), each with its
device time.  The first and the last of them are left out of both sides: the
trace's edges may cut them, and a grouped program of long requests lasts over
a second of an 8 s trace.  From the program's counters, read just before and
near the end of the trace: the slot tokens dispatched
(``device_batcher.padded.slot_tokens``: rows x sequence bucket of every
dispatch, padding included, because the padded path computes every slot) and
the number of dispatches, which give the mean operations of one program.
Operations per slot token depend a little on the sequence bucket (attention's
share); that mix is taken from the per-shape dispatch labels
(``roofline.buckets``: ``vote1(n=64,s=512)``, ``many(r=4,n=64,s=512)``) and
``bench/flops.py``.  The programs' own time, not window time, is the divisor:
operations are counted for the events whose times are summed and for no
others; the idle share is reported apart.
"""

from __future__ import annotations

import re

import flops
import layers
import xplane

_LABEL = re.compile(r"^(\w+)\(([^)]*)\)")


def slot_shape(label: str):
    """``many(r=4,n=64,s=128)`` -> (256, 128); None for a label it cannot read
    (a mesh suffix ``@dp..`` is fine, an unknown kind is not)."""
    m = _LABEL.match(label)
    if not m:
        return None
    try:
        args = dict(part.split("=") for part in m.group(2).split(","))
        rows = int(args["n"]) * int(args.get("r", 1))
        return rows, int(args["s"])
    except (KeyError, ValueError):
        return None


def _delta(ctx, path: str) -> float:
    before = layers.dig(ctx["profile"]["before"], path) or 0.0
    return float(layers.dig(ctx["profile"]["after"], path) or 0.0) - float(before)


def reduce(ctx):
    before = (ctx["profile"]["before"].get("roofline") or {}).get("buckets", {})
    after = (ctx["profile"]["after"].get("roofline") or {}).get("buckets", {})
    ops, tokens = 0, 0
    for label, row in after.items():
        count = row.get("count", 0) - before.get(label, {}).get("count", 0)
        if count <= 0:
            continue
        shape = slot_shape(label)
        if shape is None:
            return None  # a dispatch whose shape cannot be read: no guess
        tokens += count * shape[0] * shape[1]
        ops += count * flops.forward_flops(
            ctx["config"]["family"], ctx["cfg"], *shape
        )
    slot_tokens = _delta(ctx, "device_batcher.padded.slot_tokens")
    dispatches = _delta(ctx, "device_batcher.dispatches")
    events = xplane.module_events(
        ctx["trace"], ctx["config"].get("trace_modules", [])
    )[1:-1]
    seconds = sum(dur for _, dur in events) / 1e9
    if not (ops and slot_tokens and dispatches and events and seconds):
        return None
    per_program = (ops / tokens) * slot_tokens / dispatches
    total = per_program * len(events)
    return 100.0 * total / (seconds * ctx["peaks"]["bf16_flops_per_s"])
