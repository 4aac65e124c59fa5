"""Device time by named scope, and host spans beside it: what the trace
reducers of PR 24 share.

What the raw trace holds (opened by hand, my chip run, PR 24): on the device
plane every event of the ``XLA Ops`` line points at an EVENT METADATA whose
name is the whole HLO instruction (``%copy.24 = bf16[..] copy(bf16[..]
%fusion.7)``) and whose stats carry ``tf_op``, the instruction's ``op_name``:
``jit(_embed_and_vote)/jit(embed)/encoder_layers/while/body/closed_call/mlp/
...i,io->...o/dot_general:``.  The ``jax.named_scope`` names are components of
that path.  ``ProfileData`` shows events' own stats and not their metadata's,
which is why PR 23 could not see them; ``xspace.py`` reads the wire.  On the
host planes the program's ``obs.host_span``s are events named ``http:arrive``,
``batcher:stage`` and so on, their attributes (``rid``, ``group``) as stats, on
the same clock as the device's events.

``scoped(path)`` cuts a trace down to what is read here, and the recorded
fixture of the tests (``tests/data/trace_scoped.json``) is that same form:

  {"modules": [[name, start_ns, dur_ns]],
   "instructions": [{"name", "program", "tf_op", "category", "operands"}],
   "ops": [[instruction index, start_ns, dur_ns]],
   "spans": [[line, name, start_ns, dur_ns, {attribute: value}]]}

An operation's scope is the innermost component of its path that is one of
``SCOPES``.  An operation with no path (a parameter's prefetch, a layout copy
the compiler added) takes the scope of its one consumer in the same program,
where it has exactly one and that one has a scope; what is left is
``unscoped``, under its own name, never guessed into a layer.
"""

from __future__ import annotations

import bisect
import os
import re

import xplane
import xspace

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# every jax.named_scope of models/ and parallel/ (bert.py, deberta.py,
# embedder.py, reranker.py, ring.py)
SCOPES = frozenset(
    (
        "embeddings", "encoder_layers", "qkv_proj", "fused_attention",
        "fused_attention_seg", "einsum_attention", "ring_attention",
        "attention", "rel_bias", "attn_out", "attn_ln", "mlp", "mlp_ln",
        "pool", "head", "consensus_vote", "consensus_vote_many",
        "stream_masked_vote", "rm_vote",
    )
)
# the four shares that are metrics; the rest of 100 (layer norms, embeddings,
# pooling, vote, the scan's own slicing) is PERF.md's table, by scope
GROUPS = {
    "attention": (
        "fused_attention", "fused_attention_seg", "einsum_attention",
        "ring_attention", "attention", "rel_bias",
    ),
    "projections": ("qkv_proj", "attn_out"),
    "mlp": ("mlp",),
    "unscoped": ("unscoped",),
}
HOST_SPANS = frozenset(
    (
        "http:arrive", "http:parse", "host:tokenize", "batcher:idle",
        "batcher:slots_full", "batcher:stage", "device:wait",
        "host:finalize", "http:respond", "lwc:clock",
    )
)

_OPERAND = re.compile(r"%([\w.\-]+)")
_CALLEE = re.compile(r"\b[\w_]+=%[\w.\-]+")


def scope_of(tf_op) -> str:
    if tf_op:
        for part in reversed(tf_op.split("/")):
            if part in SCOPES:
                return part
    return "unscoped"


def _instruction(meta: dict) -> dict:
    """One EVENT METADATA of the ``XLA Ops`` line as an instruction."""
    text = meta["name"]
    head, _, body = text.partition(" = ")
    # operands are the %names inside the call's parentheses; the computations
    # an instruction calls (``calls=%fused_computation.3``) are not operands
    _, _, args = body.partition("(")
    operands = _OPERAND.findall(_CALLEE.sub("", args))
    stats = meta["stats"]
    return {
        "name": (meta.get("display_name") or head).lstrip("%"),
        "program": str(stats.get("program_id", "")),
        "tf_op": stats.get("tf_op"),
        "category": stats.get("hlo_category"),
        "operands": operands,
    }


def scoped(path: str) -> dict:
    """The trace at ``path`` in the form the module docstring gives: the
    first device plane that ran operations, and the program's host spans."""
    planes = xspace.read(path, host_names=HOST_SPANS)
    out = {"modules": [], "instructions": [], "ops": [], "spans": []}
    for plane in planes:
        if plane["name"].startswith("/device:TPU") and not out["ops"]:
            index = {}
            for line in plane["lines"]:
                if line["name"] == "XLA Modules":
                    out["modules"] = [
                        [plane["event_metadata"][mid]["name"], start, dur]
                        for mid, start, dur, _ in line["events"]
                    ]
                elif line["name"] == "XLA Ops":
                    for mid, start, dur, _ in line["events"]:
                        if mid not in index:
                            index[mid] = len(out["instructions"])
                            out["instructions"].append(
                                _instruction(plane["event_metadata"][mid])
                            )
                        out["ops"].append([index[mid], start, dur])
        elif plane["name"].startswith("/host:"):
            for line in plane["lines"]:
                for mid, start, dur, stats in line["events"]:
                    name = plane["event_metadata"][mid]["name"]
                    out["spans"].append([line["name"], name, start, dur, stats])
    out["ops"].sort(key=lambda op: op[1])
    out["spans"].sort(key=lambda span: span[2])
    return out


_CACHE: dict = {}


def trace_of(ctx: dict):
    """The scoped trace of the run a reducer is called for.  ``reduce_all``
    hands a reducer the trace as ``ProfileData`` gives it, without the
    metadata; the file itself is the newest ``.xplane.pb`` under the
    benchmark's work directory, the one this run's profile just wrote.  Tests
    pass theirs as ``ctx["scoped"]``."""
    if "scoped" in ctx:
        return ctx["scoped"]
    path = xplane.newest_xplane(os.path.join(ROOT, ".bench_work"))
    if path is None:
        return None
    key = (path, os.path.getmtime(path))
    if key not in _CACHE:
        _CACHE.clear()
        _CACHE[key] = scoped(path)
    return _CACHE[key]


def scopes(trace: dict) -> list:
    """The scope of each instruction, by its index."""
    instructions = trace["instructions"]
    own = [scope_of(ins["tf_op"]) for ins in instructions]
    consumers: dict = {}
    for i, ins in enumerate(instructions):
        for operand in ins["operands"]:
            consumers.setdefault((ins["program"], operand), []).append(i)

    def inherited(i: int, depth: int) -> str:
        if own[i] != "unscoped" or instructions[i]["tf_op"] or depth > 4:
            return own[i]  # a path without a scope is not the compiler's
        users = consumers.get(
            (instructions[i]["program"], instructions[i]["name"]), []
        )
        return inherited(users[0], depth + 1) if len(users) == 1 else "unscoped"

    return [inherited(i, 0) for i in range(len(instructions))]


def programs(trace: dict, prefixes: list) -> list:
    """(start_ns, end_ns) of the model programs' executions, the first and
    the last left out: the trace's edges may cut them (``forward_mfu``)."""
    runs = sorted(
        (start, start + dur)
        for name, start, dur in trace["modules"]
        if any(name.startswith(p + "(") or name == p for p in prefixes)
    )
    return runs[1:-1]


def by_scope(trace: dict, prefixes: list):
    """Device time of the kept programs' operations by (scope, operation
    kind), containers left out, and the programs' own device time:
    ``({(scope, kind): ns}, program_ns)``."""
    runs = programs(trace, prefixes)
    if not runs:
        return {}, 0.0
    scope = scopes(trace)
    starts = [op[1] for op in trace["ops"]]
    out: dict = {}
    for lo, hi in runs:
        first = bisect.bisect_left(starts, lo)
        last = bisect.bisect_left(starts, hi)
        for index, _, dur in trace["ops"][first:last]:
            kind = xplane._op_key(trace["instructions"][index]["name"])
            if kind in xplane.CONTAINERS:
                continue
            key = (scope[index], kind)
            out[key] = out.get(key, 0.0) + dur
    return out, float(sum(hi - lo for lo, hi in runs))


def share(ctx: dict, group: str):
    """``forward.share.<group>.*``: per cent of the model programs' device
    time spent in operations under the group's scopes."""
    trace = trace_of(ctx)
    if not trace:
        return None
    table, program_ns = by_scope(trace, ctx["config"].get("trace_modules", []))
    if not program_ns or not table:
        return None
    if not any(ins["tf_op"] for ins in trace["instructions"]):
        return None  # a runtime that records no paths: nothing to read
    wanted = GROUPS[group]
    return 100.0 * sum(ns for (s, _), ns in table.items() if s in wanted) / program_ns


def kernel_ns(trace: dict, prefixes: list, kernels: tuple) -> float:
    """Device time of the named kernels' own events in the kept programs."""
    table, _ = by_scope(trace, prefixes)
    return sum(ns for (_, kind), ns in table.items() if kind in kernels)


def in_flight(trace: dict, lo: float, hi: float) -> list:
    """(start_ns, end_ns) per request between its ``http:arrive`` and the end
    of its ``http:respond``, cut to [lo, hi].  A request that answers inside
    the trace without having arrived inside it was in flight when the trace
    began and counts from ``lo``; one that arrives and does not answer counts
    to ``hi``.  (One in flight over the whole trace is in neither list: no
    request of these cells lasts 8 s.)"""
    arrived, answered = {}, {}
    for _, name, start, dur, stats in trace["spans"]:
        rid = str(stats.get("rid"))
        if name == "http:arrive":
            arrived[rid] = start
        elif name == "http:respond":
            answered[rid] = start + dur
    out = []
    for rid in arrived.keys() | answered.keys():
        start = max(arrived.get(rid, lo), lo)
        end = min(answered.get(rid, hi), hi)
        if end > start:
            out.append((start, end))
    return out


def overlap_ns(a: list, b: list) -> float:
    """Length of the intersection of two unions of (start, end) intervals."""
    total = xplane.union_seconds(a) + xplane.union_seconds(b)
    return (total - xplane.union_seconds(a + b)) * 1e9


def idle_with_work(ctx: dict):
    """``device.idle_with_work_share``: per cent of the traced window (first
    operation's start to last operation's end, as ``xplane.busy`` takes it)
    in which no operation ran on the device AND at least one request was in
    flight."""
    trace = trace_of(ctx)
    if not trace or not trace["ops"]:
        return None
    if not any(name == "http:arrive" for _, name, *_ in trace["spans"]):
        return None  # a program without host spans on the profiler's clock
    busy = [(start, start + dur) for _, start, dur in trace["ops"]]
    lo, hi = busy[0][0], max(end for _, end in busy)
    idle = xplane.gaps(busy)
    return 100.0 * overlap_ns(idle, in_flight(trace, lo, hi)) / (hi - lo)


def table_text(trace: dict, prefixes: list) -> str:
    """PERF.md's scope x operation-kind table of one trace, by hand:
    ``python3 bench/tools/scope_table.py``."""
    table, program_ns = by_scope(trace, prefixes)
    if not program_ns:
        return "no model program inside the trace"
    rows = sorted(table.items(), key=lambda kv: -kv[1])
    lines = [f"programs' device time {program_ns / 1e6:.3f} ms"]
    by: dict = {}
    for (scope, _), ns in rows:
        by[scope] = by.get(scope, 0.0) + ns
    for scope, ns in sorted(by.items(), key=lambda kv: -kv[1]):
        lines.append(f"{scope:22s} {100 * ns / program_ns:7.3f}%")
        for (s, kind), part in rows:
            if s == scope and part / program_ns >= 5e-5:
                lines.append(f"    {kind:34s} {100 * part / program_ns:7.3f}%")
    rest = program_ns - sum(by.values())
    lines.append(f"{'(between operations)':22s} {100 * rest / program_ns:7.3f}%")
    return "\n".join(lines)
