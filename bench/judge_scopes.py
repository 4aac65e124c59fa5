"""Device time of the judge's programs by its own named scopes.

``scope_time.py`` knows the encoders' scopes; the judge's decoder
(``models/glm_moe.py``) names its own, and nests them: the decoded token runs
under ``decode_step`` and, inside it, under the same names as the prefill
(``latent_q``, ``causal_attention``, ...).  An operation's scope here is
``decode_step`` where that is anywhere on its path, else the innermost of
``SCOPES``; an operation with no path takes its one consumer's, as in
``scope_time.scopes``; what is left is ``unscoped``.  The same trace form,
programs, kinds and containers as ``scope_time``.
"""

from __future__ import annotations

import bisect

import scope_time
import xplane

SCOPES = frozenset(
    (
        "embed_tokens", "latent_q", "latent_kv", "causal_attention", "attn_out",
        "router", "experts_routed", "expert_shared", "dense_mlp", "head_read",
        "decode_step", "ballot_vote",
    )
)
# the five shares that are metrics; the rest of 100 (embedding, head reads,
# the vote) is PERF.md's table, by scope
GROUPS = {
    "latent_attention": ("causal_attention",),
    "projections": ("latent_q", "latent_kv", "attn_out"),
    "experts": ("router", "experts_routed", "expert_shared", "dense_mlp"),
    "decode": ("decode_step",),
    "unscoped": ("unscoped",),
}


def scope_of(tf_op) -> str:
    if tf_op:
        parts = tf_op.split("/")
        if "decode_step" in parts:
            return "decode_step"
        for part in reversed(parts):
            if part in SCOPES:
                return part
    return "unscoped"


def scopes(trace: dict) -> list:
    instructions = trace["instructions"]
    own = [scope_of(ins["tf_op"]) for ins in instructions]
    consumers: dict = {}
    for i, ins in enumerate(instructions):
        for operand in ins["operands"]:
            consumers.setdefault((ins["program"], operand), []).append(i)

    def inherited(i: int, depth: int) -> str:
        if own[i] != "unscoped" or instructions[i]["tf_op"] or depth > 4:
            return own[i]
        users = consumers.get((instructions[i]["program"], instructions[i]["name"]), [])
        return inherited(users[0], depth + 1) if len(users) == 1 else "unscoped"

    return [inherited(i, 0) for i in range(len(instructions))]


def by_scope(trace: dict, prefixes: list):
    """({(scope, operation kind): ns}, the kept programs' own ns)."""
    runs = scope_time.programs(trace, prefixes)
    if not runs:
        return {}, 0.0
    scope = scopes(trace)
    starts = [op[1] for op in trace["ops"]]
    out: dict = {}
    for lo, hi in runs:
        first, last = bisect.bisect_left(starts, lo), bisect.bisect_left(starts, hi)
        for index, _, dur in trace["ops"][first:last]:
            kind = xplane._op_key(trace["instructions"][index]["name"])
            if kind in xplane.CONTAINERS:
                continue
            key = (scope[index], kind)
            out[key] = out.get(key, 0.0) + dur
    return out, float(sum(hi - lo for lo, hi in runs))


def share(ctx: dict, group: str):
    """``forward.share.<group>.judge``: per cent of the judge programs' device
    time in operations under the group's scopes."""
    trace = scope_time.trace_of(ctx)
    if not trace:
        return None
    table, program_ns = by_scope(trace, ctx["config"].get("trace_modules", []))
    if not program_ns or not table:
        return None
    if not any(ins["tf_op"] for ins in trace["instructions"]):
        return None
    wanted = GROUPS[group]
    return 100.0 * sum(ns for (s, _), ns in table.items() if s in wanted) / program_ns


def dispatched(ctx: dict):
    """[(rows, seq, count)] of the judge dispatches between the profile's two
    /metrics readings, from their labels ``judge(n=3,s=8192)``; None where a
    label cannot be read."""
    from reducers import forward_mfu

    before = (ctx["profile"]["before"].get("roofline") or {}).get("buckets", {})
    after = (ctx["profile"]["after"].get("roofline") or {}).get("buckets", {})
    out = []
    for label, row in after.items():
        count = row.get("count", 0) - before.get(label, {}).get("count", 0)
        if count <= 0:
            continue
        shape = forward_mfu.slot_shape(label)
        if shape is None:
            return None
        out.append((*shape, count))
    return out


def kernel_roofline(ctx: dict, kernels: tuple, flops, moved):
    """Per cent of its roofline a kernel reached: the least time the chip
    could take for the kernel's work in the kept programs (the larger of its
    operations over the bf16 peak and its bytes over the memory's rate) over
    the time of the kernel's own events.  ``flops`` and ``moved`` are
    functions of (cfg, rows, seq) for ONE dispatch."""
    trace = scope_time.trace_of(ctx)
    shapes = dispatched(ctx)
    if not trace or not shapes:
        return None
    prefixes = ctx["config"].get("trace_modules", [])
    kept = len(scope_time.programs(trace, prefixes))
    seconds = scope_time.kernel_ns(trace, prefixes, kernels) / 1e9
    total = sum(count for *_, count in shapes)
    if not (kept and seconds and total):
        return None
    peaks = ctx["peaks"]
    least = 0.0
    for rows, seq, count in shapes:
        ops, data = flops(ctx["cfg"], rows, seq), moved(ctx["cfg"], rows, seq)
        least += count * max(
            ops / peaks["bf16_flops_per_s"], data / peaks["hbm_bytes_per_s"]
        )
    return 100.0 * (least / total * kept) / seconds
