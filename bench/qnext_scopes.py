"""Device time of the second judge's programs by ITS named scopes.

``judge_scopes.py`` holds the first judge's scope table, a fixed set; the
decoder of gated delta-rule and gated full-attention layers
(``models/qwen3_next.py``) names other parts, so its table is here, read the
same way: an operation's scope is ``decode_step`` where that is anywhere on
its path, else the innermost of ``SCOPES``; an operation with no path takes
its one consumer's; what is left is ``unscoped``.  The same trace form,
programs, kinds and containers as ``scope_time``; ``judge_scopes``'
``dispatched`` and ``kernel_roofline`` are called as they are.

The experts' operations in every share of a peak come from the program's
counter of the pairs that reached an expert held here (``held_pairs``), not
from ``num_experts_per_tok`` a token: the routing is uneven, so the share
held is a share only on average.  A program without the counter (any before
the decoder existed) gives nothing to read and every reader returns None.
"""

from __future__ import annotations

import bisect

import byname
import judge_scopes
import scope_time
import xplane

SCOPES = frozenset(
    (
        "embed_tokens", "linear_in", "linear_conv", "delta_rule", "linear_norm",
        "linear_out", "attn_qkv", "causal_attention", "attn_out", "router",
        "experts_routed", "expert_shared", "head_read", "decode_step", "ballot_vote",
    )
)
# the six shares that are metrics; the rest of 100 (embedding, head reads,
# the vote) is PERF.md's table, by scope
GROUPS = {
    "linear_attention": ("linear_conv", "delta_rule", "linear_norm"),
    "attention": ("causal_attention",),
    "experts": ("router", "experts_routed", "expert_shared"),
    "projections": ("linear_in", "linear_out", "attn_qkv", "attn_out"),
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
    """``forward.share.<group>.qnext``: per cent of the judge programs' device
    time in operations under the group's scopes."""
    trace = scope_time.trace_of(ctx)
    if not trace or not any(ins["tf_op"] for ins in trace["instructions"]):
        return None
    table, program_ns = by_scope(trace, ctx["config"].get("trace_modules", []))
    if not program_ns or not table:
        return None
    wanted = GROUPS[group]
    return 100.0 * sum(ns for (s, _), ns in table.items() if s in wanted) / program_ns


def held_pairs(ctx: dict):
    """Pairs a dispatch sent to the experts held here, all its layers: the
    mean over the dispatches between the profile's two /metrics readings
    (``judge.expert_pairs_here`` over ``judge.dispatches``); None where the
    program keeps no such counter or nothing was dispatched."""
    before = ctx["profile"]["before"].get("judge") or {}
    after = ctx["profile"]["after"].get("judge") or {}
    if "expert_pairs_here" not in after:
        return None
    dispatches = after.get("dispatches", 0) - before.get("dispatches", 0)
    if dispatches <= 0:
        return None
    return (after["expert_pairs_here"] - before.get("expert_pairs_here", 0)) / dispatches


def with_pairs(ctx: dict, function):
    """A family's (cfg, rows, seq, held_pairs) count as the (cfg, rows, seq)
    function ``judge_scopes.kernel_roofline`` calls; None without the counter."""
    pairs = held_pairs(ctx)
    if pairs is None:
        return None
    return lambda cfg, rows, seq: function(cfg, rows, seq, pairs)


def family_of(ctx: dict):
    return byname.module("families", ctx["config"]["family"])


def mfu(ctx: dict):
    """``forward.mfu.qnext``: the operations of the judge programs that ran
    inside the trace over their own device time times the bf16 peak, the
    first and the last program left out of both sides (``forward_mfu``)."""
    trace = scope_time.trace_of(ctx)
    shapes = judge_scopes.dispatched(ctx)
    count = with_pairs(ctx, family_of(ctx).forward_flops)
    if not trace or not shapes or count is None:
        return None
    runs = scope_time.programs(trace, ctx["config"].get("trace_modules", []))
    seconds = sum(hi - lo for lo, hi in runs) / 1e9
    total = sum(n for *_, n in shapes)
    if not (runs and seconds and total):
        return None
    per_program = sum(n * count(ctx["cfg"], rows, seq) for rows, seq, n in shapes) / total
    return 100.0 * per_program * len(runs) / (seconds * ctx["peaks"]["bf16_flops_per_s"])
