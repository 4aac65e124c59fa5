"""Device time of the fifth judge's programs by ITS named scopes.

The decoder of grouped-query attention of two kinds (``models/afmoe.py``)
names parts no other table knows: ``attn_qkv`` (the second judge has the name,
for other work), ``window_attention`` beside ``causal_attention`` in ONE stack,
``attn_gate`` (an elementwise gate) and ``mlp_norm`` (the norm BEHIND the MLP,
before the sum).  The other four tables are fixed sets (PERF.md, question 24),
so the table is here, read the same way: an operation's scope is
``decode_step`` where that is anywhere on its path, else the innermost of
``SCOPES``; an operation with no path takes its one consumer's; what is left
is ``unscoped``.  The same trace form, programs, kinds and containers as
``scope_time``: the held experts' ``lax.cond`` reaches the trace as an
operation named ``cond`` that spans its branch's own events, and
``xplane.CONTAINERS`` leaves it out (since PR 48, in every table), so that
this table's shares add up to the program.

The experts' operations in every share of a peak come from the program's
counter of the pairs that reached an expert held here: ``qnext_scopes``'
``with_pairs``, ``family_of`` and ``mfu`` are called as they are, and so is
``judge_scopes.kernel_roofline``.  A program that names no ``mlp_norm`` (every
other judge's, and any before this decoder existed) gives nothing to read and
every reader returns None.
"""

from __future__ import annotations

import bisect

import judge_scopes
import qnext_scopes
import scope_time
import xplane

SCOPES = frozenset(
    (
        "embed_tokens", "attn_qkv", "window_attention", "causal_attention", "attn_gate",
        "attn_out", "router", "experts_routed", "expert_shared", "dense_mlp", "mlp_norm",
        "head_read", "decode_step", "ballot_vote",
    )
)
# the six shares that are metrics; the rest of 100 (embedding, head reads,
# the vote) is PERF.md's table, by scope
GROUPS = {
    "window_attention": ("window_attention",),
    "full_attention": ("causal_attention",),
    "projections": ("attn_qkv", "attn_gate", "attn_out"),
    "experts": ("router", "experts_routed", "expert_shared", "dense_mlp", "mlp_norm"),
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


def served(ctx: dict):
    """The trace, where its programs name the norm behind an MLP; else None
    (no trace, another judge's program)."""
    trace = scope_time.trace_of(ctx)
    if trace and any(
        "mlp_norm" in (ins["tf_op"] or "").split("/") for ins in trace["instructions"]
    ):
        return trace
    return None


def share(ctx: dict, group: str):
    """``forward.share.<group>.trinity``: per cent of the judge programs'
    device time in operations under the group's scopes."""
    trace = served(ctx)
    if not trace:
        return None
    table, program_ns = by_scope(trace, ctx["config"].get("trace_modules", []))
    if not program_ns or not table:
        return None
    wanted = GROUPS[group]
    return 100.0 * sum(ns for (s, _), ns in table.items() if s in wanted) / program_ns


def mfu(ctx: dict):
    """``forward.mfu.trinity``: ``qnext_scopes.mfu`` over this decoder's
    programs (the family counts the band, the causal half and the held pairs)."""
    return qnext_scopes.mfu(ctx) if served(ctx) else None


def roofline(ctx: dict, kernels: tuple, which: str):
    """Per cent of its roofline a kernel reached: the family's
    ``<which>_flops`` and ``<which>_bytes`` against the kernel's own events;
    None for a family that counts no such kernel."""
    family = qnext_scopes.family_of(ctx)
    flops, moved = getattr(family, which + "_flops", None), getattr(family, which + "_bytes", None)
    if flops is None or moved is None or not served(ctx):
        return None
    return judge_scopes.kernel_roofline(ctx, kernels, flops, moved)
