"""Device time of the third judge's programs by ITS named scopes.

The decoder with a learned sparse selection (``models/glm_moe.py`` under a
configuration with an indexer) names the first judge's parts and five more:
``index_q``, ``index_k`` (the indexer's products), ``index_scores`` and
``index_select`` (its two kernels) and ``selected_attention`` (the attention
kernel over the chosen keys, where the first judge has ``causal_attention``).
``judge_scopes.SCOPES`` is a fixed set, so the table is here, read the same
way: an operation's scope is ``decode_step`` where that is anywhere on its
path, else the innermost of ``SCOPES``; an operation with no path takes its
one consumer's; what is left is ``unscoped``.  The same trace form, programs,
kinds and containers as ``scope_time``.

The experts' operations in every share of a peak come from the program's
counter of the pairs that reached an expert held here, as the second judge's
do: ``qnext_scopes``' ``held_pairs``, ``with_pairs``, ``family_of`` and ``mfu``
are called as they are, and so is ``judge_scopes.kernel_roofline``.  A program
that names none of this table's own scopes (any before the decoder existed)
gives nothing to read and every reader returns None.
"""

from __future__ import annotations

import bisect

import judge_scopes
import qnext_scopes
import scope_time
import xplane

OWN = frozenset(("index_q", "index_k", "index_scores", "index_select", "selected_attention"))
SCOPES = judge_scopes.SCOPES | OWN
# the six shares that are metrics; the rest of 100 (embedding, head reads,
# the vote) is PERF.md's table, by scope
GROUPS = {
    "indexer": ("index_q", "index_k", "index_scores", "index_select"),
    "selected_attention": ("selected_attention",),
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


def selects(ctx: dict):
    """The trace, where its programs name a scope of the selection; else None
    (no trace, or a program from before the decoder existed)."""
    trace = scope_time.trace_of(ctx)
    if trace and any(
        OWN.intersection((ins["tf_op"] or "").split("/")) for ins in trace["instructions"]
    ):
        return trace
    return None


def share(ctx: dict, group: str):
    """``forward.share.<group>.glm5``: per cent of the judge programs' device
    time in operations under the group's scopes."""
    trace = selects(ctx)
    if not trace:
        return None
    table, program_ns = by_scope(trace, ctx["config"].get("trace_modules", []))
    if not program_ns or not table:
        return None
    wanted = GROUPS[group]
    return 100.0 * sum(ns for (s, _), ns in table.items() if s in wanted) / program_ns


def roofline(ctx: dict, kernels: tuple, which: str):
    """Per cent of its roofline a kernel of the selection reached: the
    family's ``<which>_flops`` and ``<which>_bytes`` against the kernel's own
    events; None for a family that counts no such kernel."""
    family = qnext_scopes.family_of(ctx)
    flops, moved = getattr(family, which + "_flops", None), getattr(family, which + "_bytes", None)
    if flops is None or moved is None or not selects(ctx):
        return None
    return judge_scopes.kernel_roofline(ctx, kernels, flops, moved)
