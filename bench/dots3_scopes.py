"""Device time of the fourth judge's programs by ITS named scopes.

The decoder whose attention layers are of two kinds (``models/glm_moe.py``
under a configuration with ``layer_types``) names the third judge's parts and
two more: ``window_attention`` (the kernel on a sliding layer, where a full
layer has ``selected_attention``) and ``attn_gate`` (the head gate's product
and sigmoid).  ``glm5_scopes.SCOPES`` is a fixed set, so the table is here,
read the same way: an operation's scope is ``decode_step`` where that is
anywhere on its path, else the innermost of ``SCOPES``; an operation with no
path takes its one consumer's; what is left is ``unscoped``.  The same trace
form, programs, kinds and containers as ``scope_time``.

The experts' operations in every share of a peak come from the program's
counter of the pairs that reached an expert held here: ``qnext_scopes``'
``with_pairs``, ``family_of`` and ``mfu`` are called as they are, and so is
``judge_scopes.kernel_roofline``.  A program that names no
``window_attention`` (any before the decoder told its layers apart, and every
other judge's) gives nothing to read and every reader returns None.
"""

from __future__ import annotations

import bisect

import glm5_scopes
import judge_scopes
import qnext_scopes
import scope_time
import xplane

OWN = frozenset(("window_attention", "attn_gate"))
SCOPES = glm5_scopes.SCOPES | OWN
# the seven shares that are metrics; the rest of 100 (embedding, head reads,
# the vote) is PERF.md's table, by scope
GROUPS = {
    "window_attention": ("window_attention",),
    "selected_attention": ("selected_attention",),
    "indexer": ("index_q", "index_k", "index_scores", "index_select"),
    "projections": ("latent_q", "latent_kv", "attn_gate", "attn_out"),
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


def slides(ctx: dict):
    """The trace, where its programs name a layer with a window; else None
    (no trace, a program from before the decoder told its layers apart,
    another judge's)."""
    trace = scope_time.trace_of(ctx)
    if trace and any(
        "window_attention" in (ins["tf_op"] or "").split("/") for ins in trace["instructions"]
    ):
        return trace
    return None


def share(ctx: dict, group: str):
    """``forward.share.<group>.dots3``: per cent of the judge programs' device
    time in operations under the group's scopes."""
    trace = slides(ctx)
    if not trace:
        return None
    table, program_ns = by_scope(trace, ctx["config"].get("trace_modules", []))
    if not program_ns or not table:
        return None
    wanted = GROUPS[group]
    return 100.0 * sum(ns for (s, _), ns in table.items() if s in wanted) / program_ns


def mfu(ctx: dict):
    """``forward.mfu.dots3``: ``qnext_scopes.mfu`` over this decoder's
    programs (the family counts the band, the selection and the held pairs)."""
    return qnext_scopes.mfu(ctx) if slides(ctx) else None


def roofline(ctx: dict, kernels: tuple, which: str):
    """Per cent of its roofline a kernel reached: the family's
    ``<which>_flops`` and ``<which>_bytes`` against the kernel's own events;
    None for a family that counts no such kernel."""
    family = qnext_scopes.family_of(ctx)
    flops, moved = getattr(family, which + "_flops", None), getattr(family, which + "_bytes", None)
    if flops is None or moved is None or not slides(ctx):
        return None
    return judge_scopes.kernel_roofline(ctx, kernels, flops, moved)
