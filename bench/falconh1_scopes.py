"""Device time of the seventh judge's programs by ITS named scopes.

The decoder whose every block runs a state-space mixer and attention side by
side (``models/falcon_h1.py``) names parts no other table knows: ``ssm_in``,
``ssm_conv``, ``ssd_scan``, ``ssm_norm``, ``ssm_out`` (the mixer: its input
products, the convolution, the chunked state-space dual kernel, the gated norm
over groups, its output product) beside ``attn_qkv``, ``causal_attention`` (the
rotary turn and the kernel), ``attn_out`` and ``mlp``.  The other six tables are
fixed sets (PERF.md, question 24), so the table is here, read the same way: an
operation's scope is ``decode_step`` where that is anywhere on its path, else
the innermost of ``SCOPES``; an operation with no path takes its one
consumer's; what is left is ``unscoped``.  The same trace form, programs, kinds
and containers as ``scope_time``, so that this table's shares add up to the
program.

``qnext_scopes``' ``family_of`` and ``mfu`` are called as they are (the family
has no experts: the counted pairs are none and count nothing), and so is
``judge_scopes.kernel_roofline``.  A program that names no ``ssd_scan`` (every
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
        "embed_tokens", "ssm_in", "ssm_conv", "ssd_scan", "ssm_norm", "ssm_out", "attn_qkv",
        "causal_attention", "attn_out", "mlp", "head_read", "decode_step", "ballot_vote",
    )
)
# the six shares that are metrics; the rest of 100 (embedding, head reads,
# the vote) is PERF.md's table, by scope
GROUPS = {
    "state_space": ("ssm_conv", "ssd_scan", "ssm_norm"),
    "attention": ("causal_attention",),
    "projections": ("ssm_in", "ssm_out", "attn_qkv", "attn_out"),
    "mlp": ("mlp",),
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
    """The trace, where its programs name the state-space dual scan; else None
    (no trace, another judge's program)."""
    trace = scope_time.trace_of(ctx)
    if trace and any(
        "ssd_scan" in (ins["tf_op"] or "").split("/") for ins in trace["instructions"]
    ):
        return trace
    return None


def share(ctx: dict, group: str):
    """``forward.share.<group>.falconh1``: per cent of the judge programs'
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
    """``forward.mfu.falconh1``: ``qnext_scopes.mfu`` over this decoder's
    programs (the family counts every layer at every slot, as the program runs
    them)."""
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
