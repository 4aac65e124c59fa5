"""Device time of the sixth judge's programs by ITS named scopes.

The decoder that feeds a decoder (``models/sambay.py``) names parts no other
table knows: ``mamba_in``, ``mamba_conv``, ``selective_scan``, ``mamba_out``
(a state-space layer), ``diff_norm`` (two softmaxes subtracted and normed),
``mlp`` (a dense MLP every layer), and ``cross_decoder``: everything the
program runs at the row the panel reads and nowhere else (the full layer's
own attention, the memory units, the cross layers, their MLPs), whose inner
scopes (``causal_attention``, ``memory_unit``, ``cross_attention`` and again
``mlp``, ``diff_norm``, ``attn_out``) are the stage table's and not a share's.
The other five tables are fixed sets (PERF.md, question 24), so the table is
here, read the same way with ONE rule more: an operation's scope is
``decode_step`` where that is anywhere on its path, else ``cross_decoder``
where that is, else the innermost of ``SCOPES``; an operation with no path
takes its one consumer's; what is left is ``unscoped``.  The same trace form,
programs, kinds and containers as ``scope_time`` (the program has no
``lax.cond``; ``xplane.CONTAINERS`` leaves ``cond`` out should one appear), so
that this table's shares add up to the program.

``qnext_scopes``' ``family_of`` and ``mfu`` are called as they are (the family
has no experts: the counted pairs are none and count nothing), and so is
``judge_scopes.kernel_roofline``.  A program that names no ``selective_scan``
(every other judge's, and any before this decoder existed) gives nothing to
read and every reader returns None.
"""

from __future__ import annotations

import bisect

import judge_scopes
import qnext_scopes
import scope_time
import xplane

SCOPES = frozenset(
    (
        "embed_tokens", "mamba_in", "mamba_conv", "selective_scan", "mamba_out", "attn_qkv",
        "window_attention", "diff_norm", "attn_out", "mlp", "cross_decoder", "head_read",
        "decode_step", "ballot_vote",
    )
)
WHOLE = ("decode_step", "cross_decoder")  # anywhere on a path, in this order
# the six shares that are metrics; the rest of 100 (embedding, head reads,
# the vote) is PERF.md's table, by scope
GROUPS = {
    "state_space": ("mamba_in", "mamba_conv", "selective_scan", "mamba_out"),
    "attention": ("attn_qkv", "window_attention", "diff_norm", "attn_out"),
    "mlp": ("mlp",),
    "cross_decoder": ("cross_decoder",),
    "decode": ("decode_step",),
    "unscoped": ("unscoped",),
}


def scope_of(tf_op) -> str:
    if tf_op:
        parts = tf_op.split("/")
        for whole in WHOLE:
            if whole in parts:
                return whole
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
    """The trace, where its programs name a selective scan; else None (no
    trace, another judge's program)."""
    trace = scope_time.trace_of(ctx)
    if trace and any(
        "selective_scan" in (ins["tf_op"] or "").split("/") for ins in trace["instructions"]
    ):
        return trace
    return None


def share(ctx: dict, group: str):
    """``forward.share.<group>.phi4flash``: per cent of the judge programs'
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
    """``forward.mfu.phi4flash``: ``qnext_scopes.mfu`` over this decoder's
    programs (the family counts the work the answer needs: the first half at
    every slot, the second at the two positions read)."""
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
