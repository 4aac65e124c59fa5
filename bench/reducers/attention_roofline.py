"""kernel.attention_roofline.*: attention's operations over the time of the
fused attention KERNEL's own events times the bf16 peak.

Compute-bound: at 64 x 512 a layer's two products are 69 GFLOP against 0.27
GB of q, k, v and the output, 0.35 ms of the chip's arithmetic against 0.33
ms of its memory, and a grouped program is eight times both.  Operations are
counted as ``forward_mfu`` counts the forward's: ``2 * 2 * rows * seq * seq *
hidden`` a layer (q k^T and probs v), rows and seq from the dispatch labels of
the traced interval, the mean of one program times the programs kept (the
first and last are left out).  The time is the kernel's events alone: the
head transposes and copies around it are in ``forward.share.attention``.
"""

import scope_time
from reducers import forward_mfu

KERNELS = ("fused_attention_tiled", "fused_attention_tiled_seg")


def attention_flops(cfg: dict, rows: int, seq: int) -> int:
    return cfg["num_hidden_layers"] * 2 * 2 * rows * seq * seq * cfg["hidden_size"]


def reduce(ctx):
    trace = scope_time.trace_of(ctx)
    if not trace:
        return None
    prefixes = ctx["config"].get("trace_modules", [])
    before = (ctx["profile"]["before"].get("roofline") or {}).get("buckets", {})
    after = (ctx["profile"]["after"].get("roofline") or {}).get("buckets", {})
    ops, dispatched = 0, 0
    for label, row in after.items():
        count = row.get("count", 0) - before.get(label, {}).get("count", 0)
        if count <= 0:
            continue
        shape = forward_mfu.slot_shape(label)
        if shape is None:
            return None  # a dispatch whose shape cannot be read: no guess
        ops += count * attention_flops(ctx["cfg"], *shape)
        dispatched += count
    kept = len(scope_time.programs(trace, prefixes))
    seconds = scope_time.kernel_ns(trace, prefixes, KERNELS) / 1e9
    if not (ops and dispatched and kept and seconds):
        return None
    total = ops / dispatched * kept
    return 100.0 * total / (seconds * ctx["peaks"]["bf16_flops_per_s"])
