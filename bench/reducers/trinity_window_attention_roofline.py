"""kernel.window_attention_roofline.trinity: grouped-query attention over a
window, against its roofline.

The pairs INSIDE THE BAND of a panel (58,722,304 a call of 16,384 slots at 4096
keys a query, 43.7% of the causal ones) times 48 heads x (128 + 128) dims x 2
are 4.33 TFLOP a layer a dispatch (22 ms of the chip's arithmetic) against
1.4 GB of q, k, v and the context (1.7 ms of its memory): compute-bound.
Operations and bytes are the family's (``bench/families/afmoe.py``: the
mathematics), so a kernel that multiplies a whole tile along the band's old
edge reads under the band's share of what it multiplies
(``ops/causal_attention.py::work_over_window``: 1.25 at blocks of 2048) and
none can read over 100.  The time is the kernel's own events
(``window_attention_blockwise``, the jitted name the sliding layers' kernel
runs under; the full layer's is ``causal_attention_blockwise``).
"""

import trinity_scopes

KERNELS = ("window_attention_blockwise",)


def reduce(ctx):
    return trinity_scopes.roofline(ctx, KERNELS, "window_attention")
