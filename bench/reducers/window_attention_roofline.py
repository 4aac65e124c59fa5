"""kernel.window_attention_roofline.dots3: attention over a window, against
its roofline.

The pairs INSIDE THE BAND of a panel (4,071,168 a call of 8192 slots at 513
keys a query, 12.1% of the causal ones) times 64 heads x (256 + 128) dims x 2
are 0.60 TFLOP a layer a dispatch (3.0 ms of the chip's arithmetic) against
1.9 GB of q, k, v and the context (2.4 ms of its memory): compute-bound by a
little.  Operations and bytes are the family's (``bench/families/dots3_note.py``:
the mathematics), so a kernel that multiplies whole tiles along the band's
two edges reads under the band's share of what it multiplies
(``ops/causal_attention.py::work_over_window``: 1.74 at blocks of 512) and none
can read over 100.  The time is the kernel's own events
(``window_attention_blockwise``, the jitted name the sliding layers' kernel
runs under; a full layer's is ``causal_attention_blockwise``).
"""

import dots3_scopes

KERNELS = ("window_attention_blockwise",)


def reduce(ctx):
    return dots3_scopes.roofline(ctx, KERNELS, "window_attention")
