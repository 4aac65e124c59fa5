"""kernel.window_attention_roofline.phi4flash: differential attention over a
window of 512 keys, against its roofline.

The pairs INSIDE THE BAND of a panel (4,063,488 a call of 8192 slots, 12.1% of
the causal ones) times 40 heads x (64 + 128) dims x 2 are 0.187 TFLOP a layer
a dispatch (0.95 ms of the chip's arithmetic) against 0.50 GB of q, k, v and
the context (0.61 ms of its memory): compute-bound as counted.  Operations and
bytes are the family's (``bench/families/phi4flash.py``: the mathematics, a
head 64 wide), so a kernel that lays a head of 64 in 128 lanes and multiplies
whole tiles along the band's edges (``ops/causal_attention.py::
work_over_window``: 1.50 at blocks of 512) reads under both shares of what it
multiplies and none can read over 100.  The time is the kernel's own events
(``window_attention_blockwise``, the jitted name the sliding layers' kernel
runs under).  The full layer attends at the row read alone, one row of scores
a call in XLA: no kernel of its own, so no ``causal_attention_roofline`` here.
"""

import phi4flash_scopes

KERNELS = ("window_attention_blockwise",)


def reduce(ctx):
    return phi4flash_scopes.roofline(ctx, KERNELS, "window_attention")
