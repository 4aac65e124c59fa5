"""kernel.selective_scan_roofline.phi4flash: the selective scan against its
roofline.

A MEMORY roofline, as the delta rule's and the head norms' are counted: xc and
dt read and m written once a channel, B and C once a state, 0.76 GB a layer a
dispatch of 3 x 8192 slots (0.92 ms of the chip's memory; 6.8 GB and 8.3 ms the
nine layers) against 12 GFLOP of recurrence (0.06 ms of a peak that is the
MXU's, which this kernel cannot use).  The state updates are the vector unit's
work, for which ``bench/peaks.json`` has no line: 2.0 G of them a layer, an
``exp`` and six multiplies and adds each, so what they cost reads as distance
from the roofline.  Operations and bytes are the family's
(``bench/families/phi4flash.py``: the recurrence as written, the arrays at
their own size, whatever the kernel lays B and C in); the time is the kernel's
own events (``selective_scan_chunked``, the ``jax.jit`` that holds the
``pallas_call``): the convolution, the two small products and the gate around
it are in ``forward.share.state_space.phi4flash``.
"""

import phi4flash_scopes

KERNELS = ("selective_scan_chunked",)


def reduce(ctx):
    return phi4flash_scopes.roofline(ctx, KERNELS, "selective_scan")
