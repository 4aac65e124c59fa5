"""kernel.ssd_roofline.falconh1: the chunked state-space dual kernel against its
roofline.

As counted the kernel is compute-bound, narrowly: a layer's dispatch of 3 x 8192
slots is 0.132 TFLOP of the PUBLISHED algorithm at the PUBLISHED chunk of 128
(a slot: C Bᵀ a group 131,072, the masked scores times x 1,048,576, the chunk's
state and the state's output 2,097,152 each: 0.67 ms of the chip's arithmetic)
against 0.456 GB (xs and y once, B, C and dt once: 0.56 ms of its memory).
Operations and bytes are the family's (``bench/families/falcon_h1.py``
``ssd_flops`` / ``ssd_bytes``: whatever chunk or order the program's kernel
takes, so a kernel that multiplies less for the same answer reads higher and
none can read over 100 by repeating work); the time is the kernel's own events
(``ssd_chunked``, the ``jax.jit`` that holds the ``pallas_call`` and lays the
steps out a head a row): the convolution, the gate and the grouped norm around
it are in ``forward.share.state_space.falconh1``.  What keeps the kernel from
its roofline is how it is written (the decays' exponentials and masks on the
vector unit, four dependent products a head), not the MXU.
"""

import falconh1_scopes

KERNELS = ("ssd_chunked",)


def reduce(ctx):
    return falconh1_scopes.roofline(ctx, KERNELS, "ssd")
