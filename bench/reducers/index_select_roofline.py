"""kernel.index_select_roofline.glm5: the kernel that chooses each query's keys
against its MEMORY roofline.

Choosing multiplies nothing: its least time is a float32 score read and one
byte of the choice written a causal pair (0.5 GB a layer a dispatch of 3 x
8192 slots, 0.6 ms); what the kernel takes beyond that is its passes over a
row block in VMEM, a bit of the threshold a pass.  Bytes are the family's
(``index_select_bytes``); the time is the kernel's own events
(``index_select``).
"""

import glm5_scopes

KERNELS = ("index_select",)


def reduce(ctx):
    return glm5_scopes.roofline(ctx, KERNELS, "index_select")
