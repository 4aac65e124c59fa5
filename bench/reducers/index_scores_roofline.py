"""kernel.index_scores_roofline.glm5: the indexer's score kernel against its
roofline.

Compute-bound: a layer's scores over a panel's causal pairs are 32 heads x 128
dims x 2 a pair, 0.82 TFLOP a dispatch of 3 x 8192 slots (4.2 ms of the chip's
arithmetic) against 0.2 GB of index queries and 0.4 GB of float32 scores
written (0.75 ms of its memory).  Operations and bytes are the family's
(``bench/families/glm_moe_dsa.py``: the CAUSAL pairs, the layers that own an
indexer); the time is the kernel's own events (``index_scores``, the
``jax.jit`` that holds the ``pallas_call``): the indexer's three products
before it are in ``forward.share.indexer.glm5``.
"""

import glm5_scopes

KERNELS = ("index_scores",)


def reduce(ctx):
    return glm5_scopes.roofline(ctx, KERNELS, "index_scores")
