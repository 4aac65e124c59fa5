"""Device-mesh scale-out: shardings, collectives, batch parallelism.

The reference's only concurrency is single-process async IO (SURVEY §2.8);
its "distributed backend" is HTTPS.  Here distribution is first-class and
TPU-shaped:

* ``mesh``      — mesh construction over dp/tp[/sp] axes (ICI within a
  slice, DCN across hosts comes free with jax.distributed process groups);
* ``sharding``  — NamedSharding rules: batch over ``dp``, optional tensor
  parallelism of attention heads + MLP over ``tp`` (bge-class models need
  only DP — SURVEY §2.8 notes this explicitly — but TP is implemented and
  dry-run tested so larger encoders drop in); ``shard_embedder_mesh`` is
  the one way an embedder goes onto a mesh (``MESH_ENABLED``);
* ``collectives`` — the consensus reduction as explicit ICI collectives:
  candidates sharded over the mesh, ``all_gather`` for pairwise cosine,
  ``psum`` for the global softmax — replacing the reference's host-side
  tally loop with on-device communication;
* ``batch``     — archive batch re-scoring sharded over ``dp``;
* ``ring``      — sequence/context parallelism: blockwise ring attention
  over an ``sp`` axis (ppermute k/v rotation + online softmax), making
  long-context encoders first-class — per-device attention memory is
  O(s^2/sp^2);
* ``dist``      — multi-host (DCN) process-group initialization.

No pipeline parallelism (a 12-24 layer encoder has no use for stages).
A judge's experts live on one chip.  models/qwen3_next.py serves the
share of a wider router's experts that its checkpoint names and returns the
partial sum; the exchange of tokens and sums over the chips that would hold
the other shares is not built: no expert parallelism yet.
"""

from .dist import maybe_initialize_distributed  # noqa: F401
from .mesh import make_mesh  # noqa: F401
from . import batch, collectives, ring, sharding  # noqa: F401
