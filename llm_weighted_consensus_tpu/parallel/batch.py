"""Archive batch re-scoring over the mesh.

The checkpoint/resume analog of the reference is its completions archive
(SURVEY §5); re-scoring 10k archived score requests is a single dp-sharded
batched tally — the whole archive crosses the PJRT boundary once.
"""

from __future__ import annotations

from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops import consensus


def rescore_batch(
    votes: np.ndarray,
    weights: np.ndarray,
    vote_mask: Optional[np.ndarray] = None,
    mesh: Optional[Mesh] = None,
):
    """votes[B, M, N], weights[B, M] -> (choice_weight[B, N], conf[B, N]).

    With a mesh, B shards over EVERY mesh axis (pad to a multiple);
    without, runs on the default device.  The per-request tallies are
    independent — embarrassingly parallel over B — so any mesh shape
    (dp×tp serving, dp×sp sequence-parallel serving) flattens into one
    batch axis and the only comms are the initial shard placement.
    """
    b = votes.shape[0]
    if vote_mask is None:
        vote_mask = np.ones(weights.shape, dtype=np.float32)
    if mesh is None:
        return consensus.tally_batch(
            jnp.asarray(votes), jnp.asarray(weights), jnp.asarray(vote_mask)
        )
    axes = tuple(mesh.axis_names)
    n_dev = int(np.prod([mesh.shape[a] for a in axes]))
    pad = (-b) % n_dev
    if pad:
        votes = np.pad(votes, ((0, pad), (0, 0), (0, 0)))
        weights = np.pad(weights, ((0, pad), (0, 0)))
        vote_mask = np.pad(vote_mask, ((0, pad), (0, 0)))
    sharding = NamedSharding(mesh, P(axes))
    vs = jax.device_put(jnp.asarray(votes), sharding)
    ws = jax.device_put(jnp.asarray(weights), sharding)
    ms = jax.device_put(jnp.asarray(vote_mask), sharding)
    cw, conf = consensus.tally_batch(vs, ws, ms)
    return cw[:b], conf[:b]
