"""The consensus reduction as explicit ICI collectives.

Replaces the reference's host-side tally loop (score client.rs:384-456)
with on-device communication (SURVEY §2.8): candidates are sharded over the
``dp`` axis; each shard computes its local similarity block against an
``all_gather`` of every candidate embedding, and the softmax normalizer is
a ``psum`` — all riding ICI, never the host.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P



def sharded_cosine_vote(
    embeddings: jax.Array,
    mesh: Mesh,
    temperature: float | jax.Array = 0.05,
    n_valid: int | None = None,
) -> jax.Array:
    """embeddings[N, D] (N divisible by mesh dp) -> confidence[N_valid].

    Matches ops.similarity.cosine_consensus_vote numerically; computed
    distributed: local block matmul against the all-gathered embeddings,
    mean off-diagonal similarity, global max/sum via psum for the softmax.

    ``n_valid`` is the count of real candidate rows when the caller
    already padded (the mesh serving path pads to the AOT row bucket
    before dispatch); rows at and past ``n_valid`` are masked out of the
    mean and the softmax exactly like the internal dp padding, and only
    the first ``n_valid`` confidences are returned.  ``temperature`` may
    be a traced scalar: it rides as a replicated shard_map operand, not
    a closure capture, so this reduction composes under an outer ``jit``
    (the one-dispatch embed+vote executable in models/embedder.py).
    """
    n = embeddings.shape[0] if n_valid is None else n_valid
    dp = mesh.shape["dp"]
    if embeddings.shape[0] % dp != 0:
        # pad candidates to the shard grid; padded rows masked out below
        pad = dp - embeddings.shape[0] % dp
        embeddings = jnp.pad(embeddings, ((0, pad), (0, 0)))
    np_ = embeddings.shape[0]
    temp = jnp.asarray(temperature, jnp.float32)

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P("dp", None), P()),
        out_specs=P("dp"),
        check_vma=False,
    )
    def vote(x_local, temp):
        shard = jax.lax.axis_index("dp")
        local_n = x_local.shape[0]
        # normalize locally (row-wise, no comms)
        x32 = x_local.astype(jnp.float32)
        norm = jnp.sqrt(jnp.sum(x32 * x32, axis=-1, keepdims=True))
        x_n = x32 / jnp.maximum(norm, 1e-12)
        # ICI all-gather of all candidates' normalized embeddings
        x_all = jax.lax.all_gather(x_n, "dp", tiled=True)  # [Np, D]
        sims = jnp.einsum(
            "ld,nd->ln",
            x_n,
            x_all,
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        )  # [local_n, Np]
        # global row/col ids for diagonal + padding masks
        row_ids = shard * local_n + jnp.arange(local_n)
        col_ids = jnp.arange(np_)
        valid_col = (col_ids < n)[None, :] & (
            col_ids[None, :] != row_ids[:, None]
        )
        mean_sim = jnp.sum(jnp.where(valid_col, sims, 0.0), axis=-1) / max(
            n - 1, 1
        )
        logits = mean_sim / temp
        row_valid = row_ids < n
        logits = jnp.where(row_valid, logits, -jnp.inf)
        # globally-stable softmax: psum-reduced max and sum over shards
        local_max = jnp.max(logits)
        global_max = jax.lax.pmax(local_max, "dp")
        e = jnp.where(row_valid, jnp.exp(logits - global_max), 0.0)
        denom = jax.lax.psum(jnp.sum(e), "dp")
        return e / denom

    return vote(embeddings, temp)[:n]


def sharded_tally(
    votes: jax.Array, weights: jax.Array, mesh: Mesh
) -> jax.Array:
    """votes[M, N] sharded over judges (dp), weights[M] -> confidence[N].

    Each shard tallies its local judges; the cross-judge reduction is one
    psum over ICI.  M must divide by dp.
    """
    m, n = votes.shape
    dp = mesh.shape["dp"]
    if m % dp != 0:
        pad = dp - m % dp
        votes = jnp.pad(votes, ((0, pad), (0, 0)))
        weights = jnp.pad(weights, (0, pad))

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P("dp", None), P("dp")),
        out_specs=P(),
        check_vma=False,
    )
    def tally(v_local, w_local):
        local = jnp.einsum(
            "m,mn->n",
            w_local.astype(jnp.float32),
            v_local.astype(jnp.float32),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        )
        choice_weight = jax.lax.psum(local, "dp")
        total = jnp.sum(choice_weight)
        return jnp.where(total > 0, choice_weight / total, 0.0)

    return tally(votes, weights)
