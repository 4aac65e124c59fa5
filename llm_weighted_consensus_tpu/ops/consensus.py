"""Weighted consensus tally on device.

Device twin of the exact-Decimal host tally (reference score
client.rs:384-456, SURVEY §3.5 hot loop #3):

    choice_weight[n] = sum_m votes[m, n] * weights[m]
    confidence[n]    = choice_weight[n] / sum(choice_weight)   (0 if sum==0)
    judge_confidence[m] = sum_n votes[m, n] * confidence[n]

Shapes are static; failed judges are represented by a zero ``vote_mask``
row (SURVEY §5: a failed shard masks a mesh slot — vote row zeroed, weight
renormalized — instead of aborting the batch).  All functions accept a
leading batch dimension via vmap and are safe under pjit/shard_map: the
reductions are plain sums XLA turns into psums over sharded axes.

Tolerance contract vs the Decimal host path: f32 accumulation, votes sum to
1 +- 1e-6 (tests/test_ops.py).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


@jax.jit
def tally(votes: jax.Array, weights: jax.Array, vote_mask=None):
    """votes[M, N] (rows sum to 1 or are zero), weights[M] ->
    (choice_weight[N], confidence[N]).

    ``vote_mask[M]`` zeroes failed judges (1.0 = counted).
    """
    votes = votes.astype(jnp.float32)
    weights = weights.astype(jnp.float32)
    if vote_mask is not None:
        weights = weights * vote_mask.astype(jnp.float32)
    # MXU-friendly: a single [1,M]x[M,N] contraction
    choice_weight = jnp.einsum(
        "m,mn->n", weights, votes, preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST
    )
    total = jnp.sum(choice_weight)
    confidence = jnp.where(total > 0, choice_weight / total, 0.0)
    return choice_weight, confidence


@jax.jit
def judge_confidence(votes: jax.Array, confidence: jax.Array) -> jax.Array:
    """Per-judge confidence: how much the consensus agrees with each judge
    (client.rs:438-449): votes[M, N] x confidence[N] -> [M]."""
    return jnp.einsum(
        "mn,n->m",
        votes.astype(jnp.float32),
        confidence.astype(jnp.float32),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )


# Batched forms for archive re-scoring: one pjit/vmap
# over a [B, M, N] vote tensor re-scores B archived requests at once.
_tally_batch = jax.jit(jax.vmap(tally, in_axes=(0, 0, 0)))
judge_confidence_batch = jax.jit(jax.vmap(judge_confidence))


def tally_batch(votes: jax.Array, weights: jax.Array, vote_mask=None):
    """Batched tally; ``vote_mask`` optional like :func:`tally`."""
    if vote_mask is None:
        vote_mask = jnp.ones(weights.shape, dtype=jnp.float32)
    return _tally_batch(votes, weights, vote_mask)


@jax.jit
def incremental_tally(
    running_weight: jax.Array,
    new_vote: jax.Array,
    new_weight: jax.Array,
):
    """Streaming update: fold one completed judge vote into the running
    tally (incremental on-device consensus).

    Recomputes confidence after each completed vote without re-reducing the
    full vote matrix: O(N) per update.
    """
    running_weight = running_weight + new_vote.astype(jnp.float32) * new_weight
    total = jnp.sum(running_weight)
    confidence = jnp.where(total > 0, running_weight / total, 0.0)
    return running_weight, confidence


def all_failed(vote_mask: jax.Array) -> jax.Array:
    """AllVotesFailed predicate on device: no judge produced a vote."""
    return jnp.sum(vote_mask.astype(jnp.float32)) == 0
