"""Embedding cosine math: pairwise similarity, consensus votes, top-k.

Pure TPU territory (SURVEY §3.5 item 4): the reference keeps its trained
weight path behind the ``weight::Fetcher`` seam and does no tensor math;
here the embedding consensus becomes real device kernels:

* ``cosine_consensus_vote`` — self-consistency scoring:
  each candidate's confidence is the softmax of its mean cosine similarity
  to all other candidates (centroid agreement);
* ``top_k_similar`` — training-table lookup: nearest archived prompts per
  judge (trained weights);
* all matmuls bf16-in/f32-accumulate for the MXU.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


@jax.jit
def l2_normalize(x: jax.Array, eps: float = 1e-12) -> jax.Array:
    x = x.astype(jnp.float32)
    norm = jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True))
    return x / jnp.maximum(norm, eps)


@jax.jit
def cosine_similarity(a: jax.Array, b: jax.Array) -> jax.Array:
    """a[B, D], b[C, D] -> [B, C] cosine similarity (one MXU contraction)."""
    return jnp.einsum(
        "bd,cd->bc",
        l2_normalize(a),
        l2_normalize(b),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )


@jax.jit
def pairwise_cosine(x: jax.Array) -> jax.Array:
    """x[N, D] -> [N, N] full pairwise cosine similarity."""
    n = l2_normalize(x)
    return jnp.einsum("nd,md->nm", n, n, preferred_element_type=jnp.float32, precision=jax.lax.Precision.HIGHEST)


def dyn_cosine_vote(embeddings: jax.Array, temperature) -> jax.Array:
    """``cosine_consensus_vote`` numerics with a TRACED temperature and
    optional leading batch dims: embeddings[..., N, D] ->
    confidence[..., N].

    The ONE implementation of the vote math — the jitted static-
    temperature wrapper below and the serving batched paths
    (models/embedder.py) all reduce to this.  Temperature must be traced
    on user-facing paths: a jit-static temperature would compile a fresh
    program per distinct user value (a recompile-DoS through
    POST /consensus).
    """
    nrm = l2_normalize(embeddings)
    sims = jnp.einsum(
        "...nd,...md->...nm",
        nrm,
        nrm,
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )
    n = sims.shape[-1]
    off_diag = sims - jnp.eye(n, dtype=sims.dtype) * sims
    mean_sim = jnp.sum(off_diag, axis=-1) / jnp.maximum(n - 1, 1)
    return jax.nn.softmax(mean_sim / temperature, axis=-1)


@partial(jax.jit, static_argnames=("temperature",))
def cosine_consensus_vote(
    embeddings: jax.Array, temperature: float = 0.05
) -> jax.Array:
    """embeddings[N, D] -> confidence[N]: softmax over mean off-diagonal
    cosine similarity (the embedding self-consistency vote).

    Candidates that agree with the cluster get high confidence; outliers
    get low.  ``temperature`` sharpens the softmax (0.05 suits bge-class
    cosine ranges).
    """
    return dyn_cosine_vote(embeddings, temperature)


@jax.jit
def masked_cosine_vote(
    embeddings: jax.Array, valid: jax.Array, temperature: float = 0.05
) -> jax.Array:
    """``cosine_consensus_vote`` over a FIXED-capacity buffer with a
    dynamic valid mask: embeddings[CAP, D], valid[CAP] (1.0 = live row)
    -> confidence[CAP], softmax over valid rows only (invalid rows 0).

    The streaming-consensus state keeps one device-resident buffer and
    flips mask bits as candidates finish, so the jit specializes per
    capacity bucket instead of per candidate count — and the embed +
    revote fuse into ONE dispatch per update (one link round-trip).
    """
    valid = valid.astype(jnp.float32)
    sims = pairwise_cosine(embeddings).astype(jnp.float32)
    pair = valid[:, None] * valid[None, :]
    cap = sims.shape[0]
    off_diag = pair * (1.0 - jnp.eye(cap, dtype=sims.dtype))
    n_valid = jnp.sum(valid)
    mean_sim = jnp.sum(sims * off_diag, axis=-1) / jnp.maximum(
        n_valid - 1.0, 1.0
    )
    logits = jnp.where(valid > 0, mean_sim / temperature, -1e9)
    conf = jax.nn.softmax(logits)
    return jnp.where(valid > 0, conf, 0.0)


@partial(jax.jit, static_argnames=("k",))
def top_k_similar(table: jax.Array, queries: jax.Array, k: int):
    """table[T, D], queries[B, D] -> (scores[B, k], indices[B, k]).

    The training-table nearest-row lookup: embed the prompt, find its k
    closest archived prompts per judge table.
    """
    sims = cosine_similarity(queries, table)
    return jax.lax.top_k(sims, k)


@partial(jax.jit, static_argnames=("k",))
def training_table_weights_batched(
    tables: jax.Array,
    row_mask: jax.Array,
    table_scores: jax.Array,
    query: jax.Array,
    min_weight: jax.Array,
    max_weight: jax.Array,
    k: int,
) -> jax.Array:
    """Per-judge trained weights with per-judge tables, ONE dispatch.

    tables[J, T, D] judge-specific prompt embeddings padded to a common row
    count; row_mask[J, T] 1 for real rows; table_scores[J, T] historical
    accuracy; query[D]; min/max_weight[J].  Returns weights[J].  Padded
    rows are masked out of the top-k and of the attention softmax, so a
    judge with fewer than ``k`` real rows attends only to its real rows
    (matching the per-judge ``k=min(top, rows)`` of the loop form).
    """
    j, t, d = tables.shape
    nq = l2_normalize(query)[None, :]  # [1, D]
    nt = l2_normalize(tables.reshape(j * t, d)).reshape(j, t, d)
    sims = jnp.einsum(
        "jtd,od->jt", nt, nq, preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )
    valid = row_mask > 0
    sims = jnp.where(valid, sims, -jnp.inf)
    k_eff = min(k, t)
    top_scores, top_idx = jax.lax.top_k(sims, k_eff)  # [J, k]
    top_valid = jnp.isfinite(top_scores)
    # masked softmax over each judge's valid top rows
    logits = jnp.where(top_valid, top_scores / 0.05, -jnp.inf)
    mx = jnp.max(
        jnp.where(top_valid, logits, -1e30), axis=-1, keepdims=True
    )
    e = jnp.where(top_valid, jnp.exp(logits - mx), 0.0)
    attn = e / jnp.maximum(jnp.sum(e, axis=-1, keepdims=True), 1e-30)
    gathered = jnp.take_along_axis(
        table_scores.astype(jnp.float32), top_idx, axis=-1
    )  # [J, k]
    quality = jnp.sum(attn * gathered, axis=-1)  # [J]
    lo = min_weight.astype(jnp.float32)
    hi = max_weight.astype(jnp.float32)
    return lo + (hi - lo) * quality


@partial(jax.jit, static_argnames=("k",))
def training_table_weights(
    table: jax.Array,
    table_scores: jax.Array,
    queries: jax.Array,
    min_weight: jax.Array,
    max_weight: jax.Array,
    k: int,
) -> jax.Array:
    """Trained per-judge weights from a training table.

    table[T, D] archived prompt embeddings; table_scores[J, T] per-judge
    historical accuracy in [0, 1]; queries[B, D] prompt embeddings;
    min/max_weight[J] per-judge bounds.  Returns weights[B, J]:
    similarity-weighted mean of the top-k rows' scores, linearly
    interpolated into [min_weight, max_weight].
    """
    scores, idx = top_k_similar(table, queries, k)  # [B,k] both
    # softmax over similarity -> attention over the k nearest rows
    attn = jax.nn.softmax(scores / 0.05, axis=-1)  # [B, k]
    per_judge = table_scores.astype(jnp.float32)[:, idx]  # [J, B, k]
    quality = jnp.einsum(
        "bk,jbk->bj", attn, per_judge, preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST
    )  # [B, J] in [0, 1]
    lo = min_weight.astype(jnp.float32)[None, :]
    hi = max_weight.astype(jnp.float32)[None, :]
    return lo + (hi - lo) * quality
