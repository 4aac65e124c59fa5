"""Ring attention: sequence/context parallelism over an ``sp`` mesh axis.

The reference has no long-context machinery (SURVEY §5: "no ring
attention/CP/Ulysses"); for the TPU framework long-context is first-class.
The design follows blockwise ring attention (Liu et al.): the sequence axis
is sharded over the mesh, every device holds one q block permanently, and
k/v blocks rotate around the ring via ``lax.ppermute`` while an
online-softmax accumulator (running max, running denominator, weighted sum)
folds each visiting block in.  After ``sp`` steps every q block has
attended to the full sequence, no device ever materializes more than a
[b, s/sp, s/sp] score tile, and each permute's communication overlaps the
next block's compute (XLA schedules the ppermute DMA concurrently).

Memory: full attention needs O(s^2) scores; ring needs O(s^2/sp^2) per
device — the enabler for 8k-32k-token encoder contexts on fixed VMEM/HBM.

Exact numerics: online softmax is algebraically identical to one softmax
over the full row (up to f32 reassociation); parity with the einsum path
is asserted in tests/test_ring.py.

Layering: ``ring_attention`` is a pure collective, usable inside any
``shard_map`` with a named axis; ``ring_encode``/``ring_embed`` wrap the
whole BERT forward with sequence sharding (positions offset per shard,
layers scanned as usual).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

NEG_INF = -1e9


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    bias: jax.Array,
    scale: float,
    axis_name: str,
) -> jax.Array:
    """Blockwise ring attention (call inside shard_map over ``axis_name``).

    q/k/v: [b, s_local, nh, hd] — this device's sequence shard;
    bias:   [b, s_local] additive key-side padding bias for the LOCAL keys
    (0 real, -1e9 pad), the same convention as ops.attention.

    Returns ctx[b, s_local, nh, hd] equal to full softmax(QK^T*scale+bias)V
    over the GLOBAL sequence.
    """
    sp = jax.lax.psum(1, axis_name)
    b, s_loc, nh, hd = q.shape
    qf = q.astype(jnp.float32)

    # online-softmax state per q position/head
    acc = jnp.zeros((b, s_loc, nh, hd), jnp.float32)
    denom = jnp.zeros((b, s_loc, nh), jnp.float32)
    run_max = jnp.full((b, s_loc, nh), NEG_INF, jnp.float32)

    perm = [(i, (i + 1) % sp) for i in range(sp)]  # ring: shard i -> i+1

    k_cur, v_cur, bias_cur = k, v, bias
    for step in range(sp):
        kf = k_cur.astype(jnp.float32)
        vf = v_cur.astype(jnp.float32)
        # [b, q_loc, k_loc, nh]
        logits = (
            jnp.einsum("bqnd,bknd->bqkn", qf, kf,
                       preferred_element_type=jnp.float32)
            * scale
        )
        logits = logits + bias_cur[:, None, :, None].astype(jnp.float32)
        blk_max = jnp.max(logits, axis=2)  # [b, q_loc, nh]
        new_max = jnp.maximum(run_max, blk_max)
        correction = jnp.exp(run_max - new_max)
        p = jnp.exp(logits - new_max[:, :, None, :])  # [b, q, k, nh]
        acc = acc * correction[:, :, :, None] + jnp.einsum(
            "bqkn,bknd->bqnd", p, vf, preferred_element_type=jnp.float32
        )
        denom = denom * correction + jnp.sum(p, axis=2)
        run_max = new_max
        if step < sp - 1:
            # rotate k/v/bias one step around the ring; the DMA overlaps
            # the next iteration's einsums.  Skipped on the last step —
            # collectives carry channel ids and are not reliably DCE'd,
            # so the wasted rotation would cost real ICI traffic.
            k_cur = jax.lax.ppermute(k_cur, axis_name, perm)
            v_cur = jax.lax.ppermute(v_cur, axis_name, perm)
            bias_cur = jax.lax.ppermute(bias_cur, axis_name, perm)

    ctx = acc / denom[:, :, :, None]
    return ctx.astype(q.dtype)


# ---------------------------------------------------------------------------
# Sequence-sharded encoder forward
# ---------------------------------------------------------------------------


def _replicated_like(tree):
    return jax.tree_util.tree_map(lambda _: P(), tree)


def ring_encode(
    params: dict,
    input_ids: jax.Array,
    attention_mask: jax.Array,
    config,
    mesh: Mesh,
    sp_axis: str = "sp",
    dp_axis=None,
) -> jax.Array:
    """BERT forward with the SEQUENCE axis sharded over ``sp_axis``:
    ids/mask[b, s] -> hidden[b, s, h], s sharded, params replicated.
    ``dp_axis`` additionally shards the batch axis (dp x sp mesh).

    Inside the shard each device embeds its own sequence slice (positions
    offset by shard index) and attention runs as a ring.  The global
    sequence length must divide the sp axis size.
    """
    from ..models import bert

    if config.attention_impl != "ring":
        raise ValueError(
            "ring_encode needs a config with attention_impl='ring' "
            f"(got {config.attention_impl!r})"
        )
    s = input_ids.shape[1]
    sp = mesh.shape[sp_axis]
    if s % sp != 0:
        raise ValueError(f"sequence {s} must divide sp={sp}")
    from ..models.configs import usable_positions

    if s > usable_positions(config):
        # jnp gathers clamp out-of-range indices, which would silently
        # reuse the last position embedding instead of failing
        raise ValueError(
            f"sequence {s} exceeds the position table's usable window "
            f"({usable_positions(config)}); long contexts need a config "
            "with a matching position table"
        )

    seq_spec = P(dp_axis, sp_axis)

    def local_forward(params, ids, mask):
        s_loc = ids.shape[1]
        offset = jax.lax.axis_index(sp_axis) * s_loc
        return bert.encode(
            params, ids, mask, config, position_offset=offset
        )

    return jax.shard_map(
        local_forward,
        mesh=mesh,
        in_specs=(_replicated_like(params), seq_spec, seq_spec),
        out_specs=P(dp_axis, sp_axis, None),
        check_vma=False,
    )(params, input_ids, attention_mask)


@partial(
    jax.jit,
    static_argnames=(
        "config", "mesh", "sp_axis", "dp_axis", "pooling", "normalize"
    ),
)
def _ring_embed_jit(
    params, ids, mask, config, mesh, sp_axis, dp_axis, pooling, normalize
):
    from ..models import bert

    hidden = ring_encode(params, ids, mask, config, mesh, sp_axis, dp_axis)
    return bert.pool(hidden, mask, pooling, normalize)


@partial(
    jax.jit,
    static_argnames=(
        "n", "config", "mesh", "sp_axis", "dp_axis", "pooling"
    ),
)
def _ring_embed_and_vote(
    params, ids, mask, temperature, n, config, mesh, sp_axis, dp_axis, pooling
):
    """Ring-dispatch twin of ``_mesh_embed_and_vote`` (models/embedder.py):
    sequence-sharded encoder forward + pooling + the dp-sharded consensus
    reduction under ONE jit, so a long-context scored request pays one
    dispatch.  The pooled embeddings leave the ring shard_map sharded
    (batch over dp, the contracted seq axis reduced over sp by GSPMD);
    the vote's shard_map re-enters over dp with sp/tp implicitly
    replicated.  Temperature is always traced — same no-recompile
    contract as the dense mesh vote."""
    from ..models import bert
    from .collectives import sharded_cosine_vote

    hidden = ring_encode(params, ids, mask, config, mesh, sp_axis, dp_axis)
    emb = bert.pool(hidden, mask, pooling, True)
    with jax.named_scope("consensus_vote"):
        return sharded_cosine_vote(emb, mesh, temperature, n_valid=n)


def ring_embed(
    params: dict,
    input_ids: jax.Array,
    attention_mask: jax.Array,
    config,
    mesh: Mesh,
    sp_axis: str = "sp",
    dp_axis=None,
    pooling: str = "cls",
    normalize: bool = True,
) -> jax.Array:
    """Sequence-parallel twin of ``bert.embed``: pooled embeddings for
    long-context inputs, attention memory O(s^2/sp^2) per device."""
    in_sharding = NamedSharding(mesh, P(dp_axis, sp_axis))
    with mesh:
        return _ring_embed_jit(
            params,
            jax.device_put(input_ids, in_sharding),
            jax.device_put(attention_mask, in_sharding),
            config,
            mesh,
            sp_axis,
            dp_axis,
            pooling,
            normalize,
        )
