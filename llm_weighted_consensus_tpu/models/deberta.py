"""DeBERTa-style encoder + scalar reward head (reward-model re-ranking).

``scorer: rm`` replaces the cosine self-consistency vote with a trained
reward model (deberta-v3 class).  The architectural difference from BERT is
**disentangled attention**: no absolute position embeddings; instead every
layer adds content->position and position->content terms computed against a
shared relative-position embedding table:

    score(i, j) = q_c[i]·k_c[j] + q_c[i]·k_r[d(i,j)] + k_c[j]·q_r[d(i,j)]

scaled by 1/sqrt(3*head_dim), with d(i, j) the bucketed relative distance
(log-bucketed for v3 checkpoints, clamped otherwise — configs.py).  This
keeps shapes static (the relative index matrix is precomputed per seq
length) and every contraction on the MXU.

The reward head follows HF ``ContextPooler`` + 1-logit classifier: CLS
pooled state -> dense -> exact-erf GELU -> dense(1) -> scalar reward per
(prompt, candidate) sequence — numerics-pinned to
``transformers.DebertaV2Model``/``ForSequenceClassification`` in
tests/test_hf_parity.py, so real v3 RM checkpoints reproduce their
trained rewards.  ``reward_consensus_vote`` turns N candidate rewards
into a confidence distribution, slotting into the same tally as ballot
votes.
"""

from __future__ import annotations

from functools import partial
import jax
import jax.numpy as jnp

from .configs import DebertaConfig
from .layers import (
    dense as _dense,
    dense_cfg as _dense_cfg,
    dense_init as _dense_init,
    gelu_erf as _gelu_erf,
    layer_norm as _layer_norm,
    ln_init as _ln_init,
    mlp_cfg as _mlp_cfg,
)


def init_params(rng, config: DebertaConfig, dtype=jnp.float32) -> dict:
    keys = jax.random.split(rng, 6)
    h, i, k = config.hidden_size, config.intermediate_size, config.att_span

    def layer_params(layer_rng):
        ks = jax.random.split(layer_rng, 8)
        return {
            "attn_q": _dense_init(ks[0], h, h, dtype),
            "attn_k": _dense_init(ks[1], h, h, dtype),
            "attn_v": _dense_init(ks[2], h, h, dtype),
            "pos_q": _dense_init(ks[3], h, h, dtype),
            "pos_k": _dense_init(ks[4], h, h, dtype),
            "attn_out": _dense_init(ks[5], h, h, dtype),
            "attn_ln": _ln_init(h, dtype),
            "mlp_in": _dense_init(ks[6], h, i, dtype),
            "mlp_out": _dense_init(ks[7], i, h, dtype),
            "mlp_ln": _ln_init(h, dtype),
        }

    layers = jax.vmap(layer_params)(
        jax.random.split(keys[0], config.num_layers)
    )
    return {
        "token_embed": (
            jax.random.normal(keys[1], (config.vocab_size, h), jnp.float32)
            * 0.02
        ).astype(dtype),
        "embed_ln": _ln_init(h, dtype),
        # shared relative position table covering [-k, k)
        "rel_embed": (
            jax.random.normal(keys[2], (2 * k, h), jnp.float32) * 0.02
        ).astype(dtype),
        "rel_ln": _ln_init(h, dtype),
        "layers": layers,
        "head_dense": _dense_init(keys[3], h, h, dtype),
        "head_out": _dense_init(keys[4], h, 1, dtype),
    }


def _rel_index(seq: int, config: DebertaConfig) -> jax.Array:
    """[seq, seq] relative-position table indices in [0, 2*att_span).

    ``position_buckets > 0``: HF ``make_log_bucket_position`` — positions
    within ±buckets/2 index exactly, farther ones land in log-spaced
    buckets out to ``max_relative_positions`` (how every released v3
    checkpoint was trained).  Otherwise plain clamp."""
    span = config.att_span
    pos = jnp.arange(seq)
    rel = pos[:, None] - pos[None, :]
    if config.position_buckets > 0:
        mid = config.position_buckets // 2
        abs_pos = jnp.where(
            (rel < mid) & (rel > -mid), mid - 1, jnp.abs(rel)
        ).astype(jnp.float32)
        log_pos = (
            jnp.ceil(
                jnp.log(abs_pos / mid)
                / jnp.log((config.max_relative_positions - 1) / mid)
                * (mid - 1)
            )
            + mid
        )
        rel = jnp.where(
            jnp.abs(rel) <= mid,
            rel,
            (log_pos * jnp.sign(rel)).astype(rel.dtype),
        )
    return jnp.clip(rel, -span, span - 1) + span


def _disentangled_attention(x, rel, p, mask_bias, config: DebertaConfig):
    b, s, h = x.shape
    nh, hd = config.num_heads, config.head_dim
    k = config.att_span

    # the scope names are bert.py's where the work is the same, so a
    # trace reads alike for both families (bench/scope_time.py);
    # ``rel_bias`` is this family's own: the bucketed position terms
    with jax.named_scope("qkv_proj"):
        q_c = _dense_cfg(x, p["attn_q"], config).reshape(b, s, nh, hd)
        k_c = _dense_cfg(x, p["attn_k"], config).reshape(b, s, nh, hd)
        v = _dense_cfg(x, p["attn_v"], config).reshape(b, s, nh, hd)
    with jax.named_scope("rel_bias"):
        # relative projections of the shared table: [2k, nh, hd] — always
        # full precision: one tiny matmul per forward, position-sensitive
        q_r = _dense(rel, p["pos_q"]).reshape(2 * k, nh, hd)
        k_r = _dense(rel, p["pos_k"]).reshape(2 * k, nh, hd)
        rel_idx = _rel_index(s, config)  # [s, s]

    # The three disentangled score tensors store in the activation dtype
    # (f32 MXU accumulation unchanged) like every other matmul in the
    # model family — on the bf16 TPU path that halves the HBM traffic of
    # THREE [b, nh, s, s] intermediates + two bucket gathers (the same
    # r4 cut measured on bert.py's single logits tensor); the f32 parity
    # path is byte-identical.  Softmax stays f32 per the module contract.
    with jax.named_scope("rel_bias"):
        # content -> position: q_c against every bucket, then gather per
        # (i, j)
        c2p_all = jnp.einsum(
            "bqnd,rnd->bnqr", q_c, k_r, preferred_element_type=x.dtype
        )  # [b, nh, s, 2k]
        c2p = jnp.take_along_axis(
            c2p_all, rel_idx[None, None, :, :], axis=-1
        )  # [b, nh, s, s]
        # position -> content: k_c against every bucket, transposed gather
        p2c_all = jnp.einsum(
            "bknd,rnd->bnkr", k_c, q_r, preferred_element_type=x.dtype
        )  # [b, nh, s, 2k]
        p2c = jnp.take_along_axis(
            p2c_all, rel_idx.T[None, None, :, :], axis=-1
        )  # [b, nh, k_pos=s, q_pos=s] -> transpose to [b, nh, q, k]
        p2c = jnp.swapaxes(p2c, -1, -2)

    with jax.named_scope("attention"):
        # content -> content
        c2c = jnp.einsum(
            "bqnd,bknd->bnqk", q_c, k_c, preferred_element_type=x.dtype
        )
        # python-float scale + same-dtype bias keep the sum in x.dtype (an
        # f32 scalar would silently promote all three tensors back to f32)
        scale = 1.0 / float(3 * hd) ** 0.5
        logits = (c2c + c2p + p2c) * scale + mask_bias.astype(x.dtype)
        probs = jax.nn.softmax(
            logits.astype(jnp.float32), axis=-1
        ).astype(x.dtype)
        ctx = jnp.einsum(
            "bnqk,bknd->bqnd", probs, v, preferred_element_type=jnp.float32
        ).astype(x.dtype)
    with jax.named_scope("attn_out"):
        return _dense_cfg(ctx.reshape(b, s, h), p["attn_out"], config)


def encode(
    params: dict,
    input_ids: jax.Array,
    attention_mask: jax.Array,
    config: DebertaConfig,
) -> jax.Array:
    with jax.named_scope("embeddings"):
        x = params["token_embed"][input_ids]
        x = _layer_norm(x, params["embed_ln"], config.layer_norm_eps)
        rel = _layer_norm(
            params["rel_embed"], params["rel_ln"], config.layer_norm_eps
        )
    mask_bias = jnp.where(
        attention_mask[:, None, None, :] > 0, 0.0, -1e9
    ).astype(jnp.float32)

    def body(carry, layer_p):
        attn = _disentangled_attention(carry, rel, layer_p, mask_bias, config)
        with jax.named_scope("attn_ln"):
            y = _layer_norm(
                carry + attn, layer_p["attn_ln"], config.layer_norm_eps
            )
        # exact-erf GELU (layers.gelu_erf: exact for f32, A&S for bf16):
        # HF deberta-v2's hidden_act is "gelu" = erf — jax.nn.gelu's
        # default tanh approximation silently diverged here (r4 fix; the
        # head below already used approximate=False).  On the int8 path
        # mlp_cfg folds the GELU into the mlp_in kernel epilogue.
        with jax.named_scope("mlp"):
            mlp = _mlp_cfg(y, layer_p["mlp_in"], layer_p["mlp_out"], config)
        with jax.named_scope("mlp_ln"):
            return _layer_norm(
                y + mlp, layer_p["mlp_ln"], config.layer_norm_eps
            ), None

    with jax.named_scope("encoder_layers"):
        x, _ = jax.lax.scan(body, x, params["layers"])
    return x


@partial(jax.jit, static_argnames=("config",))
def reward(
    params: dict,
    input_ids: jax.Array,
    attention_mask: jax.Array,
    config: DebertaConfig,
) -> jax.Array:
    """(prompt ++ candidate) token batch -> scalar reward per row [b].

    Head = HF ``ContextPooler`` semantics (exact-erf GELU over a dense of
    the CLS state — transformers' default ``pooler_hidden_act="gelu"``)
    followed by the 1-logit classifier, so
    ``DebertaV2ForSequenceClassification`` RM checkpoints reproduce their
    trained rewards (tests/test_hf_parity.py)."""
    hidden = encode(params, input_ids, attention_mask, config)
    with jax.named_scope("head"):
        cls = hidden[:, 0, :].astype(jnp.float32)
        z = _dense(cls, params["head_dense"]).astype(jnp.float32)
        z = jax.nn.gelu(z, approximate=False)
        return _dense(z, params["head_out"]).astype(jnp.float32)[:, 0]


@partial(jax.jit, static_argnames=("temperature",))
def reward_consensus_vote(
    rewards: jax.Array, temperature: float = 1.0
) -> jax.Array:
    """rewards[N] -> confidence[N]: RM re-ranking as a consensus vote
    (drop-in for ops.similarity.cosine_consensus_vote)."""
    return jax.nn.softmax(rewards.astype(jnp.float32) / temperature)


def from_hf_weights(
    state_dict: dict, config: DebertaConfig, dtype=jnp.float32
) -> dict:
    """Map a HuggingFace DeBERTa-v2/v3 state dict into our pytree.

    Accepts ``DebertaV2ForSequenceClassification`` reward models (e.g.
    the OpenAssistant deberta-v3 RM family): the
    ``pooler.dense`` + ``classifier`` head maps onto ``head_dense`` /
    ``head_out``; encoder-only checkpoints load with a random-init head
    (fine-tune via train/).  v3 shares the content projections for the
    disentangled position attention (HF ``share_att_key=True``, so the
    checkpoint has no separate position projections) — our ``pos_q`` /
    ``pos_k`` load the content ``query_proj`` / ``key_proj`` weights,
    reproducing exactly that sharing.
    """

    def get(name):
        return jnp.asarray(state_dict[name], dtype=dtype)

    def dense(prefix):
        # torch Linear stores [out, in]; ours is [in, out]
        return {
            "kernel": get(f"{prefix}.weight").T,
            "bias": get(f"{prefix}.bias"),
        }

    def ln(prefix):
        return {"scale": get(f"{prefix}.weight"), "bias": get(f"{prefix}.bias")}

    def maybe_head(dense_prefix, fallback_shape_rng):
        if f"{dense_prefix}.weight" in state_dict:
            return dense(dense_prefix)
        rng, in_dim, out_dim = fallback_shape_rng
        from .layers import dense_init

        return dense_init(rng, in_dim, out_dim, dtype)

    layers = []
    for i in range(config.num_layers):
        base = f"encoder.layer.{i}"
        q = dense(f"{base}.attention.self.query_proj")
        k = dense(f"{base}.attention.self.key_proj")
        layers.append(
            {
                "attn_q": q,
                "attn_k": k,
                "attn_v": dense(f"{base}.attention.self.value_proj"),
                # share_att_key: position attention reuses content q/k
                "pos_q": q,
                "pos_k": k,
                "attn_out": dense(f"{base}.attention.output.dense"),
                "attn_ln": ln(f"{base}.attention.output.LayerNorm"),
                "mlp_in": dense(f"{base}.intermediate.dense"),
                "mlp_out": dense(f"{base}.output.dense"),
                "mlp_ln": ln(f"{base}.output.LayerNorm"),
            }
        )
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *layers)

    h = config.hidden_size
    rngs = jax.random.split(jax.random.PRNGKey(0), 2)
    rel = get("encoder.rel_embeddings.weight")
    want_rows = 2 * config.att_span
    if rel.shape[0] != want_rows:
        raise ValueError(
            f"rel_embeddings has {rel.shape[0]} rows; config expects "
            f"{want_rows} (2 x att_span) — set position_buckets="
            f"{rel.shape[0] // 2} (v3 log-bucketed checkpoints) or "
            f"max_relative_positions={rel.shape[0] // 2} with "
            "position_buckets=0 (clamp scheme)"
        )
    return {
        "token_embed": get("embeddings.word_embeddings.weight"),
        "embed_ln": ln("embeddings.LayerNorm"),
        "rel_embed": rel,
        "rel_ln": ln("encoder.LayerNorm"),
        "layers": stacked,
        "head_dense": maybe_head("pooler.dense", (rngs[0], h, h)),
        "head_out": maybe_head("classifier", (rngs[1], h, 1)),
    }
