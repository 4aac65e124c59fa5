"""Functional JAX BERT encoder (BGE-class).

TPU-first design decisions (SURVEY §2.8 note: pure DP suffices for
bge-small/large — no TP needed; the batch axis is the sharded axis):

* params are a plain nested-dict pytree — shard/checkpoint/donate freely;
* all matmuls run in the param dtype (bf16 on TPU) with f32 accumulation on
  the MXU (``preferred_element_type``); layernorm and softmax always f32;
* static shapes only: (batch, seq) fixed per jit specialization, attention
  mask handles padding — no data-dependent control flow;
* one fused forward: embeddings -> N transformer layers (lax.scan over
  stacked layer params so XLA compiles ONE layer body regardless of depth)
  -> pooled embedding.

Weight layout matches HuggingFace BERT so real bge checkpoints load via
``from_hf_weights`` when available offline; random init otherwise.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from .configs import BertConfig
from .layers import (
    dense as _dense,
    dense_cfg as _dense_cfg,
    dense_init as _dense_init,
    gelu_erf as _gelu_erf,
    layer_norm as _layer_norm,
    ln_init as _ln_init,
    mlp_cfg as _mlp_cfg,
)


def init_params(rng: jax.Array, config: BertConfig, dtype=jnp.float32) -> dict:
    """Random-init parameters; layer params are stacked on a leading axis
    for the scanned layer body."""
    keys = jax.random.split(rng, 8)
    h, i = config.hidden_size, config.intermediate_size

    def layer_params(layer_rng):
        ks = jax.random.split(layer_rng, 6)
        return {
            "attn_q": _dense_init(ks[0], h, h, dtype),
            "attn_k": _dense_init(ks[1], h, h, dtype),
            "attn_v": _dense_init(ks[2], h, h, dtype),
            "attn_out": _dense_init(ks[3], h, h, dtype),
            "attn_ln": _ln_init(h, dtype),
            "mlp_in": _dense_init(ks[4], h, i, dtype),
            "mlp_out": _dense_init(ks[5], i, h, dtype),
            "mlp_ln": _ln_init(h, dtype),
        }

    layer_keys = jax.random.split(keys[0], config.num_layers)
    layers = jax.vmap(layer_params)(layer_keys)

    return {
        "token_embed": (
            jax.random.normal(
                keys[1], (config.vocab_size, h), jnp.float32
            )
            * 0.02
        ).astype(dtype),
        "position_embed": (
            jax.random.normal(
                keys[2], (config.max_position_embeddings, h), jnp.float32
            )
            * 0.02
        ).astype(dtype),
        "type_embed": (
            jax.random.normal(keys[3], (config.type_vocab_size, h), jnp.float32)
            * 0.02
        ).astype(dtype),
        "embed_ln": _ln_init(h, dtype),
        "layers": layers,
    }


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _attention(x, p, mask_bias, config: BertConfig):
    b, s, h = x.shape
    nh, hd = config.num_heads, config.head_dim
    fused = _use_fused_attention(config, b, s, hd, x.dtype)

    def heads(t):
        # ring and einsum work head by head; the kernel reads the
        # projections' own [b, s, h] and writes what attn_out reads
        return t if fused else t.reshape(b, s, nh, hd)

    with jax.named_scope("qkv_proj"):
        q = heads(_dense_cfg(x, p["attn_q"], config))
        k = heads(_dense_cfg(x, p["attn_k"], config))
        v = heads(_dense_cfg(x, p["attn_v"], config))
    scale = 1.0 / float(hd) ** 0.5
    if config.attention_impl == "ring":
        # sequence-parallel ring attention: only valid inside a shard_map
        # over config.ring_axis (parallel/ring.py::ring_encode sets it up)
        from ..parallel.ring import ring_attention

        with jax.named_scope("ring_attention"):
            ctx = ring_attention(
                q, k, v, mask_bias[:, 0, 0, :], scale, config.ring_axis
            )
    elif fused:
        from ..ops.attention import best_heads_per_step, fused_attention_tiled

        # forced mode may arrive with best==0 (caller takes the
        # VMEM responsibility); the kernel runs its one-row step then
        kk = best_heads_per_step(b, s, nh, hd, x.dtype.itemsize)
        with jax.named_scope("fused_attention"):
            # mask_bias is [b, 1, 1, s]; the kernel wants the [b, s] key bias
            ctx = fused_attention_tiled(
                q, k, v, mask_bias[:, 0, 0, :], scale, nh,
                heads_per_step=kk,
            )
    else:
        with jax.named_scope("einsum_attention"):
            # [b, nh, s, s] logits: f32 accumulation on the MXU, stored in
            # the activation dtype like every other matmul in this module
            # (bf16 storage halves the attention HBM traffic — the one
            # materialized intermediate XLA cannot fuse away); softmax
            # itself stays f32 per the module contract
            logits = (
                jnp.einsum(
                    "bqnd,bknd->bnqk", q, k,
                    preferred_element_type=x.dtype,
                )
                * scale
            )
            # [b, 1, 1, s] key-padding bias, broadcast over heads and
            # queries
            logits = logits + mask_bias.astype(x.dtype)
            probs = jax.nn.softmax(
                logits.astype(jnp.float32), axis=-1
            ).astype(x.dtype)
            ctx = jnp.einsum(
                "bnqk,bknd->bqnd", probs, v,
                preferred_element_type=jnp.float32,
            ).astype(x.dtype)
    with jax.named_scope("attn_out"):
        return _dense_cfg(ctx.reshape(b, s, h), p["attn_out"], config)


def _use_fused_attention(
    config: BertConfig, b: int, s: int, hd: int, dtype
) -> bool:
    from ..ops.attention import attention_fits, best_heads_per_step

    impl = config.attention_impl
    if impl in ("einsum", "ring"):
        return False
    if impl == "fused":
        # forced: the caller takes responsibility for the VMEM budget
        # (Mosaic fails loudly if one (s, s) tile cannot fit)
        return True
    if not attention_fits(s, hd):
        return False
    if best_heads_per_step(b, s, config.num_heads, hd, dtype.itemsize) < 1:
        # the kernel's own cost model says no tile fits (e.g. f32
        # activations at s=1024): einsum, not a thrashing kernel
        return False
    # "auto": the kernel from s=512 on a TPU, where the [b, nh, s, s]
    # intermediates of the einsum path dominate.  The kernel reads and
    # writes the encoder's [b, s, h] and pays no transposes (PR 25: one
    # layer's attention at 64 x 512 in 0.94 ms against einsum's 3.06 on
    # a v5e); the crossover itself is older than that layout and is an
    # open question of PERF.md, with what the chip says under 512.
    return jax.default_backend() == "tpu" and s >= 512


def _layer(x, p, mask_bias, config: BertConfig):
    attn = _attention(x, p, mask_bias, config)
    with jax.named_scope("attn_ln"):
        x = _layer_norm(x + attn, p["attn_ln"], config.layer_norm_eps)
    # GELU fuses into the mlp_in epilogue on the int8 path (layers.mlp_cfg)
    with jax.named_scope("mlp"):
        mlp = _mlp_cfg(x, p["mlp_in"], p["mlp_out"], config)
    with jax.named_scope("mlp_ln"):
        return _layer_norm(x + mlp, p["mlp_ln"], config.layer_norm_eps)


def encode(
    params: dict,
    input_ids: jax.Array,
    attention_mask: jax.Array,
    config: BertConfig,
    token_type_ids: Optional[jax.Array] = None,
    position_offset=0,
) -> jax.Array:
    """input_ids[b, s], attention_mask[b, s] -> hidden[b, s, h].

    ``position_offset`` shifts the position embeddings — used by the
    sequence-parallel forward (parallel/ring.py) where each shard holds a
    slice of the global sequence."""
    from .configs import position_base

    b, s = input_ids.shape
    base = position_base(config)
    if (
        isinstance(position_offset, int)
        and base + s + position_offset > config.max_position_embeddings
    ):
        # gathers clamp out-of-range indices — fail loudly instead of
        # silently reusing the last position embedding
        raise ValueError(
            f"sequence {s} (+offset {position_offset}, position base "
            f"{base}) exceeds max_position_embeddings="
            f"{config.max_position_embeddings}"
        )
    with jax.named_scope("embeddings"):
        x = params["token_embed"][input_ids]
        # left-aligned masks make roberta's cumsum positions an arange
        # with a base offset (pad positions get wrong embeddings but their
        # hidden states are masked out of attention and pooling)
        pos_index = (jnp.arange(s) + position_offset + base)[None, :]
        x = x + params["position_embed"][pos_index]
        if token_type_ids is None:
            token_type_ids = jnp.zeros_like(input_ids)
        x = x + params["type_embed"][token_type_ids]
        x = _layer_norm(x, params["embed_ln"], config.layer_norm_eps)

    mask_bias = jnp.where(
        attention_mask[:, None, None, :] > 0, 0.0, -1e9
    ).astype(jnp.float32)

    # scan over stacked layers: ONE compiled layer body for any depth
    def body(carry, layer_p):
        return _layer(carry, layer_p, mask_bias, config), None

    with jax.named_scope("encoder_layers"):
        x, _ = jax.lax.scan(body, x, params["layers"])
    return x


def pool(
    hidden: jax.Array,
    attention_mask: jax.Array,
    pooling: str = "cls",
    normalize: bool = True,
) -> jax.Array:
    """hidden[b, s, h] -> embedding[b, h] (bge uses CLS + l2-normalize)."""
    with jax.named_scope("pool"):
        if pooling == "cls":
            emb = hidden[:, 0, :]
        elif pooling == "mean":
            # f32 reductions regardless of activation dtype (module contract):
            # bf16 cannot even represent token counts > 256 exactly
            mask = attention_mask[:, :, None].astype(jnp.float32)
            emb = jnp.sum(hidden.astype(jnp.float32) * mask, axis=1) / jnp.maximum(
                jnp.sum(mask, axis=1), 1
            )
        else:
            raise ValueError(f"unknown pooling {pooling!r}")
        emb = emb.astype(jnp.float32)
        if normalize:
            norm = jnp.sqrt(jnp.sum(emb * emb, axis=-1, keepdims=True))
            emb = emb / jnp.maximum(norm, 1e-12)
        return emb


@partial(jax.jit, static_argnames=("config", "pooling", "normalize"))
def embed(
    params: dict,
    input_ids: jax.Array,
    attention_mask: jax.Array,
    config: BertConfig,
    pooling: str = "cls",
    normalize: bool = True,
) -> jax.Array:
    """The jitted end-to-end embedding forward: ids -> pooled vectors."""
    hidden = encode(params, input_ids, attention_mask, config)
    return pool(hidden, attention_mask, pooling, normalize)


# ---------------------------------------------------------------------------
# HF checkpoint import (offline)
# ---------------------------------------------------------------------------

_HF_LAYER_MAP = {
    "attn_q": "attention.self.query",
    "attn_k": "attention.self.key",
    "attn_v": "attention.self.value",
    "attn_out": "attention.output.dense",
    "mlp_in": "intermediate.dense",
    "mlp_out": "output.dense",
}
_HF_LN_MAP = {
    "attn_ln": "attention.output.LayerNorm",
    "mlp_ln": "output.LayerNorm",
}


def from_hf_weights(state_dict: dict, config: BertConfig, dtype=jnp.float32) -> dict:
    """Map a HuggingFace BERT state dict (numpy arrays) into our pytree.

    Works with any BERT-architecture checkpoint (bge-*-en-v1.5 included)
    loaded from local files — no network access is assumed here.
    """

    def get(name):
        arr = state_dict[name]
        return jnp.asarray(arr, dtype=dtype)

    def dense(prefix):
        return {
            # torch Linear stores [out, in]; ours is [in, out]
            "kernel": get(f"{prefix}.weight").T,
            "bias": get(f"{prefix}.bias"),
        }

    def ln(prefix):
        return {"scale": get(f"{prefix}.weight"), "bias": get(f"{prefix}.bias")}

    layers = []
    for i in range(config.num_layers):
        base = f"encoder.layer.{i}"
        layer = {
            name: dense(f"{base}.{hf}") for name, hf in _HF_LAYER_MAP.items()
        }
        layer.update(
            {name: ln(f"{base}.{hf}") for name, hf in _HF_LN_MAP.items()}
        )
        layers.append(layer)
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *layers)

    return {
        "token_embed": get("embeddings.word_embeddings.weight"),
        "position_embed": get("embeddings.position_embeddings.weight"),
        "type_embed": get("embeddings.token_type_embeddings.weight"),
        "embed_ln": ln("embeddings.LayerNorm"),
        "layers": stacked,
    }
