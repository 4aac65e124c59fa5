"""A causal decoder of gated delta-rule layers, three to one with gated full
attention, over sparse experts of which this chip may hold a share: a judge.

``model_type`` ``qwen3_next`` (Qwen/Qwen3-Next-80B-A3B-Instruct), written
from its configuration.  ``rms0(x, w) = x / sqrt(mean(x^2) + eps) * (1 + w)``
(the scale is stored zero-centred).  Layer i is a full-attention layer where
``(i + 1) % full_attention_interval == 0``, else a linear one:

  x0      = embed[ids]
  linear layer:
    h       = rms0(x, w_in)
    q|k|v   = silu(conv4(W_qkv · h))          depthwise, causal, over q|k|v
    q, k    = l2(q), l2(k) a head ;  q = q / sqrt(dk)      (inside the rule's kernel)
    beta    = sigmoid(W_b · h) ;  g = -exp(A_log) · softplus(W_a · h + dt_bias)
    o       = gated delta rule (ops/gated_delta.py) a value head, a key
              head serving ``value heads / key heads`` of them
    x       = x + W_out · (norm_w · o / rms(o) · silu(W_z · h))
  full layer:
    h       = rms0(x, w_in)
    q, gate = W_q · h  (a head: query | gate) ;  k, v = W_k · h, W_v · h
    q, k    = rope(rms0(q, q_norm)), rope(rms0(k, k_norm))     the first
              ``rotary_dim`` dims of a head, pairs (i, i + rotary_dim / 2)
    a       = causal softmax(q · k / sqrt(head_dim)) v         a key head
              serving ``heads / kv heads`` query heads
    x       = x + W_o · (a · sigmoid(gate))
  every layer's second half:
    h       = rms0(x, w_post)
    p       = softmax(W_g · h) over the router's experts, float32; the top k,
              divided by their sum
    x       = x + Σ_{e chosen, e held here} p_e · SwiGLU_e(h)
                + sigmoid(w_sg · h) · SwiGLU_shared(h)
  logits  = W_head · rms0(x[last], w_final)

THE SHARE.  The checkpoint may name experts 0..E-1 of a router wider than E:
the chip's share where several chips share each layer's experts and each
keeps the token mixers, the router, the shared expert and the vocabulary
whole.  The router then still chooses among all its experts; the pairs whose
expert is here are laid out and multiplied, none of them dropped, and the
layer's output is this chip's partial sum (the shared expert counted here):
what the experts elsewhere would add is left out.

TWO KINDS OF CACHE, side by side.  A linear layer leaves a convolution tail
(the last ``kernel - 1`` inputs of the convolution, [b, 3, channels]) and the
recurrent state [b, value heads, dk, dv] float32; a full layer leaves its
rotated keys and its values [b, s, kv heads * head_dim].  Calls are
right-padded to one bucket.  Causal attention never sees the slots past a
call's length; a recurrence would: so positions >= ``lens`` enter the delta
rule with beta 0 and log-decay 0, which leave the state as it was, and the
tail is read at ``lens - 3 .. lens - 1``.  The state a decode step starts
from is the state after token ``lens - 1``.  The decoded token goes one
recurrent step through the linear layers and one row against the cached keys
through the full ones.

``jax.named_scope`` names every part, so that a device trace can be read by
layer: ``linear_in``, ``linear_conv``, ``delta_rule``, ``linear_norm``,
``linear_out``; ``attn_qkv``, ``causal_attention``, ``attn_out``; ``router``,
``experts_routed`` (with its three stages), ``expert_shared``; and the
protocol's ``embed_tokens``, ``head_read``.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from ..ops.causal_attention import causal_attention_blockwise
from ..ops.gated_delta import gated_delta_rule, gated_delta_step
from .configs import Qwen3NextConfig
from .decoder_parts import (  # noqa: F401  (quantize_dense: the panel's protocol)
    attend_cached, dense, experts_grouped, layers_past_usual, quantize_dense, rms, rope,
    rope_angles, swiglu, tiles_laid_and_in_use,
)
from .decoder_parts import gated as _gated


def _rms0(x, weight, eps: float):
    """The zero-centred scale: (1 + weight)."""
    return rms(x, 1.0 + weight.astype(jnp.float32), eps)


# -- the linear layer ------------------------------------------------------------


def _linear_dims(config: Qwen3NextConfig):
    hk, hv = config.linear_num_key_heads, config.linear_num_value_heads
    dk, dv = config.linear_key_head_dim, config.linear_value_head_dim
    return hk, hv, dk, dv


def _linear_in(h, p: dict):
    """h [..., hidden] -> (q|k|v before the convolution, z, b|a float32)."""
    ba = jnp.einsum(
        "...i,io->...o", h, p["w_ba"], preferred_element_type=jnp.float32
    )
    return dense(h, p["in_qkv"]), dense(h, p["in_z"]), ba


def _after_conv(conv, dtype, config: Qwen3NextConfig):
    """The convolution's output [..., channels] float32 -> silu of it as q, k
    [..., hk * dk] and v [..., hv * dv] in ``dtype``: the rule takes q and k
    to unit length a head itself (ops/gated_delta.py)."""
    hk, _, dk, _ = _linear_dims(config)
    mixed = jax.nn.silu(conv).astype(dtype)
    return mixed[..., : hk * dk], mixed[..., hk * dk: 2 * hk * dk], mixed[..., 2 * hk * dk:]


def _gates(ba, p: dict, config: Qwen3NextConfig):
    """b|a [..., 2 * hv] float32 -> (g, beta) [..., hv] float32."""
    hv = config.linear_num_value_heads
    beta = jax.nn.sigmoid(ba[..., :hv])
    g = -jnp.exp(p["a_log"].astype(jnp.float32)) * jax.nn.softplus(
        ba[..., hv:] + p["dt_bias"].astype(jnp.float32)
    )
    return g, beta


def _gated_norm(normed, z, p: dict, config: Qwen3NextConfig):
    """norm_w · (o / rms(o)) · silu(z) a value head (a plain scale, the norm
    before the gate): ``normed`` = o / rms(o) and z, [..., hv * dv] ->
    [..., hv * dv] in z's dtype.  No reshape to heads: the scale is tiled."""
    scale = jnp.tile(p["norm"].astype(jnp.float32), config.linear_num_value_heads)
    y = (normed.astype(jnp.float32) * scale).astype(z.dtype).astype(jnp.float32)
    return (y * jax.nn.silu(z.astype(jnp.float32))).astype(z.dtype)


def _linear_prefill(h, p: dict, lens, config: Qwen3NextConfig):
    """h [b, s, hidden] -> (the layer's output [b, s, hidden], (convolution
    tail [b, taps - 1, channels], state [b, hv, dk, dv] float32)), both as
    they stand after token ``lens - 1``."""
    b, s, _ = h.shape
    taps = config.linear_conv_kernel_dim
    with jax.named_scope("linear_in"):
        mixed, z, ba = _linear_in(h, p)
    with jax.named_scope("linear_conv"):
        at = lens[:, None] - (taps - 1) + jnp.arange(taps - 1)[None, :]  # [b, taps - 1]
        tail = jnp.take_along_axis(mixed, jnp.maximum(at, 0)[:, :, None], axis=1)
        tail = jnp.where((at >= 0)[:, :, None], tail, 0)
        padded = jnp.pad(mixed, ((0, 0), (taps - 1, 0), (0, 0)))
        conv = sum(
            padded[:, j:j + s].astype(jnp.float32) * p["conv"][j].astype(jnp.float32)
            for j in range(taps)
        )
        q, k, v = _after_conv(conv, h.dtype, config)
    with jax.named_scope("delta_rule"):
        g, beta = _gates(ba, p, config)
        real = (jnp.arange(s)[None, :] < lens[:, None])[:, :, None]
        o, state = gated_delta_rule(
            q, k, v, jnp.where(real, g, 0.0), jnp.where(real, beta, 0.0),
            key_heads=config.linear_num_key_heads, norm_eps=config.rms_norm_eps,
        )
    with jax.named_scope("linear_norm"):
        y = _gated_norm(o, z, p, config)
    with jax.named_scope("linear_out"):
        return dense(y, p["out"]), (tail, state)


def _linear_decode(h, p: dict, cache, config: Qwen3NextConfig):
    """One token a call, one step of the recurrence: h [b, hidden]."""
    hk, hv, dk, dv = _linear_dims(config)
    tail, state = cache
    with jax.named_scope("linear_in"):
        mixed, z, ba = _linear_in(h, p)
    with jax.named_scope("linear_conv"):
        window = jnp.concatenate([tail, mixed[:, None, :]], axis=1).astype(jnp.float32)
        conv = jnp.sum(window * p["conv"].astype(jnp.float32)[None], axis=1)
        q, k, v = _after_conv(conv, h.dtype, config)
    with jax.named_scope("delta_rule"):
        g, beta = _gates(ba, p, config)
        b = h.shape[0]
        spread = lambda x: jnp.repeat(x.reshape(b, hk, dk), hv // hk, axis=1)  # noqa: E731
        o, _ = gated_delta_step(state, spread(q), spread(k), v.reshape(b, hv, dv), g, beta)
        o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + config.rms_norm_eps)
    with jax.named_scope("linear_norm"):
        y = _gated_norm(o.reshape(b, hv * dv).astype(h.dtype), z, p, config)
    with jax.named_scope("linear_out"):
        return dense(y, p["out"])


# -- the full-attention layer -------------------------------------------------------


def _qkv(h, p: dict, positions, config: Qwen3NextConfig):
    """h [..., hidden] at ``positions`` [...] -> q [..., heads * hd] and
    k [..., kv * hd] (normalised, turned), v [..., kv * hd], gate."""
    hd, rot = config.head_dim, config.rotary_dim
    cos, sin = rope_angles(positions, rot, config.rope_theta)
    cos, sin = cos[..., None, :], sin[..., None, :]

    def turned(x, norm):
        x = _rms0(x.reshape(*x.shape[:-1], -1, hd), norm, config.rms_norm_eps)
        x = jnp.concatenate([rope(x[..., :rot], cos, sin), x[..., rot:]], axis=-1)
        return x.reshape(*x.shape[:-2], -1)

    q = turned(dense(h, p["q"]), p["q_norm"])
    k = turned(dense(h, p["k"]), p["k_norm"])
    return q, k, dense(h, p["v"]), dense(h, p["gate"])


def _attention_prefill(h, p: dict, config: Qwen3NextConfig):
    """h [b, s, hidden] -> (the layer's output, (rotated keys, values))."""
    s = h.shape[1]
    with jax.named_scope("attn_qkv"):
        q, k, v, gate = _qkv(h, p, jnp.arange(s), config)
    with jax.named_scope("causal_attention"):
        ctx = causal_attention_blockwise(
            q, k, v, heads=config.num_heads, kv_heads=config.num_kv_heads,
            scale=1.0 / math.sqrt(config.head_dim),
        )
    with jax.named_scope("attn_out"):
        return dense(_gated(ctx, gate), p["o"]), (k, v)


def _attention_decode(h, p: dict, lens, cache, config: Qwen3NextConfig):
    """One token a call at position ``lens[b]``, one row of scores against
    the cached keys (slots >= lens[b] are padding) and its own."""
    b = h.shape[0]
    heads, kv, hd = config.num_heads, config.num_kv_heads, config.head_dim
    with jax.named_scope("attn_qkv"):
        q, k_new, v_new, gate = _qkv(h, p, lens, config)
        k_all = jnp.concatenate([cache[0], k_new[:, None, :]], axis=1)
        v_all = jnp.concatenate([cache[1], v_new[:, None, :]], axis=1)
    with jax.named_scope("causal_attention"):
        ctx = attend_cached(q, k_all, v_all, lens, kv)
    with jax.named_scope("attn_out"):
        return dense(_gated(ctx.reshape(b, heads * hd), gate), p["o"])


# -- the sparse half ------------------------------------------------------------------


def route(h, p: dict, config: Qwen3NextConfig):
    """h [t, hidden] -> (experts [t, k] int32 among the ROUTER's, weights
    [t, k] float32 summing to 1 a token)."""
    logits = jnp.dot(
        h.astype(jnp.float32), p["router"], precision=jax.lax.Precision.HIGHEST
    )
    prob = jax.nn.softmax(logits, axis=-1)
    # the k largest by k passes of a maximum (the first of equals, as a stable
    # sort has them): 2.8 ms a layer where lax.top_k's sort of [24576, 512]
    # takes 4.6 (my chip runs, PR 31)
    columns = jnp.arange(prob.shape[1], dtype=jnp.int32)[None, :]
    chosen, weight = [], []
    for _ in range(config.num_experts_per_tok):
        top = jnp.max(prob, axis=1, keepdims=True)
        index = jnp.min(jnp.where(prob == top, columns, prob.shape[1]), axis=1, keepdims=True)
        chosen.append(index)
        weight.append(top)
        prob = jnp.where(columns == index, -1.0, prob)
    weight = jnp.concatenate(weight, axis=1)
    return jnp.concatenate(chosen, axis=1), weight / jnp.sum(weight, axis=1, keepdims=True)


def _moe(h, p: dict, config: Qwen3NextConfig):
    """h [t, hidden] -> (this chip's partial sum [t, hidden], pairs routed to
    each expert held and, last, elsewhere)."""
    with jax.named_scope("router"):
        chosen, weight = route(h, p, config)
    with jax.named_scope("experts_routed"):
        routed, counts = experts_grouped(
            h, chosen, weight, p, config.num_experts, held=p["w_gate"].shape[0]
        )
    with jax.named_scope("expert_shared"):
        opened = jax.nn.sigmoid(
            jnp.einsum("ti,io->to", h, p["shared_gate"], preferred_element_type=jnp.float32)
        )
        shared = (swiglu(h, p["shared"]).astype(jnp.float32) * opened).astype(h.dtype)
    return routed + shared, counts


def _sparse(x, layer: dict, config: Qwen3NextConfig):
    h = _rms0(x, layer["post_norm"], config.rms_norm_eps)
    flat, counts = _moe(h.reshape(-1, h.shape[-1]), layer["moe"], config)
    return x + flat.reshape(h.shape), counts


# -- the panel's protocol (models/judge.py) ------------------------------------------------


def prefill(params: dict, ids, config: Qwen3NextConfig, lens=None, tallies=None):
    """ids [b, s] right-padded calls of ``lens`` tokens -> (hidden [b, s,
    hidden] before the final norm, a layer's cache of its kind, pairs routed
    a layer).  Without ``lens`` every slot is a token.  ``tallies`` is the
    panel protocol's; this decoder counts nothing into it."""
    if lens is None:
        lens = jnp.full((ids.shape[0],), ids.shape[1], jnp.int32)
    with jax.named_scope("embed_tokens"):
        x = jnp.take(params["token_embed"], ids, axis=0)
    caches, loads = [], []
    for layer in params["layers"]:
        h = _rms0(x, layer["input_norm"], config.rms_norm_eps)
        if "attn" in layer:
            out, cache = _attention_prefill(h, layer["attn"], config)
        else:
            out, cache = _linear_prefill(h, layer["linear"], lens, config)
        caches.append(cache)
        x, counts = _sparse(x + out, layer, config)
        loads.append(counts)
    return x, caches, loads


def decode_step(params: dict, token, lens, caches, config: Qwen3NextConfig):
    """One token a call at position ``lens`` -> hidden [b, hidden]."""
    with jax.named_scope("embed_tokens"):
        x = jnp.take(params["token_embed"], token, axis=0)
    for layer, cache in zip(params["layers"], caches):
        h = _rms0(x, layer["input_norm"], config.rms_norm_eps)
        if "attn" in layer:
            out = _attention_decode(h, layer["attn"], lens, cache, config)
        else:
            out = _linear_decode(h, layer["linear"], cache, config)
        x, _ = _sparse(x + out, layer, config)
    return x


def head_logprobs(params: dict, hidden, config: Qwen3NextConfig):
    """hidden [b, hidden] -> log-probabilities over the vocabulary, float32."""
    with jax.named_scope("head_read"):
        h = _rms0(hidden, params["final_norm"], config.rms_norm_eps)
        logits = jnp.einsum(
            "bh,hv->bv", h, params["lm_head"], preferred_element_type=jnp.float32
        )
        return logits - jax.nn.logsumexp(logits, axis=-1, keepdims=True)


def experts_held(params: dict, config: Qwen3NextConfig) -> int:
    """The experts this chip holds, 0..E-1 of the router's."""
    return int(params["layers"][0]["moe"]["w_gate"].shape[0])


def recurrent_layers(config: Qwen3NextConfig) -> int:
    return sum(not config.is_full_attention(i) for i in range(config.num_layers))


def whole_bound_layers(load, config: Qwen3NextConfig) -> int:
    """Of a dispatch's sparse layers (``load``: ``prefill``'s pairs routed a
    layer), those that ran over the layout's whole bound."""
    return layers_past_usual(load, config.num_experts)


def expert_tiles(load, config: Qwen3NextConfig) -> tuple[int, int]:
    """Of a dispatch's sparse layers, the row tiles their layouts laid and
    those that hold a pair (``decoder_parts.tiles_laid_and_in_use``)."""
    return tiles_laid_and_in_use(load, config.num_experts)


# -- parameters ---------------------------------------------------------------------------


def init_params(rng, config: Qwen3NextConfig, dtype=jnp.float32, held=None) -> dict:
    """Random parameters in the served layout (tests, shape work); ``held``
    experts of the router's (all of them unless given)."""
    std = 0.02
    drawn = iter(range(1 << 30))

    def normal(*shape, dt=dtype):  # a key of its own per tensor
        key = jax.random.fold_in(rng, next(drawn))
        return (jax.random.normal(key, shape, jnp.float32) * std).astype(dt)

    def kernel(i, o):
        return {"kernel": normal(i, o)}

    h, hd = config.hidden_size, config.head_dim
    hk, hv, dk, dv = _linear_dims(config)
    experts, width = held or config.num_experts, config.moe_intermediate_size
    shared = config.shared_expert_intermediate_size
    layers = []
    for i in range(config.num_layers):
        layer = {"input_norm": normal(h), "post_norm": normal(h)}
        if config.is_full_attention(i):
            layer["attn"] = {
                "q": kernel(h, config.num_heads * hd),
                "gate": kernel(h, config.num_heads * hd),
                "k": kernel(h, config.num_kv_heads * hd),
                "v": kernel(h, config.num_kv_heads * hd),
                "q_norm": normal(hd),
                "k_norm": normal(hd),
                "o": kernel(config.num_heads * hd, h),
            }
        else:
            layer["linear"] = {
                "in_qkv": kernel(h, 2 * hk * dk + hv * dv),
                "in_z": kernel(h, hv * dv),
                "w_ba": normal(h, 2 * hv),
                "conv": normal(config.linear_conv_kernel_dim, 2 * hk * dk + hv * dv),
                "a_log": normal(hv, dt=jnp.float32),
                "dt_bias": normal(hv, dt=jnp.float32),
                "norm": (1.0 + normal(dv, dt=jnp.float32)).astype(dtype),
                "out": kernel(hv * dv, h),
            }
        layer["moe"] = {
            "router": normal(h, config.num_experts, dt=jnp.float32),
            "w_gate": normal(experts, h, width),
            "w_up": normal(experts, h, width),
            "w_down": normal(experts, width, h),
            "shared": {
                "gate": kernel(h, shared), "up": kernel(h, shared), "down": kernel(shared, h),
            },
            "shared_gate": normal(h, 1),
        }
        layers.append(layer)
    return {
        "token_embed": normal(config.vocab_size, h),
        "final_norm": normal(h),
        "lm_head": normal(h, config.vocab_size),
        "layers": layers,
    }


def from_hf_weights(state, config: Qwen3NextConfig, dtype=jnp.float32):
    """HF-named tensors (a mapping that may open each tensor lazily:
    ``loading.open_checkpoint``) -> (params, config).  A layer goes to the
    device before the next is read.  Depth and share are the checkpoint's:
    the layers it names, from 0 up, and the experts it names, from 0 up, of
    a router as wide as ``mlp.gate.weight`` says.

    ``in_proj_qkvz`` and ``in_proj_ba`` are laid out by key head in the
    checkpoint (a key head: q | k | its value heads' v | their z; b | a) and
    ``q_proj`` by head (query | gate); each is taken apart into products
    whose columns run head-major (q | k | v for the convolution's channel
    order, which is the checkpoint's ``conv1d``'s)."""
    import numpy as np

    prefix = "model." if "model.embed_tokens.weight" in state else ""
    depth = 0
    while f"{prefix}layers.{depth}.input_layernorm.weight" in state:
        depth += 1
    if depth == 0:
        raise ValueError("the checkpoint names no layer (layers.0.input_layernorm.weight)")
    held = 0
    while f"{prefix}layers.0.mlp.experts.{held}.gate_proj.weight" in state:
        held += 1
    if held == 0:
        raise ValueError("the checkpoint names no expert (layers.0.mlp.experts.0)")
    if depth != config.num_layers:
        config = dataclasses.replace(config, num_layers=depth)

    def get(name):
        return np.asarray(state[prefix + name])

    def put(array, dt=dtype):
        return jnp.asarray(array).astype(dt)

    swap = jax.jit(lambda w: jnp.swapaxes(w, -1, -2))

    def kernel(rows):  # HF [out, in] -> [in, out], transposed on the device
        return {"kernel": swap(put(rows))}

    hd = config.head_dim
    hk, hv, dk, dv = _linear_dims(config)
    per = hv // hk
    layers = []
    for i in range(depth):
        base = f"layers.{i}"
        layer = {
            "input_norm": put(get(f"{base}.input_layernorm.weight")),
            "post_norm": put(get(f"{base}.post_attention_layernorm.weight")),
        }
        if config.is_full_attention(i):
            att = f"{base}.self_attn"
            q = get(f"{att}.q_proj.weight").reshape(config.num_heads, 2, hd, -1)
            layer["attn"] = {
                "q": kernel(q[:, 0].reshape(config.num_heads * hd, -1)),
                "gate": kernel(q[:, 1].reshape(config.num_heads * hd, -1)),
                "k": kernel(get(f"{att}.k_proj.weight")),
                "v": kernel(get(f"{att}.v_proj.weight")),
                "q_norm": put(get(f"{att}.q_norm.weight")),
                "k_norm": put(get(f"{att}.k_norm.weight")),
                "o": kernel(get(f"{att}.o_proj.weight")),
            }
        else:
            lin = f"{base}.linear_attn"
            qkvz = get(f"{lin}.in_proj_qkvz.weight").reshape(hk, 2 * dk + 2 * per * dv, -1)
            cut = np.cumsum([dk, dk, per * dv])
            q, k, v, z = np.split(qkvz, cut, axis=1)
            flat = lambda x: x.reshape(-1, x.shape[-1])  # noqa: E731
            ba = get(f"{lin}.in_proj_ba.weight").reshape(hk, 2 * per, -1)
            layer["linear"] = {
                "in_qkv": kernel(np.concatenate([flat(q), flat(k), flat(v)], axis=0)),
                "in_z": kernel(flat(z)),
                "w_ba": swap(put(np.concatenate([flat(ba[:, :per]), flat(ba[:, per:])], axis=0))),
                "conv": swap(put(get(f"{lin}.conv1d.weight")[:, 0, :])),
                "a_log": put(get(f"{lin}.A_log"), jnp.float32),
                "dt_bias": put(get(f"{lin}.dt_bias"), jnp.float32),
                "norm": put(get(f"{lin}.norm.weight")),
                "out": kernel(get(f"{lin}.out_proj.weight")),
            }

        def experts(kind):
            stacked = np.stack(
                [get(f"{base}.mlp.experts.{e}.{kind}_proj.weight") for e in range(held)]
            )
            return swap(put(stacked))

        layer["moe"] = {
            "router": swap(put(get(f"{base}.mlp.gate.weight"), jnp.float32)),
            "w_gate": experts("gate"),
            "w_up": experts("up"),
            "w_down": experts("down"),
            "shared": {
                kind: kernel(get(f"{base}.mlp.shared_expert.{kind}_proj.weight"))
                for kind in ("gate", "up", "down")
            },
            "shared_gate": swap(put(get(f"{base}.mlp.shared_expert_gate.weight"))),
        }
        layers.append(layer)
    params = {
        "token_embed": put(get("embed_tokens.weight")),
        "final_norm": put(get("norm.weight")),
        "lm_head": swap(put(np.asarray(state["lm_head.weight"]))),
        "layers": layers,
    }
    return params, config
