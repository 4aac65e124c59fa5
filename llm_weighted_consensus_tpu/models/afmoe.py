"""A causal decoder of grouped-query attention of TWO kinds in one stack, an
elementwise gate on every head's output, four norms a layer, over sparse
experts of which this chip may hold a share: a judge.

``model_type`` ``afmoe`` (arcee-ai/Trinity-Large-Preview), written from its
configuration.  ``rms(x, w) = x / sqrt(mean(x^2) + eps) · w``; layer i is
``sliding_attention`` or ``full_attention`` by ``layer_types[i]``, dense where
i < ``num_dense_layers``:

  x0      = embed[ids] · sqrt(hidden_size)                 ``mup_enabled``
  per layer:
    h     = rms(x, w_in)
    q, k, v = W_q · h, W_k · h, W_v · h                    heads | kv | kv heads of hd
    g     = W_g · h                                        [heads x hd], the gate
    q, k  = rms(q, q_norm), rms(k, k_norm)                 a head, over its hd dims
    sliding: q, k = rope(q), rope(k)                       all hd dims, pairs (i, i + hd / 2)
             a = softmax over t - window < s <= t of (q · k / sqrt(hd)) v
    full:    no turn at all;  a = causal softmax(q · k / sqrt(hd)) v
             a key head serves ``heads / kv heads`` query heads
    x     = x + rms(W_o · (a · sigmoid(g)), w_post_attn)   the norm BEFORE the sum
    h     = rms(x, w_pre_mlp)
    dense:   m = SwiGLU(h)
    sparse:  s = sigmoid(W_r · h) float32 over the router's experts; the top k
             of s + expert_bias (the bias chooses, it does not weigh);
             p = s[chosen] / (Σ s[chosen] + 1e-20) · route_scale
             m = Σ_{e chosen, e held here} p_e · SwiGLU_e(h) + SwiGLU_shared(h)
    x     = x + rms(m, w_post_mlp)
  logits  = W_head · rms(x[last], w_final)

WHAT A CHECKPOINT NAMES IS WHAT IS SERVED.  One pipeline stage of a deployment
names a RUN of the published layers by their published numbers
(``model.layers.5`` .. ``model.layers.9``): layer n is of the kind
``layer_types[n]`` gives it (nothing in a layer's tensors tells a sliding layer
from a full one) and dense where it names ``mlp.gate_proj``.  It may name
experts 0..E-1 of a router wider than E: the chip's share where several chips
share each layer's experts and each keeps both kinds of attention, the gate,
the router, the shared expert and its slice of the vocabulary whole.  The
router then still chooses among all its experts; the pairs whose expert is
here are laid out and multiplied, none of them dropped
(``decoder_parts.experts_grouped(..., held=)``), and the layer's output is this
chip's partial sum: what the experts elsewhere would add is left out.

TWO KINDS OF CACHE, two lengths, in one list.  A full layer leaves its keys
(unturned) and values over every slot, [b, s, kv heads * hd]; a sliding layer
its TURNED keys and its values at the ``window - 1`` positions before a call's
length, gathered at ``lens`` after the whole-length prefill: all the token at
``lens`` can see (slot j is position ``lens - (window - 1) + j``; those before
position 0 are padding).  The decoded token goes one row against the window's
keys on the sliding layers and against every key on the full ones
(``decoder_parts.attend_cached``).  ``prefill`` counts ``window_keys``: the
pairs inside the sliding layers' bands, and the causal pairs those were taken
from.

A sliding layer's prefill runs ``window_attention_blockwise``, a full layer's
``causal_attention_blockwise`` (``ops/causal_attention.py``: one body, each
under its own jitted name), both with ``kv_heads``: a query head finds its key
head's block through the index map and no key is repeated in memory.  At
16,384 slots and a window of 4096 the window's blocks are 2048 and a query
block meets three key blocks (``work_over_window`` 1.0625: the old edge's
block and the diagonal's both in stripes).  Heads of one 128-lane column
are normalised and turned where the product wrote them, in one pass
(``ops/head_norm.py``).

``jax.named_scope`` names every part, so that a device trace can be read by
layer: ``embed_tokens``; ``attn_qkv`` (the three products, the gate's, the head
norms, the turn), ``window_attention``, ``causal_attention``, ``attn_gate``,
``attn_out`` (the output product and the norm behind it); ``router``,
``experts_routed`` (its three stages beneath), ``expert_shared``,
``dense_mlp``, ``mlp_norm`` (the norm behind the MLP); ``head_read``.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from ..ops import head_norm
from ..ops.causal_attention import (
    band_pairs, causal_attention_blockwise, window_attention_blockwise,
)
from .configs import AfmoeConfig
from .decoder_parts import (  # noqa: F401  (quantize_dense: the panel's protocol)
    attend_cached, dense, experts_grouped, gated, layers_past_usual, quantize_dense, rms,
    rope_angles, route_sigmoid, swiglu, tiles_laid_and_in_use, turn_heads,
)

# -- attention, of either kind -----------------------------------------------------


def _heads(x, weight, positions, config: AfmoeConfig, turn: bool):
    """x [..., heads * hd] at ``positions`` [...]: every head normalised over
    its hd dims (``weight`` [hd]) and, with ``turn``, turned.  A prefill's
    [b, s, width] with heads of one 128-lane column stays as the product wrote
    it (``ops/head_norm.py``: norm and turn in one pass, rounded once); any
    other shape (a tiny preset, a decode step's rows) is cut into heads."""
    hd, eps = config.head_dim, config.rms_norm_eps
    angles = rope_angles(positions, hd, config.rope_theta) if turn else ()
    if positions.shape == x.shape[1:-1] and head_norm.fits(x.shape, hd):
        return head_norm.head_norm_turn(x, weight, *angles, eps=eps)
    x = rms(x.reshape(*x.shape[:-1], -1, hd), weight, eps).reshape(x.shape)
    return turn_heads(x, *angles, x.shape[-1] // hd, 0) if turn else x


def _qkv(h, p: dict, positions, config: AfmoeConfig, slides: bool):
    """h [..., hidden] at ``positions`` [...] -> q [..., heads * hd] and k
    [..., kv * hd], each head normalised and, on a sliding layer, turned; v
    [..., kv * hd]; the gate [..., heads * hd]."""
    q = _heads(dense(h, p["q"]), p["q_norm"], positions, config, slides)
    k = _heads(dense(h, p["k"]), p["k_norm"], positions, config, slides)
    return q, k, dense(h, p["v"]), dense(h, p["gate"])


def _attn_out(ctx, gate, p: dict, norm, eps: float):
    with jax.named_scope("attn_gate"):
        ctx = gated(ctx, gate)
    with jax.named_scope("attn_out"):
        return rms(dense(ctx, p["o"]), norm, eps)


def _attention_prefill(h, layer: dict, lens, config: AfmoeConfig, slides: bool):
    """h [b, s, hidden] -> (the branch's normed output [b, s, hidden], (keys,
    values)): over every slot on a full layer, over the ``window - 1``
    positions before ``lens`` on a sliding one."""
    p, s = layer["attn"], h.shape[1]
    heads, kv = config.num_heads, config.num_kv_heads
    scale = 1.0 / math.sqrt(config.head_dim)
    with jax.named_scope("attn_qkv"):
        q, k, v, gate = _qkv(h, p, jnp.arange(s), config, slides)
    if slides:
        with jax.named_scope("window_attention"):
            ctx = window_attention_blockwise(
                q, k, v, heads=heads, kv_heads=kv, scale=scale, window=config.sliding_window
            )
        with jax.named_scope("attn_qkv"):
            back = config.sliding_window - 1
            at = jnp.maximum(lens[:, None] - back + jnp.arange(back), 0)[..., None]
            k, v = (jnp.take_along_axis(x, at, axis=1) for x in (k, v))
    else:
        with jax.named_scope("causal_attention"):
            ctx = causal_attention_blockwise(q, k, v, heads=heads, kv_heads=kv, scale=scale)
    return _attn_out(ctx, gate, p, layer["post_attn_norm"], config.rms_norm_eps), (k, v)


def _attention_decode(h, layer: dict, lens, cache, config: AfmoeConfig, slides: bool):
    """One token a call at position ``lens[b]``, one row of scores against the
    layer's cache (a full layer's every position, slots >= lens[b] padding; a
    sliding layer's window) and its own key."""
    p, b = layer["attn"], h.shape[0]
    with jax.named_scope("attn_qkv"):
        q, k_new, v_new, gate = _qkv(h, p, lens, config, slides)
        k_all = jnp.concatenate([cache[0], k_new[:, None, :]], axis=1)
        v_all = jnp.concatenate([cache[1], v_new[:, None, :]], axis=1)
    with jax.named_scope("window_attention" if slides else "causal_attention"):
        ctx = attend_cached(q, k_all, v_all, lens, config.num_kv_heads, window=slides)
    return _attn_out(
        ctx.reshape(b, -1), gate, p, layer["post_attn_norm"], config.rms_norm_eps
    )


# -- the second half ------------------------------------------------------------------


def route(h, p: dict, config: AfmoeConfig):
    """h [t, hidden] -> (experts [t, k] int32 among the ROUTER's, weights
    [t, k] float32): the latent-attention judges' router to the letter.  (The
    1e-20 the family adds beside the chosen scores' sum is under the last bit
    of a float32 sum of k sigmoids; the plain reference adds it.)"""
    return route_sigmoid(h, p, config.num_experts_per_tok, config.route_scale)


def _held(p: dict, config: AfmoeConfig):
    """The experts 0..held-1 a sparse layer holds of its router's, or None
    where it holds them all."""
    held = p["w_gate"].shape[0]
    return None if held == config.num_experts else held


def _moe(h, p: dict, config: AfmoeConfig):
    """h [t, hidden] -> (output [t, hidden], pairs routed to each expert; a
    share's partial sum, and last the pairs routed elsewhere)."""
    with jax.named_scope("router"):
        chosen, weight = route(h, p, config)
    with jax.named_scope("experts_routed"):
        routed, counts = experts_grouped(
            h, chosen, weight, p, config.num_experts, held=_held(p, config)
        )
    with jax.named_scope("expert_shared"):
        shared = swiglu(h, p["shared"])
    return routed + shared, counts


def _mlp(x, layer: dict, config: AfmoeConfig):
    """The layer's second half over the stream x [..., hidden]: (x + the
    branch's normed output, counts | None)."""
    eps = config.rms_norm_eps
    h = rms(x, layer["pre_mlp_norm"], eps)
    if "mlp" in layer:
        with jax.named_scope("dense_mlp"):
            out, counts = swiglu(h, layer["mlp"]), None
    else:
        flat, counts = _moe(h.reshape(-1, h.shape[-1]), layer["moe"], config)
        out = flat.reshape(h.shape)
    with jax.named_scope("mlp_norm"):
        return x + rms(out, layer["post_mlp_norm"], eps), counts


# -- the panel's protocol (models/judge.py) ---------------------------------------------


def _embed(params: dict, ids, config: AfmoeConfig):
    with jax.named_scope("embed_tokens"):
        x = jnp.take(params["token_embed"], ids, axis=0)
        if not config.mup_enabled:
            return x
        return (x.astype(jnp.float32) * math.sqrt(config.hidden_size)).astype(x.dtype)


def prefill(params: dict, ids, config: AfmoeConfig, lens=None, tallies=None):
    """ids [b, s] right-padded calls of ``lens`` tokens -> (hidden [b, s,
    hidden] before the final norm, a layer's (keys, values) of its kind, pairs
    routed a sparse layer).  Without ``lens`` every slot is a token.  A
    ``tallies`` dict handed in receives ``window_keys`` [2] int32: the pairs
    inside the sliding layers' bands, and the causal pairs those were taken
    from, over every slot."""
    b, s = ids.shape
    if lens is None:
        lens = jnp.full((b,), s, jnp.int32)
    x = _embed(params, ids, config)
    caches, loads, sliding = [], [], 0
    for i, layer in enumerate(params["layers"]):
        slides = config.slides(i)
        sliding += slides
        h = rms(x, layer["input_norm"], config.rms_norm_eps)
        out, cache = _attention_prefill(h, layer, lens, config, slides)
        caches.append(cache)
        x, counts = _mlp(x + out, layer, config)
        if counts is not None:
            loads.append(counts)
    if sliding and tallies is not None:
        tallies["window_keys"] = jnp.asarray(
            [sliding * b * band_pairs(s, config.sliding_window),
             sliding * b * (s * (s + 1) // 2)], jnp.int32,
        )
    return x, caches, loads


def decode_step(params: dict, token, lens, caches, config: AfmoeConfig):
    """One token a call at position ``lens`` -> hidden [b, hidden]."""
    x = _embed(params, token, config)
    for i, (layer, cache) in enumerate(zip(params["layers"], caches)):
        h = rms(x, layer["input_norm"], config.rms_norm_eps)
        out = _attention_decode(h, layer, lens, cache, config, config.slides(i))
        x, _ = _mlp(x + out, layer, config)
    return x


def head_logprobs(params: dict, hidden, config: AfmoeConfig):
    """hidden [b, hidden] -> log-probabilities over the vocabulary, float32."""
    with jax.named_scope("head_read"):
        h = rms(hidden, params["final_norm"], config.rms_norm_eps)
        logits = jnp.einsum(
            "bh,hv->bv", h, params["lm_head"], preferred_element_type=jnp.float32
        )
        return logits - jax.nn.logsumexp(logits, axis=-1, keepdims=True)


def experts_held(params: dict, config: AfmoeConfig) -> int:
    """The experts this chip holds, 0..E-1 of the router's (all of them
    unless the checkpoint named a share)."""
    for layer in params["layers"]:
        if "moe" in layer:
            return int(layer["moe"]["w_gate"].shape[0])
    return config.num_experts


def whole_bound_layers(load, config: AfmoeConfig) -> int:
    """Of a dispatch's sparse layers, those that ran over the layout's whole
    bound.  With every expert held (``load`` [layers, experts]) the layout
    has one bound and no usual load."""
    if load.size and load.shape[1] == config.num_experts:
        return 0
    return layers_past_usual(load, config.num_experts)


def expert_tiles(load, config: AfmoeConfig) -> tuple[int, int]:
    """Of a dispatch's sparse layers, the row tiles their layouts laid and
    those that hold a pair (``decoder_parts.tiles_laid_and_in_use``)."""
    share = bool(load.size) and load.shape[1] != config.num_experts
    return tiles_laid_and_in_use(load, config.num_experts, share)


# -- parameters -------------------------------------------------------------------------


def init_params(rng, config: AfmoeConfig, dtype=jnp.float32, held=None) -> dict:
    """Random parameters in the served layout (tests, shape work); ``held``
    experts of the router's (all of them unless given)."""
    std = 0.02
    drawn = iter(range(1 << 30))

    def normal(*shape, dt=dtype, mean=0.0):  # a key of its own per tensor
        key = jax.random.fold_in(rng, next(drawn))
        return (mean + jax.random.normal(key, shape, jnp.float32) * std).astype(dt)

    def kernel(i, o):
        return {"kernel": normal(i, o)}

    def mlp(width):
        return {"gate": kernel(h, width), "up": kernel(h, width), "down": kernel(width, h)}

    h, hd = config.hidden_size, config.head_dim
    wide, narrow = config.num_heads * hd, config.num_kv_heads * hd
    experts, width = held or config.num_experts, config.moe_intermediate_size
    layers = []
    for i in range(config.num_layers):
        layer = {
            name: normal(h, mean=1.0)
            for name in ("input_norm", "post_attn_norm", "pre_mlp_norm", "post_mlp_norm")
        }
        layer["attn"] = {
            "q": kernel(h, wide), "k": kernel(h, narrow), "v": kernel(h, narrow),
            "gate": kernel(h, wide), "o": kernel(wide, h),
            "q_norm": normal(hd, mean=1.0), "k_norm": normal(hd, mean=1.0),
        }
        if config.is_dense(i):
            layer["mlp"] = mlp(config.intermediate_size)
        else:
            layer["moe"] = {
                "router": normal(h, config.num_experts, dt=jnp.float32),
                "bias": normal(config.num_experts, dt=jnp.float32),
                "w_gate": normal(experts, h, width),
                "w_up": normal(experts, h, width),
                "w_down": normal(experts, width, h),
                "shared": mlp(width * config.num_shared_experts),
            }
        layers.append(layer)
    return {
        "token_embed": normal(config.vocab_size, h),
        "final_norm": normal(h, mean=1.0),
        "lm_head": normal(h, config.vocab_size),
        "layers": layers,
    }


def from_hf_weights(state, config: AfmoeConfig, dtype=jnp.float32):
    """HF-named tensors (a mapping that may open each tensor lazily:
    ``loading.open_checkpoint``) -> (params, config).  A layer goes to the
    device before the next is read.  What is served is what the checkpoint
    names: a run of the published layers from the first it names, each of the
    kind ``config.layer_types`` gives its PUBLISHED number and dense where it
    names ``mlp.gate_proj`` (the dense layers lead); experts 0..E-1 of a
    router as wide as the preset's; the rows of the vocabulary
    ``embed_tokens`` holds.  The config handed back counts from the first
    layer served."""
    import numpy as np

    prefix = "model." if "model.embed_tokens.weight" in state else ""
    named = [
        n for n in range(config.num_layers)
        if f"{prefix}layers.{n}.input_layernorm.weight" in state
    ]
    if not named:
        raise ValueError("the checkpoint names no layer (layers.N.input_layernorm.weight)")
    if named != list(range(named[0], named[0] + len(named))):
        raise ValueError(f"the checkpoint's layers {named} are no run of the published ones")
    dense_layers = sum(f"{prefix}layers.{n}.mlp.gate_proj.weight" in state for n in named)
    sparse = named[dense_layers:]
    if any(f"{prefix}layers.{n}.mlp.gate_proj.weight" in state for n in sparse):
        raise ValueError("a dense layer behind a sparse one is not served")
    held = 0
    while sparse and f"{prefix}layers.{sparse[0]}.mlp.experts.{held}.gate_proj.weight" in state:
        held += 1
    if sparse and not held:
        raise ValueError(f"layer {sparse[0]} names no expert (mlp.experts.0) and no dense MLP")
    embed = np.asarray(state[prefix + "embed_tokens.weight"])
    config = dataclasses.replace(
        config, num_layers=len(named), num_dense_layers=dense_layers,
        layer_types=tuple(config.layer_types[n] for n in named),
        vocab_size=int(embed.shape[0]),
    )

    def get(name):
        return np.asarray(state[prefix + name])

    def put(array, dt=dtype):
        return jnp.asarray(array).astype(dt)

    swap = jax.jit(lambda w: jnp.swapaxes(w, -1, -2))

    def kernel(name):  # HF [out, in] -> [in, out], transposed on the device
        return {"kernel": swap(put(get(name + ".weight")))}

    def mlp(base):
        return {kind: kernel(f"{base}.{kind}_proj") for kind in ("gate", "up", "down")}

    wide = config.num_heads * config.head_dim
    layers = []
    for n in named:
        base, att = f"layers.{n}", f"layers.{n}.self_attn"
        layer = {
            "input_norm": put(get(f"{base}.input_layernorm.weight")),
            "post_attn_norm": put(get(f"{base}.post_attention_layernorm.weight")),
            "pre_mlp_norm": put(get(f"{base}.pre_mlp_layernorm.weight")),
            "post_mlp_norm": put(get(f"{base}.post_mlp_layernorm.weight")),
            "attn": {
                **{k: kernel(f"{att}.{k}_proj") for k in ("q", "k", "v", "gate", "o")},
                "q_norm": put(get(f"{att}.q_norm.weight")),
                "k_norm": put(get(f"{att}.k_norm.weight")),
            },
        }
        if layer["attn"]["q"]["kernel"].shape != (config.hidden_size, wide):
            raise ValueError(
                f"layer {n}: q_proj is {layer['attn']['q']['kernel'].shape[::-1]}, "
                f"the preset's is {(wide, config.hidden_size)}"
            )
        if n in sparse:
            def experts(kind):
                stacked = np.stack(
                    [get(f"{base}.mlp.experts.{e}.{kind}_proj.weight") for e in range(held)]
                )
                return swap(put(stacked))

            layer["moe"] = {
                "router": swap(put(get(f"{base}.mlp.router.gate.weight"), jnp.float32)),
                "bias": put(get(f"{base}.mlp.expert_bias"), jnp.float32),
                "w_gate": experts("gate"),
                "w_up": experts("up"),
                "w_down": experts("down"),
                "shared": mlp(f"{base}.mlp.shared_experts"),
            }
        else:
            layer["mlp"] = mlp(f"{base}.mlp")
        layers.append(layer)
    params = {
        "token_embed": put(embed),
        "final_norm": put(get("norm.weight")),
        "lm_head": swap(put(np.asarray(state["lm_head.weight"]))),
        "layers": layers,
    }
    return params, config
