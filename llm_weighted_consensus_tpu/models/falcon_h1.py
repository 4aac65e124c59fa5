"""A decoder whose every block runs a Mamba-2 (SSD) mixer and grouped-query
attention SIDE BY SIDE on one normed input and sums them, every product behind
one of the published µP multipliers: a judge.

``model_type`` ``falcon_h1`` (tiiuae/Falcon-H1-34B-Instruct), written from its
configuration and held to ``transformers``' ``FalconH1ForCausalLM`` through the
plain reference (``bench/references/falcon_h1_judge.py``, ``tests/
test_falcon_h1.py``).  ``rms(x; w) = x / sqrt(mean(x^2) + eps) · w``; every
layer, as published (m_* a multiplier of the configuration):

  x0      = embed[ids] · m_embedding
  h       = rms(x; input_layernorm)
  -- the mixer --
  [z | xBC | dt] = (W_in (h · m_ssm_in)) · mup       mup = m_ssm[0..4] over [z | x | B | C | dt]
  xBC     = silu(conv1d(xBC) + bias)                 a channel, ``d_conv`` taps, causal
  [xs | B | C] = xBC                                 heads x P | groups x N | groups x N
  dt      = softplus(dt + dt_bias);  a = -exp(A_log)   a head
  S_t     = exp(dt_t a) S_{t-1} + dt_t xs_t (x) B_t  head j reads group j // (heads / groups)
  y_t     = S_t C_t + D xs_t                         (``ops/ssd.py`` has the chunked form)
  ssm     = (W_out rms_groups(y · silu(z); norm)) · m_ssm_out     the norm over ``groups`` groups
  -- attention --
  q, k, v = W_q a, (W_k a) · m_key, W_v a,  a = h · m_attention_in
  q, k    = rope(q), rope(k)                         all hd dims, pairs (i, i + hd / 2)
  att     = (W_o causal_softmax(q k^T / sqrt(hd)) v) · m_attention_out
  x       = x + ssm + att
  -- the MLP --
  g       = rms(x; pre_ff_layernorm)
  x       = x + (W_down (W_up g · silu((W_gate g) · m_mlp[0]))) · m_mlp[1]
  logits  = (W_head rms(x[last]; final_layernorm)) · m_lm_head          untied

WHERE A MULTIPLIER IS APPLIED.  A product is linear, so ``(W (h · m)) · mup`` is
``(W h) · (m · mup)``: every multiplier of a product's input or output is ONE
scale on the product's float32 accumulator before it is rounded (``_times``),
so no weight and no activation is rounded anew for it.  The keys' multiplier is
neither on the keys nor on their cache: the scores' scale is ``m_key /
sqrt(hd)`` in the prefill's kernel and on the decoded row's query.  The same
numbers as published but for where bf16 rounds; the reference applies each
where the family's code does.

PADDING.  Calls are right-padded and everything here is causal, so a padded
slot reaches no real position through the convolution or the attention; its
step is zeroed inside the scan's kernel (``lens``), so it moves no state.  The
published masking of padded inputs (zeroed before the input product and behind
the convolution) is for left-padded batches and changes nothing here.

TWO KINDS OF CACHE A LAYER.  Every layer leaves BOTH its turned keys and its
values over every slot, [b, s, kv heads * hd] each, AND the mixer's
convolution tail (the ``d_conv - 1`` rows of the pre-convolution [x | B | C]
before ``lens``) and scan state [b, heads, P, N] float32 as they stand after
token ``lens - 1``: a layer's entry is ``((keys, values), (tail, state))``.
The decoded token takes one row against the keys (``decoder_parts.
attend_cached``), one convolution row and one step of the recurrence
(``ops/ssd.py::ssd_step``).  ``prefill`` counts ``state_positions``: the
positions that moved a scan state, and layers x slots.

``jax.named_scope`` names every part, so that a device trace can be read by
layer: ``embed_tokens``; ``ssm_in`` (the input norm and the mixer's three input
products), ``ssm_conv``, ``ssd_scan`` (the step's softplus and the kernel),
``ssm_norm`` (the gate and the grouped norm), ``ssm_out``; ``attn_qkv``,
``causal_attention`` (the rotary turn and the kernel), ``attn_out`` (the output
product and the block's sum); ``mlp``; ``head_read``.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.causal_attention import causal_attention_blockwise
from ..ops.ssd import ssd_chunked, ssd_step
from .configs import FalconH1Config
from .decoder_parts import (  # noqa: F401  (quantize_dense: the panel's protocol)
    attend_cached, dense, quantize_dense, rms, rope_angles, turn_heads,
)


def _times(x, p: dict, scale, dtype=None):
    """``dense(x, p) · scale``, the scale (a number, or one a column) on the
    product's float32 result, which is rounded once (to ``dtype``, or x's)."""
    if "kernel_q" in p:
        y = dense(x, p).astype(jnp.float32)
    else:
        y = jnp.einsum("...i,io->...o", x, p["kernel"], preferred_element_type=jnp.float32)
    return (y * scale).astype(dtype or x.dtype)


def _xbc_scale(config: FalconH1Config):
    """The multipliers of the input product's [x | B | C] columns."""
    m, wide = config.ssm_multipliers, config.ssm_groups * config.d_state
    cols = np.concatenate(
        [np.full((config.d_ssm,), m[1]), np.full((wide,), m[2]), np.full((wide,), m[3])]
    )
    return jnp.asarray(cols * config.ssm_in_multiplier, jnp.float32)


# -- the mixer --------------------------------------------------------------------------


def _ssm_in(h, p: dict, config: FalconH1Config):
    """h [..., hidden] -> z [..., d_ssm], the pre-convolution [x | B | C]
    [..., conv_dim], the raw step [..., heads] float32."""
    m_in, m = config.ssm_in_multiplier, config.ssm_multipliers
    z = _times(h, p["in_z"], m_in * m[0])
    xbc = _times(h, p["in_xbc"], _xbc_scale(config))
    dt = _times(h, p["in_dt"], m_in * m[4], jnp.float32)
    return z, xbc, dt


def _split(xbc, config: FalconH1Config):
    wide = config.ssm_groups * config.d_state
    cut = config.d_ssm
    return xbc[..., :cut], xbc[..., cut:cut + wide], xbc[..., cut + wide:]


def _step_sizes(dt, p: dict):
    return jax.nn.softplus(dt + p["dt_bias"].astype(jnp.float32))


def _rates(p: dict):
    return -jnp.exp(p["a_log"].astype(jnp.float32))


def _ssm_norm(y, z, p: dict, config: FalconH1Config):
    """rms over each of the ``ssm_groups`` groups of (y · silu(z)).  A group
    is cut where it lies (whole 128-lane columns at the published widths: a
    reshape to [.., groups, width] lays a prefill's float32 array out anew)."""
    with jax.named_scope("ssm_norm"):
        gated = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
        parts = zip(
            jnp.split(gated, config.ssm_groups, axis=-1), jnp.split(p["norm"], config.ssm_groups)
        )
        return jnp.concatenate(
            [rms(part, weight, config.rms_norm_eps) for part, weight in parts], axis=-1
        ).astype(z.dtype)


def _mixer_prefill(h, p: dict, lens, config: FalconH1Config):
    """h [b, s, hidden] -> (the branch's output [b, s, hidden], (convolution
    tail, state) after ``lens - 1``)."""
    s, taps = h.shape[1], config.d_conv
    with jax.named_scope("ssm_in"):
        z, xbc, dt = _ssm_in(h, p, config)
    with jax.named_scope("ssm_conv"):
        at = lens[:, None] - (taps - 1) + jnp.arange(taps - 1)[None, :]  # [b, taps - 1]
        tail = jnp.take_along_axis(xbc, jnp.maximum(at, 0)[:, :, None], axis=1)
        tail = jnp.where((at >= 0)[:, :, None], tail, 0)
        padded = jnp.pad(xbc, ((0, 0), (taps - 1, 0), (0, 0)))
        conv = sum(
            padded[:, j:j + s].astype(jnp.float32) * p["conv"][j].astype(jnp.float32)
            for j in range(taps)
        )
        xbc = jax.nn.silu(conv + p["conv_bias"].astype(jnp.float32)).astype(h.dtype)
        xs, b, c = _split(xbc, config)
    with jax.named_scope("ssd_scan"):
        y, state = ssd_chunked(
            xs, _step_sizes(dt, p), _rates(p), b, c, p["d"], lens, groups=config.ssm_groups
        )
    m = _ssm_norm(y, z, p, config)
    with jax.named_scope("ssm_out"):
        return _times(m, p["out"], config.ssm_out_multiplier), (tail, state)


def _mixer_decode(h, p: dict, cache, config: FalconH1Config):
    """One token a call, one step of the recurrence: h [b, hidden] -> the
    branch's output [b, hidden]."""
    tail, state = cache
    with jax.named_scope("ssm_in"):
        z, xbc, dt = _ssm_in(h, p, config)
    with jax.named_scope("ssm_conv"):
        taps = jnp.concatenate([tail, xbc[:, None, :]], axis=1).astype(jnp.float32)
        conv = jnp.sum(taps * p["conv"].astype(jnp.float32)[None], axis=1)
        xbc = jax.nn.silu(conv + p["conv_bias"].astype(jnp.float32)).astype(h.dtype)
        xs, b, c = _split(xbc, config)
    with jax.named_scope("ssd_scan"):
        y, _ = ssd_step(state, xs, _step_sizes(dt, p), _rates(p), b, c, p["d"])
    m = _ssm_norm(y.astype(h.dtype), z, p, config)
    with jax.named_scope("ssm_out"):
        return _times(m, p["out"], config.ssm_out_multiplier)


# -- attention --------------------------------------------------------------------------


def _qkv(h, p: dict, config: FalconH1Config):
    m = config.attention_in_multiplier
    with jax.named_scope("attn_qkv"):
        return tuple(_times(h, p[which], m) for which in "qkv")


def _turn(x, positions, heads: int, config: FalconH1Config):
    cos, sin = rope_angles(positions, config.head_dim, config.rope_theta)
    return turn_heads(x, cos, sin, heads, 0)


def _score_scale(config: FalconH1Config) -> float:
    """q · (k m_key) / sqrt(hd): the keys' multiplier goes with the scores'."""
    return config.key_multiplier / math.sqrt(config.head_dim)


def _attention_prefill(h, p: dict, config: FalconH1Config):
    """h [b, s, hidden] -> (the context [b, s, heads * hd], (turned keys,
    values) over every slot)."""
    heads, kv = config.num_heads, config.num_kv_heads
    q, k, v = _qkv(h, p, config)
    with jax.named_scope("causal_attention"):
        positions = jnp.arange(h.shape[1])
        q, k = _turn(q, positions, heads, config), _turn(k, positions, kv, config)
        ctx = causal_attention_blockwise(
            q, k, v, heads=heads, kv_heads=kv, scale=_score_scale(config)
        )
    return ctx, (k, v)


def _attention_decode(h, p: dict, lens, cache, config: FalconH1Config):
    """One token a call at position ``lens[b]``: one row of scores against the
    cached keys (slots >= lens[b] padding) and its own."""
    heads, kv = config.num_heads, config.num_kv_heads
    q, k_new, v_new = _qkv(h, p, config)
    with jax.named_scope("causal_attention"):
        q, k_new = _turn(q, lens, heads, config), _turn(k_new, lens, kv, config)
        q = (q.astype(jnp.float32) * config.key_multiplier).astype(q.dtype)
        k_all = jnp.concatenate([cache[0], k_new[:, None, :]], axis=1)
        v_all = jnp.concatenate([cache[1], v_new[:, None, :]], axis=1)
        return attend_cached(q, k_all, v_all, lens, kv).reshape(h.shape[0], -1)


# -- the block --------------------------------------------------------------------------


def _mlp(x, layer: dict, config: FalconH1Config):
    with jax.named_scope("mlp"):
        p, (m_gate, m_down) = layer["mlp"], config.mlp_multipliers
        g = rms(x, layer["pre_ff_norm"], config.rms_norm_eps)
        gate = _times(g, p["gate"], m_gate).astype(jnp.float32)
        up = dense(g, p["up"]).astype(jnp.float32)
        return x + _times((up * jax.nn.silu(gate)).astype(x.dtype), p["down"], m_down)


def _block_sum(x, ssm, ctx, p: dict, config: FalconH1Config):
    with jax.named_scope("attn_out"):
        return x + ssm + _times(ctx, p["o"], config.attention_out_multiplier)


def _input_norm(x, layer: dict, config: FalconH1Config):
    with jax.named_scope("ssm_in"):
        return rms(x, layer["input_norm"], config.rms_norm_eps)


# -- the panel's protocol (models/judge.py) ---------------------------------------------


def _embed(params: dict, ids, config: FalconH1Config):
    with jax.named_scope("embed_tokens"):
        x = jnp.take(params["token_embed"], ids, axis=0)
        return (x.astype(jnp.float32) * config.embedding_multiplier).astype(x.dtype)


def prefill(params: dict, ids, config: FalconH1Config, lens=None, tallies=None):
    """ids [b, s] right-padded calls of ``lens`` tokens -> (hidden [b, s,
    hidden] before the final norm, a layer's ((keys, values), (convolution
    tail, state)), no loads).  Without ``lens`` every slot is a token.  A
    ``tallies`` dict handed in receives ``state_positions`` [2] int32: the
    positions that moved a scan state, over the layers, and layers x slots."""
    b, s = ids.shape
    if lens is None:
        lens = jnp.full((b,), s, jnp.int32)
    x = _embed(params, ids, config)
    caches = []
    for layer in params["layers"]:
        h = _input_norm(x, layer, config)
        ssm, carried = _mixer_prefill(h, layer["mamba"], lens, config)
        ctx, kv = _attention_prefill(h, layer["attn"], config)
        caches.append((kv, carried))
        x = _mlp(_block_sum(x, ssm, ctx, layer["attn"], config), layer, config)
    if tallies is not None:
        layers = len(params["layers"])
        tallies["state_positions"] = jnp.stack(
            [layers * jnp.sum(jnp.minimum(lens, s)), jnp.int32(layers * b * s)]
        ).astype(jnp.int32)
    return x, caches, []


def decode_step(params: dict, token, lens, caches, config: FalconH1Config):
    """One token a call at position ``lens`` -> hidden [b, hidden]."""
    x = _embed(params, token, config)
    for layer, (kv, carried) in zip(params["layers"], caches):
        h = _input_norm(x, layer, config)
        ssm = _mixer_decode(h, layer["mamba"], carried, config)
        ctx = _attention_decode(h, layer["attn"], lens, kv, config)
        x = _mlp(_block_sum(x, ssm, ctx, layer["attn"], config), layer, config)
    return x


def head_logprobs(params: dict, hidden, config: FalconH1Config):
    """hidden [b, hidden] -> log-probabilities over the vocabulary, float32."""
    with jax.named_scope("head_read"):
        h = rms(hidden, params["final_norm"], config.rms_norm_eps)
        logits = jnp.einsum(
            "bh,hv->bv", h, params["lm_head"], preferred_element_type=jnp.float32
        ) * config.lm_head_multiplier
        return logits - jax.nn.logsumexp(logits, axis=-1, keepdims=True)


def experts_held(params: dict, config: FalconH1Config) -> int:
    return 0


def whole_bound_layers(load, config: FalconH1Config) -> int:
    return 0


def expert_tiles(load, config: FalconH1Config) -> tuple[int, int]:
    return 0, 0


# -- parameters -------------------------------------------------------------------------


def init_params(rng, config: FalconH1Config, dtype=jnp.float32) -> dict:
    """Random parameters in the served layout (tests, shape work)."""
    std = 0.02
    drawn = iter(range(1 << 30))

    def normal(*shape, dt=dtype, mean=0.0):  # a key of its own per tensor
        key = jax.random.fold_in(rng, next(drawn))
        return (mean + jax.random.normal(key, shape, jnp.float32) * std).astype(dt)

    def kernel(i, o):
        return {"kernel": normal(i, o)}

    h, width = config.hidden_size, config.intermediate_size
    wide, narrow = config.num_heads * config.head_dim, config.num_kv_heads * config.head_dim
    heads, inner = config.ssm_heads, config.d_ssm
    layers = []
    for _ in range(config.num_layers):
        layers.append({
            "input_norm": normal(h, mean=1.0), "pre_ff_norm": normal(h, mean=1.0),
            "mamba": {
                "in_z": kernel(h, inner), "in_xbc": kernel(h, config.conv_dim),
                "in_dt": kernel(h, heads),
                "conv": normal(config.d_conv, config.conv_dim),
                "conv_bias": normal(config.conv_dim),
                "a_log": normal(heads, dt=jnp.float32), "d": normal(heads, mean=1.0),
                "dt_bias": normal(heads), "norm": normal(inner, mean=1.0),
                "out": kernel(inner, h),
            },
            "attn": {
                "q": kernel(h, wide), "k": kernel(h, narrow), "v": kernel(h, narrow),
                "o": kernel(wide, h),
            },
            "mlp": {"gate": kernel(h, width), "up": kernel(h, width), "down": kernel(width, h)},
        })
    return {
        "token_embed": normal(config.vocab_size, h),
        "final_norm": normal(h, mean=1.0),
        "lm_head": normal(h, config.vocab_size),
        "layers": layers,
    }


def from_hf_weights(state, config: FalconH1Config, dtype=jnp.float32):
    """HF-named tensors (a mapping that may open each tensor lazily:
    ``loading.open_checkpoint``) -> (params, config).  A layer goes to the
    device before the next is read.  What is served is what the checkpoint
    names: its layers from 0 up (every layer is the same kind, so a run of
    them is a pipeline stage) and the rows of the vocabulary ``embed_tokens``
    holds; the head is a tensor of its own.  The mixer's fused ``in_proj`` is
    cut into its [z | x B C | dt] products."""
    prefix = "model." if "model.embed_tokens.weight" in state else ""
    depth = 0
    while f"{prefix}layers.{depth}.input_layernorm.weight" in state:
        depth += 1
    if not depth:
        raise ValueError("the checkpoint names no layer (layers.0.input_layernorm.weight)")
    embed = np.asarray(state[prefix + "embed_tokens.weight"])
    config = dataclasses.replace(config, num_layers=depth, vocab_size=int(embed.shape[0]))
    inner, conv_dim = config.d_ssm, config.conv_dim

    def get(name):
        return np.asarray(state[prefix + name])

    def put(array, dt=dtype):
        return jnp.asarray(array).astype(dt)

    swap = jax.jit(lambda w: jnp.swapaxes(w, -1, -2))

    def kernel(name):  # HF [out, in] -> [in, out], transposed on the device
        return {"kernel": swap(put(get(name + ".weight")))}

    layers = []
    for n in range(depth):
        base, mix, att = f"layers.{n}", f"layers.{n}.mamba", f"layers.{n}.self_attn"
        fused = swap(put(get(f"{mix}.in_proj.weight")))  # [hidden, z | x B C | dt]
        if fused.shape != (config.hidden_size, inner + conv_dim + config.ssm_heads):
            raise ValueError(
                f"layer {n}: in_proj is {fused.shape[::-1]}, the preset's is "
                f"{(inner + conv_dim + config.ssm_heads, config.hidden_size)}"
            )
        layers.append({
            "input_norm": put(get(f"{base}.input_layernorm.weight")),
            "pre_ff_norm": put(get(f"{base}.pre_ff_layernorm.weight")),
            "mamba": {
                "in_z": {"kernel": fused[:, :inner]},
                "in_xbc": {"kernel": fused[:, inner:inner + conv_dim]},
                "in_dt": {"kernel": fused[:, inner + conv_dim:]},
                "conv": put(get(f"{mix}.conv1d.weight")[:, 0, :].T),
                "conv_bias": put(get(f"{mix}.conv1d.bias")),
                "a_log": put(get(f"{mix}.A_log"), jnp.float32),
                "d": put(get(f"{mix}.D")),
                "dt_bias": put(get(f"{mix}.dt_bias")),
                "norm": put(get(f"{mix}.norm.weight")),
                "out": kernel(f"{mix}.out_proj"),
            },
            "attn": {which: kernel(f"{att}.{which}_proj") for which in "qkvo"},
            "mlp": {
                which: kernel(f"{base}.feed_forward.{which}_proj")
                for which in ("gate", "up", "down")
            },
        })
    params = {
        "token_embed": put(embed),
        "final_norm": put(get("final_layernorm.weight")),
        "lm_head": swap(put(np.asarray(state["lm_head.weight"]))),
        "layers": layers,
    }
    return params, config
