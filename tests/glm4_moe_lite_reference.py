"""Tier-1 copy of the judge's plain reference: the ``glm4_moe_lite`` forward
in numpy float64, from the equations of its configuration, reading a
checkpoint by its HuggingFace names.  Imports nothing of the program.

  logits(state, cfg, ids) -> [T, vocab], the full forward over T positions
  (no cache, no kernel, whole [T, T] scores, every expert looped over the
  tokens routed to it).

The benchmark's own copy (``bench/references/glm4_moe_lite_judge.py``) is the
same mathematics in float32 ``jax.numpy`` at the configuration's size.
"""

import numpy as np


def random_state(cfg: dict, seed: int) -> dict:
    """An HF-named checkpoint of ``cfg``'s shapes, N(0, 0.02), float32."""
    rng = np.random.default_rng(seed)
    h, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    state = {}

    def w(name, *shape):
        state[name] = (rng.standard_normal(shape) * 0.02).astype(np.float32)

    def scale(name, n):
        state[name] = (1 + rng.standard_normal(n) * 0.02).astype(np.float32)

    def swiglu(base, width):
        w(f"{base}.gate_proj.weight", width, h)
        w(f"{base}.up_proj.weight", width, h)
        w(f"{base}.down_proj.weight", h, width)

    w("model.embed_tokens.weight", cfg["vocab_size"], h)
    for i in range(cfg["num_hidden_layers"]):
        base = f"model.layers.{i}"
        scale(f"{base}.input_layernorm.weight", h)
        scale(f"{base}.post_attention_layernorm.weight", h)
        att = f"{base}.self_attn"
        w(f"{att}.q_a_proj.weight", cfg["q_lora_rank"], h)
        scale(f"{att}.q_a_layernorm.weight", cfg["q_lora_rank"])
        w(f"{att}.q_b_proj.weight", heads * (nope + rope), cfg["q_lora_rank"])
        w(f"{att}.kv_a_proj_with_mqa.weight", cfg["kv_lora_rank"] + rope, h)
        scale(f"{att}.kv_a_layernorm.weight", cfg["kv_lora_rank"])
        w(f"{att}.kv_b_proj.weight", heads * (nope + dv), cfg["kv_lora_rank"])
        w(f"{att}.o_proj.weight", h, heads * dv)
        if i < cfg["first_k_dense_replace"]:
            swiglu(f"{base}.mlp", cfg["intermediate_size"])
        else:
            w(f"{base}.mlp.gate.weight", cfg["n_routed_experts"], h)
            w(f"{base}.mlp.gate.e_score_correction_bias", cfg["n_routed_experts"])
            for e in range(cfg["n_routed_experts"]):
                swiglu(f"{base}.mlp.experts.{e}", cfg["moe_intermediate_size"])
            swiglu(
                f"{base}.mlp.shared_experts",
                cfg["moe_intermediate_size"] * cfg["n_shared_experts"],
            )
    scale("model.norm.weight", h)
    w("lm_head.weight", cfg["vocab_size"], h)
    return state


def rms(x, weight, eps):
    return x / np.sqrt(np.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def rope_interleaved(x, positions, theta):
    """x [T, ..., d]: pairs (2i, 2i+1) turn by positions * theta^(-2i/d)."""
    d = x.shape[-1]
    inv = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    angle = positions.astype(np.float64)[:, None] * inv  # [T, d/2]
    shape = (len(positions),) + (1,) * (x.ndim - 2) + (d // 2,)
    cos, sin = np.cos(angle).reshape(shape), np.sin(angle).reshape(shape)
    even, odd = x[..., 0::2], x[..., 1::2]
    out = np.empty_like(x)
    out[..., 0::2] = even * cos - odd * sin
    out[..., 1::2] = odd * cos + even * sin
    return out


def silu(x):
    return x / (1.0 + np.exp(-x))


def swiglu(x, get, base):
    gate = x @ get(f"{base}.gate_proj.weight").T
    up = x @ get(f"{base}.up_proj.weight").T
    return (silu(gate) * up) @ get(f"{base}.down_proj.weight").T


def route(x, gate_weight, bias, k, scaling):
    """Scores sigmoid(W_g x); the top k of score + bias are chosen, weighed
    by the unbiased scores normalised to 1, times ``scaling``."""
    score = 1.0 / (1.0 + np.exp(-(x @ gate_weight.T)))
    chosen = np.argsort(-(score + bias), axis=1, kind="stable")[:, :k]
    weight = np.take_along_axis(score, chosen, axis=1)
    return chosen, weight / weight.sum(axis=1, keepdims=True) * scaling


def logits(state, cfg: dict, ids) -> np.ndarray:
    def get(name):
        return np.asarray(state[name]).astype(np.float64)

    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    heads = cfg["num_attention_heads"]
    nope, rope, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    rank = cfg["kv_lora_rank"]
    ids = np.asarray(ids)
    t = len(ids)
    positions = np.arange(t)
    x = get("model.embed_tokens.weight")[ids]
    for i in range(cfg["num_hidden_layers"]):
        base = f"model.layers.{i}"
        att = f"{base}.self_attn"
        h = rms(x, get(f"{base}.input_layernorm.weight"), eps)
        cq = rms(h @ get(f"{att}.q_a_proj.weight").T, get(f"{att}.q_a_layernorm.weight"), eps)
        q = (cq @ get(f"{att}.q_b_proj.weight").T).reshape(t, heads, nope + rope)
        q_nope, q_rope = q[..., :nope], rope_interleaved(q[..., nope:], positions, theta)
        kv = h @ get(f"{att}.kv_a_proj_with_mqa.weight").T
        c = rms(kv[:, :rank], get(f"{att}.kv_a_layernorm.weight"), eps)
        k_rope = rope_interleaved(kv[:, rank:], positions, theta)  # [T, rope], all heads'
        kvb = (c @ get(f"{att}.kv_b_proj.weight").T).reshape(t, heads, nope + dv)
        k_nope, v = kvb[..., :nope], kvb[..., nope:]
        scores = np.einsum("qhd,khd->hqk", q_nope, k_nope) + np.einsum(
            "qhd,kd->hqk", q_rope, k_rope
        )
        scores = scores / np.sqrt(nope + rope)
        scores = np.where(np.tril(np.ones((t, t), bool)), scores, -np.inf)
        probs = np.exp(scores - scores.max(axis=-1, keepdims=True))
        probs /= probs.sum(axis=-1, keepdims=True)
        ctx = np.einsum("hqk,khd->qhd", probs, v).reshape(t, heads * dv)
        x = x + ctx @ get(f"{att}.o_proj.weight").T
        h = rms(x, get(f"{base}.post_attention_layernorm.weight"), eps)
        if i < cfg["first_k_dense_replace"]:
            x = x + swiglu(h, get, f"{base}.mlp")
            continue
        chosen, weight = route(
            h, get(f"{base}.mlp.gate.weight"),
            get(f"{base}.mlp.gate.e_score_correction_bias"),
            cfg["num_experts_per_tok"], cfg["routed_scaling_factor"],
        )
        out = swiglu(h, get, f"{base}.mlp.shared_experts")
        for e in range(cfg["n_routed_experts"]):
            tokens, slot = np.nonzero(chosen == e)
            if len(tokens):
                y = swiglu(h[tokens], get, f"{base}.mlp.experts.{e}")
                out[tokens] += y * weight[tokens, slot][:, None]
        x = x + out
    return rms(x, get("model.norm.weight"), eps) @ get("lm_head.weight").T
