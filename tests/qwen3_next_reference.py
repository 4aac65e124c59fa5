"""Tier-1 copy of the second judge's plain reference: the ``qwen3_next`` forward
in numpy float64, from the equations of its configuration, reading a
checkpoint by its HuggingFace names.  Imports nothing of the program.

  logits(state, cfg, ids) -> [T, vocab], the full forward over T positions:
  the delta rule in its RECURRENT form, a token at a time; whole [T, T]
  scores in the full-attention layers; a loop over each token's experts.

``cfg`` is the published ``config.json``'s keys.  ``cfg["num_experts"]`` is
the ROUTER's width; the experts summed are those the checkpoint names
(``experts=`` narrows that further: the tests add up the shares), and what
the others would add is left out, the shared expert counted when ``shared``.

Departures from the published description, the same as the program's: the
release's multi-token-prediction module is not in ``config.json`` and is left
out; nothing else.  The benchmark's own copy
(``bench/references/qwen3_next_judge.py``) is the same mathematics in float32
``jax.numpy`` at the configuration's size.
"""

import numpy as np


def random_state(cfg: dict, seed: int, held=None, memory=None) -> dict:
    """An HF-named checkpoint of ``cfg``'s shapes, N(0, 0.02), float32, with
    experts 0..held-1 of the router's (all of them unless given).  ``memory``
    gives ``A_log`` and ``dt_bias`` values under which a head forgets about
    that share of its state a token (|g| ~ memory); without it both are
    N(0, 0.02) like everything else, and a state is gone within a few
    tokens."""
    rng = np.random.default_rng(seed)
    h, hd = cfg["hidden_size"], cfg["head_dim"]
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    state = {}

    def w(name, *shape):
        state[name] = (rng.standard_normal(shape) * 0.02).astype(np.float32)

    def swiglu(base, width):
        w(f"{base}.gate_proj.weight", width, h)
        w(f"{base}.up_proj.weight", width, h)
        w(f"{base}.down_proj.weight", h, width)

    w("model.embed_tokens.weight", cfg["vocab_size"], h)
    for i in range(cfg["num_hidden_layers"]):
        base = f"model.layers.{i}"
        w(f"{base}.input_layernorm.weight", h)
        w(f"{base}.post_attention_layernorm.weight", h)
        if (i + 1) % cfg["full_attention_interval"] == 0:
            att = f"{base}.self_attn"
            w(f"{att}.q_proj.weight", cfg["num_attention_heads"] * hd * 2, h)
            w(f"{att}.k_proj.weight", cfg["num_key_value_heads"] * hd, h)
            w(f"{att}.v_proj.weight", cfg["num_key_value_heads"] * hd, h)
            w(f"{att}.o_proj.weight", h, cfg["num_attention_heads"] * hd)
            w(f"{att}.q_norm.weight", hd)
            w(f"{att}.k_norm.weight", hd)
        else:
            lin = f"{base}.linear_attn"
            w(f"{lin}.in_proj_qkvz.weight", 2 * hk * dk + 2 * hv * dv, h)
            w(f"{lin}.in_proj_ba.weight", 2 * hv, h)
            w(f"{lin}.conv1d.weight", 2 * hk * dk + hv * dv, 1, cfg["linear_conv_kernel_dim"])
            w(f"{lin}.A_log", hv)
            w(f"{lin}.dt_bias", hv)
            if memory is not None:
                # g = -exp(A_log) softplus(a + dt_bias), a near 0
                state[f"{lin}.A_log"] += np.float32(np.log(memory / np.log(2.0)))
            state[f"{lin}.norm.weight"] = (1 + rng.standard_normal(dv) * 0.02).astype(np.float32)
            w(f"{lin}.out_proj.weight", h, hv * dv)
        w(f"{base}.mlp.gate.weight", cfg["num_experts"], h)
        for e in range(cfg["num_experts"] if held is None else held):
            swiglu(f"{base}.mlp.experts.{e}", cfg["moe_intermediate_size"])
        swiglu(f"{base}.mlp.shared_expert", cfg["shared_expert_intermediate_size"])
        w(f"{base}.mlp.shared_expert_gate.weight", 1, h)
    w("model.norm.weight", h)
    w("lm_head.weight", cfg["vocab_size"], h)
    return state


def rms0(x, weight, eps):
    """The zero-centred scale: (1 + weight)."""
    return x / np.sqrt(np.mean(x * x, axis=-1, keepdims=True) + eps) * (1.0 + weight)


def silu(x):
    return x / (1.0 + np.exp(-x))


def sigmoid(x):
    return 1.0 / (1.0 + np.exp(-x))


def softplus(x):
    return np.logaddexp(0.0, x)


def l2(x, eps=1e-6):
    return x / np.sqrt(np.sum(x * x, axis=-1, keepdims=True) + eps)


def swiglu(x, get, base):
    gate = x @ get(f"{base}.gate_proj.weight").T
    up = x @ get(f"{base}.up_proj.weight").T
    return (silu(gate) * up) @ get(f"{base}.down_proj.weight").T


def delta_rule(q, k, v, g, beta, state=None):
    """The recurrence, a token at a time.  q, k [T, H, dk] (unit length, q
    scaled), v [T, H, dv], g, beta [T, H] -> (o [T, H, dv], the state after
    the last token [H, dk, dv])."""
    t, heads, dk = k.shape
    s = np.zeros((heads, dk, v.shape[-1])) if state is None else state.copy()
    out = np.zeros_like(v)
    for i in range(t):
        s = s * np.exp(g[i])[:, None, None]
        held = np.einsum("hkv,hk->hv", s, k[i])
        d = (v[i] - held) * beta[i][:, None]
        s = s + k[i][:, :, None] * d[:, None, :]
        out[i] = np.einsum("hkv,hk->hv", s, q[i])
    return out, s


def linear_inputs(h, get, cfg, lin):
    """h [T, hidden] -> q, k [T, hv, dk], v [T, hv, dv], z [T, hv, dv], g,
    beta [T, hv], and the convolution's input [T, channels] (its last three
    rows are the tail a cache keeps)."""
    t = h.shape[0]
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    per, taps = hv // hk, cfg["linear_conv_kernel_dim"]
    # a key head's rows: q dk | k dk | its value heads' v | their z;  b | a
    qkvz = (h @ get(f"{lin}.in_proj_qkvz.weight").T).reshape(t, hk, 2 * dk + 2 * per * dv)
    ba = (h @ get(f"{lin}.in_proj_ba.weight").T).reshape(t, hk, 2 * per)
    q, k = qkvz[..., :dk], qkvz[..., dk:2 * dk]
    v = qkvz[..., 2 * dk:2 * dk + per * dv].reshape(t, hv, dv)
    z = qkvz[..., 2 * dk + per * dv:].reshape(t, hv, dv)
    b, a = ba[..., :per].reshape(t, hv), ba[..., per:].reshape(t, hv)
    mixed = np.concatenate([q.reshape(t, -1), k.reshape(t, -1), v.reshape(t, -1)], axis=1)
    weight = get(f"{lin}.conv1d.weight")[:, 0, :]  # [channels, taps]
    padded = np.concatenate([np.zeros((taps - 1, mixed.shape[1])), mixed])
    conv = silu(sum(padded[j:j + t] * weight[:, j] for j in range(taps)))
    q = l2(conv[:, : hk * dk].reshape(t, hk, dk)) * dk ** -0.5
    k = l2(conv[:, hk * dk: 2 * hk * dk].reshape(t, hk, dk))
    v = conv[:, 2 * hk * dk:].reshape(t, hv, dv)
    q, k = np.repeat(q, per, axis=1), np.repeat(k, per, axis=1)
    beta = sigmoid(b)
    g = -np.exp(get(f"{lin}.A_log")) * softplus(a + get(f"{lin}.dt_bias"))
    return q, k, v, z, g, beta, mixed


def linear_layer(h, get, cfg, lin):
    """-> (the layer's output [T, hidden], (tail [3, channels], state))."""
    t = h.shape[0]
    q, k, v, z, g, beta, mixed = linear_inputs(h, get, cfg, lin)
    o, state = delta_rule(q, k, v, g, beta)
    o = o / np.sqrt(np.mean(o * o, axis=-1, keepdims=True) + cfg["rms_norm_eps"])
    o = o * get(f"{lin}.norm.weight") * silu(z)  # a plain scale, norm before gate
    taps = cfg["linear_conv_kernel_dim"]
    tail = np.concatenate([np.zeros((taps - 1, mixed.shape[1])), mixed])[-(taps - 1):]
    return o.reshape(t, -1) @ get(f"{lin}.out_proj.weight").T, (tail, state)


def rope_half(x, positions, theta):
    """x [T, H, rot]: pairs (i, i + rot / 2) turn by positions * theta^(-2i/rot)."""
    rot = x.shape[-1]
    inv = theta ** (-np.arange(0, rot, 2, dtype=np.float64) / rot)
    angle = positions.astype(np.float64)[:, None] * inv
    cos, sin = np.cos(angle)[:, None, :], np.sin(angle)[:, None, :]
    x1, x2 = x[..., : rot // 2], x[..., rot // 2:]
    return np.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def full_layer(h, get, cfg, att):
    t = h.shape[0]
    heads, kv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    rot = int(hd * cfg["partial_rotary_factor"])
    eps, positions = cfg["rms_norm_eps"], np.arange(t)
    qg = (h @ get(f"{att}.q_proj.weight").T).reshape(t, heads, 2 * hd)  # query | gate
    q, gate = qg[..., :hd], qg[..., hd:]
    k = (h @ get(f"{att}.k_proj.weight").T).reshape(t, kv, hd)
    v = (h @ get(f"{att}.v_proj.weight").T).reshape(t, kv, hd)
    q = rms0(q, get(f"{att}.q_norm.weight"), eps)
    k = rms0(k, get(f"{att}.k_norm.weight"), eps)
    q = np.concatenate([rope_half(q[..., :rot], positions, cfg["rope_theta"]), q[..., rot:]], -1)
    k = np.concatenate([rope_half(k[..., :rot], positions, cfg["rope_theta"]), k[..., rot:]], -1)
    k, v = np.repeat(k, heads // kv, axis=1), np.repeat(v, heads // kv, axis=1)
    scores = np.einsum("qhd,khd->hqk", q, k) * hd ** -0.5
    scores = np.where(np.tril(np.ones((t, t), bool)), scores, -np.inf)
    probs = np.exp(scores - scores.max(axis=-1, keepdims=True))
    probs /= probs.sum(axis=-1, keepdims=True)
    ctx = np.einsum("hqk,khd->qhd", probs, v) * sigmoid(gate)
    return ctx.reshape(t, heads * hd) @ get(f"{att}.o_proj.weight").T


def route(x, gate_weight, k):
    """softmax over the router's experts; the k largest, divided by their sum."""
    logits = x @ gate_weight.T
    p = np.exp(logits - logits.max(axis=1, keepdims=True))
    p /= p.sum(axis=1, keepdims=True)
    chosen = np.argsort(-p, axis=1, kind="stable")[:, :k]
    weight = np.take_along_axis(p, chosen, axis=1)
    return chosen, weight / weight.sum(axis=1, keepdims=True)


def sparse_half(h, state, get, cfg, base, experts=None, shared=True):
    """Σ over a token's chosen experts that are in ``experts`` (default: every
    expert the checkpoint names) + the gated shared expert (when ``shared``)."""
    if experts is None:
        experts = [
            e for e in range(cfg["num_experts"])
            if f"{base}.mlp.experts.{e}.gate_proj.weight" in state
        ]
    chosen, weight = route(h, get(f"{base}.mlp.gate.weight"), cfg["num_experts_per_tok"])
    out = np.zeros_like(h)
    here = set(experts)
    for token in range(h.shape[0]):
        for e, w in zip(chosen[token], weight[token]):
            if int(e) in here:
                out[token] += w * swiglu(h[token], get, f"{base}.mlp.experts.{int(e)}")
    if shared:
        opened = sigmoid(h @ get(f"{base}.mlp.shared_expert_gate.weight").T)
        out += opened * swiglu(h, get, f"{base}.mlp.shared_expert")
    return out


def hidden(state, cfg: dict, ids, experts=None, shared=True, caches=None):
    """The forward up to the final norm's input: [T, hidden].  ``caches``, a
    list, receives each linear layer's (tail, state) after the last token."""
    def get(name):
        return np.asarray(state[name]).astype(np.float64)

    eps = cfg["rms_norm_eps"]
    x = get("model.embed_tokens.weight")[np.asarray(ids)]
    for i in range(cfg["num_hidden_layers"]):
        base = f"model.layers.{i}"
        h = rms0(x, get(f"{base}.input_layernorm.weight"), eps)
        if (i + 1) % cfg["full_attention_interval"] == 0:
            x = x + full_layer(h, get, cfg, f"{base}.self_attn")
        else:
            out, cache = linear_layer(h, get, cfg, f"{base}.linear_attn")
            x = x + out
            if caches is not None:
                caches.append(cache)
        h = rms0(x, get(f"{base}.post_attention_layernorm.weight"), eps)
        x = x + sparse_half(h, state, get, cfg, base, experts, shared)
    return x


def logits(state, cfg: dict, ids, **kwargs) -> np.ndarray:
    x = hidden(state, cfg, ids, **kwargs)
    norm = np.asarray(state["model.norm.weight"]).astype(np.float64)
    head = np.asarray(state["lm_head.weight"]).astype(np.float64)
    return rms0(x, norm, cfg["rms_norm_eps"]) @ head.T
