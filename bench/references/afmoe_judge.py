"""Plain reference: the ``afmoe`` decoder as a judge reads a ballot.

Written from the model's configuration (arcee-ai/Trinity-Large-Preview
``config.json``) and the equations below in float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``.  No kernel, no cache, no batch,
nothing of the program: it reads the seeded checkpoint by its HuggingFace
names, one layer at a time (``read_checkpoint`` opens a tensor when it is asked
for), tokenizes for itself and builds each call's ballot for itself from the
request and the call's seed (the ballot and the tokens are the protocol's, not
the model's: they are the first judge's reference's,
``references/glm4_moe_lite_judge.py``, taken as they are).

``rms(x, w) = x / sqrt(mean(x^2) + eps) · w``, eps 1e-5.  Served layer i is
the PUBLISHED layer ``n = layers_served[i]`` and is named so in the checkpoint
(``model.layers.n``); it is of the kind ``layer_types[n]`` and dense where
i < ``num_dense_layers`` (the stage's count: its dense layers lead).

  x0      = embed[ids] · sqrt(hidden_size)                 mup_enabled
  per layer:
    h     = rms(x, input_layernorm)
    q, k, v = q_proj · h, k_proj · h, v_proj · h           48 | 8 | 8 heads of 128
    g     = gate_proj · h                                  [48 x 128]
    q, k  = rms(q, q_norm), rms(k, k_norm)                 a head, over its 128 dims
    sliding: q, k = rope(q), rope(k)      all 128 dims, pairs (i, i + 64), theta 1e4
             a = softmax over t - 4096 < s <= t of (q · k / sqrt(128)) v
    full:    no turn at all;  a = softmax over s <= t of (q · k / sqrt(128)) v
             a key head serves 6 query heads
    x     = x + rms(o_proj · (a · sigmoid(g)), post_attention_layernorm)
    h     = rms(x, pre_mlp_layernorm)
    dense:   m = SwiGLU(h)
    sparse:  s = sigmoid(router.gate · h) over the ROUTER's experts; the top 4 of
             s + expert_bias;  p = s[chosen] / (Σ s[chosen] + 1e-20) · route_scale
             m = Σ_{e chosen, e < held} p_e · SwiGLU_e(h) + SwiGLU_shared(h)
    x     = x + rms(m, post_mlp_layernorm)
  logits  = lm_head · rms(x, norm)

THE SHARE.  The checkpoint names experts 0..held-1 of a router
``num_experts_routed`` wide (``cfg["num_experts"]`` is ``held``): what the
experts elsewhere would add is left out here as in the program, and the partial
sum goes on to the next layer.

Attention goes in blocks of queries against all keys, every head at once
(48 heads x 256 queries x 16,640 keys of float32 are 0.8 GB; the whole square
would be 51), under a mask of whole rows: a sliding layer's row t marks
t - window < s <= t, a full layer's s <= t.  The (token, expert) pairs are
sorted by expert in blocks of tokens and each held expert's products run over
its own rows and over no others (``jax.lax.ragged_dot``; the pairs elsewhere
are a last group whose expert is all zeros).

Given a call's prompt plus the key letter the PROGRAM chose, ONE forward over
T + 1 positions gives the first level's logits at position T - 1 and the
second level's at position T: the program's second read came through one row
against a window of cached, turned keys on the sliding layers and against
every cached key on the full ones, so this is prefill-then-decode through both
kinds of cache against the full forward pass.
"""

from __future__ import annotations

import importlib.util
import os

import numpy as np


def _beside(name: str):
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), name + ".py")
    spec = importlib.util.spec_from_file_location("bench_references__" + name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


_protocol = _beside("glm4_moe_lite_judge")
ALPHABET = _protocol.ALPHABET
ballot, key_ids, call_ids, letter_id = (
    _protocol.ballot, _protocol.key_ids, _protocol.call_ids, _protocol.letter_id
)
QUERY_BLOCK = 256
TOKEN_BLOCK = 256
ROUTE_EPS = 1e-20
SLIDING = "sliding_attention"


def published(cfg: dict, layer: int) -> int:
    """The published number of served layer ``layer``: its name in the checkpoint."""
    return cfg["layers_served"][layer]


def kind_of(cfg: dict, layer: int) -> str:
    return cfg["layer_types"][published(cfg, layer)]


def is_dense(cfg: dict, layer: int) -> bool:
    return layer < cfg["num_dense_layers"]


def _rms(x, weight, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def _rope(x, theta):
    """x [T, heads, d], position = row: pairs (i, i + d / 2), every dim turns."""
    import jax.numpy as jnp

    t, d = x.shape[0], x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _swiglu(x, gate, up, down):
    import jax

    return (jax.nn.silu(x @ gate.T) * (x @ up.T)) @ down.T


def _make(cfg: dict):
    """(layer, head, sparse): ``layer(x, p, mlp, kind)`` runs one layer of
    ``kind`` over one sequence; ``sparse(h, mlp)`` is a sparse layer's MLP
    alone (before its norm)."""
    import jax
    import jax.numpy as jnp

    eps, hidden = cfg["rms_norm_eps"], cfg["hidden_size"]
    heads, kv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    window, theta = cfg["sliding_window"], float(cfg["rope_theta"])
    held, k_top, scale = cfg["num_experts"], cfg["num_experts_per_tok"], cfg["route_scale"]

    def attention(x, p, kind):
        t = x.shape[0]
        h = _rms(x, p["input_norm"], eps)
        q = _rms((h @ p["q"].T).reshape(t, heads, hd), p["q_norm"], eps)
        k = _rms((h @ p["k"].T).reshape(t, kv, hd), p["k_norm"], eps)
        v = (h @ p["v"].T).reshape(t, kv, hd)
        if kind == SLIDING:
            q, k = _rope(q, theta), _rope(k, theta)
        q = q.reshape(t, kv, heads // kv, hd)
        block = min(QUERY_BLOCK, t)

        def one(start):
            qb = jax.lax.dynamic_slice_in_dim(q, start, block)
            scores = jnp.einsum("qgrd,kgd->grqk", qb, k) / jnp.sqrt(jnp.float32(hd))
            rows, cols = start + jnp.arange(block)[:, None], jnp.arange(t)[None, :]
            seen = cols <= rows
            if kind == SLIDING:
                seen = seen & (cols > rows - window)
            scores = jnp.where(seen, scores, -jnp.inf)
            return jnp.einsum("grqk,kgd->qgrd", jax.nn.softmax(scores, axis=-1), v)

        a = jax.lax.map(one, jnp.arange(0, t, block)).reshape(t, heads * hd)
        a = a * jax.nn.sigmoid(h @ p["g"].T)
        return x + _rms(a @ p["o"].T, p["post_attn_norm"], eps)

    def sparse(h, p):
        """Over blocks of tokens: the chosen experts that are held (e < held),
        weighed, + the shared expert."""

        def one(hb):
            score = jax.nn.sigmoid(hb @ p["router"].T)
            _, chosen = jax.lax.top_k(score + p["bias"], k_top)
            weight = jnp.take_along_axis(score, chosen, axis=1)
            weight = weight / (jnp.sum(weight, axis=1, keepdims=True) + ROUTE_EPS) * scale
            # the pairs elsewhere: one last group, whose expert is all zeros
            expert_of_pair = jnp.minimum(chosen.reshape(-1), held)
            order = jnp.argsort(expert_of_pair, stable=True)
            sizes = jnp.zeros((held + 1,), jnp.int32).at[expert_of_pair].add(1)
            rows = hb[order // k_top]

            def product(x, w):  # w [held + 1, in, out]
                return jax.lax.ragged_dot(x, w, sizes)

            y = product(jax.nn.silu(product(rows, p["e_gate"])) * product(rows, p["e_up"]), p["e_down"])
            y = y * weight.reshape(-1)[order][:, None]
            return jnp.zeros_like(hb).at[order // k_top].add(y)

        block = min(TOKEN_BLOCK, h.shape[0])
        routed = jax.lax.map(one, h.reshape(-1, block, h.shape[1])).reshape(h.shape)
        return routed + _swiglu(h, p["s_gate"], p["s_up"], p["s_down"])

    def second_half(x, mlp):
        h = _rms(x, mlp["pre_mlp_norm"], eps)
        if "router" in mlp:
            m = sparse(h, mlp)
        else:
            m = _swiglu(h, mlp["d_gate"], mlp["d_up"], mlp["d_down"])
        return x + _rms(m, mlp["post_mlp_norm"], eps)

    @jax.jit
    def full_layer(x, p, mlp):
        return second_half(attention(x, p, "full_attention"), mlp)

    @jax.jit
    def sliding_layer(x, p, mlp):
        return second_half(attention(x, p, SLIDING), mlp)

    def layer(x, p, mlp, kind):
        return (sliding_layer if kind == SLIDING else full_layer)(x, p, mlp)

    @jax.jit
    def embed(table, ids):
        x = table[ids]
        return x * jnp.sqrt(jnp.float32(hidden)) if cfg["mup_enabled"] else x

    @jax.jit
    def head(x, rows, norm, weight, ids):
        return (_rms(x[rows], norm, eps) @ weight.T)[:, ids]

    return layer, head, jax.jit(sparse), embed


_FUNCTIONS: dict = {}


def functions(cfg: dict):
    key = tuple(sorted((k, v) for k, v in cfg.items() if isinstance(v, (int, float))))
    if key not in _FUNCTIONS:
        _FUNCTIONS[key] = _make(cfg)
    return _FUNCTIONS[key]


def layer_weights(state, cfg: dict, i: int):
    """(attention weights, the second half's weights) of served layer i,
    float32, by their HuggingFace names."""
    import jax.numpy as jnp

    def f32(name):
        return jnp.asarray(np.asarray(state[name])).astype(jnp.float32)

    def swiglu_weights(base, prefix):
        return {f"{prefix}_{k}": f32(f"{base}.{k}_proj.weight") for k in ("gate", "up", "down")}

    def stacked(base, kind):
        """The held experts [in, out] and, last, an expert of zeros: where the
        pairs routed elsewhere go."""
        held = [
            np.ascontiguousarray(
                np.asarray(state[f"{base}.mlp.experts.{e}.{kind}_proj.weight"]).T
            )
            for e in range(cfg["num_experts"])
        ]
        return jnp.asarray(np.stack(held + [np.zeros_like(held[0])])).astype(jnp.float32)

    base = f"model.layers.{published(cfg, i)}"
    att = f"{base}.self_attn"
    p = {
        "input_norm": f32(f"{base}.input_layernorm.weight"),
        "post_attn_norm": f32(f"{base}.post_attention_layernorm.weight"),
        **{k: f32(f"{att}.{k}_proj.weight") for k in ("q", "k", "v", "o")},
        "g": f32(f"{att}.gate_proj.weight"),
        "q_norm": f32(f"{att}.q_norm.weight"),
        "k_norm": f32(f"{att}.k_norm.weight"),
    }
    mlp = {
        "pre_mlp_norm": f32(f"{base}.pre_mlp_layernorm.weight"),
        "post_mlp_norm": f32(f"{base}.post_mlp_layernorm.weight"),
    }
    if is_dense(cfg, i):
        return p, {**mlp, **swiglu_weights(f"{base}.mlp", "d")}
    return p, {
        **mlp,
        "router": f32(f"{base}.mlp.router.gate.weight"),
        "bias": f32(f"{base}.mlp.expert_bias"),
        **{f"e_{kind}": stacked(base, kind) for kind in ("gate", "up", "down")},
        **swiglu_weights(f"{base}.mlp.shared_experts", "s"),
    }


def hidden_states(state, cfg: dict, sequences: list) -> list:
    """Each sequence of token ids through every layer: [T_padded, hidden]
    float32 before the final norm, a sequence padded with token 0 up to a
    whole block (a padded position is past every real one, so no real query
    sees it).  Every sequence goes through a layer before the next layer's
    weights are read."""
    import jax
    import jax.numpy as jnp

    layer, _, _, embed = functions(cfg)
    with jax.default_matmul_precision("highest"):
        width = -(-max(len(ids) for ids in sequences) // QUERY_BLOCK) * QUERY_BLOCK
        table = jnp.asarray(np.asarray(state["model.embed_tokens.weight"])).astype(jnp.float32)
        xs = []
        for ids in sequences:
            padded = np.zeros((width,), np.int32)
            padded[: len(ids)] = ids
            xs.append(embed(table, jnp.asarray(padded)))
        del table
        for i in range(cfg["num_hidden_layers"]):
            p, mlp = layer_weights(state, cfg, i)
            for j in range(len(xs)):  # a sequence's old state goes as its new one comes
                xs[j] = layer(xs[j], p, mlp, kind_of(cfg, i))
            del p, mlp
    return xs


def read_logits(state, cfg: dict, calls: list, letter_ids: list) -> list:
    """``calls`` is [(ids, rows)]: token ids of one sequence and the positions
    to read.  Returns, per call, logits [len(rows), len(letter_ids)] at those
    positions for those token ids, float64 on the host."""
    import jax
    import jax.numpy as jnp

    if not calls:
        return []
    _, head, _, _ = functions(cfg)
    xs = hidden_states(state, cfg, [ids for ids, _ in calls])
    with jax.default_matmul_precision("highest"):
        norm = jnp.asarray(np.asarray(state["model.norm.weight"])).astype(jnp.float32)
        weight = jnp.asarray(np.asarray(state["lm_head.weight"])).astype(jnp.float32)
        ids = jnp.asarray(np.asarray(letter_ids, np.int32))
        return [
            np.asarray(
                head(x, jnp.asarray(np.asarray(rows, np.int32)), norm, weight, ids), np.float64
            )
            for x, (_, rows) in zip(xs, calls)
        ]
