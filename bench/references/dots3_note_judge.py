"""Plain reference: the ``dots3_note`` decoder as a judge reads a ballot.

Written from the model's configuration (dots-studio/dots3-note-prev
``config.json``) and the layer as ISSUE 39 reads it, in float32 ``jax.numpy``
under ``jax.default_matmul_precision("highest")``.  No kernel, no cache,
nothing of the program: it reads the seeded checkpoint by its HuggingFace
names, one layer at a time, tokenizes for itself and builds each call's ballot
for itself from the request and the call's seed (ballot and tokens are the
protocol's, not the model's: the first judge's reference's,
``glm4_moe_lite_judge.py`` beside this file, taken as they are).

  x0 = embed[ids]
  per layer i, of kind K = layer_types[layers_served[i]], h = rms(x):
    geometry   full_attention:    the plain keys (128 heads, q_lora 1024, kv_lora 512,
                                  nope 128 | rope 64, v 128, theta 8e7)
               sliding_attention: the swa_* keys (64 heads, q_lora 1024, kv_lora 1024,
                                  nope 192 | rope 64, v 128, theta 5e4)
    c_q   = sqrt(hidden / q_lora) rms(W_qa h)                apply_mla_qkv_lora_rescale
    c, kr = W_kva h ;  c = sqrt(hidden / kv_lora) rms(c)     kr is not rescaled
    q     = W_qb c_q ;  k_nope, v = W_kvb c
    the rope dims of q and kr turn in interleaved pairs (2i, 2i+1) by
    position * theta_K^(-2i/rope); kr is one key for every head
    A_t   = full:    lax.top_k over s <= t of the indexer's score, min(index_topk, t + 1) keys
                     q_I = wq_b c_q (64 heads of 128), k_I = LayerNorm(wk h; eps 1e-6), the
                     first rope dims of each turned (theta of the full kind),
                     w = weights_proj h * heads^-1/2 * dim^-1/2,
                     score[t, s] = sum_j w[t, j] ReLU(q_I[t, j] . k_I[s])
            sliding: t - sliding_window_size < s <= t        (513: itself and 512 before)
    a_j   = softmax over s in A_t of (q_nope.k_nope + q_rope.kr) / sqrt(nope + rope) v_j
    g     = sigmoid(W_g h)                                   a scalar a head
    x     = x + W_o concat_j(g_j a_j)
    h     = rms(x)
    x     = x + SwiGLU(h)                                    published layer < first_k_dense_replace
    x     = x + sum_{e in top8(s + bias), e < held} s_e / sum s . factor . SwiGLU_e(h)
              + SwiGLU_shared(h),  s = sigmoid(W_gate h)     the others
  logits = W_head rms(x)

THE SHARE.  The checkpoint names experts 0..held-1 (``cfg["n_routed_experts"]``)
of a router ``cfg["n_routed_experts_routed"]`` wide: the top 8 are chosen over
the whole router and weighed by their sum, what the experts elsewhere would add
is left out here as in the program, and the partial sum goes on to the next
layer.

Attention and the indexer go in blocks of queries against ALL keys under a
whole mask row (a window layer's too: the band is a mask, nothing is skipped),
so that 8k positions fit; the experts go in blocks of tokens, a block's pairs
sorted by expert (``jax.lax.ragged_dot``; the pairs elsewhere are a last group
whose expert is all zeros).

Given a call's prompt plus the key letter the PROGRAM chose, ONE forward over
T + 1 positions gives the first level's logits at position T - 1 and the
second level's at position T: the program's second read came through its four
kinds of cache on the absorbed path, so this is prefill-then-decode against
the full forward pass.
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
INDEX_NORM_EPS = 1e-6
SLIDING = "sliding_attention"
# what a test may leave out of the mathematics, to see that it matters
WHOLE = {"gate": True, "rescale": True, "window": True}


def kind_of(cfg: dict, layer: int) -> str:
    return cfg["layer_types"][cfg["layers_served"][layer]]


def is_dense(cfg: dict, layer: int) -> bool:
    return cfg["layers_served"][layer] < cfg["first_k_dense_replace"]


def _rms(x, weight, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def _rope(x, theta):
    """x [T, ..., d], position = row: interleaved pairs (2i, 2i + 1)."""
    import jax.numpy as jnp

    t, d = x.shape[0], x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = (jnp.arange(t, dtype=jnp.float32)[:, None] * inv).reshape(
        (t,) + (1,) * (x.ndim - 2) + (d // 2,)
    )
    even, odd = x[..., 0::2], x[..., 1::2]
    pairs = [even * jnp.cos(angle) - odd * jnp.sin(angle), odd * jnp.cos(angle) + even * jnp.sin(angle)]
    return jnp.stack(pairs, axis=-1).reshape(x.shape)


def _swiglu(x, gate, up, down):
    import jax

    return (jax.nn.silu(x @ gate.T) * (x @ up.T)) @ down.T


def _make(cfg: dict, parts: dict):
    """(layer, head, sparse): ``layer(x, p, mlp, kind)`` runs one layer of
    ``kind`` over one sequence and returns (x, the [T, T] mask it attended
    under); ``sparse(h, mlp)`` is a sparse layer's second half alone."""
    import jax
    import jax.numpy as jnp

    eps, hidden = cfg["rms_norm_eps"], cfg["hidden_size"]
    held, k_top = cfg["n_routed_experts"], cfg["num_experts_per_tok"]
    factor = cfg["routed_scaling_factor"]
    i_heads, i_dim, i_top = cfg["index_n_heads"], cfg["index_head_dim"], cfg["index_topk"]
    window = cfg["sliding_window_size"]
    full_theta, full_rope = float(cfg["rope_theta"]), cfg["qk_rope_head_dim"]

    def shapes(kind):
        swa = "swa_" if kind == SLIDING else ""
        return tuple(
            cfg[swa + key] for key in (
                "num_attention_heads", "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
                "qk_rope_head_dim", "v_head_dim", "rope_theta",
            )
        )

    def query_blocks(t):
        size = min(QUERY_BLOCK, t)
        return size, jnp.arange(0, t, size)

    def selected(h, cq, p):
        """[T, T] bool: row t marks the min(index_topk, t + 1) keys at or
        before t that the indexer scores highest."""
        t = h.shape[0]
        q = (cq @ p["i_q"].T).reshape(t, i_heads, i_dim)
        q = jnp.concatenate([_rope(q[..., :full_rope], full_theta), q[..., full_rope:]], axis=-1)
        k = h @ p["i_k"].T
        k = k - jnp.mean(k, axis=-1, keepdims=True)
        k = k / jnp.sqrt(jnp.mean(k * k, axis=-1, keepdims=True) + INDEX_NORM_EPS)
        k = k * p["i_k_norm"] + p["i_k_bias"]
        k = jnp.concatenate([_rope(k[:, :full_rope], full_theta), k[:, full_rope:]], axis=-1)
        w = (h @ p["i_w"].T) * (i_heads**-0.5 * i_dim**-0.5)
        block, starts = query_blocks(t)

        def one(start):
            qb = jax.lax.dynamic_slice_in_dim(q, start, block)
            wb = jax.lax.dynamic_slice_in_dim(w, start, block)
            score = jnp.einsum("qj,jqk->qk", wb, jax.nn.relu(jnp.einsum("qjd,kd->jqk", qb, k)))
            rows = start + jnp.arange(block)[:, None]
            score = jnp.where(jnp.arange(t)[None, :] <= rows, score, -jnp.inf)
            value, index = jax.lax.top_k(score, min(i_top, t))
            # a query before position index_topk has fewer keys than that:
            # what top_k adds from past it (-inf) is not chosen
            return jnp.zeros((block, t), bool).at[jnp.arange(block)[:, None], index].set(
                value > -jnp.inf
            )

        return jax.lax.map(one, starts).reshape(t, t)

    def banded(t):
        """[T, T] bool: row t marks t - window < s <= t."""
        rows, cols = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
        seen = cols <= rows
        return seen & (cols > rows - window) if parts["window"] else seen

    def attention(x, p, kind):
        t = x.shape[0]
        heads, q_rank, kv_rank, nope, rope, dv, theta = shapes(kind)
        a_q = (hidden / q_rank) ** 0.5 if parts["rescale"] else 1.0
        a_kv = (hidden / kv_rank) ** 0.5 if parts["rescale"] else 1.0
        h = _rms(x, p["input_norm"], eps)
        cq = a_q * _rms(h @ p["q_a"].T, p["q_a_norm"], eps)
        seen = banded(t) if kind == SLIDING else selected(h, cq, p)
        q = (cq @ p["q_b"].T).reshape(t, heads, nope + rope)
        q_nope, q_rope = q[..., :nope], _rope(q[..., nope:], theta)
        kv = h @ p["kv_a"].T
        c = a_kv * _rms(kv[:, :kv_rank], p["kv_a_norm"], eps)
        k_rope = _rope(kv[:, kv_rank:], theta)
        kvb = (c @ p["kv_b"].T).reshape(t, heads, nope + dv)
        k_nope, v = kvb[..., :nope], kvb[..., nope:]
        block, starts = query_blocks(t)

        def one(start):
            qn = jax.lax.dynamic_slice_in_dim(q_nope, start, block)
            qr = jax.lax.dynamic_slice_in_dim(q_rope, start, block)
            row = jax.lax.dynamic_slice_in_dim(seen, start, block)
            scores = jnp.einsum("qhd,khd->hqk", qn, k_nope) + jnp.einsum("qhd,kd->hqk", qr, k_rope)
            scores = jnp.where(row[None], scores / jnp.sqrt(jnp.float32(nope + rope)), -jnp.inf)
            return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)

        a = jax.lax.map(one, starts).reshape(t, heads, dv)
        if parts["gate"]:
            a = a * jax.nn.sigmoid(h @ p["g"].T)[..., None]
        x = x + a.reshape(t, heads * dv) @ p["o"].T
        return x, _rms(x, p["post_norm"], eps), seen

    def sparse(h, p):
        """Over blocks of tokens: the chosen experts that are held (e < held),
        weighed, + the shared expert."""

        def one(hb):
            score = jax.nn.sigmoid(hb @ p["gate"].T)
            _, chosen = jax.lax.top_k(score + p["bias"], k_top)
            weight = jnp.take_along_axis(score, chosen, axis=1)
            weight = weight / jnp.sum(weight, axis=1, keepdims=True) * factor
            # the pairs elsewhere: one last group, whose expert is all zeros
            expert_of_pair = jnp.minimum(chosen.reshape(-1), held)
            order = jnp.argsort(expert_of_pair, stable=True)
            sizes = jnp.zeros((held + 1,), jnp.int32).at[expert_of_pair].add(1)
            rows = hb[order // k_top]

            def product(x, w):  # w [held + 1, out, in]: the checkpoint's layout
                return jax.lax.ragged_dot(x, jnp.swapaxes(w, 1, 2), sizes)

            y = product(jax.nn.silu(product(rows, p["e_gate"])) * product(rows, p["e_up"]), p["e_down"])
            y = y * weight.reshape(-1)[order][:, None]
            return jnp.zeros_like(hb).at[order // k_top].add(y)

        block = min(TOKEN_BLOCK, h.shape[0])
        routed = jax.lax.map(one, h.reshape(-1, block, h.shape[1])).reshape(h.shape)
        return routed + _swiglu(h, p["s_gate"], p["s_up"], p["s_down"])

    @jax.jit
    def full_layer(x, p, mlp):
        return after(*attention(x, p, "full_attention"), mlp)

    @jax.jit
    def sliding_layer(x, p, mlp):
        return after(*attention(x, p, SLIDING), mlp)

    def after(x, h, seen, mlp):
        if "gate" in mlp:
            return x + sparse(h, mlp), seen
        return x + _swiglu(h, mlp["d_gate"], mlp["d_up"], mlp["d_down"]), seen

    def layer(x, p, mlp, kind):
        return (sliding_layer if kind == SLIDING else full_layer)(x, p, mlp)

    @jax.jit
    def head(x, rows, norm, weight, ids):
        return (_rms(x[rows], norm, eps) @ weight.T)[:, ids]

    return layer, head, jax.jit(sparse)


_FUNCTIONS: dict = {}


def functions(cfg: dict, **left_out):
    """``left_out`` (tests): gate=False, rescale=False or window=False runs
    the same forward without that part."""
    parts = {**WHOLE, **left_out}
    key = tuple(sorted((k, v) for k, v in cfg.items() if isinstance(v, (int, float))))
    key += tuple(sorted(parts.items()))
    if key not in _FUNCTIONS:
        _FUNCTIONS[key] = _make(cfg, parts)
    return _FUNCTIONS[key]


def layer_weights(state, cfg: dict, i: int):
    """(attention weights, the second half's weights) of layer i, float32, by
    their HuggingFace names."""
    import jax.numpy as jnp

    def f32(name):
        return jnp.asarray(np.asarray(state[name])).astype(jnp.float32)

    def swiglu_weights(base, prefix):
        return {f"{prefix}_{k}": f32(f"{base}.{k}_proj.weight") for k in ("gate", "up", "down")}

    def stacked(base, kind):
        """The held experts and, last, an expert of zeros: where the pairs
        routed elsewhere go."""
        held = [
            jnp.asarray(np.asarray(state[f"{base}.mlp.experts.{e}.{kind}_proj.weight"]))
            for e in range(cfg["n_routed_experts"])
        ]
        return jnp.stack(held + [jnp.zeros_like(held[0])]).astype(jnp.float32)

    base = f"model.layers.{i}"
    att = f"{base}.self_attn"
    p = {
        "input_norm": f32(f"{base}.input_layernorm.weight"),
        "post_norm": f32(f"{base}.post_attention_layernorm.weight"),
        "q_a": f32(f"{att}.q_a_proj.weight"),
        "q_a_norm": f32(f"{att}.q_a_layernorm.weight"),
        "q_b": f32(f"{att}.q_b_proj.weight"),
        "kv_a": f32(f"{att}.kv_a_proj_with_mqa.weight"),
        "kv_a_norm": f32(f"{att}.kv_a_layernorm.weight"),
        "kv_b": f32(f"{att}.kv_b_proj.weight"),
        "o": f32(f"{att}.o_proj.weight"),
        "g": f32(f"{att}.g_proj.weight"),
    }
    if kind_of(cfg, i) != SLIDING:
        p.update(
            i_q=f32(f"{att}.indexer.wq_b.weight"), i_k=f32(f"{att}.indexer.wk.weight"),
            i_k_norm=f32(f"{att}.indexer.k_norm.weight"),
            i_k_bias=f32(f"{att}.indexer.k_norm.bias"),
            i_w=f32(f"{att}.indexer.weights_proj.weight"),
        )
    if is_dense(cfg, i):
        return p, swiglu_weights(f"{base}.mlp", "d")
    return p, {
        "gate": f32(f"{base}.mlp.gate.weight"),
        "bias": f32(f"{base}.mlp.gate.e_score_correction_bias"),
        **{f"e_{kind}": stacked(base, kind) for kind in ("gate", "up", "down")},
        **swiglu_weights(f"{base}.mlp.shared_experts", "s"),
    }


def hidden_states(
    state, cfg: dict, sequences: list, selections: list | None = None, **left_out
) -> list:
    """Each sequence of token ids through every layer: [T_padded, hidden]
    float32 before the final norm, a sequence padded with token 0 up to a
    whole block (a padded position is past every real one, so no real query
    sees it).  Every sequence goes through a layer before the next layer's
    weights are read.  ``selections``, a list handed in, receives a layer's
    [T, T] masks, a list a layer (tests)."""
    import jax
    import jax.numpy as jnp

    layer, _, _ = functions(cfg, **left_out)
    with jax.default_matmul_precision("highest"):
        width = -(-max(len(ids) for ids in sequences) // QUERY_BLOCK) * QUERY_BLOCK
        embed = jnp.asarray(np.asarray(state["model.embed_tokens.weight"])).astype(jnp.float32)
        xs = []
        for ids in sequences:
            padded = np.zeros((width,), np.int32)
            padded[: len(ids)] = ids
            xs.append(embed[jnp.asarray(padded)])
        del embed
        for i in range(cfg["num_hidden_layers"]):
            p, mlp = layer_weights(state, cfg, i)
            seen = []
            for j in range(len(xs)):  # a sequence's old state goes as its new one comes
                xs[j], mask = layer(xs[j], p, mlp, kind_of(cfg, i))
                if selections is not None:
                    seen.append(np.asarray(mask))
                del mask
            if selections is not None:
                selections.append(seen)
            del p, mlp
    return xs


def read_logits(state, cfg: dict, calls: list, letter_ids: list, **left_out) -> list:
    """``calls`` is [(ids, rows)]: token ids of one sequence and the positions
    to read.  Returns, per call, logits [len(rows), len(letter_ids)] at those
    positions for those token ids, float64 on the host."""
    import jax
    import jax.numpy as jnp

    if not calls:
        return []
    _, head, _ = functions(cfg, **left_out)
    xs = hidden_states(state, cfg, [ids for ids, _ in calls], **left_out)
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
