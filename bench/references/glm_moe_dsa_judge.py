"""Plain reference: the ``glm_moe_dsa`` decoder as a judge reads a ballot.

Written from the model's configuration (zai-org/GLM-5.2 ``config.json``;
latent attention and router as the DeepSeek-V3 layout has them, the learned
sparse selection as the DeepSeek-V3.2 ``Indexer`` has it) in float32
``jax.numpy`` under ``jax.default_matmul_precision("highest")``.  No kernel,
no cache, nothing of the program: it reads the seeded checkpoint by its
HuggingFace names, one layer at a time, tokenizes for itself and builds each
call's ballot for itself from the request and the call's seed (ballot and
tokens are the protocol's, not the model's: they are the first judge's
reference's, ``glm4_moe_lite_judge.py`` beside this file, taken as they are).

  x0      = embed[ids]
  per layer, h = rms(x):
    c_q   = rms(W_qa h) ;  q = W_qb c_q                   64 heads of 192 | 64
    c, kr = W_kva h ;  c = rms(c) ;  k_nope, v = W_kvb c  192 | 256 a head
    the 64 rotary dims turn in interleaved pairs (2i, 2i+1) by
    position * theta^(-2i/64); kr is one key for every head
    a layer whose ``indexer_types`` entry is "full" (``self_attn.indexer``):
      q_I   = wq_b c_q                                    32 heads of 128
      k_I   = LayerNorm(wk h; k_norm, eps 1e-6)           ONE key of 128
      the FIRST 64 dims of an index head and of the key turn, interleaved
      w     = weights_proj h * 32^-1/2 * 128^-1/2
      score[t, s] = sum_j w[t, j] ReLU(q_I[t, j] . k_I[s])      s <= t
      S_t   = lax.top_k(score[t, :t + 1], min(index_topk, t + 1))
    a "shared" layer: S_t is the last "full" layer's
    a     = softmax over s in S_t of (q_nope.k_nope + q_rope.kr) / 16
    x     = x + W_o (a v)
    h     = rms(x)
    x     = x + SwiGLU(h)                                 a dense layer
    x     = x + sum_{e in top8(s + bias), e < held} 2.5 s_e / sum s . SwiGLU_e(h)
              + SwiGLU_shared(h),  s = sigmoid(W_g h)     a sparse layer
  logits  = W_head rms(x)

THE SHARE.  The checkpoint names experts 0..held-1 (``cfg["n_routed_experts"]``)
of a router ``cfg["n_routed_experts_routed"]`` wide: the top 8 are chosen over
the whole router and weighed by their sum, what the experts elsewhere would
add is left out here as in the program, and the partial sum goes on to the
next layer.  Which layers are dense is ``mlp_layer_types`` and which own an
indexer ``indexer_types``, the published lists: layer i of the checkpoint is
published layer ``layers_served[i]``.

Departures from the published inference code, the same as the program's and
the configuration's ``assumed``: its Hadamard rotation of q_I and k_I
(orthogonal: every q_I . k_I is what it was) and its fp8 quantisation of them
are left out; no multi-token-prediction layer; decoding is constrained to the
ballot's keys.

Attention and the indexer go in blocks of queries against all keys, so that
8k positions fit; a block's choice is scattered into a [block, T] mask from
``lax.top_k``'s indices.  The experts go in blocks of tokens: a block's
(token, choice) pairs are sorted by expert and each held expert's products
run over its own rows (``jax.lax.ragged_dot``; the pairs elsewhere are a last
group whose expert is all zeros).

Given a call's prompt plus the key letter the PROGRAM chose, ONE forward over
T + 1 positions gives the first level's logits at position T - 1 and the
second level's at position T: the program's second read came through its
three caches on the absorbed path with a selection of its own, so this is
prefill-then-decode against the full forward pass.
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


def _rms(x, weight, eps):
    import jax.numpy as jnp

    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)) * weight


def _rope(x, theta):
    """x [T, ..., d], position = row: interleaved pairs."""
    import jax.numpy as jnp

    t, d = x.shape[0], x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv
    shape = (t,) + (1,) * (x.ndim - 2) + (d // 2,)
    cos, sin = jnp.cos(angle).reshape(shape), jnp.sin(angle).reshape(shape)
    even, odd = x[..., 0::2], x[..., 1::2]
    turned = jnp.stack([even * cos - odd * sin, odd * cos + even * sin], axis=-1)
    return turned.reshape(x.shape)


def _swiglu(x, gate, up, down):
    import jax

    return (jax.nn.silu(x @ gate.T) * (x @ up.T)) @ down.T


def kind_of(cfg: dict, which: str, layer: int) -> str:
    """A served layer's entry of a published list."""
    return cfg[which][cfg["layers_served"][layer]]


def theta_of(cfg: dict) -> float:
    return float((cfg.get("rope_parameters") or {}).get("rope_theta", cfg.get("rope_theta", 0)))


def _make_layer(cfg: dict):
    """(layer, head, sparse): ``layer(x, p, mlp, keep)`` runs one layer over
    one sequence and returns (x, the selection it attended over), ``keep``
    None on a layer that owns an indexer; ``sparse(h, mlp)`` is a sparse
    layer's second half alone (the tests' whole layer)."""
    import jax
    import jax.numpy as jnp

    eps, theta = cfg["rms_norm_eps"], theta_of(cfg)
    heads, rank = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, rope, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    held, k_top = cfg["n_routed_experts"], cfg["num_experts_per_tok"]
    scaling = cfg["routed_scaling_factor"]
    i_heads, i_dim, i_top = cfg["index_n_heads"], cfg["index_head_dim"], cfg["index_topk"]

    def blocks(t, size):
        size = min(size, t)
        return size, jnp.arange(0, t, size)

    def select(h, cq, p):
        """[T, T] bool: row t marks the min(index_topk, t + 1) keys at or
        before t that the indexer scores highest."""
        t = h.shape[0]
        q = (cq @ p["i_q"].T).reshape(t, i_heads, i_dim)
        q = jnp.concatenate([_rope(q[..., :rope], theta), q[..., rope:]], axis=-1)
        k = h @ p["i_k"].T
        k = k - jnp.mean(k, axis=-1, keepdims=True)
        k = k / jnp.sqrt(jnp.mean(k * k, axis=-1, keepdims=True) + INDEX_NORM_EPS)
        k = k * p["i_k_norm"] + p["i_k_bias"]
        k = jnp.concatenate([_rope(k[:, :rope], theta), k[:, rope:]], axis=-1)
        w = (h @ p["i_w"].T) * (i_heads**-0.5 * i_dim**-0.5)
        block, starts = blocks(t, QUERY_BLOCK)

        def one(start):
            qb = jax.lax.dynamic_slice_in_dim(q, start, block)
            wb = jax.lax.dynamic_slice_in_dim(w, start, block)
            score = jnp.einsum("qj,jqk->qk", wb, jax.nn.relu(jnp.einsum("qjd,kd->jqk", qb, k)))
            rows = start + jnp.arange(block)[:, None]
            score = jnp.where(jnp.arange(t)[None, :] <= rows, score, -jnp.inf)
            value, index = jax.lax.top_k(score, min(i_top, t))
            chosen = jnp.zeros((block, t), bool)
            # a query before position index_topk has fewer keys than that:
            # what top_k adds from past it (-inf) is not chosen
            return chosen.at[jnp.arange(block)[:, None], index].set(value > -jnp.inf)

        return jax.lax.map(one, starts).reshape(t, t)

    def attention(x, p, keep):
        t = x.shape[0]
        h = _rms(x, p["input_norm"], eps)
        cq = _rms(h @ p["q_a"].T, p["q_a_norm"], eps)
        if keep is None:
            keep = select(h, cq, p)
        q = (cq @ p["q_b"].T).reshape(t, heads, nope + rope)
        q_nope, q_rope = q[..., :nope], _rope(q[..., nope:], theta)
        kv = h @ p["kv_a"].T
        c = _rms(kv[:, :rank], p["kv_a_norm"], eps)
        k_rope = _rope(kv[:, rank:], theta)
        kvb = (c @ p["kv_b"].T).reshape(t, heads, nope + dv)
        k_nope, v = kvb[..., :nope], kvb[..., nope:]
        block, starts = blocks(t, QUERY_BLOCK)

        def one(start):
            qn = jax.lax.dynamic_slice_in_dim(q_nope, start, block)
            qr = jax.lax.dynamic_slice_in_dim(q_rope, start, block)
            seen = jax.lax.dynamic_slice_in_dim(keep, start, block)
            scores = jnp.einsum("qhd,khd->hqk", qn, k_nope) + jnp.einsum(
                "qhd,kd->hqk", qr, k_rope
            )
            scores = scores / jnp.sqrt(jnp.float32(nope + rope))
            scores = jnp.where(seen[None], scores, -jnp.inf)
            return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)

        ctx = jax.lax.map(one, starts).reshape(t, heads * dv)
        x = x + ctx @ p["o"].T
        return x, _rms(x, p["post_norm"], eps), keep

    def sparse(h, p):
        """Over blocks of tokens: the chosen experts that are held (e < held),
        weighed, + the shared expert."""
        e_gate, e_up, e_down = p["e_gate"], p["e_up"], p["e_down"]

        def one(hb):
            score = jax.nn.sigmoid(hb @ p["gate"].T)
            _, chosen = jax.lax.top_k(score + p["bias"], k_top)
            weight = jnp.take_along_axis(score, chosen, axis=1)
            weight = weight / jnp.sum(weight, axis=1, keepdims=True) * scaling
            # the pairs elsewhere: one last group, whose expert is all zeros
            expert_of_pair = jnp.minimum(chosen.reshape(-1), held)
            order = jnp.argsort(expert_of_pair, stable=True)
            sizes = jnp.zeros((held + 1,), jnp.int32).at[expert_of_pair].add(1)
            rows = hb[order // k_top]

            def product(x, w):  # w [held + 1, out, in]: the checkpoint's layout, a zero expert last
                return jax.lax.ragged_dot(x, jnp.swapaxes(w, 1, 2), sizes)

            y = product(jax.nn.silu(product(rows, e_gate)) * product(rows, e_up), e_down)
            y = y * weight.reshape(-1)[order][:, None]
            return jnp.zeros_like(hb).at[order // k_top].add(y)

        block, _ = blocks(h.shape[0], TOKEN_BLOCK)
        routed = jax.lax.map(one, h.reshape(-1, block, h.shape[1])).reshape(h.shape)
        return routed + _swiglu(h, p["s_gate"], p["s_up"], p["s_down"])

    @jax.jit
    def layer(x, p, mlp, keep):
        x, h, keep = attention(x, p, keep)
        if "gate" in mlp:
            return x + sparse(h, mlp), keep
        return x + _swiglu(h, mlp["d_gate"], mlp["d_up"], mlp["d_down"]), keep

    @jax.jit
    def head(x, rows, norm, weight, ids):
        return (_rms(x[rows], norm, eps) @ weight.T)[:, ids]

    return layer, head, jax.jit(sparse)


_FUNCTIONS: dict = {}


def functions(cfg: dict):
    key = tuple(sorted((k, v) for k, v in cfg.items() if isinstance(v, (int, float))))
    key += (theta_of(cfg),)
    if key not in _FUNCTIONS:
        _FUNCTIONS[key] = _make_layer(cfg)
    return _FUNCTIONS[key]


def layer_weights(state, cfg: dict, i: int):
    """(attention weights, the second half's weights) of layer i, float32, by
    their HuggingFace names."""
    import jax.numpy as jnp

    def f32(name):
        return jnp.asarray(np.asarray(state[name])).astype(jnp.float32)

    def swiglu_weights(base, prefix):
        return {
            f"{prefix}_{k}": f32(f"{base}.{k}_proj.weight") for k in ("gate", "up", "down")
        }

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
    }
    if kind_of(cfg, "indexer_types", i) == "full":
        p.update(
            i_q=f32(f"{att}.indexer.wq_b.weight"), i_k=f32(f"{att}.indexer.wk.weight"),
            i_k_norm=f32(f"{att}.indexer.k_norm.weight"),
            i_k_bias=f32(f"{att}.indexer.k_norm.bias"),
            i_w=f32(f"{att}.indexer.weights_proj.weight"),
        )
    if kind_of(cfg, "mlp_layer_types", i) == "dense":
        return p, swiglu_weights(f"{base}.mlp", "d")
    return p, {
        "gate": f32(f"{base}.mlp.gate.weight"),
        "bias": f32(f"{base}.mlp.gate.e_score_correction_bias"),
        **{f"e_{kind}": stacked(base, kind) for kind in ("gate", "up", "down")},
        **swiglu_weights(f"{base}.mlp.shared_experts", "s"),
    }


def hidden_states(state, cfg: dict, sequences: list, selections: list | None = None) -> list:
    """Each sequence of token ids through every layer: [T_padded, hidden]
    float32 before the final norm, a sequence padded with token 0 up to a
    whole block (a padded position is past every real one, so no real query
    sees it).  Every sequence goes through a layer before the next layer's
    weights are read.  ``selections``, a list handed in, receives a layer's
    [T, T] choices, a list a layer (tests)."""
    import jax
    import jax.numpy as jnp

    layer, _, _ = functions(cfg)
    with jax.default_matmul_precision("highest"):
        width = -(-max(len(ids) for ids in sequences) // QUERY_BLOCK) * QUERY_BLOCK
        embed = jnp.asarray(np.asarray(state["model.embed_tokens.weight"])).astype(jnp.float32)
        xs = []
        for ids in sequences:
            padded = np.zeros((width,), np.int32)
            padded[: len(ids)] = ids
            xs.append(embed[jnp.asarray(padded)])
        del embed
        keeps = [None] * len(xs)
        for i in range(cfg["num_hidden_layers"]):
            p, mlp = layer_weights(state, cfg, i)
            if kind_of(cfg, "indexer_types", i) == "full":
                keeps = [None] * len(xs)
            for j in range(len(xs)):  # a sequence's old state goes as its new one comes
                xs[j], keep = layer(xs[j], p, mlp, keeps[j])
                keeps[j] = np.asarray(keep)  # on the host: 64 MB a sequence at 8k
            if selections is not None:
                selections.append(list(keeps))
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
    _, head, _ = functions(cfg)
    xs = hidden_states(state, cfg, [ids for ids, _ in calls])
    with jax.default_matmul_precision("highest"):
        norm = jnp.asarray(np.asarray(state["model.norm.weight"])).astype(jnp.float32)
        weight = jnp.asarray(np.asarray(state["lm_head.weight"])).astype(jnp.float32)
        ids = jnp.asarray(np.asarray(letter_ids, np.int32))
        return [
            np.asarray(
                head(x, jnp.asarray(np.asarray(rows, np.int32)), norm, weight, ids),
                np.float64,
            )
            for x, (_, rows) in zip(xs, calls)
        ]
