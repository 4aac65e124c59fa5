"""Plain reference: DeBERTa-v3 encoder with disentangled attention, context
pooler and one-logit reward head; the vote is softmax(reward / T).

Written from He et al. 2021 ("DeBERTa", section 3.1, and "DeBERTaV3") and the
published DebertaV2 implementation the v3 checkpoints were trained with, in
float32 ``jax.numpy`` under ``jax.default_matmul_precision("highest")``.  No
code of the program; the checkpoint is read by its HuggingFace names.

  x0   = LayerNorm(word[ids])                 (no absolute positions, eps 1e-7)
  P    = LayerNorm_enc(rel_embeddings)        (2k rows, k = position_buckets)
  per layer, per head (size d):
    Qc, Kc, V = x Wq, x Wk, x Wv
    Kr, Qr    = P Wk, P Wq                    (share_att_key: v3 has no
                                               separate position projections)
    A[i, j] = ( Qc_i . Kc_j  +  Qc_i . Kr[delta(i, j)]  +  Kc_j . Qr[delta(i, j)] )
              / sqrt(3 d)
    delta(i, j) = clip(bucket(i - j) + k, 0, 2k - 1), bucket = the v3 log
              bucketing: exact within +-k/2, log-spaced out to 511 beyond
    x = LayerNorm(x + softmax(A + mask) V Wo);  x = LayerNorm(x + MLP_gelu(x))
  reward = w_cls . gelu(W_pool x[:, 0] + b_pool) + b_cls

Departure from the paper, following the published implementation: the paper
writes the position-to-content term with delta(j, i); the implementation
that trained every released checkpoint indexes it with delta(i, j) as above.

``lowered=True`` takes every content and MLP matrix product in int8 (symmetric,
per output channel for weights, per row for activations): the precision below
the declared bf16, the control the check has to fail.
"""

from __future__ import annotations

import math

import numpy as np

LN_EPS = 1e-7
_LAYER = (
    "attention.self.query_proj", "attention.self.key_proj",
    "attention.self.value_proj", "attention.output.dense",
    "intermediate.dense", "output.dense",
)
_LNS = ("attention.output.LayerNorm", "output.LayerNorm")


def load(state: dict, cfg: dict):
    import jax.numpy as jnp

    def f32(name):
        return jnp.asarray(np.asarray(state[name]).astype(np.float32))

    def pair(prefix):
        return (f32(prefix + ".weight"), f32(prefix + ".bias"))

    layers = []
    for i in range(cfg["num_hidden_layers"]):
        base = f"deberta.encoder.layer.{i}."
        layers.append({n: pair(base + n) for n in _LAYER + _LNS})
    return {
        "word": f32("deberta.embeddings.word_embeddings.weight"),
        "emb_ln": pair("deberta.embeddings.LayerNorm"),
        "rel": f32("deberta.encoder.rel_embeddings.weight"),
        "rel_ln": pair("deberta.encoder.LayerNorm"),
        "layers": layers,
        "pooler": pair("pooler.dense"),
        "classifier": pair("classifier"),
    }


def log_bucket(rel: np.ndarray, buckets: int, max_position: int) -> np.ndarray:
    """The v3 bucketing of a signed distance (float64 on the host)."""
    mid = buckets // 2
    sign = np.sign(rel)
    abs_pos = np.where((rel < mid) & (rel > -mid), mid - 1, np.abs(rel)).astype(
        np.float64
    )
    log_pos = (
        np.ceil(np.log(abs_pos / mid) / math.log((max_position - 1) / mid) * (mid - 1))
        + mid
    )
    return np.where(np.abs(rel) <= mid, rel, (log_pos * sign).astype(rel.dtype))


def delta(seq: int, cfg: dict) -> np.ndarray:
    """[seq, seq] rows of the relative table.  ``max_relative_positions`` -1
    (as published) means the position table's 512; ``position_buckets`` 0
    (only the tiny test preset) is the plain clamp without log buckets."""
    farthest = cfg["max_relative_positions"]
    if farthest < 1:
        farthest = cfg["max_position_embeddings"]
    pos = np.arange(seq)
    rel = pos[:, None] - pos[None, :]
    span = cfg["position_buckets"] or farthest
    if cfg["position_buckets"] > 0:
        rel = log_bucket(rel, cfg["position_buckets"], farthest)
    return (np.clip(rel, -span, span - 1) + span).astype(np.int32)


def _layer_norm(x, wb):
    import jax.numpy as jnp

    w, b = wb
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + LN_EPS) * w + b


def _dense(x, wb, int8: bool = False):
    import jax.numpy as jnp

    w, b = wb
    if int8:
        sx = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 127.0
        sx = jnp.where(sx == 0, 1.0, sx)
        sw = jnp.max(jnp.abs(w), axis=-1, keepdims=True) / 127.0
        sw = jnp.where(sw == 0, 1.0, sw)
        x = jnp.round(x / sx) * sx
        w = jnp.round(w / sw) * sw
    return x @ w.T + b


def _make_layer(heads: int, int8: bool):
    import jax
    import jax.numpy as jnp

    def layer(x, rel, idx, bias, p):
        b, s, h = x.shape
        d = h // heads

        def split(t):  # [b, s, h] -> [b, heads, s, d]
            return t.reshape(b, s, heads, d).transpose(0, 2, 1, 3)

        q = split(_dense(x, p["attention.self.query_proj"], int8))
        k = split(_dense(x, p["attention.self.key_proj"], int8))
        v = split(_dense(x, p["attention.self.value_proj"], int8))
        r = rel.shape[0]
        kr = _dense(rel, p["attention.self.key_proj"]).reshape(r, heads, d)
        qr = _dense(rel, p["attention.self.query_proj"]).reshape(r, heads, d)
        c2c = jnp.einsum("bhid,bhjd->bhij", q, k)
        c2p_all = jnp.einsum("bhid,rhd->bhir", q, kr)
        c2p = jnp.take_along_axis(c2p_all, idx[None, None], axis=-1)
        p2c_all = jnp.einsum("bhjd,rhd->bhjr", k, qr)
        # [b, h, j, i] gathered at delta(i, j), then put back as [b, h, i, j]
        p2c = jnp.take_along_axis(p2c_all, idx.T[None, None], axis=-1)
        p2c = jnp.swapaxes(p2c, -1, -2)
        scores = (c2c + c2p + p2c) / jnp.sqrt(jnp.float32(3 * d))
        probs = jax.nn.softmax(scores + bias, axis=-1)
        ctx = jnp.einsum("bhij,bhjd->bhid", probs, v)
        ctx = ctx.transpose(0, 2, 1, 3).reshape(b, s, h)
        x = _layer_norm(
            x + _dense(ctx, p["attention.output.dense"], int8),
            p["attention.output.LayerNorm"],
        )
        inner = jax.nn.gelu(
            _dense(x, p["intermediate.dense"], int8), approximate=False
        )
        return _layer_norm(
            x + _dense(inner, p["output.dense"], int8), p["output.LayerNorm"]
        )

    return jax.jit(layer)


_LAYER_FNS: dict = {}


def rewards(weights: dict, cfg: dict, ids, mask, int8: bool = False):
    """ids, mask [B, S] -> reward [B], layer by layer."""
    import jax
    import jax.numpy as jnp

    key = (cfg["num_attention_heads"], int8)
    if key not in _LAYER_FNS:
        _LAYER_FNS[key] = _make_layer(*key)
    layer = _LAYER_FNS[key]
    with jax.default_matmul_precision("highest"):
        ids = jnp.asarray(ids, dtype=jnp.int32)
        x = _layer_norm(weights["word"][ids], weights["emb_ln"])
        rel = _layer_norm(weights["rel"], weights["rel_ln"])
        idx = jnp.asarray(delta(ids.shape[1], cfg))
        bias = (1.0 - jnp.asarray(mask, dtype=jnp.float32))[:, None, None, :]
        bias = bias * jnp.float32(-1e9)
        for p in weights["layers"]:
            x = layer(x, rel, idx, bias, p)
        pooled = jax.nn.gelu(_dense(x[:, 0], weights["pooler"]), approximate=False)
        return _dense(pooled, weights["classifier"])[:, 0]


def inputs(req: dict, cfg: dict, tok: dict):
    """[CLS] prompt candidate [SEP] per row (the program joins prompt and
    candidate with a newline, which the unigram path treats as white space),
    one word one token, cut to ``max_tokens``, padded to a multiple of 32."""
    cap = int(cfg["max_tokens"])
    prompt = np.asarray(req.get("prompt", ()), dtype=np.int64)
    rows = []
    for words in req["words"]:
        body = np.concatenate([prompt, words])[: cap - 2] + tok["first_word"]
        rows.append([tok["cls"], *body.tolist(), tok["sep"]])
    width = min(-(-max(len(r) for r in rows) // 32) * 32, cfg["max_position_embeddings"])
    ids = np.full((len(rows), width), tok["pad"], dtype=np.int32)
    mask = np.zeros((len(rows), width), dtype=np.int32)
    for i, row in enumerate(rows):
        ids[i, : len(row)] = row
        mask[i, : len(row)] = 1
    return ids, mask


def logits(weights, cfg, ids, mask, lowered: bool = False) -> np.ndarray:
    return np.asarray(rewards(weights, cfg, ids, mask, int8=lowered), dtype=np.float64)
