"""Plain reference: BERT encoder, CLS pooling, cosine self-consistency vote.

Written from the published equations (Devlin et al. 2018, the BertModel
layout of the BAAI/bge-*-en-v1.5 checkpoints) in float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``.  No kernels, no batching tricks,
no code of the program: it reads the checkpoint by its HuggingFace names.

  x0      = LayerNorm(word[ids] + position[0..S) + type[0])
  per layer:
    q,k,v = x Wq^T + bq, ...          heads of size hidden/heads
    a     = softmax(q k^T / sqrt(d) + mask) v
    x     = LayerNorm(x + a Wo^T + bo)
    x     = LayerNorm(x + gelu(x Wi^T + bi) Wo2^T + bo2)     (erf gelu)
  e       = x[:, 0] / |x[:, 0]|                              (CLS pooling)
  logit_i = mean over j != i of <e_i, e_j>
  conf    = softmax(logit / temperature)

``logits(...)`` returns the vote's logits (before temperature), one row per
candidate; ``lowered=True`` is the same forward with every matrix product
taken in int8 (symmetric, per output channel for weights and per row for
activations), the precision below the bf16 the configuration states: the
control the check has to fail.
"""

from __future__ import annotations

import numpy as np

LN_EPS = 1e-12


def load(state: dict, cfg: dict):
    """HF-named numpy arrays -> float32 device arrays, layers stacked."""
    import jax.numpy as jnp

    def f32(name):
        return jnp.asarray(np.asarray(state[name]).astype(np.float32))

    layer_names = (
        "attention.self.query", "attention.self.key", "attention.self.value",
        "attention.output.dense", "intermediate.dense", "output.dense",
    )
    ln_names = ("attention.output.LayerNorm", "output.LayerNorm")
    layers = []
    for i in range(cfg["num_hidden_layers"]):
        base = f"encoder.layer.{i}"
        layer = {}
        for n in layer_names:
            layer[n] = (f32(f"{base}.{n}.weight"), f32(f"{base}.{n}.bias"))
        for n in ln_names:
            layer[n] = (f32(f"{base}.{n}.weight"), f32(f"{base}.{n}.bias"))
        layers.append(layer)
    return {
        "word": f32("embeddings.word_embeddings.weight"),
        "position": f32("embeddings.position_embeddings.weight"),
        "type": f32("embeddings.token_type_embeddings.weight"),
        "emb_ln": (
            f32("embeddings.LayerNorm.weight"),
            f32("embeddings.LayerNorm.bias"),
        ),
        "layers": layers,
    }


def _layer_norm(x, wb):
    import jax.numpy as jnp

    w, b = wb
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + LN_EPS) * w + b


def _dense(x, wb, int8: bool):
    import jax.numpy as jnp

    w, b = wb  # torch layout [out, in]
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

    def layer(x, bias, p):
        b, s, h = x.shape
        d = h // heads

        def split(t):
            return t.reshape(b, s, heads, d).transpose(0, 2, 1, 3)

        q = split(_dense(x, p["attention.self.query"], int8))
        k = split(_dense(x, p["attention.self.key"], int8))
        v = split(_dense(x, p["attention.self.value"], int8))
        scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / jnp.sqrt(
            jnp.float32(d)
        )
        probs = jax.nn.softmax(scores + bias, axis=-1)
        ctx = jnp.einsum("bhqk,bhkd->bhqd", probs, v)
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


def embeddings(weights: dict, cfg: dict, ids: np.ndarray, mask: np.ndarray,
               lowered: bool = False):
    """ids, mask [B, S] -> unit CLS vectors [B, H], layer by layer."""
    import jax
    import jax.numpy as jnp

    key = (cfg["num_attention_heads"], bool(lowered))
    if key not in _LAYER_FNS:
        _LAYER_FNS[key] = _make_layer(*key)
    layer = _LAYER_FNS[key]
    with jax.default_matmul_precision("highest"):
        ids = jnp.asarray(ids, dtype=jnp.int32)
        s = ids.shape[1]
        x = (
            weights["word"][ids]
            + weights["position"][None, :s]
            + weights["type"][0][None, None]
        )
        x = _layer_norm(x, weights["emb_ln"])
        bias = (1.0 - jnp.asarray(mask, dtype=jnp.float32))[:, None, None, :]
        bias = bias * jnp.float32(-1e9)
        for p in weights["layers"]:
            x = layer(x, bias, p)
        cls = x[:, 0]
        return cls / jnp.linalg.norm(cls, axis=-1, keepdims=True)


def vote_logits(emb) -> np.ndarray:
    """unit vectors [N, H] -> mean cosine to the others [N] (float64 on the
    host: N is at most a few hundred)."""
    e = np.asarray(emb, dtype=np.float64)
    sims = e @ e.T
    n = sims.shape[0]
    return (sims.sum(axis=1) - np.diag(sims)) / max(n - 1, 1)


def inputs(req: dict, cfg: dict, tok: dict):
    """The benchmark's own tokenization of a request: one word is one token,
    word k is id ``first_word + k``; [CLS] words [SEP], cut to ``max_tokens``
    as BERT tokenizers cut (the [SEP] stays), padded to a multiple of 32."""
    cap = int(cfg["max_tokens"])
    rows = [
        [tok["cls"], *(w[: cap - 2] + tok["first_word"]).tolist(), tok["sep"]]
        for w in req["words"]
    ]
    width = -(-max(len(r) for r in rows) // 32) * 32
    ids = np.full((len(rows), width), tok["pad"], dtype=np.int32)
    mask = np.zeros((len(rows), width), dtype=np.int32)
    for i, row in enumerate(rows):
        ids[i, : len(row)] = row
        mask[i, : len(row)] = 1
    return ids, mask


def logits(weights, cfg, ids, mask, lowered: bool = False) -> np.ndarray:
    return vote_logits(embeddings(weights, cfg, ids, mask, lowered=lowered))
