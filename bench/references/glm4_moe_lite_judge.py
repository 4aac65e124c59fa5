"""Plain reference: the ``glm4_moe_lite`` decoder as a judge reads a ballot.

Written from the model's configuration (zai-org/GLM-4.7-Flash ``config.json``;
the DeepSeek-V3 layout) in float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``.  No kernel, no cache, nothing of
the program: it reads the seeded checkpoint by its HuggingFace names, one
layer at a time (``read_checkpoint`` opens a tensor when it is asked for),
tokenizes for itself and builds each call's ballot for itself from the
request and the call's seed.

  x0      = embed[ids]
  per layer:
    h     = rms(x) ;  q = W_qb · rms(W_qa · h)          heads of 192 | 64
    c, kr = W_kva · h ;  c = rms(c) ;  k_nope, v = W_kvb · c
    the 64 rotary dims turn in interleaved pairs (2i, 2i+1) by
    position * theta^(-2i/64); kr is one key for every head
    a     = softmax over keys <= query of (q_nope·k_nope + q_rope·kr) / 16
    x     = x + W_o · (a v)
    h     = rms(x)
    x     = x + SwiGLU(h)                               layer 0
    x     = x + Σ_{e in top4(s + bias)} 1.8 s_e / Σ s · SwiGLU_e(h)
              + SwiGLU_shared(h),  s = sigmoid(W_g · h)  layers 1..
  logits  = W_head · rms(x)

Attention goes in blocks of queries against all keys, so that 8k positions
fit; the (token, expert) pairs are sorted by expert and each expert's
products run over its own rows and over no others (``jax.lax.ragged_dot``,
XLA's own grouped product).

``ballot(seed, n)`` mirrors the published prefix-tree construction the
program serves (``random.Random(seed)``: shuffle the candidates, split them
evenly over the fewest branches that hold them, a fresh shuffle of the
20-letter alphabet at every node, then shuffle the (key, candidate) pairs
for presentation).

Given a call's prompt plus the key letter the PROGRAM chose, ONE forward over
T + 1 positions gives the first level's logits at position T - 1 and the
second level's at position T: the program's second read came through its
latent cache on the absorbed path, so this is prefill-then-decode against the
full forward pass.
"""

from __future__ import annotations

import random

import numpy as np

ALPHABET = "ABCDEFGHIJKLMNOPQRST"
QUERY_BLOCK = 512


# -- the ballot ----------------------------------------------------------------


def _node(rng, source: list, limit: int, depth: int):
    letters = list(ALPHABET)
    rng.shuffle(letters)
    if depth == 1:
        return {letters[i]: idx for i, idx in enumerate(source)}
    capacity = limit ** (depth - 1)
    n = min(-(-len(source) // capacity), limit)
    base, extra = divmod(len(source), n)
    branch, offset = {}, 0
    for i in range(n):
        size = base + (1 if i < extra else 0)
        branch[letters[i]] = _node(rng, source[offset : offset + size], limit, depth - 1)
        offset += size
    return branch


def _collect(node, prefix: str, out: list) -> None:
    if isinstance(node, int):
        out.append((prefix, node))
        return
    for letter, child in node.items():
        _collect(child, f"{prefix}`{letter}`", out)


def ballot(seed: int, n: int):
    """(root, depth, [(key, candidate)] in presentation order)."""
    rng = random.Random(seed)
    source = list(range(n))
    rng.shuffle(source)
    depth = 1
    while len(ALPHABET) ** depth < n:
        depth += 1
    root = _node(rng, source, len(ALPHABET), depth)
    pairs: list = []
    _collect(root, "", pairs)
    rng.shuffle(pairs)
    return root, depth, pairs


# -- tokens ----------------------------------------------------------------------


def key_ids(key: str, tok: dict) -> list:
    """`C``B`: -> opening backtick, C, the double backtick, B, backtick-colon."""
    letters = [c for c in key if c in ALPHABET]
    out = [tok["tick_open"]]
    for i, letter in enumerate(letters):
        if i:
            out.append(tok["tick_tick"])
        out.append(tok["letter_first"] + ALPHABET.index(letter))
    return out + [tok["tick_colon"]]


def call_ids(req: dict, pairs: list, tok: dict) -> list:
    """One call's prompt: [BOS], the conversation, "Select the response:",
    per candidate in presentation order its key and its words, and the
    answer's opening backtick."""
    first = tok["first_word"]
    ids = [tok["bos"], *(np.asarray(req["prompt"]) + first).tolist(), *tok["instruction"]]
    for key, candidate in pairs:
        ids += key_ids(key, tok)
        ids += (np.asarray(req["words"][candidate]) + first).tolist()
    return ids + [tok["tick_open"]]


def letter_id(letter: str, tok: dict) -> int:
    return tok["letter_first"] + ALPHABET.index(letter)


# -- the forward -------------------------------------------------------------------


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


def _make_layer(cfg: dict):
    """One layer over one sequence, jitted: (x, attention weights, the
    layer's second half) -> x."""
    import jax
    import jax.numpy as jnp

    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    heads, rank = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, rope, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    experts, k_top = cfg["n_routed_experts"], cfg["num_experts_per_tok"]
    scaling = cfg["routed_scaling_factor"]

    def attention(x, p):
        t = x.shape[0]
        h = _rms(x, p["input_norm"], eps)
        q = (_rms(h @ p["q_a"].T, p["q_a_norm"], eps) @ p["q_b"].T).reshape(
            t, heads, nope + rope
        )
        q_nope, q_rope = q[..., :nope], _rope(q[..., nope:], theta)
        kv = h @ p["kv_a"].T
        c = _rms(kv[:, :rank], p["kv_a_norm"], eps)
        k_rope = _rope(kv[:, rank:], theta)
        kvb = (c @ p["kv_b"].T).reshape(t, heads, nope + dv)
        k_nope, v = kvb[..., :nope], kvb[..., nope:]
        block = min(QUERY_BLOCK, t)

        def one(start):
            qn = jax.lax.dynamic_slice_in_dim(q_nope, start, block)
            qr = jax.lax.dynamic_slice_in_dim(q_rope, start, block)
            scores = jnp.einsum("qhd,khd->hqk", qn, k_nope) + jnp.einsum(
                "qhd,kd->hqk", qr, k_rope
            )
            scores = scores / jnp.sqrt(jnp.float32(nope + rope))
            rows = start + jnp.arange(block)[:, None]
            scores = jnp.where(jnp.arange(t)[None, :] <= rows, scores, -jnp.inf)
            return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)

        ctx = jax.lax.map(one, jnp.arange(0, t, block)).reshape(t, heads * dv)
        x = x + ctx @ p["o"].T
        return x, _rms(x, p["post_norm"], eps)

    def sparse(h, p):
        """Σ over the k chosen experts of weight x SwiGLU_e(h) + the shared
        expert: the (token, choice) pairs sorted by expert, each expert's
        products over its own run of rows (``jax.lax.ragged_dot``)."""
        t = h.shape[0]
        score = jax.nn.sigmoid(h @ p["gate"].T)
        ranked, chosen = jax.lax.top_k(score + p["bias"], k_top + 1)
        margin = ranked[:, k_top - 1] - ranked[:, k_top]  # last chosen - first left out
        chosen = chosen[:, :k_top]
        weight = jnp.take_along_axis(score, chosen, axis=1)
        weight = weight / jnp.sum(weight, axis=1, keepdims=True) * scaling
        expert_of_pair = chosen.reshape(-1)
        order = jnp.argsort(expert_of_pair, stable=True)
        sizes = jnp.zeros((experts,), jnp.int32).at[expert_of_pair].add(1)
        rows = h[order // k_top]

        def product(x, w):  # w [E, out, in], the checkpoint's own layout
            return jax.lax.ragged_dot(x, jnp.swapaxes(w, 1, 2), sizes)

        y = product(jax.nn.silu(product(rows, p["e_gate"])) * product(rows, p["e_up"]),
                    p["e_down"])
        y = y * weight.reshape(-1)[order][:, None]
        routed = jnp.zeros_like(h).at[order // k_top].add(y)
        return routed + _swiglu(h, p["s_gate"], p["s_up"], p["s_down"]), margin

    @jax.jit
    def layer(x, p, mlp):
        """-> x, and each row's router margin (inf where no router chose)."""
        x, h = attention(x, p)
        if "gate" in mlp:
            out, margin = sparse(h, mlp)
            return x + out, margin
        out = _swiglu(h, mlp["d_gate"], mlp["d_up"], mlp["d_down"])
        return x + out, jnp.full((x.shape[0],), jnp.inf, jnp.float32)

    @jax.jit
    def head(x, rows, norm, weight, ids):
        return (_rms(x[rows], norm, eps) @ weight.T)[:, ids]

    return layer, head


_FUNCTIONS: dict = {}


def _functions(cfg: dict):
    key = tuple(sorted((k, v) for k, v in cfg.items() if isinstance(v, (int, float))))
    if key not in _FUNCTIONS:
        _FUNCTIONS[key] = _make_layer(cfg)
    return _FUNCTIONS[key]


def read_logits(state, cfg: dict, calls: list, letter_ids: list) -> list:
    """``calls`` is [(ids, rows)]: token ids of one sequence and the positions
    to read.  Returns, per call, logits [len(rows), len(letter_ids)] at those
    positions for those token ids, float64 on the host.  Every call goes
    through a layer before the next layer's weights are read."""
    return read_logits_and_margins(state, cfg, calls, letter_ids)[0]


def read_logits_and_margins(state, cfg: dict, calls: list, letter_ids: list) -> tuple:
    """``read_logits``, and beside each read how firmly THIS forward routed
    the read's own row: per call [len(rows)], the least over the sparse layers
    of (the last chosen expert's score + bias) - (the first left out's).  A
    row whose margin is under what bf16 rounds away is routed by rounding, in
    any implementation; the check says which reads it therefore leaves out of
    its median, by this number alone and by nothing of the program."""
    import jax
    import jax.numpy as jnp

    if not calls:
        return [], []
    layer, head = _functions(cfg)
    experts = cfg["n_routed_experts"]

    def f32(name):
        return jnp.asarray(np.asarray(state[name])).astype(jnp.float32)

    def swiglu_weights(base, prefix):
        return {
            f"{prefix}_{k}": f32(f"{base}.{k}_proj.weight") for k in ("gate", "up", "down")
        }

    def stacked(base, kind):
        return jnp.stack(
            [
                jnp.asarray(np.asarray(state[f"{base}.mlp.experts.{e}.{kind}_proj.weight"]))
                for e in range(experts)
            ]
        ).astype(jnp.float32)

    with jax.default_matmul_precision("highest"):
        width = -(-max(len(ids) for ids, _ in calls) // QUERY_BLOCK) * QUERY_BLOCK
        embed = f32("model.embed_tokens.weight")
        xs = []
        for ids, _ in calls:
            padded = np.zeros((width,), np.int32)
            padded[: len(ids)] = ids
            xs.append(embed[jnp.asarray(padded)])
        del embed
        margins = [np.full((len(rows),), np.inf) for _, rows in calls]
        for i in range(cfg["num_hidden_layers"]):
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
            if i < cfg["first_k_dense_replace"]:
                mlp = swiglu_weights(f"{base}.mlp", "d")
            else:
                mlp = {
                    "gate": f32(f"{base}.mlp.gate.weight"),
                    "bias": f32(f"{base}.mlp.gate.e_score_correction_bias"),
                    **{f"e_{kind}": stacked(base, kind) for kind in ("gate", "up", "down")},
                    **swiglu_weights(f"{base}.mlp.shared_experts", "s"),
                }
            for j, (_, rows) in enumerate(calls):
                xs[j], margin = layer(xs[j], p, mlp)
                margins[j] = np.minimum(margins[j], np.asarray(margin, np.float64)[rows])
            del p, mlp
        norm, weight = f32("model.norm.weight"), f32("lm_head.weight")
        ids = jnp.asarray(np.asarray(letter_ids, np.int32))
        logits = [
            np.asarray(
                head(x, jnp.asarray(np.asarray(rows, np.int32)), norm, weight, ids),
                np.float64,
            )
            for x, (_, rows) in zip(xs, calls)
        ]
    return logits, margins
