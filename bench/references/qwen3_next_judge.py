"""Plain reference: the ``qwen3_next`` decoder as a judge reads a ballot.

Written from the model's configuration (Qwen/Qwen3-Next-80B-A3B-Instruct
``config.json``) in float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``.  No kernel, no cache, no chunk,
nothing of the program: it reads the seeded checkpoint by its HuggingFace
names, one layer at a time (``read_checkpoint`` opens a tensor when it is
asked for), tokenizes for itself and builds each call's ballot for itself
from the request and the call's seed (the ballot and the tokens are the
protocol's, not the model's: they are the first judge's reference's,
``references/glm4_moe_lite_judge.py``, taken as they are).

``rms0(x, w) = x / sqrt(mean(x^2) + 1e-6) · (1 + w)``; layer i is a
full-attention layer where (i + 1) % 4 == 0, else a linear one.

  x0      = embed[ids]
  linear layer (``linear_attn``):
    in_proj_qkvz, in_proj_ba laid out by KEY head: q dk | k dk | its value
    heads' v | their z;  b | a
    q|k|v   = silu(depthwise causal conv, 4 taps, no bias, over q | k | v)
    q, k    = l2 a head (eps 1e-6), each key head repeated for its value
              heads;  q · dk^-0.5
    beta    = sigmoid(b) ;  g = -exp(A_log) · softplus(a + dt_bias)
    a value head, THE RECURRENT FORM, a position at a time (``lax.scan``):
      S <- S exp(g_t) ; d = (v_t - S^T k_t) beta_t ; S <- S + k_t d^T ;
      o_t = S^T q_t
    x       = x + out_proj · (norm.weight · o / rms(o) · silu(z))
  full layer (``self_attn``):
    q_proj a head: query hd | gate hd ;  k_proj, v_proj kv heads of hd
    q, k    = rms0 over hd (q_norm, k_norm), rotary on the first 64 dims of a
              head, pairs (i, i + 32), theta 1e7
    a       = softmax over keys <= query of q·k / sqrt(hd), a key head
              serving heads / kv query heads
    x       = x + o_proj · (a · sigmoid(gate))
  every layer (``mlp``):
    p       = softmax(gate · h) over the ROUTER's experts; the top k, divided
              by their sum
    x       = x + Σ_{e chosen, e < held} p_e · SwiGLU_e(h)
                + sigmoid(shared_expert_gate · h) · SwiGLU_shared(h)
  logits  = lm_head · rms0(x, norm)

THE SHARE.  The checkpoint names experts 0..held-1 of a router
``num_experts_routed`` wide (``cfg["num_experts"]`` is ``held``): what the
experts elsewhere would add is left out here as in the program, and the
partial sum goes on to the next layer.

Departures from the published description, the same as the program's and the
configuration's: the release's multi-token-prediction module is not in
``config.json`` and is left out; decoding is constrained to the ballot's keys.

Attention goes in blocks of queries against all keys, so that 8k positions
fit; the (token, expert) pairs are sorted by expert and each held expert's
products run over its own rows and over no others (``jax.lax.ragged_dot``;
the pairs elsewhere are a last group whose expert is all zeros).

Given a call's prompt plus the key letter the PROGRAM chose, ONE forward over
T + 1 positions gives the first level's logits at position T - 1 and the
second level's at position T: the program's second read came through a
recurrent step and a row against its cached keys, so this is
prefill-then-decode against the full forward pass, and the program's chunked
rule against the recurrence.
"""

from __future__ import annotations

import numpy as np

import byname

_protocol = byname.module("references", "glm4_moe_lite_judge")
ALPHABET = _protocol.ALPHABET
ballot, key_ids, call_ids, letter_id = (
    _protocol.ballot, _protocol.key_ids, _protocol.call_ids, _protocol.letter_id
)
QUERY_BLOCK = 512


def _rms0(x, weight, eps):
    import jax.numpy as jnp

    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)) * (1.0 + weight)


def _l2(x):
    import jax.numpy as jnp

    return x * (1.0 / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6))


def _rope_half(x, theta):
    """x [T, H, rot], position = row: pairs (i, i + rot / 2)."""
    import jax.numpy as jnp

    t, rot = x.shape[0], x.shape[-1]
    inv = theta ** (-jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    x1, x2 = x[..., : rot // 2], x[..., rot // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _swiglu(x, gate, up, down):
    import jax

    return (jax.nn.silu(x @ gate.T) * (x @ up.T)) @ down.T


def _make_layer(cfg: dict):
    """One layer over one sequence, jitted: (x, the token mixer's weights,
    the sparse half's) -> x."""
    import jax
    import jax.numpy as jnp

    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    heads, kv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    rot = int(hd * cfg["partial_rotary_factor"])
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    per, taps = hv // hk, cfg["linear_conv_kernel_dim"]
    held, k_top = cfg["num_experts"], cfg["num_experts_per_tok"]

    def linear(x, p):
        t = x.shape[0]
        h = _rms0(x, p["input_norm"], eps)
        qkvz = (h @ p["in_qkvz"].T).reshape(t, hk, 2 * dk + 2 * per * dv)
        ba = (h @ p["in_ba"].T).reshape(t, hk, 2 * per)
        v = qkvz[..., 2 * dk:2 * dk + per * dv].reshape(t, hv * dv)
        z = qkvz[..., 2 * dk + per * dv:].reshape(t, hv, dv)
        mixed = jnp.concatenate(
            [qkvz[..., :dk].reshape(t, hk * dk), qkvz[..., dk:2 * dk].reshape(t, hk * dk), v],
            axis=1,
        )
        padded = jnp.pad(mixed, ((taps - 1, 0), (0, 0)))
        weight = p["conv"][:, 0, :]  # [channels, taps]
        conv = jax.nn.silu(sum(padded[j:j + t] * weight[:, j] for j in range(taps)))
        q = _l2(conv[:, : hk * dk].reshape(t, hk, dk)) * dk ** -0.5
        k = _l2(conv[:, hk * dk: 2 * hk * dk].reshape(t, hk, dk))
        q, k = jnp.repeat(q, per, axis=1), jnp.repeat(k, per, axis=1)
        v = conv[:, 2 * hk * dk:].reshape(t, hv, dv)
        beta = jax.nn.sigmoid(ba[..., :per].reshape(t, hv))
        g = -jnp.exp(p["a_log"]) * jax.nn.softplus(ba[..., per:].reshape(t, hv) + p["dt_bias"])

        def step(s, xs):
            q_t, k_t, v_t, g_t, beta_t = xs
            s = s * jnp.exp(g_t)[:, None, None]
            d = (v_t - jnp.einsum("hkv,hk->hv", s, k_t)) * beta_t[:, None]
            s = s + k_t[:, :, None] * d[:, None, :]
            return s, jnp.einsum("hkv,hk->hv", s, q_t)

        _, o = jax.lax.scan(step, jnp.zeros((hv, dk, dv), jnp.float32), (q, k, v, g, beta))
        o = o * (1.0 / jnp.sqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps))
        o = o * p["norm"] * jax.nn.silu(z)  # a plain scale, the norm before the gate
        return x + o.reshape(t, hv * dv) @ p["out"].T

    def full(x, p):
        t = x.shape[0]
        h = _rms0(x, p["input_norm"], eps)
        qg = (h @ p["q"].T).reshape(t, heads, 2 * hd)
        q, gate = qg[..., :hd], qg[..., hd:]
        k = _rms0((h @ p["k"].T).reshape(t, kv, hd), p["k_norm"], eps)
        q = _rms0(q, p["q_norm"], eps)
        v = (h @ p["v"].T).reshape(t, kv, hd)
        q = jnp.concatenate([_rope_half(q[..., :rot], theta), q[..., rot:]], axis=-1)
        k = jnp.concatenate([_rope_half(k[..., :rot], theta), k[..., rot:]], axis=-1)
        q = q.reshape(t, kv, heads // kv, hd)
        block = min(QUERY_BLOCK, t)

        def one(start):
            qb = jax.lax.dynamic_slice_in_dim(q, start, block)
            scores = jnp.einsum("qgrd,kgd->grqk", qb, k) / jnp.sqrt(jnp.float32(hd))
            rows = start + jnp.arange(block)[:, None]
            scores = jnp.where(jnp.arange(t)[None, :] <= rows, scores, -jnp.inf)
            return jnp.einsum("grqk,kgd->qgrd", jax.nn.softmax(scores, axis=-1), v)

        ctx = jax.lax.map(one, jnp.arange(0, t, block)).reshape(t, heads, hd)
        ctx = ctx * jax.nn.sigmoid(gate)
        return x + ctx.reshape(t, heads * hd) @ p["o"].T

    def sparse(x, p):
        """Σ over a token's chosen experts that are held (e < held) of weight
        x SwiGLU_e(h), + the gated shared expert."""
        h = _rms0(x, p["post_norm"], eps)
        prob = jax.nn.softmax(h @ p["gate"].T, axis=-1)
        weight, chosen = jax.lax.top_k(prob, k_top)
        weight = weight / jnp.sum(weight, axis=1, keepdims=True)
        # the pairs elsewhere: one last group, whose expert is all zeros
        expert_of_pair = jnp.minimum(chosen.reshape(-1), held)
        order = jnp.argsort(expert_of_pair, stable=True)
        sizes = jnp.zeros((held + 1,), jnp.int32).at[expert_of_pair].add(1)
        rows = h[order // k_top]

        def product(x, w):  # w [held, out, in], the checkpoint's own layout
            w = jnp.concatenate([w, jnp.zeros_like(w[:1])])
            return jax.lax.ragged_dot(x, jnp.swapaxes(w, 1, 2), sizes)

        y = product(jax.nn.silu(product(rows, p["e_gate"])) * product(rows, p["e_up"]),
                    p["e_down"])
        y = y * weight.reshape(-1)[order][:, None]
        routed = jnp.zeros_like(h).at[order // k_top].add(y)
        opened = jax.nn.sigmoid(h @ p["s_open"].T)
        return x + routed + opened * _swiglu(h, p["s_gate"], p["s_up"], p["s_down"])

    @jax.jit
    def linear_layer(x, p, mlp):
        return sparse(linear(x, p), mlp)

    @jax.jit
    def full_layer(x, p, mlp):
        return sparse(full(x, p), mlp)

    @jax.jit
    def head(x, rows, norm, weight, ids):
        return (_rms0(x[rows], norm, eps) @ weight.T)[:, ids]

    return linear_layer, full_layer, head


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
    import jax
    import jax.numpy as jnp

    if not calls:
        return []
    linear_layer, full_layer, head = _functions(cfg)

    def f32(name):
        return jnp.asarray(np.asarray(state[name])).astype(jnp.float32)

    def stacked(base, kind):
        return jnp.stack(
            [
                jnp.asarray(np.asarray(state[f"{base}.mlp.experts.{e}.{kind}_proj.weight"]))
                for e in range(cfg["num_experts"])
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
        for i in range(cfg["num_hidden_layers"]):
            base = f"model.layers.{i}"
            p = {"input_norm": f32(f"{base}.input_layernorm.weight")}
            if (i + 1) % cfg["full_attention_interval"] == 0:
                att = f"{base}.self_attn"
                p.update(
                    {k: f32(f"{att}.{k}_proj.weight") for k in ("q", "k", "v", "o")},
                    q_norm=f32(f"{att}.q_norm.weight"), k_norm=f32(f"{att}.k_norm.weight"),
                )
                layer = full_layer
            else:
                lin = f"{base}.linear_attn"
                p.update(
                    in_qkvz=f32(f"{lin}.in_proj_qkvz.weight"), in_ba=f32(f"{lin}.in_proj_ba.weight"),
                    conv=f32(f"{lin}.conv1d.weight"), a_log=f32(f"{lin}.A_log"),
                    dt_bias=f32(f"{lin}.dt_bias"), norm=f32(f"{lin}.norm.weight"),
                    out=f32(f"{lin}.out_proj.weight"),
                )
                layer = linear_layer
            mlp = {
                "post_norm": f32(f"{base}.post_attention_layernorm.weight"),
                "gate": f32(f"{base}.mlp.gate.weight"),
                **{f"e_{kind}": stacked(base, kind) for kind in ("gate", "up", "down")},
                **{
                    f"s_{kind}": f32(f"{base}.mlp.shared_expert.{kind}_proj.weight")
                    for kind in ("gate", "up", "down")
                },
                "s_open": f32(f"{base}.mlp.shared_expert_gate.weight"),
            }
            xs = [layer(x, p, mlp) for x in xs]
            del p, mlp
        norm, weight = f32("model.norm.weight"), f32("lm_head.weight")
        ids = jnp.asarray(np.asarray(letter_ids, np.int32))
        return [
            np.asarray(
                head(x, jnp.asarray(np.asarray(rows, np.int32)), norm, weight, ids),
                np.float64,
            )
            for x, (_, rows) in zip(xs, calls)
        ]
