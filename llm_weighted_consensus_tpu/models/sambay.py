"""A decoder that feeds a decoder: Mamba layers and differential attention over
a window, ONE full layer whose keys and values are cached once for every cross
layer behind it, gated memory units on the last Mamba layer's scan: a judge.

``model_type`` ``phi4flash`` (microsoft/Phi-4-mini-flash-reasoning: SambaY),
written from its configuration and the family's papers.  ``LN(x; w, b)`` is
LayerNorm with a bias; layer i of n, K = ``config.kv_layer`` (n / 2 + 1):

  x0      = embed[ids]                                     no scale, no positions
  per layer:
    x     = x + mixer_i(LN(x; input_norm))
    x     = x + fc2 · (silu(g) ⊙ u),  [g | u] = fc1 · LN(x; post_norm)
  logits  = embed · LN(x[last]; final_norm)                tied

  mixer_i = Mamba             i even, i < K     (layer K - 1 also hands on its
                                                 scan's output m: THE MEMORY)
            sliding attention i odd,  i < K     ``sliding_window`` keys, its own among them
            full attention    i = K             its k, v are THE cache
            memory unit       i even, i > K     out · (m ⊙ silu(in · h)), m at the SAME position
            cross attention   i odd,  i > K     a query of its own against layer K's k, v

Mamba (``ops/selective_scan.py`` has the recurrence): [xs | z] = in · h; xc =
silu(causal convolution of ``d_conv`` taps a channel + bias); [dl | B | C] =
x · xc; dt_raw = dt · dl; m = the scan of xc under softplus(dt_raw + dt_bias),
A = -exp(A_log), B, C and D; out · (m ⊙ silu(z)).

Differential attention (sliding, full and cross alike): query heads (2j, 2j + 1)
are the two softmaxes of pair j, key heads (2m, 2m + 1) their keys where
j // G == m (G = heads / key heads), value heads (2m, 2m + 1) side by side ONE
value head V_m of 2 hd lanes that both weigh:

    a1_j = softmax(q_2j · k_2m / sqrt(hd)) V_m,   a2_j = softmax(q_2j+1 · k_2m+1 / sqrt(hd)) V_m
    λ    = exp(λq1 · λk1) - exp(λq2 · λk2) + λ_init(i),   λ_init(i) = 0.8 - 0.6 exp(-0.3 i)
    o_j  = rms(a1_j - λ a2_j; subln) · (1 - λ_init(i))     over the 2 hd lanes
    out  = W_o · [o_0 | o_1 | ..] + b_o                    Wq, Wk, Wv with biases too

HOW THE HEADS LIE.  ``ops/causal_attention.py`` takes a head as whole 128-lane
columns and finds a query head's key block by ``head // group``.  So the
loader lays every query and key head in ``head_lanes`` lanes (hd, then zero
lanes: zero columns of Wq and Wk, every q · k what it was, and the MXU, which
contracts in 128s, pays nothing) and ORDERS the query heads so that both
groupings are a division: laid head p = 2G m + G r + jj is the published head
2 (G m + jj) + r (softmax r of pair j = G m + jj).  Then p // G = 2m + r is its
key head, as published, and p // 2G = m its value head (the kernel's
``value_heads``): no key and no value is repeated in memory.  The context
comes back in the laid order, [m][r][jj] blocks of 2 hd lanes, so a1 and a2 of
a key pair's G pairs are two aligned halves and their difference is already in
the published order of pairs, which is the order of W_o's rows.

THE SPLIT.  Layers behind K at a position feed no other position (a cross
layer reads layer K's keys, a memory unit layer K - 1's scan at its own
position), and the panel reads the hidden state at ``lens - 1`` alone.  So
``prefill`` runs layers 0 .. K - 1 at every slot, layer K's keys and values at
every slot, and THE ROW at ``lens - 1`` (the stream and layer K - 1's m there)
through layer K's own attention and everything behind it: one row of scores a
layer against the cache's first ``lens`` keys.  It returns that row, [b, 1,
hidden].  ``tallies`` receives ``layer_positions`` [2] int32: positions x
layers computed (layer K counts whole: its keys are made at every slot), and
layers x slots.

THE CACHES, one entry a layer.  A Mamba layer: its convolution's tail [b,
d_conv - 1, d_inner] and its state [b, d_inner, d_state] float32 as they stand
after token ``lens - 1`` (dt is zero at and past ``lens``, so a padded slot
leaves the state alone).  A sliding layer: the ``window - 1`` keys and values
before ``lens``.  Layer K: its keys [b, s, kv heads * head_lanes] and values
[b, s, kv heads * hd] at every slot, ONCE: every cross layer's entry is that
same pair, not a copy.  A memory unit: nothing.  ``decode_step`` appends the
token's key and value to layer K's pair once, and the cross layers read the
appended arrays.

``jax.named_scope`` names every part, so that a device trace can be read by
layer: ``embed_tokens``; ``mamba_in``, ``mamba_conv`` (the convolution and the
two small products behind it), ``selective_scan`` (the kernel and the gate),
``mamba_out``; ``attn_qkv``, ``window_attention``, ``diff_norm``, ``attn_out``;
``mlp``; ``cross_decoder`` (everything at the read position: ``causal_attention``
for layer K's row, ``memory_unit`` and ``cross_attention`` beneath it);
``head_read``.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from ..ops import head_norm
from ..ops.causal_attention import band_pairs, block_for, window_attention_blockwise
from ..ops.selective_scan import selective_scan, selective_scan_step
from .configs import Phi4FlashConfig
from .decoder_parts import dense, quantize_dense, rms, swiglu  # noqa: F401  (the panel's protocol)
from .layers import layer_norm

_LANES = 128


def head_lanes(config: Phi4FlashConfig) -> int:
    """Lanes a query or key head is laid in: whole 128-lane columns."""
    return -(-config.head_dim // _LANES) * _LANES


def laid_heads(config: Phi4FlashConfig) -> list:
    """The published query head that lies at each laid position (see the
    module's text); its own inverse."""
    group = config.num_heads // config.num_kv_heads
    return [
        2 * (group * m + jj) + r
        for m in range(config.num_kv_heads // 2) for r in range(2) for jj in range(group)
    ]


def _silu_gate(m, z):
    """m ⊙ silu(z), lane for lane, in float32."""
    return (m.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))).astype(z.dtype)


def _rows_at(x, at):
    """x [b, s, w] -> x[b, at[b]] [b, w]."""
    return jnp.take_along_axis(x, at[:, None, None], axis=1)[:, 0]


# -- Mamba --------------------------------------------------------------------------


def _scan_inputs(xc, p: dict, config: Phi4FlashConfig):
    """xc [..., d_inner] -> (dt_raw [..., d_inner], B, C [..., d_state])."""
    rank, n = config.dt_rank, config.d_state
    dbc = dense(xc, p["x"])
    return dense(dbc[..., :rank], p["dt"]), dbc[..., rank:rank + n], dbc[..., rank + n:]


def _rates(p: dict):
    return -jnp.exp(p["a_log"].astype(jnp.float32))


def _mamba_prefill(h, p: dict, lens, config: Phi4FlashConfig):
    """h [b, s, hidden] -> (the layer's output [b, s, hidden], the scan's
    output m [b, s, d_inner], (convolution tail, state) after ``lens - 1``)."""
    s, taps = h.shape[1], config.d_conv
    with jax.named_scope("mamba_in"):
        xs, z = dense(h, p["in_x"]), dense(h, p["in_z"])
    with jax.named_scope("mamba_conv"):
        at = lens[:, None] - (taps - 1) + jnp.arange(taps - 1)[None, :]  # [b, taps - 1]
        tail = jnp.take_along_axis(xs, jnp.maximum(at, 0)[:, :, None], axis=1)
        tail = jnp.where((at >= 0)[:, :, None], tail, 0)
        padded = jnp.pad(xs, ((0, 0), (taps - 1, 0), (0, 0)))
        conv = sum(
            padded[:, j:j + s].astype(jnp.float32) * p["conv"][j].astype(jnp.float32)
            for j in range(taps)
        )
        xc = jax.nn.silu(conv + p["conv_bias"].astype(jnp.float32)).astype(h.dtype)
        dt_raw, b, c = _scan_inputs(xc, p, config)
    with jax.named_scope("selective_scan"):
        m, state = selective_scan(xc, dt_raw, p["dt_bias"], _rates(p), b, c, p["d"], lens)
        y = _silu_gate(m, z)
    with jax.named_scope("mamba_out"):
        return dense(y, p["out"]), m, (tail, state)


def _mamba_decode(h, p: dict, cache, config: Phi4FlashConfig):
    """One token a call, one step of the recurrence: h [b, hidden] -> (the
    layer's output, the scan's output m [b, d_inner])."""
    tail, state = cache
    with jax.named_scope("mamba_in"):
        xs, z = dense(h, p["in_x"]), dense(h, p["in_z"])
    with jax.named_scope("mamba_conv"):
        taps = jnp.concatenate([tail, xs[:, None, :]], axis=1).astype(jnp.float32)
        conv = jnp.sum(taps * p["conv"].astype(jnp.float32)[None], axis=1)
        xc = jax.nn.silu(conv + p["conv_bias"].astype(jnp.float32)).astype(h.dtype)
        dt_raw, b, c = _scan_inputs(xc, p, config)
    with jax.named_scope("selective_scan"):
        m, _ = selective_scan_step(state, xc, dt_raw, p["dt_bias"], _rates(p), b, c, p["d"])
        m = m.astype(h.dtype)
        y = _silu_gate(m, z)
    with jax.named_scope("mamba_out"):
        return dense(y, p["out"]), m


def _memory_unit(h, m, p: dict):
    """A gate on another layer's scan: out · (m ⊙ silu(in · h))."""
    with jax.named_scope("memory_unit"):
        return dense(_silu_gate(m, dense(h, p["in"])), p["out"])


# -- differential attention ---------------------------------------------------------


def _project(h, p: dict, which: str):
    return dense(h, p[which]) + p[which + "_bias"].astype(h.dtype)


def _diff_norm(ctx, p: dict, config: Phi4FlashConfig, layer: int):
    """ctx [..., heads * 2 hd] in the laid order -> [..., hidden]: a1 - λ a2 a
    pair, its norm over the 2 hd lanes, the (1 - λ_init) scale.  The lanes
    are cut where they lie (a key pair's [a1 | a2] halves: a reshape to heads
    would lay a prefill's array out again, ``ops/head_norm.py``), and a
    prefill's pairs of one 128-lane column are normalised by that kernel."""
    with jax.named_scope("diff_norm"):
        dv = 2 * config.head_dim
        half = config.num_heads // config.num_kv_heads * dv  # a key pair's a1, or its a2
        f32 = lambda name: p[name].astype(jnp.float32)  # noqa: E731
        init = config.lambda_init(layer)
        lam = (
            jnp.exp(jnp.sum(f32("lambda_q1") * f32("lambda_k1")))
            - jnp.exp(jnp.sum(f32("lambda_q2") * f32("lambda_k2"))) + init
        )
        cut = lambda r: jnp.concatenate(  # noqa: E731
            [ctx[..., c0 + r * half:c0 + (r + 1) * half]
             for c0 in range(0, ctx.shape[-1], 2 * half)], axis=-1,
        ).astype(jnp.float32)
        diff = cut(0) - lam * cut(1)  # [..., pairs * dv], the published order
        weight = f32("subln") * (1.0 - init)
        if head_norm.fits(diff.shape, dv):
            return head_norm.head_norm_turn(
                diff.astype(ctx.dtype), weight, eps=config.layer_norm_eps
            )
        diff = diff.reshape(*diff.shape[:-1], -1, dv)
        return rms(diff, weight, config.layer_norm_eps).astype(ctx.dtype).reshape(
            *ctx.shape[:-1], -1
        )


def _attn_out(ctx, p: dict, config: Phi4FlashConfig, layer: int):
    o = _diff_norm(ctx, p, config, layer)
    with jax.named_scope("attn_out"):
        return _project(o, p, "o")


def _attend_rows(q, k_all, v_all, seen, config: Phi4FlashConfig):
    """One row a call against cached keys: q [b, heads * lanes] in the laid
    order, k_all [b, slots, kv heads * lanes], v_all [b, slots, kv heads * hd],
    ``seen`` [b, slots] -> the context [b, heads * 2 hd] in the laid order.
    Scores and softmax in float32."""
    b, slots = k_all.shape[:2]
    kv, lanes = config.num_kv_heads, head_lanes(config)
    scores = jnp.einsum(
        "bgrd,btgd->bgrt", q.reshape(b, kv, -1, lanes), k_all.reshape(b, slots, kv, lanes),
        preferred_element_type=jnp.float32,
    ) * config.head_dim ** -0.5
    scores = jnp.where(seen[:, None, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    ctx = jnp.einsum(  # two key heads' rows on ONE value head of 2 hd lanes
        "bmrt,btmd->bmrd", probs.reshape(b, kv // 2, -1, slots),
        v_all.reshape(b, slots, kv // 2, -1), preferred_element_type=jnp.float32,
    )
    return ctx.astype(q.dtype).reshape(b, -1)


def _sliding_prefill(h, p: dict, lens, config: Phi4FlashConfig, layer: int):
    """h [b, s, hidden] -> (the layer's output, (keys, values) at the
    ``window - 1`` positions before ``lens``)."""
    with jax.named_scope("attn_qkv"):
        q, k, v = (_project(h, p, which) for which in "qkv")
    with jax.named_scope("window_attention"):
        # blocks as wide as the window: a query block meets two key blocks, the
        # old edge's and the diagonal's (``window_block`` would halve them, three
        # times the grid steps for the same pairs multiplied)
        block = block_for(h.shape[1], cap=max(config.sliding_window, 8))
        ctx = window_attention_blockwise(
            q, k, v, heads=config.num_heads, kv_heads=config.num_kv_heads,
            value_heads=config.num_kv_heads // 2, scale=config.head_dim ** -0.5,
            window=config.sliding_window, block_q=block, block_k=block,
        )
    with jax.named_scope("attn_qkv"):
        back = config.sliding_window - 1
        at = jnp.maximum(lens[:, None] - back + jnp.arange(back), 0)[..., None]
        k, v = (jnp.take_along_axis(x, at, axis=1) for x in (k, v))
    return _attn_out(ctx, p, config, layer), (k, v)


def _sliding_decode(h, p: dict, lens, cache, config: Phi4FlashConfig, layer: int):
    """One token a call at position ``lens[b]`` against its window's cache
    (slot j is position ``lens - (window - 1) + j``) and its own key."""
    with jax.named_scope("attn_qkv"):
        q, k_new, v_new = (_project(h, p, which) for which in "qkv")
        k_all = jnp.concatenate([cache[0], k_new[:, None, :]], axis=1)
        v_all = jnp.concatenate([cache[1], v_new[:, None, :]], axis=1)
    with jax.named_scope("window_attention"):
        slots = k_all.shape[1]
        seen = jnp.arange(slots)[None, :] >= slots - 1 - lens[:, None]
        ctx = _attend_rows(q, k_all, v_all, seen, config)
    return _attn_out(ctx, p, config, layer)


def _cross_row(h, p: dict, kv, seen, config: Phi4FlashConfig, layer: int, scope: str):
    """One row a call, a query of its own against layer K's keys and values."""
    with jax.named_scope(scope):
        ctx = _attend_rows(_project(h, p, "q"), *kv, seen, config)
    return _attn_out(ctx, p, config, layer)


# -- the panel's protocol (models/judge.py) ---------------------------------------------


def _norm(x, layer: dict, which: str, config: Phi4FlashConfig):
    return layer_norm(x, layer[which], config.layer_norm_eps)


def _mlp(x, layer: dict, config: Phi4FlashConfig):
    with jax.named_scope("mlp"):
        return x + swiglu(_norm(x, layer, "post_norm", config), layer["mlp"])


def _embed(params: dict, ids):
    with jax.named_scope("embed_tokens"):
        return jnp.take(params["token_embed"], ids, axis=0)


def _behind(params: dict, x, memory, kv, seen, config: Phi4FlashConfig):
    """Layer K's own attention and every layer behind it over one row a call:
    x [b, hidden] the stream in front of layer K, ``memory`` layer K - 1's scan
    there, ``kv`` layer K's keys and values of which the row sees ``seen``."""
    for i in range(config.kv_layer, config.num_layers):
        layer = params["layers"][i]
        h = _norm(x, layer, "input_norm", config)
        if config.kind(i) == "memory":
            out = _memory_unit(h, memory, layer["memory"])
        else:
            scope = "causal_attention" if i == config.kv_layer else "cross_attention"
            out = _cross_row(h, layer["attn"], kv, seen, config, i, scope)
        x = _mlp(x + out, layer, config)
    return x


def prefill(params: dict, ids, config: Phi4FlashConfig, lens=None, tallies=None):
    """ids [b, s] right-padded calls of ``lens`` tokens -> (hidden [b, 1,
    hidden] at ``lens - 1`` before the final norm, a cache a layer, no loads).
    Without ``lens`` every slot is a token.  A ``tallies`` dict handed in
    receives ``window_keys`` [2] int32 (the pairs inside the sliding layers'
    bands, and the causal pairs those were taken from, over every slot) and
    ``layer_positions`` [2] int32 (positions x layers computed, and layers x
    slots)."""
    b, s = ids.shape
    if lens is None:
        lens = jnp.full((b,), s, jnp.int32)
    x = _embed(params, ids)
    caches, memory, top = [], None, config.kv_layer
    for i in range(top):
        layer = params["layers"][i]
        h = _norm(x, layer, "input_norm", config)
        if config.kind(i) == "mamba":
            out, memory, cache = _mamba_prefill(h, layer["mamba"], lens, config)
        else:
            out, cache = _sliding_prefill(h, layer["attn"], lens, config, i)
        caches.append(cache)
        x = _mlp(x + out, layer, config)
    # layer K: keys and values at every slot, everything else at the row read
    layer = params["layers"][top]
    with jax.named_scope("attn_qkv"):
        h = _norm(x, layer, "input_norm", config)
        kv = (_project(h, layer["attn"], "k"), _project(h, layer["attn"], "v"))
    with jax.named_scope("cross_decoder"):
        seen = jnp.arange(s)[None, :] < lens[:, None]
        row = _behind(params, _rows_at(x, lens - 1), _rows_at(memory, lens - 1), kv, seen, config)
    for i in range(top, config.num_layers):
        caches.append(() if config.kind(i) == "memory" else kv)
    if tallies is not None:
        sliding = sum(config.kind(i) == "sliding" for i in range(top))
        tallies["window_keys"] = jnp.asarray(
            [sliding * b * band_pairs(s, config.sliding_window),
             sliding * b * (s * (s + 1) // 2)], jnp.int32,
        )
        tallies["layer_positions"] = jnp.asarray(
            [b * ((top + 1) * s + config.num_layers - top - 1), b * config.num_layers * s],
            jnp.int32,
        )
    return row[:, None, :], caches, []


def decode_step(params: dict, token, lens, caches, config: Phi4FlashConfig):
    """One token a call at position ``lens`` -> hidden [b, hidden]."""
    x = _embed(params, token)
    top, memory = config.kv_layer, None
    for i in range(top):
        layer = params["layers"][i]
        h = _norm(x, layer, "input_norm", config)
        if config.kind(i) == "mamba":
            out, memory = _mamba_decode(h, layer["mamba"], caches[i], config)
        else:
            out = _sliding_decode(h, layer["attn"], lens, caches[i], config, i)
        x = _mlp(x + out, layer, config)
    # the token's key and value behind layer K's, once, for every layer that reads them
    layer = params["layers"][top]
    with jax.named_scope("attn_qkv"):
        h = _norm(x, layer, "input_norm", config)
        kv = tuple(
            jnp.concatenate([old, _project(h, layer["attn"], which)[:, None, :]], axis=1)
            for old, which in zip(caches[top], "kv")
        )
    with jax.named_scope("cross_decoder"):
        slots = kv[0].shape[1]
        t = jnp.arange(slots)[None, :]
        seen = (t < lens[:, None]) | (t == slots - 1)  # the cache, and itself
        return _behind(params, x, memory, kv, seen, config)


def head_logprobs(params: dict, hidden, config: Phi4FlashConfig):
    """hidden [b, hidden] -> log-probabilities over the vocabulary, float32;
    the head is the embedding."""
    with jax.named_scope("head_read"):
        h = _norm(hidden, params, "final_norm", config)
        logits = jnp.einsum(
            "bh,vh->bv", h, params["token_embed"], preferred_element_type=jnp.float32
        )
        return logits - jax.nn.logsumexp(logits, axis=-1, keepdims=True)


def experts_held(params: dict, config: Phi4FlashConfig) -> int:
    return 0


def whole_bound_layers(load, config: Phi4FlashConfig) -> int:
    return 0


def expert_tiles(load, config: Phi4FlashConfig) -> tuple[int, int]:
    return 0, 0


# -- parameters -------------------------------------------------------------------------


def _lay_heads(w, heads: int, config: Phi4FlashConfig, order=None):
    """w [..., heads * hd] -> [..., heads * head_lanes]: every head in whole
    columns with zero lanes behind it, the heads in ``order``."""
    hd, lanes = config.head_dim, head_lanes(config)
    w = w.reshape(*w.shape[:-1], heads, hd)
    if order is not None:
        w = w[..., jnp.asarray(order), :]
    w = jnp.pad(w, [(0, 0)] * (w.ndim - 1) + [(0, lanes - hd)])
    return w.reshape(*w.shape[:-2], heads * lanes)


def init_params(rng, config: Phi4FlashConfig, dtype=jnp.float32) -> dict:
    """Random parameters in the served layout (tests, shape work)."""
    std = 0.02
    drawn = iter(range(1 << 30))

    def normal(*shape, dt=dtype, mean=0.0):  # a key of its own per tensor
        key = jax.random.fold_in(rng, next(drawn))
        return (mean + jax.random.normal(key, shape, jnp.float32) * std).astype(dt)

    def kernel(i, o):
        return {"kernel": normal(i, o)}

    def norm():
        return {"scale": normal(h, mean=1.0), "bias": normal(h)}

    h, hd, inner, n = config.hidden_size, config.head_dim, config.d_inner, config.d_state
    heads, kv = config.num_heads, config.num_kv_heads
    order = laid_heads(config)
    layers = []
    for i in range(config.num_layers):
        kind = config.kind(i)
        layer = {
            "input_norm": norm(), "post_norm": norm(),
            "mlp": {
                "gate": kernel(h, config.intermediate_size),
                "up": kernel(h, config.intermediate_size),
                "down": kernel(config.intermediate_size, h),
            },
        }
        if kind == "mamba":
            layer["mamba"] = {
                "in_x": kernel(h, inner), "in_z": kernel(h, inner),
                "conv": normal(config.d_conv, inner), "conv_bias": normal(inner),
                "x": kernel(inner, config.dt_rank + 2 * n),
                "dt": kernel(config.dt_rank, inner), "dt_bias": normal(inner),
                "a_log": normal(inner, n, dt=jnp.float32), "d": normal(inner, mean=1.0),
                "out": kernel(inner, h),
            }
        elif kind == "memory":
            layer["memory"] = {"in": kernel(h, inner), "out": kernel(inner, h)}
        else:
            attn = {
                "q": {"kernel": _lay_heads(normal(h, heads * hd), heads, config, order)},
                "q_bias": _lay_heads(normal(heads * hd), heads, config, order),
                "o": kernel(h, h), "o_bias": normal(h),
                **{name: normal(hd, dt=jnp.float32)
                   for name in ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2")},
                "subln": normal(2 * hd, mean=1.0),
            }
            if kind != "cross":
                attn.update(
                    k={"kernel": _lay_heads(normal(h, kv * hd), kv, config)},
                    k_bias=_lay_heads(normal(kv * hd), kv, config),
                    v=kernel(h, kv * hd), v_bias=normal(kv * hd),
                )
            layer["attn"] = attn
        layers.append(layer)
    return {
        "token_embed": normal(config.vocab_size, h),
        "final_norm": norm(),
        "layers": layers,
    }


def from_hf_weights(state, config: Phi4FlashConfig, dtype=jnp.float32):
    """HF-named tensors (a mapping that may open each tensor lazily:
    ``loading.open_checkpoint``) -> (params, config).  A layer goes to the
    device before the next is read.  What is served is what the checkpoint
    names: its layers from 0 up, each of the kind its number gives it in a
    stack of that depth, and the rows of the vocabulary ``embed_tokens`` holds
    (the head is the embedding: ``tie_word_embeddings``).  The fused ``Wqkv``
    is cut into its three products and the query and key heads are laid in
    whole columns, the query heads in the kernel's order (the module's text)."""
    import numpy as np

    prefix = "model." if "model.embed_tokens.weight" in state else ""
    depth = 0
    while f"{prefix}layers.{depth}.input_layernorm.weight" in state:
        depth += 1
    if not depth:
        raise ValueError("the checkpoint names no layer (layers.0.input_layernorm.weight)")
    embed = np.asarray(state[prefix + "embed_tokens.weight"])
    config = dataclasses.replace(config, num_layers=depth, vocab_size=int(embed.shape[0]))
    heads, kv, hd = config.num_heads, config.num_kv_heads, config.head_dim
    order = laid_heads(config)

    def get(name):
        return np.asarray(state[prefix + name])

    def put(array, dt=dtype):
        return jnp.asarray(array).astype(dt)

    swap = jax.jit(lambda w: jnp.swapaxes(w, -1, -2))

    def kernel(name):  # HF [out, in] -> [in, out], transposed on the device
        return {"kernel": swap(put(get(name + ".weight")))}

    def norm(name):
        return {"scale": put(get(name + ".weight")), "bias": put(get(name + ".bias"))}

    layers = []
    for n in range(depth):
        base, mix = f"layers.{n}", f"layers.{n}.attn"
        kind = config.kind(n)
        fc1 = swap(put(get(f"{base}.mlp.fc1.weight")))  # [hidden, gate | up]
        layer = {
            "input_norm": norm(f"{base}.input_layernorm"),
            "post_norm": norm(f"{base}.post_attention_layernorm"),
            "mlp": {
                "gate": {"kernel": fc1[:, : config.intermediate_size]},
                "up": {"kernel": fc1[:, config.intermediate_size:]},
                "down": kernel(f"{base}.mlp.fc2"),
            },
        }
        if kind == "mamba":
            fused = swap(put(get(f"{mix}.in_proj.weight")))  # [hidden, xs | z]
            if fused.shape != (config.hidden_size, 2 * config.d_inner):
                raise ValueError(
                    f"layer {n}: in_proj is {fused.shape[::-1]}, the preset's is "
                    f"{(2 * config.d_inner, config.hidden_size)}"
                )
            layer["mamba"] = {
                "in_x": {"kernel": fused[:, : config.d_inner]},
                "in_z": {"kernel": fused[:, config.d_inner:]},
                "conv": put(get(f"{mix}.conv1d.weight")[:, 0, :].T),
                "conv_bias": put(get(f"{mix}.conv1d.bias")),
                "x": kernel(f"{mix}.x_proj"),
                "dt": kernel(f"{mix}.dt_proj"),
                "dt_bias": put(get(f"{mix}.dt_proj.bias")),
                "a_log": put(get(f"{mix}.A_log"), jnp.float32),
                "d": put(get(f"{mix}.D")),
                "out": kernel(f"{mix}.out_proj"),
            }
        elif kind == "memory":
            layer["memory"] = {"in": kernel(f"{mix}.in_proj"), "out": kernel(f"{mix}.out_proj")}
        else:
            inner = f"{mix}.inner_cross_attn"
            wide = heads * hd
            fused = "Wq" if kind == "cross" else "Wqkv"
            w, bias = swap(put(get(f"{mix}.{fused}.weight"))), put(get(f"{mix}.{fused}.bias"))
            if w.shape[1] != (wide if kind == "cross" else wide + 2 * kv * hd):
                raise ValueError(
                    f"layer {n}: {fused} is {w.shape[::-1]}, the preset's {heads} query "
                    f"heads on {kv} key heads of {hd} give another"
                )
            attn = {
                "q": {"kernel": _lay_heads(w[:, :wide], heads, config, order)},
                "q_bias": _lay_heads(bias[:wide], heads, config, order),
                "o": kernel(f"{mix}.out_proj"),
                "o_bias": put(get(f"{mix}.out_proj.bias")),
                **{name: put(get(f"{inner}.{name}"), jnp.float32)
                   for name in ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2")},
                "subln": put(get(f"{inner}.subln.weight")),
            }
            if kind != "cross":
                cut = wide + kv * hd
                attn.update(
                    k={"kernel": _lay_heads(w[:, wide:cut], kv, config)},
                    k_bias=_lay_heads(bias[wide:cut], kv, config),
                    v={"kernel": w[:, cut:]}, v_bias=bias[cut:],
                )
            layer["attn"] = attn
        layers.append(layer)
    params = {
        "token_embed": put(embed),
        "final_norm": norm("final_layernorm"),
        "layers": layers,
    }
    return params, config
