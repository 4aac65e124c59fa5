"""A causal decoder with latent attention and sparse experts: the judge.

``model_type`` ``glm4_moe_lite`` (zai-org/GLM-4.7-Flash), the DeepSeek-V3
layout, ``glm_moe_dsa`` (zai-org/GLM-5.2), the same layers behind a learned
sparse selection, and ``dots3_note`` (dots-studio/dots3-note-prev), whose
attention layers are of TWO kinds; written from their configurations:

  x0      = embed[ids]
  per layer:
    h     = rms(x, w_in)
    q     = W_qb · rms(W_qa · h)              heads of nope + rope dims
    c, kr = W_kva · h ;  c = rms(c)           latent (cached), one rotary key
    k, v  = W_kvb · c                         per head: nope dims | values
    a     = causal softmax((q_nope·k + rope(q_rope)·rope(kr)) / sqrt(d)) v
    x     = x + W_o · a
    h     = rms(x, w_post)
    x     = x + SwiGLU(h)                     the first ``first_k_dense_replace`` layers
    x     = x + Σ_top-k w_e · SwiGLU_e(h) + SwiGLU_shared(h)   the others
  logits  = W_head · rms(x[last], w_final)

The router scores every expert with ``sigmoid(W_g · h)`` in float32, chooses
the top k of score + ``e_score_correction_bias`` (the bias chooses, it does
not weigh), and weighs the chosen by their unbiased scores, normalised to sum
1, times ``routed_scaling_factor``.

With an indexer (``index_topk`` > 0; the DeepSeek-V3.2 ``Indexer``) a query
attends only the keys a second, small attention chooses.  A layer whose
``indexer_types`` entry is ``full`` owns one:

    q_I   = W_Iq · rms(W_qa · h)                 heads of ``index_head_dim``
    k_I   = LayerNorm(W_Ik · h)                  ONE key a position
    w     = W_Iw · h · heads^-1/2 · dim^-1/2     a weight a head, float32
    score[t, s] = Σ_j w[t, j] · ReLU(q_I[t, j] · k_I[s])          s <= t
    S_t   = the min(index_topk, t + 1) positions of largest score

(the first ``qk_rope_head_dim`` dims of an index head and of the key turned),
and the softmax above runs over s in S_t.  A ``shared`` layer attends over the
S_t of the last ``full`` layer before it and has no indexer weights: the
selection is the one value the layer loop carries.  ``ops/sparse_index.py``
scores and chooses; the choice is one int8 a (query, key) pair, which the
attention kernel reads as a tile beside q, k and v.  The cache then has three
kinds: the latent, the rotary key and, on a ``full`` layer, the index keys,
from which the decoded token chooses among the positions it sees.

Two kinds of attention layer in one stack (``layer_types`` non-empty).  Every
attention layer reads ONE small record, ``config.geometry(layer)``
(``configs.AttentionGeometry``): heads, the two latent ranks, nope | rope |
value dims, the rotary base, a window, a gate, the latents' scales.  A
decoder of one kind is the case where every layer's record is the same; the
loops, the kernels' calls and the loader have no other path.

    kind of layer i  = layer_types[i]          full_attention | sliding_attention
    full:    the plain fields; owns an indexer (EVERY full layer: none shares)
    sliding: the ``swa_*`` fields; query t attends t - window < s <= t
             (``sliding_window`` keys, its own among them); no indexer, and
             whatever an earlier layer chose is nothing to it
    cq    = a_q  · rms(W_qa · h)               a_q  = sqrt(hidden / q_lora_rank)
    c     = a_kv · rms(c)                      a_kv = sqrt(hidden / kv_lora_rank)
            (``lora_rescale``; folded into the norm's weight in float32, so the
            latent is rounded once; the rotary key is never rescaled)
    g     = sigmoid(W_g · h)   [heads]         ``attention_gate``: a scalar a head
    x     = x + W_o · concat_j(g_j · a_j)      (scope ``attn_gate``)

A value head may be narrower than a key head (128 against 192 or 256): the
attention kernels take the two widths apart.  A key head wider than one
128-lane column that is not whole columns (128 | 64 = 192) is LAID in whole
columns by the loader (``AttentionGeometry.laid``: zero rows 128..191 of the
head in ``q_b``, zero lanes in ``w_k``, the rotary lanes last and inside one
column, so ``ops/rotary.py`` turns them in place); every q·k is what it was and
the softmax scale stays 1 / sqrt(192).  A sliding layer's prefill runs
``window_attention_blockwise`` (the blockwise kernel's body over the band's
steps, scope ``window_attention``) and leaves a WINDOWED cache: the (c, kr) of
the ``window - 1`` positions before a call's length, all the next token can
see.  So the cache has four kinds in one list (a full layer's latent, rotary
key and index keys over every position; a sliding layer's latent, of its own
rank, and rotary key over the window), and a decode step is absorbed
attention over the token's own top ``index_topk`` on the full layers and over
the window on the sliding ones.  ``prefill`` counts ``window_keys`` (the pairs
inside the bands, the causal pairs) beside ``index_keys``.

A sparse layer may hold a SHARE of its router's experts (the checkpoint names
experts 0..E-1 of ``n_routed_experts``): ``experts_grouped(..., held=E)``
returns this chip's partial sum.

Two attention paths over the same weights.  PREFILL makes keys and values
from the latent (``W_kvb`` applied) and runs the causal blockwise kernel
(``ops/causal_attention.py``); what it leaves behind is the latent cache:
``c`` and the rotary key, 576 values a token a layer.  DECODE is the
ABSORBED path: ``W_kvb``'s key half is folded into the query and its value
half into the output, so scores are taken against the cached latent itself
and no key or value is ever rebuilt.

Rotary dims: the checkpoint stores a head's rotary dims interleaved (pair
(2i, 2i+1) turns together, as in the DeepSeek-V3 checkpoints).  The loader
moves them to (i, i + d/2), the same permutation on the query's and the
key's rows, which leaves every q·k unchanged and lets the rotation be a
half-swap.  WHERE the turn happens follows from the array's shape
(``_turn_heads``).  A prefill's queries [b, s, heads * hd] with heads of
whole 128-lane columns and the rotary dims inside one of them (the published
presets: 192 | 64 of 256 lanes, or 128 | 64 laid in 256; an index head's first
64 of 128) stay as the
product wrote them and are turned IN PLACE by ``ops/rotary.py``'s kernel,
which reads and writes only the column that holds the rotary lanes; nothing of
the queries' size is sliced, padded or concatenated.  Any other shape (the
tiny presets' 24 | 8, a decode step's [b, width] rows) is cut apart, turned by
``decoder_parts.rope`` and put together again: the same float32 arithmetic,
rounded once.  The ONE rotary key (``_latent``, what the cache holds) is narrow
and always takes ``decoder_parts.rope``; a prefill then writes it into every
head's rope lanes through the key product itself (``_keys``: ``w_k``'s rope
lanes are zero, so [c | kr] x [w_k ; I] puts it there exactly), at every shape.

Routed experts run as TWO kernels over the tokens routed to each
(``ops/grouped_matmul.py``): gate and up as one product with SwiGLU on its
float32 accumulators, then down with the router's weight of each row on its
accumulator.  Around them three stages, each a ``jax.named_scope`` under
``experts_routed``: ``experts_layout`` (the pairs sorted by expert into padded
row tiles, and the rows of ``h`` gathered into that order from column chunks
that stay in VMEM), ``experts_swiglu`` (the kernels), ``experts_combine``
(each token's k rows gathered back, a gather a choice, and summed; nothing
is multiplied there).  A decode step's handful of tokens goes through the
same path in tiles of 16 rows (tiles that hold no row fetch and compute
nothing).  ``jax.named_scope`` names every part, so that a
device trace can be read by layer.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..ops.causal_attention import (
    band_pairs, causal_attention_blockwise, window_attention_blockwise,
)
from ..ops.sparse_index import (
    index_scores, index_scores_einsum, index_select, select_topk_dense,
)
from .configs import AttentionGeometry, GlmMoeLiteConfig
from .decoder_parts import dense as _dense
from .decoder_parts import (  # noqa: F401
    experts_grouped, layers_past_usual, quantize_dense, tiles_laid_and_in_use,
)
from .decoder_parts import rms as _rms
from .decoder_parts import rope as _rope
from .decoder_parts import rope_angles as _rope_angles
from .decoder_parts import route_sigmoid
from .decoder_parts import swiglu as _swiglu
from .decoder_parts import turn_heads as _turn_heads


def _scaled(weight, scale: float):
    """A norm's weight with a latent's rescale folded in (float32, so the
    normalised latent is still rounded once); the weight itself at 1."""
    return weight if scale == 1.0 else weight.astype(jnp.float32) * scale


def _queries(h, p: dict, cos, sin, geo: AttentionGeometry, eps: float):
    """h [..., hidden] -> (q [..., heads * laid], a head's last rope lanes
    turned, the normalised (and rescaled) query latent [..., q_lora_rank] an
    indexer reads too); cos, sin [..., rope / 2], a position's."""
    cq = _rms(_dense(h, p["q_a"]), _scaled(p["q_a_norm"], geo.q_scale), eps)
    q = _dense(cq, p["q_b"])
    return _turn_heads(q, cos, sin, geo.heads, geo.laid - geo.rope), cq


def _latent(h, p: dict, cos, sin, geo: AttentionGeometry, eps: float):
    """h [..., hidden] -> (c [..., kv_lora_rank] normalised (and rescaled),
    rotary key [..., rope] turned, never rescaled): what the cache holds."""
    rank = geo.kv_lora_rank
    kv = _dense(h, p["kv_a"])
    c = _rms(kv[..., :rank], _scaled(p["kv_a_norm"], geo.kv_scale), eps)
    return c, _rope(kv[..., rank:], cos, sin)


def _gated(ctx, h, p: dict, heads: int):
    """The headwise gate: ctx [..., heads * dv] times sigmoid(W_g h) a head; a
    layer without a gate hands ctx back.  A head's scalar is spread over its
    dv lanes by a product with a 0/1 matrix [heads, heads * dv] (exact: one
    term a lane), so the multiply is that product's epilogue over the flat
    context: cutting the context into [.., heads, dv] to broadcast the gate
    made XLA lay out the broadcast and its reshape whole, 3.2 GB of float32 a
    full layer (the chip's compiler in the sandbox, PR 39)."""
    if "gate" not in p:
        return ctx
    with jax.named_scope("attn_gate"):
        g = jax.nn.sigmoid(_dense(h, p["gate"]).astype(jnp.float32)).astype(ctx.dtype)
        spread = jnp.repeat(jnp.eye(heads, dtype=ctx.dtype), ctx.shape[-1] // heads, axis=1)
        wide = jnp.einsum("...h,hn->...n", g, spread, preferred_element_type=jnp.float32)
        return (ctx.astype(jnp.float32) * wide).astype(ctx.dtype)


def _keys(c, kr, w_k):
    """The latent c [b, s, rank] and the rotary key kr [b, s, rope] -> k
    [b, s, heads * (nope + rope)]: W_kvb's key half applied, the one rotary
    key in every head's rope lanes.  ``w_k``'s rope lanes are zero, so the
    rotary key rides the same product through an identity into them,
    [c | kr] x [w_k ; I]: nothing of the keys' size is passed over again."""
    rank, heads, dq = w_k.shape
    rope = kr.shape[-1]
    into = jnp.eye(rope, dq, k=dq - rope, dtype=w_k.dtype)
    w = jnp.concatenate([w_k, jnp.broadcast_to(into[:, None, :], (rope, heads, dq))])
    return jnp.einsum(
        "bsc,cn->bsn", jnp.concatenate([c, kr], axis=-1),
        w.reshape(rank + rope, heads * dq), preferred_element_type=jnp.float32,
    ).astype(c.dtype)


def _index_terms(h, cq, p: dict, cos, sin, config: GlmMoeLiteConfig):
    """The indexer's three products over h [..., hidden] and the query latent
    cq: (q_I [..., heads * dim] turned, k_I [..., dim] normalised and turned,
    w [..., heads] float32, the heads' weights with both scales folded in).
    cos, sin [..., rope / 2], a position's."""
    heads, dim = config.index_n_heads, config.index_head_dim
    with jax.named_scope("index_q"):
        # an index head's first ``qk_rope_head_dim`` dims turn, the rest stay
        q = _turn_heads(_dense(cq, p["q"]), cos, sin, heads, 0)
        w = jnp.einsum(
            "...i,io->...o", h, p["w"], preferred_element_type=jnp.float32
        ) * (heads**-0.5 * dim**-0.5)
    with jax.named_scope("index_k"):
        k = _dense(h, p["k"]).astype(jnp.float32)
        k = k - jnp.mean(k, axis=-1, keepdims=True)
        k = k * jax.lax.rsqrt(jnp.mean(k * k, axis=-1, keepdims=True) + config.index_norm_eps)
        k = (k * p["k_norm"].astype(jnp.float32) + p["k_bias"].astype(jnp.float32)).astype(h.dtype)
        k = _turn_heads(k, cos, sin, 1, 0)
    return q, k, w


def _attention_prefill(
    h, p: dict, config: GlmMoeLiteConfig, keep=None, geo: AttentionGeometry | None = None,
    lens=None,
):
    """h [b, s, hidden] -> (attention output [b, s, hidden], the layer's
    cache, the selection it attended over).  A layer with an indexer chooses
    each query's keys (``keep`` [b, s, s] int8, ``ops/sparse_index.py``) and
    caches its index keys beside (c, kr); a layer without one attends over
    the ``keep`` it is handed, the last chosen; None is every causal key.  A
    layer with a WINDOW attends its band whatever it is handed, and caches
    the (c, kr) of the ``window - 1`` positions before ``lens`` only (what the
    next token can see; slots before position 0 are padding).  ``geo`` is the
    layer's geometry where layers are of two kinds; a decoder of one kind has
    one, and its call names none."""
    s = h.shape[1]
    geo = geo or config.geometry(0)
    heads, eps = geo.heads, config.rms_norm_eps
    cos, sin = _rope_angles(jnp.arange(s), geo.rope, geo.theta)
    with jax.named_scope("latent_q"):
        q, cq = _queries(h, p, cos, sin, geo, eps)
    with jax.named_scope("latent_kv"):
        c, kr = _latent(h, p, cos, sin, geo, eps)
        k = _keys(c, kr, p["w_k"])
        v = jnp.einsum(
            "bsc,cv->bsv", c, p["w_v"], preferred_element_type=jnp.float32
        ).astype(h.dtype)
    cache = (c, kr)
    scale = 1.0 / math.sqrt(geo.head_dim)
    if geo.window:
        with jax.named_scope("window_attention"):
            ctx = window_attention_blockwise(
                q, k, v, heads=heads, scale=scale, window=geo.window
            )
        with jax.named_scope("latent_kv"):
            back = geo.window - 1
            ends = jnp.full((h.shape[0],), s, jnp.int32) if lens is None else lens
            at = jnp.maximum(ends[:, None] - back + jnp.arange(back), 0)[..., None]
            cache = tuple(jnp.take_along_axis(x, at, axis=1) for x in cache)
    else:
        if "indexer" in p:
            q_i, k_i, w = _index_terms(h, cq, p["indexer"], cos, sin, config)
            with jax.named_scope("index_scores"):
                scores = index_scores(q_i, k_i, w, heads=config.index_n_heads)
            with jax.named_scope("index_select"):
                keep = index_select(scores, k=config.index_topk)
            cache = (c, kr, k_i)
        with jax.named_scope("causal_attention" if keep is None else "selected_attention"):
            ctx = causal_attention_blockwise(q, k, v, keep, heads=heads, scale=scale)
    ctx = _gated(ctx, h, p, heads)
    with jax.named_scope("attn_out"):
        out = _dense(ctx, p["o"])
    return out, cache, keep


def _attention_decode(
    h, p: dict, lens, cache, config: GlmMoeLiteConfig, chosen=None,
    geo: AttentionGeometry | None = None,
):
    """One token a call through the latent cache, the absorbed path.
    h [b, hidden] at position ``lens[b]``; cache (c [b, s, rank], kr
    [b, s, rope]) holds positions < lens[b] (later slots are padding), and
    on a layer with an indexer its index keys [b, s, dim] too: there the
    token chooses ``index_topk`` of the positions it sees, and ``chosen``
    [b, s + 1] bool goes on to the layers without one.  A layer with a WINDOW
    holds the ``window - 1`` positions before ``lens[b]`` (slot j is position
    ``lens[b] - (window - 1) + j``; those before 0 are padding) and attends
    them and itself, whatever was chosen elsewhere.  ``geo`` as in
    ``_attention_prefill``.  Returns (output, chosen)."""
    b = h.shape[0]
    geo = geo or config.geometry(0)
    heads, eps = geo.heads, config.rms_norm_eps
    cos, sin = _rope_angles(lens, geo.rope, geo.theta)
    with jax.named_scope("latent_q"):
        q, cq = _queries(h, p, cos, sin, geo, eps)
        q = q.reshape(b, heads, geo.laid)
        # W_kvb's key half folded into the query: scores against the latent
        q_lat = jnp.einsum(
            "bhd,chd->bhc", q, p["w_k"], preferred_element_type=jnp.float32
        ).astype(h.dtype)
    with jax.named_scope("latent_kv"):
        c_new, kr_new = _latent(h, p, cos, sin, geo, eps)
        c_all = jnp.concatenate([cache[0], c_new[:, None, :]], axis=1)
        kr_all = jnp.concatenate([cache[1], kr_new[:, None, :]], axis=1)
    with jax.named_scope("window_attention" if geo.window else "causal_attention"):
        scores = jnp.einsum(
            "bhc,btc->bht", q_lat, c_all, preferred_element_type=jnp.float32
        ) + jnp.einsum(
            "bhr,btr->bht", q[..., geo.laid - geo.rope:], kr_all,
            preferred_element_type=jnp.float32,
        )
        slots = c_all.shape[1]
        t = jnp.arange(slots)[None, :]
        if geo.window:  # the slots at or past position 0, and itself
            seen = t >= slots - 1 - lens[:, None]
        else:
            seen = (t < lens[:, None]) | (t == slots - 1)  # the cache, and itself
        if "indexer" in p:
            q_i, k_i, w = _index_terms(h, cq, p["indexer"], cos, sin, config)
            with jax.named_scope("index_scores"):
                index = index_scores_einsum(
                    q_i[:, None, :], jnp.concatenate([cache[2], k_i[:, None, :]], axis=1),
                    w[:, None, :], heads=config.index_n_heads,
                )[:, 0]
            with jax.named_scope("index_select"):
                chosen = select_topk_dense(index, seen, config.index_topk)
        if chosen is not None and not geo.window:
            seen = chosen
        scores = jnp.where(seen[:, None, :], scores / math.sqrt(geo.head_dim), -1e30)
        probs = jax.nn.softmax(scores, axis=-1).astype(h.dtype)
        o_lat = jnp.einsum(
            "bht,btc->bhc", probs, c_all, preferred_element_type=jnp.float32
        ).astype(h.dtype)
        # ... and its value half folded into the output
        w_v = p["w_v"].reshape(geo.kv_lora_rank, heads, geo.v)
        ctx = jnp.einsum(
            "bhc,chv->bhv", o_lat, w_v, preferred_element_type=jnp.float32
        ).astype(h.dtype)
    ctx = _gated(ctx.reshape(b, heads * geo.v), h, p, heads)
    with jax.named_scope("attn_out"):
        return _dense(ctx, p["o"]), chosen


def route(h, p: dict, config: GlmMoeLiteConfig):
    """h [t, hidden] -> (experts [t, k] int32, weights [t, k] float32)."""
    return route_sigmoid(h, p, config.num_experts_per_tok, config.routed_scaling_factor)


def _held(p: dict, config: GlmMoeLiteConfig):
    """The experts 0..held-1 a sparse layer holds of its router's, or None
    where it holds them all."""
    held = p["w_gate"].shape[0]
    return None if held == config.n_routed_experts else held


def _moe(h, p: dict, config: GlmMoeLiteConfig):
    """h [t, hidden] -> (output [t, hidden], pairs routed to each expert; a
    share's partial sum, and last the pairs routed elsewhere)."""
    with jax.named_scope("router"):
        chosen, weight = route(h, p, config)
    with jax.named_scope("experts_routed"):
        routed, counts = experts_grouped(
            h, chosen, weight, p, config.n_routed_experts, held=_held(p, config)
        )
    with jax.named_scope("expert_shared"):
        shared = _swiglu(h, p["shared"])
    return routed + shared, counts


def _mlp(h, layer: dict, config: GlmMoeLiteConfig):
    """The layer's second half over h [..., hidden]: (output, counts | None)."""
    if "mlp" in layer:
        with jax.named_scope("dense_mlp"):
            return _swiglu(h, layer["mlp"]), None
    flat, counts = _moe(h.reshape(-1, h.shape[-1]), layer["moe"], config)
    return flat.reshape(h.shape), counts


def prefill(params: dict, ids, config: GlmMoeLiteConfig, lens=None, tallies=None):
    """ids [b, s] -> (hidden [b, s, hidden] before the final norm, the
    latent cache a layer, pairs routed to each expert a sparse layer).
    ``lens`` is the panel protocol's (``models/judge.py``): causal attention
    never sees the slots past a call's length, so it is not read here.
    Where the decoder has an indexer, a ``tallies`` dict handed in receives
    ``index_keys`` [2] int32: the (query, key) pairs its layers with an
    indexer chose, and the causal pairs they chose from, over every slot.
    Where it has layers with a window, ``window_keys`` [2]: the pairs inside
    their bands, and the causal pairs those were taken from; such a layer's
    cache is what the token at ``lens`` can see (``_attention_prefill``)."""
    with jax.named_scope("embed_tokens"):
        x = jnp.take(params["token_embed"], ids, axis=0)
    caches, loads = [], []
    keep, picked, owners, windows = None, jnp.int32(0), 0, []
    for i, layer in enumerate(params["layers"]):
        h = _rms(x, layer["input_norm"], config.rms_norm_eps)
        geo = config.geometry(i)
        windows += [geo.window] if geo.window else []
        # a decoder of one kind names no geometry and has no cache that ends
        # at ``lens``: its call is as it was
        kind = {"geo": geo, "lens": lens} if config.layer_types else {}
        out, cache, keep = _attention_prefill(h, layer["attn"], config, keep, **kind)
        if "indexer" in layer["attn"]:
            with jax.named_scope("index_select"):
                picked = picked + jnp.sum(keep, dtype=jnp.int32)
            owners += 1
        x = x + out
        caches.append(cache)
        out, counts = _mlp(_rms(x, layer["post_norm"], config.rms_norm_eps), layer, config)
        x = x + out
        if counts is not None:
            loads.append(counts)
    b, s = ids.shape
    if owners and tallies is not None:
        tallies["index_keys"] = jnp.stack([picked, jnp.int32(owners * b * (s * (s + 1) // 2))])
    if windows and tallies is not None:
        banded = sum(band_pairs(s, window) for window in windows)
        tallies["window_keys"] = jnp.asarray(
            [b * banded, len(windows) * b * (s * (s + 1) // 2)], jnp.int32
        )
    return x, caches, loads


def decode_step(params: dict, token, lens, caches, config: GlmMoeLiteConfig):
    """One token a call at position ``lens`` -> hidden [b, hidden]."""
    with jax.named_scope("embed_tokens"):
        x = jnp.take(params["token_embed"], token, axis=0)
    chosen = None
    for i, (layer, cache) in enumerate(zip(params["layers"], caches)):
        h = _rms(x, layer["input_norm"], config.rms_norm_eps)
        # a decoder without an indexer hands nothing on, one of one kind names
        # no geometry: its call is as it was
        carried = () if chosen is None else (chosen,)
        kind = {"geo": config.geometry(i)} if config.layer_types else {}
        out, chosen = _attention_decode(h, layer["attn"], lens, cache, config, *carried, **kind)
        x = x + out
        out, _ = _mlp(_rms(x, layer["post_norm"], config.rms_norm_eps), layer, config)
        x = x + out
    return x


def head_logprobs(params: dict, hidden, config: GlmMoeLiteConfig):
    """hidden [b, hidden] -> log-probabilities over the vocabulary, float32."""
    with jax.named_scope("head_read"):
        h = _rms(hidden, params["final_norm"], config.rms_norm_eps)
        logits = jnp.einsum(
            "bh,hv->bv", h, params["lm_head"], preferred_element_type=jnp.float32
        )
        return logits - jax.nn.logsumexp(logits, axis=-1, keepdims=True)


def experts_held(params: dict, config: GlmMoeLiteConfig) -> int:
    """The experts this chip holds, 0..E-1 of the router's (all of them
    unless the checkpoint named a share)."""
    for layer in params["layers"]:
        if "moe" in layer:
            return int(layer["moe"]["w_gate"].shape[0])
    return config.n_routed_experts


def whole_bound_layers(load, config: GlmMoeLiteConfig) -> int:
    """Of a dispatch's sparse layers, those that ran over the layout's whole
    bound.  With every expert held (``load`` [layers, experts]) the layout
    has one bound and no usual load."""
    if load.size and load.shape[1] == config.n_routed_experts:
        return 0
    return layers_past_usual(load, config.n_routed_experts)


def expert_tiles(load, config: GlmMoeLiteConfig) -> tuple[int, int]:
    """Of a dispatch's sparse layers, the row tiles their layouts laid and
    those that hold a pair (``decoder_parts.tiles_laid_and_in_use``)."""
    share = bool(load.size) and load.shape[1] != config.n_routed_experts
    return tiles_laid_and_in_use(load, config.n_routed_experts, share)


# -- parameters ---------------------------------------------------------------


def _owns_indexer(config: GlmMoeLiteConfig, layer: int) -> bool:
    """Where layers are of two kinds every full layer owns its indexer and a
    sliding one has none; else ``indexer_types`` says."""
    if not config.index_topk:
        return False
    if config.layer_types:
        return not config.slides(layer)
    return config.indexer_types[layer] == "full"


def init_params(rng, config: GlmMoeLiteConfig, dtype=jnp.float32, held=None) -> dict:
    """Random parameters in the served layout (tests, shape work); ``held``
    experts 0..held-1 of the router's where a share is wanted."""
    std = 0.02
    drawn = iter(range(1 << 30))

    def draw(shape):  # a key of its own per tensor
        return jax.random.normal(jax.random.fold_in(rng, next(drawn)), shape, jnp.float32)

    def normal(*shape, dt=dtype):
        return (draw(shape) * std).astype(dt)

    def dense(i, o):
        return {"kernel": normal(i, o)}

    def scale(n):
        return (1.0 + draw((n,)) * std).astype(dtype)

    def swiglu(width):
        h = config.hidden_size
        return {"gate": dense(h, width), "up": dense(h, width), "down": dense(width, h)}

    h = config.hidden_size
    layers = []
    for i in range(config.num_layers):
        geo = config.geometry(i)
        heads, rank = geo.heads, geo.kv_lora_rank
        w_k = normal(rank, heads, geo.laid)

        def q_b():
            # the lanes a laid head adds, [nope, laid - rope), are zero
            w = normal(geo.q_lora_rank, heads, geo.laid)
            w = w.at[:, :, geo.nope:geo.laid - geo.rope].set(0)
            return {"kernel": w.reshape(geo.q_lora_rank, heads * geo.laid)}

        layer = {
            "input_norm": scale(h),
            "post_norm": scale(h),
            "attn": {
                "q_a": dense(h, geo.q_lora_rank),
                "q_a_norm": scale(geo.q_lora_rank),
                "q_b": q_b(),
                "kv_a": dense(h, rank + geo.rope),
                "kv_a_norm": scale(rank),
                "w_k": w_k.at[:, :, geo.nope:].set(0),
                "w_v": normal(rank, heads * geo.v),
                "o": dense(heads * geo.v, h),
            },
        }
        if geo.gate:
            layer["attn"]["gate"] = dense(h, heads)
        if _owns_indexer(config, i):
            dim = config.index_head_dim
            layer["attn"]["indexer"] = {
                "q": dense(geo.q_lora_rank, config.index_n_heads * dim),
                "k": dense(h, dim),
                "k_norm": scale(dim),
                "k_bias": normal(dim),
                "w": normal(h, config.index_n_heads),
            }
        if i < config.first_k_dense_replace:
            layer["mlp"] = swiglu(config.intermediate_size)
        else:
            e, inter = config.n_routed_experts, config.moe_intermediate_size
            here = held or e
            layer["moe"] = {
                "router": normal(h, e, dt=jnp.float32),
                "bias": normal(e, dt=jnp.float32),
                "w_gate": normal(here, h, inter),
                "w_up": normal(here, h, inter),
                "w_down": normal(here, inter, h),
                "shared": swiglu(inter * config.n_shared_experts),
            }
        layers.append(layer)
    return {
        "token_embed": normal(config.vocab_size, h),
        "final_norm": scale(h),
        "lm_head": normal(h, config.vocab_size),
        "layers": layers,
    }


def _deinterleave(rows: int):
    """Row order that moves interleaved rotary pairs (2i, 2i+1) to
    (i, i + rows / 2)."""
    return list(range(0, rows, 2)) + list(range(1, rows, 2))


def from_hf_weights(state, config: GlmMoeLiteConfig, dtype=jnp.float32):
    """HF-named tensors (a mapping that may open each tensor lazily:
    ``loading.open_checkpoint``) -> (params, config).  A layer goes to the
    device before the next is read, so the host never holds the checkpoint
    whole.  What is served is what the checkpoint names: its layers, from 0
    up; of each, whether it is dense or sparse (``mlp.gate.weight``) and
    whether it owns an indexer (``self_attn.indexer.wq_b.weight``: one
    pipeline stage of a deployment names its own layers from 0, whatever
    their place in the published pattern); experts 0..E-1 of the preset's
    router (fewer than it is wide: one chip's share); and the rows of the
    vocabulary that ``embed_tokens`` holds.
    """
    import dataclasses

    import numpy as np

    prefix = "model." if "model.embed_tokens.weight" in state else ""
    depth = 0
    while f"{prefix}layers.{depth}.input_layernorm.weight" in state:
        depth += 1
    if depth == 0:
        raise ValueError("the checkpoint names no layer (layers.0.input_layernorm.weight)")
    embed = np.asarray(state[prefix + "embed_tokens.weight"])
    sparse = [f"{prefix}layers.{i}.mlp.gate.weight" in state for i in range(depth)]
    dense_layers = sparse.index(True) if True in sparse else depth
    if not all(sparse[dense_layers:]):
        raise ValueError("a dense layer behind a sparse one is not served")
    held = 0
    while f"{prefix}layers.{dense_layers}.mlp.experts.{held}.gate_proj.weight" in state:
        held += 1
    named = [
        f"{prefix}layers.{i}.self_attn.indexer.wq_b.weight" in state for i in range(depth)
    ]
    owners, kinds = (), ()
    if config.layer_types:  # two kinds: a full layer owns its indexer, a sliding one has none
        kinds = tuple("full_attention" if own else "sliding_attention" for own in named)
    elif config.index_topk:
        owners = tuple("full" if own else "shared" for own in named)
        if owners[0] != "full":
            raise ValueError("the first layer served owns no indexer: nothing to attend over")
    config = dataclasses.replace(
        config, num_layers=depth, first_k_dense_replace=dense_layers,
        indexer_types=owners, layer_types=kinds, vocab_size=int(embed.shape[0]),
    )

    def get(name):
        return np.asarray(state[prefix + name])

    def put(array, dt=dtype):
        return jnp.asarray(array).astype(dt)

    swap = jax.jit(lambda w: jnp.swapaxes(w, -1, -2))

    def dense(name):  # HF [out, in] -> [in, out], transposed on the device
        return {"kernel": swap(put(get(name + ".weight")))}

    def swiglu(base):
        return {k: dense(f"{base}.{k}_proj") for k in ("gate", "up", "down")}

    rope = config.qk_rope_head_dim
    order = np.asarray(_deinterleave(rope))

    def indexer(base):
        """An index head's first ``rope`` dims are stored interleaved like the
        attention's: the same move on the query's rows, the key's and the
        key norm's leaves every q_I . k_I as it was."""
        dim = config.index_head_dim
        turned = np.concatenate([order, np.arange(rope, dim)])
        q = get(f"{base}.wq_b.weight").reshape(config.index_n_heads, dim, -1)[:, turned]
        return {
            "q": {"kernel": swap(put(q.reshape(config.index_n_heads * dim, -1)))},
            "k": {"kernel": swap(put(get(f"{base}.wk.weight")[turned]))},
            "k_norm": put(get(f"{base}.k_norm.weight")[turned]),
            "k_bias": put(get(f"{base}.k_norm.bias")[turned]),
            "w": swap(put(get(f"{base}.weights_proj.weight"))),
        }

    layers = []
    for i in range(depth):
        base = f"layers.{i}"
        att = f"{base}.self_attn"
        geo = config.geometry(i)
        heads, rank, nope, dv = geo.heads, geo.kv_lora_rank, geo.nope, geo.v
        order = np.asarray(_deinterleave(geo.rope))
        q_b = get(f"{att}.q_b_proj.weight")
        if q_b.shape != (heads * geo.head_dim, geo.q_lora_rank):
            raise ValueError(
                f"layer {i} ({'sliding' if geo.window else 'full'} by what it names): "
                f"q_b_proj is {q_b.shape}, its kind's is {(heads * geo.head_dim, geo.q_lora_rank)}"
            )
        q_b = q_b.reshape(heads, geo.head_dim, -1)
        # a head wider than one column is laid in whole columns: zero rows
        # between its nope and rope dims (``AttentionGeometry.laid``)
        between = np.zeros((heads, geo.laid - geo.head_dim, q_b.shape[2]), q_b.dtype)
        q_b = np.concatenate([q_b[:, :nope], between, q_b[:, nope:][:, order]], axis=1)
        kv_a = get(f"{att}.kv_a_proj_with_mqa.weight")
        kv_a = np.concatenate([kv_a[:rank], kv_a[rank:][order]], axis=0)
        kv_b = get(f"{att}.kv_b_proj.weight").reshape(heads, nope + dv, rank)
        w_k = np.zeros((heads, geo.laid, rank), kv_b.dtype)
        w_k[:, :nope] = kv_b[:, :nope]
        layer = {
            "input_norm": put(get(f"{base}.input_layernorm.weight")),
            "post_norm": put(get(f"{base}.post_attention_layernorm.weight")),
            "attn": {
                "q_a": dense(f"{att}.q_a_proj"),
                "q_a_norm": put(get(f"{att}.q_a_layernorm.weight")),
                "q_b": {"kernel": swap(put(q_b.reshape(heads * geo.laid, -1)))},
                "kv_a": {"kernel": swap(put(kv_a))},
                "kv_a_norm": put(get(f"{att}.kv_a_layernorm.weight")),
                "w_k": jnp.transpose(put(w_k), (2, 0, 1)),
                "w_v": swap(put(kv_b[:, nope:].reshape(heads * dv, rank))),
                "o": dense(f"{att}.o_proj"),
            },
        }
        if geo.gate:
            layer["attn"]["gate"] = dense(f"{att}.g_proj")
        if _owns_indexer(config, i):
            layer["attn"]["indexer"] = indexer(f"{att}.indexer")
        if i < config.first_k_dense_replace:
            layer["mlp"] = swiglu(f"{base}.mlp")
        else:
            def experts(kind):
                stacked = np.stack(
                    [
                        get(f"{base}.mlp.experts.{e}.{kind}_proj.weight")
                        for e in range(held)
                    ]
                )
                return swap(put(stacked))

            layer["moe"] = {
                "router": swap(put(get(f"{base}.mlp.gate.weight"), jnp.float32)),
                "bias": put(
                    get(f"{base}.mlp.gate.e_score_correction_bias"), jnp.float32
                ),
                "w_gate": experts("gate"),
                "w_up": experts("up"),
                "w_down": experts("down"),
                "shared": swiglu(f"{base}.mlp.shared_experts"),
            }
        layers.append(layer)
    params = {
        "token_embed": put(embed),
        "final_norm": put(get("norm.weight")),
        "lm_head": swap(put(np.asarray(state["lm_head.weight"]))),
        "layers": layers,
    }
    return params, config
