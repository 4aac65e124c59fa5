"""Plain reference: the ``falcon_h1`` decoder as a judge reads a ballot.

Written from the model's configuration (tiiuae/Falcon-H1-34B-Instruct
``config.json``) and the equations below in float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``, and HELD TO the family's published
implementation: ``tests/test_falcon_h1.py`` feeds one state dict to
``transformers``' ``FalconH1ForCausalLM`` (its ``torch_forward`` path, float32)
and to this file and compares the logits of a whole forward and of one step
through its cache.  No kernel, no cache, no batch, nothing of the program: it
reads the seeded checkpoint by its HuggingFace names, one layer at a time
(``read_checkpoint`` opens a tensor when it is asked for: a layer is 1.72 GB in
float32), tokenizes for itself and builds each call's ballot for itself from
the request and the call's seed (the ballot and the tokens are the protocol's,
not the model's: they are the first judge's reference's,
``references/glm4_moe_lite_judge.py``, taken as they are).

``rms(x; w) = x / sqrt(mean(x^2) + eps) · w``, eps ``rms_norm_eps``.  Every
layer, every multiplier WHERE THE FAMILY'S CODE APPLIES IT:

  x0   = embed[ids] · embedding_multiplier
  h    = rms(x; input_layernorm)
  p    = (in_proj · (h · ssm_in_multiplier)) ⊙ mup       mup = ssm_multipliers[0..4] over [z | x | B | C | dt]
  [z | xBC | dt] = p                                     d_ssm | d_ssm + 2 groups N | heads
  xBC  = silu(Σ_j conv1d.w[:, j] ⊙ xBC[t - (taps - 1) + j] + conv1d.b);   [xs | B | C] = xBC
  dt   = softplus(dt + dt_bias);  A = -exp(A_log)        a head
  S_t  = exp(dt_t A) S_{t-1} + dt_t xs_t ⊗ B_t           S [head, P, N]; head j reads group j // (heads / groups)
  y_t  = S_t C_t + D xs_t
  ssm  = (out_proj · rms_groups(y ⊙ silu(z); norm)) · ssm_out_multiplier     the norm over ``groups`` groups
  a    = h · attention_in_multiplier
  q, k, v = q_proj a, (k_proj a) · key_multiplier, v_proj a;  q, k = rope(q), rope(k)
         rope over all hd dims, halves rotated (``rotate_half``), theta ``rope_theta``
  att  = (o_proj · causal_softmax(q kᵀ / sqrt(hd)) v) · attention_out_multiplier
  x    = x + ssm + att
  x    = x + (down · (up g ⊙ silu((gate g) · mlp_multipliers[0]))) · mlp_multipliers[1],  g = rms(x; pre_ff_layernorm)
  logits = (lm_head · rms(x; final_layernorm)) · lm_head_multiplier

THE SCAN is the UNFUSED CHUNKED form (the state-space dual as the Mamba-2 paper
writes it: within a chunk of ``CHUNK`` positions the decays' running sums, the
masked C Bᵀ times the inputs; between chunks a ``lax.scan`` of 60 steps over
the chunks' states), in float32 einsums, no kernel: the recurrence a position
at a time is 7,680 steps of a 4 MB state a call and layer, 830,000 steps a run
of the cell's check, which a run stopped at 360 s cannot afford.  The program's
kernel is held to the plain recurrence in tier-1 (``tests/test_ssd.py``, under
the published long-memory initialisation), and this form to the family's own.

Attention goes in blocks of queries against all keys, every head at once,
queries, keys and values laid heads first ([key head, .., position, lanes]), the
order the products contract in (PR 45's lesson: with the positions first the
chip's compiler turned the scores of every block about, 0.86 s a layer for
0.06).  A layer is two programs (the two mixers and their sum; the MLP), each
compiled once in a thread of its own while the weights load.

Given a call's prompt plus the key letter the PROGRAM chose, ONE forward over
T + 1 positions gives the first level's logits at position T - 1 and the second
level's at position T: the program's second read came through its key cache,
its convolution's tail and its scan's state, so this is prefill-then-decode
through a layer's two kinds of cache against the full forward pass.
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
CHUNK = 128  # positions of a chunk of the scan's chunked form; QUERY_BLOCK is whole chunks


def _rms(x, weight, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def _rope(x, theta):
    """x [..., T, d], position = row: out = x cos + rotate_half(x) sin."""
    import jax.numpy as jnp

    t, d = x.shape[-2], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    freqs = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = (jnp.concatenate([f(freqs), f(freqs)], axis=-1) for f in (jnp.cos, jnp.sin))
    turned = jnp.concatenate([-x[..., d // 2:], x[..., : d // 2]], axis=-1)
    return x * cos + turned * sin


def ssd(xs, dt, a, b, c, d, chunk: int = CHUNK):
    """The scan, unfused and chunked: xs [T, G, J, P] (G groups of J heads),
    dt [T, G, J] (> 0), a and d [G, J], b and c [T, G, N] -> y [T, G, J, P].
    T is whole chunks."""
    import jax
    import jax.numpy as jnp

    t, g, j, p = xs.shape
    n = b.shape[-1]
    chunks = t // chunk
    # heads first, then a chunk's positions: the order the products contract in
    xs = xs.reshape(chunks, chunk, g, j, p).transpose(0, 2, 3, 1, 4)  # [c, G, J, L, P]
    dt = dt.reshape(chunks, chunk, g, j).transpose(0, 2, 3, 1)  # [c, G, J, L]
    b = b.reshape(chunks, chunk, g, n).transpose(0, 2, 1, 3)  # [c, G, L, N]
    c = c.reshape(chunks, chunk, g, n).transpose(0, 2, 1, 3)
    run = jnp.cumsum(dt * a[None, :, :, None], axis=-1)  # the decays' running sum inside a chunk
    # inside a chunk: position l reads position s <= l through C_l . B_s under exp(run_l - run_s)
    lower = jnp.arange(chunk)[:, None] >= jnp.arange(chunk)[None, :]
    decay = jnp.exp(jnp.where(lower, run[..., :, None] - run[..., None, :], -jnp.inf))
    scores = jnp.einsum("cgln,cgsn->cgls", c, b)
    weighed = xs * dt[..., None]  # dt_s xs_s
    within = jnp.einsum("cgjls,cgjsp->cgjlp", scores[:, :, None] * decay, weighed)
    # what each chunk adds to the state, decayed to the chunk's end
    to_end = jnp.exp(run[..., -1:] - run)
    added = jnp.einsum("cgln,cgjlp->cgjpn", b, weighed * to_end[..., None])
    whole = jnp.exp(run[..., -1])  # [c, G, J]: a chunk's whole decay

    def step(state, at):
        keep, add = at
        return state * keep[..., None, None] + add, state  # the state ENTERING the chunk

    _, entering = jax.lax.scan(step, jnp.zeros((g, j, p, n), jnp.float32), (whole, added))
    carried = jnp.einsum("cgln,cgjpn->cgjlp", c, entering) * jnp.exp(run)[..., None]
    y = within + carried + d[None, :, :, None, None] * xs
    return y.transpose(0, 3, 1, 2, 4).reshape(t, g, j, p)


def _make(cfg: dict):
    """({name: a jitted function}, the head).  ``mixers(x, p)`` is a layer's
    first half (both mixers on one normed input, summed into the stream),
    ``mlp(x, p)`` its second.  Each traces at ``highest`` whatever thread
    compiles it."""
    import jax
    import jax.numpy as jnp

    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    heads, kv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    inner, n, taps = cfg["mamba_d_ssm"], cfg["mamba_d_state"], cfg["mamba_d_conv"]
    groups, ssm_heads = cfg["mamba_n_groups"], cfg["mamba_n_heads"]
    wide = groups * n
    m_ssm, (m_gate, m_down) = cfg["ssm_multipliers"], cfg["mlp_multipliers"]
    mup = np.concatenate([
        np.full((inner,), m_ssm[0]), np.full((inner,), m_ssm[1]), np.full((wide,), m_ssm[2]),
        np.full((wide,), m_ssm[3]), np.full((ssm_heads,), m_ssm[4]),
    ]).astype(np.float32)

    def mixer(h, p):
        t = h.shape[0]
        proj = ((h * cfg["ssm_in_multiplier"]) @ p["in_proj"].T) * mup
        z, xbc, dt = jnp.split(proj, [inner, 2 * inner + 2 * wide], axis=1)
        padded = jnp.pad(xbc, ((taps - 1, 0), (0, 0)))
        conv = sum(padded[j:j + t] * p["conv_w"][:, j] for j in range(taps)) + p["conv_b"]
        xbc = jax.nn.silu(conv)
        xs, b, c = jnp.split(xbc, [inner, inner + wide], axis=1)
        dt = jax.nn.softplus(dt + p["dt_bias"])
        per = ssm_heads // groups
        y = ssd(
            xs.reshape(t, groups, per, -1), dt.reshape(t, groups, per),
            -jnp.exp(p["A_log"]).reshape(groups, per), b.reshape(t, groups, n),
            c.reshape(t, groups, n), p["D"].reshape(groups, per),
        ).reshape(t, inner)
        gated = (y * jax.nn.silu(z)).reshape(t, groups, -1)
        normed = _rms(gated, p["norm"].reshape(groups, -1), eps).reshape(t, inner)
        return (normed @ p["out_proj"].T) * cfg["ssm_out_multiplier"]

    def attention(h, p):
        t = h.shape[0]
        a = h * cfg["attention_in_multiplier"]
        # heads first, then positions: [key head, its query heads, T, hd]
        q = (a @ p["q_proj"].T).reshape(t, kv, heads // kv, hd).transpose(1, 2, 0, 3)
        k = ((a @ p["k_proj"].T) * cfg["key_multiplier"]).reshape(t, kv, hd).transpose(1, 0, 2)
        v = (a @ p["v_proj"].T).reshape(t, kv, hd).transpose(1, 0, 2)
        q, k = _rope(q, theta), _rope(k, theta)
        block = min(QUERY_BLOCK, t)

        def one(start):
            qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=2)
            scores = jnp.einsum("grqd,gkd->grqk", qb, k) / jnp.sqrt(jnp.float32(hd))
            rows, cols = start + jnp.arange(block)[:, None], jnp.arange(t)[None, :]
            probs = jax.nn.softmax(jnp.where(cols <= rows, scores, -jnp.inf), axis=-1)
            return jnp.einsum("grqk,gkd->qgrd", probs, v)  # [block, key head, r, hd]

        ctx = jax.lax.map(one, jnp.arange(0, t, block)).reshape(t, heads * hd)
        return (ctx @ p["o_proj"].T) * cfg["attention_out_multiplier"]

    def mixers(x, p):
        h = _rms(x, p["input_norm"], eps)
        return x + mixer(h, p) + attention(h, p)

    def mlp(x, p):
        g = _rms(x, p["pre_ff_norm"], eps)
        y = (g @ p["up"].T) * jax.nn.silu((g @ p["gate"].T) * m_gate)
        return x + (y @ p["down"].T) * m_down

    def at_highest(f):
        def traced(*args):
            with jax.default_matmul_precision("highest"):
                return f(*args)

        return jax.jit(traced)

    @jax.jit
    def head(x, rows, weight, table):
        with jax.default_matmul_precision("highest"):
            h = _rms(x[rows], weight, eps)
            return (h @ table.astype(jnp.float32).T) * cfg["lm_head_multiplier"]

    return {"mixers": at_highest(mixers), "mlp": at_highest(mlp)}, head


_FUNCTIONS: dict = {}
_COMPILED: dict = {}


def _sizes(cfg: dict) -> tuple:
    flat = []
    for k, v in sorted(cfg.items()):
        if isinstance(v, (int, float)):
            flat.append((k, v))
        elif isinstance(v, (list, tuple)) and all(isinstance(e, (int, float)) for e in v):
            flat.append((k, tuple(v)))
    return tuple(flat)


def functions(cfg: dict):
    if _sizes(cfg) not in _FUNCTIONS:
        _FUNCTIONS[_sizes(cfg)] = _make(cfg)
    return _FUNCTIONS[_sizes(cfg)]


def layer_weights(state, i: int) -> tuple:
    """Layer i's weights, float32, by their HuggingFace names: (the mixers',
    the MLP's)."""
    import jax.numpy as jnp

    def f32(name):
        return jnp.asarray(np.asarray(state[name])).astype(jnp.float32)

    base = f"model.layers.{i}"
    mix, att, ff = base + ".mamba", base + ".self_attn", base + ".feed_forward"
    first = {
        "input_norm": f32(f"{base}.input_layernorm.weight"),
        "in_proj": f32(f"{mix}.in_proj.weight"),
        "conv_w": f32(f"{mix}.conv1d.weight")[:, 0, :], "conv_b": f32(f"{mix}.conv1d.bias"),
        "A_log": f32(f"{mix}.A_log"), "D": f32(f"{mix}.D"), "dt_bias": f32(f"{mix}.dt_bias"),
        "norm": f32(f"{mix}.norm.weight"), "out_proj": f32(f"{mix}.out_proj.weight"),
        **{f"{w}_proj": f32(f"{att}.{w}_proj.weight") for w in "qkvo"},
    }
    second = {
        "pre_ff_norm": f32(f"{base}.pre_ff_layernorm.weight"),
        **{w: f32(f"{ff}.{w}_proj.weight") for w in ("gate", "up", "down")},
    }
    return first, second


def _compiled(cfg: dict, state, width: int) -> dict:
    """{name: a Future of the program compiled for sequences of ``width``
    slots}, both compiles started at once, each in a thread of its own.  The
    weights' shapes are read from the checkpoint's first layer."""
    import jax
    import jax.numpy as jnp
    from concurrent.futures import ThreadPoolExecutor

    key = (_sizes(cfg), width)
    if key in _COMPILED:
        return _COMPILED[key]

    def like(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32)

    x = like(width, cfg["hidden_size"])
    programs, _ = functions(cfg)
    shapes = jax.tree_util.tree_map(lambda a: like(*a.shape), layer_weights(state, 0))
    pool = ThreadPoolExecutor(2)
    out = {
        name: pool.submit(lambda name=name, p=p: programs[name].lower(x, p).compile())
        for name, p in zip(("mixers", "mlp"), shapes)
    }
    pool.shutdown(wait=False)
    _COMPILED[key] = out
    return out


def hidden_states(state, cfg: dict, sequences: list) -> list:
    """Each sequence of token ids through every layer: [T_padded, hidden]
    float32 before the final norm, a sequence padded with token 0 up to a
    whole block (a padded position is past every real one, so no real query
    sees it and no real position's state has met it).  Every sequence goes
    through a layer before the next layer's weights are read."""
    import jax.numpy as jnp

    width = -(-max(len(ids) for ids in sequences) // QUERY_BLOCK) * QUERY_BLOCK
    compiled = _compiled(cfg, state, width)
    table = np.asarray(state["model.embed_tokens.weight"])  # bf16 rows, on the host
    xs = []
    for ids in sequences:
        padded = np.zeros((width,), np.int32)
        padded[: len(ids)] = ids
        rows = jnp.asarray(table[padded]).astype(jnp.float32)
        xs.append(rows * cfg["embedding_multiplier"])
    del table
    for i in range(cfg["num_hidden_layers"]):
        first, second = layer_weights(state, i)
        mixers, mlp = compiled["mixers"].result(), compiled["mlp"].result()
        for j, x in enumerate(xs):  # a sequence's old state goes as its new one comes
            xs[j] = mlp(mixers(x, first), second)
        del first, second
    return xs


def read_logits(state, cfg: dict, calls: list, letter_ids: list) -> list:
    """``calls`` is [(ids, rows)]: token ids of one sequence and the positions
    to read.  Returns, per call, logits [len(rows), len(letter_ids)] at those
    positions for those token ids, float64 on the host."""
    import jax.numpy as jnp

    if not calls:
        return []
    _, head = functions(cfg)
    xs = hidden_states(state, cfg, [ids for ids, _ in calls])
    weight = jnp.asarray(np.asarray(state["model.final_layernorm.weight"])).astype(jnp.float32)
    # the head's rows at the letters alone (the head is a tensor of its own: untied)
    table = jnp.asarray(np.asarray(state["lm_head.weight"])[np.asarray(letter_ids, np.int64)])
    return [
        np.asarray(head(x, jnp.asarray(np.asarray(rows, np.int32)), weight, table), np.float64)
        for x, (_, rows) in zip(xs, calls)
    ]
