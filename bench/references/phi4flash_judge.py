"""Plain reference: the ``phi4flash`` decoder as a judge reads a ballot.

Written from the model's configuration (microsoft/Phi-4-mini-flash-reasoning
``config.json``) and the equations below in float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``.  No kernel, no cache, no batch,
nothing of the program: it reads the seeded checkpoint by its HuggingFace
names, one layer at a time (``read_checkpoint`` opens a tensor when it is asked
for), tokenizes for itself and builds each call's ballot for itself from the
request and the call's seed (the ballot and the tokens are the protocol's, not
the model's: they are the first judge's reference's,
``references/glm4_moe_lite_judge.py``, taken as they are).

``LN(x; w, b)`` is LayerNorm over the hidden size, eps ``layer_norm_eps``.
Layer i of n, K = n / 2 + 1; EVERY layer runs at EVERY position (the reference
knows nothing of which positions a judge reads):

  x0      = embed[ids]
  per layer:  x = x + mixer_i(LN(x; input_layernorm))
              x = x + fc2 · (silu(g) ⊙ u),   [g | u] = fc1 · LN(x; post_attention_layernorm)
  logits  = embed · LN(x; final_layernorm)

  Mamba (i even, i < K):  [xs | z] = in_proj · h
        xc[t]  = silu(Σ_j conv1d.w[:, j] ⊙ xs[t - (taps - 1) + j] + conv1d.b)
        [dl | B | C] = x_proj · xc;   dt = softplus(dt_proj · dl + dt_proj.bias)
        s[t]   = exp(dt[t] ⊗ A) ⊙ s[t - 1] + (dt[t] ⊙ xc[t]) ⊗ B[t],   A = -exp(A_log),  s[-1] = 0
        m[t]   = s[t] · C[t] + D ⊙ xc[t]
        out    = out_proj · (m ⊙ silu(z));   layer K - 1's m is THE MEMORY
  memory unit (i even, i > K):  out_proj · (memory ⊙ silu(in_proj · h)), position for position
  attention (i odd): q = Wq h + bq, and where i <= K also k, v of the same fused Wqkv; where
        i > K the k and v are layer K's, as layer K projected them.  Query heads (2j, 2j + 1)
        are pair j's two softmaxes, on key heads (2m, 2m + 1) with m = j // (pairs a key pair);
        value heads (2m, 2m + 1) side by side are V_m:
        a_r    = softmax(q_(2j+r) · k_(2m+r) / sqrt(hd)) V_m      r = 0, 1
        λ      = exp(λq1 · λk1) - exp(λq2 · λk2) + λ_init,   λ_init = 0.8 - 0.6 exp(-0.3 i)
        o_j    = rms(a_0 - λ a_1; subln) · (1 - λ_init)
        out    = out_proj · [o_0 | o_1 | ..] + b
        position t sees t - window < s <= t where i < K, and s <= t where i >= K

The scan is the recurrence as written, a ``lax.scan`` over the positions.
Attention goes in blocks of queries against all keys, every head at once,
under a mask of whole rows; queries, keys and values are laid heads first
([key pair, .., position, lanes]), the order the products contract in: with the
positions first the chip's compiler turned the scores of every block about and
a full layer took 0.85 s for 0.06 (my chip runs, PR 45).

What a run of the cell can afford (the driver stops a run at 360 s, set-up,
window and this check together): a float32 product at ``highest`` costs the
chip's compiler some 5 s wherever it stands, so the MLP is one program for
all 32 layers, a mixer one program a kind (a sliding and the full layer share
theirs: the reach of a query is a number handed in, as λ_init is), five in
all, compiled side by side in threads (``_compiled``) while the weights load.
Nothing else is shared or cached, and every layer runs at every position.

Given a call's prompt plus the key letter the PROGRAM chose, ONE forward over
T + 1 positions gives the first level's logits at position T - 1 and the
second level's at position T: the program ran its second half at two positions
alone and its second read came through a scan's state and a convolution's
tail, a window of cached keys, and one set of keys that eight layers read, so
this is the split and prefill-then-decode through three kinds of cache against
the full forward pass.
"""

from __future__ import annotations

import importlib.util
import math
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
RMS_EPS = 1e-5  # the differential pair's norm


def kv_layer(cfg: dict) -> int:
    return cfg["num_hidden_layers"] // 2 + 1


def kind_of(cfg: dict, layer: int) -> str:
    """``mamba`` | ``sliding`` | ``full`` | ``memory`` | ``cross``."""
    top = kv_layer(cfg)
    if layer % cfg["mb_per_layer"] == 0:
        return "mamba" if layer < top else "memory"
    if layer < top:
        return "sliding"
    return "full" if layer == top else "cross"


def lambda_init(layer: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


def _ln(x, weight, bias, eps):
    import jax.numpy as jnp

    centred = x - jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(centred * centred, axis=-1, keepdims=True)
    return centred / jnp.sqrt(var + eps) * weight + bias


def _make(cfg: dict):
    """({name: a jitted function}, embed, head).  ``mlp(x, p)`` is every
    layer's second half; the mixers are ``mamba(x, p)`` -> (x, its scan's
    output), ``self(x, p, λ_init, reach)`` -> (x, (k, v)) for a sliding and
    the full layer, ``memory(x, p, the last Mamba layer's scan output)`` -> x
    and ``cross(x, p, λ_init, reach, the full layer's (k, v))`` -> x.  One
    program a KIND of mixer: λ_init and the reach of a query are a layer's
    numbers, handed in.  Each traces at ``highest`` whatever thread compiles
    it."""
    import jax
    import jax.numpy as jnp

    eps, hidden = cfg["layer_norm_eps"], cfg["hidden_size"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = hidden // heads
    pairs, key_pairs = heads // 2, kv // 2
    inner, n = cfg["mamba_expand"] * hidden, cfg["mamba_d_state"]
    rank, taps = cfg["mamba_dt_rank"], cfg["mamba_d_conv"]

    def mlp(x, p):
        gu = _ln(x, p["post_w"], p["post_b"], eps) @ p["fc1"].T
        half = gu.shape[-1] // 2
        return x + (jax.nn.silu(gu[:, :half]) * gu[:, half:]) @ p["fc2"].T

    def mamba(x, p):
        t = x.shape[0]
        h = _ln(x, p["in_w"], p["in_b"], eps)
        xz = h @ p["in_proj"].T
        xs, z = xz[:, :inner], xz[:, inner:]
        padded = jnp.pad(xs, ((taps - 1, 0), (0, 0)))
        conv = sum(padded[j:j + t] * p["conv_w"][:, j] for j in range(taps)) + p["conv_b"]
        xc = jax.nn.silu(conv)
        dbc = xc @ p["x_proj"].T
        dl, b, c = dbc[:, :rank], dbc[:, rank:rank + n], dbc[:, rank + n:]
        dt = jax.nn.softplus(dl @ p["dt_proj"].T + p["dt_b"])
        a = -jnp.exp(p["A_log"])

        def step(s, at):
            dt_t, x_t, b_t, c_t = at
            s = jnp.exp(dt_t[:, None] * a) * s + (dt_t * x_t)[:, None] * b_t[None, :]
            return s, s @ c_t

        _, m = jax.lax.scan(step, jnp.zeros((inner, n), jnp.float32), (dt, xc, b, c))
        m = m + p["D"] * xc
        return x + (m * jax.nn.silu(z)) @ p["out_proj"].T, m

    def attention(x, p, init, reach, shared=None):
        """``reach``: the keys a query sees, its own among them (the window, or
        every position).  ``shared`` None: a layer with keys and values of its
        own."""
        t = x.shape[0]
        h = _ln(x, p["in_w"], p["in_b"], eps)
        if shared is None:
            qkv = h @ p["Wqkv"].T + p["bqkv"]  # q | k | v, as the checkpoint fuses them
            q, k, v = jnp.split(qkv, [heads * hd, (heads + kv) * hd], axis=1)
            # heads first, then positions: [key pair m, its key r, t, hd] and [m, t, 2 hd]
            k = k.reshape(t, key_pairs, 2, hd).transpose(1, 2, 0, 3)
            v = v.reshape(t, key_pairs, 2 * hd).transpose(1, 0, 2)
        else:
            q, (k, v) = h @ p["Wq"].T + p["bq"], shared
        # [m, r, the pairs j on key pair m, t, hd]: query head 2 (m J + j) + r
        q = q.reshape(t, key_pairs, pairs // key_pairs, 2, hd).transpose(1, 3, 2, 0, 4)
        lam = (
            jnp.exp(jnp.sum(p["lambda_q1"] * p["lambda_k1"]))
            - jnp.exp(jnp.sum(p["lambda_q2"] * p["lambda_k2"])) + init
        )
        block = min(QUERY_BLOCK, t)

        def one(start):
            qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=3)
            scores = jnp.einsum("mrjqd,mrkd->mrjqk", qb, k) / jnp.sqrt(jnp.float32(hd))
            rows, cols = start + jnp.arange(block)[:, None], jnp.arange(t)[None, :]
            seen = (cols <= rows) & (cols > rows - reach)
            probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
            a = jnp.einsum("mrjqk,mkd->qmjrd", probs, v)  # [block, key pairs, j, 2, 2 hd]
            diff = a[..., 0, :] - lam * a[..., 1, :]
            norm = diff / jnp.sqrt(jnp.mean(diff * diff, axis=-1, keepdims=True) + RMS_EPS)
            return norm * p["subln"] * (1.0 - init)

        o = jax.lax.map(one, jnp.arange(0, t, block)).reshape(t, hidden)
        return x + o @ p["out_proj"].T + p["bo"], (k, v)

    def memory_unit(x, p, memory):
        h = _ln(x, p["in_w"], p["in_b"], eps)
        return x + (memory * jax.nn.silu(h @ p["in_proj"].T)) @ p["out_proj"].T

    def at_highest(f):
        def traced(*args):
            with jax.default_matmul_precision("highest"):
                return f(*args)

        return jax.jit(traced)

    programs = {
        "mlp": at_highest(mlp),
        "mamba": at_highest(mamba),
        "self": at_highest(attention),
        "memory": at_highest(memory_unit),
        "cross": at_highest(lambda x, p, init, reach, shared: attention(x, p, init, reach, shared)[0]),
    }

    @jax.jit
    def head(x, rows, weight, bias, table, ids):
        return _ln(x[rows], weight, bias, eps) @ table[ids].astype(jnp.float32).T

    return programs, jax.jit(lambda table, ids: table[ids]), head


_FUNCTIONS: dict = {}
_COMPILED: dict = {}


def _sizes(cfg: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in cfg.items() if isinstance(v, (int, float))))


def functions(cfg: dict):
    if _sizes(cfg) not in _FUNCTIONS:
        _FUNCTIONS[_sizes(cfg)] = _make(cfg)
    return _FUNCTIONS[_sizes(cfg)]


def layer_weights(state, cfg: dict, i: int) -> tuple:
    """Layer i's weights, float32, by their HuggingFace names: (the mixer's,
    the MLP's)."""
    import jax.numpy as jnp

    def f32(name):
        return jnp.asarray(np.asarray(state[name])).astype(jnp.float32)

    base, mix = f"model.layers.{i}", f"model.layers.{i}.attn"
    kind = kind_of(cfg, i)
    p = {
        "in_w": f32(f"{base}.input_layernorm.weight"),
        "in_b": f32(f"{base}.input_layernorm.bias"),
    }
    mlp = {
        "post_w": f32(f"{base}.post_attention_layernorm.weight"),
        "post_b": f32(f"{base}.post_attention_layernorm.bias"),
        "fc1": f32(f"{base}.mlp.fc1.weight"),
        "fc2": f32(f"{base}.mlp.fc2.weight"),
    }
    if kind == "mamba":
        p.update(
            in_proj=f32(f"{mix}.in_proj.weight"), conv_w=f32(f"{mix}.conv1d.weight")[:, 0, :],
            conv_b=f32(f"{mix}.conv1d.bias"), x_proj=f32(f"{mix}.x_proj.weight"),
            dt_proj=f32(f"{mix}.dt_proj.weight"), dt_b=f32(f"{mix}.dt_proj.bias"),
            A_log=f32(f"{mix}.A_log"), D=f32(f"{mix}.D"), out_proj=f32(f"{mix}.out_proj.weight"),
        )
    elif kind == "memory":
        p.update(in_proj=f32(f"{mix}.in_proj.weight"), out_proj=f32(f"{mix}.out_proj.weight"))
    else:
        fused = "Wq" if kind == "cross" else "Wqkv"
        p.update({fused: f32(f"{mix}.{fused}.weight"), "b" + fused[1:]: f32(f"{mix}.{fused}.bias")})
        p.update(
            out_proj=f32(f"{mix}.out_proj.weight"), bo=f32(f"{mix}.out_proj.bias"),
            subln=f32(f"{mix}.inner_cross_attn.subln.weight"),
            **{name: f32(f"{mix}.inner_cross_attn.{name}")
               for name in ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2")},
        )
    return p, mlp


def _program(kind: str) -> str:
    return "self" if kind in ("sliding", "full") else kind


def _compiled(cfg: dict, state, width: int) -> dict:
    """{name: a Future of the program compiled for sequences of ``width``
    slots}, every program's compile started at once in a thread of its own.
    A layer of each kind is read for its shapes alone."""
    import jax
    import jax.numpy as jnp
    from concurrent.futures import ThreadPoolExecutor

    key = (_sizes(cfg), width)
    if key in _COMPILED:
        return _COMPILED[key]

    def like(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32)

    hidden, kv = cfg["hidden_size"], cfg["num_key_value_heads"]
    hd = hidden // cfg["num_attention_heads"]
    x, init, reach = like(width, hidden), like(), jax.ShapeDtypeStruct((), jnp.int32)
    given = {  # what a mixer is handed beside x and its weights
        "mamba": (), "self": (init, reach),
        "memory": (like(width, cfg["mamba_expand"] * hidden),),
        "cross": (init, reach, (like(kv // 2, 2, width, hd), like(kv // 2, width, 2 * hd))),
    }
    programs, _, _ = functions(cfg)
    pool = ThreadPoolExecutor(len(programs))
    out = {}
    for i in range(cfg["num_hidden_layers"]):
        name = _program(kind_of(cfg, i))
        if name in out:
            continue
        p, mlp = jax.tree_util.tree_map(lambda a: like(*a.shape), layer_weights(state, cfg, i))
        out[name] = pool.submit(lambda name=name, p=p: programs[name].lower(x, p, *given[name]).compile())
        if "mlp" not in out:
            out["mlp"] = pool.submit(lambda mlp=mlp: programs["mlp"].lower(x, mlp).compile())
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

    _, embed, _ = functions(cfg)
    width = -(-max(len(ids) for ids in sequences) // QUERY_BLOCK) * QUERY_BLOCK
    compiled = _compiled(cfg, state, width)
    table = jnp.asarray(np.asarray(state["model.embed_tokens.weight"])).astype(jnp.float32)
    xs = []
    for ids in sequences:
        padded = np.zeros((width,), np.int32)
        padded[: len(ids)] = ids
        xs.append((embed(table, jnp.asarray(padded)), None, None))
    del table
    for i in range(cfg["num_hidden_layers"]):
        (p, second), kind = layer_weights(state, cfg, i), kind_of(cfg, i)
        mixer, mlp = compiled[_program(kind)].result(), compiled["mlp"].result()
        init = jnp.float32(lambda_init(i))
        reach = jnp.int32(cfg["sliding_window"] if kind == "sliding" else width)
        for j, (x, memory, shared) in enumerate(xs):  # a sequence's old state goes as its new one comes
            if kind == "mamba":
                x, memory = mixer(x, p)
            elif kind == "memory":
                x = mixer(x, p, memory)
            elif kind == "cross":
                x = mixer(x, p, init, reach, shared)
            else:
                x, kv = mixer(x, p, init, reach)
                if kind == "full":
                    shared = kv
            xs[j] = (mlp(x, second), memory, shared)
        del p, second
    return [x for x, _, _ in xs]


def read_logits(state, cfg: dict, calls: list, letter_ids: list) -> list:
    """``calls`` is [(ids, rows)]: token ids of one sequence and the positions
    to read.  Returns, per call, logits [len(rows), len(letter_ids)] at those
    positions for those token ids, float64 on the host."""
    import jax
    import jax.numpy as jnp

    if not calls:
        return []
    _, _, head = functions(cfg)
    xs = hidden_states(state, cfg, [ids for ids, _ in calls])
    with jax.default_matmul_precision("highest"):
        weight = jnp.asarray(np.asarray(state["model.final_layernorm.weight"])).astype(jnp.float32)
        bias = jnp.asarray(np.asarray(state["model.final_layernorm.bias"])).astype(jnp.float32)
        table = jnp.asarray(np.asarray(state["model.embed_tokens.weight"]))  # tied; bf16 rows
        ids = jnp.asarray(np.asarray(letter_ids, np.int32))
        return [
            np.asarray(
                head(x, jnp.asarray(np.asarray(rows, np.int32)), weight, bias, table, ids),
                np.float64,
            )
            for x, (_, rows) in zip(xs, calls)
        ]
