"""Encoder tests on the CPU mesh: shapes, masking invariance, determinism,
HF weight import mapping, embedder wire contract, DeBERTa RM."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp

from llm_weighted_consensus_tpu.models import bert, configs, deberta, tokenizer
from llm_weighted_consensus_tpu.models.embedder import TpuEmbedder

TINY = configs.TEST_TINY
DTINY = configs.DEBERTA_TEST_TINY


@pytest.fixture(scope="module")
def params():
    return bert.init_params(jax.random.PRNGKey(0), TINY)


def toks(batch, seq, seed=0, n_pad=0):
    rng = np.random.default_rng(seed)
    ids = rng.integers(3, TINY.vocab_size, size=(batch, seq)).astype(np.int32)
    mask = np.ones((batch, seq), dtype=np.int32)
    if n_pad:
        ids[:, -n_pad:] = 0
        mask[:, -n_pad:] = 0
    return jnp.asarray(ids), jnp.asarray(mask)


# -- bert ---------------------------------------------------------------------


def test_encode_shapes_and_pool(params):
    ids, mask = toks(3, 16)
    hidden = bert.encode(params, ids, mask, TINY)
    assert hidden.shape == (3, 16, TINY.hidden_size)
    emb = bert.pool(hidden, mask, "cls")
    assert emb.shape == (3, TINY.hidden_size)
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(emb), axis=1), 1.0, atol=1e-5
    )
    mean_emb = bert.pool(hidden, mask, "mean")
    assert not np.allclose(np.asarray(emb), np.asarray(mean_emb))


def test_padding_invariance(params):
    # embeddings must not depend on pad tokens beyond the mask
    ids, mask = toks(2, 12, seed=1, n_pad=4)
    e1 = bert.embed(params, ids, mask, TINY, pooling="mean")
    ids2 = np.asarray(ids).copy()
    ids2[:, -4:] = 7  # garbage in padded slots
    e2 = bert.embed(params, jnp.asarray(ids2), mask, TINY, pooling="mean")
    np.testing.assert_allclose(np.asarray(e1), np.asarray(e2), atol=1e-5)


def test_deterministic(params):
    ids, mask = toks(2, 8, seed=2)
    e1 = bert.embed(params, ids, mask, TINY)
    e2 = bert.embed(params, ids, mask, TINY)
    np.testing.assert_array_equal(np.asarray(e1), np.asarray(e2))


def test_from_hf_weights_roundtrip(params):
    """Export init params to HF naming, re-import, get identical outputs."""
    sd = {}
    p = jax.tree_util.tree_map(np.asarray, params)
    sd["embeddings.word_embeddings.weight"] = p["token_embed"]
    sd["embeddings.position_embeddings.weight"] = p["position_embed"]
    sd["embeddings.token_type_embeddings.weight"] = p["type_embed"]
    sd["embeddings.LayerNorm.weight"] = p["embed_ln"]["scale"]
    sd["embeddings.LayerNorm.bias"] = p["embed_ln"]["bias"]
    for i in range(TINY.num_layers):
        base = f"encoder.layer.{i}"
        for ours, hf in bert._HF_LAYER_MAP.items():
            sd[f"{base}.{hf}.weight"] = p["layers"][ours]["kernel"][i].T
            sd[f"{base}.{hf}.bias"] = p["layers"][ours]["bias"][i]
        for ours, hf in bert._HF_LN_MAP.items():
            sd[f"{base}.{hf}.weight"] = p["layers"][ours]["scale"][i]
            sd[f"{base}.{hf}.bias"] = p["layers"][ours]["bias"][i]
    imported = bert.from_hf_weights(sd, TINY)
    ids, mask = toks(2, 8, seed=3)
    np.testing.assert_allclose(
        np.asarray(bert.embed(params, ids, mask, TINY)),
        np.asarray(bert.embed(imported, ids, mask, TINY)),
        atol=1e-6,
    )


# -- tokenizer ----------------------------------------------------------------


def test_wordpiece_greedy_longest_match():
    vocab = {t: i for i, t in enumerate(
        ["[PAD]", "[UNK]", "[CLS]", "[SEP]", "un", "##aff", "##able", "aff",
         "hello", "world", "!"]
    )}
    tok = tokenizer.WordPieceTokenizer(vocab)
    ids, mask = tok.encode_batch(["hello world!", "unaffable"], max_length=16)
    assert ids.shape == (2, 16)
    row0 = [i for i in ids[0] if i != tok.pad_id]
    assert row0 == [vocab["[CLS]"], vocab["hello"], vocab["world"], vocab["!"], vocab["[SEP]"]]
    row1 = [i for i in ids[1] if i != tok.pad_id]
    assert row1 == [vocab["[CLS]"], vocab["un"], vocab["##aff"], vocab["##able"], vocab["[SEP]"]]
    assert mask[0].sum() == 5 and mask[1].sum() == 5


def test_wordpiece_unknown_word():
    vocab = {t: i for i, t in enumerate(["[PAD]", "[UNK]", "[CLS]", "[SEP]", "a"])}
    tok = tokenizer.WordPieceTokenizer(vocab)
    ids, _ = tok.encode_batch(["xyzzy"], max_length=8)
    assert vocab["[UNK]"] in ids[0]


def test_hash_tokenizer_deterministic_and_padded():
    tok = tokenizer.HashTokenizer(vocab_size=512)
    a1, m1 = tok.encode_batch(["the same text"], max_length=12)
    a2, _ = tok.encode_batch(["the same text"], max_length=12)
    np.testing.assert_array_equal(a1, a2)
    b, _ = tok.encode_batch(["different text"], max_length=12)
    assert not np.array_equal(a1, b)
    assert a1[0, 0] == tok.cls_id
    assert (a1[0][m1[0] == 0] == tok.pad_id).all()
    assert a1.max() < 512


def test_basic_tokenize():
    assert tokenizer.basic_tokenize("Héllo, World!") == ["hello", ",", "world", "!"]


# -- embedder -----------------------------------------------------------------


def test_embedder_pipeline_and_wire_response():
    emb = TpuEmbedder(
        "test-tiny", config=configs.TEST_TINY, max_tokens=32, seed=1
    )
    texts = ["the answer is 42", "the answer is 42!", "bananas are yellow"]
    vecs = emb.embed_texts(texts)
    assert vecs.shape == (3, TINY.hidden_size)
    np.testing.assert_allclose(np.linalg.norm(vecs, axis=1), 1.0, atol=1e-5)

    resp = emb.embeddings_response(texts)
    obj = resp.to_json_obj()
    assert obj["object"] == "list"
    assert len(obj["data"]) == 3
    assert obj["data"][2]["index"] == 2
    assert obj["usage"]["total_tokens"] == resp.usage.prompt_tokens > 0
    assert obj["model"] == "test-tiny"


def test_embedder_bucketing_consistency():
    # same text embeds identically regardless of batch padding bucket
    emb = TpuEmbedder("test-tiny", config=configs.TEST_TINY, max_tokens=32, seed=1)
    alone = emb.embed_texts(["consistent text"])
    batched = emb.embed_texts(["consistent text"] + ["filler"] * 4)
    np.testing.assert_allclose(alone[0], batched[0], atol=1e-5)


def test_embedder_cosine_consensus_integration():
    from llm_weighted_consensus_tpu.ops.similarity import cosine_consensus_vote

    emb = TpuEmbedder("test-tiny", config=configs.TEST_TINY, max_tokens=32, seed=1)
    texts = ["answer A", "answer A", "answer A", "something wildly different 12345"]
    conf = np.asarray(cosine_consensus_vote(jnp.asarray(emb.embed_texts(texts))))
    assert conf.argmax() < 3
    assert conf.sum() == pytest.approx(1.0, abs=1e-5)


# -- deberta RM ---------------------------------------------------------------


@pytest.fixture(scope="module")
def rm_params():
    return deberta.init_params(jax.random.PRNGKey(0), DTINY)


def test_reward_shapes_and_determinism(rm_params):
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(1, DTINY.vocab_size, size=(4, 24)), jnp.int32)
    mask = jnp.ones((4, 24), jnp.int32)
    r1 = deberta.reward(rm_params, ids, mask, DTINY)
    r2 = deberta.reward(rm_params, ids, mask, DTINY)
    assert r1.shape == (4,)
    np.testing.assert_array_equal(np.asarray(r1), np.asarray(r2))
    assert len(set(np.asarray(r1).round(6))) > 1  # not constant


def test_reward_padding_invariance(rm_params):
    rng = np.random.default_rng(1)
    ids = rng.integers(1, DTINY.vocab_size, size=(2, 16)).astype(np.int32)
    mask = np.ones((2, 16), dtype=np.int32)
    ids[:, -5:] = 0
    mask[:, -5:] = 0
    r1 = deberta.reward(rm_params, jnp.asarray(ids), jnp.asarray(mask), DTINY)
    ids2 = ids.copy()
    ids2[:, -5:] = 9
    r2 = deberta.reward(rm_params, jnp.asarray(ids2), jnp.asarray(mask), DTINY)
    np.testing.assert_allclose(np.asarray(r1), np.asarray(r2), atol=1e-5)


def test_reward_position_sensitivity(rm_params):
    # disentangled attention must make reward order-sensitive
    rng = np.random.default_rng(2)
    seqa = rng.integers(1, DTINY.vocab_size, size=(1, 12)).astype(np.int32)
    seqb = seqa[:, ::-1].copy()
    mask = jnp.ones((1, 12), jnp.int32)
    ra = deberta.reward(rm_params, jnp.asarray(seqa), mask, DTINY)
    rb = deberta.reward(rm_params, jnp.asarray(seqb), mask, DTINY)
    assert abs(float(ra[0]) - float(rb[0])) > 1e-6


def test_reward_consensus_vote(rm_params):
    rewards = jnp.asarray([2.0, 0.0, -1.0])
    conf = np.asarray(deberta.reward_consensus_vote(rewards))
    assert conf.sum() == pytest.approx(1.0, abs=1e-6)
    assert conf[0] > conf[1] > conf[2]


# -- sequence bucketing -------------------------------------------------------


def test_seq_bucket_multiples_of_16_then_sparse():
    from llm_weighted_consensus_tpu.models.embedder import _seq_bucket

    assert _seq_bucket(1, 512) == 16
    assert _seq_bucket(100, 512) == 112  # the ~100-token serving case
    assert _seq_bucket(112, 512) == 112
    assert _seq_bucket(113, 512) == 128
    assert _seq_bucket(130, 512) == 192
    assert _seq_bucket(500, 512) == 512
    # caps at the window
    assert _seq_bucket(100, 64) == 64
    # long-context presets keep doubling (bounded jit specializations)
    assert _seq_bucket(600, 8192) == 1024
    assert _seq_bucket(5000, 8192) == 8192


def test_tokenize_lands_in_seq_bucket():
    emb = TpuEmbedder("test-tiny", config=TINY, max_tokens=128, seed=1)
    # ~20 tokens -> the 32 bucket, not 128
    ids, mask = emb.tokenize(["word " * 20])
    assert ids.shape[1] in (32, 48)  # tokenizer-dependent, never 128
    assert ids.shape == mask.shape


# -- GELU numerics ------------------------------------------------------------


def _bf16_ordered(values: np.ndarray) -> np.ndarray:
    """bf16 bit patterns -> monotonically ordered ints (sign-magnitude fix)
    so ulp distance is |a - b|."""
    v = np.asarray(jnp.asarray(values, jnp.bfloat16)).view(np.uint16)
    mag = (v & 0x7FFF).astype(np.int32)
    return np.where(v & 0x8000, -mag, mag)


def test_gelu_bf16_fast_path_matches_exact_erf_exhaustively():
    """The bf16 GELU fast path (A&S erfc on hardware exp, bert._gelu_erf)
    must agree with the exact-erf f32 GELU after bf16 rounding on ALL
    finite bf16 inputs — enumerated exhaustively, not sampled — to within
    1 bf16 ulp (near-rounding-midpoint flips are inherent to ANY f32
    evaluation: XLA's own f32 erf GELU flips 635 of these inputs vs the
    f64 truth).  In the deep tail (x < -3, |gelu| < 0.003) a small
    absolute bound applies instead."""
    all_u16 = np.arange(65536, dtype=np.uint16)
    xs64 = all_u16.view(jnp.bfloat16.dtype).astype(np.float64)
    sane = np.isfinite(xs64)  # every finite bf16, huge magnitudes included
    xs = jnp.asarray(xs64[sane], jnp.bfloat16)

    fast = np.asarray(bert._gelu_erf(xs), np.float64)
    # reference: float64 stdlib erfc, rounded once to bf16 — the actual
    # ground truth.  Neither XLA's erf nor f64 x*0.5*(1+erf(z)) works as
    # the reference: XLA-CPU's vectorized f32 erf has been seen to
    # saturate 1 ulp LATE at huge |z| (erf(-8e6) = -0.9999998, turning
    # x*Phi into ~x), and the canonical 1+erf form
    # cancels to -0.0 once f64 erf saturates (|z| > 5.86) — where the
    # A&S erfc fast path still carries the correct ~1e-16 tail values.
    import math

    erfc64 = np.frompyfunc(math.erfc, 1, 1)
    x64 = xs64[sane]
    true64 = x64 * 0.5 * erfc64(-x64 / math.sqrt(2)).astype(np.float64)
    exact32 = np.asarray(jnp.asarray(true64, jnp.bfloat16), np.float64)
    # near/sub-min-normal outputs (|gelu| < 2^-125): XLA flushes bf16
    # subnormals to zero on cast while numpy keeps them (and rounds
    # boundary values up to min normal) — both the fast path and XLA's
    # exact-erf path flush identically, so compare those only for "both
    # tiny"
    tiny_cut = 2.0 ** -125
    normal = np.abs(exact32) >= tiny_cut
    assert np.abs(fast[~normal]).max() <= tiny_cut

    main = (xs64[sane] >= -3.0) & normal
    ulp = np.abs(_bf16_ordered(fast) - _bf16_ordered(exact32))
    assert ulp[main].max() <= 1, (
        f"max ulp distance {ulp[main].max()} in main range; "
        f"worst x={xs64[sane][main][ulp[main].argmax()]}"
    )
    frac = (ulp[main] > 0).mean()
    assert frac < 0.02, f"{(ulp[main] > 0).sum()} 1-ulp flips ({frac:.2%})"
    tail = (xs64[sane] < -3.0) & normal
    # f32 cancellation in the A&S polynomial costs a few bf16 ulps out in
    # the tail; 2e-5 absolute on values |gelu| < 0.005 is far below the
    # bf16 resolution of any downstream O(1)-scale accumulation
    assert np.abs(fast[tail] - exact32[tail]).max() < 2e-5
    assert np.abs(exact32[tail]).max() < 0.005


def test_gelu_f32_path_is_exact_erf():
    x = jnp.linspace(-6, 6, 4001, dtype=jnp.float32)
    ours = np.asarray(bert._gelu_erf(x))
    ref = np.asarray(x * 0.5 * (1.0 + jax.lax.erf(x * (2.0 ** -0.5))))
    np.testing.assert_array_equal(ours, ref)


# -- fused attention (ops/attention.py) ---------------------------------------


def test_fused_attention_matches_einsum(params):
    from dataclasses import replace

    ids, mask = toks(4, 24, n_pad=7)
    cfg_e = replace(TINY, attention_impl="einsum")
    cfg_f = replace(TINY, attention_impl="fused")
    e1 = bert.embed(params, ids, mask, cfg_e)
    e2 = bert.embed(params, ids, mask, cfg_f)
    np.testing.assert_allclose(np.asarray(e1), np.asarray(e2), atol=2e-5)


def test_fused_attention_padding_invariance(params):
    from dataclasses import replace

    cfg_f = replace(TINY, attention_impl="fused")
    ids, mask = toks(2, 16, n_pad=5)
    e1 = bert.embed(params, ids, mask, cfg_f)
    # extending padding must not change the embedding of real tokens
    ids2 = jnp.pad(ids, ((0, 0), (0, 8)))
    mask2 = jnp.pad(mask, ((0, 0), (0, 8)))
    e2 = bert.embed(params, ids2, mask2, cfg_f)
    np.testing.assert_allclose(np.asarray(e1), np.asarray(e2), atol=2e-5)


# The shapes that decide the kernel's column block: hd 64 with several
# 128-lane blocks of two heads, hd 32 (four heads a block), and test-tiny,
# whose whole hidden (64) is under one 128-lane tile.
ATTN_SHAPES = {
    "hd64-h256": dict(hidden_size=256, num_heads=4),
    "hd32-h384": dict(hidden_size=384, num_heads=12),
    "test-tiny": dict(hidden_size=64, num_heads=4),
    # a head fills its tile: no idle lane beside it for the row's sum
    "hd128-h256": dict(hidden_size=256, num_heads=2),
}


def attn_case(shape, dtype, impl, b=4, s=32, seed=0, keys="ragged", gain=1.0):
    """One attention block's inputs: (config, layer params, x, mask_bias,
    real-token mask).  ``keys``: every row ragged from s // 2; or row 0 with
    ``one`` real key somewhere; or row 0 all padding but the ``first`` token.
    ``gain`` multiplies x: at 30 the scores pass what ``exp`` can hold
    unshifted."""
    from dataclasses import replace

    cfg = replace(TINY, attention_impl=impl, **ATTN_SHAPES[shape])
    layer = jax.tree_util.tree_map(
        lambda a: a[0],
        bert.init_params(jax.random.PRNGKey(seed), cfg, dtype=dtype)["layers"],
    )
    rng = np.random.default_rng(seed)
    x = jnp.asarray(gain * rng.standard_normal((b, s, cfg.hidden_size)), dtype)
    lens = rng.integers(s // 2, s + 1, b)
    pos = np.arange(s)[None, :]
    real = pos < lens[:, None]
    if keys == "one":
        real[0] = pos[0] == rng.integers(1, s)
    elif keys == "first":
        real[0] = pos[0] == 0
    bias = jnp.where(jnp.asarray(real)[:, None, None, :], 0.0, -1e9)
    return cfg, layer, x, bias.astype(jnp.float32), real


def walk_eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs it calls (jit, scan,
    cond), except a pallas_call's kernel body: what is inside the kernel
    lives in VMEM, not in the program's arrays."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call":
            continue
        for param in eqn.params.values():
            for sub in param if isinstance(param, (list, tuple)) else [param]:
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from walk_eqns(inner)


def test_fused_attention_stays_in_the_encoder_layout():
    """The structural guard that the relayout copies cannot come back:
    around the kernel no transpose, and no array with hd as its minor
    dimension (half-filled 128-lane tiles)."""
    cfg, layer, x, bias, _ = attn_case("hd64-h256", jnp.float32, "fused")
    hd = cfg.head_dim
    assert hd not in (x.shape[1], cfg.hidden_size, cfg.intermediate_size)
    jaxpr = jax.make_jaxpr(
        lambda x, bias: bert._attention(x, layer, bias, cfg)
    )(x, bias)
    eqns = list(walk_eqns(jaxpr.jaxpr))
    names = [e.primitive.name for e in eqns]
    assert names.count("pallas_call") == 1
    assert "transpose" not in names
    for e in eqns:
        for var in list(e.invars) + list(e.outvars):
            shape = getattr(var.aval, "shape", ())
            assert not shape or shape[-1] != hd, (e.primitive.name, shape)


def _attn_block_cases():
    """(shape, dtype, what ``attn_case`` takes beside them), with its id."""
    f32, bf16 = "float32", "bfloat16"
    tiled = ["hd64-h256", "hd32-h384", "hd128-h256"]
    cases = [(w, d, {}) for w in [*tiled[:2], "test-tiny", tiled[2]]
             for d in (f32, bf16)]
    # the kernel's own lengths: one lane group of keys, the cells' bucket,
    # the longest it takes (f32 at 1024 is past VMEM on the chip, not here)
    cases += [(w, d, dict(b=2, s=128)) for w in tiled for d in (f32, bf16)]
    cases += [(w, d, dict(b=2, s=512))
              for w, d in zip(tiled, (bf16, f32, bf16))]
    cases += [(tiled[0], bf16, dict(b=1, s=1024)),
              (tiled[2], f32, dict(b=1, s=1024))]
    # what the row's sum and maximum must survive: a row of one real key, a
    # row of [CLS] alone, scores that ``exp`` unshifted would overflow on
    cases += [(w, d, dict(s=128, keys=keys))
              for w in tiled for keys in ("one", "first") for d in (f32, bf16)]
    cases += [(w, f32, dict(s=128, gain=30.0)) for w in tiled]
    for shape, dtype, case in cases:
        tag = "".join(f"-{k[0]}{v}" for k, v in case.items() if k != "b")
        yield pytest.param(shape, dtype, case, id=f"{shape}-{dtype}{tag}")


@pytest.mark.parametrize("shape, dtype, case", _attn_block_cases())
def test_fused_attention_block_matches_einsum(shape, dtype, case):
    dt = jnp.dtype(dtype)
    cfg, layer, x, bias, real = attn_case(shape, dt, "fused", **case)
    from dataclasses import replace

    got = bert._attention(x, layer, bias, cfg)
    want = bert._attention(
        x, layer, bias, replace(cfg, attention_impl="einsum")
    )
    assert got.shape == want.shape == x.shape and got.dtype == dt
    assert np.isfinite(np.asarray(got, np.float32)).all()
    if case.get("gain"):
        # the case is what it says: the largest score is past exp's range
        q = bert._dense_cfg(x, layer["attn_q"], cfg)
        k = bert._dense_cfg(x, layer["attn_k"], cfg)
        hd = cfg.head_dim
        top = jnp.einsum("bqd,bkd->bqk", q[..., :hd], k[..., :hd]).max()
        assert top / hd**0.5 > 89.0, top
    # pad-slot query rows are dropped by pooling
    rows = np.asarray(real)[:, :, None]
    # the values, and so the context, grow with the gain
    tol = (2e-5 if dt == jnp.float32 else 2e-2) * case.get("gain", 1.0)
    np.testing.assert_allclose(
        np.asarray(got, np.float32) * rows,
        np.asarray(want, np.float32) * rows,
        atol=tol, rtol=tol,
    )


def fit_need(b, s, nh, hd, itemsize, kk):
    """VMEM bytes of one grid step as best_heads_per_step reckons them."""
    from llm_weighted_consensus_tpu.ops import attention

    g = attention.heads_per_block(nh, hd)
    width = -(-g * hd // 128) * 128
    # heads that share a tile: result, numerators, denominators, [v | 1]
    shared = hd < 128 and 128 % hd == 0 and g * hd % 128 == 0
    products = s * 128 * (3 * 4 + itemsize if shared else 4)
    blocks = (kk // g) * (8 * s * width * itemsize + 16 * s * 4)
    return blocks + 2 * s * s * 4 + products


@pytest.mark.parametrize(
    "b, s, nh, hd, itemsize, g, rows",
    [
        (64, 512, 16, 64, 2, 2, 4),  # bge-large, one request
        (512, 512, 16, 64, 2, 2, 4),  # bge-large, a full group of 8
        (64, 512, 12, 32, 2, 4, 4),  # bge-small: four heads to 128 lanes
        (64, 512, 12, 64, 2, 2, 4),  # bge-base
        (4, 32, 4, 16, 4, 4, 4),  # test-tiny: the whole hidden, under a tile
        (3, 512, 16, 64, 2, 2, 1),  # an odd batch: one row a step
        (64, 512, 3, 48, 2, 0, 0),  # 144 lanes: no whole tiles, not under one
        (64, 1024, 16, 64, 4, 2, 0),  # f32 at 1024: the score tiles alone
        (64, 1024, 16, 64, 2, 2, 1),  # bf16 at 1024: one row a step, served
        (64, 512, 8, 128, 2, 1, 4),  # a head a tile: the sum stays on the tile
        (64, 1024, 8, 128, 2, 1, 2),
    ],
)
def test_best_heads_per_step_is_the_new_blocks_fit(
    b, s, nh, hd, itemsize, g, rows
):
    from llm_weighted_consensus_tpu.ops import attention

    assert attention.heads_per_block(nh, hd) == g
    kk = attention.best_heads_per_step(b, s, nh, hd, itemsize)
    budget = attention.VMEM_BUDGET
    assert kk == rows * g
    if g == 0 or fit_need(b, s, nh, hd, itemsize, g) > budget:
        assert kk == 0  # callers fall back to einsum
        return
    assert rows >= 1 and b % rows == 0
    assert fit_need(b, s, nh, hd, itemsize, kk) <= budget
    # the largest: twice the rows would not divide b, pass the step's
    # most, or not fit
    assert (
        b % (2 * rows)
        or 2 * rows > attention.MAX_ROWS_PER_STEP
        or fit_need(b, s, nh, hd, itemsize, 2 * kk) > budget
    )


@pytest.mark.parametrize(
    "backend, impl, nh, hd, b, s, fused",
    [
        ("tpu", "auto", 16, 64, 64, 512, True),  # the benchmark's bucket
        ("tpu", "auto", 16, 64, 512, 512, True),
        ("tpu", "auto", 16, 64, 64, 256, False),  # the bypass: under 512
        ("tpu", "auto", 16, 64, 64, 128, False),
        ("cpu", "auto", 16, 64, 64, 512, False),  # any bucket off a TPU
        ("tpu", "auto", 3, 48, 64, 512, False),  # cannot be carved
        ("tpu", "einsum", 16, 64, 64, 512, False),
        ("tpu", "ring", 16, 64, 64, 512, False),
        ("cpu", "fused", 16, 64, 64, 128, True),  # forced
    ],
)
def test_use_fused_attention_policy(
    monkeypatch, backend, impl, nh, hd, b, s, fused
):
    from dataclasses import replace

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    cfg = replace(
        TINY, hidden_size=nh * hd, num_heads=nh, attention_impl=impl
    )
    dt = jnp.dtype(jnp.bfloat16)
    assert bert._use_fused_attention(cfg, b, s, hd, dt) is fused


def test_auto_attention_off_tpu_is_the_einsum_path():
    """What every bucket under 512 runs (and every bucket off a TPU):
    the einsum path, its head reshape in place and no kernel."""
    cfg, layer, x, bias, _ = attn_case("hd64-h256", jnp.float32, "auto")
    jaxpr = jax.make_jaxpr(
        lambda x, bias: bert._attention(x, layer, bias, cfg)
    )(x, bias)
    eqns = list(walk_eqns(jaxpr.jaxpr))
    names = [e.primitive.name for e in eqns]
    assert "pallas_call" not in names
    b, s, _ = x.shape
    heads = (b, s, cfg.num_heads, cfg.head_dim)
    shapes = [v.aval.shape for e in eqns for v in e.outvars]
    assert shapes.count(heads) >= 4  # q, k, v and the context


def test_embed_and_vote_many_matches_single():
    emb = TpuEmbedder("test-tiny")
    rng = np.random.default_rng(3)
    reqs = []
    for r in range(3):
        ids = rng.integers(3, TINY.vocab_size, size=(4, 16)).astype(np.int32)
        mask = np.ones((4, 16), dtype=np.int32)
        reqs.append((ids, mask))
    batched = emb.consensus_confidence_tokens_many(
        np.stack([r[0] for r in reqs]), np.stack([r[1] for r in reqs])
    )
    batched = np.asarray(batched)
    assert batched.shape == (3, 4)
    for i, (ids, mask) in enumerate(reqs):
        single = np.asarray(emb.consensus_confidence_tokens(ids, mask))
        np.testing.assert_allclose(batched[i], single, atol=1e-5)


def test_model_family_presets_and_pooling():
    """e5/gte families: same BERT arch, masked-mean pooling by default;
    bge stays CLS.  All presets are loadable shapes."""
    from llm_weighted_consensus_tpu.models import configs
    from llm_weighted_consensus_tpu.models.embedder import TpuEmbedder

    assert configs.PRESETS["bge-large-en"].pooling == "cls"
    for name in ("e5-small-v2", "e5-base-v2", "e5-large-v2",
                 "gte-small", "gte-base", "gte-large"):
        assert configs.PRESETS[name].pooling == "mean", name
    # e5 shapes mirror bge shapes (both BERT arch)
    assert (
        configs.PRESETS["e5-large-v2"].hidden_size
        == configs.PRESETS["bge-large-en"].hidden_size
    )
    # the embedder picks up the family default and honors overrides
    emb = TpuEmbedder(
        "e5-small-v2", config=configs.TEST_TINY, max_tokens=32
    )
    assert emb.pooling == "cls"  # TEST_TINY's own default
    import dataclasses

    mean_tiny = dataclasses.replace(configs.TEST_TINY, pooling="mean")
    emb = TpuEmbedder("e5-small-v2", config=mean_tiny, max_tokens=32)
    assert emb.pooling == "mean"
    emb = TpuEmbedder(
        "e5-small-v2", config=mean_tiny, max_tokens=32, pooling="cls"
    )
    assert emb.pooling == "cls"
    # mean pooling produces valid normalized embeddings
    emb = TpuEmbedder("test-tiny", config=mean_tiny, max_tokens=32)
    out = emb.embed_texts(["hello world", "longer text with more words"])
    np.testing.assert_allclose(
        np.linalg.norm(out, axis=1), 1.0, atol=1e-5
    )


def test_bf16_serving_numerics_track_f32():
    """The TPU serving dtype (bf16 logit/score storage, models/bert.py)
    asserted against the f32 path ON CPU — an executable bound, not a
    comment (ADVICE r4): end-to-end embedding cosine stays high and the
    consensus vote keeps its argmax and a close distribution."""
    kwargs = dict(config=TINY, max_tokens=32, seed=3)
    f32 = TpuEmbedder("test-tiny", **kwargs)
    bf16 = TpuEmbedder("test-tiny", dtype=jnp.bfloat16, **kwargs)
    texts = [
        "the answer is four",
        "the answer is four",
        "the answer is four!",
        "bananas and poetry 999",
    ]
    ef = np.asarray(f32.embed_texts(texts), np.float32)
    eb = np.asarray(bf16.embed_texts(texts), np.float32)
    cos = (ef * eb).sum(axis=1)  # embeddings are l2-normalized
    assert cos.min() > 0.995, cos
    cf = np.asarray(f32.consensus_confidence(texts))
    cb = np.asarray(bf16.consensus_confidence(texts))
    assert cf.argmax() == cb.argmax()
    assert abs(float(cb.sum()) - 1.0) < 1e-3
    assert np.abs(cf - cb).max() < 0.05, (cf, cb)


def test_bf16_golden_checkpoint_vote_agreement():
    """bf16 through the committed HF-snapshot golden checkpoint: real
    weights, real tokenizer — the serving dtype must preserve the vote
    (same contract test_quant.py pins for int8)."""
    import json
    import os

    fixture = os.path.join(os.path.dirname(__file__), "fixtures", "bge_micro")
    if not os.path.isdir(fixture):
        pytest.skip("golden checkpoint fixture missing")
    from llm_weighted_consensus_tpu.models.loading import (
        find_vocab,
        load_params,
    )
    from llm_weighted_consensus_tpu.models.tokenizer import load_tokenizer

    with open(os.path.join(fixture, "config.json")) as f:
        cfg = json.load(f)
    config = configs.BertConfig(
        vocab_size=cfg["vocab_size"],
        hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        intermediate_size=cfg["intermediate_size"],
        max_position_embeddings=cfg["max_position_embeddings"],
        type_vocab_size=cfg["type_vocab_size"],
        layer_norm_eps=cfg["layer_norm_eps"],
    )
    params = load_params(fixture, config)
    tok = load_tokenizer(find_vocab(fixture))
    kwargs = dict(config=config, tokenizer=tok, max_tokens=64)
    f32 = TpuEmbedder("bge-micro", params=params, **kwargs)
    bf16 = TpuEmbedder(
        "bge-micro", params=params, dtype=jnp.bfloat16, **kwargs
    )
    texts = [
        "paris is the capital of france",
        "the capital of france is paris",
        "paris, france's capital city",
        "bananas are curved and yellow",
    ]
    ef = np.asarray(f32.embed_texts(texts), np.float32)
    eb = np.asarray(bf16.embed_texts(texts), np.float32)
    cos = (ef * eb).sum(axis=1)
    assert cos.min() > 0.99, cos
    cf = np.asarray(f32.consensus_confidence(texts))
    cb = np.asarray(bf16.consensus_confidence(texts))
    assert cf.argmax() == cb.argmax()
    assert np.abs(cf - cb).max() < 0.05, (cf, cb)


def test_bf16_reranker_preserves_reward_ordering():
    """DeBERTa's three disentangled score tensors store in the activation
    dtype (r4 cut); the bf16 RM must keep the reward ORDER and a close
    softmax distribution vs the f32 path — executable bound on CPU, same
    contract as test_quant.py's int8 RM test (ADVICE r4)."""
    from llm_weighted_consensus_tpu.models.reranker import TpuReranker

    kwargs = dict(config=DTINY, max_tokens=48, seed=5)
    full = TpuReranker("deberta-test-tiny", **kwargs)
    bf16 = TpuReranker("deberta-test-tiny", dtype=jnp.bfloat16, **kwargs)
    texts = [
        "the answer is four because two plus two",
        "the answer is five because arithmetic",
        "completely unrelated text about weather",
    ]
    cf, tf = full.rerank_confidence(texts, prompt="what is 2+2?")
    cb, tb = bf16.rerank_confidence(texts, prompt="what is 2+2?")
    assert tf == tb
    # Order is only observable above bf16 resolution: random-init rewards
    # can land within ~1e-5 of each other, where bf16's ~3 decimal digits
    # legitimately tie.  Assert pairwise order for every pair the f32
    # path itself separates beyond bf16 noise, instead of a full argsort
    # (which would flip on those ties and fail spuriously).
    sep = 5e-3
    for i in range(len(texts)):
        for j in range(len(texts)):
            if cf[i] - cf[j] > sep:
                assert cb[i] > cb[j], (i, j, cf, cb)
    assert np.abs(cf - cb).max() < 0.05, (cf, cb)
