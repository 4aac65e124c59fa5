"""int8 (W8A8) serving mode: numerics, plumbing, and sharding.

The quantized path is opt-in (models/quant.py, ``quantize="int8"``) and
has no reference analog (the reference's model compute is upstream HTTP);
these tests pin what the mode promises: per-matmul quantization error at
the int8-resolution scale, end-to-end embeddings close to the
full-precision path, consensus votes that agree with full precision on
clusterable candidates, and TP-shardability of the quantized pytree.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp

from llm_weighted_consensus_tpu.models import bert, configs
from llm_weighted_consensus_tpu.models.embedder import TpuEmbedder
from llm_weighted_consensus_tpu.models.quant import (
    dense_int8,
    quantize_bert_params,
    quantize_weight,
)

TINY = configs.TEST_TINY


def test_quantize_weight_roundtrip_error_bounded():
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.standard_normal((64, 32)) * 0.1, jnp.float32)
    q, scale = quantize_weight(w)
    assert q.dtype == jnp.int8 and scale.shape == (32,)
    deq = np.asarray(q, np.float32) * np.asarray(scale)[None, :]
    # symmetric int8 round-off: half a step of each channel's scale
    err = np.abs(deq - np.asarray(w))
    assert (err <= np.asarray(scale)[None, :] * 0.5 + 1e-9).all()


def test_dense_int8_matches_f32_dense():
    from llm_weighted_consensus_tpu.models.layers import dense

    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((8, 48)), jnp.float32)
    p = {
        "kernel": jnp.asarray(rng.standard_normal((48, 24)) * 0.2, jnp.float32),
        "bias": jnp.asarray(rng.standard_normal(24) * 0.1, jnp.float32),
    }
    kq, scale = quantize_weight(p["kernel"])
    out_q = np.asarray(dense_int8(x, {"kernel_q": kq, "scale": scale, "bias": p["bias"]}))
    out_f = np.asarray(dense(x, p))
    # W8A8 error scale: ~1/127 relative per factor; contraction over 48
    # terms averages it out
    denom = np.abs(out_f).max()
    assert np.abs(out_q - out_f).max() / denom < 0.03


def test_quantized_forward_tracks_full_precision():
    params = bert.init_params(jax.random.PRNGKey(0), TINY)
    qparams = quantize_bert_params(params)
    import dataclasses

    qcfg = dataclasses.replace(TINY, quantize="int8")
    rng = np.random.default_rng(2)
    ids = jnp.asarray(rng.integers(3, TINY.vocab_size, (4, 16)), jnp.int32)
    mask = jnp.ones((4, 16), jnp.int32)
    full = np.asarray(bert.embed(params, ids, mask, TINY))
    quant = np.asarray(bert.embed(qparams, ids, mask, qcfg))
    # l2-normalized embeddings: cosine similarity is the honest metric
    cos = (full * quant).sum(axis=1)
    assert cos.min() > 0.98, cos


def test_quantized_embedder_vote_agrees_with_full_precision():
    kwargs = dict(config=TINY, max_tokens=32, seed=3)
    full = TpuEmbedder("test-tiny", **kwargs)
    quant = TpuEmbedder("test-tiny", quantize="int8", **kwargs)
    assert quant.config.quantize == "int8"
    assert "kernel_q" in quant.params["layers"]["attn_q"]
    texts = [
        "the answer is four",
        "the answer is four",
        "the answer is four!",
        "bananas and poetry 999",
    ]
    cf = np.asarray(full.consensus_confidence(texts))
    cq = np.asarray(quant.consensus_confidence(texts))
    assert cf.argmax() == cq.argmax()
    assert abs(float(cq.sum()) - 1.0) < 1e-3
    # distribution stays close, not just the argmax
    assert np.abs(cf - cq).max() < 0.1, (cf, cq)


def test_quantized_golden_checkpoint_vote_agreement():
    """The committed HF-snapshot golden checkpoint through both paths:
    real weights, real tokenizer — quantization must preserve the vote."""
    import os

    fixture = os.path.join(
        os.path.dirname(__file__), "fixtures", "bge_micro"
    )
    if not os.path.isdir(fixture):
        pytest.skip("golden checkpoint fixture missing")
    import json

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
    full = TpuEmbedder("bge-micro", params=params, **kwargs)
    quant = TpuEmbedder(
        "bge-micro", params=params, quantize="int8", **kwargs
    )
    texts = [
        "paris is the capital of france",
        "the capital of france is paris",
        "paris, france's capital city",
        "bananas are curved and yellow",
    ]
    cf = np.asarray(full.consensus_confidence(texts))
    cq = np.asarray(quant.consensus_confidence(texts))
    assert cf.argmax() == cq.argmax()
    assert np.abs(cf - cq).max() < 0.1, (cf, cq)


def test_quantized_reranker_preserves_reward_ordering():
    """The int8 RM must keep the reward ORDER (what re-ranking consumes)
    and a close softmax distribution vs the full-precision path."""
    from llm_weighted_consensus_tpu.models.reranker import TpuReranker

    kwargs = dict(config=configs.DEBERTA_TEST_TINY, max_tokens=48, seed=5)
    full = TpuReranker("deberta-test-tiny", **kwargs)
    quant = TpuReranker("deberta-test-tiny", quantize="int8", **kwargs)
    assert quant.config.quantize == "int8"
    # positional projections stay full precision by design
    assert "kernel" in quant.params["layers"]["pos_q"]
    assert "kernel_q" in quant.params["layers"]["attn_q"]
    texts = [
        "the answer is four because two plus two",
        "the answer is five because arithmetic",
        "completely unrelated text about weather",
    ]
    cf, tf = full.rerank_confidence(texts, prompt="what is 2+2?")
    cq, tq = quant.rerank_confidence(texts, prompt="what is 2+2?")
    assert tf == tq
    assert list(np.argsort(cf)) == list(np.argsort(cq)), (cf, cq)
    assert np.abs(cf - cq).max() < 0.1, (cf, cq)


def test_quantized_params_shard_on_dp_tp_mesh():
    from llm_weighted_consensus_tpu.parallel.mesh import make_mesh
    from llm_weighted_consensus_tpu.parallel.sharding import (
        shard_embedder_mesh,
    )

    n = min(len(jax.devices()), 4)
    if n < 4:
        pytest.skip("needs 4 virtual devices")
    emb = TpuEmbedder(
        "test-tiny", config=TINY, max_tokens=32, seed=3, quantize="int8"
    )
    ref = TpuEmbedder("test-tiny", config=TINY, max_tokens=32, seed=3,
                      quantize="int8")
    texts = ["alpha one", "alpha one", "beta two", "gamma three"]
    want = np.asarray(ref.consensus_confidence(texts))
    mesh = make_mesh(dp=2, tp=2, devices=jax.devices()[:4])
    shard_embedder_mesh(emb, mesh)
    got = np.asarray(emb.consensus_confidence(texts))
    np.testing.assert_allclose(got, want, atol=2e-4)


def test_quantized_bf16_combined_golden_checkpoint():
    """int8 weights + bf16 activations COMBINED — the exact chip serving
    mode (EMBEDDER_QUANTIZE=int8 on TPU runs bf16 activations) — on the
    committed real-weights golden checkpoint: vote argmax preserved,
    distribution close to the f32 full-precision path.  r5: the two modes
    were only pinned separately (test_quant int8@f32, test_models
    bf16@full-precision)."""
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
    full = TpuEmbedder("bge-micro", params=params, **kwargs)
    both = TpuEmbedder(
        "bge-micro", params=params, quantize="int8",
        dtype=jnp.bfloat16, **kwargs
    )
    texts = [
        "paris is the capital of france",
        "the capital of france is paris",
        "paris, france's capital city",
        "bananas are curved and yellow",
    ]
    ef = np.asarray(full.embed_texts(texts), np.float32)
    eb = np.asarray(both.embed_texts(texts), np.float32)
    cos = (ef * eb).sum(axis=1)
    assert cos.min() > 0.98, cos
    cf = np.asarray(full.consensus_confidence(texts))
    cb = np.asarray(both.consensus_confidence(texts))
    assert cf.argmax() == cb.argmax()
    assert np.abs(cf - cb).max() < 0.1, (cf, cb)


# -- fused W8A8 Pallas kernel (ops/kernels.w8a8_matmul) -----------------------


def _int8_params(rng, k, n):
    w = jnp.asarray(rng.standard_normal((k, n)) * 0.2, jnp.float32)
    b = jnp.asarray(rng.standard_normal(n) * 0.1, jnp.float32)
    kq, scale = quantize_weight(w)
    return {"kernel_q": kq, "scale": scale, "bias": b}


def test_w8a8_kernel_matches_xla_int8_path():
    """Interpret-mode Pallas kernel vs the dot_general int8 fallback: SAME
    quantization math (per-token activation scales, int32 accumulation,
    rank-1 dequant), so they must agree to float round-off — not merely
    to quantization error."""
    rng = np.random.default_rng(7)
    p = _int8_params(rng, 48, 24)
    for shape in [(8, 48), (2, 5, 48)]:
        x = jnp.asarray(rng.standard_normal(shape), jnp.float32)
        got = np.asarray(dense_int8(x, p, impl="pallas"))
        want = np.asarray(dense_int8(x, p, impl="xla"))
        assert got.shape == want.shape == (*shape[:-1], 24)
        np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_w8a8_kernel_gelu_epilogue_matches_xla():
    """gelu=True fuses the activation into the kernel epilogue; parity
    with the unfused XLA path (dense_int8 + gelu_erf) in BOTH dtypes —
    the epilogue switches erf flavors on dtype exactly like gelu_erf."""
    rng = np.random.default_rng(8)
    p = _int8_params(rng, 32, 16)
    for dtype, tol in [(jnp.float32, 2e-5), (jnp.bfloat16, 2e-2)]:
        x = jnp.asarray(rng.standard_normal((8, 32)), dtype)
        got = np.asarray(
            dense_int8(x, p, gelu=True, impl="pallas"), np.float32
        )
        want = np.asarray(
            dense_int8(x, p, gelu=True, impl="xla"), np.float32
        )
        np.testing.assert_allclose(got, want, atol=tol, rtol=tol)


def test_w8a8_oversize_shape_falls_back_to_xla():
    """A weight block past the VMEM budget must route to the XLA int8
    fallback inside dense_int8 (same numerics, no kernel) instead of
    lowering an unfittable pallas_call."""
    from llm_weighted_consensus_tpu.ops.kernels import w8a8_shape_fits

    assert not w8a8_shape_fits(128, 4096, 4096, 4)
    rng = np.random.default_rng(9)
    p = _int8_params(rng, 4096, 16)  # k big enough only with tiny n: fits
    assert w8a8_shape_fits(8, 4096, 16, 4)
    # the gate itself is exercised end-to-end by the jaxpr dispatch test
    x = jnp.asarray(rng.standard_normal((8, 4096)), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(dense_int8(x, p, impl="pallas")),
        np.asarray(dense_int8(x, p, impl="xla")),
        atol=2e-4, rtol=2e-4,
    )


def test_int8_pallas_forward_matches_full_precision_pinned():
    """The ACCEPTANCE bound: interpret-mode fused path vs the bf16-free
    full-precision forward — embedding cosine >= 0.98 per row and vote
    top-1 agreement, pinned (not relative to the XLA int8 path)."""
    import dataclasses

    params = bert.init_params(jax.random.PRNGKey(0), TINY)
    qparams = quantize_bert_params(params)
    qcfg = dataclasses.replace(TINY, quantize="int8-pallas")
    rng = np.random.default_rng(10)
    ids = jnp.asarray(rng.integers(3, TINY.vocab_size, (4, 16)), jnp.int32)
    mask = jnp.ones((4, 16), jnp.int32)
    full = np.asarray(bert.embed(params, ids, mask, TINY))
    fused = np.asarray(bert.embed(qparams, ids, mask, qcfg))
    cos = (full * fused).sum(axis=1)
    assert cos.min() > 0.98, cos

    kwargs = dict(config=TINY, max_tokens=32, seed=3)
    ref = TpuEmbedder("test-tiny", **kwargs)
    emb = TpuEmbedder("test-tiny", quantize="int8-pallas", **kwargs)
    texts = [
        "the answer is four",
        "the answer is four",
        "the answer is four!",
        "bananas and poetry 999",
    ]
    cf = np.asarray(ref.consensus_confidence(texts))
    cq = np.asarray(emb.consensus_confidence(texts))
    assert cf.argmax() == cq.argmax()
    assert np.abs(cf - cq).max() < 0.1, (cf, cq)


def test_int8_pallas_and_xla_dispatch_evidence():
    """The traced forward PROVES which path runs: int8-pallas contains
    pallas_call W8A8 eqns and zero int8->float dequant converts (the
    storage-format anti-pattern the fused path replaced); int8-xla keeps
    the dot_general fallback (no kernel, int8 operands feed the matmul
    directly — still no dequant-to-bf16-then-matmul)."""
    from llm_weighted_consensus_tpu.analysis.jaxpr_audit import walk_jaxpr

    rng = np.random.default_rng(11)
    ids = rng.integers(3, TINY.vocab_size, (4, 16)).astype(np.int32)
    mask = np.ones((4, 16), np.int32)

    def counts(emb):
        closed = jax.make_jaxpr(
            lambda p, i, m: bert.embed(
                p, i, m, emb.config, pooling=emb.pooling
            )
        )(emb.params, jnp.asarray(ids), jnp.asarray(mask))
        n = {"pallas_call": 0, "dequant": 0}

        def visit(eqn):
            if eqn.primitive.name == "pallas_call":
                n["pallas_call"] += 1
            if eqn.primitive.name == "convert_element_type":
                src, dst = eqn.invars[0].aval, eqn.outvars[0].aval
                if src.dtype == jnp.int8 and jnp.issubdtype(
                    dst.dtype, jnp.floating
                ):
                    n["dequant"] += 1

        walk_jaxpr(closed.jaxpr, visit)
        return n

    fused = counts(TpuEmbedder("test-tiny", config=TINY, max_tokens=32,
                               seed=3, quantize="int8-pallas"))
    assert fused["pallas_call"] > 0, fused
    assert fused["dequant"] == 0, fused

    xla = counts(TpuEmbedder("test-tiny", config=TINY, max_tokens=32,
                             seed=3, quantize="int8-xla"))
    assert xla["pallas_call"] == 0, xla


def test_quant_mode_validation_and_auto_selection():
    from llm_weighted_consensus_tpu.models.quant import (
        QUANT_MODES,
        impl_for,
        resolve_quantize,
    )

    assert set(QUANT_MODES) == {
        "none", "int8", "int8-pallas", "int8-xla", "int4-pallas"
    }
    assert impl_for("int8-pallas") == "pallas"
    assert impl_for("int8-xla") == "xla"
    # int4 has no XLA kernel twin: the pallas impl (interpret mode off
    # TPU) is the only W4A8 path, everywhere
    assert impl_for("int4-pallas") == "pallas"
    # auto mode picks by backend: xla everywhere but tpu
    expect = "pallas" if jax.default_backend() == "tpu" else "xla"
    assert impl_for("int8") == expect
    with pytest.raises(ValueError):
        impl_for("none")
    with pytest.raises(ValueError):
        resolve_quantize(TINY, {}, "int4")


# -- W4A8 packed-int4 weights -------------------------------------------------


def test_quantize_weight_int4_roundtrip_error_bounded():
    from llm_weighted_consensus_tpu.models.quant import (
        _unpack_int4,
        quantize_weight_int4,
    )

    rng = np.random.default_rng(12)
    w = jnp.asarray(rng.standard_normal((64, 32)) * 0.1, jnp.float32)
    kq, scale = quantize_weight_int4(w)
    from llm_weighted_consensus_tpu.ops.kernels import W4A8_PACK_K

    # two nibbles per byte along a K axis padded to the kernel's pack
    # block: half the padded rows, same output channels
    assert kq.dtype == jnp.uint8 and kq.shape == (W4A8_PACK_K // 2, 32)
    assert scale.shape == (32,)
    deq = np.asarray(_unpack_int4(kq, 64), np.float32) * np.asarray(scale)[None]
    # symmetric int4 round-off: half a step of each channel's scale
    err = np.abs(deq - np.asarray(w))
    assert (err <= np.asarray(scale)[None, :] * 0.5 + 1e-9).all()


def test_w4a8_kernel_matches_xla_unpack_path():
    """The in-kernel nibble unpack vs the XLA unpack-then-int8 fallback:
    SAME quantized math (identical int4 decode, per-token activation
    scales, int32 accumulation), so parity is float round-off — the
    JXA011-tolerance evidence that packing changed the storage, not the
    answer."""
    from llm_weighted_consensus_tpu.models.quant import (
        dense_int4,
        quantize_weight_int4,
    )

    rng = np.random.default_rng(13)
    w = jnp.asarray(rng.standard_normal((48, 24)) * 0.2, jnp.float32)
    b = jnp.asarray(rng.standard_normal(24) * 0.1, jnp.float32)
    kq, scale = quantize_weight_int4(w)
    p = {"kernel_q": kq, "scale": scale, "bias": b}
    for shape in [(8, 48), (2, 5, 48)]:
        x = jnp.asarray(rng.standard_normal(shape), jnp.float32)
        got = np.asarray(dense_int4(x, p, impl="pallas"))
        want = np.asarray(dense_int4(x, p, impl="xla"))
        assert got.shape == want.shape == (*shape[:-1], 24)
        np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4)
    # the fused-gelu epilogue carries over from the W8A8 kernel
    x = jnp.asarray(rng.standard_normal((8, 48)), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(dense_int4(x, p, gelu=True, impl="pallas")),
        np.asarray(dense_int4(x, p, gelu=True, impl="xla")),
        atol=1e-4, rtol=1e-4,
    )


def test_int4_pallas_forward_tracks_full_precision():
    """End-to-end W4A8 acceptance: int4 is coarser than int8, but the
    l2-normalized embeddings must stay directionally faithful and the
    consensus vote must agree on top-1."""
    import dataclasses

    from llm_weighted_consensus_tpu.models.quant import (
        is_int4,
        quantize_bert_params_int4,
    )

    params = bert.init_params(jax.random.PRNGKey(0), TINY)
    qparams = quantize_bert_params_int4(params)
    assert is_int4(qparams)
    qcfg = dataclasses.replace(TINY, quantize="int4-pallas")
    rng = np.random.default_rng(14)
    ids = jnp.asarray(rng.integers(3, TINY.vocab_size, (4, 16)), jnp.int32)
    mask = jnp.ones((4, 16), jnp.int32)
    full = np.asarray(bert.embed(params, ids, mask, TINY))
    fused = np.asarray(bert.embed(qparams, ids, mask, qcfg))
    cos = (full * fused).sum(axis=1)
    assert cos.min() > 0.95, cos

    kwargs = dict(config=TINY, max_tokens=32, seed=3)
    ref = TpuEmbedder("test-tiny", **kwargs)
    emb = TpuEmbedder("test-tiny", quantize="int4-pallas", **kwargs)
    assert emb.config.quantize == "int4-pallas"
    texts = [
        "the answer is four",
        "the answer is four",
        "the answer is four!",
        "bananas and poetry 999",
    ]
    cf = np.asarray(ref.consensus_confidence(texts))
    cq = np.asarray(emb.consensus_confidence(texts))
    assert cf.argmax() == cq.argmax()
    assert np.abs(cf - cq).max() < 0.15, (cf, cq)
