"""Ring attention / sequence parallelism (parallel/ring.py): exact parity
with full attention on the virtual CPU mesh, long sequences, padding,
dp x sp meshes, and the memory claim (per-device score tile is local)."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from llm_weighted_consensus_tpu.models import bert
from llm_weighted_consensus_tpu.models.configs import BertConfig, TEST_TINY
from llm_weighted_consensus_tpu.parallel import ring

import dataclasses


def sp_mesh(sp, dp=1):
    devices = np.array(jax.devices()[: dp * sp]).reshape(dp, sp)
    return Mesh(devices, ("dp", "sp"))


def full_attention_reference(q, k, v, bias, scale):
    logits = (
        jnp.einsum("bqnd,bknd->bnqk", q, k).astype(jnp.float32) * scale
    )
    logits = logits + bias[:, None, None, :]
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bnqk,bknd->bqnd", probs, v).astype(q.dtype)


@pytest.mark.parametrize("sp", [2, 4, 8])
def test_ring_attention_matches_full(sp):
    rng = np.random.default_rng(0)
    b, s, nh, hd = 2, 32, 4, 8
    q = jnp.asarray(rng.normal(size=(b, s, nh, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, s, nh, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, s, nh, hd)), jnp.float32)
    # ragged padding on the key side
    bias = np.zeros((b, s), np.float32)
    bias[0, 28:] = ring.NEG_INF
    bias[1, 17:] = ring.NEG_INF
    bias = jnp.asarray(bias)
    scale = 1.0 / np.sqrt(hd)

    expected = full_attention_reference(q, k, v, bias, scale)

    mesh = sp_mesh(sp)
    spec = P(None, "sp")

    ringed = jax.shard_map(
        lambda q, k, v, b: ring.ring_attention(q, k, v, b, scale, "sp"),
        mesh=mesh,
        in_specs=(spec, spec, spec, spec),
        out_specs=P(None, "sp", None, None),
        check_vma=False,
    )(q, k, v, bias)
    np.testing.assert_allclose(
        np.asarray(ringed), np.asarray(expected), atol=1e-5
    )


def test_ring_encode_matches_full_forward():
    config = dataclasses.replace(TEST_TINY, attention_impl="einsum")
    ring_config = dataclasses.replace(TEST_TINY, attention_impl="ring")
    params = bert.init_params(jax.random.PRNGKey(0), config)
    rng = np.random.default_rng(1)
    b, s = 2, 32
    ids = jnp.asarray(rng.integers(3, config.vocab_size, (b, s)), jnp.int32)
    mask = np.ones((b, s), np.int32)
    mask[1, 20:] = 0
    mask = jnp.asarray(mask)

    full = np.asarray(bert.encode(params, ids, mask, config))
    mesh = sp_mesh(8)
    ringed = np.asarray(
        ring.ring_encode(params, ids, mask, ring_config, mesh)
    )
    real = np.asarray(mask).astype(bool)
    np.testing.assert_allclose(ringed[real], full[real], atol=1e-4)


def test_ring_embed_matches_bert_embed():
    config = dataclasses.replace(TEST_TINY, attention_impl="einsum")
    ring_config = dataclasses.replace(TEST_TINY, attention_impl="ring")
    params = bert.init_params(jax.random.PRNGKey(2), config)
    rng = np.random.default_rng(3)
    b, s = 4, 64
    ids = jnp.asarray(rng.integers(3, config.vocab_size, (b, s)), jnp.int32)
    mask = jnp.ones((b, s), jnp.int32)

    full = np.asarray(bert.embed(params, ids, mask, config))
    mesh = sp_mesh(8)
    ringed = np.asarray(
        ring.ring_embed(params, ids, mask, ring_config, mesh)
    )
    np.testing.assert_allclose(ringed, full, atol=1e-4)


def test_ring_long_context_beyond_single_window():
    """The point of the feature: a sequence longer than TEST_TINY's
    default window still encodes — each device only holds s/sp."""
    long_config = BertConfig(
        vocab_size=256,
        hidden_size=32,
        num_layers=2,
        num_heads=2,
        intermediate_size=64,
        max_position_embeddings=2048,
        attention_impl="ring",
    )
    full_config = dataclasses.replace(long_config, attention_impl="einsum")
    params = bert.init_params(jax.random.PRNGKey(4), long_config)
    rng = np.random.default_rng(5)
    b, s = 1, 1024
    ids = jnp.asarray(rng.integers(3, 256, (b, s)), jnp.int32)
    mask = jnp.ones((b, s), jnp.int32)
    mesh = sp_mesh(8)
    ringed = np.asarray(ring.ring_embed(params, ids, mask, long_config, mesh))
    full = np.asarray(bert.embed(params, ids, mask, full_config))
    np.testing.assert_allclose(ringed, full, atol=1e-4)


def test_ring_with_dp_and_sp_axes():
    """2D mesh: batch over dp, sequence over sp, one forward."""
    config = dataclasses.replace(TEST_TINY, attention_impl="einsum")
    ring_config = dataclasses.replace(TEST_TINY, attention_impl="ring")
    params = bert.init_params(jax.random.PRNGKey(6), config)
    rng = np.random.default_rng(7)
    b, s = 4, 16
    ids = jnp.asarray(rng.integers(3, config.vocab_size, (b, s)), jnp.int32)
    mask = jnp.ones((b, s), jnp.int32)
    mesh = sp_mesh(sp=4, dp=2)

    from jax.sharding import NamedSharding

    hidden = ring.ring_encode(
        params,
        jax.device_put(ids, NamedSharding(mesh, P("dp", "sp"))),
        jax.device_put(mask, NamedSharding(mesh, P("dp", "sp"))),
        ring_config,
        mesh,
        dp_axis="dp",
    )
    full = np.asarray(bert.encode(params, ids, mask, config))
    np.testing.assert_allclose(np.asarray(hidden), full, atol=1e-4)


def test_ring_rejects_bad_shapes():
    ring_config = dataclasses.replace(TEST_TINY, attention_impl="ring")
    params = bert.init_params(jax.random.PRNGKey(0), ring_config)
    mesh = sp_mesh(8)
    ids = jnp.zeros((1, 12), jnp.int32)  # 12 % 8 != 0
    with pytest.raises(ValueError, match="divide"):
        ring.ring_encode(params, ids, jnp.ones_like(ids), ring_config, mesh)
    einsum_config = dataclasses.replace(TEST_TINY, attention_impl="einsum")
    with pytest.raises(ValueError, match="attention_impl"):
        ring.ring_encode(
            params,
            jnp.zeros((1, 16), jnp.int32),
            jnp.ones((1, 16), jnp.int32),
            einsum_config,
            mesh,
        )


def test_ring_rejects_sequence_beyond_position_table():
    ring_config = dataclasses.replace(TEST_TINY, attention_impl="ring")
    params = bert.init_params(jax.random.PRNGKey(0), ring_config)
    mesh = sp_mesh(8)
    s = 128  # TEST_TINY max_position_embeddings = 64
    ids = jnp.zeros((1, s), jnp.int32)
    with pytest.raises(ValueError, match="usable window"):
        ring.ring_encode(params, ids, jnp.ones_like(ids), ring_config, mesh)
    # the plain forward rejects it too
    einsum_config = dataclasses.replace(TEST_TINY, attention_impl="einsum")
    with pytest.raises(ValueError, match="max_position_embeddings"):
        bert.encode(params, ids, jnp.ones_like(ids), einsum_config)


# -- sequence-parallel serving wiring (mesh mode's ring route) ----------------


def ring_route(embedder, texts):
    """What the batcher does with an over-length request on an sp-bearing
    mesh: tokenize to the ring window, dispatch through the ring."""
    return embedder.embed_tokens_ring(*embedder.tokenize_ring(texts))


def mesh_embedder(dp, sp, devices=None):
    from llm_weighted_consensus_tpu.models.embedder import TpuEmbedder
    from llm_weighted_consensus_tpu.parallel.mesh import make_mesh
    from llm_weighted_consensus_tpu.parallel.sharding import (
        shard_embedder_mesh,
    )

    emb = TpuEmbedder("test-tiny", config=TEST_TINY, max_tokens=64, seed=2)
    shard_embedder_mesh(emb, make_mesh(dp=dp, sp=sp, devices=devices))
    return emb


def test_shard_embedder_sp_matches_plain_embedder():
    from llm_weighted_consensus_tpu.models.embedder import TpuEmbedder

    plain = TpuEmbedder("test-tiny", config=TEST_TINY, max_tokens=64, seed=2)
    ringed = mesh_embedder(dp=1, sp=8)
    texts = [
        "a longer text with many words " * 2,
        "short",
        "and a third document",
    ]
    np.testing.assert_allclose(
        ring_route(ringed, texts), plain.embed_texts(texts), atol=1e-4
    )


def test_build_embedder_mesh_sp_round_trip():
    from llm_weighted_consensus_tpu.serve import Config
    from llm_weighted_consensus_tpu.serve.__main__ import build_embedder

    config = Config.from_env(
        {
            "EMBEDDER_MODEL": "test-tiny",
            "EMBEDDER_MAX_TOKENS": "64",
            "MESH_ENABLED": "1",
            "MESH_SHAPE": "2x1x4",
        }
    )
    embedder = build_embedder(config)
    assert dict(embedder.mesh.shape) == {"dp": 2, "tp": 1, "sp": 4}
    assert embedder.ring_available()
    out = ring_route(embedder, ["long context through the ring"])
    np.testing.assert_allclose(np.linalg.norm(out, axis=1), 1.0, atol=1e-5)

    # under mesh mode tp and sp are axes of one mesh and combine (the
    # hook path refused the pair)
    both = build_embedder(
        Config.from_env(
            {
                "EMBEDDER_MODEL": "test-tiny",
                "MESH_ENABLED": "1",
                "MESH_SHAPE": "2x2x2",
            }
        )
    )
    assert dict(both.mesh.shape) == {"dp": 2, "tp": 2, "sp": 2}
    assert both.ring_available()


def test_long_context_preset_exists():
    from llm_weighted_consensus_tpu.models.configs import PRESETS

    cfg = PRESETS["bert-long-8k"]
    assert cfg.max_position_embeddings == 8192
    assert cfg.hidden_size == 1024


def test_sp_serving_edge_configs():
    """Reviewer repros: non-power-of-two dp divides via batch_multiple;
    sp that does not divide the position table caps the ring window; sp=0
    is a clean config error."""
    from llm_weighted_consensus_tpu.models.embedder import TpuEmbedder
    from llm_weighted_consensus_tpu.serve import Config

    # dp=3 x sp=2 on 6 devices: batch pads to a dp multiple, not a crash
    emb = mesh_embedder(dp=3, sp=2, devices=jax.devices()[:6])
    assert emb.batch_multiple == 3
    plain = TpuEmbedder("test-tiny", config=TEST_TINY, max_tokens=64, seed=2)
    texts = ["one", "two", "three", "four"]  # 4 texts, pads to 6 rows
    np.testing.assert_allclose(
        ring_route(emb, texts), plain.embed_texts(texts), atol=1e-4
    )

    # sp=3 does not divide max_pos 64: ring window capped to 63,
    # full-length inputs still embed (never 500)
    emb3 = mesh_embedder(dp=1, sp=3, devices=jax.devices()[:3])
    assert emb3.ring_max_tokens == 63
    ids, mask = emb3.tokenize_ring(["word " * 200])  # truncates
    assert ids.shape[1] == 63
    out = emb3.embed_tokens_ring(ids, mask)  # embeds, no error
    np.testing.assert_allclose(np.linalg.norm(out, axis=1), 1.0, atol=1e-5)

    # sp=0 is rejected at start-up with a clear error
    with pytest.raises(ValueError, match="positive axes"):
        Config.from_env(
            {
                "EMBEDDER_MODEL": "test-tiny",
                "MESH_ENABLED": "1",
                "MESH_SHAPE": "2x1x0",
            }
        )


def test_mesh_sp_autofill_dp_and_long_default_window():
    from llm_weighted_consensus_tpu.serve import Config
    from llm_weighted_consensus_tpu.serve.__main__ import build_embedder

    # dp unset -> every device not consumed by sp becomes dp
    assert dict(mesh_embedder(dp=None, sp=2).mesh.shape) == {
        "dp": 4, "tp": 1, "sp": 2
    }
    # MESH_SHAPE unset -> every local device on dp
    alone = build_embedder(
        Config.from_env({"EMBEDDER_MODEL": "test-tiny", "MESH_ENABLED": "1"})
    )
    assert dict(alone.mesh.shape) == {"dp": 8, "tp": 1}
    assert not alone.ring_available()
    # the ring window is the full position table (test-tiny: 64) whatever
    # the dense window: a long input is routed, not cut at the dense cap
    embedder = build_embedder(
        Config.from_env(
            {
                "EMBEDDER_MODEL": "test-tiny",
                "EMBEDDER_MAX_TOKENS": "32",
                "MESH_ENABLED": "1",
                "MESH_SHAPE": "4x1x2",
            }
        )
    )
    assert embedder.max_tokens == 32
    assert embedder.ring_max_tokens == 64
    assert embedder.tokenize_ring(["word " * 200])[0].shape[1] == 64


def test_ring_with_roberta_positions():
    """Sequence-parallel forward composes with the roberta position scheme
    (bge-m3 backbone): shard offsets + position base give every shard its
    correct global positions."""
    roberta = BertConfig(
        vocab_size=128,
        hidden_size=32,
        num_layers=2,
        num_heads=2,
        intermediate_size=64,
        max_position_embeddings=66,  # 64 usable
        type_vocab_size=1,
        pad_token_id=1,
        position_style="roberta",
        attention_impl="ring",
    )
    full_config = dataclasses.replace(roberta, attention_impl="einsum")
    params = bert.init_params(jax.random.PRNGKey(9), roberta)
    rng = np.random.default_rng(10)
    b, s = 2, 64
    ids = jnp.asarray(rng.integers(4, 128, (b, s)), jnp.int32)
    mask = jnp.ones((b, s), jnp.int32)
    mesh = sp_mesh(8)
    ringed = np.asarray(ring.ring_embed(params, ids, mask, roberta, mesh))
    full = np.asarray(bert.embed(params, ids, mask, full_config))
    np.testing.assert_allclose(ringed, full, atol=1e-4)
    # the usable-window guard accounts for the position base
    too_long = jnp.zeros((1, 72), jnp.int32)
    with pytest.raises(ValueError, match="usable window"):
        ring.ring_encode(
            params, too_long, jnp.ones_like(too_long), roberta, mesh
        )
