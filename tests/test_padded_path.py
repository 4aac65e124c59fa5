"""The padded serving path of the encoder: what a mixed-length queue rests on.

Every request's rows are padded to a sequence bucket and a row bucket, items
tokenized at submit time are joined into one batch, and the batcher counts
real tokens against dispatched slots (the benchmark's
``packing.padding_share`` reads those counters).  What this pins:

* the forward is invariant to the buckets a text lands in;
* pad slots are inert, whatever ids they hold;
* ``DeviceBatcher._prepared_rows`` joins submit-time rows into the batch a
  group-level tokenize would have produced, byte for byte;
* the ``padded`` counters of ``utilization()`` per dispatch kind;
* ``models/embedder._bucket``, the row ladder;
* the batcher has one path: no packing switch is read anywhere.
"""

import asyncio
import dataclasses

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp

from llm_weighted_consensus_tpu.models import bert
from llm_weighted_consensus_tpu.models.configs import TEST_TINY
from llm_weighted_consensus_tpu.models.embedder import (
    TpuEmbedder,
    _bucket,
    _seq_bucket,
)
from llm_weighted_consensus_tpu.serve.batcher import DeviceBatcher, _Item
from llm_weighted_consensus_tpu.serve.config import Config
from llm_weighted_consensus_tpu.serve.metrics import Metrics


def go(coro):
    return asyncio.new_event_loop().run_until_complete(coro)


def words(n, salt=0):
    """A text of n words, each one token of the hash tokenizer."""
    return " ".join(f"w{salt}x{i}" for i in range(n))


# -- (a) the forward does not depend on the buckets ---------------------------

# (rows in the wide batch, words of its longest text): the text under test
# sits alone at (16 rows, 16 slots) and then inside these
BUCKET_PAIRS = {
    "rows32-seq32": (17, 25),
    "rows64-seq64": (33, 60),
}


@pytest.mark.parametrize("pair", list(BUCKET_PAIRS))
@pytest.mark.parametrize("pooling", ["cls", "mean"])
@pytest.mark.parametrize("quantize", ["none", "int8-xla", "int8-pallas"])
def test_embedding_is_invariant_to_its_buckets(quantize, pooling, pair):
    """A text alone at its own sequence bucket and inside a batch padded to
    a wider sequence bucket AND a larger row bucket embeds the same: a
    mixed-length group may pad every member to its longest."""
    rows, longest = BUCKET_PAIRS[pair]
    emb = TpuEmbedder(
        "test-tiny", config=TEST_TINY, max_tokens=64, quantize=quantize,
        pooling=pooling, seed=3,
    )
    text = "weighted consensus on tensor units"
    ids1, mask1 = emb.tokenize([text])
    fillers = [words(3 + i % 5, salt=i) for i in range(rows - 2)]
    idsw, maskw = emb.tokenize([text] + fillers + [words(longest)])
    cap = emb.MAX_DEVICE_BATCH
    assert ids1.shape == (1, 16) and _bucket(1, cap) == 16
    wide_s = _seq_bucket(longest + 2, 64)
    assert idsw.shape == (rows, wide_s) and wide_s > 16
    assert _bucket(rows, cap) > 16
    alone = np.asarray(emb.embed_tokens(ids1, mask1))[0]
    inside = np.asarray(emb.embed_tokens(idsw, maskw))[0]
    np.testing.assert_allclose(alone, inside, atol=1e-6)


# -- (b) pad slots are inert --------------------------------------------------


@pytest.mark.parametrize("impl", ["einsum", "fused"])
def test_ids_under_mask_zero_change_no_real_row(impl):
    """Other ids in the pad slots leave every real position's hidden state
    and both poolings as they were: bit for bit through the einsum path (a
    masked key's probability is exactly 0), within the kernel's tolerance
    through the fused one (interpreted here)."""
    cfg = dataclasses.replace(TEST_TINY, attention_impl=impl)
    params = bert.init_params(jax.random.PRNGKey(5), cfg)
    rng = np.random.default_rng(5)
    b, s = 4, 32
    lens = np.array([32, 20, 9, 1])
    mask = (np.arange(s)[None, :] < lens[:, None]).astype(np.int32)
    ids = rng.integers(3, cfg.vocab_size, (b, s)).astype(np.int32)
    zeros = np.where(mask > 0, ids, 0)
    other = np.where(
        mask > 0, ids, rng.integers(3, cfg.vocab_size, (b, s))
    ).astype(np.int32)
    assert (zeros != other).any()
    real = mask[:, :, None] > 0

    def run(tokens):
        hidden = bert.encode(params, jnp.asarray(tokens), jnp.asarray(mask), cfg)
        return (
            np.where(real, np.asarray(hidden), 0.0),
            np.asarray(bert.pool(hidden, jnp.asarray(mask), "cls")),
            np.asarray(bert.pool(hidden, jnp.asarray(mask), "mean")),
        )

    for got, want in zip(run(other), run(zeros)):
        if impl == "einsum":
            np.testing.assert_array_equal(got, want)
        else:
            np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


# -- (c) submit-time rows join into the group's batch -------------------------


@pytest.fixture(scope="module")
def wide_tokenizer_embedder():
    # tokenized only, never run: a position table wide enough for the
    # 128 bucket
    cfg = dataclasses.replace(TEST_TINY, max_position_embeddings=160)
    return TpuEmbedder("test-tiny", config=cfg, max_tokens=128, seed=1)


@pytest.mark.parametrize(
    "buckets",
    [(16, 64), (64, 64), (16, 128, 32), (48,)],
    ids=["16+64", "64+64", "16+128+32", "alone"],
)
def test_prepared_rows_join_byte_identically(wide_tokenizer_embedder, buckets):
    """Items tokenized one by one at submit time, each at its own sequence
    bucket, give the (ids, mask) that tokenizing the group at once gives:
    the pad id and mask 0 in the gap."""
    emb = wide_tokenizer_embedder
    batcher = DeviceBatcher(emb, None, host_tokenizer_workers=1)
    group = []
    for i, bucket in enumerate(buckets):
        # the longest text fills its bucket but for [CLS] and [SEP]
        texts = [words(bucket - 2, salt=i), words(2, salt=10 + i)]
        assert emb.tokenize(texts)[0].shape == (2, bucket)
        item = _Item("embed", ("embed", None), (texts, None), None)
        item.prepared = batcher._tok_pool.submit(batcher._prepare_item, item)
        group.append(item)
    got_ids, got_mask = batcher._prepared_rows(group, emb)
    want_ids, want_mask = emb.tokenize(
        [t for item in group for t in item.payload[0]]
    )
    assert got_ids.shape == (2 * len(buckets), max(buckets))
    assert got_ids.dtype == want_ids.dtype and got_mask.dtype == want_mask.dtype
    assert got_ids.tobytes() == want_ids.tobytes()
    assert got_mask.tobytes() == want_mask.tobytes()
    gap = got_mask == 0
    assert (got_ids[gap] == emb.tokenizer.pad_id).all()
    assert all(item.rows_ready is not None for item in group)
    batcher.close()


# -- (d) the counters behind packing.padding_share ----------------------------


@pytest.fixture(scope="module")
def embedder():
    return TpuEmbedder("test-tiny", config=TEST_TINY, max_tokens=32, seed=1)


def padded(batcher):
    counters = batcher.utilization()["padded"]
    return counters["real_tokens"], counters["slot_tokens"]


CANDIDATES = [f"candidate {i % 3} says so" for i in range(6)]


def test_padded_counters_of_a_solo_consensus(embedder):
    """One request alone: n rows of the sequence bucket."""
    batcher = DeviceBatcher(embedder, Metrics(), window_ms=1.0)
    go(batcher.consensus(CANDIDATES))
    ids, mask = embedder.tokenize(CANDIDATES)
    assert padded(batcher) == (int(mask.sum()), 6 * ids.shape[1])
    waste = batcher.utilization()["padded"]["padding_waste"]
    assert waste == round(1.0 - mask.sum() / ids.size, 4)


def test_padded_counters_of_a_group_of_three(embedder):
    """Three same-shape requests in one dispatch: the request dimension is
    padded to its power of two, 4 x n x s slots."""
    metrics = Metrics()
    batcher = DeviceBatcher(embedder, metrics, window_ms=30.0)
    requests = [CANDIDATES, CANDIDATES[::-1], CANDIDATES[1:] + CANDIDATES[:1]]

    async def run():
        return await asyncio.gather(*(batcher.consensus(r) for r in requests))

    go(run())
    assert metrics.snapshot()["series"]["device:batch:consensus"]["count"] == 1
    ids, mask = embedder.tokenize([t for r in requests for t in r])
    assert padded(batcher) == (int(mask.sum()), 4 * 6 * ids.shape[1])


def test_padded_counters_of_an_embed_of_five_rows(embedder):
    """An embed pads its rows to the row bucket (16 at the least)."""
    batcher = DeviceBatcher(embedder, Metrics(), window_ms=1.0)
    texts = [words(2 + i, salt=i) for i in range(5)]
    go(batcher.embed(texts))
    ids, mask = embedder.tokenize(texts)
    assert padded(batcher) == (int(mask.sum()), 16 * ids.shape[1])


# -- (e) the row ladder -------------------------------------------------------


@pytest.mark.parametrize(
    "n, cap, want",
    [
        (1, 4096, 16),  # the floor
        (16, 4096, 16),  # at a rung
        (17, 4096, 32),  # over it
        (32, 4096, 32),
        (33, 4096, 64),
        (64, 4096, 64),  # the benchmark's request
        (65, 4096, 128),
        (4095, 4096, 4096),  # under the cap
        (4096, 4096, 4096),  # at it
        (5000, 4096, 4096),  # over it: the cap, the caller chunks
        (100, 64, 64),  # a cap under the rung
        (10, 8, 8),  # a cap under the floor
    ],
)
def test_row_bucket_ladder(n, cap, want):
    assert _bucket(n, cap) == want
    assert _bucket(want, cap) == want  # a bucket is its own bucket


# -- (f) the batcher has one path ---------------------------------------------


def test_packing_names_in_the_environment_change_no_config():
    base = {"EMBEDDER_MODEL": "test-tiny", "EMBEDDER_MAX_TOKENS": "32"}
    gone = {
        "PACKING_ENABLED": "1",
        "PACKING_ROW_TOKENS": "64",
        "PACKING_MAX_ROWS": "2",
        "PACKING_MAX_SEGMENTS": "4",
        "PREFIX_DEDUP": "0",
        "PREFIX_DEDUP_MIN_CHARS": "8",
    }
    assert Config.from_env({**base, **gone}) == Config.from_env(base)
    names = {f.name for f in dataclasses.fields(Config)}
    assert not [n for n in names if "packing" in n or "prefix_dedup" in n]


def test_batcher_takes_no_packing_argument(embedder):
    with pytest.raises(TypeError, match="packing"):
        DeviceBatcher(embedder, None, **{"packing": True})
    with pytest.raises(TypeError, match="prefix_dedup"):
        DeviceBatcher(embedder, None, **{"prefix_dedup": False})


def test_utilization_has_padded_and_no_packing_section(embedder):
    util = DeviceBatcher(embedder, Metrics()).utilization()
    assert set(util["padded"]) == {
        "real_tokens", "slot_tokens", "padding_waste",
    }
    assert util["padded"]["padding_waste"] == 0.0  # nothing dispatched yet
    assert "packing" not in util


# -- (g) kinds never share a dispatch -----------------------------------------


def test_embed_and_consensus_in_one_window_are_two_dispatches(embedder):
    """An embed and a consensus item that arrive together leave as one
    dispatch each, and each gets what its direct call returns."""
    metrics = Metrics()
    batcher = DeviceBatcher(embedder, metrics, window_ms=30.0)
    texts = [words(3, salt=1), words(9, salt=2)]

    async def run():
        return await asyncio.gather(
            batcher.embed(texts), batcher.consensus(CANDIDATES, 0.07)
        )

    (vecs, tokens), (conf, conf_tokens) = go(run())
    series = metrics.snapshot()["series"]
    assert series["device:batch:embed"]["count"] == 1
    assert series["device:batch:consensus"]["count"] == 1
    util = metrics.snapshot()["device_batcher"]
    assert util["dispatches"] == 2 and util["items"] == 2
    np.testing.assert_allclose(vecs, embedder.embed_texts(texts), atol=1e-6)
    assert tokens == embedder.token_count(texts)
    np.testing.assert_allclose(
        conf,
        np.asarray(embedder.consensus_confidence(CANDIDATES, temperature=0.07)),
        atol=1e-6,
    )
    assert conf_tokens == embedder.token_count(CANDIDATES)
