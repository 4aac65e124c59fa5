"""Multichat fan-out client: slot semantics, dedup identity, error
isolation, unary fold, streaming incremental consensus (SURVEY §2.10)."""

import asyncio
from decimal import Decimal

import pytest

from llm_weighted_consensus_tpu import registry
from llm_weighted_consensus_tpu.clients.chat import (
    ApiBase,
    BackoffPolicy,
    DefaultChatClient,
)
from llm_weighted_consensus_tpu.clients.multichat import (
    MultichatClient,
    generator_slots,
)
from llm_weighted_consensus_tpu.identity.model import ModelBase
from llm_weighted_consensus_tpu.types.multichat_request import (
    ChatCompletionCreateParams as MultichatParams,
)
from llm_weighted_consensus_tpu.types.multichat_response import ChatCompletion

from fakes import FakeTransport, Script, chunk_obj

NO_RETRY = BackoffPolicy(max_elapsed_ms=0)


def go(coro):
    return asyncio.new_event_loop().run_until_complete(coro)


def make_model(judges):
    return ModelBase.from_json_obj({"llms": judges}).into_model_validate()


def inline(model):
    return {"llms": [llm.base.to_json_obj() for llm in model.llms]}


def make_client(scripts):
    transport = FakeTransport(scripts)
    chat = DefaultChatClient(
        transport, [ApiBase("https://up.example", "k")], backoff=NO_RETRY
    )
    return MultichatClient(chat, registry.InMemoryModelRegistry()), transport


def params(model, **kw):
    return MultichatParams.from_json_obj(
        {
            "messages": [{"role": "user", "content": "answer the question"}],
            "model": inline(model),
            **kw,
        }
    )


def test_generator_slots_dedup_and_duplicates():
    # two judges = same generator (weight/output_mode reset), one distinct
    model = make_model(
        [
            {"model": "gen-a", "weight": {"type": "static", "weight": 2}},
            {"model": "gen-a", "weight": {"type": "static", "weight": 5}},
            {"model": "gen-b"},
        ]
    )
    slots = generator_slots(model)
    assert [s for s, _ in slots] == [0, 1, 2]
    ids = [llm.multichat_id for _, llm in slots]
    assert len(set(ids)) == 2  # two distinct generators across three slots
    # duplicates share identity and occupy consecutive slots
    dup_slots = [s for (s, llm) in slots if ids.count(llm.multichat_id) == 2]
    assert dup_slots[1] == dup_slots[0] + 1


def test_fanout_unary_fold_and_identity():
    model = make_model([{"model": "gen-a"}, {"model": "gen-b"}])
    order = [llm.base.model for _, llm in generator_slots(model)]
    by_model = {
        "gen-a": Script([chunk_obj("alpha ", model="gen-a"), chunk_obj("answer", model="gen-a", finish="stop")]),
        "gen-b": Script([chunk_obj("beta answer", model="gen-b", finish="stop")]),
    }
    client, t = make_client([by_model[m] for m in order])
    result = go(client.create_unary(None, params(model)))
    assert isinstance(result, ChatCompletion)
    assert len(result.choices) == 2
    by_slot = {c.index: c for c in result.choices}
    texts = {by_slot[0].message.content, by_slot[1].message.content}
    assert texts == {"alpha answer", "beta answer"}
    for c in result.choices:
        assert c.model is not None and len(c.model) == 22  # multichat_id
        assert c.completion_metadata is not None
    assert result.id.startswith("mchcpl-")


def test_slot_error_isolation():
    model = make_model([{"model": "gen-a"}, {"model": "gen-b"}])
    order = [llm.base.model for _, llm in generator_slots(model)]
    by_model = {
        "gen-a": Script(status=500, body=b'{"err": 1}'),
        "gen-b": Script([chunk_obj("ok", model="gen-b", finish="stop")]),
    }
    client, _ = make_client([by_model[m] for m in order])
    result = go(client.create_unary(None, params(model)))
    by_err = {c.index: c.error for c in result.choices}
    errors = [e for e in by_err.values() if e is not None]
    assert len(errors) == 1
    assert errors[0].code == 500
    ok = [c for c in result.choices if c.error is None][0]
    assert ok.message.content == "ok"


def test_seed_offset_per_slot():
    # identical generators: seeds offset so samples differ
    model = make_model([{"model": "gen-a"}, {"model": "gen-a"}])
    client, t = make_client(
        [
            Script([chunk_obj("s0", finish="stop")]),
            Script([chunk_obj("s1", finish="stop")]),
        ]
    )
    go(client.create_unary(None, params(model, seed=100)))
    seeds = sorted(b["seed"] for _, _, b in t.requests)
    assert seeds == [100, 101]


def test_multichat_as_score_candidates():
    """config 2 shape: multichat generates candidates, score judges them."""
    from llm_weighted_consensus_tpu import archive
    from llm_weighted_consensus_tpu.clients.score import ScoreClient
    from llm_weighted_consensus_tpu.types.score_request import (
        ChatCompletionCreateParams as ScoreParams,
    )
    import random

    gen_model = make_model([{"model": "gen-a"}, {"model": "gen-b"}])
    order = [llm.base.model for _, llm in generator_slots(gen_model)]
    by_model = {
        "gen-a": Script([chunk_obj("it is 42", model="gen-a", finish="stop")]),
        "gen-b": Script([chunk_obj("it is 41", model="gen-b", finish="stop")]),
    }
    client, _ = make_client([by_model[m] for m in order])
    mc = go(client.create_unary(None, params(gen_model)))

    store = archive.InMemoryArchive()
    store.put_multichat(mc)

    # score the archived multichat candidates
    from llm_weighted_consensus_tpu.ballot import PrefixTree, branch_limit

    SEED = 7
    rng = random.Random(SEED)
    tree = PrefixTree.build(rng, 2, 20)
    keys = {idx: k for k, idx in tree.key_indices(rng)}
    # find which slot said 42
    slot42 = next(c.index for c in mc.choices if "42" in c.message.content)

    judge_model = make_model([{"model": "judge-x"}])
    transport = FakeTransport(
        [Script([chunk_obj(f"the answer {keys[slot42]}", finish="stop")])]
    )
    chat = DefaultChatClient(
        transport, [ApiBase("https://up.example", "k")], backoff=NO_RETRY
    )
    score = ScoreClient(
        chat,
        registry.InMemoryModelRegistry(),
        archive_fetcher=store,
        rng_factory=lambda: random.Random(SEED),
    )
    sp = ScoreParams.from_json_obj(
        {
            "messages": [{"role": "user", "content": "which?"}],
            "model": {"llms": [llm.base.to_json_obj() for llm in judge_model.llms]},
            "choices": [
                {"type": "multichat_completion", "id": mc.id, "choice_index": 0},
                {"type": "multichat_completion", "id": mc.id, "choice_index": 1},
            ],
        }
    )
    result = go(score.create_unary(None, sp))
    cand = {c.index: c for c in result.choices if c.index < 2}
    assert cand[slot42].confidence == Decimal(1)
    # provenance: candidate carries the multichat generator id
    assert cand[0].model is not None


def test_streaming_self_consistency_incremental():
    jax = pytest.importorskip("jax")
    from llm_weighted_consensus_tpu.clients.multichat import (
        StreamingSelfConsistency,
    )
    from llm_weighted_consensus_tpu.models.configs import TEST_TINY
    from llm_weighted_consensus_tpu.models.embedder import TpuEmbedder
    from llm_weighted_consensus_tpu.types.multichat_response import (
        ChatCompletionChunk,
    )

    emb = TpuEmbedder("test-tiny", config=TEST_TINY, max_tokens=32, seed=3)
    sc = StreamingSelfConsistency(emb)

    def chunk(slot, content=None, finish=None):
        return ChatCompletionChunk.from_json_obj(
            {
                "id": "mc",
                "object": "chat.completion.chunk",
                "created": 1,
                "model": "m",
                "choices": [
                    {
                        "index": slot,
                        "delta": {"content": content} if content else {},
                        "finish_reason": finish,
                    }
                ],
            }
        )

    assert sc.push_chunk(chunk(0, "the answer is 42")) is None
    assert sc.push_chunk(chunk(0, finish="stop")) is None  # only 1 finished
    sc.push_chunk(chunk(1, "the answer is 42"))
    conf2 = sc.push_chunk(chunk(1, finish="stop"))
    assert conf2 is not None and set(conf2) == {0, 1}
    sc.push_chunk(chunk(2, "bananas bananas bananas"))
    conf3 = sc.push_chunk(chunk(2, finish="stop"))
    assert set(conf3) == {0, 1, 2}
    assert sum(conf3.values()) == pytest.approx(1.0, abs=1e-5)
    # the two agreeing candidates outrank the outlier
    assert conf3[0] > conf3[2] and conf3[1] > conf3[2]
    # errored slots never enter the consensus
    err = ChatCompletionChunk.from_json_obj(
        {
            "id": "mc",
            "object": "chat.completion.chunk",
            "created": 1,
            "model": "m",
            "choices": [
                {
                    "index": 3,
                    "delta": {},
                    "finish_reason": "error",
                    "error": {"code": 500, "message": "boom"},
                }
            ],
        }
    )
    assert sc.push_chunk(err) is None
    assert 3 not in sc.confidence and 3 in sc.failed


def test_streaming_consensus_grows_past_initial_capacity():
    """More candidates than the initial device-buffer capacity: the buffer
    doubles and the final distribution matches the one-shot vote."""
    import numpy as np

    pytest.importorskip("jax")
    from llm_weighted_consensus_tpu.clients.multichat import (
        StreamingSelfConsistency,
    )
    from llm_weighted_consensus_tpu.models.configs import TEST_TINY
    from llm_weighted_consensus_tpu.models.embedder import TpuEmbedder
    from llm_weighted_consensus_tpu.types.multichat_response import (
        ChatCompletionChunk,
    )

    def make_chunk(slot, content, finish):
        return ChatCompletionChunk.from_json_obj(
            {
                "id": "mc",
                "object": "chat.completion.chunk",
                "created": 1,
                "model": "m",
                "choices": [
                    {
                        "index": slot,
                        "delta": {"content": content},
                        "finish_reason": finish,
                    }
                ],
            }
        )

    emb = TpuEmbedder("test-tiny", config=TEST_TINY, max_tokens=32, seed=3)
    sc = StreamingSelfConsistency(emb)
    sc.INITIAL_CAPACITY = 4
    n = 10
    texts = [f"candidate answer number {i % 3}" for i in range(n)]
    last = None
    for i, text in enumerate(texts):
        out = sc.push_chunk(make_chunk(i, text, finish="stop"))
        if out is not None:
            last = out
    assert last is not None and len(last) == n
    assert sum(last.values()) == pytest.approx(1.0, abs=1e-4)
    one_shot = np.asarray(emb.consensus_confidence(texts))
    np.testing.assert_allclose(
        [last[i] for i in range(n)], one_shot, atol=1e-4
    )


def test_streaming_consensus_failed_embed_leaves_no_phantom():
    """A raising embedder must not commit a phantom slot: the candidate
    retries on the next finish signal and the distribution stays honest."""
    pytest.importorskip("jax")
    from llm_weighted_consensus_tpu.clients.multichat import (
        StreamingSelfConsistency,
    )
    from llm_weighted_consensus_tpu.models.configs import TEST_TINY
    from llm_weighted_consensus_tpu.models.embedder import TpuEmbedder
    from llm_weighted_consensus_tpu.types.multichat_response import (
        ChatCompletionChunk,
    )

    def make_chunk(slot, content, finish):
        return ChatCompletionChunk.from_json_obj(
            {
                "id": "mc",
                "object": "chat.completion.chunk",
                "created": 1,
                "model": "m",
                "choices": [
                    {
                        "index": slot,
                        "delta": {"content": content},
                        "finish_reason": finish,
                    }
                ],
            }
        )

    emb = TpuEmbedder("test-tiny", config=TEST_TINY, max_tokens=32, seed=3)
    sc = StreamingSelfConsistency(emb)
    real = emb.stream_vote_update
    fail = {"next": True}

    def flaky(*args, **kwargs):
        if fail["next"]:
            fail["next"] = False
            raise RuntimeError("transient device OOM")
        return real(*args, **kwargs)

    emb.stream_vote_update = flaky
    with pytest.raises(RuntimeError):
        sc.push_chunk(make_chunk(0, "the answer", finish="stop"))
    assert sc.count == 0  # no phantom
    # the same slot retries (finish signal arrives again) and succeeds
    sc.push_chunk(make_chunk(0, "the answer", finish="stop"))
    conf = sc.push_chunk(make_chunk(1, "the answer", finish="stop"))
    assert set(conf) == {0, 1}
    assert sum(conf.values()) == pytest.approx(1.0, abs=1e-5)
    assert all(v > 0 for v in conf.values())
