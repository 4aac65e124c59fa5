"""Gateway: SSE frames + [DONE], unary JSON, error bodies, env config
(main.rs:142-232 parity), /multichat and /embeddings extensions."""

import asyncio
import json
import random

import pytest
from aiohttp.test_utils import TestClient, TestServer

from llm_weighted_consensus_tpu import archive, registry
from llm_weighted_consensus_tpu.ballot import PrefixTree
from llm_weighted_consensus_tpu.clients.chat import (
    ApiBase,
    BackoffPolicy,
    DefaultChatClient,
)
from llm_weighted_consensus_tpu.clients.multichat import MultichatClient
from llm_weighted_consensus_tpu.clients.score import ScoreClient
from llm_weighted_consensus_tpu.identity.model import ModelBase
from llm_weighted_consensus_tpu.serve import Config, build_app

from fakes import FakeTransport, Script, chunk_obj

SEED = 11
NO_RETRY = BackoffPolicy(max_elapsed_ms=0)


def go(coro):
    return asyncio.new_event_loop().run_until_complete(coro)


def make_app(scripts, embedder=None):
    transport = FakeTransport(scripts)
    chat = DefaultChatClient(
        transport, [ApiBase("https://up.example", "k")], backoff=NO_RETRY
    )
    reg = registry.InMemoryModelRegistry()
    store = archive.InMemoryArchive()
    score = ScoreClient(
        chat, reg, archive_fetcher=store,
        rng_factory=lambda: random.Random(SEED),
    )
    multichat = MultichatClient(chat, reg, archive_fetcher=store)
    return build_app(chat, score, multichat, embedder), transport


def ballot_keys(n):
    rng = random.Random(SEED)
    tree = PrefixTree.build(rng, n, 20)
    return {idx: k for k, idx in tree.key_indices(rng)}


def inline_model(judges):
    model = ModelBase.from_json_obj({"llms": judges}).into_model_validate()
    return {"llms": [llm.base.to_json_obj() for llm in model.llms]}


def post_json(client, path, obj):
    # jsonutil handles Decimal weights; stdlib json cannot
    from llm_weighted_consensus_tpu.utils import jsonutil

    return client.post(
        path,
        data=jsonutil.dumps(obj),
        headers={"content-type": "application/json"},
    )


async def with_client(app, fn):
    client = TestClient(TestServer(app))
    await client.start_server()
    try:
        return await fn(client)
    finally:
        await client.close()


def sse_events(text):
    events = []
    for block in text.split("\n\n"):
        if block.startswith("data: "):
            events.append(block[len("data: "):])
    return events


# -- /chat/completions --------------------------------------------------------


def test_chat_unary_json():
    app, _ = make_app([Script([chunk_obj("hi there", finish="stop")])])

    async def run(client):
        resp = await client.post(
            "/chat/completions",
            json={"model": "m", "messages": [{"role": "user", "content": "q"}]},
        )
        assert resp.status == 200
        body = await resp.json()
        assert body["object"] == "chat.completion"
        assert body["choices"][0]["message"]["content"] == "hi there"

    go(with_client(app, run))


def test_chat_streaming_sse_with_done():
    app, _ = make_app([Script([chunk_obj("a"), chunk_obj("b", finish="stop")])])

    async def run(client):
        resp = await client.post(
            "/chat/completions",
            json={
                "model": "m",
                "stream": True,
                "messages": [{"role": "user", "content": "q"}],
            },
        )
        assert resp.status == 200
        assert resp.headers["content-type"].startswith("text/event-stream")
        events = sse_events(await resp.text())
        assert events[-1] == "[DONE]"
        chunks = [json.loads(e) for e in events[:-1]]
        assert chunks[0]["object"] == "chat.completion.chunk"
        contents = [
            c["choices"][0]["delta"].get("content")
            for c in chunks
            if c["choices"]
        ]
        assert "a" in contents and "b" in contents

    go(with_client(app, run))


def test_chat_upstream_failure_maps_status():
    app, _ = make_app([Script(status=503, body=b'{"busy": 1}')])

    async def run(client):
        resp = await client.post(
            "/chat/completions",
            json={"model": "m", "messages": [{"role": "user", "content": "q"}]},
        )
        assert resp.status == 503
        body = await resp.json()
        assert body["kind"] == "chat"

    go(with_client(app, run))


def test_malformed_body_is_400():
    app, _ = make_app([])

    async def run(client):
        resp = await client.post("/chat/completions", json={"model": "m"})
        assert resp.status == 400
        body = await resp.json()
        assert body["code"] == 400
        assert "messages" in str(body["message"])

    go(with_client(app, run))


# -- /score/completions -------------------------------------------------------


def test_score_streaming_protocol_over_http():
    keys = ballot_keys(2)
    app, _ = make_app(
        [Script([chunk_obj(f"pick {keys[1]}", finish="stop")])]
    )

    async def run(client):
        resp = await post_json(
            client,
            "/score/completions",
            {
                "stream": True,
                "messages": [{"role": "user", "content": "q"}],
                "model": inline_model([{"model": "j1"}]),
                "choices": ["first", "second"],
            },
        )
        assert resp.status == 200
        events = sse_events(await resp.text())
        assert events[-1] == "[DONE]"
        chunks = [json.loads(e) for e in events[:-1]]
        # initial chunk: both candidates finished
        assert [c["index"] for c in chunks[0]["choices"]] == [0, 1]
        # final frame carries weight/confidence
        final = chunks[-1]
        cand = {c["index"]: c for c in final["choices"] if c["index"] < 2}
        assert cand[1]["confidence"] == 1  # bare JSON number (Decimal exact)
        assert final["usage"] is not None

    go(with_client(app, run))


def test_score_unary_and_expected_two_choices():
    app, _ = make_app([])

    async def run(client):
        resp = await post_json(
            client,
            "/score/completions",
            {
                "messages": [{"role": "user", "content": "q"}],
                "model": inline_model([{"model": "j1"}]),
                "choices": ["only"],
            },
        )
        assert resp.status == 400
        body = await resp.json()
        assert body["error"]["kind"] == "expected_two_or_more_choices"

    go(with_client(app, run))


def test_score_all_failed_error_frame_in_stream():
    app, _ = make_app([Script(status=418, body=b"{}")])

    async def run(client):
        resp = await post_json(
            client,
            "/score/completions",
            {
                "stream": True,
                "messages": [{"role": "user", "content": "q"}],
                "model": inline_model([{"model": "j1"}]),
                "choices": ["a", "b"],
            },
        )
        events = sse_events(await resp.text())
        assert events[-1] == "[DONE]"
        error_frame = json.loads(events[-2])
        assert error_frame["code"] == 418
        assert error_frame["message"]["error"]["kind"] == "all_votes_failed"

    go(with_client(app, run))


# -- /multichat/completions ---------------------------------------------------


def test_multichat_endpoint():
    app, _ = make_app(
        [
            Script([chunk_obj("answer one", model="g1", finish="stop")]),
            Script([chunk_obj("answer two", model="g2", finish="stop")]),
        ]
    )

    async def run(client):
        resp = await post_json(
            client,
            "/multichat/completions",
            {
                "messages": [{"role": "user", "content": "q"}],
                "model": inline_model([{"model": "g1"}, {"model": "g2"}]),
            },
        )
        assert resp.status == 200
        body = await resp.json()
        texts = {c["message"]["content"] for c in body["choices"]}
        assert texts == {"answer one", "answer two"}
        assert {c["index"] for c in body["choices"]} == {0, 1}

    go(with_client(app, run))


# -- /embeddings --------------------------------------------------------------


def test_embeddings_endpoint():
    pytest.importorskip("jax")
    from llm_weighted_consensus_tpu.models.configs import TEST_TINY
    from llm_weighted_consensus_tpu.models.embedder import TpuEmbedder

    embedder = TpuEmbedder("test-tiny", config=TEST_TINY, max_tokens=32)
    app, _ = make_app([], embedder=embedder)

    async def run(client):
        resp = await client.post(
            "/embeddings",
            json={"model": "test-tiny", "input": ["hello", "world"]},
        )
        assert resp.status == 200
        body = await resp.json()
        assert body["object"] == "list"
        assert len(body["data"]) == 2
        assert len(body["data"][0]["embedding"]) == TEST_TINY.hidden_size
        assert body["usage"]["total_tokens"] > 0

    go(with_client(app, run))


def test_healthz():
    app, _ = make_app([])

    async def run(client):
        resp = await client.get("/healthz")
        assert (await resp.json()) == {"ok": True}

    go(with_client(app, run))


# -- config -------------------------------------------------------------------


def test_config_env_parity():
    env = {
        "OPENAI_APIS": '[{"api_base": "https://a", "api_key": "k1"}, {"api_base": "https://b", "api_key": "k2"}]',
        "BACKOFF_MULTIPLIER": "2.5",
        "FIRST_CHUNK_TIMEOUT_MILLIS": "1234",
        "PORT": "8080",
        "EMBEDDER_MODEL": "bge-small-en",
        "MESH_ENABLED": "1",
        "MESH_SHAPE": "4x1",
    }
    c = Config.from_env(env)
    assert [a.api_base for a in c.api_bases()] == ["https://a", "https://b"]
    assert c.backoff_policy().multiplier == 2.5
    assert c.first_chunk_timeout_millis == 1234
    assert c.port == 8080
    assert c.embedder_model == "bge-small-en"
    assert c.mesh_enabled and c.mesh_shape == (4, 1)
    # defaults (main.rs:5-20)
    assert c.backoff_policy().initial_interval_ms == 100
    assert c.other_chunk_timeout_millis == 60000


def test_config_warmup_parsing():
    c = Config.from_env({"WARMUP": "64x112, 64x128"})
    assert c.warmup == [(64, 112), (64, 128)]
    assert Config.from_env({}).warmup == []
    assert Config.from_env({"WARMUP": ""}).warmup == []
    import pytest as _pytest

    for bad in (
        "64x", "x128", "1x16", "64x0", "64x112x3", "sixtyfour",
        "640x112",  # above the /consensus candidate ceiling: unreachable
    ):
        with _pytest.raises(ValueError):
            Config.from_env({"WARMUP": bad})


def test_warmup_compiles_configured_shapes():
    """WARMUP specs run the consensus path at startup (pre-compile); the
    warmed embedder then serves those shapes without further tracing."""
    pytest.importorskip("jax")
    from llm_weighted_consensus_tpu.serve.__main__ import _warmup_embedder

    embedder = _tiny_embedder()
    calls = []
    real = embedder.consensus_confidence_tokens
    embedder.consensus_confidence_tokens = lambda ids, mask, *a: (
        calls.append((ids.shape, mask.shape)) or real(ids, mask, *a)
    )
    # aot=False pins the dispatch-loop warmup (the WARMUP_AOT=0 /
    # mesh-sharded route); the AOT default is pinned in tests/test_aot.py
    _warmup_embedder(embedder, [(4, 16), (6, 30), (6, 32)], aot=False)
    # S snaps to the serving seq bucket (30 -> 32); specs that collapse
    # to the same compiled shape dedup (6x30 == 6x32 -> one dispatch)
    assert calls == [((4, 16), (4, 16)), ((6, 32), (6, 32))]


def test_config_warmup_r_parsing():
    c = Config.from_env({"WARMUP": "64x112", "WARMUP_R": "2, 3, 4"})
    assert c.warmup_r == [2, 4]  # 3 snaps to the pow2 bucket 4, dedups
    assert Config.from_env({}).warmup_r == []
    assert Config.from_env({"WARMUP_R": ""}).warmup_r == []
    import pytest as _pytest

    for bad in ("0", "-2", "two", "2x3"):
        with _pytest.raises(ValueError):
            Config.from_env({"WARMUP": "64x112", "WARMUP_R": bad})


def test_warmup_r_compiles_grouped_path():
    """WARMUP_R warms the batcher's grouped dispatch per shape — a
    distinct specialization per R bucket the single-request warm does
    not cover (ADVICE r4) — and the warmed grouped output still sums to
    one per request slot."""
    pytest.importorskip("jax")
    import numpy as np

    from llm_weighted_consensus_tpu.serve.__main__ import _warmup_embedder

    embedder = _tiny_embedder()
    many_calls = []
    real_many = embedder.consensus_confidence_tokens_many
    embedder.consensus_confidence_tokens_many = lambda ids, mask, *a: (
        many_calls.append(ids.shape) or real_many(ids, mask, *a)
    )
    # aot=False: the grouped DISPATCH warm (AOT grouped buckets are
    # pinned in tests/test_aot.py)
    _warmup_embedder(embedder, [(4, 16)], r_buckets=[1, 2], aot=False)
    # R=1 rides the single-request path (already warmed); only R=2 hits
    # the grouped dispatch
    assert many_calls == [(2, 4, 16)]
    conf = np.asarray(real_many(np.zeros((2, 4, 16), np.int32),
                                np.eye(1, 16, dtype=np.int32)[None]
                                .repeat(4, 0)[None].repeat(2, 0)
                                .reshape(2, 4, 16)))
    np.testing.assert_allclose(conf.sum(axis=1), 1.0, atol=1e-4)


def test_config_single_api_base_fallback():
    c = Config.from_env({"OPENAI_API_BASE": "https://x", "OPENAI_API_KEY": "s"})
    assert [a.api_key for a in c.api_bases()] == ["s"]
    assert Config.from_env({}).openai_apis == []


# -- streaming consensus frames + /metrics ------------------------------------


def _multichat_body(n_gens, consensus=True):
    return {
        "stream": True,
        "consensus": consensus,
        "messages": [{"role": "user", "content": "q"}],
        "model": inline_model([{"model": f"gen-{i}"} for i in range(n_gens)]),
    }


def test_multichat_streaming_consensus_frames():
    from llm_weighted_consensus_tpu.models.embedder import TpuEmbedder

    embedder = TpuEmbedder("test-tiny")
    scripts = [
        Script([chunk_obj(f"the answer is {i % 2}", finish="stop")])
        for i in range(3)
    ]
    app, _ = make_app(scripts, embedder=embedder)

    async def run(client):
        resp = await post_json(
            client, "/multichat/completions", _multichat_body(3)
        )
        assert resp.status == 200
        events = sse_events(await resp.text())
        assert events[-1] == "[DONE]"
        frames = [json.loads(e) for e in events[:-1]]
        consensus = [
            f for f in frames if f.get("object") == "multichat.consensus"
        ]
        # 3 generators finish -> updates at the 2nd and 3rd completion
        assert len(consensus) == 2
        final = consensus[-1]["confidence"]
        assert set(final) == {"0", "1", "2"}
        assert abs(sum(final.values()) - 1.0) < 1e-5
        # the metrics endpoint saw the requests and the device updates
        m = await (await client.get("/metrics")).json()
        series = m["series"]
        assert series["http:/multichat/completions"]["count"] == 1
        assert series["device:consensus_update"]["count"] == 2
        assert "p50_ms" in series["http:/multichat/completions"]

    go(with_client(app, run))


def test_multichat_no_consensus_without_flag():
    from llm_weighted_consensus_tpu.models.embedder import TpuEmbedder

    embedder = TpuEmbedder("test-tiny")
    scripts = [
        Script([chunk_obj("a", finish="stop")]),
        Script([chunk_obj("b", finish="stop")]),
    ]
    app, _ = make_app(scripts, embedder=embedder)

    async def run(client):
        resp = await post_json(
            client, "/multichat/completions", _multichat_body(2, consensus=False)
        )
        events = sse_events(await resp.text())
        frames = [json.loads(e) for e in events[:-1]]
        assert not any(
            f.get("object") == "multichat.consensus" for f in frames
        )

    go(with_client(app, run))


def test_metrics_counters_move():
    app, _ = make_app([Script([chunk_obj("hi", finish="stop")])])

    async def run(client):
        before = (await (await client.get("/metrics")).json())["series"]
        assert "http:/chat/completions" not in before
        await client.post(
            "/chat/completions",
            json={"model": "m", "messages": [{"role": "user", "content": "q"}]},
        )
        after = (await (await client.get("/metrics")).json())["series"]
        assert after["http:/chat/completions"]["count"] == 1
        assert after["http:/chat/completions"]["errors"] == 0

    go(with_client(app, run))


def test_streaming_consensus_loop_not_blocked():
    """The loop must keep serving while consensus embeds run (VERDICT r1
    item 8).  The embedder is artificially slowed to 150 ms per embed; if
    embeds ran on the loop thread, the concurrent /healthz probes would
    stall behind them — off-loop, every probe returns fast."""
    import time as _t

    from llm_weighted_consensus_tpu.models.embedder import TpuEmbedder

    embedder = TpuEmbedder("test-tiny")
    real_update = embedder.stream_vote_update
    embed_threads = []

    def slow_update(*args, **kwargs):
        embed_threads.append(__import__("threading").get_ident())
        _t.sleep(0.15)
        return real_update(*args, **kwargs)

    embedder.stream_vote_update = slow_update
    scripts = [
        Script([chunk_obj(f"answer {i}", finish="stop")]) for i in range(4)
    ]
    app, _ = make_app(scripts, embedder=embedder)

    async def run(client):
        loop_thread = __import__("threading").get_ident()

        async def stream():
            resp = await post_json(
                client, "/multichat/completions", _multichat_body(4)
            )
            return await resp.text()

        async def pings():
            # interleave healthz probes with the streaming request
            stamps = []
            for _ in range(8):
                t0 = asyncio.get_event_loop().time()
                assert (await client.get("/healthz")).status == 200
                stamps.append(asyncio.get_event_loop().time() - t0)
                await asyncio.sleep(0.05)
            return stamps, loop_thread

        text, (stamps, loop_thread) = await asyncio.gather(stream(), pings())
        assert "multichat.consensus" in text
        # embeds ran, off the event-loop thread
        assert embed_threads and all(t != loop_thread for t in embed_threads)
        # healthz stays responsive: probes never wait out a 150 ms embed
        assert max(stamps) < 0.1

    go(with_client(app, run))


# -- /consensus: the device self-consistency scorer as a service --------------


def _tiny_embedder():
    from llm_weighted_consensus_tpu.models.embedder import TpuEmbedder

    return TpuEmbedder("test-tiny", max_tokens=32)


def test_consensus_endpoint_round_trip():
    pytest.importorskip("jax")
    app, _ = make_app([], embedder=_tiny_embedder())

    async def run(client):
        resp = await post_json(
            client,
            "/consensus",
            {"input": ["the answer is 42", "the answer is 42!", "cabbage"]},
        )
        assert resp.status == 200
        body = await resp.json()
        assert body["model"] == "test-tiny"
        conf = body["confidence"]
        assert len(conf) == 3
        assert sum(conf) == pytest.approx(1.0, abs=1e-5)
        # the two agreeing candidates outrank the outlier
        assert min(conf[0], conf[1]) > conf[2]

    go(with_client(app, run))


def test_consensus_endpoint_serves_quantized_embedder():
    """EMBEDDER_QUANTIZE=int8 end to end: the served vote distribution
    must track the full-precision serving path on the same inputs."""
    pytest.importorskip("jax")
    import numpy as np

    from llm_weighted_consensus_tpu.models.embedder import TpuEmbedder

    texts = ["the answer is 42", "the answer is 42!", "cabbage soup 99"]
    results = {}
    for mode in ("none", "int8"):
        app, _ = make_app(
            [], embedder=TpuEmbedder("test-tiny", max_tokens=32, quantize=mode)
        )

        async def run(client):
            resp = await post_json(client, "/consensus", {"input": texts})
            assert resp.status == 200
            results[mode] = (await resp.json())["confidence"]

        go(with_client(app, run))
    full, quant = np.asarray(results["none"]), np.asarray(results["int8"])
    assert full.argmax() == quant.argmax()
    assert np.abs(full - quant).max() < 0.1


def test_consensus_endpoint_validation():
    pytest.importorskip("jax")
    app, _ = make_app([], embedder=_tiny_embedder())

    async def run(client):
        for bad in (
            {"input": ["only one"]},
            {"input": "not a list"},
            {"input": ["a", 7]},
            [1, 2],
        ):
            resp = await post_json(client, "/consensus", bad)
            assert resp.status == 400, bad
        # no embedder -> route absent entirely
        return True

    go(with_client(app, run))
    app_no_embedder, _ = make_app([])

    async def run2(client):
        resp = await post_json(client, "/consensus", {"input": ["a", "b"]})
        assert resp.status == 404

    go(with_client(app_no_embedder, run2))


def _tiny_reranker():
    from llm_weighted_consensus_tpu.models.reranker import TpuReranker

    return TpuReranker("deberta-test-tiny", max_tokens=32)


def test_consensus_rm_scorer_round_trip():
    """{"scorer": "rm"} re-ranks by reward model, with the prompt
    prepended to every candidate."""
    pytest.importorskip("jax")
    from llm_weighted_consensus_tpu.clients.multichat import MultichatClient
    from llm_weighted_consensus_tpu.serve import build_app

    transport = FakeTransport([])
    chat = DefaultChatClient(
        transport, [ApiBase("https://up.example", "k")], backoff=NO_RETRY
    )
    reg = registry.InMemoryModelRegistry()
    store = archive.InMemoryArchive()
    score = ScoreClient(
        chat, reg, archive_fetcher=store,
        rng_factory=lambda: random.Random(SEED),
    )
    multichat = MultichatClient(chat, reg, archive_fetcher=store)
    app = build_app(
        chat, score, multichat, _tiny_embedder(), reranker=_tiny_reranker()
    )

    async def run(client):
        resp = await post_json(
            client,
            "/consensus",
            {
                "input": ["the answer is 42", "it is 41", "cabbage"],
                "scorer": "rm",
                "prompt": "what is the answer?",
            },
        )
        assert resp.status == 200
        body = await resp.json()
        assert body["scorer"] == "rm"
        assert body["model"] == "deberta-test-tiny"
        conf = body["confidence"]
        assert len(conf) == 3
        assert sum(conf) == pytest.approx(1.0, abs=1e-5)
        assert body["usage"]["prompt_tokens"] > 0
        # cosine scorer still serves on the same route
        resp2 = await post_json(
            client, "/consensus", {"input": ["a b", "a b", "zq"]}
        )
        assert resp2.status == 200
        assert (await resp2.json())["scorer"] == "cosine"
        # unknown scorer and unavailable-scorer validation
        resp3 = await post_json(
            client, "/consensus", {"input": ["a", "b"], "scorer": "magic"}
        )
        assert resp3.status == 400
        resp4 = await post_json(
            client,
            "/consensus",
            {"input": ["a", "b"], "scorer": "rm", "prompt": 7},
        )
        assert resp4.status == 400

    go(with_client(app, run))


def test_consensus_rm_unavailable_is_400():
    pytest.importorskip("jax")
    app, _ = make_app([], embedder=_tiny_embedder())  # no reranker

    async def run(client):
        resp = await post_json(
            client, "/consensus", {"input": ["a", "b"], "scorer": "rm"}
        )
        assert resp.status == 400
        assert "RM_MODEL" in (await resp.json())["message"]

    go(with_client(app, run))


def test_build_reranker_gate_and_presets(monkeypatch):
    """build_reranker mirrors the embedder's synthetic-params discipline."""
    pytest.importorskip("jax")
    from llm_weighted_consensus_tpu.serve.__main__ import build_reranker

    monkeypatch.delenv("LWC_ALLOW_RANDOM_PARAMS", raising=False)
    config = Config.from_env({"RM_MODEL": "deberta-test-tiny"})
    with pytest.raises(ValueError) as err:
        build_reranker(config)
    assert "RM_WEIGHTS" in str(err.value)
    assert build_reranker(config, allow_synthetic=True) is not None
    with pytest.raises(ValueError) as err2:
        build_reranker(Config.from_env({"RM_MODEL": "deberta-enormous"}))
    assert "RM_MODEL" in str(err2.value)
    assert build_reranker(Config.from_env({})) is None


def test_consensus_endpoint_batches_concurrent_requests():
    """K concurrent /consensus posts coalesce into fewer device dispatches
    (the VERDICT r2 item-1 'K requests -> <<K device entries' gate)."""
    pytest.importorskip("jax")
    from llm_weighted_consensus_tpu.serve.gateway import METRICS_KEY

    app, _ = make_app([], embedder=_tiny_embedder())

    async def run(client):
        async def one(i):
            resp = await post_json(
                client,
                "/consensus",
                {"input": [f"text {i} a", f"text {i} a", f"other {i}"]},
            )
            assert resp.status == 200
            return await resp.json()

        # warm the r=1 and r-bucket compiles so the timed coalesce isn't
        # serialized by compilation
        await one(0)
        before = app[METRICS_KEY].snapshot()["device_batcher"]["dispatches"]
        results = await asyncio.gather(*(one(i) for i in range(8)))
        assert all(len(r["confidence"]) == 3 for r in results)
        util = app[METRICS_KEY].snapshot()["device_batcher"]
        dispatched = util["dispatches"] - before
        # the actual coalescing gate: 8 concurrent requests must share
        # dispatches, not get one each
        assert 0 < dispatched < 8, util

    go(with_client(app, run))


def test_synthetic_params_refused_without_gate(monkeypatch):
    """Production startup refuses random-init weights + hash tokenizer
    unless explicitly opted in; the error names the fix."""
    pytest.importorskip("jax")
    from llm_weighted_consensus_tpu.serve.__main__ import build_embedder

    monkeypatch.delenv("LWC_ALLOW_RANDOM_PARAMS", raising=False)
    config = Config.from_env(
        {"EMBEDDER_MODEL": "test-tiny", "EMBEDDER_MAX_TOKENS": "32"}
    )
    with pytest.raises(ValueError) as err:
        build_embedder(config)
    msg = str(err.value)
    assert "EMBEDDER_WEIGHTS" in msg
    assert "LWC_ALLOW_RANDOM_PARAMS" in msg
    assert "random-init" in msg and "hash tokenizer" in msg


def test_synthetic_params_warn_with_gate(monkeypatch, caplog):
    """With the gate (or fake-upstream demo mode) synthetic params serve,
    but the startup log shouts about it."""
    pytest.importorskip("jax")
    import logging

    from llm_weighted_consensus_tpu.serve.__main__ import build_embedder

    monkeypatch.delenv("LWC_ALLOW_RANDOM_PARAMS", raising=False)
    config = Config.from_env(
        {"EMBEDDER_MODEL": "test-tiny", "EMBEDDER_MAX_TOKENS": "32"}
    )
    with caplog.at_level(logging.WARNING, logger="lwc.serve"):
        embedder = build_embedder(config, allow_synthetic=True)
    assert embedder is not None
    assert any(
        "SYNTHETIC EMBEDDER PARAMS" in rec.message for rec in caplog.records
    )


def test_real_weights_and_vocab_serve_without_warning(tmp_path, caplog):
    """A real checkpoint + vocab is NOT synthetic: no gate needed, no
    warning logged."""
    pytest.importorskip("jax")
    import logging

    import jax

    from llm_weighted_consensus_tpu.models import bert
    from llm_weighted_consensus_tpu.models.configs import TEST_TINY
    from llm_weighted_consensus_tpu.serve.__main__ import build_embedder
    from llm_weighted_consensus_tpu.train import save_checkpoint

    params = bert.init_params(jax.random.PRNGKey(0), TEST_TINY)
    ckpt = tmp_path / "ckpt"
    save_checkpoint(str(ckpt), params)
    vocab = tmp_path / "vocab.txt"
    vocab.write_text(
        "\n".join(["[PAD]", "[UNK]", "[CLS]", "[SEP]", "a", "b"]) + "\n"
    )
    config = Config.from_env(
        {
            "EMBEDDER_MODEL": "test-tiny",
            "EMBEDDER_WEIGHTS": str(ckpt),
            "EMBEDDER_VOCAB": str(vocab),
            "EMBEDDER_MAX_TOKENS": "32",
        }
    )
    with caplog.at_level(logging.WARNING, logger="lwc.serve"):
        embedder = build_embedder(config)
    assert embedder is not None
    assert not [r for r in caplog.records if r.name == "lwc.serve"]


def test_missing_vocab_path_errors_instead_of_hash_fallback(tmp_path):
    """A typo'd EMBEDDER_VOCAB must error at startup, not silently serve
    hash tokenization (or misdiagnose as 'no EMBEDDER_VOCAB')."""
    pytest.importorskip("jax")
    from llm_weighted_consensus_tpu.serve.__main__ import build_embedder

    config = Config.from_env(
        {
            "EMBEDDER_MODEL": "test-tiny",
            "EMBEDDER_VOCAB": str(tmp_path / "typo.txt"),
            "EMBEDDER_MAX_TOKENS": "32",
        }
    )
    with pytest.raises(FileNotFoundError) as err:
        build_embedder(config)
    assert "typo.txt" in str(err.value)


def test_unknown_embedder_model_names_flag_and_presets():
    pytest.importorskip("jax")
    from llm_weighted_consensus_tpu.serve.__main__ import build_embedder

    config = Config.from_env({"EMBEDDER_MODEL": "bge-enormous"})
    with pytest.raises(ValueError) as err:
        build_embedder(config)
    msg = str(err.value)
    assert "EMBEDDER_MODEL" in msg and "bge-enormous" in msg
    assert "bge-small-en" in msg  # lists valid presets


def test_unwritable_archive_path_names_env_var(tmp_path):
    from llm_weighted_consensus_tpu.serve.__main__ import build_service

    missing = tmp_path / "nope" / "archive.json"
    config = Config.from_env({"ARCHIVE_PATH": str(missing)})
    with pytest.raises(OSError) as err:
        build_service(config, fake_upstream=True)
    assert "ARCHIVE_PATH" in str(err.value)


# -- mesh-configured serving (MESH_ENABLED / MESH_SHAPE) ----------------------


def test_mesh_dp_service_round_trip():
    """MESH_SHAPE=8x1 -> build_embedder places the device side on a dp mesh;
    /embeddings and a trained-weights score request round-trip through the
    dp-sharded embedder."""
    pytest.importorskip("jax")
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from llm_weighted_consensus_tpu.serve.__main__ import build_embedder
    from llm_weighted_consensus_tpu.weights import WeightFetchers
    from llm_weighted_consensus_tpu.weights.training_table import (
        TpuTrainingTableFetcher,
    )

    config = Config.from_env(
        {
            "EMBEDDER_MODEL": "test-tiny",
            "EMBEDDER_MAX_TOKENS": "32",
            "MESH_ENABLED": "1",
            "MESH_SHAPE": "8x1",
        }
    )
    embedder = build_embedder(config)
    assert dict(embedder.mesh.shape) == {"dp": 8, "tp": 1}
    ids, mask = embedder.tokenize(["text"] * 8)
    dev_ids, _ = embedder.put_batch(jnp.asarray(ids), jnp.asarray(mask))
    assert dev_ids.sharding.spec == P("dp", None)
    # uneven batches degrade to replicated placement, not an error
    ids5, mask5 = embedder.tokenize(["text"] * 5)
    dev5, _ = embedder.put_batch(jnp.asarray(ids5), jnp.asarray(mask5))
    assert dev5.sharding.spec == P()
    # ...but the consensus hot path pads to the dp multiple, so N=5
    # candidates still take the dp-split fast path — and padding must not
    # perturb the vote (same softmax as an unsharded embedder)
    assert embedder.batch_multiple == 8
    import numpy as np

    from llm_weighted_consensus_tpu.models.configs import TEST_TINY
    from llm_weighted_consensus_tpu.models.embedder import TpuEmbedder

    texts5 = [f"candidate {i}" for i in range(5)]
    conf = np.asarray(embedder.consensus_confidence(texts5))
    plain = TpuEmbedder(
        "test-tiny", config=TEST_TINY, max_tokens=32, seed=0
    )
    np.testing.assert_allclose(
        conf, np.asarray(plain.consensus_confidence(texts5)), atol=1e-5
    )

    keys = ballot_keys(2)
    transport = FakeTransport(
        [Script([chunk_obj(f"pick {keys[0]}", finish="stop")])]
    )
    chat = DefaultChatClient(
        transport, [ApiBase("https://up.example", "k")], backoff=NO_RETRY
    )
    reg = registry.InMemoryModelRegistry()
    store = archive.InMemoryArchive()
    score = ScoreClient(
        chat,
        reg,
        archive_fetcher=store,
        weight_fetchers=WeightFetchers(
            training_table_fetcher=TpuTrainingTableFetcher(embedder)
        ),
        rng_factory=lambda: random.Random(SEED),
    )
    app = build_app(chat, score, None, embedder)

    async def run(client):
        resp = await client.post(
            "/embeddings", json={"model": "test-tiny", "input": ["a", "b"]}
        )
        assert resp.status == 200
        body = await resp.json()
        assert len(body["data"]) == 2

        resp = await post_json(
            client,
            "/score/completions",
            {
                "messages": [{"role": "user", "content": "q"}],
                "model": {
                    "llms": [
                        {
                            "model": "j1",
                            "weight": {
                                "type": "training_table",
                                "base_weight": 1,
                                "min_weight": 1,
                                "max_weight": 5,
                            },
                        }
                    ],
                    "weight": {
                        "type": "training_table",
                        "embeddings": {
                            "model": "test-tiny", "max_tokens": 32
                        },
                        "top": 3,
                    },
                },
                "choices": ["first", "second"],
            },
        )
        assert resp.status == 200
        body = await resp.json()
        # weight evidence from the on-mesh embedder is echoed back
        assert body["weight_data"] is not None
        usage = body["weight_data"]["embeddings_response"]["usage"]
        assert usage["total_tokens"] > 0
        cand = {c["index"]: c for c in body["choices"] if c["index"] < 2}
        assert cand[0]["confidence"] == 1

    go(with_client(app, run))


def test_consensus_overlay_degrades_on_embedder_failure():
    """An embedder crash mid-stream must not tear down the multichat SSE
    stream: consensus frames stop, multichat chunks keep flowing, [DONE]
    still terminates."""
    from llm_weighted_consensus_tpu.models.embedder import TpuEmbedder

    embedder = TpuEmbedder("test-tiny")

    def boom(*args, **kwargs):
        raise RuntimeError("device OOM")

    embedder.stream_vote_update = boom
    scripts = [
        Script([chunk_obj(f"answer {i}", finish="stop")]) for i in range(3)
    ]
    app, _ = make_app(scripts, embedder=embedder)

    async def run(client):
        resp = await post_json(
            client, "/multichat/completions", _multichat_body(3)
        )
        assert resp.status == 200
        events = sse_events(await resp.text())
        assert events[-1] == "[DONE]"
        frames = [json.loads(e) for e in events[:-1]]
        assert not any(
            f.get("object") == "multichat.consensus" for f in frames
        )
        # every generator's answer still arrived
        texts = {
            c["delta"].get("content")
            for f in frames
            for c in f.get("choices", [])
            if c.get("delta", {}).get("content")
        }
        assert texts == {"answer 0", "answer 1", "answer 2"}
        # the failure was recorded out-of-band
        m = await (await client.get("/metrics")).json()
        assert m["series"]["device:consensus_update"]["errors"] >= 1

    go(with_client(app, run))


def test_metrics_unmatched_paths_bucket_together():
    app, _ = make_app([])

    async def run(client):
        for path in ("/nope-a", "/nope-b", "/nope-c"):
            assert (await client.get(path)).status == 404
        m = await (await client.get("/metrics")).json()
        series = m["series"]
        assert series["http:unmatched"]["count"] == 3
        assert not any("nope" in k for k in series)

    go(with_client(app, run))


def test_profile_endpoints(tmp_path):
    pytest.importorskip("jax")
    from llm_weighted_consensus_tpu.models.embedder import TpuEmbedder

    embedder = TpuEmbedder("test-tiny")
    transport = FakeTransport([])
    chat = DefaultChatClient(
        transport, [ApiBase("https://up.example", "k")], backoff=NO_RETRY
    )
    reg = registry.InMemoryModelRegistry()
    store = archive.InMemoryArchive()
    score = ScoreClient(chat, reg, archive_fetcher=store)
    prof_dir = str(tmp_path / "traces")
    app = build_app(chat, score, None, embedder, profile_dir=prof_dir)

    async def run(client):
        # traced request between start and stop
        assert (await client.post("/profile/start")).status == 200
        # double start is a clean 400
        assert (await client.post("/profile/start")).status == 400
        resp = await client.post(
            "/embeddings", json={"model": "test-tiny", "input": ["trace me"]}
        )
        assert resp.status == 200
        assert (await client.post("/profile/stop")).status == 200
        assert (await client.post("/profile/stop")).status == 400
        # a trace landed on disk
        import os

        found = [
            os.path.join(r, f)
            for r, _, fs in os.walk(prof_dir)
            for f in fs
        ]
        assert found, "no trace files written"

    go(with_client(app, run))


def test_profile_endpoints_absent_without_config():
    app, _ = make_app([])

    async def run(client):
        assert (await client.post("/profile/start")).status == 404

    go(with_client(app, run))


def test_archive_path_snapshot_on_shutdown(tmp_path):
    """ARCHIVE_PATH: the service loads an existing snapshot at startup and
    writes one back on graceful shutdown (checkpoint/resume)."""
    from llm_weighted_consensus_tpu import archive
    from llm_weighted_consensus_tpu.serve.__main__ import build_service
    from llm_weighted_consensus_tpu.types.chat_response import (
        ChatCompletion as ChatUnary,
    )

    path = str(tmp_path / "archive.json")
    seed = archive.InMemoryArchive()
    seed.put_chat(
        ChatUnary.from_json_obj(
            {
                "id": "cc-seeded",
                "object": "chat.completion",
                "created": 1,
                "model": "m",
                "choices": [
                    {
                        "index": 0,
                        "message": {"role": "assistant", "content": "hi"},
                        "finish_reason": "stop",
                    }
                ],
            }
        )
    )
    seed.save(path)

    config = Config.from_env(
        {"ARCHIVE_PATH": path, "OPENAI_API_BASE": "https://up.example",
         "OPENAI_API_KEY": "k"}
    )
    assert config.archive_path == path
    app = build_service(config)

    # startup load: the seeded completion is in the service's live store
    from llm_weighted_consensus_tpu.serve.__main__ import ARCHIVE_KEY

    store = app[ARCHIVE_KEY]
    assert store.chat_ids() == ["cc-seeded"]
    # ...and fetchable exactly as rehydration would fetch it
    fetched = go(store.fetch_chat_completion(None, "cc-seeded"))
    assert fetched.choices[0].message.content == "hi"

    async def run(client):
        assert (await client.get("/healthz")).status == 200

    go(with_client(app, run))  # with_client closes -> on_cleanup save
    reloaded = archive.InMemoryArchive.load(path)
    assert reloaded.chat_ids() == ["cc-seeded"]


def test_archive_write_stores_served_unary_completions():
    """ARCHIVE_WRITE: a served score completion is archived with its
    ballots, so its id is referenceable and revote-able afterwards."""
    from llm_weighted_consensus_tpu.archive.rescore import rescore_archive
    from llm_weighted_consensus_tpu.serve.__main__ import _ArchivingClient

    keys = ballot_keys(2)
    transport = FakeTransport(
        [Script([chunk_obj(f"pick {keys[0]}", finish="stop")])]
    )
    chat = DefaultChatClient(
        transport, [ApiBase("https://up.example", "k")], backoff=NO_RETRY
    )
    reg = registry.InMemoryModelRegistry()
    store = archive.InMemoryArchive()
    score = ScoreClient(
        chat, reg, archive_fetcher=store,
        rng_factory=lambda: random.Random(SEED),
        ballot_sink=store.put_ballot,
    )
    def put_score(result, params):
        store.put_score(result)
        store.put_score_request(result.id, params)

    app = build_app(chat, _ArchivingClient(score, put_score), None)

    async def run(client):
        resp = await post_json(
            client,
            "/score/completions",
            {
                "messages": [{"role": "user", "content": "q"}],
                "model": inline_model([{"model": "j1"}]),
                "choices": ["first", "second"],
            },
        )
        assert resp.status == 200
        return (await resp.json())["id"]

    cid = go(with_client(app, run))
    assert store.score_ids() == [cid]
    assert store.score_ballots(cid) is not None
    results = rescore_archive(store, revote=True)
    conf = [float(x) for x in results[cid]["confidence"]]
    assert conf[0] == pytest.approx(1.0)


def test_archive_write_config_defaults():
    on = Config.from_env({"ARCHIVE_PATH": "/tmp/x.json"})
    assert on.archive_write is True
    off = Config.from_env({"ARCHIVE_PATH": "/tmp/x.json", "ARCHIVE_WRITE": "0"})
    assert off.archive_write is False
    bare = Config.from_env({})
    assert bare.archive_write is False
    explicit = Config.from_env({"ARCHIVE_WRITE": "1"})
    assert explicit.archive_write is True
    # streaming tee + cap flags
    assert bare.archive_streaming is False
    assert bare.archive_max_completions == 65536
    custom = Config.from_env(
        {"ARCHIVE_STREAMING": "1", "ARCHIVE_MAX_COMPLETIONS": "100"}
    )
    assert custom.archive_streaming is True
    assert custom.archive_max_completions == 100
    with pytest.raises(ValueError):  # negative cap is a config error
        Config.from_env({"ARCHIVE_MAX_COMPLETIONS": "-1"})


def test_archive_cap_fifo_eviction():
    """max_completions bounds each table FIFO; evicting a score completion
    drops its ballots + request record (ADVICE r2: unbounded growth)."""
    from types import SimpleNamespace

    store = archive.InMemoryArchive(max_completions=3)
    for i in range(5):
        cid = f"scrcpl-{i}"
        store.put_ballot(cid, 0, [("`A`", 0), ("`B`", 1)])
        store.put_score(SimpleNamespace(id=cid))
        store.put_score_request(cid, object())
    assert store.score_ids() == ["scrcpl-2", "scrcpl-3", "scrcpl-4"]
    assert store.score_ballots("scrcpl-0") is None
    assert store.score_request("scrcpl-0") is None
    assert store.score_ballots("scrcpl-4") is not None
    # chat and multichat tables have their own FIFOs
    for i in range(5):
        store.put_chat(SimpleNamespace(id=f"chtcpl-{i}"))
        store.put_multichat(SimpleNamespace(id=f"mchcpl-{i}"))
    assert store.chat_ids() == ["chtcpl-2", "chtcpl-3", "chtcpl-4"]
    assert store.multichat_ids() == ["mchcpl-2", "mchcpl-3", "mchcpl-4"]
    # enforce_cap trims an over-cap store after the cap is lowered
    store.max_completions = 1
    store.enforce_cap()
    assert store.score_ids() == ["scrcpl-4"]


def _make_archiving_score(scripts, stream_fold):
    from llm_weighted_consensus_tpu.serve.__main__ import _ArchivingClient

    transport = FakeTransport(scripts)
    chat = DefaultChatClient(
        transport, [ApiBase("https://up.example", "k")], backoff=NO_RETRY
    )
    store = archive.InMemoryArchive()
    score = ScoreClient(
        chat,
        registry.InMemoryModelRegistry(),
        archive_fetcher=store,
        rng_factory=lambda: random.Random(SEED),
    )

    def put_score(result, params):
        store.put_score(result)
        store.put_score_request(result.id, params)

    return _ArchivingClient(score, put_score, stream_fold=stream_fold), store


def test_archive_streaming_tee_folds_completed_stream():
    """ARCHIVE_STREAMING: a fully-consumed stream archives its folded
    unary form (unary = fold(chunks) — the merge-algebra contract)."""
    from llm_weighted_consensus_tpu.types import score_response
    from llm_weighted_consensus_tpu.types.score_request import (
        ChatCompletionCreateParams as SP,
    )

    keys = ballot_keys(2)
    client, store = _make_archiving_score(
        [Script([chunk_obj(f"pick {keys[0]}", model="j1", finish="stop")])],
        score_response.ChatCompletion.from_streaming,
    )
    params = SP.from_json_obj(
        {
            "messages": [{"role": "user", "content": "q"}],
            "model": inline_model([{"model": "j1"}]),
            "choices": ["first", "second"],
        }
    )

    async def run():
        stream = await client.create_streaming(None, params)
        async for _ in stream:
            pass

    go(run())
    [cid] = store.score_ids()
    completion = store.score_completion(cid)
    assert completion.id == cid
    # the folded unary carries the full consensus result: two candidates
    # with confidence and the judge choice with its vote
    candidates = [c for c in completion.choices if c.model_index is None]
    assert len(candidates) == 2
    assert float(candidates[0].confidence) == pytest.approx(1.0)
    judges = [c for c in completion.choices if c.model_index is not None]
    assert judges and judges[0].message.vote is not None
    # the request archived beside it feeds training-table learning
    assert store.score_request(cid) is not None


def test_archive_streaming_error_item_passes_through_unarchived():
    """Mid-stream error items (ChatError frames) pass through to the
    client unchanged and poison the fold — the errored stream is not
    archived, and the tee never crashes the client-facing stream."""
    from llm_weighted_consensus_tpu.errors import ChatError
    from llm_weighted_consensus_tpu.serve.__main__ import _ArchivingClient
    from llm_weighted_consensus_tpu.types import chat_response

    chunk = chat_response.ChatCompletionChunk.from_json_obj(
        {
            "id": "c1",
            "object": "chat.completion.chunk",
            "created": 0,
            "model": "m",
            "choices": [
                {"index": 0, "delta": {"content": "hi"}, "finish_reason": None}
            ],
        }
    )
    error = ChatError("deserialize_chat_completion_chunk", "bad frame")
    closed = []

    async def inner_stream():
        try:
            yield chunk
            yield error
            yield chunk.clone()
        finally:
            closed.append(True)

    class Inner:
        async def create_streaming(self, ctx, params):
            return inner_stream()

    archived = []
    client = _ArchivingClient(
        Inner(),
        lambda result, params: archived.append(result),
        stream_fold=chat_response.ChatCompletion.from_streaming,
    )

    async def run():
        stream = await client.create_streaming(None, None)
        return [item async for item in stream]

    items = go(run())
    assert len(items) == 3 and items[1] is error
    assert archived == []  # errored stream: nothing archived
    assert closed == [True]  # inner stream released


def test_archive_streaming_tee_closes_inner_on_abandon():
    """Client disconnect (aclose on the tee) propagates to the inner
    stream so the upstream connection is released promptly."""
    from llm_weighted_consensus_tpu.serve.__main__ import _ArchivingClient
    from llm_weighted_consensus_tpu.types import chat_response

    chunk = chat_response.ChatCompletionChunk.from_json_obj(
        {
            "id": "c1",
            "object": "chat.completion.chunk",
            "created": 0,
            "model": "m",
            "choices": [
                {"index": 0, "delta": {"content": "hi"}, "finish_reason": None}
            ],
        }
    )
    closed = []

    async def inner_stream():
        try:
            while True:
                yield chunk
        finally:
            closed.append(True)

    class Inner:
        async def create_streaming(self, ctx, params):
            return inner_stream()

    archived = []
    client = _ArchivingClient(
        Inner(),
        lambda result, params: archived.append(result),
        stream_fold=chat_response.ChatCompletion.from_streaming,
    )

    async def run():
        stream = await client.create_streaming(None, None)
        async for _ in stream:
            break
        await stream.aclose()

    go(run())
    assert closed == [True]
    assert archived == []


def test_archive_streaming_through_http_service():
    """End-to-end over HTTP: build_service with ARCHIVE_STREAMING=1 + the
    real fake-upstream server; a fully-consumed SSE stream archives its
    folded unary (the manual drive from r3, as CI)."""
    from aiohttp import web
    from aiohttp.test_utils import unused_port

    from llm_weighted_consensus_tpu.serve.__main__ import (
        ARCHIVE_KEY,
        _fake_upstream,
        build_service,
    )
    from llm_weighted_consensus_tpu.utils import jsonutil

    # ephemeral fake-upstream port: a fixed one would collide with any
    # concurrently-running demo.sh gateway
    fake_port = unused_port()
    config = Config.from_env(
        {"ARCHIVE_WRITE": "1", "ARCHIVE_STREAMING": "1"}
    )
    app = build_service(
        config, fake_upstream=True, fake_upstream_port=fake_port
    )
    store = app[ARCHIVE_KEY]

    async def run():
        fake_app = web.Application()
        fake_app.router.add_post("/v1/chat/completions", _fake_upstream)
        fake = TestServer(fake_app, port=fake_port)
        await fake.start_server()
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            resp = await client.post(
                "/score/completions",
                data=jsonutil.dumps(
                    {
                        "stream": True,
                        "messages": [{"role": "user", "content": "pick"}],
                        "model": {"llms": [{"model": "fake-judge"}]},
                        "choices": ["alpha", "beta"],
                    }
                ),
                headers={"content-type": "application/json"},
            )
            text = await resp.text()
            assert resp.status == 200
            assert text.rstrip().endswith("data: [DONE]")
        finally:
            await client.close()
            await fake.close()

    go(run())
    [cid] = store.score_ids()
    completion = store.score_completion(cid)
    # folded unary: candidates with confidence + the judge's vote, and
    # the request + ballots beside it (learning inputs)
    candidates = [c for c in completion.choices if c.model_index is None]
    assert len(candidates) == 2
    assert sum(float(c.confidence) for c in candidates) == pytest.approx(1.0)
    assert store.score_request(cid) is not None
    assert store.score_ballots(cid)


def test_archive_streaming_abandoned_stream_not_archived():
    """A stream the client abandons mid-way archives nothing — a partial
    fold would look like a complete completion."""
    from llm_weighted_consensus_tpu.types import score_response
    from llm_weighted_consensus_tpu.types.score_request import (
        ChatCompletionCreateParams as SP,
    )

    keys = ballot_keys(2)
    client, store = _make_archiving_score(
        [Script([chunk_obj(f"pick {keys[0]}", model="j1", finish="stop")])],
        score_response.ChatCompletion.from_streaming,
    )
    params = SP.from_json_obj(
        {
            "messages": [{"role": "user", "content": "q"}],
            "model": inline_model([{"model": "j1"}]),
            "choices": ["first", "second"],
        }
    )

    async def run():
        stream = await client.create_streaming(None, params)
        async for _ in stream:
            break  # abandon after the first chunk
        await stream.aclose()

    go(run())
    assert store.score_ids() == []


def test_archive_rescore_endpoint():
    """POST /archive/rescore: reweight archived completions over HTTP,
    apply back into the store."""
    from llm_weighted_consensus_tpu.serve.__main__ import (
        ARCHIVE_KEY,
        build_service,
    )
    from llm_weighted_consensus_tpu.utils import jsonutil

    config = Config.from_env(
        {"OPENAI_API_BASE": "https://up.example", "OPENAI_API_KEY": "k"}
    )
    app = build_service(config)
    store = app[ARCHIVE_KEY]

    # seed two archived score completions via the real engine
    keys = ballot_keys(2)
    transport = FakeTransport(
        [
            Script([chunk_obj(f"pick {keys[0]}", model="ja", finish="stop")]),
            Script([chunk_obj(f"pick {keys[1]}", model="jb", finish="stop")]),
        ]
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
    from llm_weighted_consensus_tpu.types.score_request import (
        ChatCompletionCreateParams as SP,
    )

    model = inline_model([{"model": "ja"}, {"model": "jb"}])
    result = go(
        score.create_unary(
            None,
            SP.from_json_obj(
                {
                    "messages": [{"role": "user", "content": "q"}],
                    "model": model,
                    "choices": ["a", "b"],
                }
            ),
        )
    )
    store.put_score(result)
    judge_ids = sorted({c.model for c in result.choices if c.model})

    async def run(client):
        resp = await client.post(
            "/archive/rescore",
            data=jsonutil.dumps(
                {
                    "weight_overrides": {judge_ids[0]: 3.0},
                    "apply": True,
                    "include_results": True,
                }
            ),
            headers={"content-type": "application/json"},
        )
        assert resp.status == 200
        body = await resp.json()
        assert body["rescored"] == 1
        assert body["applied"] == 1
        conf = [float(x) for x in body["results"][result.id]["confidence"]]
        assert conf[0] + conf[1] == pytest.approx(1.0)
        assert 0.75 in [pytest.approx(c) for c in conf]

    go(with_client(app, run))
    # applied back into the archived wire object
    cand = {c.index: c for c in store._score[result.id].choices if c.index < 2}
    assert {float(cand[0].confidence), float(cand[1].confidence)} == {
        0.75,
        0.25,
    }


def test_archive_rescore_endpoint_validates_input():
    from llm_weighted_consensus_tpu.serve.__main__ import build_service

    config = Config.from_env(
        {"OPENAI_API_BASE": "https://up.example", "OPENAI_API_KEY": "k"}
    )
    app = build_service(config)

    async def run(client):
        hdr = {"content-type": "application/json"}
        resp = await client.post(
            "/archive/rescore", data=b'{"ids": ["nope"]}', headers=hdr
        )
        assert resp.status == 400
        assert "unknown" in (await resp.json())["message"]
        resp = await client.post(
            "/archive/rescore", data=b'{"ids": "abc"}', headers=hdr
        )
        assert resp.status == 400
        resp = await client.post("/archive/rescore", data=b"[]", headers=hdr)
        assert resp.status == 400
        # empty body = rescore everything (empty archive -> 0)
        resp = await client.post("/archive/rescore", data=b"{}", headers=hdr)
        assert resp.status == 200
        assert (await resp.json())["rescored"] == 0

    go(with_client(app, run))


def test_endpoints_never_500_on_malformed_bodies():
    """Adversarial input sweep: every POST endpoint answers malformed or
    type-confused JSON with a clean 4xx — never a 500/stack trace."""
    pytest.importorskip("jax")
    from llm_weighted_consensus_tpu.serve.__main__ import build_service

    config = Config.from_env(
        {
            "OPENAI_API_BASE": "https://up.example",
            "OPENAI_API_KEY": "k",
            "EMBEDDER_MODEL": "test-tiny",
            "EMBEDDER_MAX_TOKENS": "32",
        }
    )
    app = build_service(config)

    bodies = [
        b"",
        b"not json",
        b"[]",
        b"42",
        b'"string"',
        b"{}",
        b'{"messages": 7}',
        b'{"messages": [{"role": "nope"}]}',
        b'{"messages": [], "model": {"llms": []}, "choices": []}',
        b'{"messages": [{"role": "user", "content": "q"}], "model": 5, "choices": ["a", "b"]}',
        b'{"model": {"llms": [{"model": ""}]}}',
        b'{"input": 12}',
        b'{"input": [1, 2, 3]}',
        b'{"ids": {"a": 1}}',
        b'{"labels": "x", "model": {"llms": [{"model": "j"}]}}',
        b'{"weight_overrides": {"j": "NaN-ish"}}',
        ('{"messages": [{"role": "user", "content": "' + "x" * 10000 + '"}]}').encode(),
    ]
    endpoints = [
        "/chat/completions",
        "/score/completions",
        "/multichat/completions",
        "/embeddings",
        "/archive/rescore",
        "/weights/learn",
    ]

    async def run(client):
        for path in endpoints:
            for body in bodies:
                resp = await client.post(
                    path,
                    data=body,
                    headers={"content-type": "application/json"},
                )
                assert resp.status < 500, (
                    path,
                    body[:60],
                    resp.status,
                    (await resp.text())[:200],
                )

    go(with_client(app, run))


def test_oversized_body_keeps_413():
    """aiohttp's body-too-large rejection must keep its 413 status — the
    broad parse guard re-raises HTTPException."""
    from aiohttp import web as aioweb

    app, _ = make_app([])
    app._client_max_size = 1024  # shrink the limit for the test

    async def run(client):
        big = b'{"messages": "' + b"x" * 4096 + b'"}'
        resp = await client.post(
            "/chat/completions",
            data=big,
            headers={"content-type": "application/json"},
        )
        assert resp.status == 413

    go(with_client(app, run))


def test_unexpected_500_never_leaks_exception_text():
    """Unexpected (non-StatusError) exceptions map to the uniform
    ``{"code": 500, "message": "internal error"}`` envelope — the
    exception text stays in the server log and NEVER reaches the response
    body, matching the reference's envelope (src/error.rs:8-13)."""
    from llm_weighted_consensus_tpu.serve.gateway import build_app

    secret = "sk-internal-XYZ /root/secret/path.py line 42"

    class Exploding:
        async def create_unary(self, ctx, params):
            raise RuntimeError(secret)

        async def create_streaming(self, ctx, params):
            raise RuntimeError(secret)

    stub = Exploding()
    app = build_app(stub, stub, stub)

    async def run(client):
        for stream in (False, True):
            resp = await client.post(
                "/chat/completions",
                json={
                    "model": "m",
                    "stream": stream,
                    "messages": [{"role": "user", "content": "q"}],
                },
            )
            assert resp.status == 500
            text = await resp.text()
            assert secret not in text
            assert json.loads(text) == {
                "code": 500,
                "message": "internal error",
            }

    go(with_client(app, run))


def test_unexpected_midstream_error_frame_never_leaks():
    """The stream is already 200/SSE when an unexpected exception
    surfaces as a stream item: the error FRAME gets the uniform envelope
    too — the leak fix covers mid-stream, not just pre-stream
    (errors.to_response_error fallback)."""
    from llm_weighted_consensus_tpu.serve.gateway import build_app
    from llm_weighted_consensus_tpu.types.chat_response import (
        ChatCompletionChunk,
    )

    secret = "ClientConnectorError(host='internal-api.corp', sk-XYZ)"

    class MidstreamExploding:
        async def create_unary(self, ctx, params):
            raise AssertionError("unary not used here")

        async def create_streaming(self, ctx, params):
            async def gen():
                yield ChatCompletionChunk.from_json_obj(
                    chunk_obj("partial")
                )
                yield RuntimeError(secret)

            return gen()

    stub = MidstreamExploding()
    app = build_app(stub, stub, stub)

    async def run(client):
        resp = await client.post(
            "/chat/completions",
            json={
                "model": "m",
                "stream": True,
                "messages": [{"role": "user", "content": "q"}],
            },
        )
        assert resp.status == 200  # stream already established
        text = await resp.text()
        assert secret not in text
        events = sse_events(text)
        assert events[-1] == "[DONE]"
        error_frame = json.loads(events[-2])
        assert error_frame == {"code": 500, "message": "internal error"}

    go(with_client(app, run))


def test_warmup_r_without_warmup_fails_loudly():
    """WARMUP_R names buckets *per WARMUP shape*; with no shapes it would
    silently warm nothing — startup must refuse instead."""
    with pytest.raises(ValueError, match="WARMUP_R"):
        Config.from_env({"WARMUP_R": "2"})


def test_parse_phase_masks_non_valueerror_exceptions():
    """Expected malformed-input classes (SchemaError/JSONDecodeError, both
    ValueErrors) echo their path-annotated text; a latent decoder bug
    (non-ValueError) is masked like the 500 envelope — detail never
    reaches the body."""
    from llm_weighted_consensus_tpu.serve.gateway import (
        _parse_error_response,
    )
    from llm_weighted_consensus_tpu.types.base import SchemaError

    echoed = _parse_error_response(SchemaError("temperature", "expected number"))
    assert json.loads(echoed.text)["message"] == "temperature: expected number"

    secret = "'NoneType' object has no attribute '/etc/internal'"
    masked = _parse_error_response(AttributeError(secret))
    assert masked.status == 400
    body = json.loads(masked.text)
    assert body == {"code": 400, "message": "malformed request body"}
    assert secret not in masked.text


def test_client_disconnect_mid_stream_cancels_pipeline():
    """Regression (ISSUE PR 4 satellite): a client vanishing mid-SSE must
    tear the pipeline down — _respond_streaming catches the broken-pipe
    write, counts it, and its finally acloses the generator chain (whose
    cleanup cancels judge pumps and pending batcher items)."""
    from llm_weighted_consensus_tpu.serve.gateway import METRICS_KEY

    keys = ballot_keys(2)
    app, _ = make_app(
        [
            # the judge client looks one chunk ahead, so "thinking" goes
            # out when "still thinking" arrives, at once; the last chunk
            # arrives half a second late: the client is long gone by the
            # time the server tries to write the frames behind it.  (Two
            # chunks would all be written in one burst when the late one
            # lands — aiohttp 3.13's write does not yield to the loop —
            # before the server could see the client leave.)
            Script(
                [
                    chunk_obj("thinking"),
                    chunk_obj("still thinking"),
                    chunk_obj(f"pick {keys[1]}", finish="stop"),
                ],
                delays={2: 0.5},
            )
        ]
    )

    async def run(client):
        resp = await post_json(
            client,
            "/score/completions",
            {
                "stream": True,
                "messages": [{"role": "user", "content": "q"}],
                "model": inline_model([{"model": "j1"}]),
                "choices": ["first", "second"],
            },
        )
        assert resp.status == 200
        await resp.content.readany()  # first frame made it through
        resp.close()  # sever the connection mid-stream
        metrics = app[METRICS_KEY]
        for _ in range(300):
            series = metrics.snapshot()["series"]
            if "http:client_disconnect" in series:
                break
            await asyncio.sleep(0.01)
        assert series["http:client_disconnect"]["count"] == 1
        assert series["http:client_disconnect"]["errors"] == 1

    go(with_client(app, run))


def test_overloaded_error_response_carries_retry_after():
    from llm_weighted_consensus_tpu.errors import OverloadedError
    from llm_weighted_consensus_tpu.serve.gateway import _error_response

    resp = _error_response(OverloadedError("batcher_queue_full"))
    assert resp.status == 503
    assert resp.headers["Retry-After"] == "1"
    body = json.loads(resp.text)
    assert body["message"]["shed_reason"] == "batcher_queue_full"

    resp = _error_response(
        OverloadedError("inflight_limit", retry_after_ms=3200.0)
    )
    assert resp.headers["Retry-After"] == "4"
