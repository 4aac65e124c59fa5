"""TPU trained-weights path: embedding lookup -> per-judge weights within
[min, max] bounds; evidence echo + usage seeding (SURVEY §2.1 weight seam)."""

import asyncio
from decimal import Decimal

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from llm_weighted_consensus_tpu.identity.model import ModelBase
from llm_weighted_consensus_tpu.models.configs import TEST_TINY
from llm_weighted_consensus_tpu.models.embedder import TpuEmbedder
from llm_weighted_consensus_tpu.types.score_request import (
    ChatCompletionCreateParams as ScoreParams,
)
from llm_weighted_consensus_tpu.types.score_response import TrainingTableData
from llm_weighted_consensus_tpu.weights.training_table import (
    TpuTrainingTableFetcher,
    TrainingTableStore,
)


def go(coro):
    return asyncio.new_event_loop().run_until_complete(coro)


def tt_model(n_judges=2):
    return ModelBase.from_json_obj(
        {
            "llms": [
                {
                    "model": f"judge-{i}",
                    "weight": {
                        "type": "training_table",
                        "base_weight": 1,
                        "min_weight": 1,
                        "max_weight": 5,
                    },
                }
                for i in range(n_judges)
            ],
            "weight": {
                "type": "training_table",
                "embeddings": {"model": "test-tiny", "max_tokens": 32},
                "top": 3,
            },
        }
    ).into_model_validate()


def params(text="what is the answer?"):
    return ScoreParams.from_json_obj(
        {
            "messages": [{"role": "user", "content": text}],
            "model": "x" * 22,
            "choices": ["a", "b"],
        }
    )


@pytest.fixture(scope="module")
def embedder():
    return TpuEmbedder("test-tiny", config=TEST_TINY, max_tokens=32, seed=5)


def test_fallback_to_base_weight_without_table(embedder):
    model = tt_model()
    fetcher = TpuTrainingTableFetcher(embedder)
    weights, data = go(fetcher.fetch(None, params(), model))
    assert weights == [Decimal(1), Decimal(1)]
    assert isinstance(data, TrainingTableData)
    assert data.embeddings_response.usage.total_tokens > 0
    assert len(data.embeddings_response.data) == 1


def test_table_lookup_discriminates_judges(embedder):
    model = tt_model()
    store = TrainingTableStore()
    prompt = "what is the answer?"
    query_vec = embedder.embed_texts([prompt])[0]
    rng = np.random.default_rng(0)
    near = np.stack([query_vec + 0.001 * rng.normal(size=query_vec.shape) for _ in range(5)])
    # judge 0: historically perfect on similar prompts; judge 1: terrible
    good, bad = model.llms[0], model.llms[1]
    store.add_rows(good.training_table_id, near, np.ones(5))
    store.add_rows(bad.training_table_id, near, np.zeros(5))
    fetcher = TpuTrainingTableFetcher(embedder, store)
    weights, _ = go(fetcher.fetch(None, params(prompt), model))
    w = {llm.index: weights[llm.index] for llm in model.llms}
    assert float(w[good.index]) == pytest.approx(5.0, abs=0.3)
    assert float(w[bad.index]) == pytest.approx(1.0, abs=0.3)
    # bounds respected
    assert all(Decimal(1) <= x <= Decimal(5) for x in weights)


def test_store_appends_rows():
    store = TrainingTableStore()
    store.add_rows("t1", np.ones((2, 4)), np.ones(2))
    store.add_rows("t1", np.zeros((3, 4)), np.zeros(3))
    emb, scores = store.get("t1")
    assert emb.shape == (5, 4) and scores.shape == (5,)
    assert store.get("missing") is None


# -- archive batch re-score ---------------------------------------------------


def test_archive_rescore_reweighting():
    import random

    from llm_weighted_consensus_tpu import archive, registry
    from llm_weighted_consensus_tpu.archive.rescore import (
        apply_rescore,
        rescore_archive,
        vote_matrix,
    )
    from llm_weighted_consensus_tpu.ballot import PrefixTree
    from llm_weighted_consensus_tpu.clients.chat import (
        ApiBase,
        BackoffPolicy,
        DefaultChatClient,
    )
    from llm_weighted_consensus_tpu.clients.score import ScoreClient
    import sys, os
    sys.path.insert(0, os.path.dirname(__file__))
    from fakes import FakeTransport, Script, chunk_obj

    SEED = 13
    rng = random.Random(SEED)
    tree = PrefixTree.build(rng, 2, 20)
    keys = {idx: k for k, idx in tree.key_indices(rng)}

    model = ModelBase.from_json_obj(
        {
            "llms": [
                {"model": "j-a", "weight": {"type": "static", "weight": 1}},
                {"model": "j-b", "weight": {"type": "static", "weight": 1}},
            ]
        }
    ).into_model_validate()
    order = [llm.base.model for llm in model.llms]
    by_model = {
        "j-a": Script([chunk_obj(f"pick {keys[0]}", model="j-a", finish="stop")]),
        "j-b": Script([chunk_obj(f"pick {keys[1]}", model="j-b", finish="stop")]),
    }
    transport = FakeTransport([by_model[m] for m in order])
    chat = DefaultChatClient(
        transport, [ApiBase("https://up.example", "k")],
        backoff=BackoffPolicy(max_elapsed_ms=0),
    )
    store = archive.InMemoryArchive()
    score = ScoreClient(
        chat, registry.InMemoryModelRegistry(), archive_fetcher=store,
        rng_factory=lambda: random.Random(SEED),
    )
    result = go(
        score.create_unary(
            None,
            ScoreParams.from_json_obj(
                {
                    "messages": [{"role": "user", "content": "q"}],
                    "model": {"llms": [llm.base.to_json_obj() for llm in model.llms]},
                    "choices": ["a", "b"],
                }
            ),
        )
    )
    store.put_score(result)

    # stored tie: 0.5 / 0.5
    votes, weights, mask = vote_matrix(result)
    assert votes.shape == (2, 2) and mask.tolist() == [1.0, 1.0]
    cand = {c.index: c for c in result.choices if c.index < 2}
    assert float(cand[0].confidence) == pytest.approx(0.5)

    # reweight judge a 3:1 and re-tally on device - no upstream requests
    a_id = next(llm.id for llm in model.llms if llm.base.model == "j-a")
    results = rescore_archive(store, weight_overrides={a_id: 3.0})
    conf = [float(x) for x in results[result.id]["confidence"]]
    assert conf[0] == pytest.approx(0.75) and conf[1] == pytest.approx(0.25)
    assert apply_rescore(store, results) == 1
    assert float(cand[0].confidence) == pytest.approx(0.75)


def test_archive_rescore_mesh_10k_shape():
    """config-4 shape: thousands of archived vote matrices, one mesh batch."""
    from llm_weighted_consensus_tpu.parallel import make_mesh
    from llm_weighted_consensus_tpu.parallel.batch import rescore_batch

    rng = np.random.default_rng(0)
    b, m, n = 2048, 8, 8
    votes = rng.random((b, m, n)).astype(np.float32)
    votes /= votes.sum(axis=2, keepdims=True)
    weights = rng.uniform(0.5, 2.0, (b, m)).astype(np.float32)
    mesh = make_mesh(dp=8, tp=1)
    _, conf = rescore_batch(votes, weights, mesh=mesh)
    assert conf.shape == (b, n)
    np.testing.assert_allclose(np.asarray(conf).sum(axis=1), 1.0, atol=1e-5)


# -- device soft-vote re-extraction (revote) ----------------------------------


def _soft_vote_archive(p0=0.7, seed=13):
    """One-judge (top_logprobs=2) score completion archived WITH its ballot:
    the judge's key token carries a {p0, 1-p0} top_logprobs distribution
    over the two sibling letters."""
    import math
    import random

    from llm_weighted_consensus_tpu import archive, registry
    from llm_weighted_consensus_tpu.ballot import PrefixTree
    from llm_weighted_consensus_tpu.ballot.tree import branch_limit
    from llm_weighted_consensus_tpu.clients.chat import (
        ApiBase,
        BackoffPolicy,
        DefaultChatClient,
    )
    from llm_weighted_consensus_tpu.clients.score import ScoreClient
    import sys, os
    sys.path.insert(0, os.path.dirname(__file__))
    from fakes import FakeTransport, Script, chunk_obj

    rng = random.Random(seed)
    tree = PrefixTree.build(rng, 2, branch_limit(2))
    pairs = tree.key_indices(rng)
    key0 = pairs[0][0]
    branch = tree.walk(key0)
    letters = list(branch)
    lp = {
        "content": [
            {"token": "`", "logprob": -0.01, "top_logprobs": []},
            {
                "token": key0[1],
                "logprob": math.log(p0),
                "top_logprobs": [
                    {"token": letters[0], "logprob": math.log(p0)},
                    {"token": letters[1], "logprob": math.log(1 - p0)},
                ],
            },
            {"token": "`", "logprob": -0.01, "top_logprobs": []},
        ]
    }
    transport = FakeTransport(
        [Script([chunk_obj(key0, finish="stop", logprobs=lp)])]
    )
    chat = DefaultChatClient(
        transport, [ApiBase("https://up.example", "k")],
        backoff=BackoffPolicy(max_elapsed_ms=0),
    )
    store = archive.InMemoryArchive()
    score = ScoreClient(
        chat, registry.InMemoryModelRegistry(), archive_fetcher=store,
        rng_factory=lambda: random.Random(seed),
        ballot_sink=store.put_ballot,
    )
    result = go(
        score.create_unary(
            None,
            ScoreParams.from_json_obj(
                {
                    "messages": [{"role": "user", "content": "q"}],
                    "model": {
                        "llms": [
                            {
                                "model": "judge-a",
                                "top_logprobs": 2,
                                "weight": {"type": "static", "weight": 1},
                            }
                        ]
                    },
                    "choices": ["a", "b"],
                }
            ),
        )
    )
    store.put_score(result)
    return store, result, branch, letters


def test_revote_matches_host_decimal_extraction():
    """Device re-extraction (softmax_votes over stored logprobs) must agree
    with the live host Decimal path that produced the stored votes."""
    from llm_weighted_consensus_tpu.archive.rescore import rescore_archive

    store, result, branch, letters = _soft_vote_archive(p0=0.7)
    judge = [c for c in result.choices if c.index >= 2][0]
    host_vote = [float(v) for v in judge.message.vote]
    assert sum(host_vote) == pytest.approx(1.0)

    results = rescore_archive(store, revote=True)
    conf = [float(x) for x in results[result.id]["confidence"]]
    np.testing.assert_allclose(conf, host_vote, atol=1e-6)


def test_revote_recomputes_from_tampered_logprobs():
    """revote re-derives votes from logprobs — it must track a changed
    distribution while the stored-vote path keeps the old one."""
    from llm_weighted_consensus_tpu.archive.rescore import rescore_archive

    store, result, branch, letters = _soft_vote_archive(p0=0.7)
    import math
    from decimal import Decimal

    judge = [c for c in result.choices if c.index >= 2][0]
    alts = judge.logprobs.content[1].top_logprobs
    alts[0].logprob = Decimal(str(math.log(0.2)))
    alts[1].logprob = Decimal(str(math.log(0.8)))

    stale = rescore_archive(store, revote=False)[result.id]["confidence"]
    fresh = rescore_archive(store, revote=True)[result.id]["confidence"]
    i0, i1 = branch[letters[0]], branch[letters[1]]
    assert float(stale[i0]) == pytest.approx(0.7, abs=1e-6)
    assert float(fresh[i0]) == pytest.approx(0.2, abs=1e-6)
    assert float(fresh[i1]) == pytest.approx(0.8, abs=1e-6)


def test_revote_without_ballots_falls_back_to_stored_votes():
    from llm_weighted_consensus_tpu.archive.rescore import rescore_archive

    store, result, *_ = _soft_vote_archive(p0=0.6)
    store._ballots.clear()  # simulate an archive without ballot records
    with_stored = rescore_archive(store, revote=False)[result.id]
    fallback = rescore_archive(store, revote=True)[result.id]
    assert [float(x) for x in fallback["confidence"]] == pytest.approx(
        [float(x) for x in with_stored["confidence"]]
    )


def test_revote_handles_tick_stripped_content():
    """A judge that answered without backtick quoting still re-extracts:
    find_key returns the stripped key and leaf_branch_of matches it by
    letter sequence (as the live tree.walk does)."""
    from llm_weighted_consensus_tpu.archive.rescore import rescore_archive

    store, result, branch, letters = _soft_vote_archive(p0=0.7)
    judge = [c for c in result.choices if c.index >= 2][0]
    judge.message.content = judge.message.content.replace("`", "")
    host_vote = [float(v) for v in judge.message.vote]

    results = rescore_archive(store, revote=True)
    conf = [float(x) for x in results[result.id]["confidence"]]
    np.testing.assert_allclose(conf, host_vote, atol=1e-6)


def test_archive_snapshot_round_trip(tmp_path):
    """save -> load preserves completions (Decimal-exact votes), ballots,
    and keeps device revote working from the reloaded store."""
    from llm_weighted_consensus_tpu import archive
    from llm_weighted_consensus_tpu.archive.rescore import rescore_archive

    store, result, branch, letters = _soft_vote_archive(p0=0.7)
    path = str(tmp_path / "archive.json")
    store.save(path)
    reloaded = archive.InMemoryArchive.load(path)

    assert reloaded.score_ids() == store.score_ids()
    orig = store._score[result.id]
    copy = reloaded._score[result.id]
    assert copy.to_json_obj() == orig.to_json_obj()
    judge = [c for c in copy.choices if c.index >= 2][0]
    # Decimal-exact vote round trip
    assert judge.message.vote == [
        c.message.vote for c in orig.choices if c.index >= 2
    ][0]
    assert reloaded.score_ballots(result.id) is not None

    before = rescore_archive(store, revote=True)[result.id]["confidence"]
    after = rescore_archive(reloaded, revote=True)[result.id]["confidence"]
    assert [float(x) for x in after] == pytest.approx(
        [float(x) for x in before]
    )


def test_archive_snapshot_rejects_unknown_version(tmp_path):
    from llm_weighted_consensus_tpu import archive

    path = str(tmp_path / "bad.json")
    with open(path, "w") as f:
        f.write('{"version": 99}')
    with pytest.raises(ValueError, match="version"):
        archive.InMemoryArchive.load(path)


def test_ballot_table_is_bounded():
    from llm_weighted_consensus_tpu import archive

    store = archive.InMemoryArchive()
    cap = store.MAX_BALLOT_COMPLETIONS
    for i in range(cap + 10):
        store.put_ballot(f"scrcpl-{i}", 0, [("`A`", 0), ("`B`", 1)])
    assert len(store._ballots) == cap
    # FIFO: oldest evicted, newest kept
    assert store.score_ballots("scrcpl-0") is None
    assert store.score_ballots(f"scrcpl-{cap + 9}") is not None


class _ScoreStub:
    def __init__(self, cid):
        self.id = cid


def test_ballot_eviction_prefers_unarchived():
    """FIFO eviction must never drop an ARCHIVED completion's ballots —
    those are exactly the ones revote still needs — and archived entries
    do not count against the orphan cap."""
    from llm_weighted_consensus_tpu import archive

    store = archive.InMemoryArchive()
    cap = store.MAX_BALLOT_COMPLETIONS
    store.put_ballot("scrcpl-keep", 0, [("`A`", 0)])
    store.put_score(_ScoreStub("scrcpl-keep"))  # archived
    for i in range(cap + 5):
        store.put_ballot(f"scrcpl-{i}", 0, [("`A`", 0)])
    assert store.score_ballots("scrcpl-keep") is not None
    # cap orphans + the archived one
    assert len(store._ballots) == cap + 1


# -- training-table learning from archived outcomes ---------------------------


def _panel_and_archive(embedder, votes_by_judge, prompt="what is 2+2?"):
    """Run one score request through the real client with J judges voting
    per ``votes_by_judge`` (list of candidate indices), archive completion
    + request, return (store, model, result)."""
    import random

    from llm_weighted_consensus_tpu import archive, registry
    from llm_weighted_consensus_tpu.ballot import PrefixTree
    from llm_weighted_consensus_tpu.clients.chat import (
        ApiBase,
        BackoffPolicy,
        DefaultChatClient,
    )
    from llm_weighted_consensus_tpu.clients.score import ScoreClient
    import sys, os
    sys.path.insert(0, os.path.dirname(__file__))
    from fakes import FakeTransport, Script, chunk_obj

    seed = 17
    rng = random.Random(seed)
    tree = PrefixTree.build(rng, 2, 20)
    keys = {idx: k for k, idx in tree.key_indices(rng)}

    model = ModelBase.from_json_obj(
        {
            "llms": [
                {
                    "model": f"learn-judge-{j}",
                    "weight": {
                        "type": "training_table",
                        "base_weight": 1,
                        "min_weight": 1,
                        "max_weight": 5,
                    },
                }
                for j in range(len(votes_by_judge))
            ],
            "weight": {
                "type": "training_table",
                "embeddings": {"model": "test-tiny", "max_tokens": 32},
                "top": 3,
            },
        }
    ).into_model_validate()
    # scripts are consumed in panel (sorted-by-id) order; map back to the
    # requested vote per judge name
    vote_by_name = {
        f"learn-judge-{j}": v for j, v in enumerate(votes_by_judge)
    }
    scripts = [
        Script(
            [
                chunk_obj(
                    f"pick {keys[vote_by_name[llm.base.model]]}",
                    model=llm.base.model,
                    finish="stop",
                )
            ]
        )
        for llm in model.llms
    ]
    transport = FakeTransport(scripts)
    chat = DefaultChatClient(
        transport, [ApiBase("https://up.example", "k")],
        backoff=BackoffPolicy(max_elapsed_ms=0),
    )
    from llm_weighted_consensus_tpu.weights import WeightFetchers

    store = archive.InMemoryArchive()
    score = ScoreClient(
        chat, registry.InMemoryModelRegistry(), archive_fetcher=store,
        rng_factory=lambda: random.Random(seed),
        weight_fetchers=WeightFetchers(
            training_table_fetcher=TpuTrainingTableFetcher(embedder)
        ),
    )
    params = ScoreParams.from_json_obj(
        {
            "messages": [{"role": "user", "content": prompt}],
            "model": {
                "llms": [llm.base.to_json_obj() for llm in model.llms],
                "weight": {
                    "type": "training_table",
                    "embeddings": {"model": "test-tiny", "max_tokens": 32},
                    "top": 3,
                },
            },
            "choices": ["four", "five"],
        }
    )
    result = go(score.create_unary(None, params))
    store.put_score(result)
    store.put_score_request(result.id, params)
    return store, model, result


def test_judge_alignment_scores_self_consistency_and_supervised(embedder):
    from llm_weighted_consensus_tpu.weights.learning import (
        judge_alignment_scores,
    )

    # 3 judges: two vote candidate 0, one votes candidate 1 -> conf 2/3, 1/3
    store, model, result = _panel_and_archive(embedder, [0, 0, 1])
    scores = judge_alignment_scores(result)
    by_name = {}
    for choice in result.choices:
        if choice.model_index is not None:
            llm = next(
                l for l in model.llms if l.id == choice.model
            )
            by_name[llm.base.model] = scores[choice.model_index]
    assert by_name["learn-judge-0"] == pytest.approx(2 / 3)
    assert by_name["learn-judge-1"] == pytest.approx(2 / 3)
    assert by_name["learn-judge-2"] == pytest.approx(1 / 3)

    # supervised: candidate 1 was actually correct
    supervised = judge_alignment_scores(result, label=1)
    by_name_sup = {}
    for choice in result.choices:
        if choice.model_index is not None:
            llm = next(l for l in model.llms if l.id == choice.model)
            by_name_sup[llm.base.model] = supervised[choice.model_index]
    assert by_name_sup["learn-judge-0"] == 0.0
    assert by_name_sup["learn-judge-2"] == 1.0


def test_populate_from_archive_closes_the_loop(embedder):
    """serve -> archive -> learn -> the next lookup weights majority judges
    above the dissenter."""
    import asyncio
    from decimal import Decimal

    from llm_weighted_consensus_tpu.weights.learning import (
        populate_from_archive,
    )
    from llm_weighted_consensus_tpu.weights.training_table import (
        TpuTrainingTableFetcher,
        TrainingTableStore,
    )

    prompt = "what is 2+2?"
    store, model, result = _panel_and_archive(
        embedder, [0, 0, 1], prompt=prompt
    )
    tables = TrainingTableStore()
    added = populate_from_archive(store, embedder, model, tables)
    assert added == 3  # one row per judge
    assert len(tables) == 3  # distinct training_table_ids... or fewer

    fetcher = TpuTrainingTableFetcher(embedder, tables)
    request = store.score_request(result.id)
    weights, _ = asyncio.new_event_loop().run_until_complete(
        fetcher.fetch(None, request, model)
    )
    by_name = {
        llm.base.model: float(weights[llm.index]) for llm in model.llms
    }
    assert by_name["learn-judge-0"] > by_name["learn-judge-2"]
    assert by_name["learn-judge-0"] == pytest.approx(
        1 + (5 - 1) * 2 / 3, abs=0.2
    )
    assert all(Decimal(1) <= w <= Decimal(5) for w in weights)


def test_training_table_store_snapshot_round_trip(tmp_path):
    from llm_weighted_consensus_tpu.weights.training_table import (
        TrainingTableStore,
    )

    store = TrainingTableStore()
    rng = np.random.default_rng(0)
    store.add_rows("t1", rng.random((3, 8)), np.asarray([0.1, 0.5, 0.9]))
    store.add_rows("t2", rng.random((2, 8)), np.asarray([1.0, 0.0]))
    path = str(tmp_path / "tables.npz")
    store.save(path)
    loaded = TrainingTableStore.load(path)
    assert len(loaded) == 2
    for tid in ("t1", "t2"):
        e0, s0 = store.get(tid)
        e1, s1 = loaded.get(tid)
        np.testing.assert_array_equal(e0, e1)
        np.testing.assert_array_equal(s0, s1)


def test_populate_is_idempotent(embedder):
    from llm_weighted_consensus_tpu.weights.learning import (
        populate_from_archive,
    )
    from llm_weighted_consensus_tpu.weights.training_table import (
        TrainingTableStore,
    )

    store, model, result = _panel_and_archive(embedder, [0, 1])
    tables = TrainingTableStore()
    assert populate_from_archive(store, embedder, model, tables) == 2
    # a second sync pass over the same archive adds nothing
    assert populate_from_archive(store, embedder, model, tables) == 0
    emb, scores = tables.get(model.llms[0].training_table_id)
    assert emb.shape[0] == 1


def test_weights_learn_endpoint_and_tables_snapshot(embedder, tmp_path):
    """POST /weights/learn over the live service: archive -> rows; the
    tables snapshot persists on shutdown and reloads."""
    import asyncio

    from aiohttp.test_utils import TestClient, TestServer

    from llm_weighted_consensus_tpu.serve import Config
    from llm_weighted_consensus_tpu.serve.__main__ import (
        ARCHIVE_KEY,
        TABLES_KEY,
        build_service,
    )
    from llm_weighted_consensus_tpu.utils import jsonutil
    from llm_weighted_consensus_tpu.weights.training_table import (
        TrainingTableStore,
    )

    seed_store, model, result = _panel_and_archive(embedder, [0, 0, 1])
    tables_path = str(tmp_path / "tables.npz")
    config = Config.from_env(
        {
            "EMBEDDER_MODEL": "test-tiny",
            "EMBEDDER_MAX_TOKENS": "32",
            "TABLES_PATH": tables_path,
        }
    )
    app = build_service(config, fake_upstream=True)
    # seed the service's archive with the externally-scored history
    app[ARCHIVE_KEY]._score.update(seed_store._score)
    app[ARCHIVE_KEY]._score_requests.update(seed_store._score_requests)

    body = jsonutil.dumps(
        {"model": {
            "llms": [llm.base.to_json_obj() for llm in model.llms],
            "weight": {
                "type": "training_table",
                "embeddings": {"model": "test-tiny", "max_tokens": 32},
                "top": 3,
            },
        }}
    )

    async def run():
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            resp = await client.post(
                "/weights/learn",
                data=body,
                headers={"content-type": "application/json"},
            )
            assert resp.status == 200
            assert (await resp.json())["rows_added"] == 3
            # idempotent second pass
            resp = await client.post(
                "/weights/learn",
                data=body,
                headers={"content-type": "application/json"},
            )
            assert (await resp.json())["rows_added"] == 0
            # malformed body is a clean 400
            resp = await client.post(
                "/weights/learn",
                data=b"{}",
                headers={"content-type": "application/json"},
            )
            assert resp.status == 400
        finally:
            await client.close()  # -> on_cleanup -> tables snapshot

    asyncio.new_event_loop().run_until_complete(run())
    assert len(app[TABLES_KEY]) == 3
    reloaded = TrainingTableStore.load(tables_path)
    assert len(reloaded) == 3
    # ingestion keys are table-scoped: one per judge table for this cid
    assert any(
        key.endswith(f"/{result.id}") for key in reloaded._ingested
    )


def test_fetcher_keeps_shared_empty_store(embedder):
    """Regression: an EMPTY shared store is falsy (len 0); the fetcher must
    still use it — learning populates it AFTER the fetcher is built."""
    from llm_weighted_consensus_tpu.weights.training_table import (
        TpuTrainingTableFetcher,
        TrainingTableStore,
    )

    shared = TrainingTableStore()
    fetcher = TpuTrainingTableFetcher(embedder, shared)
    assert fetcher.store is shared
    shared.add_rows("t", np.ones((1, 4)), np.ones(1))
    assert fetcher.store.get("t") is not None


def test_ballot_cap_never_starves_inflight_or_archived():
    """Saturation regression: with the cap full of ARCHIVED ballots, a new
    in-flight request's ballots must survive until its put_score."""
    from llm_weighted_consensus_tpu import archive

    store = archive.InMemoryArchive()
    cap = store.MAX_BALLOT_COMPLETIONS
    for i in range(cap):
        cid = f"scrcpl-{i}"
        store.put_ballot(cid, 0, [("`A`", 0)])
        store.put_score(_ScoreStub(cid))  # archived
    store.put_ballot("scrcpl-inflight", 0, [("`A`", 0)])
    assert store.score_ballots("scrcpl-inflight") is not None
    # archived ones all retained too (growth beyond cap is the archive's)
    assert store.score_ballots("scrcpl-0") is not None


def test_archived_ballots_beyond_cap_do_not_drain_inflight_orphans():
    """ADVICE r3: an archive holding more than MAX_BALLOT_COMPLETIONS
    archived-with-ballots completions must NOT put the eviction loop
    permanently over cap — concurrent in-flight requests' ballots all
    survive each other's put_ballot calls."""
    from llm_weighted_consensus_tpu import archive

    store = archive.InMemoryArchive()
    store.MAX_BALLOT_COMPLETIONS = 4  # instance override: cheap test
    for i in range(10):  # 10 archived-with-ballots > cap of 4
        cid = f"scrcpl-{i}"
        store.put_ballot(cid, 0, [("`A`", 0)])
        store.put_score(_ScoreStub(cid))
    # interleaved in-flight requests: under the old total-ballots cap,
    # every put_ballot here drained the OTHER request's orphan ballots
    store.put_ballot("scrcpl-a", 0, [("`A`", 0)])
    store.put_ballot("scrcpl-b", 0, [("`A`", 0)])
    store.put_ballot("scrcpl-a", 1, [("`B`", 1)])
    store.put_ballot("scrcpl-b", 1, [("`B`", 1)])
    assert store.score_ballots("scrcpl-a") == {0: [("`A`", 0)], 1: [("`B`", 1)]}
    assert store.score_ballots("scrcpl-b") == {0: [("`A`", 0)], 1: [("`B`", 1)]}
    assert len(store._ballots) == 12  # 10 archived + 2 orphans


def test_late_ballot_for_oldest_orphan_does_not_wedge_eviction():
    """ADVICE r3: when the FIFO front IS the in-flight completion (a late
    ballot for an old, still-orphaned completion), eviction must rotate
    past it and keep draining newer orphans instead of breaking while
    over cap."""
    from llm_weighted_consensus_tpu import archive

    store = archive.InMemoryArchive()
    store.put_ballot("scrcpl-x", 0, [("`A`", 0)])  # oldest orphan
    for i in range(5):
        store.put_ballot(f"scrcpl-y{i}", 0, [("`A`", 0)])
    # drop the cap below the live orphan count, then deliver a late
    # ballot for the FIFO-front completion
    store.MAX_BALLOT_COMPLETIONS = 4
    store.put_ballot("scrcpl-x", 1, [("`B`", 1)])
    # the in-flight front survives; the OLDEST other orphans were evicted
    assert store.score_ballots("scrcpl-x") is not None
    assert store.score_ballots("scrcpl-y0") is None
    assert store.score_ballots("scrcpl-y1") is None
    assert store.score_ballots("scrcpl-y4") is not None
    assert len(store._ballots) == 4


def test_second_panel_learns_from_same_archive(embedder):
    """Cross-panel learning semantics: a RE-WEIGHTED panel shares its
    judges' weight-invariant table ids (no duplicate rows, lookups just
    work); a panel with genuinely different judge configs gets its own
    tables populated from the same archived history."""
    from llm_weighted_consensus_tpu.weights.learning import (
        populate_from_archive,
    )
    from llm_weighted_consensus_tpu.weights.training_table import (
        TrainingTableStore,
    )

    def panel(extra=None):
        judges = []
        for j in range(2):
            judge = {
                "model": f"learn-judge-{j}",
                "weight": {
                    "type": "training_table",
                    "base_weight": 1,
                    "min_weight": 1,
                    "max_weight": 5,
                },
            }
            judge.update(extra or {})
            judges.append(judge)
        return ModelBase.from_json_obj(
            {
                "llms": judges,
                "weight": {
                    "type": "training_table",
                    "embeddings": {"model": "test-tiny", "max_tokens": 32},
                    "top": 3,
                },
            }
        ).into_model_validate()

    store, model_a, result = _panel_and_archive(embedder, [0, 1])
    tables = TrainingTableStore()
    assert populate_from_archive(store, embedder, model_a, tables) == 2

    # re-weighted panel: same judges, new weight bounds -> SAME table ids
    # (weight-invariant identity) -> nothing to re-learn, no duplicates
    reweighted = panel({"weight": {
        "type": "training_table", "base_weight": 2,
        "min_weight": 1, "max_weight": 5,
    }})
    # same tt ids (panels sort judges by full id, so compare as sets)
    assert {l.training_table_id for l in reweighted.llms} == {
        l.training_table_id for l in model_a.llms
    }
    assert populate_from_archive(store, embedder, reweighted, tables) == 0
    emb, _ = tables.get(model_a.llms[0].training_table_id)
    assert emb.shape[0] == 1  # still one row per judge

    # genuinely different judge config (temperature) -> new table ids ->
    # the same archived history is learned into the new tables (matched
    # via the archived request's inline panel, weight-invariant ids)
    hotter = panel({"temperature": 0.5})
    assert hotter.llms[0].training_table_id != model_a.llms[0].training_table_id
    # matching falls back to the ARCHIVED judges' ids, so these rows are
    # keyed by the archived tables (already ingested) -> 0 new rows; the
    # hotter panel's own tables stay empty because no archived judge
    # matches its config
    assert populate_from_archive(store, embedder, hotter, tables) == 0
    assert tables.get(hotter.llms[0].training_table_id) is None


def test_populate_duplicate_ids_and_failure_do_not_poison(embedder):
    from llm_weighted_consensus_tpu.weights.learning import (
        populate_from_archive,
    )
    from llm_weighted_consensus_tpu.weights.training_table import (
        TrainingTableStore,
    )

    store, model, result = _panel_and_archive(embedder, [0, 1])
    tables = TrainingTableStore()
    # duplicate ids in one call add rows once
    added = populate_from_archive(
        store, embedder, model, tables, ids=[result.id, result.id]
    )
    assert added == 2
    emb, _ = tables.get(model.llms[0].training_table_id)
    assert emb.shape[0] == 1

    # a failing add_rows must NOT mark anything ingested
    fresh = TrainingTableStore()
    # poison the table with wrong-dim rows so concatenate raises
    for llm in model.llms:
        fresh.add_rows(llm.training_table_id, np.ones((1, 3)), np.ones(1))
    with pytest.raises(ValueError):
        populate_from_archive(store, embedder, model, fresh)
    assert not any(
        key.endswith(f"/{result.id}") for key in fresh._ingested
    )
