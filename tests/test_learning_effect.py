"""End-to-end evidence that trained weights IMPROVE consensus accuracy
(VERDICT r3 item 4) — not just that the plumbing moves rows around.

Synthetic closed loop with planted judge reliabilities:

* two topics of prompts (distinct vocabulary, so their embeddings
  cluster);
* judge "alpha-expert" always votes the correct candidate on topic-alpha
  prompts and always the WRONG one on topic-beta; "beta-expert" is the
  mirror image;
* a supervised archive of scored completions is learned into training
  tables via ``populate_from_archive`` (the /weights/learn machinery);
* on HELD-OUT prompts, the learned per-judge weights must steer the
  production tally (ops.consensus.tally) to the planted truth strictly
  more often than static equal weights do — and stay inside each judge's
  [min_weight, max_weight] band.

Reference anchor: the weight seam this realizes,
score/completions/weight.rs:5-18,99-117 (lookup contract
model/mod.rs:278-429); row production is external in the reference, so
the closed-loop accuracy claim is this framework's own to prove.
"""

import asyncio

import numpy as np
import pytest

import jax

from llm_weighted_consensus_tpu.identity.model import ModelBase
from llm_weighted_consensus_tpu.models import configs
from llm_weighted_consensus_tpu.models.embedder import TpuEmbedder
from llm_weighted_consensus_tpu.types import score_request, score_response
from llm_weighted_consensus_tpu import archive

TOPIC_WORDS = {
    "alpha": "arithmetic sums integers count total add",
    "beta": "poetry meter rhyme stanza verse lyric",
}
CANDIDATES = ["four", "five"]


@pytest.fixture(scope="module", name="embedder")
def embedder_fixture():
    return TpuEmbedder(
        "test-tiny", config=configs.TEST_TINY, max_tokens=32, seed=1
    )


def make_panel():
    return ModelBase.from_json_obj(
        {
            "llms": [
                {
                    "model": name,
                    "weight": {
                        "type": "training_table",
                        "base_weight": 1,
                        "min_weight": 1,
                        "max_weight": 5,
                    },
                }
                for name in ("alpha-expert", "beta-expert")
            ],
            "weight": {
                "type": "training_table",
                "embeddings": {"model": "test-tiny", "max_tokens": 32},
                "top": 3,
            },
        }
    ).into_model_validate()


def prompt_text(topic: str, i: int) -> str:
    words = TOPIC_WORDS[topic].split()
    # vary the filler so every prompt embeds differently within its topic
    return (
        f"{topic} question {i}: " + " ".join(words[(i + j) % len(words)]
        for j in range(4))
    )


def judge_vote(judge_name: str, topic: str, correct: int) -> list:
    """Planted reliability: the expert of the topic votes the truth, the
    other expert votes the other candidate."""
    expert_topic = judge_name.split("-")[0]
    pick = correct if expert_topic == topic else 1 - correct
    return [1 if i == pick else 0 for i in range(len(CANDIDATES))]


def make_params(model, prompt: str):
    return score_request.ChatCompletionCreateParams.from_json_obj(
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
            "choices": list(CANDIDATES),
        }
    )


def archived_completion(cid: str, model, topic: str, correct: int):
    """A scored completion shaped like the score client's output:
    N candidate choices (model_index null) then one choice per judge
    (model = judge id, message.vote = the judge's vote vector)."""
    n = len(CANDIDATES)
    choices = [
        {
            "index": i,
            "message": {"role": "assistant", "content": text},
            "confidence": 1.0 / n,
            "model_index": None,
            "model": None,
        }
        for i, text in enumerate(CANDIDATES)
    ]
    for llm in model.llms:
        choices.append(
            {
                "index": n + llm.index,
                "message": {
                    "role": "assistant",
                    "content": "voted",
                    "vote": judge_vote(llm.base.model, topic, correct),
                },
                "model_index": llm.index,
                "model": llm.id,
            }
        )
    return score_response.ChatCompletion.from_json_obj(
        {
            "id": cid,
            "created": 0,
            "model": "panel",
            "object": "chat.completion",
            "choices": choices,
        }
    )


def build_archive(model, n_per_topic: int):
    store = archive.InMemoryArchive()
    labels = {}
    k = 0
    for topic in ("alpha", "beta"):
        for i in range(n_per_topic):
            correct = k % 2  # alternate so neither candidate is a prior
            cid = f"scrcpl-learn-{topic}-{i}"
            store.put_score(archived_completion(cid, model, topic, correct))
            store.put_score_request(
                cid, make_params(model, prompt_text(topic, i))
            )
            labels[cid] = correct
            k += 1
    return store, labels


def tally_top1(weights, votes) -> int:
    from llm_weighted_consensus_tpu.ops.consensus import tally

    _, confidence = tally(
        jax.numpy.asarray(votes, jax.numpy.float32),
        jax.numpy.asarray(weights, jax.numpy.float32),
    )
    return int(np.argmax(np.asarray(confidence)))


def evaluate_held_out(fetcher, model, n_train: int, per_topic: int = 12):
    """Held-out accuracy of learned vs static weights over both topics.

    Returns (learned_acc, static_acc, total, all_weights)."""
    loop = asyncio.new_event_loop()
    try:
        learned_hits = static_hits = total = 0
        all_weights = []
        ordered = sorted(model.llms, key=lambda l: l.index)
        for topic in ("alpha", "beta"):
            for i in range(n_train, n_train + per_topic):
                correct = total % 2
                params = make_params(model, prompt_text(topic, i))
                weights, _ = loop.run_until_complete(
                    fetcher.fetch(None, params, model)
                )
                all_weights.extend(weights)
                votes = [
                    judge_vote(llm.base.model, topic, correct)
                    for llm in ordered
                ]
                w = [float(weights[llm.index]) for llm in ordered]
                learned_hits += tally_top1(w, votes) == correct
                static_hits += tally_top1([1.0] * len(w), votes) == correct
                total += 1
    finally:
        loop.close()
    return learned_hits / total, static_hits / total, total, all_weights


def test_learned_weights_beat_static_on_held_out_prompts(embedder):
    from llm_weighted_consensus_tpu.weights.learning import (
        populate_from_archive,
    )
    from llm_weighted_consensus_tpu.weights.training_table import (
        TpuTrainingTableFetcher,
        TrainingTableStore,
    )

    model = make_panel()
    n_train = 40
    store, labels = build_archive(model, n_train)
    tables = TrainingTableStore()
    added = populate_from_archive(
        store, embedder, model, tables, labels=labels
    )
    assert added == 2 * 2 * n_train  # one row per judge per completion

    fetcher = TpuTrainingTableFetcher(embedder, tables)
    # held-out prompts: indices the training range never saw
    learned_acc, static_acc, total, all_weights = evaluate_held_out(
        fetcher, model, n_train
    )
    # the planted setup makes static weights a coin-flip (the two experts
    # always disagree, so equal weights tie); learned weights must
    # recover the per-topic expert and land (near-)perfect
    assert learned_acc > static_acc, (learned_acc, static_acc)
    assert learned_acc >= 0.9, learned_acc
    assert static_acc <= 0.6, static_acc
    # weights stay inside every judge's configured band
    from decimal import Decimal

    assert all(Decimal(1) <= w <= Decimal(5) for w in all_weights)


def test_learning_is_topic_conditional_not_global(embedder):
    """The learned weight for a judge must DEPEND on the prompt's topic —
    the alpha expert outweighs the beta expert on alpha prompts and vice
    versa.  (A global per-judge average would pass the accuracy test with
    a lucky panel; this pins the lookup's locality.)"""
    from llm_weighted_consensus_tpu.weights.learning import (
        populate_from_archive,
    )
    from llm_weighted_consensus_tpu.weights.training_table import (
        TpuTrainingTableFetcher,
        TrainingTableStore,
    )

    model = make_panel()
    store, labels = build_archive(model, 40)
    tables = TrainingTableStore()
    populate_from_archive(store, embedder, model, tables, labels=labels)
    fetcher = TpuTrainingTableFetcher(embedder, tables)
    by_name = {llm.base.model: llm.index for llm in model.llms}

    loop = asyncio.new_event_loop()
    try:
        for topic, expert in (("alpha", "alpha-expert"), ("beta", "beta-expert")):
            other = "beta-expert" if expert == "alpha-expert" else "alpha-expert"
            wins = 0
            for i in range(50, 58):  # held-out
                params = make_params(model, prompt_text(topic, i))
                weights, _ = loop.run_until_complete(
                    fetcher.fetch(None, params, model)
                )
                wins += float(weights[by_name[expert]]) > float(
                    weights[by_name[other]]
                )
            assert wins >= 7, (topic, wins)
    finally:
        loop.close()
