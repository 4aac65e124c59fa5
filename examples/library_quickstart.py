#!/usr/bin/env python
"""Library quickstart — the framework WITHOUT the HTTP gateway.

Shows the three layers a library consumer composes directly:

1. pure core        — wire types, the chunk-merge algebra, panel identity
2. consensus engine — ScoreClient over a (scripted) upstream transport
3. device core      — TpuEmbedder: texts -> consensus confidence on TPU
                      (CPU here; same code path on a chip)

Run:  python examples/library_quickstart.py
(Self-contained: fixes sys.path relative to this file and defaults to the
CPU backend.  Set LWC_QUICKSTART_PLATFORM to tour on real hardware
instead.)
"""

import asyncio
import os
import random
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tests"))


def _force_cpu() -> None:
    """Default the demo onto the CPU backend (before jax imports)."""
    if os.environ.get("LWC_QUICKSTART_PLATFORM"):
        return  # user explicitly wants real hardware
    os.environ["JAX_PLATFORMS"] = "cpu"


def pure_core() -> None:
    """Parse real OpenAI-shaped chunk JSON, fold -> unary, hash a panel."""
    from llm_weighted_consensus_tpu.identity.model import ModelBase
    from llm_weighted_consensus_tpu.types.base import fold_chunks
    from llm_weighted_consensus_tpu.types.chat_response import (
        ChatCompletionChunk,
    )

    chunks = [
        ChatCompletionChunk.from_json_obj(
            {
                "id": "c1",
                "object": "chat.completion.chunk",
                "created": 1,
                "model": "m",
                "choices": [
                    {"index": 0, "delta": {"role": "assistant", "content": part}}
                ],
            }
        )
        for part in ("The answer ", "is 42.")
    ]
    unary = fold_chunks(chunks)
    assert unary.choices[0].delta.content == "The answer is 42."
    print("pure core: fold(chunks) ->", unary.choices[0].delta.content)

    panel = ModelBase.from_json_obj(
        {"llms": [{"model": "judge-a"}, {"model": "judge-b", "weight": {"type": "static", "weight": 2}}]}
    ).into_model_validate()
    print("pure core: panel ids:", [llm.id for llm in panel.llms])


async def consensus_engine() -> None:
    """Score 2 candidates with a 1-judge panel over a scripted upstream."""
    from fakes import FakeTransport, Script, chunk_obj

    from llm_weighted_consensus_tpu import archive, registry
    from llm_weighted_consensus_tpu.ballot import PrefixTree
    from llm_weighted_consensus_tpu.clients.chat import (
        ApiBase,
        BackoffPolicy,
        DefaultChatClient,
    )
    from llm_weighted_consensus_tpu.clients.score import ScoreClient
    from llm_weighted_consensus_tpu.types.score_request import (
        ChatCompletionCreateParams,
    )

    seed = 11
    rng = random.Random(seed)
    tree = PrefixTree.build(rng, 2, 20)
    keys = {idx: k for k, idx in tree.key_indices(rng)}
    chat = DefaultChatClient(
        FakeTransport([Script([chunk_obj(f"I pick {keys[1]}", finish="stop")])]),
        [ApiBase("https://up.example", "key")],
        backoff=BackoffPolicy(max_elapsed_ms=0),
    )
    score = ScoreClient(
        chat,
        registry.InMemoryModelRegistry(),
        archive_fetcher=archive.InMemoryArchive(),
        rng_factory=lambda: random.Random(seed),
    )
    params = ChatCompletionCreateParams.from_json_obj(
        {
            "messages": [{"role": "user", "content": "what is 6*7?"}],
            "model": {"llms": [{"model": "judge-a"}]},
            "choices": ["41", "42"],
        }
    )
    result = await score.create_unary(None, params)
    confs = {c.index: c.confidence for c in result.choices if c.index < 2}
    print("consensus engine: per-candidate confidence:", confs)
    assert confs[1] == 1  # the scripted judge picked candidate 1


def device_core() -> None:
    """The device scorer: (a) the fused cosine-consensus vote on an
    explicit agreement cluster, (b) the embedder API end-to-end.

    No semantically trained checkpoint ships in this repo (the committed
    bge-micro golden is a reduced-vocab numeric-parity fixture), so (a)
    shows the vote math on hand-made embeddings — 3 agreeing candidates
    + 1 outlier — and (b) shows the texts-in/confidence-out API; point
    EMBEDDER-style weights (models/loading.py) at a real bge checkpoint
    and the cluster of paraphrases wins exactly like (a).
    """
    import numpy as np

    from llm_weighted_consensus_tpu.models.configs import TEST_TINY
    from llm_weighted_consensus_tpu.models.embedder import TpuEmbedder
    from llm_weighted_consensus_tpu.ops.similarity import (
        cosine_consensus_vote,
    )

    rng = np.random.default_rng(0)
    center = rng.normal(size=64)
    cluster = [center + 0.1 * rng.normal(size=64) for _ in range(3)]
    outlier = rng.normal(size=64)
    emb = np.stack(cluster + [outlier]).astype(np.float32)
    emb /= np.linalg.norm(emb, axis=1, keepdims=True)
    conf = np.asarray(cosine_consensus_vote(emb))
    print("device core: vote over 3-cluster + outlier:",
          [round(float(c), 3) for c in conf])
    assert conf.argmax() < 3 and conf[3] == conf.min()
    assert abs(float(conf.sum()) - 1.0) < 1e-3

    embedder = TpuEmbedder("test-tiny", config=TEST_TINY, max_tokens=32)
    conf2 = np.asarray(
        embedder.consensus_confidence(
            ["the answer is 42", "42 is the answer", "it comes to 42",
             "i refuse to answer"]
        )
    )
    print("device core: texts -> confidence (random-init weights):",
          [round(float(c), 3) for c in conf2])
    assert abs(float(conf2.sum()) - 1.0) < 1e-3


if __name__ == "__main__":
    _force_cpu()
    pure_core()
    asyncio.run(consensus_engine())
    device_core()
    print("quickstart complete")
