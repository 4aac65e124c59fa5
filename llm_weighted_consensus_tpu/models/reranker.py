"""Served reward-model re-ranking (``POST /consensus {"scorer": "rm"}``).

``TpuReranker`` mirrors ``TpuEmbedder``'s host<->device contract for the
DeBERTa reward model: tokenize candidates on host (unigram spm for real
checkpoints, hash fallback for shape work), run the disentangled-attention
encoder + reward head on device, and return softmax(reward/temperature) —
RM re-ranking as a drop-in consensus vote.  Wired into ``POST /consensus``
via ``{"scorer": "rm"}`` when the gateway has ``RM_MODEL`` configured.

Unlike the embedder there is no request micro-batching (the reward
softmax normalizes over exactly the request's candidates, so requests
cannot share a softmax) — concurrent RM requests ride the executor and
the device queue.  Temperature is traced (no recompile per user value);
the candidate count is a static shape, bounded by the gateway's
MAX_CONSENSUS_CANDIDATES.
"""

from __future__ import annotations

from functools import partial
from typing import Iterable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import deberta
from . import dispatch_seam as _seam
from .configs import DEBERTA_TEST_TINY, DEBERTA_V3_BASE, DebertaConfig
from .tokenizer import BaseTokenizer, load_tokenizer

RM_PRESETS = {
    "deberta-v3-base": DEBERTA_V3_BASE,
    "deberta-test-tiny": DEBERTA_TEST_TINY,
}


@partial(jax.jit, static_argnames=("config",))
def _reward_and_vote(params, ids, mask, temperature, config):
    """Fused reward + softmax vote: one dispatch per request."""
    rewards = deberta.reward(params, ids, mask, config)
    with jax.named_scope("rm_vote"):
        return jax.nn.softmax(rewards.astype(jnp.float32) / temperature)


class TpuReranker:
    """A DeBERTa reward model ready to re-rank candidate batches."""

    def __init__(
        self,
        model: str = "deberta-v3-base",
        *,
        params: Optional[dict] = None,
        config: Optional[DebertaConfig] = None,
        tokenizer: Optional[BaseTokenizer] = None,
        dtype=None,
        max_tokens: int = 512,
        seed: int = 0,
        quantize: str = "none",
    ) -> None:
        self.model_name = model
        self.config = config or RM_PRESETS[model]
        self.max_tokens = max_tokens
        if dtype is None:
            dtype = (
                jnp.bfloat16 if jax.default_backend() == "tpu" else jnp.float32
            )
        self.dtype = dtype
        self.tokenizer = tokenizer or load_tokenizer(
            vocab_size=self.config.vocab_size
        )
        if params is None:
            params = deberta.init_params(
                jax.random.PRNGKey(seed), self.config, dtype=dtype
            )
        # shared quantize entry point with TpuEmbedder (models/quant.py)
        from .quant import resolve_quantize

        self.config, params = resolve_quantize(self.config, params, quantize)
        self.params = params

    def tokenize(self, texts: Iterable[str]):
        from .embedder import _seq_bucket

        ids, mask = self.tokenizer.encode_batch(
            list(texts), self.max_tokens
        )
        # shrink to the content bucket like the embedder (bounds jit
        # specializations per (N, S-bucket)); multiples-of-16 buckets so
        # ~100-token (prompt + candidate) pairs stop paying pow2 padding
        # (the r4 serving-path cut, embedder._SEQ_BUCKETS)
        seq = _seq_bucket(int(mask.sum(axis=1).max(initial=1)), self.max_tokens)
        return ids[:, :seq], mask[:, :seq]

    def rerank_confidence(
        self,
        texts: list,
        prompt: Optional[str] = None,
        temperature: float = 1.0,
    ):
        """N candidate texts -> (confidence[N], token_count), confidence
        = softmax(reward / T) in one fused dispatch.

        ``prompt`` (the question being answered) is prepended to every
        candidate — reward models score (prompt, candidate) pairs."""
        if prompt:
            texts = [f"{prompt}\n{text}" for text in texts]
        ids, mask = self.tokenize(texts)
        # the dispatch label, like the embedder's ``vote1(n=..,s=..)``:
        # device time per (N, sequence bucket) in the ``roofline``
        # section of /metrics and under the ``device_dispatch`` phase
        label = f"rm_vote(n={ids.shape[0]},s={ids.shape[1]})"
        conf = _seam.dispatch(
            label,
            lambda: _reward_and_vote(
                self.params,
                jnp.asarray(ids),
                jnp.asarray(mask),
                float(temperature),
                self.config,
            ),
        )
        return np.asarray(conf), int(mask.sum())


def load_rm_params(path: str, config: DebertaConfig, dtype=None):
    """RM params from a local checkpoint: HF DeBERTa-v3 snapshot dir or
    weights file (``deberta.from_hf_weights``), or an orbax dir.

    Returns ``(params, head_loaded)`` — ``head_loaded`` is False when the
    checkpoint was encoder-only and the reward head had to random-init
    (serving such params is gated like any other synthetic state)."""
    import os

    from .loading import _HF_FILES, _is_orbax_dir, _load_state_dict

    if dtype is None:
        dtype = (
            jnp.bfloat16 if jax.default_backend() == "tpu" else jnp.float32
        )

    def from_state(state_path):
        state = _strip_deberta_prefix(_load_state_dict(state_path))
        return (
            deberta.from_hf_weights(state, config, dtype=dtype),
            "pooler.dense.weight" in state,
        )

    if os.path.isdir(path):
        for name in _HF_FILES:
            candidate = os.path.join(path, name)
            if os.path.exists(candidate):
                return from_state(candidate)
        if _is_orbax_dir(path):
            from .. import train

            like = deberta.init_params(
                jax.random.PRNGKey(0), config, dtype=dtype
            )
            # orbax checkpoints are written by train/ with the head
            # included — head_loaded by construction
            return train.load_checkpoint(path, like=like), True
        raise FileNotFoundError(
            f"{path!r} contains neither an HF weights file nor an orbax "
            "checkpoint"
        )
    if not os.path.exists(path):
        raise FileNotFoundError(path)
    return from_state(path)


def _strip_deberta_prefix(state: dict) -> dict:
    """HF task-model checkpoints prefix the backbone with ``deberta.``;
    head weights (pooler/classifier) stay unprefixed."""
    return {
        (key[len("deberta."):] if key.startswith("deberta.") else key): value
        for key, value in state.items()
    }
