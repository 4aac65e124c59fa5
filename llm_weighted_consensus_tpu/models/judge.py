"""A local judge panel: the host side of ``POST /consensus`` ``scorer: judge``.

The reference's judges are upstream chat models: each is shown a ballot (the
candidates under randomized prefix-tree keys, ``ballot/tree.py``), answers
with a key, and its ``top_logprobs`` at the key's last letter become its vote
(``ballot/vote.py``).  ``TpuJudge`` runs that protocol on the device: a panel
is a few calls of one causal decoder (``models/glm_moe.py``) over the same
candidates under differently seeded ballots, and what an upstream judge's
``top_logprobs`` would have carried is read from the decoder's own head.

A call's prompt, token by token (each piece goes through the tokenizer on
its own, so a candidate is tokenized once however many ballots show it):

  [BOS]  conversation  "Select the response:"  then per candidate, in the
  ballot's shuffled order,  "`K`:" candidate-text  and last the opening
  backtick of the answer.

The instruction is ``ballot.prompting.ballot_instruction``'s forced-output
form: decoding is constrained to the ballot's keys, so no key list is
spelled out.  The next token after the prompt is the key's first letter.
At depth 2 (more than 20 candidates) the likeliest first letter is decoded
and ONE step through the latent cache reads the second letter under the
chosen branch's mask; the backticks between a key's letters are the
grammar's, not the model's, and are not decoded.

Every call is padded to ONE sequence bucket, ``max_tokens``: a panel is one
device program of static shape (calls x max_tokens), whatever the number of
candidates.  The program is compiled for the default panel before the server
listens.
"""

from __future__ import annotations

import random
import threading
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..ballot.prompting import ballot_instruction
from ..ballot.tree import ALPHABET, PrefixTree
from ..ops import causal_attention
from . import dispatch_seam as _seam
from . import glm_moe
from .configs import GLM_4_7_FLASH, GLM_TEST_TINY, GlmMoeLiteConfig
from .tokenizer import BaseTokenizer, load_tokenizer

JUDGE_PRESETS = {
    "glm-4.7-flash": GLM_4_7_FLASH,
    "glm-test-tiny": GLM_TEST_TINY,
}
DEFAULT_PANEL = ((0, 1.0), (1, 1.0), (2, 1.0))  # (ballot seed, weight) a call
MAX_PANEL = 8
_LETTERS = len(ALPHABET)


class _Call:
    """One call's ballot: its seed and weight, the tree, the candidates in
    presentation order under their keys."""

    __slots__ = ("seed", "weight", "tree", "key_indices")

    def __init__(self, seed: int, weight: float, n: int) -> None:
        rng = random.Random(seed)
        self.seed, self.weight = seed, weight
        self.tree = PrefixTree.build(rng, n, _LETTERS)
        self.key_indices = self.tree.key_indices(rng)


class PreparedPanel:
    """A panel's host work, done before it reaches the device: ids [calls,
    max_tokens], their lengths, the ballots and their letter masks."""

    __slots__ = (
        "calls", "n", "depth", "ids", "lens", "first_valid", "second_valid",
        "tokens",
    )


class TpuJudge:
    """A causal sparse-expert decoder ready to judge candidate sets."""

    def __init__(
        self,
        model: str = "glm-4.7-flash",
        *,
        params: Optional[dict] = None,
        config: Optional[GlmMoeLiteConfig] = None,
        tokenizer: Optional[BaseTokenizer] = None,
        dtype=None,
        max_tokens: int = 8192,
        seed: int = 0,
        quantize: str = "none",
    ) -> None:
        self.model_name = model
        self.config = config or JUDGE_PRESETS[model]
        if max_tokens % 8:
            raise ValueError("JUDGE_MAX_TOKENS must be a multiple of 8")
        self.max_tokens = int(max_tokens)
        if dtype is None:
            dtype = (
                jnp.bfloat16 if jax.default_backend() == "tpu" else jnp.float32
            )
        self.tokenizer = tokenizer or load_tokenizer(
            vocab_size=self.config.vocab_size
        )
        if params is None:
            params = glm_moe.init_params(
                jax.random.PRNGKey(seed), self.config, dtype=dtype
            )
        if quantize not in ("none", "int8"):
            raise ValueError("JUDGE_QUANTIZE must be 'none' or 'int8'")
        if quantize == "int8":
            import dataclasses

            params = glm_moe.quantize_dense(params)
            self.config = dataclasses.replace(self.config, quantize="int8")
        self.params = params
        self.device_timing = True
        # the key letters as the tokens that follow an opening backtick
        self.letter_ids = np.asarray(
            [self.encode(f"`{letter}")[-1] for letter in ALPHABET], np.int32
        )
        head = ballot_instruction("\x00", [], "json_schema").split("\x00")[0]
        self._head_ids = self.encode(head)
        self._open_ids = self.encode("`")
        self._letter_ids_dev = jnp.asarray(self.letter_ids)
        self._keys: dict = {}
        # counters of the ``judge`` section of /metrics; dispatches finish
        # on the batcher's waiter threads
        self._lock = threading.Lock()
        self._stats = {
            "dispatches": 0,
            "calls": 0,
            "prefill_tokens": 0,
            "padded_tokens": 0,
            "attention_work_over_causal": 0.0,
            "expert_load_max_over_mean_sum": 0.0,
            "expert_tokens": [0] * self.config.n_routed_experts,
        }

    # -- host ---------------------------------------------------------------

    def encode(self, text: str) -> list:
        """Token ids of ``text`` alone: no [CLS]/[SEP], never truncated (a
        text has at most a token a character; the native tokenizers size
        their output buffer by the cap they are given)."""
        return list(self.tokenizer._encode(text, len(text) + 2))[1:-1]

    def _key_ids(self, key: str) -> list:
        """A ballot entry's key, "`C``B`:", as tokens; there are 420 keys."""
        ids = self._keys.get(key)
        if ids is None:
            ids = self._keys[key] = self.encode(key + ":")
        return ids

    def prepare(self, texts: list, prompt: Optional[str], panel=None) -> PreparedPanel:
        """Tokenize once a candidate, build each call's ballot and prompt."""
        panel = tuple(panel) if panel else DEFAULT_PANEL
        n = len(texts)
        bos = [getattr(self.tokenizer, "cls_id", 0)]
        conversation = self.encode(prompt) if prompt else []
        candidates = [self.encode(text) for text in texts]
        out = PreparedPanel()
        out.n = n
        out.calls = [_Call(int(seed), float(weight), n) for seed, weight in panel]
        out.depth = out.calls[0].tree.depth
        if out.depth > 2:
            raise ValueError("a ballot deeper than two letters is not served")
        b = len(out.calls)
        pad = getattr(self.tokenizer, "pad_id", 0)
        out.ids = np.full((b, self.max_tokens), pad, np.int32)
        out.lens = np.zeros((b,), np.int32)
        out.first_valid = np.zeros((b, _LETTERS), bool)
        out.second_valid = np.zeros((b, _LETTERS, _LETTERS), bool)
        for i, call in enumerate(out.calls):
            row = bos + conversation + self._head_ids
            for key, index in call.key_indices:
                row += self._key_ids(key) + candidates[index]
            row += self._open_ids
            if len(row) > self.max_tokens:
                raise ValueError(
                    f"a judge call of {len(row)} tokens exceeds "
                    f"JUDGE_MAX_TOKENS={self.max_tokens}"
                )
            out.ids[i, : len(row)] = row
            out.lens[i] = len(row)
            for letter, node in call.tree.root.items():
                j = ALPHABET.index(letter)
                out.first_valid[i, j] = True
                if isinstance(node, dict):
                    for sibling in node:
                        out.second_valid[i, j, ALPHABET.index(sibling)] = True
        out.tokens = int(out.lens.sum())
        return out

    def label(self, prepared: PreparedPanel) -> str:
        """The dispatch label, read like the embedder's: n rows of s tokens."""
        return f"judge(n={len(prepared.calls)},s={self.max_tokens})"

    # -- device -------------------------------------------------------------

    def _run(self, ids, lens, first_valid, second_valid, depth: int):
        return glm_moe.judge_panel(
            self.params,
            jnp.asarray(ids),
            jnp.asarray(lens),
            self._letter_ids_dev,
            jnp.asarray(first_valid),
            jnp.asarray(second_valid),
            config=self.config,
            depth=depth,
        )

    def dispatch(self, prepared: PreparedPanel):
        """Enqueue the panel's program (``dispatch_seam.dispatch``: deferred
        under the batcher's sink, blocking for a direct caller)."""
        return _seam.dispatch(
            self.label(prepared),
            lambda: self._run(
                prepared.ids, prepared.lens, prepared.first_valid,
                prepared.second_valid, prepared.depth,
            ),
            timed=self.device_timing,
        )

    def finalize(self, prepared: PreparedPanel, out) -> tuple:
        """Device outputs -> (confidence [N], prompt tokens, ballots)."""
        first = np.asarray(out["first_logprobs"], np.float64)
        chosen = np.asarray(out["chosen"])
        votes = np.asarray(out["votes"], np.float64)  # over the letters read
        second = (
            np.asarray(out["second_logprobs"], np.float64)
            if prepared.depth == 2
            else None
        )
        n = prepared.n
        tally = np.zeros((n,), np.float64)
        ballots = []
        for i, call in enumerate(prepared.calls):
            root = call.tree.root
            letter = ALPHABET[int(chosen[i])]
            branch = root[letter] if prepared.depth == 2 else root
            read = second[i] if prepared.depth == 2 else first[i]
            vote = np.zeros((n,), np.float64)
            siblings = {}
            for sibling, candidate in branch.items():
                j = ALPHABET.index(sibling)
                vote[candidate] = votes[i, j]
                siblings[sibling] = {
                    "logprob": float(read[j]), "candidate": int(candidate)
                }
            tally += vote * call.weight
            best = max(siblings, key=lambda s: siblings[s]["logprob"])
            entry = {"seed": call.seed, "weight": call.weight}
            if prepared.depth == 2:
                entry["first"] = {
                    key: float(first[i, ALPHABET.index(key)]) for key in root
                }
                entry["key"] = f"`{letter}``{best}`"
            else:
                entry["key"] = f"`{best}`"
            entry["siblings"] = siblings
            ballots.append(entry)
        confidence = tally / sum(call.weight for call in prepared.calls)
        self._count(prepared, np.asarray(out["expert_load"]))
        return confidence, prepared.tokens, ballots

    def _count(self, prepared: PreparedPanel, load) -> None:
        ratio = 0.0
        if load.size and load.sum():
            # a dispatch's largest load over its mean, the worst layer's
            ratio = float((load.max(axis=1) / load.mean(axis=1)).max())
        # what the attention kernel's schedule multiplies over what the
        # causal mask keeps: a number of the bucket
        slots = prepared.ids.shape[1]
        block = causal_attention.block_for(slots)
        work = causal_attention.work_over_causal(slots, block, block)
        with self._lock:
            s = self._stats
            s["dispatches"] += 1
            s["calls"] += len(prepared.calls)
            s["prefill_tokens"] += prepared.tokens
            s["padded_tokens"] += prepared.ids.size - prepared.tokens
            # the dispatches' mean
            s["attention_work_over_causal"] += (
                work - s["attention_work_over_causal"]
            ) / s["dispatches"]
            s["expert_load_max_over_mean_sum"] += ratio
            if load.size:
                totals = load.sum(axis=0)
                s["expert_tokens"] = [
                    a + int(b) for a, b in zip(s["expert_tokens"], totals)
                ]

    def judge(self, texts: list, prompt: Optional[str] = None, panel=None):
        """Direct (unbatched) entry: texts -> (confidence, tokens, ballots)."""
        prepared = self.prepare(texts, prompt, panel)
        return self.finalize(prepared, self.dispatch(prepared))

    def warmup(self) -> float:
        """Compile and run once the default panel's program at depth 2;
        seconds taken."""
        t0 = time.perf_counter()
        b = len(DEFAULT_PANEL)
        ids = np.zeros((b, self.max_tokens), np.int32)
        lens = np.full((b,), 2, np.int32)
        valid = np.ones((b, _LETTERS), bool)
        _seam.wait_device_ready(
            self._run(ids, lens, valid, np.ones((b, _LETTERS, _LETTERS), bool), 2)
        )
        return time.perf_counter() - t0

    # -- introspection --------------------------------------------------------

    def jit_stats(self) -> dict:
        return {"judge_panel": glm_moe.judge_panel._cache_size()}

    def stats(self) -> dict:
        """The ``judge`` section of /metrics."""
        with self._lock:
            return {
                "model": self.model_name,
                "layers": self.config.num_layers,
                "max_tokens": self.max_tokens,
                **self._stats,
                "expert_tokens": list(self._stats["expert_tokens"]),
            }


def load_judge_params(path: str, config: GlmMoeLiteConfig, dtype=None):
    """(params, config) from an HF checkpoint, one file or sharded
    (``loading.open_checkpoint``); the depth served is the checkpoint's."""
    from .loading import open_checkpoint

    if dtype is None:
        dtype = jnp.bfloat16 if jax.default_backend() == "tpu" else jnp.float32
    return glm_moe.from_hf_weights(open_checkpoint(path), config, dtype=dtype)
