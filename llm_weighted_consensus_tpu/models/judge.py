"""A local judge panel: the host side of ``POST /consensus`` ``scorer: judge``.

The reference's judges are upstream chat models: each is shown a ballot (the
candidates under randomized prefix-tree keys, ``ballot/tree.py``), answers
with a key, and its ``top_logprobs`` at the key's last letter become its vote
(``ballot/vote.py``).  ``TpuJudge`` runs that protocol on the device: a panel
is a few calls of one causal decoder over the same candidates under
differently seeded ballots, and what an upstream judge's ``top_logprobs``
would have carried is read from the decoder's own head.

Seven decoders serve (``JUDGE_PRESETS``; the preset's configuration class
says which module): ``models/glm_moe.py`` runs ``glm-4.7-flash`` (latent
attention, every expert held), ``glm-5.2`` (a learned sparse selection in
front of latent attention, a share of the router's experts held) and
``dots3-note-prev`` (attention layers of TWO kinds told apart by the layer's
kind: full layers behind an indexer each, sliding layers of another geometry
over a window of 513 keys, a sigmoid gate a head, rescaled latents; a
windowed latent cache beside the three kinds the second has), and
``models/qwen3_next.py`` the fourth (gated delta-rule layers three to one
with gated full attention, a share held), and ``models/afmoe.py`` the fifth,
``trinity-large-preview`` (grouped-query attention of two kinds: sliding layers
that turn their heads over a window of 4096 keys, full layers that turn
nothing; an elementwise gate, four norms a layer; a windowed cache of keys and
values beside the whole-length one; a share held), and ``models/sambay.py`` the
sixth, ``phi-4-mini-flash-reasoning`` (a decoder that feeds a decoder: Mamba
layers and differential attention over a window, one full layer whose keys
eight layers read, gated memory units; no experts; its last layers run at the
row the panel reads and nowhere else), and ``models/falcon_h1.py`` the seventh,
``falcon-h1-34b-instruct`` (a Mamba-2 (SSD) mixer and grouped-query attention
side by side in every block, every product behind a published µP multiplier;
no experts; every LAYER keeps two kinds of cache at once).  The panel's
protocol is no part of any: ``judge_panel`` below is ONE jitted program over what a decoder
module gives,

  ``prefill(params, ids, config, lens=, tallies=)``  -> hidden [b, s, h] (or
      [b, 1, h]: the row at ``lens - 1`` alone, from a decoder that computed
      no other), a cache a layer (of whatever kind, or kinds, the layer keeps),
      pairs routed a sparse layer; what else it counted on the device goes into the
      ``tallies`` dict by name (``index_keys``: pairs chosen and causal pairs;
      ``window_keys``: pairs inside the sliding layers' bands and causal pairs;
      ``layer_positions``: (position, layer) pairs computed, and layers x slots;
      ``state_positions``: positions that moved a scan state, and layers x slots)
  ``decode_step(params, token, lens, caches, config)``  -> hidden [b, h]
  ``head_logprobs(params, hidden, config)``  -> [b, vocabulary] float32

beside ``init_params``, ``from_hf_weights``, ``quantize_dense``,
``experts_held(params, config)``,
``whole_bound_layers(load, config)`` (the host's count, from the pairs
routed, of the sparse layers that ran over their layout's whole bound) and
``expert_tiles(load, config)`` (likewise, the row tiles the sparse layers'
layouts laid and those of them that hold a pair).

A call's prompt, token by token (each piece goes through the tokenizer on
its own, so a candidate is tokenized once however many ballots show it):

  [BOS]  conversation  "Select the response:"  then per candidate, in the
  ballot's shuffled order,  "`K`:" candidate-text  and last the opening
  backtick of the answer.

The instruction is ``ballot.prompting.ballot_instruction``'s forced-output
form: decoding is constrained to the ballot's keys, so no key list is
spelled out.  The next token after the prompt is the key's first letter.
At depth 2 (more than 20 candidates) the likeliest first letter is decoded
and ONE step through the decoder's caches reads the second letter under the
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
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..ballot.prompting import ballot_instruction
from ..ballot.tree import ALPHABET, PrefixTree
from ..ops.votes import softmax_votes
from . import dispatch_seam as _seam
from . import afmoe, falcon_h1, glm_moe, qwen3_next, sambay
from .configs import (
    AFMOE_TEST_TINY, DOTS3_NOTE_PREV, DOTS3_TEST_TINY, FALCON_H1_34B_INSTRUCT,
    FALCON_H1_TEST_TINY, GLM_4_7_FLASH, GLM_5_2, GLM_DSA_TEST_TINY, GLM_TEST_TINY,
    PHI4FLASH_TEST_TINY, PHI_4_MINI_FLASH_REASONING, QWEN3_NEXT_80B_A3B,
    QWEN3_NEXT_TEST_TINY, TRINITY_LARGE_PREVIEW, AfmoeConfig, FalconH1Config,
    GlmMoeLiteConfig, Phi4FlashConfig, Qwen3NextConfig,
)
from .tokenizer import BaseTokenizer, load_tokenizer

JUDGE_PRESETS = {
    "glm-4.7-flash": GLM_4_7_FLASH,
    "glm-test-tiny": GLM_TEST_TINY,
    "glm-5.2": GLM_5_2,
    "glm-dsa-test-tiny": GLM_DSA_TEST_TINY,
    "dots3-note-prev": DOTS3_NOTE_PREV,
    "dots3-test-tiny": DOTS3_TEST_TINY,
    "qwen3-next-80b-a3b": QWEN3_NEXT_80B_A3B,
    "qwen3-next-test-tiny": QWEN3_NEXT_TEST_TINY,
    "trinity-large-preview": TRINITY_LARGE_PREVIEW,
    "afmoe-test-tiny": AFMOE_TEST_TINY,
    "phi-4-mini-flash-reasoning": PHI_4_MINI_FLASH_REASONING,
    "phi4flash-test-tiny": PHI4FLASH_TEST_TINY,
    "falcon-h1-34b-instruct": FALCON_H1_34B_INSTRUCT,
    "falcon-h1-test-tiny": FALCON_H1_TEST_TINY,
}
_DECODERS = {
    GlmMoeLiteConfig: glm_moe, Qwen3NextConfig: qwen3_next, AfmoeConfig: afmoe,
    Phi4FlashConfig: sambay, FalconH1Config: falcon_h1,
}
DEFAULT_PANEL = ((0, 1.0), (1, 1.0), (2, 1.0))  # (ballot seed, weight) a call
# a pair a decoder's ``prefill`` counted on the device (``tallies``) -> the two
# counters of /metrics ``judge`` it is summed into, dispatch by dispatch
_TALLIES = {
    "index_keys": ("index_keys_selected", "index_keys_causal"),
    "window_keys": ("window_keys_band", "window_keys_causal"),
    "layer_positions": ("layer_positions_run", "layer_positions_whole"),
    "state_positions": ("state_positions_moved", "state_positions_whole"),
}
MAX_PANEL = 8
_LETTERS = len(ALPHABET)


def decoder_of(config):
    """The module that runs a preset's configuration."""
    return _DECODERS[type(config)]


def _masked(logprobs, letter_ids, valid):
    """Vocabulary log-probabilities [b, V] at the letters' token ids [K];
    letters that are no sibling read -inf."""
    return jnp.where(valid, logprobs[:, letter_ids], -jnp.inf)


@partial(jax.jit, static_argnames=("decoder", "config", "depth"))
def judge_panel(
    params, ids, lens, letter_ids, first_valid, second_valid, *,
    decoder, config, depth: int,
):
    """A panel's calls in one program.  ids [b, s] right-padded prompts of
    ``lens`` tokens, each ending where the key begins.  ``letter_ids`` [K]
    are the key letters' token ids; ``first_valid`` [b, K] marks the letters
    of a ballot's first level, ``second_valid`` [b, K, K] the sibling letters
    under each first letter.  ``decoder`` is the module whose ``prefill``,
    ``decode_step`` and ``head_logprobs`` run ``config``.

    Per call: causal prefill (which leaves each layer's cache as it stands
    after token ``lens - 1``), the head at the last real position, the first
    level's masked log-probabilities; at depth 2 the likeliest letter is
    decoded (greedy), one step runs through the caches, and the head is read
    again under the chosen branch's mask.  ``votes`` [b, K] is
    ``softmax_votes`` over the last read, a distribution over the K letters;
    which candidate a letter selects is the host's to say, so one program
    serves every candidate count.
    """
    b = ids.shape[0]
    tallies: dict = {}
    hidden, caches, loads = decoder.prefill(params, ids, config, lens=lens, tallies=tallies)
    if hidden.shape[1] == 1:  # a decoder that ran its last layers at the row read alone
        last = hidden[:, 0]
    else:
        last = jnp.take_along_axis(hidden, (lens - 1)[:, None, None], axis=1)[:, 0]
    first = _masked(decoder.head_logprobs(params, last, config), letter_ids, first_valid)
    chosen = jnp.argmax(first, axis=1).astype(jnp.int32)
    out = {
        "first_logprobs": first,
        "chosen": chosen,
        "expert_load": jnp.stack(loads) if loads else jnp.zeros((0, 1), jnp.int32),
        **tallies,
    }
    read, valid = first, first_valid
    if depth == 2:
        with jax.named_scope("decode_step"):
            step = decoder.decode_step(params, letter_ids[chosen], lens, caches, config)
            valid = second_valid[jnp.arange(b), chosen]
            read = _masked(decoder.head_logprobs(params, step, config), letter_ids, valid)
        out["second_logprobs"] = read
    with jax.named_scope("ballot_vote"):
        letters = jnp.broadcast_to(jnp.arange(valid.shape[1]), valid.shape)
        out["votes"] = softmax_votes(
            jnp.where(valid, read, 0.0), jnp.where(valid, letters, -1), valid,
            valid.shape[1],
        )
    return out


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
        config=None,
        tokenizer: Optional[BaseTokenizer] = None,
        dtype=None,
        max_tokens: int = 8192,
        seed: int = 0,
        quantize: str = "none",
    ) -> None:
        self.model_name = model
        self.config = config or JUDGE_PRESETS[model]
        self.decoder = decoder_of(self.config)
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
            params = self.decoder.init_params(
                jax.random.PRNGKey(seed), self.config, dtype=dtype
            )
        if quantize not in ("none", "int8"):
            raise ValueError("JUDGE_QUANTIZE must be 'none' or 'int8'")
        if quantize == "int8":
            import dataclasses

            params = self.decoder.quantize_dense(params)
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
            "expert_load_max_over_mean_sum": 0.0,
            # pairs (token, choice) the routers made, those sent to an expert
            # held here and those sent elsewhere; ``expert_tokens`` is over
            # the held
            "expert_pairs_routed": 0,
            "expert_pairs_here": 0,
            "expert_pairs_elsewhere": 0,
            # sparse layers, summed over dispatches, whose tiles in use passed
            # the usual load's rows and ran over the layout's whole bound
            "expert_layers_whole_bound": 0,
            # row tiles, summed over sparse layers and dispatches, that the
            # layouts laid (a grid step each a column block of the experts'
            # kernels) and those of them that hold a pair
            "expert_tiles_laid": 0,
            "expert_tiles_in_use": 0,
            # (query, key) pairs, summed over dispatches and the layers that
            # own an indexer: those a query may see, and those it chose
            "index_keys_causal": 0,
            "index_keys_selected": 0,
            # ... and over the layers with a window: those a query may see,
            # and those inside its band
            "window_keys_causal": 0,
            "window_keys_band": 0,
            # (position, layer) pairs, summed over dispatches' prefills: those a
            # decoder computed (one that runs its last layers at the row read
            # alone), and layers x slots
            "layer_positions_run": 0,
            "layer_positions_whole": 0,
            # positions, summed over dispatches and the layers that keep a scan
            # state: those that moved it (a padded slot moves none), and
            # layers x slots
            "state_positions_moved": 0,
            "state_positions_whole": 0,
            "expert_tokens": [0] * self.decoder.experts_held(params, self.config),
        }
        self._held = len(self._stats["expert_tokens"])

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
        return judge_panel(
            self.params,
            jnp.asarray(ids),
            jnp.asarray(lens),
            self._letter_ids_dev,
            jnp.asarray(first_valid),
            jnp.asarray(second_valid),
            decoder=self.decoder,
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
        self._count(
            prepared, np.asarray(out["expert_load"]),
            {name: out[name] for name in _TALLIES if name in out},
        )
        return confidence, prepared.tokens, ballots

    def _count(self, prepared: PreparedPanel, load, tallies: dict) -> None:
        # a decoder that holds a share of its router's experts counts, after
        # the held ones, the pairs routed elsewhere
        whole_bound = self.decoder.whole_bound_layers(load, self.config)
        tiles_laid, tiles_in_use = self.decoder.expert_tiles(load, self.config)
        elsewhere = int(load[:, self._held:].sum()) if load.size else 0
        load = load[:, :self._held] if load.size else load
        ratio = 0.0
        if load.size and load.sum():
            # a dispatch's largest load over its mean, the worst layer's
            ratio = float((load.max(axis=1) / load.mean(axis=1)).max())
        with self._lock:
            s = self._stats
            s["dispatches"] += 1
            s["calls"] += len(prepared.calls)
            s["prefill_tokens"] += prepared.tokens
            s["padded_tokens"] += prepared.ids.size - prepared.tokens
            s["expert_load_max_over_mean_sum"] += ratio
            s["expert_pairs_elsewhere"] += elsewhere
            s["expert_pairs_routed"] += elsewhere
            s["expert_layers_whole_bound"] += whole_bound
            s["expert_tiles_laid"] += tiles_laid
            s["expert_tiles_in_use"] += tiles_in_use
            for name, pair in tallies.items():
                for key, counted in zip(_TALLIES[name], np.asarray(pair)):
                    s[key] += int(counted)
            if load.size:
                totals = load.sum(axis=0)
                s["expert_pairs_here"] += int(totals.sum())
                s["expert_pairs_routed"] += int(totals.sum())
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
        return {"judge_panel": judge_panel._cache_size()}

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


def load_judge_params(path: str, config, dtype=None):
    """(params, config) from an HF checkpoint, one file or sharded
    (``loading.open_checkpoint``); the depth served is the checkpoint's, and
    so is the share of a wider router's experts."""
    from .loading import open_checkpoint

    if dtype is None:
        dtype = jnp.bfloat16 if jax.default_backend() == "tpu" else jnp.float32
    return decoder_of(config).from_hf_weights(open_checkpoint(path), config, dtype=dtype)
