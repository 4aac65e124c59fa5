"""Generator ``judge_panel``: POST /consensus ``scorer: judge`` requests, a
local judge panel over N sampled answers to one conversation.

A request: a conversation of ``prompt_words`` words, ``n`` candidates that
are variants of one answer (a share ``changed`` of each one's words replaced,
as sampled answers to one question resemble each other), and a panel of
``len(panel_weights)`` calls with ballot seeds drawn from the run's seed and
the mix's weights.  The mix's keys are ``generators/consensus.py``'s: ``n``
(one value), ``words`` (fixed) times ``jitter`` [lo, hi] gives each
candidate's length, the ``n`` stratified quantiles of the uniform distribution
on [lo, hi] x words in shuffled order, the same multiset in every request: the seed chooses the words, the order and the ballots, and
never how much work a request is (``generators/consensus.py`` says why).

Closed loop only: ``callers`` callers, ``pool_per_s`` requests generated per
second of window (an upper bound on what they can finish).

A call's length in tokens, as the program assembles it (models/judge.py):
[BOS], the conversation, the instruction's three words, per candidate its
key (```C``B`:``: 5 pieces at depth 2, 3 at depth 1) and its words, and the
answer's opening backtick.
"""

from __future__ import annotations

import math

import numpy as np

PATH = "/consensus"
KEEP = ("confidence", "ballots")
ALPHABET = "ABCDEFGHIJKLMNOPQRST"
INSTRUCTION_WORDS = 3  # "Select the response:"


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([int(seed), stream]))


def candidate_lengths(mix: dict, n: int) -> np.ndarray:
    if mix["words"]["kind"] != "fixed":
        raise ValueError(f"unknown length kind {mix['words']['kind']!r}")
    lo, hi = mix.get("jitter", [1.0, 1.0])
    strata = (np.arange(n) + 0.5) / n
    lengths = np.rint(int(mix["words"]["value"]) * (lo + (hi - lo) * strata))
    return np.maximum(1, lengths).astype(np.int64)


def _texts(mix: dict, n: int, vocab_words: int, rng) -> list:
    lengths = rng.permutation(candidate_lengths(mix, n))
    answer = rng.integers(0, vocab_words, size=int(lengths.max()))
    out = []
    for length in lengths:
        body = answer[:length].copy()
        flips = max(1, int(round(float(mix["changed"]) * length)))
        where = rng.choice(length, size=flips, replace=False)
        body[where] = rng.integers(0, vocab_words, size=flips)
        out.append(body)
    return out


def generate(mix: dict, seed: int, seconds: float, vocab_words: int) -> list:
    """Requests, each a dict with ``index``, ``caller``, ``turn``, ``n``,
    ``words`` (one int array a candidate), ``prompt`` (an int array) and
    ``panel`` ([(ballot seed, weight)])."""
    if mix["loop"] != "closed":
        raise ValueError("judge_panel generates closed loops only")
    callers = int(mix["callers"])
    count = math.ceil(float(mix["pool_per_s"]) * seconds / callers) * callers
    rng = _rng(seed, 31)
    (n,) = mix["n"]["values"]
    requests = []
    for i in range(count):
        seeds = rng.integers(0, 2**31 - 1, size=len(mix["panel_weights"]))
        requests.append(
            {
                "index": i,
                "caller": i % callers,
                "turn": i // callers,
                "n": n,
                "words": _texts(mix, n, vocab_words, rng),
                "prompt": rng.integers(0, vocab_words, size=int(mix["prompt_words"])),
                "panel": [
                    (int(s), float(w)) for s, w in zip(seeds, mix["panel_weights"])
                ],
            }
        )
    return requests


def warm_sample(mix: dict, seed: int, vocab_words: int) -> list:
    """One request of the mix's shape with words of its own."""
    return generate({**mix, "callers": 1, "pool_per_s": 1.0}, seed + 1, 1.0, vocab_words)


def render_text(words: np.ndarray) -> str:
    return " ".join([f"w{k}" for k in words.tolist()])


def render_body(req: dict) -> dict:
    return {
        "scorer": "judge",
        "input": [render_text(w) for w in req["words"]],
        "prompt": render_text(req["prompt"]),
        "panel": [{"seed": s, "weight": w} for s, w in req["panel"]],
    }


def request_tokens(req: dict, overhead: int) -> int:
    """One call's tokens: what the program pads to its sequence bucket.
    ``overhead`` is the configuration's: [BOS] and the opening backtick."""
    key_pieces = 3 if req["n"] <= len(ALPHABET) else 5
    words = sum(len(w) for w in req["words"])
    return (
        overhead + len(req["prompt"]) + INSTRUCTION_WORDS
        + req["n"] * key_pieces + words
    )


def well_formed(kept: dict, req: dict) -> bool:
    """N finite values summing to 1 within 1e-3, and a ballot a call, each
    with its seed, a key and its siblings' finite log-probabilities."""
    conf, ballots = kept.get("confidence"), kept.get("ballots")
    if not (
        isinstance(conf, list)
        and len(conf) == req["n"]
        and all(isinstance(c, float) and math.isfinite(c) for c in conf)
        and abs(sum(conf) - 1.0) <= 1e-3
        and isinstance(ballots, list)
        and len(ballots) == len(req["panel"])
    ):
        return False
    for ballot, (seed, _) in zip(ballots, req["panel"]):
        siblings = ballot.get("siblings") if isinstance(ballot, dict) else None
        if not (
            isinstance(siblings, dict)
            and siblings
            and ballot.get("seed") == seed
            and isinstance(ballot.get("key"), str)
            and all(
                isinstance(e, dict)
                and isinstance(e.get("logprob"), float)
                and math.isfinite(e["logprob"])
                and isinstance(e.get("candidate"), int)
                for e in siblings.values()
            )
        ):
            return False
    return True
