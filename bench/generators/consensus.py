"""The one traffic generator: POST /consensus requests from a mix's parameters.

A mix is a data file under ``bench/traffic/``; this module turns it, a seed and
a window length into a schedule.  Everything that sets how much WORK a run does
(how many requests, their candidate counts and lengths, the gaps between
arrivals) is a fixed multiset worked out from the mix alone, by stratified
quantiles and not by drawing: the seed only shuffles which request comes when
and chooses the words; the arrival times themselves are the mix's.  So two seeds offer the same load in another order, and runs differ by
the system's noise and not by the generator's.

Parameters (all in the mix's file):

  loop        "open" | "closed"
  rate        open: requests per second (the count is round(rate * seconds))
  arrivals    open: {"kind": "poisson"},
              {"kind": "bursty", "mean_burst": 4, "within_ms": 10}, or
              {"kind": "poisson_bursts", "every_s": 25, "size": 12,
              "within_ms": 10}: Poisson arrivals, and every ``every_s``
              seconds (first at half of it) ``size`` requests together;
              ``rate`` counts both
  callers     closed: concurrent callers; each owns a list of requests
  pool_per_s  closed: requests generated per second of window (an upper bound
              on what the callers can finish; a caller that runs out stops)
  n           {"values": [8, 32, 64], "weights": [1, 2, 1]}
  words       a request's base length in words: {"kind": "fixed", "value": 118}
              or {"kind": "lognormal", "median": 180, "sigma": 0.5,
              "min": 48, "max": 480}
  jitter      per-candidate length factor, uniform [lo, hi] (default [1, 1])
  candidate_words  instead of words x jitter: each candidate's own length, a
              spec like ``words``; every request holds the same N quantiles
  cap_words   ceiling on a candidate's length after jitter
  preamble    share of each candidate that is the request's shared preamble
  changed     share of the answer's words changed per candidate
  scorer      "cosine" (default) | "rm";  prompt_words: length of the prompt
  warm_groups read by the harness, not here: the group sizes it makes the
              batcher dispatch once before the window (bench/run.py)

What the harness takes from a generator besides ``generate`` (bench/run.py
names no route and no field of an answer): ``PATH``, the route every request
of a mix is posted to; ``KEEP``, the fields of a 200 answer the load generator
keeps; ``well_formed(kept, req)``; ``request_tokens(req, overhead)``, a
request's size as the program's sequence bucket sees it; ``warm_sample``, a
few requests of the mix's shapes for warming; ``blocker(body)``, the same
request under another grouping key of the batcher (only a mix with
``warm_groups`` needs it); ``render_body`` and ``render_text``.
"""

from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np

PATH = "/consensus"
KEEP = ("confidence",)
BLOCKER_TEMPERATURE = 0.051  # another grouping key than the default 0.05


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([int(seed), stream]))


def _strata(count: int) -> np.ndarray:
    """Mid-points of ``count`` equal slices of (0, 1)."""
    return (np.arange(count) + 0.5) / count


def exponential_gaps(count: int, total: float) -> np.ndarray:
    """``count`` gaps with the exponential distribution's quantiles, scaled to
    sum to ``total``: a Poisson process's gaps without the draw."""
    gaps = -np.log1p(-_strata(count))
    return gaps * (total / gaps.sum())


def geometric_sizes(bursts: int, total: int, mean: float) -> np.ndarray:
    """``bursts`` geometric burst sizes (mean ``mean``) by quantile, nudged so
    that they sum to ``total``."""
    p = 1.0 / mean
    sizes = np.maximum(
        1, np.ceil(np.log1p(-_strata(bursts)) / math.log1p(-p))
    ).astype(np.int64)
    diff = int(total - sizes.sum())
    order = np.argsort(-sizes)
    i = 0
    while diff != 0:
        j = order[i % bursts]
        if diff > 0:
            sizes[j] += 1
            diff -= 1
        elif sizes[j] > 1:
            sizes[j] -= 1
            diff += 1
        i += 1
    return sizes


def base_lengths(spec: dict, count: int) -> np.ndarray:
    if spec["kind"] == "fixed":
        return np.full(count, int(spec["value"]), dtype=np.int64)
    if spec["kind"] == "lognormal":
        normal = NormalDist()
        z = np.array([normal.inv_cdf(float(u)) for u in _strata(count)])
        x = float(spec["median"]) * np.exp(float(spec["sigma"]) * z)
        return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)
    raise ValueError(f"unknown length kind {spec['kind']!r}")


def candidate_counts(spec: dict, count: int) -> np.ndarray:
    values = np.asarray(spec["values"], dtype=np.int64)
    weights = np.asarray(spec.get("weights", [1] * len(values)), dtype=float)
    edges = np.cumsum(weights / weights.sum())
    return values[np.minimum(np.searchsorted(edges, _strata(count)), len(values) - 1)]


def arrival_times(mix: dict, count: int, seconds: float) -> np.ndarray:
    """Due times in [0, seconds), open loop: a function of the mix alone
    (``schedule_seed``), the same for every run seed.  The order of the gaps
    decides where arrivals cluster and so what the tail waits for; shuffled
    by the run seed, the 95th percentile swung with the seed and not with the
    system."""
    arrivals = mix.get("arrivals", {"kind": "poisson"})
    rng = _rng(int(mix.get("schedule_seed", 0)), 11)
    if arrivals["kind"] == "poisson":
        gaps = rng.permutation(exponential_gaps(count, seconds))
        return np.cumsum(gaps) - gaps[0]
    if arrivals["kind"] == "bursty":
        bursts = max(1, round(count / float(arrivals["mean_burst"])))
        sizes = rng.permutation(
            geometric_sizes(bursts, count, float(arrivals["mean_burst"]))
        )
        gaps = rng.permutation(exponential_gaps(bursts, seconds))
        starts = np.cumsum(gaps) - gaps[0]
        within = float(arrivals["within_ms"]) / 1e3
        due = np.concatenate(
            [
                start + np.sort(rng.uniform(0.0, within, size=int(size)))
                for start, size in zip(starts, sizes)
            ]
        )
        return np.minimum(np.sort(due), np.nextafter(seconds, 0.0))
    if arrivals["kind"] == "poisson_bursts":
        every, size = float(arrivals["every_s"]), int(arrivals["size"])
        starts = np.arange(every / 2.0, seconds, every)
        starts = starts[: max(0, (count - 1) // size)]
        singles = count - size * len(starts)
        gaps = rng.permutation(exponential_gaps(singles, seconds))
        within = float(arrivals["within_ms"]) / 1e3
        due = [np.cumsum(gaps) - gaps[0]]
        due += [start + np.sort(rng.uniform(0.0, within, size=size)) for start in starts]
        return np.minimum(np.sort(np.concatenate(due)), np.nextafter(seconds, 0.0))
    raise ValueError(f"unknown arrivals kind {arrivals['kind']!r}")


def _texts(mix: dict, n: int, base: int, vocab_words: int, rng) -> list:
    """Word indices of one request's candidates (a list of int arrays)."""
    lo, hi = mix.get("jitter", [1.0, 1.0])
    cap = int(mix.get("cap_words", 10**9))
    if "candidate_words" in mix:
        lengths = rng.permutation(base_lengths(mix["candidate_words"], n)).astype(int)
    else:
        factors = rng.permutation(lo + (hi - lo) * _strata(n))
        lengths = np.minimum(np.maximum(1, np.rint(base * factors)), cap).astype(int)
    longest = int(lengths.max())
    pre_share = float(mix.get("preamble", 0.0))
    changed = float(mix.get("changed", 0.1))
    preamble = rng.integers(0, vocab_words, size=longest)
    answer = rng.integers(0, vocab_words, size=longest)
    out = []
    for length in lengths:
        n_pre = int(round(pre_share * length))
        body = answer[: length - n_pre].copy()
        flips = max(1, int(round(changed * len(body)))) if len(body) else 0
        where = rng.choice(len(body), size=flips, replace=False) if flips else []
        body[where] = rng.integers(0, vocab_words, size=flips)
        out.append(np.concatenate([preamble[:n_pre], body]))
    return out


def generate(mix: dict, seed: int, seconds: float, vocab_words: int) -> list:
    """The schedule: a list of requests, each a dict with ``due_s`` (open
    loop) or ``caller`` and ``turn`` (closed loop), ``n``, ``words`` (one int
    array of word indices per candidate), ``scorer`` and, for ``rm``,
    ``prompt`` (an int array)."""
    loop = mix["loop"]
    if loop == "open":
        count = max(1, round(float(mix["rate"]) * seconds))
    elif loop == "closed":
        callers = int(mix["callers"])
        per_caller = math.ceil(float(mix["pool_per_s"]) * seconds / callers)
        count = per_caller * callers
    else:
        raise ValueError(f"unknown loop {loop!r}")
    order = _rng(seed, 12).permutation(count)
    ns = candidate_counts(mix["n"], count)[order]
    bases = base_lengths(mix["words"], count)[_rng(seed, 13).permutation(count)]
    text_rng = _rng(seed, 14)
    scorer = mix.get("scorer", "cosine")
    requests = []
    for i in range(count):
        req = {
            "index": i,
            "n": int(ns[i]),
            "words": _texts(mix, int(ns[i]), int(bases[i]), vocab_words, text_rng),
            "scorer": scorer,
        }
        if scorer == "rm":
            req["prompt"] = text_rng.integers(
                0, vocab_words, size=int(mix.get("prompt_words", 0))
            )
        requests.append(req)
    if loop == "open":
        for req, due in zip(requests, arrival_times(mix, count, seconds)):
            req["due_s"] = float(due)
    else:
        for i, req in enumerate(requests):
            req["caller"], req["turn"] = i % callers, i // callers
    return requests


def render_text(words: np.ndarray) -> str:
    return " ".join([f"w{k}" for k in words.tolist()])


def render_body(req: dict) -> dict:
    body = {"input": [render_text(w) for w in req["words"]]}
    if req["scorer"] == "rm":
        body["scorer"] = "rm"
        if len(req.get("prompt", ())):
            body["prompt"] = render_text(req["prompt"])
    return body


def request_tokens(req: dict, overhead: int) -> int:
    """The vote embeds each candidate alone: a request is as long as its
    longest candidate, with the prompt where the scorer reads one."""
    longest = max(len(w) for w in req["words"])
    return longest + len(req.get("prompt", ())) + overhead


def warm_sample(mix: dict, seed: int, vocab_words: int) -> list:
    """A few requests of the mix's shapes with words of their own."""
    return generate(
        {**mix, "loop": "open", "rate": 4.0, "arrivals": {"kind": "poisson"}},
        seed + 1, 1.0, vocab_words,
    )


def blocker(body: dict) -> dict:
    return {**body, "temperature": BLOCKER_TEMPERATURE}


def well_formed(kept: dict, req: dict) -> bool:
    """N finite values that sum to 1 within 1e-3."""
    conf = kept.get("confidence")
    return (
        isinstance(conf, list)
        and len(conf) == req["n"]
        and all(isinstance(c, float) and math.isfinite(c) for c in conf)
        and abs(sum(conf) - 1.0) <= 1e-3
    )
