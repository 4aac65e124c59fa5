"""The output check of the judge cells: a panel's served ballots against the
plain reference's logits, on a seeded sample of the window's own answers.

An answer carries, per call, what an upstream judge's ``top_logprobs`` would
have: the first level's letters with their log-probabilities, the key chosen,
and the chosen branch's sibling letters with theirs and the candidate each
selects.  For each sampled call the reference (``bench/references/``) builds
the ballot from the request and the call's seed for itself, appends the
letter the PROGRAM chose, and runs one full forward over T + 1 positions: the
first level's logits at position T - 1, the second's at position T (which the
program read through its latent cache).  Numbers compared:

  ``ballot_logit_rms``     root mean square, over the sample's calls and both
                           levels, of (served log-probabilities, centred over
                           the letters read) - (reference logits at the same
                           token ids, centred).  Held against a confused
                           ballot (letters one place round read ten times the
                           limit); it cannot tell bf16 from int8 on every
                           seed, because a few reads carry most of it:
  ``ballot_read_rms_median``  the same differences taken a READ at a time (one
                           head read: one level of one call, whose letters
                           share one hidden state): the median over a level's
                           reads of each read's root mean square, the larger
                           of the two levels'.  With seeded weights a token
                           whose fourth and fifth router scores lie close is
                           routed differently in bf16 and in float32, and
                           every letter of a read behind such a token moves
                           together, far (0.2-0.5 against 0.01); a median
                           leaves those few reads out and reads what every
                           read shares, the precision's rounding.  They are
                           not always few: of a level's 24 reads 1-8 sit
                           behind such a row, and twelve did in one run of
                           the driver's (PR 48).
                           So where the configuration gives a
                           ``read_margin_floor`` and the reference says how
                           firmly IT routed each read's own row
                           (``read_logits_and_margins``), the median is over
                           the reads routed by the floor or more
                           (``firm_medians``): a rule on the reference alone.
  ``ballot_mismatches``    calls whose served letters, chosen key or
                           letter -> candidate map are not the ballot the
                           call's seed gives (a seed ignored, an order or a
                           branch confused); limit 0.
  ``confidence_abs_err``   the largest |served confidence - the tally worked
                           out again| over the sample: each call's vote is
                           exp(log-probability) over its siblings, normalised
                           (``ballot/vote.py``'s arithmetic), placed on the
                           candidates the REFERENCE's ballot gives, weighed by
                           the request's weights; limit 1e-5, whatever the
                           precision.
"""

from __future__ import annotations

import math

import numpy as np

import byname
from checks.consensus_logit import setup_jax


def sample(served: list, count: int, seed: int) -> list:
    """``count`` of the served [(request, kept)], drawn from the seed."""
    if len(served) <= count:
        return list(served)
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 22]))
    return [served[int(i)] for i in rng.permutation(len(served))[:count]]


def collect(port: int, config: dict, picked: list, render_text) -> list:
    """Nothing more is asked of the server: the answers hold the logits."""
    return [None] * len(picked)


def _centred(values) -> np.ndarray:
    values = np.asarray(values, np.float64)
    return values - values.mean()


def firm_medians(by_level: dict, margins: dict, floor: float, least: int) -> tuple:
    """The reads that count and each level's median over them.  A read counts
    when the REFERENCE routed its own row by ``floor`` or more: under that the
    row is routed by rounding, in bf16 as often one way as the other, and the
    read says which way and not how well.  A level that keeps fewer than
    ``least`` such reads is read over all of its reads, as every level is at
    floor 0 and under a reference that gives no margins: no level goes
    unread."""
    firm = {}
    for which, values in by_level.items():
        kept = [v for v, m in zip(values, margins.get(which, ())) if m >= floor]
        firm[which] = kept if len(kept) >= least and which in margins else list(values)
    return firm, {which: float(np.median(v)) for which, v in firm.items()}


def run(config, cfg, state, picked, vectors, dry, cache_dir) -> dict:
    params = config["check"]
    setup_jax(cache_dir, dry)
    ref = byname.module("references", config["reference"])
    tok = config["tokenizer"]
    letter_ids = [ref.letter_id(letter, tok) for letter in ref.ALPHABET]
    calls, plans, mismatches, tally_err = [], [], 0, 0.0
    for req, kept in picked:
        tally = np.zeros((req["n"],), np.float64)
        for (seed, weight), served in zip(req["panel"], kept["ballots"]):
            root, depth, pairs = ref.ballot(seed, req["n"])
            letters = [c for c in served["key"] if c in ref.ALPHABET]
            branch = root
            for letter in letters[:-1]:
                branch = branch.get(letter) if isinstance(branch, dict) else None
            got = {k: e["candidate"] for k, e in served["siblings"].items()}
            first_ok = depth == 1 or set(served.get("first", ())) == set(root)
            if len(letters) != depth or branch != got or not first_ok:
                mismatches += 1  # its mass is tallied nowhere either
                continue
            # the call's vote by ballot/vote.py's arithmetic, placed by the
            # REFERENCE's ballot and weighed by the request's weight
            mass = {k: math.exp(e["logprob"]) for k, e in served["siblings"].items()}
            for letter, p in mass.items():
                tally[branch[letter]] += weight * p / sum(mass.values())
            ids = ref.call_ids(req, pairs, tok)
            rows = [len(ids) - 1]
            if depth == 2:
                ids = ids + [ref.letter_id(letters[0], tok)]
                rows.append(len(ids) - 1)
            calls.append((ids, rows))
            plans.append((depth, served))
        tally /= sum(weight for _, weight in req["panel"])
        tally_err = max(
            tally_err, float(np.abs(tally - np.asarray(kept["confidence"])).max())
        )
    diffs, rotated, by_level, margin_by_level = [], [], {}, {}

    def compare(level: dict, read, which: str, margin: float) -> None:
        """level: {letter: served log-probability}; read: reference logits
        over the alphabet; margin: how firmly the reference routed the
        read's own row (None from a reference that does not say)."""
        letters = sorted(level)
        got = _centred([level[letter] for letter in letters])
        want = _centred([read[ref.ALPHABET.index(letter)] for letter in letters])
        diffs.append(got - want)
        rotated.append(np.roll(got, 1) - want)
        by_level.setdefault(which, []).append(float(math.sqrt(np.mean(diffs[-1] ** 2))))
        if margin is not None:
            margin_by_level.setdefault(which, []).append(float(margin))

    if hasattr(ref, "read_logits_and_margins"):
        reads, margins = ref.read_logits_and_margins(state, cfg, calls, letter_ids)
    else:
        reads = ref.read_logits(state, cfg, calls, letter_ids)
        margins = [[None] * len(rows) for _, rows in calls]
    for (depth, served), read, margin in zip(plans, reads, margins):
        if depth == 2:
            compare(served["first"], read[0], "first", margin[0])
        compare(
            {k: e["logprob"] for k, e in served["siblings"].items()},
            read[-1], "last", margin[-1],
        )

    flat = np.concatenate(diffs) if diffs else np.zeros(0)
    rms = float(math.sqrt(np.mean(flat**2))) if flat.size else float("inf")
    firm, medians = firm_medians(
        by_level, margin_by_level, float(params.get("read_margin_floor", 0.0)),
        int(params.get("read_margin_min_reads", 1)),
    )
    typical = max(medians.values()) if medians else float("inf")
    return {
        "numbers": [
            {"name": "ballot_read_rms_median", "value": typical,
             "limit": float(params["ballot_read_rms_median_limit"])},
            {"name": "ballot_logit_rms", "value": rms,
             "limit": float(params["ballot_logit_rms_limit"])},
            {"name": "ballot_mismatches", "value": mismatches, "limit": 0},
            {"name": "confidence_abs_err", "value": tally_err if picked else float("inf"),
             "limit": float(params["confidence_limit"])},
        ],
        "compared": int(flat.size),
        "calls": len(calls),
        "worst_abs": float(np.abs(flat).max()) if flat.size else None,
        "read_rms_median_by_level": medians,
        "reads_in_median": {which: len(v) for which, v in firm.items()},
        "read_rms_median_all_reads": max(
            (float(np.median(v)) for v in by_level.values()), default=None
        ),
        "read_rms_max": max((max(v) for v in by_level.values()), default=None),
        # not compared: every read's own root mean square, in the sample's
        # order, so that a median that reads high shows which reads carry it
        "read_rms_by_level": {
            which: [float(f"{v:.3g}") for v in values] for which, values in by_level.items()
        },
        "read_margin_by_level": {
            which: [float(f"{v:.3g}") for v in values]
            for which, values in margin_by_level.items()
        },
        # not compared: what ballot_logit_rms would read had every level's
        # log-probabilities come back one letter out of place
        "ballot_logit_rms_if_rotated": float(
            math.sqrt(np.mean(np.concatenate(rotated) ** 2))
        ) if rotated else None,
    }
