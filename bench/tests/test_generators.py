import json
import os

import numpy as np
import pytest

from generators import consensus as gen

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MIXES = sorted(f[:-5] for f in os.listdir(os.path.join(BENCH, "traffic")))


def mix(name):
    with open(os.path.join(BENCH, "traffic", name + ".json")) as f:
        return json.load(f)


def shape_multiset(requests):
    return sorted(
        (r["n"], tuple(sorted(len(w) for w in r["words"]))) for r in requests
    )


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_schedule(name):
    a = gen.generate(mix(name), 2**31 + 17, 10.0, 30518)
    b = gen.generate(mix(name), 2**31 + 17, 10.0, 30518)
    assert [gen.render_body(r) for r in a] == [gen.render_body(r) for r in b]
    assert [r.get("due_s") for r in a] == [r.get("due_s") for r in b]


@pytest.mark.parametrize("name", MIXES)
def test_seeds_offer_the_same_work_in_another_order(name):
    a = gen.generate(mix(name), 1, 10.0, 30518)
    b = gen.generate(mix(name), 4_000_000_007, 10.0, 30518)
    assert shape_multiset(a) == shape_multiset(b)
    assert gen.render_body(a[0]) != gen.render_body(b[0])
    if "due_s" in a[0]:
        if mix(name).get("arrivals", {}).get("kind") != "bursty":
            # the same multiset of gaps (less the one before the first arrival)
            pool = np.round(gen.exponential_gaps(len(a), 10.0), 9)
            for reqs in (a, b):
                gaps = np.round(np.diff(sorted(r["due_s"] for r in reqs)), 9)
                assert np.isin(gaps, pool).all()


def test_open_loop_count_and_window():
    m = mix("n64-s512.steady")
    reqs = gen.generate(m, 5, 30.0, 30518)
    assert len(reqs) == round(m["rate"] * 30.0)
    due = [r["due_s"] for r in reqs]
    assert min(due) == 0.0 and max(due) < 30.0
    assert all(r["n"] == 64 and len(r["words"]) == 64 for r in reqs)
    assert all(len(w) == 478 for r in reqs for w in r["words"])


def test_a_tenth_of_the_words_changed():
    m = mix("n64-s512.steady")
    req = gen.generate(m, 6, 1.0, 30518)[0]
    base = np.stack(req["words"])
    # the shared answer is the per-column mode; each candidate differs from
    # it in at most a tenth of its words (a redraw may land on the same word)
    mode = np.array([np.bincount(col).argmax() for col in base.T])
    differing = (base != mode).sum(axis=1)
    assert differing.max() <= 48 and differing.mean() > 40


def test_exponential_gaps_are_the_distribution_quantiles():
    gaps = gen.exponential_gaps(1000, 50.0)
    assert abs(gaps.sum() - 50.0) < 1e-9
    # an exponential's median is ln 2 of its mean
    assert abs(np.median(gaps) / gaps.mean() - np.log(2)) < 0.01
    assert abs(gaps.std() / gaps.mean() - 1.0) < 0.05


def test_geometric_burst_sizes():
    sizes = gen.geometric_sizes(100, 400, 4.0)
    assert sizes.sum() == 400 and sizes.min() >= 1
    assert 3.0 <= sizes.mean() <= 5.0 and sizes.max() > 8


def test_bursty_members_arrive_within_the_burst_width():
    m = {
        "loop": "open", "rate": 12.0,
        "arrivals": {"kind": "bursty", "mean_burst": 4, "within_ms": 10},
        "n": {"values": [8, 32, 64], "weights": [1, 2, 1]},
        "words": {"kind": "lognormal", "median": 180, "sigma": 0.5, "min": 48, "max": 480},
        "jitter": [0.7, 1.3], "cap_words": 500, "preamble": 0.3, "changed": 0.1,
    }
    reqs = gen.generate(m, 9, 30.0, 30518)
    assert len(reqs) == 360
    counts = np.bincount([r["n"] for r in reqs])
    assert (counts[8], counts[32], counts[64]) == (90, 180, 90)
    due = np.sort([r["due_s"] for r in reqs])
    close = (np.diff(due) <= 0.010).sum()
    assert close >= 200  # about three of every four requests follow within 10 ms
    lengths = [len(w) for r in reqs for w in r["words"]]
    assert min(lengths) >= 0.7 * 48 - 1 and max(lengths) <= 500
    # the shared preamble: the first 30% of two candidates of one request agree
    r = reqs[0]
    a, b = r["words"][0], r["words"][1]
    k = int(0.3 * min(len(a), len(b))) - 1
    assert (a[:k] == b[:k]).all()


def test_lognormal_lengths_by_quantile():
    spec = {"kind": "lognormal", "median": 150, "sigma": 0.5, "min": 32, "max": 400}
    x = gen.base_lengths(spec, 16)
    assert len(x) == 16 and x.min() >= 32 and x.max() <= 400
    assert 140 <= np.median(x) <= 160


def test_closed_loop_callers_and_rm_prompt():
    m = mix("rerank-n16.closed8")
    reqs = gen.generate(m, 3, 2.0, 128096)
    callers = {r["caller"] for r in reqs}
    assert callers == set(range(8))
    body = gen.render_body(reqs[0])
    assert body["scorer"] == "rm" and len(body["prompt"].split()) == 48
    assert len(body["input"]) == 16


def test_poisson_bursts_puts_whole_groups_at_fixed_times():
    m = mix("n64-s512.steady")
    assert m["arrivals"]["kind"] == "poisson_bursts"
    # two bursts in a 50 s window, whatever the mix's own file now asks for
    arrivals = {**m["arrivals"], "every_s": 25}
    m = {**m, "rate": 3.6, "arrivals": arrivals}
    due = gen.arrival_times(m, 180, 50.0)
    assert len(due) == 180 and due[0] == 0.0 and due.max() < 50.0
    size, every = arrivals["size"], arrivals["every_s"]
    for start in (every / 2, every * 1.5):
        inside = (due >= start) & (due <= start + arrivals["within_ms"] / 1e3)
        assert inside.sum() >= size
    # the same times for every run seed; the seed orders the requests only
    a = [r["due_s"] for r in gen.generate(m, 1, 50.0, 30518)]
    b = [r["due_s"] for r in gen.generate(m, 2, 50.0, 30518)]
    assert a == b
    # a window too short for a burst is Poisson alone
    assert len(gen.arrival_times(m, 36, 10.0)) == 36


@pytest.mark.parametrize("seed", [11, 2**31 + 29])
def test_a_larger_pool_of_the_judge_panel_starts_with_the_smaller_one(seed):
    """``judge_panel.generate`` draws its requests in order from one
    generator, so a window's pool at 6.0 a second (PR 48) opens with the 200
    requests the pool at 4.0 held: what the callers reached before, they
    reach again, request for request."""
    from generators import judge_panel

    six = mix("n64-c8k.closed4")
    assert six["pool_per_s"] == 6.0
    before = judge_panel.generate({**six, "pool_per_s": 4.0}, seed, 50.0, 154_000)
    after = judge_panel.generate(six, seed, 50.0, 154_000)
    assert (len(before), len(after)) == (200, 300)
    for a, b in zip(before, after):
        assert (a["index"], a["caller"], a["turn"]) == (b["index"], b["caller"], b["turn"])
        assert judge_panel.render_body(a) == judge_panel.render_body(b)
