import pytest

import byname
import run as bench_run


def rec(sent, done, due=None, status=200):
    return {"status": status, "sent_s": sent, "done_s": done, "due_s": due}


def test_open_loop_latency_from_due_and_failures_left_out():
    results = [
        rec(0.11, 0.15, due=0.10), rec(0.52, 0.70, due=0.50),
        rec(0.9, 1.4, due=0.9), rec(1.0, 1.1, due=1.0, status=503),
    ]
    out = bench_run.end_to_end(results, "open", 2.0)
    assert out["latency_p50_ms"] == pytest.approx(200.0)
    assert out["latency_p95_ms"] == pytest.approx(200.0 + 0.9 * 300.0)
    assert out["answers_per_s"] == pytest.approx(3 / 2.0)


def test_closed_loop_rate_credits_the_share_in_flight_inside_the_window():
    # two answers inside, one sent at 8 and back at 12: half of it lay inside
    results = [rec(0.0, 4.0), rec(4.0, 8.0), rec(8.0, 12.0)]
    out = bench_run.end_to_end(results, "closed", 10.0)
    assert out["answers_per_s"] == pytest.approx(2.5 / 10.0)
    assert "latency_p50_ms" not in out


def test_well_formed_answers():
    well_formed = byname.module("generators", "consensus").well_formed
    assert well_formed({"confidence": [0.25] * 4}, {"n": 4})
    assert not well_formed({"confidence": [0.25] * 3}, {"n": 4})
    assert not well_formed({"confidence": [0.5, 0.6]}, {"n": 2})
    assert not well_formed({"confidence": [float("nan"), 1.0]}, {"n": 2})
    assert not well_formed({}, {"n": 2})
    assert not well_formed({"confidence": None}, {"n": 2})


def test_compiles_are_read_from_the_jit_section():
    before = {"jit": {"aot_buckets": 8, "specializations": {"embed": 0}}}
    same = {"jit": {"aot_buckets": 8, "specializations": {"embed": 0}}}
    assert bench_run.compiled_in_window(before, same) == []
    after = {"jit": {"aot_buckets": 9, "specializations": {"embed": 1}}}
    assert len(bench_run.compiled_in_window(before, after)) == 2
    assert bench_run.compile_events({"compile_cache": {"hits": 3, "misses": 2}}) == 5


def test_held_peak_counts_the_programs_reservation():
    from server import held_peak_bytes

    # as read on the chip: buffers 0.69 GB, the largest program's 4.83 GB of
    # temporaries reserved beside them, the loading peak of buffers 1.2 GB
    rows = [
        {"id": 0, "bytes_in_use": 693916672, "peak_bytes_in_use": 1212827136,
         "bytes_reserved": 4833148928, "peak_bytes_reserved": 4833148928},
        {"id": 1, "bytes_in_use": 100, "peak_bytes_in_use": 200},
    ]
    assert held_peak_bytes(rows) == 693916672 + 4833148928
    assert held_peak_bytes([{"id": 0, "peak_bytes_in_use": 7, "bytes_in_use": 3}]) == 7
    assert held_peak_bytes([{"id": 0}]) == 0
