"""The trace arithmetic on hand-made intervals, and the reducers on a small
recorded trace (``data/trace_small.json``: the first operations of one TPU
plane of a real run of this benchmark, as ``xplane.read`` returns them)."""

import json
import os

import pytest

import xplane

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_union_merges_overlaps_and_counts_gaps_out():
    spans = [(0, 10), (5, 20), (30, 40), (40, 45), (100, 101)]
    assert xplane.union_seconds(spans) == pytest.approx(36e-9)
    assert xplane.gaps(spans) == [(20, 30), (45, 100)]
    assert xplane.union_seconds([]) == 0.0


def test_busy_on_a_made_up_trace():
    ops = [
        ("%fusion.1 = f32[] fusion()", 0, 400, {}),
        ("%fusion.2 = f32[] fusion()", 400, 100, {}),
        ("%convolution.7 = bf16[] convolution()", 1000, 1000, {}),
    ]
    trace = {
        "devices": [{"name": "/device:TPU:0", "ops": ops, "modules": [], "lines": []}],
        "host": [("python", "sleepy", 450, 600)],
    }
    out = xplane.busy(trace)
    assert out["busy_s"] == pytest.approx(1500e-9)
    assert out["window_s"] == pytest.approx(2000e-9)
    assert out["device_ops"][0] == ["convolution", pytest.approx(1000e-9)]
    ops.append(("%while.3 = () while()", 0, 2000, {}))
    assert [k for k, _ in xplane.busy(trace)["device_ops"]] == ["convolution", "fusion"]
    assert out["device_ops"][1] == ["fusion", pytest.approx(500e-9)]
    assert out["idle_gaps"][0] == ["python:sleepy", pytest.approx(500e-9)]
    assert xplane.busy({"devices": [], "host": []}) == {}


@pytest.mark.skipif(
    not os.path.exists(os.path.join(DATA, "trace_small.json")),
    reason="no recorded trace in this checkout",
)
def test_busy_on_the_recorded_trace():
    with open(os.path.join(DATA, "trace_small.json")) as f:
        trace = json.load(f)
    out = xplane.busy(trace)
    expected = trace["expected"]
    assert out["busy_s"] == pytest.approx(expected["busy_s"], rel=1e-9)
    assert out["window_s"] == pytest.approx(expected["window_s"], rel=1e-9)
    assert 0 < out["busy_s"] <= out["window_s"]
    assert [name for name, _ in out["device_ops"]][:3] == expected["top3"]


def test_forward_mfu_counts_the_dispatches_of_the_traced_interval():
    from reducers import forward_mfu

    assert forward_mfu.slot_shape("vote1(n=64,s=128)") == (64, 128)
    assert forward_mfu.slot_shape("many(r=4,n=64,s=128)") == (256, 128)
    assert forward_mfu.slot_shape("many(r=2,n=8,s=512)@dp2xtp2") == (16, 512)
    assert forward_mfu.slot_shape("mystery") is None
    cfg = {"num_hidden_layers": 24, "hidden_size": 1024, "intermediate_size": 4096}
    batcher = {"dispatches": 100, "padded": {"slot_tokens": 1000}}
    ctx = {
        "profile": {
            "before": {
                "roofline": {"buckets": {"vote1(n=64,s=128)": {"count": 5}}},
                "device_batcher": batcher,
            },
            "after": {
                "roofline": {"buckets": {
                    "vote1(n=64,s=128)": {"count": 15},
                    "many(r=2,n=64,s=128)": {"count": 5},
                }},
                "device_batcher": {
                    "dispatches": 115,
                    "padded": {"slot_tokens": 1000 + (10 * 64 + 5 * 128) * 128},
                },
            },
        },
        "config": {"family": "bert", "trace_modules": ["jit__embed_and_vote", "jit__embed_and_vote_many"]},
        "cfg": cfg,
        "trace": {"devices": [{"modules": [
            event
            for k in range(6)
            for event in (
                ("jit__embed_and_vote(123)", 10 * k, int(0.04e9), {}),
                ("jit__embed_and_vote_many(9)", 10 * k + 2, int(0.06e9), {}),
                ("jit_slice(77)", 10 * k + 4, 1, {}),
            )
        ]}]},
        "device": {"busy_s": 1.0},
        "peaks": {"bf16_flops_per_s": 197e12},
    }
    import flops

    one = flops.forward_flops("bert", cfg, 64, 128)
    two = flops.forward_flops("bert", cfg, 128, 128)
    # 15 dispatches counted by the program give the mean program; 12 model
    # programs in the trace, the first and the last left out with their time
    assert forward_mfu.reduce(ctx) == pytest.approx(
        100 * (10 * one + 5 * two) / 15 * 10 / ((0.04 + 0.06) * 5 * 197e12)
    )
    ctx["profile"]["after"]["roofline"]["buckets"]["odd(x=1)"] = {"count": 1}
    assert forward_mfu.reduce(ctx) is None
