"""PR 24's readers: the wire reader of ``.xplane.pb``, device time by scope,
the device idle with work in flight, the attention kernel's roofline share
and the data-only metrics.

On hand-made traces and /metrics documents, and on a small RECORDED trace
(``data/trace_scoped.json``: the first ten programs of the steady cell's
traced chip run of PR 24, cut by ``tools/record_scope_trace.py``, with what
the reducers gave on that cut stored as ``expected``).
"""

import json
import os
import struct

import pytest

import dots3_scopes
import glm5_scopes
import judge_scopes
import layers
import phi4flash_scopes
import qnext_scopes
import scope_time
import trinity_scopes
import xplane
import xspace
from reducers import (
    attention_roofline,
    forward_share_attention,
    forward_share_mlp,
    forward_share_projections,
    forward_share_unscoped,
    idle_with_work,
)

HERE = os.path.dirname(os.path.abspath(__file__))
DATA = os.path.join(HERE, "data")
ROOT = os.path.dirname(os.path.dirname(HERE))
PREFIXES = ["jit__embed_and_vote", "jit__embed_and_vote_many"]
PATH = "jit(f)/jit(embed)/encoder_layers/while/body/closed_call/"


# -- the wire reader -------------------------------------------------------------


def _varint(value: int) -> bytes:
    out = b""
    while True:
        byte = value & 0x7F
        value >>= 7
        out += bytes([byte | (0x80 if value else 0)])
        if not value:
            return out


def _field(number: int, payload) -> bytes:
    if isinstance(payload, int):
        return _varint(number << 3) + _varint(payload)
    return _varint(number << 3 | 2) + _varint(len(payload)) + payload


def _stat(meta_id: int, **value) -> bytes:
    kind, payload = next(iter(value.items()))
    if kind == "text":
        return _field(1, meta_id) + _field(5, payload.encode())
    if kind == "int":
        return _field(1, meta_id) + _field(4, payload)
    if kind == "ref":
        return _field(1, meta_id) + _field(7, payload)
    return _field(1, meta_id) + _varint(2 << 3 | 1) + struct.pack("<d", payload)


def _plane(name, lines, event_meta, stat_names) -> bytes:
    out = _field(2, name.encode())
    for line_name, timestamp_ns, events in lines:
        body = _field(2, line_name.encode()) + _field(3, timestamp_ns)
        for meta_id, offset_ps, duration_ps, stats in events:
            event = _field(1, meta_id) + _field(2, offset_ps) + _field(3, duration_ps)
            body += _field(4, event + b"".join(_field(4, s) for s in stats))
        out += _field(3, body)
    for key, (text, stats) in event_meta.items():
        meta = _field(1, key) + _field(2, text.encode())
        meta += b"".join(_field(5, s) for s in stats)
        out += _field(4, _field(1, key) + _field(2, meta))
    for key, text in stat_names.items():
        out += _field(5, _field(1, key) + _field(2, _field(1, key) + _field(2, text.encode())))
    return _field(1, out)


def test_wire_reader_reads_metadata_stats_event_stats_and_filters_host_names(tmp_path):
    names = {1: "tf_op", 2: "rid", 3: "hlo_category", 4: "convolution fusion", 5: "ms"}
    device = _plane(
        "/device:TPU:0",
        [("XLA Ops", 1000, [(7, 2_000_000, 500_000, []), (7, 9_000_000, 250_000, [])])],
        {7: ("%fusion.3 = f32[] fusion(f32[] %p)", [
            _stat(1, text=PATH + "mlp/dot_general:"), _stat(3, ref=4)])},
        names,
    )
    host = _plane(
        "/host:CPU",
        [("lwc-waiter_0/12", 0, [
            (1, 5_000_000, 1_000_000, [_stat(2, int=41), _stat(5, real=1.5)]),
            (2, 6_000_000, 1_000_000, []),
        ])],
        {1: ("host:finalize", []), 2: ("$threading.py:637 wait", [])},
        names,
    )
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(device + host)
    planes = xspace.read(str(path))
    dev, cpu = planes
    assert dev["name"] == "/device:TPU:0"
    meta = dev["event_metadata"][7]
    assert meta["name"].startswith("%fusion.3 = ")
    assert meta["stats"] == {
        "tf_op": PATH + "mlp/dot_general:", "hlo_category": "convolution fusion"
    }
    assert dev["lines"][0]["events"] == [(7, 3000.0, 500.0, {}), (7, 10000.0, 250.0, {})]
    assert [e[3] for e in cpu["lines"][0]["events"]] == [{"rid": 41, "ms": 1.5}, {}]
    only = xspace.read(str(path), host_names={"host:finalize"})
    assert len(only[1]["lines"][0]["events"]) == 1  # the frame is left unread
    assert len(only[0]["lines"][0]["events"]) == 2  # a device plane never filtered
    trace = scope_time.scoped(str(path))
    assert trace["instructions"] == [{
        "name": "fusion.3", "program": "", "tf_op": PATH + "mlp/dot_general:",
        "category": "convolution fusion", "operands": ["p"],
    }]
    assert trace["ops"] == [[0, 3000.0, 500.0], [0, 10000.0, 250.0]]
    assert trace["spans"] == [["lwc-waiter_0/12", "host:finalize", 5000.0, 1000.0, {"rid": 41, "ms": 1.5}]]


# -- scopes ----------------------------------------------------------------------


def test_scope_is_the_innermost_named_component_or_unscoped():
    assert scope_time.scope_of(PATH + "mlp/...i,io->...o/dot_general:") == "mlp"
    assert scope_time.scope_of(
        PATH + "fused_attention/jit(fused_attention_tiled)/transpose:"
    ) == "fused_attention"
    assert scope_time.scope_of("jit(f)/jit(embed)/encoder_layers/while:") == "encoder_layers"
    assert scope_time.scope_of("jit(f)/consensus_vote/jit(fused_cosine_vote)/pallas_call:") == "consensus_vote"
    assert scope_time.scope_of("jit(f)/jit(embed)/jit(_where)/select_n:") == "unscoped"
    assert scope_time.scope_of(None) == "unscoped"
    # every group's scopes are scopes, and no scope is in two groups
    grouped = [s for g, names in scope_time.GROUPS.items() if g != "unscoped" for s in names]
    assert set(grouped) <= scope_time.SCOPES and len(grouped) == len(set(grouped))


def ins(name, tf_op=None, operands=(), program="1"):
    return {"name": name, "program": program, "tf_op": tf_op, "category": None,
            "operands": list(operands)}


def made_up_trace():
    """Four executions of one program of 1000 ns; the middle two are kept.
    Each: mlp 400, attention kernel 200 with a 100 ns copy around it,
    projections 150, a prefetch without a path (50) whose one consumer is the
    mlp, a copy without a path with two consumers (30), a layer norm 40, the
    ``while`` that spans them all, and a ``cond`` (a ``lax.cond`` XLA left
    alone) that spans two of them: the projections and the prefetch's start."""
    instructions = [
        ins("while.9", "jit(f)/jit(embed)/encoder_layers/while:"),
        ins("fusion.1", PATH + "mlp/dot_general:", ["copy-done.1", "copy.7"]),
        ins("fused_attention_tiled", PATH + "fused_attention/jit(k)/pallas_call:", ["copy.3"]),
        ins("copy.3", PATH + "fused_attention/jit(k)/transpose:", ["fusion.4"]),
        ins("fusion.4", PATH + "qkv_proj/dot_general:", ["copy.7"]),
        ins("copy-start.1", None, ["param.2"]),
        ins("copy-done.1", None, ["copy-start.1"]),
        ins("copy.7", None, ["param.3"]),
        ins("fusion.8", PATH + "mlp_ln/div:", ["fusion.1"]),
        # the same name in ANOTHER program has its own consumers
        ins("copy.7", None, ["x"], program="2"),
        ins("cond.5", PATH + "mlp/cond:"),
    ]
    durations = [(0, 1000), (1, 400), (2, 200), (3, 100), (4, 150), (5, 20),
                 (6, 30), (7, 30), (8, 40)]
    ops, modules = [], []
    for run in range(4):
        base = 10_000 * run
        modules.append(["jit__embed_and_vote(123)", base, 1000])
        at = base
        for index, dur in durations:
            if index == 0:
                ops.append([0, base, 1000])
                continue
            if index == 4:
                ops.append([10, at, 170])  # the cond over its branch: 150 + 20
            ops.append([index, at, dur])
            at += dur
    modules.append(["jit_helper(5)", 50_000, 77])
    ops.append([9, 50_000, 77])
    return {"modules": modules, "instructions": instructions, "ops": sorted(ops, key=lambda o: o[1]), "spans": []}


def test_time_by_scope_on_a_made_up_trace():
    trace = made_up_trace()
    assert scope_time.scopes(trace) == [
        "encoder_layers", "mlp", "fused_attention", "fused_attention", "qkv_proj",
        "mlp", "mlp",  # the prefetch inherits through copy-done from the mlp
        "unscoped",  # two consumers: not guessed
        "mlp_ln", "unscoped",
        "mlp",  # the cond has a path of its own; its TIME is its branch's
    ]
    assert scope_time.programs(trace, PREFIXES) == [(10_000, 11_000), (20_000, 21_000)]
    table, program_ns = scope_time.by_scope(trace, PREFIXES)
    assert program_ns == 2000
    assert table == {
        ("mlp", "fusion"): 800, ("mlp", "copy-start"): 40, ("mlp", "copy-done"): 60,
        ("fused_attention", "fused_attention_tiled"): 400,
        ("fused_attention", "copy"): 200, ("qkv_proj", "fusion"): 300,
        ("unscoped", "copy"): 60, ("mlp_ln", "fusion"): 80,
    }  # the while and the cond are left out: their bodies' operations are on the line too
    ctx = {"scoped": trace, "config": {"trace_modules": PREFIXES}}
    shares = {
        "attention": forward_share_attention.reduce(ctx),
        "projections": forward_share_projections.reduce(ctx),
        "mlp": forward_share_mlp.reduce(ctx),
        "unscoped": forward_share_unscoped.reduce(ctx),
    }
    assert shares == {"attention": 30.0, "projections": 15.0, "mlp": 45.0, "unscoped": 3.0}
    rest = {"mlp_ln": 4.0, "(between operations)": 3.0}
    assert sum(shares.values()) + sum(rest.values()) == pytest.approx(100.0)
    text = scope_time.table_text(trace, PREFIXES)
    assert "mlp_ln                   4.000%" in text
    assert "(between operations)     3.000%" in text
    assert scope_time.kernel_ns(trace, PREFIXES, attention_roofline.KERNELS) == 400


@pytest.mark.parametrize(
    "table",
    [judge_scopes, qnext_scopes, glm5_scopes, dots3_scopes, trinity_scopes, phi4flash_scopes],
    ids=lambda t: t.__name__,
)
def test_a_cond_is_a_container_under_every_judge_s_table(table):
    """The second, third and fourth judges' tables had no word for ``cond``
    (PERF.md, question 27), the fifth and sixth a tuple of their own; now one
    list serves all: the conditional's own event spans its branch's operations
    and is counted with none of them, whatever a table calls their scopes."""
    trace = made_up_trace()
    got, program_ns = table.by_scope(trace, PREFIXES)
    assert program_ns == 2000
    assert not any(kind == "cond" for _, kind in got)
    assert sum(got.values()) == 1940  # 970 a program: the 30 between operations left
    without = dict(trace, ops=[op for op in trace["ops"] if op[0] != 10])
    assert table.by_scope(without, PREFIXES) == (got, program_ns)


def test_a_cond_is_not_among_the_device_operations():
    """``xplane.busy``: the union of the spans is the same with the
    conditional's event or without it, and ``device_ops`` does not name it."""
    trace = made_up_trace()
    names = [ins["name"] for ins in trace["instructions"]]
    ops = [(f"%{names[i]} = f32[] x()", start, dur, {}) for i, start, dur in trace["ops"]]
    device = {"name": "/device:TPU:0", "ops": ops, "modules": [], "lines": []}
    out = xplane.busy({"devices": [device], "host": []})
    assert "cond" not in [kind for kind, _ in out["device_ops"]]
    assert dict(out["device_ops"])["fusion"] == pytest.approx(4 * (400 + 150 + 40) * 1e-9)
    device["ops"] = [op for op in ops if not op[0].startswith("%cond")]
    assert xplane.busy({"devices": [device], "host": []})["busy_s"] == out["busy_s"]


def test_a_trace_without_paths_or_programs_gives_nothing():
    trace = made_up_trace()
    ctx = {"scoped": trace, "config": {"trace_modules": ["jit__other"]}}
    assert forward_share_mlp.reduce(ctx) is None
    for instruction in trace["instructions"]:
        instruction["tf_op"] = None
    ctx = {"scoped": trace, "config": {"trace_modules": PREFIXES}}
    assert forward_share_mlp.reduce(ctx) is None
    assert forward_share_unscoped.reduce(ctx) is None
    assert scope_time.trace_of({"scoped": None}) is None


def test_attention_roofline_counts_the_programs_kept_and_the_kernel_alone():
    trace = made_up_trace()
    cfg = {"num_hidden_layers": 2, "hidden_size": 8}
    # 3 dispatches of 4 x 16 in the traced interval; 2 programs kept
    one = 2 * 2 * 2 * 4 * 16 * 16 * 8
    assert attention_roofline.attention_flops(cfg, 4, 16) == one
    ctx = {
        "scoped": trace, "config": {"trace_modules": PREFIXES}, "cfg": cfg,
        "peaks": {"bf16_flops_per_s": 1e12},
        "profile": {
            "before": {"roofline": {"buckets": {"vote1(n=4,s=16)": {"count": 5}}}},
            "after": {"roofline": {"buckets": {"vote1(n=4,s=16)": {"count": 8}}}},
        },
    }
    assert attention_roofline.reduce(ctx) == pytest.approx(
        100.0 * 2 * one / (400e-9 * 1e12)
    )
    ctx["profile"]["after"]["roofline"]["buckets"]["odd label"] = {"count": 1}
    assert attention_roofline.reduce(ctx) is None  # a shape it cannot read
    del ctx["profile"]["after"]["roofline"]["buckets"]["odd label"]
    trace["instructions"][2]["name"] = "einsum.5"  # no kernel ran
    assert attention_roofline.reduce(ctx) is None


# -- the device idle with work in flight -----------------------------------------------


def test_idle_with_work_on_hand_made_intervals():
    def span(name, start, dur, rid):
        return ["loop", name, start, dur, {"rid": rid}]

    trace = {
        "instructions": [ins("fusion.1", PATH + "mlp/x:")],
        # busy 0-100, 300-400, 900-1000: idle 100-300 and 400-900 of 1000
        "ops": [[0, 0, 100], [0, 300, 100], [0, 900, 100]],
        "modules": [],
        "spans": [
            # arrived before the trace, answers at 150: in flight 0-150
            span("http:respond", 140, 10, "a"),
            # a whole request inside an idle stretch, and one across busy time
            span("http:arrive", 450, 1, "b"), span("http:respond", 540, 10, "b"),
            span("http:arrive", 250, 1, 7), span("http:respond", 410, 10, 7),
            # arrives and never answers: to the window's end
            span("http:arrive", 880, 1, "c"),
            span("batcher:idle", 100, 200, None),
        ],
    }
    flights = sorted(scope_time.in_flight(trace, 0, 1000))
    assert flights == [(0, 150), (250, 420), (450, 550), (880, 1000)]
    # idle AND in flight: 100-150, 250-300, 400-420, 450-550, 880-900
    assert idle_with_work.reduce({"scoped": trace}) == pytest.approx(24.0)
    assert scope_time.overlap_ns([(0, 10)], [(20, 30)]) == 0.0
    trace["spans"] = [s for s in trace["spans"] if s[1] != "http:arrive"]
    assert idle_with_work.reduce({"scoped": trace}) is None  # no spans: the parent


# -- the data-only metrics ---------------------------------------------------------


def metrics_doc(scale: int) -> dict:
    def phase(count, total):
        return {"count": count * scale, "sum_ms": total * scale}

    return {
        "phases": {
            "http_parse": phase(10, 120.0), "http_respond": phase(10, 15.0),
            "tokenize": phase(12, 480.0), "stage": phase(4, 100.0),
            "finalize": phase(4, 6.0),
        },
        "jit": {"backend_compiles": 40 + scale, "backend_compile_s": 3.5},
    }


@pytest.mark.parametrize(
    "name, expected",
    [
        ("edge.parse_ms", 12.0), ("edge.respond_ms", 1.5),
        ("batcher.tokenize_ms", 40.0), ("dispatch.stage_ms", 25.0),
        ("dispatch.finalize_ms", 1.5), ("jit.compiles_in_window", 2.0),
    ],
)
def test_data_only_metrics_on_two_made_up_documents(name, expected):
    with open(os.path.join(HERE, "..", "layer_metrics", name + ".json")) as f:
        spec = json.load(f)["read"]
    assert spec["from"] == "metrics"
    before, after = metrics_doc(1), metrics_doc(3)
    assert layers.read_metrics(spec, before, after) == pytest.approx(expected)
    if name == "jit.compiles_in_window":
        assert layers.read_metrics(spec, after, after) == 0.0  # reported, not left out
    # a program without the span or the counter (the parent): nothing, no raise
    assert layers.read_metrics(spec, {}, {"phases": {}, "jit": {"aot_buckets": 9}}) is None


PR24 = (
    "edge.parse_ms", "edge.respond_ms", "batcher.tokenize_ms", "dispatch.stage_ms",
    "dispatch.finalize_ms", "jit.compiles_in_window", "device.idle_with_work_share",
    *(f"forward.share.{g}.{loop}" for g in ("attention", "projections", "mlp", "unscoped")
      for loop in ("open", "closed")),
    "kernel.attention_roofline.open", "kernel.attention_roofline.closed",
)


def test_every_new_metric_has_its_file_its_cell_and_one_end_to_end_metric():
    """PR 24's seventeen, by name and not by their place in the list: cells
    and metrics have come since (PERF.md, question 21)."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    steady, closed = (w["name"] for w in bench["workloads"][:2])
    new = [m for m in bench["per_layer"] if m["name"] in PR24]
    assert len(new) == len(PR24) == 17
    for metric in new:
        path = os.path.join(HERE, "..", "layer_metrics", metric["name"] + ".json")
        with open(path) as f:
            spec = json.load(f)["read"]
        if spec["from"] == "trace":
            assert os.path.exists(
                os.path.join(HERE, "..", "reducers", spec["reducer"] + ".py")
            )
            assert metric["source"] == "device_trace"
        cell = closed if metric["name"].endswith(".closed") else steady
        assert metric["workloads"] == [cell]
        assert metric["moves"] == (
            "answers_per_s" if cell == closed else "latency_p50_ms"
        )


# -- the recorded trace -----------------------------------------------------------


def recorded():
    path = os.path.join(DATA, "trace_scoped.json")
    if not os.path.exists(path):
        pytest.skip("no recorded scoped trace in this checkout")
    with open(path) as f:
        return json.load(f)


def test_recorded_trace_shares_and_the_rest_sum_to_100():
    trace = recorded()
    table, program_ns = scope_time.by_scope(trace, PREFIXES)
    assert program_ns == pytest.approx(trace["expected"]["program_ns"])
    by: dict = {}
    for (scope, _), ns in table.items():
        by[scope] = by.get(scope, 0.0) + 100.0 * ns / program_ns
    assert by == pytest.approx(trace["expected"]["share_by_scope"])
    ctx = {"scoped": trace, "config": {"trace_modules": PREFIXES}}
    four = {g: scope_time.share(ctx, g) for g in scope_time.GROUPS}
    grouped = {s for names in scope_time.GROUPS.values() for s in names}
    rest = sum(share for scope, share in by.items() if scope not in grouped)
    between = 100.0 - sum(by.values())
    assert 0 <= between < 0.1  # the programs' time is their operations' time
    assert sum(four.values()) + rest + between == pytest.approx(100.0)
    # what the chip showed: every operation of a layer has an owner
    assert four["unscoped"] < 1.0
    assert four["mlp"] > four["projections"] > 15 and four["attention"] > 15
    assert {"attn_ln", "mlp_ln", "mlp", "pool"} <= set(by)


def test_recorded_trace_roofline_and_idle_with_work():
    trace = recorded()
    ctx = {
        "scoped": trace, "config": {"trace_modules": PREFIXES},
        "cfg": {"num_hidden_layers": 24, "hidden_size": 1024},
        "peaks": {"bf16_flops_per_s": 197e12}, "profile": trace["profile"],
    }
    roofline = attention_roofline.reduce(ctx)
    assert roofline == pytest.approx(trace["expected"]["attention_roofline"])
    assert 0 < roofline < 100
    names = {span[1] for span in trace["spans"]}
    assert {"http:arrive", "http:respond", "batcher:stage", "device:wait"} <= names
    share = idle_with_work.reduce(ctx)
    assert share == pytest.approx(trace["expected"]["idle_with_work"])
    assert 0 <= share <= 100
    # one request's spans share its rid, a group's spans name it
    arrive = next(s for s in trace["spans"] if s[1] == "http:arrive")
    rid = str(arrive[4]["rid"])
    assert any(
        s[1] == "batcher:stage" and rid in str(s[4]["rids"]).split(" ")
        for s in trace["spans"]
    )
