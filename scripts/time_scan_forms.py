#!/usr/bin/env python3
"""The selective scan's kernel ALONE on the chip, form by form (PR 47).

    chiprun -- python3 scripts/time_scan_forms.py [--module FILE] [form ...]

(host clock around ``block_until_ready`` of nine calls of
``selective_scan_chunked`` in one program, over nine, the least and the median
of ten; bf16, one Mamba layer's scan at the sixth judge's cell,
[3, 8192, 5120] with n = 16, B and C spread before the clock starts, the
second call ending inside a group of eight positions; each form is first
compared with ``selective_scan_recurrent`` on the same chip at [2, 520, 1024]
under the published rates' shape, y and the state; one JSON line a form on
stdout and in ``chiprun_out/scan_forms.jsonl``).  ``--module FILE`` takes
``served`` from another copy of ``ops/selective_scan.py`` (the parent's,
unpacked beside this tree), so that one call times both on one chip.

A form is PR 45's ``ops/selective_scan.py::_kernel`` (the state [n, group]
one value, dt and dt x whole [chunk, channels] tiles) with ``+``-joined words
applied, or ``served``, the module's own body, whatever it is:

  parent   PR 45's body: two positions a trip, ``exp(dt * a)``, a position's
           sum over the states one masked row
  u4 u8 u16   positions a trip
  exp2     ``a * log2(e)`` once a channel group, ``exp2(dt * a2)`` a position
  fold     the sums of eight positions together: a position's ``h * C`` down
           to one register, seven folds of select, roll and add, one
           unmasked [8, group] store (implies eight positions a trip)
  pro      the first and the last step a group of rows at a time, re-reading
           ``x_ref``, so that no [chunk, channels] value is live
  bcast    dt's and dt x's rows spread down the sublanes by the load (stride
           0): Mosaic refuses it on a tile wider than 128 lanes, which is why
           the served body keeps them a lane group apart
  g128 g256 g1024   channels whose state the loop carries (512 without)

  served   beside it ``gN`` (``_GROUP``), ``tN`` (``_UNROLL``, positions a
           trip) and ``iN`` (``_ROWS_IN``) set the module's constants

``--check`` runs every form once on whatever backend is there at a tiny shape
against the recurrence (the CPU's interpreter: counts nothing, times
nothing).  ``--sched DIR form`` compiles ONE form at [3, 1024, 5120] for a
described v5e with the scheduler's report dumped to DIR (``LIBTPU_INIT_ARGS``;
the process aborts after the files are written) and ``--bundles DIR`` reads
the report: bundles a grid step and a trip of each loop.  A static count, never
a time.
"""
import glob
import json
import os
import re
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ARGS = sys.argv[1:]
CHECK = "--check" in ARGS
SCHED = ARGS[ARGS.index("--sched") + 1] if "--sched" in ARGS else None
BUNDLES = ARGS[ARGS.index("--bundles") + 1] if "--bundles" in ARGS else None
MODULE = ARGS[ARGS.index("--module") + 1] if "--module" in ARGS else None
FORMS = [a for a in ARGS if not a.startswith("--") and a not in (SCHED, BUNDLES, MODULE)]
if SCHED:
    os.makedirs(SCHED, exist_ok=True)
    os.environ["LIBTPU_INIT_ARGS"] = f"--xla_jf_dump_to={SCHED} --xla_jf_dump_llo_text=true"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, HERE)
import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

if MODULE:
    import importlib.util

    spec = importlib.util.spec_from_file_location("selective_scan_beside", MODULE)
    scan = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(scan)
else:
    from llm_weighted_consensus_tpu.ops import selective_scan as scan

OUT = os.path.join(HERE, "chiprun_out")
LOG2E = 1.4426950408889634
DEFAULT = [
    "parent", "u8", "u8+exp2", "fold", "fold+exp2", "fold+exp2+pro", "fold+exp2+pro+g256",
    "served+g512+t8+i16", "served+g512+i16", "served+g512", "served+g256", "served", "served+t32",
]


def _fold(pa, pb, m, shift):
    return jnp.where(m, pa, pb) + pltpu.roll(jnp.where(m, pb, pa), shift, 0)


def body(words):
    """PR 45's ``_kernel`` with the form's words applied."""
    fold = "fold" in words
    unroll = 8 if fold else next((int(w[1:]) for w in words if re.fullmatch(r"u\d+", w)), 2)

    def kernel(*refs, **sizes):
        # PR 45's three [chunk, channels] tiles, whatever the module's call allocates
        tile = pltpu.VMEM(refs[1].shape, jnp.float32)
        at = pl.program_id(0), pl.program_id(2)  # the interpreter knows no grid inside a scope
        pl.run_scoped(lambda *tiles: scoped(at, *refs[:10], *tiles, **sizes), tile, tile, tile)

    def scoped(
        at, lens_ref, x_ref, dt_ref, bias_ref, a_ref, b_ref, c_ref, d_ref, y_ref, state_ref,
        dt_s, dtx_s, y_s, *, chunk, group, lanes,
    ):
        bi, ci = at
        channels = x_ref.shape[1]
        n = a_ref.shape[0]

        @pl.when(ci == 0)
        def _():
            state_ref[...] = jnp.zeros_like(state_ref)

        def prologue(rows, shape):
            x = x_ref[rows, :].astype(jnp.float32)
            position = ci * chunk + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
            if not isinstance(rows, slice):
                position = position + rows.start
            dt = scan._softplus(dt_ref[rows, :].astype(jnp.float32) + bias_ref[...])
            dt = jnp.where(position < lens_ref[bi], dt, 0.0)
            dt_s[rows, :] = dt
            dtx_s[rows, :] = dt * x

        def epilogue(rows):
            x = x_ref[rows, :].astype(jnp.float32)
            y_ref[rows, :] = (y_s[rows, :] + d_ref[...] * x).astype(y_ref.dtype)

        if "pro" in words:
            def row_group(i, carry):
                prologue(pl.ds(pl.multiple_of(i * 8, 8), 8), (8, channels))
                return carry

            jax.lax.fori_loop(0, chunk // 8, row_group, 0)
        else:
            prologue(slice(None), (chunk, channels))

        def across(column):
            if group == lanes:
                return column
            return pltpu.repeat(column, group // lanes, axis=1)

        def spread(ref, t, cs):
            """Row t of a tile, down n sublanes."""
            if "bcast" in words:
                return ref[pl.ds(t, n, stride=0), cs]
            return ref[pl.ds(t, 1), cs]

        row8 = jax.lax.broadcasted_iota(jnp.int32, (8, group), 0)
        for c0 in range(0, channels, group):
            cs = slice(c0, c0 + group)
            a = a_ref[:, cs]
            if "exp2" in words:
                a = a * LOG2E

            def steps(i, h, cs=cs, a=a):
                base = pl.multiple_of(i * unroll, unroll)
                parts = [None] * 8
                for j in range(unroll):
                    t = base + j
                    da = spread(dt_s, t, cs) * a
                    decay = jnp.exp2(da) if "exp2" in words else jnp.exp(da)
                    h = decay * h + spread(dtx_s, t, cs) * across(b_ref[t].astype(jnp.float32))
                    hc = h * across(c_ref[t].astype(jnp.float32))
                    if fold:
                        part = hc[:8]
                        for k in range(8, n, 8):
                            part = part + hc[k:k + 8]
                        parts[ORDER[j]] = part
                    else:
                        y_s[pl.ds(t, 1), cs] = jnp.sum(hc, axis=0, keepdims=True)
                if fold:
                    # a fold is sound where row r takes pa exactly when row r - shift takes pb
                    half = lambda off: ((row8 - off) & 7) < 4  # noqa: E731
                    q2 = _fold(parts[2], parts[6], half(1), 4)
                    q1 = _fold(parts[1], parts[5], half(2), 4)
                    q0 = _fold(parts[0], parts[4], half(3), 4)
                    q3 = _fold(parts[3], parts[7], half(0), 4)
                    r0 = _fold(q0, q2, ((row8 - 1) & 3) < 2, 2)
                    r1 = _fold(q1, q3, (row8 & 3) < 2, 2)
                    y_s[pl.ds(base, 8), cs] = _fold(r0, r1, (row8 & 1) == 0, 1)
                return h

            state_ref[:, cs] = jax.lax.fori_loop(0, chunk // unroll, steps, state_ref[:, cs])

        if "pro" in words and chunk % 16 == 0:  # y's rows are stored a packed tile at a time
            def out_group(i, carry):
                epilogue(pl.ds(pl.multiple_of(i * 16, 16), 16))
                return carry

            jax.lax.fori_loop(0, chunk // 16, out_group, 0)
        else:
            epilogue(slice(None))

    return kernel


# row r of the seven folds' result is the whole sum of the input at ORDER[r]
ORDER = (6, 5, 4, 3, 2, 1, 0, 7)
SERVED = (scan._kernel, scan._GROUP, getattr(scan, "_UNROLL", 0), getattr(scan, "_ROWS_IN", 0))


def install(form):
    words = set(form.split("+"))
    scan._kernel, scan._GROUP, scan._UNROLL, scan._ROWS_IN = SERVED
    sized = {w[0]: int(w[1:]) for w in words if re.fullmatch(r"[gti]\d+", w)}
    if "served" in words:
        if words - {"served"} - {w for w in words if w[0] in sized}:
            raise SystemExit(f"{form}: 'served' takes gN, tN and iN beside it, the body is the module's")
        scan._GROUP = sized.get("g", scan._GROUP)
        scan._UNROLL = sized.get("t", scan._UNROLL)
        scan._ROWS_IN = sized.get("i", scan._ROWS_IN)
    else:
        scan._kernel = body(words)
        scan._GROUP = sized.get("g", 512)
    jax.clear_caches()


def case(b, s, channels, dtype, n=16, seed=0):
    """Inputs under the published rates' shape (``tests/test_sambay.py``'s
    ``long_memory``): a state remembers for hundreds of tokens."""
    rng = np.random.default_rng(seed)
    f = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.float32).astype(dtype)  # noqa: E731
    dt = rng.uniform(0.001, 0.1, (b, s, channels))
    a = -jnp.asarray(np.tile(np.arange(1, n + 1), (channels, 1)) * 1e-2, jnp.float32)
    return dict(
        x=f(b, s, channels), dt_raw=jnp.asarray(np.log(np.expm1(dt)), jnp.float32).astype(dtype),
        dt_bias=jnp.zeros((channels,), jnp.float32), a=a, b=f(b, s, n), c=f(b, s, n),
        d=jnp.asarray(rng.standard_normal(channels), jnp.float32),
    )


def against_recurrence(b, s, channels, dtype, lens, n=16):
    args = case(b, s, channels, dtype, n=n, seed=s)
    lens = jnp.asarray(lens, jnp.int32)
    y, state = jax.jit(scan.selective_scan)(**args, lens=lens)
    exact = {k: v.astype(jnp.float32) for k, v in args.items()}
    want, state_want = jax.jit(scan.selective_scan_recurrent)(**exact, lens=lens)
    real = (jnp.arange(s)[None, :] < lens[:, None])[:, :, None]
    dy = jnp.where(real, jnp.abs(y.astype(jnp.float32) - want), 0.0)
    return float(dy.max()), float(jnp.abs(state - state_want).max()), float(jnp.abs(state_want).max())


def emit(**row):
    print(json.dumps(row), flush=True)
    os.makedirs(OUT, exist_ok=True)
    with open(os.path.join(OUT, "scan_forms.jsonl"), "a", encoding="utf-8") as f:
        f.write(json.dumps(row) + "\n")


LAYERS = 9  # calls in one program: the sixth judge's Mamba layers


def timed(form, b=3, s=8192, channels=5120, dtype=jnp.bfloat16, repeat=10):
    install(form)
    # float32 first: a row out of order or a roll turned the other way is no round-off
    dy32, dstate32, _ = against_recurrence(2, 520, 1024, jnp.float32, [520, 261])
    dy, dstate, size = against_recurrence(2, 520, 1024, dtype, [520, 261])
    if dy32 > 1e-3 or dstate32 > 1e-3:
        emit(form=form, wrong=True, y_f32_max_abs=dy32, state_f32_max_abs=dstate32)
        return
    args = case(b, s, channels, dtype)
    lens = jnp.asarray([s, s - 3, s // 2 + 1], jnp.int32)[:b]
    wide = lambda v: scan._across_lanes(v, scan._LANES)  # noqa: E731
    rest = (args["dt_raw"], args["dt_bias"], args["a"].T, wide(args["b"]), wide(args["c"]), args["d"])

    # One dispatch costs the host half a millisecond, so a layer's time is a
    # stack's over its depth.  The loop carries one number (a carried y would
    # be copied every trip), and the lengths take the trip's index so that the
    # call cannot be moved out of the loop.
    def layer(i, carry, x, *rest):
        y, _ = scan.selective_scan_chunked(
            x, *rest, lens - i // LAYERS, chunk=scan._CHUNK, block=channels, interpret=False
        )
        return carry + y[0, 0, :128].astype(jnp.float32).sum()

    stack = jax.jit(lambda *ops: jax.lax.fori_loop(0, LAYERS, lambda i, c: layer(i, c, *ops), 0.0))
    t0 = time.perf_counter()
    jax.block_until_ready(stack(args["x"], *rest))
    compile_s = time.perf_counter() - t0
    times = []
    for _ in range(repeat):
        t0 = time.perf_counter()
        jax.block_until_ready(stack(args["x"], *rest))
        times.append((time.perf_counter() - t0) * 1e3 / LAYERS)
    emit(form=form, module=MODULE, shape=[b, s, channels], n=16, group=scan._GROUP,
         ms_a_layer_min=min(times), ms_a_layer_median=sorted(times)[len(times) // 2],
         y_f32_max_abs=dy32, state_f32_max_abs=dstate32, y_max_abs=dy, state_max_abs=dstate,
         state_size=size, compile_s=round(compile_s, 1), device=jax.devices()[0].device_kind)


def check(form):
    install(form)
    for channels, dtype, n in ((512, jnp.float32, 16), (256, jnp.bfloat16, 16), (128, jnp.float32, 8)):
        dy, dstate, size = against_recurrence(2, 75, channels, dtype, [75, 34], n=n)
        print(form, f"channels={channels} n={n}", jnp.dtype(dtype).name, "y", dy, "state", dstate,
              "of", size, flush=True)


def sched(form):
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    install(form)
    arg = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=chip)  # noqa: E731
    b, s, channels, n = 3, 1024, 5120, 16
    tile, wide = arg((b, s, channels), jnp.bfloat16), arg((b, s, n, 128), jnp.bfloat16)
    row = arg((channels,), jnp.float32)
    scan.selective_scan_chunked.lower(
        tile, tile, row, arg((n, channels), jnp.float32), wide, wide, row, arg((b,), jnp.int32),
        chunk=128, block=channels, interpret=False,
    ).compile()


OPS = ("vmul.f32", "vadd.f32", "vsel", "vrot.slane", "vperm.slane", "vpow2.f32", "vlog2.f32",
       "vpop.eup", "vmov", "vunpack.c.l.bf16", "vunpack.c.h.bf16", "vpack.c.bf16", "vld", "vst",
       "vst.msk")


def bundles(directory):
    """(bundles of a grid step outside its loops, the loops inside it) from the
    final bundles' text: a line a bundle, ``>`` the grid's own loop and ``>>``,
    ``>>>`` the loops inside it, whose first bundle is marked ``LB`` and whose
    exit test names their trips; a loop's ``runs`` are its trips times those
    of the loops around it."""
    paths = glob.glob(os.path.join(directory, "*selective_scan_chunked*-final_bundles.txt"))
    if not paths:
        raise SystemExit(f"no final bundles of selective_scan_chunked under {directory}")
    outside, loops, around = 0, [], {}
    for line in open(paths[0], encoding="utf-8", errors="replace"):
        at = re.match(r"\s*0x[0-9a-f]+\s+(\w\w)?\s*:\s*(>+) \{", line)
        if not at:
            continue
        depth = len(at.group(2))
        if depth == 1:
            outside += 1
            continue
        if at.group(1) == "LB" or depth not in around:
            around[depth] = {"bundles": 0, "trips": 0, "depth": depth, "inside": around.get(depth - 1),
                             "ops": dict.fromkeys((*OPS, "spill_ld", "spill_st"), 0)}
            loops.append(around[depth])
        for deeper in [d for d in around if d > depth]:
            del around[deeper]
        loop = around[depth]
        loop["bundles"] += 1
        # a loop's last inner loop may hold the outer's exit test beside its own
        for trips in re.findall(r", (\d+) /\* loop exit test \*/", line):
            first = next(around[d] for d in range(depth, 1, -1) if not around[d]["trips"])
            first["trips"] = int(trips)
        for name in OPS:
            loop["ops"][name] += len(re.findall(rf" {re.escape(name)}[ (]", line))
        loop["ops"]["spill_ld"] += len(re.findall(r"vld [^;]*_spill", line))
        loop["ops"]["spill_st"] += len(re.findall(r"vst[.\w]* [^;]*_spill", line))
    for loop in loops:
        loop["runs"], up = loop["trips"], loop.pop("inside")
        while up is not None:
            loop["runs"], up = loop["runs"] * up["trips"], up.get("inside")
    return outside, loops


def count(directory, form, chunk=128, channels=5120):
    """One JSON line: the form's bundles a grid step of [chunk, channels]."""
    outside, loops = bundles(directory)
    # softplus takes a logarithm, a position's decay a power of two and no logarithm
    position = [lp for lp in loops if lp["ops"]["vpow2.f32"] and not lp["ops"]["vlog2.f32"]]
    rows = [lp for lp in loops if lp not in position]
    step = outside + sum(lp["bundles"] * lp["runs"] for lp in loops)
    trip = position[-1]
    per_trip = chunk // trip["trips"]
    lane_groups = channels // (sum(lp["runs"] for lp in position) // trip["trips"]) // 128
    slots = sum(v for k, v in trip["ops"].items() if k not in ("vpop.eup", "vld", "vst", "vst.msk")
                and not k.startswith("spill"))
    print(json.dumps({
        "form": form, "bundles_a_grid_step": step, "outside_loops": outside,
        "positions_a_trip": per_trip, "lane_groups_a_trip": lane_groups,
        "bundles_a_trip": sorted({lp["bundles"] for lp in position}),
        "bundles_a_position_and_lane_group": round(trip["bundles"] / per_trip / lane_groups, 2),
        "alu_slot_operations_a_trip": slots,
        "other_loops": sorted({(lp["bundles"], lp["runs"]) for lp in rows}),
        "trip_ops": {k: v for k, v in trip["ops"].items() if v},
    }), flush=True)


if __name__ == "__main__":
    forms = FORMS or DEFAULT
    if BUNDLES:
        count(BUNDLES, forms[0])
    elif SCHED:
        sched(forms[0])
    elif CHECK:
        for form in forms:
            check(form)
    else:
        if jax.default_backend() != "tpu":
            sys.exit("a time comes only from the chip: run through the chip tool")
        for form in forms:
            try:
                timed(form)
            except Exception as e:  # a form Mosaic refuses is a finding, not the run's end
                emit(form=form, refused=f"{type(e).__name__}: {str(e)[:300]}")
