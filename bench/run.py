#!/usr/bin/env python3
"""The benchmark's command: one run of one cell.

    python3 bench/run.py --workload NAME --seed N --seconds S --trace 0|1

Everything a cell is made of is found by name, from ``BENCHMARK.json`` at the
checkout root: its configuration (``bench/configs/<config>.json``), its
traffic mix (``bench/traffic/<traffic>.json``, read by the generator the mix
names), the per-layer metrics that list the cell
(``bench/layer_metrics/<metric>.json``; a metric read from a trace names a
reducer in ``bench/reducers/``) and the output check the configuration names
(``bench/checks/``, with its plain reference in ``bench/references/``).  The
configuration's file names the rest: its ``family`` (``bench/families/``: the
checkpoint's tensors and the forward's operations), its tokenizer ``kind``
(``bench/tokenizers/``) and, under ``serve``, how the program is handed them:
the environment variables that take the checkpoint and the vocabulary, the
``/metrics`` ``device`` key that holds the parameters' dtype, and the warm-up
recipe (``bench/warmups/``), if the program has a warm-up to spell.  The
generator owns its route and its answer: the path it posts to, the fields of
an answer that are kept and whether they are well formed.  This file names no
family, tokenizer, role, scorer, route or field (``bench/byname.py``), so
adding any of them adds files and entries and edits none.

One run, in order (what is set-up and what is not):

  set-up   checkpoint and vocabulary from --seed (public HF layout); schedule
           from --seed; the program's own server as a child, with the shapes
           the schedule reaches spelled as the configuration's warm-up recipe
           spells them; one warm request per shape and one concurrent burst;
           the load generator (a second child, no jax) loaded and connected.
           ``setup_s`` ends here.
  window   --seconds of load.  With --trace 1 a profile of TRACE_MS is taken
           at its end through POST /v1/profile.
  after    /metrics again: a compilation inside the window fails the run.
           The server is stopped and has exited before this process first
           touches jax; then the plain reference runs on the same device over
           a seeded sample of the window's own answers, and the check decides
           ``correct``.  None of this is in ``setup_s``.

The last stdout line is the result.  No accelerator, a server whose parameters
are not in the configuration's ``precision`` or whose Pallas kernels are
interpreted, or a compile inside the window:
exit code 1 and no result line.  ``--dry-run`` rehearses the same plumbing on
the CPU at the configuration's ``dry_run`` sizes and prints counts only.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import threading
import time

T_START = time.monotonic()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

import byname  # noqa: E402
import checkpoints  # noqa: E402
import layers  # noqa: E402
import stats  # noqa: E402
from server import (  # noqa: E402
    BenchError, Server, get_metrics, held_peak_bytes, http_json, tail_of,
)

TRACE_MS = 8000  # /v1/profile takes at most 10 s
# The profile is the window's last 9 s but one: when it stops, the profiler
# writes for some 15 s and the server all but stands still meanwhile, which
# must fall after the window and not inside it.
TRACE_BEFORE_END_S = 9.0
DRAIN_S = 20.0


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


def load_json(*path):
    with open(os.path.join(*path), encoding="utf-8") as f:
        return json.load(f)


def find(entries: list, name: str, what: str) -> dict:
    for entry in entries:
        if entry["name"] == name:
            return entry
    raise BenchError(f"no {what} named {name!r} in BENCHMARK.json")


# -- set-up ------------------------------------------------------------------


def model_cfg(config: dict, dry: bool) -> dict:
    """The sizes run: the configuration's file itself, or its tiny stand-in."""
    return {**config, **config["dry_run"]["sizes"]} if dry else config


def prepare_files(work: str, config: dict, cfg: dict, seed: int) -> dict:
    """Checkpoint and tokenizer file from the seed; returns their paths."""
    ckpt = os.path.join(work, "ckpt")
    checkpoints.write_checkpoint(ckpt, config["family"], cfg, seed)
    tokenizer = byname.module("tokenizers", config["tokenizer"]["kind"])
    vocab = os.path.join(ckpt, tokenizer.FILE)
    tokenizer.write(vocab, cfg["vocab_size"])
    return {"ckpt": ckpt, "vocab": vocab}


def warm_shapes(gen, requests: list, overhead: int, cap: int) -> list:
    """Distinct (N, tokens) the schedule reaches; the program snaps tokens to
    its own sequence bucket.  How long a request is, is its generator's to
    say."""
    return sorted(
        {(r["n"], min(gen.request_tokens(r, overhead), cap)) for r in requests}
    )


def server_env(config, files, shapes, work, dry) -> dict:
    """The program's environment: the configuration's own ``server_env``,
    the checkpoint and the vocabulary under the variables its ``serve`` block
    names, and the warm shapes as its recipe spells them (no recipe, no such
    variable)."""
    env = dict(config["server_env"])
    if dry:
        env["JAX_PLATFORMS"] = "cpu"
    serve = config["serve"]
    env[serve["weights_env"]] = files["ckpt"]
    env[serve["vocab_env"]] = files["vocab"]
    env["PROFILE_DIR"] = os.path.join(work, "prof")
    if "warmup" in serve:
        env.update(byname.module("warmups", serve["warmup"]).env(shapes, env))
    return env


def post_warm(port: int, path: str, body: dict) -> None:
    status, raw = http_json(port, "POST", path, body)
    if status != 200:
        raise BenchError(f"warm request: HTTP {status}: {raw[:300]!r}")


def compile_events(doc: dict) -> int:
    cache = doc.get("compile_cache") or {}
    return int(cache.get("hits") or 0) + int(cache.get("misses") or 0)


def dispatch_counts(before: dict, after: dict) -> dict:
    """Dispatches inside the window by program label (``/metrics``
    ``roofline.buckets``): which group sizes the traffic really reached."""
    was = (before.get("roofline") or {}).get("buckets") or {}
    now = (after.get("roofline") or {}).get("buckets") or {}
    out = {}
    for label, row in now.items():
        delta = int(row.get("count") or 0) - int((was.get(label) or {}).get("count") or 0)
        if delta:
            out[label] = delta
    return out


WARM_ROUNDS = 2


def warm_requests(port, gen, requests, overhead: int, mix: dict) -> dict:
    """One request per distinct shape, alone (the single-request dispatch),
    then for each size in the mix's ``warm_groups`` one grouped dispatch of
    exactly that many requests, ``warm_rounds`` times over (default 2).

    The program builds, the first time it meets one, a small helper program
    per (request bucket, group size), on top of the model programs its own
    warm-up compiled; met inside the window it stalls the dispatcher for the
    better part of a second (PERF.md, Findings).  How arrivals split into
    groups is the batcher's business, so a group of exactly r is made like
    this: six requests under another grouping key (the generator's
    ``blocker``: the same programs) go first and keep both of the batcher's
    pipeline slots busy for some 150 ms; r requests sent 60 ms behind them
    queue up meanwhile and are taken together when a slot frees.  A mix of long requests needs fewer
    blockers to keep the slots busy that long: ``warm_blockers`` (default 6).
    One blocker goes 10 ms ahead of the others: sent together they may all
    fall into the batcher's 3 ms gathering window and fill ONE slot, and the
    r requests then trickle into the free one in twos and threes, so that the
    helper of size r is met first inside the window (PERF.md, PR 26)."""
    groups = mix["warm_groups"]
    rounds = int(mix.get("warm_rounds", WARM_ROUNDS)) if groups else 0
    blockers = int(mix.get("warm_blockers", 6))
    bodies = warm_bodies(gen, requests, overhead)
    for body in bodies:
        post_warm(port, gen.PATH, body)
    for _ in range(rounds):
        for body in bodies:
            blocker = gen.blocker(body)
            for size in groups:
                ahead = start_burst(port, gen.PATH, blocker, 1)
                time.sleep(0.01)
                ahead += start_burst(port, gen.PATH, blocker, blockers - 1)
                time.sleep(0.06)
                behind = start_burst(port, gen.PATH, body, int(size))
                finish_burst(ahead + behind)
    return {"shapes": len(bodies), "rounds": rounds, "groups": list(groups)}


def warm_bodies(gen, requests: list, overhead: int) -> list:
    """The body of the first request of each distinct (N, tokens)."""
    bodies, done = [], set()
    for req in requests:
        shape = (req["n"], gen.request_tokens(req, overhead))
        if shape not in done:
            done.add(shape)
            bodies.append(gen.render_body(req))
    return bodies


def start_burst(port: int, path: str, body: dict, size: int) -> list:
    errors: list = []

    def one():
        try:
            post_warm(port, path, body)
        except BenchError as e:
            errors.append(e)

    threads = [threading.Thread(target=one) for _ in range(size)]
    for t in threads:
        t.start()
    return [(threads, errors)]


def finish_burst(bursts: list) -> None:
    for threads, errors in bursts:
        for t in threads:
            t.join()
        if errors:
            raise errors[0]


def check_device(device: dict, config: dict, chips: int, dry: bool) -> None:
    """``device`` is the section of that name of the program's /metrics; the
    configuration says under which key it holds the parameters' dtype."""
    if dry:
        return
    if device.get("platform") != "tpu":
        raise BenchError(f"no accelerator: the server runs on {device}")
    if int(device.get("device_count", 0)) < chips:
        raise BenchError(f"{chips} chips asked, the server sees {device}")
    dtype, want = device.get(config["serve"]["param_dtype"]), config["precision"]
    if dtype != want:
        raise BenchError(f"parameters are {dtype}, not {want}: {device}")
    if device.get("pallas_interpret") is not False:
        raise BenchError(f"Pallas kernels are interpreted: {device}")


# -- the window --------------------------------------------------------------


def write_schedule(path: str, gen, requests: list) -> None:
    with open(path, "w", encoding="utf-8") as f:
        for req in requests:
            item = {"index": req["index"], "body": json.dumps(gen.render_body(req))}
            for key in ("due_s", "caller", "turn"):
                if key in req:
                    item[key] = req[key]
            f.write(json.dumps(item) + "\n")


def start_loadgen(port: int, gen, schedule: str, out: str, seconds: float):
    return subprocess.Popen(
        [
            sys.executable, os.path.join(HERE, "loadgen.py"),
            "--schedule", schedule, "--out", out, "--port", str(port),
            "--path", gen.PATH, "--keep", ",".join(gen.KEEP),
            "--seconds", str(seconds), "--drain", str(DRAIN_S),
        ],
        stdin=subprocess.PIPE,
        stdout=subprocess.PIPE,
        env={k: v for k, v in os.environ.items() if k != "BENCH_RUN"},
    )


def take_profile(port: int, delay: float, out: dict) -> None:
    """POST /v1/profile after ``delay`` seconds, with /metrics read just
    before it and again half a second before the trace ends (from a second
    thread: the call itself returns only when the profiler has written).
    The per-layer metrics of a traced run are read between the window's start
    and that second reading, so what the profiler's writing does to the server
    afterwards is in none of them."""
    time.sleep(max(delay, 0.0))
    out["before"] = get_metrics(port)

    def read_inside():
        time.sleep(TRACE_MS / 1e3 - 0.5)
        out["after"] = get_metrics(port)

    reader = threading.Thread(target=read_inside)
    reader.start()
    t0 = time.monotonic()
    status, raw = http_json(port, "POST", "/v1/profile", {"duration_ms": TRACE_MS})
    out["wall_s"] = time.monotonic() - t0
    reader.join()
    out["status"] = status
    if status != 200:
        out["error"] = raw[:300].decode("utf-8", "replace")


def compiled_in_window(before: dict, after: dict) -> list:
    """Model programs compiled between two /metrics documents (the ``jit``
    section: a new specialization of a jitted entry, a new AOT bucket), as
    readable names.  Any of these fails the run."""
    changed = []
    jb, ja = before.get("jit", {}), after.get("jit", {})
    if ja.get("aot_buckets") != jb.get("aot_buckets"):
        changed.append(f"aot_buckets {jb.get('aot_buckets')} -> {ja.get('aot_buckets')}")
    for name, count in (ja.get("specializations") or {}).items():
        was = (jb.get("specializations") or {}).get(name)
        if count != was:
            changed.append(f"jit {name}: {was} -> {count} specializations")
    return changed


def read_results(path: str) -> list:
    with open(path, encoding="utf-8") as f:
        return [json.loads(line) for line in f if line.strip()]


def end_to_end(results: list, loop: str, seconds: float) -> dict:
    """Every end-to-end number the window gives; the cell reports those that
    BENCHMARK.json lists for it."""
    ok = [r for r in results if r["status"] == 200]
    out = {}
    if loop == "open":
        lat = [(r["done_s"] - r["due_s"]) * 1e3 for r in ok]
        if lat:
            out["latency_p50_ms"] = stats.percentile(lat, 50)
            out["latency_p95_ms"] = stats.percentile(lat, 95)
    # answers finished inside the window, and for those still out when it
    # closed the share of their time in flight that lay inside it: all the
    # window's work over all its time, without the one-answer steps a plain
    # count would have where answers take seconds
    done = 0.0
    for r in ok:
        if r["done_s"] <= seconds:
            done += 1.0
        elif r["sent_s"] < seconds:
            done += (seconds - r["sent_s"]) / (r["done_s"] - r["sent_s"])
    out["answers_per_s"] = done / seconds
    return out


# -- main ---------------------------------------------------------------------


def load_cell(workload: str, dry: bool, benchmark=None):
    """Everything a cell is made of, found by name: (BENCHMARK.json, its
    workload entry, the configuration, the sizes run, the mix, the generator).
    A dry run swaps in the tiny sizes, the tiny traffic and the limits that
    go with them (there the numbers compared are round-off of float32)."""
    bench = load_json(benchmark or os.path.join(ROOT, "BENCHMARK.json"))
    cell = find(bench["workloads"], workload, "workload")
    entry = find(bench["configs"], cell["config"], "config")
    config = load_json(ROOT, entry["file"])
    mix = load_json(HERE, "traffic", cell["traffic"] + ".json")
    gen = byname.module("generators", mix["generator"])
    cfg = model_cfg(config, dry)
    if dry:
        mix = {**mix, **mix.get("dry_run", {})}
        check = {**config["check"], **config["dry_run"].get("check", {})}
        env = {**config["server_env"], **config["dry_run"].get("server_env", {})}
        config = {**config, "check": check, "server_env": env}
    return bench, cell, config, cfg, mix, gen


def run(args) -> int:
    dry = args.dry_run
    bench, cell, config, cfg, mix, gen = load_cell(args.workload, dry, args.benchmark)
    work = os.path.join(ROOT, ".bench_work", cell["name"])
    os.makedirs(os.path.join(work, "prof"), exist_ok=True)

    tok = config["tokenizer"]
    vocab_words = cfg["vocab_size"] - tok["specials"]
    requests = gen.generate(mix, args.seed, args.seconds, vocab_words)
    # a few requests of the mix's shapes with words of their own, for warming
    warm = gen.warm_sample(mix, args.seed, vocab_words)
    cap = int(cfg["max_tokens"])
    shapes = warm_shapes(gen, requests + warm, tok["overhead"], cap)
    log(f"[bench] {len(requests)} requests, {len(shapes)} shapes: {shapes[:12]}")

    files = prepare_files(work, config, cfg, args.seed)
    env = server_env(config, files, shapes, work, dry)
    if args.control:
        # the precision below the declared one, switched on in the program:
        # the run the check has to call incorrect (never one of the driver's)
        env.update(config["control"]["server_env"])
    schedule = os.path.join(work, "schedule.jsonl")
    results_path = os.path.join(work, "results.jsonl")
    server_log = os.path.join(work, "server.log")
    loadgen = None
    profile: dict = {}
    with Server(env, server_log) as server:
        write_schedule(schedule, gen, requests)  # while the server starts
        loadgen = start_loadgen(
            server.port, gen, schedule, results_path, args.seconds
        )
        try:
            server.wait_listening(timeout=1150.0)
            first = get_metrics(server.port)
            check_device(first.get("device") or {}, config, cell["chips"], dry)
            log(f"[bench] t={time.monotonic() - T_START:.1f}s server listening")
            warmed = warm_requests(
                server.port, gen, requests + warm, tok["overhead"], mix
            )
            if loadgen.stdout.readline().strip() != b"ready":
                raise BenchError("load generator did not come up")
            before = get_metrics(server.port)
            setup_s = time.monotonic() - T_START
            log(f"[bench] t={setup_s:.1f}s warmed; window starts")
            loadgen.stdin.write(b"go\n")
            loadgen.stdin.flush()
            profiler = None
            if args.trace:
                profiler = threading.Thread(
                    target=take_profile,
                    args=(server.port, args.seconds - TRACE_BEFORE_END_S, profile),
                )
                profiler.start()
            summary_raw = loadgen.stdout.read()
            if loadgen.wait() != 0:
                raise BenchError("load generator failed")
            if profiler is not None:
                profiler.join()
            after = get_metrics(server.port)
            memory = server.memory_stats()
            log(f"[bench] t={time.monotonic() - T_START:.1f}s window and drain over")
            results = read_results(results_path)
            by_index = {r["index"]: r for r in requests}
            served = [r for r in results if r["status"] == 200]
            malformed = [
                r["index"] for r in served
                if not gen.well_formed(r["kept"], by_index[r["index"]])
            ]
            check = byname.module("checks", config["check"]["name"])
            picked = check.sample(
                [(by_index[r["index"]], r["kept"]) for r in served
                 if r["index"] not in malformed],
                int(config["check"]["requests"]), args.seed,
            )
            vectors = check.collect(server.port, config, picked, gen.render_text)
        finally:
            if loadgen.poll() is None:
                loadgen.kill()
                loadgen.wait()
        if server.stop() != 0:
            log("[bench] server exited non-zero after SIGTERM:\n" + tail_of(server_log))
    summary = json.loads(summary_raw.decode().strip().splitlines()[-1])
    if summary["loop"] == "closed" and summary["sent"] >= summary["scheduled"] and not dry:
        raise BenchError(
            "the closed loop's callers ran out of requests: raise pool_per_s "
            f"in the mix ({summary})"
        )

    compiled = compiled_in_window(before, after)
    if compiled:
        raise BenchError(
            "compiled inside the window (the numbers would be compile "
            "times): " + "; ".join(compiled)
        )
    failed = [r for r in results if r["status"] != 200]
    lateness = [
        (r["sent_s"] - r["due_s"]) * 1e3 for r in results if r.get("due_s") is not None
    ]
    e2e = end_to_end(results, mix["loop"], args.seconds)
    e2e["setup_s"] = setup_s
    helper_compiles = compile_events(after) - compile_events(before)
    if helper_compiles:
        log(
            f"[bench] {helper_compiles} program(s) outside the jit section "
            "compiled inside the window (see PERF.md, Open questions)"
        )
    info = {
        "loadgen": summary,
        "warmed": warmed,
        "window_compile_events": helper_compiles,
        "generator_lateness_p95_ms": stats.percentile(lateness, 95) if lateness else None,
        "shapes": len(shapes),
        "dispatches_by_program": dispatch_counts(before, after),
        "memory_stats": memory,
        "compile_cache": after.get("compile_cache"),
        "aot_buckets": (after.get("jit") or {}).get("aot_buckets"),
        "failed_examples": [
            {k: r.get(k) for k in ("index", "status", "error")} for r in failed[:3]
        ],
    }
    print(json.dumps({"info": info}), flush=True)
    if lateness and "latency_p50_ms" in e2e:
        if info["generator_lateness_p95_ms"] > 0.1 * e2e["latency_p50_ms"]:
            log(
                "[bench] the generator ran late: p95 "
                f"{info['generator_lateness_p95_ms']:.2f} ms against a p50 "
                f"latency of {e2e['latency_p50_ms']:.2f} ms"
            )

    # the server has exited: from here this process may hold the device
    log(f"[bench] t={time.monotonic() - T_START:.1f}s server stopped; the reference runs")
    if dry:
        os.environ["JAX_PLATFORMS"] = "cpu"
    verdict = check.run(
        config=config,
        cfg=cfg,
        state=checkpoints.read_checkpoint(files.get("reference_ckpt", files["ckpt"])),
        picked=picked,
        vectors=vectors,
        dry=dry,
        cache_dir=os.environ.get("JAX_COMPILATION_CACHE_DIR")
        or os.path.join(ROOT, ".jax_cache"),
    )
    numbers = [
        {"name": "malformed_answers", "value": len(malformed), "limit": 0},
        *verdict["numbers"],
    ]
    # whatever else the check gives (how many it compared, what a fault
    # would have read) is printed beside the numbers, and compared with nothing
    extras = {k: v for k, v in verdict.items() if k != "numbers"}
    print(json.dumps({"check": numbers, **extras}), flush=True)
    correct = bool(served) and all(n["value"] <= n["limit"] for n in numbers)
    log(f"[bench] t={time.monotonic() - T_START:.1f}s checked")

    device = first["device"]
    result = {
        "correct": correct,
        "attempted": len(results),
        "failed": len(failed),
    }
    if dry:
        # a CPU run gives counts, never a device metric
        result["dry_run"] = True
        result["counts"] = {
            "served": len(served),
            "dispatches": (after.get("device_batcher") or {}).get("dispatches", 0)
            - (before.get("device_batcher") or {}).get("dispatches", 0),
        }
        print_result(result, numbers)
        return 0 if correct else 1
    result["device"] = {
        "platform": device["platform"],
        "kind": device["device_kind"],
        "count": device["device_count"],
        "memory_peak_bytes": held_peak_bytes(memory),
    }
    if args.trace:
        with open(os.path.join(work, "profile_metrics.json"), "w", encoding="utf-8") as f:
            json.dump({k: profile.get(k) for k in ("before", "after", "wall_s")}, f)

        traced = layers.reduce_all(
            bench, cell, config, cfg, before, profile, work, e2e
        )
        result["metrics"] = traced["metrics"]
        result["device"].update(traced["device"])
        result["breakdown"] = traced["breakdown"]
    else:
        result["metrics"] = {
            m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
            for m in bench["end_to_end"]
            if layers.reports(m, cell["name"]) and m["name"] in e2e
        }
    print_result(result, numbers)
    return 0


def print_result(result: dict, numbers: list) -> None:
    """The result, as the last line of stdout, with every number compared
    beside its limit as its last key; the same numbers are the last lines of
    stderr."""
    for n in numbers:
        log(f"[bench] compared {n['name']}: {n['value']!r} (limit {n['limit']!r})")
    result["check"] = {
        n["name"]: {"value": n["value"], "limit": n["limit"]} for n in numbers
    }
    print(json.dumps(result), flush=True)


def main() -> int:
    parser = argparse.ArgumentParser("bench/run.py")
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=float, default=None)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--dry-run", action="store_true")
    parser.add_argument("--control", action="store_true")
    parser.add_argument("--benchmark", default=None, help="another BENCHMARK.json (tests)")
    args = parser.parse_args()
    if args.seconds is None:
        args.seconds = float(
            load_json(args.benchmark or os.path.join(ROOT, "BENCHMARK.json"))["run_seconds"]
        )
    try:
        return run(args)
    except BenchError as e:
        log(f"[bench] FAILED: {e}")
        return 1


if __name__ == "__main__":
    sys.exit(main())
