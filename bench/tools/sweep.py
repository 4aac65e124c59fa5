#!/usr/bin/env python3
"""Find a cell's knee: one server start, one short open-loop window per rate.

    python3 bench/tools/sweep.py --workload NAME --seed N --seconds 12 \
        --rates 12,16,20,24,28

Not one of the benchmark's runs: the builder uses it once, on the chip, and
writes the rate it finds into the mix's file as a number (PERF.md has the
table).  "Sustains" is judged from what each row prints: completed within the
window against offered, requests still out when the window closed, and the
median latency of the window's last third against its first third (a backlog
that grows shows as a ratio well above 1).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

import run as bench_run  # noqa: E402
import stats  # noqa: E402
from server import Server, get_metrics, held_peak_bytes  # noqa: E402


def main() -> int:
    parser = argparse.ArgumentParser("bench/tools/sweep.py")
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=1)
    parser.add_argument("--seconds", type=float, default=12.0)
    parser.add_argument("--rates", required=True)
    parser.add_argument("--dry-run", action="store_true")
    args = parser.parse_args()
    root = bench_run.ROOT
    _, cell, config, cfg, mix, gen = bench_run.load_cell(args.workload, args.dry_run)
    tok = config["tokenizer"]
    vocab_words = cfg["vocab_size"] - tok["specials"]
    rates = [float(r) for r in args.rates.split(",")]
    work = os.path.join(root, ".bench_work", "sweep." + cell["name"])
    os.makedirs(os.path.join(work, "prof"), exist_ok=True)
    schedules = {
        rate: gen.generate({**mix, "loop": "open", "rate": rate}, args.seed, args.seconds, vocab_words)
        for rate in rates
    }
    every = [r for reqs in schedules.values() for r in reqs]
    shapes = bench_run.warm_shapes(gen, every, tok["overhead"], int(cfg["max_tokens"]))
    files = bench_run.prepare_files(work, config, cfg, args.seed)
    env = bench_run.server_env(config, files, shapes, work, args.dry_run)
    with Server(env, os.path.join(work, "server.log")) as server:
        server.wait_listening(timeout=1150.0)
        first = get_metrics(server.port)
        bench_run.check_device(
            first.get("device") or {}, config, cell["chips"], args.dry_run
        )
        bench_run.warm_requests(server.port, gen, every, tok["overhead"], mix)
        for rate in rates:
            schedule = os.path.join(work, "schedule.jsonl")
            out = os.path.join(work, "results.jsonl")
            bench_run.write_schedule(schedule, gen, schedules[rate])
            before = get_metrics(server.port)
            loadgen = bench_run.start_loadgen(
                server.port, gen, schedule, out, args.seconds
            )
            if loadgen.stdout.readline().strip() != b"ready":
                raise SystemExit("load generator did not come up")
            loadgen.stdin.write(b"go\n")
            loadgen.stdin.flush()
            loadgen.stdout.read()
            loadgen.wait()
            after = get_metrics(server.port)
            results = bench_run.read_results(out)
            ok = [r for r in results if r["status"] == 200]
            lat = sorted((r["due_s"], (r["done_s"] - r["due_s"]) * 1e3) for r in ok)
            third = max(1, len(lat) // 3)
            first_third = stats.percentile([v for _, v in lat[:third]], 50)
            last_third = stats.percentile([v for _, v in lat[-third:]], 50)
            row = {
                "rate": rate,
                "offered": len(results),
                "ok": len(ok),
                "done_in_window": sum(r["done_s"] <= args.seconds for r in ok),
                "out_at_close": sum(r.get("done_s", 1e9) > args.seconds for r in results),
                "p50_ms": stats.percentile([v for _, v in lat], 50),
                "p95_ms": stats.percentile([v for _, v in lat], 95),
                "last_over_first_third": last_third / first_third,
                "lateness_p95_ms": stats.percentile(
                    [(r["sent_s"] - r["due_s"]) * 1e3 for r in results], 95
                ),
                "dispatches": after["device_batcher"]["dispatches"] - before["device_batcher"]["dispatches"],
                "compiled": bench_run.compiled_in_window(before, after),
                "compile_events": bench_run.compile_events(after) - bench_run.compile_events(before),
                "peak_bytes": held_peak_bytes(server.memory_stats()),
                "by_program": bench_run.dispatch_counts(before, after),
            }
            print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
