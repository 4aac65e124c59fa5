#!/usr/bin/env python3
"""Run one cell as the bounds ask: sets of runs with the same seeds in each
set, one process per run, and the spread of every end-to-end metric.

    python3 bench/tools/spread.py --workload NAME --seeds 11,12,13,14,15,16 \
        --sets 2 --out chiprun_out/sets/NAME.jsonl [--seconds S] [--control]

Every run's result line (with its check numbers and info) is appended to
``--out``.  At the end, per set and per metric: the median and the distance
between the first and third quartile as a share of the median
(``statistics.quantiles(values, n=4)``), each set's first run left out of
``setup_s`` (it compiles), and the second set's median against the first's.
The builder sets BENCHMARK.json's bounds from the widest of these spreads.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

import stats  # noqa: E402


def one_run(workload: str, seed: int, seconds, trace: int, control: bool) -> dict:
    cmd = [
        sys.executable, os.path.join(HERE, "run.py"),
        "--workload", workload, "--seed", str(seed), "--trace", str(trace),
    ]
    if seconds is not None:
        cmd += ["--seconds", str(seconds)]
    if control:
        cmd.append("--control")
    proc = subprocess.run(cmd, stdout=subprocess.PIPE, stderr=subprocess.PIPE)
    record = {"seed": seed, "rc": proc.returncode, "trace": trace}
    for line in proc.stdout.decode("utf-8", "replace").splitlines():
        try:
            doc = json.loads(line)
        except ValueError:
            continue
        if "correct" in doc:
            record["result"] = doc
        elif "check" in doc:
            record["check"] = doc
        elif "info" in doc:
            record["info"] = doc["info"]
    if "result" not in record:
        record["stderr"] = proc.stderr.decode("utf-8", "replace")[-1500:]
    return record


def main() -> int:
    parser = argparse.ArgumentParser("bench/tools/spread.py")
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--sets", type=int, default=2)
    parser.add_argument("--seconds", type=float, default=None)
    parser.add_argument("--trace", type=int, default=0)
    parser.add_argument("--control", action="store_true")
    parser.add_argument("--out", required=True)
    args = parser.parse_args()
    seeds = [int(s) for s in args.seeds.split(",")]
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    sets = []
    with open(args.out, "a", encoding="utf-8") as out:
        for k in range(args.sets):
            runs = []
            for seed in seeds:
                rec = one_run(args.workload, seed, args.seconds, args.trace, args.control)
                rec["set"] = k
                out.write(json.dumps(rec) + "\n")
                out.flush()
                res = rec.get("result") or {}
                brief = {
                    "set": k, "seed": seed, "rc": rec["rc"],
                    "correct": res.get("correct"), "failed": res.get("failed"),
                    "metrics": {n: m["value"] for n, m in (res.get("metrics") or {}).items()},
                    "check": {c["name"]: c["value"] for c in (rec.get("check") or {}).get("check", [])},
                    "peak": (res.get("device") or {}).get("memory_peak_bytes"),
                    "stderr": rec.get("stderr", "")[-400:],
                }
                print(json.dumps(brief), flush=True)
                runs.append(rec)
            sets.append(runs)
    summary = {}
    for k, runs in enumerate(sets):
        names = sorted(
            {n for r in runs for n in ((r.get("result") or {}).get("metrics") or {})}
        )
        for name in names:
            values = [
                r["result"]["metrics"][name]["value"]
                for i, r in enumerate(runs)
                if "result" in r and name in r["result"]["metrics"]
                and not (name == "setup_s" and i == 0 and k == 0)
            ]
            if len(values) >= 2:
                summary.setdefault(name, []).append(
                    {
                        "set": k, "n": len(values),
                        "median": statistics.median(values),
                        "iqr_share": stats.iqr_share(values),
                        "min": min(values), "max": max(values),
                    }
                )
    for name, rows in summary.items():
        line = {"metric": name, "sets": rows}
        if len(rows) >= 2:
            line["second_over_first_median"] = rows[1]["median"] / rows[0]["median"]
            line["widest_iqr_share"] = max(r["iqr_share"] for r in rows)
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
