#!/usr/bin/env python3
"""Cut a traced run down to the fixture of ``bench/tests/test_scope_time.py``.

    python3 bench/tools/record_scope_trace.py WORK_DIR OUT.json [PROGRAMS]

WORK_DIR is a run's ``.bench_work/<cell>`` (its ``prof/`` and
``profile_metrics.json``).  Keeps the first PROGRAMS (default 5) executions of
any program with the operations inside them, the host spans that
overlap that stretch, the instructions those operations point at, and of the
two /metrics documents the dispatch counts alone; stores beside them, as
``expected``, what the reducers give on exactly that cut.
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import scope_time  # noqa: E402
import xplane  # noqa: E402
from reducers import attention_roofline  # noqa: E402

PREFIXES = ["jit__embed_and_vote", "jit__embed_and_vote_many"]


def main() -> int:
    work, out = sys.argv[1], sys.argv[2]
    keep = int(sys.argv[3]) if len(sys.argv) > 3 else 5
    trace = scope_time.scoped(xplane.newest_xplane(os.path.join(work, "prof")))
    modules = sorted(trace["modules"], key=lambda m: m[1])[:keep]
    lo, hi = modules[0][1], modules[-1][1] + modules[-1][2]
    ops = [op for op in trace["ops"] if lo <= op[1] < hi]
    used = sorted({op[0] for op in ops})
    renumber = {old: new for new, old in enumerate(used)}
    with open(os.path.join(work, "profile_metrics.json"), encoding="utf-8") as f:
        profile = json.load(f)
    small = {
        "modules": modules,
        "instructions": [trace["instructions"][i] for i in used],
        "ops": [[renumber[i], start, dur] for i, start, dur in ops],
        "spans": [s for s in trace["spans"] if s[2] < hi and s[2] + s[3] > lo],
        "profile": {
            side: {"roofline": {"buckets": {
                label: {"count": row.get("count", 0)}
                for label, row in (profile[side].get("roofline") or {}).get("buckets", {}).items()
            }}}
            for side in ("before", "after")
        },
    }
    table, program_ns = scope_time.by_scope(small, PREFIXES)
    by: dict = {}
    for (scope, _), ns in table.items():
        by[scope] = by.get(scope, 0.0) + ns
    ctx = {
        "scoped": small,
        "config": {"trace_modules": PREFIXES},
        "cfg": {"num_hidden_layers": 24, "hidden_size": 1024},  # bge-large-en
        "peaks": {"bf16_flops_per_s": 197e12},
        "profile": small["profile"],
    }
    small["expected"] = {
        "program_ns": program_ns,
        "share_by_scope": {s: 100.0 * ns / program_ns for s, ns in sorted(by.items())},
        # the dispatch counts are the whole trace's and the programs the
        # cut's, so this is the reducer's arithmetic on the cut, not a
        # reading of the chip
        "attention_roofline": attention_roofline.reduce(ctx),
        "idle_with_work": scope_time.idle_with_work(ctx),
    }
    with open(out, "w", encoding="utf-8") as f:
        json.dump(small, f)
    print(json.dumps(small["expected"]))
    return 0


if __name__ == "__main__":
    sys.exit(main())
