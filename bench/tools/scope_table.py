#!/usr/bin/env python3
"""PERF.md's tables of one traced run, by hand.

    python3 bench/tools/scope_table.py PROFILE_DIR [MODULE_PREFIX ...]

Prints the device time of the model programs by scope and operation kind
(``scope_time.table_text``), then the host spans of the trace by name: how
many, their mean and their sum.  The prefixes default to the served entry
points of ``bge-large-en``.
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import scope_time  # noqa: E402
import xplane  # noqa: E402


def main() -> int:
    directory = sys.argv[1]
    prefixes = sys.argv[2:] or ["jit__embed_and_vote", "jit__embed_and_vote_many"]
    path = xplane.newest_xplane(directory)
    trace = scope_time.scoped(path)
    print(f"{path}: {os.path.getsize(path)} bytes")
    print(scope_time.table_text(trace, prefixes))
    by: dict = {}
    for _, name, _, dur, _ in trace["spans"]:
        count, total = by.get(name, (0, 0.0))
        by[name] = (count + 1, total + dur)
    for name, (count, total) in sorted(by.items()):
        print(f"{name:20s} n={count:5d} mean={total / count / 1e6:9.3f} ms sum={total / 1e9:8.3f} s")
    return 0


if __name__ == "__main__":
    sys.exit(main())
