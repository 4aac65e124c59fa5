#!/usr/bin/env python3
"""Cut a profiler trace down to a fixture for ``bench/tests/test_xplane.py``.

    python3 bench/tools/record_trace.py PROFILE_DIR OUT.json [OPS]

Keeps the first OPS (default 1500) operations of the first device plane and
the host events that overlap them, in the form ``xplane.read`` returns, with
the values ``xplane.busy`` gives on exactly that cut stored as ``expected``.
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import xplane  # noqa: E402


def main() -> int:
    directory, out = sys.argv[1], sys.argv[2]
    count = int(sys.argv[3]) if len(sys.argv) > 3 else 1500
    trace = xplane.read(xplane.newest_xplane(directory))
    dev = next(d for d in trace["devices"] if d["ops"])
    ops = sorted(dev["ops"], key=lambda op: op[1])[:count]
    lo, hi = ops[0][1], ops[-1][1] + ops[-1][2]
    small = {
        "devices": [
            {
                "name": dev["name"],
                "lines": dev["lines"],
                "modules": [],
                "ops": [
                    [name, start, dur, {k: str(v)[:120] for k, v in st.items()}]
                    for name, start, dur, st in ops
                ],
            }
        ],
        "host": [
            [line, name, start, dur]
            for line, name, start, dur in trace["host"]
            if start < hi and start + dur > lo and dur > 1e6
        ][:400],
    }
    got = xplane.busy(small)
    small["expected"] = {
        "busy_s": got["busy_s"],
        "window_s": got["window_s"],
        "top3": [name for name, _ in got["device_ops"]][:3],
    }
    with open(out, "w", encoding="utf-8") as f:
        json.dump(small, f)
    print(json.dumps(small["expected"]))
    return 0


if __name__ == "__main__":
    sys.exit(main())
