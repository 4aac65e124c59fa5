"""bench_host.py is the host-path benchmark: it must stay device-free."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def parse_single_json_line(stdout: str) -> dict:
    lines = [ln for ln in stdout.strip().splitlines() if ln.strip()]
    assert len(lines) == 1, f"expected exactly one output line, got: {lines!r}"
    return json.loads(lines[0])


def test_bench_host_is_device_free_and_emits_one_record():
    """bench_host.py must produce exactly one JSON record WITHOUT importing
    jax (its own in-process assert backs the record's jax_imported field);
    breakdown fields present so the host-path claim is driver-parseable."""
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench_host.py"),
         "--requests", "3"],
        capture_output=True,
        text=True,
        errors="replace",
        env=env,
        cwd=REPO,
        timeout=120,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    rec = parse_single_json_line(proc.stdout)
    assert rec["jax_imported"] is False
    assert rec["judges"] == 8 and rec["n_candidates"] == 64
    assert rec["p50_ms"] > 0 and rec["p99_ms"] >= rec["p50_ms"]
    assert rec["breakdown"]["tokenize_p50_ms"] > 0
    assert rec["breakdown"]["score_engine_p50_ms"] > 0
    assert rec["baseline_basis"]["answers_per_sec"] == 25.0
