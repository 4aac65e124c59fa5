"""chip_smoke.py's plumbing on CPU, and the compile-cache rule it relies on.

The smoke itself proves the system on the TPU; what can be pinned here is
that its stages run end to end at test-tiny (``--dry-run``), that the
no-argument run refuses a CPU, that the script alone fails, that its parent
process stays off jax (a chip belongs to one process at a time), and where
every entry point keeps JAX's persistent compilation cache.
"""

import json
import os
import shutil
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def run(cmd, env, cwd=REPO, timeout=600):
    return subprocess.run(
        cmd,
        capture_output=True,
        text=True,
        errors="replace",
        env=env,
        cwd=cwd,
        timeout=timeout,
    )


@pytest.fixture(scope="module")
def dry_run():
    """ONE dry run (~45 s) shared by the tests below: a subprocess plays
    the smoke's parent, then reports whether jax entered its modules."""
    code = textwrap.dedent(
        """
        import json, sys
        import chip_smoke
        rc = chip_smoke.main(["--dry-run"])
        leaked = sorted(m for m in sys.modules if m.split(".")[0] == "jax")
        print(json.dumps({"parent_rc": rc, "parent_jax_modules": leaked}))
        """
    )
    proc = run([sys.executable, "-c", code], dict(os.environ))
    assert proc.returncode == 0, proc.stderr[-3000:]
    records = [json.loads(line) for line in proc.stdout.splitlines()]
    return {
        "parent": records[-1],
        "result": records[-2],
        "summary": records[-3],
        "stages": {r["stage"]: r for r in records if "stage" in r},
        "checks": [r for r in records if "check" in r],
    }


def test_dry_run_passes_every_stage(dry_run):
    assert dry_run["parent"]["parent_rc"] == 0
    summary = dry_run["summary"]
    assert summary["summary"] is True and summary["dry_run"] is True
    # mesh included: the dry run gives itself four virtual CPU devices
    assert summary["stages"] == {
        "serve": "passed", "kernels": "passed", "mesh": "passed"
    }


def test_last_line_is_the_result_and_nothing_else(dry_run):
    """The driver reads the last line of stdout: one object with exactly
    ``ok`` and ``device``, the device with exactly platform/kind/count."""
    assert dry_run["result"] == {
        "ok": True,
        "device": {"platform": "cpu", "kind": "cpu", "count": 4},
    }
    assert isinstance(dry_run["result"]["device"]["count"], int)


def test_summary_claims_nothing(dry_run):
    summary = dry_run["summary"]
    assert summary["claim"] is None
    assert summary["timings_are"] == "setup, not measured perf"
    assert set(summary["setup_s"]) == {"serve", "kernels", "mesh"}


def test_parent_never_imports_jax(dry_run):
    """The orchestrator must stay off jax: a parent that touched it would
    hold the chip its children need."""
    assert dry_run["parent"]["parent_jax_modules"] == []


def test_every_stage_line_names_device_and_versions(dry_run):
    for name in ("probe", "serve", "kernels", "mesh"):
        stage = dry_run["stages"][name]
        assert stage["platform"] == "cpu", name
        assert stage["device_kind"] == "cpu", name
        assert stage["device_count"] == 4, name
        assert set(stage["versions"]) == {"jax", "jaxlib", "libtpu"}, name
        assert stage["pass"] is True and stage["dry_run"] is True, name
    for rec in dry_run["checks"] + [dry_run["summary"]]:
        assert (rec["platform"], rec["device_kind"], rec["device_count"]) == (
            "cpu", "cpu", 4
        ), rec
    assert dry_run["stages"]["probe"]["native"] == {
        "loaded": True, "error": None
    }


def test_warmed_bucket_is_served_from_the_aot_table(dry_run):
    """Stage serve asserts the jit section did not move across the warmed
    requests; what is left afterwards is the two AOT vote variants + embed,
    and one lazy specialization each for the unwarmed long bucket and
    /embeddings."""
    jit = dry_run["stages"]["serve"]["jit"]
    assert jit["aot_buckets"] == 3
    assert jit["specializations"]["embed_and_vote"] == 1
    assert jit["specializations"]["embed"] == 1
    assert dry_run["stages"]["serve"]["requests"] == {
        "device:batch:consensus": 5
    }
    assert dry_run["stages"]["serve"]["xplane_files"] >= 1


def test_kernel_checks_pass_and_interpret_mode_is_visible(dry_run):
    checks = dry_run["checks"]
    assert len(checks) == 11 and all(c["pass"] for c in checks)
    # on CPU no kernel is a Mosaic custom call, and the output says so;
    # the no-argument run REQUIRES the custom call in the compiled HLO
    kernels = [c for c in checks if not c["check"].startswith("forward")]
    assert len(kernels) == 8 and not any(c["mosaic"] for c in kernels)
    assert dry_run["stages"]["serve"]["param_dtype"] == "float32"


def test_second_child_hits_what_the_first_compiled(dry_run):
    kernels = dry_run["stages"]["kernels"]
    assert kernels["cache_hits_for_served_bucket"] >= 1
    assert kernels["compile_cache"]["dir"] == os.path.join(REPO, ".jax_cache")


def test_mesh_stage_serves_over_four_devices(dry_run):
    mesh = dry_run["stages"]["mesh"]
    assert mesh["jit"]["specializations"]["mesh_embed_and_vote"] == 1
    assert len(mesh["devices"]) == 4


def test_no_argument_run_refuses_a_cpu():
    """Without --dry-run the smoke needs the chip: on CPU it exits
    non-zero after the probe, prints no result, and no stage runs."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = run([sys.executable, SMOKE], env, timeout=300)
    assert proc.returncode != 0
    assert "platform cpu is not tpu" in proc.stderr
    records = [json.loads(line) for line in proc.stdout.splitlines()]
    assert [r.get("stage") for r in records] == ["probe"]
    assert not any("ok" in r for r in records)


def test_stage_failure_on_an_accepted_device_is_a_false_result(
    monkeypatch, capsys, tmp_path
):
    """Once the probe's device is accepted, a failed stage ends stdout with
    ``{"ok": false, "device": ...}`` and the exit code is still 1."""
    import importlib.util

    spec = importlib.util.spec_from_file_location("chip_smoke_ut", SMOKE)
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    probe = {
        "stage": "probe", "platform": "tpu", "device_kind": "TPU v5 lite",
        "device_count": 1, "versions": {}, "native": {"loaded": True},
    }

    def fail(*args):
        raise smoke.StageFailed("boom")

    monkeypatch.setattr(smoke, "OUT_DIR", str(tmp_path))
    monkeypatch.setattr(smoke, "run_child", lambda *a, **k: probe)
    monkeypatch.setattr(smoke, "stage_serve", fail)
    assert smoke.run_parent(dry_run=False) == 1
    out = capsys.readouterr()
    assert "boom" in out.err
    assert json.loads(out.out.splitlines()[-1]) == {
        "ok": False,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
    }


def test_script_alone_fails_without_a_result(tmp_path):
    """In a directory that holds chip_smoke.py and nothing else of the
    repo there is no program to prove: non-zero, no result line."""
    shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    env = {
        k: v for k, v in os.environ.items() if k != "PYTHONPATH"
    }
    proc = run(
        [sys.executable, "chip_smoke.py"], env, cwd=str(tmp_path), timeout=300
    )
    assert proc.returncode != 0
    assert "llm_weighted_consensus_tpu" in proc.stderr  # the import error
    assert '"ok"' not in proc.stdout


_CACHE_PROBE = textwrap.dedent(
    """
    import jax
    from llm_weighted_consensus_tpu.serve.config import (
        configure_compile_cache,
    )
    stats = configure_compile_cache()
    assert stats.snapshot()["dir"] == jax.config.jax_compilation_cache_dir
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
    assert jax.config.jax_persistent_cache_min_entry_size_bytes == 0
    print(jax.config.jax_compilation_cache_dir)
    """
)


def _cache_dir_of_fresh_process(env_dir, cwd):
    env = dict(os.environ, PYTHONPATH=REPO)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    proc = run([sys.executable, "-c", _CACHE_PROBE], env, cwd=cwd, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return proc.stdout.strip().splitlines()[-1]


def test_compile_cache_placed_from_outside_is_left_alone(tmp_path):
    pytest.importorskip("jax")
    outside = str(tmp_path / "placed-by-the-driver")
    assert _cache_dir_of_fresh_process(outside, REPO) == outside


def test_compile_cache_default_is_one_fixed_path_in_the_checkout(tmp_path):
    """Unset: the same directory from two processes started in different
    places — a path that moves (cwd, pid, tmpdir) never hits."""
    pytest.importorskip("jax")
    first = _cache_dir_of_fresh_process(None, REPO)
    second = _cache_dir_of_fresh_process(None, str(tmp_path))
    assert first == second == os.path.join(REPO, ".jax_cache")
