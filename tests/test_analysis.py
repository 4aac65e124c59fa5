"""The analysis gate: first-party lint rules + the jaxpr serving-path
audit (ISSUE 6).

Three layers:

* per-rule fixture tests — every rule fires on its violating fixture
  (exact count: the fixtures enumerate the shapes the rule knows) and
  stays silent on the conforming one; all rules together stay silent on
  every conforming fixture (no cross-rule false positives);
* the baseline machinery — suppression round-trip, mandatory reasons,
  stale-entry detection;
* THE gate — the whole package lints clean against the committed
  baseline, and the jaxpr audit of the int8 serving path (structure +
  AOT coverage + specialization guard) returns zero findings on CPU;
  plus injected-regression tests proving the auditor actually catches
  host transfers / dequant upcasts and names the offending primitive.
"""

import json
import subprocess
import sys
from pathlib import Path

import pytest

from llm_weighted_consensus_tpu.analysis import (
    apply_baseline,
    baseline_entry,
    default_baseline_path,
    load_baseline,
    run_lint,
)
from llm_weighted_consensus_tpu.analysis.rules import ALL_RULES, RULES_BY_NAME

FIXTURES = Path(__file__).parent / "fixtures" / "analysis"

# rule -> number of violations its bad fixture enumerates
EXPECTED_BAD = {
    "LWC001": 3,  # bare / BaseException / CancelledError-no-reraise
    "LWC002": 1,
    "LWC003": 1,
    "LWC004": 2,  # ContextVar.set + .activate() tokens
    "LWC005": 3,  # BinOp + AugAssign + Decimal(float)
    "LWC006": 2,  # time.sleep + open
    "LWC007": 2,  # message() + wire envelope
    "LWC008": 3,  # environ.get + getenv + environ[...] subscript
    "LWC009": 2,  # jnp call + jax call inside one coroutine
    "LWC010": 3,  # undeclared section + dead registry row + rogue span
    "LWC011": 2,  # undocumented from_env knob + stale README token
    "LWC012": 5,  # undeclared family + dead registry row + non-literal
    # name + the _total-suffixed counter header (undeclared + dead row)
    "LWC013": 2,  # jax.block_until_ready + .block_until_ready() method
    "LWC014": 6,  # unregistered lock + stale registry row + 2 unguarded
    # cross-thread accesses + reasonless exemption + exempted method
    # called without the lock held
    "LWC015": 4,  # undeclared observed edge + stale declared edge +
    # order cycle + lexical re-acquire of a non-reentrant Lock
    "LWC016": 5,  # await + wait_device_ready + upstream HTTP +
    # cross-condition wait + call-mediated blocking, all under a held lock
    "LWC017": 2,  # to_json_obj + jsonutil.dumps per merged chunk
    "LWC018": 4,  # 2 capless deques + unguarded bytes growth +
    # raw byte_stream chunks drained into a list
}


def lint_fixture(name: str, rule: str = None):
    rules = [RULES_BY_NAME[rule]] if rule else None
    return run_lint(paths=[FIXTURES / name], rules=rules)


# -- per-rule fixtures -------------------------------------------------------


def test_registry_covers_expected_rules():
    assert sorted(r.name for r in ALL_RULES) == sorted(EXPECTED_BAD)


@pytest.mark.parametrize("rule", sorted(EXPECTED_BAD))
def test_rule_fires_on_bad_fixture(rule):
    findings = lint_fixture(f"{rule.lower()}_bad.py", rule)
    assert len(findings) == EXPECTED_BAD[rule], [
        f.render() for f in findings
    ]
    assert all(f.rule == rule for f in findings)
    # findings carry the symbol (the baseline matching key)
    assert all(f.symbol for f in findings)


@pytest.mark.parametrize("rule", sorted(EXPECTED_BAD))
def test_rule_silent_on_good_fixture(rule):
    assert lint_fixture(f"{rule.lower()}_good.py", rule) == []


@pytest.mark.parametrize("rule", sorted(EXPECTED_BAD))
def test_good_fixtures_clean_under_all_rules(rule):
    """A conforming fixture must not trip ANY rule — the conforming
    idioms are exactly the repo's own, so a cross-rule false positive
    here means the gate would fight real code."""
    findings = lint_fixture(f"{rule.lower()}_good.py")
    assert findings == [], [f.render() for f in findings]


# -- baseline machinery ------------------------------------------------------


def test_baseline_round_trip(tmp_path):
    findings = run_lint(paths=[FIXTURES / "lwc001_bad.py"])
    assert findings
    entries = [baseline_entry(f, "fixture: intentionally bad") for f in findings]
    path = tmp_path / "baseline.json"
    path.write_text(json.dumps({"suppressions": entries}))

    kept, suppressed, stale = apply_baseline(findings, load_baseline(path))
    assert kept == []
    assert len(suppressed) == len(findings)
    assert stale == []

    # "the code got fixed": the same baseline against a clean file makes
    # every entry stale — the CLI fails until they're deleted
    clean = run_lint(paths=[FIXTURES / "lwc001_good.py"])
    kept2, _, stale2 = apply_baseline(clean, load_baseline(path))
    assert kept2 == clean
    assert len(stale2) == len(entries)


def test_baseline_reason_is_mandatory(tmp_path):
    path = tmp_path / "baseline.json"
    path.write_text(
        json.dumps(
            {"suppressions": [{"rule": "LWC001", "path": "x.py", "symbol": None}]}
        )
    )
    with pytest.raises(ValueError, match="reason"):
        load_baseline(path)


def test_committed_baseline_is_small_and_reasoned():
    entries = load_baseline(default_baseline_path())
    assert len(entries) <= 10
    assert all(str(e["reason"]).strip() for e in entries)


# -- THE gate: the package itself --------------------------------------------


def test_package_lints_clean_against_baseline():
    kept, _suppressed, stale = apply_baseline(run_lint(), load_baseline())
    assert stale == [], stale
    assert kept == [], "\n".join(f.render() for f in kept)


# -- CLI ---------------------------------------------------------------------


def test_cli_exit_codes(tmp_path, capsys):
    from llm_weighted_consensus_tpu.analysis.__main__ import main

    # single-file lint: the package-wide suppressions don't apply (and
    # would read stale), so scope the run to an empty baseline; the
    # jaxpr/mesh audits are package-level, not per-file — skip them
    empty = tmp_path / "empty.json"
    empty.write_text(json.dumps({"suppressions": []}))
    base = ["--no-jaxpr", "--no-mesh", "--baseline", str(empty)]
    assert main([str(FIXTURES / "lwc002_good.py"), *base]) == 0
    rc = main([str(FIXTURES / "lwc002_bad.py"), *base])
    assert rc == 1
    assert "LWC002" in capsys.readouterr().out

    stale = tmp_path / "stale.json"
    stale.write_text(
        json.dumps(
            {
                "suppressions": [
                    {
                        "rule": "LWC001",
                        "path": "gone.py",
                        "symbol": None,
                        "reason": "covered code was deleted",
                    }
                ]
            }
        )
    )
    assert (
        main(
            [
                str(FIXTURES / "lwc002_good.py"),
                "--no-jaxpr",
                "--no-mesh",
                "--baseline",
                str(stale),
            ]
        )
        == 2
    )


def test_cli_module_entry_point():
    proc = subprocess.run(
        [
            sys.executable,
            "-m",
            "llm_weighted_consensus_tpu.analysis",
            "--no-jaxpr",
            "--json",
        ],
        capture_output=True,
        text=True,
        cwd=Path(__file__).parent.parent,
        timeout=120,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    payload = json.loads(proc.stdout)
    assert payload["findings"] == []


# -- concurrency audit: injected regressions + registry drift ----------------
#
# Each test plants exactly the regression the rule exists to catch in a
# copy of the conforming fixture and asserts the NAMED rule reports it —
# the auditor must not just pass clean code, it must fail broken code.


def _mutated(tmp_path, fixture, old, new):
    src = (FIXTURES / fixture).read_text()
    assert old in src, f"mutation anchor drifted in {fixture}"
    path = tmp_path / fixture  # same filename: the inline model's
    path.write_text(src.replace(old, new))  # module suffix still matches
    return path


def _conc_lint(path, rule):
    return run_lint(paths=[path], rules=[RULES_BY_NAME[rule]])


def test_lwc014_catches_deleted_with_guard(tmp_path):
    """Injected regression: strip the ``with self._lock`` bracket off a
    guarded-field read reachable from two threads."""
    path = _mutated(
        tmp_path,
        "lwc014_good.py",
        "    def read(self):\n"
        "        with self._lock:\n"
        "            return self._count\n",
        "    def read(self):\n        return self._count\n",
    )
    findings = _conc_lint(path, "LWC014")
    assert [f.rule for f in findings] == ["LWC014"]
    assert findings[0].symbol == "Worker.read"
    assert "_count" in findings[0].message


def test_lwc015_catches_reversed_lock_order(tmp_path):
    """Injected regression: reverse the two-lock nesting in ``forward``
    while ``outer``/``helper`` still walk the declared direction — the
    undeclared inverse edge AND the resulting cycle both surface."""
    path = _mutated(
        tmp_path,
        "lwc015_good.py",
        "    with LOCK_A:\n"
        "        with LOCK_B:\n"
        "            return list(items)\n",
        "    with LOCK_B:\n"
        "        with LOCK_A:\n"
        "            return list(items)\n",
    )
    findings = _conc_lint(path, "LWC015")
    assert findings and all(f.rule == "LWC015" for f in findings)
    assert any(
        "`LOCK_B` -> `LOCK_A`" in f.message and "not declared" in f.message
        for f in findings
    )
    assert any("cycle" in f.message for f in findings)


def test_lwc016_catches_await_under_held_lock(tmp_path):
    """Injected regression: append a coroutine to ``Pump`` that awaits
    while holding the registered lock."""
    src = (FIXTURES / "lwc016_good.py").read_text()
    src += (
        "\n    async def injected(self):\n"
        "        with self._lock:\n"
        "            await self.nothing()\n"
    )
    path = tmp_path / "lwc016_good.py"
    path.write_text(src)
    findings = _conc_lint(path, "LWC016")
    assert [f.rule for f in findings] == ["LWC016"]
    assert findings[0].symbol == "Pump.injected"
    assert "await" in findings[0].message


def test_lwc014_registry_drift_unregistered_lock(tmp_path):
    """Both-ways check, way one: a new threading primitive without a
    registry row fails the lint."""
    path = _mutated(
        tmp_path,
        "lwc014_good.py",
        "        self._lock = threading.Lock()\n",
        "        self._lock = threading.Lock()\n"
        "        self._extra = threading.Lock()\n",
    )
    findings = _conc_lint(path, "LWC014")
    assert [f.rule for f in findings] == ["LWC014"]
    assert "Worker._extra" in findings[0].message
    assert "not in the lock-model registry" in findings[0].message


def test_lwc014_registry_drift_stale_row(tmp_path):
    """Both-ways check, way two: deleting the lock's creation site makes
    its registry row stale — the row must be pruned, not left to rot."""
    path = _mutated(
        tmp_path,
        "lwc014_good.py",
        "        self._lock = threading.Lock()\n",
        "",
    )
    findings = _conc_lint(path, "LWC014")
    assert [f.rule for f in findings] == ["LWC014"]
    assert findings[0].symbol == "Worker._lock"
    assert "no creation site" in findings[0].message


def test_lwc015_registry_drift_stale_order_edge(tmp_path):
    """Both-ways check for the order DAG: if no code path walks the
    declared edge any more, the declaration itself fails the lint."""
    src = (FIXTURES / "lwc015_good.py").read_text()
    for old, new in (
        (
            "    with LOCK_A:\n"
            "        with LOCK_B:\n"
            "            return list(items)\n",
            "    with LOCK_B:\n        return list(items)\n",
        ),
        (
            "    with LOCK_A:\n        return helper(items)\n",
            "    return helper(items)\n",
        ),
    ):
        assert old in src, "mutation anchor drifted in lwc015_good.py"
        src = src.replace(old, new)
    path = tmp_path / "lwc015_good.py"
    path.write_text(src)
    findings = _conc_lint(path, "LWC015")
    assert [f.rule for f in findings] == ["LWC015"]
    assert findings[0].symbol == "LOCK_A->LOCK_B"
    assert "no longer observed" in findings[0].message


def test_package_model_covers_every_primitive_both_ways():
    """The acceptance: 100% registry coverage over the package's real
    threading primitives, in both directions — every creation site has a
    row (no LWC014 unregistered findings) and every row has a creation
    site (no stale findings) on the committed tree."""
    findings = run_lint(rules=[RULES_BY_NAME["LWC014"]])
    drift = [
        f
        for f in findings
        if "not in the lock-model registry" in f.message
        or "no creation site" in f.message
    ]
    assert drift == [], "\n".join(f.render() for f in drift)


# -- jaxpr audit -------------------------------------------------------------

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from llm_weighted_consensus_tpu.analysis.jaxpr_audit import (  # noqa: E402
    audit_traced,
    run_jaxpr_audit,
)

SDS = jax.ShapeDtypeStruct


def test_jaxpr_audit_serving_path_clean():
    """The acceptance: the int8 serving path (every AOT bucket,
    structure + coverage + specialization guard) audits clean on CPU."""
    findings = run_jaxpr_audit()
    assert findings == [], "\n".join(f.render() for f in findings)


def test_audit_clean_toy_fn_passes():
    w = jnp.ones((4, 4), jnp.float32)
    assert (
        audit_traced(lambda x: jnp.dot(x, w), (SDS((4, 4), jnp.float32),), "ok")
        == []
    )


def test_audit_names_injected_device_put():
    w = jnp.ones((4, 4), jnp.float32)
    findings = audit_traced(
        lambda x: jnp.dot(jax.device_put(x), w),
        (SDS((4, 4), jnp.float32),),
        "toy",
    )
    assert [f.rule for f in findings] == ["JXA001"]
    assert "device_put" in findings[0].message


def test_audit_names_injected_callback():
    findings = audit_traced(
        lambda x: jax.pure_callback(
            lambda v: np.asarray(v), SDS((4,), jnp.float32), x
        ),
        (SDS((4,), jnp.float32),),
        "toy",
    )
    assert [f.rule for f in findings] == ["JXA001"]
    assert "pure_callback" in findings[0].message


def test_audit_catches_trace_time_device_get():
    """jax.device_get/np.asarray on a tracer never reaches the jaxpr —
    the auditor reports the trace-time concretization as JXA001."""
    findings = audit_traced(
        lambda x: jnp.asarray(np.asarray(x)) + 1.0,
        (SDS((4,), jnp.float32),),
        "toy",
    )
    assert [f.rule for f in findings] == ["JXA001"]
    assert "trace time" in findings[0].message


def test_audit_names_injected_int8_upcast():
    w = jnp.ones((4, 4), jnp.float32)
    findings = audit_traced(
        lambda q: jnp.dot(q.astype(jnp.float32), w),
        (SDS((4, 4), jnp.int8),),
        "toy",
    )
    assert [f.rule for f in findings] == ["JXA002"]
    assert "convert_element_type" in findings[0].message


def test_audit_flags_missing_pallas_kernel():
    """expect_pallas asserts the fused kernel is still in the forward."""
    w = jnp.ones((4, 4), jnp.float32)
    findings = audit_traced(
        lambda x: jnp.dot(x, w),
        (SDS((4, 4), jnp.float32),),
        "toy",
        expect_pallas=True,
    )
    assert [f.rule for f in findings] == ["JXA002"]
    assert "pallas" in findings[0].message


# -- mesh audit (JXA006-011) -------------------------------------------------

from jax.sharding import PartitionSpec as P  # noqa: E402

from llm_weighted_consensus_tpu.analysis.budgets import (  # noqa: E402
    check_allowlist_stale,
    compare_budgets,
)
from llm_weighted_consensus_tpu.analysis.mesh_audit import (  # noqa: E402
    audit_hlo_collectives,
    audit_replication,
    audit_rule_coverage,
    audit_serving_executables,
    run_mesh_audit,
)

_TOY_TREE = {
    "embed": SDS((512, 1024), jnp.float32),  # 2 MiB: above threshold
    "layers": {"kernel": SDS((8, 8), jnp.float32)},
}
_TOY_RULES = (
    ("embed", r"embed", P()),
    ("kernel", r"layers/kernel", P(None, "tp")),
)


def test_mesh_audit_serving_path_clean():
    """The acceptance: coverage, replication policy, collective plan,
    committed budgets, and sharded-vs-single-device equivalence of every
    serving bucket on the simulated mesh — zero findings."""
    findings = run_mesh_audit()
    assert findings == [], "\n".join(f.render() for f in findings)


def test_mesh_audit_catches_wrong_math_in_committed_executable():
    """Injected regression: the audit runs against the embedder's ACTUAL
    AOT table, so an executable whose math is wrong — here the warmed
    vote1 entry swapped for a same-aval compile that scales the vote —
    must surface as JXA011 naming the bucket."""
    from llm_weighted_consensus_tpu.models.embedder import (
        TpuEmbedder,
        _mesh_embed_and_vote,
    )
    from llm_weighted_consensus_tpu.parallel.mesh import make_mesh
    from llm_weighted_consensus_tpu.parallel.sharding import (
        shard_embedder_mesh,
    )

    mesh = make_mesh(dp=4, tp=2)
    ref = TpuEmbedder("test-tiny", max_tokens=64, seed=0, quantize="none")
    emb = TpuEmbedder("test-tiny", max_tokens=64, seed=0, quantize="none")
    shard_embedder_mesh(emb, mesh)
    n, s = 4, 16
    emb.aot_warmup([(n, s)])

    pad_n = n + (-n) % emb.batch_multiple
    iav = SDS((pad_n, s), jnp.int32, sharding=emb.batch_sharding)
    temp_av = SDS((), jnp.float32, sharding=emb.repl_sharding)
    wrong = (
        jax.jit(
            lambda p, i, m, t: 1.5
            * _mesh_embed_and_vote(
                p, i, m, t, n, emb.config, emb.pooling, emb.mesh
            )
        )
        .lower(emb.params, iav, iav, temp_av)
        .compile()
    )
    emb._aot[emb._aot_key(("vote1", n, s))] = wrong

    findings, _ = audit_serving_executables(
        emb, ref, specs=((n, s),), r_buckets=()
    )
    hits = [
        f for f in findings if f.rule == "JXA011" and "vote1" in f.path
    ]
    assert hits, "\n".join(f.render() for f in findings)


def test_mesh_audit_catches_unwarmed_fault_ladder_rung():
    """Injected regression for JXA012: with warm_ladder neutered, every
    fallback rung is missing its AOT buckets — the ladder audit must name
    the uncovered buckets AND flag that rung dispatches lazily jitted
    instead of riding committed executables."""
    from llm_weighted_consensus_tpu.analysis.mesh_audit import (
        _audit_fault_ladder,
    )
    from llm_weighted_consensus_tpu.resilience import MeshFaultManager

    real = MeshFaultManager.warm_ladder
    MeshFaultManager.warm_ladder = lambda self, *a, **k: []
    try:
        findings = _audit_fault_ladder("test-tiny", 4, 2, ((4, 16),), ())
    finally:
        MeshFaultManager.warm_ladder = real
    missing = [
        f
        for f in findings
        if f.rule == "JXA012" and "no AOT executable" in f.message
    ]
    lazy = [
        f
        for f in findings
        if f.rule == "JXA012" and "lazily jitted" in f.message
    ]
    assert missing and lazy, "\n".join(f.render() for f in findings)
    # both fallback rungs of the 4x2 ladder are implicated
    assert {f.path for f in missing} == {
        "mesh:ladder:2x2",
        "mesh:ladder:1x2",
    }


def test_coverage_clean_on_toy_tree():
    assert audit_rule_coverage(_TOY_RULES, _TOY_TREE, "toy") == []


def test_coverage_flags_uncovered_param_leaf():
    """Injected regression: a param leaf no rule matches is JXA006."""
    rules = (_TOY_RULES[0],)  # drop the kernel rule
    findings = audit_rule_coverage(rules, _TOY_TREE, "toy")
    assert [f.rule for f in findings] == ["JXA006"]
    assert findings[0].symbol == "layers/kernel"
    assert "NO partition rule" in findings[0].message


def test_coverage_flags_unused_rule():
    """Injected regression: a rule matching no leaf is JXA006 too."""
    rules = _TOY_RULES + (("ghost", r"layers/nonexistent", P()),)
    findings = audit_rule_coverage(rules, _TOY_TREE, "toy")
    assert [f.rule for f in findings] == ["JXA006"]
    assert findings[0].symbol == "ghost"
    assert "no param leaf" in findings[0].message


def test_coverage_flags_ambiguous_leaf():
    rules = _TOY_RULES + (("dup", r"emb.*", P()),)
    findings = audit_rule_coverage(rules, _TOY_TREE, "toy")
    assert {f.rule for f in findings} == {"JXA006"}
    assert any("ambiguous" in f.message for f in findings)


def test_replication_flags_oversized_replicated_tensor():
    """Injected regression: a >threshold leaf left fully replicated
    without an allowlist entry is JXA007."""
    findings, matched = audit_replication(
        _TOY_RULES, _TOY_TREE, "toy", threshold_bytes=1 << 20, allowlist=[]
    )
    assert [f.rule for f in findings] == ["JXA007"]
    assert findings[0].symbol == "embed"
    assert matched == set()


def test_replication_allowlist_and_stale_detection():
    allow = [{"pattern": "embed", "reason": "gather beats all-to-all"}]
    findings, matched = audit_replication(
        _TOY_RULES, _TOY_TREE, "toy", threshold_bytes=1 << 20, allowlist=allow
    )
    assert findings == []
    assert matched == {"embed"}
    assert check_allowlist_stale(allow, matched) == []
    # the allowlisted tensor got sharded/removed: the entry is stale
    stale = check_allowlist_stale(allow, set())
    assert [f.rule for f in stale] == ["JXA010"]
    assert "stale replicated_allowlist" in stale[0].message


_SHARDED_HLO = """\
ENTRY %main { %ar = f32[8,16] all-reduce(f32[8,16] %x) }
"""


def test_hlo_collectives_clean():
    assert audit_hlo_collectives(_SHARDED_HLO, "toy") == []


def test_hlo_flags_missing_expected_collective():
    """Injected regression: HLO without the TP reduction is JXA008 (the
    layout degenerated to replication)."""
    findings = audit_hlo_collectives(
        "ENTRY %main { %d = f32[8,16] dot(%x, %w) }", "toy"
    )
    assert [f.rule for f in findings] == ["JXA008"]
    assert "expected collective" in findings[0].message


def test_hlo_flags_forbidden_collective():
    """Injected regression: an all-to-all (or host transfer) in the hot
    path is JXA008."""
    findings = audit_hlo_collectives(
        _SHARDED_HLO + "%a2a = f32[8,16] all-to-all(%x)\n", "toy"
    )
    assert [f.rule for f in findings] == ["JXA008"]
    assert "all-to-all" in findings[0].message
    findings = audit_hlo_collectives(
        _SHARDED_HLO + "%s = f32[] send(%x), is_host_transfer=true\n", "toy"
    )
    assert [f.rule for f in findings] == ["JXA008"]


_BUDGETS = {
    "scope": {"model": "toy"},
    "tolerance": {"hbm_bytes": 0.25, "flops": 0.25, "bytes_accessed": 0.25},
    "buckets": {
        "vote1(n=8,s=16)": {
            "hbm_bytes": 1000.0,
            "flops": 2000.0,
            "bytes_accessed": 3000.0,
        }
    },
}
_IN_BAND = {
    "vote1(n=8,s=16)": {
        "hbm_bytes": 1100.0,
        "flops": 2100.0,
        "bytes_accessed": 2900.0,
    }
}


def test_budgets_in_band_clean():
    assert (
        compare_budgets(_IN_BAND, _BUDGETS, scope={"model": "toy"}) == []
    )


def test_budgets_flag_breach():
    """Injected regression: a measured figure past the tolerance band is
    JXA009, naming the bucket and metric."""
    over = {
        "vote1(n=8,s=16)": {
            "hbm_bytes": 1500.0,  # 1.5x vs ±25%
            "flops": 2000.0,
            "bytes_accessed": 3000.0,
        }
    }
    findings = compare_budgets(over, _BUDGETS, scope={"model": "toy"})
    assert [f.rule for f in findings] == ["JXA009"]
    assert findings[0].symbol == "vote1(n=8,s=16)"
    assert "hbm_bytes" in findings[0].message
    assert "outgrew" in findings[0].message


def test_budgets_flag_missing_and_stale_entries():
    """Injected regressions: an audited bucket with no committed entry,
    and a committed bucket the audit no longer lowers — both JXA010."""
    measured = dict(_IN_BAND)
    measured["many(r=4,n=8,s=16)"] = {"hbm_bytes": 1.0}
    findings = compare_budgets(measured, _BUDGETS, scope={"model": "toy"})
    assert [f.rule for f in findings] == ["JXA010"]
    assert "no committed budget" in findings[0].message

    findings = compare_budgets({}, _BUDGETS, scope={"model": "toy"})
    assert [f.rule for f in findings] == ["JXA010"]
    assert "stale budget entry" in findings[0].message


def test_budgets_flag_scope_mismatch_and_missing_file():
    findings = compare_budgets(_IN_BAND, _BUDGETS, scope={"model": "other"})
    assert [f.rule for f in findings] == ["JXA010"]
    assert "scope" in findings[0].message
    findings = compare_budgets(_IN_BAND, {}, scope={"model": "toy"})
    assert [f.rule for f in findings] == ["JXA010"]
    assert "--write-budgets" in findings[0].message
