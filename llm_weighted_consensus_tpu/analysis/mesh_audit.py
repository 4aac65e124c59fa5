"""Mesh-aware sharding & resource audit of the serving path (JXA006–011).

GSPMD sharding is propagated at trace time, which makes it *auditable*
at trace time: this module builds the first-class mesh embedder exactly
as ``serve/__main__.py`` does (``shard_embedder_mesh`` + ``aot_warmup``)
under a simulated v5e-8 mesh (8 virtual CPU devices via
``parallel/dist.py``'s ``--xla_force_host_platform_device_count``
plumbing, dp=4 × tp=2 by default) and audits the ACTUAL serving
executables in the embedder's AOT table — the same
``jit``-with-shardings callables the batcher dispatches, not a parallel
re-lowering that could drift from what serves traffic.  Checked: the
partition plan, the collective plan, and the resource envelope, before
a single TPU chip is rented:

* **JXA006 rule coverage** — against the first-class partition-rule
  tables in ``parallel/sharding.py``, every param leaf of every audited
  tree (bert + deberta, full-precision + int8) matches EXACTLY one rule
  and every rule matches at least one leaf: no silently-replicated new
  param, no dead rule rotting in the table.
* **JXA007 oversized replication** — shape-only (``jax.eval_shape``)
  trees of the big real presets: any leaf above
  ``replicated_threshold_bytes`` whose spec replicates it across the
  mesh must have an explicit ``replicated_allowlist`` entry (with a
  written reason) in ``analysis/budgets.json``.
* **JXA008 collective plan** — the compiled HLO of every bucket
  contains the expected cross-device reduction (all-reduce /
  reduce-scatter / all-gather: the Megatron TP layout's two
  reductions per layer) and NONE of the forbidden ops: no all-to-all,
  no host transfer inside the hot path.
* **JXA009/JXA010 resource budgets** — per-bucket static HBM footprint
  (argument+output+temp bytes, XLA ``memory_analysis``) and
  flops / bytes-accessed (``cost_analysis``) compared against the
  committed ``analysis/budgets.json`` within a tolerance band; missing
  and stale entries fail too (``budgets.py``).
* **JXA011 numerical equivalence** — each warmed bucket is driven
  through the embedder's PUBLIC dispatch method against a same-seed
  single-device reference embedder on identical inputs; results must
  agree to float32 reduction-reordering tolerance, and a ``jit_stats``
  bracket asserts the dispatches really rode the audited executables
  (zero specialization growth).
* **JXA012 fault-ladder coverage** — the mesh fault-domain downsize
  ladder (``resilience/meshfault.py``): every fallback rung must hold a
  full AOT bucket set after ``warm_ladder`` (a missing rung bucket means
  a mid-incident downsize compiles under fire), and driving the public
  dispatch on each downsized rung must pass the JXA011 parity gate
  against the single-device reference with zero specialization growth.
  The ladder audit runs twice: once on the dense dp×tp mesh and once on
  the sp-bearing ring mesh (dp halves, tp AND sp preserved per rung,
  ring buckets pre-warmed under each rung's ``("mesh", dp, tp, sp)``
  namespace).
* **JXA013 roofline coverage** — every audited bucket must have a
  live row in ``analysis/roofline.json`` (flops / bytes-accessed plus
  per-chip backend peaks) so the serving gauge can report speed-of-light
  attainment; missing rows, stale rows, drifted figures, and bad peaks
  all fail (``roofline.py``).

Device plumbing: the checks need ``dp*tp`` devices.  Under tier-1
pytest the conftest already forces 8 virtual CPU devices, so everything
runs in-process; the bare CLI process has one device, so
``run_mesh_audit`` respawns itself as a subprocess with
``force_cpu_env`` — the same recipe the DCN smoke uses.

The long-context ring (sequence-parallel) serving path is audited on
an sp-bearing sibling mesh: the sp axis folds out of dp
(``dp//sp × tp × sp``, default 2×2×2) so the device budget stays
``dp*tp``, and the warmed ``("ring", B, S)`` / ``("ring_vote", N, S)``
executables get the same JXA008–011 treatment — their figures land in
``budgets.json`` / ``roofline.json`` next to the dense buckets, and
JXA011 parity runs ring-vs-dense against the single-device reference.

Env knobs (all optional): ``ANALYSIS_MESH_MODEL`` (embedder preset,
default ``test-tiny``), ``ANALYSIS_MESH_DP`` / ``ANALYSIS_MESH_TP``
(mesh shape, default 4×2), ``ANALYSIS_MESH_SP`` (ring mesh sp axis,
default 2; 1 disables the ring audit), ``ANALYSIS_MESH_SPECS``
(``NxS`` list, default ``8x16``), ``ANALYSIS_MESH_R_BUCKETS`` (default
``2``), ``ANALYSIS_MESH_RING_BUCKETS`` (``NxS`` list, default
``2x64``; empty disables the ring audit), ``ANALYSIS_BUDGETS``
(budgets file override), ``ANALYSIS_ROOFLINE``
(roofline file override), ``ANALYSIS_SKIP_MESH=1``
to skip (honored by the CLI and scripts/t1.sh; tier-1 does not set it).

Re-baselining: ``python -m llm_weighted_consensus_tpu.analysis.mesh_audit
--write-budgets`` re-measures and rewrites ``budgets.json`` (tolerance,
threshold, and allowlist preserved); ``--write-roofline`` does the same
for ``roofline.json`` (peaks and tolerance preserved); review the diff.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Set, Tuple

from .budgets import (
    allowlisted,
    check_allowlist_stale,
    compare_budgets,
    default_budgets_path,
    load_budgets,
    replicated_allowlist,
    replicated_threshold,
)
from .engine import Finding
from .roofline import (
    compare_roofline,
    default_roofline_path,
    load_roofline,
    write_roofline,
)

_DEFAULT_MODEL = "test-tiny"
_DEFAULT_DP, _DEFAULT_TP = 4, 2
_DEFAULT_SPECS = ((8, 16),)
_DEFAULT_R_BUCKETS = (2,)
# the long-context ring audit folds the sp axis out of dp (dp//sp x tp
# x sp) so the device budget stays dp*tp; sp=2 over the default 4x2
# mesh gives the 2x2x2 sp-bearing shape serve/__main__.py would build
# from MESH_SHAPE=2x2x2
_DEFAULT_SP = 2
_DEFAULT_RING_BUCKETS = ((2, 64),)

# shape-only presets for the coverage/replication checks: the BIG trees,
# because that is where an accidentally replicated table costs real HBM
_COVERAGE_PRESETS = ("bge-large-en",)
_COVERAGE_RM_PRESETS = ("deberta-v3-base",)

# the reduction the Megatron TP layout must insert, and the ops the
# serving path must never contain (an all-to-all means a layout went
# resharding-crazy; a host transfer stalls the whole dispatch)
EXPECTED_COLLECTIVES = (r"all-reduce|reduce-scatter|all-gather",)
FORBIDDEN_COLLECTIVES = (r"all-to-all", r"is_host_transfer=true")


def _env_int(name: str, default: int) -> int:
    raw = os.environ.get(name, "")
    return int(raw) if raw.strip() else default


def _env_mesh() -> Tuple[int, int]:
    return (
        _env_int("ANALYSIS_MESH_DP", _DEFAULT_DP),
        _env_int("ANALYSIS_MESH_TP", _DEFAULT_TP),
    )


def _env_model() -> str:
    return os.environ.get("ANALYSIS_MESH_MODEL", "") or _DEFAULT_MODEL


def _env_specs() -> Tuple[Tuple[int, int], ...]:
    raw = os.environ.get("ANALYSIS_MESH_SPECS", "")
    if not raw.strip():
        return _DEFAULT_SPECS
    return tuple(
        tuple(int(x) for x in part.strip().lower().split("x"))
        for part in raw.split(",")
        if part.strip()
    )


def _env_r_buckets() -> Tuple[int, ...]:
    raw = os.environ.get("ANALYSIS_MESH_R_BUCKETS", "")
    if not raw.strip():
        return _DEFAULT_R_BUCKETS
    return tuple(int(p) for p in raw.split(",") if p.strip())


def _env_sp() -> int:
    return _env_int("ANALYSIS_MESH_SP", _DEFAULT_SP)


def _env_ring_buckets() -> Tuple[Tuple[int, int], ...]:
    """``NxS`` long-context ring buckets; an explicitly empty
    ``ANALYSIS_MESH_RING_BUCKETS`` disables the ring audit."""
    raw = os.environ.get("ANALYSIS_MESH_RING_BUCKETS")
    if raw is None:
        return _DEFAULT_RING_BUCKETS
    return tuple(
        tuple(int(x) for x in part.strip().lower().split("x"))
        for part in raw.split(",")
        if part.strip()
    )


def _ring_enabled() -> bool:
    dp, tp = _env_mesh()
    sp = _env_sp()
    return bool(_env_ring_buckets()) and sp > 1 and dp % sp == 0


def _budgets_path() -> Path:
    raw = os.environ.get("ANALYSIS_BUDGETS", "")
    return Path(raw) if raw.strip() else default_budgets_path()


def _roofline_path() -> Path:
    raw = os.environ.get("ANALYSIS_ROOFLINE", "")
    return Path(raw) if raw.strip() else default_roofline_path()


def _scope() -> dict:
    dp, tp = _env_mesh()
    return {
        "model": _env_model(),
        "dp": dp,
        "tp": tp,
        "specs": ["x".join(map(str, s)) for s in _env_specs()],
        "r_buckets": list(_env_r_buckets()),
        "sp": _env_sp(),
        "ring_buckets": [
            "x".join(map(str, b)) for b in _env_ring_buckets()
        ],
    }


# ---------------------------------------------------------------------------
# JXA006/JXA007 — partition-rule coverage and replication policy
# ---------------------------------------------------------------------------


def audit_rule_coverage(rules, tree, label: str) -> List[Finding]:
    """JXA006: every leaf exactly one rule; every rule at least one leaf."""
    from ..parallel.sharding import match_report

    findings: List[Finding] = []
    leaf_matches, rule_counts = match_report(rules, tree)
    for path, hits in sorted(leaf_matches.items()):
        if len(hits) == 0:
            findings.append(
                Finding(
                    rule="JXA006",
                    path=f"mesh:{label}",
                    line=0,
                    symbol=path,
                    message=(
                        f"param leaf `{path}` matches NO partition rule: "
                        "it would silently fall back to whatever XLA "
                        "propagates — add a rule (or fix the pattern)"
                    ),
                )
            )
        elif len(hits) > 1:
            findings.append(
                Finding(
                    rule="JXA006",
                    path=f"mesh:{label}",
                    line=0,
                    symbol=path,
                    message=(
                        f"param leaf `{path}` matches {len(hits)} rules "
                        f"({', '.join(hits)}): ambiguous — first-match-"
                        "wins hides whichever layout lost"
                    ),
                )
            )
    for name, count in rule_counts.items():
        if count == 0:
            findings.append(
                Finding(
                    rule="JXA006",
                    path=f"mesh:{label}",
                    line=0,
                    symbol=name,
                    message=(
                        f"partition rule `{name}` matches no param leaf: "
                        "a dead rule is a layout decision nobody audits "
                        "— delete it or fix its pattern"
                    ),
                )
            )
    return findings


def audit_replication(
    rules,
    tree,
    label: str,
    threshold_bytes: int,
    allowlist: Sequence[dict],
) -> Tuple[List[Finding], Set[str]]:
    """JXA007: no leaf above the size threshold replicated across the
    mesh without an explicit allowlist entry.  Returns the findings and
    the set of allowlist patterns that earned their keep."""
    from ..parallel.sharding import match_partition_rules, tree_path_leaves

    findings: List[Finding] = []
    matched_patterns: Set[str] = set()
    try:
        spec_tree = match_partition_rules(rules, tree)
    except ValueError:
        # JXA006 owns uncovered leaves; nothing to size-check here
        return findings, matched_patterns
    specs = dict(tree_path_leaves(spec_tree))
    for path, leaf in tree_path_leaves(tree):
        shape = getattr(leaf, "shape", ())
        dtype = getattr(leaf, "dtype", None)
        if dtype is None:
            continue
        size = int(dtype.itemsize)
        for dim in shape:
            size *= int(dim)
        if size <= threshold_bytes:
            continue
        spec = specs[path]
        if any(axis is not None for axis in spec):
            continue  # sharded somewhere: not replicated
        pattern = allowlisted(path, allowlist)
        if pattern is not None:
            matched_patterns.add(pattern)
            continue
        findings.append(
            Finding(
                rule="JXA007",
                path=f"mesh:{label}",
                line=0,
                symbol=path,
                message=(
                    f"`{path}` ({size} bytes, {'x'.join(map(str, shape))} "
                    f"{dtype}) is fully replicated and above the "
                    f"{threshold_bytes}-byte threshold: shard it or add "
                    "a replicated_allowlist entry with a reason to "
                    "analysis/budgets.json"
                ),
            )
        )
    return findings, matched_patterns


def _shape_trees():
    """(label, rules, shape-only tree) for every audited param layout —
    big real presets, full-precision and int8, bert and deberta."""
    import jax

    from ..models import bert, deberta, quant
    from ..models.configs import PRESETS
    from ..models.reranker import RM_PRESETS
    from ..parallel.sharding import (
        bert_partition_rules,
        deberta_partition_rules,
    )

    rng = jax.random.PRNGKey(0)
    out = []
    for preset in _COVERAGE_PRESETS:
        config = PRESETS[preset]
        tree = jax.eval_shape(lambda c=config: bert.init_params(rng, c))
        out.append((f"bert:{preset}", bert_partition_rules(), tree))
        qtree = jax.eval_shape(
            lambda c=config: quant.quantize_bert_params(
                bert.init_params(rng, c)
            )
        )
        out.append(
            (
                f"bert:{preset}:int8",
                bert_partition_rules(quantized=True),
                qtree,
            )
        )
    for preset in _COVERAGE_RM_PRESETS:
        config = RM_PRESETS[preset]
        tree = jax.eval_shape(
            lambda c=config: deberta.init_params(rng, c)
        )
        out.append((f"deberta:{preset}", deberta_partition_rules(), tree))
        qtree = jax.eval_shape(
            lambda c=config: quant.quantize_deberta_params(
                deberta.init_params(rng, c)
            )
        )
        out.append(
            (
                f"deberta:{preset}:int8",
                deberta_partition_rules(quantized=True),
                qtree,
            )
        )
    return out


# ---------------------------------------------------------------------------
# JXA008 — the collective plan, as a pure function over HLO text
# ---------------------------------------------------------------------------


def audit_hlo_collectives(
    hlo_text: str,
    label: str,
    expect: Sequence[str] = EXPECTED_COLLECTIVES,
    forbid: Sequence[str] = FORBIDDEN_COLLECTIVES,
) -> List[Finding]:
    """Each ``expect`` regex must match the compiled HLO at least once
    (the sharded layout really inserted its reduction); each ``forbid``
    regex must match zero times."""
    import re

    findings: List[Finding] = []
    for pattern in expect:
        if re.search(pattern, hlo_text) is None:
            findings.append(
                Finding(
                    rule="JXA008",
                    path=f"mesh:{label}",
                    line=0,
                    message=(
                        f"expected collective `{pattern}` absent from the "
                        "lowered HLO: the TP layout degenerated (params "
                        "replicated instead of split?) — the mesh buys "
                        "nothing"
                    ),
                )
            )
    for pattern in forbid:
        match = re.search(pattern, hlo_text)
        if match is not None:
            findings.append(
                Finding(
                    rule="JXA008",
                    path=f"mesh:{label}",
                    line=0,
                    message=(
                        f"forbidden op `{match.group(0)}` in the lowered "
                        "HLO: an all-to-all / host transfer inside the "
                        "serving hot path wedges scarce interconnect"
                    ),
                )
            )
    return findings


# ---------------------------------------------------------------------------
# JXA008–011 — lower/compile every bucket on the simulated mesh
# ---------------------------------------------------------------------------


def _exe_figures(exe) -> Dict[str, float]:
    """The budget/roofline figures of one compiled executable: static
    HBM footprint (``memory_analysis``) plus flops / bytes-accessed
    (``cost_analysis``) — the shared measurement for every audited
    bucket (dense and ring)."""
    mem = exe.memory_analysis()
    figures = {
        "hbm_bytes": float(
            mem.argument_size_in_bytes
            + mem.output_size_in_bytes
            + mem.temp_size_in_bytes
        ),
    }
    cost = exe.cost_analysis()
    cost0 = cost[0] if isinstance(cost, (list, tuple)) else cost
    figures["flops"] = float(cost0.get("flops", 0.0))
    figures["bytes_accessed"] = float(cost0.get("bytes accessed", 0.0))
    return figures


def audit_serving_executables(
    embedder, ref, specs, r_buckets
) -> Tuple[List[Finding], Dict[str, Dict[str, float]]]:
    """JXA008–011 against a warmed mesh embedder's AOT table — the very
    ``jit``-with-shardings executables the batcher dispatches, not a
    parallel re-lowering that could drift from what serves traffic.

    Per bucket: JXA008/009/010 read the committed executable straight
    out of ``embedder._aot`` (a missing bucket is itself a finding —
    lazy jit at serve time breaks the zero-specialization contract).
    JXA011 then drives the PUBLIC dispatch method end-to-end against a
    same-seed single-device reference embedder, and the whole dispatch
    block is bracketed by ``jit_stats`` snapshots: any specialization
    growth means the dispatches bypassed the audited executables, which
    would make the audit above vacuous — also a finding.

    ``ref`` must be the same preset/seed left single-device; its
    dispatches run BEFORE the snapshot bracket because the module-level
    jit caches are shared across embedder instances.
    """
    import numpy as np

    from ..models.embedder import _bucket, _seq_bucket

    findings: List[Finding] = []
    measured: Dict[str, Dict[str, float]] = {}
    bm = embedder.batch_multiple
    rng = np.random.default_rng(0)
    vocab = embedder.config.vocab_size
    temp = 1.0
    atol = 1e-4

    def account(label, key):
        exe = embedder._aot.get(embedder._aot_key(key))
        if exe is None:
            findings.append(
                Finding(
                    rule="JXA008",
                    path=f"mesh:{label}",
                    line=0,
                    message=(
                        f"no AOT executable at serving bucket {key}: "
                        "aot_warmup did not cover it, so mesh traffic at "
                        "this bucket would lazily jit mid-request (the "
                        "zero-specialization contract breaks)"
                    ),
                )
            )
            return
        findings.extend(audit_hlo_collectives(exe.as_text(), label))
        measured[label] = _exe_figures(exe)

    def check(label, got, want):
        got, want = np.asarray(got), np.asarray(want)
        if not np.allclose(got, want, atol=atol, rtol=1e-4):
            worst = float(np.max(np.abs(got - want)))
            findings.append(
                Finding(
                    rule="JXA011",
                    path=f"mesh:{label}",
                    line=0,
                    message=(
                        "mesh dispatch diverges from the single-device "
                        f"reference (max abs diff {worst:.2e} > {atol}): "
                        "the partition plan changed the math, not just "
                        "the layout"
                    ),
                )
            )

    # Build every input and its single-device reference output FIRST:
    # the reference dispatches specialize the SHARED module-level jit
    # caches, and the zero-growth bracket below must see mesh traffic
    # only.
    cases = []  # (kind, label, aot bucket key, np inputs, ref output)
    for n, s in specs:
        s = _seq_bucket(s, embedder.max_tokens)
        ids = rng.integers(3, vocab, (n, s)).astype(np.int32)
        mask = np.ones((n, s), np.int32)
        ref_out = np.asarray(
            ref.consensus_confidence_tokens(ids, mask, temperature=temp)
        )
        cases.append(
            ("vote1", f"vote1(n={n},s={s})", ("vote1", n, s),
             (ids, mask), ref_out)
        )

        pad_b = _bucket(n, embedder.MAX_DEVICE_BATCH)
        pad_b += (-pad_b) % bm
        bids = rng.integers(3, vocab, (pad_b, s)).astype(np.int32)
        bmask = np.ones((pad_b, s), np.int32)
        ref_out = np.asarray(ref.embed_tokens(bids, bmask))
        cases.append(
            ("embed", f"embed(b={pad_b},s={s})", ("embed", pad_b, s),
             (bids, bmask), ref_out)
        )

        for r in r_buckets:
            if r < 2:
                continue
            gids = rng.integers(3, vocab, (r, n, s)).astype(np.int32)
            gmask = np.ones((r, n, s), np.int32)
            ref_out = np.asarray(
                ref.consensus_confidence_tokens_many(
                    gids, gmask, temperature=temp
                )
            )
            cases.append(
                ("many", f"many(r={r},n={n},s={s})", ("many", r, n, s),
                 (gids, gmask), ref_out)
            )

    before = embedder.jit_stats()["specializations"]
    for kind, label, key, args, ref_out in cases:
        account(label, key)
        if kind == "vote1":
            got = embedder.consensus_confidence_tokens(
                args[0], args[1], temperature=temp
            )
        elif kind == "embed":
            got = embedder.embed_tokens(*args)
        else:
            got = embedder.consensus_confidence_tokens_many(
                args[0], args[1], temperature=temp
            )
        check(label, got, ref_out)
    after = embedder.jit_stats()["specializations"]
    grew = {
        name: f"{before.get(name, 0)}->{count}"
        for name, count in after.items()
        if count > before.get(name, 0)
    }
    if grew:
        findings.append(
            Finding(
                rule="JXA008",
                path="mesh:dispatch",
                line=0,
                message=(
                    "mesh dispatches bypassed the audited AOT executables "
                    f"and lazily jitted instead ({grew}): the bucket "
                    "figures above describe executables that served no "
                    "traffic"
                ),
            )
        )
    return findings, measured


def _measure_buckets(
    model: str, dp: int, tp: int, specs, r_buckets
) -> Tuple[List[Finding], Dict[str, Dict[str, float]]]:
    """Build the first-class mesh embedder exactly as serve/__main__.py
    does — ``shard_embedder_mesh`` + ``aot_warmup`` — then audit its AOT
    table (``audit_serving_executables``)."""
    from ..models.embedder import TpuEmbedder
    from ..parallel.mesh import make_mesh
    from ..parallel.sharding import shard_embedder_mesh

    mesh = make_mesh(dp=dp, tp=tp)
    # the JXA011 oracle: same preset + seed, left single-device
    ref = TpuEmbedder(model, max_tokens=64, seed=0, quantize="none")
    embedder = TpuEmbedder(model, max_tokens=64, seed=0, quantize="none")
    shard_embedder_mesh(embedder, mesh)
    embedder.aot_warmup(
        list(specs), r_buckets=[r for r in r_buckets if r >= 2]
    )
    return audit_serving_executables(embedder, ref, specs, r_buckets)


def _audit_fault_ladder(
    model: str, dp: int, tp: int, specs, r_buckets
) -> List[Finding]:
    """JXA012: walk the MeshFaultManager downsize ladder as an incident
    would — warm it, then downsize rung by rung — and on every fallback
    rung assert (a) each serving bucket has a committed AOT executable
    under that rung's ``("mesh", dp, tp)`` namespace and (b) the public
    dispatch agrees with the single-device reference with zero jit
    growth (the JXA011 gate, re-applied to the degraded shapes).  A
    rung that fails either check means the fault path itself is the
    outage: a downsize mid-incident would compile — or worse, compute
    wrong numbers — exactly when the service can least afford it."""
    import numpy as np

    from ..models.embedder import TpuEmbedder, _bucket, _seq_bucket
    from ..parallel.mesh import make_mesh
    from ..parallel.sharding import shard_embedder_mesh
    from ..resilience import MeshFaultManager

    findings: List[Finding] = []
    ref = TpuEmbedder(model, max_tokens=64, seed=0, quantize="none")
    embedder = TpuEmbedder(model, max_tokens=64, seed=0, quantize="none")
    shard_embedder_mesh(embedder, make_mesh(dp=dp, tp=tp))
    manager = MeshFaultManager(embedder, shape=(dp, tp))
    r2 = [r for r in r_buckets if r >= 2]
    manager.warm_ladder(list(specs), r2)

    rng = np.random.default_rng(0)
    vocab = embedder.config.vocab_size
    atol = 1e-4
    # reference outputs FIRST: the module-level jit caches are shared, so
    # the zero-growth brackets below must see rung traffic only
    cases = []
    for n, s in specs:
        s = _seq_bucket(s, embedder.max_tokens)
        ids = rng.integers(3, vocab, (n, s)).astype(np.int32)
        mask = np.ones((n, s), np.int32)
        ref_out = np.asarray(ref.consensus_confidence_tokens(ids, mask))
        cases.append((n, s, ids, mask, ref_out))

    for rung_dp, rung_tp in manager.build_ladder()[1:]:
        label = f"ladder:{rung_dp}x{rung_tp}"
        if not manager.downsize():
            findings.append(
                Finding(
                    rule="JXA012",
                    path=f"mesh:{label}",
                    line=0,
                    message=(
                        "downsize() refused a declared ladder rung: the "
                        "ladder the manager walks is not the ladder it "
                        "declared"
                    ),
                )
            )
            break
        # (a) full AOT bucket coverage under this rung's key namespace
        bm = embedder.batch_multiple
        keys = []
        for n, s in specs:
            s = _seq_bucket(s, embedder.max_tokens)
            keys.append(("vote1", n, s))
            pad_b = _bucket(n, embedder.MAX_DEVICE_BATCH)
            pad_b += (-pad_b) % bm
            keys.append(("embed", pad_b, s))
            keys.extend(("many", r, n, s) for r in r2)
        for key in keys:
            if embedder._aot.get(embedder._aot_key(key)) is None:
                findings.append(
                    Finding(
                        rule="JXA012",
                        path=f"mesh:{label}",
                        line=0,
                        message=(
                            f"no AOT executable at fallback-rung bucket "
                            f"{key}: warm_ladder did not cover it, so a "
                            f"downsize to {rung_dp}x{rung_tp} would "
                            "compile mid-incident"
                        ),
                    )
                )
        # (b) parity + zero growth through the public dispatch ON the rung
        before = embedder.jit_stats()["specializations"]
        for n, s, ids, mask, ref_out in cases:
            got = np.asarray(embedder.consensus_confidence_tokens(ids, mask))
            if not np.allclose(got, ref_out, atol=atol, rtol=1e-4):
                worst = float(np.max(np.abs(got - ref_out)))
                findings.append(
                    Finding(
                        rule="JXA012",
                        path=f"mesh:{label}",
                        line=0,
                        message=(
                            "degraded-rung dispatch diverges from the "
                            "single-device reference (max abs diff "
                            f"{worst:.2e} > {atol}): the re-dispatched "
                            "answers after a real downsize would be wrong"
                        ),
                    )
                )
        after = embedder.jit_stats()["specializations"]
        grew = {
            name: f"{before.get(name, 0)}->{count}"
            for name, count in after.items()
            if count > before.get(name, 0)
        }
        if grew:
            findings.append(
                Finding(
                    rule="JXA012",
                    path=f"mesh:{label}",
                    line=0,
                    message=(
                        "rung dispatches bypassed the warmed executables "
                        f"and lazily jitted instead ({grew})"
                    ),
                )
            )
    return findings


def _ring_bucket_keys(embedder, ring_buckets):
    """The (label, AOT sub-key) pairs ``aot_warmup(...,
    ring_buckets=...)`` lands for a warmed sp-mesh embedder — snapped
    through the same sequence-bucket + sp-multiple rounding the warmup
    and the dispatch both apply, so the audit checks the keys that
    actually serve."""
    from ..models.embedder import _bucket, _seq_bucket

    sp = embedder.mesh_sp
    bm = embedder.batch_multiple
    out = []
    for n, s in ring_buckets:
        s = _seq_bucket(s, embedder.ring_max_tokens)
        s = min(s + (-s) % sp, embedder.ring_max_tokens)
        out.append((f"ring_vote(n={n},s={s})", ("ring_vote", n, s)))
        pad_b = _bucket(n, embedder.MAX_DEVICE_BATCH)
        pad_b += (-pad_b) % bm
        out.append((f"ring(b={pad_b},s={s})", ("ring", pad_b, s)))
    return out


def _measure_ring_buckets(
    model: str, dp: int, tp: int, sp: int, ring_buckets
) -> Tuple[List[Finding], Dict[str, Dict[str, float]]]:
    """JXA008–011 over the long-context ring (sequence-parallel)
    buckets: build the sp-bearing mesh embedder exactly as
    serve/__main__.py does from ``MESH_SHAPE=dpxTPxSP`` +
    ``LONG_CONTEXT_WARMUP`` (the sp axis folds out of dp so the device
    budget stays ``dp*tp``), then audit the warmed ring executables —
    collective plan and resource figures straight off the AOT table,
    and ring-vs-dense parity through the PUBLIC ring dispatch against a
    same-seed single-device reference (the ring rotation must be a
    layout change, not a math change), bracketed by the usual
    zero-specialization guard."""
    import numpy as np

    from ..models.embedder import TpuEmbedder
    from ..parallel.mesh import make_mesh
    from ..parallel.sharding import shard_embedder_mesh

    findings: List[Finding] = []
    measured: Dict[str, Dict[str, float]] = {}
    mesh = make_mesh(dp=dp // sp, tp=tp, sp=sp)
    ref = TpuEmbedder(model, max_tokens=64, seed=0, quantize="none")
    embedder = TpuEmbedder(model, max_tokens=64, seed=0, quantize="none")
    shard_embedder_mesh(embedder, mesh)
    embedder.aot_warmup([], ring_buckets=list(ring_buckets))

    rng = np.random.default_rng(3)
    vocab = embedder.config.vocab_size
    temp = 1.0
    atol = 1e-4

    def account(label, key):
        exe = embedder._aot.get(embedder._ring_aot_key(key))
        if exe is None:
            findings.append(
                Finding(
                    rule="JXA008",
                    path=f"mesh:{label}",
                    line=0,
                    message=(
                        f"no AOT executable at ring bucket {key}: "
                        "aot_warmup(ring_buckets=...) did not cover it, "
                        "so long-context traffic at this bucket would "
                        "lazily jit mid-request"
                    ),
                )
            )
            return
        findings.extend(audit_hlo_collectives(exe.as_text(), label))
        measured[label] = _exe_figures(exe)

    # inputs + single-device DENSE reference outputs first (shared jit
    # caches; the zero-growth bracket below must see ring traffic only)
    cases = []
    for label, key in _ring_bucket_keys(embedder, ring_buckets):
        kind, s = key[0], key[-1]
        if kind == "ring_vote":
            n = key[1]
            ids = rng.integers(3, vocab, (n, s)).astype(np.int32)
            mask = np.ones((n, s), np.int32)
            ref_out = np.asarray(
                ref.consensus_confidence_tokens(ids, mask, temperature=temp)
            )
        else:
            pad_b = key[1]
            ids = rng.integers(3, vocab, (pad_b, s)).astype(np.int32)
            mask = np.ones((pad_b, s), np.int32)
            ref_out = np.asarray(ref.embed_tokens(ids, mask))
        cases.append((kind, label, key, (ids, mask), ref_out))

    before = embedder.jit_stats()["specializations"]
    for kind, label, key, args, ref_out in cases:
        account(label, key)
        if kind == "ring_vote":
            got = embedder.consensus_confidence_tokens_ring(
                args[0], args[1], temperature=temp
            )
        else:
            got = embedder.embed_tokens_ring(*args)
        got = np.asarray(got)
        if not np.allclose(got, ref_out, atol=atol, rtol=1e-4):
            worst = float(np.max(np.abs(got - ref_out)))
            findings.append(
                Finding(
                    rule="JXA011",
                    path=f"mesh:{label}",
                    line=0,
                    message=(
                        "ring dispatch diverges from the single-device "
                        f"dense reference (max abs diff {worst:.2e} > "
                        f"{atol}): the sequence rotation changed the "
                        "math, not just the layout"
                    ),
                )
            )
    after = embedder.jit_stats()["specializations"]
    grew = {
        name: f"{before.get(name, 0)}->{count}"
        for name, count in after.items()
        if count > before.get(name, 0)
    }
    if grew:
        findings.append(
            Finding(
                rule="JXA008",
                path="mesh:ring-dispatch",
                line=0,
                message=(
                    "ring dispatches bypassed the audited AOT "
                    f"executables and lazily jitted instead ({grew}): "
                    "the ring bucket figures above describe executables "
                    "that served no traffic"
                ),
            )
        )
    return findings, measured


def _audit_ring_fault_ladder(
    model: str, dp: int, tp: int, sp: int, ring_buckets
) -> List[Finding]:
    """JXA012 on the sp-bearing mesh: walk the downsize ladder of a
    ring-serving embedder (dp halves, tp AND sp preserved per rung) and
    on every fallback rung assert the ring buckets were pre-warmed
    under that rung's ``("mesh", dp, tp, sp)`` namespace and that the
    public ring dispatch still matches the single-device dense
    reference with zero jit growth — a downsize mid-incident must not
    compile a ring executable or corrupt a long-context answer."""
    import numpy as np

    from ..models.embedder import TpuEmbedder
    from ..parallel.mesh import make_mesh
    from ..parallel.sharding import shard_embedder_mesh
    from ..resilience import MeshFaultManager

    findings: List[Finding] = []
    rdp = dp // sp
    if rdp < 2:
        return findings  # no rung below the full shape to walk
    ref = TpuEmbedder(model, max_tokens=64, seed=0, quantize="none")
    embedder = TpuEmbedder(model, max_tokens=64, seed=0, quantize="none")
    shard_embedder_mesh(embedder, make_mesh(dp=rdp, tp=tp, sp=sp))
    manager = MeshFaultManager(embedder, shape=(rdp, tp))
    manager.warm_ladder([], ring_buckets=list(ring_buckets))

    rng = np.random.default_rng(5)
    vocab = embedder.config.vocab_size
    atol = 1e-4
    cases = []
    for label, key in _ring_bucket_keys(embedder, ring_buckets):
        if key[0] != "ring_vote":
            continue
        n, s = key[1], key[2]
        ids = rng.integers(3, vocab, (n, s)).astype(np.int32)
        mask = np.ones((n, s), np.int32)
        ref_out = np.asarray(ref.consensus_confidence_tokens(ids, mask))
        cases.append((n, s, ids, mask, ref_out))

    for rung_dp, rung_tp in manager.build_ladder()[1:]:
        label = f"ring-ladder:{rung_dp}x{rung_tp}x{sp}"
        if not manager.downsize():
            findings.append(
                Finding(
                    rule="JXA012",
                    path=f"mesh:{label}",
                    line=0,
                    message=(
                        "downsize() refused a declared ladder rung on "
                        "the sp-bearing mesh: the ladder the manager "
                        "walks is not the ladder it declared"
                    ),
                )
            )
            break
        for _blabel, key in _ring_bucket_keys(embedder, ring_buckets):
            if embedder._aot.get(embedder._ring_aot_key(key)) is None:
                findings.append(
                    Finding(
                        rule="JXA012",
                        path=f"mesh:{label}",
                        line=0,
                        message=(
                            f"no AOT executable at fallback-rung ring "
                            f"bucket {key}: warm_ladder did not cover "
                            f"it, so a downsize to {rung_dp}x{rung_tp}"
                            f"x{sp} would compile a long-context "
                            "executable mid-incident"
                        ),
                    )
                )
        before = embedder.jit_stats()["specializations"]
        for n, s, ids, mask, ref_out in cases:
            got = np.asarray(
                embedder.consensus_confidence_tokens_ring(ids, mask)
            )
            if not np.allclose(got, ref_out, atol=atol, rtol=1e-4):
                worst = float(np.max(np.abs(got - ref_out)))
                findings.append(
                    Finding(
                        rule="JXA012",
                        path=f"mesh:{label}",
                        line=0,
                        message=(
                            "degraded-rung ring dispatch diverges from "
                            "the single-device dense reference (max abs "
                            f"diff {worst:.2e} > {atol}): re-dispatched "
                            "long-context answers after a real downsize "
                            "would be wrong"
                        ),
                    )
                )
        after = embedder.jit_stats()["specializations"]
        grew = {
            name: f"{before.get(name, 0)}->{count}"
            for name, count in after.items()
            if count > before.get(name, 0)
        }
        if grew:
            findings.append(
                Finding(
                    rule="JXA012",
                    path=f"mesh:{label}",
                    line=0,
                    message=(
                        "rung ring dispatches bypassed the warmed "
                        f"executables and lazily jitted instead ({grew})"
                    ),
                )
            )
    return findings


# ---------------------------------------------------------------------------
# Orchestration: in-process when devices suffice, else self-respawn
# ---------------------------------------------------------------------------


def _devices_ok(need: int) -> bool:
    import jax

    return jax.device_count() >= need


def _respawn(
    need: int, write_budgets: bool, write_roofline: bool = False
) -> List[Finding]:
    """Re-run this module in a child with ``need`` virtual CPU devices
    (the parent's jax backend, if initialized, is stuck at its device
    count — XLA_FLAGS are read once at first backend init)."""
    from ..parallel.dist import force_cpu_env

    cmd = [
        sys.executable,
        "-m",
        "llm_weighted_consensus_tpu.analysis.mesh_audit",
        "--json",
    ]
    if write_budgets:
        cmd.append("--write-budgets")
    if write_roofline:
        cmd.append("--write-roofline")
    env = force_cpu_env(dict(os.environ), n_devices=need)
    proc = subprocess.run(
        cmd, capture_output=True, text=True, env=env, timeout=600
    )
    try:
        payload = json.loads(proc.stdout)
        return [Finding(**entry) for entry in payload["findings"]]
    except (json.JSONDecodeError, KeyError, TypeError):
        tail = (proc.stderr or proc.stdout or "")[-800:]
        return [
            Finding(
                rule="JXA008",
                path="mesh:subprocess",
                line=0,
                message=(
                    "mesh audit subprocess failed (exit "
                    f"{proc.returncode}); tail: {tail!r}"
                ),
            )
        ]


def _audit_in_process(
    write_budgets: bool = False,
    write_roofline_file: bool = False,
) -> Tuple[List[Finding], Dict[str, Dict[str, float]]]:
    findings: List[Finding] = []
    budgets_path = _budgets_path()
    budgets = load_budgets(budgets_path)
    allowlist = replicated_allowlist(budgets)
    threshold = replicated_threshold(budgets)
    matched: Set[str] = set()
    for label, rules, tree in _shape_trees():
        findings += audit_rule_coverage(rules, tree, label)
        repl_findings, repl_matched = audit_replication(
            rules, tree, label, threshold, allowlist
        )
        findings += repl_findings
        matched |= repl_matched
    findings += check_allowlist_stale(allowlist, matched)

    dp, tp = _env_mesh()
    bucket_findings, measured = _measure_buckets(
        _env_model(), dp, tp,
        _env_specs(), _env_r_buckets(),
    )
    findings += bucket_findings
    # JXA012 rung figures carry no committed budget baseline; the ladder
    # audit contributes findings only, never entries in ``measured``.
    findings += _audit_fault_ladder(
        _env_model(), dp, tp,
        _env_specs(), _env_r_buckets(),
    )
    # long-context ring buckets on the sp-bearing mesh: same JXA008–011
    # treatment (figures land in budgets/roofline next to the dense
    # buckets), plus the sp-preserving downsize ladder (JXA012)
    if _ring_enabled():
        sp = _env_sp()
        ring_findings, ring_measured = _measure_ring_buckets(
            _env_model(), dp, tp, sp, _env_ring_buckets()
        )
        findings += ring_findings
        measured.update(ring_measured)
        findings += _audit_ring_fault_ladder(
            _env_model(), dp, tp, sp, _env_ring_buckets()
        )
    if write_budgets:
        _write_budgets_file(budgets_path, measured, budgets)
    else:
        findings += compare_budgets(measured, budgets, scope=_scope())
    # JXA013: the same measured cost figures must back a committed
    # roofline row per bucket, or serving would run without its
    # speed-of-light attainment gauge.
    roofline_path = _roofline_path()
    roofline = load_roofline(roofline_path)
    if write_roofline_file:
        write_roofline(roofline_path, measured, _scope(), roofline)
    else:
        findings += compare_roofline(measured, roofline, scope=_scope())
    return findings, measured


def _write_budgets_file(
    path: Path, measured: Dict[str, Dict[str, float]], previous: dict
) -> None:
    """Fresh measurements under the committed policy knobs (tolerance,
    threshold, allowlist survive a re-baseline; figures do not)."""
    payload = {
        "_doc": (
            "Committed per-bucket resource budgets for the mesh audit "
            "(JXA009/JXA010). Re-baseline: python -m "
            "llm_weighted_consensus_tpu.analysis.mesh_audit "
            "--write-budgets, then review the diff. Policy: DESIGN.md "
            "'Static analysis v2'."
        ),
        "scope": _scope(),
        "tolerance": previous.get(
            "tolerance",
            {"hbm_bytes": 0.25, "flops": 0.25, "bytes_accessed": 0.25},
        ),
        "replicated_threshold_bytes": previous.get(
            "replicated_threshold_bytes", 1 << 20
        ),
        "replicated_allowlist": previous.get("replicated_allowlist", []),
        "buckets": {
            label: {k: round(v, 1) for k, v in figures.items()}
            for label, figures in sorted(measured.items())
        },
    }
    path.write_text(json.dumps(payload, indent=2) + "\n", encoding="utf-8")


def run_mesh_audit(
    write_budgets: bool = False, write_roofline: bool = False
) -> List[Finding]:
    """Entry point for the analysis CLI and tier-1: in-process when the
    backend already has dp*tp devices (pytest's virtual-CPU env),
    subprocess respawn otherwise."""
    dp, tp = _env_mesh()
    if not _devices_ok(dp * tp):
        return _respawn(dp * tp, write_budgets, write_roofline)
    findings, _ = _audit_in_process(write_budgets, write_roofline)
    return findings


def main(argv=None) -> int:
    import argparse

    parser = argparse.ArgumentParser(
        prog="python -m llm_weighted_consensus_tpu.analysis.mesh_audit",
        description="simulated-mesh sharding & resource audit (JXA006-011)",
    )
    parser.add_argument(
        "--json", action="store_true", help="machine-readable output"
    )
    parser.add_argument(
        "--write-budgets",
        action="store_true",
        help="re-measure and rewrite analysis/budgets.json "
        "(policy knobs preserved); review the diff",
    )
    parser.add_argument(
        "--write-roofline",
        action="store_true",
        help="re-measure and rewrite analysis/roofline.json "
        "(peaks and tolerance preserved); review the diff",
    )
    args = parser.parse_args(argv)

    dp, tp = _env_mesh()
    if not _devices_ok(dp * tp):
        findings = _respawn(dp * tp, args.write_budgets, args.write_roofline)
        measured = {}
    else:
        findings, measured = _audit_in_process(
            args.write_budgets, args.write_roofline
        )
    if args.json:
        print(
            json.dumps(
                {
                    "findings": [vars(f) for f in findings],
                    "measured": measured,
                    "scope": _scope(),
                }
            )
        )
    else:
        for finding in findings:
            print(finding.render())
        print(
            f"mesh audit: {len(findings)} finding(s), "
            f"{len(measured)} bucket(s) measured",
            file=sys.stderr,
        )
    return 1 if findings else 0


if __name__ == "__main__":
    sys.exit(main())
