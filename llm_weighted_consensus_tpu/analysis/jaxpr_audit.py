"""CPU-only jaxpr audit of the compiled serving path.

Lowers the embedder/consensus serving functions exactly as the gateway
dispatches them (per AOT bucket, int8 path pinned) and statically
asserts the compiled hot path's invariants — no TPU required, because
every check is on the *traced* structure (``jax.make_jaxpr``) or the
jit-dispatch bookkeeping (PR 3's ``jit_stats``), not on device output:

* **JXA001 host-transfer** — no host↔device primitives inside the
  jitted hot path: callbacks (``pure_callback``/``io_callback``/
  ``debug_callback``), ``device_put``, infeed/outfeed.  A
  ``jax.device_get``/``np.asarray`` on a traced value never reaches
  the jaxpr — it explodes at trace time — so the auditor catches the
  concretization error and reports it as the same class of finding.
* **JXA002 dequant-regression** — the W8A8 int8 path keeps its shape:
  at least one Pallas kernel in the forward, and ZERO
  ``convert_element_type`` int8→float (the storage-format anti-pattern
  — dequantizing ``kernel_q`` back to bf16 before a bf16 matmul — that
  the fused path replaced).
* **JXA003 f64-promotion** — no float64 avals anywhere in the traced
  serving math (an x64 leak doubles every buffer and halves MXU rate).
* **JXA004 missing-aot-bucket / JXA005 stray-specialization** — after
  ``aot_warmup`` over the serving specs, every expected bucket key is
  present in the executable table, and driving one of everything the
  gateway dispatches creates ZERO new jit specializations
  (``jit_stats`` delta per entry point).

The quantized-path rules extend to the packed-int4 W4A8 mode: the
serving entry points are re-traced with ``int4-pallas`` pinned
(JXA002's dequant predicate widens to uint8 nibble storage -> float,
the lost-int4-kernel regression), and a second AOT guard warms and
drives an ``int4-pallas`` embedder through the shared bucket-key
namespace (JXA004/005).  The sequence-parallel ring entry points
(``parallel.ring._ring_embed_jit`` / ``_ring_embed_and_vote``) are
traced under a live ``sp`` mesh for JXA001/2/3 whenever the backend has
>= 2 devices (tier-1 always does).

Env knobs (all optional): ``ANALYSIS_JAXPR_MODEL`` (preset, default
``test-tiny``), ``ANALYSIS_JAXPR_SPECS`` (comma list of ``NxS``,
default ``4x16``), ``ANALYSIS_JAXPR_R_BUCKETS`` (comma list, default
``2``), ``ANALYSIS_SKIP_JAXPR=1`` to skip the audit entirely (the CLI honors
it; tier-1 does not set it).

jax is imported lazily inside the entry points so importing
``analysis`` stays stdlib-cheap.
"""

from __future__ import annotations

import os
from typing import List, Optional, Sequence, Tuple

from .engine import Finding

# primitive names that mean the "hot loop never touches the host"
# contract is broken
_HOST_PRIMS = {
    "pure_callback",
    "io_callback",
    "debug_callback",
    "callback",
    "device_put",
    "infeed",
    "outfeed",
}

_DEFAULT_MODEL = "test-tiny"
_DEFAULT_SPECS = ((4, 16),)
_DEFAULT_R_BUCKETS = (2,)


def _env_specs() -> Tuple[Tuple[int, int], ...]:
    raw = os.environ.get("ANALYSIS_JAXPR_SPECS", "")
    if not raw.strip():
        return _DEFAULT_SPECS
    specs = []
    for part in raw.split(","):
        n, s = part.strip().lower().split("x")
        specs.append((int(n), int(s)))
    return tuple(specs)


def _env_r_buckets() -> Tuple[int, ...]:
    raw = os.environ.get("ANALYSIS_JAXPR_R_BUCKETS", "")
    if not raw.strip():
        return _DEFAULT_R_BUCKETS
    return tuple(int(p) for p in raw.split(",") if p.strip())


# ---------------------------------------------------------------------------
# jaxpr walking + the three structural checks
# ---------------------------------------------------------------------------


def walk_jaxpr(jaxpr, visit) -> None:
    """Depth-first over every equation, descending into sub-jaxprs
    (pjit bodies, scan/cond branches, Pallas kernel bodies)."""
    for eqn in jaxpr.eqns:
        visit(eqn)
        for sub in eqn.params.values():
            if hasattr(sub, "eqns"):
                walk_jaxpr(sub, visit)
            elif hasattr(sub, "jaxpr"):
                walk_jaxpr(sub.jaxpr, visit)


def audit_closed_jaxpr(
    closed, label: str, *, expect_pallas: bool = False, int4: bool = False
) -> List[Finding]:
    """The structural checks over one traced function (a
    ``jax.make_jaxpr`` result).  ``expect_pallas`` additionally asserts
    the fused quantized kernel is still present (JXA002's other half);
    ``int4`` widens the dequant predicate to the packed W4A8 layout
    (uint8 nibble storage -> float is the lost-int4-kernel regression,
    exactly as int8 -> float is the lost-int8-kernel one)."""
    import jax.numpy as jnp

    findings: List[Finding] = []
    pallas_calls = 0

    def visit(eqn):
        nonlocal pallas_calls
        name = eqn.primitive.name
        if name == "pallas_call":
            pallas_calls += 1
        if name in _HOST_PRIMS or name.endswith("_callback"):
            findings.append(
                Finding(
                    rule="JXA001",
                    path=f"jaxpr:{label}",
                    line=0,
                    message=(
                        f"host-transfer primitive `{name}` inside the "
                        "jitted serving path; the hot loop must not "
                        "touch the host"
                    ),
                )
            )
        if name == "convert_element_type":
            src = eqn.invars[0].aval
            dst = eqn.outvars[0].aval
            if src.dtype == jnp.int8 and jnp.issubdtype(
                dst.dtype, jnp.floating
            ):
                findings.append(
                    Finding(
                        rule="JXA002",
                        path=f"jaxpr:{label}",
                        line=0,
                        message=(
                            "`convert_element_type` int8->"
                            f"{dst.dtype.name}: a dequantize-then-"
                            "float-matmul regression in the W8A8 path"
                        ),
                    )
                )
            if int4 and src.dtype == jnp.uint8 and jnp.issubdtype(
                dst.dtype, jnp.floating
            ):
                findings.append(
                    Finding(
                        rule="JXA002",
                        path=f"jaxpr:{label}",
                        line=0,
                        message=(
                            "`convert_element_type` uint8->"
                            f"{dst.dtype.name}: the packed int4 nibbles "
                            "were dequantized to float outside the "
                            "fused W4A8 kernel"
                        ),
                    )
                )
        for var in eqn.outvars:
            aval = getattr(var, "aval", None)
            dtype = getattr(aval, "dtype", None)
            if dtype is not None and str(dtype) == "float64":
                findings.append(
                    Finding(
                        rule="JXA003",
                        path=f"jaxpr:{label}",
                        line=0,
                        message=(
                            f"float64 aval out of `{eqn.primitive.name}`"
                            ": x64 promotion leaked into the serving "
                            "math"
                        ),
                    )
                )

    walk_jaxpr(closed.jaxpr, visit)
    if expect_pallas and pallas_calls == 0:
        kernel = "W4A8" if int4 else "W8A8"
        findings.append(
            Finding(
                rule="JXA002",
                path=f"jaxpr:{label}",
                line=0,
                message=(
                    f"{'int4' if int4 else 'int8'} path traced with ZERO "
                    f"pallas_call equations; the fused {kernel} kernel "
                    "fell out of the forward"
                ),
            )
        )
    return findings


def audit_traced(
    fn,
    example_args: Sequence,
    label: str,
    *,
    expect_pallas: bool = False,
    int4: bool = False,
) -> List[Finding]:
    """Trace ``fn(*example_args)`` and run the structural checks.

    Trace-time concretization failures (``jax.device_get`` /
    ``np.asarray`` on a tracer) are reported as JXA001 rather than
    raised: they are the most literal form of "host transfer inside the
    jitted path"."""
    import jax

    try:
        closed = jax.make_jaxpr(fn)(*example_args)
    except (
        jax.errors.TracerArrayConversionError,
        jax.errors.ConcretizationTypeError,
        jax.errors.UnexpectedTracerError,
    ) as exc:
        return [
            Finding(
                rule="JXA001",
                path=f"jaxpr:{label}",
                line=0,
                message=(
                    "host transfer at trace time (device_get/"
                    "np.asarray on a traced value): "
                    f"{type(exc).__name__}"
                ),
            )
        ]
    return audit_closed_jaxpr(
        closed, label, expect_pallas=expect_pallas, int4=int4
    )


# ---------------------------------------------------------------------------
# the serving-path audit proper
# ---------------------------------------------------------------------------


def _structure_findings(model: str, specs, r_buckets) -> List[Finding]:
    """Trace every serving entry point with the Pallas int8 impl pinned
    (``int8-pallas`` traces fine off-TPU; compilation isn't needed for
    structure) and run the JXA001/2/3 checks per AOT bucket."""
    import jax
    import jax.numpy as jnp

    from ..models.embedder import (
        TpuEmbedder,
        _bucket,
        _embed_and_vote,
        _embed_and_vote_many,
        _seq_bucket,
        _stream_vote_update,
    )
    from ..models import bert

    embedder = TpuEmbedder(model, max_tokens=64, seed=0, quantize="int8-pallas")
    sds = jax.ShapeDtypeStruct
    temp = sds((), jnp.float32)
    findings: List[Finding] = []
    hidden = embedder.config.hidden_size
    for n, s in specs:
        s = _seq_bucket(s, embedder.max_tokens)
        ids = sds((n, s), jnp.int32)
        for use_fused in (True, False):
            findings.extend(
                audit_traced(
                    lambda p, i, m, t, _n=n, _f=use_fused: _embed_and_vote(
                        p, i, m, t, _n, embedder.config, embedder.pooling, _f
                    ),
                    (embedder.params, ids, ids, temp),
                    f"vote1(n={n},s={s},fused={use_fused})",
                    expect_pallas=True,
                )
            )
        pad_b = _bucket(n, embedder.MAX_DEVICE_BATCH)
        bids = sds((pad_b, s), jnp.int32)
        findings.extend(
            audit_traced(
                lambda p, i, m: bert.embed(
                    p, i, m, embedder.config,
                    pooling=embedder.pooling, normalize=True,
                ),
                (embedder.params, bids, bids),
                f"embed(b={pad_b},s={s})",
                expect_pallas=True,
            )
        )
        for r in r_buckets:
            if r < 2:
                continue
            flat = sds((r * n, s), jnp.int32)
            findings.extend(
                audit_traced(
                    lambda p, i, m, t, _r=r, _n=n: _embed_and_vote_many(
                        p, i, m, t, _r, _n, embedder.config, embedder.pooling
                    ),
                    (embedder.params, flat, flat, temp),
                    f"many(r={r},n={n},s={s})",
                    expect_pallas=True,
                )
            )
        # one streaming-consensus step at this bucket's capacity
        cap = _bucket(n, embedder.MAX_DEVICE_BATCH)
        findings.extend(
            audit_traced(
                lambda p, i, m, b, v, pos, t: _stream_vote_update(
                    p, i, m, b, v, pos, embedder.config,
                    embedder.pooling, t,
                ),
                (
                    embedder.params,
                    sds((1, s), jnp.int32),
                    sds((1, s), jnp.int32),
                    sds((cap, hidden), jnp.float32),
                    sds((cap,), jnp.float32),
                    sds((), jnp.int32),
                    temp,
                ),
                f"stream(cap={cap},s={s})",
                expect_pallas=True,
            )
        )
    findings += _int4_structure_findings(model, specs)
    findings += _ring_structure_findings(model, specs)
    return findings


def _int4_structure_findings(model: str, specs) -> List[Finding]:
    """The W4A8 twin of the int8 structure audit: trace the serving
    entry points with ``int4-pallas`` pinned and assert the fused packed
    kernel is present (and that no uint8->float dequant crept in — the
    lost-int4-kernel regression, JXA002)."""
    import jax
    import jax.numpy as jnp

    from ..models.embedder import (
        TpuEmbedder,
        _bucket,
        _embed_and_vote,
        _seq_bucket,
    )
    from ..models import bert

    embedder = TpuEmbedder(
        model, max_tokens=64, seed=0, quantize="int4-pallas"
    )
    sds = jax.ShapeDtypeStruct
    temp = sds((), jnp.float32)
    findings: List[Finding] = []
    for n, s in specs:
        s = _seq_bucket(s, embedder.max_tokens)
        ids = sds((n, s), jnp.int32)
        findings.extend(
            audit_traced(
                lambda p, i, m, t, _n=n: _embed_and_vote(
                    p, i, m, t, _n, embedder.config, embedder.pooling, True
                ),
                (embedder.params, ids, ids, temp),
                f"int4:vote1(n={n},s={s})",
                expect_pallas=True,
                int4=True,
            )
        )
        pad_b = _bucket(n, embedder.MAX_DEVICE_BATCH)
        bids = sds((pad_b, s), jnp.int32)
        findings.extend(
            audit_traced(
                lambda p, i, m: bert.embed(
                    p, i, m, embedder.config,
                    pooling=embedder.pooling, normalize=True,
                ),
                (embedder.params, bids, bids),
                f"int4:embed(b={pad_b},s={s})",
                expect_pallas=True,
                int4=True,
            )
        )
    return findings


def _ring_structure_findings(model: str, specs) -> List[Finding]:
    """JXA001/2/3 over the sequence-parallel (ring attention) serving
    entry points — the exact jitted functions the sp-mesh batcher
    dispatches (``parallel.ring._ring_embed_jit`` /
    ``_ring_embed_and_vote``).  The ring shard_map needs a live mesh
    with an ``sp`` axis, so this leg runs only when the backend has at
    least two devices (tier-1's 8 virtual CPUs always qualify; a bare
    single-device CLI run skips it — the mesh audit still covers the
    compiled ring executables there)."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    if jax.device_count() < 2:
        return []

    from ..models.embedder import TpuEmbedder, _seq_bucket
    from ..parallel.mesh import make_mesh
    from ..parallel.ring import _ring_embed_and_vote, _ring_embed_jit

    sp = 2
    mesh = make_mesh(dp=1, tp=1, sp=sp)
    embedder = TpuEmbedder(model, max_tokens=64, seed=0, quantize="int8-pallas")
    ring_config = dataclasses.replace(
        embedder.config, attention_impl="ring", ring_axis="sp"
    )
    sds = jax.ShapeDtypeStruct
    temp = sds((), jnp.float32)
    findings: List[Finding] = []
    for n, s in specs:
        s = _seq_bucket(s, embedder.max_tokens)
        s = min(s + (-s) % sp, embedder.max_tokens)
        ids = sds((n, s), jnp.int32)
        findings.extend(
            audit_traced(
                lambda p, i, m: _ring_embed_jit(
                    p, i, m, ring_config, mesh, "sp", "dp",
                    embedder.pooling, True,
                ),
                (embedder.params, ids, ids),
                f"ring(b={n},s={s})",
                expect_pallas=True,
            )
        )
        findings.extend(
            audit_traced(
                lambda p, i, m, t, _n=n: _ring_embed_and_vote(
                    p, i, m, t, _n, ring_config, mesh, "sp", "dp",
                    embedder.pooling,
                ),
                (embedder.params, ids, ids, temp),
                f"ring_vote(n={n},s={s})",
                expect_pallas=True,
            )
        )
    return findings


def _aot_findings(model: str, specs, r_buckets) -> List[Finding]:
    """The specialization guard: warm every serving bucket with the
    auto int8 impl (the one CPU can execute), assert every expected
    key landed in the executable table, drive one of everything the
    gateway dispatches and assert the jit caches did not grow."""
    import numpy as np

    from ..models.embedder import TpuEmbedder, _bucket, _seq_bucket

    embedder = TpuEmbedder(model, max_tokens=64, seed=0, quantize="int8")
    findings: List[Finding] = []
    warm_specs = [(n, s) for n, s in specs]
    embedder.aot_warmup(warm_specs, r_buckets=list(r_buckets))

    rng = np.random.default_rng(7)
    for n, s in specs:
        s = _seq_bucket(s, embedder.max_tokens)
        expected = [("vote1", n, s, True), ("vote1", n, s, False)]
        expected.append(("embed", _bucket(n, embedder.MAX_DEVICE_BATCH), s))
        for r in r_buckets:
            if r >= 2:
                expected.append(("many", r, n, s))
        for key in expected:
            if key not in embedder._aot:
                findings.append(
                    Finding(
                        rule="JXA004",
                        path=f"jaxpr:aot({model})",
                        line=0,
                        message=(
                            f"serving bucket {key} missing from the AOT "
                            "executable table after warmup — this shape "
                            "will lazily specialize under live traffic"
                        ),
                    )
                )
    stats0 = embedder.jit_stats()["specializations"]
    for n, s in specs:
        s = _seq_bucket(s, embedder.max_tokens)
        vocab = embedder.config.vocab_size
        ids = rng.integers(3, vocab, (n, s)).astype(np.int32)
        mask = np.ones((n, s), np.int32)
        embedder.consensus_confidence_tokens(ids, mask)
        embedder.consensus_confidence_tokens(ids, mask, temperature=0.2)
        embedder.embed_tokens(ids, mask)
        for r in r_buckets:
            if r < 2:
                continue
            embedder.consensus_confidence_tokens_many(
                np.stack([ids] * r), np.stack([mask] * r)
            )
    stats1 = embedder.jit_stats()["specializations"]
    for entry, count in stats1.items():
        grew = count - stats0.get(entry, 0)
        if grew > 0:
            findings.append(
                Finding(
                    rule="JXA005",
                    path=f"jaxpr:aot({model})",
                    line=0,
                    message=(
                        f"`{entry}` grew {grew} jit specialization(s) "
                        "under post-warmup traffic at warmed buckets — "
                        "the AOT table is not being consulted"
                    ),
                )
            )
    findings += _int4_aot_findings(model, specs)
    return findings


def _int4_aot_findings(model: str, specs) -> List[Finding]:
    """JXA004/JXA005 for the ``int4-pallas`` serving mode: the packed
    W4A8 path shares the AOT key namespace with every other quantize
    mode, so warmup must land the same bucket keys and post-warmup
    traffic must ride them with zero jit growth.  The fused kernel runs
    in interpret mode on CPU, so this drives real dispatches in tier-1."""
    import numpy as np

    from ..models.embedder import TpuEmbedder, _bucket, _seq_bucket

    embedder = TpuEmbedder(
        model, max_tokens=64, seed=0, quantize="int4-pallas"
    )
    findings: List[Finding] = []
    embedder.aot_warmup([(n, s) for n, s in specs])
    rng = np.random.default_rng(11)
    vocab = embedder.config.vocab_size
    for n, s in specs:
        s = _seq_bucket(s, embedder.max_tokens)
        expected = [
            ("vote1", n, s, True),
            ("vote1", n, s, False),
            ("embed", _bucket(n, embedder.MAX_DEVICE_BATCH), s),
        ]
        for key in expected:
            if key not in embedder._aot:
                findings.append(
                    Finding(
                        rule="JXA004",
                        path=f"jaxpr:aot({model},int4)",
                        line=0,
                        message=(
                            f"int4-pallas serving bucket {key} missing "
                            "from the AOT executable table after warmup "
                            "— this shape will lazily specialize under "
                            "live traffic"
                        ),
                    )
                )
    stats0 = embedder.jit_stats()["specializations"]
    for n, s in specs:
        s = _seq_bucket(s, embedder.max_tokens)
        ids = rng.integers(3, vocab, (n, s)).astype(np.int32)
        mask = np.ones((n, s), np.int32)
        embedder.consensus_confidence_tokens(ids, mask)
        embedder.embed_tokens(ids, mask)
    stats1 = embedder.jit_stats()["specializations"]
    for entry, count in stats1.items():
        grew = count - stats0.get(entry, 0)
        if grew > 0:
            findings.append(
                Finding(
                    rule="JXA005",
                    path=f"jaxpr:aot({model},int4)",
                    line=0,
                    message=(
                        f"`{entry}` grew {grew} jit specialization(s) "
                        "under post-warmup int4-pallas traffic — the "
                        "AOT table is not being consulted"
                    ),
                )
            )
    return findings


def run_jaxpr_audit(
    model: Optional[str] = None,
    specs: Optional[Sequence[Tuple[int, int]]] = None,
    r_buckets: Optional[Sequence[int]] = None,
) -> List[Finding]:
    """The full audit: structure (traced int8-pallas path) + AOT
    coverage/specialization guard.  CPU-safe; ~seconds on test-tiny."""
    model = model or os.environ.get("ANALYSIS_JAXPR_MODEL", _DEFAULT_MODEL)
    specs = tuple(specs) if specs is not None else _env_specs()
    r_buckets = (
        tuple(r_buckets) if r_buckets is not None else _env_r_buckets()
    )
    findings = _structure_findings(model, specs, r_buckets)
    findings += _aot_findings(model, specs, r_buckets)
    return findings
