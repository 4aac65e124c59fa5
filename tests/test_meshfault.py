"""Mesh fault domains (ISSUE PR 10): classify → downsize → re-dispatch
→ probe → upsize, on the tier-1 8-virtual-device CPU mesh.

What this pins:

* fault classification at the embedder/batcher seam — injected and
  XlaRuntimeError-shaped faults sort transient/persistent, ordinary
  application errors stay on the fail-the-group path, and the
  transient-streak / watchdog-overdue escalations fire;
* the downsize ladder — dp halving with tp preserved, every rung's mesh
  a device-prefix submesh, every rung AOT-warmed under its own
  ``("mesh", dp, tp)`` key namespace at startup;
* the batcher's re-dispatch contract — a faulted group re-queues onto
  the downsized shape and the answers are numerically identical to a
  fault-free run; past-deadline items shed 504 exactly like the PR 4
  drain path; admission and batcher capacity rescale to the surviving
  chip fraction;
* recovery — ``try_recover`` re-validates the full mesh and upsizes
  back (or keeps the mesh down while the plan still faults);
* the acceptance drill — seeded ``DEVICE_FAULT_PLAN``, persistent fault
  mid-traffic, exactly one downsize, zero non-504 request errors,
  ``/readyz`` flying the ``degraded_mesh`` flag until the upsize;
* identity — no manager attached (MESH_FAULT_ENABLED unset) and
  manager-attached-but-healthy both serve byte-identically, and the
  config validation refuses the nonsensical knob combos.
"""

import asyncio
import threading
import time

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from llm_weighted_consensus_tpu.errors import DeadlineExceededError
from llm_weighted_consensus_tpu.models import configs
from llm_weighted_consensus_tpu.models.embedder import TpuEmbedder
from llm_weighted_consensus_tpu.parallel.mesh import make_mesh
from llm_weighted_consensus_tpu.parallel.sharding import shard_embedder_mesh
from llm_weighted_consensus_tpu.resilience import (
    Deadline,
    DeviceFaultPlan,
    InjectedHangError,
    InjectedPersistentError,
    InjectedTransientError,
    MeshFaultManager,
    classify_dispatch_error,
)
from llm_weighted_consensus_tpu.serve.batcher import DeviceBatcher
from llm_weighted_consensus_tpu.serve.config import Config
from llm_weighted_consensus_tpu.serve.metrics import Metrics

TINY = configs.TEST_TINY
DP, TP = 4, 2
N, S, R = 4, 16, 2

TEXTS = [f"candidate number {i % 3} under fault" for i in range(6)]


def go(coro):
    return asyncio.new_event_loop().run_until_complete(coro)


def make_embedder(**kw):
    kw.setdefault("config", TINY)
    return TpuEmbedder("test-tiny", max_tokens=32, seed=3, **kw)


def mesh_embedder(dp=DP, tp=TP, **kw):
    emb = make_embedder(**kw)
    shard_embedder_mesh(emb, make_mesh(dp=dp, tp=tp))
    return emb


def manager_for(emb, dp=DP, tp=TP, **kw):
    mgr = MeshFaultManager(emb, shape=(dp, tp), **kw)
    mgr.build_ladder()
    return mgr


class _FakeXlaRuntimeError(Exception):
    pass


_FakeXlaRuntimeError.__name__ = "XlaRuntimeError"


# -- classification -----------------------------------------------------------


def test_classify_dispatch_error_sorts_kinds():
    assert classify_dispatch_error(InjectedTransientError("x")) == "transient"
    assert (
        classify_dispatch_error(InjectedPersistentError("x")) == "persistent"
    )
    # a hang surfaces transient; the watchdog note escalates it
    assert classify_dispatch_error(InjectedHangError("x")) == "transient"
    # ordinary application errors are NOT device faults
    assert classify_dispatch_error(ValueError("bad input")) is None
    assert classify_dispatch_error(RuntimeError("app bug")) is None
    # XlaRuntimeError statuses, matched by type name (no jaxlib import)
    err = _FakeXlaRuntimeError("RESOURCE_EXHAUSTED: out of memory")
    assert classify_dispatch_error(err) == "transient"
    err = _FakeXlaRuntimeError("INTERNAL: device halted")
    assert classify_dispatch_error(err) == "persistent"
    # unknown XLA status: one free retry beats losing half the mesh
    err = _FakeXlaRuntimeError("something new")
    assert classify_dispatch_error(err) == "transient"


def test_manager_classify_escalates_transient_streak():
    emb = mesh_embedder()
    mgr = manager_for(emb, transient_retries=2)
    t = InjectedTransientError("blip")
    assert mgr.classify(t) == "transient"
    assert mgr.classify(t) == "transient"
    # streak 3 > retries 2: the "transient" fault is a wedge in disguise
    assert mgr.classify(t) == "persistent"
    # a clean dispatch resets the streak
    assert mgr.classify(t) == "transient"
    mgr.note_dispatch_ok()
    assert mgr.classify(t) == "transient"
    # application errors pass through unclassified regardless of state
    assert mgr.classify(ValueError("app")) is None


def test_manager_classify_watchdog_overdue_escalates():
    mgr = manager_for(mesh_embedder())
    mgr.note_watchdog_trip()
    assert mgr.classify(InjectedHangError("wedge")) == "persistent"
    # the note is consumed — the next blip is just a blip
    assert mgr.classify(InjectedTransientError("blip")) == "transient"


# -- DEVICE_FAULT_PLAN --------------------------------------------------------


def test_device_fault_plan_seeded_is_deterministic():
    a = DeviceFaultPlan(seed=7, probabilities={"transient": 0.5})
    b = DeviceFaultPlan(seed=7, probabilities={"transient": 0.5})
    draws_a = [a.next_fault() for _ in range(64)]
    draws_b = [b.next_fault() for _ in range(64)]
    assert draws_a == draws_b
    assert a.snapshot() == b.snapshot()
    assert a.snapshot()["requests"] == 64


def test_device_fault_plan_parse_and_script():
    plan = DeviceFaultPlan.parse(
        "seed=3,hang_ms=10,script=persistent|ok|transient"
    )
    assert plan.hang_ms == 10.0
    assert plan.next_fault() == "persistent"
    assert plan.next_fault() is None
    assert plan.next_fault() == "transient"
    # healthy after script exhaustion
    assert plan.next_fault() is None
    assert plan.snapshot() == {
        "requests": 4,
        "injected": {"transient": 1, "persistent": 1},
    }
    with pytest.raises(ValueError, match="unknown key"):
        DeviceFaultPlan.parse("sneed=3")
    with pytest.raises(ValueError, match="unknown fault"):
        DeviceFaultPlan.parse("script=kaboom")
    with pytest.raises(ValueError, match="key=value"):
        DeviceFaultPlan.parse("persistent")


def test_maybe_inject_raises_per_script():
    mgr = manager_for(
        mesh_embedder(),
        fault_plan=DeviceFaultPlan.scripted(
            ["transient", None, "hang"], hang_ms=1.0
        ),
    )
    with pytest.raises(InjectedTransientError):
        mgr.maybe_inject()
    mgr.maybe_inject()  # healthy slot
    # the hang sleeps its bounded hang_ms then raises — never blocks
    with pytest.raises(InjectedHangError):
        mgr.maybe_inject()


# -- the ladder ---------------------------------------------------------------


def test_ladder_walk_8_to_1_dp_halving_tp_preserved():
    emb = mesh_embedder(dp=8, tp=1)
    mgr = manager_for(emb, dp=8, tp=1)
    assert mgr.build_ladder() == [(8, 1), (4, 1), (2, 1), (1, 1)]
    assert mgr.current_shape == (8, 1)
    assert not mgr.degraded and not mgr.exhausted
    devices0 = list(emb.mesh.devices.reshape(-1))
    for expect in [(4, 1), (2, 1), (1, 1)]:
        assert mgr.downsize() is True
        assert mgr.current_shape == expect
        assert emb.mesh_shape == expect
        # every rung is a PREFIX submesh of the full device list
        assert (
            list(emb.mesh.devices.reshape(-1))
            == devices0[: expect[0] * expect[1]]
        )
        assert mgr.degraded
    assert mgr.exhausted
    # past the last rung: the caller's cue to flip the CPU twin
    assert mgr.downsize() is False
    snap = mgr.snapshot()
    assert snap["downsizes"] == 3
    assert snap["epoch"] == 3
    # the dropped tails accumulate as the faulted domain: 7 of 8 devices
    assert len(snap["faulted_devices"]) == 7


def test_ladder_preserves_tp():
    mgr = manager_for(mesh_embedder())
    assert mgr.build_ladder() == [(4, 2), (2, 2), (1, 2)]


def test_warm_ladder_aot_covers_every_rung():
    emb = mesh_embedder()
    mgr = manager_for(emb)
    timings = mgr.warm_ladder([(N, S)], [R])
    # 3 executables (vote1/embed/many) x 3 rungs
    assert len(timings) == 9
    assert emb.aot_mesh_shapes() == [(4, 2), (2, 2), (1, 2)]
    # the embedder exits warmed AND sharded at the full shape
    assert emb.mesh_shape == (DP, TP)
    # warm again: idempotent, nothing recompiles
    assert mgr.warm_ladder([(N, S)], [R]) == []


def test_downsized_rung_serves_warmed_zero_new_specializations():
    """The executable-table swap: post-downsize traffic on the surviving
    submesh hits the rung's precompiled executables — no compile storm."""
    emb = mesh_embedder()
    mgr = manager_for(emb)
    mgr.warm_ladder([(N, S)], [R])
    assert mgr.downsize() is True
    rng = np.random.default_rng(5)
    ids = rng.integers(3, TINY.vocab_size, (N, S)).astype(np.int32)
    mask = np.ones((N, S), np.int32)
    stats0 = emb.jit_stats()["specializations"]
    out = np.asarray(emb.consensus_confidence_tokens(ids, mask))
    assert np.all(np.isfinite(out))
    assert emb.jit_stats()["specializations"] == stats0


# -- shape-transition serialization -------------------------------------------


def test_shape_transition_waits_for_inflight_dispatch():
    """The shape gate: downsize() must drain in-flight dispatches before
    re-sharding — the batcher's executor has pipeline_depth (default 2)
    workers, so a concurrent dispatch thread can be mid-PJRT on the old
    params when the fault handler runs."""
    emb = mesh_embedder()
    mgr = manager_for(emb)
    dispatching = threading.Event()
    finish_dispatch = threading.Event()
    order = []

    def dispatch_thread():
        with mgr.dispatch_guard():
            dispatching.set()
            finish_dispatch.wait(5.0)
            order.append("dispatch")

    def downsize_thread():
        mgr.downsize()
        order.append("downsize")

    t = threading.Thread(target=dispatch_thread)
    t.start()
    assert dispatching.wait(5.0)
    w = threading.Thread(target=downsize_thread)
    w.start()
    time.sleep(0.05)
    # the re-shard is parked behind the in-flight dispatch
    assert order == []
    assert mgr.current_shape == (DP, TP)
    finish_dispatch.set()
    t.join(5.0)
    w.join(5.0)
    assert order == ["dispatch", "downsize"]
    assert mgr.current_shape == (2, 2)


def test_stale_epoch_fault_skips_ladder_step():
    """Pipelined groups faulting on the SAME dead device must cost one
    rung: a downsize carrying a pre-transition epoch stamp re-queues
    without stepping the ladder again."""
    mgr = manager_for(mesh_embedder())
    epoch0 = mgr.epoch
    assert mgr.downsize(observed_epoch=epoch0) is True
    assert mgr.current_shape == (2, 2)
    # the second in-flight group observed the same pre-downsize epoch:
    # its fault is old news — True (re-queue) but no rung spent
    assert mgr.downsize(observed_epoch=epoch0) is True
    assert mgr.current_shape == (2, 2)
    snap = mgr.snapshot()
    assert snap["downsizes"] == 1
    assert snap["epoch"] == 1


def test_concurrent_persistent_faults_downsize_once_through_batcher():
    """End to end: two pipelined dispatch groups both drawing persistent
    faults from one fault event step the ladder exactly once, and every
    re-dispatched answer still matches the fault-free run."""
    ref = make_embedder()
    emb = mesh_embedder()
    mgr = manager_for(
        emb,
        fault_plan=DeviceFaultPlan.scripted(["persistent", "persistent"]),
    )
    mgr.warm_ladder([(N, S)], [R])
    batcher = DeviceBatcher(
        emb, Metrics(), window_ms=20.0, pipeline_depth=2, meshfault=mgr
    )
    short = TEXTS[:4]

    async def run():
        # different candidate counts -> different keys -> two groups,
        # dispatched concurrently on the 2-deep pipeline; both draw a
        # persistent fault before either handler can downsize
        return await asyncio.gather(
            batcher.consensus(TEXTS), batcher.consensus(short)
        )

    (conf_a, _), (conf_b, _) = go(run())
    np.testing.assert_allclose(
        conf_a, np.asarray(ref.consensus_confidence(TEXTS)), atol=1e-5
    )
    np.testing.assert_allclose(
        conf_b, np.asarray(ref.consensus_confidence(short)), atol=1e-5
    )
    snap = mgr.snapshot()
    # ONE fault event, one rung — not one per in-flight group
    assert snap["downsizes"] == 1
    assert snap["current_shape"] == [2, 2]
    assert snap["re_dispatches"] >= 2


# -- re-dispatch through the batcher ------------------------------------------


def test_persistent_fault_downsizes_once_and_matches_clean_run():
    """The acceptance core: a persistent fault mid-dispatch costs one
    ladder rung and ZERO request errors — the re-dispatched answers are
    numerically identical to a fault-free run."""
    ref = make_embedder()
    emb = mesh_embedder()
    mgr = manager_for(
        emb, fault_plan=DeviceFaultPlan.scripted(["persistent"])
    )
    mgr.warm_ladder([(N, S)], [R])
    metrics = Metrics()
    batcher = DeviceBatcher(emb, metrics, window_ms=20.0, meshfault=mgr)

    async def run():
        return await asyncio.gather(
            batcher.consensus(TEXTS),
            batcher.consensus(list(reversed(TEXTS))),
        )

    (conf_a, tok_a), (conf_b, _) = go(run())
    np.testing.assert_allclose(
        conf_a, np.asarray(ref.consensus_confidence(TEXTS)), atol=1e-5
    )
    np.testing.assert_allclose(
        conf_b,
        np.asarray(ref.consensus_confidence(list(reversed(TEXTS)))),
        atol=1e-5,
    )
    assert tok_a == ref.token_count(TEXTS)
    snap = mgr.snapshot()
    assert snap["downsizes"] == 1
    assert snap["current_shape"] == [2, 2]
    assert snap["re_dispatches"] >= 1
    assert mgr.degraded


def test_transient_fault_retries_on_same_shape():
    ref = make_embedder()
    emb = mesh_embedder()
    mgr = manager_for(
        emb, fault_plan=DeviceFaultPlan.scripted(["transient"])
    )
    batcher = DeviceBatcher(emb, Metrics(), window_ms=10.0, meshfault=mgr)
    conf, _ = go(batcher.consensus(TEXTS))
    np.testing.assert_allclose(
        conf, np.asarray(ref.consensus_confidence(TEXTS)), atol=1e-5
    )
    snap = mgr.snapshot()
    # retried on the FULL shape: transient faults don't spend rungs
    assert snap["downsizes"] == 0
    assert snap["current_shape"] == [DP, TP]
    assert snap["re_dispatches"] >= 1


def test_redispatch_sheds_expired_deadline_as_504():
    """Re-queue is deadline-bounded: an item past its budget at re-queue
    time sheds 504 (the PR 4 contract) instead of riding the new shape."""
    emb = mesh_embedder()
    mgr = manager_for(
        emb,
        fault_plan=DeviceFaultPlan.scripted(["hang"], hang_ms=60.0),
    )
    metrics = Metrics()
    batcher = DeviceBatcher(emb, metrics, window_ms=5.0, meshfault=mgr)

    async def run():
        # 20 ms budget, 60 ms injected hang: expired by re-queue time
        token = Deadline(0.02).activate()
        try:
            with pytest.raises(DeadlineExceededError) as ei:
                await batcher.embed(["too late by redispatch"])
            assert ei.value.status() == 504
        finally:
            Deadline.deactivate(token)

    go(run())
    assert batcher.shed_deadline == 1
    assert (
        metrics.snapshot()["series"]["device:shed:deadline"]["errors"] == 1
    )


def test_redispatch_limit_is_observable_in_metrics():
    """An item failed at REDISPATCH_LIMIT must show in /metrics like the
    adjacent deadline shed — a fault loop exhausting items cannot be
    invisible."""
    emb = mesh_embedder()
    # transient_retries high enough that the streak never escalates to
    # persistent: every fault re-queues on the same shape until the
    # per-item limit trips
    mgr = manager_for(
        emb,
        transient_retries=100,
        fault_plan=DeviceFaultPlan.scripted(
            ["transient"] * (DeviceBatcher.REDISPATCH_LIMIT + 1)
        ),
    )
    metrics = Metrics()
    batcher = DeviceBatcher(emb, metrics, window_ms=5.0, meshfault=mgr)

    async def run():
        with pytest.raises(InjectedTransientError):
            await batcher.embed(["recycled until the limit"])

    go(run())
    assert batcher.shed_redispatch_limit == 1
    assert (
        metrics.snapshot()["series"]["device:shed:redispatch"]["errors"]
        == 1
    )
    assert mgr.snapshot()["re_dispatches"] == DeviceBatcher.REDISPATCH_LIMIT


def test_application_errors_keep_fail_the_group_path():
    """A non-device error must NOT touch the ladder: the group fails
    exactly as it did before the fault-domain subsystem existed."""
    emb = mesh_embedder()
    mgr = manager_for(emb)
    batcher = DeviceBatcher(emb, Metrics(), window_ms=5.0, meshfault=mgr)
    boom = ValueError("tokenizer exploded")

    def bad_dispatch(group, embedder):
        raise boom

    # instance attribute shadows the bound method the dispatch getattr
    # resolves — the injected application error, not a device fault
    batcher._dispatch_embed = bad_dispatch

    async def run():
        with pytest.raises(ValueError, match="tokenizer exploded"):
            await batcher.embed(["doomed"])

    go(run())
    assert mgr.snapshot()["downsizes"] == 0
    assert mgr.snapshot()["re_dispatches"] == 0


def test_ladder_exhaustion_flips_cpu_fallback():
    """Satellite precedence, bottom half: when every rung is spent the
    batcher flips to the CPU twin — the last resort, never the first."""
    emb = mesh_embedder(dp=2, tp=1)
    fallback = make_embedder()
    mgr = manager_for(
        emb,
        dp=2,
        tp=1,
        fault_plan=DeviceFaultPlan.scripted(["persistent", "persistent"]),
    )
    batcher = DeviceBatcher(
        emb,
        Metrics(),
        window_ms=10.0,
        meshfault=mgr,
        fallback_embedder=fallback,
    )
    conf, _ = go(batcher.consensus(TEXTS))
    np.testing.assert_allclose(
        conf, np.asarray(fallback.consensus_confidence(TEXTS)), atol=1e-5
    )
    assert mgr.exhausted
    assert batcher._use_fallback is True


# -- rescale hooks ------------------------------------------------------------


def test_downsize_rescales_admission_and_batcher_capacity():
    from llm_weighted_consensus_tpu.resilience import (
        AdmissionConfig,
        AdmissionController,
    )

    emb = mesh_embedder()
    mgr = manager_for(emb)
    admission = AdmissionController(
        AdmissionConfig(max_inflight=16, adaptive=True, min_limit=2)
    )
    batcher = DeviceBatcher(
        emb, Metrics(), window_ms=5.0, max_batch=32, max_rows=64
    )
    mgr.rescale_hooks.append(admission.rescale)
    mgr.rescale_hooks.append(batcher.rescale_capacity)

    assert mgr.downsize() is True  # 4x2 -> 2x2: half the chips
    assert admission.snapshot()["mesh_scale"] == 0.5
    assert admission.limit == 8.0
    assert batcher.max_batch == 16 and batcher.max_rows == 32
    # the scaled cap sheds at half the configured in-flight bound
    admission.inflight = 8
    assert admission.try_acquire() == "inflight_limit"

    assert mgr.downsize() is True  # 2x2 -> 1x2: quarter capacity
    assert admission.snapshot()["mesh_scale"] == 0.25
    assert batcher.max_batch == 8 and batcher.max_rows == 16

    mgr.try_recover()  # full shape restores full capacity
    assert "mesh_scale" not in admission.snapshot()
    assert batcher.max_batch == 32 and batcher.max_rows == 64
    admission.inflight = 8
    assert admission.try_acquire() is None


# -- recovery -----------------------------------------------------------------


def test_try_recover_upsizes_and_matches_clean_run():
    ref = make_embedder()
    emb = mesh_embedder()
    mgr = manager_for(emb)
    mgr.warm_ladder([(N, S)], [R])
    assert mgr.downsize() is True
    epoch_down = mgr.epoch
    assert mgr.try_recover() is True
    assert mgr.current_shape == (DP, TP)
    assert emb.mesh_shape == (DP, TP)
    assert not mgr.degraded
    assert mgr.epoch == epoch_down + 1
    snap = mgr.snapshot()
    assert snap["upsizes"] == 1
    assert snap["faulted_devices"] == []
    # post-upsize numerics: identical to a never-faulted embedder
    batcher = DeviceBatcher(emb, Metrics(), window_ms=10.0, meshfault=mgr)
    conf, _ = go(batcher.consensus(TEXTS))
    np.testing.assert_allclose(
        conf, np.asarray(ref.consensus_confidence(TEXTS)), atol=1e-5
    )


def test_try_recover_holds_while_plan_still_faulty():
    emb = mesh_embedder()
    mgr = manager_for(
        emb,
        fault_plan=DeviceFaultPlan.scripted(["persistent", None]),
    )
    # downsize() consumes no plan draws — only dispatches and probes do
    assert mgr.downsize() is True
    # probe draw #1 is persistent: the mesh stays down
    assert mgr.try_recover() is False
    assert mgr.degraded
    assert mgr.snapshot()["probe_failures"] == 1
    # probe draw #2 is healthy: upsize proceeds
    assert mgr.try_recover() is True
    assert not mgr.degraded


def test_probe_fn_failure_rolls_back_upsize():
    emb = mesh_embedder()
    mgr = manager_for(emb)
    assert mgr.downsize() is True

    def bad_probe():
        raise InjectedPersistentError("still dead")

    mgr.probe_fn = bad_probe
    assert mgr.try_recover() is False
    assert mgr.degraded
    assert emb.mesh_shape == (2, 2)  # rolled back to the surviving rung
    assert mgr.snapshot()["probe_failures"] == 1


def test_probe_backoff_scales_with_failures_and_resets():
    emb = mesh_embedder()
    mgr = manager_for(
        emb,
        fault_plan=DeviceFaultPlan.scripted(
            ["persistent", "persistent", None]
        ),
    )
    assert mgr.downsize() is True  # consumes no plan draws
    assert mgr.probe_backoff_scale() == 1.0
    assert mgr.try_recover() is False  # probe draw #1 faults
    assert mgr.probe_backoff_scale() == 2.0
    assert mgr.try_recover() is False  # probe draw #2 faults
    assert mgr.probe_backoff_scale() == 4.0
    assert mgr.snapshot()["probe_backoff"] == 4.0
    assert mgr.try_recover() is True  # healthy: upsize resets backoff
    assert mgr.probe_backoff_scale() == 1.0


def test_blind_upsize_warns_once(caplog):
    """No probe_fn and no fault plan: the upsize is unvalidated, which
    deserves a loud (but one-time) warning — production wires a real
    probe_fn in serve/__main__.py."""
    import logging

    mgr = manager_for(mesh_embedder())
    assert mgr.downsize() is True
    with caplog.at_level(logging.WARNING, logger="lwc.resilience"):
        assert mgr.try_recover() is True
        assert mgr.downsize() is True
        assert mgr.try_recover() is True
    warnings = [
        r for r in caplog.records if "no probe_fn" in r.getMessage()
    ]
    assert len(warnings) == 1


def test_not_degraded_try_recover_is_noop():
    mgr = manager_for(mesh_embedder())
    assert mgr.try_recover() is False
    assert mgr.snapshot()["upsizes"] == 0


# -- identity when off --------------------------------------------------------


def test_no_manager_is_todays_behavior():
    """MESH_FAULT_ENABLED unset: the batcher has no manager, dispatch
    errors fail the group exactly as before this PR."""
    ref = make_embedder()
    emb = mesh_embedder()
    batcher = DeviceBatcher(emb, Metrics(), window_ms=10.0)
    assert batcher.meshfault is None
    conf, _ = go(batcher.consensus(TEXTS))
    np.testing.assert_allclose(
        conf, np.asarray(ref.consensus_confidence(TEXTS)), atol=1e-5
    )


def test_healthy_plan_is_identity():
    ref = make_embedder()
    emb = mesh_embedder()
    mgr = manager_for(
        emb, fault_plan=DeviceFaultPlan.scripted([None, None, None])
    )
    batcher = DeviceBatcher(emb, Metrics(), window_ms=10.0, meshfault=mgr)
    conf, _ = go(batcher.consensus(TEXTS))
    np.testing.assert_allclose(
        conf, np.asarray(ref.consensus_confidence(TEXTS)), atol=1e-5
    )
    snap = mgr.snapshot()
    assert snap["downsizes"] == 0 and snap["re_dispatches"] == 0
    assert not mgr.degraded


# -- config -------------------------------------------------------------------

MESH_ENV = {"MESH_ENABLED": "1", "MESH_SHAPE": f"{DP}x{TP}"}


def test_config_off_by_default():
    config = Config.from_env({})
    assert config.mesh_fault_enabled is False
    assert config.device_fault_plan is None
    assert config.device_fault_injection_plan() is None


def test_config_parses_and_builds_plan():
    config = Config.from_env(
        dict(
            MESH_ENV,
            MESH_FAULT_ENABLED="1",
            MESH_FAULT_TRANSIENT_RETRIES="5",
            MESH_FAULT_PROBE_MILLIS="250",
            DEVICE_FAULT_PLAN="seed=9,transient=0.1",
        )
    )
    assert config.mesh_fault_enabled is True
    assert config.mesh_fault_transient_retries == 5
    assert config.mesh_fault_probe_millis == 250.0
    plan = config.device_fault_injection_plan()
    assert isinstance(plan, DeviceFaultPlan)
    assert plan.seed == 9


def test_config_validation_refuses_nonsense():
    with pytest.raises(ValueError, match="needs MESH_ENABLED"):
        Config.from_env({"MESH_FAULT_ENABLED": "1"})
    with pytest.raises(ValueError, match="MESH_FAULT_ENABLED is not"):
        Config.from_env(dict(MESH_ENV, DEVICE_FAULT_PLAN="seed=1"))
    with pytest.raises(ValueError, match="must be >= 0"):
        Config.from_env(
            dict(
                MESH_ENV,
                MESH_FAULT_ENABLED="1",
                MESH_FAULT_PROBE_MILLIS="-1",
            )
        )


def test_config_cpu_fallback_precedence():
    """Satellite 1: in mesh mode the CPU twin without the ladder is
    refused at startup — it must be the post-exhaustion last resort."""
    with pytest.raises(ValueError, match="last resort AFTER"):
        Config.from_env(
            dict(
                MESH_ENV,
                DEVICE_WATCHDOG_MILLIS="1000",
                DEVICE_WATCHDOG_CPU_FALLBACK="1",
            )
        )
    # with the ladder armed the combo is the documented precedence chain
    config = Config.from_env(
        dict(
            MESH_ENV,
            MESH_FAULT_ENABLED="1",
            DEVICE_WATCHDOG_MILLIS="1000",
            DEVICE_WATCHDOG_CPU_FALLBACK="1",
        )
    )
    assert config.device_watchdog_cpu_fallback is True
    # and off-mesh the twin needs no ladder (single-device semantics)
    config = Config.from_env(
        {
            "DEVICE_WATCHDOG_MILLIS": "1000",
            "DEVICE_WATCHDOG_CPU_FALLBACK": "1",
        }
    )
    assert config.mesh_fault_enabled is False


# -- the acceptance drill -----------------------------------------------------


def test_acceptance_drill_fault_mid_traffic_readyz_and_recovery():
    """The ISSUE acceptance, end to end on the simulated mesh: seeded
    persistent fault mid-traffic → exactly one downsize, zero request
    errors, answers ≡ fault-free, /readyz flies degraded_mesh while
    down and drops it after the recovery upsize."""
    aiohttp = pytest.importorskip("aiohttp")  # noqa: F841
    from llm_weighted_consensus_tpu.serve.lifecycle import (
        Lifecycle,
        health_handlers,
    )
    from llm_weighted_consensus_tpu.utils import jsonutil

    ref = make_embedder()
    emb = mesh_embedder()
    mgr = manager_for(
        emb,
        fault_plan=DeviceFaultPlan.parse("script=ok|persistent"),
    )
    mgr.warm_ladder([(N, S)], [R])
    metrics = Metrics()
    batcher = DeviceBatcher(emb, metrics, window_ms=15.0, meshfault=mgr)
    metrics.register_provider("meshfault", mgr.snapshot)
    lifecycle = Lifecycle(batcher=batcher, meshfault=mgr)
    _livez, readyz = health_handlers(lifecycle)

    def ready_body():
        resp = go(readyz(None))
        assert resp.status == 200
        return jsonutil.loads(resp.text)

    # healthy before the drill
    assert ready_body() == {"ready": True}

    async def traffic():
        return await asyncio.gather(
            batcher.consensus(TEXTS),  # dispatch 1: ok
            batcher.consensus(TEXTS),  # coalesces into dispatch 1
        )

    first = go(traffic())
    # dispatch 2 faults persistent mid-traffic and re-dispatches
    second = go(traffic())
    expect = np.asarray(ref.consensus_confidence(TEXTS))
    for conf, _ in first + second:
        np.testing.assert_allclose(conf, expect, atol=1e-5)

    snap = metrics.snapshot()["meshfault"]
    assert snap["downsizes"] == 1  # exactly one
    assert snap["current_shape"] == [2, 2]
    assert snap["fault_plan"]["injected"] == {"persistent": 1}
    body = ready_body()  # degraded but READY: still 200
    assert body["degraded_mesh"] is True
    assert body["mesh_shape"] == [2, 2]

    # recovery: the prober re-validates the full mesh and upsizes
    assert mgr.try_recover() is True
    assert ready_body() == {"ready": True}
    post, _ = go(batcher.consensus(TEXTS))
    np.testing.assert_allclose(post, expect, atol=1e-5)
    assert metrics.snapshot()["meshfault"]["upsizes"] == 1
