"""Mesh serving path (ISSUE PR 9): dp×tp first-class mesh mode under
the gateway.

What this pins, on the tier-1 8-virtual-device CPU mesh:

* batcher end-to-end parity — the dp-sharded embedder returns the same
  results as the single-device embedder through the same DeviceBatcher,
  on the padded and int8-pallas-interpret paths;
* per-(mesh-shape, bucket) AOT — ``aot_warmup`` on a mesh embedder
  compiles namespaced executables and post-warmup mesh traffic creates
  ZERO new jit specializations (the ISSUE acceptance);
* the PR 4/5 per-item contracts carry through the mesh path unchanged:
  deadline shed is still a 504 before dispatch, the watchdog brackets
  every dispatch, drain still waits for queued work;
* config: ``MESH_ENABLED`` unset is today's single-device behavior, and
  the knob validation refuses half-configured setups and the removed names.

Jit caches are process-global and SHARED across embedder instances, so
every zero-growth assertion is a delta whose reference dispatches all
run BEFORE the first snapshot (the test_aot.py discipline).
"""

import asyncio

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from llm_weighted_consensus_tpu.models import configs
from llm_weighted_consensus_tpu.models.embedder import TpuEmbedder
from llm_weighted_consensus_tpu.parallel.mesh import make_mesh
from llm_weighted_consensus_tpu.parallel.sharding import shard_embedder_mesh
from llm_weighted_consensus_tpu.serve.batcher import DeviceBatcher
from llm_weighted_consensus_tpu.serve.config import Config
from llm_weighted_consensus_tpu.serve.metrics import Metrics

TINY = configs.TEST_TINY
DP, TP = 4, 2
N, S, R = 4, 16, 2


def go(coro):
    return asyncio.new_event_loop().run_until_complete(coro)


def make_embedder(**kw):
    kw.setdefault("config", TINY)
    return TpuEmbedder("test-tiny", max_tokens=32, seed=3, **kw)


def mesh_embedder(dp=DP, tp=TP, **kw):
    emb = make_embedder(**kw)
    shard_embedder_mesh(emb, make_mesh(dp=dp, tp=tp))
    return emb


TEXTS = [f"candidate number {i % 3} for the mesh" for i in range(6)]


# -- batcher e2e parity vs single-device --------------------------------------


def test_mesh_batcher_padded_matches_single_device():
    """Concurrent embed + consensus through the batcher on the dp-sharded
    embedder ≡ the single-device embedder's direct answers."""
    ref = make_embedder()
    emb = mesh_embedder()
    metrics = Metrics()
    batcher = DeviceBatcher(emb, metrics, window_ms=20.0)

    async def run():
        return await asyncio.gather(
            batcher.consensus(TEXTS),
            batcher.consensus(list(reversed(TEXTS))),
            batcher.embed(TEXTS[:3]),
        )

    (conf_a, tok_a), (conf_b, _), (vecs, _) = go(run())
    np.testing.assert_allclose(
        conf_a, np.asarray(ref.consensus_confidence(TEXTS)), atol=1e-5
    )
    np.testing.assert_allclose(
        conf_b,
        np.asarray(ref.consensus_confidence(list(reversed(TEXTS)))),
        atol=1e-5,
    )
    np.testing.assert_allclose(
        vecs, ref.embed_texts(TEXTS[:3]), atol=1e-5
    )
    assert tok_a == ref.token_count(TEXTS)
    # same-shape consensus requests still coalesce into one dispatch
    assert metrics.snapshot()["series"]["device:batch:consensus"][
        "count"
    ] == 1


def test_mesh_batcher_int8_pallas_matches_single_device():
    """The int8-pallas interpret-mode kernels run under GSPMD exactly as
    on one device: batcher answers agree with the single-device int8
    embedder (same quantized params, seed-identical)."""
    ref = make_embedder(quantize="int8-pallas")
    emb = mesh_embedder(quantize="int8-pallas")
    batcher = DeviceBatcher(emb, Metrics(), window_ms=20.0)

    async def run():
        return await asyncio.gather(
            batcher.consensus(TEXTS), batcher.embed(TEXTS[:2])
        )

    (conf, _), (vecs, _) = go(run())
    np.testing.assert_allclose(
        conf, np.asarray(ref.consensus_confidence(TEXTS)), atol=1e-5
    )
    np.testing.assert_allclose(vecs, ref.embed_texts(TEXTS[:2]), atol=1e-5)


# -- per-(mesh-shape, bucket) AOT ---------------------------------------------


def test_mesh_aot_zero_specializations_under_mixed_load():
    """The ISSUE acceptance: mesh-sharded ``aot_warmup`` precompiles
    every (mesh-shape, bucket) executable and post-warmup mesh traffic
    creates zero jit-specialization growth."""
    emb = mesh_embedder()
    timings = emb.aot_warmup([(N, S)], r_buckets=[R])
    # consensus + embed + grouped, one executable each
    assert len(timings) == 3, [label for label, _ in timings]
    # keys are namespaced per mesh shape — a 2x4 mesh could never
    # collide with these executables
    assert set(emb._aot) == {
        ("mesh", DP, TP, "vote1", N, S),
        ("mesh", DP, TP, "embed", 16, S),
        ("mesh", DP, TP, "many", R, N, S),
    }

    rng = np.random.default_rng(12)
    ids = rng.integers(3, TINY.vocab_size, (N, S)).astype(np.int32)
    mask = np.ones((N, S), np.int32)

    stats0 = emb.jit_stats()["specializations"]
    out = [
        np.asarray(emb.consensus_confidence_tokens(ids, mask)),
        np.asarray(
            emb.consensus_confidence_tokens(ids, mask, temperature=0.2)
        ),
        np.asarray(emb.embed_tokens(ids, mask)),
        np.asarray(
            emb.consensus_confidence_tokens_many(
                np.stack([ids] * R), np.stack([mask] * R)
            )
        ),
    ]
    assert all(np.all(np.isfinite(o)) for o in out)
    assert emb.jit_stats()["specializations"] == stats0


def test_mesh_aot_warmup_allowed_legacy_hooks_still_refused():
    """Mesh mode takes the AOT branch; a one-device embedder whose
    batches are padded to a hand-set multiple still raises (a plain-aval
    executable was not lowered for the padded rows)."""
    emb = mesh_embedder()
    assert emb._aot_ready()
    padded = make_embedder()
    padded.batch_multiple = 2
    with pytest.raises(RuntimeError, match="mesh"):
        padded.aot_warmup([(N, S)])


# -- PR 4/5 per-item contracts through the mesh path --------------------------


def test_mesh_deadline_shed_before_dispatch_is_504():
    from llm_weighted_consensus_tpu.errors import DeadlineExceededError
    from llm_weighted_consensus_tpu.resilience import Deadline

    metrics = Metrics()
    batcher = DeviceBatcher(mesh_embedder(), metrics, window_ms=20.0)

    async def run():
        token = Deadline(0.0005).activate()
        try:
            with pytest.raises(DeadlineExceededError) as ei:
                await batcher.embed(["too late"])
            assert ei.value.status() == 504
        finally:
            Deadline.deactivate(token)
        emb, tokens = await batcher.embed(["in time"])
        assert emb.shape[0] == 1 and tokens > 0

    go(run())
    assert batcher.shed_deadline == 1
    assert metrics.snapshot()["series"]["device:shed:deadline"][
        "errors"
    ] == 1


def test_mesh_watchdog_brackets_dispatches():
    from llm_weighted_consensus_tpu.resilience import DeviceWatchdog

    wd = DeviceWatchdog(60_000.0)  # generous: must never trip here
    batcher = DeviceBatcher(
        mesh_embedder(), Metrics(), window_ms=5.0, watchdog=wd
    )

    async def run():
        await asyncio.gather(batcher.embed(["one"]), batcher.embed(["two"]))

    go(run())
    assert wd.dispatches >= 1
    assert wd.snapshot()["active_dispatches"] == 0
    assert wd.healthy() is True


def test_mesh_drain_waits_for_queued_work():
    batcher = DeviceBatcher(mesh_embedder(), Metrics(), window_ms=10.0)

    async def run():
        assert batcher.idle()
        t = asyncio.ensure_future(batcher.embed(["queued"]))
        await asyncio.sleep(0)
        assert not batcher.idle()
        assert await batcher.drain(5.0) is True
        assert batcher.idle()
        emb, _ = await t
        assert emb.shape[0] == 1

    go(run())


# -- config: off by default, loud on misconfiguration -------------------------


def test_mesh_config_off_by_default():
    config = Config.from_env({})
    assert config.mesh_enabled is False
    assert config.mesh_shape is None
    # and a fresh embedder is the single-device path: no mesh state, no
    # key namespacing
    emb = make_embedder()
    assert emb.mesh_mode is False
    assert emb._aot_key(("vote1", N, S)) == ("vote1", N, S)


def test_mesh_config_parses_and_validates():
    config = Config.from_env(
        {"MESH_ENABLED": "1", "MESH_SHAPE": "4x2"}
    )
    assert config.mesh_enabled is True
    assert config.mesh_shape == (4, 2)
    with pytest.raises(ValueError, match="MESH_ENABLED is not"):
        Config.from_env({"MESH_SHAPE": "4x2"})
    with pytest.raises(ValueError, match="DPxTP"):
        Config.from_env({"MESH_ENABLED": "1", "MESH_SHAPE": "4x0"})


@pytest.mark.parametrize("name", ["MESH_DP", "MESH_TP", "MESH_SP"])
def test_removed_mesh_names_are_refused_with_the_replacement(name):
    """A deployment that still sets a hook-path name must not fall
    silently to one device, with or without mesh mode beside it."""
    for env in ({name: "2"}, {name: "2", "MESH_ENABLED": "1"}):
        with pytest.raises(ValueError) as err:
            Config.from_env(env)
        assert name in str(err.value)
        assert "MESH_ENABLED=1 MESH_SHAPE=DPxTP[xSP]" in str(err.value)


def test_build_embedder_mesh_enabled_round_trip():
    """serve wiring end-to-end: MESH_ENABLED + MESH_SHAPE builds the
    sharded embedder, registers its mesh, and serves."""
    from llm_weighted_consensus_tpu.serve.__main__ import build_embedder

    config = Config.from_env(
        {
            "EMBEDDER_MODEL": "test-tiny",
            "EMBEDDER_MAX_TOKENS": "64",
            "MESH_ENABLED": "1",
            "MESH_SHAPE": f"{DP}x{TP}",
        }
    )
    embedder = build_embedder(config)
    assert embedder.mesh_mode is True
    assert embedder.mesh_shape == (DP, TP)
    assert dict(embedder.mesh.shape) == {"dp": DP, "tp": TP}
    out = embedder.embed_texts(["mesh round trip"])
    np.testing.assert_allclose(np.linalg.norm(out, axis=1), 1.0, atol=1e-5)


def test_mesh_mode_pins_kernel_choices_gspmd_cannot_partition():
    """A Mosaic kernel cannot ride jit's automatic partitioning (found on
    the first four-chip run; the CPU mesh never takes these branches):
    under a mesh the two AUTO choices resolve to their XLA twins, while a
    pinned kernel mode stays as named and fails at compile on a TPU."""
    emb = mesh_embedder(quantize="int8")
    assert emb.config.attention_impl == "einsum"
    assert emb.config.quantize == "int8-xla"
    assert emb._ring_config is None
    pinned = mesh_embedder(quantize="int8-pallas")
    assert pinned.config.quantize == "int8-pallas"
    # the single-device embedder keeps choosing by platform
    assert make_embedder(quantize="int8").config.attention_impl == "auto"
