"""Mesh scale-out on the virtual 8-device CPU mesh: collectives parity,
TP-sharded forward equivalence, training steps, batch re-score, graft
entry points (SURVEY §4: multi-device without a cluster)."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp

from llm_weighted_consensus_tpu.models import bert
from llm_weighted_consensus_tpu.models.configs import TEST_TINY
from llm_weighted_consensus_tpu.models.embedder import TpuEmbedder
from llm_weighted_consensus_tpu.ops import consensus, similarity
from llm_weighted_consensus_tpu.parallel import (
    batch as batch_mod,
    collectives,
    make_mesh,
    sharding,
)

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs the 8-device CPU mesh"
)


@pytest.fixture(scope="module")
def mesh():
    return make_mesh(dp=4, tp=2)


@pytest.fixture(scope="module")
def dp_mesh():
    return make_mesh(dp=8, tp=1)


def test_sharded_cosine_vote_matches_single_device(dp_mesh):
    rng = np.random.default_rng(0)
    emb = rng.normal(size=(16, 32)).astype(np.float32)
    dist = np.asarray(collectives.sharded_cosine_vote(jnp.asarray(emb), dp_mesh))
    single = np.asarray(similarity.cosine_consensus_vote(jnp.asarray(emb)))
    np.testing.assert_allclose(dist, single, atol=1e-5)


def test_sharded_cosine_vote_ragged_n(dp_mesh):
    # N not divisible by dp: padding must not perturb the result
    rng = np.random.default_rng(1)
    emb = rng.normal(size=(13, 16)).astype(np.float32)
    dist = np.asarray(collectives.sharded_cosine_vote(jnp.asarray(emb), dp_mesh))
    single = np.asarray(similarity.cosine_consensus_vote(jnp.asarray(emb)))
    np.testing.assert_allclose(dist, single, atol=1e-5)
    assert dist.shape == (13,)


def test_sharded_tally_matches_single_device(dp_mesh):
    rng = np.random.default_rng(2)
    v = rng.random((24, 5)).astype(np.float32)
    v /= v.sum(axis=1, keepdims=True)
    w = rng.uniform(0.5, 2.0, 24).astype(np.float32)
    dist = np.asarray(collectives.sharded_tally(jnp.asarray(v), jnp.asarray(w), dp_mesh))
    _, single = consensus.tally(jnp.asarray(v), jnp.asarray(w))
    np.testing.assert_allclose(dist, np.asarray(single), atol=1e-5)


def test_tp_sharded_forward_matches_replicated(mesh):
    params = bert.init_params(jax.random.PRNGKey(0), TEST_TINY)
    rng = np.random.default_rng(3)
    ids = jnp.asarray(rng.integers(3, TEST_TINY.vocab_size, (4, 16)), jnp.int32)
    mask = jnp.ones((4, 16), jnp.int32)
    base = np.asarray(bert.embed(params, ids, mask, TEST_TINY))
    sharded = sharding.shard_bert_params(params, mesh, tp=True)
    ids_s = jax.device_put(ids, sharding.batch_sharding(mesh))
    mask_s = jax.device_put(mask, sharding.batch_sharding(mesh))
    out = np.asarray(bert.embed(sharded, ids_s, mask_s, TEST_TINY))
    np.testing.assert_allclose(out, base, atol=1e-5)


def test_partition_rules_equal_legacy_template():
    """The rule table IS the spec template: matching the rules against a
    real param tree reproduces bert_param_specs leaf-for-leaf (plain and
    int8), so the audit-friendly dual can never drift from the layout
    the serving path actually uses."""
    from llm_weighted_consensus_tpu.models.quant import quantize_bert_params

    params = bert.init_params(jax.random.PRNGKey(0), TEST_TINY)
    for quantized in (False, True):
        tree = quantize_bert_params(params) if quantized else params
        got = sharding.match_partition_rules(
            sharding.bert_partition_rules(quantized=quantized), tree
        )
        want = sharding.bert_param_specs(quantized=quantized)
        got_leaves = dict(sharding.tree_path_leaves(got))
        want_leaves = dict(sharding.tree_path_leaves(want))
        assert got_leaves == want_leaves, quantized


@pytest.mark.parametrize("arch", ["bert", "deberta"])
@pytest.mark.parametrize("quantized", [False, True])
def test_partition_rules_cover_every_leaf_exactly_once(arch, quantized):
    """The JXA006 contract at the unit level: every leaf of every
    audited tree matches exactly one rule and no rule is dead."""
    from llm_weighted_consensus_tpu.models import deberta
    from llm_weighted_consensus_tpu.models.quant import (
        quantize_bert_params,
        quantize_deberta_params,
    )
    from llm_weighted_consensus_tpu.models.reranker import RM_PRESETS

    rng = jax.random.PRNGKey(0)
    if arch == "bert":
        init = lambda: bert.init_params(rng, TEST_TINY)
        quant = quantize_bert_params
    else:
        init = lambda: deberta.init_params(
            rng, RM_PRESETS["deberta-test-tiny"]
        )
        quant = quantize_deberta_params
    tree = jax.eval_shape(lambda: quant(init()) if quantized else init())
    rules = sharding.partition_rules_for(arch, quantized=quantized)
    leaf_matches, rule_counts = sharding.match_report(rules, tree)
    assert all(len(hits) == 1 for hits in leaf_matches.values()), {
        p: h for p, h in leaf_matches.items() if len(h) != 1
    }
    assert all(count >= 1 for count in rule_counts.values()), rule_counts


def test_match_partition_rules_raises_on_uncovered_leaf():
    rules = (("only_a", r"a", sharding.P(None)),)
    with pytest.raises(ValueError, match="no partition rule"):
        sharding.match_partition_rules(
            rules, {"a": jnp.zeros(2), "b": jnp.zeros(2)}
        )


def test_shard_by_rules_places_tp_layout(mesh):
    """shard_by_rules puts column kernels on the tp axis and strips tp
    when asked — and the placed tree still runs the forward."""
    params = bert.init_params(jax.random.PRNGKey(0), TEST_TINY)
    rules = sharding.bert_partition_rules()
    placed = sharding.shard_by_rules(params, mesh, rules)
    spec = placed["layers"]["attn_q"]["kernel"].sharding.spec
    assert "tp" in tuple(spec)
    off = sharding.shard_by_rules(params, mesh, rules, tp=False)
    spec_off = off["layers"]["attn_q"]["kernel"].sharding.spec
    assert "tp" not in tuple(spec_off)


def test_shard_embedder_same_results(dp_mesh):
    emb = TpuEmbedder("test-tiny", config=TEST_TINY, max_tokens=32, seed=1)
    texts = [f"text number {i}" for i in range(8)]
    base = emb.embed_texts(texts)
    sharding.shard_embedder_mesh(emb, dp_mesh)
    out = emb.embed_texts(texts)
    np.testing.assert_allclose(out, base, atol=1e-5)


def test_rescore_batch_mesh_matches_local(dp_mesh):
    rng = np.random.default_rng(4)
    b, m, n = 19, 4, 6  # ragged batch
    v = rng.random((b, m, n)).astype(np.float32)
    v /= v.sum(axis=2, keepdims=True)
    w = np.ones((b, m), dtype=np.float32)
    _, conf_mesh = batch_mod.rescore_batch(v, w, mesh=dp_mesh)
    _, conf_local = batch_mod.rescore_batch(v, w)
    np.testing.assert_allclose(
        np.asarray(conf_mesh), np.asarray(conf_local), atol=1e-6
    )
    assert conf_mesh.shape == (b, n)


def test_rescore_batch_arbitrary_axis_names():
    """rescore_batch shards over EVERY axis of any mesh — the sp-serving
    mesh ("dp", "sp") included, so a service on an sp-bearing mesh
    re-scores sharded (ADVICE r2: such a mesh used to silently run
    unsharded)."""
    from llm_weighted_consensus_tpu.parallel.mesh import make_mesh

    sp_mesh = make_mesh(dp=2, tp=4, names=("dp", "sp"))
    rng = np.random.default_rng(9)
    b, m, n = 11, 3, 4
    v = rng.random((b, m, n)).astype(np.float32)
    v /= v.sum(axis=2, keepdims=True)
    w = np.ones((b, m), dtype=np.float32)
    _, conf_mesh = batch_mod.rescore_batch(v, w, mesh=sp_mesh)
    _, conf_local = batch_mod.rescore_batch(v, w)
    np.testing.assert_allclose(
        np.asarray(conf_mesh), np.asarray(conf_local), atol=1e-6
    )


def test_contrastive_training_reduces_loss(dp_mesh):
    from llm_weighted_consensus_tpu import train

    config = TEST_TINY
    params = bert.init_params(jax.random.PRNGKey(0), config)
    params = sharding.shard_bert_params(params, dp_mesh, tp=False)
    optimizer = train.make_optimizer(lr=1e-3)
    opt_state = optimizer.init(params)
    rng = np.random.default_rng(5)
    b, s = 8, 16
    bs = sharding.batch_sharding(dp_mesh)
    q = jax.device_put(
        jnp.asarray(rng.integers(3, config.vocab_size, (b, s)), jnp.int32), bs
    )
    p = jax.device_put(
        jnp.asarray(rng.integers(3, config.vocab_size, (b, s)), jnp.int32), bs
    )
    ones = jax.device_put(jnp.ones((b, s), jnp.int32), bs)
    losses = []
    for _ in range(5):
        params, opt_state, loss = train.contrastive_train_step(
            params, opt_state, q, ones, p, ones, config, optimizer
        )
        losses.append(float(loss))
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0]


def test_reward_training_reduces_loss():
    from llm_weighted_consensus_tpu import train
    from llm_weighted_consensus_tpu.models import deberta
    from llm_weighted_consensus_tpu.models.configs import DEBERTA_TEST_TINY

    config = DEBERTA_TEST_TINY
    params = deberta.init_params(jax.random.PRNGKey(1), config)
    optimizer = train.make_optimizer(lr=1e-3)
    opt_state = optimizer.init(params)
    rng = np.random.default_rng(6)
    chosen = jnp.asarray(rng.integers(1, config.vocab_size, (4, 16)), jnp.int32)
    rejected = jnp.asarray(rng.integers(1, config.vocab_size, (4, 16)), jnp.int32)
    ones = jnp.ones((4, 16), jnp.int32)
    losses = []
    for _ in range(5):
        params, opt_state, loss = train.reward_train_step(
            params, opt_state, chosen, ones, rejected, ones, config, optimizer
        )
        losses.append(float(loss))
    assert losses[-1] < losses[0]


def test_checkpoint_roundtrip(tmp_path):
    from llm_weighted_consensus_tpu import train

    params = bert.init_params(jax.random.PRNGKey(2), TEST_TINY)
    path = str(tmp_path / "ckpt")
    train.save_checkpoint(path, params)
    restored = train.load_checkpoint(path, like=params)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a), np.asarray(b)),
        params,
        restored,
    )


def test_graft_entry_points():
    import __graft_entry__ as ge

    fn, args = ge.entry()
    out = jax.jit(fn)(*args)
    assert out.shape == (8,)
    assert float(jnp.sum(out)) == pytest.approx(1.0, abs=1e-5)
    ge.dryrun_multichip(8)


@pytest.mark.parametrize("n", [16, 32])
def test_graft_dryrun_subprocess_fallback(n):
    """n_devices above the live device count must run in a virtual-CPU
    subprocess (the chip machine has one chip, or four).  Both
    sizes run the FULL dryrun — dp×tp training step, collective consensus,
    rescore shard shapes, ring parity, tp-locality — so nothing bakes in
    the suite's n=8 (VERDICT r4 next-5)."""
    import __graft_entry__ as ge

    assert len(jax.devices()) < n
    ge.dryrun_multichip(n)


def test_multihost_flag_off_is_noop(monkeypatch):
    from llm_weighted_consensus_tpu.parallel import dist

    called = []
    monkeypatch.setattr(
        jax.distributed, "initialize", lambda **kw: called.append(kw)
    )
    assert dist.maybe_initialize_distributed({}) is False
    assert dist.maybe_initialize_distributed({"MULTIHOST": "0"}) is False
    assert called == []


def test_multihost_flag_parses_env(monkeypatch):
    from llm_weighted_consensus_tpu.parallel import dist

    called = []
    monkeypatch.setattr(
        jax.distributed, "initialize", lambda **kw: called.append(kw)
    )
    env = {
        "MULTIHOST": "1",
        "COORDINATOR_ADDRESS": "10.0.0.1:8476",
        "NUM_PROCESSES": "2",
        "PROCESS_ID": "1",
    }
    assert dist.maybe_initialize_distributed(env) is True
    assert called == [
        {
            "coordinator_address": "10.0.0.1:8476",
            "num_processes": 2,
            "process_id": 1,
        }
    ]
    # autodetection path: flag alone passes no kwargs
    assert dist.maybe_initialize_distributed({"MULTIHOST": "true"}) is True
    assert called[-1] == {}


def test_force_cpu_env_pins_cpu_and_device_count():
    """parallel.dist.force_cpu_env: pins JAX_PLATFORMS=cpu and rewrites
    the device count while preserving unrelated XLA flags."""
    from llm_weighted_consensus_tpu.parallel.dist import force_cpu_env

    env = {
        "JAX_PLATFORMS": "tpu",
        "XLA_FLAGS": "--xla_foo=1 --xla_force_host_platform_device_count=3",
        "OTHER": "kept",
    }
    out = force_cpu_env(env, 8)
    assert out is env  # mutate+return contract
    assert out["JAX_PLATFORMS"] == "cpu"
    assert out["OTHER"] == "kept"
    assert out["XLA_FLAGS"].split() == [
        "--xla_foo=1", "--xla_force_host_platform_device_count=8"
    ]



def test_train_resume_equivalence(tmp_path):
    """Checkpoint/resume depth (SURVEY §5): an interrupted contrastive run
    resumed from the FULL train state (params + adam moments + step)
    continues with the same losses as the uninterrupted run — params-only
    resume would reset the moments and diverge."""
    from llm_weighted_consensus_tpu import train

    config = TEST_TINY
    optimizer = train.make_optimizer(lr=1e-3)
    rng = np.random.default_rng(7)
    b, s = 4, 16
    batches = [
        (
            jnp.asarray(rng.integers(3, config.vocab_size, (b, s)), jnp.int32),
            jnp.asarray(rng.integers(3, config.vocab_size, (b, s)), jnp.int32),
        )
        for _ in range(5)
    ]
    ones = jnp.ones((b, s), jnp.int32)

    def run(params, opt_state, batch_list):
        losses = []
        for q, p in batch_list:
            params, opt_state, loss = train.contrastive_train_step(
                params, opt_state, q, ones, p, ones, config, optimizer
            )
            losses.append(float(loss))
        return params, opt_state, losses

    # uninterrupted: 5 steps straight through
    params0 = bert.init_params(jax.random.PRNGKey(3), config)
    _, _, straight = run(params0, optimizer.init(params0), batches)

    # interrupted: 3 steps, full-state checkpoint, fresh process-analog
    # restore (like-trees rebuilt from scratch), 2 more steps
    params0 = bert.init_params(jax.random.PRNGKey(3), config)
    params_a, opt_a, first3 = run(params0, optimizer.init(params0), batches[:3])
    path = str(tmp_path / "train_ckpt")
    train.save_train_state(path, params_a, opt_a, step=3)

    like_params = bert.init_params(jax.random.PRNGKey(9), config)  # other seed
    like_opt = optimizer.init(like_params)
    params_b, opt_b, step = train.load_train_state(path, like_params, like_opt)
    assert step == 3
    _, _, last2 = run(params_b, opt_b, batches[3:])

    np.testing.assert_allclose(first3 + last2, straight, rtol=1e-5)
