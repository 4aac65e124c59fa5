"""Each plain reference against the program's own forward in float32 on the
CPU, at a size a test run can hold; and the control: the same reference with
its matrix products in int8 has to read far above what the sound path reads,
or the check could not tell one precision from another."""

import dataclasses

import numpy as np
import pytest

import checkpoints
from checks import consensus_logit
from generators import consensus as gen
from references import bert_cls_cosine, deberta_v3_reward

BERT = {
    "vocab_size": 512, "hidden_size": 64, "num_hidden_layers": 2,
    "num_attention_heads": 4, "intermediate_size": 128,
    "max_position_embeddings": 64, "type_vocab_size": 2, "max_tokens": 64,
}
BERT_TOK = {"pad": 0, "unk": 1, "cls": 2, "sep": 3, "first_word": 4}
DEBERTA = {
    "vocab_size": 512, "hidden_size": 64, "num_hidden_layers": 2,
    "num_attention_heads": 4, "intermediate_size": 128,
    "max_position_embeddings": 64, "max_relative_positions": -1,
    "position_buckets": 8, "max_tokens": 64,
}
DEBERTA_TOK = {"pad": 0, "cls": 1, "sep": 2, "unk": 3, "first_word": 4}
MIX = {
    "loop": "open", "rate": 4.0, "n": {"values": [8]},
    "words": {"kind": "fixed", "value": 40}, "changed": 0.1,
}


def requests(seed, scorer="cosine"):
    mix = dict(MIX, scorer=scorer, prompt_words=6)
    return gen.generate(mix, seed, 1.0, 508)


def program_bert_logits(state, ids, mask):
    import jax.numpy as jnp

    from llm_weighted_consensus_tpu.models import bert
    from llm_weighted_consensus_tpu.models.configs import TEST_TINY

    f32 = {k: np.asarray(v).astype(np.float32) for k, v in state.items()}
    params = bert.from_hf_weights(f32, TEST_TINY, dtype=jnp.float32)
    emb = np.asarray(
        bert.embed(params, jnp.asarray(ids), jnp.asarray(mask), TEST_TINY), np.float64
    )
    return bert_cls_cosine.vote_logits(emb)


@pytest.mark.parametrize("seed", [1, 2**31 + 3])
def test_bert_reference_is_the_programs_forward_in_float32(seed):
    state = checkpoints.make_state("bert", BERT, seed)
    weights = bert_cls_cosine.load(state, BERT)
    for req in requests(seed):
        ids, mask = bert_cls_cosine.inputs(req, BERT, BERT_TOK)
        want = bert_cls_cosine.logits(weights, BERT, ids, mask)
        got = program_bert_logits(state, ids, mask)
        assert np.abs(got - want).max() < 2e-6


def program_rewards(state, cfg, ids, mask):
    import jax.numpy as jnp

    from llm_weighted_consensus_tpu.models import deberta
    from llm_weighted_consensus_tpu.models.configs import DEBERTA_TEST_TINY
    from llm_weighted_consensus_tpu.models.reranker import _strip_deberta_prefix

    pcfg = dataclasses.replace(
        DEBERTA_TEST_TINY,
        position_buckets=cfg["position_buckets"],
        max_relative_positions=cfg["max_position_embeddings"],
    )
    f32 = _strip_deberta_prefix(
        {k: np.asarray(v).astype(np.float32) for k, v in state.items()}
    )
    params = deberta.from_hf_weights(f32, pcfg, dtype=jnp.float32)
    return np.asarray(
        deberta.reward(params, jnp.asarray(ids), jnp.asarray(mask), pcfg), np.float64
    )


@pytest.mark.parametrize("buckets", [8, 0])
def test_deberta_reference_is_the_programs_forward_in_float32(buckets):
    cfg = dict(DEBERTA, position_buckets=buckets)
    if buckets == 0:
        cfg["max_relative_positions"] = 64
    state = checkpoints.make_state("deberta-v2", cfg, 5)
    weights = deberta_v3_reward.load(state, cfg)
    for req in requests(5, scorer="rm"):
        ids, mask = deberta_v3_reward.inputs(req, cfg, DEBERTA_TOK)
        want = deberta_v3_reward.logits(weights, cfg, ids, mask)
        got = program_rewards(state, cfg, ids, mask)
        assert np.abs(got - want).max() < 2e-6


def test_log_buckets_at_the_published_sizes():
    """Exact inside +-128, log-spaced beyond, 511 lands in the last row."""
    cfg = {"position_buckets": 256, "max_relative_positions": -1,
           "max_position_embeddings": 512}
    idx = deberta_v3_reward.delta(512, cfg)
    assert idx[0, 0] == 256 and idx[100, 0] == 356 and idx[0, 100] == 156
    assert idx[511, 0] == 511 and idx[0, 511] == 1
    assert (np.diff(idx[:, 0]) >= 0).all()


WIDER = dict(BERT, hidden_size=128, num_hidden_layers=4, intermediate_size=256)


def _kept(logit, temperature):
    """An answer as the load generator keeps it for this check."""
    return {"confidence": _confidence(logit, temperature)}


def _confidence(logit, temperature):
    z = (logit - logit.max()) / temperature
    return (np.exp(z) / np.exp(z).sum()).tolist()


def _check(ref, cfg, tok, state, served, temperature, cache):
    config = {
        "reference": ref.__name__.split(".")[-1],
        "tokenizer": tok,
        "check": {"requests": 8, "temperature": temperature, "logit_rms_limit": 1.0},
    }
    out = consensus_logit.run(config, cfg, state, served, [None] * len(served), True, cache)
    assert out["compared"] == 32
    return out["numbers"][0]["value"]


def test_the_control_fails_where_the_sound_path_passes_bert(tmp_path):
    """The sound path is the program's float32 forward; the control is the
    reference put in the program's place with its matrix products in int8.
    Fully distinct candidates and four layers: at this size the vote's logits
    spread by 1e-5, and float32 cosines alone carry 6e-8 of round-off."""
    import jax.numpy as jnp

    from llm_weighted_consensus_tpu.models import bert
    from llm_weighted_consensus_tpu.models.configs import TEST_TINY

    pcfg = dataclasses.replace(
        TEST_TINY, hidden_size=128, num_layers=4, intermediate_size=256
    )
    state = checkpoints.make_state("bert", WIDER, 11)
    weights = bert_cls_cosine.load(state, WIDER)
    f32 = {k: np.asarray(v).astype(np.float32) for k, v in state.items()}
    params = bert.from_hf_weights(f32, pcfg, dtype=jnp.float32)
    mix = dict(MIX, changed=1.0)
    sound, control = [], []
    for req in gen.generate(mix, 11, 1.0, 508):
        ids, mask = bert_cls_cosine.inputs(req, WIDER, BERT_TOK)
        emb = np.asarray(
            bert.embed(params, jnp.asarray(ids), jnp.asarray(mask), pcfg), np.float64
        )
        sound.append((req, _kept(bert_cls_cosine.vote_logits(emb), 0.05)))
        low = bert_cls_cosine.logits(weights, WIDER, ids, mask, lowered=True)
        control.append((req, _kept(low, 0.05)))
    cache = str(tmp_path / "jax_cache")
    a = _check(bert_cls_cosine, WIDER, BERT_TOK, state, sound, 0.05, cache)
    b = _check(bert_cls_cosine, WIDER, BERT_TOK, state, control, 0.05, cache)
    assert a < 1.5e-7 and b > 4.5e-7  # a limit of 2.6e-7 stands between


def test_the_control_fails_where_the_sound_path_passes_deberta(tmp_path):
    state = checkpoints.make_state("deberta-v2", DEBERTA, 11)
    weights = deberta_v3_reward.load(state, DEBERTA)
    sound, control = [], []
    for req in requests(11, "rm"):
        ids, mask = deberta_v3_reward.inputs(req, DEBERTA, DEBERTA_TOK)
        sound.append((req, _kept(program_rewards(state, DEBERTA, ids, mask), 1.0)))
        low = deberta_v3_reward.logits(weights, DEBERTA, ids, mask, lowered=True)
        control.append((req, _kept(low, 1.0)))
    cache = str(tmp_path / "jax_cache")
    a = _check(deberta_v3_reward, DEBERTA, DEBERTA_TOK, state, sound, 1.0, cache)
    b = _check(deberta_v3_reward, DEBERTA, DEBERTA_TOK, state, control, 1.0, cache)
    print("deberta sound", a, "control", b)
    assert a < 1e-7 and b > 10 * max(a, 1e-7)


def test_sample_keeps_the_longest_and_every_candidate_count():
    served = [
        ({"n": n, "words": [np.zeros(length, int)] * n, "index": i}, None)
        for i, (n, length) in enumerate(
            [(8, 10), (8, 12), (32, 10), (64, 400), (8, 11), (32, 9), (8, 13)]
        )
    ]
    picked = consensus_logit.sample(served, 4, seed=3)
    ns = sorted(req["n"] for req, _ in picked)
    assert len(picked) == 4 and set(ns) == {8, 32, 64}
    assert any(req["index"] == 3 for req, _ in picked)
    assert [r["index"] for r, _ in picked] == [
        r["index"] for r, _ in consensus_logit.sample(served, 4, seed=3)
    ]
