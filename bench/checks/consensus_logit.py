"""The output check of the /consensus cells: served confidences against the
plain reference's vote, on a seeded sample of the window's own answers.

A confidence vector is softmax(logit / T), so T * ln(confidence), centred over
the request's candidates, is the served logit up to a constant.  The number
compared is the root mean square, over every candidate of every sampled
request, of (served centred logit - reference centred logit):

  ``logit_rms``   against the limit in the configuration's ``check`` block.

Where the configuration's check also has an ``embedding_limit`` (the encoder
cells), the sampled requests' texts go once more through POST /embeddings
before the server stops, and a second number is compared:

  ``embedding_1_minus_cos``   mean over candidates of 1 - cosine(served
                              vector, reference vector).

The vote cancels most of an encoder's error (it is common to the candidates),
so ``logit_rms`` guards the timed path's own arithmetic, order and slicing and
separates precisions by about three; the embedding's angle does not cancel and
separates them by more than ten.

A maximum over one request swings with the seed; a root mean square over
several hundred candidates does not, which is what lets one limit stand
between the declared precision and the one below it (PERF.md, "The check").
The sample holds the window's longest request and at least one request of
every candidate count, the rest drawn from the seed.
"""

from __future__ import annotations

import math

import numpy as np

import byname


def sample(served: list, count: int, seed: int) -> list:
    """``served`` is [(request, kept)], ``kept`` the fields of the answer
    that the generator had kept (here ``confidence``).  The longest request,
    one of each candidate count, then seeded draws up to ``count``."""
    if len(served) <= count:
        return list(served)
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 21]))
    order = [int(i) for i in rng.permutation(len(served))]

    def size(i):
        return sum(len(w) for w in served[i][0]["words"])

    chosen = [max(range(len(served)), key=size)]
    for n in sorted({req["n"] for req, _ in served}):
        if all(served[i][0]["n"] != n for i in chosen):
            chosen.append(next(i for i in order if served[i][0]["n"] == n))
    for i in order:
        if len(chosen) >= count:
            break
        if i not in chosen:
            chosen.append(i)
    return [served[i] for i in chosen]


def centred_logits(confidence: list, temperature: float) -> np.ndarray:
    logit = temperature * np.log(np.asarray(confidence, dtype=np.float64))
    return logit - logit.mean()


def setup_jax(cache_dir: str, dry: bool):
    import jax

    if not dry:  # a CPU rehearsal keeps no cache (and XLA:CPU warns on reload)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    platform = jax.devices()[0].platform
    if not dry and platform != "tpu":
        raise SystemExit(f"[bench] the reference found no accelerator ({platform})")
    return jax


def collect(port: int, config: dict, picked: list, render_text) -> list:
    """While the server is still up: for a configuration whose check has an
    ``embedding_limit``, the sampled requests' texts once more through
    POST /embeddings (the same encoder behind the product's other endpoint).
    Returns one [N, H] array per request, or None."""
    if "embedding_limit" not in config["check"]:
        return [None] * len(picked)
    import json

    from server import BenchError, http_json

    out = []
    for req, _ in picked:
        body = {
            "model": config["server_env"]["EMBEDDER_MODEL"],
            "input": [render_text(w) for w in req["words"]],
        }
        status, raw = http_json(port, "POST", "/embeddings", body)
        if status != 200:
            raise BenchError(f"/embeddings: HTTP {status}: {raw[:200]!r}")
        rows = sorted(json.loads(raw)["data"], key=lambda r: r["index"])
        out.append(np.asarray([r["embedding"] for r in rows], dtype=np.float64))
    return out


def run(config, cfg, state, picked, vectors, dry, cache_dir, lowered=False) -> dict:
    """``picked`` is the sample [(request, kept)], ``vectors`` what
    ``collect`` returned for it."""
    params = config["check"]
    setup_jax(cache_dir, dry)
    ref = byname.module("references", config["reference"])
    weights = ref.load(state, cfg)
    diffs, rotated, angles = [], [], []
    for (req, kept), served_vecs in zip(picked, vectors):
        inputs = ref.inputs(req, cfg, config["tokenizer"])
        if served_vecs is not None:
            emb = np.asarray(
                ref.embeddings(weights, cfg, *inputs, lowered=lowered), np.float64
            )
            want = ref.vote_logits(emb)
            unit = served_vecs / np.linalg.norm(served_vecs, axis=1, keepdims=True)
            angles.append(1.0 - np.sum(unit * emb, axis=1))
        else:
            want = ref.logits(weights, cfg, *inputs, lowered=lowered)
        want = np.asarray(want, dtype=np.float64)
        got = centred_logits(kept["confidence"], float(params["temperature"]))
        diffs.append(got - (want - want.mean()))
        rotated.append(np.roll(got, 1) - (want - want.mean()))
    flat = np.concatenate(diffs) if diffs else np.zeros(0)
    value = float(math.sqrt(np.mean(flat**2))) if flat.size else float("inf")
    numbers = [
        {"name": "logit_rms", "value": value, "limit": float(params["logit_rms_limit"])},
    ]
    if angles:
        numbers.append(
            {
                "name": "embedding_1_minus_cos",
                "value": float(np.mean(np.concatenate(angles))),
                "limit": float(params["embedding_limit"]),
            }
        )
    return {
        "numbers": numbers,
        "compared": int(flat.size),
        "worst_abs": float(np.abs(flat).max()) if flat.size else None,
        # not compared: what logit_rms would read had every answer come back
        # one candidate out of place (the fault its limit is held against)
        "logit_rms_if_rotated": float(math.sqrt(np.mean(np.concatenate(rotated) ** 2)))
        if rotated
        else None,
    }
