#!/usr/bin/env python3
"""Chip smoke: the quickest proof that the system still starts on the TPU.

    python chip_smoke.py            # needs the chip; fails on anything else
    python chip_smoke.py --dry-run  # the same plumbing on CPU at test-tiny

Drives the served consensus path once through its normal entry point
(``python -m llm_weighted_consensus_tpu.serve``) at the full width of
bge-large-en — 24 layers, hidden 1024, 16 heads, bf16, N=64 candidates in
the seq-128 bucket — with random weights made from a seed, then checks
every Pallas kernel against its plain reference on the device, then (on a
host with four chips) the same server over a 2x2 mesh.

The parent process never imports jax: a chip belongs to one process at a
time, so each stage is a child process, run one after another, and every
child is stopped before the next starts.  One JSON line per stage on
stdout, then a summary line (``"claim": null``), and as the LAST line the
result, exactly
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``
with the device as JAX reports it.  On failure the reason goes to stderr and
the exit code is 1; where no accelerator was found there is no result line.

No timing printed here is a performance number: ``setup_s`` fields are
cold set-up (process start, weight init, compilation), reported so a
compile-cache hit is visible.  Performance is measured by the benchmark,
not by this script.
"""

from __future__ import annotations

import argparse
import http.client
import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(HERE, "chiprun_out", "chip_smoke")
TIMINGS_ARE = "setup, not measured perf"
DEVICE_KEYS = ("platform", "device_kind", "device_count")

# What each run drives.  ``words`` + [CLS] + [SEP] must land in the warmed
# ``seq`` bucket (models/embedder.py _SEQ_BUCKETS); ``long_words`` in the
# widest dense bucket, where bge-large's attention policy switches to the
# fused kernel on the TPU.
FULL = {
    "model": "bge-large-en",
    "n": 64,
    "seq": 128,
    "words": 118,
    "long_n": 16,
    "long_seq": 512,
    "long_words": 500,
    "max_tokens": 512,
}
DRY = {
    "model": "test-tiny",
    "n": 8,
    "seq": 32,
    "words": 26,
    "long_n": 4,
    "long_seq": 64,
    "long_words": 58,
    "max_tokens": 64,
}

# ---------------------------------------------------------------------------
# parent: orchestration, stdlib only
# ---------------------------------------------------------------------------


def emit(record: dict) -> None:
    print(json.dumps(record), flush=True)


class StageFailed(Exception):
    pass


def need(cond, message: str) -> None:
    """A check that raises (``assert`` vanishes under -O)."""
    if not cond:
        raise StageFailed(message)


def child_env(dry_run: bool) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = HERE + os.pathsep + env.get("PYTHONPATH", "")
    if dry_run:
        # four virtual CPU devices, so the mesh stage's plumbing runs too
        env["JAX_PLATFORMS"] = "cpu"
        flags = [
            f
            for f in env.get("XLA_FLAGS", "").split()
            if "host_platform_device_count" not in f
        ]
        flags.append("--xla_force_host_platform_device_count=4")
        env["XLA_FLAGS"] = " ".join(flags)
    return env


def run_child(name: str, dry_run: bool, timeout: float) -> dict:
    """Run ``chip_smoke.py --child <name>`` to its end; relay its JSON
    lines; return the last one.  stderr goes to a file under OUT_DIR (the
    tail is shown on failure)."""
    cmd = [sys.executable, os.path.abspath(__file__), "--child", name]
    if dry_run:
        cmd.append("--dry-run")
    err_path = os.path.join(OUT_DIR, f"{name}.stderr.log")
    with open(err_path, "wb") as err:
        try:
            proc = subprocess.run(
                cmd,
                cwd=HERE,
                env=child_env(dry_run),
                stdout=subprocess.PIPE,
                stderr=err,
                timeout=timeout,
            )
        except subprocess.TimeoutExpired:
            raise StageFailed(f"child `{name}` exceeded {timeout:.0f}s")
    last = None
    for line in proc.stdout.decode("utf-8", "replace").splitlines():
        try:
            record = json.loads(line)
        except ValueError:
            continue
        if isinstance(record, dict):
            emit(record)
            last = record
    if proc.returncode != 0 or last is None:
        raise StageFailed(
            f"child `{name}` exited {proc.returncode}: "
            + tail_of(err_path)
        )
    return last


def tail_of(path: str, limit: int = 3000) -> str:
    try:
        with open(path, "rb") as f:
            return f.read()[-limit:].decode("utf-8", "replace")
    except OSError:
        return ""


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def http_json(port: int, method: str, path: str, body=None, timeout=600.0):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        payload = None if body is None else json.dumps(body).encode("utf-8")
        conn.request(
            method, path, payload, {"content-type": "application/json"}
        )
        resp = conn.getresponse()
        raw = resp.read()
    finally:
        conn.close()
    return resp.status, raw


def make_vocab(path: str, size: int) -> list:
    """A WordPiece vocab of ``size`` entries written to ``path`` — the
    specials plus whole words — so the server tokenizes through the real
    tokenizer (native C++ on ASCII) and not the hash fallback.  Returns
    the words."""
    words = [f"w{i}" for i in range(size - 4)]
    with open(path, "w", encoding="utf-8") as f:
        f.write("\n".join(["[PAD]", "[UNK]", "[CLS]", "[SEP]"] + words) + "\n")
    return words


def make_texts(words: list, n: int, length: int, seed: int) -> list:
    """``n`` candidates of ``length`` words: a shared answer with a few
    words changed per candidate, the shape self-consistency samples have."""
    import random

    rng = random.Random(seed)
    base = [rng.choice(words) for _ in range(length)]
    texts = []
    for _ in range(n):
        cand = list(base)
        for _ in range(max(1, length // 10)):
            cand[rng.randrange(length)] = rng.choice(words)
        texts.append(" ".join(cand))
    return texts


def check_confidence(status: int, raw: bytes, n: int, what: str) -> None:
    import math

    need(status == 200, f"{what}: HTTP {status}: {raw[:300]!r}")
    conf = json.loads(raw)["confidence"]
    need(len(conf) == n, f"{what}: {len(conf)} confidences, expected {n}")
    need(all(math.isfinite(c) for c in conf), f"{what}: non-finite value")
    need(abs(sum(conf) - 1.0) <= 1e-3, f"{what}: sum {sum(conf)} != 1")


def stage_serve(sizes: dict, dry_run: bool, mesh: bool, probe: dict) -> dict:
    """Start the server, drive it over HTTP, read /metrics, SIGTERM it."""
    name = "mesh" if mesh else "serve"
    port = free_port()
    vocab_size = 512 if dry_run else 30522
    vocab_path = os.path.join(OUT_DIR, "vocab.txt")
    words = make_vocab(vocab_path, vocab_size)
    prof_dir = os.path.join(OUT_DIR, f"prof_{name}")
    env = child_env(dry_run)
    env.update(
        EMBEDDER_MODEL=sizes["model"],
        EMBEDDER_VOCAB=vocab_path,
        EMBEDDER_MAX_TOKENS=str(sizes["max_tokens"]),
        WARMUP=f"{sizes['n']}x{sizes['seq']}",
        WARMUP_AOT="1",
        PROFILE_DIR=prof_dir,
    )
    if mesh:
        env.update(MESH_ENABLED="1", MESH_SHAPE="2x2")
    err_path = os.path.join(OUT_DIR, f"{name}.stderr.log")
    t0 = time.monotonic()
    with open(err_path, "wb") as err:
        server = subprocess.Popen(
            [
                sys.executable, "-m", "llm_weighted_consensus_tpu.serve",
                "--port", str(port), "--fake-upstream",
            ],
            cwd=HERE,
            env=env,
            stdout=subprocess.PIPE,
            stderr=err,
        )
    try:
        wait_listening(server, timeout=900.0)
        setup_s = round(time.monotonic() - t0, 1)
        record = drive_server(
            port, sizes, words, prof_dir, mesh, probe, dry_run
        )
        # graceful exit inside DRAIN_TIMEOUT_MILLIS (10 s by default)
        server.send_signal(signal.SIGTERM)
        try:
            rc = server.wait(timeout=30)
        except subprocess.TimeoutExpired:
            raise StageFailed("server still running 30s after SIGTERM")
        need(rc == 0, f"server exited {rc} after SIGTERM")
    except StageFailed as e:
        raise StageFailed(f"{e}\n--- server stderr ---\n{tail_of(err_path)}")
    finally:
        if server.poll() is None:
            server.kill()
            server.wait()
    return {"stage": name, "setup_s": setup_s, **record}


def wait_listening(server: subprocess.Popen, timeout: float) -> None:
    """Block until the server prints ``listening on``; keep draining its
    stdout afterwards so it can never block on a full pipe."""
    ready = threading.Event()

    def pump():
        for raw in server.stdout:
            if b"listening on" in raw:
                ready.set()
        ready.set()  # EOF: the server died; the caller sees poll() != None

    threading.Thread(target=pump, daemon=True).start()
    need(ready.wait(timeout), f"server not listening after {timeout:.0f}s")
    need(server.poll() is None, f"server exited {server.poll()} at start-up")


def drive_server(port, sizes, words, prof_dir, mesh, probe, dry_run) -> dict:
    """The requests and the /metrics checks; returns the stage record."""
    n, long_n = sizes["n"], sizes["long_n"]

    def metrics() -> dict:
        status, raw = http_json(port, "GET", "/metrics")
        need(status == 200, f"/metrics: HTTP {status}")
        return json.loads(raw)

    before = metrics()
    device = before.get("device") or {}
    for key in DEVICE_KEYS:
        need(
            device.get(key) == probe[key],
            f"/metrics device.{key} = {device.get(key)!r}, the probe saw "
            f"{probe[key]!r}",
        )
    if not dry_run:
        need(device.get("param_dtype") == "bfloat16", f"params {device}")
        need(device.get("pallas_interpret") is False, f"interpret: {device}")

    # the warmed bucket, a few times
    for i in range(3):
        texts = make_texts(words, n, sizes["words"], seed=i)
        status, raw = http_json(port, "POST", "/consensus", {"input": texts})
        check_confidence(status, raw, n, f"/consensus #{i}")
    warmed = metrics()
    need(
        warmed["jit"]["specializations"] == before["jit"]["specializations"]
        and warmed["jit"]["aot_buckets"] == before["jit"]["aot_buckets"] > 0,
        "the warmed bucket compiled again: jit "
        f"{before['jit']} -> {warmed['jit']}",
    )

    # the widest dense bucket (compiles here) with a profile taken across it
    profile = {}

    def take_profile():
        profile["status"], _ = http_json(
            port, "POST", "/v1/profile", {"duration_ms": 200}
        )

    long_texts = make_texts(words, long_n, sizes["long_words"], seed=7)
    status, raw = http_json(port, "POST", "/consensus", {"input": long_texts})
    check_confidence(status, raw, long_n, "/consensus long")
    profiler = threading.Thread(target=take_profile)
    profiler.start()
    status, raw = http_json(port, "POST", "/consensus", {"input": long_texts})
    check_confidence(status, raw, long_n, "/consensus long (profiled)")
    profiler.join()
    need(profile.get("status") == 200, f"/v1/profile: {profile}")
    traces = [
        os.path.join(root, f)
        for root, _, files in os.walk(prof_dir)
        for f in files
        if f.endswith(".xplane.pb")
    ]
    need(traces, f"no .xplane.pb under {prof_dir}")

    status, raw = http_json(
        port, "POST", "/embeddings",
        {"model": sizes["model"], "input": make_texts(words, 3, 12, seed=9)},
    )
    need(status == 200, f"/embeddings: HTTP {status}: {raw[:300]!r}")
    rows = json.loads(raw)["data"]
    need(len(rows) == 3, f"/embeddings: {len(rows)} rows")

    status, raw = http_json(
        port, "POST", "/score/completions",
        {
            "stream": True,
            "messages": [{"role": "user", "content": "which is best?"}],
            "model": {"llms": [{"model": f"judge-{j}"} for j in "abc"]},
            "choices": make_texts(words, 3, 12, seed=11),
        },
    )
    need(status == 200, f"/score/completions: HTTP {status}: {raw[:300]!r}")
    need(
        raw.rstrip().endswith(b"data: [DONE]"),
        f"/score/completions stream ended {raw[-80:]!r}",
    )

    after = metrics()
    label = "device:batch:consensus"
    series = after.get("series", {}).get(label)
    need(
        series is not None and series["count"] >= 5,
        f"series {label}: {series}; have {sorted(after.get('series', {}))}",
    )
    if mesh:
        need(after["mesh"]["devices"] == 4, f"mesh: {after['mesh']}")
        # the warmed bucket's device timings carry the mesh shape
        bucket = f"vote1(n={n},s={sizes['seq']})@dp2xtp2"
        timed = after["roofline"]["buckets"]
        need(bucket in timed, f"no {bucket} among {sorted(timed)}")
        used = [d.get("bytes_in_use") for d in after["device"]["devices"]]
        if not dry_run:
            need(
                len(used) == 4 and all(used),
                f"memory not in use on every chip: {used}",
            )
    return {
        **{key: device[key] for key in DEVICE_KEYS},
        "versions": probe["versions"],
        "model": sizes["model"],
        "param_dtype": device["param_dtype"],
        "devices": after["device"]["devices"],
        "jit": after["jit"],
        "compile_cache": after.get("compile_cache"),
        "requests": {label: series["count"]},
        "xplane_files": len(traces),
        "pass": True,
        "dry_run": dry_run,
        "timings_are": TIMINGS_ARE,
    }


def result_line(ok: bool, probe: dict) -> dict:
    """The result, always the LAST line of stdout when there is one: exactly
    these keys, the device as JAX reported it to the probe child."""
    return {
        "ok": ok,
        "device": {
            "platform": probe["platform"],
            "kind": probe["device_kind"],
            "count": probe["device_count"],
        },
    }


def run_parent(dry_run: bool) -> int:
    os.makedirs(OUT_DIR, exist_ok=True)
    sizes = DRY if dry_run else FULL
    stages = {}
    probe = {}
    device_accepted = False
    try:
        probe = run_child("probe", dry_run, timeout=300)
        if not dry_run:
            need(
                probe["platform"] == "tpu",
                f"platform {probe['platform']} is not tpu",
            )
        device_accepted = True
        need(probe["native"]["loaded"], f"native library: {probe['native']}")
        stages["serve"] = stage_serve(sizes, dry_run, False, probe)
        emit(stages["serve"])
        stages["kernels"] = run_child("kernels", dry_run, timeout=900)
        need(stages["kernels"]["pass"], "stage kernels failed")
        if probe["device_count"] >= 4:
            stages["mesh"] = stage_serve(sizes, dry_run, True, probe)
        else:
            stages["mesh"] = {
                "stage": "mesh",
                **{key: probe[key] for key in DEVICE_KEYS},
                "skipped": f"{probe['device_count']} device",
            }
        emit(stages["mesh"])
    except StageFailed as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr)
        print(
            json.dumps(
                {"ok": False, "passed": sorted(stages), "claim": None}
            ),
            file=sys.stderr,
            flush=True,
        )
        # without the device there is no result on stdout at all; with it,
        # a stage that failed there is a result, and it says so
        if device_accepted:
            emit(result_line(False, probe))
        return 1
    emit(
        {
            "summary": True,
            **{key: probe[key] for key in DEVICE_KEYS},
            "dry_run": dry_run,
            "versions": probe["versions"],
            "stages": {
                name: "skipped" if "skipped" in rec else "passed"
                for name, rec in stages.items()
            },
            "setup_s": {
                name: rec["setup_s"]
                for name, rec in stages.items()
                if "setup_s" in rec
            },
            "timings_are": TIMINGS_ARE,
            "claim": None,
        }
    )
    emit(result_line(True, probe))
    return 0


# ---------------------------------------------------------------------------
# children: each owns the device for its lifetime
# ---------------------------------------------------------------------------


def versions() -> dict:
    import importlib.metadata as md

    out = {}
    for dist in ("jax", "jaxlib", "libtpu"):
        try:
            out[dist] = md.version(dist)
        except md.PackageNotFoundError:
            out[dist] = None
    return out


def child_probe(dry_run: bool) -> int:
    """What JAX found, which versions, and whether the native library
    builds from the sources in this checkout."""
    from llm_weighted_consensus_tpu.utils import device_summary, native

    t0 = time.monotonic()
    native.load_library()
    native_s = round(time.monotonic() - t0, 1)
    emit(
        {
            "stage": "probe",
            **device_summary(),
            "versions": versions(),
            "native": native.status(),
            "native_setup_s": native_s,
            "pass": True,
            "dry_run": dry_run,
            "timings_are": TIMINGS_ARE,
        }
    )
    return 0


def child_kernels(dry_run: bool) -> int:
    """Every Pallas kernel at the served shapes: compiled by Mosaic (the
    custom call must be in the compiled HLO, so a shape gate that quietly
    routed to the reference fails), run, and compared on the device with
    its plain reference; then the quantized forwards end to end."""
    import dataclasses
    import traceback

    import jax
    import jax.numpy as jnp
    import numpy as np

    from llm_weighted_consensus_tpu.models import bert, quant
    from llm_weighted_consensus_tpu.models.configs import PRESETS
    from llm_weighted_consensus_tpu.models.embedder import TpuEmbedder
    from llm_weighted_consensus_tpu.ops import kernels, similarity
    from llm_weighted_consensus_tpu.serve.config import (
        configure_compile_cache,
    )
    from llm_weighted_consensus_tpu.utils import device_summary

    t_start = time.monotonic()
    cache = configure_compile_cache()
    sizes = DRY if dry_run else FULL
    config = PRESETS[sizes["model"]]
    dtype = jnp.float32 if dry_run else jnp.bfloat16
    # the bf16 bound tests/test_quant.py pins for kernel-vs-XLA parity;
    # f32 (dry run) uses the f32 one
    tol = 2e-5 if dry_run else 2e-2
    n, seq = sizes["n"], sizes["seq"]
    long_n, long_seq = sizes["long_n"], sizes["long_seq"]
    h, inter = config.hidden_size, config.intermediate_size
    rng = np.random.default_rng(0)
    checks = []
    device = device_summary()  # every line says where it ran

    def normal(shape, scale=1.0, dt=dtype):
        return jnp.asarray(rng.standard_normal(shape) * scale, dt)

    def crashed(rec, e):
        """A check that raised: keep going (every check is reported, then
        the stage fails), message in the record, traceback in a file."""
        rec["pass"] = False
        rec["error"] = f"{type(e).__name__}: {e}"[-1500:]
        with open(os.path.join(OUT_DIR, f"{rec['check']}.err"), "w") as f:
            traceback.print_exc(file=f)

    def check(name, kernel_fn, ref_fn, args, tol=tol):
        """Compile ``kernel_fn``, require the Mosaic custom call, run it
        and ``ref_fn`` on the device, compare."""
        rec = {"check": name, **device}
        try:
            t0 = time.monotonic()
            compiled = jax.jit(kernel_fn).lower(*args).compile()
            rec["compile_s"] = round(time.monotonic() - t0, 1)
            rec["mosaic"] = "tpu_custom_call" in compiled.as_text()
            got = np.asarray(compiled(*args), np.float32)
            want = np.asarray(jax.jit(ref_fn)(*args), np.float32)
            rec["max_abs_err"] = float(np.max(np.abs(got - want)))
            rec["pass"] = bool(
                got.shape == want.shape
                and np.isfinite(got).all()
                and np.allclose(got, want, atol=tol, rtol=tol)
                and (rec["mosaic"] or dry_run)
            )
        except Exception as e:
            crashed(rec, e)
        checks.append(rec)
        emit(rec)

    # the vote: N candidates of the model's hidden size
    check(
        "fused_cosine_vote",
        kernels.fused_cosine_vote,
        similarity.cosine_consensus_vote,
        (normal((n, h), dt=jnp.float32),),
        tol=1e-5 if dry_run else 1e-4,
    )

    # attention, through the encoder's own attention block so the served
    # heads_per_step is the one compiled: fused vs einsum
    layer = jax.tree_util.tree_map(
        lambda a: a[0],
        bert.init_params(jax.random.PRNGKey(1), config, dtype=dtype)["layers"],
    )
    x = normal((long_n, long_seq, h))
    lens = rng.integers(long_seq // 2, long_seq + 1, long_n)
    mask = jnp.asarray(np.arange(long_seq)[None, :] < lens[:, None], jnp.int32)
    bias = jnp.where(mask[:, None, None, :] > 0, 0.0, -1e9).astype(jnp.float32)
    fused_cfg = dataclasses.replace(config, attention_impl="fused")
    einsum_cfg = dataclasses.replace(config, attention_impl="einsum")
    check(
        "fused_attention_tiled",
        lambda x, b: bert._attention(x, layer, b, fused_cfg),
        lambda x, b: bert._attention(x, layer, b, einsum_cfg),
        (x, bias),
    )

    # the quantized matmuls at the three encoder shapes, M = N * seq rows
    m = n * seq
    for tag, k, nn, gelu in (
        ("qkvo", h, h, False),
        ("mlp_in", h, inter, True),
        ("mlp_out", inter, h, False),
    ):
        w = normal((k, nn), 0.02, jnp.float32)
        b = normal((nn,), 0.02, dtype)
        xs = normal((m, k))
        kq, scale = quant.quantize_weight(w)
        p8 = {"kernel_q": kq, "scale": scale, "bias": b}
        check(
            f"w8a8_matmul[{tag}]",
            lambda x, p=p8, g=gelu: quant.dense_int8(
                x, p, gelu=g, impl="pallas"
            ),
            lambda x, p=p8, g=gelu: quant.dense_int8(x, p, gelu=g, impl="xla"),
            (xs,),
        )
        kq4, scale4 = quant.quantize_weight_int4(w)
        p4 = {"kernel_q": kq4, "scale": scale4, "bias": b}
        check(
            f"w4a8_matmul[{tag}]",
            lambda x, p=p4, g=gelu: quant.dense_int4(
                x, p, gelu=g, impl="pallas"
            ),
            lambda x, p=p4, g=gelu: quant.dense_int4(x, p, gelu=g, impl="xla"),
            (xs,),
        )

    # whole forwards through the embedder.  The unquantized one is the
    # server's own warmed bucket: its compilations must come out of the
    # persistent cache the serve stage filled.
    ids = rng.integers(4, config.vocab_size, (n, seq)).astype(np.int32)
    tok_mask = np.ones((n, seq), np.int32)

    def embedder(layers, quantize):
        return TpuEmbedder(
            sizes["model"],
            config=dataclasses.replace(config, num_layers=layers),
            dtype=dtype,
            quantize=quantize,
            max_tokens=sizes["max_tokens"],
        )

    def outputs(emb):
        return (
            np.asarray(emb.consensus_confidence_tokens(ids, tok_mask)),
            np.asarray(emb.embed_tokens(ids, tok_mask)),
        )

    ref = embedder(config.num_layers, "none")
    hits_before = cache.snapshot()["hits"]
    ref.aot_warmup([(n, seq)])
    cache_hits = cache.snapshot()["hits"] - hits_before
    # bounds from tests/test_quant.py, which pins them on a 2-layer model;
    # int4 round-off compounds with depth (0.86 cosine at 24 random-weight
    # layers, CPU f32), so its forward is cut to the depth of the pin
    unquantized = {}  # layers -> (confidence, embeddings)
    for mode, layers, min_cos, max_diff in (
        ("none", config.num_layers, None, None),
        ("int8", config.num_layers, 0.98, 0.1),
        ("int4-pallas", 2, 0.95, 0.15),
    ):
        rec = {"check": f"forward[{mode}]", **device, "layers": layers}
        try:
            t0 = time.monotonic()
            conf, emb = outputs(
                ref if mode == "none" else embedder(layers, mode)
            )
            rec["setup_s"] = round(time.monotonic() - t0, 1)
            rec["pass"] = bool(
                conf.shape == (n,)
                and np.isfinite(conf).all()
                and abs(conf.sum() - 1.0) <= 1e-3
            )
            if mode == "none":
                unquantized[layers] = conf, emb
            else:
                if layers not in unquantized:
                    unquantized[layers] = outputs(embedder(layers, "none"))
                want_conf, want_emb = unquantized[layers]
                rec["min_cosine"] = float((emb * want_emb).sum(1).min())
                rec["max_conf_diff"] = float(np.abs(conf - want_conf).max())
                rec["pass"] = bool(
                    rec["pass"]
                    and rec["min_cosine"] > min_cos
                    and rec["max_conf_diff"] < max_diff
                )
        except Exception as e:
            crashed(rec, e)
        checks.append(rec)
        emit(rec)
    failed = [c["check"] for c in checks if not c["pass"]]
    if not dry_run and cache_hits < 1:
        failed.append("compile cache: no hit for the bucket stage serve warmed")
    emit(
        {
            "stage": "kernels",
            **device,
            "versions": versions(),
            "setup_s": round(time.monotonic() - t_start, 1),
            "checks": len(checks),
            "failed": failed,
            "compile_cache": cache.snapshot(),
            "cache_hits_for_served_bucket": cache_hits,
            "pass": not failed,
            "dry_run": dry_run,
            "timings_are": TIMINGS_ARE,
        }
    )
    return 0


CHILDREN = {"probe": child_probe, "kernels": child_kernels}


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument(
        "--dry-run",
        action="store_true",
        help="run the same plumbing on CPU at test-tiny (marked "
        '"dry_run": true); proves nothing about the chip',
    )
    parser.add_argument("--child", choices=sorted(CHILDREN), help=argparse.SUPPRESS)
    args = parser.parse_args(argv)
    if args.child:
        os.makedirs(OUT_DIR, exist_ok=True)
        return CHILDREN[args.child](args.dry_run)
    return run_parent(args.dry_run)


if __name__ == "__main__":
    sys.exit(main())
