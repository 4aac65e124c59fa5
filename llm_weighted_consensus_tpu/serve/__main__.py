"""Service entry point: ``python -m llm_weighted_consensus_tpu.serve``.

Wires env config into the client stack (main.rs wiring parity: default
clients + unimplemented fetchers unless stores are configured) and serves.
``--fake-upstream`` starts a loopback scripted provider and points the
chat client at it — the zero-key local demo / verification mode.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import random

from aiohttp import web

from .. import archive, registry
from ..clients.chat import AiohttpTransport, ApiBase, DefaultChatClient
from ..clients.multichat import MultichatClient
from ..clients.score import ScoreClient
from ..weights import WeightFetchers
from . import startup
from .config import Config, configure_compile_cache, load_dotenv
from .gateway import LIFECYCLE_KEY, _parse_error_response, build_app

FAKE_PORT = 5990

# the service's archive store, exposed for introspection/tests
ARCHIVE_KEY: web.AppKey = web.AppKey("archive", object)
# the live judge training tables (when an embedder is configured)
TABLES_KEY: web.AppKey = web.AppKey("tables", object)


def _rescore_handler(store, lock, mesh=None):
    """POST /archive/rescore: re-tally archived score completions on device
    (``archive/rescore.py`` as a service operation), dp-sharded when
    the service has a mesh.

    Body (all optional): {"weight_overrides": {judge id: weight},
    "ids": [completion ids], "revote": bool (re-extract soft votes from
    stored logprobs), "apply": bool (write results back into the archive),
    "include_results": bool}.

    Locking: the device compute runs on an executor WITHOUT the lock —
    it reads only fields no other writer touches (judge votes/weights;
    ``apply`` writes candidate fields, ``learn`` writes tables), so a 10k
    re-score doesn't block archiving writes.  ``apply`` then runs ON THE
    EVENT LOOP under the lock: sync code on the loop is atomic w.r.t.
    every request handler, so no reader can observe a half-applied
    completion (weight updated, confidence not).
    """
    from ..archive.rescore import apply_rescore, rescore_archive
    from ..utils import jsonutil

    def bad_request(message):
        return web.Response(
            status=400,
            text=jsonutil.dumps({"code": 400, "message": message}),
            content_type="application/json",
        )

    async def handler(request: web.Request):
        try:
            body = jsonutil.loads(await request.text() or "{}")
            if not isinstance(body, dict):
                return bad_request("body must be a JSON object")
            oraw = body.get("weight_overrides") or {}
            if not isinstance(oraw, dict):
                raise ValueError(
                    "`weight_overrides` must map judge ids to numbers"
                )
            from decimal import Decimal as _Decimal

            overrides = {}
            for judge, w in oraw.items():
                if isinstance(w, bool) or not isinstance(
                    w, (int, float, _Decimal)
                ):
                    raise ValueError(
                        f"`weight_overrides[{judge!r}]` must be a number"
                    )
                overrides[str(judge)] = float(w)
            ids = body.get("ids")
            revote = bool(body.get("revote", False))
            apply = bool(body.get("apply", False))
            include = bool(body.get("include_results", False))
        except web.HTTPException:
            raise  # e.g. 413 body-too-large must keep its status
        except Exception as e:  # parse phase: malformed input, not a fault
            return _parse_error_response(e)
        # validation beyond parsing stays OUTSIDE the blanket except: a
        # store fault must surface as a 500, not masquerade as a 400
        if ids is not None:
            if not isinstance(ids, list):
                return bad_request("`ids` must be a list")
            unknown = [
                cid for cid in ids if store.score_completion(cid) is None
            ]
            if unknown:
                return bad_request(
                    f"unknown score completion ids: {unknown[:5]}"
                )

        def run():
            return rescore_archive(
                store,
                mesh=mesh,
                weight_overrides=overrides or None,
                ids=ids,
                revote=revote,
            )

        results = await asyncio.get_running_loop().run_in_executor(None, run)
        applied = 0
        if apply:
            # on-loop + locked: atomic for readers, serialized vs learn
            async with lock:
                applied = apply_rescore(store, results)
        out = {"rescored": len(results), "applied": applied}
        if include:
            out["results"] = results
        return web.Response(
            text=jsonutil.dumps(out), content_type="application/json"
        )

    return handler


def _learn_handler(store, embedder, tables, lock):
    """POST /weights/learn: build training-table rows from the archive.

    Body: {"model": <inline panel JSON>, "labels": {completion_id: correct
    candidate index}?, "ids": [completion ids]?}.  Runs on an executor (it
    embeds prompts on device) and returns {"rows_added": N}.  Idempotent —
    already-ingested completions are skipped.  The shared lock serializes
    learn passes against each other (both would pass the is_ingested check
    before either marks) and against archive mutations (rescore apply).
    """
    from ..identity.model import ModelBase
    from ..utils import jsonutil
    from ..weights.learning import populate_from_archive

    async def handler(request: web.Request):
        try:
            body = jsonutil.loads(await request.text())
            if not isinstance(body, dict):
                raise ValueError("body must be a JSON object")
            if "model" not in body:
                raise ValueError("missing required field `model`")
            model = ModelBase.from_json_obj(
                body["model"]
            ).into_model_validate()
            lraw = body.get("labels") or {}
            if not isinstance(lraw, dict):
                raise ValueError(
                    "`labels` must map completion ids to candidate indexes"
                )
            labels = {}
            for cid, idx in lraw.items():
                if isinstance(idx, bool) or not isinstance(idx, int):
                    raise ValueError(
                        f"`labels[{cid!r}]` must be an integer index"
                    )
                labels[str(cid)] = int(idx)
            ids = body.get("ids")
        except web.HTTPException:
            raise  # e.g. 413 body-too-large must keep its status
        except Exception as e:  # parse phase: malformed input, not a fault
            return _parse_error_response(e)
        async with lock:
            added = await asyncio.get_running_loop().run_in_executor(
                None,
                lambda: populate_from_archive(
                    store, embedder, model, tables, ids=ids, labels=labels
                ),
            )
        return web.json_response({"rows_added": added})

    return handler


async def _fake_upstream(request: web.Request) -> web.StreamResponse:
    """A scripted judge provider: finds the ballot in the system prompt and
    votes for a random key; plain chat otherwise.

    ``FAKE_UPSTREAM_DELAY_MS`` (process env, read per request) adds a
    judge-latency sleep before the first frame, so load/drain scenarios
    (the chaos SIGTERM drill) exercise requests that HOLD their admission
    slot for a realistic interval instead of completing in microseconds."""
    import os

    delay_ms = float(os.environ.get("FAKE_UPSTREAM_DELAY_MS", "0") or 0.0)
    if delay_ms > 0:
        await asyncio.sleep(delay_ms / 1e3)
    body = await request.json()
    content = "This is a fake upstream completion."
    for message in reversed(body.get("messages", [])):
        if message.get("role") == "system" and "Select the response:" in str(
            message.get("content", "")
        ):
            text = message["content"]
            ballot = json.loads(
                text.split("Select the response:\n\n", 1)[1].split(
                    "\n\nOutput", 1
                )[0]
            )
            content = f"I select {random.choice(list(ballot))}"
            break
    resp = web.StreamResponse(
        headers={"content-type": "text/event-stream"}
    )
    await resp.prepare(request)
    for i, frag in enumerate((content[: len(content) // 2], content[len(content) // 2 :])):
        chunk = {
            "id": "fake-1",
            "object": "chat.completion.chunk",
            "created": 0,
            "model": body.get("model", "fake"),
            "choices": [
                {
                    "index": 0,
                    "delta": (
                        {"role": "assistant", "content": frag}
                        if i == 0
                        else {"content": frag}
                    ),
                    "finish_reason": None,
                }
            ],
        }
        await resp.write(f"data: {json.dumps(chunk)}\n\n".encode())
    final = {
        "id": "fake-1",
        "object": "chat.completion.chunk",
        "created": 0,
        "model": body.get("model", "fake"),
        "choices": [{"index": 0, "delta": {}, "finish_reason": "stop"}],
        "usage": {"prompt_tokens": 10, "completion_tokens": 10, "total_tokens": 20},
    }
    await resp.write(f"data: {json.dumps(final)}\n\ndata: [DONE]\n\n".encode())
    return resp


def _synthetic_params_allowed(allow_synthetic: bool) -> bool:
    import os

    from ..utils import env_truthy

    return allow_synthetic or env_truthy(
        os.environ.get("LWC_ALLOW_RANDOM_PARAMS", "")
    )


def build_embedder(config: Config, allow_synthetic: bool = False):
    """The service's device side: an embedder from env config.  With
    MESH_ENABLED it serves in first-class mesh mode — params placed once
    by the partition-rule tables, batches sharded over dp, encoder params
    Megatron-split over tp, per-(mesh-shape, bucket) AOT executables
    (parallel/sharding.py shard_embedder_mesh); without it, one device.

    Serving synthetic state — random-init weights (no EMBEDDER_WEIGHTS) or
    the hash tokenizer (no real vocab) — produces embeddings that LOOK
    valid but are garbage; it is refused unless explicitly opted into via
    ``allow_synthetic`` (set for --fake-upstream demo mode) or
    ``LWC_ALLOW_RANDOM_PARAMS=1``, and logged loudly even then."""
    if not config.embedder_model:
        return None
    from ..models.configs import PRESETS
    from ..models.embedder import TpuEmbedder
    from ..models.spm import scheme_for_model
    from ..models.tokenizer import load_tokenizer

    if config.embedder_model not in PRESETS:
        raise ValueError(
            f"EMBEDDER_MODEL={config.embedder_model!r} is not a known "
            f"preset; valid values: {', '.join(sorted(PRESETS))}"
        )

    params = None
    vocab_path = config.embedder_vocab
    # the checkpoint opened to the parameters on the device: ``weights``
    # of the /metrics ``startup`` section
    with startup.stopwatch("weights"):
        if config.embedder_weights:
            from ..models.loading import find_vocab, load_params

            params = load_params(
                config.embedder_weights, PRESETS[config.embedder_model]
            )
            if not vocab_path:
                vocab_path = find_vocab(config.embedder_weights)
        embedder = TpuEmbedder(
            config.embedder_model,
            params=params,
            # only override the tokenizer when a real vocab is available;
            # TpuEmbedder's default hash fallback sizes to the model vocab.
            # scheme matters only for spm protos (bge-m3 -> xlmr convention)
            tokenizer=(
                load_tokenizer(
                    vocab_path,
                    scheme=scheme_for_model(config.embedder_model),
                )
                if vocab_path
                else None
            ),
            max_tokens=config.embedder_max_tokens,
            quantize=config.embedder_quantize,
        )
        _params_on_device(embedder)
    from ..models.tokenizer import HashTokenizer

    synthetic = []
    if params is None:
        synthetic.append("random-init weights (no EMBEDDER_WEIGHTS)")
    if isinstance(embedder.tokenizer, HashTokenizer):
        synthetic.append(
            "hash tokenizer (no EMBEDDER_VOCAB and no vocab/spm file "
            "beside EMBEDDER_WEIGHTS)"
        )
    if synthetic:
        detail = (
            f"EMBEDDER_MODEL={config.embedder_model} would serve "
            + " and ".join(synthetic)
            + " — embeddings and trained-weight lookups would be garbage "
            "that looks valid."
        )
        if not _synthetic_params_allowed(allow_synthetic):
            raise ValueError(
                detail
                + " Point EMBEDDER_WEIGHTS at a checkpoint, or opt into "
                "synthetic params explicitly with LWC_ALLOW_RANDOM_PARAMS=1 "
                "(tests/demo only)."
            )
        import logging

        logging.getLogger("lwc.serve").warning(
            "SYNTHETIC EMBEDDER PARAMS: %s Serving anyway "
            "(LWC_ALLOW_RANDOM_PARAMS / fake-upstream demo mode).",
            detail,
        )
    if config.mesh_enabled:
        import jax

        from ..parallel.mesh import make_mesh
        from ..parallel.sharding import shard_embedder_mesh

        # the serving mesh is HOST-LOCAL: a request lands on one host and
        # must be executable without the other hosts' cooperation (they
        # serve their own traffic).  Single-host: local == global.  See
        # DESIGN.md §multi-host.  MESH_SHAPE unset = every local device
        # on dp (tp=1)
        shape = config.mesh_shape
        mesh = make_mesh(
            dp=shape[0] if shape else None,
            tp=shape[1] if shape else 1,
            sp=shape[2] if shape and len(shape) > 2 else 1,
            devices=jax.local_devices(),
        )
        with startup.stopwatch("weights"):
            shard_embedder_mesh(embedder, mesh)
            _params_on_device(embedder)
    return embedder


def _params_on_device(model) -> None:
    """Block until ``model.params`` are there: the transfers a loader
    starts are asynchronous, and the ``weights`` stopwatch ends when they
    have landed, not when they were asked for."""
    from ..models.dispatch_seam import wait_device_ready

    wait_device_ready(model.params)


def build_reranker(config: Config, allow_synthetic: bool = False):
    """The RM-scoring device side (POST /consensus {"scorer": "rm"}):
    a DeBERTa reward model from env config.  Same synthetic-params
    discipline as ``build_embedder``."""
    if not config.rm_model:
        return None
    from ..models.reranker import RM_PRESETS, TpuReranker, load_rm_params
    from ..models.spm import scheme_for_model
    from ..models.tokenizer import HashTokenizer, load_tokenizer

    if config.rm_model not in RM_PRESETS:
        raise ValueError(
            f"RM_MODEL={config.rm_model!r} is not a known preset; "
            f"valid values: {', '.join(sorted(RM_PRESETS))}"
        )
    params = None
    head_loaded = False
    vocab_path = config.rm_vocab
    if config.rm_weights:
        from ..models.loading import find_vocab

        params, head_loaded = load_rm_params(
            config.rm_weights, RM_PRESETS[config.rm_model]
        )
        if not vocab_path:
            vocab_path = find_vocab(config.rm_weights)
    reranker = TpuReranker(
        config.rm_model,
        params=params,
        tokenizer=(
            load_tokenizer(
                vocab_path, scheme=scheme_for_model(config.rm_model)
            )
            if vocab_path
            else None
        ),
        max_tokens=config.rm_max_tokens,
        quantize=config.rm_quantize,
    )
    synthetic = []
    if params is None:
        synthetic.append("random-init RM weights (no RM_WEIGHTS)")
    elif not head_loaded:
        synthetic.append(
            "a RANDOM-INIT reward head (encoder-only checkpoint — no "
            "pooler/classifier weights in RM_WEIGHTS)"
        )
    if isinstance(reranker.tokenizer, HashTokenizer):
        synthetic.append(
            "hash tokenizer (no RM_VOCAB and no vocab/spm file beside "
            "RM_WEIGHTS)"
        )
    if synthetic:
        detail = (
            f"RM_MODEL={config.rm_model} would serve "
            + " and ".join(synthetic)
            + " — reward re-ranking would be garbage that looks valid."
        )
        if not _synthetic_params_allowed(allow_synthetic):
            raise ValueError(
                detail
                + " Point RM_WEIGHTS at a checkpoint, or opt in with "
                "LWC_ALLOW_RANDOM_PARAMS=1 (tests/demo only)."
            )
        import logging

        logging.getLogger("lwc.serve").warning(
            "SYNTHETIC RM PARAMS: %s Serving anyway "
            "(LWC_ALLOW_RANDOM_PARAMS / fake-upstream demo mode).",
            detail,
        )
    if config.mesh_enabled:
        import jax

        from ..parallel.mesh import make_mesh
        from ..parallel.sharding import shard_reranker_mesh

        shape = config.mesh_shape
        mesh = make_mesh(
            dp=shape[0] if shape else None,
            tp=shape[1] if shape else 1,
            devices=jax.local_devices(),
        )
        shard_reranker_mesh(reranker, mesh)
    return reranker


def build_judge(config: Config, allow_synthetic: bool = False):
    """The local judge panel (POST /consensus {"scorer": "judge"}): a
    causal sparse-expert decoder from env config, its program compiled for
    the default panel before the server listens.  Same synthetic-params
    discipline as ``build_embedder``."""
    if not config.judge_model:
        return None
    import logging

    from ..models.judge import JUDGE_PRESETS, TpuJudge, load_judge_params
    from ..models.tokenizer import HashTokenizer, load_tokenizer

    if config.judge_model not in JUDGE_PRESETS:
        raise ValueError(
            f"JUDGE_MODEL={config.judge_model!r} is not a known preset; "
            f"valid values: {', '.join(sorted(JUDGE_PRESETS))}"
        )
    log = logging.getLogger("lwc.serve")
    preset = JUDGE_PRESETS[config.judge_model]
    params = None
    vocab_path = config.judge_vocab
    if config.judge_weights:
        from ..models.loading import find_vocab

        with startup.stopwatch("weights") as loading:
            params, preset = load_judge_params(config.judge_weights, preset)
        log.info(
            "judge: %d layers of %s loaded in %.1fs",
            preset.num_layers, config.judge_weights, loading.seconds,
        )
        if not vocab_path:
            vocab_path = find_vocab(config.judge_weights)
    with startup.stopwatch("weights"):
        judge = TpuJudge(
            config.judge_model,
            params=params,
            config=preset,
            tokenizer=(
                load_tokenizer(vocab_path, scheme="deberta")
                if vocab_path
                else None
            ),
            max_tokens=config.judge_max_tokens,
            quantize=config.judge_quantize,
        )
        _params_on_device(judge)
    synthetic = []
    if params is None:
        synthetic.append("random-init judge weights (no JUDGE_WEIGHTS)")
    if isinstance(judge.tokenizer, HashTokenizer):
        synthetic.append(
            "hash tokenizer (no JUDGE_VOCAB and no vocab/spm file beside "
            "JUDGE_WEIGHTS)"
        )
    if synthetic:
        detail = (
            f"JUDGE_MODEL={config.judge_model} would serve "
            + " and ".join(synthetic)
            + " — its votes would be garbage that looks valid."
        )
        if not _synthetic_params_allowed(allow_synthetic):
            raise ValueError(
                detail
                + " Point JUDGE_WEIGHTS at a checkpoint, or opt in with "
                "LWC_ALLOW_RANDOM_PARAMS=1 (tests/demo only)."
            )
        log.warning(
            "SYNTHETIC JUDGE PARAMS: %s Serving anyway "
            "(LWC_ALLOW_RANDOM_PARAMS / fake-upstream demo mode).",
            detail,
        )
    judge.device_timing = config.metrics_device_timing
    with startup.stopwatch("warmup"):
        warmed_s = judge.warmup()
    log.info(
        "judge: panel program (calls=3, s=%d) compiled in %.1fs",
        judge.max_tokens, warmed_s,
    )
    return judge


class _ArchivingClient:
    """Wraps a client so every served UNARY completion is archived (its id
    becomes referenceable by later requests); everything else delegates.
    ``put(result, params)`` receives the request too — the score path
    archives it beside the completion, feeding training-table learning
    (weights/learning.py).

    Streaming: by default streamed responses are consumed by the HTTP
    caller chunk-by-chunk and are NOT archived (the reference archives
    nothing, so parity holds; only unary callers feed rescore/learning).
    With ``stream_fold`` set (ARCHIVE_STREAMING=1), the chunk stream is
    teed into the merge algebra — each chunk ``push``ed into a running
    aggregate, the folded unary archived at clean stream end (``unary =
    fold(chunks)``, the types/base.py contract, mirroring how unary is
    *defined* in the reference, chat client.rs:170-191).  A stream the
    client abandons mid-way archives nothing: a partial fold would be
    indistinguishable from a complete completion."""

    def __init__(self, inner, put, stream_fold=None):
        self._inner = inner
        self._put = put
        self._stream_fold = stream_fold

    def __getattr__(self, name):
        return getattr(self._inner, name)

    async def create_unary(self, ctx, params):
        result = await self._inner.create_unary(ctx, params)
        self._put(result, params)
        return result

    async def create_streaming(self, ctx, params):
        stream = await self._inner.create_streaming(ctx, params)
        if self._stream_fold is None:
            return stream
        return self._tee(stream, params)

    async def _tee(self, stream, params):
        aggregate = None
        foldable = True
        completed = False
        try:
            async for chunk in stream:
                # the fold is a side-channel and must NEVER break the
                # client-facing stream: error items (e.g. ChatError
                # frames the chat stream yields mid-stream) and any
                # clone/push failure poison the fold — nothing gets
                # archived — while every chunk still reaches the client.
                # Error isolation is identical with and without the tee.
                if foldable:
                    try:
                        if isinstance(chunk, Exception):
                            foldable = False
                        elif aggregate is None:
                            aggregate = chunk.clone()
                        else:
                            aggregate.push(chunk)
                    except Exception:
                        foldable = False
                        aggregate = None
                yield chunk
            completed = True
        finally:
            # propagate close (client disconnects surface as
            # GeneratorExit here) so the upstream connection is released
            # promptly — same contract as gateway._respond_streaming
            aclose = getattr(stream, "aclose", None)
            if aclose is not None:
                await aclose()
        if completed and foldable and aggregate is not None:
            try:
                self._put(self._stream_fold(aggregate), params)
            except Exception:
                import logging

                logging.getLogger("lwc.serve").warning(
                    "streamed completion could not be archived "
                    "(fold/store failure); the response was served intact",
                    exc_info=True,
                )


def _warmup_embedder(
    embedder,
    specs: list,
    r_buckets: list = (),
    aot: bool = True,
    ring_buckets: list = (),
) -> None:
    """Pre-compile the consensus path for the given ``NxS`` shapes at
    startup (WARMUP env, serve/config.py) so the first real request
    doesn't pay a multi-second jit compile.  Each spec warms the
    single-request dispatch at exactly that (candidate count, seq
    bucket); invalid specs fail startup loudly (a silently skipped
    warmup defeats its purpose).  S snaps to the serving seq bucket the
    tokenizer would pick, so the compiled shape is the one traffic
    actually hits.

    ``r_buckets`` (WARMUP_R) additionally warms the batcher's grouped
    dispatch (``consensus_confidence_tokens_many``) at each concurrency
    bucket per shape — a distinct XLA specialization per power-of-two R,
    which the single-request warm does NOT cover (ADVICE r4): without it
    the first concurrent burst at a warmed NxS still pays the compile.

    ``aot`` (WARMUP_AOT, default on) compiles each bucket ahead-of-time
    (``TpuEmbedder.aot_warmup``: ``.lower().compile()``, no device
    dispatch) and serves warmed buckets from the embedder's executable
    table — zero jit specializations after startup.  First-class mesh
    embedders (MESH_ENABLED) take the AOT branch too: their buckets
    lower with sharded avals into per-(mesh-shape, bucket) executables.
    The dispatch loop below stays for ``WARMUP_AOT=0`` alone: it warms
    the lazy-jit path by running each shape once.

    ``ring_buckets`` (LONG_CONTEXT_WARMUP NxS specs) warms the
    sequence-parallel ring dispatch on an sp-bearing mesh — AOT only,
    and a no-op unless the embedder's mesh carries an sp axis."""
    import logging
    import time as _time

    import numpy as np

    from ..models.embedder import _seq_bucket

    log = logging.getLogger("lwc.serve")
    # dedup AFTER bucket snapping: 64x100 and 64x112 are the same
    # compiled shape, and a second dispatch of it is pure wasted startup
    snapped = list(
        dict.fromkeys(
            (n, _seq_bucket(s, embedder.max_tokens)) for n, s in specs
        )
    )
    if aot:
        for label, dt in embedder.aot_warmup(
            snapped, r_buckets, ring_buckets=ring_buckets
        ):
            log.info("warmup AOT %s compiled in %.1fs", label, dt)
        return
    for n, s in snapped:
        ids = np.zeros((n, s), dtype=np.int32)
        mask = np.zeros((n, s), dtype=np.int32)
        mask[:, 0] = 1  # one real token per row: a clean forward
        t0 = _time.perf_counter()
        np.asarray(embedder.consensus_confidence_tokens(ids, mask))
        log.info(
            "warmup %dx%d compiled in %.1fs",
            n, s, _time.perf_counter() - t0,
        )
        for r in r_buckets:
            if r < 2:
                continue  # R=1 groups dispatch the single-request path
            ids_r = np.zeros((r, n, s), dtype=np.int32)
            mask_r = np.zeros((r, n, s), dtype=np.int32)
            mask_r[:, :, 0] = 1
            t0 = _time.perf_counter()
            np.asarray(
                embedder.consensus_confidence_tokens_many(ids_r, mask_r)
            )
            log.info(
                "warmup grouped R=%d %dx%d compiled in %.1fs",
                r, n, s, _time.perf_counter() - t0,
            )


def _build_cpu_fallback(config: Config, fake_upstream: bool):
    """(embedder, device-context factory) for DEVICE_WATCHDOG_CPU_FALLBACK:
    a CPU twin of the serving embedder, built at startup (weights reload
    from the same checkpoint) while the device is still healthy.  Mesh
    flags and int8 quantization are stripped — the fallback's whole job
    is to exist off the wedged device, not to be fast — and every
    dispatch through it runs under ``jax.default_device(cpu)`` so its
    computations never queue behind the hung dispatch.  Failure to build
    one degrades to watchdog-without-fallback (device endpoints shed
    while unhealthy) rather than failing startup."""
    import dataclasses
    import logging

    log = logging.getLogger("lwc.serve")
    try:
        import jax

        cpu = jax.devices("cpu")[0]
        with jax.default_device(cpu):
            fallback = build_embedder(
                dataclasses.replace(
                    config,
                    mesh_enabled=False,
                    mesh_shape=None,
                    embedder_quantize="none",
                ),
                allow_synthetic=fake_upstream,
            )
    except Exception:
        log.warning(
            "DEVICE_WATCHDOG_CPU_FALLBACK: could not build the CPU "
            "fallback embedder; device endpoints will shed while the "
            "watchdog holds the device unhealthy",
            exc_info=True,
        )
        return None, None

    def fallback_context():
        import jax

        return jax.default_device(jax.devices("cpu")[0])

    log.info(
        "device watchdog CPU fallback ready (%s)", config.embedder_model
    )
    return fallback, fallback_context


def _device_stats(embedder, reranker, judge=None) -> dict:
    """The ``device`` section of /metrics (and the start-up log line):
    what this process computes on.  The models pick their param dtype,
    their quantized-matmul implementation and Pallas interpret mode from
    the platform; this is where an operator — or chip_smoke.py — sees
    which way those went.  Dtypes are read off the params themselves."""
    import jax

    from ..utils import device_summary

    devices = jax.local_devices()
    per_device = []
    for d in devices:
        row = {"id": d.id}
        stats = d.memory_stats()  # None where the backend keeps none (CPU)
        if stats:
            # ``*_in_use`` counts buffers; a compiled program's
            # temporaries live in a region the runtime RESERVES (it
            # grows to the largest program run and is kept), so what the
            # device holds is the two together
            for key in (
                "bytes_in_use",
                "peak_bytes_in_use",
                "bytes_reserved",
                "peak_bytes_reserved",
            ):
                row[key] = stats.get(key)
        per_device.append(row)
    out = {
        **device_summary(),
        "pallas_interpret": devices[0].platform != "tpu",
        "devices": per_device,
    }
    for prefix, model in (("", embedder), ("rm_", reranker), ("judge_", judge)):
        if model is not None:
            out[prefix + "param_dtype"] = model.params["token_embed"].dtype.name
            out[prefix + "quantize"] = model.config.quantize
    return out


def build_service(
    config: Config,
    fake_upstream: bool = False,
    fake_upstream_port: int = FAKE_PORT,
    compile_cache=None,
):
    import os

    api_bases = config.api_bases()
    if fake_upstream:
        api_bases = [
            ApiBase(f"http://127.0.0.1:{fake_upstream_port}/v1", "fake-key")
        ]
    if config.archive_path and os.path.exists(config.archive_path):
        store = archive.InMemoryArchive.load(config.archive_path)
    else:
        store = archive.InMemoryArchive()
    # bound service memory growth (ARCHIVE_MAX_COMPLETIONS; 0 = unbounded)
    store.max_completions = config.archive_max_completions or None
    store.enforce_cap()  # an over-cap loaded snapshot trims at startup
    if config.archive_path:
        # fail FAST on an unwritable path: the shutdown save is the last
        # moment we could find out, and by then the archive would be lost.
        # A tiny probe, not a full save — re-serializing a just-loaded
        # multi-GB snapshot would double startup IO for nothing.
        from ..utils.io import probe_writable_config

        probe_writable_config(
            config.archive_path,
            "ARCHIVE_PATH",
            "snapshots would be lost at shutdown",
        )
        if not os.path.exists(config.archive_path):
            store.save(config.archive_path)
    transport = AiohttpTransport(
        connect_timeout_ms=config.connect_timeout_millis
    )
    # FAULT_PLAN (chaos runs): wrap the real transport in the seeded
    # fault injector; the wrapper's close() closes the inner session
    fault_plan = config.fault_injection_plan()
    if fault_plan is not None:
        from ..resilience import FaultInjectionTransport

        transport = FaultInjectionTransport(transport, fault_plan)
    resilience = config.resilience_policy()
    chat_client = DefaultChatClient(
        transport,
        api_bases,
        backoff=config.backoff_policy(),
        user_agent=config.openai_user_agent,
        x_title=config.openai_x_title,
        referer=config.openai_referer,
        first_chunk_timeout_ms=config.first_chunk_timeout_millis,
        other_chunk_timeout_ms=config.other_chunk_timeout_millis,
        archive_fetcher=store,
        resilience=resilience,
        # hostile-upstream byte budgets (JUDGE_STREAM_MAX_BYTES /
        # SSE_MAX_EVENT_BYTES): cap trips degrade the judge leg instead
        # of growing host memory without bound
        judge_stream_max_bytes=config.judge_stream_max_bytes,
        sse_max_event_bytes=config.sse_max_event_bytes,
    )
    model_registry = registry.InMemoryModelRegistry()
    # --fake-upstream is demo/test mode: synthetic embedder params are
    # allowed (still logged); production startup refuses them
    if config.embedder_model or config.rm_model or config.judge_model:
        # the backend's own start-up (the TPU runtime's, seconds) is paid
        # by whoever touches a device first: here, so that ``weights`` of
        # the ``startup`` section holds the checkpoints alone
        # (``listening`` holds it either way)
        import jax

        jax.devices()
    embedder = build_embedder(config, allow_synthetic=fake_upstream)
    reranker = build_reranker(config, allow_synthetic=fake_upstream)
    judge = build_judge(config, allow_synthetic=fake_upstream)
    if embedder is not None or reranker is not None or judge is not None:
        import logging

        # before warmup: if a compile hangs or dies, what it ran on is
        # already in the log
        logging.getLogger("lwc.serve").info(
            "device: %s", _device_stats(embedder, reranker, judge)
        )
    if embedder is not None:
        # per-bucket device timing (phases/roofline sections), measured
        # enqueue-to-ready: under the batcher the readiness wait runs on
        # a waiter thread (models/dispatch_seam.py), so timing no longer
        # serializes the dispatch pipeline; =0 only darkens the device
        # rows and roofline attainment
        embedder.device_timing = config.metrics_device_timing
    if embedder is not None and config.aot_cache_dir:
        # AOT_CACHE_DIR: fleet-shared serialized-executable store — the
        # warmup below deserializes any bucket a peer (or a previous run
        # of this replica) already compiled, and persists what it
        # compiles itself.  Attached before warmup so the very first
        # _aot_compile call can restore.
        from ..models.aot_store import AotStore

        embedder.aot_store = AotStore(
            config.aot_cache_dir, meta=embedder.aot_cache_meta()
        )
    if embedder is not None and config.warmup:
        with startup.stopwatch("warmup"):
            _warmup_embedder(
                embedder,
                config.warmup,
                config.warmup_r,
                aot=config.warmup_aot,
                ring_buckets=config.long_context_warmup,
            )
    # mesh fault domains (MESH_FAULT_ENABLED, resilience/meshfault.py):
    # the downsize ladder is declared — and every fallback rung AOT-warmed
    # under its own ("mesh", dp, tp) key namespace — at startup, so a
    # mid-traffic downsize is a param re-shard + executable-table swap,
    # never a compile storm
    meshfault = None
    if (
        config.mesh_fault_enabled
        and embedder is not None
        and getattr(embedder, "mesh_mode", False)
    ):
        import logging

        from ..resilience import MeshFaultManager

        meshfault = MeshFaultManager(
            embedder,
            shape=embedder.mesh_shape,
            transient_retries=config.mesh_fault_transient_retries,
            probe_millis=config.mesh_fault_probe_millis,
            fault_plan=config.device_fault_injection_plan(),
        )
        _mf_log = logging.getLogger("lwc.serve")
        _mf_log.info(
            "mesh fault ladder: %s",
            " -> ".join(f"{d}x{t}" for d, t in meshfault.build_ladder()),
        )
        if config.warmup and config.warmup_aot:
            from ..models.embedder import _seq_bucket

            snapped = list(
                dict.fromkeys(
                    (n, _seq_bucket(s, embedder.max_tokens))
                    for n, s in config.warmup
                )
            )
            for label, dt in meshfault.warm_ladder(
                snapped, config.warmup_r, config.long_context_warmup
            ):
                _mf_log.info(
                    "mesh fault ladder AOT %s compiled in %.1fs", label, dt
                )
        if config.mesh_fault_probe_millis > 0:
            # real recovery validation: try_recover re-shards to the
            # full mesh and runs this tiny dispatch across it BEFORE
            # reporting recovered; a device-classified raise rolls the
            # upsize back.  Without it (and without a fault plan) the
            # prober would blindly upsize and flap down again on the
            # next real dispatch.  Uses the first warmed consensus spec
            # so in a warmed service the probe hits an AOT executable.
            import numpy as _np

            from ..models.embedder import _seq_bucket

            if config.warmup:
                _pn, _ps = config.warmup[0]
                _probe_shape = (_pn, _seq_bucket(_ps, embedder.max_tokens))
            else:
                _probe_shape = (2, _seq_bucket(8, embedder.max_tokens))

            def _mesh_probe(shape=_probe_shape):
                n, s = shape
                ids = _np.zeros((n, s), dtype=_np.int32)
                mask = _np.zeros((n, s), dtype=_np.int32)
                mask[:, 0] = 1  # one real token per row: a clean forward
                _np.asarray(
                    embedder.consensus_confidence_tokens(ids, mask)
                )

            meshfault.probe_fn = _mesh_probe
    from .metrics import Metrics

    # metrics exist regardless of the device side: the result cache's
    # counters (and the HTTP series) are host-only observability
    metrics = Metrics()
    # set-up's stopwatches (serve/startup.py); ``compile`` as it stands
    # when read
    metrics.register_provider(
        "startup", lambda: startup.snapshot(compile_cache)
    )
    if compile_cache is not None:
        metrics.register_provider("compile_cache", compile_cache.snapshot)
    if embedder is not None or reranker is not None or judge is not None:
        metrics.register_provider(
            "device", lambda: _device_stats(embedder, reranker, judge)
        )
    if embedder is None and judge is not None:
        # a judge alone: its one entry point's specializations, and every
        # compilation the backend was asked for, under the same section
        _compiles = (
            compile_cache.compiles if compile_cache is not None else dict
        )
        metrics.register_provider(
            "jit",
            lambda: {"specializations": judge.jit_stats(), **_compiles()},
        )
    if embedder is not None:
        # jit-cache introspection on /metrics: AOT bucket count + live
        # specialization counts (asserting "zero new specializations
        # post-warmup" is observable in production, not just in tests)
        # and every compilation the backend was asked for, the helper
        # programs no entry point names included (config.py)
        def _jit_stats():
            stats = embedder.jit_stats()
            if judge is not None:
                stats["specializations"].update(judge.jit_stats())
            if compile_cache is not None:
                stats.update(compile_cache.compiles())
            return stats

        metrics.register_provider("jit", _jit_stats)
    if embedder is not None and getattr(embedder, "mesh_mode", False):
        # mesh-serving introspection: the shape traffic shards over and
        # the per-(mesh-shape, bucket) AOT coverage

        def _mesh_stats():
            dp, tp = embedder.mesh_shape
            sp = getattr(embedder, "mesh_sp", 1)
            return {
                "enabled": True,
                "dp": dp,
                "tp": tp,
                "sp": sp,
                "devices": dp * tp * sp,
                "ring": bool(embedder.ring_available()),
                "ring_max_tokens": embedder.ring_max_tokens,
                "aot_buckets": sum(
                    1 for key in embedder._aot if key and key[0] == "mesh"
                ),
            }

        metrics.register_provider("mesh", _mesh_stats)
    if meshfault is not None:
        # degraded-mesh introspection: current/full shape, epoch,
        # downsize/upsize/re-dispatch counters, faulted device ids
        metrics.register_provider("meshfault", meshfault.snapshot)
    score_cache = None
    embed_cache = None
    if config.score_cache_ttl_sec > 0:
        from ..cache import EmbeddingCache, ScoreCache

        score_cache = ScoreCache(
            config.score_cache_ttl_sec,
            config.score_cache_max_bytes,
            config.score_cache_dir,
        )
        if config.score_cache_embed:
            embed_cache = EmbeddingCache(
                config.score_cache_ttl_sec,
                config.score_cache_embed_max_bytes,
            )
    # FLEET_*: the replicated-cache tier (fleet/).  Config validation
    # guarantees the score cache exists whenever the fleet is on; the
    # coordinator serves owner-side state from it and peers publish
    # into it (fleet/handlers.py)
    fleet = None
    fleet_cfg = config.fleet_config()
    if fleet_cfg is not None and score_cache is not None:
        from ..fleet import FleetCoordinator

        fleet = FleetCoordinator(fleet_cfg)
        fleet.cache = score_cache
    # device watchdog (DEVICE_WATCHDOG_MILLIS > 0): brackets every
    # batched dispatch; a hung PJRT call flips readiness and — with the
    # CPU fallback built below — reroutes device work off the chip
    watchdog = None
    if config.device_watchdog_millis > 0:
        from ..resilience import DeviceWatchdog

        watchdog = DeviceWatchdog(
            config.device_watchdog_millis,
            interval_ms=config.device_watchdog_interval_millis,
        )
    fallback_embedder = None
    fallback_context = None
    if (
        watchdog is not None
        and config.device_watchdog_cpu_fallback
        and embedder is not None
    ):
        fallback_embedder, fallback_context = _build_cpu_fallback(
            config, fake_upstream
        )
    batcher = None
    if embedder is not None or judge is not None:
        from .batcher import DeviceBatcher

        batcher = DeviceBatcher(
            embedder,
            metrics,
            judge=judge,
            window_ms=config.batch_window_ms,
            max_batch=config.batch_max,
            pipeline_depth=config.batch_pipeline,
            max_rows=config.batch_max_rows,
            host_tokenizer_workers=config.host_tokenizer_workers,
            staging_buffers=config.staging_buffers,
            embed_cache=embed_cache,
            max_queue_depth=config.admission_max_queue_depth,
            watchdog=watchdog,
            fallback_embedder=fallback_embedder,
            fallback_context=fallback_context,
            meshfault=meshfault,
        )
    if watchdog is not None:
        import logging

        _log = logging.getLogger("lwc.serve")
        _batcher = batcher
        _meshfault = meshfault

        def _mesh_absorbs() -> bool:
            # MESH_FAULT_ENABLED precedence (serve/config.py): the wedge
            # goes to the downsize ladder, not straight to the CPU twin —
            # the twin is the post-exhaustion last resort, and the
            # batcher's fault handler flips it only when downsize()
            # reports the ladder spent
            return (
                _meshfault is not None
                and _batcher is not None
                and not _batcher._use_fallback
            )

        def _on_trip(kind: str, overdue_ms: float) -> None:
            _log.error(
                "device watchdog TRIPPED: %s dispatch overdue after "
                "%.0f ms%s",
                kind,
                overdue_ms,
                (
                    "; escalating to the mesh fault ladder"
                    if _mesh_absorbs()
                    else "; routing device work to the CPU fallback"
                    if _batcher is not None
                    and _batcher.fallback_embedder is not None
                    else "; device endpoints will shed until it completes"
                ),
            )
            if _mesh_absorbs():
                _meshfault.note_watchdog_trip()
                return
            if _batcher is not None:
                _batcher.use_fallback(True)

        def _on_recover() -> None:
            _log.warning(
                "device watchdog recovered: the overdue dispatch "
                "completed, device traffic resumes"
            )
            if _meshfault is not None:
                # mesh-fault mode never flipped the fallback on trip, and
                # a post-exhaustion fallback must survive the recovery —
                # a completed wedge does not un-exhaust the ladder
                return
            if _batcher is not None:
                _batcher.use_fallback(False)

        watchdog.on_trip = _on_trip
        watchdog.on_recover = _on_recover
        watchdog.start()

    # LOCK_WITNESS=1: runtime lockdep (analysis/witness.py) — wrap the
    # registered threading primitives so real acquisition order is
    # validated against the declared DAG (analysis/concurrency_model.py)
    # while the server runs; the snapshot rides /metrics and the drain
    # path prints the summary the soak drill asserts on
    witness = None
    if config.lock_witness:
        from ..analysis.witness import LockWitness
        from ..obs import phases as _obs_phases
        from ..obs import quality as _obs_quality

        witness = LockWitness()
        _obs_phases._AGG._lock = witness.wrap_lock(
            "PhaseAggregator._lock", _obs_phases._AGG._lock
        )
        _obs_quality._AGG._lock = witness.wrap_lock(
            "QualityAggregator._lock", _obs_quality._AGG._lock
        )
        if watchdog is not None:
            watchdog._lock = witness.wrap_lock(
                "DeviceWatchdog._lock", watchdog._lock
            )
        if batcher is not None:
            batcher._stats_lock = witness.wrap_lock(
                "DeviceBatcher._stats_lock", batcher._stats_lock
            )
        if meshfault is not None:
            meshfault._lock = witness.wrap_lock(
                "MeshFaultManager._lock", meshfault._lock
            )
            witness.wrap_gate(meshfault._shape_gate)
        pool = getattr(embedder, "staging_pool", None)
        if pool is not None:
            pool._lock = witness.wrap_lock("StagingPool._lock", pool._lock)
        metrics.register_provider("lock_witness", witness.snapshot)

    # admission gate: always present (with every knob 0 it never sheds,
    # it only tracks in-flight work for the drain path); device-
    # dependent endpoints additionally shed while the watchdog holds
    # the device unhealthy and no CPU fallback can absorb the work
    from ..resilience import AdmissionController

    def _device_gate():
        if watchdog is not None and not watchdog.healthy():
            if batcher is None or batcher.fallback_embedder is None:
                return "device_unhealthy"
        return None

    # MEMGUARD: host memory governor (resilience/memguard.py) — soft
    # pressure shrinks cache/trace budgets and decays the AIMD limit,
    # hard pressure sheds at admission with shed_reason "memory".  None
    # when disabled or when /proc/meminfo is unreadable and no explicit
    # watermarks were given (the governor never guesses)
    memguard = config.memguard()
    admission = AdmissionController(
        config.admission_config(),
        device_gate=_device_gate,
        mem_gate=memguard.gate if memguard is not None else None,
    )
    if meshfault is not None:
        # every shape change rescales admission (hard cap + AIMD limit)
        # and the batcher's group capacity to the surviving chip fraction
        meshfault.rescale_hooks.append(admission.rescale)
        if batcher is not None:
            meshfault.rescale_hooks.append(batcher.rescale_capacity)
    weight_fetchers = WeightFetchers()
    tables = None
    if embedder is not None:
        from ..weights.training_table import (
            TpuTrainingTableFetcher,
            TrainingTableStore,
        )

        if config.tables_path and os.path.exists(config.tables_path):
            tables = TrainingTableStore.load(config.tables_path)
        else:
            tables = TrainingTableStore()
        if config.tables_path:
            from ..utils.io import probe_writable_config

            probe_writable_config(
                config.tables_path,
                "TABLES_PATH",
                "learned weights would be lost at shutdown",
            )
        weight_fetchers = WeightFetchers(
            training_table_fetcher=TpuTrainingTableFetcher(
                embedder, tables, batcher=batcher
            )
        )
    # QUALITY_*: drift-window knobs applied to the process-global
    # consensus-quality aggregator (always on, like the phase aggregate)
    from ..obs import configure_quality

    configure_quality(
        window=config.quality_window,
        drift_threshold=config.quality_drift_threshold,
    )
    # LEDGER_*: per-request consensus-outcome records (obs/ledger.py);
    # None keeps the tally ledger-free
    ledger = config.outcome_ledger()
    # WEIGHTS_*: versioned live judge-weight tables behind atomic
    # hot-swap (weights/live.py); None keeps static-weight behavior
    live_weights = config.live_weights()
    if live_weights is not None and config.weights_path:
        from ..utils.io import probe_writable_config

        probe_writable_config(
            config.weights_path,
            "WEIGHTS_PATH",
            "hot-swapped weight tables would be lost at shutdown",
        )
    score_client = ScoreClient(
        chat_client,
        model_registry,
        weight_fetchers=weight_fetchers,
        archive_fetcher=store,
        # ballots stored alongside enable logprob re-extraction in batch
        # re-score (archive/rescore.py revote)
        ballot_sink=store.put_ballot if config.archive_write else None,
        # SCORE_CACHE_TTL > 0: content-addressed result cache with
        # single-flight dedup (cache/); None preserves pre-cache behavior
        cache=score_cache,
        # RESILIENCE_*: shared retry budget + weight-quorum degradation
        resilience=resilience,
        # JUDGE_BIAS_PLAN: deterministic vote perturbation (drills only)
        bias_plan=config.judge_bias_injection_plan(),
        ledger=ledger,
        # FLEET_*: cross-replica peer fetch + single-flight leases; None
        # preserves single-replica behavior
        fleet=fleet,
        # HOST_FASTPATH: fixed-point vectorized tally (clients/tally.py)
        host_fastpath=config.host_fastpath,
        live_weights=live_weights,
    )
    multichat_client = MultichatClient(
        chat_client, model_registry, archive_fetcher=store
    )
    gw_chat, gw_score, gw_multichat = chat_client, score_client, multichat_client
    if config.archive_write:
        from ..types import chat_response, multichat_response, score_response

        def put_score(result, params):
            store.put_score(result)
            store.put_score_request(result.id, params)

        def fold(unary_cls):
            # ARCHIVE_STREAMING: tee streams into the merge-algebra fold
            if not config.archive_streaming:
                return None
            return unary_cls.from_streaming

        gw_chat = _ArchivingClient(
            chat_client,
            lambda result, params: store.put_chat(result),
            stream_fold=fold(chat_response.ChatCompletion),
        )
        gw_score = _ArchivingClient(
            score_client,
            put_score,
            stream_fold=fold(score_response.ChatCompletion),
        )
        gw_multichat = _ArchivingClient(
            multichat_client,
            lambda result, params: store.put_multichat(result),
            stream_fold=fold(multichat_response.ChatCompletion),
        )
    # the drain/readiness state machine: SIGTERM flips /readyz, stops
    # admission, drains in-flight streams + the batcher queue (bounded
    # by DRAIN_TIMEOUT_MILLIS), flushes the cache disk tier once
    from .lifecycle import Lifecycle

    # TRACE_*: request tracing (obs/); None preserves untraced behavior.
    # Hoisted so the memory governor can shrink the ring under pressure
    trace_sink = config.trace_sink()
    if memguard is not None:
        memguard.govern(
            caches=[c for c in (score_cache, embed_cache) if c is not None],
            sinks=[s for s in (trace_sink,) if s is not None],
            admission=admission,
        )
        memguard.start()
    lifecycle = Lifecycle(
        admission=admission,
        batcher=batcher,
        caches=(score_cache, embed_cache),
        watchdog=watchdog,
        memguard=memguard,
        meshfault=meshfault,
        drain_timeout_ms=config.drain_timeout_millis,
        # FLEET_*: the drain hands this replica's hot set to its
        # post-drain owners before /readyz flips
        fleet=fleet,
    )
    app = build_app(
        gw_chat,
        gw_score,
        gw_multichat,
        embedder,
        metrics=metrics,
        profile_dir=config.profile_dir,
        batcher=batcher,
        reranker=reranker,
        judge=judge,
        resilience=resilience,
        fault_plan=fault_plan,
        admission=admission,
        lifecycle=lifecycle,
        watchdog=watchdog,
        trace_sink=trace_sink,
        ledger=ledger,
        fleet=fleet,
        # HOST_FASTPATH: splice-serialized SSE frames (serve/frames.py)
        host_fastpath=config.host_fastpath,
        memguard=memguard,
        # MAX_BODY_BYTES: aiohttp client_max_size — every route,
        # /fleet/v1 included, 413s render the payload_too_large envelope
        max_body_bytes=config.max_body_bytes,
        # WEIGHTS_* / OFFLINE_*: live weight hot-swap endpoints and the
        # offline-lane rescore driver (ISSUE 20)
        live_weights=live_weights,
        offline_enabled=config.offline_enabled,
        offline_inflight=config.offline_inflight,
    )
    app[ARCHIVE_KEY] = store
    # one lock for every handler that mutates the archive/tables
    archive_lock = asyncio.Lock()
    app.router.add_post(
        "/archive/rescore",
        _rescore_handler(
            store,
            archive_lock,
            # the batched tally shards over every axis of the serving mesh
            mesh=getattr(embedder, "mesh", None),
        ),
    )
    if tables is not None:
        app[TABLES_KEY] = tables
        app.router.add_post(
            "/weights/learn",
            _learn_handler(store, embedder, tables, archive_lock),
        )
    if config.archive_path:
        path = config.archive_path

        async def _save_archive(app):
            store.save(path)

        app.on_cleanup.append(_save_archive)
    if tables is not None and config.tables_path:
        tables_path = config.tables_path

        async def _save_tables(app):
            tables.save(tables_path)

        app.on_cleanup.append(_save_tables)

    async def _close_transport(app):
        await transport.close()

    app.on_cleanup.append(_close_transport)
    if fleet is not None:

        async def _close_fleet(app):
            await fleet.close()

        app.on_cleanup.append(_close_fleet)
    if watchdog is not None:
        # signal-free shutdowns (tests, embedding into another runner)
        # must still stop the monitor thread; stop() is idempotent with
        # the drain path's
        async def _stop_watchdog(app):
            watchdog.stop()

        app.on_cleanup.append(_stop_watchdog)
    if witness is not None:
        # the soak drill greps this line after SIGTERM: a clean run
        # reports its real acquisition evidence on the way out
        async def _report_witness(app):
            print(witness.summary_line(), flush=True)

        app.on_cleanup.append(_report_witness)
    if (
        meshfault is not None
        and config.mesh_fault_probe_millis > 0
        and batcher is not None
    ):
        # recovery prober (MESH_FAULT_PROBE_MILLIS > 0): while degraded,
        # periodically re-validate the full mesh (probe_fn above: a real
        # full-mesh dispatch) and upsize back.  try_recover holds the
        # shape gate's exclusive side across the re-shard + probe, so it
        # is serialized with in-flight dispatches regardless of which
        # executor thread runs it; repeated probe failures back off
        # exponentially (each failed probe is a re-shard + rollback —
        # work worth not repeating every interval against a dead chip).
        probe_sec = config.mesh_fault_probe_millis / 1e3
        prober_tasks: list = []

        async def _start_mesh_prober(app):
            loop = asyncio.get_running_loop()

            async def _probe_loop():
                while True:
                    await asyncio.sleep(
                        probe_sec * meshfault.probe_backoff_scale()
                    )
                    if meshfault.degraded:
                        await loop.run_in_executor(
                            batcher._executor, meshfault.try_recover
                        )

            prober_tasks.append(loop.create_task(_probe_loop()))

        async def _stop_mesh_prober(app):
            for task in prober_tasks:
                task.cancel()
                try:
                    await task
                except asyncio.CancelledError:
                    pass

        app.on_startup.append(_start_mesh_prober)
        app.on_cleanup.append(_stop_mesh_prober)
    return app


async def _serve(
    config: Config, fake_upstream: bool, compile_cache=None
) -> None:
    if fake_upstream:
        fake_app = web.Application()
        fake_app.router.add_post("/v1/chat/completions", _fake_upstream)
        fake_runner = web.AppRunner(fake_app)
        await fake_runner.setup()
        await web.TCPSite(fake_runner, "127.0.0.1", FAKE_PORT).start()

    app = build_service(
        config, fake_upstream=fake_upstream, compile_cache=compile_cache
    )
    runner = web.AppRunner(app)
    await runner.setup()
    await web.TCPSite(runner, config.address, config.port).start()
    startup.listening()
    print(f"listening on {config.address}:{config.port}", flush=True)

    # SIGINT/SIGTERM set a stop event instead of raising KeyboardInterrupt
    # mid-coroutine: cleanup (archive/tables snapshots, session close) then
    # runs to completion with no interrupt in flight — asyncio's default
    # handling can fire KeyboardInterrupt INSIDE a cleanup hook and lose
    # whichever snapshot hadn't been written yet
    import logging
    import signal

    stop = asyncio.Event()
    loop = asyncio.get_running_loop()
    lifecycle = app.get(LIFECYCLE_KEY)

    def _drained(task: asyncio.Task) -> None:
        if not task.cancelled() and task.exception() is not None:
            logging.getLogger("lwc.serve").error(
                "graceful drain failed; shutting down anyway",
                exc_info=task.exception(),
            )
        stop.set()

    def _on_signal() -> None:
        if lifecycle is None:
            stop.set()
            return
        # graceful drain: /readyz flips and admission stops BEFORE the
        # listener closes (runner.cleanup runs only after the drain
        # task completes and sets the stop event).  begin_drain is
        # idempotent — repeated signals join the drain in progress.
        print("draining (SIGTERM/SIGINT received)...", flush=True)
        lifecycle.begin_drain().add_done_callback(_drained)

    handled = []
    for sig in (signal.SIGINT, signal.SIGTERM):
        try:
            loop.add_signal_handler(sig, _on_signal)
            handled.append(sig)
        except (NotImplementedError, RuntimeError):
            pass
    try:
        await stop.wait()
    finally:
        # handlers stay installed THROUGH cleanup: a repeated signal
        # (operator mashing ctrl-C, a supervisor forwarding the signal)
        # must not interrupt a snapshot mid-write
        await runner.cleanup()
        for sig in handled:
            loop.remove_signal_handler(sig)


def main() -> None:
    parser = argparse.ArgumentParser("llm-weighted-consensus-tpu gateway")
    parser.add_argument("--port", type=int, default=None)
    parser.add_argument("--address", default=None)
    parser.add_argument(
        "--fake-upstream",
        action="store_true",
        help="serve against a loopback scripted provider (no API keys)",
    )
    args = parser.parse_args()
    import logging

    # the package's own start-up lines (device, warmup compile times) at
    # INFO; everything else — aiohttp's per-request access log — at WARNING
    logging.basicConfig(
        level=logging.WARNING,
        format="%(asctime)s %(name)s %(levelname)s %(message)s",
    )
    logging.getLogger("lwc").setLevel(logging.INFO)
    load_dotenv()
    # must precede any jax backend use (mesh construction in build_service)
    from ..parallel.dist import maybe_initialize_distributed

    maybe_initialize_distributed()
    config = Config.from_env()
    if args.port is not None:
        config.port = args.port
    if args.address is not None:
        config.address = args.address
    if not args.fake_upstream and not config.openai_apis:
        raise SystemExit(
            "Either OPENAI_APIS or both OPENAI_API_BASE and OPENAI_API_KEY "
            "must be set (or pass --fake-upstream)"
        )
    # before the first compilation: jax decides there whether it has a
    # persistent cache
    compile_cache = configure_compile_cache()
    asyncio.run(_serve(config, args.fake_upstream, compile_cache))


if __name__ == "__main__":
    main()
