"""Host<->device embedding pipeline exposing the OpenAI wire contract.

The serve-path boundary (SURVEY §3.1 note: "the trained-weight path crosses
host<->device (PJRT) instead" of HTTP): texts are tokenized on host, padded
to bucketed static shapes (bounding jit specializations), embedded by the
jitted BERT forward, and returned both as arrays (device consumers) and as
``CreateEmbeddingResponse`` JSON (wire consumers + usage accounting that
seeds ``weight_data`` cost, score client.rs:330-337).
"""

from __future__ import annotations

from functools import partial
from typing import Iterable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..types.chat_response import Usage
from ..types.embeddings import CreateEmbeddingResponse, Embedding
from . import bert
from .configs import PRESETS, BertConfig
from .dispatch_seam import StagingPool, active_sink, dispatch
from .tokenizer import BaseTokenizer, load_tokenizer


DEFAULT_VOTE_TEMPERATURE = 0.05


@partial(
    jax.jit, static_argnames=("n", "config", "pooling", "use_fused")
)
def _embed_and_vote(
    params, ids, mask, temperature, n, config, pooling, use_fused
):
    """Single-dispatch self-consistency: encoder forward + cosine consensus
    vote fused under one jit so nothing round-trips the host between them
    (the serving hot path: one upload, one tiny download).  At the default
    temperature the vote runs in the fused Pallas kernel (VMEM-resident
    normalize+cosine+softmax, which bakes the temperature in); any other
    temperature takes the jnp composition with temperature TRACED, so
    user-supplied values never trigger recompiles.  Rows past ``n`` are
    dp-alignment padding (sliced off before the vote so they cannot
    perturb the softmax)."""
    from ..ops.kernels import fused_cosine_vote
    from ..ops.similarity import dyn_cosine_vote

    emb = bert.embed(params, ids, mask, config, pooling=pooling)
    with jax.named_scope("consensus_vote"):
        if use_fused:
            return fused_cosine_vote(
                emb[:n], temperature=DEFAULT_VOTE_TEMPERATURE
            )
        return dyn_cosine_vote(emb[:n], temperature)


@partial(
    jax.jit, static_argnames=("n", "config", "pooling", "mesh")
)
def _mesh_embed_and_vote(
    params, ids, mask, temperature, n, config, pooling, mesh
):
    """Mesh-serving twin of ``_embed_and_vote``: encoder forward (params
    Megatron-split over ``tp``, batch rows over ``dp`` via the input
    shardings the caller staged) + the dp-sharded consensus reduction
    (parallel/collectives.py) fused under ONE jit-with-shardings
    dispatch, so embed and vote never round-trip the host and the vote's
    all-gather/psum ride ICI.  Temperature is always TRACED here — the
    fused Pallas vote is a single-device kernel (interpret-mode on CPU)
    and never runs under SPMD — so user-supplied values cannot trigger
    recompiles.  Rows at and past ``n`` are dp-alignment padding, masked
    inside the sharded vote (``n_valid``) rather than sliced pre-vote: a
    pre-vote slice would break the even dp row split."""
    from ..parallel.collectives import sharded_cosine_vote

    emb = bert.embed(params, ids, mask, config, pooling=pooling)
    with jax.named_scope("consensus_vote"):
        return sharded_cosine_vote(emb, mesh, temperature, n_valid=n)


@partial(
    jax.jit, static_argnames=("r", "n", "config", "pooling")
)
def _embed_and_vote_many(
    params, ids, mask, temperature, r, n, config, pooling
):
    """Batched self-consistency: ids/mask[>=R*N, S] -> confidence[R, N].

    R concurrent requests share ONE device dispatch (dynamic batching —
    the encoder sees one [R*N, S] batch), amortizing the per-dispatch
    host<->device round-trip.
    The vote is one R-batched einsum + softmax (same numerics as
    ``ops.similarity.cosine_consensus_vote``) rather than R unrolled
    kernel calls — compile time stays flat in R, and the caller buckets R
    to a power of two so only log2 specializations ever compile.  Rows
    past ``r*n`` are bucket/dp-alignment padding, sliced off pre-vote."""
    from ..ops.similarity import dyn_cosine_vote

    emb = bert.embed(params, ids, mask, config, pooling=pooling)
    emb = emb[: r * n].reshape(r, n, -1)
    with jax.named_scope("consensus_vote_many"):
        return dyn_cosine_vote(emb, temperature)


@partial(
    jax.jit,
    static_argnames=("config", "pooling"),
    # buf/valid are the canonical donation case: same-shape state in, new
    # state out — XLA aliases them in place, so the steady-state stream
    # loop allocates nothing (SURVEY §7's last hard-part; VERDICT r3
    # item 1a).  Callers MUST rebind to the returned buffers (they all
    # do: the old ones are dead after this call).  ids/mask are NOT
    # donated: int32 inputs alias no f32 output, so XLA ignores the
    # donation and warns — measured no-op.
    donate_argnums=(3, 4),
)
def _stream_vote_update(
    params, ids, mask, buf, valid, position, config, pooling, temperature
):
    """One streaming-consensus step on device: embed ids[1, S], write the
    vector into buf[position], set valid[position], masked revote.  The
    capacity (buf.shape[0]) is the only streaming-dependent shape, so the
    jit specializes per capacity bucket, not per candidate count."""
    from ..ops.similarity import masked_cosine_vote

    vec = bert.embed(params, ids, mask, config, pooling=pooling)[0]
    buf = buf.at[position].set(vec.astype(buf.dtype))
    valid = valid.at[position].set(1.0)
    with jax.named_scope("stream_masked_vote"):
        conf = masked_cosine_vote(buf, valid, temperature)
    return buf, valid, conf


@partial(
    jax.jit,
    static_argnames=("config", "pooling"),
    donate_argnums=(3, 4),  # see _stream_vote_update
)
def _stream_vote_update_many(
    params, ids, mask, bufs, valids, positions, config, pooling, temperature
):
    """R concurrent streaming-consensus steps in ONE dispatch: embed
    ids[R, S] as one encoder batch, then vmap the scatter + masked revote
    over the R per-stream buffers (same capacity bucket).  The serving
    micro-batcher (serve/batcher.py) groups live streams' updates into
    this; R=1 callers use ``_stream_vote_update``.  Rows past R (batch
    bucketing) are sliced off before the vmap."""
    from ..ops.similarity import masked_cosine_vote

    r = bufs.shape[0]
    vecs = bert.embed(params, ids, mask, config, pooling=pooling)[:r]

    def update(buf, valid, vec, position):
        buf = buf.at[position].set(vec.astype(buf.dtype))
        valid = valid.at[position].set(1.0)
        with jax.named_scope("stream_masked_vote"):
            conf = masked_cosine_vote(buf, valid, temperature)
        return buf, valid, conf

    return jax.vmap(update)(bufs, valids, vecs, positions)


def _bucket(n: int, cap: int) -> int:
    """Next power of two >= n (min 16), capped."""
    size = 16
    while size < n:
        size *= 2
    return min(size, cap)


# Sequence-length buckets: multiples of 16 up to 128 (XLA tiles cleanly at
# /8 boundaries; the fused-attention block constraint needs /8 too), then
# sparse above, doubling past 512 so long-context presets (bge-m3 8k)
# keep a bounded jit-specialization count.  Finer than the power-of-two
# batch buckets on purpose: a ~100-token corpus padding to 128 pays 23%
# padding FLOPs (VERDICT r3 item 1b), while a 112 bucket recovers most of
# it, and the bucket count stays small enough that lazy jit
# specialization is cheap (compile is per-bucket, once).
_SEQ_BUCKETS = (
    16, 32, 48, 64, 80, 96, 112, 128, 192, 256, 384, 512,
    1024, 2048, 4096, 8192,
)


def _seq_bucket(n: int, cap: int) -> int:
    for size in _SEQ_BUCKETS:
        if size >= n:
            return min(size, cap)
    return min(n, cap)


class TpuEmbedder:
    """A BGE-class encoder ready to embed batches on device.

    ``params=None`` random-inits (tests / no local checkpoint); pass a
    pytree from ``bert.from_hf_weights`` for real bge weights.
    Two placements: one device (as built), or first-class mesh serving
    once ``parallel.shard_embedder_mesh`` has flipped the instance
    (params partitioned by the rule tables, per-(mesh-shape, bucket) AOT
    executables).
    """

    def __init__(
        self,
        model: str = "bge-small-en",
        *,
        params: Optional[dict] = None,
        config: Optional[BertConfig] = None,
        tokenizer: Optional[BaseTokenizer] = None,
        dtype=None,
        max_tokens: int = 512,
        pooling: Optional[str] = None,
        seed: int = 0,
        quantize: str = "none",
    ) -> None:
        from .configs import usable_positions

        self.model_name = model
        self.config = config or PRESETS[model]
        self.max_tokens = min(max_tokens, usable_positions(self.config))
        # family default from the config (bge: CLS, e5/gte: masked mean)
        # unless the caller overrides
        self.pooling = pooling if pooling is not None else self.config.pooling
        if dtype is None:
            dtype = (
                jnp.bfloat16 if jax.default_backend() == "tpu" else jnp.float32
            )
        self.dtype = dtype
        self.tokenizer = tokenizer or load_tokenizer(
            vocab_size=self.config.vocab_size
        )
        if params is None:
            params = bert.init_params(
                jax.random.PRNGKey(seed), self.config, dtype=dtype
            )
        # validate + stamp the quantize mode and (once, at load) quantize
        # full-precision params — the shared entry point with TpuReranker
        from .quant import resolve_quantize

        self.config, params = resolve_quantize(self.config, params, quantize)
        self.params = params
        # placement of a lazy-jit dispatch's inputs: the identity on one
        # device; shard_embedder_mesh replaces it with the dp split
        self.put_batch = lambda ids, mask: (ids, mask)
        # AOT-compiled executables (aot_warmup) keyed by call signature;
        # the dispatch methods consult this FIRST, so warmed buckets
        # never touch the jit dispatch cache (zero new specializations
        # after startup — see jit_stats)
        self._aot = {}
        # fleet-shared serialized-executable store (models/aot_store.py,
        # AOT_CACHE_DIR; serve/__main__ attaches it before warmup): when
        # set, _aot_compile deserializes a peer's executable instead of
        # compiling, and persists anything it does compile
        self.aot_store = None
        self._aot_restored = 0
        # batches are padded up to a multiple of this before dispatch so
        # the dp split divides evenly (shard_embedder_mesh sets it to dp)
        self.batch_multiple = 1
        # first-class mesh serving (parallel.shard_embedder_mesh /
        # MESH_ENABLED): params placed by the partition-rule tables and
        # dispatches staged with real input shardings.  Mesh mode keeps
        # the AOT fast path (executables lower with sharded avals, keyed
        # per mesh shape).
        self.mesh_mode = False
        self.mesh = None
        self.mesh_shape = None
        self.batch_sharding = None
        self.repl_sharding = None
        # long-context ring dispatch (MESH_SHAPE=dp,tp,sp;
        # parallel.shard_embedder_mesh sets these when the mesh carries
        # an sp axis).  mesh_sp == 1 means no ring path: every dense
        # dispatch above stays byte-identical to the 2-axis mesh.
        self.mesh_sp = 1
        self.ring_sharding = None
        self.ring_max_tokens = None
        self._ring_config = None
        # per-(mesh-shape, bucket) device timing at the dispatch seam
        # (obs/phases.py; METRICS_DEVICE_TIMING=0 clears it).  Direct
        # callers pay a block-until-ready bracket; under the batcher's
        # deferred-readiness sink (dispatch_seam.py) the dispatch thread
        # returns at enqueue and the waiter records the same numbers
        self.device_timing = True
        # host staging-buffer pool for the padded dispatch paths
        # (STAGING_BUFFERS; serve/__main__ re-sizes it): buffers recycle
        # via the batcher's waiter once the consuming dispatch is ready
        self.staging_pool = StagingPool(per_bucket=2)
        # device-resident vote-temperature scalars per value (satellite:
        # every consensus dispatch used to re-transfer the same float);
        # entries pin the sharding they were placed with, so a mesh
        # re-shard (new repl_sharding object) can never serve stale
        # placements
        self._temp_cache: dict = {}

    # -- AOT bucket precompile ------------------------------------------------

    def _aot_ready(self) -> bool:
        """Whether the AOT fast path is usable: the first-class mesh
        mode, whose executables lower with sharded ShapeDtypeStructs and
        so carry the input shardings, or one device with unpadded
        batches (a plain-aval executable knows nothing of rows padded to
        a hand-set ``batch_multiple``)."""
        return self.mesh_mode or self.batch_multiple == 1

    def _aot_key(self, key: tuple) -> tuple:
        """AOT table key, namespaced per mesh shape in mesh mode: the
        same bucket compiles to a DIFFERENT executable per (dp, tp) —
        input shardings and the vote's collectives are baked in — so the
        table holds per-(mesh-shape, bucket) entries that can never be
        confused with single-device ones."""
        if self.mesh_mode:
            return ("mesh",) + tuple(self.mesh_shape) + key
        return key

    def _ring_aot_key(self, key: tuple) -> tuple:
        """AOT key for the ring (sequence-parallel) dispatch: namespaced
        by the FULL (dp, tp, sp) shape, so ring executables — which bake
        the (dp, sp) input sharding and the ring collectives — can never
        collide with the dense ("mesh", dp, tp, ...) entries even when
        the bucket tuple matches."""
        return ("mesh",) + tuple(self.mesh_shape) + (self.mesh_sp,) + key

    def ring_available(self) -> bool:
        """Whether the long-context ring dispatch is wired: first-class
        mesh mode on a mesh with an sp axis (shard_embedder_mesh set
        ``mesh_sp``/``ring_sharding``/``_ring_config``).  The batcher
        routes over-length requests here only when this holds; otherwise
        they truncate at ``max_tokens`` exactly as before."""
        return self.mesh_mode and self.mesh_sp > 1

    def _stage_batch(self, *arrays):
        """Stage host int32 arrays for an AOT executable call: mesh mode
        device_puts with the baked batch sharding (rows split over dp);
        single-device is the plain transfer the executable expects."""
        if self.mesh_mode:
            return tuple(
                jax.device_put(np.asarray(a), self.batch_sharding)
                for a in arrays
            )
        return tuple(jnp.asarray(a) for a in arrays)

    def _timed_dispatch(self, label: str, fn):
        """Run one device dispatch under its canonical bucket label —
        the SAME label the mesh audit measures and ``roofline.json``
        keys, suffixed ``@dp{dp}xtp{tp}`` in mesh mode so the fault
        ladder's rungs report separately — and account its device wall
        time into the global phase aggregator (the ``device_dispatch``
        phase + the roofline gauge's per-bucket p50).

        Two readiness modes (``dispatch_seam.dispatch``): under the
        batcher's deferred-readiness sink the PJRT call is merely
        ENQUEUED here — a PendingDispatch record hands (label, t0,
        output) to the waiter, which blocks, records the identical
        numbers, and frees this thread to stage the next group.  Without
        a sink (direct callers) the block-until-ready bracket runs
        inline.  Either way the device's account (obs/account.py) is
        told of the enqueue and of the ready."""
        if active_sink() is None and not self.device_timing:
            return fn()
        if self.mesh_mode:
            dp, tp = self.mesh_shape
            label = f"{label}@dp{dp}xtp{tp}"
            if self.mesh_sp > 1:
                label = f"{label}xsp{self.mesh_sp}"
        return dispatch(label, fn, timed=self.device_timing)

    def _finish(self, out):
        """Materialize a dispatch output for host consumers — unless a
        deferred-readiness sink is active, in which case the device
        array is returned as-is (slices stay lazy) and the batcher's
        waiter converts after readiness."""
        if active_sink() is not None:
            return out
        return np.asarray(out)

    def _stage_temp(self, temperature):
        """The vote temperature as a device scalar (replicated over the
        mesh in mesh mode — the executable baked that sharding), cached
        per value: the serving paths send the same default temperature
        on every consensus dispatch, and the fresh host->device scalar
        transfer it used to pay is pure per-dispatch overhead.  Each
        entry pins the sharding object it was placed with; a re-shard
        replaces ``repl_sharding``, so stale placements miss."""
        key = float(temperature)
        expected = self.repl_sharding if self.mesh_mode else None
        hit = self._temp_cache.get(key)
        if hit is not None and hit[0] is expected:
            return hit[1]
        t = jnp.asarray(key, jnp.float32)
        if self.mesh_mode:
            t = jax.device_put(t, self.repl_sharding)
        if len(self._temp_cache) >= 64:
            self._temp_cache.clear()
        self._temp_cache[key] = (expected, t)
        return t

    def _stage_pad(self, ids, mask, pad_b: int, pad_attend: bool = False):
        """Pad the batch dim to ``pad_b`` rows.  Under a deferred-
        readiness sink the rows land in reusable per-bucket staging
        buffers (StagingPool) instead of fresh ``np.pad`` copies — the
        buffers recycle via the waiter once the consuming dispatch is
        ready, because an async ``device_put`` may still be reading
        them before that.  ``pad_attend`` makes pad rows attend to one
        [PAD] token (the consensus contract; callers slice them off
        pre-vote)."""
        b = ids.shape[0]
        sink = active_sink()
        pool = self.staging_pool
        if (
            sink is not None
            and pool is not None
            and pool.enabled
            and ids.dtype == np.int32
            and mask.dtype == np.int32
        ):
            pids = pool.acquire((pad_b, ids.shape[1]), np.int32)
            pmask = pool.acquire((pad_b, mask.shape[1]), np.int32)
            pids[:b] = ids
            pids[b:] = 0
            pmask[:b] = mask
            pmask[b:] = 0
            if pad_attend:
                pmask[b:, 0] = 1
            sink.staged.extend((pids, pmask))
            return pids, pmask
        ids = np.pad(np.asarray(ids), ((0, pad_b - b), (0, 0)))
        mask = np.pad(np.asarray(mask), ((0, pad_b - b), (0, 0)))
        if pad_attend:
            mask[b:, 0] = 1
        return ids, mask

    def _aot_lookup(self, key, ids, mask):
        if not self._aot or not self._aot_ready():
            return None
        # executables were lowered for int32 ids/mask (the tokenizer's
        # dtype); anything else falls back to the jit path rather than
        # tripping the compiled call's aval check
        if ids.dtype != np.int32 or mask.dtype != np.int32:
            return None
        return self._aot.get(key)

    def aot_cache_meta(self) -> dict:
        """The environment digest preimage for the shared executable
        store (models/aot_store.py): everything that makes a serialized
        executable non-portable.  Any difference between the compiling
        and restoring replica lands them in different store namespaces,
        so an incompatible artifact is never even opened."""
        dev = jax.devices()[0]
        return {
            "jax": jax.__version__,
            "backend": jax.default_backend(),
            "device_kind": dev.device_kind,
            "device_count": jax.device_count(),
            "config": repr(self.config),
            "pooling": self.pooling,
            "max_tokens": self.max_tokens,
        }

    def _execution_devices(self) -> list:
        """The devices this embedder's executables run on, in assignment
        order: the mesh's, or the one device the params live on."""
        if self.mesh_mode:
            return list(self.mesh.devices.flat)
        return list(jax.tree_util.tree_leaves(self.params)[0].devices())

    def _aot_compile(self, timings, key, label, lower) -> None:
        """Fill ``self._aot[key]``: from the shared artifact store when
        a compatible serialized executable exists (AOT_CACHE_DIR), else
        by lowering and compiling — then persisting the result so the
        next replica (or this one after a restart) deserializes in
        milliseconds instead.  ``lower`` is a thunk returning the
        Lowered, deferred so a store hit skips tracing entirely."""
        import time as _time

        if key in self._aot:
            return
        store = self.aot_store
        if store is not None:
            t0 = _time.perf_counter()
            compiled = store.load(key, self._execution_devices())
            if compiled is not None:
                self._aot[key] = compiled
                self._aot_restored += 1
                timings.append((
                    f"{label} [deserialized]", _time.perf_counter() - t0
                ))
                return
        t0 = _time.perf_counter()
        compiled = lower().compile()
        self._aot[key] = compiled
        timings.append((label, _time.perf_counter() - t0))
        if store is not None:
            store.save(key, compiled)

    def aot_warmup(
        self,
        specs: list,
        r_buckets: list = (),
        ring_buckets: list = (),
    ) -> list:
        """AOT-lower-and-compile (``.lower().compile()``) every serving
        bucket up front: for each (N, S) spec the single-request consensus
        dispatch (both vote variants — the fused-kernel default and the
        traced-temperature fallback), the plain embed forward at its
        padded batch bucket, and (per ``r_buckets`` entry >= 2) the
        batcher's grouped dispatch.

        The executables land in ``self._aot`` and the dispatch methods
        call them directly, bypassing jit dispatch entirely — post-warmup
        traffic at warmed buckets creates ZERO new jit specializations.
        (On jax 0.9 ``.lower().compile()`` leaves the jit dispatch cache
        empty — ``_cache_size()`` stays 0 — but memoizes the compilation:
        a later jit call at the same shapes re-traces and adds its
        dispatch entry without compiling again.  Holding the executables
        here skips that trace too, and keeps ``jit_stats`` flat.)  The
        compilations also land in the persistent XLA cache
        (serve/config.py ``configure_compile_cache``), so restarts
        deserialize instead of recompiling.

        In mesh mode (``shard_embedder_mesh``) the same buckets lower
        with SHARDED avals — batch rows split over ``dp``, params
        already carrying their Megatron placement — producing one
        executable per (mesh-shape, bucket) with the input shardings
        and the vote's collectives baked in; see ``_aot_warmup_mesh``.

        Returns [(label, seconds)] for startup logging."""
        if not self._aot_ready():
            raise RuntimeError(
                "AOT warmup needs the single-device embedder or the "
                "first-class mesh mode (shard_embedder_mesh)"
            )
        if self.mesh_mode:
            return self._aot_warmup_mesh(specs, r_buckets, ring_buckets)
        # ring buckets need an sp mesh; single-device warmup ignores them
        sds = jax.ShapeDtypeStruct
        temp_av = sds((), jnp.float32)
        timings = []
        for n, s in specs:
            s = _seq_bucket(s, self.max_tokens)
            ids_av = sds((n, s), jnp.int32)
            for use_fused in (True, False):
                self._aot_compile(
                    timings,
                    ("vote1", n, s, use_fused),
                    f"consensus {n}x{s} fused={use_fused}",
                    lambda a=ids_av, n=n, f=use_fused: _embed_and_vote.lower(
                        self.params, a, a, temp_av,
                        n, self.config, self.pooling, f,
                    ),
                )
            pad_b = _bucket(n, self.MAX_DEVICE_BATCH)
            b_av = sds((pad_b, s), jnp.int32)
            self._aot_compile(
                timings,
                ("embed", pad_b, s),
                f"embed {pad_b}x{s}",
                lambda a=b_av: bert.embed.lower(
                    self.params, a, a, self.config,
                    pooling=self.pooling, normalize=True,
                ),
            )
            for r in r_buckets:
                if r < 2:
                    continue  # R=1 groups dispatch the single-request path
                flat_av = sds((r * n, s), jnp.int32)
                self._aot_compile(
                    timings,
                    ("many", r, n, s),
                    f"grouped R={r} {n}x{s}",
                    lambda a=flat_av, r=r, n=n: _embed_and_vote_many.lower(
                        self.params, a, a, temp_av,
                        r, n, self.config, self.pooling,
                    ),
                )
        return timings

    def _aot_warmup_mesh(
        self,
        specs: list,
        r_buckets: list = (),
        ring_buckets: list = (),
    ) -> list:
        """The mesh-mode half of ``aot_warmup``: lower every serving
        bucket with SHARDED avals (batch rows over ``dp`` via the
        NamedSharding-carrying ShapeDtypeStructs, params concrete and
        already placed) so ``.lower().compile()`` bakes the input
        shardings and the vote collectives into each executable.  Keys
        are namespaced per mesh shape (``_aot_key``); batch dims are
        padded to the dp multiple exactly like the dispatch methods pad,
        so lookup keys always line up.  One consensus executable per
        (N, S) — the mesh vote always traces its temperature (the fused
        Pallas variant is single-device-only), so there is no
        ``use_fused`` split here."""
        sds = jax.ShapeDtypeStruct
        bm = self.batch_multiple
        dp, tp = self.mesh_shape
        tag = f"mesh {dp}x{tp}"

        def iav(rows, cols):
            return sds((rows, cols), jnp.int32, sharding=self.batch_sharding)

        temp_av = sds((), jnp.float32, sharding=self.repl_sharding)
        timings = []
        for n, s in specs:
            s = _seq_bucket(s, self.max_tokens)
            pad_n = n + (-n) % bm
            self._aot_compile(
                timings,
                self._aot_key(("vote1", n, s)),
                f"{tag} consensus {n}x{s}",
                lambda a=iav(pad_n, s), n=n: _mesh_embed_and_vote.lower(
                    self.params, a, a, temp_av,
                    n, self.config, self.pooling, self.mesh,
                ),
            )
            pad_b = _bucket(n, self.MAX_DEVICE_BATCH)
            pad_b += (-pad_b) % bm
            self._aot_compile(
                timings,
                self._aot_key(("embed", pad_b, s)),
                f"{tag} embed {pad_b}x{s}",
                lambda a=iav(pad_b, s): bert.embed.lower(
                    self.params, a, a, self.config,
                    pooling=self.pooling, normalize=True,
                ),
            )
            for r in r_buckets:
                if r < 2:
                    continue  # R=1 groups dispatch the single-request path
                flat_n = r * n + (-(r * n)) % bm
                self._aot_compile(
                    timings,
                    self._aot_key(("many", r, n, s)),
                    f"{tag} grouped R={r} {n}x{s}",
                    lambda a=iav(flat_n, s), r=r, n=n: (
                        _embed_and_vote_many.lower(
                            self.params, a, a, temp_av,
                            r, n, self.config, self.pooling,
                        )
                    ),
                )
        # long-context ring buckets (N, S): only meaningful with an sp
        # mesh axis — without one the ring shard_map has no axis to ring
        # over, and warming nothing here keeps the 2-axis AOT table
        # byte-identical to the pre-sp serving path
        if ring_buckets and self.ring_available():
            from ..parallel.ring import _ring_embed_and_vote, _ring_embed_jit

            sp = self.mesh_sp
            rtag = f"mesh {dp}x{tp}x{sp}"

            def rav(rows, cols):
                return sds(
                    (rows, cols), jnp.int32, sharding=self.ring_sharding
                )

            for n, s in ring_buckets:
                s = _seq_bucket(s, self.ring_max_tokens)
                s = min(s + (-s) % sp, self.ring_max_tokens)
                pad_n = n + (-n) % bm
                self._aot_compile(
                    timings,
                    self._ring_aot_key(("ring_vote", n, s)),
                    f"{rtag} ring consensus {n}x{s}",
                    lambda a=rav(pad_n, s), n=n: _ring_embed_and_vote.lower(
                        self.params, a, a, temp_av,
                        n, self._ring_config, self.mesh, "sp", "dp",
                        self.pooling,
                    ),
                )
                pad_b = _bucket(n, self.MAX_DEVICE_BATCH)
                pad_b += (-pad_b) % bm
                self._aot_compile(
                    timings,
                    self._ring_aot_key(("ring", pad_b, s)),
                    f"{rtag} ring embed {pad_b}x{s}",
                    lambda a=rav(pad_b, s): _ring_embed_jit.lower(
                        self.params, a, a,
                        self._ring_config, self.mesh, "sp", "dp",
                        self.pooling, True,
                    ),
                )
        return timings

    def aot_mesh_shapes(self) -> list:
        """The (dp, tp) shapes with warmed mesh-mode AOT executables,
        sorted largest-first — the fault-domain ladder audit
        (analysis/mesh_audit.py JXA012) and the ``meshfault`` /metrics
        section read this to prove every fallback rung was prewarmed."""
        shapes = {
            (key[1], key[2])
            for key in self._aot
            if key and key[0] == "mesh"
        }
        return sorted(shapes, reverse=True)

    def jit_stats(self) -> dict:
        """Jit-cache introspection: AOT bucket count + per-entry-point
        specialization counts (serve /metrics "jit" section; the warmup
        test asserts the counts stay flat under post-warmup load)."""
        from ..parallel.ring import _ring_embed_and_vote, _ring_embed_jit

        return {
            "aot_buckets": len(self._aot),
            "aot_restored": self._aot_restored,
            "specializations": {
                "embed_and_vote": _embed_and_vote._cache_size(),
                "embed_and_vote_many": _embed_and_vote_many._cache_size(),
                "mesh_embed_and_vote": _mesh_embed_and_vote._cache_size(),
                "embed": bert.embed._cache_size(),
                "stream_vote_update": _stream_vote_update._cache_size(),
                "stream_vote_update_many": (
                    _stream_vote_update_many._cache_size()
                ),
                "ring_embed": _ring_embed_jit._cache_size(),
                "ring_embed_and_vote": _ring_embed_and_vote._cache_size(),
            },
        }

    # -- core ----------------------------------------------------------------

    def tokenize(self, texts: Iterable[str], max_tokens: Optional[int] = None):
        cap = min(max_tokens or self.max_tokens, self.max_tokens)
        ids, mask = self.tokenizer.encode_batch(list(texts), cap)
        seq = _seq_bucket(int(mask.sum(axis=1).max(initial=1)), cap)
        return ids[:, :seq], mask[:, :seq]

    def embed_texts(
        self, texts: list, max_tokens: Optional[int] = None
    ) -> np.ndarray:
        """texts -> embeddings[B, H] (f32, l2-normalized)."""
        ids, mask = self.tokenize(texts, max_tokens)
        return self.embed_tokens(ids, mask)

    MAX_DEVICE_BATCH = 4096

    def embed_tokens(self, ids: np.ndarray, mask: np.ndarray) -> np.ndarray:
        b = ids.shape[0]
        if b > self.MAX_DEVICE_BATCH:
            # chunk oversized batches; each chunk reuses the same jit
            # specialization (fixed bucketed shapes)
            chunks = [
                self.embed_tokens(
                    ids[i : i + self.MAX_DEVICE_BATCH],
                    mask[i : i + self.MAX_DEVICE_BATCH],
                )
                for i in range(0, b, self.MAX_DEVICE_BATCH)
            ]
            return np.concatenate(chunks, axis=0)
        pad_b = _bucket(b, self.MAX_DEVICE_BATCH)
        pad_b += (-pad_b) % self.batch_multiple  # keep the dp split divisible
        if pad_b != b:
            ids, mask = self._stage_pad(ids, mask, pad_b)
        label = f"embed(b={pad_b},s={ids.shape[1]})"
        exe = self._aot_lookup(
            self._aot_key(("embed", pad_b, ids.shape[1])), ids, mask
        )
        if exe is not None:
            dev_ids, dev_mask = self._stage_batch(ids, mask)
            emb = self._timed_dispatch(
                label, lambda: exe(self.params, dev_ids, dev_mask)
            )
            return self._finish(emb[:b])
        dev_ids, dev_mask = self.put_batch(jnp.asarray(ids), jnp.asarray(mask))
        emb = self._timed_dispatch(
            label,
            lambda: bert.embed(
                self.params,
                dev_ids,
                dev_mask,
                self.config,
                pooling=self.pooling,
                normalize=True,
            ),
        )
        return self._finish(emb[:b])

    # -- long-context ring (sequence-parallel) path ---------------------------

    def _stage_ring(self, *arrays):
        """Stage host int32 arrays for a ring dispatch: rows over ``dp``
        AND the sequence axis over ``sp`` (the sharding the ring
        executables baked).  Callers pad batch to the dp multiple and
        sequence to an sp multiple first, so the split always divides."""
        return tuple(
            jax.device_put(np.asarray(a), self.ring_sharding)
            for a in arrays
        )

    def _require_ring(self):
        if not self.ring_available():
            raise RuntimeError(
                "ring dispatch needs first-class mesh mode on a mesh "
                "with an sp axis (MESH_SHAPE=dp,tp,sp + "
                "shard_embedder_mesh)"
            )

    def _ring_pad_seq(self, ids, mask):
        """Pad the sequence axis to an sp multiple (pads are masked
        keys; ring attention ignores them)."""
        pad_s = (-ids.shape[1]) % self.mesh_sp
        if pad_s:
            ids = np.pad(np.asarray(ids), ((0, 0), (0, pad_s)))
            mask = np.pad(np.asarray(mask), ((0, 0), (0, pad_s)))
        return ids, mask

    def tokenize_ring(
        self, texts: Iterable[str], max_tokens: Optional[int] = None
    ):
        """``tokenize`` for the ring path: caps at ``ring_max_tokens``
        (the position window rounded down to an sp multiple) instead of
        the dense ``max_tokens``, and rounds the trimmed sequence bucket
        UP to an sp multiple so the dispatch never re-pads."""
        self._require_ring()
        sp = self.mesh_sp
        cap = min(max_tokens or self.ring_max_tokens, self.ring_max_tokens)
        cap = max((cap // sp) * sp, sp)
        ids, mask = self.tokenizer.encode_batch(list(texts), cap)
        seq = _seq_bucket(int(mask.sum(axis=1).max(initial=1)), cap)
        seq = min(seq + (-seq) % sp, cap)
        return ids[:, :seq], mask[:, :seq]

    def embed_tokens_ring(self, ids: np.ndarray, mask: np.ndarray):
        """Long-context twin of ``embed_tokens``: the sequence axis is
        sharded over ``sp`` and attention runs as a ring
        (parallel/ring.py), so sequences up to ``ring_max_tokens`` —
        beyond what one device's attention memory can serve — dispatch
        as a single executable.  Same AOT-first contract, keyed under
        the ("mesh", dp, tp, sp, "ring", b, s) namespace."""
        self._require_ring()
        b = ids.shape[0]
        ids, mask = self._ring_pad_seq(ids, mask)
        pad_b = _bucket(b, self.MAX_DEVICE_BATCH)
        pad_b += (-pad_b) % self.batch_multiple
        if pad_b != b:
            ids, mask = self._stage_pad(ids, mask, pad_b)
        s = ids.shape[1]
        label = f"ring(b={pad_b},s={s})"
        exe = self._aot_lookup(
            self._ring_aot_key(("ring", pad_b, s)), ids, mask
        )
        dev_ids, dev_mask = self._stage_ring(ids, mask)
        if exe is not None:
            emb = self._timed_dispatch(
                label, lambda: exe(self.params, dev_ids, dev_mask)
            )
            return self._finish(emb[:b])
        from ..parallel.ring import _ring_embed_jit

        emb = self._timed_dispatch(
            label,
            lambda: _ring_embed_jit(
                self.params, dev_ids, dev_mask, self._ring_config,
                self.mesh, "sp", "dp", self.pooling, True,
            ),
        )
        return self._finish(emb[:b])

    def consensus_confidence_tokens_ring(
        self, ids: np.ndarray, mask: np.ndarray, temperature: float = 0.05
    ):
        """Long-context twin of ``consensus_confidence_tokens``:
        sequence-sharded encoder + the dp-sharded consensus vote in one
        dispatch (parallel.ring._ring_embed_and_vote).  Temperature is
        always traced, pad rows masked via n_valid — the same contract
        as the dense mesh vote."""
        self._require_ring()
        n = ids.shape[0]
        ids, mask = self._ring_pad_seq(ids, mask)
        ids, mask = self._pad_rows(ids, mask)
        s = ids.shape[1]
        label = f"ring_vote(n={n},s={s})"
        exe = self._aot_lookup(
            self._ring_aot_key(("ring_vote", n, s)), ids, mask
        )
        temp = self._stage_temp(temperature)
        dev_ids, dev_mask = self._stage_ring(ids, mask)
        if exe is not None:
            return self._timed_dispatch(
                label, lambda: exe(self.params, dev_ids, dev_mask, temp)
            )
        from ..parallel.ring import _ring_embed_and_vote

        return self._timed_dispatch(
            label,
            lambda: _ring_embed_and_vote(
                self.params, dev_ids, dev_mask, temp, n,
                self._ring_config, self.mesh, "sp", "dp", self.pooling,
            ),
        )

    def consensus_confidence(
        self,
        texts: list,
        max_tokens: Optional[int] = None,
        temperature: float = 0.05,
    ) -> np.ndarray:
        """texts (N candidates) -> confidence[N]: the whole embed + cosine
        self-consistency vote in ONE device dispatch."""
        ids, mask = self.tokenize(texts, max_tokens)
        return self.consensus_confidence_tokens(ids, mask, temperature)

    def _pad_rows(self, ids: np.ndarray, mask: np.ndarray):
        """Pad the batch dim to a multiple of ``batch_multiple`` so the dp
        sharding divides evenly.  Pad rows attend to one [PAD] token (a
        clean forward, no 0/0 pooling); callers slice them off pre-vote.
        Batched callers ride the staging pool via ``_stage_pad``."""
        pad = (-ids.shape[0]) % self.batch_multiple
        if pad:
            ids, mask = self._stage_pad(
                ids, mask, ids.shape[0] + pad, pad_attend=True
            )
        return ids, mask

    def consensus_confidence_tokens(
        self, ids: np.ndarray, mask: np.ndarray, temperature: float = 0.05
    ):
        n = ids.shape[0]
        ids, mask = self._pad_rows(ids, mask)
        label = f"vote1(n={n},s={ids.shape[1]})"
        if self.mesh_mode:
            # one jit-with-shardings dispatch: encoder + the dp-sharded
            # vote reduction; temperature always traced (the fused
            # Pallas vote never runs under SPMD), pad rows masked via
            # n_valid inside the sharded vote
            exe = self._aot_lookup(
                self._aot_key(("vote1", n, ids.shape[1])), ids, mask
            )
            temp = self._stage_temp(temperature)
            dev_ids, dev_mask = self._stage_batch(ids, mask)
            if exe is not None:
                return self._timed_dispatch(
                    label, lambda: exe(self.params, dev_ids, dev_mask, temp)
                )
            return self._timed_dispatch(
                label,
                lambda: _mesh_embed_and_vote(
                    self.params, dev_ids, dev_mask, temp,
                    n, self.config, self.pooling, self.mesh,
                ),
            )
        # the Pallas fast path bakes its temperature in; any other
        # value rides the traced-jnp vote (no per-value recompiles)
        use_fused = float(temperature) == DEFAULT_VOTE_TEMPERATURE
        exe = self._aot_lookup(
            ("vote1", ids.shape[0], ids.shape[1], use_fused), ids, mask
        )
        if exe is not None:
            temp = self._stage_temp(temperature)
            return self._timed_dispatch(
                label,
                lambda: exe(
                    self.params,
                    jnp.asarray(ids),
                    jnp.asarray(mask),
                    temp,
                ),
            )
        dev_ids, dev_mask = self.put_batch(jnp.asarray(ids), jnp.asarray(mask))
        return self._timed_dispatch(
            label,
            lambda: _embed_and_vote(
                self.params,
                dev_ids,
                dev_mask,
                float(temperature),
                n,
                self.config,
                self.pooling,
                use_fused=use_fused,
            ),
        )

    def consensus_confidence_tokens_many(
        self, ids: np.ndarray, mask: np.ndarray, temperature: float = 0.05
    ):
        """ids/mask[R, N, S] (R concurrent requests) -> confidence[R, N] in
        ONE device dispatch (dynamic batching for the serving loop).

        R buckets to the next power of two before the jit: the batcher's
        group size varies with load, and an exact-R specialization would
        recompile the full encoder per distinct concurrency level (tens
        of seconds each for bge-large).  Pad request slots attend to one
        [PAD] token; their confidences are sliced off."""
        from ..utils import next_pow2

        r, n, s = ids.shape
        r_bucket = next_pow2(r)
        if r_bucket != r:
            pad = (r_bucket - r) * n
            ids = np.concatenate(
                [ids.reshape(r * n, s), np.zeros((pad, s), ids.dtype)]
            )
            mask = np.concatenate(
                [mask.reshape(r * n, s), np.zeros((pad, s), mask.dtype)]
            )
            mask[r * n :, 0] = 1
        else:
            ids = ids.reshape(r * n, s)
            mask = mask.reshape(r * n, s)
        flat_ids, flat_mask = self._pad_rows(ids, mask)
        label = f"many(r={r_bucket},n={n},s={s})"
        exe = self._aot_lookup(
            self._aot_key(("many", r_bucket, n, s)), flat_ids, flat_mask
        )
        if exe is not None:
            dev_ids, dev_mask = self._stage_batch(flat_ids, flat_mask)
            conf = self._timed_dispatch(
                label,
                lambda: exe(
                    self.params,
                    dev_ids,
                    dev_mask,
                    self._stage_temp(temperature),
                ),
            )
            return conf[:r]
        dev_ids, dev_mask = self.put_batch(
            jnp.asarray(flat_ids), jnp.asarray(flat_mask)
        )
        conf = self._timed_dispatch(
            label,
            lambda: _embed_and_vote_many(
                self.params, dev_ids, dev_mask, float(temperature), r_bucket,
                n, self.config, self.pooling,
            ),
        )
        return conf[:r]

    def token_count(self, texts: list, max_tokens: Optional[int] = None) -> int:
        _, mask = self.tokenize(texts, max_tokens)
        return int(mask.sum())

    def stream_vote_update(
        self,
        text: str,
        buf,
        valid,
        position: int,
        temperature: float = 0.05,
    ):
        """Streaming-consensus step: embed ONE new candidate into slot
        ``position`` of the device-resident buffer and recompute the
        masked consensus vote — embed + revote fused in ONE dispatch, so a
        live stream pays one link round-trip per finished candidate
        instead of two.  Returns (buf, valid, confidence[CAP]); buf/valid
        stay on device, fetch only the confidence."""
        ids, mask = self.tokenize([text])
        return _stream_vote_update(
            self.params,
            jnp.asarray(ids),
            jnp.asarray(mask),
            buf,
            valid,
            position,
            self.config,
            self.pooling,
            temperature,
        )

    def stream_vote_update_many(
        self,
        texts: list,
        bufs: list,
        valids: list,
        positions: list,
        temperature: float = 0.05,
    ):
        """R streaming-consensus steps (one per live stream, same capacity
        bucket) in ONE dispatch -> (bufs[R, CAP, H], valids[R, CAP],
        confidences[R, CAP]).  The batch dim is bucketed so the jit
        specializes per (R-bucket, CAP, S-bucket), not per exact R; pad
        rows attend to one [PAD] token and their outputs are sliced off."""
        r = len(texts)
        ids, mask = self.tokenize(texts)
        pad = _bucket(r, self.MAX_DEVICE_BATCH) - r
        dev_bufs = jnp.stack(bufs)
        dev_valids = jnp.stack(valids)
        pos = np.zeros((r + pad,), dtype=np.int32)
        pos[:r] = positions
        if pad:
            ids = np.pad(ids, ((0, pad), (0, 0)))
            mask = np.pad(mask, ((0, pad), (0, 0)))
            mask[-pad:, 0] = 1
            dev_bufs = jnp.pad(dev_bufs, ((0, pad), (0, 0), (0, 0)))
            dev_valids = jnp.pad(dev_valids, ((0, pad), (0, 0)))
        out_bufs, out_valids, confs = _stream_vote_update_many(
            self.params,
            jnp.asarray(ids),
            jnp.asarray(mask),
            dev_bufs,
            dev_valids,
            jnp.asarray(pos),
            self.config,
            self.pooling,
            temperature,
        )
        return out_bufs[:r], out_valids[:r], confs[:r]

    # -- wire contract --------------------------------------------------------

    def wire_response(
        self, emb: np.ndarray, tokens: int
    ) -> CreateEmbeddingResponse:
        """Wrap already-computed embeddings as the OpenAI response
        (types/embeddings.py) with usage = real token counts for cost
        accounting — the assembly half of ``embeddings_response``, split
        out so batched callers (serve/batcher.py) can reuse it.

        Row assembly is one bulk ``tolist()`` — a single C-level
        device-to-host conversion — instead of a Python ``float(v)``
        call per element; values are identical (``tolist`` applies the
        same per-element widening ``item()`` conversion)."""
        rows = np.asarray(emb).tolist()
        return CreateEmbeddingResponse(
            object="list",
            data=[
                Embedding(
                    object="embedding",
                    index=i,
                    embedding=row,
                )
                for i, row in enumerate(rows)
            ],
            model=self.model_name,
            usage=Usage(
                prompt_tokens=tokens, completion_tokens=0, total_tokens=tokens
            ),
        )

    def embeddings_response(
        self, texts: list, max_tokens: Optional[int] = None
    ) -> CreateEmbeddingResponse:
        """The OpenAI embeddings response.  Tokenizes once."""
        ids, mask = self.tokenize(texts, max_tokens)
        emb = self.embed_tokens(ids, mask)
        return self.wire_response(emb, int(mask.sum()))
