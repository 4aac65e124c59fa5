"""Env-first service configuration.

Parity: the reference's 16 env vars (main.rs:3-37) with identical names and
defaults, plus TPU-framework additions (encoder + mesh flags).  ``.env``
loading mirrors dotenv: simple KEY=VALUE lines, environment wins.

TPU additions:

* ``EMBEDDER_MODEL``  — encoder preset: ``bge-{small,base,large}-en`` (CLS
  pooling), ``e5-{small,base,large}-v2`` / ``gte-{small,base,large}``
  (masked-mean pooling — family default applied automatically).  Unset =
  no device side (static weights only).
* ``EMBEDDER_WEIGHTS`` — local checkpoint for the encoder: an HF snapshot
  dir (model.safetensors / pytorch_model.bin), a single weights file, or
  an orbax dir (models/loading.py).  Unset = random init (demo mode).
* ``EMBEDDER_VOCAB``  — path to a WordPiece ``vocab.txt``; defaults to
  the vocab.txt beside EMBEDDER_WEIGHTS when present, else hash-tokenizer
  fallback.
* ``EMBEDDER_QUANTIZE`` — ``int8`` serves the encoder W8A8 on the MXU's
  int8 path (2x bf16 peak; opt-in, accuracy pinned in tests/test_quant.py)
  via the fused Pallas quantized-matmul kernel (activation quant + int8
  matmul + dequant/bias/GELU epilogue in one kernel — ops/kernels.py).
  ``int8-pallas`` / ``int8-xla`` pin the kernel vs the XLA dot_general
  fallback (debugging).  Default ``none``.
* ``EMBEDDER_MAX_TOKENS`` — truncation window of the dense dispatch.
  Default 512.
* ``MESH_ENABLED`` — first-class mesh serving: embed and consensus
  dispatches run on a (dp, tp) ICI mesh with params placed once by the
  partition-rule tables (batches shard over ``dp``, encoder params
  Megatron-split over ``tp``, parallel/sharding.py), real input
  shardings on every dispatch, and per-(mesh-shape, bucket) AOT
  executables; AOT warmup stays available.  Off by default: unset is one
  device.
* ``MESH_SHAPE`` — the mesh layout for ``MESH_ENABLED`` as ``DPxTP``
  (e.g. ``4x2`` = batches split 4-way, encoder params 2-way) or
  ``DPxTPxSP`` (e.g. ``2x2x2`` adds a 2-way sequence-parallel axis:
  over-length score/embed requests dispatch as ring attention over
  ``sp`` instead of truncating, parallel/ring.py).  Without the sp
  axis the serving path is byte-identical to the 2-axis form.  Unset
  with ``MESH_ENABLED=1`` uses every local device on ``dp`` (tp=1);
  setting it without ``MESH_ENABLED`` is an error.
* ``LONG_CONTEXT_WARMUP`` — ring AOT buckets as ``NxS`` specs (e.g.
  ``4x4096,1x8192``): with an sp-bearing ``MESH_SHAPE`` these
  long-context consensus/embed shapes compile at startup, so the first
  over-length request pays no trace.  N=1 warms the plain embed path.
  Requires ``MESH_SHAPE=DPxTPxSP``; empty = ring shapes compile lazily.
* ``MULTIHOST`` — set to 1 on each host of a multi-host slice to call
  ``jax.distributed.initialize`` before mesh construction (parallel/dist.py).
* ``JAX_COMPILATION_CACHE_DIR`` — JAX's own variable, read by JAX at
  import: where the persistent XLA compilation cache lives.  The
  program sets no directory over it; unset, every entry point uses the
  fixed ``.jax_cache`` at the checkout root (``configure_compile_cache``), so
  restarts and sibling processes hit what an earlier one compiled.
* ``PROFILE_DIR`` — arms ``POST /profile/start`` / ``POST /profile/stop``
  and the one-shot ``POST /v1/profile`` (bounded ``duration_ms`` capture
  window, admission-exempt so an overload can be profiled while the gate
  sheds): JAX profiler traces (xprof format, viewable in
  TensorBoard/xprof) are written under this directory.  Unset =
  start/stop disabled (404) and ``/v1/profile`` answers 403.  While a
  profile runs, every ``obs.host_span`` of the serving path
  (``http:arrive``, ``http:parse``, ``host:tokenize``, ``batcher:idle``,
  ``batcher:slots_full``, ``batcher:stage``, ``device:wait``,
  ``host:finalize``, ``http:respond``; each with the request's ``rid``
  or its dispatch ``group``) is in the trace too, on the profiler's
  clock beside the device's operations, between two ``lwc:clock`` marks
  that carry ``perf_counter_ns`` and ``epoch_ns``.  ``/v1/profile``
  captures with the Python tracer off (the host planes hold those spans
  and the runtime's own events, not every Python frame);
  ``/profile/start`` keeps the profiler's defaults.
* ``RM_MODEL`` / ``RM_WEIGHTS`` / ``RM_VOCAB`` / ``RM_MAX_TOKENS`` /
  ``RM_QUANTIZE`` (``int8`` = W8A8 RM serving, default ``none``) — a
  DeBERTa reward model serving ``POST /consensus {"scorer": "rm"}``:
  candidates re-rank by softmax(reward).  Same synthetic-params gate as
  the embedder; real checkpoints load from HF DeBERTa-v2/v3 snapshots or
  orbax dirs.
* ``JUDGE_MODEL`` / ``JUDGE_WEIGHTS`` / ``JUDGE_VOCAB`` /
  ``JUDGE_MAX_TOKENS`` / ``JUDGE_QUANTIZE`` — a causal sparse-expert
  decoder serving ``POST /consensus {"scorer": "judge"}``: a LOCAL judge
  panel, each call a prefill of the candidates under a seeded prefix-tree
  ballot, one decoded key letter and a masked read of its siblings'
  log-probabilities (models/judge.py).  ``JUDGE_MODEL`` names one of seven
  decoders: ``glm-4.7-flash`` (latent attention over every causal key,
  every expert held; models/glm_moe.py), ``glm-5.2`` (the same module under
  a configuration with an indexer: a learned sparse selection, the 2048
  highest-scored keys a query, chosen on the layers that own an indexer and
  shared with the layers behind them, in front of 64-head latent attention;
  the indexer's keys cached beside the latent and the rotary key),
  ``dots3-note-prev`` (the same module again, its attention layers of two
  kinds told apart by ``layer_types``: full layers of 128 heads each behind
  an indexer of its own, sliding layers of 64 heads of another geometry over
  the 513 keys up to the query, every head behind a sigmoid gate, the
  latents rescaled; a sliding layer caches only the last 512 positions a
  call), ``qwen3-next-80b-a3b`` (gated delta-rule layers three to one with gated
  full attention, a recurrent state and a convolution tail cached beside
  the keys; models/qwen3_next.py), ``trinity-large-preview``
  (grouped-query attention of two kinds in one stack, 48 heads on 8 key
  heads: sliding layers that turn their heads and attend the 4096 keys up
  to the query, full layers that turn nothing; an elementwise gate, a norm
  behind each branch; a sliding layer caches its window's keys and values
  only; models/afmoe.py), ``phi-4-mini-flash-reasoning`` (a decoder that
  feeds a decoder, no experts, whole on one chip: Mamba layers and
  differential attention over the 512 keys up to the query, one full layer
  whose keys and values are cached once for the seven cross layers behind
  it, gated memory units on the last Mamba layer's scan; a Mamba layer
  caches its state and its convolution's tail, a sliding layer its window;
  the layers behind the full one run at the row the panel reads and nowhere
  else; models/sambay.py) or ``falcon-h1-34b-instruct`` (a Mamba-2 (SSD)
  mixer and grouped-query attention, 20 heads on 4 key heads, side by side
  on one normed input in every block, every product behind a published µP
  multiplier, no experts; every layer caches its keys and values AND its
  mixer's convolution tail and scan state; models/falcon_h1.py); each has a
  tiny twin for tests
  (``glm-test-tiny``, ``glm-dsa-test-tiny``, ``dots3-test-tiny``,
  ``qwen3-next-test-tiny``, ``afmoe-test-tiny``, ``phi4flash-test-tiny``,
  ``falcon-h1-test-tiny``).
  ``JUDGE_WEIGHTS`` is an HF checkpoint, one ``model.safetensors`` or
  sharded; what is served is what it names: its layers from 0 up (and of
  each whether it is dense or sparse and whether it owns an indexer, which
  under ``dots3-note-prev`` says its kind, full or sliding: one pipeline
  stage of a deployment; under ``trinity-large-preview`` the stage names
  its layers by their PUBLISHED numbers, ``model.layers.5`` and up, and a
  layer's kind is the preset's ``layer_types`` entry of that number),
  experts 0..E-1 of a wider router (one
  chip's share of a layer's experts: the pairs routed elsewhere are left
  out of this chip's partial sum) and, for the ``glm``, ``afmoe``,
  ``phi4flash`` and ``falcon_h1`` decoders, the rows
  of the vocabulary its embedding holds (a slice is a smaller vocabulary).
  ``JUDGE_MAX_TOKENS`` (default 8192) is the ONE sequence bucket every
  call is padded to.  ``JUDGE_QUANTIZE=int8`` runs the dense products
  W8A8 (``quant.dense_int8``).  A server with a judge and no embedder
  starts and serves.  Same synthetic-params gate as the embedder.
* ``ARCHIVE_PATH`` — JSON snapshot for the completions archive
  (checkpoint/resume): loaded at startup when the file exists, saved on
  graceful shutdown.  Unset = in-memory only.
* ``ARCHIVE_WRITE`` — archive every UNARY completion the gateway serves
  (with per-judge ballots and the originating score request, enabling
  logprob re-extraction and training-table learning), making its id
  referenceable in later requests.  Defaults on when ``ARCHIVE_PATH`` is
  set; ``ARCHIVE_WRITE=0`` disables.  ``POST /archive/rescore`` re-tallies
  archived completions on device (weight overrides, optional logprob
  revote, optional write-back).
* ``ARCHIVE_STREAMING`` — with ``ARCHIVE_WRITE``, also archive STREAMED
  completions: the gateway tees each chunk stream into the merge-algebra
  fold and archives the unary form at stream end (``unary =
  fold(chunks)`` — types/base.py).  Off by default: real traffic is
  mostly streaming, so this retains every served response.
* ``ARCHIVE_MAX_COMPLETIONS`` — FIFO cap per archive table (chat / score
  / multichat), bounding a long-running service's memory; evicting a
  score completion drops its ballots + request record.  ``0`` =
  unbounded.  Default 65536.
* ``TABLES_PATH`` — .npz snapshot for the judge training tables: loaded
  at startup when present, saved on graceful shutdown.  With an embedder
  configured, ``POST /weights/learn`` builds rows from the archive into
  the live tables (weights/learning.py).
* ``BATCH_WINDOW_MS`` — the micro-batching accumulation window
  (serve/batcher.py): concurrent requests' device work arriving within
  this window (or behind an in-flight dispatch) is fused into one batched
  device call.  ``0`` disables the idle wait but still batches behind
  in-flight dispatches.  Default 3.
* ``BATCH_MAX`` — max items per fused device dispatch (oversized groups
  chunk).  Default 64.
* ``BATCH_PIPELINE`` — device dispatches allowed in flight concurrently
  (the host side of batch k+1 overlaps batch k's device execution).
  The overlap holds with device timing on too: the dispatch thread
  returns at PJRT enqueue and a waiter thread records readiness
  (models/dispatch_seam.py).  Default 2; 1 = fully serialized.
* ``HOST_TOKENIZER_WORKERS`` — host threads tokenizing each item at
  SUBMIT time, so ``_dispatch_*`` only concatenates pre-built rows and
  group k+1's tokenization never rides the dispatch thread behind
  group k.  ``0`` tokenizes on the dispatch
  thread (the pre-overlap behavior).  Default 2.
* ``HOST_FASTPATH`` — host fast lane for the streaming consensus path:
  per-chunk SSE frames are assembled by splicing changed fields into
  precompiled byte templates (serve/frames.py) instead of a full
  ``to_json_obj`` + ``dumps`` per chunk, and the per-push weighted
  tally runs on scaled-int64 numpy vectors (clients/tally.py) with the
  Decimal fold retained as the final-frame authority.  Both lanes fall
  back loudly to the slow path whenever exactness cannot be proven, so
  output bytes are identical either way.  Default ``0`` (off).
* ``STAGING_BUFFERS`` — reusable host staging buffers kept per
  (shape, dtype) bucket for the padded dispatch paths; the batcher's
  waiter recycles each buffer once its transfer is ready instead of
  allocating fresh ``np.pad`` copies per dispatch.  ``0`` disables
  reuse.  Default 2.
* ``WARMUP`` — consensus shapes to pre-compile at startup, e.g.
  ``64x112,64x128`` (``NxS`` pairs): the first request at a shape
  otherwise pays a multi-second jit compile (each (N, seq-bucket) is
  its own XLA specialization); the persistent compile cache makes
  later restarts near-instant.  Invalid specs fail startup loudly.
* ``WARMUP_R`` — concurrency buckets to ALSO pre-compile for each
  ``WARMUP`` shape through the batcher's grouped path, e.g. ``2,4``:
  the grouped dispatch (``consensus_confidence_tokens_many``) is a
  DISTINCT XLA specialization per power-of-two R bucket, so a warmed
  ``64x112`` alone still pays a multi-second compile on the first
  *concurrent* burst at that shape.  Values snap to the next power of
  two (the runtime bucketing) and dedup.  Default empty: only the
  single-request (R=1) path is warmed.
* ``WARMUP_AOT`` — ``1`` (default): warm via AOT ``.lower().compile()``
  — every warmed bucket's executable is compiled WITHOUT a device
  dispatch and cached on the embedder, and post-warmup traffic at those
  buckets calls the executables directly (zero jit specializations
  after startup; the ``jit`` section of ``/metrics`` shows the counts).
  ``0`` falls back to dispatch-based warmup (also what mesh-sharded
  embedders use: AOT lowering doesn't carry their shardings).
* ``BATCH_MAX_ROWS`` — encoder rows per fused dispatch; a synchronized
  burst of requests chunks into this many rows per dispatch so the
  pipeline has pieces to overlap.  Default 512.
* ``SCORE_CACHE_TTL`` — seconds a cached consensus result stays
  servable.  ``0`` (the default) disables the result cache entirely:
  the service behaves exactly as before the cache existed.  When >0,
  score requests are fingerprinted (cache/fingerprint.py: panel id +
  canonicalized messages + choices + sampling params, JSON field order
  irrelevant) and identical requests within the TTL replay the recorded
  chunk stream instead of re-running the judge fan-out; identical
  *concurrent* requests collapse onto one in-flight fan-out
  (single-flight).  Per-request opt-out: ``"cache_bypass": true``.
* ``SCORE_CACHE_MAX_BYTES`` — byte budget for the in-memory score result
  LRU.  Default 67108864 (64 MiB).
* ``SCORE_CACHE_DIR`` — append-only JSONL disk tier for the score cache
  (the compile-cache pattern applied to results): entries persist
  across restarts and reload at startup, expired ones skipped.  Unset =
  memory only.
* ``SCORE_CACHE_EMBED`` — also memoize embedding rows per
  (model, truncation window, text) in the micro-batcher, so hot rows
  skip device dispatch.  Defaults on whenever ``SCORE_CACHE_TTL`` > 0;
  ``SCORE_CACHE_EMBED=0`` disables.
* ``SCORE_CACHE_EMBED_MAX_BYTES`` — byte budget for the embedding row
  cache.  Default 33554432 (32 MiB).

Cache counters (hits/misses/evictions/in-flight collapses) surface as
the ``score_cache`` / ``embed_cache`` sections of ``GET /metrics``.

Fleet tier (fleet/): N gateway replicas with ``FLEET_*`` set serve as
ONE tier — consistent-hash ownership of cache fingerprints, peer-to-peer
result fetch before going upstream, cross-replica single-flight leases
(a fleet-wide hot key hits the upstream judges exactly once), and
drain-time hot-set handoff.  Everything unset = single-replica behavior
untouched; a dead or unreachable peer degrades to exactly that:

* ``FLEET_SELF`` — this replica's own base URL as peers reach it
  (e.g. ``http://10.0.0.3:5000``).  Required to enable the fleet;
  requires ``SCORE_CACHE_TTL`` > 0 (the fleet shares score-cache
  entries) and a roster via one of the next two knobs.
* ``FLEET_PEERS`` — static comma-separated roster of replica base URLs,
  ``FLEET_SELF`` included.
* ``FLEET_PEERS_FILE`` — file-watched roster instead (one URL per
  line, ``#`` comments allowed), re-read within ~1 s of an mtime
  change so replicas join/leave without restarts.  Mutually exclusive
  with ``FLEET_PEERS``.
* ``FLEET_VNODES`` — virtual nodes per replica on the ownership ring
  (higher = smoother key balance, larger ring).  Default 64.
* ``FLEET_LEASE_MILLIS`` — cross-replica single-flight lease TTL: how
  long the owner waits for a lease holder's publish before waiters
  fall back to local compute (a dead holder costs one duplicate
  fan-out, never a stuck request).  Default 10000.
* ``FLEET_FETCH_TIMEOUT_MILLIS`` — per-peer-call timeout, always
  additionally clamped to HALF the remaining request deadline so the
  local-compute fallback keeps enough budget to run.  Default 2000.
* ``AOT_CACHE_DIR`` — fleet-shared serialized-executable store
  (models/aot_store.py): the first replica to AOT-compile a warmup
  bucket serializes the executable here, and every later replica (or
  restart) deserializes in milliseconds instead of compiling —
  seconds-fast warm cold start, zero jit compilations on the first
  request.  Keyed by an environment digest (jax version, backend,
  device kind/count, model config), so incompatible artifacts are
  never even opened.  Useful fleet or single-replica; independent of
  the ``FLEET_*`` knobs.

Resilience (all opt-in; everything unset = pre-resilience behavior,
byte for byte):

* ``CONNECT_TIMEOUT_MILLIS`` — TCP connect timeout for the upstream
  HTTP transport (previously hard-coded 30 s).  Default 30000.
* ``RESILIENCE_BREAKER_THRESHOLD`` — failure rate in (0, 1] that opens
  a per-upstream circuit breaker (keyed api_base+model).  ``0`` (the
  default) disables breakers entirely.
* ``RESILIENCE_BREAKER_WINDOW`` / ``RESILIENCE_BREAKER_MIN_SAMPLES`` /
  ``RESILIENCE_BREAKER_COOLDOWN_MILLIS`` — sliding-window size, the
  volume threshold before the rate is meaningful, and how long an open
  breaker refuses before half-open probing.  Defaults 20 / 5 / 5000.
* ``RESILIENCE_RETRY_BUDGET`` — retries one score request's judge
  fan-out may spend collectively (token bucket; anti-retry-storm).
  ``0`` = unlimited (no budget).
* ``RESILIENCE_HEDGE_MILLIS`` — static hedge delay: an attempt with no
  first chunk after this long races a backup against the next endpoint
  (the loser is cancelled).  ``0`` = no hedging.
* ``RESILIENCE_HEDGE_QUANTILE`` — hedge at an observed first-chunk
  latency quantile (e.g. ``0.95``) once enough samples exist, falling
  back to ``RESILIENCE_HEDGE_MILLIS`` before that.  ``0`` = static only.
* ``RESILIENCE_DEADLINE_MILLIS`` — default per-request deadline the
  gateway stamps on score/chat requests (clients override per request
  via the ``x-deadline-ms`` header); flows through the fan-out so
  timeouts, backoff sleeps and hedges respect the remaining budget.
  ``0`` = none.
* ``RESILIENCE_QUORUM`` — fraction of total panel weight that must
  settle before the quorum early-exit may cancel stragglers whose votes
  cannot flip the argmax; the final frame ships with ``degraded: true``
  (and is never cached).  ``0`` = always wait for the full panel.
* ``FAULT_PLAN`` — chaos-run fault injection at the transport seam,
  e.g. ``seed=42,connect=0.1,5xx=0.1,stall_first=0.1,stall_ms=200``
  (resilience/faults.py); the hostile-ingest kinds (``giant_line``,
  ``newline_less_flood``, ``oversized_unary``, ``binary_garbage``)
  size their payloads with ``flood_bytes`` (default 8 MiB).  Never set
  in production.

Hostile input & memory pressure (clients/sse.py byte budgets,
resilience/memguard.py — on by default, 0 disables each cap):

* ``JUDGE_STREAM_MAX_BYTES`` — cumulative byte budget for one judge's
  SSE stream leg; also caps the body read on a non-200 upstream
  response.  A trip surfaces as a per-judge ``ingest_cap`` error entry
  in a degraded (never-cached) final frame, counts against that
  upstream's breaker, and is hedgeable like any first-chunk failure.
  Default 33554432 (32 MiB); ``0`` = uncapped.
* ``SSE_MAX_EVENT_BYTES`` — byte cap on one SSE event's accumulated
  ``data:`` payload AND on the parser's newline-less buffered residue
  (one knob bounds both, Python and native parsers identically).
  Default 4194304 (4 MiB); ``0`` = uncapped.
* ``MAX_BODY_BYTES`` — gateway request-body cap (aiohttp
  ``client_max_size``, /fleet/v1 included); oversized requests get a
  structured ``413 {"kind": "payload_too_large"}`` envelope.  Default
  1048576 (1 MiB); ``0`` = aiohttp's own default cap.
* ``MEMGUARD`` — ``1`` (default) runs the host memory governor: RSS
  sampled each ``MEMGUARD_INTERVAL_MILLIS`` against soft/hard
  watermarks.  Soft pressure shrinks the cache byte budgets, trace
  ring and AIMD admission limit (restored on recovery); hard pressure
  sheds new non-exempt work (``503 shed_reason: memory``) and flags
  ``degraded_mem`` on /readyz (still 200).  Recovery is hysteretic.
  ``0`` disables.
* ``MEM_SOFT_BYTES`` / ``MEM_HARD_BYTES`` — the watermarks; ``0``
  (default) = auto at 80% / 90% of /proc/meminfo MemTotal (the
  governor disables itself when MemTotal is unreadable).
* ``MEMGUARD_INTERVAL_MILLIS`` — governor sampling period.
  Default 1000.

Resilience counters + breaker states surface as the ``resilience``
section of ``GET /metrics``.

Overload & lifecycle (resilience/admission.py, resilience/watchdog.py,
serve/lifecycle.py; all opt-in except graceful drain, which only changes
shutdown):

* ``ADMISSION_MAX_INFLIGHT`` — hard cap on concurrently admitted
  requests; excess work is shed at the gateway door with
  ``503 + Retry-After`` and a ``shed_reason`` body instead of queueing.
  ``0`` (the default) disables shedding — the admission gate then only
  tracks in-flight work (the gauge the drain path uses).
* ``ADMISSION_MAX_QUEUE_DEPTH`` — bound on the device batcher's pending
  queue: arrivals beyond it fail fast with 503
  (``shed_reason: batcher_queue_full``).  ``0`` = unbounded.
* ``ADMISSION_ADAPTIVE`` — ``1`` enables the AIMD/gradient concurrency
  limit under the hard cap (Netflix concurrency-limits style): observed
  latency beyond ``ADMISSION_LATENCY_FACTOR`` x a drifting baseline
  decays the limit multiplicatively; a full-but-healthy pipe recovers
  it additively.  Requires ``ADMISSION_MAX_INFLIGHT`` > 0.
* ``ADMISSION_MIN_LIMIT`` / ``ADMISSION_LATENCY_FACTOR`` /
  ``ADMISSION_RETRY_AFTER_MILLIS`` — adaptive floor, the congestion
  threshold multiplier (> 1), and the Retry-After hint on sheds.
  Defaults 2 / 2.0 / 1000.
* ``DRAIN_TIMEOUT_MILLIS`` — SIGTERM/SIGINT graceful-drain budget:
  ``/readyz`` flips to 503, new work sheds (``shed_reason: draining``),
  in-flight streams finish to their ``[DONE]`` and the batcher queue
  empties, the cache disk tier is flushed exactly once, then exit 0.
  Default 10000.
* ``DEVICE_WATCHDOG_MILLIS`` — a device dispatch exceeding this marks
  the device unhealthy (a hung PJRT call): ``/readyz`` flips
  and admission sheds device-dependent endpoints
  (``shed_reason: device_unhealthy``) until the dispatch completes.
  ``0`` (the default) disables the watchdog.
* ``DEVICE_WATCHDOG_INTERVAL_MILLIS`` — monitor-thread check period;
  ``0`` = auto (a quarter of the timeout).
* ``DEVICE_WATCHDOG_CPU_FALLBACK`` — ``1`` builds a CPU twin of the
  embedder at startup and routes embed/consensus dispatches to it while
  the device is unhealthy (degraded but alive beats shedding).
  Requires ``DEVICE_WATCHDOG_MILLIS`` > 0.  Precedence under
  ``MESH_ENABLED``: the twin is single-device, so collapsing a live
  dp×tp mesh onto it is an outage with extra steps — in mesh mode this
  flag therefore ALSO requires ``MESH_FAULT_ENABLED``, and the twin
  only serves after the downsize ladder is exhausted (a watchdog trip
  marks the next classified fault persistent instead of flipping the
  fallback directly).

Mesh fault domains (resilience/meshfault.py; requires ``MESH_ENABLED``,
all opt-in — unset keeps the PR 9 mesh path byte-for-byte):

* ``MESH_FAULT_ENABLED`` — ``1`` arms the mesh fault-domain subsystem:
  dispatch failures classify transient/persistent at the
  embedder/batcher seam, a persistent fault downsizes the mesh one
  rung along the dp-halving ladder (params re-shard onto the surviving
  submesh, dispatch swaps to that rung's AOT executables — every rung
  is warmed at startup), in-flight items re-dispatch on the new shape
  bounded by their deadlines, admission/batcher capacity rescale to
  the surviving chips, and ``/readyz`` stays 200 with a
  ``degraded_mesh`` flag.  Counters ride the ``meshfault`` /metrics
  section.
* ``MESH_FAULT_TRANSIENT_RETRIES`` — consecutive transient dispatch
  faults tolerated (each re-queues and retries on the SAME shape)
  before the streak escalates to persistent and walks the ladder.
  Default 2.
* ``MESH_FAULT_PROBE_MILLIS`` — recovery-prober period: while
  degraded, every interval the full mesh is re-validated with a real
  probe dispatch (a failed probe rolls the upsize back and backs the
  interval off exponentially) and, when healthy, the mesh upsizes back
  to the full shape (capacity restored, ``degraded_mesh`` clears).
  ``0`` (the default) disables automatic recovery.
* ``DEVICE_FAULT_PLAN`` — deterministic device-fault injection at the
  dispatch seam (the ``FAULT_PLAN`` contract at the embedder boundary),
  e.g. ``seed=42,persistent=0.05`` or
  ``script=ok|transient|persistent|ok,hang_ms=50`` with kinds
  ``transient`` / ``persistent`` / ``hang``.  Chaos runs and tier-1
  drills only; never set in production.

Shed/drain/watchdog counters and the inflight/queue-depth gauges
surface as the ``admission`` / ``device_watchdog`` / ``lifecycle`` /
``device_batcher`` sections of ``GET /metrics``.  ``/healthz`` remains
as a deprecated alias of the ``/livez`` + ``/readyz`` split.

Tracing (obs/; all opt-in — with every ``TRACE_*`` knob unset no root
span is ever created and the hot path pays one contextvar read):

* ``TRACE_SAMPLE_RATE`` — head-based sampling probability in [0, 1]:
  the gateway flips this coin once per request at the door.  Degraded,
  shed and errored requests are ALWAYS captured once tracing is
  enabled, regardless of the rate.  ``> 0`` enables tracing.
* ``TRACE_ENABLED`` — ``1`` enables tracing even at rate 0 (capture
  only the degraded/shed/error traces — the cheapest useful setting).
* ``TRACE_RING`` — completed traces kept in memory for
  ``GET /v1/traces`` (index) and ``GET /v1/traces/{trace_id}`` (full
  span tree); oldest evicted first.  Default 256.
* ``TRACE_DIR`` — optional JSONL disk tier: one JSON line per kept
  trace appended to ``traces-<pid>.jsonl`` under this directory
  (setting it also enables tracing).

Performance observability (obs/phases.py, obs/histogram.py,
analysis/roofline.py — DESIGN.md "Performance observability"):

* ``METRICS_DEVICE_TIMING`` — per-bucket device-time measurement at the
  embedder seam: every dispatch is timed enqueue-to-ready and lands in
  the ``phases`` / ``roofline`` sections of ``GET /metrics`` keyed by
  its (mesh-shape, bucket) label.  Under
  the batcher the readiness wait runs on a waiter thread
  (models/dispatch_seam.py), so timing does NOT serialize the dispatch
  pipeline; direct embedder callers pay an inline bracket.  Default on;
  ``0`` skips the recording (device rows and roofline attainment go
  dark, the other phases keep reporting; the device's account,
  ``device_batcher.account``, hears of every dispatch that passes the
  waiter either way, and of no inline one that is not waited for).
  ``GET /metrics?format=prometheus`` renders the same data as
  OpenMetrics text with trace-id exemplars on the hot series.

Consensus-quality observability (obs/quality.py, obs/ledger.py —
DESIGN.md "Consensus quality"; the scorecard/SLI aggregates are always
on like the phase histograms, these knobs tune or extend them):

* ``QUALITY_WINDOW`` — ballots in each judge's sliding drift window;
  a judge is compared against its pre-window baseline and flagged only
  once BOTH hold a full window (cold judges never flag on noise).
  Default 64.
* ``QUALITY_DRIFT_THRESHOLD`` — how far a judge's windowed agreement
  rate or vote-mass-on-winner may fall below its baseline before the
  drift detector flags it, as an absolute rate drop in (0, 1].
  Default 0.25.
* ``LEDGER_RING`` — consensus-outcome records kept in memory (one per
  scored request: panel id, per-judge votes + weights, confidence
  vector, degraded/quorum verdict, trace id — the training substrate
  for weight learning and archive re-scoring).  ``0`` (the default)
  disables the ledger unless ``LEDGER_DIR`` is set (which implies a
  ring of 256).
* ``LEDGER_DIR`` — append-only JSONL disk tier for the ledger:
  one self-describing line per record in ``ledger-<pid>.jsonl``
  (setting it also enables the ledger).
* ``LEDGER_ROTATE_BYTES`` — rotate the active ledger file to a sealed
  timestamped shard (``ledger-<pid>-<ts>-<seq>.jsonl``) once it
  reaches this size; sealed shards still match the read glob, so
  ``load_ledger_records`` and the train/ shard feed see every
  generation.  ``0`` (the default) keeps one ever-growing file.

Offline lane & weight learning (train/, weights/live.py — DESIGN.md
"Offline lane & weight learning"):

* ``WEIGHTS_ENABLED`` — arm the versioned live weight store and the
  ``GET/PUT /v1/weights`` hot-swap endpoints; per-judge overrides
  apply to every tally, the applied version is stamped on each
  ``consensus:tally`` span and ledger record, and shadow-table
  counters feed the quality scorecards.  Default off.
* ``WEIGHTS_PATH`` — persist the live weight tables as JSON
  (``lwc.weights.v1``) so a hot-swapped table survives a restart;
  setting it implies ``WEIGHTS_ENABLED``.
* ``OFFLINE_ENABLED`` — expose ``POST /v1/train/rescore``: an
  admin-only drive of the batcher's offline priority class (archive
  or synthetic candidate groups re-scored whenever the latency lane
  has no ready group).  Default off; the offline class itself always
  exists in the batcher.
* ``OFFLINE_INFLIGHT`` — candidate groups the offline feeder keeps in
  flight (its only backpressure; >= 2 sustains device occupancy on an
  idle mesh).  Default 4.
* ``JUDGE_BIAS_PLAN`` — deterministic per-judge vote perturbation at
  the extraction seam (the ``FAULT_PLAN`` contract applied to a judge's
  ballot), e.g. ``judge=2,after=16,flip=1.0,seed=7`` with kinds
  ``flip`` / ``uniform`` / ``invert`` (resilience/faults.py
  JudgeBiasPlan).  Consensus-quality drills and tier-1 tests only;
  never set in production.

Scorecards ride ``GET /v1/judges`` (+ ``/v1/judges/{id}``) and the
``quality`` section of ``GET /metrics``; the ledger's counters ride
the ``ledger`` section.

Incoming ``traceparent`` headers (W3C) are honored — the caller's
trace id is adopted and its sampled flag forces capture — and every
upstream judge call carries a ``traceparent`` naming the attempt span
as parent.  Kept/dropped/forced counters surface as the ``traces``
section of ``GET /metrics``; per-series ``trace_id`` exemplars ride
the existing latency sections.
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field
from typing import Optional

from ..utils import env_truthy, jsonutil


# The path must not move between processes (a pid, a timestamp or a
# tmpdir never hits), and it must be somewhere the checkout's owner
# controls: inside the checkout, ignored by git.
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ),
    ".jax_cache",
)


class CompileCacheStats:
    """Persistent-cache hit/miss counts of this process, from JAX's own
    monitoring events (a hit is a compile request answered from the
    directory; a miss is an executable compiled and written to it), and
    beside them EVERY compilation the process asked its backend for, with
    the seconds each took: the jitted entry points' specializations, AOT
    buckets, and the un-named helper programs that a slice or a
    ``device_put`` of a new shape builds lazily and that no other counter
    sees.  A cache hit is one of them too: the request still stalls its
    caller while the executable loads."""

    _EVENTS = {
        "/jax/compilation_cache/cache_hits": "hits",
        "/jax/compilation_cache/cache_misses": "misses",
    }
    _BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self, directory: str) -> None:
        import jax

        self.directory = directory
        self.counts = {"hits": 0, "misses": 0}
        self.backend_compiles = 0
        self.backend_compile_s = 0.0
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration
        )

    def _on_event(self, event: str, **kwargs) -> None:
        name = self._EVENTS.get(event)
        if name is not None:
            self.counts[name] += 1

    def _on_duration(self, event: str, seconds: float, **kwargs) -> None:
        if event == self._BACKEND_COMPILE:
            self.backend_compiles += 1
            self.backend_compile_s += seconds

    def snapshot(self) -> dict:
        return {"dir": self.directory, **self.counts}

    def compiles(self) -> dict:
        """The ``jit`` section's ``backend_compiles`` /
        ``backend_compile_s``."""
        return {
            "backend_compiles": self.backend_compiles,
            "backend_compile_s": round(self.backend_compile_s, 6),
        }


def configure_compile_cache() -> CompileCacheStats:
    """Turn the persistent XLA compilation cache on for this process —
    every entry point calls this once, before its first compilation
    (JAX decides whether it has a cache at the first compile).  Where
    ``JAX_COMPILATION_CACHE_DIR`` is set JAX has already read it and no
    directory is set here; otherwise the fixed in-checkout one is.
    Every specialization is cached, not only slow ones: the serving loop
    has a handful of bucketed shapes and all of them matter cold."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update(
            "jax_compilation_cache_dir", DEFAULT_CACHE_DIR
        )
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return CompileCacheStats(jax.config.jax_compilation_cache_dir)


def _parse_warmup(raw) -> list:
    """"64x112,64x128" -> [(64, 112), (64, 128)].  Raises on malformed
    specs: a silently dropped warmup defeats its purpose."""
    if not raw:
        return []
    out = []
    for part in str(raw).split(","):
        part = part.strip()
        if not part:
            continue
        from .gateway import MAX_CONSENSUS_CANDIDATES

        try:
            n_s = part.split("x")
            n, s = int(n_s[0]), int(n_s[1])
            if (
                len(n_s) != 2
                or not 2 <= n <= MAX_CONSENSUS_CANDIDATES
                or s < 1
            ):
                raise ValueError
        except (ValueError, IndexError):
            raise ValueError(
                f"WARMUP spec {part!r}: expected NxS with 2 <= N <= "
                f"{MAX_CONSENSUS_CANDIDATES} candidates (the /consensus "
                "request ceiling — warming an unreachable shape burns "
                "startup time for nothing) and S >= 1 tokens (e.g. 64x112)"
            ) from None
        out.append((n, s))
    return out


def _parse_warmup_r(raw) -> list:
    """"2,4" -> [2, 4], snapped to the runtime's power-of-two R buckets
    and deduped ("3" warms the same specialization as "4").  Raises on
    malformed or non-positive values, same loud-failure contract as
    ``_parse_warmup``."""
    if not raw:
        return []
    buckets = []
    for part in str(raw).split(","):
        part = part.strip()
        if not part:
            continue
        try:
            r = int(part)
            if r < 1:
                raise ValueError
        except ValueError:
            raise ValueError(
                f"WARMUP_R value {part!r}: expected a positive integer "
                "concurrency bucket (e.g. 2)"
            ) from None
        from ..utils import next_pow2

        bucket = next_pow2(r)
        if bucket not in buckets:
            buckets.append(bucket)
    return buckets


def _parse_long_context_warmup(raw) -> list:
    """"4x4096,1x8192" -> [(4, 4096), (1, 8192)]: ring AOT buckets for
    ``MESH_SHAPE=DPxTPxSP`` serving (N candidates x S tokens; N=1 warms
    the plain long-document embed path, so the floor is 1 where
    ``WARMUP``'s is 2).  Same loud-failure contract as
    ``_parse_warmup``."""
    if not raw:
        return []
    out = []
    for part in str(raw).split(","):
        part = part.strip()
        if not part:
            continue
        try:
            n_s = part.split("x")
            n, s = int(n_s[0]), int(n_s[1])
            if len(n_s) != 2 or n < 1 or s < 1:
                raise ValueError
        except (ValueError, IndexError):
            raise ValueError(
                f"LONG_CONTEXT_WARMUP spec {part!r}: expected NxS with "
                "N >= 1 candidates and S >= 1 tokens (e.g. 4x4096)"
            ) from None
        out.append((n, s))
    return out


def _parse_mesh_shape(raw) -> Optional[tuple]:
    """"4x2" -> (4, 2); "2x2x2" -> (2, 2, 2).  The optional third axis
    is sequence parallelism (ring attention, parallel/ring.py) — the
    2-form stays the exact pre-sp serving path.  Raises on malformed
    values, same loud-failure contract as ``_parse_warmup``: a silently
    dropped mesh shape would serve single-device while claiming a
    mesh."""
    if not raw:
        return None
    try:
        parts = [int(p) for p in str(raw).strip().split("x")]
        if len(parts) not in (2, 3) or any(p < 1 for p in parts):
            raise ValueError
    except (ValueError, IndexError):
        raise ValueError(
            f"MESH_SHAPE {raw!r}: expected DPxTP or DPxTPxSP with "
            "positive axes (e.g. 4x2 = batches split 4-way, encoder "
            "params 2-way; 2x2x2 adds 2-way sequence parallelism for "
            "long-context serving)"
        ) from None
    if len(parts) == 3 and parts[2] == 1:
        # sp=1 is exactly the 2-axis mesh; normalize so downstream code
        # (and the byte-identical no-sp contract) sees one canonical form
        parts = parts[:2]
    return tuple(parts)


# names this program once read and no longer does: a deployment that
# still sets one must hear of it at start-up, not fall to one device
_REMOVED_NAMES = ("MESH_DP", "MESH_TP", "MESH_SP")


def _refuse_removed_names(env: dict) -> None:
    for name in _REMOVED_NAMES:
        if env.get(name):
            raise ValueError(
                f"{name} is no longer read: a mesh is configured with "
                "MESH_ENABLED=1 MESH_SHAPE=DPxTP[xSP] (e.g. 4x2, or 2x1x4 "
                "for 4-way sequence parallelism)"
            )


def _parse_peer_list(raw) -> list:
    """"http://a:5000, http://b:5000" -> normalized URL list (trailing
    slashes stripped, empties dropped)."""
    if not raw:
        return []
    return [p.strip().rstrip("/") for p in str(raw).split(",") if p.strip()]


def _non_negative_int(env: dict, name: str, default: int) -> int:
    value = int(env.get(name, default))
    if value < 0:
        raise ValueError(
            f"{name}={value} must be >= 0 (0 = unbounded)"
        )
    return value


def load_dotenv(path: str = ".env") -> None:
    if not os.path.exists(path):
        return
    with open(path, encoding="utf-8") as f:
        for line in f:
            line = line.strip()
            if not line or line.startswith("#") or "=" not in line:
                continue
            if line.startswith("export "):
                line = line[len("export "):]
            key, _, value = line.partition("=")
            value = value.strip()
            # dotenv-style quoted values; unquoted values drop trailing
            # inline comments
            if len(value) >= 2 and value[0] == value[-1] and value[0] in "'\"":
                value = value[1:-1]
            elif " #" in value:
                value = value.split(" #", 1)[0].rstrip()
            os.environ.setdefault(key.strip(), value)


@dataclass
class Config:
    # backoff (main.rs:5-16)
    backoff_initial_interval_millis: float = 100.0
    backoff_randomization_factor: float = 0.5
    backoff_multiplier: float = 1.5
    backoff_max_interval_millis: float = 1000.0
    backoff_max_elapsed_time_millis: float = 40000.0
    # stream timeouts (main.rs:17-20)
    first_chunk_timeout_millis: float = 10000.0
    other_chunk_timeout_millis: float = 60000.0
    # TCP connect timeout (was hard-coded sock_connect=30)
    connect_timeout_millis: float = 30000.0
    # upstream endpoints (main.rs:21-33)
    openai_apis: list = field(default_factory=list)  # [{api_base, api_key}]
    openai_user_agent: Optional[str] = None
    openai_x_title: Optional[str] = None
    openai_referer: Optional[str] = None
    # bind (main.rs:34-37)
    address: str = "0.0.0.0"
    port: int = 5000
    # TPU-framework additions
    embedder_model: Optional[str] = None  # e.g. "bge-small-en"
    embedder_weights: Optional[str] = None  # local checkpoint path
    embedder_vocab: Optional[str] = None  # path to vocab.txt
    embedder_max_tokens: int = 512
    embedder_quantize: str = "none"  # "int8" = W8A8 serving (models/quant.py)
    # reward-model re-ranking service (POST /consensus {"scorer": "rm"})
    rm_model: Optional[str] = None  # e.g. "deberta-v3-base"
    rm_weights: Optional[str] = None  # local HF/orbax checkpoint
    rm_vocab: Optional[str] = None  # spm.model / vocab.txt
    rm_max_tokens: int = 512
    rm_quantize: str = "none"  # "int8" = W8A8 RM serving (models/quant.py)
    # -- local judge panel (POST /consensus {"scorer": "judge"}) --
    judge_model: Optional[str] = None  # e.g. "glm-4.7-flash"
    judge_weights: Optional[str] = None  # HF checkpoint, one file or sharded
    judge_vocab: Optional[str] = None  # spm.model / vocab.txt
    judge_max_tokens: int = 8192  # the one sequence bucket of a call
    judge_quantize: str = "none"  # "int8" = W8A8 dense products
    # first-class mesh serving (parallel/sharding.py shard_embedder_mesh):
    # off by default = the single-device path bit-for-bit
    mesh_enabled: bool = False
    mesh_shape: Optional[tuple] = None  # (dp, tp[, sp]) from "DPxTP[xSP]"
    # ring AOT buckets (NxS) warmed when MESH_SHAPE carries an sp axis
    long_context_warmup: list = field(default_factory=list)
    profile_dir: Optional[str] = None
    archive_path: Optional[str] = None
    archive_write: bool = False
    # also archive STREAMED completions by teeing the chunk stream into
    # the fold (unary = fold(chunks)) at stream end; off by default —
    # folding retains every streamed response in memory
    archive_streaming: bool = False
    # FIFO cap per completion table; 0 = unbounded
    archive_max_completions: int = 65536
    tables_path: Optional[str] = None
    batch_window_ms: float = 3.0
    batch_max: int = 64
    # concurrent device dispatches in flight (host staging of batch k+1
    # overlaps device compute of batch k)
    batch_pipeline: int = 2
    # encoder rows per dispatch (bursts chunk into overlappable pieces)
    batch_max_rows: int = 512
    # submit-time tokenization pool (0 = tokenize on dispatch thread)
    host_tokenizer_workers: int = 2
    # host fast lane for the streaming consensus path (serve/frames.py
    # splice templates + clients/tally.py fixed-point tally); off = the
    # byte-identical slow path everywhere
    host_fastpath: bool = False
    # reusable host staging buffers per (shape, dtype); 0 = no reuse
    staging_buffers: int = 2
    # [(n_candidates, seq), ...] consensus shapes to pre-compile at
    # startup (WARMUP env, e.g. "64x112,64x128"); [] = lazy compiles
    warmup: list = field(default_factory=list)
    # power-of-two concurrency buckets to pre-compile the grouped
    # (consensus_confidence_tokens_many) path for, per WARMUP shape
    # (WARMUP_R env, e.g. "2,4"); [] = single-request path only
    warmup_r: list = field(default_factory=list)
    # AOT-compile warmed buckets (.lower().compile(), no device
    # dispatch) and serve them from the embedder's executable table;
    # False = dispatch-based warmup (WARMUP_AOT env)
    warmup_aot: bool = True
    # consensus result cache (cache/): TTL seconds, 0 = disabled (exact
    # pre-cache behavior); byte budget for the in-memory LRU; optional
    # JSONL disk tier for warm restarts
    score_cache_ttl_sec: float = 0.0
    score_cache_max_bytes: int = 64 * 1024 * 1024
    score_cache_dir: Optional[str] = None
    # per-row embedding memoization in the micro-batcher; defaults on
    # whenever the score cache is on
    score_cache_embed: bool = False
    score_cache_embed_max_bytes: int = 32 * 1024 * 1024
    # resilience subsystem (resilience/): every knob defaults to "off";
    # resilience_policy() returns None when nothing is enabled so the
    # clients run their pre-resilience code paths untouched
    resilience_breaker_threshold: float = 0.0  # 0 = breakers disabled
    resilience_breaker_window: int = 20
    resilience_breaker_min_samples: int = 5
    resilience_breaker_cooldown_millis: float = 5000.0
    resilience_retry_budget: int = 0  # 0 = unlimited
    resilience_hedge_millis: float = 0.0  # 0 = no hedging
    resilience_hedge_quantile: float = 0.0  # 0 = static delay only
    resilience_deadline_millis: float = 0.0  # 0 = no default deadline
    resilience_quorum: float = 0.0  # 0 = wait for the full panel
    # chaos-run fault injection spec (resilience/faults.py); None = off
    fault_plan: Optional[str] = None
    # ingest byte budgets (clients/sse.py, clients/chat.py): per-judge
    # cumulative stream budget (doubles as the non-200 body-read cap)
    # and the SSE event/residue cap.  Library defaults are 0/off; the
    # SERVING layer turns them on here — 0 disables a cap explicitly
    judge_stream_max_bytes: int = 32 * 1024 * 1024
    sse_max_event_bytes: int = 4 * 1024 * 1024
    # gateway request-body cap -> aiohttp client_max_size (413 with a
    # structured payload_too_large envelope); 0 = aiohttp's default
    max_body_bytes: int = 1024 * 1024
    # host memory governor (resilience/memguard.py): soft/hard RSS
    # watermarks (0 = auto from MemTotal), sampling period, on/off
    memguard_enabled: bool = True
    mem_soft_bytes: int = 0
    mem_hard_bytes: int = 0
    memguard_interval_millis: float = 1000.0
    # overload protection (resilience/admission.py): hard in-flight cap
    # (0 = no shedding, gauge only), batcher queue bound (0 = unbounded),
    # and the AIMD/gradient adaptive limit under the cap
    admission_max_inflight: int = 0
    admission_max_queue_depth: int = 0
    admission_adaptive: bool = False
    admission_min_limit: int = 2
    admission_latency_factor: float = 2.0
    admission_retry_after_millis: float = 1000.0
    # graceful-drain budget on SIGTERM/SIGINT (serve/lifecycle.py)
    drain_timeout_millis: float = 10000.0
    # device dispatch watchdog (resilience/watchdog.py); 0 = off
    device_watchdog_millis: float = 0.0
    device_watchdog_interval_millis: float = 0.0  # 0 = auto (timeout/4)
    device_watchdog_cpu_fallback: bool = False
    # mesh fault domains (resilience/meshfault.py): classification +
    # downsize ladder + re-dispatch; requires mesh_enabled, off = the
    # PR 9 mesh path untouched
    mesh_fault_enabled: bool = False
    # consecutive transient faults tolerated before escalating to a
    # persistent (ladder-walking) fault
    mesh_fault_transient_retries: int = 2
    # recovery-prober period; 0 = no automatic upsize
    mesh_fault_probe_millis: float = 0.0
    # deterministic device-fault injection spec (DeviceFaultPlan.parse);
    # None = off (chaos runs and tier-1 drills only)
    device_fault_plan: Optional[str] = None
    # runtime lockdep (analysis/witness.py): wrap the registered
    # threading primitives and validate real acquisition order against
    # the declared DAG; off by default — intended for chaos/soak drills
    # (~1 dict update per lock acquisition when on)
    lock_witness: bool = False
    # request tracing (obs/): head-sample rate, forced-on flag (capture
    # only degraded/shed/error at rate 0), ring capacity, JSONL dir.
    # trace_sink() returns None when nothing enables tracing, keeping
    # the untraced hot path at one contextvar read per helper call.
    trace_sample_rate: float = 0.0
    trace_enabled: bool = False
    trace_ring: int = 256
    trace_dir: Optional[str] = None
    # per-bucket device timing (enqueue-to-ready at the embedder seam;
    # waiter-thread readiness under the batcher, inline bracket for
    # direct callers) feeding the phases/roofline metrics sections;
    # METRICS_DEVICE_TIMING=0 skips the recording entirely
    metrics_device_timing: bool = True
    # consensus-quality observability (obs/quality.py): drift-window
    # size and the agreement/calibration drop that flags a judge
    quality_window: int = 64
    quality_drift_threshold: float = 0.25
    # consensus-outcome ledger (obs/ledger.py): ring capacity (0 = off
    # unless ledger_dir is set), the optional JSONL disk tier, and the
    # size at which the active file seals into a timestamped shard
    ledger_ring: int = 0
    ledger_dir: Optional[str] = None
    ledger_rotate_bytes: int = 0
    # versioned live weight tables (weights/live.py): hot-swap via
    # GET/PUT /v1/weights; weights_path persists them across restarts
    # (and implies enabled)
    weights_enabled: bool = False
    weights_path: Optional[str] = None
    # offline lane driver (train/feed.py): POST /v1/train/rescore gate
    # and the feeder's in-flight group bound
    offline_enabled: bool = False
    offline_inflight: int = 4
    # deterministic judge-vote perturbation spec (JudgeBiasPlan.parse);
    # None = off (consensus-quality drills and tier-1 tests only)
    judge_bias_plan: Optional[str] = None
    # fleet tier (fleet/): replicated score cache with consistent-hash
    # ownership and cross-replica single-flight leases.  fleet_self
    # unset = everything off; fleet_config() returns None
    fleet_self: Optional[str] = None
    fleet_peers: list = field(default_factory=list)
    fleet_peers_file: Optional[str] = None
    fleet_vnodes: int = 64
    fleet_lease_millis: float = 10000.0
    fleet_fetch_timeout_millis: float = 2000.0
    # deterministic peer fault injection spec (fleet/faults.py
    # FleetFaultPlan.parse); None = the seam is a single is-None check
    fleet_fault_plan: Optional[str] = None
    # consecutive peer transport failures before quarantine (0 = never
    # quarantine), and how often a quarantined peer is probed for
    # re-admission
    fleet_quarantine_failures: int = 3
    fleet_probe_millis: float = 1000.0
    # fleet-shared serialized-executable store (models/aot_store.py);
    # None = compile every AOT bucket locally as before
    aot_cache_dir: Optional[str] = None

    @classmethod
    def from_env(cls, env: Optional[dict] = None) -> "Config":
        env = dict(os.environ if env is None else env)
        _refuse_removed_names(env)

        def get_f(name, default):
            return float(env.get(name, default))

        apis_json = env.get("OPENAI_APIS")
        if apis_json:
            apis = jsonutil.loads(apis_json)
        else:
            base, key = env.get("OPENAI_API_BASE"), env.get("OPENAI_API_KEY")
            if base and key:
                apis = [{"api_base": base, "api_key": key}]
            else:
                apis = []
        config = cls(
            backoff_initial_interval_millis=get_f(
                "BACKOFF_INITIAL_INTERVAL_MILLIS", 100
            ),
            backoff_randomization_factor=get_f(
                "BACKOFF_RANDOMIZATION_FACTOR", 0.5
            ),
            backoff_multiplier=get_f("BACKOFF_MULTIPLIER", 1.5),
            backoff_max_interval_millis=get_f(
                "BACKOFF_MAX_INTERVAL_MILLIS", 1000
            ),
            backoff_max_elapsed_time_millis=get_f(
                "BACKOFF_MAX_ELAPSED_TIME_MILLIS", 40000
            ),
            first_chunk_timeout_millis=get_f(
                "FIRST_CHUNK_TIMEOUT_MILLIS", 10000
            ),
            other_chunk_timeout_millis=get_f(
                "OTHER_CHUNK_TIMEOUT_MILLIS", 60000
            ),
            connect_timeout_millis=get_f("CONNECT_TIMEOUT_MILLIS", 30000),
            openai_apis=apis,
            openai_user_agent=env.get("OPENAI_USER_AGENT"),
            openai_x_title=env.get("OPENAI_X_TITLE"),
            openai_referer=env.get("OPENAI_REFERER"),
            address=env.get("ADDRESS", "0.0.0.0"),
            port=int(env.get("PORT", 5000)),
            embedder_model=env.get("EMBEDDER_MODEL"),
            embedder_weights=env.get("EMBEDDER_WEIGHTS"),
            embedder_vocab=env.get("EMBEDDER_VOCAB"),
            embedder_max_tokens=int(env.get("EMBEDDER_MAX_TOKENS") or 512),
            embedder_quantize=env.get("EMBEDDER_QUANTIZE") or "none",
            rm_model=env.get("RM_MODEL"),
            rm_weights=env.get("RM_WEIGHTS"),
            rm_vocab=env.get("RM_VOCAB"),
            rm_max_tokens=int(env.get("RM_MAX_TOKENS", 512)),
            rm_quantize=env.get("RM_QUANTIZE") or "none",
            judge_model=env.get("JUDGE_MODEL") or None,
            judge_weights=env.get("JUDGE_WEIGHTS") or None,
            judge_vocab=env.get("JUDGE_VOCAB") or None,
            judge_max_tokens=int(env.get("JUDGE_MAX_TOKENS", 8192)),
            judge_quantize=env.get("JUDGE_QUANTIZE") or "none",
            mesh_enabled=env_truthy(env.get("MESH_ENABLED", "0")),
            mesh_shape=_parse_mesh_shape(env.get("MESH_SHAPE")),
            long_context_warmup=_parse_long_context_warmup(
                env.get("LONG_CONTEXT_WARMUP")
            ),
            profile_dir=env.get("PROFILE_DIR"),
            archive_path=env.get("ARCHIVE_PATH"),
            archive_write=env_truthy(
                env.get("ARCHIVE_WRITE", "1" if env.get("ARCHIVE_PATH") else "0")
            ),
            archive_streaming=env_truthy(env.get("ARCHIVE_STREAMING", "0")),
            archive_max_completions=_non_negative_int(
                env, "ARCHIVE_MAX_COMPLETIONS", 65536
            ),
            tables_path=env.get("TABLES_PATH"),
            batch_window_ms=get_f("BATCH_WINDOW_MS", 3.0),
            batch_max=int(env.get("BATCH_MAX", 64)),
            batch_pipeline=max(1, int(env.get("BATCH_PIPELINE", 2))),
            batch_max_rows=max(1, int(env.get("BATCH_MAX_ROWS", 512))),
            host_tokenizer_workers=_non_negative_int(
                env, "HOST_TOKENIZER_WORKERS", 2
            ),
            host_fastpath=env_truthy(env.get("HOST_FASTPATH", "0")),
            staging_buffers=_non_negative_int(env, "STAGING_BUFFERS", 2),
            warmup=_parse_warmup(env.get("WARMUP")),
            warmup_r=_parse_warmup_r(env.get("WARMUP_R")),
            warmup_aot=env_truthy(env.get("WARMUP_AOT", "1")),
            score_cache_ttl_sec=max(0.0, get_f("SCORE_CACHE_TTL", 0)),
            score_cache_max_bytes=_non_negative_int(
                env, "SCORE_CACHE_MAX_BYTES", 64 * 1024 * 1024
            ),
            score_cache_dir=env.get("SCORE_CACHE_DIR"),
            score_cache_embed=env_truthy(
                env.get(
                    "SCORE_CACHE_EMBED",
                    "1" if float(env.get("SCORE_CACHE_TTL", 0) or 0) > 0 else "0",
                )
            ),
            score_cache_embed_max_bytes=_non_negative_int(
                env, "SCORE_CACHE_EMBED_MAX_BYTES", 32 * 1024 * 1024
            ),
            resilience_breaker_threshold=get_f(
                "RESILIENCE_BREAKER_THRESHOLD", 0
            ),
            resilience_breaker_window=max(
                1, int(env.get("RESILIENCE_BREAKER_WINDOW", 20))
            ),
            resilience_breaker_min_samples=max(
                1, int(env.get("RESILIENCE_BREAKER_MIN_SAMPLES", 5))
            ),
            resilience_breaker_cooldown_millis=get_f(
                "RESILIENCE_BREAKER_COOLDOWN_MILLIS", 5000
            ),
            resilience_retry_budget=_non_negative_int(
                env, "RESILIENCE_RETRY_BUDGET", 0
            ),
            resilience_hedge_millis=get_f("RESILIENCE_HEDGE_MILLIS", 0),
            resilience_hedge_quantile=get_f("RESILIENCE_HEDGE_QUANTILE", 0),
            resilience_deadline_millis=get_f("RESILIENCE_DEADLINE_MILLIS", 0),
            resilience_quorum=get_f("RESILIENCE_QUORUM", 0),
            fault_plan=env.get("FAULT_PLAN"),
            judge_stream_max_bytes=_non_negative_int(
                env, "JUDGE_STREAM_MAX_BYTES", 32 * 1024 * 1024
            ),
            sse_max_event_bytes=_non_negative_int(
                env, "SSE_MAX_EVENT_BYTES", 4 * 1024 * 1024
            ),
            max_body_bytes=_non_negative_int(
                env, "MAX_BODY_BYTES", 1024 * 1024
            ),
            memguard_enabled=env_truthy(env.get("MEMGUARD", "1")),
            mem_soft_bytes=_non_negative_int(env, "MEM_SOFT_BYTES", 0),
            mem_hard_bytes=_non_negative_int(env, "MEM_HARD_BYTES", 0),
            memguard_interval_millis=get_f("MEMGUARD_INTERVAL_MILLIS", 1000),
            admission_max_inflight=_non_negative_int(
                env, "ADMISSION_MAX_INFLIGHT", 0
            ),
            admission_max_queue_depth=_non_negative_int(
                env, "ADMISSION_MAX_QUEUE_DEPTH", 0
            ),
            admission_adaptive=env_truthy(env.get("ADMISSION_ADAPTIVE", "0")),
            admission_min_limit=max(
                1, int(env.get("ADMISSION_MIN_LIMIT", 2))
            ),
            admission_latency_factor=get_f("ADMISSION_LATENCY_FACTOR", 2.0),
            admission_retry_after_millis=get_f(
                "ADMISSION_RETRY_AFTER_MILLIS", 1000
            ),
            drain_timeout_millis=get_f("DRAIN_TIMEOUT_MILLIS", 10000),
            device_watchdog_millis=get_f("DEVICE_WATCHDOG_MILLIS", 0),
            device_watchdog_interval_millis=get_f(
                "DEVICE_WATCHDOG_INTERVAL_MILLIS", 0
            ),
            device_watchdog_cpu_fallback=env_truthy(
                env.get("DEVICE_WATCHDOG_CPU_FALLBACK", "0")
            ),
            mesh_fault_enabled=env_truthy(
                env.get("MESH_FAULT_ENABLED", "0")
            ),
            mesh_fault_transient_retries=_non_negative_int(
                env, "MESH_FAULT_TRANSIENT_RETRIES", 2
            ),
            mesh_fault_probe_millis=get_f("MESH_FAULT_PROBE_MILLIS", 0),
            device_fault_plan=env.get("DEVICE_FAULT_PLAN"),
            lock_witness=env_truthy(env.get("LOCK_WITNESS", "0")),
            trace_sample_rate=get_f("TRACE_SAMPLE_RATE", 0),
            trace_enabled=env_truthy(env.get("TRACE_ENABLED", "0")),
            trace_ring=max(1, int(env.get("TRACE_RING", 256))),
            trace_dir=env.get("TRACE_DIR"),
            metrics_device_timing=env_truthy(
                env.get("METRICS_DEVICE_TIMING", "1")
            ),
            quality_window=int(env.get("QUALITY_WINDOW", 64)),
            quality_drift_threshold=get_f("QUALITY_DRIFT_THRESHOLD", 0.25),
            ledger_ring=_non_negative_int(env, "LEDGER_RING", 0),
            ledger_dir=env.get("LEDGER_DIR"),
            ledger_rotate_bytes=_non_negative_int(
                env, "LEDGER_ROTATE_BYTES", 0
            ),
            weights_enabled=env_truthy(env.get("WEIGHTS_ENABLED", "0")),
            weights_path=env.get("WEIGHTS_PATH"),
            offline_enabled=env_truthy(env.get("OFFLINE_ENABLED", "0")),
            offline_inflight=_non_negative_int(env, "OFFLINE_INFLIGHT", 4),
            judge_bias_plan=env.get("JUDGE_BIAS_PLAN"),
            fleet_self=env.get("FLEET_SELF"),
            fleet_peers=_parse_peer_list(env.get("FLEET_PEERS")),
            fleet_peers_file=env.get("FLEET_PEERS_FILE"),
            fleet_vnodes=max(1, int(env.get("FLEET_VNODES", 64))),
            fleet_lease_millis=get_f("FLEET_LEASE_MILLIS", 10000),
            fleet_fetch_timeout_millis=get_f(
                "FLEET_FETCH_TIMEOUT_MILLIS", 2000
            ),
            fleet_fault_plan=env.get("FLEET_FAULT_PLAN"),
            fleet_quarantine_failures=_non_negative_int(
                env, "FLEET_QUARANTINE_FAILURES", 3
            ),
            fleet_probe_millis=get_f("FLEET_PROBE_MILLIS", 1000),
            aot_cache_dir=env.get("AOT_CACHE_DIR"),
        )
        if config.quality_window < 1:
            raise ValueError(
                f"QUALITY_WINDOW={config.quality_window} must be >= 1 "
                "(ballots per judge in the sliding drift window)"
            )
        if not 0 < config.quality_drift_threshold <= 1:
            raise ValueError(
                f"QUALITY_DRIFT_THRESHOLD={config.quality_drift_threshold} "
                "must be an absolute rate drop in (0, 1]"
            )
        if not 0 <= config.resilience_quorum <= 1:
            raise ValueError(
                f"RESILIENCE_QUORUM={config.resilience_quorum} must be a "
                "weight fraction in [0, 1]"
            )
        if not 0 <= config.resilience_hedge_quantile < 1:
            raise ValueError(
                f"RESILIENCE_HEDGE_QUANTILE={config.resilience_hedge_quantile}"
                " must be a quantile in [0, 1)"
            )
        if config.admission_adaptive and config.admission_max_inflight <= 0:
            raise ValueError(
                "ADMISSION_ADAPTIVE=1 needs ADMISSION_MAX_INFLIGHT > 0: "
                "the adaptive limit operates UNDER the hard cap (set e.g. "
                "ADMISSION_MAX_INFLIGHT=64 ADMISSION_ADAPTIVE=1)"
            )
        if config.admission_latency_factor <= 1.0:
            raise ValueError(
                f"ADMISSION_LATENCY_FACTOR={config.admission_latency_factor} "
                "must be > 1 (it multiplies the latency baseline to form "
                "the congestion threshold)"
            )
        if (
            config.mem_soft_bytes > 0
            and config.mem_hard_bytes > 0
            and config.mem_hard_bytes < config.mem_soft_bytes
        ):
            raise ValueError(
                f"MEM_HARD_BYTES={config.mem_hard_bytes} must be >= "
                f"MEM_SOFT_BYTES={config.mem_soft_bytes}: the hard "
                "watermark sheds work the soft watermark only degrades"
            )
        if config.memguard_interval_millis <= 0:
            raise ValueError(
                f"MEMGUARD_INTERVAL_MILLIS={config.memguard_interval_millis}"
                " must be > 0 (the governor's RSS sampling period)"
            )
        if config.drain_timeout_millis < 0:
            raise ValueError(
                f"DRAIN_TIMEOUT_MILLIS={config.drain_timeout_millis} "
                "must be >= 0 (0 = shed immediately, no drain wait)"
            )
        if config.device_watchdog_millis < 0:
            raise ValueError(
                f"DEVICE_WATCHDOG_MILLIS={config.device_watchdog_millis} "
                "must be >= 0 (0 = watchdog disabled)"
            )
        if (
            config.device_watchdog_cpu_fallback
            and config.device_watchdog_millis <= 0
        ):
            raise ValueError(
                "DEVICE_WATCHDOG_CPU_FALLBACK=1 needs "
                "DEVICE_WATCHDOG_MILLIS > 0: without the watchdog nothing "
                "ever routes work to the fallback"
            )
        if not 0 <= config.trace_sample_rate <= 1:
            raise ValueError(
                f"TRACE_SAMPLE_RATE={config.trace_sample_rate} must be a "
                "probability in [0, 1]"
            )
        if config.mesh_shape is not None and not config.mesh_enabled:
            raise ValueError(
                "MESH_SHAPE is set but MESH_ENABLED is not: the shape only "
                "configures the first-class mesh mode (set MESH_ENABLED=1 "
                "MESH_SHAPE=4x2)"
            )
        if config.long_context_warmup and (
            config.mesh_shape is None or len(config.mesh_shape) != 3
        ):
            raise ValueError(
                "LONG_CONTEXT_WARMUP is set but MESH_SHAPE carries no sp "
                "axis: ring buckets only compile on a sequence-parallel "
                "mesh (set MESH_SHAPE=DPxTPxSP, e.g. 2x2x2, or unset "
                "LONG_CONTEXT_WARMUP)"
            )
        if config.mesh_fault_enabled and not config.mesh_enabled:
            raise ValueError(
                "MESH_FAULT_ENABLED=1 needs MESH_ENABLED=1: fault domains, "
                "the downsize ladder and re-dispatch all operate on the "
                "first-class serving mesh (set MESH_ENABLED=1, optionally "
                "MESH_SHAPE=DPxTP)"
            )
        if config.device_fault_plan and not config.mesh_fault_enabled:
            raise ValueError(
                "DEVICE_FAULT_PLAN is set but MESH_FAULT_ENABLED is not: "
                "the injection seam lives in the mesh fault-domain "
                "subsystem, so the plan would silently never fire (set "
                "MESH_FAULT_ENABLED=1, or unset DEVICE_FAULT_PLAN)"
            )
        if config.mesh_fault_probe_millis < 0:
            raise ValueError(
                f"MESH_FAULT_PROBE_MILLIS={config.mesh_fault_probe_millis} "
                "must be >= 0 (0 = no automatic recovery upsize)"
            )
        if (
            config.mesh_enabled
            and config.device_watchdog_cpu_fallback
            and not config.mesh_fault_enabled
        ):
            # precedence contract: the CPU twin is single-device, so in
            # mesh mode it must be the LAST resort — after the downsize
            # ladder is exhausted — never the first response to a trip.
            # Without the fault-domain subsystem there is no ladder, and
            # a watchdog trip would collapse the whole mesh onto one CPU.
            raise ValueError(
                "DEVICE_WATCHDOG_CPU_FALLBACK=1 with MESH_ENABLED=1 needs "
                "MESH_FAULT_ENABLED=1: the CPU twin is single-device, so "
                "in mesh mode it is the last resort AFTER the downsize "
                "ladder is exhausted — enabling it without the ladder "
                "would collapse the mesh to one CPU on the first trip"
            )
        if config.warmup_r and not config.warmup:
            # same loud-failure contract as _parse_warmup: WARMUP_R names
            # concurrency buckets *per WARMUP shape* — without shapes it
            # would silently warm nothing
            raise ValueError(
                "WARMUP_R is set but WARMUP is empty: the grouped-path "
                "warmup needs NxS shapes to compile (set WARMUP, e.g. "
                "WARMUP=64x112 WARMUP_R=2)"
            )
        if config.fleet_peers and config.fleet_peers_file:
            raise ValueError(
                "FLEET_PEERS and FLEET_PEERS_FILE are mutually exclusive: "
                "one roster source of truth (static list OR watched file)"
            )
        if (config.fleet_peers or config.fleet_peers_file) and (
            not config.fleet_self
        ):
            raise ValueError(
                "a fleet roster is set but FLEET_SELF is not: replicas "
                "must know their own base URL to place themselves on the "
                "ownership ring (set e.g. FLEET_SELF=http://10.0.0.3:5000)"
            )
        if config.fleet_self:
            if not (config.fleet_peers or config.fleet_peers_file):
                raise ValueError(
                    "FLEET_SELF is set but no roster is: the fleet needs "
                    "FLEET_PEERS (static) or FLEET_PEERS_FILE (watched) — "
                    "a roster of one is valid but must be explicit"
                )
            if config.fleet_peers and (
                config.fleet_self.rstrip("/") not in config.fleet_peers
            ):
                raise ValueError(
                    f"FLEET_SELF={config.fleet_self} is not in FLEET_PEERS: "
                    "the static roster must include this replica, or peers "
                    "would route its owned keys elsewhere"
                )
            if config.score_cache_ttl_sec <= 0:
                raise ValueError(
                    "FLEET_SELF is set but SCORE_CACHE_TTL is 0: the fleet "
                    "tier replicates score-cache entries, so without a "
                    "cache there is nothing to own, lease, or hand off "
                    "(set SCORE_CACHE_TTL > 0)"
                )
            if config.fleet_lease_millis <= 0:
                raise ValueError(
                    f"FLEET_LEASE_MILLIS={config.fleet_lease_millis} must "
                    "be > 0 (the lease TTL bounds how long waiters trust a "
                    "possibly-dead holder)"
                )
            if config.fleet_fetch_timeout_millis <= 0:
                raise ValueError(
                    f"FLEET_FETCH_TIMEOUT_MILLIS="
                    f"{config.fleet_fetch_timeout_millis} must be > 0"
                )
            if config.fleet_probe_millis <= 0:
                raise ValueError(
                    f"FLEET_PROBE_MILLIS={config.fleet_probe_millis} must "
                    "be > 0 (how often a quarantined peer is probed for "
                    "re-admission, and the owner-side lease-wait slice)"
                )
        if config.fleet_fault_plan is not None:
            # parse eagerly so a typo fails at startup, not mid-drill
            from ..fleet.faults import FleetFaultPlan

            FleetFaultPlan.parse(config.fleet_fault_plan)
        if config.offline_enabled and config.offline_inflight < 1:
            raise ValueError(
                f"OFFLINE_INFLIGHT={config.offline_inflight} must be >= 1 "
                "(concurrent offline-lane groups; a zero-slot rescore "
                "drive can never make progress)"
            )
        return config

    def backoff_policy(self):
        from ..clients.chat import BackoffPolicy

        return BackoffPolicy(
            initial_interval_ms=self.backoff_initial_interval_millis,
            randomization_factor=self.backoff_randomization_factor,
            multiplier=self.backoff_multiplier,
            max_interval_ms=self.backoff_max_interval_millis,
            max_elapsed_ms=self.backoff_max_elapsed_time_millis,
        )

    def api_bases(self) -> list:
        from ..clients.chat import ApiBase

        return [ApiBase.from_json_obj(a) for a in self.openai_apis]

    def resilience_policy(self):
        """The configured ResiliencePolicy, or None when every knob is off
        (None keeps the clients on their pre-resilience code paths)."""
        from ..resilience import (
            BreakerConfig,
            BreakerRegistry,
            HedgePolicy,
            ResiliencePolicy,
        )

        breakers = None
        if self.resilience_breaker_threshold > 0:
            breakers = BreakerRegistry(
                BreakerConfig(
                    threshold=self.resilience_breaker_threshold,
                    window=self.resilience_breaker_window,
                    min_samples=self.resilience_breaker_min_samples,
                    cooldown_ms=self.resilience_breaker_cooldown_millis,
                )
            )
        hedge = None
        if self.resilience_hedge_millis > 0 or self.resilience_hedge_quantile > 0:
            hedge = HedgePolicy(
                delay_ms=self.resilience_hedge_millis,
                quantile=self.resilience_hedge_quantile,
            )
        if (
            breakers is None
            and hedge is None
            and self.resilience_retry_budget <= 0
            and self.resilience_quorum <= 0
            and self.resilience_deadline_millis <= 0
        ):
            return None
        return ResiliencePolicy(
            breakers=breakers,
            hedge=hedge,
            retry_budget_tokens=self.resilience_retry_budget,
            quorum_fraction=self.resilience_quorum,
            deadline_ms=self.resilience_deadline_millis,
        )

    def admission_config(self):
        """The AdmissionConfig for the gateway's admission gate.  Always
        returns one (unlike resilience_policy): with every knob at 0 the
        controller never sheds — it only tracks in-flight work, which
        the drain path needs regardless of overload configuration."""
        from ..resilience import AdmissionConfig

        return AdmissionConfig(
            max_inflight=self.admission_max_inflight,
            max_queue_depth=self.admission_max_queue_depth,
            adaptive=self.admission_adaptive,
            min_limit=self.admission_min_limit,
            latency_factor=self.admission_latency_factor,
            retry_after_ms=self.admission_retry_after_millis,
        )

    def fault_injection_plan(self):
        """Parsed FAULT_PLAN, or None (chaos runs only)."""
        if not self.fault_plan:
            return None
        from ..resilience import FaultPlan

        return FaultPlan.parse(self.fault_plan)

    def memguard(self):
        """The configured MemGuard, or None when MEMGUARD=0 or an auto
        watermark is needed but MemTotal is unreadable (the governor
        never guesses — resilience_policy() discipline)."""
        if not self.memguard_enabled:
            return None
        from ..resilience.memguard import MemGuard, resolve_watermarks

        marks = resolve_watermarks(self.mem_soft_bytes, self.mem_hard_bytes)
        if marks is None:
            return None
        return MemGuard(
            marks[0], marks[1], interval_ms=self.memguard_interval_millis
        )

    def device_fault_injection_plan(self):
        """Parsed DEVICE_FAULT_PLAN, or None (chaos/drill runs only)."""
        if not self.device_fault_plan:
            return None
        from ..resilience import DeviceFaultPlan

        return DeviceFaultPlan.parse(self.device_fault_plan)

    def judge_bias_injection_plan(self):
        """Parsed JUDGE_BIAS_PLAN, or None (quality drills only)."""
        if not self.judge_bias_plan:
            return None
        from ..resilience import JudgeBiasPlan

        return JudgeBiasPlan.parse(self.judge_bias_plan)

    def outcome_ledger(self):
        """The configured OutcomeLedger, or None when nothing enables it
        (None keeps the tally seam ledger-free — resilience_policy()
        discipline).  LEDGER_DIR alone implies the default ring of 256."""
        if self.ledger_ring <= 0 and not self.ledger_dir:
            return None
        from ..obs import OutcomeLedger

        return OutcomeLedger(
            capacity=self.ledger_ring if self.ledger_ring > 0 else 256,
            disk_dir=self.ledger_dir,
            rotate_bytes=self.ledger_rotate_bytes,
        )

    def live_weights(self):
        """The configured LiveWeightStore, or None when nothing enables
        it (None keeps the scoring path on its static-weight reads —
        resilience_policy() discipline).  WEIGHTS_PATH alone implies
        enabled: pointing at a table means serving it."""
        if not (self.weights_enabled or self.weights_path):
            return None
        from ..weights.live import LiveWeightStore

        return LiveWeightStore(path=self.weights_path)

    def trace_sink(self):
        """The configured TraceSink, or None when nothing enables
        tracing (None keeps every instrumentation site on its one-
        contextvar-read no-op path — resilience_policy() discipline)."""
        if not (
            self.trace_enabled
            or self.trace_sample_rate > 0
            or self.trace_dir
        ):
            return None
        from ..obs import TraceSink

        return TraceSink(
            capacity=self.trace_ring,
            sample_rate=self.trace_sample_rate,
            disk_dir=self.trace_dir,
        )

    def fleet_config(self):
        """The fleet membership config (fleet/membership.py), or None
        when the fleet tier is off (single-replica behavior untouched —
        resilience_policy() discipline)."""
        if not self.fleet_self:
            return None
        from ..fleet import FleetConfig

        return FleetConfig(
            self_url=self.fleet_self.rstrip("/"),
            peers=list(self.fleet_peers),
            peers_file=self.fleet_peers_file,
            vnodes=self.fleet_vnodes,
            lease_millis=self.fleet_lease_millis,
            fetch_timeout_millis=self.fleet_fetch_timeout_millis,
            fault_plan_spec=self.fleet_fault_plan,
            quarantine_failures=self.fleet_quarantine_failures,
            probe_millis=self.fleet_probe_millis,
        )
