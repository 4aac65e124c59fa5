"""The lock-model registry: every threading primitive in the package.

``CONCURRENCY_MODEL`` is the declarative table the concurrency rules
(LWC014–016, ``analysis/concurrency.py``) and the runtime
``LockWitness`` (``analysis/witness.py``) both consume.  It is enforced
BOTH ways, like the LWC010/011 registries:

* a ``threading.Lock``/``RLock``/``Condition`` assignment anywhere in
  the package that has no entry here fails LWC014 (unregistered lock);
* an entry whose creation site no longer exists fails LWC014 (stale
  registry row) — the table only ever shrinks honestly.

Per-lock entry fields:

``module``
    Repo-relative path suffix of the file that creates the lock (the
    both-ways match key; fixtures under ``tests/fixtures/analysis/``
    declare their own table with their own file name here).
``kind``
    ``"lock"`` | ``"rlock"`` | ``"condition"``.  LWC015 flags lexical
    re-acquisition of a ``"lock"`` (self-deadlock); the witness allows
    re-entrant acquire only for ``"rlock"``/``"condition"``.
``guards``
    The instance fields this lock protects.  LWC014 flags any
    read/write of one of these outside a ``with <lock>`` scope once the
    field is reachable from >= 2 thread entry points.  Fields NOT
    listed are intentionally unguarded (construction-time config,
    single-thread state, or benign monotonic flags) — the table is the
    place that intent is recorded.
``acquire_via``
    Method names whose call inside a ``with`` acquires this lock
    indirectly — the shape gate's ``shared()``/``exclusive()``
    contextmanagers and the batcher-facing ``dispatch_guard()`` alias.
``long_held``
    True for the reader/writer shape gate: its shared side is DESIGNED
    to be held across an entire device staging (including tokenizer
    waits and the PJRT enqueue), so LWC016's held-across-blocking check
    exempts it.  The underlying ``Condition`` is only ever held for the
    bookkeeping instants inside the gate.

``order`` declares the acquisition-order DAG edges the static analysis
(LWC015) must observe — a declared edge the lock-acquisition graph no
longer contains is stale and fails, an observed edge missing here
fails, and any cycle over declared + observed edges fails.
``order_runtime`` declares edges only the runtime witness can see
(paths the static call graph cannot resolve, e.g. through callbacks
installed at serve-build time); each carries its reason.  The witness
validates real interleavings against the union of both.

Unguarded-by-design notes (fields deliberately absent from ``guards``):

* ``MeshFaultManager._rungs`` — built once (idempotent ``build_ladder``
  at construction/first-downsize) and append-free afterwards; readers
  index an immutable list.
* ``MeshFaultManager.rescale_hooks`` / ``probe_fn`` / ``fault_plan`` —
  wired at serve-build time before any dispatch thread exists.
* ``DeviceBatcher._use_fallback`` — a benign monotonic bool flag read
  by the dispatch hot path; a torn read costs one routed-then-retried
  dispatch, never corruption.
* ``DeviceWatchdog._thread`` / ``_stop`` — monitor-thread lifecycle,
  mutated only from the owning (loop) side in ``start``/``stop``.
* ``StagingPool.per_bucket`` — construction-time capacity config; the
  batcher sizes it before the first dispatch thread starts.
"""

CONCURRENCY_MODEL = {
    "locks": {
        "PhaseAggregator._lock": {
            "module": "llm_weighted_consensus_tpu/obs/phases.py",
            "kind": "lock",
            "guards": ("_phases", "_device"),
        },
        "DeviceAccount._lock": {
            "module": "llm_weighted_consensus_tpu/obs/account.py",
            "kind": "lock",
            "guards": (
                "_last",
                "_requests",
                "_programs",
                "_covers",
                "_last_ready",
                "_starved_since",
                "_enqueued_s",
                "_idle_s",
                "_starved_s",
                "_by",
                "_service_s",
                "_waited_s",
                "_dispatches",
                "_stalls",
                "_starved_max_ms",
                "_starved",
            ),
        },
        "QualityAggregator._lock": {
            "module": "llm_weighted_consensus_tpu/obs/quality.py",
            "kind": "lock",
            "guards": (
                "_judges",
                "_pairs",
                "_margin",
                "_outcomes",
                "_requests",
                "_exemplar",
                "window",
                "drift_threshold",
            ),
        },
        "StagingPool._lock": {
            "module": "llm_weighted_consensus_tpu/models/dispatch_seam.py",
            "kind": "lock",
            "guards": ("_free", "hits", "misses"),
        },
        "DeviceWatchdog._lock": {
            "module": "llm_weighted_consensus_tpu/resilience/watchdog.py",
            "kind": "lock",
            "guards": (
                "_active",
                "_seq",
                "_healthy",
                "trips",
                "recoveries",
                "dispatches",
                "_last_overdue_ms",
                "_last_label",
            ),
        },
        "MemGuard._lock": {
            "module": "llm_weighted_consensus_tpu/resilience/memguard.py",
            "kind": "lock",
            "guards": (
                "_level",
                "last_rss",
                "peak_rss",
                "soft_trips",
                "hard_trips",
                "recoveries",
            ),
        },
        "_ShapeGate._cond": {
            "module": "llm_weighted_consensus_tpu/resilience/meshfault.py",
            "kind": "condition",
            "guards": ("_readers", "_writer", "_writers_waiting"),
            "acquire_via": ("shared", "exclusive", "dispatch_guard"),
            "long_held": True,
        },
        "MeshFaultManager._lock": {
            "module": "llm_weighted_consensus_tpu/resilience/meshfault.py",
            "kind": "rlock",
            "guards": (
                "_rung_index",
                "_epoch",
                "_downsizes",
                "_upsizes",
                "_re_dispatches",
                "_probe_failures",
                "_consecutive_probe_failures",
                "_transient_streak",
                "_watchdog_overdue",
                "_faulted_devices",
                "_warned_blind_upsize",
            ),
        },
        "ChoiceIndexer._lock": {
            "module": "llm_weighted_consensus_tpu/utils/__init__.py",
            "kind": "lock",
            "guards": ("_counter", "_indices"),
        },
        "LockWitness._mu": {
            "module": "llm_weighted_consensus_tpu/analysis/witness.py",
            "kind": "lock",
            "guards": ("_edges", "_violations", "_acquisitions"),
        },
        "TpuJudge._lock": {
            "module": "llm_weighted_consensus_tpu/models/judge.py",
            "kind": "lock",
            "guards": ("_stats",),
        },
        "DeviceBatcher._stats_lock": {
            "module": "llm_weighted_consensus_tpu/serve/batcher.py",
            "kind": "lock",
            "guards": (
                "_pad_real_tokens",
                "_pad_slot_tokens",
                "fallback_dispatches",
            ),
        },
    },
    # static acquisition-order DAG: "u before v" — LWC015 enforces these
    # both ways against the with/acquire graph and fails on any cycle
    "order": (
        # downsize/try_recover/warm_ladder take the gate's exclusive
        # side, then the manager lock for the rung/epoch bookkeeping;
        # maybe_inject draws the fault plan under the manager lock while
        # the dispatch thread holds the gate's shared side
        ("_ShapeGate._cond", "MeshFaultManager._lock"),
        # the dispatch path stages padded rows into the staging pool
        # while holding the gate's shared side
        ("_ShapeGate._cond", "StagingPool._lock"),
        # pack-plan/device phase observations land in the phase
        # aggregator from inside the guarded dispatch
        ("_ShapeGate._cond", "PhaseAggregator._lock"),
        # the guarded dispatch's enqueue tells the device's account
        # (DispatchSink.add), which takes its own lock and no other
        ("_ShapeGate._cond", "DeviceAccount._lock"),
        # occupancy/padding counters update under the batcher's stats
        # lock from inside the guarded dispatch
        ("_ShapeGate._cond", "DeviceBatcher._stats_lock"),
        # the guarded dispatch brackets device work with watchdog
        # begin/end, which take the watchdog lock
        ("_ShapeGate._cond", "DeviceWatchdog._lock"),
    ),
    # edges only real interleavings exercise (the static call graph
    # cannot resolve these paths); validated by the LockWitness
    "order_runtime": (),
}
