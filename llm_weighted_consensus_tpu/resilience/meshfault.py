"""Mesh fault domains: keep serving through device loss (``MESH_FAULT_*``).

PR 9 put the serving path on a dp×tp mesh; this module is its failure
story.  A bad chip on an 8-chip dispatch used to surface as an
``XlaRuntimeError`` (or a wedge the watchdog catches) with exactly one
recovery lever — collapse to the single-device CPU twin.  Instead:

* ``classify_dispatch_error`` sorts dispatch failures at the
  embedder/batcher seam into *transient* (retry on the same shape) vs
  *persistent* (a device is gone) vs not-a-device-fault (ordinary
  application errors keep their existing fail-the-group path);
* ``MeshFaultManager`` owns a pre-declared **downsize ladder** — dp
  halving toward 1, tp preserved — with every rung's mesh built over a
  device-list *prefix* (parallel/mesh.py reshapes ``devices[:n]``, so
  each rung is a subset of the last and dropping the tail sheds the
  faulted fault-domain).  Every rung is ``aot_warmup``-ed at startup
  under its own ``("mesh", dp, tp)`` key namespace, so a downsize is a
  param re-shard plus an executable-table swap, not a compile storm;
* the batcher re-queues the failed group's in-flight items onto the new
  shape, bounded by their propagated deadlines (past-budget items shed
  504 exactly like the PR 4 drain path), and the admission controller's
  AIMD limit plus the batcher capacity rescale to the surviving chips;
* a recovery prober periodically re-validates the full mesh and upsizes
  back; readiness stays up throughout, flagged ``degraded_mesh``.

``DeviceFaultPlan`` is the deterministic injection seam (the
``DEVICE_FAULT_PLAN`` env spec), mirroring ``faults.FaultPlan``'s
seeded-plan contract at the embedder dispatch boundary instead of the
Transport seam: raise-transient, raise-persistent, or hang (a bounded
sleep the watchdog can observe, then a raise — so tier-1 never blocks).

Pure-core hygiene: jax is imported lazily inside the methods that
re-shard, never at module scope.
"""

from __future__ import annotations

import contextlib
import random
import threading
import time
from typing import Dict, List, Optional

# device-fault kinds, in the fixed order the sampler walks (order is
# part of the determinism contract — do not reorder)
TRANSIENT = "transient"
PERSISTENT = "persistent"
HANG = "hang"

DEVICE_FAULT_KINDS = (TRANSIENT, PERSISTENT, HANG)

# XlaRuntimeError status substrings that mean "retry the dispatch":
# allocator pressure and preempted/aborted collectives clear on their
# own; anything stateful (device halted, data loss) does not.
_TRANSIENT_STATUSES = ("RESOURCE_EXHAUSTED", "ABORTED", "UNAVAILABLE")
_PERSISTENT_STATUSES = (
    "DATA_LOSS",
    "INTERNAL",
    "FAILED_PRECONDITION",
    "device halted",
    "Device lost",
)


class InjectedTransientError(RuntimeError):
    """A ``DeviceFaultPlan`` transient dispatch failure."""


class InjectedPersistentError(RuntimeError):
    """A ``DeviceFaultPlan`` persistent device loss."""


class InjectedHangError(RuntimeError):
    """Raised after a ``DeviceFaultPlan`` hang's bounded sleep (the real
    failure mode never returns; the sleep gives the watchdog its overdue
    observation, then this raise unwedges the test)."""


def classify_dispatch_error(exc: BaseException) -> Optional[str]:
    """Sort a dispatch exception: ``"transient"`` / ``"persistent"`` /
    ``None`` (not a device fault — ordinary application error).

    Matches ``XlaRuntimeError`` by type NAME, not import: the class
    lives in jaxlib and this module keeps the pure-core no-jax-at-scope
    rule.  Unknown XLA statuses classify transient — one free retry
    costs a dispatch, while a wrong "persistent" costs half the mesh
    (the escalation counter in ``MeshFaultManager.classify`` converts a
    transient streak into persistent anyway).
    """
    if isinstance(exc, InjectedTransientError):
        return TRANSIENT
    if isinstance(exc, InjectedPersistentError):
        return PERSISTENT
    if isinstance(exc, InjectedHangError):
        return TRANSIENT  # escalated via the watchdog-overdue note
    if type(exc).__name__ != "XlaRuntimeError":
        return None
    msg = str(exc)
    if any(status in msg for status in _PERSISTENT_STATUSES):
        return PERSISTENT
    if any(status in msg for status in _TRANSIENT_STATUSES):
        return TRANSIENT
    return TRANSIENT


class DeviceFaultPlan:
    """Per-dispatch device-fault schedule: seeded sampling or a script.

    The ``faults.FaultPlan`` contract verbatim — one
    ``random.Random(seed)`` drawn once per dispatch in dispatch order,
    or ``scripted([...])`` replay — but at the embedder dispatch
    boundary with the device failure modes: ``transient``,
    ``persistent``, ``hang``.
    """

    def __init__(
        self,
        seed: int = 0,
        probabilities: Optional[Dict[str, float]] = None,
        hang_ms: float = 50.0,
        script: Optional[List[Optional[str]]] = None,
    ) -> None:
        self.seed = int(seed)
        self.rng = random.Random(self.seed)
        self.probabilities = {
            kind: float((probabilities or {}).get(kind, 0.0))
            for kind in DEVICE_FAULT_KINDS
        }
        self.hang_ms = float(hang_ms)
        self._script = list(script) if script is not None else None
        self._script_pos = 0
        self.requests = 0
        self.injected: Dict[str, int] = {
            kind: 0 for kind in DEVICE_FAULT_KINDS
        }

    @classmethod
    def scripted(
        cls, faults: List[Optional[str]], *, hang_ms: float = 50.0
    ) -> "DeviceFaultPlan":
        """Replay ``faults`` verbatim (None = healthy dispatch); healthy
        after exhaustion."""
        return cls(script=faults, hang_ms=hang_ms)

    @classmethod
    def parse(cls, spec: str) -> "DeviceFaultPlan":
        """Parse a ``DEVICE_FAULT_PLAN`` env spec.

        Comma-separated ``key=value``: ``seed``, ``hang_ms``, one key
        per fault kind with its probability, or ``script=a|b|ok|c``
        (``ok``/empty = healthy slot).
        """
        seed = 0
        hang_ms = 50.0
        probs: Dict[str, float] = {}
        script: Optional[List[Optional[str]]] = None
        for part in spec.split(","):
            part = part.strip()
            if not part:
                continue
            if "=" not in part:
                raise ValueError(
                    f"DEVICE_FAULT_PLAN: expected key=value, got {part!r}"
                )
            key, _, value = part.partition("=")
            key = key.strip()
            value = value.strip()
            if key == "seed":
                seed = int(value)
            elif key == "hang_ms":
                hang_ms = float(value)
            elif key == "script":
                script = [
                    None if slot in ("", "ok") else slot
                    for slot in value.split("|")
                ]
                for slot in script:
                    if slot is not None and slot not in DEVICE_FAULT_KINDS:
                        raise ValueError(
                            f"DEVICE_FAULT_PLAN: unknown fault {slot!r}"
                        )
            elif key in DEVICE_FAULT_KINDS:
                probs[key] = float(value)
            else:
                raise ValueError(f"DEVICE_FAULT_PLAN: unknown key {key!r}")
        return cls(
            seed=seed, probabilities=probs, hang_ms=hang_ms, script=script
        )

    def next_fault(self) -> Optional[str]:
        """The fault for the next dispatch (None = healthy)."""
        self.requests += 1
        if self._script is not None:
            if self._script_pos >= len(self._script):
                return None
            fault = self._script[self._script_pos]
            self._script_pos += 1
            if fault is not None:
                self.injected[fault] += 1
            return fault
        draw = self.rng.random()
        edge = 0.0
        for kind in DEVICE_FAULT_KINDS:
            edge += self.probabilities[kind]
            if draw < edge:
                self.injected[kind] += 1
                return kind
        return None

    def snapshot(self) -> dict:
        return {
            "requests": self.requests,
            "injected": {k: v for k, v in self.injected.items() if v},
        }


class _ShapeGate:
    """Reader-writer gate serializing mesh shape transitions with device
    dispatches.

    The batcher's dispatch executor runs ``pipeline_depth`` worker
    threads (2 by default), so "run the re-shard on the executor" does
    NOT serialize it with dispatches — a second worker can be mid-PJRT
    on the old params while ``shard_embedder_mesh`` mutates them.  Every
    dispatch therefore holds the *shared* side for the duration of its
    device call, and ``downsize``/``try_recover`` hold the *exclusive*
    side across the re-shard: a shape change waits out in-flight
    dispatches, and dispatches never observe a torn embedder.  Writer
    preference (a waiting writer blocks new readers) bounds the wait to
    the dispatches already in flight.
    """

    def __init__(self) -> None:
        self._cond = threading.Condition()
        self._readers = 0
        self._writer = False
        self._writers_waiting = 0

    @contextlib.contextmanager
    def shared(self):
        with self._cond:
            while self._writer or self._writers_waiting:
                self._cond.wait()
            self._readers += 1
        try:
            yield
        finally:
            with self._cond:
                self._readers -= 1
                if not self._readers:
                    self._cond.notify_all()

    @contextlib.contextmanager
    def exclusive(self):
        with self._cond:
            self._writers_waiting += 1
            try:
                while self._writer or self._readers:
                    self._cond.wait()
            finally:
                self._writers_waiting -= 1
            self._writer = True
        try:
            yield
        finally:
            with self._cond:
                self._writer = False
                self._cond.notify_all()


class _Rung:
    """One ladder step: the shape plus the Mesh built at warmup.

    The Mesh object itself is load-bearing: mesh-mode AOT executables
    bake NamedShardings referencing the exact device set they lowered
    against, so downsizing MUST re-shard onto this stored mesh — an
    equal-shape mesh over different devices would fail the executables'
    aval check.
    """

    __slots__ = ("dp", "tp", "mesh", "devices")

    def __init__(self, dp: int, tp: int, mesh, devices: list) -> None:
        self.dp = dp
        self.tp = tp
        self.mesh = mesh
        self.devices = devices  # row-major prefix of the full device list


class MeshFaultManager:
    """The mesh fault-domain brain: classify → downsize → re-dispatch →
    probe → upsize.

    Thread-safety: ``classify``/``note_*``/``snapshot`` and the state
    properties run under a lock (dispatch executor threads + event loop
    all call in).  ``downsize`` and ``try_recover`` mutate the embedder,
    which the dispatch threads read mid-PJRT-call; they take the
    exclusive side of the shape gate (``_ShapeGate``) across the
    re-shard while every dispatch holds the shared side
    (``dispatch_guard``, wired in the batcher's ``_dispatch``), so a
    shape change drains in-flight dispatches first no matter which
    thread runs it.
    """

    def __init__(
        self,
        embedder,
        *,
        shape: tuple,
        transient_retries: int = 2,
        probe_millis: float = 0.0,
        fault_plan: Optional[DeviceFaultPlan] = None,
        clock=time.monotonic,
    ) -> None:
        self.embedder = embedder
        self.full_shape = (int(shape[0]), int(shape[1]))
        # sequence-parallel width (MESH_SHAPE=dp,tp,sp): preserved down
        # every rung like tp — the ladder halves dp only, so a degraded
        # mesh keeps serving long-context ring traffic
        self.sp = int(getattr(embedder, "mesh_sp", 1) or 1)
        self.transient_retries = int(transient_retries)
        self.probe_millis = float(probe_millis)
        self.fault_plan = fault_plan
        self.clock = clock
        # callables(scale: float) run after every shape change — the
        # admission AIMD limit and the batcher capacity rescale hooks
        self.rescale_hooks: list = []
        # optional zero-arg probe run on the full shape by try_recover
        # AFTER the upsize re-shard; a device-classified raise rolls the
        # upsize back
        self.probe_fn = None
        # re-entrant: snapshot() reads the locked state properties while
        # already holding the lock
        self._lock = threading.RLock()
        self._shape_gate = _ShapeGate()
        self._rungs: List[_Rung] = []
        self._rung_index = 0
        self._epoch = 0
        self._downsizes = 0
        self._upsizes = 0
        self._re_dispatches = 0
        self._probe_failures = 0
        self._consecutive_probe_failures = 0
        self._warned_blind_upsize = False
        self._transient_streak = 0
        self._watchdog_overdue = False
        self._faulted_devices: list = []

    # -- ladder construction / warmup ----------------------------------------

    def build_ladder(self) -> List[tuple]:
        """Declare the downsize ladder (without warming it): dp halving
        from the full shape toward 1, tp preserved, each rung's mesh a
        row-major device-list prefix of the previous rung's."""
        from ..parallel.mesh import make_mesh

        if self._rungs:
            return [(r.dp, r.tp) for r in self._rungs]
        full_mesh = self.embedder.mesh
        if full_mesh is None:
            raise RuntimeError(
                "MeshFaultManager needs a mesh-sharded embedder "
                "(parallel.shard_embedder_mesh) before build_ladder"
            )
        devices = list(full_mesh.devices.reshape(-1))
        dp, tp = self.full_shape
        sp = self.sp
        self._rungs = [_Rung(dp, tp, full_mesh, devices)]
        step = dp // 2
        while step >= 1:
            sub = devices[: step * tp * sp]
            mesh = make_mesh(dp=step, tp=tp, sp=sp, devices=sub)
            self._rungs.append(_Rung(step, tp, mesh, sub))
            step //= 2
        return [(r.dp, r.tp) for r in self._rungs]

    def warm_ladder(
        self,
        specs: list,
        r_buckets: list = (),
        ring_buckets: list = (),
    ) -> list:
        """AOT-warm every fallback rung so a downsize never compiles.

        Walks the ladder bottom-up (smallest rung first, full shape
        last): each step re-shards the embedder's params onto the rung
        mesh and runs the same ``aot_warmup`` bucket set the primary
        shape got, landing executables under that rung's
        ``("mesh", dp, tp)`` key namespace.  The final step is the full
        shape, so the embedder exits warmed AND sharded exactly as it
        entered.  Returns [(label, seconds)] for startup logging.
        """
        from ..parallel.sharding import shard_embedder_mesh

        self.build_ladder()
        timings = []
        with self._shape_gate.exclusive():
            for rung in reversed(self._rungs):
                shard_embedder_mesh(self.embedder, rung.mesh)
                timings.extend(
                    self.embedder.aot_warmup(specs, r_buckets, ring_buckets)
                )
        return timings

    # -- dispatch/transition serialization ------------------------------------

    def dispatch_guard(self):
        """Shared-side context for one device dispatch: the batcher's
        ``_dispatch`` holds this across the embedder call, so
        ``downsize``/``try_recover`` (exclusive side) wait out in-flight
        dispatches before re-sharding instead of tearing the params a
        concurrent executor thread is reading."""
        return self._shape_gate.shared()

    # -- classification -------------------------------------------------------

    def classify(self, exc: BaseException) -> Optional[str]:
        """Policy layered over ``classify_dispatch_error``: a watchdog
        overdue note or a transient streak past ``transient_retries``
        escalates transient to persistent."""
        kind = classify_dispatch_error(exc)
        if kind is None:
            return None
        with self._lock:
            if kind == PERSISTENT:
                self._transient_streak = 0
                self._watchdog_overdue = False
                return PERSISTENT
            if self._watchdog_overdue:
                self._watchdog_overdue = False
                self._transient_streak = 0
                return PERSISTENT
            self._transient_streak += 1
            if self._transient_streak > self.transient_retries:
                self._transient_streak = 0
                return PERSISTENT
            return TRANSIENT

    def note_watchdog_trip(self) -> None:
        """Mark the next classified dispatch failure persistent: a
        watchdog-overdue dispatch is a wedge, not a blip (wired from the
        watchdog's on_trip in serve/__main__.py)."""
        with self._lock:
            self._watchdog_overdue = True

    def note_dispatch_ok(self) -> None:
        """A clean dispatch resets the transient-escalation streak."""
        with self._lock:
            self._transient_streak = 0
            self._watchdog_overdue = False

    def note_redispatch(self, count: int = 1) -> None:
        with self._lock:
            self._re_dispatches += int(count)

    # -- injection seam --------------------------------------------------------

    def maybe_inject(self) -> None:
        """The ``DEVICE_FAULT_PLAN`` seam, called at the top of every
        device dispatch (serve/batcher.py's ``_dispatch``, on the
        dispatch thread).  ``hang`` sleeps ``hang_ms`` — long enough for
        the watchdog monitor to observe the overdue bracket — then
        raises, so tier-1 can never block on a real wedge."""
        plan = self.fault_plan
        if plan is None:
            return
        with self._lock:
            fault = plan.next_fault()
        if fault == TRANSIENT:
            raise InjectedTransientError(
                "DEVICE_FAULT_PLAN: injected transient dispatch failure"
            )
        if fault == PERSISTENT:
            raise InjectedPersistentError(
                "DEVICE_FAULT_PLAN: injected persistent device loss"
            )
        if fault == HANG:
            time.sleep(plan.hang_ms / 1000.0)
            raise InjectedHangError(
                "DEVICE_FAULT_PLAN: injected dispatch hang"
            )

    # -- shape transitions -----------------------------------------------------

    @property
    def degraded(self) -> bool:
        with self._lock:
            return self._rung_index > 0

    @property
    def exhausted(self) -> bool:
        """Past the last rung: every fallback shape is spent and the
        CPU twin (DEVICE_WATCHDOG_CPU_FALLBACK) is the only lever left."""
        with self._lock:
            return self._rung_index >= len(self._rungs) - 1

    @property
    def epoch(self) -> int:
        with self._lock:
            return self._epoch

    @property
    def current_shape(self) -> tuple:
        with self._lock:
            if not self._rungs:
                return self.full_shape
            rung = self._rungs[self._rung_index]
            return (rung.dp, rung.tp)

    def _rescale(self) -> None:
        scale = self.current_shape[0] / self.full_shape[0]
        for hook in self.rescale_hooks:
            hook(scale)

    def downsize(self, observed_epoch: Optional[int] = None) -> bool:
        """Step down one ladder rung: re-shard params onto the stored
        surviving submesh (the executable-table swap is implicit —
        dispatch keys follow ``embedder.mesh_shape``), record the
        dropped tail devices as the faulted domain, bump the epoch, and
        rescale admission/batcher capacity.  Returns False when the
        ladder is exhausted (caller falls back to the CPU twin).

        ``observed_epoch`` is the mesh epoch the failed dispatch was
        stamped with at launch.  Pipelined dispatches can fault on the
        SAME dead device concurrently; only the first fault per epoch
        may step the ladder — a stale epoch means the shape already
        changed since this dispatch launched, so the fault is old news
        and the caller should just re-queue onto the current shape
        (returns True without stepping).

        Runs under the exclusive side of the shape gate: the re-shard
        waits out in-flight dispatches (which hold the shared side), so
        it is safe from any thread — including the multi-worker dispatch
        executor.
        """
        from ..parallel.sharding import shard_embedder_mesh

        self.build_ladder()
        with self._shape_gate.exclusive():
            with self._lock:
                if (
                    observed_epoch is not None
                    and observed_epoch != self._epoch
                ):
                    return True
                if self._rung_index >= len(self._rungs) - 1:
                    return False
                old = self._rungs[self._rung_index]
                self._rung_index += 1
                rung = self._rungs[self._rung_index]
                dropped = [
                    d for d in old.devices if d not in rung.devices
                ]
                self._faulted_devices.extend(
                    getattr(d, "id", d) for d in dropped
                )
                self._downsizes += 1
                self._epoch += 1
                self._transient_streak = 0
                self._watchdog_overdue = False
            shard_embedder_mesh(self.embedder, rung.mesh)
        self._rescale()
        return True

    def try_recover(self) -> bool:
        """The recovery probe: while degraded, re-validate the full mesh
        and upsize back.  A ``DeviceFaultPlan`` draw models the probe
        dispatch (a still-faulty plan keeps the mesh down); with a real
        ``probe_fn`` attached (serve/__main__.py wires a warmed-bucket
        full-mesh dispatch), the upsize re-shard happens first and a
        device-classified raise rolls it back.  With NEITHER, the upsize
        is unvalidated — a still-dead device faults the next dispatch
        and the mesh flaps down again — so that mode logs a one-time
        warning.  Holds the exclusive side of the shape gate across the
        re-shard + probe + possible rollback, like ``downsize``.
        """
        from ..parallel.sharding import shard_embedder_mesh

        if not self.degraded:
            return False
        if self.fault_plan is not None:
            with self._lock:
                fault = self.fault_plan.next_fault()
            if fault is not None:
                with self._lock:
                    self._probe_failures += 1
                    self._consecutive_probe_failures += 1
                return False
        elif self.probe_fn is None:
            # test-and-set under the lock: two prober threads racing the
            # unlocked flag would both pass the check and double-warn
            with self._lock:
                warn = not self._warned_blind_upsize
                self._warned_blind_upsize = True
            if warn:
                import logging

                logging.getLogger("lwc.resilience").warning(
                    "mesh fault recovery has no probe_fn and no "
                    "DEVICE_FAULT_PLAN: upsizing to the full mesh "
                    "without validating it — a still-dead device will "
                    "fault the next dispatch and downsize again (attach "
                    "probe_fn, as serve/__main__.py does, to validate "
                    "before upsizing)"
                )
        with self._shape_gate.exclusive():
            with self._lock:
                prev_index = self._rung_index
            full = self._rungs[0]
            shard_embedder_mesh(self.embedder, full.mesh)
            if self.probe_fn is not None:
                try:
                    self.probe_fn()
                except Exception as exc:
                    # roll back FIRST either way: the manager still
                    # reports the surviving rung, so the embedder must
                    # not be left sharded at the full shape
                    shard_embedder_mesh(
                        self.embedder, self._rungs[prev_index].mesh
                    )
                    if classify_dispatch_error(exc) is None:
                        raise  # probe bug, not a device fault
                    with self._lock:
                        self._probe_failures += 1
                        self._consecutive_probe_failures += 1
                    self._rescale()
                    return False
            with self._lock:
                self._rung_index = 0
                self._upsizes += 1
                self._epoch += 1
                self._faulted_devices.clear()
                self._consecutive_probe_failures = 0
                self._transient_streak = 0
                self._watchdog_overdue = False
        self._rescale()
        return True

    def probe_backoff_scale(self, cap: float = 32.0) -> float:
        """Multiplier for the prober's sleep between attempts: doubles
        per consecutive probe failure (capped) so a long-dead device is
        probed ever more lazily — each failed probe re-shards and
        rolls back, work worth not repeating every interval — and resets
        to 1 on a successful upsize."""
        with self._lock:
            return float(
                min(2.0 ** self._consecutive_probe_failures, cap)
            )

    # -- observability ---------------------------------------------------------

    def snapshot(self) -> dict:
        """The ``meshfault`` /metrics section."""
        with self._lock:
            snap = {
                "current_shape": list(self.current_shape),
                "full_shape": list(self.full_shape),
                "sp": self.sp,
                "degraded": self.degraded,
                "epoch": self._epoch,
                "downsizes": self._downsizes,
                "upsizes": self._upsizes,
                "re_dispatches": self._re_dispatches,
                "probe_failures": self._probe_failures,
                "probe_backoff": self.probe_backoff_scale(),
                "faulted_devices": list(self._faulted_devices),
                "ladder": [[r.dp, r.tp] for r in self._rungs],
            }
            if self.fault_plan is not None:
                snap["fault_plan"] = self.fault_plan.snapshot()
        return snap
