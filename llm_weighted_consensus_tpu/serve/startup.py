"""Set-up's stopwatches: the ``startup`` section of ``/metrics`` (ISSUE 37).

``setup_s`` of a benchmark run is an end-to-end metric; these say what of it
is the program's, each as ``startup.<phase>.seconds``:

``listening``
    process start (the kernel's own stamp, ``/proc/self/stat``) to the
    server's socket open; the two below lie inside it.
``weights``
    a checkpoint opened to its parameters on the device (a mesh's
    re-placement too), summed over the embedder and the judge.  The
    backend's own start-up is not in it: ``build_service`` touches the
    devices first.
``warmup``
    the embedder's warm-up (``_warmup_embedder``) and the judge's.
``compile``
    every compilation the backend was asked for, in seconds, as it stands
    when read (``CompileCacheStats.backend_compile_s``): the warm requests'
    lazy programs are in it.

Written from the main thread before the server listens; read after.
"""

from __future__ import annotations

import os
import time

_SECONDS: dict = {}
_IMPORTED = time.monotonic()


class stopwatch:
    """``with stopwatch("weights"): ...`` adds the block's seconds to the
    phase and keeps them as ``seconds``."""

    __slots__ = ("phase", "seconds", "_t0")

    def __init__(self, phase: str) -> None:
        self.phase = phase
        self.seconds = 0.0

    def __enter__(self) -> "stopwatch":
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        self.seconds = time.perf_counter() - self._t0
        add(self.phase, self.seconds)
        return False


def add(phase: str, seconds: float) -> None:
    _SECONDS[phase] = _SECONDS.get(phase, 0.0) + float(seconds)


def process_age_s() -> float:
    """Seconds since the kernel started this process; since this module was
    imported where ``/proc`` does not say."""
    try:
        with open("/proc/self/stat", encoding="ascii") as f:
            # the fields after the command's closing parenthesis; starttime
            # is the 22nd of the line, in clock ticks since boot
            started = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime", encoding="ascii") as f:
            uptime = float(f.read().split()[0])
        return max(0.0, uptime - started / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return time.monotonic() - _IMPORTED


def listening() -> None:
    """The server's socket is open."""
    _SECONDS["listening"] = process_age_s()


def snapshot(compile_cache=None) -> dict:
    seconds = dict(_SECONDS)
    if compile_cache is not None:
        seconds["compile"] = compile_cache.backend_compile_s
    return {
        phase: {"seconds": round(value, 3)} for phase, value in seconds.items()
    }
