"""Loader for the framework's native (C++) runtime library.

One shared library (``native/liblwc_native.so``) carries every native
component — the SSE parser and the WordPiece and unigram/SentencePiece
tokenizers — compiled on first use from the sources in ``native/``.  The compile goes to a temp file then
``os.replace`` so concurrent builders can't hand anyone a truncated .so
(and processes that already mapped the old inode keep it).  Loading is
blocking: call from sync startup code, never from the event loop.

``LWC_NATIVE=0`` disables all native paths (``LWC_NATIVE_SSE=0`` keeps
working for the SSE parser specifically, handled in clients/sse.py).
"""

from __future__ import annotations

import ctypes
import glob
import logging
import os
import subprocess
from typing import Optional

NATIVE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    "native",
)
NATIVE_SO = os.path.join(NATIVE_DIR, "liblwc_native.so")

_lib = None
_tried = False
# why the last load_library() call returned None (None while it has not
# run, or when it loaded): callers fall back to slower pure-Python paths,
# so the reason must be findable (``status``)
_error: Optional[str] = None


def _sources() -> list:
    return sorted(glob.glob(os.path.join(NATIVE_DIR, "*.cpp")))


def _stale(sources: list) -> bool:
    if not os.path.exists(NATIVE_SO):
        return True
    built = os.path.getmtime(NATIVE_SO)
    return any(os.path.getmtime(s) > built for s in sources)


def load_library() -> Optional[ctypes.CDLL]:
    """The native library, building it on first call; None — remembered,
    and logged once with the reason — when it can't be built/loaded or
    ``LWC_NATIVE=0``."""
    global _lib, _tried, _error
    if _tried:
        return _lib
    _tried = True
    if os.environ.get("LWC_NATIVE", "1").lower() in ("0", "false", "no"):
        _error = "disabled by LWC_NATIVE=0"
        return None
    try:
        sources = _sources()
        # no sources (a packaged deployment shipping only the .so) is fine:
        # load the prebuilt library as-is
        if sources and _stale(sources):
            tmp = f"{NATIVE_SO}.tmp.{os.getpid()}"
            subprocess.run(
                [
                    "g++", "-O3", "-std=c++17", "-fPIC", "-Wall",
                    "-shared", "-o", tmp, *sources,
                ],
                check=True,
                capture_output=True,
                timeout=120,
            )
            os.replace(tmp, NATIVE_SO)
        _lib = ctypes.CDLL(NATIVE_SO)
    except subprocess.CalledProcessError as e:
        _error = f"g++ failed: {e.stderr.decode('utf-8', 'replace')[-500:]}"
    except (OSError, subprocess.TimeoutExpired) as e:
        # no compiler, no sources and no prebuilt .so, or an unloadable one
        _error = repr(e)
    if _error is not None:
        logging.getLogger("lwc.native").warning(
            "native library unavailable, the SSE parser and the "
            "tokenizers take their pure-Python paths: %s",
            _error,
        )
    return _lib


def status() -> dict:
    """Whether the native library is loaded in this process, and if not,
    why (``error`` is None before the first ``load_library`` call)."""
    return {"loaded": _lib is not None, "error": _error}
