"""The system under test as a child process, and the HTTP calls made to it.

The server is the program's own entry, ``python -m
llm_weighted_consensus_tpu.serve --port P --fake-upstream``, started from the
checkout root with the environment the configuration's file gives, through
``serve_child.py``, which adds a reader of the device's memory statistics and
changes nothing.  The benchmark's parent never imports jax while the server
lives: a chip belongs to one process at a time.
"""

from __future__ import annotations

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
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class BenchError(Exception):
    """The run cannot produce a result (no result line, exit code 1)."""


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def http_json(port: int, method: str, path: str, body=None, timeout=600.0):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        payload = None if body is None else json.dumps(body).encode("utf-8")
        conn.request(method, path, payload, {"content-type": "application/json"})
        resp = conn.getresponse()
        raw = resp.read()
    finally:
        conn.close()
    return resp.status, raw


def get_metrics(port: int) -> dict:
    status, raw = http_json(port, "GET", "/metrics")
    if status != 200:
        raise BenchError(f"/metrics: HTTP {status}")
    return json.loads(raw)


def tail_of(path: str, limit: int = 3000) -> str:
    try:
        with open(path, "rb") as f:
            f.seek(0, os.SEEK_END)
            f.seek(max(0, f.tell() - limit))
            return f.read().decode("utf-8", "replace")
    except OSError:
        return ""


def held_peak_bytes(rows: list) -> int:
    """The most a device held, from ``Device.memory_stats()`` of every device.

    The runtime counts buffers (weights, inputs, outputs) under
    ``bytes_in_use`` and the temporaries of compiled programs under
    ``bytes_reserved``, a region that grows to the largest program run and is
    kept; ``peak_bytes_in_use`` alone misses it.  Held at the moment of
    reading: buffers plus the largest reservation.  The peak of the buffers
    alone (reached while a checkpoint loads, before any program ran) counts
    where it is larger."""
    best = 0
    for row in rows:
        held = int(row.get("bytes_in_use") or 0) + int(row.get("peak_bytes_reserved") or 0)
        best = max(best, held, int(row.get("peak_bytes_in_use") or 0))
    return best


class Server:
    """``with Server(env, log_path) as s:`` — started on entry, listening when
    ``wait_listening`` returns, SIGTERMed (then killed) on exit."""

    def __init__(self, env: dict, log_path: str):
        self.port = free_port()
        self.log_path = log_path
        full = dict(os.environ)
        full.update(env)
        full["PYTHONPATH"] = ROOT + os.pathsep + full.get("PYTHONPATH", "")
        self._env = full
        base = os.path.splitext(log_path)[0]
        self._memory_request = base + ".memory.request"
        self._memory_out = base + ".memory.json"
        self.proc = None
        self._ready = threading.Event()

    def __enter__(self):
        for path in (self._memory_request, self._memory_out):
            if os.path.exists(path):
                os.remove(path)
        with open(self.log_path, "wb") as err:
            self.proc = subprocess.Popen(
                [
                    sys.executable, os.path.join(HERE, "serve_child.py"),
                    "--memory-request", self._memory_request,
                    "--memory-out", self._memory_out,
                    "--", "--port", str(self.port), "--fake-upstream",
                ],
                cwd=ROOT,
                env=self._env,
                stdout=subprocess.PIPE,
                stderr=err,
            )
        threading.Thread(target=self._pump, daemon=True).start()
        return self

    def _pump(self):
        # keep draining stdout so the server can never block on a full pipe
        for raw in self.proc.stdout:
            if b"listening on" in raw:
                self._ready.set()
        self._ready.set()

    def wait_listening(self, timeout: float) -> None:
        if not self._ready.wait(timeout) or self.proc.poll() is not None:
            raise BenchError(
                f"server not listening (exit {self.proc.poll()}):\n"
                + tail_of(self.log_path)
            )

    def memory_stats(self, timeout: float = 20.0) -> list:
        """``Device.memory_stats()`` of every device the server holds, read
        inside its process now."""
        if os.path.exists(self._memory_out):
            os.remove(self._memory_out)
        with open(self._memory_request, "w", encoding="utf-8"):
            pass
        deadline = time.monotonic() + timeout
        while not os.path.exists(self._memory_out):
            if time.monotonic() > deadline or self.proc.poll() is not None:
                raise BenchError("the server's memory statistics did not come")
            time.sleep(0.05)
        with open(self._memory_out, encoding="utf-8") as f:
            return json.load(f)

    def stop(self) -> int:
        if self.proc is None or self.proc.poll() is not None:
            return self.proc.returncode if self.proc else 0
        self.proc.send_signal(signal.SIGTERM)
        try:
            return self.proc.wait(timeout=30)
        except subprocess.TimeoutExpired:
            self.proc.kill()
            return self.proc.wait()

    def __exit__(self, *exc):
        self.stop()
        return False
