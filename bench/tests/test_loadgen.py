"""The load generator against a stand-in server: latency runs from the moment
a request was due, a stall is charged to every request it delays, the closed
loop stops taking requests when the window closes, and what is not back by
the end of the drain is recorded as failed."""

import asyncio
import json
import os
import subprocess
import sys
import threading

import pytest
from aiohttp import web

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class StandIn:
    """One request at a time, ``service_s`` each (a single-server queue)."""

    def __init__(self, service_s: float):
        self.service_s = service_s
        self.loop = asyncio.new_event_loop()
        self.port = None
        self._started = threading.Event()
        self.thread = threading.Thread(target=self._run, daemon=True)

    def _run(self):
        asyncio.set_event_loop(self.loop)
        lock = asyncio.Lock()

        async def handler(request):
            await request.read()
            async with lock:
                await asyncio.sleep(self.service_s)
            return web.json_response({"confidence": [0.5, 0.5]})

        async def start():
            app = web.Application()
            app.router.add_post("/consensus", handler)
            self.runner = web.AppRunner(app)
            await self.runner.setup()
            site = web.TCPSite(self.runner, "127.0.0.1", 0)
            await site.start()
            self.port = site._server.sockets[0].getsockname()[1]
            self._started.set()

        self.loop.run_until_complete(start())
        self.loop.run_forever()

    def __enter__(self):
        self.thread.start()
        assert self._started.wait(10)
        return self

    def __exit__(self, *exc):
        async def stop():
            await self.runner.cleanup()

        asyncio.run_coroutine_threadsafe(stop(), self.loop).result(10)
        self.loop.call_soon_threadsafe(self.loop.stop)
        self.thread.join(10)
        assert not self.thread.is_alive()


def drive(tmp_path, port, items, seconds, drain):
    schedule = tmp_path / "schedule.jsonl"
    out = tmp_path / "results.jsonl"
    schedule.write_text("".join(json.dumps(it) + "\n" for it in items))
    proc = subprocess.Popen(
        [
            sys.executable, os.path.join(BENCH, "loadgen.py"),
            "--schedule", str(schedule), "--out", str(out), "--port", str(port),
            "--path", "/consensus", "--keep", "confidence",
            "--seconds", str(seconds), "--drain", str(drain),
        ],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE,
    )
    try:
        assert proc.stdout.readline().strip() == b"ready"
        proc.stdin.write(b"go\n")
        proc.stdin.flush()
        summary = json.loads(proc.stdout.read().decode().strip().splitlines()[-1])
        assert proc.wait(timeout=30) == 0
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    results = [json.loads(line) for line in out.read_text().splitlines()]
    return summary, sorted(results, key=lambda r: r["index"])


def test_open_loop_latency_runs_from_the_due_time(tmp_path):
    """Four requests due together at a 50 ms single server: the k-th waits
    behind k others, and its latency from the due time shows it."""
    body = json.dumps({"input": ["a", "b"]})
    items = [{"index": i, "due_s": 0.2, "body": body} for i in range(4)]
    with StandIn(0.05) as server:
        summary, results = drive(tmp_path, server.port, items, 1.0, 2.0)
    assert summary["loop"] == "open" and summary["sent"] == 4
    assert all(r["status"] == 200 and r["kept"] == {"confidence": [0.5, 0.5]} for r in results)
    latency = sorted(r["done_s"] - r["due_s"] for r in results)
    for k, value in enumerate(latency):
        assert 0.05 * (k + 1) <= value <= 0.05 * (k + 1) + 0.1
    # the generator itself was on time
    assert max(r["sent_s"] - r["due_s"] for r in results) < 0.05


def test_what_is_not_back_by_the_end_of_the_drain_failed(tmp_path):
    body = json.dumps({"input": ["a", "b"]})
    items = [{"index": i, "due_s": 0.0, "body": body} for i in range(3)]
    with StandIn(0.4) as server:
        _, results = drive(tmp_path, server.port, items, 0.3, 0.3)
    statuses = [r["status"] for r in results]
    assert statuses.count(200) == 1 and statuses.count(0) == 2
    assert all("not back" in r["error"] for r in results if r["status"] == 0)


def test_closed_loop_one_request_per_caller_at_a_time(tmp_path):
    body = json.dumps({"input": ["a", "b"]})
    items = [
        {"index": c * 50 + t, "caller": c, "turn": t, "body": body}
        for c in range(2) for t in range(50)
    ]
    with StandIn(0.02) as server:
        summary, results = drive(tmp_path, server.port, items, 0.5, 1.0)
    assert summary["loop"] == "closed"
    # a 20 ms single server finishes about 25 in half a second
    assert 10 <= len(results) <= 28
    for caller in range(2):
        mine = sorted(
            (r for r in results if r["index"] // 50 == caller), key=lambda r: r["sent_s"]
        )
        for prev, nxt in zip(mine, mine[1:]):
            assert nxt["sent_s"] >= prev["done_s"]
    assert max(r["sent_s"] for r in results) < 0.5
