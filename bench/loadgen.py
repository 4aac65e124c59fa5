#!/usr/bin/env python3
"""Load generator: a process of its own, which never imports jax.

    python3 bench/loadgen.py --schedule FILE --out FILE --port P \
        --path ROUTE --keep FIELD,FIELD --seconds S --drain D

Reads the schedule (one JSON line per request: ``due_s`` for an open loop,
``caller``/``turn`` for a closed one, and the request ``body`` as a string),
connects, prints ``ready`` and waits for a line on stdin.  Then it starts its
clock and sends.

Open loop: each request is sent when it is due, whatever the server is doing,
and its latency runs from the moment it was DUE, so a stall is charged to every
request it delayed.  Closed loop: each caller sends its next request when the
last one came back, and stops taking new ones when the window closes.  After
the window, requests still out get ``drain`` seconds; what is not back by then
is recorded as failed (``status`` 0).

One result line per request goes to ``--out``: index, due_s, sent_s, done_s,
status, and under ``kept`` the fields of a 200 answer that ``--keep`` names
(the mix's generator says which, and judges them).  The last stdout line is a
summary.  One thread, one event loop: bodies are strings made before the
window, so the generator's own work in the window is a write and a parse.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import sys
import time

import aiohttp


async def _send(session, url, keep, item, t0, results):
    rec = {"index": item["index"], "due_s": item.get("due_s"), "status": 0}
    rec["sent_s"] = time.monotonic() - t0
    try:
        async with session.post(
            url,
            data=item["body"],
            headers={"content-type": "application/json"},
        ) as resp:
            raw = await resp.read()
            rec["status"] = resp.status
        rec["done_s"] = time.monotonic() - t0
        if rec["status"] == 200:
            answer = json.loads(raw)
            rec["kept"] = {field: answer.get(field) for field in keep}
        else:
            rec["error"] = raw[:200].decode("utf-8", "replace")
    except asyncio.CancelledError:
        rec["error"] = "not back when the drain ended"
        results.append(rec)
        raise
    except (aiohttp.ClientError, OSError, ValueError) as e:
        rec["done_s"] = time.monotonic() - t0
        rec["error"] = f"{type(e).__name__}: {e}"[:200]
    results.append(rec)


async def _open_loop(session, url, keep, items, seconds, drain, t0, results):
    tasks = []
    for item in sorted(items, key=lambda it: it["due_s"]):
        wait = item["due_s"] - (time.monotonic() - t0)
        if wait > 0:
            await asyncio.sleep(wait)
        tasks.append(
            asyncio.create_task(_send(session, url, keep, item, t0, results))
        )
    await _finish(tasks, seconds + drain - (time.monotonic() - t0))


async def _closed_loop(session, url, keep, items, seconds, drain, t0, results):
    by_caller: dict = {}
    for item in sorted(items, key=lambda it: it["turn"]):
        by_caller.setdefault(item["caller"], []).append(item)

    async def caller(mine):
        for item in mine:
            if time.monotonic() - t0 >= seconds:
                return
            await _send(session, url, keep, item, t0, results)

    tasks = [asyncio.create_task(caller(mine)) for mine in by_caller.values()]
    await _finish(tasks, seconds + drain)


async def _finish(tasks, timeout):
    if not tasks:
        return
    _, pending = await asyncio.wait(tasks, timeout=max(timeout, 0.0))
    for task in pending:
        task.cancel()
    for task in tasks:
        try:
            await task
        except asyncio.CancelledError:
            pass


async def run(args) -> dict:
    with open(args.schedule, encoding="utf-8") as f:
        items = [json.loads(line) for line in f if line.strip()]
    closed = bool(items) and "caller" in items[0]
    url = f"http://127.0.0.1:{args.port}{args.path}"
    keep = [field for field in args.keep.split(",") if field]
    results: list = []
    connector = aiohttp.TCPConnector(limit=0)
    timeout = aiohttp.ClientTimeout(total=None)
    async with aiohttp.ClientSession(connector=connector, timeout=timeout) as session:
        print("ready", flush=True)
        loop = asyncio.get_running_loop()
        line = await loop.run_in_executor(None, sys.stdin.readline)
        if not line.strip():
            raise SystemExit("loadgen: stdin closed before the start signal")
        t0 = time.monotonic()
        runner = _closed_loop if closed else _open_loop
        await runner(
            session, url, keep, items, args.seconds, args.drain, t0, results
        )
        elapsed = time.monotonic() - t0
    with open(args.out, "w", encoding="utf-8") as f:
        for rec in results:
            f.write(json.dumps(rec) + "\n")
    return {
        "loop": "closed" if closed else "open",
        "scheduled": len(items),
        "sent": len(results),
        "elapsed_s": elapsed,
    }


def main() -> None:
    parser = argparse.ArgumentParser("bench load generator")
    parser.add_argument("--schedule", required=True)
    parser.add_argument("--out", required=True)
    parser.add_argument("--port", type=int, required=True)
    parser.add_argument("--path", required=True)
    parser.add_argument("--keep", default="", help="fields of a 200 answer to keep")
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--drain", type=float, default=10.0)
    args = parser.parse_args()
    print(json.dumps(asyncio.run(run(args))), flush=True)


if __name__ == "__main__":
    main()
