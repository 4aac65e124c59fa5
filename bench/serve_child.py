#!/usr/bin/env python3
"""The system under test as the benchmark starts it: the program's own entry,
``python -m llm_weighted_consensus_tpu.serve``, run unchanged through
``runpy`` in this process's main thread, with one reader beside it.

Only the process that holds the chip can read its memory, and the program's
``/metrics`` gives ``bytes_in_use`` and ``peak_bytes_in_use`` alone.  On the
TPU runtime the temporaries of compiled programs (activations) are held in a
region of their own that those two do not count: ``bytes_reserved`` and
``peak_bytes_reserved`` of ``Device.memory_stats()``.  So a daemon thread
here waits for the harness to ask (a file appears), then writes every local
device's ``memory_stats()`` as JAX reports them, and nothing else.  It does
not touch jax before it is asked, which is after the window, when the server
has long initialized it.

    python3 bench/serve_child.py --memory-request FILE --memory-out FILE \
        -- --port P --fake-upstream
"""

from __future__ import annotations

import json
import os
import runpy
import sys
import threading
import time


def answer_memory_requests(request: str, out: str) -> None:
    while True:
        if os.path.exists(request):
            import jax

            rows = [
                {"id": d.id, **(d.memory_stats() or {})} for d in jax.local_devices()
            ]
            with open(out + ".tmp", "w", encoding="utf-8") as f:
                json.dump(rows, f)
            os.remove(request)
            os.replace(out + ".tmp", out)
        time.sleep(0.1)


def main() -> None:
    args = sys.argv[1:]
    split = args.index("--")
    own, program = args[:split], args[split + 1 :]
    request = own[own.index("--memory-request") + 1]
    out = own[own.index("--memory-out") + 1]
    threading.Thread(
        target=answer_memory_requests, args=(request, out), daemon=True
    ).start()
    sys.argv = ["llm_weighted_consensus_tpu.serve", *program]
    runpy.run_module("llm_weighted_consensus_tpu.serve", run_name="__main__")


if __name__ == "__main__":
    main()
