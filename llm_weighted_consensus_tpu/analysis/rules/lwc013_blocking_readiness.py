"""LWC013 — blocking readiness call outside the sanctioned waiter.

The host<->device overlap contract (models/dispatch_seam.py) is that
the dispatch hot path returns at PJRT ENQUEUE: readiness — the blocking
``block_until_ready`` / ``device_get`` — belongs to the batcher's
waiter thread, reached only through ``wait_device_ready``.  One stray
bracket on the dispatch path silently re-serializes the pipeline (the
exact regression ISSUE 13 removed) without failing any functional
test, so the gate is static.

Allowed:

* ``wait_device_ready`` itself (models/dispatch_seam.py) — the ONE
  sanctioned blocking readiness call, run by waiter threads;
* ``parallel/multihost_smoke.py`` — an offline probe/benchmark, not a
  serving path; it blocks on purpose to measure.

Note that ``np.asarray`` on a device array also blocks, but flagging every
asarray would drown the signal — the finalize-closure convention
(serve/batcher.py) covers those by construction.
"""

from __future__ import annotations

import ast
from typing import List

from ..engine import Finding, ParsedModule, body_nodes, dotted_name
from . import Rule

_BLOCKING = ("block_until_ready", "device_get")

_EXEMPT_SUFFIXES = ("parallel/multihost_smoke.py",)

# function qualnames allowed to block (the waiter seam itself)
_ALLOWED_SYMBOLS = {"wait_device_ready"}


def check(module: ParsedModule) -> List[Finding]:
    if module.rel.endswith(_EXEMPT_SUFFIXES):
        return []
    findings: List[Finding] = []
    for fn in module.functions():
        if fn.qualname in _ALLOWED_SYMBOLS:
            continue
        for node in body_nodes(fn.node):
            if not isinstance(node, ast.Call):
                continue
            dotted = dotted_name(node.func)
            if dotted is None:
                continue
            if dotted.rsplit(".", 1)[-1] not in _BLOCKING:
                continue
            findings.append(
                Finding(
                    rule=RULE.name,
                    path=module.rel,
                    line=node.lineno,
                    symbol=fn.qualname,
                    message=(
                        f"`{dotted}(...)` blocks on device readiness "
                        "outside the waiter seam: the dispatch path "
                        "must return at enqueue — defer through "
                        "dispatch_seam (wait_device_ready runs on the "
                        "waiter thread)"
                    ),
                )
            )
    return findings


RULE = Rule(
    name="LWC013",
    summary="blocking device-readiness call outside the waiter seam",
    check=check,
)
