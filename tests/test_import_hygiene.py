"""Pure-core import hygiene: the wire-type and identity layers must be
loadable with neither a device runtime nor an HTTP stack installed — the
analog of the reference keeping its core wasm-compatible (main.rs gates
the server features behind cfg flags so the type crates build anywhere).

A subprocess import with jax/aiohttp poisoned proves it structurally:
if anything in types/ or identity/ (or their transitive imports through
errors/utils) pulls either in, the import fails loudly.
"""

import json
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent

_PROBE = r"""
import sys

class _Poison:
    # meta_path finder (find_spec API; find_module is dead in 3.12)
    # that fails any import of the banned runtime stacks
    def __init__(self, name):
        self.name = name

    def find_spec(self, fullname, path=None, target=None):
        if fullname == self.name or fullname.startswith(self.name + "."):
            raise ImportError(f"POISONED: pure core imported {fullname}")

for banned in ("jax", "jaxlib", "aiohttp", "torch", "flax"):
    sys.meta_path.insert(0, _Poison(banned))

import llm_weighted_consensus_tpu.types.chat_request
import llm_weighted_consensus_tpu.types.chat_response
import llm_weighted_consensus_tpu.types.score_request
import llm_weighted_consensus_tpu.types.score_response
import llm_weighted_consensus_tpu.types.multichat_request
import llm_weighted_consensus_tpu.types.multichat_response
import llm_weighted_consensus_tpu.types.embeddings
import llm_weighted_consensus_tpu.identity.llm
import llm_weighted_consensus_tpu.identity.model
import llm_weighted_consensus_tpu.errors
import llm_weighted_consensus_tpu.weights
import llm_weighted_consensus_tpu.ballot
import llm_weighted_consensus_tpu.cache
import llm_weighted_consensus_tpu.cache.fingerprint
import llm_weighted_consensus_tpu.cache.store
import llm_weighted_consensus_tpu.cache.singleflight
import llm_weighted_consensus_tpu.cache.replay

import json as _json
loaded = sorted(
    m for m in sys.modules
    if m.split(".")[0] in ("jax", "jaxlib", "aiohttp", "torch", "flax")
)
print(_json.dumps({"ok": True, "leaked": loaded}))
"""


def test_types_and_identity_import_without_jax_or_aiohttp():
    proc = subprocess.run(
        [sys.executable, "-c", _PROBE],
        capture_output=True,
        text=True,
        timeout=120,
        cwd=str(REPO),
        # a bare environment: nothing ambient may preload a dependency
        env={"PYTHONPATH": str(REPO), "PATH": "/usr/bin:/bin"},
    )
    assert proc.returncode == 0, (
        f"pure-core import failed:\n{proc.stdout}\n{proc.stderr}"
    )
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["ok"] and out["leaked"] == [], out
