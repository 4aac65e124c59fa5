"""Fleet-shared store of serialized compiled executables.

``aot_warmup`` (models/embedder.py) pre-compiles every configured
bucket so no request pays a jit compile.  On a single replica that cost
is paid once per process start; in a fleet it is paid once per REPLICA
— a new replica joining an autoscaled tier burns tens of seconds of
XLA compilation to produce byte-identical executables its peers
already hold.  This store closes that gap: the first replica to compile
a bucket serializes the executable (``jax.experimental
.serialize_executable``) into a shared artifact directory, and every
later replica — or the same replica after a restart — deserializes in
milliseconds instead of compiling.

Artifact layout (``aot/v1``)::

    <root>/<digest>/            one namespace per environment digest
        meta.json               the digest preimage, for humans
        <key-hash>.aotx         pickle of (payload, in_tree, out_tree)

The digest folds in everything that makes an executable non-portable:
jax version, backend, device kind and count, model name/config/dtype,
pooling, and max_tokens.  Any change lands in a fresh namespace, so a
stale artifact can never be deserialized into an incompatible runtime —
invalidation is by construction, not by cleanup.  Per-key filenames
hash the full warmup key (``("mesh", dp, tp, sp, bucket)`` prefixes
included), so single-device, mesh, and ring executables for the same
bucket shapes can never collide.

Every path fails open: an unreadable, truncated, or version-skewed
artifact returns None — logged once per store with the reason — and the
caller compiles exactly as before the store existed.  Writes are atomic
(tmp + rename) so a replica crashing mid-save never poisons a peer.
"""

from __future__ import annotations

import logging
import os
import pickle

from ..identity import hash_json_obj, id_string

FORMAT = "aot/v1"


def _key_name(key) -> str:
    return id_string(hash_json_obj(repr(key))) + ".aotx"


class AotStore:
    def __init__(self, root: str, *, meta: dict) -> None:
        self.meta = dict(meta, format=FORMAT)
        self.digest = id_string(hash_json_obj(self.meta))
        self.dir = os.path.join(root, self.digest)
        self.loads = 0
        self.saves = 0
        self.load_failures = 0
        self.save_failures = 0
        self._warned = set()

    def _path(self, key) -> str:
        return os.path.join(self.dir, _key_name(key))

    def _warn_once(self, what: str, key) -> None:
        """Log the first fail-open fault of each kind with its traceback
        (every warmup bucket failing the same way would otherwise repeat
        it); call from the except block."""
        if what not in self._warned:
            self._warned.add(what)
            logging.getLogger("lwc.serve").warning(
                "AOT store %s failed for %r under %s; compiling instead "
                "(further %s failures are only counted)",
                what, key, self.dir, what,
                exc_info=True,
            )

    def load(self, key, devices):
        """The deserialized executable for ``key``, loaded onto
        ``devices`` — the ones it was compiled for, in assignment order;
        left to its default, jax loads it over every local device and a
        single-device executable then rejects its arguments.  None when
        missing, unreadable, or incompatible: the caller compiles."""
        try:
            with open(self._path(key), "rb") as f:
                payload, in_tree, out_tree = pickle.load(f)
            from jax.experimental import serialize_executable

            compiled = serialize_executable.deserialize_and_load(
                payload, in_tree, out_tree, execution_devices=devices
            )
        except FileNotFoundError:
            return None
        except Exception:
            self.load_failures += 1
            self._warn_once("load", key)
            return None
        self.loads += 1
        return compiled

    def save(self, key, compiled) -> bool:
        try:
            from jax.experimental import serialize_executable

            payload, in_tree, out_tree = serialize_executable.serialize(
                compiled
            )
            os.makedirs(self.dir, exist_ok=True)
            meta_path = os.path.join(self.dir, "meta.json")
            if not os.path.exists(meta_path):
                from ..utils import jsonutil

                self._atomic_write(
                    meta_path,
                    jsonutil.dumps(self.meta, pretty=True).encode("utf-8"),
                )
            self._atomic_write(
                self._path(key),
                pickle.dumps((payload, in_tree, out_tree)),
            )
        except Exception:
            self.save_failures += 1
            self._warn_once("save", key)
            return False
        self.saves += 1
        return True

    def _atomic_write(self, path: str, data: bytes) -> None:
        tmp = path + f".tmp.{os.getpid()}"
        with open(tmp, "wb") as f:
            f.write(data)
        os.replace(tmp, path)

    def stats(self) -> dict:
        return {
            "dir": self.dir,
            "loads": self.loads,
            "saves": self.saves,
            "load_failures": self.load_failures,
            "save_failures": self.save_failures,
        }
