"""Multi-host (DCN) initialization for multi-slice / multi-host TPU pods.

The reference's "distributed backend" is HTTPS+SSE to upstream APIs
(SURVEY §2.8); ours is the JAX runtime itself.  Within one host's slice
the mesh collectives ride ICI; across hosts JAX runs one process per host
and XLA routes inter-host collective traffic over DCN automatically once
``jax.distributed.initialize`` has formed the process group.

Design note (what changes at 2+ hosts) — see DESIGN.md §multi-host:
* every host runs this same binary with ``MULTIHOST=1`` and the same
  ``COORDINATOR_ADDRESS``; host 0 doubles as the coordinator;
* ``jax.devices()`` then reports the GLOBAL device list, so
  ``parallel.mesh.make_mesh`` transparently builds a global mesh — mesh
  construction, shardings, and collectives are unchanged by design;
* keep ``dp`` as the outer (cross-host) mesh axis: candidate batches are
  embarrassingly parallel so only the final tally's psum crosses DCN,
  while ``tp``'s per-layer all-reduces stay on intra-host ICI.
"""

from __future__ import annotations

import os
from typing import Mapping, Optional


def force_cpu_env(env: dict, n_devices: int) -> dict:
    """Mutate+return ``env`` so a FRESH process initializes jax on
    ``n_devices`` virtual CPU devices: ``JAX_PLATFORMS=cpu`` and the
    host-platform device-count flag, other ``XLA_FLAGS`` kept.  Used by
    the DCN smoke's worker processes, the mesh audit's respawn and
    ``__graft_entry__``'s virtual-mesh dryrun."""
    env["JAX_PLATFORMS"] = "cpu"
    flags = " ".join(
        part
        for part in env.get("XLA_FLAGS", "").split()
        if "host_platform_device_count" not in part
    )
    env["XLA_FLAGS"] = (
        f"{flags} --xla_force_host_platform_device_count={n_devices}"
    ).strip()
    return env


def multihost_requested(env: Optional[Mapping] = None) -> bool:
    from ..utils import env_truthy

    env = os.environ if env is None else env
    return env_truthy(env.get("MULTIHOST", ""))


def maybe_initialize_distributed(env: Optional[Mapping] = None) -> bool:
    """Form the multi-host process group iff ``MULTIHOST`` is truthy.

    Reads ``COORDINATOR_ADDRESS`` / ``NUM_PROCESSES`` / ``PROCESS_ID``
    (all optional: on Cloud TPU metadata autodetection fills them in).
    Single-host behavior is unchanged: without the flag this is a no-op
    and returns False.  Call before any other jax API touches a backend.
    """
    env = os.environ if env is None else env
    if not multihost_requested(env):
        return False
    import jax

    kwargs = {}
    if env.get("COORDINATOR_ADDRESS"):
        kwargs["coordinator_address"] = env["COORDINATOR_ADDRESS"]
    if env.get("NUM_PROCESSES"):
        kwargs["num_processes"] = int(env["NUM_PROCESSES"])
    if env.get("PROCESS_ID"):
        kwargs["process_id"] = int(env["PROCESS_ID"])
    jax.distributed.initialize(**kwargs)
    return True
