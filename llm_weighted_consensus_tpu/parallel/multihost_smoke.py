"""Multi-process DCN smoke: actually form a ``jax.distributed`` group.

`parallel.dist.maybe_initialize_distributed` is the multi-host entry point;
this module proves it forms a real process group without TPU pod hardware:
N CPU processes rendezvous at a localhost coordinator, build ONE GLOBAL
mesh over ``jax.devices()``, and run the consensus reduction with the
cross-process psum riding the distributed backend — the same code path
that rides DCN on a pod (SURVEY §2.8 "DCN for multi-host slices";
DESIGN.md §multi-host).

With ``devices_per_proc > 1`` (VERDICT r3 item 5) each process hosts
several virtual devices and the group EXECUTES the DESIGN.md axis
placement instead of just arguing it: a global (dp=processes,
tp=devices-per-process) mesh runs the TP-sharded encoder forward
(Megatron split, parallel/sharding.py) + the dp tally, every process
checks the sharded output against an unsharded local reference, and the
compiled HLO's replica groups are asserted to keep tp collectives INSIDE
a process (ICI) while only dp-sized groups cross the process boundary
(DCN).

Two entry points:

* ``python -m llm_weighted_consensus_tpu.parallel.multihost_smoke`` — one
  worker process (env: COORDINATOR_ADDRESS/NUM_PROCESSES/PROCESS_ID/
  DEVICES_PER_PROC, set by the launcher).  Prints ``MULTIHOST_OK {json}``
  on success.
* ``run_group(num_processes, devices_per_proc)`` — spawn the workers,
  collect and cross-check their outputs; used by tests/test_multihost.py
  and ``__graft_entry__.dryrun_multihost``.
"""

from __future__ import annotations

import json
import os
import socket
import subprocess
import sys

# deterministic fixture: M=4 judges, N=3 candidates
VOTES = [
    [1.0, 0.0, 0.0],
    [0.0, 1.0, 0.0],
    [0.25, 0.5, 0.25],
    [0.0, 0.0, 1.0],
]
WEIGHTS = [2.0, 1.0, 1.0, 0.5]


def expected_confidence():
    total = sum(WEIGHTS)
    per = [
        sum(VOTES[m][n] * WEIGHTS[m] for m in range(len(WEIGHTS)))
        for n in range(len(VOTES[0]))
    ]
    return [p / total for p in per]


def _parse_replica_groups(hlo_text: str) -> list:
    """Every ``replica_groups=...`` in an HLO dump as a list of id lists.

    Handles both the explicit ``{{0,1},{2,3}}`` form and the iota form
    ``[G,S]<=[N]`` (row-major) / ``[G,S]<=[a,b]T(1,0)`` (transposed).
    """
    import re

    groups = []
    for m in re.finditer(r"replica_groups=\{\{([0-9,{} ]*)\}\}", hlo_text):
        # tolerate whitespace between groups: '{{0,1}, {2,3}}' must split
        # into two groups, not merge into one
        for grp in re.split(r"\}\s*,\s*\{", m.group(1)):
            ids = [
                int(x)
                for x in grp.replace("{", "").replace("}", "").split(",")
                if x.strip()
            ]
            if ids:
                groups.append(ids)
    for m in re.finditer(
        r"replica_groups=\[(\d+),(\d+)\]<=\[([0-9,]+)\](T\(([0-9,]+)\))?",
        hlo_text,
    ):
        g, s = int(m.group(1)), int(m.group(2))
        dims = [int(x) for x in m.group(3).split(",")]
        import numpy as np

        ids = np.arange(int(np.prod(dims))).reshape(dims)
        if m.group(5):
            ids = ids.transpose([int(x) for x in m.group(5).split(",")])
        for row in ids.reshape(g, s):
            groups.append([int(x) for x in row])
    return groups


def _collective_boundary_report(hlo_text: str, num: int, dpp: int) -> dict:
    """Classify every replica group: within one process's device block
    (tp riding ICI) or crossing processes (dp riding DCN)."""
    blocks = [set(range(p * dpp, (p + 1) * dpp)) for p in range(num)]
    within = crossing = 0
    crossing_sizes = set()
    for grp in _parse_replica_groups(hlo_text):
        ids = set(grp)
        if any(ids <= b for b in blocks):
            within += 1
        else:
            crossing += 1
            crossing_sizes.add(len(grp))
    return {
        "within_process_groups": within,
        "crossing_groups": crossing,
        "crossing_group_sizes": sorted(crossing_sizes),
    }


def worker_main() -> None:
    """One process of the group (see module doc)."""
    from .dist import maybe_initialize_distributed

    assert maybe_initialize_distributed(), "MULTIHOST env not set?"
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from .collectives import sharded_tally

    num = int(os.environ["NUM_PROCESSES"])
    dpp = int(os.environ.get("DEVICES_PER_PROC", "1"))
    assert jax.process_count() == num, (
        f"process group has {jax.process_count()} processes, want {num}"
    )
    devices = jax.devices()  # GLOBAL list across the group
    assert len(devices) == num * dpp, (
        f"{len(devices)} global devices, want {num * dpp}"
    )
    # dp outer (across processes / DCN), tp inner (within a process / ICI):
    # jax.devices() is process-major, so the reshape rows are processes
    if dpp > 1:
        mesh = Mesh(np.array(devices).reshape(num, dpp), ("dp", "tp"))
    else:
        mesh = Mesh(np.array(devices), ("dp",))

    # dp must divide the row count; replicating the WHOLE vote table
    # scales numerator and denominator of the normalized tally equally,
    # so expected_confidence() is unchanged at any group size
    reps = 1
    while (len(VOTES) * reps) % num:
        reps += 1
    votes_np = np.array(VOTES * reps, np.float32)
    weights_np = np.array(WEIGHTS * reps, np.float32)

    def globalize(arr, spec):
        sharding = NamedSharding(mesh, spec)
        return jax.make_array_from_callback(
            arr.shape, sharding, lambda idx: arr[idx]
        )

    votes = globalize(votes_np, P("dp", None))
    weights = globalize(weights_np, P("dp"))
    import time

    t0 = time.perf_counter()
    conf = jax.block_until_ready(sharded_tally(votes, weights, mesh))
    tally_ms = round((time.perf_counter() - t0) * 1e3, 3)
    assert conf.is_fully_replicated
    out = {
        "process_id": jax.process_index(),
        "num_processes": num,
        "global_devices": len(devices),
        "mesh_shape": {"dp": num, "tp": dpp},
        "confidence": np.asarray(conf).tolist(),
        # wall time of the cross-process psum incl. compile on first use —
        # the number that blows up when dp traffic accidentally rides a
        # slow transport, which the bare ok-marker never showed
        "tally_ms": tally_ms,
    }

    if dpp > 1:
        out.update(_encoder_phase(mesh, num, dpp))

    print("MULTIHOST_OK " + json.dumps(out), flush=True)


def _encoder_phase(mesh, num: int, dpp: int) -> dict:
    """TP-sharded encoder forward on the global (dp, tp) mesh: numerics
    checked against an unsharded local reference, and the compiled HLO's
    collectives classified by process boundary (see module doc)."""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..models import bert
    from ..models.configs import TEST_TINY
    from .sharding import bert_param_specs, shard_bert_params

    config = TEST_TINY
    params = bert.init_params(jax.random.PRNGKey(0), config)
    rng = np.random.default_rng(7)
    batch = 2 * num  # divides dp
    ids_np = rng.integers(
        3, config.vocab_size, size=(batch, 16)
    ).astype(np.int32)
    mask_np = np.ones((batch, 16), np.int32)

    # local, unsharded reference BEFORE params are device_put on the mesh
    ref = np.asarray(
        bert.embed(params, ids_np, mask_np, config, pooling="cls")
    )

    sharded_params = shard_bert_params(params, mesh, tp=True)
    batch_sharding = NamedSharding(mesh, P("dp", None))

    def globalize(arr):
        return jax.make_array_from_callback(
            arr.shape, batch_sharding, lambda idx: arr[idx]
        )

    fwd = jax.jit(
        lambda p, i, m: bert.embed(p, i, m, config, pooling="cls"),
        in_shardings=(
            jax.tree_util.tree_map(
                lambda s: NamedSharding(mesh, s),
                bert_param_specs(tp=True),
                is_leaf=lambda x: isinstance(x, P),
            ),
            batch_sharding,
            batch_sharding,
        ),
        out_shardings=NamedSharding(mesh, P()),
    )
    ids = globalize(ids_np)
    mask = globalize(mask_np)
    import time

    t0 = time.perf_counter()
    lowered = fwd.lower(sharded_params, ids, mask)
    compiled = lowered.compile()
    compile_ms = round((time.perf_counter() - t0) * 1e3, 3)
    t0 = time.perf_counter()
    emb = np.asarray(
        jax.block_until_ready(compiled(sharded_params, ids, mask))
    )
    forward_ms = round((time.perf_counter() - t0) * 1e3, 3)
    err = float(np.max(np.abs(emb - ref)))

    report = _collective_boundary_report(
        compiled.as_text(), num, dpp
    )
    report["encoder_max_err_vs_unsharded"] = err
    report["encoder_checksum"] = float(np.sum(emb, dtype=np.float64))
    report["encoder_compile_ms"] = compile_ms
    report["encoder_forward_ms"] = forward_ms
    return report


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def run_group(
    num_processes: int = 2,
    timeout: float = 300.0,
    attempts: int = 2,
    devices_per_proc: int = 1,
) -> list:
    """Spawn the worker group; return per-process result dicts.

    Raises on any worker failure or cross-process disagreement — this is
    the pass/fail gate for the DCN smoke.  The coordinator port is probed
    then released (TOCTOU window before the coordinator re-binds it), so
    one retry with a fresh port absorbs the rare steal.

    With ``devices_per_proc > 1`` the group also runs the TP-sharded
    encoder phase and this gate additionally asserts: all processes agree
    on the encoder output (and match the unsharded reference), the HLO
    has at least one within-process collective (the Megatron TP
    all-reduces), and every process-crossing replica group has exactly
    ``num_processes`` participants — i.e. ONLY dp traffic crosses the
    DCN boundary.
    """
    last: Exception = RuntimeError("unreachable")
    for _ in range(attempts):
        try:
            return _run_group_once(num_processes, timeout, devices_per_proc)
        except RuntimeError as exc:
            last = exc
    raise last


def _run_group_once(
    num_processes: int, timeout: float, devices_per_proc: int = 1
) -> list:
    coordinator = f"127.0.0.1:{_free_port()}"
    repo_root = os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )
    from .dist import force_cpu_env

    procs = []
    for pid in range(num_processes):
        # the smoke is about GROUP FORMATION, so workers run pure-CPU
        env = force_cpu_env(dict(os.environ), n_devices=devices_per_proc)
        env.update(
            MULTIHOST="1",
            COORDINATOR_ADDRESS=coordinator,
            NUM_PROCESSES=str(num_processes),
            PROCESS_ID=str(pid),
            DEVICES_PER_PROC=str(devices_per_proc),
            PYTHONPATH=repo_root
            + os.pathsep
            + os.environ.get("PYTHONPATH", ""),
        )
        procs.append(
            subprocess.Popen(
                [
                    sys.executable,
                    "-m",
                    "llm_weighted_consensus_tpu.parallel.multihost_smoke",
                ],
                env=env,
                stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT,
                text=True,
            )
        )
    results = []
    failures = []
    for pid, proc in enumerate(procs):
        try:
            out, _ = proc.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            proc.kill()
            out, _ = proc.communicate()
            failures.append(f"process {pid} timed out:\n{out}")
            continue
        marker = [
            line for line in out.splitlines() if line.startswith("MULTIHOST_OK ")
        ]
        if proc.returncode != 0 or not marker:
            failures.append(
                f"process {pid} rc={proc.returncode}:\n{out[-2000:]}"
            )
            continue
        results.append(json.loads(marker[0][len("MULTIHOST_OK "):]))
    if failures:
        raise RuntimeError("DCN smoke failed:\n" + "\n---\n".join(failures))
    results.sort(key=lambda r: r["process_id"])
    confs = [r["confidence"] for r in results]
    first = confs[0]
    for other in confs[1:]:
        if any(abs(a - b) > 1e-6 for a, b in zip(first, other)):
            raise RuntimeError(
                f"processes disagree on the tally: {confs}"
            )
    exp = expected_confidence()
    if any(abs(a - b) > 1e-5 for a, b in zip(first, exp)):
        raise RuntimeError(f"tally {first} != expected {exp}")
    for r in results:
        if r["num_processes"] != num_processes or r[
            "global_devices"
        ] != num_processes * devices_per_proc:
            raise RuntimeError(f"group shape wrong: {r}")
    if devices_per_proc > 1:
        sums = {round(r["encoder_checksum"], 4) for r in results}
        if len(sums) != 1:
            raise RuntimeError(
                f"processes disagree on the encoder output: {sums}"
            )
        for r in results:
            if r["encoder_max_err_vs_unsharded"] > 2e-4:
                raise RuntimeError(
                    "sharded encoder diverges from the unsharded "
                    f"reference: {r['encoder_max_err_vs_unsharded']}"
                )
            if r["within_process_groups"] < 1:
                raise RuntimeError(
                    "no within-process collective found — the Megatron "
                    f"TP all-reduces are missing: {r}"
                )
            if r["crossing_groups"] < 1:
                # without at least one crossing group the 'dp rides DCN'
                # half of the claim is vacuous (e.g. the batch silently
                # became replicated and nothing spans processes)
                raise RuntimeError(
                    f"no process-crossing collective found: {r}"
                )
            bad = [
                s for s in r["crossing_group_sizes"] if s != num_processes
            ]
            if bad:
                raise RuntimeError(
                    "collective groups larger than dp cross the process "
                    f"boundary (tp traffic on DCN): sizes {bad}"
                )
    return results


if __name__ == "__main__":
    worker_main()
