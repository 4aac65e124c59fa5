"""CLI for the offline lane: ``python -m llm_weighted_consensus_tpu.train``.

Two subcommands (ISSUE 20 tentpole piece b):

``fit``
    Stream the ledger shards under ``--ledger-dir`` (default:
    ``LEDGER_DIR``) through the batched JAX learner (``train/fit.py``)
    and print the versioned weights report.  ``--out`` writes the
    table in the ``lwc.weights.v1`` format ``WEIGHTS_PATH`` loads at
    startup; ``--put`` hot-swaps it into a RUNNING server via
    PUT /v1/weights (zero-restart promotion).

``rescore``
    Saturate the offline priority class: build the env-configured
    embedder (the same ``build_embedder`` the server uses), assemble
    candidate groups from the ``ARCHIVE_PATH`` snapshot — or
    ``--synthetic N`` deterministic groups — and drive them through
    ``DeviceBatcher.consensus(priority="offline")``.  Prints the
    groups/items pushed and the merged offline device occupancy, the
    near-100%-on-an-idle-mesh acceptance gauge.
"""

from __future__ import annotations

import argparse
import asyncio
import sys

from ..utils import jsonutil


def _cmd_fit(args) -> int:
    from .fit import fit_from_ledger

    if not args.ledger_dir:
        # knobs enter through Config.from_env (LWC008) — the CLI default
        # is the server's own LEDGER_DIR so fit trains on what serve wrote
        from ..serve.config import Config

        args.ledger_dir = Config.from_env().ledger_dir
    if not args.ledger_dir:
        print(
            "fit: no ledger directory (--ledger-dir or LEDGER_DIR)",
            file=sys.stderr,
        )
        return 2
    labels = None
    if args.labels:
        with open(args.labels, encoding="utf-8") as f:
            labels = {
                str(k): int(v) for k, v in jsonutil.loads(f.read()).items()
            }
    report = fit_from_ledger(
        args.ledger_dir,
        labels=labels,
        steps=args.steps,
        lr=args.lr,
        holdout_every=args.holdout_every,
    )
    if report is None:
        print("fit: no trainable records in the ledger", file=sys.stderr)
        return 1
    if args.out:
        from ..utils.io import atomic_write

        doc = {
            "schema": "lwc.weights.v1",
            "active": {
                "version": report["version"],
                "weights": {
                    k: str(v) for k, v in report["weights"].items()
                },
            },
            "shadow": None,
        }
        payload = jsonutil.dumps(doc).encode("utf-8")
        atomic_write(args.out, lambda f: f.write(payload))
    if args.put:
        import urllib.request

        body = jsonutil.dumps(
            {
                "version": report["version"],
                "weights": report["weights"],
                "mode": args.put_mode,
            }
        ).encode("utf-8")
        req = urllib.request.Request(
            args.put.rstrip("/") + "/v1/weights",
            data=body,
            method="PUT",
            headers={"content-type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=30) as resp:
            report["put"] = jsonutil.loads(resp.read().decode("utf-8"))
    print(jsonutil.dumps(report))
    return 0


async def _rescore_async(args) -> dict:
    from ..serve.__main__ import build_embedder
    from ..serve.batcher import DeviceBatcher
    from ..serve.config import Config
    from .feed import OfflineFeed, archive_groups, synthetic_groups

    config = Config.from_env()
    embedder = build_embedder(config, allow_synthetic=True)
    if embedder is None:
        raise SystemExit("rescore: no embedder configured (EMBED_MODEL)")
    batcher = DeviceBatcher(
        embedder,
        None,
        window_ms=config.batch_window_ms,
        max_batch=config.batch_max,
        pipeline_depth=config.batch_pipeline,
        max_rows=config.batch_max_rows,
        host_tokenizer_workers=config.host_tokenizer_workers,
        staging_buffers=config.staging_buffers,
    )
    try:
        if args.synthetic:
            groups = synthetic_groups(args.synthetic, args.n, seed=args.seed)
        else:
            import os

            from .. import archive

            if not config.archive_path or not os.path.exists(
                config.archive_path
            ):
                raise SystemExit(
                    "rescore: no archive snapshot (ARCHIVE_PATH) — "
                    "use --synthetic N for a synthetic feed"
                )
            store = archive.InMemoryArchive.load(config.archive_path)
            groups = list(archive_groups(store))
        feed = OfflineFeed(batcher, inflight=args.inflight)
        import time

        t0 = time.perf_counter()
        _results, occupancy = await feed.drive(groups)
        wall = time.perf_counter() - t0
        util = batcher.utilization()
        return {
            "groups": feed.groups,
            "items": feed.items,
            "errors": feed.errors,
            "wall_sec": round(wall, 3),
            "offline_occupancy": occupancy,
            "lanes": util["lanes"],
        }
    finally:
        batcher.close()


def _cmd_rescore(args) -> int:
    stats = asyncio.run(_rescore_async(args))
    print(jsonutil.dumps(stats))
    return 0


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m llm_weighted_consensus_tpu.train"
    )
    sub = parser.add_subparsers(dest="command", required=True)

    fit = sub.add_parser("fit", help="fit per-judge weights from the ledger")
    fit.add_argument(
        "--ledger-dir", default=None, help="default: the server's LEDGER_DIR"
    )
    fit.add_argument(
        "--labels",
        default=None,
        help="JSON file mapping record id -> candidate label (supervised); "
        "without it, records score self-consistently against their winner",
    )
    fit.add_argument("--steps", type=int, default=300)
    fit.add_argument("--lr", type=float, default=0.1)
    fit.add_argument("--holdout-every", type=int, default=4)
    fit.add_argument(
        "--out", default=None, help="write the lwc.weights.v1 table here"
    )
    fit.add_argument(
        "--put",
        default=None,
        help="base URL of a running server to hot-swap via PUT /v1/weights",
    )
    fit.add_argument("--put-mode", choices=("active", "shadow"), default="active")
    fit.set_defaults(run=_cmd_fit)

    rescore = sub.add_parser(
        "rescore", help="drive archive/synthetic groups through the offline lane"
    )
    rescore.add_argument(
        "--synthetic",
        type=int,
        default=0,
        help="drive N deterministic synthetic groups instead of the archive",
    )
    rescore.add_argument("--n", type=int, default=8, help="candidates per group")
    rescore.add_argument("--seed", type=int, default=0)
    rescore.add_argument("--inflight", type=int, default=4)
    rescore.set_defaults(run=_cmd_rescore)

    args = parser.parse_args(argv)
    return args.run(args)


if __name__ == "__main__":
    sys.exit(main())
