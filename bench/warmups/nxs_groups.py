"""Warm-up recipe ``nxs_groups``: how the program's embedder takes the shapes
to compile before it listens.

``WARMUP=NxS,...`` is every (candidates, tokens) shape the schedule reaches
(the program snaps tokens to its own sequence bucket).  ``WARMUP_R`` is the
group sizes the batcher can form of them: powers of two from 2 up to the
largest group, which is the smaller of ``BATCH_MAX`` requests and
``BATCH_MAX_ROWS`` rows over a request's N (the program's defaults 64 and
512 where the configuration's ``server_env`` sets neither)."""


def env(shapes: list, server_env: dict) -> dict:
    out = {"WARMUP": ",".join(f"{n}x{s}" for n, s in shapes)}
    max_rows = int(server_env.get("BATCH_MAX_ROWS", 512))
    max_batch = int(server_env.get("BATCH_MAX", 64))
    r_top = max(min(max_batch, max_rows // n) for n, _ in shapes)
    r_buckets = [2**k for k in range(1, 12) if 2**k <= max(r_top, 1)]
    if r_buckets:
        out["WARMUP_R"] = ",".join(str(r) for r in r_buckets)
    return out
