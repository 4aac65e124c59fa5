#!/usr/bin/env python3
"""A share's way back from the experts at a width that is no whole tile of
words, each form ALONE on the chip (PR 40).

    chiprun -- python3 scripts/time_held_forms.py         (host clock around
    ``block_until_ready``, min of 5; one JSON line a form on stdout and in
    ``chiprun_out/held_forms.jsonl``; ``--tiny`` rehearses on the CPU and
    times nothing; ``--layer`` times the whole layer's two forms only)

A sparse layer of the fourth judge's cell: 24,576 tokens, 8 choices of a
router 256 wide, experts 0..15 held (6.25% of the pairs by an even spread),
hidden 5120, experts 1536 wide, bf16; the down product over ``usual_rows`` =
49,152 rows and over the layout's whole bound.

  (i)   the padded slab: the down product leaves a row a slab of 24 sublanes,
        20 written, and ``held_rows_sum`` copies a slab a held pair;
  (ii)  the masked gathers: the down product in column chunks, k gathers a
        chunk, three pairs in four fetched to be masked (what ``row_slabs``
        answering (0, 0) keeps);
  (iii) the down product 6144 wide (``w_down`` with 1024 columns of zeros
        behind it), the unpadded slab of 24 sublanes walked and the sum cut
        back to 5120 columns: nothing but the weight moves.
Each checked against (ii) bit for bit before it is timed; then the whole layer
(``experts_grouped``, its ``lax.cond`` in it) with the walk and with the
gathers.
"""
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
import jax
import jax.numpy as jnp
import numpy as np

from llm_weighted_consensus_tpu.models import decoder_parts
from llm_weighted_consensus_tpu.ops import grouped_matmul as gm

TINY = "--tiny" in sys.argv
T, K, ROUTER, HELD = (24_576, 8, 256, 16) if not TINY else (160, 4, 32, 12)
HIDDEN, WIDTH, DT = (5120, 1536, jnp.bfloat16) if not TINY else (2560, 16, jnp.bfloat16)
OUT = os.path.join(ROOT, "chiprun_out")


def timed(name, fn, *args, repeat=5, **note):
    f = jax.jit(fn)
    t0 = time.perf_counter()
    out = jax.block_until_ready(f(*args))
    compile_s = time.perf_counter() - t0
    if TINY:
        return out
    jax.block_until_ready(f(*args))
    times = []
    for _ in range(repeat):
        t0 = time.perf_counter()
        jax.block_until_ready(f(*args))
        times.append((time.perf_counter() - t0) * 1e3)
    row = {"form": name, "ms_min": min(times), "ms_median": sorted(times)[len(times) // 2],
           "compile_s": round(compile_s, 1), "device": jax.devices()[0].device_kind, **note}
    print(json.dumps(row), flush=True)
    os.makedirs(OUT, exist_ok=True)
    with open(os.path.join(OUT, "held_forms.jsonl"), "a", encoding="utf-8") as f:
        f.write(json.dumps(row) + "\n")
    return out


def same(name, got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    if not np.array_equal(got, want):
        sys.exit(f"{name}: not the masked gathers' bits ({np.abs(got - want).max()} apart)")


def gathers(y, rows_of, here):
    take = lambda part, j: jnp.where(  # noqa: E731
        here[:, j, None], part[rows_of[:, j]].astype(jnp.float32), 0.0
    )
    routed = [sum(take(part, j) for j in range(K)) for part in y]
    return jnp.concatenate(routed, axis=1).astype(DT)


def main():
    rng = np.random.default_rng(40)
    chosen = jnp.asarray(
        np.argsort(rng.random((T, ROUTER)), axis=1)[:, :K].astype(np.int32)
    )
    weight = jnp.asarray(rng.random((T, K)), jnp.float32)
    tile = gm.tile_for(T * K, ROUTER)
    tables, here = gm.route_layout_held(chosen.reshape(-1), weight.reshape(-1), HELD, tile)
    _, row_of_pair, tile_expert, used, counts, row_weight = tables
    whole = tables[0].shape[0]
    usual = min(whole, decoder_parts.usual_rows(T * K, ROUTER, HELD, tile))
    held_pairs = int(np.asarray(counts)[:HELD].sum())
    assert int(used[0]) * tile <= usual, "the drawn routing passes the usual load"
    key = jax.random.PRNGKey(40)
    draw = lambda i, *shape, std=1.0: (  # noqa: E731
        jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32) * std
    ).astype(DT)
    w_down = draw(1, HELD, WIDTH, HIDDEN, std=0.05)
    wide = (-(-HIDDEN // 2048)) * 2048 if not TINY else 4096  # the next unpadded slab
    w_wide = jnp.pad(w_down, ((0, 0), (0, 0), (0, wide - HIDDEN)))
    walked = jnp.where(here, row_of_pair, -1)
    here2 = here.reshape(T, K)
    masked = jnp.where(here2, row_of_pair.reshape(T, K), 0)
    note = dict(held_pairs=held_pairs, pairs=T * K)

    for label, rows in () if "--layer" in sys.argv else (("usual", usual), ("whole", whole)):
        x = draw(2, rows, WIDTH)
        product = lambda x, w, **kw: gm.grouped_expert_product(  # noqa: E731
            x, w, tile_expert[: rows // tile], used, row_weight=row_weight[:rows], tile=tile, **kw
        )
        chunks = gm.column_chunks(rows, HIDDEN, 2)
        note.update(rows=rows, column_chunks=chunks)
        # (ii) the masked gathers, their two halves and the pair
        y_chunks = timed(f"{label}:(ii) down, {chunks} column chunks", lambda x, w: product(
            x, w, out_chunks=chunks), x, w_down, **note)
        want = timed(f"{label}:(ii) masked gathers alone", gathers, y_chunks, masked, here2, **note)
        timed(f"{label}:(ii) down + masked gathers", lambda x, w: gathers(
            product(x, w, out_chunks=chunks), masked, here2), x, w_down, **note)
        del y_chunks
        # (i) the padded slab
        y = timed(f"{label}:(i) down, padded slab", lambda x, w: product(x, w, slabs=True),
                  x, w_down, slab=gm.row_slabs(HIDDEN, DT), **note)
        got = timed(f"{label}:(i) held_rows_sum alone", lambda y: gm.held_rows_sum(
            y, walked, k=K, width=HIDDEN), y, slab=gm.row_slabs(HIDDEN, DT), **note)
        same("(i)", got, want)
        timed(f"{label}:(i) held_rows_sum alone, no pair held", lambda y: gm.held_rows_sum(
            y, jnp.full_like(walked, -1), k=K, width=HIDDEN), y, **note)
        del y
        got = timed(f"{label}:(i) down + held_rows_sum", lambda x, w: gm.held_rows_sum(
            product(x, w, slabs=True), walked, k=K, width=HIDDEN), x, w_down, **note)
        same("(i) pair", got, want)
        # (iii) the down product a whole slab wide
        got = timed(f"{label}:(iii) down {wide} wide + held_rows_sum + cut", lambda x, w: (
            gm.held_rows_sum(product(x, w, slabs=True), walked, k=K, width=wide)[:, :HIDDEN]
        ), x, w_wide, slab=gm.row_slabs(wide, DT), **note)
        same("(iii)", got, want)
        del x, got, want
    del w_wide

    # the whole layer, its lax.cond in it
    h = draw(3, T, HIDDEN)
    p = {"w_gate": draw(4, HELD, HIDDEN, WIDTH, std=0.02),
         "w_up": draw(5, HELD, HIDDEN, WIDTH, std=0.02), "w_down": w_down}
    layer = lambda h, p: decoder_parts.experts_grouped(h, chosen, weight, p, ROUTER, held=HELD)[0]  # noqa: E731
    got = timed("layer: experts_grouped, the walk", layer, h, p, **note)
    answer = gm.row_slabs
    gm.row_slabs = lambda width, dtype: (0, 0)
    try:
        # a function of its own: jax keeps the walk's trace under ``layer``
        want = timed("layer: experts_grouped, the masked gathers", lambda h, p: layer(h, p),
                     h, p, **note)
    finally:
        gm.row_slabs = answer
    same("layer", got, want)
    print(json.dumps({"ok": True, "checked": "every form bit for bit the masked gathers'"}))


if __name__ == "__main__":
    if jax.default_backend() != "tpu" and not TINY:
        sys.exit("a time comes only from the chip: run through the chip tool")
    main()
