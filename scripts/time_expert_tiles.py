#!/usr/bin/env python3
"""What the row tiles that hold no pair cost the held experts' kernels, each
kernel ALONE on the chip (PR 43).

    chiprun -- python3 scripts/time_expert_tiles.py [--tree DIR] [--tiny]

(host clock around ``block_until_ready``, min and median of 7, bf16; one JSON
line a form on stdout and in ``chiprun_out/expert_tiles.jsonl``).  ``--tree``
imports the package from another checkout (the parent's, unpacked beside this
one), so that one call times both sides on one chip, a process a side;
``--tiny`` rehearses on the CPU and times nothing.

A sparse layer's two kernels at a share's shapes, the layout as
``decoder_parts.usual_rows`` sizes it and ``tiles_used`` what the seeded
router fills of it:

- the fifth judge's: 98,304 rows = 384 row tiles, 112 in use, 32 experts held,
  hidden 3072, experts 3072 wide (gate-up in column blocks of 768, the down
  product whole, a row a slab of 16 sublanes);
- the third judge's: 49,152 rows = 192 row tiles, 60 in use, 16 held, hidden
  6144, experts 2048 wide (column blocks of 512; a slab of 24).

Each kernel three ways: over the layout as laid (``laid``), over the layout
CUT to its tiles in use (``cut``: what the kernel costs where no step is
empty), and over the layout as laid with NO tile in use (``empty``: the steps
alone, and the weight blocks, which a step fetches whether or not it
multiplies: 1.2 GB at the fifth judge's shapes).  ``laid`` less ``cut`` is
what the empty steps cost.
"""
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ARGS = sys.argv[1:]
TREE = os.path.abspath(ARGS[ARGS.index("--tree") + 1]) if "--tree" in ARGS else HERE
TINY = "--tiny" in ARGS
sys.path.insert(0, TREE)
import jax
import jax.numpy as jnp

from llm_weighted_consensus_tpu.ops import grouped_matmul as gm

DT = jnp.bfloat16
OUT = os.path.join(HERE, "chiprun_out")


def emit(name, **numbers):
    row = {"form": name, "tree": os.path.relpath(TREE, HERE),
           "device": jax.devices()[0].device_kind, **numbers}
    print(json.dumps(row), flush=True)
    os.makedirs(OUT, exist_ok=True)
    with open(os.path.join(OUT, "expert_tiles.jsonl"), "a", encoding="utf-8") as f:
        f.write(json.dumps(row) + "\n")


def timed(name, fn, *args, repeat=7, **note):
    f = jax.jit(fn)
    t0 = time.perf_counter()
    jax.block_until_ready(f(*args))
    compile_s = time.perf_counter() - t0
    if TINY:
        return
    jax.block_until_ready(f(*args))
    times = []
    for _ in range(repeat):
        t0 = time.perf_counter()
        jax.block_until_ready(f(*args))
        times.append((time.perf_counter() - t0) * 1e3)
    emit(name, ms_min=min(times), ms_median=sorted(times)[len(times) // 2],
         compile_s=round(compile_s, 1), **note)


def share(judge, *, tiles, in_use, held, hidden, width, tile=gm.TILE):
    rows = tiles * tile
    rand = lambda i, *shape: (  # noqa: E731
        jax.random.normal(jax.random.PRNGKey(i), shape, jnp.float32) * 0.05
    ).astype(DT)
    h, mid = rand(1, rows, hidden), rand(2, rows, width)
    w_gate, w_up, w_down = rand(3, held, hidden, width), rand(4, held, hidden, width), rand(
        5, held, width, hidden)
    weight = jnp.abs(rand(6, rows).astype(jnp.float32))
    # the tiles in use spread evenly over the held experts, in order; the
    # rest name the last expert held, as ``route_layout_held`` leaves them
    tile_expert = jnp.minimum(jnp.arange(tiles, dtype=jnp.int32) * held // in_use, held - 1)

    # the weights go in as arguments: closed over, they are constants of the
    # program, 2.8 GB of it and 100 s of compile a form (PR 43's first call)
    def gate_up(x, te, used, w_gate, w_up):
        return gm.grouped_expert_product(x, w_gate, te, used, w_up=w_up, tile=tile)

    def down(x, weight, te, used, w_down):
        return gm.grouped_expert_product(
            x, w_down, te, used, row_weight=weight, tile=tile, slabs=True)

    note = dict(tiles=tiles, in_use=in_use, held=held, hidden=hidden, width=width)
    used, none = jnp.asarray([in_use], jnp.int32), jnp.zeros((1,), jnp.int32)
    cut = in_use * tile
    up = (w_gate, w_up)
    timed(f"{judge}:gate_up:laid", gate_up, h, tile_expert, used, *up, **note)
    timed(f"{judge}:gate_up:cut", gate_up, h[:cut], tile_expert[:in_use], used, *up, **note)
    timed(f"{judge}:gate_up:empty", gate_up, h, tile_expert, none, *up, **note)
    timed(f"{judge}:down:laid", down, mid, weight, tile_expert, used, w_down, **note)
    timed(
        f"{judge}:down:cut", down, mid[:cut], weight[:cut], tile_expert[:in_use], used, w_down,
        **note,
    )
    timed(f"{judge}:down:empty", down, mid, weight, tile_expert, none, w_down, **note)


if __name__ == "__main__":
    if TINY:
        share("tiny", tiles=12, in_use=4, held=3, hidden=2048, width=256, tile=16)
        sys.exit(0)
    if jax.default_backend() != "tpu":
        sys.exit("a time comes only from the chip: run through the chip tool")
    share("trinity", tiles=384, in_use=112, held=32, hidden=3072, width=3072)
    share("glm-5.2", tiles=192, in_use=60, held=16, hidden=6144, width=2048)
