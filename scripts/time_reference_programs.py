#!/usr/bin/env python3
"""The sixth judge's plain reference ALONE on the chip, a program at a time (PR 45).

    chiprun -- python3 scripts/time_reference_programs.py [--tiny]

``bench/references/phi4flash_judge.py`` at the cell's own size (7,680
positions, the published widths, float32 at ``highest``) over weights drawn
here: how long its five programs take to compile side by side in threads, as
the reference compiles them, and how long a layer of each kind runs (host
clock around ``block_until_ready``; one JSON line a reading on stdout and all
of them in ``chiprun_out/reference_programs.json``).  A call of the cell is
9 x (mamba + mlp) + 9 x (self + mlp) + 7 x (memory + mlp) + 7 x (cross + mlp),
and a run's check 18 calls, the weights' 8 s and the compile: the driver stops
a run at 360 s, set-up, window and check together, so a reference is held
against what is left (``PERF.md`` section 7, PR 45: sixteen attention layers
written with the positions before the heads took 0.86 s each for 0.06).
``--tiny`` rehearses on the CPU at the dry run's sizes.
"""
import importlib.util
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(HERE, "bench"))
import jax
import jax.numpy as jnp
import numpy as np


def beside(*parts):
    path = os.path.join(HERE, "bench", *parts)
    spec = importlib.util.spec_from_file_location("_".join(parts)[:-3], path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def main():
    tiny = "--tiny" in sys.argv[1:]
    ref = beside("references", "phi4flash_judge.py")
    family = beside("families", "phi4flash.py")
    with open(os.path.join(HERE, "bench", "configs", "phi-4-mini-flash-reasoning.json")) as f:
        cfg = json.load(f)
    if tiny:
        cfg.update({k: v for k, v in cfg["dry_run"]["sizes"].items() if k != "max_tokens"})
    width = 512 if tiny else 7680
    shapes = {name: (shape, kind) for name, shape, kind in family.tensors(cfg)}
    rng = np.random.default_rng(0)

    class Drawn(dict):  # a tensor is drawn when it is asked for, as a checkpoint reads one
        def __getitem__(self, name):
            shape, kind = shapes[name]
            x = rng.standard_normal(shape, dtype=np.float32) * 0.02
            return (x + (kind == "ln_scale")).astype(jnp.bfloat16)

    state, out = Drawn(), {}

    def say(key, value):
        out[key] = value
        print(json.dumps({key: value}), flush=True)

    t0 = time.time()
    compiled = ref._compiled(cfg, state, width)
    say("compiles_submitted_s", round(time.time() - t0, 1))  # five layers drawn for their shapes
    done = {}
    for name, future in compiled.items():
        future.result()
        done[name] = round(time.time() - t0, 1)
    say("compiled_at_s", done)

    top = ref.kv_layer(cfg)
    weights = {ref.kind_of(cfg, i): ref.layer_weights(state, cfg, i) for i in (0, 1, top, top + 1, top + 2)}
    x = jax.random.normal(jax.random.PRNGKey(0), (width, cfg["hidden_size"]), jnp.float32)
    init = jnp.float32(0.5)

    def run(label, name, *args):
        program, seconds = compiled[name].result(), []
        for _ in range(2):
            t0 = time.time()
            result = jax.block_until_ready(program(*args))
            seconds.append(round(time.time() - t0, 3))
        say(label + "_s", seconds)
        return result

    _, memory = run("mamba", "mamba", x, weights["mamba"][0])
    run("sliding", "self", x, weights["sliding"][0], init, jnp.int32(cfg["sliding_window"]))
    _, kv = run("full", "self", x, weights["full"][0], init, jnp.int32(width))
    run("memory", "memory", x, weights["memory"][0], memory)
    run("cross", "cross", x, weights["cross"][0], init, jnp.int32(width), kv)
    run("mlp", "mlp", x, weights["mamba"][1])
    os.makedirs(os.path.join(HERE, "chiprun_out"), exist_ok=True)
    with open(os.path.join(HERE, "chiprun_out", "reference_programs.json"), "w") as f:
        json.dump(out, f)


if __name__ == "__main__":
    main()
