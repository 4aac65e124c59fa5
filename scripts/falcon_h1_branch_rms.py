#!/usr/bin/env python3
"""How large each branch of a Falcon-H1 block is beside the stream it is added
to, under seeded weights of a given standard deviation: the reading behind the
benchmark family's ``INIT_STD`` (``bench/families/falcon_h1.py``, ISSUE 49).

    JAX_PLATFORMS=cpu python scripts/falcon_h1_branch_rms.py [--std 0.1] [--seed 1] [--positions 256]

The benchmark's float32 reference (``bench/references/falcon_h1_judge.py``) at
the PUBLISHED widths over a few hundred positions of random tokens, a layer at
a time (1.72 GB of float32 a layer; the embedding's rows are drawn for the
tokens alone), on the CPU.  A branch alone is the layer's first half with the
other branch's output multiplier set to 0.  One JSON line a layer: the root
mean square of the stream entering it, of each branch's output, and their
ratios.  With the published multipliers a branch under a few per cent of the
stream would leave the cell's check blind to that branch (and to the int8
control): the family states the deviation at which each ratio lies between a
quarter and four.
"""

import argparse
import importlib.util
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "bench"))


def bench_file(directory, name):
    path = os.path.join(ROOT, "bench", directory, name + ".py")
    spec = importlib.util.spec_from_file_location(f"branch_{directory}_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--std", type=float, default=None)
    parser.add_argument("--seed", type=int, default=1)
    parser.add_argument("--positions", type=int, default=256)
    args = parser.parse_args()
    import jax.numpy as jnp

    family, reference = bench_file("families", "falcon_h1"), bench_file("references", "falcon_h1_judge")
    std = family.INIT_STD if args.std is None else args.std
    with open(os.path.join(ROOT, "bench", "configs", "falcon-h1-34b-instruct.json"), encoding="utf-8") as f:
        cfg = json.load(f)
    rng = np.random.default_rng(args.seed)

    def draw(shape, kind):
        x = rng.standard_normal(shape, dtype=np.float32) * np.float32(std)
        return x + np.float32(1.0) if kind == "ln_scale" else x

    rms = lambda x: float(jnp.sqrt(jnp.mean(jnp.square(x))))  # noqa: E731
    variants = {
        "both": cfg,
        "ssm": {**cfg, "attention_out_multiplier": 0.0},
        "attention": {**cfg, "ssm_out_multiplier": 0.0},
    }
    programs = {name: reference.functions(c)[0] for name, c in variants.items()}
    specs = {name: (shape, kind) for name, shape, kind in family.tensors({**cfg, "num_hidden_layers": 1})}
    x = jnp.asarray(draw((args.positions, cfg["hidden_size"]), "normal") * cfg["embedding_multiplier"])
    for layer in range(cfg["num_hidden_layers"]):
        state = {name: draw(*spec) for name, spec in specs.items() if ".layers.0." in name}
        first, second = reference.layer_weights(state, 0)
        del state
        after = programs["both"]["mixers"](x, first)
        line = {
            "layer": layer, "std": std, "stream": rms(x),
            "ssm": rms(programs["ssm"]["mixers"](x, first) - x),
            "attention": rms(programs["attention"]["mixers"](x, first) - x),
        }
        out = programs["both"]["mlp"](after, second)
        line["stream_before_mlp"], line["mlp"] = rms(after), rms(out - after)
        for branch, base in (("ssm", "stream"), ("attention", "stream"), ("mlp", "stream_before_mlp")):
            line[branch + "_over_stream"] = line[branch] / line[base]
        print(json.dumps(line), flush=True)
        x = out
    return 0


if __name__ == "__main__":
    sys.exit(main())
