"""Seeded checkpoints in the public (HuggingFace) layout.

The benchmark makes the model's weights itself, from ``--seed``, and hands
them to the program the way a user hands it a published checkpoint: a
``model.safetensors`` with the HuggingFace tensor names plus the tokenizer
file beside it (``bench/tokenizers/``).  The plain references read the same
file by the same public names, so neither side takes anything the other has
made.  Which tensors a checkpoint holds is its family's business:
``bench/families/<family>.py`` gives ``tensors(cfg)``.

Values are drawn per tensor (one generator per tensor, keyed by the seed
and the tensor's position in the family's list), so the file does not depend
on how many threads wrote it, nor on how it is cut into shards.  Every value
is a bfloat16 number: the checkpoint IS bf16, as served, and the float32
reference upcasts the very same numbers.

A checkpoint over ``SHARD_BYTES`` is written as HuggingFace writes one that
large: ``model-0000i-of-0000n.safetensors`` and
``model.safetensors.index.json``, one shard in memory at a time.  Whatever
the layout, ``read_checkpoint`` gives a mapping that opens a tensor when it
is asked for, so a reference can run layer by layer over a checkpoint that
would not fit the host twice.
"""

from __future__ import annotations

import json
import math
import os
from collections.abc import Mapping
from concurrent.futures import ThreadPoolExecutor

import ml_dtypes
import numpy as np

import byname

BF16 = ml_dtypes.bfloat16
INIT_STD = 0.02  # BERT's and DeBERTa's published initializer_range
SHARD_BYTES = 5_000_000_000  # HuggingFace's default max_shard_size, "5GB"
SINGLE = "model.safetensors"
INDEX = "model.safetensors.index.json"


def specs_of(family: str, cfg: dict) -> tuple:
    """The family's tensor list and its initializer's standard deviation."""
    mod = byname.module("families", family)
    return mod.tensors(cfg), float(getattr(mod, "INIT_STD", INIT_STD))


def _draw(seed: int, index: int, shape: tuple, kind: str, std: float) -> np.ndarray:
    rng = np.random.default_rng(np.random.SeedSequence([seed, 7001, index]))
    x = rng.standard_normal(shape, dtype=np.float32)
    x *= np.float32(std)
    if kind == "ln_scale":
        x += np.float32(1.0)
    return x.astype(BF16)


def _draw_all(seed: int, indexed: list, std: float, threads: int = 0) -> dict:
    """``indexed`` is [(position in the family's list, (name, shape, kind))]."""
    threads = threads or min(16, os.cpu_count() or 1)
    with ThreadPoolExecutor(max_workers=threads) as pool:
        arrays = list(
            pool.map(lambda item: _draw(seed, item[0], item[1][1], item[1][2], std), indexed)
        )
    return {spec[0]: arr for (_, spec), arr in zip(indexed, arrays)}


def make_state(family: str, cfg: dict, seed: int, threads: int = 0) -> dict:
    """name -> bfloat16 array, the whole checkpoint, from the seed."""
    specs, std = specs_of(family, cfg)
    return _draw_all(seed, list(enumerate(specs)), std, threads)


def plan_shards(specs: list, shard_bytes: int) -> list:
    """The family's list cut, in its order, into runs of at most
    ``shard_bytes`` (a tensor larger than that is a shard of its own)."""
    shards, size = [[]], 0
    for index, spec in enumerate(specs):
        nbytes = 2 * math.prod(spec[1])
        if shards[-1] and size + nbytes > shard_bytes:
            shards.append([])
            size = 0
        shards[-1].append((index, spec))
        size += nbytes
    return shards


def write_checkpoint(
    directory: str, family: str, cfg: dict, seed: int, shard_bytes: int = SHARD_BYTES
) -> str:
    """The checkpoint's files under ``directory``; returns the path of the
    file that names them all (the one file, or the index of the shards)."""
    from safetensors.numpy import save_file

    os.makedirs(directory, exist_ok=True)
    for stale in os.listdir(directory):  # a work directory is used again
        if stale.endswith(".safetensors") or stale == INDEX:
            os.remove(os.path.join(directory, stale))
    specs, std = specs_of(family, cfg)
    shards = plan_shards(specs, shard_bytes)
    if len(shards) == 1:
        path = os.path.join(directory, SINGLE)
        save_file(_draw_all(seed, shards[0], std), path)
        return path
    weight_map, total = {}, 0
    for i, shard in enumerate(shards, start=1):
        name = f"model-{i:05d}-of-{len(shards):05d}.safetensors"
        state = _draw_all(seed, shard, std)
        save_file(state, os.path.join(directory, name))
        for tensor, array in state.items():
            weight_map[tensor] = name
            total += array.nbytes
        del state
    path = os.path.join(directory, INDEX)
    with open(path, "w", encoding="utf-8") as f:
        json.dump({"metadata": {"total_size": total}, "weight_map": weight_map}, f, indent=2)
    return path


class Checkpoint(Mapping):
    """name -> array over a checkpoint's files, one or sharded: a tensor is
    read from its file when it is asked for, and nothing is kept."""

    def __init__(self, directory: str):
        index = os.path.join(directory, INDEX)
        if os.path.exists(index):
            with open(index, encoding="utf-8") as f:
                files = json.load(f)["weight_map"]
        else:
            from safetensors import safe_open

            with safe_open(os.path.join(directory, SINGLE), framework="np") as f:
                files = {name: SINGLE for name in f.keys()}
        self._directory = directory
        self._files = files

    def __getitem__(self, name: str) -> np.ndarray:
        from safetensors import safe_open

        path = os.path.join(self._directory, self._files[name])
        with safe_open(path, framework="np") as f:
            return f.get_tensor(name)

    def __iter__(self):
        return iter(self._files)

    def __len__(self) -> int:
        return len(self._files)


def read_checkpoint(directory: str) -> Checkpoint:
    return Checkpoint(directory)
