"""Offline encoder weight loading (no network — local files only).

One entry point, three accepted layouts:

* an HF snapshot directory (``model.safetensors`` or ``pytorch_model.bin``
  + usually ``vocab.txt``) — the layout ``huggingface_hub`` snapshots use
  and the one tests/test_hf_parity.py documents for golden checks;
* a single weights file (``.safetensors`` / ``.bin`` / ``.pt``);
* an orbax checkpoint directory written by ``train.save_checkpoint``.

HF state dicts may carry a ``bert.`` prefix (BertForSequenceClassification
etc.); it is stripped so plain ``BertModel`` and task-head checkpoints both
load.  Reference note: the reference delegates inference upstream
(src/chat/completions/client.rs:308-332) and ships no weight loading at
all — local weights are this framework's whole point.
"""

from __future__ import annotations

import os
from typing import Optional

import numpy as np

from .configs import BertConfig

_HF_FILES = ("model.safetensors", "pytorch_model.bin")


def _strip_prefix(state: dict) -> dict:
    if any(key.startswith("bert.") for key in state):
        return {
            (key[len("bert."):] if key.startswith("bert.") else key): value
            for key, value in state.items()
        }
    return state


def _load_state_dict(path: str) -> dict:
    """weights file -> {name: np.ndarray}."""
    if path.endswith(".safetensors"):
        from safetensors.numpy import load_file

        return dict(load_file(path))
    import torch

    state = torch.load(path, map_location="cpu", weights_only=True)
    return {k: v.numpy() for k, v in state.items()}


_HF_INDEX = "model.safetensors.index.json"


class _LazyCheckpoint:
    """name -> array over a safetensors checkpoint, one file or HF's
    sharded layout (``model-0000i-of-0000n.safetensors`` named by
    ``model.safetensors.index.json``): a tensor is read when it is asked
    for and nothing is kept, so a loader can go layer by layer over a
    checkpoint larger than it wants on the host."""

    def __init__(self, directory: str, files: dict) -> None:
        self._directory = directory
        self._files = files

    def __contains__(self, name) -> bool:
        return name in self._files

    def __iter__(self):
        return iter(self._files)

    def __len__(self) -> int:
        return len(self._files)

    def keys(self):
        return self._files.keys()

    def __getitem__(self, name: str) -> np.ndarray:
        from safetensors import safe_open

        path = os.path.join(self._directory, self._files[name])
        with safe_open(path, framework="np") as f:
            return f.get_tensor(name)


def open_checkpoint(path: str) -> _LazyCheckpoint:
    """A lazy name -> array mapping over ``path``: an HF snapshot directory
    (sharded or one ``model.safetensors``), the shards' index file, or one
    ``.safetensors`` file."""
    import json

    from safetensors import safe_open

    if os.path.isdir(path):
        index = os.path.join(path, _HF_INDEX)
        path = index if os.path.exists(index) else os.path.join(path, _HF_FILES[0])
    if not os.path.exists(path):
        raise FileNotFoundError(path)
    directory = os.path.dirname(os.path.abspath(path))
    if path.endswith(".json"):
        with open(path, encoding="utf-8") as f:
            return _LazyCheckpoint(directory, json.load(f)["weight_map"])
    with safe_open(path, framework="np") as f:
        single = os.path.basename(path)
        return _LazyCheckpoint(directory, {name: single for name in f.keys()})


def _is_orbax_dir(path: str) -> bool:
    if not os.path.isdir(path):
        return False
    entries = set(os.listdir(path))
    return bool(
        entries
        & {"_METADATA", "manifest.ocdbt", "_CHECKPOINT_METADATA", "d"}
    )


def load_params(path: str, config: BertConfig, dtype=None) -> dict:
    """Encoder params pytree from a local checkpoint (see module doc)."""
    import jax
    import jax.numpy as jnp

    from . import bert

    if dtype is None:
        dtype = (
            jnp.bfloat16 if jax.default_backend() == "tpu" else jnp.float32
        )
    if os.path.isdir(path):
        for name in _HF_FILES:
            candidate = os.path.join(path, name)
            if os.path.exists(candidate):
                state = _strip_prefix(_load_state_dict(candidate))
                return bert.from_hf_weights(state, config, dtype=dtype)
        if _is_orbax_dir(path):
            from .. import train

            like = bert.init_params(
                jax.random.PRNGKey(0), config, dtype=dtype
            )
            return train.load_checkpoint(path, like=like)
        raise FileNotFoundError(
            f"{path!r} is a directory but contains neither an HF weights "
            f"file ({'/'.join(_HF_FILES)}) nor an orbax checkpoint"
        )
    if not os.path.exists(path):
        raise FileNotFoundError(path)
    state = _strip_prefix(_load_state_dict(path))
    return bert.from_hf_weights(state, config, dtype=dtype)


def find_vocab(weights_path: str) -> Optional[str]:
    """Tokenizer asset sitting next to the weights, if any (HF snapshot
    layout): WordPiece ``vocab.txt`` or a SentencePiece model proto
    (XLM-R/bge-m3 ship ``sentencepiece.bpe.model``, DeBERTa ``spm.model``)."""
    from .spm import SPM_FILES

    root = (
        weights_path
        if os.path.isdir(weights_path)
        else os.path.dirname(weights_path)
    )
    for name in ("vocab.txt",) + SPM_FILES:
        candidate = os.path.join(root, name)
        if os.path.exists(candidate):
            return candidate
    return None
