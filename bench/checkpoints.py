"""Seeded checkpoints and vocabularies in the public (HuggingFace) layout.

The benchmark makes the model's weights itself, from ``--seed``, and hands
them to the program the way a user hands it a published checkpoint: a
``model.safetensors`` with the HuggingFace tensor names plus the tokenizer
file beside it.  The plain references read the same file by the same public
names, so neither side takes anything the other has made.

Values are drawn per tensor (one generator per tensor, keyed by the seed
and the tensor's position in the list), so the file does not depend on how
many threads wrote it.  Every value is a bfloat16 number: the checkpoint IS
bf16, as served, and the float32 reference upcasts the very same numbers.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import ml_dtypes
import numpy as np

BF16 = ml_dtypes.bfloat16
SPECIALS_WORDPIECE = ["[PAD]", "[UNK]", "[CLS]", "[SEP]"]
INIT_STD = 0.02  # BERT's and DeBERTa's published initializer_range


def bert_tensors(cfg: dict) -> list:
    """(name, shape, kind) for a BertModel state dict; kind is ``normal``
    (weights, biases: N(0, 0.02)) or ``ln_scale`` (1 + N(0, 0.02))."""
    h, inter = cfg["hidden_size"], cfg["intermediate_size"]
    out = [
        ("embeddings.word_embeddings.weight", (cfg["vocab_size"], h), "normal"),
        (
            "embeddings.position_embeddings.weight",
            (cfg["max_position_embeddings"], h),
            "normal",
        ),
        (
            "embeddings.token_type_embeddings.weight",
            (cfg["type_vocab_size"], h),
            "normal",
        ),
        ("embeddings.LayerNorm.weight", (h,), "ln_scale"),
        ("embeddings.LayerNorm.bias", (h,), "normal"),
    ]
    for i in range(cfg["num_hidden_layers"]):
        base = f"encoder.layer.{i}"
        for name, shape in (
            ("attention.self.query", (h, h)),
            ("attention.self.key", (h, h)),
            ("attention.self.value", (h, h)),
            ("attention.output.dense", (h, h)),
            ("intermediate.dense", (inter, h)),
            ("output.dense", (h, inter)),
        ):
            out.append((f"{base}.{name}.weight", shape, "normal"))
            out.append((f"{base}.{name}.bias", (shape[0],), "normal"))
        for name in ("attention.output.LayerNorm", "output.LayerNorm"):
            out.append((f"{base}.{name}.weight", (h,), "ln_scale"))
            out.append((f"{base}.{name}.bias", (h,), "normal"))
    return out


def deberta_tensors(cfg: dict) -> list:
    """A DebertaV2ForSequenceClassification state dict (v3 layout: shared
    position projections, relative embeddings with their LayerNorm, context
    pooler, one-logit classifier)."""
    h, inter = cfg["hidden_size"], cfg["intermediate_size"]
    rel = 2 * (cfg["position_buckets"] or cfg["max_relative_positions"])
    out = [
        ("deberta.embeddings.word_embeddings.weight", (cfg["vocab_size"], h), "normal"),
        ("deberta.embeddings.LayerNorm.weight", (h,), "ln_scale"),
        ("deberta.embeddings.LayerNorm.bias", (h,), "normal"),
        ("deberta.encoder.rel_embeddings.weight", (rel, h), "normal"),
        ("deberta.encoder.LayerNorm.weight", (h,), "ln_scale"),
        ("deberta.encoder.LayerNorm.bias", (h,), "normal"),
    ]
    for i in range(cfg["num_hidden_layers"]):
        base = f"deberta.encoder.layer.{i}"
        for name, shape in (
            ("attention.self.query_proj", (h, h)),
            ("attention.self.key_proj", (h, h)),
            ("attention.self.value_proj", (h, h)),
            ("attention.output.dense", (h, h)),
            ("intermediate.dense", (inter, h)),
            ("output.dense", (h, inter)),
        ):
            out.append((f"{base}.{name}.weight", shape, "normal"))
            out.append((f"{base}.{name}.bias", (shape[0],), "normal"))
        for name in ("attention.output.LayerNorm", "output.LayerNorm"):
            out.append((f"{base}.{name}.weight", (h,), "ln_scale"))
            out.append((f"{base}.{name}.bias", (h,), "normal"))
    out += [
        ("pooler.dense.weight", (h, h), "normal"),
        ("pooler.dense.bias", (h,), "normal"),
        ("classifier.weight", (1, h), "normal"),
        ("classifier.bias", (1,), "normal"),
    ]
    return out


FAMILIES = {"bert": bert_tensors, "deberta-v2": deberta_tensors}


def _draw(seed: int, index: int, shape: tuple, kind: str) -> np.ndarray:
    rng = np.random.default_rng(np.random.SeedSequence([seed, 7001, index]))
    x = rng.standard_normal(shape, dtype=np.float32)
    x *= np.float32(INIT_STD)
    if kind == "ln_scale":
        x += np.float32(1.0)
    return x.astype(BF16)


def make_state(family: str, cfg: dict, seed: int, threads: int = 0) -> dict:
    """name -> bfloat16 array, the whole checkpoint, from the seed."""
    specs = FAMILIES[family](cfg)
    threads = threads or min(16, os.cpu_count() or 1)
    with ThreadPoolExecutor(max_workers=threads) as pool:
        arrays = list(
            pool.map(
                lambda item: _draw(seed, item[0], item[1][1], item[1][2]),
                enumerate(specs),
            )
        )
    return {spec[0]: arr for spec, arr in zip(specs, arrays)}


def write_checkpoint(directory: str, family: str, cfg: dict, seed: int) -> str:
    from safetensors.numpy import save_file

    os.makedirs(directory, exist_ok=True)
    path = os.path.join(directory, "model.safetensors")
    save_file(make_state(family, cfg, seed), path)
    return path


def read_checkpoint(directory: str) -> dict:
    from safetensors.numpy import load_file

    return load_file(os.path.join(directory, "model.safetensors"))


# -- vocabularies: whole words, so one word is one token ---------------------


def words_for(vocab_size: int, specials: int) -> int:
    return vocab_size - specials


def write_wordpiece_vocab(path: str, vocab_size: int) -> None:
    """``vocab.txt``: four specials, then the words ``w0`` ... — the real
    WordPiece path (not the program's hash fallback) maps each word to one
    id: word k is id 4 + k, [CLS] 2, [SEP] 3, [PAD] 0."""
    words = (f"w{i}" for i in range(words_for(vocab_size, 4)))
    with open(path, "w", encoding="utf-8") as f:
        f.write("\n".join([*SPECIALS_WORDPIECE, *words]) + "\n")



SPECIALS_DEBERTA = ["[PAD]", "[CLS]", "[SEP]", "[UNK]"]


def _varint(value: int) -> bytes:
    out = bytearray()
    while True:
        byte = value & 0x7F
        value >>= 7
        out.append(byte | (0x80 if value else 0))
        if not value:
            return bytes(out)


def write_sentencepiece_model(path: str, vocab_size: int) -> None:
    """``spm.model``: a SentencePiece ``ModelProto`` holding only its pieces
    (field 1: piece, score, type), in the DeBERTa-v2/v3 convention: [PAD] 0,
    [CLS] 1, [SEP] 2 control pieces, [UNK] 3, then one whole-word piece
    ``▁w<k>`` per word, id 4 + k, all of one score.  A whole word is the only
    segmentation its characters have, so one word is one token through the
    real unigram path."""
    import struct

    normal, unknown, control = 1, 2, 3
    score = struct.pack("<f", -10.0)
    chunks = []

    def piece(text: str, kind: int) -> None:
        raw = text.encode("utf-8")
        inner = b"\x0a" + _varint(len(raw)) + raw + b"\x15" + score
        inner += b"\x18" + _varint(kind)
        chunks.append(b"\x0a" + _varint(len(inner)) + inner)

    for name in SPECIALS_DEBERTA:
        piece(name, unknown if name == "[UNK]" else control)
    for k in range(words_for(vocab_size, 4)):
        piece(f"▁w{k}", normal)
    with open(path, "wb") as f:
        f.write(b"".join(chunks))
