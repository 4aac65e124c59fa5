"""Tokenizer kind ``sentencepiece``: an ``spm.model`` of whole-word pieces,
so that one word is one token through the program's real unigram path."""

import struct

FILE = "spm.model"
SPECIALS = ["[PAD]", "[CLS]", "[SEP]", "[UNK]"]  # the DeBERTa-v2/v3 convention


def _varint(value: int) -> bytes:
    out = bytearray()
    while True:
        byte = value & 0x7F
        value >>= 7
        out.append(byte | (0x80 if value else 0))
        if not value:
            return bytes(out)


def write(path: str, vocab_size: int) -> None:
    """A SentencePiece ``ModelProto`` holding only its pieces (field 1:
    piece, score, type): [PAD] 0, [CLS] 1, [SEP] 2 control pieces, [UNK] 3,
    then one whole-word piece ``▁w<k>`` per word, id 4 + k, all of one
    score.  A whole word is the only segmentation its characters have, so
    one word is one token through the real unigram path."""
    normal, unknown, control = 1, 2, 3
    score = struct.pack("<f", -10.0)
    chunks = []

    def piece(text: str, kind: int) -> None:
        raw = text.encode("utf-8")
        inner = b"\x0a" + _varint(len(raw)) + raw + b"\x15" + score
        inner += b"\x18" + _varint(kind)
        chunks.append(b"\x0a" + _varint(len(inner)) + inner)

    for name in SPECIALS:
        piece(name, unknown if name == "[UNK]" else control)
    for k in range(vocab_size - len(SPECIALS)):
        piece(f"▁w{k}", normal)
    with open(path, "wb") as f:
        f.write(b"".join(chunks))
