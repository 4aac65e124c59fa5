"""Tokenizer kind ``sentencepiece-ballot``: an ``spm.model`` of whole-word
pieces with a ballot's markup among them, so that through the program's real
unigram path one word is one token and a prefix-tree key is its letters and
its backticks.

The published tokenizer of the judge's model is byte-level BPE, which the
program does not have; what the cell needs of a tokenizer is that path, a
vocabulary of the published size, and a token a key letter.  Pieces, in id
order: [PAD] 0, [CLS] 1 (the sequence's first token), [SEP] 2, [UNK] 3; the
twenty key letters ``A``..``T`` as CONTINUATION pieces (no ``▁``: the token a
letter is inside a key and right after an opening backtick), 4..23; the
markup ``▁` `` (a key's opening backtick at a word's start) 24, `` `` ``
(between two letters) 25, `` `: `` (a key's end in the ballot) 26, `` ` ``
27, ``:`` 28; the instruction's words ``▁Select`` ``▁the`` ``▁response:``
29..31; then one piece ``▁w<k>`` per word, id 32 + k.  All of one score, so
the segmentation with the fewest pieces wins: a key ```C``B`:`` is five
tokens, a word one.
"""

import struct

FILE = "spm.model"
SPECIALS = ["[PAD]", "[CLS]", "[SEP]", "[UNK]"]
LETTERS = list("ABCDEFGHIJKLMNOPQRST")
MARKUP = ["▁`", "``", "`:", "`", ":", "▁Select", "▁the", "▁response:"]
FIRST_WORD = len(SPECIALS) + len(LETTERS) + len(MARKUP)  # 32


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
    piece, score, type), as ``tokenizers/sentencepiece.py`` writes one."""
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
    for text in LETTERS + MARKUP:
        piece(text, normal)
    for k in range(vocab_size - FIRST_WORD):
        piece(f"▁w{k}", normal)
    with open(path, "wb") as f:
        f.write(b"".join(chunks))
