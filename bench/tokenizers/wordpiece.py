"""Tokenizer kind ``wordpiece``: a ``vocab.txt`` of whole words, so that one
word is one token through the program's real WordPiece path."""

FILE = "vocab.txt"
SPECIALS = ["[PAD]", "[UNK]", "[CLS]", "[SEP]"]


def write(path: str, vocab_size: int) -> None:
    """Four specials, then the words ``w0`` ... — the real WordPiece path (not
    the program's hash fallback) maps each word to one id: word k is id
    4 + k, [CLS] 2, [SEP] 3, [PAD] 0."""
    words = (f"w{i}" for i in range(vocab_size - len(SPECIALS)))
    with open(path, "w", encoding="utf-8") as f:
        f.write("\n".join([*SPECIALS, *words]) + "\n")
