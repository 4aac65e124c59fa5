"""Parity corpus for the two SSE parsers (Python SSEParser + C++
NativeSSEParser via ctypes): identical events for every corpus entry under
every chunk split, CRLF, comments, non-data fields, and flush semantics.

The native library builds on demand (``make -C native``); tests skip if the
toolchain can't produce it.
"""

import pathlib

import pytest

from llm_weighted_consensus_tpu.clients import sse
from llm_weighted_consensus_tpu.errors import IngestCapError

CORPUS = [
    # (name, raw bytes, expected events, expected flush tail)
    (
        "single event",
        b"data: hello\n\n",
        ["hello"],
        None,
    ),
    (
        "two events",
        b"data: one\n\ndata: two\n\n",
        ["one", "two"],
        None,
    ),
    (
        "multi-line data joined by newline",
        b"data: line1\ndata: line2\n\n",
        ["line1\nline2"],
        None,
    ),
    (
        "crlf endings",
        b"data: a\r\n\r\ndata: b\r\n\r\n",
        ["a", "b"],
        None,
    ),
    (
        "comments ignored",
        b": keep-alive\ndata: x\n: another\n\n",
        ["x"],
        None,
    ),
    (
        "other fields ignored",
        b"event: message\nid: 7\nretry: 100\ndata: y\n\n",
        ["y"],
        None,
    ),
    (
        "no space after colon",
        b"data:tight\n\n",
        ["tight"],
        None,
    ),
    (
        "only first space stripped",
        b"data:  two spaces\n\n",
        [" two spaces"],
        None,
    ),
    (
        "bare data line (no colon)",
        b"data\n\n",
        [""],
        None,
    ),
    (
        "empty data value",
        b"data:\n\n",
        [""],
        None,
    ),
    (
        "blank line without data is not an event",
        b"\n\n: c\n\ndata: z\n\n",
        ["z"],
        None,
    ),
    (
        "trailing unterminated event -> flush",
        b"data: done-frame",
        [],
        "done-frame",
    ),
    (
        "unterminated multi-line -> flush",
        b"data: p\ndata: q",
        [],
        "p\nq",
    ),
    (
        "done terminator frame",
        b'data: {"k": 1}\n\ndata: [DONE]\n\n',
        ['{"k": 1}', "[DONE]"],
        None,
    ),
    (
        "unicode",
        "data: voilà ✓\n\n".encode("utf-8"),
        ["voilà ✓"],
        None,
    ),
    (
        "stream cut between CR and LF of the blank line",
        b"data: x\n\r",
        [],
        "x",
    ),
    (
        "stream cut right after the data line's LF",
        b"data: y\n",
        [],
        "y",
    ),
]

SPLITS = [1, 2, 3, 7, 1 << 30]  # feed chunk sizes; last = one shot


def run_parser(parser, raw: bytes, split: int):
    events = []
    for i in range(0, len(raw), split):
        events.extend(parser.feed(raw[i : i + split]))
    tail = parser.flush()
    return events, tail


@pytest.fixture(scope="module")
def native_lib():
    lib = sse.load_native_library()
    if lib is None:
        pytest.skip("native SSE parser not buildable here")
    return lib


@pytest.mark.parametrize(
    "name,raw,expected,tail", CORPUS, ids=[c[0] for c in CORPUS]
)
@pytest.mark.parametrize("split", SPLITS)
def test_python_parser_corpus(name, raw, expected, tail, split):
    events, got_tail = run_parser(sse.SSEParser(), raw, split)
    assert events == expected
    assert got_tail == tail


@pytest.mark.parametrize(
    "name,raw,expected,tail", CORPUS, ids=[c[0] for c in CORPUS]
)
@pytest.mark.parametrize("split", SPLITS)
def test_native_parser_corpus(native_lib, name, raw, expected, tail, split):
    events, got_tail = run_parser(sse.NativeSSEParser(native_lib), raw, split)
    assert events == expected
    assert got_tail == tail


def test_parsers_agree_on_random_streams(native_lib):
    import random

    rng = random.Random(7)
    fields = [
        b"data: payload %d\n",
        b"data:x%d\n",
        b"\n",
        b"\r\n",
        b": comment %d\n",
        b"event: e%d\n",
        b"data: multi\ndata: line %d\n",
    ]
    for trial in range(50):
        raw = b"".join(
            (f % i if b"%d" in f else f)
            for i, f in (
                (i, rng.choice(fields))
                for i in range(rng.randint(1, 30))
            )
        )
        split = rng.choice([1, 2, 5, 13, len(raw) or 1])
        py = run_parser(sse.SSEParser(), raw, split)
        nat = run_parser(sse.NativeSSEParser(native_lib), raw, split)
        assert py == nat, f"trial {trial}: {raw!r}"


def test_make_parser_prefers_native_and_falls_back(monkeypatch):
    lib = sse.load_native_library()
    p = sse.make_parser()
    if lib is not None:
        assert isinstance(p, sse.NativeSSEParser)
    else:
        assert isinstance(p, sse.SSEParser)
    # forced fallback
    monkeypatch.setattr(sse, "_native_lib", None)
    monkeypatch.setattr(sse, "_native_tried", True)
    assert isinstance(sse.make_parser(), sse.SSEParser)


def test_native_parser_is_on_the_chat_client_path(native_lib):
    """The chat client's decode loop constructs its parser via make_parser,
    so the native parser serves real streams when built."""
    import inspect

    from llm_weighted_consensus_tpu.clients import chat

    src = inspect.getsource(chat)
    assert "make_parser()" in src
    assert isinstance(sse.make_parser(), sse.NativeSSEParser)


# -- byte-budget cap parity (ISSUE 19 ingest plane) ---------------------------
#
# Trip semantics are part of the Python/native parity contract: same
# events before the trip, same trip kind at the same observed byte
# boundary, same dropped state, and both parsers stay usable after.
# Driven over the committed hostile corpus (tests/fixtures/ingest/).

INGEST_CORPUS = pathlib.Path(__file__).parent / "fixtures" / "ingest"

CAP_FILES = [
    "giant_line.sse",
    "newline_less_flood.bin",
    "binary_garbage.bin",
    "interleaved.sse",
]
CAP_SPLITS = [1, 7, 1 << 30]
CAP_CONFIGS = [(4096, 0), (0, 4096), (4096, 4096)]


def run_capped(parser, raw: bytes, split: int):
    """Feed chunked bytes through a capped parser; collect everything
    observable: events, flush tail, every trip (kind + observed bytes),
    the trip counter, and a usable-after-trip probe event."""
    events, trips = [], []
    for i in range(0, len(raw), split):
        try:
            for event in parser.feed(raw[i : i + split]):
                events.append(event)
        except IngestCapError as e:
            trips.append((e.what, e.observed_bytes))
    try:
        tail = parser.flush()
    except IngestCapError as e:
        trips.append((e.what, e.observed_bytes))
        tail = None
    probe = list(parser.feed(b"\n\ndata: after-trip\n\n"))
    return events, tail, trips, parser.cap_trips, probe


@pytest.mark.parametrize(
    "buf_cap,ev_cap", CAP_CONFIGS, ids=["buffer", "event", "both"]
)
@pytest.mark.parametrize("split", CAP_SPLITS)
@pytest.mark.parametrize("name", CAP_FILES)
def test_parsers_agree_on_cap_trips(
    native_lib, name, split, buf_cap, ev_cap
):
    raw = (INGEST_CORPUS / name).read_bytes()
    py = run_capped(
        sse.SSEParser(max_buffer_bytes=buf_cap, max_event_bytes=ev_cap),
        raw,
        split,
    )
    nat = run_capped(
        sse.NativeSSEParser(
            native_lib, max_buffer_bytes=buf_cap, max_event_bytes=ev_cap
        ),
        raw,
        split,
    )
    assert py == nat, f"{name} split={split} caps=({buf_cap},{ev_cap})"


def test_parsers_agree_on_capped_random_streams(native_lib):
    import random

    rng = random.Random(19)
    for trial in range(30):
        # random mix of healthy lines, giant lines and newline-less runs
        parts = []
        for _ in range(rng.randint(1, 12)):
            roll = rng.random()
            if roll < 0.5:
                parts.append(b"data: ok %d\n\n" % rng.randint(0, 99))
            elif roll < 0.75:
                parts.append(
                    b"data: " + b"A" * rng.randint(100, 700) + b"\n\n"
                )
            else:
                parts.append(b"B" * rng.randint(100, 700))
        raw = b"".join(parts)
        split = rng.choice([1, 3, 17, len(raw) or 1])
        caps = rng.choice(CAP_CONFIGS + [(256, 256)])
        py = run_capped(
            sse.SSEParser(
                max_buffer_bytes=caps[0], max_event_bytes=caps[1]
            ),
            raw,
            split,
        )
        nat = run_capped(
            sse.NativeSSEParser(
                native_lib,
                max_buffer_bytes=caps[0],
                max_event_bytes=caps[1],
            ),
            raw,
            split,
        )
        assert py == nat, f"trial {trial}: caps={caps} {raw!r}"


# -- native WordPiece (ASCII fast path) ---------------------------------------

WP_VOCAB = (
    ["[PAD]", "[UNK]", "[CLS]", "[SEP]", "[MASK]"]
    + ["the", "quick", "brown", "fox", "jump", "##s", "##ed", "over"]
    + ["lazy", "dog", "un", "##believ", "##able", ",", ".", "!", "?"]
    + list("abcdefghijklmnopqrstuvwxyz")
    + ["##" + c for c in "abcdefghijklmnopqrstuvwxyz"]
)

WP_TEXTS = [
    "The quick brown fox jumps over the lazy dog.",
    "unbelievable!",
    "Jumped, jumped?  JUMPED",
    "tabs\tand\nnewlines",
    "xq" * 60,  # > max word chars -> [UNK]
    "",
    "a " * 200,  # truncation
    "punct,,,!!chains..",
]


def _wp(use_native):
    from llm_weighted_consensus_tpu.models.tokenizer import WordPieceTokenizer

    # dedupe ("##s" appears in both the word list and the letter pieces)
    # so ids stay contiguous — the native bridge requires ids 0..n-1
    vocab = {
        token: i for i, token in enumerate(dict.fromkeys(WP_VOCAB))
    }
    return WordPieceTokenizer(vocab, use_native=use_native)


@pytest.fixture(scope="module")
def native_wp():
    wp = _wp(use_native=True)
    if wp._native is None:
        pytest.skip("native wordpiece not buildable here")
    return wp


def test_native_wordpiece_matches_python(native_wp):
    python = _wp(use_native=False)
    for max_len in (8, 16, 64):
        ids_n, mask_n = native_wp.encode_batch(WP_TEXTS, max_len)
        ids_p, mask_p = python.encode_batch(WP_TEXTS, max_len)
        assert ids_n.tolist() == ids_p.tolist(), max_len
        assert mask_n.tolist() == mask_p.tolist()


def test_native_wordpiece_random_ascii_parity(native_wp):
    import random
    import string

    python = _wp(use_native=False)
    rng = random.Random(3)
    chars = string.ascii_letters + string.punctuation + " \t"
    texts = [
        "".join(rng.choice(chars) for _ in range(rng.randint(0, 80)))
        for _ in range(200)
    ]
    ids_n, _ = native_wp.encode_batch(texts, 32)
    ids_p, _ = python.encode_batch(texts, 32)
    assert ids_n.tolist() == ids_p.tolist()


def test_non_ascii_falls_back_to_python_path(native_wp):
    python = _wp(use_native=False)
    texts = ["café naïve voilà", "Ünïcödé everywhere", "mixed ascii café"]
    ids_n, _ = native_wp.encode_batch(texts, 16)
    ids_p, _ = python.encode_batch(texts, 16)
    assert ids_n.tolist() == ids_p.tolist()


def test_ascii_control_chars_parity(native_wp):
    """\\x1c-\\x1f are whitespace to Python's str.isspace(): the native
    path must split on them too."""
    python = _wp(use_native=False)
    texts = ["a\x1cb", "the\x1dquick", "fox\x1e\x1fdog", "a\x0bb\x0cc"]
    ids_n, _ = native_wp.encode_batch(texts, 16)
    ids_p, _ = python.encode_batch(texts, 16)
    assert ids_n.tolist() == ids_p.tolist()


def test_native_wordpiece_thread_safety(native_wp):
    """wp_encode releases the GIL; concurrent encodes (the gateway's
    executor shape) must not corrupt each other's output."""
    import random
    from concurrent.futures import ThreadPoolExecutor

    python = _wp(use_native=False)
    rng = random.Random(9)
    words = ["the", "quick", "brown", "fox", "unbelievable", "dog!"]
    texts = [
        " ".join(rng.choice(words) for _ in range(rng.randint(1, 40)))
        for _ in range(200)
    ]
    lengths = [8 + (i % 5) * 24 for i in range(len(texts))]
    expected = [
        python._encode(t, n) for t, n in zip(texts, lengths)
    ]
    with ThreadPoolExecutor(8) as pool:
        got = list(
            pool.map(
                lambda tn: native_wp._encode(tn[0], tn[1]),
                zip(texts, lengths),
            )
        )
    assert got == expected


# -- native unigram / SentencePiece (ASCII fast path) -------------------------


def _spm(use_native, scheme="xlmr"):
    from test_spm import XLMR_PIECES

    from llm_weighted_consensus_tpu.models.spm import (
        CONTROL,
        NORMAL,
        UNKNOWN,
        UnigramTokenizer,
    )

    if scheme == "deberta":
        pieces = [
            ("[PAD]", 0.0, CONTROL),
            ("[CLS]", 0.0, CONTROL),
            ("[SEP]", 0.0, CONTROL),
            ("[UNK]", 0.0, UNKNOWN),
        ] + [(p, s, t) for p, s, t in XLMR_PIECES if t == NORMAL]
    else:
        pieces = XLMR_PIECES
    return UnigramTokenizer(pieces, scheme=scheme, use_native=use_native)


SPM_TEXTS = [
    "hello world",
    "ab abc bca cab",
    "the tokenizers tokenize tokens",
    "zzz unknown zz chars",
    "mixed abz zab zzab",
    "",
    "a",
    "hello " * 100,  # truncation
    "tabs\tand\nnewlines hello",
    "ctrl\x00chars\x1cjoin",  # dropped controls JOIN adjacent chars
]


@pytest.fixture(scope="module")
def native_spm():
    tok = _spm(use_native=True)
    if tok._native is None:
        pytest.skip("native unigram not buildable here")
    return tok


def test_native_unigram_matches_python(native_spm):
    python = _spm(use_native=False)
    for max_len in (8, 16, 64):
        ids_n, mask_n = native_spm.encode_batch(SPM_TEXTS, max_len)
        ids_p, mask_p = python.encode_batch(SPM_TEXTS, max_len)
        assert ids_n.tolist() == ids_p.tolist(), max_len
        assert mask_n.tolist() == mask_p.tolist()


def test_native_unigram_deberta_scheme_parity():
    native = _spm(use_native=True, scheme="deberta")
    if native._native is None:
        pytest.skip("native unigram not buildable here")
    python = _spm(use_native=False, scheme="deberta")
    ids_n, _ = native.encode_batch(SPM_TEXTS, 24)
    ids_p, _ = python.encode_batch(SPM_TEXTS, 24)
    assert ids_n.tolist() == ids_p.tolist()


def test_native_unigram_random_ascii_parity(native_spm):
    import random
    import string

    python = _spm(use_native=False)
    rng = random.Random(5)
    chars = "abchelowrdtknizs " + string.punctuation + "\t"
    texts = [
        "".join(rng.choice(chars) for _ in range(rng.randint(0, 120)))
        for _ in range(300)
    ]
    ids_n, _ = native_spm.encode_batch(texts, 48)
    ids_p, _ = python.encode_batch(texts, 48)
    assert ids_n.tolist() == ids_p.tolist()


def test_native_unigram_non_ascii_falls_back(native_spm):
    python = _spm(use_native=False)
    texts = ["héllo wörld", "ｈｅｌｌｏ fullwidth", "mixed ascii héllo"]
    ids_n, _ = native_spm.encode_batch(texts, 16)
    ids_p, _ = python.encode_batch(texts, 16)
    assert ids_n.tolist() == ids_p.tolist()


def test_native_unigram_thread_safety(native_spm):
    from concurrent.futures import ThreadPoolExecutor
    import random

    python = _spm(use_native=False)
    rng = random.Random(11)
    words = ["hello", "world", "ab", "abc", "tokens", "zzq"]
    texts = [
        " ".join(rng.choice(words) for _ in range(rng.randint(1, 60)))
        for _ in range(200)
    ]
    lengths = [8 + (i % 5) * 16 for i in range(len(texts))]
    expected = [python._encode(t, n) for t, n in zip(texts, lengths)]
    with ThreadPoolExecutor(8) as pool:
        got = list(
            pool.map(
                lambda tn: native_spm._encode(tn[0], tn[1]),
                zip(texts, lengths),
            )
        )
    assert got == expected


def test_native_unigram_newline_piece_does_not_shift_ids():
    """A vocab piece containing a newline must not break the blob's line
    framing (it would silently shift every later piece id)."""
    from llm_weighted_consensus_tpu.models.spm import (
        NORMAL,
        UNKNOWN,
        UnigramTokenizer,
    )

    pieces = [
        ("<unk>", 0.0, UNKNOWN),
        ("\n", -2.5, NORMAL),
        ("▁hello", -1.0, NORMAL),
        ("▁world", -1.2, NORMAL),
    ]
    native = UnigramTokenizer(pieces, scheme="xlmr", use_native=True)
    if native._native is None:
        pytest.skip("native unigram not buildable here")
    python = UnigramTokenizer(pieces, scheme="xlmr", use_native=False)
    ids_n, _ = native.encode_batch(["hello world"], 8)
    ids_p, _ = python.encode_batch(["hello world"], 8)
    assert ids_n.tolist() == ids_p.tolist()


def test_native_unigram_normal_piece_at_unk_index_parity():
    """When the unk index holds a NORMAL piece, it still participates in
    segmentation (remapped to unk on emit), exactly like Python."""
    from llm_weighted_consensus_tpu.models.spm import (
        NORMAL,
        UnigramTokenizer,
    )

    pieces = [
        ("▁ab", -1.0, NORMAL),  # unk_spm defaults to 0: this piece
        ("▁a", -5.0, NORMAL),
        ("b", -5.0, NORMAL),
    ]
    native = UnigramTokenizer(pieces, scheme="xlmr", use_native=True)
    if native._native is None:
        pytest.skip("native unigram not buildable here")
    python = UnigramTokenizer(pieces, scheme="xlmr", use_native=False)
    ids_n, _ = native.encode_batch(["ab", "a b ab"], 8)
    ids_p, _ = python.encode_batch(["ab", "a b ab"], 8)
    assert ids_n.tolist() == ids_p.tolist()


def test_unavailable_library_is_logged_once_with_its_reason(
    monkeypatch, caplog, tmp_path
):
    """A failed build must not silently put tokenisation and SSE parsing
    on their pure-Python paths: one warning with the compiler's message,
    and ``status()`` keeps the reason."""
    import logging

    from llm_weighted_consensus_tpu.utils import native

    bad = tmp_path / "broken.cpp"
    bad.write_text("this is not C++\n")
    monkeypatch.setattr(native, "_tried", False)
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_error", None)
    monkeypatch.setattr(native, "NATIVE_SO", str(tmp_path / "lib.so"))
    monkeypatch.setattr(native, "_sources", lambda: [str(bad)])
    with caplog.at_level(logging.WARNING, logger="lwc.native"):
        assert native.load_library() is None
        assert native.load_library() is None  # remembered, not retried
    warnings = [r for r in caplog.records if r.name == "lwc.native"]
    assert len(warnings) == 1
    assert "g++ failed" in warnings[0].getMessage()
    status = native.status()
    assert status["loaded"] is False and "g++ failed" in status["error"]
