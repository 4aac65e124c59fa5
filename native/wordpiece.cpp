// Native WordPiece tokenizer — the ASCII fast path of
// models/tokenizer.py::WordPieceTokenizer (host-side hot loop: tokenization
// is inside the serving path's timed part).
//
// Scope: byte-for-byte parity with the Python implementation for pure-ASCII
// input (lowercase, whitespace/punctuation split, greedy longest-match with
// "##" continuations, [CLS]/[SEP] framing, truncation).  Non-ASCII text
// needs Unicode NFD + combining-mark stripping, which stays in Python — the
// wrapper routes per text.  Parity corpus: tests/test_native.py.
//
// C ABI (consumed via ctypes, no pybind11 in the image):
//   wp_new(vocab_bytes, len)                  -> handle (one token per
//                                                '\n'-separated line; id =
//                                                line number)
//   wp_encode(h, text, len, max_len, out_ids) -> number of ids written
//                                                (<= max_len), -1 on error
//   wp_free(h)

#include <cctype>
#include <cstddef>
#include <cstdint>
#include <cstring>
#include <string>
#include <unordered_map>
#include <vector>

namespace {

constexpr size_t kMaxCharsPerWord = 100;

struct WordPiece {
  std::unordered_map<std::string, int32_t> vocab;
  int32_t cls_id = -1, sep_id = -1, unk_id = -1;

  bool load(const char* bytes, size_t len) {
    size_t start = 0;
    int32_t id = 0;
    while (start <= len) {
      const char* nl = static_cast<const char*>(
          memchr(bytes + start, '\n', len - start));
      size_t end = nl ? static_cast<size_t>(nl - bytes) : len;
      size_t tok_end = end;
      if (tok_end > start && bytes[tok_end - 1] == '\r') --tok_end;
      if (tok_end > start || nl) {
        // skip a trailing empty line after the final newline
        if (tok_end > start) {
          vocab.emplace(std::string(bytes + start, tok_end - start), id);
        }
        ++id;
      }
      if (!nl) break;
      start = end + 1;
    }
    auto find = [&](const char* t) {
      auto it = vocab.find(t);
      return it == vocab.end() ? -1 : it->second;
    };
    cls_id = find("[CLS]");
    sep_id = find("[SEP]");
    unk_id = find("[UNK]");
    return cls_id >= 0 && sep_id >= 0 && unk_id >= 0;
  }

  static bool is_punct(unsigned char c) {
    return (c >= 33 && c <= 47) || (c >= 58 && c <= 64) ||
           (c >= 91 && c <= 96) || (c >= 123 && c <= 126);
  }

  // Python str.isspace() for ASCII: C isspace's set plus the separator
  // control chars 0x1c-0x1f (parity with basic_tokenize)
  static bool is_space(unsigned char c) {
    return isspace(c) || (c >= 0x1c && c <= 0x1f);
  }

  // greedy longest-match; appends piece ids (or [UNK]) to out
  void wordpiece(const std::string& word, std::vector<int32_t>& out) const {
    if (word.size() > kMaxCharsPerWord) {
      out.push_back(unk_id);
      return;
    }
    size_t start = 0;
    std::vector<int32_t> pieces;
    std::string piece;
    while (start < word.size()) {
      size_t end = word.size();
      int32_t piece_id = -1;
      while (start < end) {
        piece.assign(start > 0 ? "##" : "");
        piece.append(word, start, end - start);
        auto it = vocab.find(piece);
        if (it != vocab.end()) {
          piece_id = it->second;
          break;
        }
        --end;
      }
      if (piece_id < 0) {
        out.push_back(unk_id);
        return;
      }
      pieces.push_back(piece_id);
      start = end;
    }
    out.insert(out.end(), pieces.begin(), pieces.end());
  }

  // ASCII basic tokenize + wordpiece + [CLS]/[SEP] framing + truncation —
  // mirrors WordPieceTokenizer._encode + basic_tokenize for ASCII input
  // (lowercasing only; NFD is the identity on ASCII, and ASCII has no
  // combining marks).
  int64_t encode(const char* text, size_t len, int64_t max_len,
                 int32_t* out_ids) const {
    if (max_len < 2) return -1;
    std::vector<int32_t> ids;
    ids.reserve(static_cast<size_t>(max_len));
    ids.push_back(cls_id);
    std::string word;
    bool full = false;
    auto flush_word = [&](std::string* w) {
      if (!w->empty() && !full) {
        wordpiece(*w, ids);
        if (static_cast<int64_t>(ids.size()) >= max_len - 1) full = true;
      }
      w->clear();
    };
    for (size_t i = 0; i < len && !full; ++i) {
      unsigned char c = static_cast<unsigned char>(text[i]);
      if (is_space(c)) {
        flush_word(&word);
      } else if (is_punct(c)) {
        flush_word(&word);
        if (!full) {
          std::string p(1, static_cast<char>(c));
          wordpiece(p, ids);
          if (static_cast<int64_t>(ids.size()) >= max_len - 1) full = true;
        }
      } else {
        word.push_back(static_cast<char>(tolower(c)));
      }
    }
    flush_word(&word);
    if (static_cast<int64_t>(ids.size()) > max_len - 1) {
      ids.resize(static_cast<size_t>(max_len - 1));
    }
    ids.push_back(sep_id);
    memcpy(out_ids, ids.data(), ids.size() * sizeof(int32_t));
    return static_cast<int64_t>(ids.size());
  }
};

}  // namespace

extern "C" {

void* wp_new(const uint8_t* vocab_bytes, size_t len) {
  auto* wp = new WordPiece();
  if (!wp->load(reinterpret_cast<const char*>(vocab_bytes), len)) {
    delete wp;
    return nullptr;
  }
  return wp;
}

void wp_free(void* handle) { delete static_cast<WordPiece*>(handle); }

int64_t wp_encode(void* handle, const uint8_t* text, size_t len,
                  int64_t max_len, int32_t* out_ids) {
  return static_cast<WordPiece*>(handle)->encode(
      reinterpret_cast<const char*>(text), len, max_len, out_ids);
}

}  // extern "C"
