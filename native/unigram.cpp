// Native unigram (SentencePiece) tokenizer — the ASCII fast path of
// models/spm.py::UnigramTokenizer (host-side hot loop: spm tokenization is
// inside the reranker's and the bge-m3 serving path, where
// inputs run to 8k tokens).
//
// Scope: exact parity with the Python implementation for pure-ASCII input:
// control-char normalization (NFKC is the identity on ASCII), whitespace
// split, metaspace prefix, max-sum Viterbi over piece scores with the
// min_score-10 unknown fallback, unknown-run fusing, scheme id mapping and
// [CLS]/[SEP]-style framing with truncation.  Non-ASCII text needs real
// NFKC, which stays in Python — the wrapper routes per text.  Parity
// corpus: tests/test_native.py.
//
// C ABI (consumed via ctypes, no pybind11 in the image):
//   spm_new(blob, len)   -> handle.  Blob layout (built by spm.py):
//                           line 1: "cls sep unk offset unk_spm" (final
//                           input ids for the specials, spm->input id
//                           offset, and the spm index whose matches remap
//                           to unk — mirroring Python's _token_to_id);
//                           then one line per piece, in spm-id order:
//                           "<score>\t<matchable 0|1>\t<piece-utf8>"
//                           (unmatchable pieces write an EMPTY text field
//                           so line framing survives any piece bytes)
//   spm_encode(h, text, len, max_len, out_ids) -> ids written, -1 on error
//   spm_free(h)

#include <charconv>
#include <cmath>
#include <cstddef>
#include <cstdint>
#include <cstdlib>
#include <cstring>
#include <limits>
#include <string>
#include <unordered_map>
#include <vector>

namespace {

const char kSpace[] = "\xe2\x96\x81";  // ▁ metaspace marker (3 bytes)
constexpr double kUnkPenalty = 10.0;

struct Unigram {
  std::unordered_map<std::string, std::pair<int32_t, double>> pieces;
  int32_t cls_id = -1, sep_id = -1, unk_id = -1, offset = 0;
  int32_t unk_spm = -1;
  double unk_score = 0.0;
  size_t max_piece_len = 1;

  bool load(const char* bytes, size_t len) {
    size_t pos = 0;
    auto next_line = [&](std::string* out) {
      if (pos >= len) return false;
      const char* nl = static_cast<const char*>(
          memchr(bytes + pos, '\n', len - pos));
      size_t end = nl ? static_cast<size_t>(nl - bytes) : len;
      out->assign(bytes + pos, end - pos);
      pos = nl ? end + 1 : len;
      return true;
    };
    std::string line;
    if (!next_line(&line)) return false;
    if (sscanf(line.c_str(), "%d %d %d %d %d", &cls_id, &sep_id, &unk_id,
               &offset, &unk_spm) != 5) {
      return false;
    }
    double min_score = std::numeric_limits<double>::infinity();
    int32_t id = 0;
    bool any = false;
    while (next_line(&line)) {
      size_t t1 = line.find('\t');
      size_t t2 = t1 == std::string::npos ? t1 : line.find('\t', t1 + 1);
      if (t2 == std::string::npos) return false;
      // std::from_chars: locale-independent (strtod would truncate at
      // the decimal point under comma-decimal LC_NUMERIC locales)
      double score = 0.0;
      auto res =
          std::from_chars(line.data(), line.data() + t1, score);
      if (res.ec != std::errc()) return false;
      bool matchable = line[t1 + 1] == '1';
      std::string piece = line.substr(t2 + 1);
      if (matchable && !piece.empty()) {
        // last duplicate wins (parity with Python's dict comprehensions)
        pieces[piece] = std::make_pair(id, score);
        if (piece.size() > max_piece_len) max_piece_len = piece.size();
        if (score < min_score) min_score = score;
        any = true;
      }
      ++id;
    }
    unk_score = (any ? min_score : 0.0) - kUnkPenalty;
    return cls_id >= 0 && sep_id >= 0 && unk_id >= 0 && any;
  }

  // Viterbi over one metaspace chunk ("▁" + ascii word).  Byte positions
  // are char positions everywhere except inside the 3-byte ▁, handled by
  // a boundary mask.  Appends final INPUT ids (offset applied, unknown
  // runs fused to unk_id) to out.
  void segment(const std::string& chunk, std::vector<int32_t>& out) const {
    const size_t L = chunk.size();
    std::vector<char> boundary(L + 1, 1);
    for (size_t i = 0; i + sizeof(kSpace) - 1 <= L; ++i) {
      if (memcmp(chunk.data() + i, kSpace, 3) == 0) {
        boundary[i + 1] = boundary[i + 2] = 0;
        i += 2;
      }
    }
    constexpr double NEG = -std::numeric_limits<double>::infinity();
    std::vector<double> best(L + 1, NEG);
    std::vector<size_t> prev(L + 1, 0);
    std::vector<char> known(L + 1, 0);
    best[0] = 0.0;
    std::string piece;
    for (size_t i = 0; i < L; ++i) {
      if (!boundary[i] || best[i] == NEG) continue;
      const size_t hi = std::min(L, i + max_piece_len);
      for (size_t j = i + 1; j <= hi; ++j) {
        if (!boundary[j]) continue;
        piece.assign(chunk, i, j - i);
        auto it = pieces.find(piece);
        if (it != pieces.end() && best[i] + it->second.second > best[j]) {
          best[j] = best[i] + it->second.second;
          prev[j] = i;
          known[j] = 1;
        }
      }
      // single unknown char fallback (one codepoint: 3 bytes for ▁)
      size_t j = i + 1;
      while (j <= L && !boundary[j]) ++j;
      if (j <= L && best[i] + unk_score > best[j]) {
        best[j] = best[i] + unk_score;
        prev[j] = i;
        known[j] = 0;
      }
    }
    // backtrack spans, then emit fused (consecutive unknowns -> one unk)
    struct Span {
      size_t start, end;
      char is_known;
    };
    std::vector<Span> spans;
    size_t j = L;
    while (j > 0) {
      spans.push_back({prev[j], j, known[j]});
      j = prev[j];
    }
    bool prev_unk = false;
    for (auto it = spans.rbegin(); it != spans.rend(); ++it) {
      if (it->is_known) {
        piece.assign(chunk, it->start, it->end - it->start);
        const int32_t pid = pieces.at(piece).first;
        // a matched piece AT the unk index emits unk (Python
        // _token_to_id parity) but does NOT fuse with unknown runs
        out.push_back(pid == unk_spm ? unk_id : pid + offset);
        prev_unk = false;
      } else if (!prev_unk) {
        out.push_back(unk_id);
        prev_unk = true;
      }
    }
  }

  int64_t encode(const char* text, size_t len, int64_t max_len,
                 int32_t* out_ids) const {
    if (max_len < 2) return -1;
    std::vector<int32_t> ids;
    ids.reserve(static_cast<size_t>(max_len));
    ids.push_back(cls_id);
    std::string word;
    bool full = false;
    auto flush_word = [&](std::string* w) {
      if (w->size() > 3 && !full) {  // > metaspace prefix alone
        segment(*w, ids);
        if (static_cast<int64_t>(ids.size()) >= max_len - 1) full = true;
      }
      w->clear();
    };
    for (size_t i = 0; i < len && !full; ++i) {
      unsigned char c = static_cast<unsigned char>(text[i]);
      if (c == ' ' || c == '\t' || c == '\n' || c == '\r' || c == '\v' ||
          c == '\f') {
        flush_word(&word);
      } else if (c < 0x20 || c == 0x7f) {
        // other ASCII controls: dropped by normalize() (category Cc)
      } else {
        if (word.empty()) word.assign(kSpace);
        word.push_back(static_cast<char>(c));
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

void* spm_new(const uint8_t* blob, size_t len) {
  auto* spm = new Unigram();
  if (!spm->load(reinterpret_cast<const char*>(blob), len)) {
    delete spm;
    return nullptr;
  }
  return spm;
}

void spm_free(void* handle) { delete static_cast<Unigram*>(handle); }

int64_t spm_encode(void* handle, const uint8_t* text, size_t len,
                   int64_t max_len, int32_t* out_ids) {
  return static_cast<Unigram*>(handle)->encode(
      reinterpret_cast<const char*>(text), len, max_len, out_ids);
}

}  // extern "C"
