// Native ingest: single-pass text -> dense double matrix, plus binning.
//
// The native counterpart of the reference's hand-rolled parsers
// (reference src/io/parser.hpp:15-109, parser.cpp) and of the
// Feature::PushData binning path (include/LightGBM/feature.h:72-75,
// bin.h:296-309 ValueToBin binary search) — re-designed for the TPU
// framework's ingest shape: the output is one row-major [rows, cols]
// double buffer (numpy-owned) that host-side binning turns into the
// [F, N] uint8 HBM matrix, not per-feature push targets.
//
// Token semantics match the Python fallback (io/parser.py) and the
// reference's Atof (include/LightGBM/utils/common.h:89-199): na / nan /
// null / empty -> 0.0, inf/-inf via strtod, short rows zero-filled.
//
// Built lazily by lightgbm_tpu/native/__init__.py with
//   g++ -O3 -shared -fPIC -std=c++17 ingest.cpp -o _ingest.so
// and loaded through ctypes (no pybind11 in this image).

#include <algorithm>
#include <atomic>
#include <cctype>
#include <cmath>
#include <cstdint>
#include <cstdio>
#include <cstdlib>
#include <cstring>
#include <limits>
#include <locale.h>
#include <random>
#include <string>
#include <thread>
#include <vector>

namespace {

inline bool is_eol(char c) { return c == '\n' || c == '\r'; }

inline bool in_set(const char* set, char c) {
  for (const char* s = set; *s; ++s)
    if (*s == c) return true;
  return false;
}

// The reference Atof's digit-accumulation arithmetic, bit-for-bit
// (common.h:110-172).  NOT correctly-rounded conversion: it can differ
// from strtod by ulps, and ValueToBin of knife-edge values (e.g. "1.457"
// against a boundary at 1.4569999999999999) then lands in a different
// bin, diverging validation scores from the reference.  Only called for
// tokens strtod already validated as plain decimals.
inline double atof_ref(const char* b, const char* e) {
  const char* p = b;
  double sign = 1.0;
  if (p < e && *p == '-') { sign = -1.0; ++p; }
  else if (p < e && *p == '+') ++p;
  double value = 0.0;
  while (p < e && *p >= '0' && *p <= '9') {
    value = value * 10.0 + (*p - '0');
    ++p;
  }
  if (p < e && *p == '.') {
    double pow10 = 10.0;
    ++p;
    while (p < e && *p >= '0' && *p <= '9') {
      value += (*p - '0') / pow10;
      pow10 *= 10.0;
      ++p;
    }
  }
  int frac = 0;
  double scale = 1.0;
  if (p < e && (*p == 'e' || *p == 'E')) {
    ++p;
    if (p < e && *p == '-') { frac = 1; ++p; }
    else if (p < e && *p == '+') ++p;
    unsigned int expon = 0;
    while (p < e && *p >= '0' && *p <= '9') {
      expon = expon * 10 + (*p - '0');
      ++p;
    }
    if (expon > 308) expon = 308;
    while (expon >= 50) { scale *= 1E50; expon -= 50; }
    while (expon >= 8) { scale *= 1E8; expon -= 8; }
    while (expon > 0) { scale *= 10.0; expon -= 1; }
  }
  return sign * (frac ? (value / scale) : (value * scale));
}

// One-pass fast path: parse [+-]digits[.digits][eE[+-]digits] with the
// reference Atof arithmetic, validating as it goes.  *match=false means
// the token is not a plain decimal (caller falls to the strtod path);
// acceptance is exactly is_plain_decimal's.
inline double parse_fast(const char* b, const char* e, bool* match) {
  const char* p = b;
  double sign = 1.0;
  if (p < e && *p == '-') { sign = -1.0; ++p; }
  else if (p < e && *p == '+') ++p;
  bool digit = false;
  double value = 0.0;
  while (p < e && *p >= '0' && *p <= '9') {
    value = value * 10.0 + (*p - '0');
    digit = true;
    ++p;
  }
  if (p < e && *p == '.') {
    double pow10 = 10.0;
    ++p;
    while (p < e && *p >= '0' && *p <= '9') {
      value += (*p - '0') / pow10;
      pow10 *= 10.0;
      digit = true;
      ++p;
    }
  }
  if (!digit) { *match = false; return 0.0; }
  int frac = 0;
  double scale = 1.0;
  if (p < e && (*p == 'e' || *p == 'E')) {
    ++p;
    if (p < e && *p == '-') { frac = 1; ++p; }
    else if (p < e && *p == '+') ++p;
    bool edig = false;
    unsigned int expon = 0;
    while (p < e && *p >= '0' && *p <= '9') {
      expon = expon * 10 + (*p - '0');
      edig = true;
      ++p;
    }
    if (!edig) { *match = false; return 0.0; }
    if (expon > 308) expon = 308;
    while (expon >= 50) { scale *= 1E50; expon -= 50; }
    while (expon >= 8) { scale *= 1E8; expon -= 8; }
    while (expon > 0) { scale *= 10.0; expon -= 1; }
  }
  if (p != e) { *match = false; return 0.0; }
  *match = true;
  return sign * (frac ? (value / scale) : (value * scale));
}

inline bool is_plain_decimal(const char* b, const char* e) {
  const char* p = b + ((b < e && (*b == '+' || *b == '-')) ? 1 : 0);
  if (p == e) return false;
  bool digit = false;
  while (p < e && *p >= '0' && *p <= '9') { digit = true; ++p; }
  if (p < e && *p == '.') {
    ++p;
    while (p < e && *p >= '0' && *p <= '9') { digit = true; ++p; }
  }
  if (!digit) return false;
  if (p < e && (*p == 'e' || *p == 'E')) {
    ++p;
    if (p < e && (*p == '+' || *p == '-')) ++p;
    if (p == e) return false;
    while (p < e && *p >= '0' && *p <= '9') ++p;
  }
  return p == e;
}

// Token semantics of the reference Atof (common.h:200-290) and the Python
// fallback's _clean_token (io/parser.py): the WHOLE token (up to the next
// terminator in `terms` or EOL, whitespace-stripped) must be numeric, or
// one of na/nan/null/empty -> 0; inf -> +-1e308; anything else is a parse
// error (*ok = false).  Numbers are parsed with an explicit "C" locale so
// an embedding process's setlocale() cannot change the decimal point.
inline double parse_value(const char* p, const char* end, const char* terms,
                          const char** out, bool* ok) {
  static locale_t c_loc = newlocale(LC_ALL_MASK, "C", (locale_t)0);
  const char* s = p;
  while (s < end && !is_eol(*s) && !in_set(terms, *s)) ++s;
  *out = s;
  const char* b = p;  // strip surrounding whitespace like Python .strip()
  const char* e = s;
  while (b < e && (*b == ' ' || *b == '\t')) ++b;
  while (e > b && (e[-1] == ' ' || e[-1] == '\t')) --e;
  if (b == e) return 0.0;  // empty field
  // fast path: plain decimals (the overwhelmingly common case) parse in
  // ONE validating pass with the reference's Atof arithmetic — a plain
  // decimal always fully consumes under strtod, so skipping the strtod
  // validation changes nothing except the redundant passes (measured
  // >2x ingest throughput)
  bool fmatch = false;
  double fv = parse_fast(b, e, &fmatch);
  if (fmatch) return fv;
  // hex floats ("0x10") parse via strtod but Python float() rejects them;
  // treat as unknown tokens so both ingest paths agree
  const char* h = b + (*b == '+' || *b == '-');
  if (e - h > 1 && h[0] == '0' && (h[1] == 'x' || h[1] == 'X')) {
    *ok = false;
    return 0.0;
  }
  char* q = nullptr;
  double v = c_loc ? strtod_l(b, &q, c_loc) : std::strtod(b, &q);
  if (q == e) {  // fully numeric (partial consumption falls through)
    if (v != v) v = 0.0;       // "nan" via strtod -> 0 like the reference
    if (v > 1e308) v = 1e308;  // "inf" -> +-1e308 (common.h:284)
    if (v < -1e308) v = -1e308;
    return v;
  }
  size_t n = static_cast<size_t>(e - b);
  char t[5] = {0, 0, 0, 0, 0};
  for (size_t i = 0; i < n && i < 4; ++i) t[i] = std::tolower(b[i]);
  if ((n == 2 && !std::strcmp(t, "na")) || (n == 3 && !std::strcmp(t, "nan")) ||
      (n == 4 && !std::strcmp(t, "null")))
    return 0.0;
  *ok = false;
  return 0.0;
}

inline uint8_t bin_of(double v, const double* bounds, int32_t num_bin) {
  int32_t lo = 0, hi = num_bin - 1;
  while (lo < hi) {
    int32_t mid = (lo + hi) >> 1;
    if (v <= bounds[mid])
      hi = mid;
    else
      lo = mid + 1;
  }
  return static_cast<uint8_t>(lo);
}

// Split [buf, buf+len) into nt byte ranges aligned to line starts.
// Returns nt+1 boundaries; empty ranges are possible for tiny buffers.
inline std::vector<const char*> split_at_lines(const char* buf, int64_t len,
                                               int nt) {
  const char* end = buf + len;
  std::vector<const char*> cuts(nt + 1, end);
  cuts[0] = buf;
  for (int t = 1; t < nt; ++t) {
    const char* p = buf + len * t / nt;
    if (p <= cuts[t - 1]) p = cuts[t - 1];
    if (p == buf) p = buf + 1;   // p[-1] below must stay in bounds
    // advance to the first line start at/after p
    while (p < end && !is_eol(p[-1])) ++p;
    cuts[t] = p;
  }
  return cuts;
}

inline int64_t count_lines_range(const char* p, const char* end) {
  int64_t n = 0;
  while (p < end) {
    const char* line = p;
    while (p < end && !is_eol(*p)) ++p;
    if (p > line) ++n;
    while (p < end && is_eol(*p)) ++p;
  }
  return n;
}

inline int resolve_threads(int32_t nthreads, int64_t len) {
  if (nthreads > 0) return nthreads;   // explicit request honored exactly
  int nt = static_cast<int>(std::thread::hardware_concurrency());
  if (nt < 1) nt = 1;
  // don't spawn default threads for buffers too small to amortize them
  int64_t per = 1 << 18;
  if (len / per + 1 < nt) nt = static_cast<int>(len / per + 1);
  return nt;
}

// Output-capacity violation sentinel (distinct from -(row+1) parse
// errors): the caller's row expectation went stale, e.g. the file grew
// between the two streaming passes.
constexpr int64_t kOverflow = INT64_MIN;

// Per-thread line ranges + row/output offsets shared by the _mt parsers.
struct ThreadPlan {
  std::vector<const char*> cuts;
  std::vector<int64_t> row0, out0;
  int nt = 1;
};

// keep_rows bounds reads of `keep`; false when the chunk holds more
// lines than the caller planned for (treat as kOverflow).
inline bool plan_ranges(const char* buf, int64_t len, int nt,
                        const uint8_t* keep, int64_t keep_rows,
                        ThreadPlan* plan) {
  plan->nt = nt;
  plan->cuts = split_at_lines(buf, len, nt);
  std::vector<int64_t> cnt(nt, 0);
  {
    std::vector<std::thread> th;
    for (int t = 0; t < nt; ++t)
      th.emplace_back([&, t] {
        cnt[t] = count_lines_range(plan->cuts[t], plan->cuts[t + 1]);
      });
    for (auto& x : th) x.join();
  }
  plan->row0.assign(nt + 1, 0);
  for (int t = 0; t < nt; ++t) plan->row0[t + 1] = plan->row0[t] + cnt[t];
  if (keep) {
    if (plan->row0[nt] > keep_rows) return false;
    plan->out0.assign(nt + 1, 0);
    for (int t = 0; t < nt; ++t) {
      int64_t k = 0;
      for (int64_t r = plan->row0[t]; r < plan->row0[t + 1]; ++r)
        k += keep[r] != 0;
      plan->out0[t + 1] = plan->out0[t] + k;
    }
  } else {
    plan->out0 = plan->row0;
  }
  return true;
}

inline void record_err(std::atomic<int64_t>* err, int64_t row) {
  int64_t prev = err->load();
  while ((prev < 0 || row < prev) &&
         !err->compare_exchange_weak(prev, row)) {
  }
}

// Feature-major row-tile staging: a straight bins_out[f*stride + out]
// write touches F cache lines stride bytes apart PER ROW (measured ~3x
// slower than the parse); buffering TILE rows and flushing per-feature
// keeps writes cache-resident then sequential.
struct BinTile {
  static constexpr int64_t TILE = 512;
  std::vector<uint8_t> buf;
  int64_t nfeat, tbase;
  uint8_t* out;
  int64_t stride;
  BinTile(int64_t nf, uint8_t* bins_out, int64_t stride_, int64_t start)
      : buf(static_cast<size_t>(nf) * TILE),
        nfeat(nf), tbase(start), out(bins_out), stride(stride_) {}
  uint8_t* row(int64_t o) { return buf.data() + (o - tbase); }
  void flush(int64_t upto) {
    int64_t cnt = upto - tbase;
    for (int64_t f = 0; f < nfeat; ++f)
      std::memcpy(out + f * stride + tbase, buf.data() + f * TILE, cnt);
    tbase = upto;
  }
  void maybe_flush(int64_t o) {
    if (o - tbase == TILE) flush(o);
  }
};

}  // namespace

extern "C" {

// Non-empty line count of a text buffer (thread-parallel scan).
int64_t lgt_count_lines(const char* buf, int64_t len, int32_t nthreads) {
  int nt = resolve_threads(nthreads, len);
  if (nt <= 1) return count_lines_range(buf, buf + len);
  auto cuts = split_at_lines(buf, len, nt);
  std::vector<int64_t> cnt(nt, 0);
  std::vector<std::thread> th;
  for (int t = 0; t < nt; ++t)
    th.emplace_back([&, t] { cnt[t] = count_lines_range(cuts[t], cuts[t + 1]); });
  for (auto& x : th) x.join();
  int64_t total = 0;
  for (int64_t c : cnt) total += c;
  return total;
}

// Byte spans (start, length) of non-empty lines; returns the count
// (at most cap).  Lets callers slice sampled lines without a Python
// split of the whole chunk.
int64_t lgt_line_spans(const char* buf, int64_t len, int64_t* starts,
                       int64_t* lens, int64_t cap) {
  const char* p = buf;
  const char* end = buf + len;
  int64_t n = 0;
  while (p < end && n < cap) {
    const char* line = p;
    while (p < end && !is_eol(*p)) ++p;
    if (p > line) {
      starts[n] = line - buf;
      lens[n] = p - line;
      ++n;
    }
    while (p < end && is_eol(*p)) ++p;
  }
  return n;
}

// Fused multithreaded parse + quantize of a dense CSV/TSV chunk — the
// TPU-native equivalent of the reference's OpenMP block-parallel loading
// (src/io/dataset_loader.cpp:715-790 block parse + Feature::PushData
// binning): each thread parses a byte range and writes bins straight
// into the feature-major [F, stride] matrix, so the transient per-chunk
// float matrix of the two-phase path never exists.
//
// col_map [ncols] per FILE column: -2 label, -3 weight, -4 query id,
// -1 dropped, >= 0 inner feature index (bin bounds at
// bounds[boffs[f] .. boffs[f+1])).  keep (optional, [chunk rows]) marks
// rows this rank owns; skipped rows are not parsed (the reference's
// filtered rows are never pushed either).  Outputs are written at kept-
// row positions starting from 0: bins_out[f*stride + i], label_out[i],
// weight_out[i] (when non-null), qid_out[i] (when non-null).
// Returns kept-row count, or -(chunk_row+1) for the earliest parse
// error; *rows_seen_out = non-empty lines in the chunk.
int64_t lgt_parse_bin_dense_mt(
    const char* buf, int64_t len, char sep, int64_t ncols,
    const int32_t* col_map, const double* bounds, const int64_t* boffs,
    const int32_t* num_bins, const uint8_t* keep, int64_t keep_rows,
    uint8_t* bins_out, int64_t stride, int64_t out_cap, float* label_out,
    float* weight_out, int64_t* qid_out, int32_t nthreads,
    int64_t* rows_seen_out) {
  int nt = resolve_threads(nthreads, len);
  ThreadPlan plan;
  if (!plan_ranges(buf, len, nt, keep, keep_rows, &plan)) return kOverflow;
  *rows_seen_out = plan.row0[nt];
  if (plan.out0[nt] > out_cap) return kOverflow;

  std::atomic<int64_t> err(-1);   // earliest failing chunk row, or -1
  int64_t nfeat = 0;
  for (int64_t c = 0; c < ncols; ++c)
    if (col_map[c] >= 0 && col_map[c] + 1 > nfeat) nfeat = col_map[c] + 1;
  auto worker = [&](int t) {
    const char* p = plan.cuts[t];
    const char* end = plan.cuts[t + 1];
    const char terms[2] = {sep, 0};
    int64_t row = plan.row0[t];
    int64_t out = plan.out0[t];
    bool ok = true;
    BinTile tile(nfeat, bins_out, stride, out);
    while (p < end) {
      while (p < end && is_eol(*p)) ++p;
      if (p >= end) break;
      const char* line_end = p;
      while (line_end < end && !is_eol(*line_end)) ++line_end;
      if (line_end == p) continue;
      if (keep && !keep[row]) {   // not ours: skip without parsing
        p = line_end;
        ++row;
        continue;
      }
      uint8_t* trow = tile.row(out);
      int64_t c = 0;
      while (p < line_end && c < ncols) {
        double v = parse_value(p, line_end, terms, &p, &ok);
        if (!ok) {
          record_err(&err, row);
          tile.flush(out);
          return;
        }
        int32_t act = col_map[c];
        if (act >= 0)
          // dense parsers drop |v| <= 1e-10 features to the value-0
          // default (reference parser.hpp:32,62 never emit them; the
          // DenseBin default is ValueToBin(0), dense_bin.hpp:19-24).
          // Labels/weights/qids below keep tiny values, like the
          // reference's label assignment before the cutoff.
          trow[act * BinTile::TILE] =
              bin_of(std::fabs(v) > 1e-10 ? v : 0.0,
                     bounds + boffs[act], num_bins[act]);
        else if (act == -2)
          label_out[out] = static_cast<float>(v);
        else if (act == -3 && weight_out)
          weight_out[out] = static_cast<float>(v);
        else if (act == -4 && qid_out)
          qid_out[out] = static_cast<int64_t>(v);
        ++c;
        while (p < line_end && *p != sep) ++p;
        if (p < line_end) ++p;
      }
      // short rows: remaining columns take value 0.0 like lgt_parse_dense
      for (; c < ncols; ++c) {
        int32_t act = col_map[c];
        if (act >= 0)
          trow[act * BinTile::TILE] =
              bin_of(0.0, bounds + boffs[act], num_bins[act]);
        else if (act == -2)
          label_out[out] = 0.0f;
        else if (act == -3 && weight_out)
          weight_out[out] = 0.0f;
        else if (act == -4 && qid_out)
          qid_out[out] = 0;
      }
      p = line_end;
      ++row;
      ++out;
      tile.maybe_flush(out);
    }
    tile.flush(out);
  };
  {
    std::vector<std::thread> th;
    for (int t = 0; t < nt; ++t) th.emplace_back(worker, t);
    for (auto& x : th) x.join();
  }
  int64_t e = err.load();
  if (e >= 0) return -(e + 1);
  return plan.out0[nt];
}

// Fused multithreaded parse + quantize of a libsvm chunk.  Same output
// contract as lgt_parse_bin_dense_mt; absent features take zero_bin[f]
// (the bin of 0.0, precomputed by the caller).  feat_map [max_idx+1]
// maps file feature index -> inner feature (-1 dropped).
int64_t lgt_parse_bin_libsvm_mt(
    const char* buf, int64_t len, int64_t max_idx, const int32_t* feat_map,
    const double* bounds, const int64_t* boffs, const int32_t* num_bins,
    const uint8_t* zero_bin, int64_t nfeat, const uint8_t* keep,
    int64_t keep_rows, uint8_t* bins_out, int64_t stride, int64_t out_cap,
    float* label_out, int32_t nthreads, int64_t* rows_seen_out) {
  int nt = resolve_threads(nthreads, len);
  ThreadPlan plan;
  if (!plan_ranges(buf, len, nt, keep, keep_rows, &plan)) return kOverflow;
  *rows_seen_out = plan.row0[nt];
  if (plan.out0[nt] > out_cap) return kOverflow;

  std::atomic<int64_t> err(-1);
  auto worker = [&](int t) {
    const char* p = plan.cuts[t];
    const char* end = plan.cuts[t + 1];
    int64_t row = plan.row0[t];
    int64_t out = plan.out0[t];
    bool ok = true;
    BinTile tile(nfeat, bins_out, stride, out);
    while (p < end) {
      while (p < end && is_eol(*p)) ++p;
      if (p >= end) break;
      const char* line_end = p;
      while (line_end < end && !is_eol(*line_end)) ++line_end;
      if (line_end == p) continue;
      if (keep && !keep[row]) {
        p = line_end;
        ++row;
        continue;
      }
      uint8_t* trow = tile.row(out);
      for (int64_t f = 0; f < nfeat; ++f)
        trow[f * BinTile::TILE] = zero_bin[f];
      double v = parse_value(p, line_end, " \t", &p, &ok);
      if (!ok) {
        record_err(&err, row);
        tile.flush(out);
        return;
      }
      label_out[out] = static_cast<float>(v);
      while (p < line_end) {
        while (p < line_end && (*p == ' ' || *p == '\t')) ++p;
        if (p >= line_end) break;
        char* q = nullptr;
        long long idx = std::strtoll(p, &q, 10);
        if (q == p || q >= line_end || *q != ':') {
          while (p < line_end && *p != ' ' && *p != '\t') ++p;
          continue;
        }
        p = q + 1;
        v = parse_value(p, line_end, " \t:", &p, &ok);
        if (!ok) {
          record_err(&err, row);
          tile.flush(out);
          return;
        }
        if (idx >= 0 && idx <= max_idx) {
          int32_t act = feat_map[idx];
          if (act >= 0)
            trow[act * BinTile::TILE] =
                bin_of(v, bounds + boffs[act], num_bins[act]);
        }
      }
      p = line_end;
      ++row;
      ++out;
      tile.maybe_flush(out);
    }
    tile.flush(out);
  };
  {
    std::vector<std::thread> th;
    for (int t = 0; t < nt; ++t) th.emplace_back(worker, t);
    for (auto& x : th) x.join();
  }
  int64_t e = err.load();
  if (e >= 0) return -(e + 1);
  return plan.out0[nt];
}

// Multithreaded dense parse into a row-major [rows, cols] double matrix
// (one-round loading / CLI predict path).  Same line semantics as
// lgt_parse_dense; rows beyond `rows` are ignored.
int64_t lgt_parse_dense_mt(const char* buf, int64_t len, char sep,
                           double* out, int64_t rows, int64_t cols,
                           int32_t nthreads) {
  int nt = resolve_threads(nthreads, len);
  ThreadPlan plan;
  plan_ranges(buf, len, nt, nullptr, 0, &plan);

  std::atomic<int64_t> err(-1);
  auto worker = [&](int t) {
    const char* p = plan.cuts[t];
    const char* end = plan.cuts[t + 1];
    const char terms[2] = {sep, 0};
    int64_t r = plan.row0[t];
    bool ok = true;
    while (p < end && r < rows) {
      while (p < end && is_eol(*p)) ++p;
      if (p >= end) break;
      const char* line_end = p;
      while (line_end < end && !is_eol(*line_end)) ++line_end;
      if (line_end == p) continue;
      double* row = out + r * cols;
      int64_t c = 0;
      while (p < line_end && c < cols) {
        row[c++] = parse_value(p, line_end, terms, &p, &ok);
        if (!ok) {
          record_err(&err, r);
          return;
        }
        while (p < line_end && *p != sep) ++p;
        if (p < line_end) ++p;
      }
      for (; c < cols; ++c) row[c] = 0.0;
      p = line_end;
      ++r;
    }
  };
  {
    std::vector<std::thread> th;
    for (int t = 0; t < nt; ++t) th.emplace_back(worker, t);
    for (auto& x : th) x.join();
  }
  int64_t e = err.load();
  if (e >= 0) return -(e + 1);
  return std::min(plan.row0[nt], rows);
}

// Count rows (non-empty lines) and columns (separators in the first
// non-empty line + 1) of a dense CSV/TSV buffer.
void lgt_scan_dense(const char* buf, int64_t len, char sep,
                    int64_t* rows_out, int64_t* cols_out) {
  const char* p = buf;
  const char* end = buf + len;
  int64_t rows = 0, cols = 0;
  while (p < end) {
    const char* line = p;
    while (p < end && !is_eol(*p)) ++p;
    if (p > line) {  // non-empty
      if (rows == 0) {
        cols = 1;
        for (const char* s = line; s < p; ++s)
          if (*s == sep) ++cols;
      }
      ++rows;
    }
    while (p < end && is_eol(*p)) ++p;
  }
  *rows_out = rows;
  *cols_out = cols;
}

// Fill a row-major [rows, cols] buffer from a dense CSV/TSV text.
// Missing trailing fields are 0-filled; extra fields are ignored.
// Returns the number of rows written, or -(row+1) on a parse error.
int64_t lgt_parse_dense(const char* buf, int64_t len, char sep, double* out,
                        int64_t rows, int64_t cols) {
  const char* p = buf;
  const char* end = buf + len;
  int64_t r = 0;
  bool ok = true;
  while (p < end && r < rows) {
    while (p < end && is_eol(*p)) ++p;
    if (p >= end) break;
    const char* line_end = p;
    while (line_end < end && !is_eol(*line_end)) ++line_end;
    if (line_end == p) continue;
    double* row = out + r * cols;
    int64_t c = 0;
    const char terms[2] = {sep, 0};
    while (p < line_end && c < cols) {
      row[c++] = parse_value(p, line_end, terms, &p, &ok);
      if (!ok) return -(r + 1);
      while (p < line_end && *p != sep) ++p;  // skip to separator
      if (p < line_end) ++p;                  // past separator
    }
    for (; c < cols; ++c) row[c] = 0.0;
    p = line_end;
    ++r;
  }
  return r;
}

// Feature indices above this are treated as malformed tokens and
// skipped (the reference parses them through atoi into int, UB there;
// a bound keeps a corrupt file from requesting a 2^63-column matrix).
constexpr int64_t kMaxFeatureIdx = (int64_t(1) << 31) - 1;

// Scan a libsvm buffer: rows and the maximum feature index seen.
void lgt_scan_libsvm(const char* buf, int64_t len, int64_t* rows_out,
                     int64_t* max_idx_out) {
  const char* p = buf;
  const char* end = buf + len;
  int64_t rows = 0, max_idx = -1;
  while (p < end) {
    const char* line_end = p;
    while (line_end < end && !is_eol(*line_end)) ++line_end;
    if (line_end > p) {
      ++rows;
      for (const char* s = p; s < line_end; ++s) {
        if (*s == ':') {
          const char* b = s;
          while (b > p && b[-1] >= '0' && b[-1] <= '9') --b;
          if (b < s) {
            int64_t idx = std::strtoll(b, nullptr, 10);
            if (idx > max_idx && idx <= kMaxFeatureIdx) max_idx = idx;
          }
        }
      }
    }
    p = line_end;
    while (p < end && is_eol(*p)) ++p;
  }
  *rows_out = rows;
  *max_idx_out = max_idx;
}

// Fill label [rows] + dense feats [rows, ncols] from a libsvm buffer
// (0-based indices like the reference LibSVMParser, src/io/parser.hpp:80-109).
int64_t lgt_parse_libsvm(const char* buf, int64_t len, double* label_out,
                         double* feats_out, int64_t rows, int64_t ncols) {
  const char* p = buf;
  const char* end = buf + len;
  int64_t r = 0;
  bool ok = true;
  std::memset(feats_out, 0, sizeof(double) * rows * ncols);
  while (p < end && r < rows) {
    while (p < end && is_eol(*p)) ++p;
    if (p >= end) break;
    const char* line_end = p;
    while (line_end < end && !is_eol(*line_end)) ++line_end;
    if (line_end == p) continue;
    label_out[r] = parse_value(p, line_end, " \t", &p, &ok);
    if (!ok) return -(r + 1);
    double* row = feats_out + r * ncols;
    while (p < line_end) {
      while (p < line_end && (*p == ' ' || *p == '\t')) ++p;
      if (p >= line_end) break;
      char* q = nullptr;
      long long idx = std::strtoll(p, &q, 10);
      if (q == p || q >= line_end || *q != ':') {  // skip malformed token
        while (p < line_end && *p != ' ' && *p != '\t') ++p;
        continue;
      }
      p = q + 1;  // past ':'
      double v = parse_value(p, line_end, " \t:", &p, &ok);
      if (!ok) return -(r + 1);
      if (idx >= 0 && idx < ncols) row[idx] = v;
    }
    p = line_end;
    ++r;
  }
  return r;
}

// Lambdarank gradients (the one objective whose reference semantics are
// not order-free: reference src/objective/rank_objective.hpp:76-164).
// Two properties force a native path for bit-parity with golden models:
//   1. docs are ranked with non-stable std::sort, so the tie permutation
//      (all scores equal at iteration 1!) is the libstdc++ introsort one;
//   2. per-pair fp32 lambdas are accumulated sequentially in sorted order.
// The Python fallback (objectives.py LambdarankNDCG._one_query) computes
// the same math vectorized and is kept for no-toolchain environments.
//
// score/label are per-query slices laid out [N]; qb is [num_queries+1]
// boundaries; sigmoid_table is the precomputed LUT with (min_input,
// idx_factor) addressing, matching GetSigmoid (rank_objective.hpp:166-175).
void lgt_lambdarank_grads(const float* score, const float* label,
                          const int32_t* qb, int64_t num_queries,
                          const float* inv_max_dcg, const float* label_gain,
                          const float* discount, const float* sigmoid_table,
                          int64_t sigmoid_bins, float min_input,
                          float max_input, float idx_factor,
                          const float* weights, float* lambdas,
                          float* hessians) {
  const float kMinScore = -std::numeric_limits<float>::infinity();
  auto sig = [&](float s) -> float {
    if (s <= min_input) return sigmoid_table[0];
    if (s >= max_input) return sigmoid_table[sigmoid_bins - 1];
    return sigmoid_table[static_cast<size_t>((s - min_input) * idx_factor)];
  };
  for (int64_t q = 0; q < num_queries; ++q) {
    const int32_t start = qb[q];
    const int32_t cnt = qb[q + 1] - start;
    const float inv_mdcg = inv_max_dcg[q];
    const float* sc = score + start;
    const float* lb = label + start;
    float* lam = lambdas + start;
    float* hes = hessians + start;
    for (int32_t i = 0; i < cnt; ++i) lam[i] = hes[i] = 0.0f;
    std::vector<int32_t> order(cnt);
    for (int32_t i = 0; i < cnt; ++i) order[i] = i;
    std::sort(order.begin(), order.end(),
              [sc](int32_t a, int32_t b) { return sc[a] > sc[b]; });
    if (cnt == 0) continue;
    const float best = sc[order[0]];
    int32_t worst_pos = cnt - 1;
    if (worst_pos > 0 && sc[order[worst_pos]] == kMinScore) --worst_pos;
    const float worst = sc[order[worst_pos]];
    for (int32_t i = 0; i < cnt; ++i) {
      const int32_t hi = order[i];
      if (sc[hi] == kMinScore) continue;
      const int hi_lab = static_cast<int>(lb[hi]);
      const float hi_gain = label_gain[hi_lab];
      const float hi_disc = discount[i];
      float sum_lam = 0.0f, sum_hes = 0.0f;
      for (int32_t j = 0; j < cnt; ++j) {
        if (i == j) continue;
        const int32_t lo = order[j];
        const int lo_lab = static_cast<int>(lb[lo]);
        if (hi_lab <= lo_lab || sc[lo] == kMinScore) continue;
        const float ds = sc[hi] - sc[lo];
        float delta = (hi_gain - label_gain[lo_lab]) *
                      std::fabs(hi_disc - discount[j]) * inv_mdcg;
        if (hi_lab != lo_lab && best != worst)
          delta /= (0.01f + std::fabs(ds));
        float pl = sig(ds);
        float ph = pl * (2.0f - pl);
        pl *= -delta;
        ph *= 2 * delta;
        sum_lam += pl;
        sum_hes += ph;
        lam[lo] -= pl;
        hes[lo] += ph;
      }
      lam[hi] += sum_lam;
      hes[hi] += sum_hes;
    }
    if (weights) {
      for (int32_t i = 0; i < cnt; ++i) {
        lam[i] *= weights[start + i];
        hes[i] *= weights[start + i];
      }
    }
  }
}

// NDCG@ks over all queries (reference src/metric/rank_metric.hpp:89-145 +
// src/metric/dcg_calculator.cpp).  Native for the same reason as the
// lambdarank gradients: the top-k membership under tied scores follows
// std::sort's permutation, and DCG / inverse-max-DCG accumulate in fp32.
// out[j] = sum over queries of NDCG@ks[j] (caller divides by the weight
// sum).  All-negative queries contribute 1.0 regardless of weight — a
// reference quirk (rank_metric.hpp:120-123) reproduced on purpose.
void lgt_ndcg_eval(const float* score, const float* label, const int32_t* qb,
                   int64_t num_queries, const int32_t* ks, int64_t num_k,
                   const float* label_gain, int64_t num_gain,
                   const float* query_weights, double* out) {
  std::vector<float> discount;
  {
    int32_t max_cnt = 1;
    for (int64_t q = 0; q < num_queries; ++q)
      max_cnt = std::max(max_cnt, qb[q + 1] - qb[q]);
    discount.resize(max_cnt);
    for (int32_t i = 0; i < max_cnt; ++i)
      discount[i] = 1.0f / std::log2(2.0f + i);
  }
  for (int64_t j = 0; j < num_k; ++j) out[j] = 0.0;
  std::vector<int32_t> label_cnt(num_gain);
  std::vector<float> inv(num_k), dcgs(num_k);
  std::vector<int32_t> order;
  for (int64_t q = 0; q < num_queries; ++q) {
    const int32_t start = qb[q];
    const int32_t cnt = qb[q + 1] - start;
    const float* lb = label + start;
    const float* sc = score + start;
    // inverse max DCG at each k, one pass (dcg_calculator.cpp:58-88)
    std::fill(label_cnt.begin(), label_cnt.end(), 0);
    for (int32_t i = 0; i < cnt; ++i) ++label_cnt[static_cast<int>(lb[i])];
    float cur = 0.0f;
    int32_t left = 0;
    int top = static_cast<int>(num_gain) - 1;
    for (int64_t j = 0; j < num_k; ++j) {
      int32_t k = std::min(ks[j], cnt);
      for (int32_t p = left; p < k; ++p) {
        while (top > 0 && label_cnt[top] <= 0) --top;
        if (top < 0) break;
        cur += discount[p] * label_gain[top];
        --label_cnt[top];
      }
      inv[j] = cur > 0.0f ? 1.0f / cur : -1.0f;
      left = k;
    }
    if (inv[0] <= 0.0f) {
      for (int64_t j = 0; j < num_k; ++j) out[j] += 1.0;
      continue;
    }
    // DCG at each k over the std::sort order (dcg_calculator.cpp:112-136)
    order.resize(cnt);
    for (int32_t i = 0; i < cnt; ++i) order[i] = i;
    std::sort(order.begin(), order.end(),
              [sc](int32_t a, int32_t b) { return sc[a] > sc[b]; });
    cur = 0.0f;
    left = 0;
    for (int64_t j = 0; j < num_k; ++j) {
      int32_t k = std::min(ks[j], cnt);
      for (int32_t p = left; p < k; ++p)
        cur += label_gain[static_cast<int>(lb[order[p]])] * discount[p];
      dcgs[j] = cur;
      left = k;
    }
    const float w = query_weights ? query_weights[q] : 1.0f;
    for (int64_t j = 0; j < num_k; ++j)
      out[j] += static_cast<double>(dcgs[j] * inv[j] * w);
  }
}

// Feature-importance ordering: the reference sorts (count, name) pairs
// with non-stable std::sort comparing ONLY the count
// (src/boosting/gbdt.cpp:466-477), so the order among equal counts is
// whatever libstdc++ introsort leaves.  Running the same std::sort (same
// comparator, same libstdc++) over (count, position) pairs reproduces the
// permutation exactly: every control-flow decision in introsort is a
// comparator call, and the comparator never reads .second.
// Whitespace-separated doubles with the reference's Atof semantics
// (StringToArray<double>, common.h:229-247): fills out[0..n), returns the
// number parsed, or -1 on an unknown token.  Fast path for reading model
// files back (tree.py Tree.from_string float arrays).
int64_t lgt_parse_doubles(const char* buf, int64_t len, double* out,
                          int64_t n) {
  const char* p = buf;
  const char* end = buf + len;
  int64_t cnt = 0;
  bool ok = true;
  while (p < end && cnt < n) {
    while (p < end && (*p == ' ' || *p == '\t' || is_eol(*p))) ++p;
    if (p >= end) break;
    const char* q = p;
    while (q < end && *q != ' ' && *q != '\t' && !is_eol(*q)) ++q;
    const char* dummy = nullptr;
    out[cnt++] = parse_value(p, q, "", &dummy, &ok);
    if (!ok) return -1;
    p = q;
  }
  return cnt;
}

// Sequential selection-sampling acceptance mask (reference
// Random::Sample, random.h:55-67, and the GBDT::Bagging in/out-of-bag
// loop, gbdt.cpp:118-129): accept i when draw_i < (k - taken)/(n - i).
// draws are the pre-generated NextDouble stream; the exact IEEE ops of
// the reference loop, just lifted out of Python.
void lgt_selection_mask(const double* draws, int64_t n, int64_t k,
                        uint8_t* mask) {
  int64_t taken = 0;
  for (int64_t i = 0; i < n; ++i) {
    double prob = static_cast<double>(k - taken) / static_cast<double>(n - i);
    if (draws[i] < prob) {
      mask[i] = 1;
      ++taken;
    } else {
      mask[i] = 0;
    }
  }
}

// The same walk with its draws made here: n NextDouble draws of a
// std::mt19937 continued from its raw state (key: the 624 words after the
// last twist; *pos: the next word to temper, 624 = twist first; both as
// numpy's MT19937 bit generator holds them, and both advanced in place).
// NextDouble is libstdc++'s generate_canonical<double, 53> over two words,
// (x1 + x2 * 2^32) / 2^64 (utils/mt19937.py).  One pass, no array of
// draws: a bag of 68M rows costs the host under a second.
void lgt_mt_selection_mask(uint32_t* key, int64_t* pos, int64_t n, int64_t k,
                           uint8_t* mask) {
  constexpr int N = 624, M = 397;
  constexpr uint32_t A = 0x9908B0DFu, UPPER = 0x80000000u,
                     LOWER = 0x7FFFFFFFu;
  int p = static_cast<int>(*pos);
  auto next = [&]() -> uint32_t {
    if (p >= N) {
      int i = 0;
      for (; i < N - M; ++i) {
        uint32_t y = (key[i] & UPPER) | (key[i + 1] & LOWER);
        key[i] = key[i + M] ^ (y >> 1) ^ ((y & 1u) ? A : 0u);
      }
      for (; i < N - 1; ++i) {
        uint32_t y = (key[i] & UPPER) | (key[i + 1] & LOWER);
        key[i] = key[i + M - N] ^ (y >> 1) ^ ((y & 1u) ? A : 0u);
      }
      uint32_t y = (key[N - 1] & UPPER) | (key[0] & LOWER);
      key[N - 1] = key[M - 1] ^ (y >> 1) ^ ((y & 1u) ? A : 0u);
      p = 0;
    }
    uint32_t y = key[p++];
    y ^= y >> 11;
    y ^= (y << 7) & 0x9D2C5680u;
    y ^= (y << 15) & 0xEFC60000u;
    y ^= y >> 18;
    return y;
  };
  constexpr double TWO32 = 4294967296.0;
  int64_t taken = 0;
  for (int64_t i = 0; i < n; ++i) {
    double x1 = static_cast<double>(next());
    double x2 = static_cast<double>(next());
    double draw = (x1 + x2 * TWO32) / (TWO32 * TWO32);
    double prob = static_cast<double>(k - taken) / static_cast<double>(n - i);
    if (draw < prob) {
      mask[i] = 1;
      ++taken;
    } else {
      mask[i] = 0;
    }
  }
  *pos = p;
}

// ---------------------------------------------------------------------------
// Multi-machine row lottery + bin-sample reservoir.
//
// The reference partitions a NON-pre-partitioned data file across
// machines by a seeded RNG lottery: one NextInt(0, num_machines) draw
// per row (or per query when a .query sidecar exists) decides the
// owning rank, and — under two-round loading — locally-kept rows then
// feed the streaming bin-sample reservoir with NextInt(0, local_count)
// draws on the SAME mt19937 (DatasetLoader::LoadTextDataToMemory /
// SampleTextDataFromFile, src/io/dataset_loader.cpp:467-572, via
// TextReader::ReadAndFilterLines / SampleAndFilterFromFile,
// include/LightGBM/utils/text_reader.h:174-211; the RNG is
// Random(io_config.data_random_seed), include/LightGBM/utils/random.h).
//
// This kernel is that interleaved draw stream as a stateful handle fed
// chunk by chunk.  It is compiled by the same g++/libstdc++ that builds
// the reference binary here, so uniform_int_distribution's downscaling
// and rejection behavior match by construction — every rank replays the
// identical stream (the seed is config-synced), so the partition needs
// no communication.
struct LgtLottery {
  std::mt19937 gen;
  int64_t num_machines, rank, sample_cnt;
  int64_t local_cnt = 0;  // locally-kept rows so far (reservoir ub)
  int64_t filled = 0;     // reservoir slots filled so far
  uint8_t keep_cur = 0;   // current unit's lottery outcome (chunk carry)
  LgtLottery(int32_t seed, int64_t m, int64_t r, int64_t s)
      : gen(static_cast<std::mt19937::result_type>(seed)),
        num_machines(m), rank(r), sample_cnt(s) {}
  int64_t next_int(int64_t ub) {  // Random::NextInt(0, ub), random.h:30-40
    std::uniform_int_distribution<int64_t> d(0, ub - 1);
    return d(gen);
  }
};

void* lgt_lottery_new(int32_t seed, int64_t num_machines, int64_t rank,
                      int64_t sample_cnt) {
  return new LgtLottery(seed, num_machines, rank, sample_cnt);
}

void lgt_lottery_free(void* h) { delete static_cast<LgtLottery*>(h); }

// k rows of one chunk.  new_unit[i] != 0 starts a new lottery unit
// (row granularity: NULL = every row; query granularity: 1 at each
// query head, with keep_cur carrying the open query's outcome across
// chunk boundaries).  keep[i]: row kept on this rank.  slot[i]: the
// reservoir slot this row's line writes (fill slots arrive in order;
// replacement slots are < sample_cnt), or -1.  sample_cnt < 0 disables
// the reservoir entirely (one-round ReadAndFilterLines: lottery only).
void lgt_lottery_chunk(void* h, int64_t k, const uint8_t* new_unit,
                       uint8_t* keep, int64_t* slot) {
  auto* st = static_cast<LgtLottery*>(h);
  for (int64_t i = 0; i < k; ++i) {
    if (!new_unit || new_unit[i])
      st->keep_cur = st->next_int(st->num_machines) == st->rank ? 1 : 0;
    keep[i] = st->keep_cur;
    if (slot) slot[i] = -1;
    if (!st->keep_cur) continue;
    ++st->local_cnt;
    if (st->sample_cnt < 0 || !slot) continue;
    if (st->filled < st->sample_cnt) {
      slot[i] = st->filled++;
    } else {
      int64_t idx = st->next_int(st->local_cnt);
      if (idx < st->sample_cnt) slot[i] = idx;
    }
  }
}

// n NextDouble draws continuing the same stream: the one-round path's
// Random::Sample replay consumes these after the lottery
// (SampleTextDataFromMemory, dataset_loader.cpp:514-526).
void lgt_lottery_doubles(void* h, int64_t n, double* out) {
  auto* st = static_cast<LgtLottery*>(h);
  std::uniform_real_distribution<double> d(0.0, 1.0);
  for (int64_t i = 0; i < n; ++i) out[i] = d(st->gen);
}

// Bulk "%g" score formatting for task=predict output
// (Predictor::SaveTextPredictionsToFile equivalent): vals is [nrows,
// ncols] row-major; each row prints ncols "%g" fields joined by '\t'
// with a trailing '\n' — exactly what Python's "%g" % v produces for
// finite doubles, just without a million PyObject round-trips.
// out must hold >= nrows * ncols * 26 bytes; returns bytes written.
int64_t lgt_format_g(const double* vals, int64_t nrows, int64_t ncols,
                     char* out) {
  char* p = out;
  for (int64_t r = 0; r < nrows; ++r) {
    const double* row = vals + r * ncols;
    for (int64_t c = 0; c < ncols; ++c) {
      if (c) *p++ = '\t';
      p += snprintf(p, 26, "%g", row[c]);
    }
    *p++ = '\n';
  }
  return p - out;
}

void lgt_sort_importance(const uint64_t* counts, int64_t n, int32_t* perm) {
  std::vector<std::pair<size_t, size_t>> pairs(n);
  for (int64_t i = 0; i < n; ++i)
    pairs[i] = {static_cast<size_t>(counts[i]), static_cast<size_t>(i)};
  std::sort(pairs.begin(), pairs.end(),
            [](const std::pair<size_t, size_t>& lhs,
               const std::pair<size_t, size_t>& rhs) {
              return lhs.first > rhs.first;
            });
  for (int64_t i = 0; i < n; ++i)
    perm[i] = static_cast<int32_t>(pairs[i].second);
}

// value -> bin: upper-bound binary search over bin_upper_bound, exactly
// BinMapper::ValueToBin (reference include/LightGBM/bin.h:296-309).
void lgt_bin_values(const double* vals, int64_t n, const double* bounds,
                    int32_t num_bin, uint8_t* out) {
  for (int64_t i = 0; i < n; ++i) {
    double v = vals[i];
    int32_t lo = 0, hi = num_bin - 1;
    while (lo < hi) {
      int32_t mid = (lo + hi) >> 1;
      if (v <= bounds[mid])
        hi = mid;
      else
        lo = mid + 1;
    }
    out[i] = static_cast<uint8_t>(lo);
  }
}

}  // extern "C"

// ---------------------------------------------------------------------------
// Native task=predict fast path: fused parse -> tree descent -> transform ->
// "%g" format in one multithreaded pass, the warm-process equivalent of the
// reference Predictor (src/application/predictor.hpp:82-130) without the JAX
// runtime in the loop.  Byte-identical semantics:
//   - fields parse with the reference Atof arithmetic (parse_value above);
//   - dense parsers drop |v| <= 1e-10 to zero (parser.hpp:32,62) while
//     libsvm keeps every idx:val pair (parser.hpp:94-103);
//   - descent compares value <= threshold (tree.h:179-189, GetLeaf);
//   - per-class sums accumulate doubles in model order i*num_class+j
//     (gbdt.cpp:487-510, PredictRaw/Predict);
//   - the sigmoid transform replicates `1.0f/(1.0f+exp(-2.0f*sigmoid*s))`
//     including the float literals (gbdt.cpp:506) and Common::Softmax's
//     max-shift order (common.h:353-366);
//   - output lines are '\t'-joined "%g" fields (Common::Join's default
//     ostream formatting) with one row per input line.

namespace {

// Flattened forest: per-model inner-node arrays at node_off[m] and leaf
// values at leaf_off[m].  num_models = num_used_iterations * num_class.
struct Forest {
  const int32_t* sf;       // split_feature_real
  const double* thr;
  const int32_t* lc;
  const int32_t* rc;
  const double* lv;        // leaf values
  const int64_t* node_off;  // [num_models + 1]
  const int64_t* leaf_off;  // [num_models + 1]
  int64_t num_models;
  int64_t num_class;
  double sigmoid;
  int32_t mode;            // 0 = transformed, 1 = raw score, 2 = leaf index
};

// One branchless descent step: finished rows (n < 0) re-load node 0
// harmlessly and keep their leaf.  The unconditional loads keep 4
// independent chains in flight per loop (below), which is what hides the
// ~4-cycle L1 latency of the node->child pointer chase — a straight
// per-row `while (node >= 0)` loop measured ~3x slower on the 1M-row
// bench (one mispredicted exit per row per tree).
inline int32_t desc_step(const double* x, const int32_t* sf,
                         const double* thr, const int32_t* lc,
                         const int32_t* rc, int32_t n) {
  int32_t i = n & ~(n >> 31);  // max(n, 0) without a branch
  int32_t l = lc[i], r = rc[i];
  // load both children first so the select is register-register: gcc
  // emits cmov and the (data-dependent, ~50% taken) comparison never
  // becomes a mispredicting branch
  int32_t nxt = x[sf[i]] <= thr[i] ? l : r;
  return n < 0 ? n : nxt;
}

// Leaf index of model m for nb buffered rows (X row-major [nb, num_feat]),
// 4 rows interleaved.  Identical result to per-row GetLeaf descent.
inline void tree_leaves(const Forest& F, int64_t m, const double* X,
                        int64_t num_feat, int64_t nb, int32_t* out) {
  const int64_t o = F.node_off[m];
  if (F.node_off[m + 1] == o) {  // single-leaf tree
    for (int64_t b = 0; b < nb; ++b) out[b] = 0;
    return;
  }
  const int32_t* sf = F.sf + o;
  const double* thr = F.thr + o;
  const int32_t* lc = F.lc + o;
  const int32_t* rc = F.rc + o;
  int64_t b = 0;
  for (; b + 8 <= nb; b += 8) {
    const double* x0 = X + (b + 0) * num_feat;
    const double* x1 = X + (b + 1) * num_feat;
    const double* x2 = X + (b + 2) * num_feat;
    const double* x3 = X + (b + 3) * num_feat;
    const double* x4 = X + (b + 4) * num_feat;
    const double* x5 = X + (b + 5) * num_feat;
    const double* x6 = X + (b + 6) * num_feat;
    const double* x7 = X + (b + 7) * num_feat;
    int32_t n0 = 0, n1 = 0, n2 = 0, n3 = 0;
    int32_t n4 = 0, n5 = 0, n6 = 0, n7 = 0;
    // any row still descending
    while ((n0 & n1 & n2 & n3 & n4 & n5 & n6 & n7) >= 0) {
      n0 = desc_step(x0, sf, thr, lc, rc, n0);
      n1 = desc_step(x1, sf, thr, lc, rc, n1);
      n2 = desc_step(x2, sf, thr, lc, rc, n2);
      n3 = desc_step(x3, sf, thr, lc, rc, n3);
      n4 = desc_step(x4, sf, thr, lc, rc, n4);
      n5 = desc_step(x5, sf, thr, lc, rc, n5);
      n6 = desc_step(x6, sf, thr, lc, rc, n6);
      n7 = desc_step(x7, sf, thr, lc, rc, n7);
    }
    out[b + 0] = ~n0;
    out[b + 1] = ~n1;
    out[b + 2] = ~n2;
    out[b + 3] = ~n3;
    out[b + 4] = ~n4;
    out[b + 5] = ~n5;
    out[b + 6] = ~n6;
    out[b + 7] = ~n7;
  }
  for (; b < nb; ++b) {
    const double* x = X + b * num_feat;
    int32_t node = 0;
    while (node >= 0)
      node = x[sf[node]] <= thr[node] ? lc[node] : rc[node];
    out[b] = ~node;
  }
}

// Rows buffered per block before descending: big enough to amortize the
// tree-outer loop (node arrays stay L1/L2-hot across rows), capped so
// X = block * num_feat doubles stays cache-resident even for wide
// (libsvm) models.
inline int64_t predict_block_rows(int64_t num_feat) {
  // keep X within ~L1 (32 KB budget): the x[sf[node]] load sits on the
  // descent's serial dependency chain, so an L2-resident block adds
  // ~10 cycles to every level of every tree
  int64_t b = (32 << 10) / (num_feat > 0 ? num_feat * 8 : 8);
  if (b > 512) b = 512;
  if (b < 8) b = 8;
  return b;
}

// Descend + transform + format nb buffered rows into s.  leaves is a
// [block] i32 scratch; acc a [block * num_class] f64 scratch; lvidx
// (mode 2 only) a [block * num_models] i32 scratch.
inline void predict_flush(const Forest& F, const double* X, int64_t num_feat,
                          int64_t nb, int32_t* leaves, double* acc,
                          int32_t* lvidx, std::string* s) {
  char tmp[32];
  if (F.mode == 2) {
    for (int64_t m = 0; m < F.num_models; ++m) {
      tree_leaves(F, m, X, num_feat, nb, leaves);
      for (int64_t b = 0; b < nb; ++b) lvidx[b * F.num_models + m] = leaves[b];
    }
    for (int64_t b = 0; b < nb; ++b) {
      for (int64_t m = 0; m < F.num_models; ++m) {
        if (m) s->push_back('\t');
        int n = snprintf(tmp, sizeof(tmp), "%d", lvidx[b * F.num_models + m]);
        s->append(tmp, n);
      }
      s->push_back('\n');
    }
    return;
  }
  for (int64_t b = 0; b < nb * F.num_class; ++b) acc[b] = 0.0;
  // tree-outer, rows-inner: per row the additions still happen in model
  // order m = 0..num_models-1, so the double accumulation is bit-identical
  // to the reference's per-row loop (gbdt.cpp:487-494)
  for (int64_t m = 0; m < F.num_models; ++m) {
    tree_leaves(F, m, X, num_feat, nb, leaves);
    const double* lv = F.lv + F.leaf_off[m];
    double* a = acc + (m % F.num_class);
    for (int64_t b = 0; b < nb; ++b)
      a[b * F.num_class] += lv[leaves[b]];
  }
  for (int64_t b = 0; b < nb; ++b) {
    double* ret = acc + b * F.num_class;
    if (F.mode == 0) {
      if (F.sigmoid > 0 && F.num_class == 1) {
        ret[0] = 1.0f / (1.0f + std::exp(-2.0f * F.sigmoid * ret[0]));
      } else if (F.num_class > 1) {
        double wmax = ret[0];
        for (int64_t j = 1; j < F.num_class; ++j)
          wmax = std::max(ret[j], wmax);
        double wsum = 0.0f;
        for (int64_t j = 0; j < F.num_class; ++j) {
          ret[j] = std::exp(ret[j] - wmax);
          wsum += ret[j];
        }
        for (int64_t j = 0; j < F.num_class; ++j) ret[j] /= wsum;
      }
    }
    for (int64_t j = 0; j < F.num_class; ++j) {
      if (j) s->push_back('\t');
      int n = snprintf(tmp, sizeof(tmp), "%g", ret[j]);
      s->append(tmp, n);
    }
    s->push_back('\n');
  }
}

// Per-thread block state for the predict workers: rows buffered into X
// then flushed through predict_flush.
struct PredictBlock {
  int64_t cap, num_feat, nb = 0;
  std::vector<double> X;
  std::vector<int32_t> leaves;
  std::vector<double> acc;
  std::vector<int32_t> lvidx;
  PredictBlock(const Forest& F, int64_t nf)
      : cap(predict_block_rows(nf)), num_feat(nf),
        X(static_cast<size_t>(cap) * nf, 0.0),
        leaves(cap),
        acc(static_cast<size_t>(cap) * F.num_class),
        lvidx(F.mode == 2 ? static_cast<size_t>(cap) * F.num_models : 0) {}
  double* row() { return X.data() + nb * num_feat; }
  void flush(const Forest& F, std::string* s) {
    if (!nb) return;
    predict_flush(F, X.data(), num_feat, nb, leaves.data(), acc.data(),
                  lvidx.data(), s);
    std::fill(X.begin(), X.begin() + nb * num_feat, 0.0);
    nb = 0;
  }
};

// Join per-thread output strings in order into the caller's buffer.
inline int64_t gather_outputs(const std::vector<std::string>& outs,
                              char* out, int64_t out_cap) {
  int64_t total = 0;
  for (const auto& s : outs) total += static_cast<int64_t>(s.size());
  if (total > out_cap) return kOverflow;
  char* q = out;
  for (const auto& s : outs) {
    std::memcpy(q, s.data(), s.size());
    q += s.size();
  }
  return total;
}

}  // namespace

extern "C" {

// Dense CSV/TSV chunk -> formatted prediction text.  Returns bytes
// written, -(chunk_row+1) for the earliest parse error, or kOverflow if
// out_cap is too small.  The caller skips any header line and aligns
// chunks to line boundaries.
int64_t lgt_predict_dense_mt(
    const char* buf, int64_t len, char sep, int64_t label_idx,
    int64_t num_feat, const int32_t* sf, const double* thr,
    const int32_t* lc, const int32_t* rc, const double* lv,
    const int64_t* node_off, const int64_t* leaf_off, int64_t num_models,
    int64_t num_class, double sigmoid, int32_t mode, char* out,
    int64_t out_cap, int32_t nthreads, int64_t* rows_seen_out) {
  const Forest F{sf, thr, lc, rc, lv, node_off, leaf_off,
                 num_models, num_class, sigmoid, mode};
  int nt = resolve_threads(nthreads, len);
  ThreadPlan plan;
  plan_ranges(buf, len, nt, nullptr, 0, &plan);
  // the exact row count (callers size a kOverflow retry buffer from it,
  // saving the separate lgt_count_lines pass over the chunk)
  *rows_seen_out = plan.row0[nt];
  std::atomic<int64_t> err(-1);
  std::vector<std::string> outs(nt);
  auto worker = [&](int t) {
    const char* p = plan.cuts[t];
    const char* end = plan.cuts[t + 1];
    const char terms[2] = {sep, 0};
    int64_t row = plan.row0[t];
    bool ok = true;
    std::string& s = outs[t];
    PredictBlock blk(F, num_feat);
    while (p < end) {
      while (p < end && is_eol(*p)) ++p;
      if (p >= end) break;
      const char* line_end = p;
      while (line_end < end && !is_eol(*line_end)) ++line_end;
      if (line_end == p) continue;
      double* x = blk.row();
      int64_t idx = 0, bias = 0;
      while (p < line_end) {
        double v = parse_value(p, line_end, terms, &p, &ok);
        if (!ok) {
          record_err(&err, row);
          return;
        }
        if (idx == label_idx) {
          bias = -1;  // parsed and discarded (Predictor ignores labels)
        } else if (std::fabs(v) > 1e-10) {
          int64_t f = idx + bias;
          if (f >= 0 && f < num_feat) x[f] = v;
        }
        ++idx;
        while (p < line_end && *p != sep) ++p;
        if (p < line_end) ++p;
      }
      if (++blk.nb == blk.cap) blk.flush(F, &s);
      p = line_end;
      ++row;
    }
    blk.flush(F, &s);
  };
  {
    std::vector<std::thread> th;
    for (int t = 0; t < nt; ++t) th.emplace_back(worker, t);
    for (auto& x : th) x.join();
  }
  int64_t e = err.load();
  if (e >= 0) return -(e + 1);
  return gather_outputs(outs, out, out_cap);
}

// LibSVM chunk -> formatted prediction text.  Same contract as
// lgt_predict_dense_mt; the leading label token is parsed and discarded,
// idx:val pairs address features directly (parser.hpp:94-103), and
// malformed tokens are skipped like lgt_parse_bin_libsvm_mt.
int64_t lgt_predict_libsvm_mt(
    const char* buf, int64_t len, int64_t num_feat, const int32_t* sf,
    const double* thr, const int32_t* lc, const int32_t* rc,
    const double* lv, const int64_t* node_off, const int64_t* leaf_off,
    int64_t num_models, int64_t num_class, double sigmoid, int32_t mode,
    char* out, int64_t out_cap, int32_t nthreads, int64_t* rows_seen_out) {
  const Forest F{sf, thr, lc, rc, lv, node_off, leaf_off,
                 num_models, num_class, sigmoid, mode};
  int nt = resolve_threads(nthreads, len);
  ThreadPlan plan;
  plan_ranges(buf, len, nt, nullptr, 0, &plan);
  *rows_seen_out = plan.row0[nt];
  std::atomic<int64_t> err(-1);
  std::vector<std::string> outs(nt);
  auto worker = [&](int t) {
    const char* p = plan.cuts[t];
    const char* end = plan.cuts[t + 1];
    int64_t row = plan.row0[t];
    bool ok = true;
    std::string& s = outs[t];
    PredictBlock blk(F, num_feat);
    while (p < end) {
      while (p < end && is_eol(*p)) ++p;
      if (p >= end) break;
      const char* line_end = p;
      while (line_end < end && !is_eol(*line_end)) ++line_end;
      if (line_end == p) continue;
      double* x = blk.row();
      double v = parse_value(p, line_end, " \t", &p, &ok);  // label
      if (!ok) {
        record_err(&err, row);
        return;
      }
      while (p < line_end) {
        while (p < line_end && (*p == ' ' || *p == '\t')) ++p;
        if (p >= line_end) break;
        char* q = nullptr;
        long long fidx = std::strtoll(p, &q, 10);
        if (q == p || q >= line_end || *q != ':') {
          while (p < line_end && *p != ' ' && *p != '\t') ++p;
          continue;
        }
        p = q + 1;
        v = parse_value(p, line_end, " \t:", &p, &ok);
        if (!ok) {
          record_err(&err, row);
          return;
        }
        if (fidx >= 0 && fidx < num_feat) x[fidx] = v;
      }
      if (++blk.nb == blk.cap) blk.flush(F, &s);
      p = line_end;
      ++row;
    }
    blk.flush(F, &s);
  };
  {
    std::vector<std::thread> th;
    for (int t = 0; t < nt; ++t) th.emplace_back(worker, t);
    for (auto& x : th) x.join();
  }
  int64_t e = err.load();
  if (e >= 0) return -(e + 1);
  return gather_outputs(outs, out, out_cap);
}

}  // extern "C"
