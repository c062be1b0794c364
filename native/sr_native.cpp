// Native host-runtime kernels for starrocks_tpu.
//
// Reference behavior re-implemented natively (the BE's host-side hot paths):
// - hash partitioning for tablet bucketing (reference: OlapTableSink
//   partition/bucket routing, be/src/data_sink/tablet/olap_table_sink.h:52)
// - CSV -> columnar parsing for the load path (reference: formats/csv/)
// - zonemap min/max computation (reference: storage/rowset/zone_map_index)
//
// Exposed as a C ABI for ctypes; the Python side falls back to numpy when
// the shared library is unavailable.

#include <cstdint>
#include <cstring>
#include <cstdlib>
#include <cmath>
#include <thread>
#include <vector>
#include <algorithm>

extern "C" {

// --- splitmix64 bucketing ----------------------------------------------------

static inline uint64_t mix64(uint64_t z) {
  z = (z ^ (z >> 30)) * 0xBF58476D1CE4E5B9ULL;
  z = (z ^ (z >> 27)) * 0x94D049BB133111EBULL;
  return z ^ (z >> 31);
}

// out[i] ^= mix64(keys[i] * GOLDEN); callers chain per key column then mod.
void sr_hash_mix_i64(const int64_t* keys, int64_t n, uint64_t* inout) {
  for (int64_t i = 0; i < n; i++) {
    inout[i] ^= mix64((uint64_t)keys[i] * 0x9E3779B97F4A7C15ULL);
  }
}

void sr_hash_bucket(const uint64_t* h, int64_t n, int32_t nbuckets,
                    int32_t* out) {
  for (int64_t i = 0; i < n; i++) {
    out[i] = (int32_t)(h[i] % (uint64_t)nbuckets);
  }
}

// parallel variant over std::thread
void sr_hash_partition_i64_mt(const int64_t* keys, int64_t n, int32_t nbuckets,
                              int32_t* out, int32_t nthreads) {
  if (nthreads < 1) nthreads = 1;
  auto work = [&](int64_t lo, int64_t hi) {
    for (int64_t i = lo; i < hi; i++) {
      out[i] =
          (int32_t)(mix64((uint64_t)keys[i] * 0x9E3779B97F4A7C15ULL) %
                    (uint64_t)nbuckets);
    }
  };
  if (nthreads == 1 || n < 1 << 16) {
    work(0, n);
    return;
  }
  std::vector<std::thread> ts;
  int64_t step = (n + nthreads - 1) / nthreads;
  for (int t = 0; t < nthreads; t++) {
    int64_t lo = t * step, hi = std::min(n, lo + step);
    if (lo >= hi) break;
    ts.emplace_back(work, lo, hi);
  }
  for (auto& t : ts) t.join();
}

// --- zonemaps ----------------------------------------------------------------

void sr_minmax_i64(const int64_t* a, const uint8_t* valid, int64_t n,
                   int64_t* out_min, int64_t* out_max, int64_t* out_count) {
  int64_t mn = INT64_MAX, mx = INT64_MIN, cnt = 0;
  for (int64_t i = 0; i < n; i++) {
    if (valid && !valid[i]) continue;
    int64_t v = a[i];
    if (v < mn) mn = v;
    if (v > mx) mx = v;
    cnt++;
  }
  *out_min = mn;
  *out_max = mx;
  *out_count = cnt;
}

void sr_minmax_f64(const double* a, const uint8_t* valid, int64_t n,
                   double* out_min, double* out_max, int64_t* out_count) {
  double mn = INFINITY, mx = -INFINITY;
  int64_t cnt = 0;
  for (int64_t i = 0; i < n; i++) {
    if (valid && !valid[i]) continue;
    double v = a[i];
    if (v < mn) mn = v;
    if (v > mx) mx = v;
    cnt++;
  }
  *out_min = mn;
  *out_max = mx;
  *out_count = cnt;
}

// --- CSV parsing -------------------------------------------------------------
// Single-pass splitter: counts rows, then parses columns into preallocated
// typed buffers. Types: 0 = int64, 1 = float64, 2 = date (YYYY-MM-DD ->
// days since epoch), 3 = string (byte offsets recorded for python-side dict
// encoding). Delimiter configurable; no quoted-field support (the python
// pyarrow path handles quoted CSVs).

int64_t sr_csv_count_rows(const char* buf, int64_t len) {
  int64_t rows = 0;
  for (int64_t i = 0; i < len; i++)
    if (buf[i] == '\n') rows++;
  if (len > 0 && buf[len - 1] != '\n') rows++;
  return rows;
}

static inline int64_t days_from_civil(int64_t y, int64_t m, int64_t d) {
  y -= m <= 2;
  int64_t era = (y >= 0 ? y : y - 399) / 400;
  int64_t yoe = y - era * 400;
  int64_t doy = (153 * (m > 2 ? m - 3 : m + 9) + 2) / 5 + d - 1;
  int64_t doe = yoe * 365 + yoe / 4 - yoe / 100 + doy;
  return era * 146097 + doe - 719468;
}

// returns number of parsed rows; -1 on structural error (bad digit, short
// date, too many fields in a line, or more rows than max_rows buffers hold).
// out_cols: array of ncols pointers (int64_t* / double* per type)
// str_offsets: for string cols, 2 entries per row (start, end) into buf,
//   stored in the column's int64 buffer as interleaved pairs.
// null_mask: ncols pointers (uint8_t*) or null; empty field -> NULL.
int64_t sr_csv_parse(const char* buf, int64_t len, char delim, int32_t ncols,
                     const int32_t* types, void** out_cols,
                     uint8_t** null_masks, int64_t max_rows) {
  int64_t row = 0;
  int64_t i = 0;
  while (i < len) {
    if (row >= max_rows) return -1;
    for (int32_t c = 0; c < ncols; c++) {
      int64_t start = i;
      while (i < len && buf[i] != delim && buf[i] != '\n') i++;
      int64_t end = i;
      bool is_null = (end == start);
      if (null_masks && null_masks[c]) null_masks[c][row] = is_null ? 0 : 1;
      switch (types[c]) {
        case 0: {  // int64
          int64_t v = 0;
          bool neg = false;
          int64_t p = start;
          if (p < end && (buf[p] == '-' || buf[p] == '+')) {
            neg = buf[p] == '-';
            p++;
          }
          for (; p < end; p++) {
            char ch = buf[p];
            if (ch < '0' || ch > '9') return -1;
            v = v * 10 + (ch - '0');
          }
          ((int64_t*)out_cols[c])[row] = neg ? -v : v;
          break;
        }
        case 1: {  // float64
          if (is_null) {
            ((double*)out_cols[c])[row] = 0.0;
          } else {
            char tmp[64];
            int64_t m = end - start;
            if (m > 63) m = 63;
            memcpy(tmp, buf + start, m);
            tmp[m] = 0;
            ((double*)out_cols[c])[row] = strtod(tmp, nullptr);
          }
          break;
        }
        case 2: {  // date YYYY-MM-DD
          if (is_null || end - start < 10) {
            ((int64_t*)out_cols[c])[row] = 0;
            if (!is_null && end - start < 10) return -1;
          } else {
            const char* s = buf + start;
            int64_t y = (s[0] - '0') * 1000 + (s[1] - '0') * 100 +
                        (s[2] - '0') * 10 + (s[3] - '0');
            int64_t mo = (s[5] - '0') * 10 + (s[6] - '0');
            int64_t d = (s[8] - '0') * 10 + (s[9] - '0');
            ((int64_t*)out_cols[c])[row] = days_from_civil(y, mo, d);
          }
          break;
        }
        case 3: {  // string: record (start, end) offsets
          ((int64_t*)out_cols[c])[row * 2] = start;
          ((int64_t*)out_cols[c])[row * 2 + 1] = end;
          break;
        }
        default:
          return -1;
      }
      if (c + 1 < ncols) {
        if (i >= len || buf[i] != delim) return -1;  // too few fields
        i++;
      }
    }
    if (i < len && buf[i] != '\n') return -1;  // too many fields in this line
    if (i < len) i++;
    row++;
  }
  return row;
}

}  // extern "C"
