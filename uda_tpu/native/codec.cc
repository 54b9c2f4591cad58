// Native IFile/VInt codec: the host-staging hot path.
//
// C++ equivalent of the reference's StreamUtility VInt/VLong codec and
// record framing walk (reference src/CommUtils/IOUtility.cc:167-397,
// src/Merger/StreamRW.cc:334-449), exposed through a C ABI consumed via
// ctypes (uda_tpu/native/__init__.py). One pass converts an IFile
// segment buffer into columnar (offset, length) arrays — the same
// contract as uda_tpu.utils.ifile.crack/crack_partial, which remain the
// pure-Python reference implementation these functions are parity-tested
// against (tests/test_native.py).

#include <algorithm>
#include <cstdint>
#include <cstddef>
#include <cstring>
#include <new>
#include <numeric>
#include <string>
#include <vector>

#include <cerrno>
#include <fcntl.h>
#include <unistd.h>

#include "vlong.h"

using uda::decode_vlong;

extern "C" {

// Error codes (negative returns)
enum : int64_t {
  UDA_ERR_CORRUPT = -1,     // negative length that isn't the EOF marker
  UDA_ERR_OVERFLOW = -2,    // more records than max_records
};

// Scan consecutive VLongs; returns count decoded (stops at truncation).
int64_t uda_decode_vlongs(const uint8_t* buf, int64_t len, int64_t* out,
                          int64_t max) {
  int64_t pos = 0, n = 0;
  while (pos < len && n < max) {
    int used = decode_vlong(buf, len, pos, &out[n]);
    if (used == 0) break;
    pos += used;
    ++n;
  }
  return n;
}

// One-pass columnar crack of an IFile segment (the native twin of
// ifile.crack_partial). Writes up to max_records (key_off, key_len,
// val_off, val_len) rows. Returns the record count or a UDA_ERR_* code;
// *consumed = bytes consumed (complete records + EOF marker),
// *saw_eof = 1 if the (-1,-1) marker was reached.
int64_t uda_crack(const uint8_t* buf, int64_t len,
                  int64_t* key_off, int64_t* key_len,
                  int64_t* val_off, int64_t* val_len,
                  int64_t max_records, int64_t* consumed, int32_t* saw_eof) {
  int64_t pos = 0, n = 0;
  *saw_eof = 0;
  while (pos < len) {
    int64_t start = pos;
    int64_t klen, vlen;
    int used = decode_vlong(buf, len, pos, &klen);
    if (used == 0) { pos = start; break; }
    int64_t p = pos + used;
    used = decode_vlong(buf, len, p, &vlen);
    if (used == 0) { pos = start; break; }
    p += used;
    if (klen == -1 && vlen == -1) {
      pos = p;
      *saw_eof = 1;
      break;
    }
    if (klen < 0 || vlen < 0) return UDA_ERR_CORRUPT;
    if (p + klen + vlen > len) { pos = start; break; }
    if (n >= max_records) return UDA_ERR_OVERFLOW;
    key_off[n] = p;
    key_len[n] = klen;
    val_off[n] = p + klen;
    val_len[n] = vlen;
    pos = p + klen + vlen;
    ++n;
  }
  *consumed = pos;
  return n;
}

// Serialize records into IFile framing (VInt klen, VInt vlen, key, val).
// Returns bytes written or -1 if out_cap is too small. Appends the EOF
// marker when write_eof != 0.
using uda::encode_vlong;

int64_t uda_write_records(const uint8_t* data,
                          const int64_t* key_off, const int64_t* key_len,
                          const int64_t* val_off, const int64_t* val_len,
                          int64_t n, uint8_t* out, int64_t out_cap,
                          int32_t write_eof) {
  int64_t pos = 0;
  for (int64_t i = 0; i < n; ++i) {
    int64_t need = key_len[i] + val_len[i] + 20;
    if (pos + need > out_cap) return -1;
    pos += encode_vlong(key_len[i], out + pos);
    pos += encode_vlong(val_len[i], out + pos);
    const uint8_t* k = data + key_off[i];
    for (int64_t j = 0; j < key_len[i]; ++j) out[pos + j] = k[j];
    pos += key_len[i];
    const uint8_t* v = data + val_off[i];
    for (int64_t j = 0; j < val_len[i]; ++j) out[pos + j] = v[j];
    pos += val_len[i];
  }
  if (write_eof) {
    if (pos + 2 > out_cap) return -1;
    out[pos++] = 0xFF;
    out[pos++] = 0xFF;
  }
  return pos;
}

}  // extern "C"

// Span gather: dst[dst_off[i] : dst_off[i]+len[i]] = src[src_off[i] : ...]
// for every record i — the byte-movement core of the streaming
// interleave and slab gather (uda_tpu/merger/streaming.py). The numpy
// fallback builds an int64 index per BYTE (8x the memory traffic);
// this is a straight memcpy per record.
extern "C" void uda_gather_spans(const uint8_t* src, const int64_t* src_off,
                                 const int64_t* lens, int64_t n,
                                 uint8_t* dst, const int64_t* dst_off) {
  for (int64_t i = 0; i < n; ++i)
    std::memcpy(dst + dst_off[i], src + src_off[i], (size_t)lens[i]);
}

// Slab gather over a per-task segment table: the in-memory emit path's
// byte movement (uda_tpu/merger/streaming.py:slab_batch). The merged
// device rows name every output record as (segment, row); one table
// entry per segment holds that segment's data and column base
// addresses, so a slab is gathered in O(records), whatever the segment
// count. Two passes because the compact buffer (all keys, then all
// values) is sized from the lengths: pass 1 validates every record and
// writes its lengths, Python allocates, pass 2 copies.
struct UdaSegment {       // one row of the int64[segments, 7] table
  const uint8_t* data;
  const int64_t* key_off;
  const int64_t* key_len;
  const int64_t* val_off;
  const int64_t* val_len;
  int64_t records;
  int64_t data_size;
};
static_assert(sizeof(UdaSegment) == 7 * sizeof(int64_t),
              "UdaSegment must match the int64[segments, 7] table");

// (segment, row) of slab record i: uint32 columns addressed by byte
// stride, so the columns of the row-major uint32 slab are read in place
static inline uint32_t slab_u32(const uint8_t* col, int64_t stride,
                                int64_t i) {
  uint32_t v;
  std::memcpy(&v, col + i * stride, sizeof v);
  return v;
}

static inline bool span_inside(int64_t off, int64_t len, int64_t size) {
  return off >= 0 && len >= 0 && off <= size && len <= size - off;
}

extern "C" {

enum : int64_t {
  UDA_SLAB_BAD_SEGMENT = 1,  // seg >= segments
  UDA_SLAB_BAD_ROW = 2,      // row >= records[seg]
  UDA_SLAB_BAD_SPAN = 3,     // key or value span outside the data
};

// Pass 1. Returns 0 with out = {sum of key_len, sum of val_len}, or a
// UDA_SLAB_* code with out[2] = the slab record it was found at.
int64_t uda_slab_lengths(const UdaSegment* table,
                         const uint8_t* seg, int64_t seg_stride,
                         const uint8_t* row, int64_t row_stride, int64_t n,
                         int64_t segments, int64_t* key_len,
                         int64_t* val_len, int64_t out[3]) {
  int64_t kt = 0, vt = 0;
  for (int64_t i = 0; i < n; ++i) {
    out[2] = i;
    const uint32_t s = slab_u32(seg, seg_stride, i);
    if ((int64_t)s >= segments) return UDA_SLAB_BAD_SEGMENT;
    const UdaSegment& t = table[s];
    const uint32_t r = slab_u32(row, row_stride, i);
    if ((int64_t)r >= t.records) return UDA_SLAB_BAD_ROW;
    const int64_t kl = t.key_len[r], vl = t.val_len[r];
    if (!span_inside(t.key_off[r], kl, t.data_size) ||
        !span_inside(t.val_off[r], vl, t.data_size))
      return UDA_SLAB_BAD_SPAN;
    key_len[i] = kl;
    val_len[i] = vl;
    kt += kl;
    vt += vl;
  }
  out[0] = kt;
  out[1] = vt;
  return 0;
}

// Pass 2, over the records and lengths pass 1 accepted (nothing is
// re-checked): buf = all keys then all values, key_dst / val_dst the
// offset of each inside it; buf holds key_total + val_total bytes.
void uda_slab_copy(const UdaSegment* table,
                   const uint8_t* seg, int64_t seg_stride,
                   const uint8_t* row, int64_t row_stride, int64_t n,
                   const int64_t* key_len, const int64_t* val_len,
                   int64_t key_total, uint8_t* buf,
                   int64_t* key_dst, int64_t* val_dst) {
  int64_t kp = 0, vp = key_total;
  for (int64_t i = 0; i < n; ++i) {
    const UdaSegment& t = table[slab_u32(seg, seg_stride, i)];
    const uint32_t r = slab_u32(row, row_stride, i);
    std::memcpy(buf + kp, t.data + t.key_off[r], (size_t)key_len[i]);
    std::memcpy(buf + vp, t.data + t.val_off[r], (size_t)val_len[i]);
    key_dst[i] = kp;
    val_dst[i] = vp;
    kp += key_len[i];
    vp += val_len[i];
  }
}

}  // extern "C"

// Run gather over a per-task table of run cursors: the streaming emit
// path's byte movement (uda_tpu/merger/streaming.py:interleave_runs).
// The merged rows name, for every output record, the sorted run that
// supplies it; a run is consumed strictly in file order, so one cursor
// per run — its path, a read buffer, how far it has read — is all the
// state there is, and a slab is gathered in O(records), whatever the
// run count. The runs are IFile-framed already: a record's span is read
// off its two VInt lengths and copied verbatim, so the slab's bytes are
// the records' framed spans back to back (the offset sidecars are the
// numpy path's). Host memory is one read buffer a run, filled by pread;
// a descriptor is held between fills only when the table was opened to
// keep them (few runs): a fill is rare (a buffer holds thousands of
// records), the run count is not held to the fd limit.
struct RunCursor {
  std::string path;            // empty: a run nobody staged
  int fd = -1;
  int64_t file_off = 0;        // next unread byte of the file
  int64_t data_size = 0;       // framed bytes, EOF marker excluded
  int64_t records = 0;
  int64_t consumed = 0;
  std::vector<uint8_t> buf;
  int64_t pos = 0, filled = 0;
};

struct RunTable {
  std::vector<RunCursor> runs;
  int64_t buf_bytes = 1 << 20;
  bool keep_open = false;
};

enum : int64_t {
  UDA_RUNS_BAD_RUN = -1,      // seg >= runs, or a run nobody staged
  UDA_RUNS_EXHAUSTED = -2,    // more records asked of a run than it has
  UDA_RUNS_CORRUPT = -3,      // framing runs past the run's bytes
  UDA_RUNS_IO = -4,           // open() / pread() failed or came short
};

// Make at least `want` bytes available at c.pos (compacting, growing
// the buffer for a record larger than it). 0 ok, or UDA_RUNS_*.
static int64_t run_fill(RunTable& t, RunCursor& c, int64_t want) {
  if (c.filled - c.pos >= want) return 0;
  if (c.pos > 0) {
    std::memmove(c.buf.data(), c.buf.data() + c.pos, c.filled - c.pos);
    c.filled -= c.pos;
    c.pos = 0;
  }
  const int64_t cap = std::max<int64_t>(
      want, std::min<int64_t>(t.buf_bytes, c.filled + c.data_size - c.file_off));
  if ((int64_t)c.buf.size() < cap) c.buf.resize(cap);
  int64_t to_read = std::min<int64_t>((int64_t)c.buf.size() - c.filled,
                                      c.data_size - c.file_off);
  if (c.filled + to_read < want) return UDA_RUNS_CORRUPT;
  if (c.fd < 0) c.fd = open(c.path.c_str(), O_RDONLY | O_CLOEXEC);
  if (c.fd < 0) return UDA_RUNS_IO;
  int64_t rc = 0;
  while (to_read > 0) {
    const ssize_t n = pread(c.fd, c.buf.data() + c.filled, (size_t)to_read,
                            (off_t)c.file_off);
    if (n < 0 && errno == EINTR) continue;
    if (n <= 0) {
      rc = UDA_RUNS_IO;
      break;
    }
    c.filled += n;
    c.file_off += n;
    to_read -= n;
  }
  if (!t.keep_open || c.file_off >= c.data_size) {
    close(c.fd);
    c.fd = -1;
  }
  return rc;
}

extern "C" {

// paths[i] NULL = a run nobody staged. sizes = framed bytes without the
// EOF marker (the caller checked the files against them).
void* uda_runs_open(const char* const* paths, const int64_t* records,
                    const int64_t* sizes, int64_t n, int64_t buf_bytes,
                    int32_t keep_open) {
  RunTable* t = new (std::nothrow) RunTable();
  if (!t) return nullptr;
  t->buf_bytes = std::max<int64_t>(buf_bytes, 64);
  t->keep_open = keep_open != 0;
  try {
    t->runs.resize((size_t)n);
    for (int64_t i = 0; i < n; ++i) {
      if (!paths[i]) continue;
      t->runs[i].path = paths[i];
      t->runs[i].records = records[i];
      t->runs[i].data_size = sizes[i];
    }
  } catch (const std::bad_alloc&) {
    delete t;
    return nullptr;
  }
  return t;
}

// Gather the slab's records, in order, into dst[0, cap): returns how
// many were gathered, with out[0] = bytes written. Fewer than n means
// the next record did not fit: out[1] = the bytes it needs (the caller
// grows dst and asks for the rest). A UDA_RUNS_* code on failure, with
// out[2] = the slab record it was found at (the task fails).
int64_t uda_runs_gather(void* h, const uint8_t* seg, int64_t seg_stride,
                        int64_t n, uint8_t* dst, int64_t cap,
                        int64_t out[3]) {
  RunTable& t = *static_cast<RunTable*>(h);
  int64_t written = 0;
  out[0] = out[1] = 0;
  try {
    for (int64_t i = 0; i < n; ++i) {
      out[2] = i;
      const uint32_t s = slab_u32(seg, seg_stride, i);
      if ((size_t)s >= t.runs.size() || t.runs[s].path.empty())
        return UDA_RUNS_BAD_RUN;
      RunCursor& c = t.runs[s];
      if (c.consumed >= c.records) return UDA_RUNS_EXHAUSTED;
      int64_t len = 0;
      for (;;) {     // the record's framed length, off its two VInts
        const int64_t avail = c.filled - c.pos;
        int64_t klen, vlen, want;
        const int u1 = uda::decode_vlong(c.buf.data(), c.filled, c.pos,
                                         &klen);
        const int u2 = u1 ? uda::decode_vlong(c.buf.data(), c.filled,
                                              c.pos + u1, &vlen) : 0;
        if (u2) {
          if (klen < 0 || vlen < 0 || klen > c.data_size ||
              vlen > c.data_size)
            return UDA_RUNS_CORRUPT;
          len = u1 + u2 + klen + vlen;
          if (avail >= len) break;
          want = len;
        } else {
          // the header itself is cut off: two VInts take 18 bytes at most
          if (avail >= 18) return UDA_RUNS_CORRUPT;
          want = avail + 1;     // the fill reads as much as the buffer holds
        }
        const int64_t rc = run_fill(t, c, want);
        if (rc) return rc;
      }
      if (len > cap - written) {
        out[0] = written;
        out[1] = len;
        return i;
      }
      std::memcpy(dst + written, c.buf.data() + c.pos, (size_t)len);
      written += len;
      c.pos += len;
      ++c.consumed;
    }
  } catch (const std::bad_alloc&) {
    return UDA_RUNS_IO;
  }
  out[0] = written;
  return n;
}

int64_t uda_runs_consumed(void* h, int64_t run) {
  RunTable& t = *static_cast<RunTable*>(h);
  return run >= 0 && (size_t)run < t.runs.size() ? t.runs[run].consumed : 0;
}

void uda_runs_close(void* h) {
  RunTable* t = static_cast<RunTable*>(h);
  if (!t) return;
  for (RunCursor& c : t->runs)
    if (c.fd >= 0) close(c.fd);
  delete t;
}

}  // extern "C"

// One-pass segment staging: everything the stage pool does to one
// cracked segment's keys (uda_tpu/merger/overlap.py:_prepare) in ONE
// call, so a stage worker gives up the interpreter lock once a segment
// and not once per numpy pass. For every record: the key's comparable
// content (key modes 0-3 of merge.cc / _KWAY_MODES), its first
// 4 * key_words bytes zero padded as big-endian uint32 words (mode 3:
// sign bit flipped), then content length, segment index and row index —
// the composite-key row of ops.merge.fill_run_rows — written straight
// into the row matrix, with the (words, length) order check and the
// sums staging needs taken on the way. A key longer than the carried
// width gets the same row — its first 4 * key_words bytes, its whole
// content length — and the caller keeps it: among keys of equal words
// the row order is then (length, row), which the emit of
// merger/overlap.py turns into the comparator's order a block at a
// time. The numpy path (pack_keys, run_row_order, fill_run_rows) is
// the fallback and the reference this is parity-tested against
// (tests/test_stage_native.py), oversize keys included.

// Rows not in (words, length) order: sort them whole. The last column
// is the row index, so rows are totally ordered and the result is the
// stable (words, length) order np.lexsort gives; that column then IS
// the order vector.
static bool stage_sort_rows(uint32_t* rows, int64_t n, int64_t cols) {
  try {
    std::vector<uint32_t> idx((size_t)n);
    std::iota(idx.begin(), idx.end(), 0u);
    const std::vector<uint32_t> src(rows, rows + n * cols);
    std::sort(idx.begin(), idx.end(), [&](uint32_t a, uint32_t b) {
      return std::lexicographical_compare(
          &src[a * cols], &src[a * cols] + cols,
          &src[b * cols], &src[b * cols] + cols);
    });
    for (int64_t i = 0; i < n; ++i)
      std::memcpy(rows + i * cols, &src[idx[(size_t)i] * cols],
                  (size_t)cols * sizeof(uint32_t));
    return true;
  } catch (const std::bad_alloc&) {
    return false;
  }
}

extern "C" {

enum : int64_t {
  UDA_STAGE_EMPTY_TEXT = 1,   // serialized Text key of no bytes
  UDA_STAGE_SHORT_BYTES = 2,  // BytesWritable key under its length field
  UDA_STAGE_BAD_SPAN = 3,     // key content negative or outside the data
  UDA_STAGE_NO_MEMORY = 4,    // no scratch to sort an unsorted segment
};

// rows is uint32[cap, key_words + 3], cap >= n; rows [n, cap) are set
// to 0xFFFFFFFF (ops.merge.PAD_WORD). Returns 0 with out = {1 if the
// segment arrived in (words, length) order, largest content length,
// sum of key_len + val_len}, or a UDA_STAGE_* code with out[3] = the
// record it was found at (rows are then unspecified).
int64_t uda_stage_segment(const uint8_t* data, int64_t data_size,
                          const int64_t* key_off, const int64_t* key_len,
                          const int64_t* val_len, int64_t n,
                          int32_t key_mode, int32_t key_words,
                          uint32_t seg_index, uint32_t* rows, int64_t cap,
                          int64_t out[4]) {
  const int64_t kw = key_words, cols = kw + 3, width = 4 * kw;
  int64_t longest = 0, bytes = 0;
  bool sorted = true;
  for (int64_t i = 0; i < n; ++i) {
    out[3] = i;
    int64_t off = key_off[i], len = key_len[i];
    bytes += len + val_len[i];
    if (key_mode == 1) {  // Text: skip the VInt length prefix
      if (len < 1) return UDA_STAGE_EMPTY_TEXT;
      if (off < 0 || off >= data_size) return UDA_STAGE_BAD_SPAN;
      const int8_t first = (int8_t)data[off];
      const int skip = first >= -112 ? 1
                       : first >= -120 ? -111 - first : -119 - first;
      off += skip;
      len -= skip;
    } else if (key_mode == 2) {  // BytesWritable: skip the 4-byte length
      if (len < 4) return UDA_STAGE_SHORT_BYTES;
      off += 4;
      len -= 4;
    }
    const int64_t take = std::min(len, width);
    if (len < 0 || off < 0 || take > data_size - off)
      return UDA_STAGE_BAD_SPAN;
    const uint8_t* k = data + off;
    uint32_t* r = rows + i * cols;
    for (int64_t w = 0; w < kw; ++w) {
      const int64_t have = take - 4 * w;
      uint32_t v = 0;
      if (have >= 4) {
        v = (uint32_t)k[4 * w] << 24 | (uint32_t)k[4 * w + 1] << 16 |
            (uint32_t)k[4 * w + 2] << 8 | (uint32_t)k[4 * w + 3];
      } else {
        for (int64_t b = 0; b < have; ++b)
          v |= (uint32_t)k[4 * w + b] << (24 - 8 * b);
      }
      r[w] = v;
    }
    if (key_mode == 3) r[0] ^= 0x80000000u;  // numeric order == memcmp order
    r[kw] = (uint32_t)len;
    r[kw + 1] = seg_index;
    r[kw + 2] = (uint32_t)i;
    if (len > longest) longest = len;
    if (sorted && i)  // (words, length) of the row before against this one
      sorted = !std::lexicographical_compare(r, r + kw + 1,
                                             r - cols, r - cols + kw + 1);
  }
  if (!sorted && !stage_sort_rows(rows, n, cols)) return UDA_STAGE_NO_MEMORY;
  std::fill(rows + n * cols, rows + cap * cols, 0xFFFFFFFFu);
  out[0] = sorted;
  out[1] = longest;
  out[2] = bytes;
  return 0;
}

}  // extern "C"
