// hbam_native: host-side native kernels for hadoop-bam-tpu.
//
// The reference's native layer is zlib behind java.util.zip JNI (SURVEY.md
// section 2.8).  Ours is explicit: a small C++ library doing the two serial,
// branchy jobs that belong on the host —
//   1. batched multithreaded BGZF DEFLATE inflate (feeding device batches),
//   2. BAM record-boundary walking (the block_size chain),
// leaving vectorizable decode to the TPU.  Exposed via plain C ABI for ctypes.
//
// Build: g++ -O3 -march=native -shared -fPIC -pthread hbam_native.cpp -lz
#include <algorithm>
#include <atomic>
#include <chrono>
#include <condition_variable>
#include <cstdint>
#include <cstring>
#include <deque>
#include <limits>
#include <mutex>
#include <thread>
#include <type_traits>
#include <vector>

#include <zlib.h>

#if defined(__SSE2__)
#include <emmintrin.h>
#endif

// libdeflate inflates raw DEFLATE ~2x faster than zlib; the build probes for
// it (utils/native.py) and falls back to plain zlib when absent.
#if defined(HBAM_USE_LIBDEFLATE)
#include <libdeflate.h>
#endif

namespace {

// One BGZF block header at src[p], inside src[0, n): the rules of
// formats/bgzf.py parse_block_header — gzip magic with FEXTRA, every FEXTRA
// subfield walked for the BC subfield, BSIZE covering header + footer, the
// whole block inside the buffer, ISIZE <= 64 KiB.  Returns the block's size
// and fills where its DEFLATE payload lies and its ISIZE, or -1 for a header
// it does not accept.
inline int64_t bgzf_header(const uint8_t* src, int64_t n, int64_t p,
                           int64_t* cdata_off, int32_t* cdata_len,
                           uint32_t* isize) {
  auto u16 = [&](int64_t q) {
    return static_cast<int64_t>(src[q]) | (static_cast<int64_t>(src[q + 1]) << 8);
  };
  if (n - p < 18) return -1;                           // truncated header
  if (src[p] != 0x1f || src[p + 1] != 0x8b || src[p + 2] != 0x08 ||
      src[p + 3] != 0x04) return -1;                   // bad magic / flags
  const int64_t xtra_end = p + 12 + u16(p + 10);
  if (n < xtra_end) return -1;                         // truncated FEXTRA
  int64_t bsize = -1;
  for (int64_t q = p + 12; q + 4 <= xtra_end; q += 4 + u16(q + 2)) {
    if (src[q] == 66 && src[q + 1] == 67 && u16(q + 2) == 2) {
      if (q + 6 <= n) bsize = u16(q + 4);
      break;
    }
  }
  if (bsize < 0) return -1;                            // no BC subfield
  const int64_t block_size = bsize + 1;
  if (block_size < xtra_end - p + 8) return -1;        // BSIZE too small
  if (n - p < block_size) return -1;                   // truncated body
  const int64_t end = p + block_size;
  const uint32_t isz = static_cast<uint32_t>(src[end - 4]) |
                       (static_cast<uint32_t>(src[end - 3]) << 8) |
                       (static_cast<uint32_t>(src[end - 2]) << 16) |
                       (static_cast<uint32_t>(src[end - 1]) << 24);
  if (isz > 0x10000u) return -1;                       // ISIZE > 64 KiB
  *cdata_off = xtra_end;
  *cdata_len = static_cast<int32_t>(block_size - (xtra_end - p) - 8);
  *isize = isz;
  return block_size;
}

}  // namespace

extern "C" {

// Inflate n_blocks independent raw-DEFLATE streams concurrently.
// src: the whole compressed span; cdata_off/cdata_len: per-block payload
// location; dst: output buffer; dst_off: per-block output position;
// expected_isize: per-block expected inflated size (from BGZF footers).
// Returns 0 on success, or (1000 + first failing block index).
int hbam_inflate_batch(const uint8_t* src,
                       const int64_t* cdata_off, const int32_t* cdata_len,
                       int32_t n_blocks,
                       uint8_t* dst, const int64_t* dst_off,
                       const int32_t* expected_isize,
                       int32_t n_threads) {
  if (n_threads < 1) n_threads = 1;
  std::atomic<int32_t> next(0);
  std::atomic<int32_t> fail(-1);
#if defined(HBAM_USE_LIBDEFLATE)
  auto worker = [&]() {
    libdeflate_decompressor* d = libdeflate_alloc_decompressor();
    if (!d) { fail.store(0); return; }
    for (;;) {
      int32_t i = next.fetch_add(1);
      if (i >= n_blocks || fail.load(std::memory_order_relaxed) >= 0) break;
      size_t out_n = 0;
      libdeflate_result rc = libdeflate_deflate_decompress(
          d, src + cdata_off[i], static_cast<size_t>(cdata_len[i]),
          dst + dst_off[i], static_cast<size_t>(expected_isize[i]), &out_n);
      if (rc != LIBDEFLATE_SUCCESS ||
          static_cast<int32_t>(out_n) != expected_isize[i]) {
        int32_t expect = -1;
        fail.compare_exchange_strong(expect, i);
        break;
      }
    }
    libdeflate_free_decompressor(d);
  };
#else
  auto worker = [&]() {
    z_stream zs;
    std::memset(&zs, 0, sizeof(zs));
    bool live = false;
    for (;;) {
      int32_t i = next.fetch_add(1);
      if (i >= n_blocks || fail.load(std::memory_order_relaxed) >= 0) break;
      if (!live) {
        if (inflateInit2(&zs, -15) != Z_OK) { fail.store(i); break; }
        live = true;
      } else {
        inflateReset(&zs);
      }
      zs.next_in = const_cast<Bytef*>(src + cdata_off[i]);
      zs.avail_in = static_cast<uInt>(cdata_len[i]);
      zs.next_out = dst + dst_off[i];
      zs.avail_out = static_cast<uInt>(expected_isize[i]);
      int rc = inflate(&zs, Z_FINISH);
      if (rc != Z_STREAM_END ||
          static_cast<int32_t>(zs.total_out) != expected_isize[i]) {
        int32_t expect = -1;
        fail.compare_exchange_strong(expect, i);
        break;
      }
    }
    if (live) inflateEnd(&zs);
  };
#endif
  if (n_threads == 1) {
    worker();
  } else {
    std::vector<std::thread> pool;
    pool.reserve(n_threads);
    for (int t = 0; t < n_threads; ++t) pool.emplace_back(worker);
    for (auto& th : pool) th.join();
  }
  int32_t f = fail.load();
  return f >= 0 ? 1000 + f : 0;
}

// Walk BAM record boundaries: offsets of each record's block_size field.
// buf/n: inflated bytes; start: first record offset; out/cap: output array.
// Writes record-start offsets; returns count (may be < actual if cap hit),
// or -1 on a malformed block_size.  *tail_off receives the offset of the
// first incomplete record (== n when the walk consumed everything).
int64_t hbam_walk_bam_records(const uint8_t* buf, int64_t n, int64_t start,
                              int64_t* out, int64_t cap, int64_t* tail_off) {
  int64_t p = start, count = 0;
  while (p + 4 <= n) {
    int32_t bs;
    std::memcpy(&bs, buf + p, 4);  // BAM is little-endian; so are our hosts
    if (bs < 32) return -1;
    if (p + 4 + bs > n) break;
    if (count < cap) out[count] = p;
    ++count;
    p += 4 + static_cast<int64_t>(bs);
  }
  if (tail_off) *tail_off = p;
  return count;
}

// Walk BAM record boundaries AND pack selected per-record byte ranges into a
// dense row tile in the same pass (the columnar host->device transfer layout:
// only projected columns cross the link).  sel_off/sel_len give n_sel source
// ranges within each record (all must lie inside the fixed 36-byte prefix,
// which every valid record has since block_size >= 32); they are packed
// back-to-back into rows of row_stride bytes.  The walk stops at the first
// record starting at or past ``stop`` (records there are owned by the next
// span — pass n to disable).  Callers must size cap for the worst case
// ((stop - start) / 36 + 1 records); the Python wrapper rejects overflow.
// Returns the record count, -1 on malformed input.
int64_t hbam_walk_bam_packed(const uint8_t* buf, int64_t n, int64_t start,
                             int64_t stop,
                             const int32_t* sel_off, const int32_t* sel_len,
                             int32_t n_sel, int32_t row_stride,
                             uint8_t* out_rows, int64_t* out_off, int64_t cap,
                             int64_t* tail_off) {
  int64_t p = start, count = 0;
  while (p + 4 <= n && p < stop) {
    int32_t bs;
    std::memcpy(&bs, buf + p, 4);
    if (bs < 32) return -1;
    if (p + 4 + bs > n) break;
    if (count < cap) {
      out_off[count] = p;
      uint8_t* row = out_rows + count * row_stride;
      const uint8_t* rec = buf + p;
      for (int32_t s = 0; s < n_sel; ++s) {
        std::memcpy(row, rec + sel_off[s], static_cast<size_t>(sel_len[s]));
        row += sel_len[s];
      }
    }
    ++count;
    p += 4 + static_cast<int64_t>(bs);
  }
  if (tail_off) *tail_off = p;
  return count;
}

// Walk BAM records and pack fixed prefix + sequence + quality payloads into
// dense tiles in one pass — the host side of the tensor-batch feed (bases
// and quals as fixed-stride device tiles).  Sequence bytes stay 4-bit
// packed (2 bases/byte [SPEC]); reads longer than max_len are truncated
// (full l_seq remains available in the prefix).  Output rows beyond the
// copied payload are NOT cleared — callers pass zeroed buffers.  Walk stops
// at ``stop`` as in hbam_walk_bam_packed.  Returns record count, or -1 on a
// malformed record.
int64_t hbam_walk_bam_payload(const uint8_t* buf, int64_t n, int64_t start,
                              int64_t stop, int32_t max_len,
                              int32_t seq_stride, int32_t qual_stride,
                              uint8_t* out_prefix, uint8_t* out_seq,
                              uint8_t* out_qual, int64_t* out_off,
                              int64_t cap, int64_t* tail_off) {
  int64_t p = start, count = 0;
  while (p + 4 <= n && p < stop) {
    int32_t bs;
    std::memcpy(&bs, buf + p, 4);
    if (bs < 32) return -1;
    if (p + 4 + bs > n) break;
    if (count < cap) {
      const uint8_t* rec = buf + p;
      std::memcpy(out_prefix + count * 36, rec, 36);
      uint8_t l_read_name = rec[12];
      uint16_t n_cigar;
      std::memcpy(&n_cigar, rec + 16, 2);
      int32_t l_seq;
      std::memcpy(&l_seq, rec + 20, 4);
      int64_t seq_off = 36 + static_cast<int64_t>(l_read_name) +
                        4 * static_cast<int64_t>(n_cigar);
      int64_t nb = (static_cast<int64_t>(l_seq) + 1) / 2;
      if (l_seq < 0 || seq_off + nb + l_seq > 4 + static_cast<int64_t>(bs))
        return -1;
      int32_t use = l_seq < max_len ? l_seq : max_len;
      std::memcpy(out_seq + count * seq_stride, rec + seq_off, (use + 1) / 2);
      std::memcpy(out_qual + count * qual_stride, rec + seq_off + nb, use);
      out_off[count] = p;
    }
    ++count;
    p += 4 + static_cast<int64_t>(bs);
  }
  if (tail_off) *tail_off = p;
  return count;
}

// Walk the chain of BGZF block headers in src[offset, n): the columnar
// block table of a compressed span in one call (ops/inflate.py
// block_table).  Accepts exactly the headers formats/bgzf.py
// parse_block_header accepts (``bgzf_header``) and writes one row per
// block.  Stops at the first header it does not accept, or when ``cap`` rows
// are written; *stop receives that header's offset (n after a clean walk).
// It names no fault: the caller re-parses from *stop in Python, which
// raises the error the Python walk always raised.  Returns the row count.
int64_t hbam_block_table(const uint8_t* src, int64_t n, int64_t offset,
                         int64_t* coffset, int64_t* cdata_off,
                         int32_t* cdata_len, int32_t* isize, int64_t cap,
                         int64_t* stop) {
  int64_t count = 0;
  int64_t p = offset;
  while (p < n && count < cap) {
    int64_t off;
    int32_t len;
    uint32_t isz;
    const int64_t block_size = bgzf_header(src, n, p, &off, &len, &isz);
    if (block_size < 0) break;
    coffset[count] = p;
    cdata_off[count] = off;
    cdata_len[count] = len;
    isize[count] = static_cast<int32_t>(isz);
    ++count;
    p += block_size;
  }
  if (stop) *stop = p;
  return count;
}

// CRC32 of a batch of byte ranges (BGZF block payload validation), threaded.
// Returns 0; crcs[i] receives the zlib CRC32 of data[off[i] .. off[i]+len[i]).
int hbam_crc32_batch(const uint8_t* data, const int64_t* off,
                     const int32_t* len, int32_t n, uint32_t* crcs,
                     int32_t n_threads) {
  if (n_threads < 1) n_threads = 1;
  std::atomic<int32_t> next(0);
  auto worker = [&]() {
    for (;;) {
      int32_t i = next.fetch_add(1);
      if (i >= n) break;
#if defined(HBAM_USE_LIBDEFLATE)
      crcs[i] = libdeflate_crc32(0, data + off[i],
                                 static_cast<size_t>(len[i]));
#else
      crcs[i] = static_cast<uint32_t>(
          crc32(0L, data + off[i], static_cast<uInt>(len[i])));
#endif
    }
  };
  std::vector<std::thread> pool;
  for (int t = 0; t < n_threads; ++t) pool.emplace_back(worker);
  for (auto& th : pool) th.join();
  return 0;
}

// Batched BGZF block deflate (writer path): compress n independent payloads.
// levels: zlib level; dst must have 64 KiB capacity per block at dst_off[i];
// out_len[i] receives each compressed size (header+cdata+footer are NOT
// added here — this is the raw DEFLATE payload only).
int hbam_deflate_batch(const uint8_t* src, const int64_t* src_off,
                       const int32_t* src_len, int32_t n_blocks,
                       uint8_t* dst, const int64_t* dst_off,
                       const int32_t* dst_cap, int32_t* out_len,
                       int32_t level, int32_t n_threads) {
  if (n_threads < 1) n_threads = 1;
  std::atomic<int32_t> next(0);
  std::atomic<int32_t> fail(-1);
#if defined(HBAM_USE_LIBDEFLATE)
  // libdeflate compresses ~3x faster than zlib at comparable ratios.
  // out_len[i] = 0 signals "did not fit in dst_cap" (incompressible) —
  // callers fall back to a stored block, matching the zlib-path contract
  // where oversized output is also a caller-handled condition.
  auto worker = [&]() {
    libdeflate_compressor* c = libdeflate_alloc_compressor(level);
    if (!c) { fail.store(0); return; }
    for (;;) {
      int32_t i = next.fetch_add(1);
      if (i >= n_blocks || fail.load(std::memory_order_relaxed) >= 0) break;
      size_t n = libdeflate_deflate_compress(
          c, src + src_off[i], static_cast<size_t>(src_len[i]),
          dst + dst_off[i], static_cast<size_t>(dst_cap[i]));
      out_len[i] = static_cast<int32_t>(n);
    }
    libdeflate_free_compressor(c);
  };
#else
  auto worker = [&]() {
    for (;;) {
      int32_t i = next.fetch_add(1);
      if (i >= n_blocks || fail.load(std::memory_order_relaxed) >= 0) break;
      z_stream zs;
      std::memset(&zs, 0, sizeof(zs));
      if (deflateInit2(&zs, level, Z_DEFLATED, -15, 8,
                       Z_DEFAULT_STRATEGY) != Z_OK) {
        fail.store(i);
        break;
      }
      zs.next_in = const_cast<Bytef*>(src + src_off[i]);
      zs.avail_in = static_cast<uInt>(src_len[i]);
      zs.next_out = dst + dst_off[i];
      zs.avail_out = static_cast<uInt>(dst_cap[i]);
      int rc = deflate(&zs, Z_FINISH);
      if (rc != Z_STREAM_END) {
        int32_t expect = -1;
        fail.compare_exchange_strong(expect, i);
      } else {
        out_len[i] = static_cast<int32_t>(zs.total_out);
      }
      deflateEnd(&zs);
    }
  };
#endif
  std::vector<std::thread> pool;
  for (int t = 0; t < n_threads; ++t) pool.emplace_back(worker);
  for (auto& th : pool) th.join();
  int32_t f = fail.load();
  return f >= 0 ? 1000 + f : 0;
}

// ---------------------------------------------------------------------------
// rANS 4x8 decode (CRAM 3.0 entropy codec [SPEC CRAMv3 section 13]).
// Frequency tables are parsed Python-side (once per stream); these run the
// per-symbol loops, which dominate CRAM decode time in pure Python.
// Semantics mirror formats/cram_codecs.py exactly, including byte-
// consumption order during renormalization.
// ---------------------------------------------------------------------------

static const uint32_t kRansLow = 1u << 23;
static const int kTfShift = 12;
static const uint32_t kTotMask = (1u << kTfShift) - 1;

// Order-0: 4 interleaved states over the whole output.
// buf[ptr..ptr+16) holds the 4 little-endian initial states.
int hbam_rans0_decode(const uint8_t* buf, int64_t buf_len, int64_t ptr,
                      const uint32_t* freqs, const uint32_t* cum,
                      const uint8_t* slot2sym,
                      uint8_t* out, int64_t out_size) {
  if (ptr + 16 > buf_len) return -1;
  uint64_t states[4];
  for (int j = 0; j < 4; ++j) {
    uint32_t s;
    std::memcpy(&s, buf + ptr + 4 * j, 4);
    states[j] = s;
  }
  ptr += 16;
  int64_t i = 0;
  for (; i + 4 <= out_size; i += 4) {
    for (int j = 0; j < 4; ++j) {
      uint64_t x = states[j];
      uint32_t m = static_cast<uint32_t>(x) & kTotMask;
      uint8_t s = slot2sym[m];
      out[i + j] = s;
      x = static_cast<uint64_t>(freqs[s]) * (x >> kTfShift) + m - cum[s];
      while (x < kRansLow) {
        if (ptr >= buf_len) return -1;
        x = (x << 8) | buf[ptr++];
      }
      states[j] = x;
    }
  }
  for (int j = 0; i + j < out_size; ++j) {
    uint64_t x = states[j];
    uint32_t m = static_cast<uint32_t>(x) & kTotMask;
    uint8_t s = slot2sym[m];
    out[i + j] = s;
    x = static_cast<uint64_t>(freqs[s]) * (x >> kTfShift) + m - cum[s];
    while (x < kRansLow) {
      if (ptr >= buf_len) return -1;
      x = (x << 8) | buf[ptr++];
    }
    states[j] = x;
  }
  // a well-formed stream decodes every state back to the encoder's
  // initial value; anything else is corruption (or a lying out_size)
  for (int j = 0; j < 4; ++j)
    if (states[j] != kRansLow) return -2;
  return 0;
}

// Order-1: per-context tables (freqs/cum [256*256], slot2sym [256*4096]);
// 4 states own the output quarters, stepped together in j order (the byte
// consumption order of the Python reference loop).
int hbam_rans1_decode(const uint8_t* buf, int64_t buf_len, int64_t ptr,
                      const uint32_t* freqs, const uint32_t* cum,
                      const uint8_t* slot2sym,
                      uint8_t* out, int64_t out_size) {
  if (ptr + 16 > buf_len) return -1;
  uint64_t states[4];
  for (int j = 0; j < 4; ++j) {
    uint32_t s;
    std::memcpy(&s, buf + ptr + 4 * j, 4);
    states[j] = s;
  }
  ptr += 16;
  const int64_t q = out_size >> 2;
  int64_t idx[4] = {0, q, 2 * q, 3 * q};
  const int64_t ends[4] = {q, 2 * q, 3 * q, out_size};
  int ctxs[4] = {0, 0, 0, 0};
  bool done_all = false;
  while (!done_all) {
    done_all = true;
    for (int j = 0; j < 4; ++j) {
      if (idx[j] >= ends[j]) continue;
      uint64_t x = states[j];
      uint32_t m = static_cast<uint32_t>(x) & kTotMask;
      int ctx = ctxs[j];
      uint8_t s = slot2sym[static_cast<int64_t>(ctx) * 4096 + m];
      out[idx[j]] = s;
      const int64_t t = static_cast<int64_t>(ctx) * 256 + s;
      x = static_cast<uint64_t>(freqs[t]) * (x >> kTfShift) + m - cum[t];
      while (x < kRansLow) {
        if (ptr >= buf_len) return -1;
        x = (x << 8) | buf[ptr++];
      }
      states[j] = x;
      ctxs[j] = s;
      if (++idx[j] < ends[j]) done_all = false;
    }
  }
  for (int j = 0; j < 4; ++j)
    if (states[j] != kRansLow) return -2;
  return 0;
}

// Decode n ITF8 varints (CRAM spec 2.3: leading-ones byte count; the
// 5-byte form keeps only the low 4 bits of its final byte) from buf into
// out.  Returns bytes consumed, or -1 if the stream ends mid-value.
// One C pass replaces the per-value Python loop in CRAM series decode.
long long hbam_itf8_decode_batch(const unsigned char* buf,
                                 long long buf_len, long long n,
                                 int32_t* out) {
  long long p = 0;
  for (long long i = 0; i < n; ++i) {
    if (p >= buf_len) return -1;
    unsigned b0 = buf[p];
    uint32_t v;
    int extra;
    if (b0 < 0x80)      { v = b0;        extra = 0; }
    else if (b0 < 0xC0) { v = b0 & 0x3F; extra = 1; }
    else if (b0 < 0xE0) { v = b0 & 0x1F; extra = 2; }
    else if (b0 < 0xF0) { v = b0 & 0x0F; extra = 3; }
    else                { v = b0 & 0x0F; extra = 4; }
    if (p + 1 + extra > buf_len) return -1;
    if (extra == 4) {
      v = (v << 28) | ((uint32_t)buf[p + 1] << 20)
        | ((uint32_t)buf[p + 2] << 12) | ((uint32_t)buf[p + 3] << 4)
        | (buf[p + 4] & 0x0F);
    } else {
      for (int j = 1; j <= extra; ++j) v = (v << 8) | buf[p + j];
    }
    out[i] = (int32_t)v;
    p += 1 + extra;
  }
  return p;
}

}  // extern "C"

// ---------------------------------------------------------------------------
// BCF GT -> ALT dosage (formats/bcf_columns.py): the per-sample reduction of
// one record's genotype vector, the semantics of _gt_group_dosage and
// formats/bcf.scan_variant_columns.  Per entry g of a width whose minimum is
// MISS and whose end-of-vector value is EOV = MISS + 1:
//   present = g != EOV
//   missing = present and (g >> 1 == 0 or g == MISS)   -- g in {0, 1, MISS}:
//             allele index (g >> 1) - 1 < 0, phase bit masked ('0|.' is 1)
//   alt     = present and (g >> 1) > 1                 -- g >= 4
// and per sample: -1 with no present entry or any missing one, else the
// count of alt entries, clamped to 127.
// ---------------------------------------------------------------------------
namespace {

template <typename T>
inline T gt_load(const uint8_t* p) {       // GT vectors sit at any offset
  T v;
  std::memcpy(&v, p, sizeof(T));
  return v;
}

// One record's row.  P > 0 is a ploidy known at compile time (1 and 2: what
// call sets use): the entry loop unrolls and the sample loop is branch-free
// in the genotype's own width, so the compiler vectorises it over samples.
// P == 0 takes any ``ploidy`` (EOV-padded mixed, polyploid) a sample at a
// time, the ALT count held wider than the int8 it is clamped into.
template <typename T, int P>
void gt_dosage_row(const uint8_t* g, int64_t ns, int32_t ploidy,
                   int8_t* out) {
  using Acc = typename std::conditional<P != 0, T, int32_t>::type;
  constexpr T kMiss = std::numeric_limits<T>::min();
  constexpr T kEov = kMiss + 1;
  const int32_t np = P ? P : ploidy;
  for (int64_t s = 0; s < ns; ++s) {
    Acc present = 0, missing = 0, n_alt = 0;
    for (int32_t k = 0; k < np; ++k) {
      const T v = gt_load<T>(g + (s * np + k) * sizeof(T));
      present |= static_cast<Acc>(v != kEov);
      missing |= static_cast<Acc>((v == kMiss) | (v == 0) | (v == 1));
      n_alt += static_cast<Acc>(v >= 4);
    }
    out[s] = static_cast<int8_t>(
        (present & ~missing & 1) ? (n_alt > 127 ? 127 : n_alt) : -1);
  }
}

using GtRowFn = void (*)(const uint8_t*, int64_t, int32_t, int8_t*);

// the row loop of a width for a ploidy the records state
template <typename T>
inline GtRowFn gt_row_for(int32_t ploidy) {
  return ploidy == 2 ? gt_dosage_row<T, 2>
       : ploidy == 1 ? gt_dosage_row<T, 1> : gt_dosage_row<T, 0>;
}

template <typename T>
void gt_dosage_rows(const uint8_t* buf, const int64_t* offs,
                    const int64_t* rows, int64_t n, int32_t ploidy,
                    int64_t ns, int8_t* out, int64_t out_stride) {
  const GtRowFn row = gt_row_for<T>(ploidy);
  for (int64_t i = 0; i < n; ++i)
    row(buf + offs[i], ns, ploidy, out + rows[i] * out_stride);
}

}  // namespace

extern "C" {

// ALT dosage of one GT layout group of a BCF span: record i keeps ploidy x
// n_sample little-endian genotypes of BCF type ``typ`` (1 int8, 2 int16,
// 3 int32) at buf[offs[i]] and gets n_sample int8 dosages in row rows[i]
// of the [out_rows, out_stride] matrix; columns past n_sample are left as
// they are.  The inner loop is chosen from (typ, ploidy), which the
// records state.  No threads: the callers' pool threads run it with the
// interpreter lock released.  Every extent is checked before anything is
// written: returns 0, -1 for a layout it cannot take, or 1 + the index of
// the first record whose payload or row lies outside its buffer.
int64_t hbam_bcf_gt_dosage(const uint8_t* buf, int64_t buf_len,
                           const int64_t* offs, const int64_t* rows,
                           int64_t n, int32_t typ, int32_t ploidy,
                           int64_t n_sample, int8_t* out, int64_t out_rows,
                           int64_t out_stride) {
  // a ploidy past 2^24 or 2^32 samples is no BCF record's (and would
  // overflow the extent below)
  if (typ < 1 || typ > 3 || ploidy < 0 || ploidy > (1 << 24) ||
      n_sample < 0 || n_sample > out_stride ||
      n_sample > (int64_t{1} << 32) || n < 0 || buf_len < 0 || out_rows < 0)
    return -1;
  const int64_t extent = (typ == 3 ? 4 : typ) * ploidy * n_sample;
  for (int64_t i = 0; i < n; ++i) {
    if (offs[i] < 0 || offs[i] > buf_len || extent > buf_len - offs[i] ||
        rows[i] < 0 || rows[i] >= out_rows)
      return 1 + i;
  }
  if (typ == 1)
    gt_dosage_rows<int8_t>(buf, offs, rows, n, ploidy, n_sample, out,
                           out_stride);
  else if (typ == 2)
    gt_dosage_rows<int16_t>(buf, offs, rows, n, ploidy, n_sample, out,
                            out_stride);
  else
    gt_dosage_rows<int32_t>(buf, offs, rows, n, ploidy, n_sample, out,
                            out_stride);
  return 0;
}

}  // extern "C"

// ---------------------------------------------------------------------------
// The BCF record walker: the length-prefixed structure of BCF2 records
// [SPEC BCF2.2] walked a record at a time, for the three places a scan walks
// one — the span read's frame chase (split/vcf_planners.py::_chase_frames,
// formats/bcf_columns.py::frame_record_starts), the columnar decode of a
// framed span (formats/bcf_columns.py::_cursor_walk + _gt_group_dosage) and
// the split guesser's candidate test (split/bcf_guesser.py::
// _plausible_offsets + _chain_ok).  The NumPy / Python code named there is
// the statement of the semantics, the oracle these are tested against and
// the path of a host without this library.  No threads: the callers' pool
// threads run them with the interpreter lock released.
// ---------------------------------------------------------------------------
namespace {

template <typename T>
inline T bcf_load(const uint8_t* p) {
  T v;
  std::memcpy(&v, p, sizeof(T));
  return v;
}

// what hbam_bcf_span_columns returns: 0, "declined" (geometry the columnar
// path leaves to the record scanner), or the check that failed
enum : int64_t {
  kBcfOk = 0,
  kBcfDeclined = 1,
  kBcfArgs = -1,            // arguments the kernel cannot take
  kBcfStartRange = -2,      // record start out of range
  kBcfSharedShort = -3,     // shared block shorter than its fixed fields
  kBcfTruncated = -4,       // record runs past the buffer
  kBcfDescOverrun = -5,     // typed-value descriptor overruns record
  kBcfExtOverrun = -6,      // extended count overruns record
  kBcfExtMalformed = -7,    // malformed extended-count scalar
  kBcfNegCount = -8,        // negative typed-value count
  kBcfUnknownType = -9,     // reserved typed-value type code
  kBcfValueOverrun = -10,   // typed value overruns record
  kBcfAlleleNotChar = -11,  // allele is not a char vector
  kBcfAlleleOverrun = -12,  // allele overruns record
  kBcfFilterOverrun = -13,  // FILTER vector overruns record
  kBcfFmtKey = -14,         // malformed FORMAT key
  kBcfFmtKeyOverrun = -15,  // FORMAT key overruns record
  kBcfFmtDataOverrun = -16, // FORMAT data overruns record
};

// element byte width a typed-value type code [SPEC BCF2.2 6.3.3]; -1 marks
// the reserved codes
constexpr int8_t kBcfElemSize[16] = {0, 1, 2, 4, -1, 4, -1, 1,
                                     -1, -1, -1, -1, -1, -1, -1, -1};

inline bool bcf_is_int(int32_t typ) { return typ >= 1 && typ <= 3; }

// sign-extended typed int of an int type at p (its bytes checked by the caller)
inline int64_t bcf_typed_int(const uint8_t* p, int32_t typ) {
  return typ == 1 ? bcf_load<int8_t>(p)
       : typ == 2 ? bcf_load<int16_t>(p) : bcf_load<int32_t>(p);
}

struct BcfDesc {
  int64_t count;
  int32_t typ;
  int64_t q;        // the cursor after the descriptor
};

// One typed-value descriptor at q (formats/bcf_columns.py::_read_descriptor):
// count and type, the real count read from the typed scalar int that follows
// where the nibble says 15.  0, or the check that failed.
inline int64_t bcf_descriptor(const uint8_t* b, int64_t q, int64_t rec_end,
                              BcfDesc* d) {
  if (q >= rec_end) return kBcfDescOverrun;
  d->count = b[q] >> 4;
  d->typ = b[q] & 0x0F;
  d->q = q + 1;
  if (d->count == 15) {
    if (q + 1 >= rec_end) return kBcfExtOverrun;
    const int32_t etyp = b[q + 1] & 0x0F;
    if ((b[q + 1] >> 4) != 1 || !bcf_is_int(etyp)) return kBcfExtMalformed;
    const int64_t esize = kBcfElemSize[etyp];
    if (q + 2 + esize > rec_end) return kBcfExtOverrun;
    d->count = bcf_typed_int(b + q + 2, etyp);
    if (d->count < 0) return kBcfNegCount;
    d->q = q + 2 + esize;
  }
  return 0;
}

// what one record's walk found past its fixed fields
struct BcfWalked {
  bool snp, pass;
  int32_t gt_typ;           // 0: no GT vector
  int64_t gt_count, gt_off;
};

// The typed-value walk of one record (formats/bcf_columns.py::_cursor_walk, a
// record at a time): ID skipped, alleles -> the SNP test, FILTER -> PASS,
// INFO jumped by l_shared, the FORMAT keys walked to GT.  Every cursor is
// checked against the record's end before the byte is read.
inline int64_t bcf_walk_record(const uint8_t* b, int64_t start,
                               int64_t end_shared, int64_t rec_end,
                               int64_t n_allele, int64_t n_fmt,
                               int64_t n_sample, int64_t gt_key,
                               BcfWalked* w) {
  BcfDesc d;
  int64_t rc = bcf_descriptor(b, start + 32, rec_end, &d);        // ID
  if (rc) return rc;
  if (kBcfElemSize[d.typ] < 0) return kBcfUnknownType;
  int64_t q = d.q + kBcfElemSize[d.typ] * d.count;
  if (q > rec_end) return kBcfValueOverrun;

  bool snp = n_allele >= 2;
  for (int64_t k = 0; k < n_allele; ++k) {
    if ((rc = bcf_descriptor(b, q, rec_end, &d))) return rc;
    if (d.typ != 7) return kBcfAlleleNotChar;
    if (d.q + d.count > rec_end) return kBcfAlleleOverrun;
    // REF only needs length 1; an ALT must also be a base
    bool ok = d.count == 1;
    if (ok && k > 0) {
      const uint8_t base = b[d.q];
      ok = base == 'A' || base == 'C' || base == 'G' || base == 'T' ||
           base == 'N';
    }
    snp = snp && ok;
    q = d.q + d.count;
  }
  w->snp = snp;

  if ((rc = bcf_descriptor(b, q, rec_end, &d))) return rc;       // FILTER
  if (kBcfElemSize[d.typ] < 0) return kBcfUnknownType;
  if (d.q + kBcfElemSize[d.typ] * d.count > rec_end)
    return kBcfFilterOverrun;
  // PASS == exactly the one int value 0
  w->pass = bcf_is_int(d.typ) && d.count == 1 &&
            bcf_typed_int(b + d.q, d.typ) == 0;

  w->gt_typ = 0;
  w->gt_count = w->gt_off = 0;
  q = end_shared;
  // an n_fmt that overruns the block is tolerated as the record path
  // tolerates it: the walk stops at the block's end
  for (int64_t j = 0; j < n_fmt && q < rec_end; ++j) {
    if ((rc = bcf_descriptor(b, q, rec_end, &d))) return rc;
    if (!bcf_is_int(d.typ) || d.count != 1) return kBcfFmtKey;
    if (d.q + kBcfElemSize[d.typ] > rec_end) return kBcfFmtKeyOverrun;
    const int64_t key = bcf_typed_int(b + d.q, d.typ);
    if ((rc = bcf_descriptor(b, d.q + kBcfElemSize[d.typ], rec_end, &d)))
      return rc;
    if (kBcfElemSize[d.typ] < 0) return kBcfUnknownType;
    // 4 x 2^31 x 2^24 at most: no overflow
    const int64_t data_len = kBcfElemSize[d.typ] * d.count * n_sample;
    if (data_len > rec_end - d.q) return kBcfFmtDataOverrun;
    if (gt_key >= 0 && key == gt_key && bcf_is_int(d.typ) && n_sample > 0) {
      w->gt_typ = d.typ;
      w->gt_count = d.count;
      w->gt_off = d.q;
    }
    q = d.q + data_len;
  }
  return 0;
}

// formats/bcf.py::plausible_record_start (the caller has p + 32 <= n)
inline bool bcf_plausible(const uint8_t* b, int64_t p, int64_t n_contigs) {
  const uint32_t l_shared = bcf_load<uint32_t>(b + p);
  const uint32_t l_indiv = bcf_load<uint32_t>(b + p + 4);
  if (l_shared < 24 || l_shared > (1u << 24) || l_indiv > (1u << 24))
    return false;
  const int32_t chrom = bcf_load<int32_t>(b + p + 8);
  if (chrom < 0 || chrom >= n_contigs) return false;
  if (bcf_load<int32_t>(b + p + 12) < -1 || bcf_load<int32_t>(b + p + 16) < 0)
    return false;
  return bcf_load<uint16_t>(b + p + 26) <= 1024;          // n_allele
}

}  // namespace

extern "C" {

// The chase over the l_shared / l_indiv prefixes (split/vcf_planners.py::
// _chase_frames): from ``from``, every record that starts before n0 and lies
// whole in buf[0, buf_len).  Writes the starts (``starts`` may be null: it
// only counts), ``*end`` = where the chase stopped (the end of the last whole
// record) and ``*need`` = 0, or the buffer length the record at ``*end``
// needs: its 8-byte header, or its whole body — the caller grows the buffer
// and calls again from ``*end``.  Returns the number of records, -1 for
// arguments it cannot take, -2 when ``cap`` starts do not hold them.
int64_t hbam_bcf_chase(const uint8_t* buf, int64_t buf_len, int64_t from,
                       int64_t n0, int64_t* starts, int64_t cap,
                       int64_t* end, int64_t* need) {
  if (buf_len < 0 || from < 0 || cap < 0) return -1;
  int64_t p = from, n = 0;
  *need = 0;
  while (p < n0) {
    if (p > buf_len - 8) { *need = p + 8; break; }
    const int64_t stop = p + 8 + int64_t{bcf_load<uint32_t>(buf + p)} +
                         int64_t{bcf_load<uint32_t>(buf + p + 4)};
    if (stop > buf_len) { *need = stop; break; }
    if (starts) {
      if (n >= cap) return -2;
      starts[n] = p;
    }
    ++n;
    p = stop;
  }
  *end = p;
  return n;
}

// A framed BCF span -> the columns of formats/bcf_columns.py::
// decode_bcf_columns, in one call: record i starts at buf[starts[i]]; its 24
// fixed bytes give chrom, pos (1-based), rlen, qual (the typed MISSING float
// -> NaN), n_allele, n_fmt; bcf_walk_record gives flags (bit 0 PASS, bit 1
// SNP) and where its GT vector lies; and the GT vector is reduced to row i
// of ``dosage`` [n, stride] by the loop hbam_bcf_gt_dosage runs for the
// record's own (type, ploidy) — every byte of the row written once, -1 past
// n_sample and throughout a record with no GT.  ``gt_key`` is "GT" in the
// header's string dictionary, or negative.
//
// The order of checks is the NumPy walk's: every record's frame first (start
// in range, l_shared >= 24, the record inside the buffer), then the geometry
// the lockstep walk declines (max_allele / max_fmt rounds), then every
// record's typed values, and last the GT geometry it declines (max_ploidy,
// more samples than the row).  So the same input gives the same answer:
// kBcfOk, kBcfDeclined (nothing useful written; the caller's record scanner
// reads the span), or the negative check that failed with info[0] = the
// record.  info[1] = records with a GT vector.
int64_t hbam_bcf_span_columns(
    const uint8_t* buf, int64_t buf_len, const int64_t* starts, int64_t n,
    int64_t gt_key, int64_t max_allele, int64_t max_fmt, int64_t max_ploidy,
    int32_t* chrom, int32_t* pos, int32_t* rlen, float* qual,
    int16_t* n_allele, int16_t* n_fmt, uint8_t* flags, int8_t* dosage,
    int64_t stride, int64_t* info) {
  if (buf_len < 0 || n < 0 || stride < 0) return kBcfArgs;
  info[0] = info[1] = 0;
  bool declined = false;
  for (int64_t i = 0; i < n; ++i) {
    const int64_t s = starts[i];
    info[0] = i;
    if (s < 0 || s > buf_len - 32) return kBcfStartRange;
    const int64_t l_shared = bcf_load<uint32_t>(buf + s);
    if (l_shared < 24) return kBcfSharedShort;
    if (s + 8 + l_shared + int64_t{bcf_load<uint32_t>(buf + s + 4)} > buf_len)
      return kBcfTruncated;
    declined |= bcf_load<uint16_t>(buf + s + 26) > max_allele ||
                buf[s + 31] > max_fmt;
  }
  if (declined) return kBcfDeclined;

  int64_t n_gt = 0;
  for (int64_t i = 0; i < n; ++i) {
    const int64_t s = starts[i];
    const uint8_t* r = buf + s;
    const int64_t end_shared = s + 8 + int64_t{bcf_load<uint32_t>(r)};
    const int64_t rec_end = end_shared + int64_t{bcf_load<uint32_t>(r + 4)};
    const uint32_t ns_nf = bcf_load<uint32_t>(r + 28);
    const int64_t n_sample = ns_nf & 0xFFFFFF;
    const int64_t na = bcf_load<uint16_t>(r + 26), nf = ns_nf >> 24;
    BcfWalked w;
    const int64_t rc = bcf_walk_record(buf, s, end_shared, rec_end, na, nf,
                                       n_sample, gt_key, &w);
    if (rc) { info[0] = i; return rc; }
    chrom[i] = bcf_load<int32_t>(r + 8);
    pos[i] = static_cast<int32_t>(bcf_load<uint32_t>(r + 12) + 1u);
    rlen[i] = bcf_load<int32_t>(r + 16);
    uint32_t qbits = bcf_load<uint32_t>(r + 20);
    if (qbits == 0x7F800001u) qbits = 0x7FC00000u;    // MISSING -> NaN
    std::memcpy(qual + i, &qbits, 4);
    n_allele[i] = static_cast<int16_t>(na);
    n_fmt[i] = static_cast<int16_t>(nf);
    flags[i] = static_cast<uint8_t>((w.pass ? 1 : 0) | (w.snp ? 2 : 0));
    if (declined) continue;             // only the checks still count
    int8_t* row = dosage + i * stride;
    if (!w.gt_typ) {
      std::memset(row, 0xFF, static_cast<size_t>(stride));
      continue;
    }
    ++n_gt;
    if (w.gt_count > max_ploidy || n_sample > stride) {
      declined = true;
      continue;
    }
    const int32_t ploidy = static_cast<int32_t>(w.gt_count);
    const GtRowFn gt_row = w.gt_typ == 1 ? gt_row_for<int8_t>(ploidy)
                         : w.gt_typ == 2 ? gt_row_for<int16_t>(ploidy)
                                         : gt_row_for<int32_t>(ploidy);
    gt_row(buf + w.gt_off, n_sample, ploidy, row);
    std::memset(row + n_sample, 0xFF, static_cast<size_t>(stride - n_sample));
  }
  info[1] = n_gt;
  return declined ? kBcfDeclined : kBcfOk;
}

// The split guesser's candidate test (split/bcf_guesser.py::_find_record):
// the smallest offset u in data[0, min(first_len, n - 32)) that passes the
// plausibility sweep (_plausible_offsets: sane block lengths, CHROM inside
// the contig dictionary, POS >= -1, rlen >= 0) and from which a chain of
// ``min_chain`` records validates (_chain_ok).  ``partial``: the window
// reaches EOF, so a chain must end exactly at its end.  -1 where there is
// none.  One scan with an early exit: a real record start is found after a
// record's length of candidates.  ``*edge`` = 1 where the answer leaned on
// the window's end (a chain that reached it, a candidate too near it to be
// tested): a longer window of the same bytes could answer otherwise.  With
// ``*edge`` 0 it could not, whatever ``partial`` is — what lets the caller
// ask a short window first.
int64_t hbam_bcf_guess(const uint8_t* data, int64_t n, int64_t first_len,
                       int64_t n_contigs, int32_t min_chain, int32_t partial,
                       int32_t* edge) {
  const int64_t hi = first_len < n - 32 ? first_len : n - 32;
  *edge = 0;
  for (int64_t u = 0; u < hi; ++u) {
    // the sweep's mask is a little stricter than the chain's test on the
    // block lengths (< 2^24 where that allows 2^24): a candidate passes both
    if (bcf_load<uint32_t>(data + u) >= (1u << 24) ||
        bcf_load<uint32_t>(data + u + 4) >= (1u << 24))
      continue;
    int64_t p = u;
    int32_t count = 0;
    bool ok = true;
    while (count < min_chain) {
      if (p == n) { *edge = 1; ok = count >= 1 || partial; break; }
      if (p + 32 > n) { *edge = 1; ok = !partial && count >= 1; break; }
      if (!bcf_plausible(data, p, n_contigs)) { ok = false; break; }
      const int64_t nxt = p + 8 + int64_t{bcf_load<uint32_t>(data + p)} +
                          int64_t{bcf_load<uint32_t>(data + p + 4)};
      if (nxt > n) { *edge = 1; ok = !partial && count >= 1; break; }
      p = nxt;
      ++count;
    }
    if (ok) return u;
  }
  if (hi < first_len) *edge = 1;
  return -1;
}

}  // extern "C"

// ---------------------------------------------------------------------------
// VCF text -> line bounds + ALT dosage in one pass over the bytes (the text
// variant feed's tokenise; parallel/variant_pipeline.py::_vcf_tokenize_numpy
// is the NumPy twin, ``_pack_variant_tiles_from_text_scalar`` the statement
// of the semantics).  A line ends at '\n' (a '\r' before it is part of its
// last field, as ``bytes.split`` has it); an empty line, a '#' line and a
// line of fewer than eight fields are no record.  No threads: the callers'
// pool threads run it with the interpreter lock released.
// ---------------------------------------------------------------------------
namespace {

// Bit j set where p[j] is a tab, j < 64.
inline uint64_t tab_mask64(const uint8_t* p) {
#if defined(__SSE2__)
  const __m128i tab = _mm_set1_epi8('\t');
  uint64_t m = 0;
  for (int j = 0; j < 4; ++j) {
    const __m128i v =
        _mm_loadu_si128(reinterpret_cast<const __m128i*>(p + 16 * j));
    m |= uint64_t{static_cast<uint16_t>(
             _mm_movemask_epi8(_mm_cmpeq_epi8(v, tab)))}
         << (16 * j);
  }
  return m;
#else
  uint64_t m = 0;
  for (int j = 0; j < 64; ++j) m |= uint64_t{p[j] == '\t'} << j;
  return m;
#endif
}

// The GT that leads the keyed cell at g, the block ending at ``end``:
// ``a/b`` or ``a|b`` of two digits gives (a > 0) + (b > 0); a '.' in
// either half, or a bare '.', gives -1 (a no-call); -2 for any other GT
// (a multi-digit allele, a haploid call, an empty cell, a '\r' after a last
// bare GT).
inline int keyed_gt(const uint8_t* g, const uint8_t* end) {
  const int64_t room = end - g;
  if (room >= 3 && (g[1] == '/' || g[1] == '|') &&
      (room == 3 || g[3] == ':' || g[3] == '\t')) {
    const unsigned a = static_cast<unsigned>(g[0]) - '0',
                   b = static_cast<unsigned>(g[2]) - '0';
    if (a <= 9 && b <= 9) return (a > 0) + (b > 0);
    if ((a <= 9 || g[0] == '.') && (b <= 9 || g[2] == '.')) return -1;
    return -2;
  }
  if (room >= 1 && g[0] == '.' &&
      (room == 1 || g[1] == ':' || g[1] == '\t'))
    return -1;
  return -2;
}

// A keyed sample block [g, end) — the cells of a line whose FORMAT is ``GT:``
// and more keys, as every caller writes them (``0/1:12,9:21:99:230,0,310``)
// — read for its dosages: a cell starts the block or follows a tab, and its
// GT is its bytes up to the first ':' (``keyed_gt``).  Work follows the
// bytes: a 64-byte stretch's tabs found at once, then each cell's few GT
// bytes; AD/DP/GQ/PL are never read one by one.  Returns the no-call cells
// of a block of exactly ``n_sample`` cells whose GTs all read, -1 for any
// other block: the caller's scalar parse reads that line, and ``out`` holds
// nothing.
int64_t vcf_keyed_walk(const uint8_t* g, const uint8_t* end, int64_t n_sample,
                       int8_t* out) {
  int64_t cells = 0, nocall = 0;
  auto cell = [&](const uint8_t* c) {
    if (cells == n_sample) return false;       // more cells than samples
    const int d = keyed_gt(c, end);
    out[cells++] = static_cast<int8_t>(d);
    nocall += d == -1;
    return d > -2;
  };
  if (!cell(g)) return -1;
  const int64_t len = end - g;
  int64_t at = 0;
  for (; at + 64 <= len; at += 64) {
    for (uint64_t m = tab_mask64(g + at); m; m &= m - 1)
      if (!cell(g + at + __builtin_ctzll(m) + 1)) return -1;
  }
  for (; at < len; ++at)
    if (g[at] == '\t' && !cell(g + at + 1)) return -1;
  return cells == n_sample ? nocall : -1;
}

// One line of text[0, n) from s: its first nine tabs into t (*nt of them) and
// its end *e (the '\n', or n).  Returns where the next line starts.
inline int64_t vcf_line(const uint8_t* text, int64_t n, int64_t s, int64_t* t,
                        int32_t* nt, int64_t* e) {
  // the first nine tabs, then the line end from the last of them on: a '\n'
  // met first ends the line and the hunt
  int32_t k = 0;
  int64_t at = s, end = -1;
  while (k < 9) {
    const uint8_t* p = text + at;
    const uint8_t* stop = text + n;
    while (p < stop && *p != '\t' && *p != '\n') ++p;     // ~150 bytes a line
    if (p == stop) { end = n; break; }
    if (*p == '\n') { end = p - text; break; }
    t[k++] = p - text;
    at = p - text + 1;
  }
  if (end < 0) {
    const void* nl = std::memchr(text + at, '\n', static_cast<size_t>(n - at));
    end = nl ? static_cast<const uint8_t*>(nl) - text : n;
  }
  *nt = k;
  *e = end;
  return end + 1;
}

// A line [s, e) with nt tabs is a record: not empty, not '#', >= 8 fields.
inline bool vcf_record(const uint8_t* text, int64_t s, int64_t e, int32_t nt) {
  return e != s && text[s] != '#' && nt >= 7;
}

// The record lines of text[0, n).
inline int64_t vcf_count_records(const uint8_t* text, int64_t n) {
  int64_t rows = 0;
  for (int64_t pos = 0; pos < n;) {
    const int64_t s = pos;
    int64_t t[9], e;
    int32_t nt;
    pos = vcf_line(text, n, s, t, &nt, &e);
    rows += vcf_record(text, s, e, nt);
  }
  return rows;
}

// The dosage row ``out`` [stride] of a record line (tabs t, nt of them, end
// e), as hbam_vcf_tokenize states it.  Returns 1 where the row is final, 0
// where the caller's scalar parse has to read the line (``out`` then holds
// nothing it may read); *nocall = the no-call cells of a keyed line whose row
// is final, -1 for any other line.
inline uint8_t vcf_dosage_row(const uint8_t* text, const int64_t* t,
                              int32_t nt, int64_t e, int64_t n_sample,
                              int8_t* out, int64_t stride, int64_t* nocall) {
  *nocall = -1;
  // FORMAT is field 8: [t[7] + 1, t[8]); sample fields need the ninth tab
  const bool has_gt = n_sample > 0 && nt == 9 && t[8] - t[7] - 1 >= 2 &&
                      text[t[7] + 1] == 'G' && text[t[7] + 2] == 'T';
  if (!has_gt) {
    std::memset(out, 0xFF, static_cast<size_t>(stride));
    return 1;
  }
  if (t[8] - t[7] - 1 > 2 && text[t[7] + 3] == ':') {
    const int64_t nc = vcf_keyed_walk(text + t[8] + 1, text + e, n_sample, out);
    if (nc < 0) return 0;
    std::memset(out + n_sample, 0xFF, static_cast<size_t>(stride - n_sample));
    *nocall = nc;
    return 1;
  }
  if (t[8] - t[7] - 1 != 2 || e - t[8] - 1 != 4 * n_sample - 1) return 0;
  const uint8_t* g = text + t[8] + 1;
  uint32_t bad = 0;
  for (int64_t i = 0; i + 1 < n_sample; ++i) {     // it vectorises
    uint32_t v;
    std::memcpy(&v, g + 4 * i, 4);
    const uint32_t c0 = v & 0xFF, c1 = (v >> 8) & 0xFF,
                   c2 = (v >> 16) & 0xFF, c3 = v >> 24;
    bad |= static_cast<uint32_t>(c0 - '0' > 9u) |
           static_cast<uint32_t>(c2 - '0' > 9u) |
           static_cast<uint32_t>((c1 != '/') & (c1 != '|')) |
           static_cast<uint32_t>(c3 != '\t');
    out[i] = static_cast<int8_t>((c0 > '0') + (c2 > '0'));
  }
  const uint8_t* last = g + 4 * (n_sample - 1);
  const uint32_t c0 = last[0], c1 = last[1], c2 = last[2];
  bad |= static_cast<uint32_t>(c0 - '0' > 9u) |
         static_cast<uint32_t>(c2 - '0' > 9u) |
         static_cast<uint32_t>((c1 != '/') & (c1 != '|'));
  out[n_sample - 1] = static_cast<int8_t>((c0 > '0') + (c2 > '0'));
  std::memset(out + n_sample, 0xFF, static_cast<size_t>(stride - n_sample));
  return !bad;
}

// The contig table (hbam_contig_table): FNV-1a over a name's bytes, linear
// probing over a power-of-two slot array of contig indices, -1 empty.
inline uint64_t contig_hash(const uint8_t* p, int64_t len) {
  uint64_t h = 0xcbf29ce484222325ull;
  for (int64_t i = 0; i < len; ++i) h = (h ^ p[i]) * 0x100000001b3ull;
  return h;
}

inline int64_t contig_slot(const uint8_t* names, const int64_t* off,
                           const int32_t* slots, int64_t mask,
                           const uint8_t* p, int64_t len) {
  for (int64_t i = static_cast<int64_t>(contig_hash(p, len)) & mask;;
       i = (i + 1) & mask) {
    const int32_t c = slots[i];
    if (c < 0 || (off[c + 1] - off[c] == len &&
                  std::memcmp(names + off[c], p, static_cast<size_t>(len)) == 0))
      return i;
  }
}

// POS [p, q) by formats' decimal rule (parallel/variant_pipeline.py::
// _fixed_field_columns): 1 to 10 digits whose value fits int32, else the
// line is odd (the scalar parse reads it, or raises what it raises).
inline bool vcf_pos(const uint8_t* p, const uint8_t* q, int32_t* pos) {
  const int64_t len = q - p;
  if (len <= 0 || len > 10) return false;
  int64_t v = 0;
  for (; p < q; ++p) {
    const unsigned d = static_cast<unsigned>(*p) - '0';
    if (d > 9) return false;
    v = v * 10 + d;
  }
  if (v > std::numeric_limits<int32_t>::max()) return false;
  *pos = static_cast<int32_t>(v);
  return true;
}

// ALT [p, q) is single ACGTN bases joined by commas.
inline bool vcf_snp_alt(const uint8_t* p, const uint8_t* q) {
  const int64_t len = q - p;
  if (len % 2 == 0) return false;
  for (int64_t j = 0; j < len; ++j) {
    const uint8_t c = p[j];
    const bool ok = j % 2 ? c == ',' :
        (c == 'A' || c == 'C' || c == 'G' || c == 'T' || c == 'N');
    if (!ok) return false;
  }
  return true;
}

}  // namespace

extern "C" {

// Every record line of text[0, n), in order, into row i of
//   bounds [cap, 11] : the line's start, its first nine tabs (the line's end
//                      where it has fewer), its end (the '\n', or n);
//   ntab   [cap]     : tabs found, nine at most;
//   bulk   [cap]     : 1 where the row of ``dosage`` is final, 0 where the
//                      line's sample fields are not a shape read here and
//                      the caller's scalar parse has to read the line;
//   dosage [cap, stride] int8 : -1 in every column of a line with no FORMAT
//                      that starts ``GT``; of a line whose FORMAT is exactly
//                      ``GT`` and whose sample block is n_sample cells
//                      ``digit sep digit`` (sep '/' or '|') joined by tabs —
//                      4 n_sample - 1 bytes, the shape of nearly every line
//                      of a phased call set — (a > 0) + (b > 0) a sample;
//                      of a keyed line — FORMAT ``GT:`` and more keys, the
//                      shape of every line a caller such as GATK writes —
//                      what ``vcf_keyed_walk`` reads, no-calls as -1; -1 in
//                      the columns past n_sample.  A row with bulk 0 holds
//                      nothing the caller may read.
//   counts [2]       : where not null, += the keyed lines with bulk 1 and
//                      the no-call cells in them.
// The branch is chosen once a line from FORMAT: a ``GT`` line takes the
// fixed-stride loop, a keyed line the walk.  Work follows the bytes: nine
// ``memchr`` for tabs, one for the line end and one pass over the sample
// block a line, whatever n_sample is.  Returns the number of records, -1
// for arguments it cannot take, -2 when ``cap`` rows do not hold them
// (nothing outside the arrays is written either way).  With ``bounds`` null
// it only counts the records: what sizes the arrays.
int64_t hbam_vcf_tokenize(const uint8_t* text, int64_t n, int64_t n_sample,
                          int64_t* bounds, int32_t* ntab, uint8_t* bulk,
                          int8_t* dosage, int64_t stride, int64_t cap,
                          int64_t* counts) {
  if (n < 0 || cap < 0 || n_sample < 0 || n_sample > stride ||
      n_sample > (int64_t{1} << 40))
    return -1;
  if (!bounds) return vcf_count_records(text, n);
  int64_t rows = 0;
  for (int64_t pos = 0; pos < n;) {
    const int64_t s = pos;
    int64_t t[9], e;
    int32_t nt;
    pos = vcf_line(text, n, s, t, &nt, &e);
    if (!vcf_record(text, s, e, nt)) continue;
    if (rows >= cap) return -2;
    int64_t* b = bounds + rows * 11;
    b[0] = s;
    for (int k = 0; k < 9; ++k) b[1 + k] = k < nt ? t[k] : e;
    b[10] = e;
    ntab[rows] = nt;
    int64_t nocall;
    bulk[rows] = vcf_dosage_row(text, t, nt, e, n_sample,
                                dosage + rows * stride, stride, &nocall);
    if (nocall >= 0 && counts) { counts[0] += 1; counts[1] += nocall; }
    ++rows;
  }
  return rows;
}

// The contig table of a header, built once (utils/native.py::contig_table):
// contig c is names[off[c], off[c + 1]); slots [n_slots], a power of two
// over n, gets the index of each distinct name — the later of two equal
// names, as a ``{name: index}`` dict has it — and -1 elsewhere.  Returns 0,
// or -1 for arguments it cannot take.
int64_t hbam_contig_table(const uint8_t* names, const int64_t* off,
                          int64_t n, int32_t* slots, int64_t n_slots) {
  if (n < 0 || n_slots <= n || (n_slots & (n_slots - 1)) ||
      n > std::numeric_limits<int32_t>::max())
    return -1;
  std::fill(slots, slots + n_slots, -1);
  for (int64_t c = 0; c < n; ++c)
    slots[contig_slot(names, off, slots, n_slots - 1, names + off[c],
                      off[c + 1] - off[c])] = static_cast<int32_t>(c);
  return 0;
}

// A span's text -> the stats columns of parallel/variant_pipeline.py::
// pack_variant_tiles_from_text in one pass over the lines, the walk and the
// dosage rows of hbam_vcf_tokenize with the fixed fields beside them.  Row i
// of a record line gets
//   chrom [cap] i32 : CHROM's index in the contig table (names, off, slots;
//                     hbam_contig_table), -1 for a name it does not hold;
//   pos   [cap] i32 : POS, 1 to 10 digits that fit int32;
//   flags [cap] u8  : bit 0 FILTER exactly ``PASS``; bit 1 REF one base and
//                     ALT single ``ACGTN`` bases joined by commas, at any
//                     ALT width;
//   dosage [cap, stride] i8 : hbam_vcf_tokenize's row.
// A line whose POS is no such number, or whose row hbam_vcf_tokenize leaves
// to the scalar parse, is refused: refused [cap, 3] gets (row, the line's
// start, its end) and its columns hold nothing the caller may read.
// counts [3] = the keyed lines not refused, their no-call cells, the refused
// lines.  Returns the number of records, -1 for arguments it cannot take, -2
// when ``cap`` rows do not hold them.  With ``chrom`` null it only counts the
// records.  No threads: the callers' pool threads run it with the
// interpreter lock released.
int64_t hbam_vcf_span_columns(
    const uint8_t* text, int64_t n, int64_t n_sample, const uint8_t* names,
    const int64_t* off, const int32_t* slots, int64_t n_slots, int32_t* chrom,
    int32_t* pos, uint8_t* flags, int8_t* dosage, int64_t stride,
    int64_t* refused, int64_t cap, int64_t* counts) {
  if (n < 0 || cap < 0 || n_sample < 0 || n_sample > stride ||
      n_sample > (int64_t{1} << 40) || n_slots < 1 ||
      (n_slots & (n_slots - 1)))
    return -1;
  if (!chrom) return vcf_count_records(text, n);
  int64_t rows = 0, keyed = 0, nocalls = 0, n_refused = 0;
  int64_t last_s = 0, last_len = -1;        // the CHROM of the line before
  int32_t last_c = -1;
  for (int64_t p = 0; p < n;) {
    const int64_t s = p;
    int64_t t[9], e;
    int32_t nt;
    p = vcf_line(text, n, s, t, &nt, &e);
    if (!vcf_record(text, s, e, nt)) continue;
    if (rows >= cap) return -2;
    const int64_t clen = t[0] - s;
    if (clen != last_len ||
        std::memcmp(text + s, text + last_s, static_cast<size_t>(clen))) {
      const int64_t slot =
          contig_slot(names, off, slots, n_slots - 1, text + s, clen);
      last_c = slots[slot];
      last_s = s;
      last_len = clen;
    }
    chrom[rows] = last_c;
    bool ok = vcf_pos(text + t[0] + 1, text + t[1], pos + rows);
    const bool pass = t[6] - t[5] - 1 == 4 &&
                      std::memcmp(text + t[5] + 1, "PASS", 4) == 0;
    const bool snp = t[3] - t[2] - 1 == 1 &&
                     vcf_snp_alt(text + t[3] + 1, text + t[4]);
    flags[rows] = static_cast<uint8_t>((pass ? 1 : 0) | (snp ? 2 : 0));
    int64_t nocall;
    ok &= vcf_dosage_row(text, t, nt, e, n_sample, dosage + rows * stride,
                         stride, &nocall) != 0;
    if (!ok) {
      int64_t* r = refused + n_refused * 3;
      r[0] = rows;
      r[1] = s;
      r[2] = e;
      ++n_refused;
    } else if (nocall >= 0) {
      ++keyed;
      nocalls += nocall;
    }
    ++rows;
  }
  counts[0] = keyed;
  counts[1] = nocalls;
  counts[2] = n_refused;
  return rows;
}

}  // extern "C"

// ---------------------------------------------------------------------------
// A BGZF text span from one positioned read to the lines it owns (split/
// vcf_planners.py::_lease_bgzf_text; ``_inflate_text_python``,
// ``_prev_block_last_byte`` and ``_owned_text`` are the statement of the
// rules).  A line belongs to the span that holds its first byte.
// ---------------------------------------------------------------------------
namespace {

// One block's payload inflated into out[0, isize); false where it does not.
inline bool inflate_block_at(const uint8_t* raw, int64_t cdata_off,
                             int32_t cdata_len, uint32_t isize, uint8_t* out) {
  const int64_t dst_off = 0;
  const int32_t want = static_cast<int32_t>(isize);
  return hbam_inflate_batch(raw, &cdata_off, &cdata_len, 1, out, &dst_off,
                            &want, 1) == 0;
}

// The last inflated byte of the block that ends exactly at raw[at], -1 where
// there is none or it is empty: the candidates of formats/bgzf.py
// find_block_starts_numpy in raw[0, at) (gzip magic, 18 header bytes before
// ``at``, XLEN 6 with the BC subfield first or XLEN 7..255) in order, the
// first whose header parses, whose block ends at ``at`` and inflates.
inline int bgzf_prev_last_byte(const uint8_t* raw, int64_t n_raw,
                               int64_t at) {
  std::vector<uint8_t> block;
  for (int64_t i = 0; i + 18 <= at; ++i) {
    const void* hit = std::memchr(raw + i, 0x1f, static_cast<size_t>(at - 17 - i));
    if (!hit) break;
    i = static_cast<const uint8_t*>(hit) - raw;
    if (raw[i + 1] != 0x8b || raw[i + 2] != 0x08 || raw[i + 3] != 0x04)
      continue;
    const int64_t xlen = raw[i + 10] | (int64_t{raw[i + 11]} << 8);
    const bool standard = xlen == 6 && raw[i + 12] == 66 &&
                          raw[i + 13] == 67 && raw[i + 14] == 2 &&
                          raw[i + 15] == 0;
    if (!standard && !(xlen > 6 && xlen < 256)) continue;
    int64_t cdata_off;
    int32_t cdata_len;
    uint32_t isize;
    const int64_t size =
        bgzf_header(raw, n_raw, i, &cdata_off, &cdata_len, &isize);
    if (size < 0 || i + size != at) continue;
    block.resize(isize + 1);
    if (!inflate_block_at(raw, cdata_off, cdata_len, isize, block.data()))
      continue;
    return isize ? block[isize - 1] : -1;
  }
  return -1;
}

}  // namespace

extern "C" {

// raw[0, n_raw) holds the file's bytes from some offset on: the span's blocks
// start at raw[at] and follow each other while one starts before
// raw[at + want]; the block that ends at raw[at] (where at > 0) lies in
// raw[0, at); the file ends at raw[file_end].  The read
//   1. walks the span's block headers (``bgzf_header``);
//   2. inflates the span's blocks into out[0, base_len), one after another;
//   3. where at > 0 and base_len > 0, finds the last byte of the block that
//      ends at raw[at] (``bgzf_prev_last_byte``): a byte other than '\n'
//      means the first line began before the span and is not its own;
//   4. ends the span's text at its last line's end: at base_len where
//      out[base_len - 1] is '\n', else after the first '\n' of the blocks
//      after the span, inflated one by one behind base_len while they lie
//      whole in raw and fit in ``out``, or at the file's end;
//   5. counts the record lines (``vcf_record``) of the owned text.
// info [6] = total (the inflated bytes in ``out``), base_len, lo, hi
// (out[lo, hi) is the owned text), records, resume (where in raw the blocks
// not read yet start).  Returns
//    0  done;
//    1  ``out`` is null or shorter than base_len: info[0, 1] only, nothing
//       inflated — the caller leases base_len bytes and some room for the
//       blocks after the span, and calls again;
//    2  the span's last line runs on past what raw holds or ``out`` can
//       take: out[0, total) holds the text read so far and info[2] lo; the
//       caller reads on from ``resume`` block by block to find hi, and
//       counts the records;
//   -1  a block header of the span does not parse, -2 a block does not
//       inflate: the caller's Python read raises what it raises.
// No threads: the callers' pool threads run it with the interpreter lock
// released.
int64_t hbam_vcf_text_span_read(const uint8_t* raw, int64_t n_raw,
                                int64_t at, int64_t want, int64_t file_end,
                                uint8_t* out, int64_t out_cap,
                                int64_t* info) {
  if (n_raw < 0 || at < 0 || want < 0 || at + want > n_raw) return -1;
  std::vector<int64_t> cdata_off, dst_off;
  std::vector<int32_t> cdata_len, isize;
  int64_t base_len = 0;
  int64_t p = at;
  while (p < at + want) {
    int64_t off;
    int32_t len;
    uint32_t isz;
    const int64_t size = bgzf_header(raw, n_raw, p, &off, &len, &isz);
    if (size < 0) return -1;
    cdata_off.push_back(off);
    cdata_len.push_back(len);
    isize.push_back(static_cast<int32_t>(isz));
    dst_off.push_back(base_len);
    base_len += isz;
    p += size;
  }
  info[0] = info[1] = base_len;
  info[2] = info[3] = info[4] = 0;
  info[5] = p;
  if (!out || out_cap < base_len) return 1;
  if (!isize.empty() &&
      hbam_inflate_batch(raw, cdata_off.data(), cdata_len.data(),
                         static_cast<int32_t>(isize.size()), out,
                         dst_off.data(), isize.data(), 1))
    return -2;
  if (base_len == 0) return 0;
  int64_t lo = 0;
  if (at > 0) {
    const int prev = bgzf_prev_last_byte(raw, n_raw, at);
    if (prev >= 0 && prev != '\n') {
      const void* nl = std::memchr(out, '\n', static_cast<size_t>(base_len));
      lo = nl ? static_cast<const uint8_t*>(nl) - out + 1 : base_len;
      if (lo >= base_len) return 0;              // one line covers the span
    }
  }
  info[2] = lo;
  int64_t total = base_len, hi = base_len;
  if (out[base_len - 1] != '\n') {
    for (;;) {
      if (p >= file_end) {                       // the file ends in the line
        hi = total;
        break;
      }
      int64_t off;
      int32_t len;
      uint32_t isz;
      const int64_t size = bgzf_header(raw, n_raw, p, &off, &len, &isz);
      if (size < 0 || out_cap - total < isz) {
        info[0] = total;
        info[5] = p;
        return 2;
      }
      if (!inflate_block_at(raw, off, len, isz, out + total)) return -2;
      const void* nl = std::memchr(out + total, '\n', isz);
      total += isz;
      p += size;
      if (nl) {
        hi = static_cast<const uint8_t*>(nl) - out + 1;
        break;
      }
    }
  }
  info[0] = total;
  info[3] = hi;
  info[4] = vcf_count_records(out + lo, hi - lo);
  info[5] = p;
  return 0;
}

}  // extern "C"

// ---------------------------------------------------------------------------
// FASTQ text -> payload tiles in one pass (the text reads' tokenise + 4-bit
// pack; api/read_datasets.py::fastq_text_to_payload_tiles is the NumPy twin
// and the oracle).  The line rules are the twin's ``_scan_lines``: a line
// ends at '\n', one '\r' before it is not part of it, a last line with no
// '\n' counts unless it is empty once its '\r' is gone.  No threads: the
// callers' pool threads run it with the interpreter lock released.
// ---------------------------------------------------------------------------
namespace {

// The line that starts at ``pos`` of text[0, n): [pos, *end) without its
// line end; returns where the next line starts, or -1 where there is no
// line at ``pos``.
inline int64_t fastq_line(const uint8_t* text, int64_t n, int64_t pos,
                          int64_t* end) {
  if (pos >= n) return -1;
  const void* nl = std::memchr(text + pos, '\n', static_cast<size_t>(n - pos));
  const int64_t e = nl ? static_cast<const uint8_t*>(nl) - text : n;
  *end = e - (e > pos && text[e - 1] == '\r');
  if (!nl && *end == pos) return -1;     // a last line of "\r" alone
  return nl ? e + 1 : n;
}

}  // namespace

extern "C" {

// Lines of text[0, n) by the rules above (a FASTQ chunk holds lines / 4
// records: the caller sizes the tiles from it).
int64_t hbam_fastq_count_lines(const uint8_t* text, int64_t n) {
  if (n <= 0) return 0;
  int64_t lines = 0;
  for (int64_t i = 0; i < n;) {         // 32-bit lanes a block: it vectorises
    const int64_t m = n - i < 4096 ? n - i : 4096;
    uint32_t c = 0;
    for (int64_t j = 0; j < m; ++j) c += text[i + j] == '\n';
    lines += c;
    i += m;
  }
  if (text[n - 1] == '\n') return lines;
  const bool lone_cr = text[n - 1] == '\r' && (n == 1 || text[n - 2] == '\n');
  return lines + !lone_cr;
}

// Every record of the FASTQ text[0, n) into row i of three tiles: seq
// [rows, seq_stride] (``nibble[base]`` codes, two a byte, the first in the
// high nibble), qual [rows, qual_stride] (the byte less ``qual_offset``,
// not below 0), lengths [rows] = min(read length, max_len); what a row
// keeps is cut at max_len and at its stride, and every byte of every row is
// written.  Returns 0 when text[0, n) was exactly ``rows`` records, else
// what it refuses, rows written before it left as they are:
//   -1 arguments it cannot take          -2 lines that are not 4 x rows
//   -3 a record without its '@' or '+'   -4 SEQ and QUAL of unequal length
//   -5 (``guard`` set) a quality outside Phred 0..93 anywhere in a field.
// Nothing is read outside text[0, n) or written outside the three tiles.
int32_t hbam_fastq_tokenize(const uint8_t* text, int64_t n,
                            const uint8_t* nibble, int64_t max_len,
                            int64_t qual_offset, int32_t guard,
                            uint8_t* seq, int64_t seq_stride, uint8_t* qual,
                            int64_t qual_stride, int32_t* lengths,
                            int64_t rows) {
  if (n < 0 || rows < 0 || max_len < 0 || max_len > INT32_MAX ||
      seq_stride < 0 || qual_stride < 0 || qual_offset < 0 ||
      qual_offset > 255)
    return -1;
  const uint8_t off = static_cast<uint8_t>(qual_offset);
  int64_t pos = 0;
  for (int64_t i = 0;; ++i) {
    int64_t s[4], e[4];
    for (int k = 0; k < 4; ++k) {
      s[k] = pos;
      pos = fastq_line(text, n, pos, &e[k]);
      if (pos < 0) return k == 0 && i == rows ? 0 : -2;
    }
    if (i >= rows) return -2;
    if (text[s[0]] != '@' || text[s[2]] != '+') return -3;
    const int64_t len = e[1] - s[1];
    if (len != e[3] - s[3]) return -4;
    const uint8_t* b = text + s[1];
    const uint8_t* q = text + s[3];
    if (guard) {
      uint8_t bad = 0;
      for (int64_t j = 0; j < len; ++j)
        bad |= static_cast<uint8_t>(q[j] < off) |
               static_cast<uint8_t>(q[j] - off > 93);
      if (bad) return -5;
    }
    const int64_t keep = len < max_len ? len : max_len;
    lengths[i] = static_cast<int32_t>(keep);
    uint8_t* srow = seq + i * seq_stride;
    const int64_t pairs = keep / 2 < seq_stride ? keep / 2 : seq_stride;
    for (int64_t j = 0; j < pairs; ++j)
      srow[j] = static_cast<uint8_t>(nibble[b[2 * j]] << 4 | nibble[b[2 * j + 1]]);
    int64_t sk = pairs;
    if ((keep & 1) && sk < seq_stride)
      srow[sk++] = static_cast<uint8_t>(nibble[b[keep - 1]] << 4);
    std::memset(srow + sk, 0, static_cast<size_t>(seq_stride - sk));
    uint8_t* qrow = qual + i * qual_stride;
    const int64_t qk = keep < qual_stride ? keep : qual_stride;
    for (int64_t j = 0; j < qk; ++j)
      qrow[j] = q[j] > off ? static_cast<uint8_t>(q[j] - off) : 0;
    std::memset(qrow + qk, 0, static_cast<size_t>(qual_stride - qk));
  }
}

}  // extern "C"

// ---------------------------------------------------------------------------
// DEFLATE decoded INSIDE one gzip member, on many cores (the stream of
// split/read_planners.py; Rapidgzip, arXiv 2308.08955; pugz): a sequencer's
// .fastq.gz is one member with no index, so a worker that starts mid-member
// knows neither where a block begins nor the 32 KiB of text before it.
//   hbam_deflate_find_block   the next bit at which a dynamic-Huffman block
//                             header parses to two valid prefix codes;
//   hbam_deflate_decode_symbols  a decoder of this repo's own that writes
//                             16-bit symbols: a byte, or 256 + k where a match
//                             reaches position k of the window it was given
//                             as unknown;
//   hbam_deflate_resolve      symbols -> bytes through the true window, one
//                             table look-up a symbol, and the bytes' CRC32.
// No threads of their own: the callers' threads run them with the
// interpreter lock released.
// ---------------------------------------------------------------------------
namespace {

constexpr int kGzWindow = 32768;
constexpr int kGzLitRoot = 10, kGzDistRoot = 8, kGzPreRoot = 7;
constexpr int kGzLitCap = 1024 + 1024, kGzDistCap = 256 + 1024;

// A table entry: bits 0-4 the code's length, 5-7 its kind, 8-12 the extra
// bits that follow (a subtable's index bits for kGzSub), 16-31 the payload:
// a literal, a length's or distance's base, a subtable's offset.
enum { kGzLit = 0, kGzLen = 1, kGzEob = 2, kGzSub = 3, kGzBad = 4 };

constexpr uint32_t gz_entry(int kind, int extra, int payload) {
  return static_cast<uint32_t>(kind) << 5 | static_cast<uint32_t>(extra) << 8 |
         static_cast<uint32_t>(payload) << 16;
}

struct GzSymbols {
  uint32_t litlen[288], dist[32], pre[19];
  GzSymbols() {
    static const uint16_t lbase[29] = {3, 4, 5, 6, 7, 8, 9, 10, 11, 13, 15, 17,
        19, 23, 27, 31, 35, 43, 51, 59, 67, 83, 99, 115, 131, 163, 195, 227,
        258};
    static const uint8_t lext[29] = {0, 0, 0, 0, 0, 0, 0, 0, 1, 1, 1, 1, 2, 2,
        2, 2, 3, 3, 3, 3, 4, 4, 4, 4, 5, 5, 5, 5, 0};
    static const uint16_t dbase[30] = {1, 2, 3, 4, 5, 7, 9, 13, 17, 25, 33, 49,
        65, 97, 129, 193, 257, 385, 513, 769, 1025, 1537, 2049, 3073, 4097,
        6145, 8193, 12289, 16385, 24577};
    static const uint8_t dext[30] = {0, 0, 0, 0, 1, 1, 2, 2, 3, 3, 4, 4, 5, 5,
        6, 6, 7, 7, 8, 8, 9, 9, 10, 10, 11, 11, 12, 12, 13, 13};
    for (int s = 0; s < 256; ++s) litlen[s] = gz_entry(kGzLit, 0, s);
    litlen[256] = gz_entry(kGzEob, 0, 0);
    for (int s = 257; s < 286; ++s)
      litlen[s] = gz_entry(kGzLen, lext[s - 257], lbase[s - 257]);
    litlen[286] = litlen[287] = gz_entry(kGzBad, 0, 0);
    for (int s = 0; s < 30; ++s) dist[s] = gz_entry(kGzLen, dext[s], dbase[s]);
    dist[30] = dist[31] = gz_entry(kGzBad, 0, 0);
    for (int s = 0; s < 19; ++s) pre[s] = gz_entry(kGzLit, 0, s);
  }
};

const GzSymbols& gz_symbols() {
  static const GzSymbols s;
  return s;
}

// What zlib's inflate_table accepts of a set of code lengths: never an
// over-subscribed one; an incomplete one only as a single code of length 1
// (or, ``may_be_empty``, no code at all).  Fills count[1..15].
bool gz_code_ok(const uint8_t* lens, int n, int* count, bool may_be_empty) {
  for (int l = 0; l <= 15; ++l) count[l] = 0;
  for (int s = 0; s < n; ++s) ++count[lens[s]];
  if (count[0] == n) return may_be_empty;
  int left = 1, used = 0, max_len = 0;
  for (int l = 1; l <= 15; ++l) {
    left = (left << 1) - count[l];
    if (left < 0) return false;
    if (count[l]) { max_len = l; used += count[l]; }
  }
  return left == 0 || (max_len == 1 && used == 1);
}

inline uint32_t gz_reverse(uint32_t code, int len) {
  uint32_t r = 0;
  for (int i = 0; i < len; ++i) { r = r << 1 | (code & 1); code >>= 1; }
  return r;
}

// The decode table of one canonical prefix code: ``root`` bits index the
// primary table, longer codes go through a subtable a prefix.  ``entries``
// gives each symbol's kind, extra bits and payload.  False for a set of
// lengths gz_code_ok refuses (or a table that would not fit ``cap``).
bool gz_build(const uint8_t* lens, int n, int root, const uint32_t* entries,
              bool may_be_empty, uint32_t* table, int cap) {
  int count[16];
  if (!gz_code_ok(lens, n, count, may_be_empty)) return false;
  const uint32_t bad = gz_entry(kGzBad, 0, 0) | 1;
  for (int i = 0; i < (1 << root); ++i) table[i] = bad;
  int offs[17];
  offs[1] = 0;
  for (int l = 1; l <= 15; ++l) offs[l + 1] = offs[l] + count[l];
  const int m = offs[16];
  uint16_t sym[288];
  uint8_t slen[288];
  uint16_t code[288];
  {
    int at[16];
    for (int l = 1; l <= 15; ++l) at[l] = offs[l];
    for (int s = 0; s < n; ++s)
      if (lens[s]) { sym[at[lens[s]]] = static_cast<uint16_t>(s);
                     slen[at[lens[s]]++] = lens[s]; }
    uint32_t c = 0;
    int prev = m ? slen[0] : 0;
    for (int i = 0; i < m; ++i) {
      c <<= (slen[i] - prev);
      prev = slen[i];
      code[i] = static_cast<uint16_t>(c++);
    }
  }
  int i = 0;
  for (; i < m && slen[i] <= root; ++i) {
    const uint32_t e = entries[sym[i]] | slen[i];
    for (uint32_t x = gz_reverse(code[i], slen[i]); x < (1u << root);
         x += 1u << slen[i])
      table[x] = e;
  }
  int used = 1 << root;
  while (i < m) {
    const int prefix = code[i] >> (slen[i] - root);
    int j = i;
    while (j < m && (code[j] >> (slen[j] - root)) == prefix) ++j;
    const int sub_bits = slen[j - 1] - root;
    if (used + (1 << sub_bits) > cap) return false;
    uint32_t* sub = table + used;
    for (int x = 0; x < (1 << sub_bits); ++x) sub[x] = bad;
    table[gz_reverse(prefix, root)] =
        gz_entry(kGzSub, sub_bits, used) | static_cast<uint32_t>(root);
    for (; i < j; ++i) {
      const int rem = slen[i] - root;
      const uint32_t e = entries[sym[i]] | slen[i];
      for (uint32_t x = gz_reverse(code[i] & ((1u << rem) - 1), rem);
           x < (1u << sub_bits); x += 1u << rem)
        sub[x] = e;
    }
    used += 1 << sub_bits;
  }
  return true;
}

// The next symbol of a table built by gz_build, its bits dropped.
#define GZ_LOOKUP(e, table, root, b)                                        \
  do {                                                                      \
    (e) = (table)[(b).buf & ((1u << (root)) - 1)];                          \
    if ((((e) >> 5) & 7) == kGzSub)                                         \
      (e) = (table)[((e) >> 16) +                                           \
                    (((b).buf >> (root)) & ((1u << (((e) >> 8) & 31)) - 1))]; \
    (b).drop((e) & 31);                                                     \
  } while (0)

// Bits of src[0, n) from any bit offset, least significant first.  Past the
// end it reads zeros and counts them (``over`` bytes): ``exhausted()`` says
// a bit that was consumed was one of those.
struct GzBits {
  const uint8_t* base;
  const uint8_t* next;
  const uint8_t* end;
  uint64_t buf;
  int cnt;
  int64_t over;

  void seek(const uint8_t* src, int64_t n, int64_t bit) {
    base = src;
    end = src + n;
    next = src + ((bit >> 3) < n ? (bit >> 3) : n);
    buf = 0;
    cnt = 0;
    over = (bit >> 3) < n ? 0 : (bit >> 3) - n;
    refill();
    drop(static_cast<int>(bit & 7));
  }
  inline void refill() {
    if (end - next >= 8) {
      uint64_t w;
      std::memcpy(&w, next, 8);      // little-endian hosts, as the BAM walk
      buf |= w << cnt;
      const int nb = (63 - cnt) >> 3;
      next += nb;
      cnt += nb << 3;
    } else {
      while (cnt <= 56) {
        if (next < end) buf |= static_cast<uint64_t>(*next++) << cnt;
        else ++over;
        cnt += 8;
      }
    }
  }
  inline uint32_t peek(int k) const {
    return static_cast<uint32_t>(buf) & ((1u << k) - 1);
  }
  inline void drop(int k) { buf >>= k; cnt -= k; }
  inline int64_t bitpos() const { return ((next - base) + over) * 8 - cnt; }
  inline bool exhausted() const { return over * 8 > cnt; }
};

// The body of a dynamic block's header after its three first bits: HLIT,
// HDIST, HCLEN, the code-length code and the two sets of lengths it spells
// (lens[0, nlen) and lens[nlen, nlen + ndist)), refused wherever zlib's
// inflate refuses them.  The caller checks ``b.exhausted()``.
bool gz_read_dynamic(GzBits& b, uint8_t* lens, int* nlen, int* ndist) {
  static const uint8_t order[19] = {16, 17, 18, 0, 8, 7, 9, 6, 10, 5, 11, 4,
                                    12, 3, 13, 2, 14, 1, 15};
  b.refill();
  *nlen = static_cast<int>(b.peek(5)) + 257;
  b.drop(5);
  *ndist = static_cast<int>(b.peek(5)) + 1;
  b.drop(5);
  const int ncode = static_cast<int>(b.peek(4)) + 4;
  b.drop(4);
  if (*nlen > 286 || *ndist > 30) return false;
  uint8_t pre[19] = {0};
  for (int i = 0; i < ncode; ++i) {
    if (b.cnt < 3) b.refill();
    pre[order[i]] = static_cast<uint8_t>(b.peek(3));
    b.drop(3);
  }
  uint32_t table[1 << kGzPreRoot];
  if (!gz_build(pre, 19, kGzPreRoot, gz_symbols().pre, false, table,
                1 << kGzPreRoot))
    return false;
  const int total = *nlen + *ndist;
  int have = 0;
  while (have < total) {
    if (b.cnt < 16) b.refill();
    uint32_t e;
    GZ_LOOKUP(e, table, kGzPreRoot, b);
    if (((e >> 5) & 7) != kGzLit) return false;
    const int s = static_cast<int>(e >> 16);
    if (s < 16) { lens[have++] = static_cast<uint8_t>(s); continue; }
    int rep;
    uint8_t fill = 0;
    if (s == 16) {
      if (!have) return false;
      fill = lens[have - 1];
      rep = 3 + static_cast<int>(b.peek(2));
      b.drop(2);
    } else if (s == 17) {
      rep = 3 + static_cast<int>(b.peek(3));
      b.drop(3);
    } else {
      rep = 11 + static_cast<int>(b.peek(7));
      b.drop(7);
    }
    if (have + rep > total) return false;
    while (rep--) lens[have++] = fill;
  }
  return lens[256] != 0;             // a block has to be able to end
}

struct GzTables {
  uint32_t lit[kGzLitCap];
  uint32_t dist[kGzDistCap];
};

bool gz_build_block(const uint8_t* lens, int nlen, int ndist, GzTables* t) {
  return gz_build(lens, nlen, kGzLitRoot, gz_symbols().litlen, false, t->lit,
                  kGzLitCap) &&
         gz_build(lens + nlen, ndist, kGzDistRoot, gz_symbols().dist, true,
                  t->dist, kGzDistCap);
}

const GzTables& gz_fixed_tables() {
  static const GzTables* fixed = [] {
    uint8_t lens[320];
    for (int s = 0; s < 288; ++s)
      lens[s] = s < 144 ? 8 : s < 256 ? 9 : s < 280 ? 7 : 8;
    for (int s = 0; s < 32; ++s) lens[288 + s] = 5;
    GzTables* t = new GzTables();
    gz_build_block(lens, 288, 32, t);
    return t;
  }();
  return *fixed;
}

}  // namespace

extern "C" {

// The first bit offset in [from_bit, until_bit) of src[0, n) at which a
// non-final dynamic-Huffman block header parses whole: BFINAL 0, BTYPE 2,
// HLIT and HDIST in range, a complete code-length code, two sets of lengths
// inflate would accept, an end-of-block code.  Stored and fixed blocks are
// not found; a header cut by the end of src is none.  -1: none.
int64_t hbam_deflate_find_block(const uint8_t* src, int64_t n,
                                int64_t from_bit, int64_t until_bit) {
  // which of a byte's eight bit offsets open with BFINAL 0, BTYPE 2 (the
  // bits 0, 0, 1), by the ten bits from the byte's first
  static const uint8_t* const opens = [] {
    uint8_t* t = new uint8_t[1024];
    for (int w = 0; w < 1024; ++w) {
      t[w] = 0;
      for (int k = 0; k < 8; ++k)
        if (((w >> k) & 7) == 4) t[w] |= static_cast<uint8_t>(1 << k);
    }
    return t;
  }();
  // the code space three code lengths of 3 bits each take, of 128
  static const uint16_t* const space3 = [] {
    uint16_t* t = new uint16_t[512];
    for (int w = 0; w < 512; ++w)
      t[w] = static_cast<uint16_t>(((128 >> (w & 7)) & 127) +
                                   ((128 >> ((w >> 3) & 7)) & 127) +
                                   ((128 >> (w >> 6)) & 127));
    return t;
  }();
  if (from_bit < 0) from_bit = 0;
  if (until_bit > n * 8) until_bit = n * 8;
  GzBits b;
  uint8_t lens[320];
  int count[16];
  for (int64_t byte = from_bit >> 3; byte * 8 < until_bit; ++byte) {
    uint32_t word = 0;
    if (byte + 4 <= n) std::memcpy(&word, src + byte, 4);
    else std::memcpy(&word, src + byte, static_cast<size_t>(n - byte));
    for (uint32_t at = opens[word & 1023]; at; at &= at - 1) {
      const int k = __builtin_ctz(at);
      const int64_t p = byte * 8 + k;
      if (p < from_bit || p >= until_bit) continue;
      const uint32_t w = word >> k;   // 25 bits or more of the header
      if (((w >> 3) & 31) > 29 || ((w >> 8) & 31) > 29) continue;
      // the code-length code's lengths, 3 bits each, have to fill the
      // code space exactly: nearly every false start ends here
      const int64_t q = p + 17;
      if ((q >> 3) + 8 <= n) {
        uint64_t x;
        std::memcpy(&x, src + (q >> 3), 8);
        x >>= (q & 7);                // 57 bits: 19 lengths at most
        x &= (uint64_t{1} << (3 * (((w >> 13) & 15) + 4))) - 1;
        int space = 0;
        for (; x; x >>= 9) space += space3[x & 511];
        if (space != 128) continue;
      }
      b.seek(src, n, p + 3);
      int nlen, ndist;
      if (!gz_read_dynamic(b, lens, &nlen, &ndist) || b.exhausted())
        continue;
      if (gz_code_ok(lens, nlen, count, false) &&
          gz_code_ok(lens + nlen, ndist, count, true))
        return p;
    }
  }
  return -1;
}

// Decode DEFLATE blocks of src[0, n) from ``start_bit`` (a block's first
// bit) into 16-bit symbols.  ``out`` holds kGzWindow symbols of window —
// the caller writes them: 256 + k at position k where the text before the
// block is unknown, the bytes themselves where it is known (``window_len``
// of them are real: a match may not reach further back) — then room for
// ``cap`` symbols and 16 of slack.  A match copies symbols, so what it
// takes from an unknown window stays marked.  Block after block, until the
// first block boundary at or past ``stop_bit`` or with ``soft_cap``
// symbols written, or the final block's end.
//
// Returns 1 at the final block's end, 0 at another boundary (*end_bit the
// boundary, *n_out the symbols up to it).  Where src or the room ends
// inside a block, the blocks decoded whole are the result (0); with none:
// -2 out of room, -3 out of input.  -1: the data is no DEFLATE.
int32_t hbam_deflate_decode_symbols(const uint8_t* src, int64_t n,
                                    int64_t start_bit, int64_t stop_bit,
                                    int64_t soft_cap, int32_t window_len,
                                    uint16_t* out, int64_t cap,
                                    int64_t* end_bit, int64_t* n_out) {
  if (start_bit < 0 || cap < 0 || window_len < 0 || window_len > kGzWindow)
    return -1;
  if (start_bit >= n * 8) return -3;
  uint16_t* o = out + kGzWindow;
  int64_t pos = 0;
  int64_t good_bit = start_bit, good_pos = 0;
  int32_t short_of = 0;
  GzTables dyn;
  uint8_t lens[320];
  GzBits b;
  b.seek(src, n, start_bit);
  for (;;) {
    b.refill();
    const uint32_t head = b.peek(3);
    b.drop(3);
    if (b.exhausted()) { short_of = -3; break; }
    const bool final_block = head & 1;
    const int type = static_cast<int>(head >> 1);
    if (type == 0) {
      const int64_t at = (b.bitpos() + 7) >> 3;
      if (at + 4 > n) { short_of = -3; break; }
      const uint32_t len = src[at] | static_cast<uint32_t>(src[at + 1]) << 8;
      const uint32_t nlen = src[at + 2] |
                            static_cast<uint32_t>(src[at + 3]) << 8;
      if ((len ^ nlen) != 0xffffu) return -1;
      if (at + 4 + len > n) { short_of = -3; break; }
      if (pos + len > cap) { short_of = -2; break; }
      const uint8_t* s = src + at + 4;
      for (uint32_t i = 0; i < len; ++i) o[pos + i] = s[i];
      pos += len;
      b.seek(src, n, (at + 4 + len) * 8);
    } else if (type == 3) {
      return -1;
    } else {
      const GzTables* t = &dyn;
      if (type == 1) {
        t = &gz_fixed_tables();
      } else {
        int nlen, ndist;
        const bool ok = gz_read_dynamic(b, lens, &nlen, &ndist);
        if (b.exhausted()) { short_of = -3; break; }
        if (!ok || !gz_build_block(lens, nlen, ndist, &dyn)) return -1;
      }
      const uint32_t* lt = t->lit;
      const uint32_t* dt = t->dist;
      for (;;) {
        if (b.cnt < 48) {
          b.refill();
          if (b.over && b.exhausted()) { short_of = -3; break; }
        }
        uint32_t e;
        GZ_LOOKUP(e, lt, kGzLitRoot, b);
        const int kind = (e >> 5) & 7;
        if (kind == kGzLit) {
          if (pos >= cap) { short_of = -2; break; }
          o[pos++] = static_cast<uint16_t>(e >> 16);
          continue;
        }
        if (kind == kGzEob) break;
        if (kind != kGzLen) return -1;
        int xb = (e >> 8) & 31;
        const int64_t len = (e >> 16) + b.peek(xb);
        b.drop(xb);
        uint32_t d;
        GZ_LOOKUP(d, dt, kGzDistRoot, b);
        if (((d >> 5) & 7) != kGzLen) return -1;
        xb = (d >> 8) & 31;
        const int64_t dist = (d >> 16) + b.peek(xb);
        b.drop(xb);
        if (dist > pos + window_len) return -1;
        if (pos + len > cap) { short_of = -2; break; }
        uint16_t* dst = o + pos;
        const uint16_t* s = dst - dist;
        if (dist >= 8) {
          for (int64_t i = 0; i < len; i += 8) std::memcpy(dst + i, s + i, 16);
        } else {
          for (int64_t i = 0; i < len; ++i) dst[i] = s[i];
        }
        pos += len;
      }
      if (short_of) break;
      if (b.exhausted()) { short_of = -3; break; }
    }
    good_bit = b.bitpos();
    good_pos = pos;
    if (final_block || good_bit >= stop_bit || pos >= soft_cap) {
      *end_bit = good_bit;
      *n_out = pos;
      return final_block ? 1 : 0;
    }
  }
  *end_bit = good_bit;
  *n_out = good_pos;
  return good_bit > start_bit ? 0 : short_of;
}

// Symbols -> bytes: a symbol under 256 is its byte, 256 + k is byte k of
// ``window`` (kGzWindow bytes: the text before the symbols' first; null
// where there is none, and a mark then reads 0).  *n_eol receives how many
// of the bytes are ``eol`` (a text's line count, for whoever cuts it by
// records).  Returns the CRC32 of the n bytes written.
uint32_t hbam_deflate_resolve(const uint16_t* syms, int64_t n,
                              const uint8_t* window, uint8_t* out,
                              uint8_t eol, int64_t* n_eol) {
  uint8_t lut[256 + kGzWindow];
  for (int s = 0; s < 256; ++s) lut[s] = static_cast<uint8_t>(s);
  if (window) std::memcpy(lut + 256, window, kGzWindow);
  else std::memset(lut + 256, 0, kGzWindow);
  int64_t eols = 0;
  for (int64_t i = 0; i < n; ++i) {
    const uint32_t s = syms[i];
    const uint8_t b = lut[s < 256 + kGzWindow ? s : 0];
    out[i] = b;
    eols += b == eol;
  }
  if (n_eol) *n_eol = eols;
  uint32_t crc = 0;
  for (int64_t at = 0; at < n; at += int64_t{1} << 30) {
    const size_t piece = static_cast<size_t>(
        n - at < (int64_t{1} << 30) ? n - at : int64_t{1} << 30);
#if defined(HBAM_USE_LIBDEFLATE)
    crc = libdeflate_crc32(crc, out + at, piece);
#else
    crc = static_cast<uint32_t>(crc32(crc, out + at,
                                      static_cast<uInt>(piece)));
#endif
  }
  return crc;
}

// The CRC32 of A ++ B from crc(A), crc(B) and len(B) (zlib's).
uint32_t hbam_crc32_combine(uint32_t crc_a, uint32_t crc_b, int64_t len_b) {
  return static_cast<uint32_t>(
      crc32_combine(crc_a, crc_b, static_cast<z_off_t>(len_b)));
}

}  // extern "C"

// ---------------------------------------------------------------------------
// Fused single-pass span decode: inflate + record walk + projection pack +
// CRC fold in ONE streamed pass over the span, chunk-granular.
//
// The two-pass hot path (hbam_inflate_batch -> DRAM, then a separate
// hbam_walk_bam_* full re-read, plus an optional third hbam_crc32_batch
// sweep) touches every inflated byte two-to-three times from DRAM.  Here a
// worker inflates a run of ``chunk_blocks`` BGZF blocks and the record walk
// consumes those bytes while they are still cache-resident; the CRC32
// check folds into the same visit.  Record boundaries chain serially
// (offset[i+1] = offset[i] + 4 + block_size[i]), so the walk advances
// behind the CONTIGUOUS inflated frontier: whichever worker extends the
// frontier drains the walk (one walker at a time; inflation of later
// chunks keeps running concurrently).  Completed walk increments are
// published as [row_lo, row_hi) ranges that hbam_fused_next hands to the
// caller as they land — the chunk-streamed handoff that lets the Python
// side start packing staging tiles before the span's tail is inflated
// (rapidgzip's chunk-pipelined consumption shape, applied host-side).
//
// Pack modes share one walk:
//   0: offsets only (callers that pack variable-length series themselves)
//   1: selected fixed-prefix ranges -> dense rows (hbam_walk_bam_packed)
//   2: prefix + 4-bit seq + qual tiles   (hbam_walk_bam_payload)
// ---------------------------------------------------------------------------

namespace {

struct HbamFusedChunk { int64_t row_lo, row_hi; };

struct HbamFusedJob {
  // borrowed inputs — the Python wrapper keeps every array alive
  const uint8_t* src;
  const int64_t* cdata_off;
  const int32_t* cdata_len;
  const int32_t* isize;
  const uint32_t* expect_crc;    // null: no CRC fold
  int32_t n_blocks;
  uint8_t* dst;                  // inflated span buffer [total]
  const int64_t* ubase;          // per-block inflated start offsets
  int64_t total;
  int64_t start_u, stop;         // walk start / ownership limit
  // pack configuration
  int32_t mode;
  const int32_t* sel_off;
  const int32_t* sel_len;
  int32_t n_sel, row_stride;
  uint8_t* out_rows;             // mode 1 rows / mode 2 prefix tile
  uint8_t* out_seq;
  uint8_t* out_qual;
  int32_t max_len, seq_stride, qual_stride;
  int64_t* out_off;
  int64_t cap;
  // chunk bookkeeping (mu guards everything below except the atomics)
  int32_t chunk_blocks, n_chunks;
  std::vector<uint8_t> chunk_done;
  int32_t frontier = 0;          // count of contiguously inflated chunks
  bool walk_active = false;
  int64_t walk_pos = 0;
  int64_t walk_limit_done = 0;   // bytes the walk has already swept
  int64_t rows = 0;
  bool finished = false;
  int32_t err_kind = 0;          // 1 inflate, 2 isize, 3 crc, 4 chain, 5 cap
  int64_t err_index = -1;        // failing block (1-3) or offset (4-5)
  std::atomic<bool> cancel{false};
  std::atomic<int32_t> next{0};
  // core-nanoseconds the workers spent in inflate (+ CRC) and in the
  // walk + pack, lock waits left out: the job's "time busy"
  std::atomic<int64_t> busy_ns{0};
  std::mutex mu;
  std::condition_variable cv;
  std::deque<HbamFusedChunk> ready;
  std::vector<std::thread> pool;
};

inline int64_t hbam_now_ns() {
  return std::chrono::duration_cast<std::chrono::nanoseconds>(
      std::chrono::steady_clock::now().time_since_epoch()).count();
}

// Walk newly contiguous bytes and pack rows.  Called with ``lk`` held;
// the walk body runs unlocked (walk_active excludes other walkers while
// inflation of later chunks proceeds in parallel).  ``walk_ns`` gains the
// nanoseconds spent in the unlocked walk.
void hbam_fused_drain(HbamFusedJob* j, std::unique_lock<std::mutex>& lk,
                      int64_t& walk_ns) {
  if (j->walk_active || j->err_kind) return;
  for (;;) {
    const bool final_pass = j->frontier >= j->n_chunks;
    const int64_t limit = final_pass
        ? j->total
        : j->ubase[static_cast<int64_t>(j->frontier) * j->chunk_blocks];
    if (j->finished) return;
    if (!final_pass && limit <= j->walk_limit_done) return;
    j->walk_active = true;
    int64_t p = j->walk_pos;
    int64_t r = j->rows;
    lk.unlock();
    const int64_t t_walk = hbam_now_ns();
    int ekind = 0;
    while (p + 4 <= limit && p < j->stop) {
      int32_t bs;
      std::memcpy(&bs, j->dst + p, 4);
      if (bs < 32) { ekind = 4; break; }
      if (p + 4 + bs > limit) break;   // record cut at the frontier: resume
      if (r >= j->cap) { ekind = 5; break; }
      const uint8_t* rec = j->dst + p;
      if (j->mode == 1) {
        uint8_t* row = j->out_rows + r * j->row_stride;
        for (int32_t s = 0; s < j->n_sel; ++s) {
          std::memcpy(row, rec + j->sel_off[s],
                      static_cast<size_t>(j->sel_len[s]));
          row += j->sel_len[s];
        }
      } else if (j->mode == 2) {
        std::memcpy(j->out_rows + r * 36, rec, 36);
        uint8_t l_read_name = rec[12];
        uint16_t n_cigar;
        std::memcpy(&n_cigar, rec + 16, 2);
        int32_t l_seq;
        std::memcpy(&l_seq, rec + 20, 4);
        int64_t seq_off = 36 + static_cast<int64_t>(l_read_name) +
                          4 * static_cast<int64_t>(n_cigar);
        int64_t nb = (static_cast<int64_t>(l_seq) + 1) / 2;
        if (l_seq < 0 || seq_off + nb + l_seq > 4 + static_cast<int64_t>(bs)) {
          ekind = 4;
          break;
        }
        int32_t use = l_seq < j->max_len ? l_seq : j->max_len;
        std::memcpy(j->out_seq + r * j->seq_stride, rec + seq_off,
                    (use + 1) / 2);
        std::memcpy(j->out_qual + r * j->qual_stride, rec + seq_off + nb,
                    use);
      }
      j->out_off[r] = p;
      ++r;
      p += 4 + static_cast<int64_t>(bs);
    }
    walk_ns += hbam_now_ns() - t_walk;
    lk.lock();
    const int64_t lo = j->rows;
    j->rows = r;
    j->walk_pos = p;
    j->walk_limit_done = limit;
    j->walk_active = false;
    if (ekind) {
      if (!j->err_kind) { j->err_kind = ekind; j->err_index = p; }
      j->cancel.store(true);
      j->cv.notify_all();
      return;
    }
    if (r > lo) {
      j->ready.push_back({lo, r});
      j->cv.notify_all();
    }
    if (final_pass) {
      j->finished = true;
      j->cv.notify_all();
      return;
    }
    // loop: the frontier may have advanced while this pass walked
  }
}

void hbam_fused_worker(HbamFusedJob* j) {
#if defined(HBAM_USE_LIBDEFLATE)
  libdeflate_decompressor* d = libdeflate_alloc_decompressor();
  if (!d) {
    std::lock_guard<std::mutex> lk(j->mu);
    if (!j->err_kind) { j->err_kind = 1; j->err_index = 0; }
    j->cancel.store(true);
    j->cv.notify_all();
    return;
  }
#else
  z_stream zs;
  std::memset(&zs, 0, sizeof(zs));
  bool live = false;
#endif
  for (;;) {
    const int32_t c = j->next.fetch_add(1);
    if (c >= j->n_chunks || j->cancel.load(std::memory_order_relaxed)) break;
    const int32_t b0 = c * j->chunk_blocks;
    const int32_t b1 = b0 + j->chunk_blocks < j->n_blocks
                           ? b0 + j->chunk_blocks : j->n_blocks;
    int ekind = 0;
    int64_t eidx = -1;
    const int64_t t_chunk = hbam_now_ns();
    for (int32_t b = b0; b < b1 && !ekind; ++b) {
#if defined(HBAM_USE_LIBDEFLATE)
      size_t out_n = 0;
      libdeflate_result rc = libdeflate_deflate_decompress(
          d, j->src + j->cdata_off[b], static_cast<size_t>(j->cdata_len[b]),
          j->dst + j->ubase[b], static_cast<size_t>(j->isize[b]), &out_n);
      if (rc != LIBDEFLATE_SUCCESS) { ekind = 1; eidx = b; }
      else if (static_cast<int32_t>(out_n) != j->isize[b]) {
        ekind = 2; eidx = b;
      }
#else
      if (!live) {
        if (inflateInit2(&zs, -15) != Z_OK) { ekind = 1; eidx = b; break; }
        live = true;
      } else {
        inflateReset(&zs);
      }
      zs.next_in = const_cast<Bytef*>(j->src + j->cdata_off[b]);
      zs.avail_in = static_cast<uInt>(j->cdata_len[b]);
      zs.next_out = j->dst + j->ubase[b];
      zs.avail_out = static_cast<uInt>(j->isize[b]);
      int rc = inflate(&zs, Z_FINISH);
      if (rc != Z_STREAM_END) { ekind = 1; eidx = b; }
      else if (static_cast<int32_t>(zs.total_out) != j->isize[b]) {
        ekind = 2; eidx = b;
      }
#endif
      if (!ekind && j->expect_crc) {
        // fold the footer check in while the block is cache-hot — this
        // is what makes check_crc nearly free on the fused path
#if defined(HBAM_USE_LIBDEFLATE)
        uint32_t got = libdeflate_crc32(0, j->dst + j->ubase[b],
                                        static_cast<size_t>(j->isize[b]));
#else
        uint32_t got = static_cast<uint32_t>(
            crc32(0L, j->dst + j->ubase[b],
                  static_cast<uInt>(j->isize[b])));
#endif
        if (got != j->expect_crc[b]) { ekind = 3; eidx = b; }
      }
    }
    int64_t chunk_ns = hbam_now_ns() - t_chunk;
    std::unique_lock<std::mutex> lk(j->mu);
    if (ekind) {
      if (!j->err_kind) { j->err_kind = ekind; j->err_index = eidx; }
      j->cancel.store(true);
      j->cv.notify_all();
      j->busy_ns.fetch_add(chunk_ns, std::memory_order_relaxed);
      break;
    }
    j->chunk_done[c] = 1;
    while (j->frontier < j->n_chunks && j->chunk_done[j->frontier])
      ++j->frontier;
    hbam_fused_drain(j, lk, chunk_ns);
    j->busy_ns.fetch_add(chunk_ns, std::memory_order_relaxed);
  }
#if defined(HBAM_USE_LIBDEFLATE)
  libdeflate_free_decompressor(d);
#else
  if (live) inflateEnd(&zs);
#endif
}

}  // namespace

extern "C" {

// Start a fused span decode; returns an opaque handle (null on bad args).
// All arrays are borrowed until hbam_fused_finish returns.  expect_crc may
// be null (no CRC fold); out_seq/out_qual are only read in mode 2 and
// sel_off/sel_len only in mode 1.
void* hbam_fused_start(const uint8_t* src, const int64_t* cdata_off,
                       const int32_t* cdata_len, const int32_t* isize,
                       const uint32_t* expect_crc, int32_t n_blocks,
                       uint8_t* dst, const int64_t* ubase, int64_t total,
                       int64_t start_u, int64_t stop, int32_t mode,
                       const int32_t* sel_off, const int32_t* sel_len,
                       int32_t n_sel, int32_t row_stride,
                       uint8_t* out_rows, uint8_t* out_seq,
                       uint8_t* out_qual, int32_t max_len,
                       int32_t seq_stride, int32_t qual_stride,
                       int64_t* out_off, int64_t cap,
                       int32_t chunk_blocks, int32_t n_threads) {
  if (n_blocks <= 0 || mode < 0 || mode > 2) return nullptr;
  if (chunk_blocks < 1) chunk_blocks = 1;
  if (n_threads < 1) n_threads = 1;
  HbamFusedJob* j = new HbamFusedJob();
  j->src = src;
  j->cdata_off = cdata_off;
  j->cdata_len = cdata_len;
  j->isize = isize;
  j->expect_crc = expect_crc;
  j->n_blocks = n_blocks;
  j->dst = dst;
  j->ubase = ubase;
  j->total = total;
  j->start_u = start_u;
  j->stop = stop;
  j->mode = mode;
  j->sel_off = sel_off;
  j->sel_len = sel_len;
  j->n_sel = n_sel;
  j->row_stride = row_stride;
  j->out_rows = out_rows;
  j->out_seq = out_seq;
  j->out_qual = out_qual;
  j->max_len = max_len;
  j->seq_stride = seq_stride;
  j->qual_stride = qual_stride;
  j->out_off = out_off;
  j->cap = cap;
  j->chunk_blocks = chunk_blocks;
  j->n_chunks = (n_blocks + chunk_blocks - 1) / chunk_blocks;
  j->chunk_done.assign(j->n_chunks, 0);
  j->walk_pos = start_u;
  if (n_threads > j->n_chunks) n_threads = j->n_chunks;
  j->pool.reserve(n_threads);
  for (int t = 0; t < n_threads; ++t)
    j->pool.emplace_back(hbam_fused_worker, j);
  return j;
}

// Block until the next walked row range is ready.  Returns 1 and fills
// [*row_lo, *row_hi); 0 when the decode completed (all chunks inflated,
// walk drained); -kind on error (kind per HbamFusedJob::err_kind).
int hbam_fused_next(void* h, int64_t* row_lo, int64_t* row_hi) {
  HbamFusedJob* j = static_cast<HbamFusedJob*>(h);
  std::unique_lock<std::mutex> lk(j->mu);
  j->cv.wait(lk, [&] {
    return j->err_kind || !j->ready.empty() || j->finished;
  });
  if (j->err_kind) return -j->err_kind;
  if (!j->ready.empty()) {
    HbamFusedChunk c = j->ready.front();
    j->ready.pop_front();
    *row_lo = c.row_lo;
    *row_hi = c.row_hi;
    return 1;
  }
  return 0;
}

// Join workers and free the job.  Returns 0 or -kind; *tail receives the
// first incomplete record's offset (== stop-trimmed walk end), *n_rows
// the packed row count, *err_index the failing block/offset on error,
// *busy_ns the core-nanoseconds the workers spent in inflate + walk + pack.
// Safe to call while workers are still running (cancels outstanding
// chunks) — but then dst/out arrays are only partially written.
int hbam_fused_finish(void* h, int64_t* tail, int64_t* n_rows,
                      int64_t* err_index, int64_t* busy_ns) {
  HbamFusedJob* j = static_cast<HbamFusedJob*>(h);
  {
    std::lock_guard<std::mutex> lk(j->mu);
    j->cancel.store(true);
    j->cv.notify_all();
  }
  for (auto& th : j->pool) th.join();
  int rc = j->err_kind ? -j->err_kind : 0;
  if (tail) *tail = j->walk_pos;
  if (n_rows) *n_rows = j->rows;
  if (err_index) *err_index = j->err_index;
  if (busy_ns) *busy_ns = j->busy_ns.load();
  delete j;
  return rc;
}

}  // extern "C"

// ---------------------------------------------------------------------------
// rANS Nx16 decode (CRAM 3.1 block method 5 [SPEC CRAMcodecs]): a whole
// stream, transforms included, in one call.  Mirrors
// formats/cram_codecs_nx16.py byte for byte — that module stays the oracle
// and the fallback: the same uint7 sizes, the same RLE'd alphabet grammar,
// the same frequency renormalisation, ONE 16-bit renormalisation step a
// symbol, N = 4 or 32 interleaved states (order-1: N contiguous fragments),
// and the STRIPE / RLE / PACK / CAT / NOSZ layouts as that module reads
// them.  Returns 0, or kNxTrunc (ran out of bytes), kNxState (a final state
// is not 2^15), kNxBad (malformed), kNxRefused (nesting the caller should
// hand to the Python decoder).
// ---------------------------------------------------------------------------

namespace {

constexpr int kNxTrunc = -1;
constexpr int kNxState = -2;
constexpr int kNxBad = -3;
constexpr int kNxRefused = -4;
constexpr int64_t kNxLow = 1 << 15;
constexpr uint8_t kNxOrder1 = 0x01, kNxX32 = 0x04, kNxStripe = 0x08,
                  kNxNosz = 0x10, kNxCat = 0x20, kNxRle = 0x40,
                  kNxPack = 0x80;

int nx_var(const uint8_t* b, int64_t n, int64_t* pos, int64_t* v) {
  int64_t x = 0;
  for (int k = 0;; ++k) {
    if (*pos >= n) return kNxTrunc;
    uint8_t c = b[(*pos)++];
    if (k >= 8) return kNxBad;          // past 56 bits: no real size
    x = (x << 7) | (c & 0x7F);
    if (!(c & 0x80)) break;
  }
  *v = x;
  return 0;
}

// The ascending-symbol alphabet with its run bytes (cram_codecs.py's
// _read_symbol_table grammar).
int nx_alphabet(const uint8_t* b, int64_t n, int64_t* pos,
                std::vector<int>* syms) {
  syms->clear();
  if (*pos >= n) return kNxTrunc;
  int j = b[(*pos)++];
  int rle = 0;
  for (;;) {
    syms->push_back(j);
    if (rle > 0) {
      --rle;
      ++j;
    } else {
      if (*pos >= n) return kNxTrunc;
      int nxt = b[(*pos)++];
      if (nxt == j + 1) {
        if (*pos >= n) return kNxTrunc;
        rle = b[(*pos)++];
        j = nxt;
      } else if (nxt == 0) {
        break;
      } else {
        j = nxt;
      }
    }
  }
  return 0;
}

// One frequency table, renormalised to 1 << shift as _read_freqs_nx16 does
// (floor scaling, present symbols at least 1, the drift on the first
// largest).
int nx_freqs(const uint8_t* b, int64_t n, int64_t* pos, int shift,
             int64_t* freqs) {
  std::vector<int> syms;
  int rc = nx_alphabet(b, n, pos, &syms);
  if (rc) return rc;
  std::memset(freqs, 0, 256 * sizeof(int64_t));
  for (int s : syms) {
    int64_t f;
    if ((rc = nx_var(b, n, pos, &f))) return rc;
    if (s > 255 || f > (int64_t(1) << 32)) return kNxBad;
    freqs[s] = f;
  }
  int64_t total = 0;
  for (int s = 0; s < 256; ++s) total += freqs[s];
  const int64_t want = int64_t(1) << shift;
  if (total != want && total > 0) {
    int64_t sum = 0;
    for (int s = 0; s < 256; ++s) {
      int64_t c = freqs[s];
      int64_t f = c * want / total;
      if (c > 0 && f == 0) f = 1;
      freqs[s] = f;
      sum += f;
    }
    int64_t drift = want - sum;
    if (drift != 0) {
      int jmax = 0;
      for (int s = 1; s < 256; ++s)
        if (freqs[s] > freqs[jmax]) jmax = s;
      if (freqs[jmax] + drift < 1) return kNxBad;
      freqs[jmax] += drift;
    }
  }
  return 0;
}

// cum[257] and the slot -> symbol map of one table (NumPy's clipped slice
// assignment: slots past the table are dropped, earlier rows kept).
void nx_tables(const int64_t* freqs, int shift, int64_t* cum,
               uint8_t* slot2sym) {
  const int64_t size = int64_t(1) << shift;
  cum[0] = 0;
  for (int s = 0; s < 256; ++s) cum[s + 1] = cum[s] + freqs[s];
  for (int s = 0; s < 256; ++s) {
    if (!freqs[s]) continue;
    int64_t lo = cum[s] < size ? cum[s] : size;
    int64_t hi = cum[s + 1] < size ? cum[s + 1] : size;
    for (int64_t k = lo; k < hi; ++k) slot2sym[k] = static_cast<uint8_t>(s);
  }
}

inline int nx_renorm(const uint8_t* b, int64_t n, int64_t* pos, int64_t* x) {
  if (*x < kNxLow) {
    if (*pos + 1 >= n) return kNxTrunc;
    *x = (*x << 16) | (b[*pos] | (int64_t(b[*pos + 1]) << 8));
    *pos += 2;
  }
  return 0;
}

int nx_states(const uint8_t* b, int64_t n, int64_t* pos, int N,
              int64_t* states) {
  if (*pos + 4 * N > n) return kNxTrunc;
  for (int j = 0; j < N; ++j) {
    uint32_t s;
    std::memcpy(&s, b + *pos + 4 * j, 4);
    states[j] = s;
  }
  *pos += 4 * N;
  return 0;
}

int nx_order0(const uint8_t* b, int64_t n, int64_t pos, int64_t out_size,
              int N, int shift, uint8_t* out) {
  int64_t freqs[256], cum[257];
  int rc = nx_freqs(b, n, &pos, shift, freqs);
  if (rc) return rc;
  std::vector<uint8_t> slot2sym(size_t(1) << shift, 0);
  nx_tables(freqs, shift, cum, slot2sym.data());
  int64_t states[32];
  if ((rc = nx_states(b, n, &pos, N, states))) return rc;
  const int64_t mask = (int64_t(1) << shift) - 1;
  const uint8_t* s2s = slot2sym.data();
  int j = 0;
  for (int64_t i = 0; i < out_size; ++i) {
    int64_t x = states[j];
    int64_t m = x & mask;
    uint8_t s = s2s[m];
    out[i] = s;
    x = freqs[s] * (x >> shift) + m - cum[s];
    if ((rc = nx_renorm(b, n, &pos, &x))) return rc;
    states[j] = x;
    if (++j == N) j = 0;
  }
  for (int k = 0; k < N; ++k)
    if (states[k] != kNxLow) return kNxState;
  return 0;
}

int nx_order1(const uint8_t* b, int64_t n, int64_t pos, int64_t out_size,
              int N, uint8_t* out) {
  if (pos >= n) return kNxTrunc;
  const int lead = b[pos++];
  const int shift = lead >> 4;
  const int64_t size = int64_t(1) << shift;
  std::vector<uint8_t> tbl;
  const uint8_t* tb = b;
  int64_t tn = n, tpos = pos;
  int rc;
  if (lead & 1) {
    // the context tables are themselves an order-0 4-way stream
    int64_t ulen, clen;
    if ((rc = nx_var(b, n, &pos, &ulen))) return rc;
    if ((rc = nx_var(b, n, &pos, &clen))) return rc;
    if (ulen > (int64_t(1) << 24)) return kNxRefused;
    int64_t avail = n - pos < clen ? n - pos : clen;
    tbl.assign(size_t(ulen), 0);
    if ((rc = nx_order0(b + pos, avail, 0, ulen, 4, 12, tbl.data())))
      return rc;
    pos += clen;
    tb = tbl.data();
    tn = ulen;
    tpos = 0;
  }
  std::vector<int> ctxs;
  if ((rc = nx_alphabet(tb, tn, &tpos, &ctxs))) return rc;
  std::vector<int64_t> freqs(256 * 256, 0), cums(256 * 257, 0);
  std::vector<uint8_t> slot2sym(size_t(256) * size_t(size), 0);
  for (int c : ctxs) {
    if (c > 255) return kNxBad;
    if ((rc = nx_freqs(tb, tn, &tpos, shift, &freqs[size_t(c) * 256])))
      return rc;
    nx_tables(&freqs[size_t(c) * 256], shift, &cums[size_t(c) * 257],
              &slot2sym[size_t(c) * size_t(size)]);
  }
  if (!(lead & 1)) pos = tpos;
  int64_t states[32];
  if ((rc = nx_states(b, n, &pos, N, states))) return rc;
  const int64_t mask = size - 1;
  const int64_t q = out_size / N;
  int ctx[32];
  for (int j = 0; j < N; ++j) ctx[j] = 0;
  auto step = [&](int j, int64_t at) -> int {
    int64_t x = states[j];
    int64_t m = x & mask;
    const int c = ctx[j];
    uint8_t s = slot2sym[size_t(c) * size_t(size) + size_t(m)];
    out[at] = s;
    x = freqs[size_t(c) * 256 + s] * (x >> shift) + m
        - cums[size_t(c) * 257 + s];
    int r = nx_renorm(b, n, &pos, &x);
    states[j] = x;
    ctx[j] = s;
    return r;
  };
  // every fragment advances one symbol a round, in j order, while all are
  // running; the last (the longest) then runs alone
  for (int64_t i = 0; i < q; ++i)
    for (int j = 0; j < N; ++j)
      if ((rc = step(j, j * q + i))) return rc;
  for (int64_t at = (N - 1) * q + q; at < out_size; ++at)
    if ((rc = step(N - 1, at))) return rc;
  for (int k = 0; k < N; ++k)
    if (states[k] != kNxLow) return kNxState;
  return 0;
}

int64_t nx_packed_size(int64_t n, int64_t nsym) {
  if (nsym <= 1) return 0;
  if (nsym <= 2) return (n + 7) / 8;
  if (nsym <= 4) return (n + 3) / 4;
  return (n + 1) / 2;
}

// Decode one stream of ``out_size`` bytes into out[0, out_size): the size
// the stream states (or, under NOSZ, the caller's) must be out_size.
int nx_decode(const uint8_t* b, int64_t n, int64_t out_size, uint8_t* out,
              int depth) {
  if (depth > 4) return kNxRefused;
  if (n <= 0) return kNxBad;
  int64_t pos = 0;
  const uint8_t flags = b[pos++];
  int rc;
  if (!(flags & kNxNosz)) {
    int64_t own;
    if ((rc = nx_var(b, n, &pos, &own))) return rc;
    if (own != out_size) return kNxBad;
  }
  if (out_size == 0) return 0;

  if (flags & kNxStripe) {
    if (pos >= n) return kNxTrunc;
    const int X = b[pos++];
    if (X == 0) std::memset(out, 0, size_t(out_size));
    std::vector<int64_t> clens(X);
    for (int j = 0; j < X; ++j)
      if ((rc = nx_var(b, n, &pos, &clens[j]))) return rc;
    std::vector<uint8_t> sub;
    for (int j = 0; j < X; ++j) {
      const int64_t sub_len = (out_size - j + X - 1) / X;
      const int64_t avail = pos >= n ? 0
          : (n - pos < clens[j] ? n - pos : clens[j]);
      if (avail <= 0) return kNxBad;
      sub.assign(size_t(sub_len), 0);
      if ((rc = nx_decode(b + pos, avail, sub_len, sub.data(), depth + 1)))
        return rc;
      for (int64_t i = 0; i < sub_len; ++i) out[j + i * X] = sub[i];
      pos += clens[j];
    }
    return 0;
  }

  const uint8_t* pack_syms = nullptr;
  int64_t nsym = 0;
  if (flags & kNxPack) {
    if (pos >= n) return kNxTrunc;
    nsym = b[pos++];
    pack_syms = b + pos;
    if (pos + nsym > n) return kNxTrunc;
    pos += nsym;
  }
  const int64_t packed = (flags & kNxPack) ? nx_packed_size(out_size, nsym)
                                           : out_size;
  std::vector<uint8_t> rle_meta_buf;
  const uint8_t* rle_meta = nullptr;
  int64_t rle_meta_len = 0, lit_len = 0;
  if (flags & kNxRle) {
    int64_t mlen;
    if ((rc = nx_var(b, n, &pos, &mlen))) return rc;
    if (mlen & 1) {
      mlen >>= 1;
      if (pos + mlen > n) return kNxTrunc;
      rle_meta = b + pos;
      rle_meta_len = mlen;
      pos += mlen;
    } else {
      mlen >>= 1;
      int64_t clen;
      if ((rc = nx_var(b, n, &pos, &clen))) return rc;
      // the symbols and a run length of at most 5 bytes a literal: more
      // than that is no stream this size could hold
      if (mlen > 257 + 5 * packed) return kNxBad;
      rle_meta_buf.assign(size_t(mlen), 0);
      if ((rc = nx_order0(b, n, pos, mlen, 4, 12, rle_meta_buf.data())))
        return rc;
      rle_meta = rle_meta_buf.data();
      rle_meta_len = mlen;
      pos += clen;
    }
    if ((rc = nx_var(b, n, &pos, &lit_len))) return rc;
    if (lit_len > packed) return kNxBad;   // a literal is >= 1 output byte
  }
  const int64_t stage_size = (flags & kNxRle) ? lit_len : packed;

  // the entropy stage writes straight into the output when no transform
  // follows it
  const bool direct = !(flags & (kNxRle | kNxPack));
  std::vector<uint8_t> stage_buf;
  uint8_t* stage;
  if (direct) {
    stage = out;
  } else {
    stage_buf.assign(size_t(stage_size), 0);
    stage = stage_buf.data();
  }
  if (flags & kNxCat) {
    if (pos > n || n - pos < stage_size) return kNxTrunc;
    std::memcpy(stage, b + pos, size_t(stage_size));
  } else {
    const int N = (flags & kNxX32) ? 32 : 4;
    rc = (flags & kNxOrder1) ? nx_order1(b, n, pos, stage_size, N, stage)
                             : nx_order0(b, n, pos, stage_size, N, 12, stage);
    if (rc) return rc;
  }

  std::vector<uint8_t> rle_out;
  const uint8_t* cur = stage;
  int64_t cur_len = stage_size;
  if (flags & kNxRle) {
    const int64_t target = packed;
    if (rle_meta_len < 1) return kNxTrunc;
    int64_t mp = 0;
    int n_use = rle_meta[mp++];
    if (n_use == 0) n_use = 256;
    bool use[256] = {false};
    for (int k = 0; k < n_use; ++k) {
      if (mp >= rle_meta_len) return kNxTrunc;
      use[rle_meta[mp++]] = true;
    }
    uint8_t* dst;
    if (flags & kNxPack) {
      rle_out.assign(size_t(target), 0);
      dst = rle_out.data();
    } else {
      dst = out;
    }
    int64_t o = 0;
    for (int64_t i = 0; i < stage_size; ++i) {
      const uint8_t s = stage[i];
      int64_t run = 1;
      if (use[s]) {
        int64_t r;
        if ((rc = nx_var(rle_meta, rle_meta_len, &mp, &r))) return rc;
        run = r + 1;
      }
      if (o + run > target) return kNxBad;   // expands past its size
      std::memset(dst + o, s, size_t(run));
      o += run;
    }
    if (o != target) return kNxBad;
    cur = dst;
    cur_len = target;
  }
  if (flags & kNxPack) {
    uint8_t* dst = out;
    if (nsym <= 1) {
      if (nsym == 0) return kNxBad;
      std::memset(dst, pack_syms[0], size_t(out_size));
      return 0;
    }
    uint8_t table[256] = {0};
    for (int64_t k = 0; k < nsym; ++k) table[k] = pack_syms[k];
    const int bits = nsym <= 2 ? 1 : nsym <= 4 ? 2 : 4;
    const int per = 8 / bits;
    const int vmask = (1 << bits) - 1;
    if (cur_len * per < out_size) return kNxBad;
    for (int64_t i = 0; i < out_size; ++i)
      dst[i] = table[(cur[i / per] >> (bits * (i % per))) & vmask];
  }
  return 0;
}

}  // namespace

extern "C" {

// One whole rANS Nx16 stream into out[out_size]: out_size is the stream's
// own size, or the caller's when the stream says NOSZ.  Returns 0 or a
// kNx* code (a stream that states another size is kNxBad).
int hbam_rans_nx16_decode(const uint8_t* buf, int64_t n, uint8_t* out,
                          int64_t out_size) {
  try {
    return nx_decode(buf, n, out_size, out, 0);
  } catch (...) {            // an allocation refused: the caller falls back
    return kNxRefused;
  }
}

}  // extern "C"

extern "C" {

// dst[dst_at[i] : dst_at[i] + lens[i]] = src[src_at[i] : src_at[i] +
// lens[i]] for every run i, in order (the CRAM columnar decoder's
// reference fill: match runs gathered from the reference window into the
// reads).  Returns 0, or -(1 + i) for the first run outside either buffer
// (nothing after it is copied).
int64_t hbam_copy_runs(uint8_t* dst, int64_t dst_n, const uint8_t* src,
                       int64_t src_n, const int64_t* dst_at,
                       const int64_t* src_at, const int64_t* lens,
                       int64_t n) {
  for (int64_t i = 0; i < n; ++i) {
    const int64_t d = dst_at[i], s = src_at[i], l = lens[i];
    if (l < 0 || d < 0 || s < 0 || d > dst_n - l || s > src_n - l)
      return -(1 + i);
    std::memcpy(dst + d, src + s, size_t(l));
  }
  return 0;
}

}  // extern "C"

extern "C" {

// n whole rANS Nx16 streams in one call: stream i is the src_len[i] bytes
// at address src[i] and decodes to the dst_len[i] bytes at address dst[i].
// rc[i] is hbam_rans_nx16_decode's return for stream i; returns how many
// failed.
int64_t hbam_rans_nx16_decode_batch(const uint64_t* src,
                                    const int64_t* src_len,
                                    const uint64_t* dst,
                                    const int64_t* dst_len, int32_t* rc,
                                    int64_t n) {
  int64_t bad = 0;
  for (int64_t i = 0; i < n; ++i) {
    try {
      rc[i] = nx_decode(reinterpret_cast<const uint8_t*>(src[i]),
                        src_len[i], dst_len[i],
                        reinterpret_cast<uint8_t*>(dst[i]), 0);
    } catch (...) {
      rc[i] = kNxRefused;
    }
    bad += rc[i] != 0;
  }
  return bad;
}

}  // extern "C"

extern "C" {

// n reads of one length rl, bases (ASCII, n * rl) and Phred qualities
// (n * ql, ql = rl or 0) -> payload tile rows: the first min(rl, max_len)
// bases as 4-bit codes through lut, two a byte high nibble first, into
// seq_out[i * seq_stride, + seq_stride); the first min(ql, max_len,
// qual_stride) qualities into qual_out[i * qual_stride, ...).  Rows are
// zero beyond what is written (the caller hands zeroed rows).  The
// uniform-length branch of api/read_datasets.py::ragged_to_payload_tiles.
void hbam_pack_reads(const uint8_t* seq, const uint8_t* qual, int64_t n,
                     int64_t rl, int64_t ql, const uint8_t* lut,
                     int64_t max_len, uint8_t* seq_out, int64_t seq_stride,
                     uint8_t* qual_out, int64_t qual_stride) {
  const int64_t L = rl < max_len ? rl : max_len;
  int64_t ks = (L + 1) / 2;
  if (ks > seq_stride) ks = seq_stride;
  int64_t kq = ql < max_len ? ql : max_len;
  if (kq > qual_stride) kq = qual_stride;
  for (int64_t i = 0; i < n; ++i) {
    const uint8_t* s = seq + i * rl;
    uint8_t* o = seq_out + i * seq_stride;
    for (int64_t k = 0; k < ks; ++k) {
      const int64_t j = 2 * k;
      const uint8_t hi = lut[s[j]];
      const uint8_t lo = j + 1 < L ? lut[s[j + 1]] : 0;
      o[k] = static_cast<uint8_t>((hi << 4) | lo);
    }
    if (kq > 0)
      std::memcpy(qual_out + i * qual_stride, qual + i * ql, size_t(kq));
  }
}

}  // extern "C"

// ---------------------------------------------------------------------------
// A CRAM slice's bases and qualities rebuilt from its predecoded columns
// [SPEC CRAM3 section 10.6]: the record loop of formats/cram_decode.py::
// _decode_mapped run over a whole slice, features in the order that decoder
// consumes them.  formats/cram_columns.py::_rebuild_numpy is the statement of
// the semantics — which checks send a slice to the record path, in which
// order, and which raise — the oracle this is tested against and the path of
// multi-reference slices.  No threads: the decode pool's threads run it with
// the interpreter lock released.
// ---------------------------------------------------------------------------
namespace {

// the payload streams, in hbam_cram_slice_rebuild's src / src_n order: bytes,
// but int64 for the byte arrays' lengths and the D / N lengths
enum : int {
  kCsQS = 0, kCsBA, kCsBS,
  kCsBBLen, kCsBBVal, kCsQQLen, kCsQQVal, kCsINLen, kCsINVal, kCsSCLen,
  kCsSCVal, kCsDL, kCsRS, kCsStreams
};

// what hbam_cram_slice_rebuild returns
enum : int64_t {
  kCramOk = 0,
  kCramNeedRef = 1,     // fetch the reference window info[0], info[1] first
  kCramGeometry = 2,    // features or the QS stream: the record path's slice
  kCramDeclined = 3,    // the BA / BS streams or the reference: the same
  kCramBadSubst = 4,    // a BS code the substitution matrix cannot take
  kCramArgs = -1,       // arguments that disagree with each other
};

constexpr int32_t kCfQualStored = 0x1, kCfUnknownBases = 0x8;

inline int cram_base_row(uint8_t b) {
  switch (b) {
    case 'A': case 'a': return 0;
    case 'C': case 'c': return 1;
    case 'G': case 'g': return 2;
    case 'T': case 't': return 3;
    default: return 4;
  }
}

// the read and reference lengths of one feature, its array's length taken
// from (and its cursor moved along) the stream its code reads
struct CramCursors {
  const int64_t* len[4];        // BB, QQ, IN, SC lengths
  int64_t len_n[4];
  int64_t at[4] = {0, 0, 0, 0};
  int64_t val_at[4] = {0, 0, 0, 0};
  const int64_t *dl, *rs;
  int64_t dl_n, rs_n, dl_at = 0, rs_at = 0;
  int64_t ba = 0, qs = 0, bs = 0;
};

inline int cram_array_slot(int32_t code) {
  switch (code) {
    case 'b': return 0;
    case 'q': return 1;
    case 'I': return 2;
    case 'S': return 3;
    default: return -1;
  }
}

}  // namespace

extern "C" {

// Slice columns -> its reads' bases and qualities, the columns of
// formats/cram_columns.py::decode_slice_columns.  Record i has flags bf[i],
// cf[i], length rl[i], 1-based position pos[i]; the k-th mapped record has
// mq[k] and fn[k] features, whose codes fc[] and position deltas fp[] run in
// record order.  src[s] / src_n[s] is payload stream s (kCs*; src_n < 0: a
// series that cannot be read at computed offsets).  table[5 * 4] is the
// substitution matrix as base bytes by (reference row A C G T N, code), 0
// where the matrix yields none.
//
// have_source 0: there is no reference.  Else ref[0, ref_n) holds the
// reference from position ref_lo on; with ref == nullptr the window is not
// fetched yet: where the slice reads the reference the call returns
// kCramNeedRef with the window [info[0], info[1]) — positions, of the reads
// that consume reference, from the first read's start to the furthest end —
// and writes nothing; the caller fetches it and calls again.
//
// Writes, on kCramOk: seq_out[0, info[2]) the bases of every record whose
// sequence is kept (not a mapped one with unknown bases, not empty),
// seq_lens / qual_lens / mapq per record, and — qual_out != nullptr —
// qual_out[0, info[3]) the qualities of the records that store them, their
// 'B' / 'Q' / 'q' overlays applied in feature order.
//
// The checks run in the NumPy rebuild's order, so one input gives one
// answer: kCramGeometry (overlapping features, a feature outside its read or
// overrunning it, a QS stream shorter than the slice reads), kCramDeclined
// (BA or BS short; a read that needs the reference with no source; a run
// outside the window), kCramBadSubst (a BS code on a read with unknown bases
// against the N row; then, after every run was found inside the window, on
// the other reads against the reference's base).
int64_t hbam_cram_slice_rebuild(
    int64_t n, const int32_t* bf, const int32_t* cf, const int32_t* rl,
    const int64_t* pos, const int32_t* fn, const int32_t* mq,
    int64_t n_mapped, const uint8_t* fc, const int32_t* fp, int64_t n_feat,
    const uint64_t* src, const int64_t* src_n, const uint8_t* table,
    int32_t have_source, const uint8_t* ref, int64_t ref_n, int64_t ref_lo,
    uint8_t* seq_out, int64_t seq_cap, uint8_t* qual_out, int64_t qual_cap,
    int64_t* seq_lens, int64_t* qual_lens, int64_t* mapq, int64_t* info) {
  if (n < 0 || n_mapped < 0 || n_feat < 0 || seq_cap < 0 || qual_cap < 0)
    return kCramArgs;
  const uint8_t* qs_src = reinterpret_cast<const uint8_t*>(src[kCsQS]);
  const uint8_t* ba_src = reinterpret_cast<const uint8_t*>(src[kCsBA]);
  const uint8_t* bs_src = reinterpret_cast<const uint8_t*>(src[kCsBS]);
  const uint8_t* vals[4];
  int64_t vals_n[4];
  CramCursors c0;
  for (int a = 0; a < 4; ++a) {
    c0.len[a] = reinterpret_cast<const int64_t*>(src[kCsBBLen + 2 * a]);
    c0.len_n[a] = src_n[kCsBBLen + 2 * a];
    vals[a] = reinterpret_cast<const uint8_t*>(src[kCsBBVal + 2 * a]);
    vals_n[a] = src_n[kCsBBVal + 2 * a];
  }
  c0.dl = reinterpret_cast<const int64_t*>(src[kCsDL]);
  c0.rs = reinterpret_cast<const int64_t*>(src[kCsRS]);
  c0.dl_n = src_n[kCsDL];
  c0.rs_n = src_n[kCsRS];
  const int64_t qs_n = src_n[kCsQS], ba_n = src_n[kCsBA], bs_n = src_n[kCsBS];

  // pass 1: the geometry of every feature, what each stream must hold, the
  // reads that need the reference and the window they span
  {
    CramCursors c = c0;
    int64_t mi = 0, k = 0;
    bool need = false, need_known = false, bad_unknown = false, take = false;
    int64_t lo = std::numeric_limits<int64_t>::max();
    int64_t hi = std::numeric_limits<int64_t>::min();
    for (int64_t i = 0; i < n; ++i) {
      const int64_t len = rl[i];
      if (len < 0) return kCramArgs;
      const bool stored = cf[i] & kCfQualStored;
      if (bf[i] & 0x4) {
        c.ba += len;
        c.qs += stored ? len : 0;
        continue;
      }
      if (mi >= n_mapped) return kCramArgs;
      const int64_t nf = fn[mi++];
      if (nf < 0 || nf > n_feat - k) return kCramArgs;
      const bool unknown = cf[i] & kCfUnknownBases;
      int64_t fpos = 0, prev_end = 1, ref_used = 0;
      bool rec_need = false;
      for (int64_t j = 0; j < nf; ++j, ++k) {
        fpos += fp[k];
        const int32_t code = fc[k];
        int64_t rlen = 0, flen = 0, qlen = 0;
        const int a = cram_array_slot(code);
        if (a >= 0) {
          if (c.at[a] >= c.len_n[a]) return kCramArgs;
          const int64_t l = c.len[a][c.at[a]++];
          if (l < 0 || l > vals_n[a] - c.val_at[a]) return kCramArgs;
          c.val_at[a] += l;
          if (code == 'q') qlen = l; else rlen = l;
          if (code == 'b') flen = l;
        } else {
          switch (code) {
            case 'X':
              rlen = flen = 1;
              rec_need = true;
              if (unknown && c.bs < bs_n) {
                const uint8_t b = bs_src[c.bs];
                bad_unknown |= b > 3 || table[4 * 4 + b] == 0;
              }
              ++c.bs;
              break;
            case 'B': rlen = flen = 1; ++c.ba; ++c.qs; break;
            case 'i': rlen = 1; ++c.ba; break;
            case 'Q': ++c.qs; break;
            case 'D':
              if (c.dl_at >= c.dl_n) return kCramArgs;
              flen = c.dl[c.dl_at++];
              break;
            case 'N':
              if (c.rs_at >= c.rs_n) return kCramArgs;
              flen = c.rs[c.rs_at++];
              break;
            case 'P': case 'H': break;
            default: return kCramArgs;
          }
        }
        const int64_t gap = fpos - prev_end;
        if (gap < 0 || fpos < 1 || fpos - 1 + (rlen > 1 ? rlen : 1) > len ||
            fpos - 1 + qlen > len)
          return kCramGeometry;
        rec_need |= gap > 0;
        ref_used += gap + flen;
        prev_end = fpos + rlen;
      }
      const int64_t tail = len - (prev_end - 1);
      if (tail < 0) return kCramGeometry;
      rec_need |= tail > 0;
      ref_used += tail;
      c.qs += stored ? len : 0;
      need |= rec_need;
      if (unknown) continue;
      need_known |= rec_need;
      if (ref_used > 0) {
        take = true;
        lo = pos[i] < lo ? pos[i] : lo;
        hi = pos[i] + ref_used > hi ? pos[i] + ref_used : hi;
      }
    }
    if (mi != n_mapped || k != n_feat) return kCramArgs;
    if (c.qs > 0 && c.qs > qs_n) return kCramGeometry;
    if (c.ba > 0 && c.ba > ba_n) return kCramDeclined;
    if (need) {
      if (!have_source && need_known) return kCramDeclined;
      if (c.bs > 0 && c.bs > bs_n) return kCramDeclined;
      if (bad_unknown) return kCramBadSubst;
      if (have_source && take && ref == nullptr) {
        info[0] = lo;
        info[1] = hi;
        return kCramNeedRef;
      }
    }
    if (c.qs > 0 && qs_src == nullptr) return kCramArgs;
  }

  // pass 2: every record rebuilt; a run outside the reference window sends
  // the slice to the record path wherever it lies, a BS code the matrix
  // cannot take raises only where no run does
  if (ref == nullptr) ref_n = 0;
  CramCursors c = c0;
  int64_t mi = 0, k = 0, seq_w = 0, qual_w = 0;
  bool bad_subst = false;
  for (int64_t i = 0; i < n; ++i) {
    const int64_t len = rl[i];
    const bool stored = cf[i] & kCfQualStored;
    qual_lens[i] = stored ? len : 0;
    if (bf[i] & 0x4) {
      mapq[i] = 0;
      seq_lens[i] = len;
      if (len > seq_cap - seq_w) return kCramArgs;
      if (len) std::memcpy(seq_out + seq_w, ba_src + c.ba, size_t(len));
      seq_w += len;
      c.ba += len;
      if (stored) {
        if (qual_out) {
          if (len > qual_cap - qual_w) return kCramArgs;
          if (len) std::memcpy(qual_out + qual_w, qs_src + c.qs, size_t(len));
        }
        qual_w += len;
        c.qs += len;
      }
      continue;
    }
    mapq[i] = mq[mi];
    const int64_t nf = fn[mi++];
    const bool keep = !(cf[i] & kCfUnknownBases) && len > 0;
    seq_lens[i] = keep ? len : 0;
    if (keep && len > seq_cap - seq_w) return kCramArgs;
    uint8_t* s = seq_out + seq_w;
    const int64_t base = pos[i] - ref_lo;
    // where this record's overlays start: its 'B' / 'Q' bytes in QS, its
    // 'q' arrays in QQ
    const int64_t k_rec = k, qs_rec = c.qs, qq_rec = c.at[1],
                  qq_val_rec = c.val_at[1];
    int64_t fpos = 0, rp = 1, ref_off = 0;
    for (int64_t j = 0; j < nf; ++j, ++k) {
      fpos += fp[k];
      const int32_t code = fc[k];
      const int64_t gap = fpos - rp;
      if (gap > 0) {
        if (keep) {
          if (base + ref_off < 0 || base + ref_off > ref_n - gap)
            return kCramDeclined;
          std::memcpy(s + rp - 1, ref + base + ref_off, size_t(gap));
        }
        ref_off += gap;
        rp += gap;
      }
      const int a = cram_array_slot(code);
      if (a >= 0) {
        const int64_t l = c.len[a][c.at[a]++];
        if (code != 'q') {
          if (keep && l)
            std::memcpy(s + rp - 1, vals[a] + c.val_at[a], size_t(l));
          rp += l;
          if (code == 'b') ref_off += l;
        }
        c.val_at[a] += l;
        continue;
      }
      switch (code) {
        case 'X': {
          const uint8_t b = bs_src[c.bs++];
          if (keep) {
            if (base + ref_off < 0 || base + ref_off >= ref_n)
              return kCramDeclined;
            const int row = cram_base_row(ref[base + ref_off]);
            const uint8_t sub = b > 3 ? 0 : table[row * 4 + b];
            bad_subst |= sub == 0;
            s[rp - 1] = sub;
          }
          ++ref_off;
          ++rp;
          break;
        }
        case 'B':
          if (keep) s[rp - 1] = ba_src[c.ba];
          ++c.ba;
          ++c.qs;
          ++ref_off;
          ++rp;
          break;
        case 'i':
          if (keep) s[rp - 1] = ba_src[c.ba];
          ++c.ba;
          ++rp;
          break;
        case 'Q': ++c.qs; break;
        case 'D': ref_off += c.dl[c.dl_at++]; break;
        case 'N': ref_off += c.rs[c.rs_at++]; break;
        default: break;                 // 'P', 'H'
      }
    }
    const int64_t tail = len - (rp - 1);
    if (tail > 0 && keep) {
      if (base + ref_off < 0 || base + ref_off > ref_n - tail)
        return kCramDeclined;
      std::memcpy(s + rp - 1, ref + base + ref_off, size_t(tail));
    }
    if (keep) seq_w += len;
    if (!stored) continue;
    if (qual_out) {
      if (len > qual_cap - qual_w) return kCramArgs;
      uint8_t* q = qual_out + qual_w;
      if (len) std::memcpy(q, qs_src + c.qs, size_t(len));
      // the overlays in feature order: 'B' / 'Q' from the QS bytes ahead of
      // the stored qualities, 'q' from QQ
      int64_t qs_at = qs_rec, qq_at = qq_val_rec, qq_len = qq_rec;
      int64_t p = 0;
      for (int64_t j = 0; j < nf; ++j) {
        p += fp[k_rec + j];
        const int32_t code = fc[k_rec + j];
        if (code == 'B' || code == 'Q') {
          q[p - 1] = qs_src[qs_at++];
        } else if (code == 'q') {
          const int64_t l = c.len[1][qq_len++];
          if (l) std::memcpy(q + p - 1, vals[1] + qq_at, size_t(l));
          qq_at += l;
        }
      }
    }
    qual_w += len;
    c.qs += len;
  }
  if (bad_subst) return kCramBadSubst;
  info[2] = seq_w;
  info[3] = qual_w;
  return kCramOk;
}

}  // extern "C"

// ---------------------------------------------------------------------------
// The GWAS job's GRM finish: A [s, s] float64 from what pass 1 accumulated
// on the chip (cohort/gwas.py::grm_from_accumulators; ops/gwas_pallas.py::
// grm_accumulate says what the accumulators hold).  _grm_from_accumulators_
// numpy is the statement of the semantics and the oracle this is tested
// against.  No threads: one pass of ~6 M elements on the verb's thread, the
// interpreter lock released.
// ---------------------------------------------------------------------------
namespace {

constexpr int64_t kGrmTile = 64;    // 64 x 64 float64 = 32 KiB of stack

}  // namespace

extern "C" {

// out[j, k] = out[k, j] = ((double)acc[j, k] - ((double)r[j] - c)) /
// max(n_grm, 1) for j <= k, read from the upper triangle of the row-major
// [sp, sp] float32 acc (its lower triangle is never read).  The matrix is
// walked in square tiles on and above the diagonal: a tile is computed into
// a stack buffer while its rows are written, then its transpose is written
// row by row from that buffer, so neither the mirror's reads nor its writes
// stride over the whole matrix.  The float64 operations are NumPy's, in its
// order: the "+ 0.0" is its mirror's add of the zero triangle (it turns
// -0.0 into +0.0), the divide a true divide: the result is bitwise the
// NumPy body's.  Returns 0, or -1 for sizes that disagree.
int64_t hbam_grm_finish(const float* acc, int64_t sp, const float* r,
                        double c, int64_t n_grm, int64_t s, double* out) {
  if (s < 0 || sp < s) return -1;
  const double n = double(n_grm > 1 ? n_grm : 1);
  double tile[kGrmTile][kGrmTile];
  for (int64_t j0 = 0; j0 < s; j0 += kGrmTile) {
    const int64_t j1 = std::min(j0 + kGrmTile, s);
    for (int64_t k0 = j0; k0 < s; k0 += kGrmTile) {
      const int64_t k1 = std::min(k0 + kGrmTile, s);
      for (int64_t j = j0; j < j1; ++j) {
        const double rj = double(r[j]) - c;
        const float* a = acc + j * sp;
        double* row = out + j * s;
        double* t = tile[j - j0];
        for (int64_t k = std::max(k0, j); k < k1; ++k) {
          const double v = ((double(a[k]) - rj) + 0.0) / n;
          t[k - k0] = v;
          row[k] = v;
        }
      }
      for (int64_t k = k0; k < k1; ++k) {
        double* row = out + k * s;
        const int64_t j_end = std::min(j1, k);
        for (int64_t j = j0; j < j_end; ++j) row[j] = tile[j - j0][k - k0];
      }
    }
  }
  return 0;
}

}  // extern "C"
