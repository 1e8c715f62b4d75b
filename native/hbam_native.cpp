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

// libdeflate inflates raw DEFLATE ~2x faster than zlib; the build probes for
// it (utils/native.py) and falls back to plain zlib when absent.
#if defined(HBAM_USE_LIBDEFLATE)
#include <libdeflate.h>
#endif

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
// parse_block_header accepts — gzip magic with FEXTRA, every FEXTRA
// subfield walked for the BC subfield, BSIZE covering header + footer, the
// whole block inside the buffer, ISIZE <= 64 KiB — and writes one row per
// block.  Stops at the first header it does not accept, or when ``cap`` rows
// are written; *stop receives that header's offset (n after a clean walk).
// It names no fault: the caller re-parses from *stop in Python, which
// raises the error the Python walk always raised.  Returns the row count.
int64_t hbam_block_table(const uint8_t* src, int64_t n, int64_t offset,
                         int64_t* coffset, int64_t* cdata_off,
                         int32_t* cdata_len, int32_t* isize, int64_t cap,
                         int64_t* stop) {
  auto u16 = [&](int64_t p) {
    return static_cast<int64_t>(src[p]) | (static_cast<int64_t>(src[p + 1]) << 8);
  };
  int64_t count = 0;
  int64_t p = offset;
  while (p < n && count < cap) {
    if (n - p < 18) break;                             // truncated header
    if (src[p] != 0x1f || src[p + 1] != 0x8b || src[p + 2] != 0x08 ||
        src[p + 3] != 0x04) break;                     // bad magic / flags
    const int64_t xtra_end = p + 12 + u16(p + 10);
    if (n < xtra_end) break;                           // truncated FEXTRA
    int64_t bsize = -1;
    for (int64_t q = p + 12; q + 4 <= xtra_end; q += 4 + u16(q + 2)) {
      if (src[q] == 66 && src[q + 1] == 67 && u16(q + 2) == 2) {
        if (q + 6 <= n) bsize = u16(q + 4);
        break;
      }
    }
    if (bsize < 0) break;                              // no BC subfield
    const int64_t block_size = bsize + 1;
    if (block_size < xtra_end - p + 8) break;          // BSIZE too small
    if (n - p < block_size) break;                     // truncated body
    const int64_t end = p + block_size;
    const uint32_t isz = static_cast<uint32_t>(src[end - 4]) |
                         (static_cast<uint32_t>(src[end - 3]) << 8) |
                         (static_cast<uint32_t>(src[end - 2]) << 16) |
                         (static_cast<uint32_t>(src[end - 1]) << 24);
    if (isz > 0x10000u) break;                         // ISIZE > 64 KiB
    coffset[count] = p;
    cdata_off[count] = xtra_end;
    cdata_len[count] = static_cast<int32_t>(block_size - (xtra_end - p) - 8);
    isize[count] = static_cast<int32_t>(isz);
    ++count;
    p = end;
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

template <typename T>
void gt_dosage_rows(const uint8_t* buf, const int64_t* offs,
                    const int64_t* rows, int64_t n, int32_t ploidy,
                    int64_t ns, int8_t* out, int64_t out_stride) {
  auto* row = ploidy == 2 ? gt_dosage_row<T, 2>
            : ploidy == 1 ? gt_dosage_row<T, 1> : gt_dosage_row<T, 0>;
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
