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
#include <mutex>
#include <thread>
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
// DEFLATE tokenizer: Huffman-decode a raw DEFLATE stream into LZ77 tokens
// WITHOUT resolving back-references — the host half of the two-stage device
// inflate experiment (ops/inflate_device.py).  The bit-serial, branchy
// Huffman stage is unvectorizable and stays on the host (threaded across
// blocks); the embarrassingly parallel copy resolution runs on the device.
//
// Token u32 layout:
//   bit 31 set   -> copy: bits 16-24 = length (3..258), bits 0-15 = dist-1
//   bit 31 clear -> literal: bits 0-7 = byte value
// [SPEC] RFC 1951 (DEFLATE): block types, code-length code order, canonical
// Huffman construction, length/distance base+extra-bit tables.

namespace {

// 64-bit bit reservoir, LSB-first; refilled with zero padding past EOF
// (consumption past the real end is caught by the ``consumed`` counter).
struct HbamBits64 {
  const uint8_t* p;
  int64_t n;
  int64_t pos;       // next unread byte
  uint64_t acc;
  int cnt;           // bits in acc (may include zero padding)
  int64_t consumed;  // bits taken so far (pad bits included)
};

inline void hbam_refill(HbamBits64* b) {
  while (b->cnt <= 56) {
    const uint64_t byte = b->pos < b->n ? b->p[b->pos++] : 0;
    b->acc |= byte << b->cnt;
    b->cnt += 8;
  }
}

inline uint32_t hbam_take(HbamBits64* b, int k) {
  const uint32_t v = static_cast<uint32_t>(b->acc) & ((1u << k) - 1u);
  b->acc >>= k;
  b->cnt -= k;
  b->consumed += k;
  return v;
}

inline uint32_t hbam_getbits(HbamBits64* b, int k) {
  hbam_refill(b);
  return hbam_take(b, k);
}

struct HbamHuff {
  uint16_t count[16];   // codes per bit length
  uint16_t sym[288];    // symbols ordered by (length, symbol)
  bool empty;
};

int hbam_build_huff(const uint8_t* lens, int n, HbamHuff* h) {
  for (int i = 0; i < 16; ++i) h->count[i] = 0;
  for (int i = 0; i < n; ++i) h->count[lens[i]]++;
  h->empty = (h->count[0] == n);
  h->count[0] = 0;
  if (h->empty) return 0;   // legal: e.g. HDIST table with no codes
  int left = 1;             // over-subscription check
  for (int l = 1; l < 16; ++l) {
    left <<= 1;
    left -= h->count[l];
    if (left < 0) return -1;
  }
  uint16_t offs[16];
  offs[1] = 0;
  for (int l = 1; l < 15; ++l)
    offs[l + 1] = static_cast<uint16_t>(offs[l] + h->count[l]);
  for (int i = 0; i < n; ++i)
    if (lens[i]) h->sym[offs[lens[i]]++] = static_cast<uint16_t>(i);
  return 0;
}

// canonical code decode, one bit at a time (fallback for codes > 10 bits
// and for the tiny code-length table); caller must hbam_refill first
inline int hbam_decode_slow(HbamBits64* b, const HbamHuff* h) {
  int code = 0, first = 0, index = 0;
  for (int l = 1; l < 16; ++l) {
    code |= static_cast<int>(hbam_take(b, 1));
    const int cnt = h->count[l];
    if (code - first < cnt) return h->sym[index + (code - first)];
    index += cnt;
    first = (first + cnt) << 1;
    code <<= 1;
  }
  return -1;
}

// one-level lookup table over the low ROOT_BITS reservoir bits (DEFLATE
// packs codes MSB-first, so table indices are bit-reversed codes); codes
// longer than ROOT_BITS leave zero entries and fall back to slow decode.
constexpr int kRootBits = 10;

struct HbamFastTable {
  uint16_t root[1 << kRootBits];  // bit15 valid, bits 9-12 len, 0-8 sym
  HbamHuff slow;
};

int hbam_build_fast(const uint8_t* lens, int n, HbamFastTable* t) {
  if (hbam_build_huff(lens, n, &t->slow)) return -1;
  std::memset(t->root, 0, sizeof(t->root));
  if (t->slow.empty) return 0;
  uint32_t next_code[16];
  uint32_t code = 0;
  for (int l = 1; l < 16; ++l) {
    code = (code + t->slow.count[l - 1]) << 1;
    next_code[l] = code;
  }
  for (int i = 0; i < n; ++i) {
    const int l = lens[i];
    if (!l) continue;
    const uint32_t c = next_code[l]++;
    if (l > kRootBits) continue;
    uint32_t r = 0;                 // reverse the l code bits
    for (int bb = 0; bb < l; ++bb) r |= ((c >> bb) & 1u) << (l - 1 - bb);
    const uint16_t e = static_cast<uint16_t>(
        0x8000u | (static_cast<uint32_t>(l) << 9) | i);
    for (uint32_t j = r; j < (1u << kRootBits); j += (1u << l))
      t->root[j] = e;
  }
  return 0;
}

inline int hbam_fast_sym(HbamBits64* b, const HbamFastTable* t) {
  const uint16_t e = t->root[b->acc & ((1u << kRootBits) - 1u)];
  if (e & 0x8000) {
    const int l = (e >> 9) & 0xF;
    b->acc >>= l;
    b->cnt -= l;
    b->consumed += l;
    return e & 0x1FF;
  }
  return hbam_decode_slow(b, &t->slow);
}

const uint16_t kLenBase[29] = {
    3,  4,  5,  6,  7,  8,  9,  10, 11,  13,  15,  17,  19,  23, 27,
    31, 35, 43, 51, 59, 67, 83, 99, 115, 131, 163, 195, 227, 258};
const uint8_t kLenExtra[29] = {0, 0, 0, 0, 0, 0, 0, 0, 1, 1, 1, 1, 2, 2, 2,
                               2, 3, 3, 3, 3, 4, 4, 4, 4, 5, 5, 5, 5, 0};
const uint16_t kDistBase[30] = {
    1,    2,    3,    4,    5,    7,    9,    13,   17,   25,
    33,   49,   65,   97,   129,  193,  257,  385,  513,  769,
    1025, 1537, 2049, 3073, 4097, 6145, 8193, 12289, 16385, 24577};
const uint8_t kDistExtra[30] = {0, 0, 0,  0,  1,  1,  2,  2,  3,  3,
                                4, 4, 5,  5,  6,  6,  7,  7,  8,  8,
                                9, 9, 10, 10, 11, 11, 12, 12, 13, 13};
const uint8_t kClPerm[19] = {16, 17, 18, 0, 8,  7, 9,  6, 10, 5,
                             11, 4,  12, 3, 13, 2, 14, 1, 15};

}  // namespace

extern "C" {

// Tokenize one raw DEFLATE stream.  tokens/cap: output token array and its
// capacity; n_tokens/out_len: tokens written and total inflated length.
// Returns 0, or <0: -1 truncated input, -2 malformed stream, -3 token
// capacity exceeded, -4 distance reaches before stream start.
int hbam_deflate_tokenize(const uint8_t* comp, int64_t comp_len,
                          uint32_t* tokens, int64_t cap,
                          int64_t* n_tokens, int64_t* out_len) {
  HbamBits64 b{comp, comp_len, 0, 0, 0, 0};
  const int64_t limit = comp_len * 8;
  int64_t nt = 0, opos = 0;
  uint32_t bfinal = 0;
  do {
    hbam_refill(&b);
    bfinal = hbam_take(&b, 1);
    const uint32_t btype = hbam_take(&b, 2);
    if (btype == 0) {             // stored: byte-align, LEN/NLEN, raw copy
      hbam_take(&b, b.cnt & 7);
      const uint32_t len = hbam_getbits(&b, 16);
      const uint32_t nlen = hbam_getbits(&b, 16);
      if (b.consumed > limit) return -1;
      if ((len ^ 0xFFFFu) != nlen) return -2;
      if (nt + len > cap) return -3;
      uint32_t remaining = len;
      while (remaining && b.cnt >= 8) {   // drain reservoir bytes first
        tokens[nt++] = hbam_take(&b, 8);
        --remaining;
      }
      if (b.consumed > limit) return -1;
      if (b.pos + remaining > b.n) return -1;
      for (uint32_t i = 0; i < remaining; ++i)
        tokens[nt++] = comp[b.pos + i];
      b.pos += remaining;
      b.consumed += 8 * static_cast<int64_t>(remaining);
      opos += len;
      continue;
    }
    static thread_local HbamFastTable lit_t, dist_t;
    if (btype == 1) {             // fixed tables [SPEC RFC1951 3.2.6]
      uint8_t lens[288];
      for (int i = 0; i < 144; ++i) lens[i] = 8;
      for (int i = 144; i < 256; ++i) lens[i] = 9;
      for (int i = 256; i < 280; ++i) lens[i] = 7;
      for (int i = 280; i < 288; ++i) lens[i] = 8;
      hbam_build_fast(lens, 288, &lit_t);
      uint8_t dlens[30];
      for (int i = 0; i < 30; ++i) dlens[i] = 5;
      hbam_build_fast(dlens, 30, &dist_t);
    } else if (btype == 2) {      // dynamic tables [SPEC RFC1951 3.2.7]
      uint32_t hlit = hbam_getbits(&b, 5) + 257;
      uint32_t hdist = hbam_getbits(&b, 5) + 1;
      uint32_t hclen = hbam_getbits(&b, 4) + 4;
      if (hlit > 286 || hdist > 30) return -2;
      uint8_t cl[19] = {0};
      for (uint32_t i = 0; i < hclen; ++i)
        cl[kClPerm[i]] = static_cast<uint8_t>(hbam_getbits(&b, 3));
      if (b.consumed > limit) return -1;
      HbamHuff clh;
      if (hbam_build_huff(cl, 19, &clh) || clh.empty) return -2;
      uint8_t lens[288 + 30] = {0};
      uint32_t idx = 0;
      while (idx < hlit + hdist) {
        hbam_refill(&b);
        if (b.consumed > limit) return -1;
        const int s = hbam_decode_slow(&b, &clh);
        if (s < 0) return -2;
        if (s < 16) {
          lens[idx++] = static_cast<uint8_t>(s);
        } else {
          uint32_t rep;
          uint8_t val = 0;
          if (s == 16) {
            if (idx == 0) return -2;
            val = lens[idx - 1];
            rep = hbam_take(&b, 2) + 3;
          } else if (s == 17) {
            rep = hbam_take(&b, 3) + 3;
          } else {
            rep = hbam_take(&b, 7) + 11;
          }
          if (idx + rep > hlit + hdist) return -2;
          while (rep--) lens[idx++] = val;
        }
      }
      if (lens[256] == 0) return -2;   // end-of-block code must exist
      if (hbam_build_fast(lens, static_cast<int>(hlit), &lit_t) ||
          lit_t.slow.empty)
        return -2;
      if (hbam_build_fast(lens + hlit, static_cast<int>(hdist), &dist_t))
        return -2;
    } else {
      return -2;                  // btype 3 is reserved
    }
    for (;;) {                    // symbol loop: one refill covers the
      hbam_refill(&b);            // worst case 15+5+15+13 = 48 bits
      if (b.consumed > limit) return -1;
      int s = hbam_fast_sym(&b, &lit_t);
      if (s < 0) return -2;
      if (s < 256) {
        if (nt >= cap) return -3;
        tokens[nt++] = static_cast<uint32_t>(s);
        ++opos;
      } else if (s == 256) {
        break;
      } else {
        s -= 257;
        if (s >= 29 || dist_t.slow.empty) return -2;
        const uint32_t length = kLenBase[s] + hbam_take(&b, kLenExtra[s]);
        const int ds = hbam_fast_sym(&b, &dist_t);
        if (ds < 0 || ds >= 30) return -2;
        const uint32_t d = kDistBase[ds] + hbam_take(&b, kDistExtra[ds]);
        if (static_cast<int64_t>(d) > opos) return -4;
        if (nt >= cap) return -3;
        tokens[nt++] = 0x80000000u | (length << 16) | (d - 1);
        opos += length;
      }
    }
  } while (!bfinal);
  if (b.consumed > limit) return -1;
  *n_tokens = nt;
  *out_len = opos;
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

// Resolve a block's LZ77 tokens into ``scratch`` (grown as needed) and
// return the CRC32 of the inflated bytes — the tokenize-time CRC fold for
// the device decode plane.  The resolved bytes are a thread-local
// throwaway: the device resolves its own copy, this exists only so
// check_crc can be verified against the BGZF footer WITHOUT a host
// inflate pass materializing in the pipeline (the resolve here is
// cache-resident and far cheaper than the Huffman stage just paid).
static uint32_t hbam_tokens_crc32(const uint32_t* toks, int64_t nt,
                                  int64_t out_len,
                                  std::vector<uint8_t>* scratch) {
  if (static_cast<int64_t>(scratch->size()) < out_len)
    scratch->resize(static_cast<size_t>(out_len));
  uint8_t* out = scratch->data();
  int64_t p = 0;
  for (int64_t t = 0; t < nt; ++t) {
    const uint32_t tok = toks[t];
    if (tok & 0x80000000u) {
      const int64_t length = (tok >> 16) & 0x1FF;
      const int64_t dist = (tok & 0xFFFFu) + 1;
      // overlapping copies (dist < length) must run byte-serial
      const uint8_t* s = out + p - dist;
      for (int64_t k = 0; k < length; ++k) out[p + k] = s[k];
      p += length;
    } else {
      out[p++] = static_cast<uint8_t>(tok & 0xFF);
    }
  }
  return static_cast<uint32_t>(
      crc32(0L, out, static_cast<uInt>(out_len)));
}

// Threaded batch tokenize over independent blocks (same pool shape as
// hbam_inflate_batch).  tokens is [n_blocks, tok_stride] row-major.
// out_crcs (nullable): per-block CRC32 of the inflated bytes, folded in
// at tokenize time from a thread-local resolve scratch.
// Returns 0, or (1000 + first failing block index + 1000000 * -rc) so the
// caller can recover both which block failed and why (rc per
// hbam_deflate_tokenize: -1 truncated, -2 malformed, -3 token capacity,
// -4 bad distance).
int hbam_deflate_tokenize_batch(const uint8_t* src, const int64_t* off,
                                const int32_t* len, int32_t n_blocks,
                                uint32_t* tokens, int64_t tok_stride,
                                int32_t* n_tokens, int32_t* out_lens,
                                uint32_t* out_crcs, int32_t n_threads) {
  if (n_threads < 1) n_threads = 1;
  std::atomic<int32_t> next(0);
  std::atomic<int32_t> fail(-1);
  auto worker = [&]() {
    std::vector<uint8_t> scratch;
    for (;;) {
      const int32_t i = next.fetch_add(1);
      if (i >= n_blocks || fail.load(std::memory_order_relaxed) >= 0) break;
      int64_t nt = 0, ol = 0;
      const int rc = hbam_deflate_tokenize(
          src + off[i], len[i],
          tokens + static_cast<int64_t>(i) * tok_stride, tok_stride, &nt,
          &ol);
      if (rc) {
        int32_t e = -1;
        fail.compare_exchange_strong(e, i + 1000000 * -rc);
        break;
      }
      n_tokens[i] = static_cast<int32_t>(nt);
      out_lens[i] = static_cast<int32_t>(ol);
      if (out_crcs)
        out_crcs[i] = hbam_tokens_crc32(
            tokens + static_cast<int64_t>(i) * tok_stride, nt, ol, &scratch);
    }
  };
  if (n_threads == 1) {
    worker();
  } else {
    std::vector<std::thread> pool;
    pool.reserve(n_threads);
    for (int t = 0; t < n_threads; ++t) pool.emplace_back(worker);
    for (auto& th : pool) th.join();
  }
  const int32_t f = fail.load();
  return f >= 0 ? 1000 + f : 0;
}

}  // extern "C"
