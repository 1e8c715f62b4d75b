"""Device DEFLATE: LZ77 back-reference resolution on the accelerator.

The reference hot loop inflates every 64 KiB BGZF block through zlib's JNI
(SURVEY.md section 3.2); the inflate CPU cost splits into two very
different halves:

1. **Huffman symbol decode** — a bit-serial, data-dependent branch cascade
   with no intra-stream parallelism.  This stays on the host
   (native/hbam_native.cpp::hbam_deflate_tokenize_batch, threaded across
   blocks), emitting fixed-width u32 LZ77 tokens:
   bit31 set -> copy (bits 16-24 length, bits 0-15 distance-1),
   bit31 clear -> literal byte.
2. **LZ77 copy resolution** — embarrassingly parallel across blocks AND,
   via pointer doubling, log-depth parallel across bytes.  This is the
   device half below.

Kernel shape (pure jnp/lax — batched gathers on the VPU, no scalar loops):

- token lengths -> exclusive cumsum gives each token's output start;
- scatter-add marks at starts, cumsum -> per-byte token id;
- per byte: ``src[p] = p - dist`` for copy bytes, ``src[p] = p`` (fixed
  point) for literals — an acyclic pointer forest rooted at literals;
- pointer doubling ``src = src[src]`` inside ``lax.while_loop`` until
  converged (<= ceil(log2(chain depth)) rounds; overlapping RLE-style
  copies are the deep-chain worst case), then one gather from the
  scattered literal bytes.

On top of the per-block resolve, this module provides the two fusions the
device decode plane (parallel/pipeline.py token-feed path) runs through:

- ``resolve_tokens_packed`` — resolve + one device-side slice/pack into a
  contiguous span buffer (replaces the old per-block host copy loop:
  ONE host sync per chunk instead of one per block);
- ``resolve_walk_fields`` — resolve + pack + an on-device record walk
  (the block_size chain traversed by the same pointer-doubling trick:
  log-depth scatter/gather rounds instead of a serial host walk) + the
  ``ops/unpack_bam.FIXED_FIELDS`` gather, so the resolved bytes NEVER
  leave the device on the stats paths: flagstat/coverage predicates read
  the columns straight from the device-resident inflated buffer.

Shape discipline: ``(B, T, P)`` are canonicalized — ``T == P`` and ``P``
clamped to the small pow2 ``P_LADDER`` — so heterogeneous chunks share
one jit cache entry per ladder rung (the compile-count test in
tests/test_inflate_device.py pins this).

Measurement discipline: the host tokenize stage, the on-chip resolve
(jitted, inputs device-resident, excludes the H2D link), and the
end-to-end span inflate are timed separately (``probe_device_plane``;
PERF.md holds what was measured on the current machine).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from hadoop_bam_tpu.formats import bgzf
from hadoop_bam_tpu.ops.rans import _round_pow2
from hadoop_bam_tpu.ops.unpack_bam import PREFIX, unpack_fixed_fields_tile
from hadoop_bam_tpu.resilience import chaos
from hadoop_bam_tpu.utils import native

# BGZF caps a block's inflated size at 64 KiB [SPEC SAMv1 4.1]
BGZF_MAX_ISIZE = 1 << 16

# The canonical per-block width ladder: P (inflated bytes per block, ==
# the token-axis pad T) snaps UP to one of these, so a run over spans
# whose max ISIZE wanders (mixed BAM/BCF/tabix block sizes, short final
# blocks) compiles each kernel at most len(P_LADDER) times instead of
# once per distinct pow2 (the jit-cache churn the round-11 issue calls
# out).  Three rungs: tiny index/EOF blocks, mid-size text blocks, and
# full 64 KiB BAM blocks.
P_LADDER = (1 << 10, 1 << 13, 1 << 16)


def ladder_pow2(x: int) -> int:
    """Snap a per-block byte width up to the canonical P_LADDER rung."""
    for p in P_LADDER:
        if x <= p:
            return p
    raise bgzf.BGZFError(
        f"block inflated size {x} exceeds the BGZF 64 KiB cap")


def records_cap(B: int, P: int) -> int:
    """Static record capacity for a [B, P] chunk's device walk: the
    minimum on-wire BAM record is 36 bytes (4-byte block_size + 32-byte
    fixed core), so B*P//32 rounded to a pow2 can never be exceeded by
    well-formed data — an overflow IS corruption (same taxonomy as the
    fused native path's capacity fault)."""
    return _round_pow2(max(16, (B * P) // 32), 16)


@functools.partial(jax.jit, static_argnames=("P",))
def resolve_tokens(tokens: jax.Array, n_tokens: jax.Array, P: int
                   ) -> jax.Array:
    """Resolve LZ77 tokens to inflated bytes: [B, T] u32 + [B] i32 -> [B, P] u8.

    Positions past each block's output length hold junk; the caller slices
    by out_lens.  P must be >= every block's inflated size."""
    B, T = tokens.shape
    is_copy = (tokens >> 31).astype(jnp.int32)
    tok_len = jnp.where(is_copy == 1,
                        ((tokens >> 16) & 0x1FF).astype(jnp.int32), 1)
    tid = jnp.arange(T, dtype=jnp.int32)[None, :]
    valid = tid < n_tokens[:, None]
    tok_len = jnp.where(valid, tok_len, 0)
    starts = jnp.cumsum(tok_len, axis=1) - tok_len          # exclusive

    # per-byte token id: scatter 1 at each token start (zero-length pads
    # land in a sacrificial extra column), cumsum, -1
    scat = jnp.where((tok_len > 0) & valid, starts, P)
    marks = jnp.zeros((B, P + 1), jnp.int32).at[
        jnp.arange(B, dtype=jnp.int32)[:, None], scat].add(1)
    tok_of_byte = jnp.cumsum(marks[:, :P], axis=1) - 1
    tok_of_byte = jnp.clip(tok_of_byte, 0, T - 1)

    w = jnp.take_along_axis(tokens, tok_of_byte, axis=1)    # token per byte
    pos = jnp.arange(P, dtype=jnp.int32)[None, :]
    byte_is_copy = (w >> 31).astype(jnp.int32)
    dist = (w & 0xFFFF).astype(jnp.int32) + 1
    src = jnp.where(byte_is_copy == 1, pos - dist, pos)
    src = jnp.clip(src, 0, P - 1)   # tokenizer guarantees dist <= position
    lit = jnp.where(byte_is_copy == 1, 0, w & 0xFF).astype(jnp.uint8)

    # pointer doubling until every byte points at its literal root; the
    # forest is acyclic (src[p] < p for copies) so this terminates in
    # <= ceil(log2(P)) rounds, far fewer for typical shallow chains
    def cond(c):
        return c[1]

    def body(c):
        s, _ = c
        s2 = jnp.take_along_axis(s, s, axis=1)
        return s2, jnp.any(s2 != s)

    src, _ = jax.lax.while_loop(cond, body, (src, jnp.bool_(True)))
    return jnp.take_along_axis(lit, src, axis=1)


def _pack_contiguous(blk_bytes: jax.Array, isize: jax.Array
                     ) -> Tuple[jax.Array, jax.Array]:
    """[B, P] per-block bytes + [B] isize -> ([B*P] contiguous buffer,
    total) — the device-side slice/pack that replaced the per-block host
    copy loop.  Bytes past ``total`` are zero."""
    B, P = blk_bytes.shape
    iz = jnp.minimum(jnp.maximum(isize.astype(jnp.int32), 0), P)
    ubase = jnp.concatenate([jnp.zeros(1, jnp.int32), jnp.cumsum(iz)])
    total = ubase[B]
    L = B * P
    q = jnp.arange(L, dtype=jnp.int32)
    # block of output byte q: last block whose start is <= q (repeated
    # boundaries from empty blocks resolve to the owning block)
    blk = jnp.searchsorted(ubase[1:], q, side="right").astype(jnp.int32)
    blk = jnp.minimum(blk, B - 1)
    off = jnp.clip(q - ubase[blk], 0, P - 1)
    out = blk_bytes.reshape(-1)[blk * P + off]
    return jnp.where(q < total, out, jnp.uint8(0)), total


@jax.jit
def resolve_tokens_packed(tokens: jax.Array, n_tokens: jax.Array,
                          isize: jax.Array) -> jax.Array:
    """Resolve a token chunk and pack it contiguous on device:
    [B, P] u32 + [B] i32 + [B] i32 -> [B*P] u8 (junk past sum(isize) is
    zeroed).  ONE host copy per chunk replaces the per-block loop."""
    B, P = tokens.shape
    blk_bytes = resolve_tokens(tokens, n_tokens, P)
    buf, _ = _pack_contiguous(blk_bytes, isize)
    return buf


def _walk_records_device(buf: jax.Array, total: jax.Array,
                         start: jax.Array, stop: jax.Array, R: int
                         ) -> Tuple[jax.Array, jax.Array, jax.Array,
                                    jax.Array]:
    """On-device BAM record walk over a contiguous inflated buffer.

    The record chain (``offset[i+1] = offset[i] + 4 + block_size[i]``) is
    a linked list rooted at ``start``; instead of a serial host walk, the
    successor array is built for EVERY byte position and the reachable
    set is computed by pointer doubling — ``ceil(log2(n_records))``
    gather+scatter rounds, each fully parallel (the same log-depth trick
    the LZ77 resolve uses).

    Returns (offsets [R] i32 — record starts owned by [start, stop),
    n_all i32 — the UNCLAMPED owned count (> R flags a capacity fault),
    tail i32 — the first incomplete record's offset (== the walked end
    when every record completed), bad i32 — 1 when a reached record has
    an absurd block_size (< 32) with its size field fully readable: the
    malformed-chain corruption the host walkers raise on)."""
    L = buf.shape[0]
    pos = jnp.arange(L, dtype=jnp.int32)
    bufp = jnp.concatenate([buf, jnp.zeros(4, jnp.uint8)]).astype(jnp.uint32)
    bs = (bufp[:L] | (bufp[1:L + 1] << 8) | (bufp[2:L + 2] << 16)
          | (bufp[3:L + 3] << 24)).astype(jnp.int32)
    has_size = pos + 4 <= total
    # bs > L can only be a record cut at the buffer end (the host path
    # extends past and completes it — the driver's tail fixup does the
    # same), never followed on device; negative/absurd bs at a reached
    # position with a readable size field is corruption
    bs_ok = has_size & (bs >= 32) & (bs <= L)
    rec_end = pos + 4 + jnp.where(bs_ok, bs, 0)
    complete = bs_ok & (rec_end <= total)
    SINK = L
    nxt = jnp.where(complete, jnp.minimum(rec_end, L), SINK)
    jumps = jnp.concatenate([nxt, jnp.array([SINK], jnp.int32)])
    marks = jnp.zeros(L + 1, jnp.int32).at[jnp.minimum(start, L)].set(1)

    def cond(c):
        return c[2]

    def body(c):
        m, j, _ = c
        prop = jnp.zeros_like(m).at[j].max(m)
        m2 = jnp.maximum(m, prop)
        return m2, j[j], jnp.any(m2 != m)

    marks, _, _ = jax.lax.while_loop(cond, body,
                                     (marks, jumps, jnp.bool_(True)))
    started = marks[:L] == 1
    term = started & ~complete
    bad = jnp.any(term & has_size & (bs < 32)).astype(jnp.int32)
    tail = jnp.min(jnp.where(term, pos, total))
    kept = started & complete & (pos < stop)
    n_all = jnp.sum(kept.astype(jnp.int32))
    rank = jnp.cumsum(kept.astype(jnp.int32)) - 1
    tgt = jnp.where(kept & (rank < R), rank, R)   # R = sacrificial sink
    offs = jnp.zeros(R + 1, jnp.int32).at[tgt].max(pos)[:R]
    return offs, n_all, tail, bad


@jax.jit
def resolve_walk_fields(tokens: jax.Array, n_tokens: jax.Array,
                        isize: jax.Array, start: jax.Array,
                        stop: jax.Array):
    """The fused device decode step: resolve + contiguous pack + record
    walk + FIXED_FIELDS gather, all on device — the resolved bytes never
    leave the accelerator.

    Inputs: one chunk's [B, P] u32 tokens (T == P canonical pad), [B] i32
    token counts and per-block ISIZEs, and the chunk's record-walk window
    ``[start, stop)`` in inflated-buffer coordinates.

    Returns (cols, valid, n_all, tail, bad): ``cols`` is the
    ops/unpack_bam fixed-field column dict of the owned records (rows
    past ``valid`` hold junk gathered at offset 0 — the standard padding
    convention), ``n_all`` the unclamped owned-record count, ``tail`` the
    first incomplete record's offset, ``bad`` the malformed-chain flag.
    Static shape per (B, P) ladder rung; R derives from them."""
    B, P = tokens.shape
    R = records_cap(B, P)
    blk_bytes = resolve_tokens(tokens, n_tokens, P)
    buf, total = _pack_contiguous(blk_bytes, isize)
    offs, n_all, tail, bad = _walk_records_device(buf, total, start, stop, R)
    L = B * P
    idx = jnp.clip(
        offs[:, None] + jnp.arange(PREFIX, dtype=jnp.int32)[None, :],
        0, L - 1)
    tile = buf[idx]
    cols = unpack_fixed_fields_tile(tile)
    valid = jnp.arange(R, dtype=jnp.int32) < jnp.minimum(n_all, R)
    return cols, valid, n_all, tail, bad


# Per-record CIGAR word capacity of the serve-tile device walk.  Reads
# with more ops than this (ultra-long split alignments) make the whole
# chunk fall back to the host build — flagged via ``over``, never
# silently truncated, because end1 derived from a truncated CIGAR would
# be WRONG (a value fault, not a capacity fault).  64 ops covers >99.9%
# of real short/long-read alignments while keeping the gather tile
# [R, 64, 4] bytes.
DEVICE_TILE_CIGAR_CAP = 64


@functools.partial(jax.jit,
                   static_argnames=("max_len", "seq_stride", "qual_stride"))
def resolve_walk_payload(tokens: jax.Array, n_tokens: jax.Array,
                         isize: jax.Array, start: jax.Array,
                         stop: jax.Array, max_len: int, seq_stride: int,
                         qual_stride: int):
    """Device decode step for the variable-length payload family:
    resolve + pack + record walk + FIXED_FIELDS gather + segmented
    seq/qual extraction — the inflated bytes never leave the device.

    The variable-length sections are flattened by the same trick the
    record walk uses: the walk's pointer-doubling offsets give each
    record's start, the fixed columns give the per-record seq offset
    (``PREFIX + l_read_name + 4*n_cigar``), and one segmented gather per
    stream lifts the packed 4-bit bases and quals into the padded
    ``[R, stride]`` tiles ops/seq_pallas consumes (same stride/truncation
    convention as the host packer ``decode_span_payload_host``).

    Returns (cols, seq, qual, valid, n_all, tail, bad); ``bad`` also
    folds in the payload-bounds fault the host walker raises as
    ``ValueError("malformed BAM record chain")`` — a record whose seq or
    qual section overruns its own block_size."""
    B, P = tokens.shape
    R = records_cap(B, P)
    blk_bytes = resolve_tokens(tokens, n_tokens, P)
    buf, total = _pack_contiguous(blk_bytes, isize)
    offs, n_all, tail, bad = _walk_records_device(buf, total, start, stop, R)
    L = B * P
    idx = jnp.clip(
        offs[:, None] + jnp.arange(PREFIX, dtype=jnp.int32)[None, :],
        0, L - 1)
    cols = unpack_fixed_fields_tile(buf[idx])
    valid = jnp.arange(R, dtype=jnp.int32) < jnp.minimum(n_all, R)
    l_seq = cols["l_seq"]
    seq_off = offs + PREFIX + cols["l_read_name"] + 4 * cols["n_cigar"]
    nb = (jnp.maximum(l_seq, 0) + 1) // 2
    pay_bad = valid & (
        (l_seq < 0)
        | ((seq_off - offs) + nb + jnp.maximum(l_seq, 0)
           > 4 + cols["block_size"]))
    bad = jnp.maximum(bad, jnp.any(pay_bad).astype(jnp.int32))
    use = jnp.where(valid, jnp.clip(l_seq, 0, max_len), 0)
    half = (use + 1) // 2
    js = jnp.arange(seq_stride, dtype=jnp.int32)[None, :]
    seq = jnp.where(
        js < half[:, None],
        buf[jnp.clip(seq_off[:, None] + js, 0, L - 1)], jnp.uint8(0))
    jq = jnp.arange(qual_stride, dtype=jnp.int32)[None, :]
    qual = jnp.where(
        jq < use[:, None],
        buf[jnp.clip(seq_off[:, None] + nb[:, None] + jq, 0, L - 1)],
        jnp.uint8(0))
    return cols, seq, qual, valid, n_all, tail, bad


@functools.partial(jax.jit, static_argnames=("cigar_cap",))
def resolve_walk_intervals(tokens: jax.Array, n_tokens: jax.Array,
                           isize: jax.Array, start: jax.Array,
                           stop: jax.Array,
                           cigar_cap: int = DEVICE_TILE_CIGAR_CAP):
    """Device decode step for the serve-tile family: resolve + pack +
    record walk + the (rid, pos1, end1) interval columns the tile filter
    consumes, with end1 derived from an on-device CIGAR walk.

    Mirrors the host chunk decode (query/engine._decode_bam_chunk +
    formats/bam.BamBatch.reference_span): reference span sums the op
    lengths of M/D/N/=/X ops; '*'-CIGAR records fall back to l_seq;
    pos1/end1 are 1-based and clamped to int32 max.  Records with more
    than ``cigar_cap`` CIGAR ops raise the ``over`` flag — the driver
    falls back to the host build for the whole chunk rather than serve a
    wrong end1.

    Returns (rid, pos1, end1, n_all, tail, bad, over); rows past the
    owned count hold the tile pad values (rid -1, pos1/end1 0)."""
    B, P = tokens.shape
    R = records_cap(B, P)
    blk_bytes = resolve_tokens(tokens, n_tokens, P)
    buf, total = _pack_contiguous(blk_bytes, isize)
    offs, n_all, tail, bad = _walk_records_device(buf, total, start, stop, R)
    L = B * P
    idx = jnp.clip(
        offs[:, None] + jnp.arange(PREFIX, dtype=jnp.int32)[None, :],
        0, L - 1)
    cols = unpack_fixed_fields_tile(buf[idx])
    valid = jnp.arange(R, dtype=jnp.int32) < jnp.minimum(n_all, R)
    n_cigar = cols["n_cigar"]
    l_seq = cols["l_seq"]
    over = jnp.any(valid & (n_cigar > cigar_cap)).astype(jnp.int32)
    cig_off = offs + PREFIX + cols["l_read_name"]
    k = jnp.arange(cigar_cap, dtype=jnp.int32)[None, :]
    widx = cig_off[:, None] + 4 * k
    b0 = buf[jnp.clip(widx, 0, L - 1)].astype(jnp.uint32)
    b1 = buf[jnp.clip(widx + 1, 0, L - 1)].astype(jnp.uint32)
    b2 = buf[jnp.clip(widx + 2, 0, L - 1)].astype(jnp.uint32)
    b3 = buf[jnp.clip(widx + 3, 0, L - 1)].astype(jnp.uint32)
    word = b0 | (b1 << 8) | (b2 << 16) | (b3 << 24)
    op = (word & 0xF).astype(jnp.int32)
    oplen = (word >> 4).astype(jnp.int32)
    consumes = ((op == 0) | (op == 2) | (op == 3) | (op == 7) | (op == 8))
    act = k < jnp.minimum(n_cigar, cigar_cap)[:, None]
    cig_span = jnp.sum(jnp.where(act & consumes, oplen, 0), axis=1)
    ref_span = jnp.where(n_cigar > 0, cig_span, jnp.maximum(l_seq, 0))
    imax = jnp.int32(2**31 - 1)
    pos1 = jnp.minimum(cols["pos"], imax - 1) + 1
    end1 = pos1 + jnp.minimum(jnp.maximum(ref_span, 1) - 1, imax - pos1)
    rid = jnp.where(valid, cols["refid"], -1)
    pos1 = jnp.where(valid, pos1, 0)
    end1 = jnp.where(valid, end1, 0)
    return rid, pos1, end1, n_all, tail, bad, over


@jax.jit
def variant_prefix_device(buf: jax.Array, starts: jax.Array):
    """BCF fixed-prefix gather riding a resolved-bytes device buffer:
    [L] u8 + [R] i32 record starts -> (chrom [R] i32, pos [R] i32,
    1-based).  The same little-endian assembly formats/bcf_columns
    applies to bytes 8..32 of each record (the 24-byte core after the
    two length words); rows whose start is a pad (< 0) gather at 0 and
    are masked by the caller's valid count."""
    L = buf.shape[0]
    idx = jnp.clip(
        starts[:, None] + jnp.arange(8, 32, dtype=jnp.int32)[None, :],
        0, L - 1)
    tile = buf[idx].astype(jnp.uint32)

    def _i32(o):
        return (tile[:, o] | (tile[:, o + 1] << 8) | (tile[:, o + 2] << 16)
                | (tile[:, o + 3] << 24)).astype(jnp.int32)

    return _i32(0), _i32(4) + 1


@functools.partial(jax.jit,
                   static_argnames=("width", "count", "n_sample"))
def variant_gt_dosage_device(buf: jax.Array, gt_off: jax.Array,
                             width: int, count: int, n_sample: int):
    """Grouped GT gather -> per-sample ALT dosage for one (int width,
    ploidy, n_sample) combo, on device: [L] u8 buffer + [R2] i32 GT data
    offsets -> [R2, n_sample] i8 dosage.

    Byte-for-byte the formats/bcf_columns._decode_columns GT semantics:
    little-endian sign-extended ints, END_OF_VECTOR sentinel trims
    ploidy, any MISSING allele (or allele value 0) makes the call
    missing (-1), otherwise dosage = count of ALT alleles, saturated at
    127.  One jit entry per combo — combos are a property of the file's
    FORMAT layout, stable across spans."""
    L = buf.shape[0]
    R2 = gt_off.shape[0]
    nbytes = width * count * n_sample
    idx = jnp.clip(
        gt_off[:, None] + jnp.arange(nbytes, dtype=jnp.int32)[None, :],
        0, L - 1)
    raw = buf[idx].astype(jnp.uint32).reshape(R2, n_sample, count, width)
    shifts = (jnp.arange(width, dtype=jnp.uint32) * 8)[None, None, None, :]
    w = jnp.sum(raw << shifts, axis=-1, dtype=jnp.uint32)
    if width < 4:
        sbit = jnp.uint32(1 << (8 * width - 1))
        w = w & jnp.uint32((1 << (8 * width)) - 1)
        g = (w ^ sbit).astype(jnp.int32) - sbit.astype(jnp.int32)
    else:
        g = w.astype(jnp.int32)
    missing_val = -(1 << (8 * width - 1))
    present = g != (missing_val + 1)          # END_OF_VECTOR sentinel
    miss = present & (((g >> 1) == 0) | (g == missing_val))
    alt = present & (((g >> 1) - 1) > 0)
    d = jnp.where(
        jnp.any(present, axis=2) & ~jnp.any(miss, axis=2),
        jnp.sum(alt.astype(jnp.int32), axis=2), -1)
    return jnp.minimum(d, 127).astype(jnp.int8)


def inflate_span_device(raw: bytes, table: Optional[dict] = None,
                        chunk: int = 64, n_threads: int = 0,
                        check_crc: bool = False
                        ) -> Tuple[np.ndarray, np.ndarray]:
    """Inflate a BGZF span with host Huffman tokenize + device LZ77 resolve.

    Same contract as ops.inflate.inflate_span: returns (contiguous
    inflated bytes, per-block starting offsets).  ``check_crc`` verifies
    every block's BGZF CRC32 footer against a CRC folded into the native
    tokenize pass (no separate host inflate sweep), raising the same
    ``BGZFError`` the host paths raise."""
    from hadoop_bam_tpu.ops.inflate import block_table, footer_crcs
    if table is None:
        table = block_table(raw)
    if not native.available():
        # PLAN class: selecting the device backend without the native
        # library is a configuration fault — classify_error must not
        # treat it as transient (old RuntimeError fell through to the
        # generic CORRUPT bucket; retrying could never heal it either)
        from hadoop_bam_tpu.utils.errors import PlanError
        raise PlanError(
            "device inflate needs the native tokenizer "
            "(hbam_deflate_tokenize_batch); native library unavailable")
    isize = table["isize"]
    n = isize.size
    ubase = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(isize, out=ubase[1:])
    dst = np.empty(int(ubase[-1]), dtype=np.uint8)
    src = np.frombuffer(raw, dtype=np.uint8)
    expect = footer_crcs(src, table) if check_crc else None

    for lo in range(0, n, chunk):
        # chaos point at the library-level device step: injected faults
        # here hit the same plane boundary the pipeline's dispatch-level
        # device.step point covers, for callers that use this entry
        # directly (chunk index rides along for schedule targeting)
        chaos.fire("device.step", chunk_lo=lo)
        hi = min(lo + chunk, n)
        sub_isize = isize[lo:hi]
        # canonical (B, T, P): P snaps to the ladder (not the chunk's own
        # pow2 — mixed spans then share one jit entry per rung), the
        # token axis pads to P, B to a pow2 row count
        P = ladder_pow2(max(16, int(sub_isize.max())) if hi > lo else 16)
        b_cap = _round_pow2(hi - lo, 8)
        try:
            out = native.deflate_tokenize_batch(
                src, table["cdata_off"][lo:hi], table["cdata_len"][lo:hi],
                P, n_threads, with_crc=check_crc)
        except ValueError as e:
            # same class as the host backends: bad DEFLATE bytes are a
            # BGZF-level corruption whichever plane finds them
            raise bgzf.BGZFError(str(e)) from e
        tokens, n_tokens, out_lens = out[:3]
        if not np.array_equal(out_lens, sub_isize):
            bad = int(np.nonzero(out_lens != sub_isize)[0][0])
            raise bgzf.BGZFError(
                f"ISIZE mismatch in block {lo + bad}: tokenized "
                f"{int(out_lens[bad])}, footer says {int(sub_isize[bad])}")
        if check_crc:
            mism = np.nonzero(out[3] != expect[lo:hi])[0]
            if mism.size:
                raise bgzf.BGZFError(
                    f"CRC32 mismatch in block(s) "
                    f"{(mism[:8] + lo).tolist()}")
        if b_cap != hi - lo:
            tokens = np.vstack(
                [tokens, np.zeros((b_cap - (hi - lo), P), np.uint32)])
            n_tokens = np.concatenate(
                [n_tokens, np.zeros(b_cap - (hi - lo), np.int32)])
        iz_pad = np.zeros(b_cap, dtype=np.int32)
        iz_pad[: hi - lo] = sub_isize
        # device-side slice/pack: the resolve output comes back as ONE
        # contiguous chunk buffer (a single host copy per chunk) instead
        # of the old per-block copy loop
        out_bytes = np.asarray(resolve_tokens_packed(
            jnp.asarray(tokens), jnp.asarray(n_tokens),
            jnp.asarray(iz_pad)))
        dst[int(ubase[lo]):int(ubase[hi])] = \
            out_bytes[: int(ubase[hi] - ubase[lo])]
    return dst, ubase[:-1]


# ---------------------------------------------------------------------------
# Plane selection probe (config.resolve_inflate_backend's "auto" input)
# ---------------------------------------------------------------------------

def probe_device_plane(payload_bytes: int = 1 << 16,
                       force: bool = False) -> dict:
    """Measure once whether the device decode plane can beat fused-native
    host inflate on THIS process's default device.

    The plane's steady-state wall is ``max(tokenize, resolve)`` (the two
    stages overlap); fused-native pays the full host inflate.  The probe
    times both halves on one synthetic 64 KiB block and reports the
    decision.  On the CPU backend the answer is forced to host (the
    device plane cannot beat host inflate when the "device" IS the host
    CPU running XLA) unless ``force`` — which tests use to exercise the
    probe mechanics."""
    import time
    import zlib

    out = {"device_wins": False, "tokenize_s": None, "resolve_s": None,
           "inflate_s": None,
           "backend": jax.default_backend()}
    if not native.available():
        return out
    if jax.default_backend() == "cpu" and not force:
        return out
    rng = np.random.RandomState(0)
    data = rng.choice(np.frombuffer(b"ACGT", np.uint8),
                      size=payload_bytes).tobytes()
    co = zlib.compressobj(6, zlib.DEFLATED, -15)
    comp = co.compress(data) + co.flush()
    src = np.frombuffer(comp, np.uint8)
    off = np.array([0], np.int64)
    ln = np.array([len(comp)], np.int32)
    P = ladder_pow2(len(data))

    from hadoop_bam_tpu.utils.metrics import METRICS

    def timeit(fn, label, reps=3):
        fn()                      # warmup (jit compile / page-in)
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            fn()
            best = min(best, time.perf_counter() - t0)
        # probe measurements feed the metrics layer, so the once-per-
        # process plane decision is visible in traces and snapshots
        METRICS.observe(f"pipeline.plane_probe_{label}", best)
        return best

    toks, nt, _ = native.deflate_tokenize_batch(src, off, ln, P, 1)
    toks_d = jnp.asarray(toks)
    nt_d = jnp.asarray(nt)
    out["tokenize_s"] = timeit(
        lambda: native.deflate_tokenize_batch(src, off, ln, P, 1),
        "tokenize_s")
    out["resolve_s"] = timeit(
        lambda: resolve_tokens(toks_d, nt_d, P).block_until_ready(),
        "resolve_s")
    # the host baseline must be the plane the device actually competes
    # with: the NATIVE batched inflate (libdeflate when built in, ~2x
    # Python zlib) — benchmarking zlib here would systematically
    # overestimate host cost and mis-pick the device plane
    dst = np.empty(len(data), dtype=np.uint8)
    dst_off = np.zeros(1, np.int64)
    isz = np.array([len(data)], np.int32)
    out["inflate_s"] = timeit(
        lambda: native.inflate_batch(src, off, ln, dst, dst_off, isz, 1),
        "inflate_s")
    out["device_wins"] = (max(out["tokenize_s"], out["resolve_s"])
                          < out["inflate_s"])
    METRICS.count("pipeline.plane_probe_device_wins",
                  int(out["device_wins"]))
    return out
