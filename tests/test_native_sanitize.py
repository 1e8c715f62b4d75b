"""Sanitizer pass over the native C++ helper (SURVEY.md section 5, race
detection/sanitizers row).

The reference's Java got memory safety from the JVM; our native library
(native/hbam_native.cpp) has threads and raw offset arithmetic, so every
exported entry point is exercised here under AddressSanitizer AND
ThreadSanitizer: the library is rebuilt with -fsanitize=<mode> and
driven from a subprocess that preloads the matching runtime (a
non-instrumented python can only host an instrumented .so via
LD_PRELOAD).  The driver uses explicit n_threads=4 calls so both
sanitizers see the pthread batch loops.
"""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# The subprocess body: build fixtures in memory and push them through every
# native entry point (BGZF header walk, inflate, CRC, record walks,
# packed/payload walks, deflate, rANS 4x8 + Nx16, the BCF GT -> dosage
# kernel, the BCF record walker (chase / span columns / guess), the CRAM
# slice rebuild, the FASTQ tokenise + pack, the VCF text tokenise (its
# GT-only loop and keyed walk), the BGZF text span read and the text span
# columns with their contig table, the DEFLATE block finder /
# symbol decoder / resolve, the GWAS job's GRM finish).
# Multi-threaded calls are explicit so ASan sees the pthread paths.  It then drives the two
# Python-threaded planes TSan should watch end to end: the staging
# packer (FeedPipeline's pack thread racing the dispatch consumer over
# reused ring slots) and a two-replica serving fleet over real TCP
# (handler threads + heartbeat + decode pool + peer fetch).
DRIVER = r"""
import io, random, sys
import numpy as np
from hadoop_bam_tpu.utils import native
assert native.available(), "sanitized native build failed to load"

from hadoop_bam_tpu.formats.bamio import BamWriter
from hadoop_bam_tpu.formats.bam import SAMHeader
from hadoop_bam_tpu.formats.sam import SamRecord

header = SAMHeader.from_sam_text("@HD\tVN:1.6\n@SQ\tSN:chr1\tLN:1000000\n")
rng = random.Random(7)
sink = io.BytesIO()
with BamWriter(sink, header) as w:
    for i in range(400):
        l = rng.randint(30, 150)
        w.write_sam_record(SamRecord(
            qname=f"r{i}", flag=rng.choice([0, 16, 99]), rname="chr1",
            pos=1 + i * 10, mapq=60, cigar=f"{l}M", rnext="=",
            pnext=1 + i, tlen=200,
            seq="".join(rng.choice("ACGT") for _ in range(l)),
            qual="".join(chr(33 + rng.randint(2, 40)) for _ in range(l))))
raw = sink.getvalue()

from hadoop_bam_tpu.ops import inflate as inflate_ops
table = inflate_ops.block_table(raw)
# the native BGZF header walk: the clean chain, a table that fills and is
# resumed, and the refusals — a buffer cut anywhere inside the first two
# blocks must stop the walk at a block start without reading past the cut
cols, stop = native.block_table(np.frombuffer(raw, np.uint8), 0)
assert stop == len(raw) and (cols[0] == table["coffset"]).all()
assert (cols[3] == table["isize"]).all()
many, stop = native.block_table(np.frombuffer(raw[-28:] * 200, np.uint8), 0)
assert many[0].size == 200 and stop == 28 * 200
starts = (0, int(table["coffset"][1]), int(table["coffset"][2]))
for cut in list(range(40)) + list(range(40, starts[2], 97)):
    part, stop = native.block_table(
        np.frombuffer(bytes(raw[:cut]), np.uint8), 0)
    assert stop == max(s for s in starts if s <= cut) or stop == cut == 0
    assert part[0].size == starts.index(stop)
data, ubase = inflate_ops.inflate_span(raw, table, backend="native",
                                       n_threads=4)
inflate_ops.verify_crcs(raw, table, data, ubase, n_threads=4)

hdr, after = SAMHeader.from_bam_bytes(data.tobytes())
offs, tail = native.walk_bam_records(data, after, 1024)
assert offs.size == 400, offs.size

rows, offs2, _ = native.walk_bam_packed(
    data, after, 1024, [(0, 4), (4, 4), (12, 2)], 10)
assert (offs2 == offs).all()
prefix, seq, qual, offs3, _ = native.walk_bam_payload(
    data, after, 1024, 160, 80, 160)
assert (offs3 == offs).all()

comp = native.deflate_raw(data.tobytes()[:4096], level=6)
assert comp is not None

# rANS 4x8 both orders (decode dispatches to the native loop when loaded)
from hadoop_bam_tpu.formats import cram_codecs
payload = bytes(rng.choice(b"ACGT!#") for _ in range(5000))
for order in (0, 1):
    enc = cram_codecs.rans4x8_encode(payload, order=order)
    got = cram_codecs.rans4x8_decode(enc)
    assert got == payload, order

# rANS Nx16: every transform, one stream a call and a batch of them, and
# every prefix and a byte flip of each frame (errors only, never a read
# past the buffer); the run copy and the read pack of the CRAM path
from hadoop_bam_tpu.formats import cram_codecs_nx16 as nx
nx_frames = []
for nx_flags in (0x00, 0x01, 0x04, 0x05, 0x08, 0x09, 0x20, 0x40, 0x41,
                 0x80, 0x81, 0xC0, 0xC1, 0x0C):
    nx_data = (bytes(sorted(payload[:1500])) if nx_flags & 0x40
               else payload[:1500])
    nx_frame = nx.rans_nx16_encode(nx_data, nx_flags)
    assert native.rans_nx16_decode(nx_frame, len(nx_data)).tobytes() \
        == nx_data
    nx_frames.append((nx_frame, nx_data))
nx_outs = [np.empty(len(d), np.uint8) for _f, d in nx_frames]
assert not native.rans_nx16_decode_batch([f for f, _d in nx_frames],
                                         nx_outs).any()
assert all(o.tobytes() == d for o, (_f, d) in zip(nx_outs, nx_frames))
for nx_frame, nx_data in nx_frames:
    for cut in range(1, len(nx_frame), max(1, len(nx_frame) // 40)):
        try:
            native.rans_nx16_decode(nx_frame[:cut], len(nx_data))
        except cram_codecs.RansError:
            pass
    nx_bad = bytearray(nx_frame)
    nx_bad[len(nx_bad) // 2] ^= 0x55
    try:
        native.rans_nx16_decode(bytes(nx_bad), len(nx_data))
    except cram_codecs.RansError:
        pass
nx_dst = np.zeros(64, np.uint8)
assert native.copy_runs(nx_dst, np.arange(64, dtype=np.uint8),
                        np.array([0, 60]), np.array([10, 0]),
                        np.array([20, 4]))
assert not native.copy_runs(nx_dst, np.arange(64, dtype=np.uint8),
                            np.array([50]), np.array([0]), np.array([20]))
from hadoop_bam_tpu.api.read_datasets import ragged_to_payload_tiles
nx_seq = np.frombuffer(bytes(rng.choice(b"ACGTN") for _ in range(151 * 40)),
                       np.uint8)
_s, _q, nx_len = ragged_to_payload_tiles(nx_seq, np.full(40, 151), nx_seq,
                                         np.full(40, 151), 96, 160, 160)
assert (nx_len == 151).all()

# fused single-pass decode: 4 workers over 1-block chunks maximizes
# frontier/drain contention (inflate workers racing the walk), streamed
# consumption, the CRC fold, and the early-cancel join path
for mode, kw in (("offsets", {}),
                 ("rows", dict(sel=[(0, 4), (4, 4), (12, 2)],
                               row_stride=10)),
                 ("payload", dict(max_len=160, seq_stride=80,
                                  qual_stride=160))):
    dec = inflate_ops.FusedSpanDecode(raw, table, start=after, mode=mode,
                                      check_crc=True, chunk_blocks=1,
                                      n_threads=4, **kw)
    for _lo, _hi in dec.chunks():
        pass
    n, tail = dec.finish()
    assert n == 400 and (dec.offsets[:n] == offs).all(), (mode, n)
assert (dec.prefix[:n] == prefix).all()
assert (dec.seq[:n] == seq).all() and (dec.qual[:n] == qual).all()
cancelled = inflate_ops.FusedSpanDecode(raw, table, start=after,
                                        chunk_blocks=1, n_threads=4)
g = cancelled.chunks()
next(g)
g.close()          # join while workers may still be inflating
assert cancelled.n_rows is not None

# batch ITF8 (CRAM fixed-series predecode), incl. the truncation path
from hadoop_bam_tpu.formats.cram import write_itf8
vals = [0, 1, 127, 128, 16383, 2**28, -1] * 50
itf = np.frombuffer(b"".join(write_itf8(v) for v in vals), np.uint8)
got, used = native.itf8_decode_batch(itf, len(vals))
assert [int(v) for v in got] == vals and used == itf.size
try:
    native.itf8_decode_batch(itf[:3], 7)
    raise AssertionError("truncated ITF8 did not raise")
except ValueError:
    pass

# BCF GT -> dosage kernel: every (width, ploidy) inner loop, payloads
# ending on the buffer's last byte (an over-read is ASan's to see), the
# refusals, and four Python threads writing disjoint rows of one matrix
# with the interpreter lock released (TSan)
import threading
from hadoop_bam_tpu.formats.bcf import BCFError
from hadoop_bam_tpu.formats.bcf_columns import _GT_DTYPES, _gt_group_dosage
nrng = np.random.default_rng(5)
for typ, dt in _GT_DTYPES.items():
    info = np.iinfo(dt)
    pool = np.array([info.min, info.min + 1, 0, 1, 2, 3, 4, 5, 9, info.max])
    for ploidy in (0, 1, 2, 3, 7):
        ns = 333
        g = nrng.choice(pool, (8, ns, ploidy)).astype(dt)
        step = g[0].nbytes + 3
        raw_gt = bytearray()
        for r in range(8):
            raw_gt += b"\x00" * 3 + g[r].tobytes()      # ends on the last byte
        bgt = np.frombuffer(bytes(raw_gt), np.uint8)
        offs_gt = np.arange(8, dtype=np.int64) * step + 3
        rows_gt = np.arange(8, dtype=np.int64)[::-1].copy()
        want = np.full((8, ns + 5), -1, np.int8)
        got = want.copy()
        _gt_group_dosage(bgt, rows_gt, offs_gt, typ, ploidy, ns, want)
        ts = [threading.Thread(target=native.bcf_gt_dosage, args=(
                  bgt, rows_gt[k::4], offs_gt[k::4], typ, ploidy, ns, got))
              for k in range(4)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(60)
        assert got.tobytes() == want.tobytes(), (typ, ploidy)
        if ploidy:
            for bad_offs, bad_rows in ((offs_gt + 1, rows_gt),
                                       (offs_gt, rows_gt + 1)):
                try:
                    native.bcf_gt_dosage(bgt, bad_rows, bad_offs, typ,
                                         ploidy, ns, got)
                    raise AssertionError("GT overrun did not raise")
                except BCFError:
                    pass
            assert got.tobytes() == want.tobytes()

# the BCF record walker (chase, span columns, guess): every built record and
# every corruption of tests/test_bcf_native_walk.py from a buffer that ends
# on its last byte, a span cut at every byte (the chase and the guess must
# not look past the cut: ASan's to see), one flipped byte anywhere, starts
# past the buffer, windows shorter than a record head, and four Python
# threads walking one buffer with the interpreter lock released (TSan)
sys.path.insert(0, "tests")
import test_bcf_native_walk as W
from hadoop_bam_tpu.formats.bcf_columns import (
    _MAX_ALLELE_ROUNDS, _MAX_FMT_ROUNDS, _MAX_GT_PLOIDY, decode_bcf_columns)
def own(b):
    return np.frombuffer(bytes(b), np.uint8).copy()     # ends on its last byte
def walk(b, starts, pad=W.PAD):
    try:
        return native.bcf_span_columns(
            own(b), np.asarray(starts, np.int64), 3, pad,
            _MAX_ALLELE_ROUNDS, _MAX_FMT_ROUNDS, _MAX_GT_PLOIDY)
    except BCFError:
        return BCFError
whdr = W._header()
for name, recs in W.BUILT.items():
    span = b"".join(recs)
    starts_w, end_w, need_w = native.bcf_chase(own(span), 0, len(span))
    assert (end_w, need_w) == (len(span), 0) and starts_w.size == len(recs)
    assert walk(span, starts_w) not in (None, BCFError), name
for name, (span, starts_w) in W.CORRUPT.items():
    if starts_w is None:
        starts_w = [0] if name != "truncated-record" else [0, len(W._GOOD)]
    assert walk(span, starts_w) is BCFError, name
span = b"".join(W.BUILT["a-span-of-every-layout"]) * 2
clean, _, _ = native.bcf_chase(own(span), 0, len(span))
for cut in range(len(span) + 1):
    part = own(span[:cut])
    got_s, end_w, need_w = native.bcf_chase(part, 0, cut)
    assert end_w <= cut and (need_w == 0) == (end_w == cut)
    assert (got_s == clean[:got_s.size]).all()
    for partial in (False, True):
        u, _edge = native.bcf_guess(part, cut, 3, 3, partial)
        assert u < 0 or u in clean
    if cut % 7 == 0:
        walk(span[:cut], clean)         # starts past the cut: refused
assert walk(span, [len(span)]) is BCFError
assert walk(span, [len(span) - 31]) is BCFError
assert walk(span, [-(1 << 62)]) is BCFError and walk(span, [1 << 62]) is BCFError
assert native.bcf_guess(own(b"\x18" * 31), 31, 3, 3, False)[0] == -1
assert native.bcf_guess(np.empty(0, np.uint8), 5, 3, 3, True)[0] == -1
assert native.bcf_chase(np.empty(0, np.uint8), 0, 0)[0].size == 0
assert native.bcf_chase(own(span), len(span) + 5, len(span) + 9)[2] > 0
for _ in range(300):
    bad = bytearray(span)
    bad[rng.randrange(len(bad))] ^= 1 << rng.randrange(8)
    walk(bad, clean)
    walk(bad, clean, pad=3)
    chased, _, _ = native.bcf_chase(own(bad), 0, len(bad))
    walk(bad, chased)
shared_w = own(span * 40)
starts_w, _, _ = native.bcf_chase(shared_w, 0, shared_w.size)
want_w = decode_bcf_columns(shared_w, whdr, W.PAD, starts_w)
outs_w = [None] * 4
def walk_thread(k):
    outs_w[k] = decode_bcf_columns(shared_w, whdr, W.PAD)
ts = [threading.Thread(target=walk_thread, args=(k,)) for k in range(4)]
for t in ts:
    t.start()
for t in ts:
    t.join(60)
for got_w in outs_w:
    assert all(got_w[k].tobytes() == want_w[k].tobytes() for k in want_w)

# the CRAM slice rebuild: every fixture of tests/test_cram_native_walk.py and
# every cut of each payload stream, from streams that end on their buffer's
# last byte (a read past one is ASan's to see), the native walk and the NumPy
# twin one outcome; four Python threads rebuilding one slice with the
# interpreter lock released (TSan)
import test_cram_native_walk as CW
def cram_owned(built, cut_cid=None, cut=None):
    comp, hdr, core, ext = built
    ext = {k: np.frombuffer(bytes(v if k != cut_cid else v[:cut]),
                            np.uint8).copy() for k, v in ext.items()}
    return comp, hdr, core, ext
for name, mk in CW.SLICES.items():
    for ref in (CW.REF, CW.LOWER, None):
        CW._both(cram_owned(mk().build()), ref)
for case in CW.GEOMETRY:
    cb = CW.Slice()
    cb.add(rl=10, ap=9, features=CW.GEOMETRY[case])
    assert CW._both(cram_owned(cb.build()), CW.REF) is None
cb = CW.Slice()
CW._every_feature(cb)
cb.add(bf=0x4, rl=6, ap=0, ba=b"ACGTNN")
CW._every_feature(cb, ap=70, name=b"two")
cbuilt = cb.build()
for series, cid in CW._stream_cids(cbuilt).items():
    for cut in range(len(cbuilt[3][cid])):
        CW._both(cram_owned(cbuilt, cid, cut), CW.REF)
cbuilt = cram_owned(CW._random_slice(random.Random(8), 300).build())
cwant = CW._plain(CW._decode(cbuilt, CW.REF))
cgot = [None] * 4
def cram_thread(k):
    cgot[k] = [CW._plain(CW._decode(cbuilt, CW.REF)) for _ in range(10)]
ts = [threading.Thread(target=cram_thread, args=(k,)) for k in range(4)]
for t in ts:
    t.start()
for t in ts:
    t.join(120)
for per in cgot:
    for g in per:
        CW._same(g, cwant)

# FASTQ text -> payload tiles in one pass: the tiles of the NumPy twin on a
# text that ends on its buffer's last byte; the same text cut at every byte
# of its first records and its last (mid-name, mid-read, inside a CRLF: a
# line end looked for past the cut is ASan's to see) — the pass takes the
# cut text or refuses it, the twin agrees; no newline at all, only
# newlines, n = 0, rows whose strides cut them; four threads at once
from hadoop_bam_tpu.api import read_datasets as rds
from hadoop_bam_tpu.formats.fastq import FastqError
def fq_rec(i, eol, qlo=33):
    ln = rng.randint(0, 40)
    return (b"@r%d" % i + eol
            + bytes(rng.choice(b"ACGTNacgt") for _ in range(ln)) + eol + b"+"
            + eol + bytes(rng.randint(qlo, qlo + 41) for _ in range(ln)) + eol)
def fq_check(text, seq_stride=12, qual_stride=24, max_len=23, off=33):
    own = np.frombuffer(bytes(text), np.uint8).copy()    # ends on its last byte
    got = native.fastq_tokenize(own, rds._NIBBLE_CODE, seq_stride,
                                qual_stride, max_len, off)
    try:
        want = rds._fastq_text_to_payload_tiles_numpy(
            bytes(text), seq_stride, qual_stride, max_len, off)
    except FastqError:
        want = None
    assert (got is None) == (want is None), bytes(text[-40:])
    if got is not None:
        for g, w in zip(got, want):
            assert g.tobytes() == w.tobytes()
for eol in (b"\n", b"\r\n"):
    fq = b"".join(fq_rec(i, eol) for i in range(60))
    fq_check(fq)
    for cut in list(range(0, 200)) + list(range(len(fq) - 120, len(fq))):
        fq_check(fq[:cut])
    fq_check(fq, 3, 5, 40)
    fq_check(fq, 0, 0, 0)
    fq_check(fq, off=64)                                 # the guard refuses
    fq_check(b"".join(fq_rec(i, eol, 64) for i in range(60)), off=64)
for odd in (b"", b"\n", b"\r", b"\n" * 64, b"\r\n" * 8, b"@" * 300,
            b"@a\nACGT\n+\nIIII", b"@a\nACGT\n+\n", b"@\n\n+\n\n"):
    fq_check(odd)
fq_ok = []
def fq_many():
    for _ in range(20):
        fq_check(fq)
    fq_ok.append(1)
ts = [threading.Thread(target=fq_many) for _ in range(4)]
for t in ts:
    t.start()
for t in ts:
    t.join(60)
assert len(fq_ok) == 4

# the VCF text tokenise (hbam_vcf_tokenize): GT-only and keyed lines
# (FORMAT GT:AD:DP:GQ:PL: calls, no-calls bare and keyed, half-missing,
# multi-allelic, multi-digit and haploid GTs, a short line, a cell of 800
# bytes past a 64-byte stretch), the text cut at every byte of its first
# lines and its last (a line that ends inside a cell, a cell that runs past
# the text: ASan's to see), each copy ending on its last byte; the native
# pass and its fallback against the scalar parse; four threads at once
from hadoop_bam_tpu.formats.vcf import VCFHeader
from hadoop_bam_tpu.parallel import variant_pipeline as vp
vs = 12
vhdr = VCFHeader.from_text(
    "##fileformat=VCFv4.2\n##contig=<ID=chr20,length=64444167>\n"
    "#CHROM\tPOS\tID\tREF\tALT\tQUAL\tFILTER\tINFO\tFORMAT"
    + "".join("\ts%d" % i for i in range(vs)) + "\n")
vgeom = vp.VariantGeometry(n_samples=vs)
vcells = ["0/0:31,0:31:93:0,93,930", "0/1:14,12:26:99:350,0,420",
          "1/1:0,30:30:90:900,90,0", "./.", "./.:0,0:0:.:0,0,0",
          "./1:3,4:7:20:90,0,80", "1/2:0,9,8:17:99:600,300,280,0,0,300",
          ".", "10/1:1,1:2:3:4,5,6", "1", "0/0:" + "9," * 400 + "9:9:9:0"]
def vline(i):
    fmt = rng.choice(["GT", "GT:AD:DP:GQ:PL", "GT:AD:DP:GQ:PL"])
    cells = [rng.choice(vcells[:8] * 4 + vcells[8:]) for _ in range(vs)]
    if fmt == "GT":
        cells = [rng.choice(["0/0", "0|1", "1/1"]) for _ in range(vs)]
    if i % 9 == 4:
        cells = cells[:-1]
    return "\t".join(["chr20", str(1000 + i), ".", "A", "C,*", "50.5",
                      rng.choice(["PASS", "VQSRTrancheSNP99.80to100.00"]),
                      "AC=1", fmt] + cells)
vtext = ("\n".join(vline(i) for i in range(60)) + "\n").encode()
def vcheck(text):
    own = np.frombuffer(bytes(text), np.uint8).copy()   # ends on its last byte
    got = vp.pack_variant_tiles_from_text(own, vhdr, vgeom)
    want = vp._pack_variant_tiles_from_text_scalar(bytes(text), vhdr, vgeom)
    for k in want:
        assert (got[k] == want[k]).all(), (k, bytes(text[-40:]))
assert native.vcf_tokenize(np.frombuffer(vtext, np.uint8).copy(), vs, 16)[4]
for cut in list(range(0, 600)) + list(range(len(vtext) - 900, len(vtext))):
    vcheck(vtext[:cut])
for odd in (b"", b"\n", b"a\t1\tc\td\te\tf\tg\th\tGT:AD\t",
            b"chr20\t1\t.\tA\tC\t5\tPASS\t.\tGT:AD\t" + b"\t" * vs):
    vcheck(odd)
v_ok = []
def v_many():
    for _ in range(20):
        vcheck(vtext)
    v_ok.append(1)
ts = [threading.Thread(target=v_many) for _ in range(4)]
for t in ts:
    t.start()
for t in ts:
    t.join(60)
assert len(v_ok) == 4

# the BGZF text span read (hbam_vcf_text_span_read): spans of a file of
# small blocks whose lines run over several, the compressed bytes cut short
# after the span (a header or body of a following block missing), the room
# behind the text from none to a block; the span columns (hbam_contig_table,
# hbam_vcf_span_columns) over the tokenise's text cut at every byte of its
# head; four threads at once
from hadoop_bam_tpu.formats import bgzf
tfile = b"".join(b"chr20\t%d\t.\tA\tG\t40\tPASS\t%s\n" % (i, b"X" * (i * 37 % 3000))
                 for i in range(80))
tblocks = [bgzf.deflate_block(tfile[lo:lo + 1000])
           for lo in range(0, len(tfile), 1000)]
traw = b"".join(tblocks) + bgzf.EOF_BLOCK
tstarts = np.cumsum([0] + [len(b) for b in tblocks]).tolist()
def tread():
    for i in range(0, len(tblocks), 3):
        for j in (i, i + 1, i + 4):
            a, e = tstarts[i], tstarts[min(j, len(tblocks))]
            r0 = max(0, a - 65536)
            for cut in (len(traw), e + 30, e):
                raw = np.frombuffer(traw[r0:cut], np.uint8).copy()
                args = (raw, a - r0, e - a, len(traw) - r0)
                rc, info = native.vcf_text_span_read(*args, None)
                assert rc == 1, rc
                for room in (0, 100, 1 << 16):
                    out = np.empty(info[0] + room, np.uint8)
                    rc, got = native.vcf_text_span_read(*args, out)
                    assert rc in (0, 2) and got[2] <= got[0], (rc, got)
vtable = native.contig_table(vhdr.contigs + ["chr%d" % i for i in range(40)])
def tcols():
    for cut in list(range(0, 400)) + [len(vtext)]:
        own = np.frombuffer(vtext[:cut], np.uint8).copy()
        native.vcf_span_columns(own, -1, vs, 16, vtable)
t_ok = []
def t_many():
    tread()
    tcols()
    t_ok.append(1)
ts = [threading.Thread(target=t_many) for _ in range(4)]
for t in ts:
    t.start()
for t in ts:
    t.join(120)
assert len(t_ok) == 4

# DEFLATE inside a member (a gzip'd FASTQ's inflate workers): the block
# finder over a buffer that ends anywhere (a header read past the end is
# ASan's to see), the symbol decoder from every block start with its input
# and its room cut short, the resolve, four threads decoding at once (the
# fixed tables and the symbol tables are built on first use: TSan), and
# the speculative stream itself at a chunk of 2 KiB
import gzip, zlib
from hadoop_bam_tpu.split import read_planners
from hadoop_bam_tpu.utils.seekable import BytesByteSource
ftext = b"".join(
    b"@r%d\n%s\n+\n%s\n" % (i, bytes(rng.choice(b"ACGT") for _ in range(90)),
                           bytes(rng.randint(35, 74) for _ in range(90)))
    for i in range(1500))
zc = zlib.compressobj(6, zlib.DEFLATED, -15)
zraw = b"".join(zc.compress(ftext[a:a + 30000]) + zc.flush(zlib.Z_BLOCK)
                for a in range(0, len(ftext), 30000)) + zc.flush()
zfix = zlib.compressobj(6, zlib.DEFLATED, -15, 8, zlib.Z_FIXED)
zfixed = zfix.compress(ftext[:20000]) + zfix.flush()
W = native.DEFLATE_WINDOW
sbuf = native.deflate_symbol_buffer(len(ftext))
starts, at = [], 0
while True:
    at = native.deflate_find_block(np.frombuffer(zraw, np.uint8).copy(),
                                   at, 8 * len(zraw))
    if at < 0:
        break
    starts.append(at)
    at += 1
assert len(starts) >= 5, starts
for cut in list(range(1, 300, 7)) + [len(zraw) - k for k in range(1, 40)]:
    part = np.frombuffer(zraw[:cut], np.uint8).copy()   # ends on its last byte
    native.deflate_find_block(part, 0, 8 * cut)
    native.deflate_decode_symbols(part, 0, 1 << 40, 1 << 40, b"", sbuf)
def decode_all(k):
    for b0 in starts[k::4]:
        rc, end, syms = native.deflate_decode_symbols(
            zraw, b0, 1 << 40, 1 << 40, None,
            native.deflate_symbol_buffer(len(ftext)))
        assert rc == 1, rc
        for room in (0, 1, 257, 5000):
            rc2, _e, s2 = native.deflate_decode_symbols(
                zraw, b0, 1 << 40, 1 << 40, None,
                native.deflate_symbol_buffer(room))
            assert rc2 in (0, -2) and s2.size <= room
        out, crc, eols = native.deflate_resolve(
            syms, ftext[:len(ftext) - syms.size])
        assert out.tobytes() == ftext[len(ftext) - syms.size:]
        assert crc == zlib.crc32(out.tobytes())
    rc, _e, syms = native.deflate_decode_symbols(
        zfixed, 0, 1 << 40, 1 << 40, b"", native.deflate_symbol_buffer(20000))
    assert rc == 1 and syms.astype(np.uint8).tobytes() == ftext[:20000]
ts = [threading.Thread(target=decode_all, args=(k,)) for k in range(4)]
for t in ts:
    t.start()
for t in ts:
    t.join(120)
    assert not t.is_alive()
assert native.crc32_combine(zlib.crc32(ftext[:777]), zlib.crc32(ftext[777:]),
                            len(ftext) - 777) == zlib.crc32(ftext)
for blob in (gzip.compress(ftext, 4) + gzip.compress(ftext[:5000], 1),
             gzip.compress(ftext, 4)[:-9]):
    gzs = read_planners._SpeculativeMembers(
        BytesByteSource(blob), "mem.gz", "fastq", 2048, 4)
    try:
        got_text = b"".join(gzs.chunks(10000, 4))
        assert got_text == ftext + ftext[:5000]
    except read_planners.FastqError as e:
        assert "truncated" in str(e) and blob[-1:] != b"\0", e
    finally:
        gzs.close()

# the GWAS job's GRM finish: accumulators and outputs that end on their
# buffer's last byte (a tile written or read past either is ASan's to see),
# S a whole number of tiles, one short of one, one past one, S = Sp; the
# NumPy body the oracle; four threads finishing one accumulator into four
# outputs with the interpreter lock released (TSan)
from hadoop_bam_tpu.cohort.gwas import _grm_from_accumulators_numpy
grng = np.random.default_rng(5)
for s, sp in ((128, 128), (127, 128), (65, 130), (1, 1), (0, 4)):
    acc = grng.standard_normal((sp, sp)).astype(np.float32)
    r = grng.standard_normal(sp).astype(np.float32)
    got = native.grm_finish(acc, r, 0.25, 7, s, np.empty((s, s)))
    assert np.array_equal(got, _grm_from_accumulators_numpy(acc, r, 0.25, 7,
                                                            s))
acc = grng.standard_normal((130, 130)).astype(np.float32)
r = grng.standard_normal(130).astype(np.float32)
gouts = [np.empty((127, 127)) for _ in range(4)]
ts = [threading.Thread(target=native.grm_finish,
                       args=(acc, r, 0.5, 3, 127, o)) for o in gouts]
for t in ts:
    t.start()
for t in ts:
    t.join(120)
    assert not t.is_alive()
want = _grm_from_accumulators_numpy(acc, r, 0.5, 3, 127)
assert all(np.array_equal(o, want) for o in gouts)

# staging packer: the FeedPipeline's background pack thread races the
# dispatching consumer over reused ring slots — drive it with a host
# dispatch so the sanitizer watches the lease/release handoff itself
from hadoop_bam_tpu.parallel.staging import FeedPipeline, TileSpec
specs = (TileSpec((4,), np.uint8, 0), TileSpec((), np.int32, 0))
spans = []
total_rows = 0
for i in range(40):
    n = rng.randint(1, 30)
    total_rows += n
    spans.append((np.full((n, 4), i % 251, np.uint8),
                  np.arange(n, dtype=np.int32)))
fp = FeedPipeline(3, 16, specs, block_n=4, ring_slots=2,
                  dispatch_depth=2)
seen = []
fp.feed(iter(spans), lambda arrays, counts: seen.append(int(counts.sum())))
assert sum(seen) == total_rows, (sum(seen), total_rows)

# serve/fleet peer fetch: two in-process replicas over real TCP.  Each
# side runs TCP handler threads, the heartbeat loop and the shared
# decode pool, and replication=1 over two replicas forces peer fetches
# — the whole fleet thread topology drives the native decode at once.
import dataclasses, os, socket, tempfile, threading
from hadoop_bam_tpu.config import DEFAULT_CONFIG
from hadoop_bam_tpu.query import QueryEngine, QueryRequest
from hadoop_bam_tpu.serve import ServeLoop, make_tcp_server
from hadoop_bam_tpu.split.bai import write_bai

tmpdir = tempfile.mkdtemp()
bam_path = os.path.join(tmpdir, "f.bam")
with open(bam_path, "wb") as fh:
    fh.write(raw)
write_bai(bam_path)
regions = ["chr1:1-2000", "chr1:2001-4100"]
oracle = [len(r.records) for r in QueryEngine().query_records(
    [QueryRequest(bam_path, rg) for rg in regions])]

def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port

p1, p2 = _free_port(), _free_port()
peer_spec = f"r1=127.0.0.1:{p1},r2=127.0.0.1:{p2}"
loops, servers, sthreads = [], [], []
for rid, port in (("r1", p1), ("r2", p2)):
    cfg = dataclasses.replace(
        DEFAULT_CONFIG, serve_replica_id=rid, serve_peers=peer_spec,
        fleet_replication=1, fleet_heartbeat_s=0.1,
        serve_prefetch=False)
    loop = ServeLoop(config=cfg)
    loop.start()
    srv = make_tcp_server(loop, host="127.0.0.1", port=port)
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    loops.append(loop)
    servers.append(srv)
    sthreads.append(t)
try:
    counts1 = [r.count for r in loops[0].query(bam_path, regions)]
    counts2 = [r.count for r in loops[1].query(bam_path, regions)]
    assert counts1 == counts2 == oracle, (counts1, counts2, oracle)
    fl1, fl2 = loops[0].fleet, loops[1].fleet
    assert fl1.peer_fetch_ok + fl2.peer_fetch_ok > 0
    assert fl1.peer_fetch_failed == fl2.peer_fetch_failed == 0
finally:
    for srv in servers:
        srv.shutdown()
        srv.server_close()
    for loop in loops:
        loop.stop()
    for t in sthreads:
        t.join(5.0)
print("SANITIZED-OK")
"""


def _san_runtime(lib):
    try:
        out = subprocess.run(["g++", f"-print-file-name={lib}"],
                             capture_output=True, text=True, timeout=30)
    except Exception:
        return None
    path = out.stdout.strip()
    return path if path and os.path.sep in path and os.path.exists(path) \
        else None


# Races/interceptor noise inside the uninstrumented jax/numpy runtime
# libraries (XLA's Eigen thread pool handing buffers to numpy memcpy,
# MLIR thread-local cache teardown) are theirs, not ours: suppress by
# module so findings in native/hbam_native.cpp still fail the test.
_TSAN_SUPPRESSIONS = """\
race:libjax_common.so
race:libjaxlib_mlir_capi.so
race:_mlir.so
race:_multiarray_umath
called_from_lib:libjax_common.so
called_from_lib:libjaxlib_mlir_capi.so
"""


@pytest.mark.parametrize("mode,lib,marker", [
    ("address", "libasan.so", "AddressSanitizer"),
    ("thread", "libtsan.so", "ThreadSanitizer"),
])
def test_native_sanitized_clean(mode, lib, marker, tmp_path):
    runtime = _san_runtime(lib)
    if runtime is None:
        pytest.skip(f"g++/{lib} not available")
    # preload libstdc++ WITH the sanitizer runtime: the interceptors
    # resolve __cxa_throw at startup, before jaxlib's pybind modules
    # (which throw C++ exceptions) are dlopened — without it ASan
    # aborts on "real___cxa_throw != 0" the first time jax raises
    stdcxx = _san_runtime("libstdc++.so.6")
    preload = f"{runtime} {stdcxx}" if stdcxx else runtime
    supp = tmp_path / "tsan.supp"
    supp.write_text(_TSAN_SUPPRESSIONS)
    env = dict(os.environ)
    env.update({
        "HBAM_NATIVE_SANITIZE": mode,
        "LD_PRELOAD": preload,
        # CPython itself "leaks" interned objects; only instrument our .so's
        # heap errors, overflows, and races with the preloaded runtime.
        "ASAN_OPTIONS": "detect_leaks=0:abort_on_error=1",
        # CPython's own lock usage is not what we're testing — disable the
        # deadlock detector and mutex-misuse reports (the libgcc unwinder
        # and XLA's pool trip bogus ones from uninstrumented code); data
        # races in the .so's threaded batch loops still abort via
        # halt_on_error
        "TSAN_OPTIONS": "detect_deadlocks=0:report_signal_unsafe=0:"
                        "report_mutex_bugs=0:halt_on_error=1:"
                        f"suppressions={supp}",
        "JAX_PLATFORMS": "cpu",
    })
    proc = subprocess.run([sys.executable, "-c", DRIVER], cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=600)
    if proc.returncode != 0 and "SANITIZED-OK" not in proc.stdout \
            and "unexpected memory mapping" in proc.stderr:
        # TSan refusing to initialize under LD_PRELOAD into an
        # uninstrumented interpreter (ASLR layout) is a host problem,
        # not a sanitizer finding
        pytest.skip(f"{lib} failed to initialize on this host")
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert "SANITIZED-OK" in proc.stdout
    assert marker not in proc.stderr, proc.stderr[-4000:]
