"""``hbam seq-stats`` on a reference-compressed CRAM 3.1 — the
``na12878-chr20-cram31-x1`` deployment at its tiny size
(``tests/cram31_reference.py``, copied verbatim to
``benchmark/gen_cram31.py``): the file decodes through both slice decoders
to the same columns, the verb runs on ``plan.execute`` and equals the plain
reference and a BAM of the same records, only the blocks it reads are
decompressed, and a corrupt block it reads fails the scan."""
from __future__ import annotations

import dataclasses
import json
import os
import struct
import sys
import zlib

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import cram31_reference as C  # noqa: E402
from test_interop_specs import _oracle_nx16_full, _uint7_get  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = json.load(open(os.path.join(
    ROOT, "benchmark", "configs", "na12878-chr20-cram31-x1.json")))
BAM_CONFIG = json.load(open(os.path.join(
    ROOT, "benchmark", "configs", "na12878-chr20-x1.json")))
SEED = 3000000019
N_CHUNKS, CHUNK = CONFIG["tiny"]["chunks"], CONFIG["tiny"]["chunk_records"]


@pytest.fixture(scope="module")
def made(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("cram31"))
    written, sums = C.write_cram(d, SEED, N_CHUNKS, CHUNK)
    return written, sums


def _cfg(fasta):
    from hadoop_bam_tpu.config import DEFAULT_CONFIG

    return dataclasses.replace(DEFAULT_CONFIG,
                               cram_reference_source_path=fasta)


def _blocks(path):
    """(container offset, [(method, ctype, cid, payload offset, csize,
    rsize)]) of every data container, walked by hand."""
    with open(path, "rb") as fh:
        buf = fh.read()
    from hadoop_bam_tpu.formats.cram import ContainerHeader, read_itf8

    pos, out = 26, []
    while pos < len(buf):
        hdr, p = ContainerHeader.from_buffer(buf, pos)
        if hdr.is_eof:
            break
        end, blocks = p + hdr.length, []
        while p < end:
            method, ctype = buf[p], buf[p + 1]
            q = p + 2
            cid, q = read_itf8(buf, q)
            csize, q = read_itf8(buf, q)
            rsize, q = read_itf8(buf, q)
            blocks.append((method, ctype, cid, q, csize, rsize))
            p = q + csize + 4
        if hdr.n_records:
            out.append((pos, blocks))
        pos = end
    return buf, out


# ---------------------------------------------------------------------------
# the yardstick's own files
# ---------------------------------------------------------------------------

def test_the_benchmarks_generator_is_this_reference_verbatim():
    with open(os.path.join(ROOT, "tests", "cram31_reference.py"), "rb") as a, \
            open(os.path.join(ROOT, "benchmark", "gen_cram31.py"), "rb") as b:
        assert a.read() == b.read()


@pytest.mark.parametrize("key", sorted(BAM_CONFIG["shape"]))
def test_shape_keys_are_the_bam_configurations(key):
    assert CONFIG["shape"][key] == BAM_CONFIG["shape"][key]


def test_configuration_states_its_cut_and_sizes():
    assert CONFIG["sizes"] == BAM_CONFIG["sizes"]
    assert CONFIG["tiny"] == BAM_CONFIG["tiny"]
    assert list(CONFIG["reduced"]) == ["records"]
    assert CONFIG["architecture"] is None
    assert CONFIG["tolerances"]["printed"] == {"mean_gc": 2e-05,
                                               "mean_qual": 0.002}
    for key in ("block_trial", "slices", "nm_tag", "names", "rans_ways",
                "fasta", "page_cache"):
        assert key in CONFIG["assumed"], key


def test_fasta_and_its_index_are_samtools_layout(made):
    written, _ = made
    with open(written.fasta + ".fai") as fh:
        name, length, offset, lb, lw = fh.read().split()
    assert (name, int(length), int(lb), int(lw)) == (
        "chr20", C.CONTIG_LEN, 60, 61)
    with open(written.fasta, "rb") as fh:
        fh.seek(int(offset) + 61 * 1000)
        line = fh.read(61)
    assert line[-1:] == b"\n" and set(line[:-1]) <= set(b"ACGT")
    ref = C.genome(SEED)
    assert line[:-1] == ref[60000:60060].tobytes()


def test_every_rans_frame_decodes_through_the_clean_room_oracle(made):
    """Every rANS Nx16 block the generator wrote, and every token stream
    of its tok3 name blocks, decodes under the independent oracle of
    tests/test_interop_specs.py to what the program's decoder gives."""
    from hadoop_bam_tpu.formats.cram_codecs_nx16 import (
        rans_nx16_decode_python,
    )

    written, _ = made
    buf, conts = _blocks(written.cram)
    seen = {C.RANS_NX16: 0, C.NAME_TOK: 0}
    for _off, blocks in conts[:6]:
        for method, _ct, cid, p, csize, rsize in blocks:
            payload = buf[p:p + csize]
            if method == C.RANS_NX16:
                got = _oracle_nx16_full(payload, rsize)
                assert got == rans_nx16_decode_python(payload, rsize), cid
                seen[method] += 1
            elif method == C.NAME_TOK:
                i = 9
                while i < len(payload):
                    clen, i = _uint7_get(payload, i + 1)
                    frame = payload[i:i + clen]
                    assert _oracle_nx16_full(frame) == \
                        rans_nx16_decode_python(frame)
                    i += clen
                seen[method] += 1
    assert seen[C.RANS_NX16] > 20 and seen[C.NAME_TOK] == len(conts[:6])


def test_the_file_is_reference_compressed_cram31(made):
    from hadoop_bam_tpu.formats.cram import FileDefinition
    from hadoop_bam_tpu.formats.cram_decode import CompressionHeader
    from hadoop_bam_tpu.formats.cramio import read_cram_header

    written, _ = made
    buf, conts = _blocks(written.cram)
    fd = FileDefinition.from_bytes(buf)
    assert (fd.major, fd.minor) == (3, 1)
    header, _ = read_cram_header(written.cram)
    assert "M5:" in header.text and "SO:coordinate" in header.text
    _off, blocks = conts[0]
    method, _ct, _cid, p, csize, rsize = blocks[0]
    comp = CompressionHeader.from_bytes(buf[p:p + csize])
    assert comp.reference_required and comp.ap_delta
    assert comp.read_names_included
    assert "BB" not in comp.data_series      # no verbatim bases
    methods = {b[0] for _o, bl in conts for b in bl}
    assert {C.RANS_NX16, C.NAME_TOK} <= methods


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def records(made):
    """Every read of the file through the record decoder, and the
    generator's reads in the same order."""
    from hadoop_bam_tpu.formats.cram_decode import FastaReferenceSource
    from hadoop_bam_tpu.formats.cramio import read_cram

    written, _ = made
    _, recs = read_cram(written.cram, FastaReferenceSource(written.fasta))
    parts = [C.chunk_reads(SEED, c, N_CHUNKS, CHUNK, written.fasta)
             for c in range(N_CHUNKS)]
    return recs, parts


def test_record_decoder_rebuilds_every_read(records):
    """Bases, qualities, CIGAR, flags and positions of every read — the
    attached and detached mates, AP delta, all five CIGAR forms and the
    unplaced tail included."""
    import benchmark.gen as G

    recs, parts = records
    f = {k: np.concatenate([p[k] for p in parts]) for k in
         ("flag", "pos", "cig", "bases", "qual", "mapq", "refid")}
    assert len(recs) == f["flag"].size
    forms = ["151M", "12S139M", "141M10S", "70M2D81M", "5S60M3I83M", "*"]
    seqs = [r.seq.encode() for r in recs]
    assert seqs == [row.tobytes() for row in f["bases"]]
    assert [r.qual.encode() for r in recs] == [
        (row + 33).astype(np.uint8).tobytes() for row in f["qual"]]
    assert [r.cigar for r in recs] == [forms[k] for k in f["cig"]]
    assert [r.flag for r in recs] == f["flag"].tolist()
    assert [r.pos for r in recs] == np.where(
        f["refid"] >= 0, f["pos"] + 1, 0).tolist()
    assert [r.mapq for r in recs] == f["mapq"].tolist()
    assert set(f["cig"].tolist()) == set(range(6))
    assert int((f["refid"] < 0).sum()) > 0            # the unplaced tail
    assert len(set(r.qname for r in recs)) * 2 == len(recs)
    del G


@pytest.mark.parametrize("span_count", [1, 3, 7])
def test_columnar_and_record_paths_agree(made, span_count):
    from hadoop_bam_tpu.api.cram_dataset import open_cram
    from hadoop_bam_tpu.formats.cram_columns import records_to_columns
    from hadoop_bam_tpu.formats.cram_decode import (
        FastaReferenceSource, decode_slice_records,
    )
    from hadoop_bam_tpu.formats.cramio import iter_container_slices
    from hadoop_bam_tpu.split.cram_planner import (
        _iter_span_containers, read_cram_span_columns,
    )

    written, _ = made
    ds = open_cram(written.cram, _cfg(written.fasta))
    ref = FastaReferenceSource(written.fasta)
    cfs = []
    for span in ds.spans(num_spans=span_count):
        cols = read_cram_span_columns(written.cram, span, header=ds.header,
                                      ref_source=ref, want_names=True)
        recs = []
        for cont in _iter_span_containers(written.cram, span):
            for comp, sh, core, ext, lens in iter_container_slices(cont):
                recs += decode_slice_records(comp, sh, core, ext,
                                             ds.header.ref_names, ref,
                                             codec_rec_lens=lens)
        want = records_to_columns(recs, want_names=True)
        for k, v in want.items():
            if isinstance(v, bytes):
                assert cols[k] == v, k
            elif k == "n":
                assert cols[k] == v
            else:
                assert np.array_equal(cols[k], v), k
        cfs.append(cols["cf"])
    cf = np.concatenate(cfs)
    assert ((cf & C.CF_MATE_DOWNSTREAM) != 0).any()         # attached
    assert ((cf & C.CF_DETACHED) != 0).any()                # detached


def test_seq_stats_never_decompresses_names_tags_or_mate_series(made):
    """The columnar decoder asks only for the series seq-stats needs:
    the read names (tok3), the NM tag, TL and the detached-mate series
    stay compressed — counted as skipped — and every block it did ask
    for is counted as read."""
    from hadoop_bam_tpu.api.cram_dataset import open_cram
    from hadoop_bam_tpu.formats.cram import LazyBlock
    from hadoop_bam_tpu.formats.cram_decode import FastaReferenceSource
    from hadoop_bam_tpu.formats.cram_columns import decode_slice_columns
    from hadoop_bam_tpu.formats.cramio import iter_container_slices
    from hadoop_bam_tpu.split.cram_planner import _iter_span_containers

    written, _ = made
    ds = open_cram(written.cram, _cfg(written.fasta))
    ref = FastaReferenceSource(written.fasta)
    never = {C.CID[k] for k in ("RN", "NM", "TL", "MF", "NS", "NP", "TS")}
    span = ds.spans(num_spans=1)[0]
    features = 0
    for cont in _iter_span_containers(written.cram, span, lazy=True):
        assert all(isinstance(b, LazyBlock) for b in cont.blocks)
        for comp, sh, core, ext, lens in iter_container_slices(cont):
            assert decode_slice_columns(comp, sh, core, ext,
                                        ds.header.ref_names, ref) is not None
        touched = {b.content_id for b in cont.blocks[3:] if b.touched}
        assert not touched & never
        assert {C.CID["QS"], C.CID["BF"], C.CID["AP"]} <= touched
        features += C.CID["FC"] in touched      # not in the unplaced tail
    assert features == N_CHUNKS


def test_counters_say_what_was_read_and_skipped(made):
    from hadoop_bam_tpu.parallel.pipeline import cram_seq_stats_file
    from hadoop_bam_tpu.utils.metrics import base_metrics

    written, sums = made
    base_metrics().reset()
    res = cram_seq_stats_file(written.cram, config=_cfg(written.fasta))
    c = base_metrics().snapshot()["counters"]
    assert res["n_reads"] == sums.n
    assert c["cram.columnar_records"] == sums.n
    assert "cram.record_path_records" not in c
    assert c["cram.blocks_skipped_bytes"] > 0
    assert c["cram.nx16_native_bytes"] > 0
    assert "cram.nx16_python_bytes" not in c
    assert c["cram.compressed_bytes"] < os.path.getsize(written.cram)
    assert c["cram.decode_busy_ns"] > c["cram.entropy_busy_ns"] > 0
    assert c["plan.executions"] == 1


# ---------------------------------------------------------------------------
# the verb
# ---------------------------------------------------------------------------

def _seq_stats(argv):
    import contextlib
    import io

    from hadoop_bam_tpu.tools.cli import main

    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = main(list(argv))
    return rc, out.getvalue()


def test_verb_equals_the_plain_reference(made):
    written, sums = made
    rc, out = _seq_stats(["seq-stats", written.cram, "--reference",
                          written.fasta])
    assert rc == 0
    assert sums.wrong(out, CONFIG["tolerances"]["printed"]) is None


def test_verb_runs_on_plan_execute(made, monkeypatch):
    """``hbam seq-stats x.cram --reference`` builds the CRAM plan and
    hands it to the one executor, whose runner is chosen by the source
    format."""
    from hadoop_bam_tpu.plan import builders, executor

    written, _ = made
    seen = []
    real = executor.execute

    def spy(plan, **kw):
        seen.append(plan)
        return real(plan, **kw)

    monkeypatch.setattr(executor, "execute", spy)
    rc, _ = _seq_stats(["seq-stats", written.cram, "--reference",
                        written.fasta])
    assert rc == 0 and len(seen) == 1
    plan = seen[0]
    assert plan.source.fmt == "cram" and plan.sink.kind == "seq_stats"
    assert [op.op for op in plan.ops] == ["cram_decode", "payload_pack",
                                          "seq_stats_reduce"]
    ref = dict(plan.ops[0].params)["reference"]
    assert ref == os.path.abspath(written.fasta)
    other = builders.cram_stats_plan(written.cram, _cfg(written.cram))
    assert other.digest() != plan.digest()


def test_verb_equals_seq_stats_on_a_bam_of_the_same_records(made, records,
                                                            tmp_path):
    from hadoop_bam_tpu.formats.bam import SAMHeader
    from hadoop_bam_tpu.formats.bamio import write_bam
    import benchmark.gen as G

    written, _ = made
    recs, _parts = records
    bam = str(tmp_path / "same.bam")
    write_bam(bam, SAMHeader.from_sam_text(G.HEADER_TEXT), recs)
    _, got = _seq_stats(["seq-stats", written.cram, "--reference",
                         written.fasta])
    _, want = _seq_stats(["seq-stats", bam])
    assert got == want


def test_a_corrupt_block_the_scan_reads_fails_it(made, tmp_path):
    """A flipped byte in a quality block fails its CRC when the scan
    decompresses it: exit non-zero, no totals.  The same flip in a read
    name block, which the scan never decompresses, leaves the answer."""
    written, sums = made
    buf, conts = _blocks(written.cram)
    for cid, ok in ((C.CID["QS"], False), (C.CID["RN"], True)):
        _off, blocks = conts[1]
        p = next(b[3] for b in blocks if b[2] == cid)
        bad = bytearray(buf)
        bad[p + 5] ^= 0x5A
        path = str(tmp_path / f"bad{cid}.cram")
        with open(path, "wb") as fh:
            fh.write(bytes(bad))
        rc, out = _seq_stats(["seq-stats", path, "--reference",
                              written.fasta])
        if ok:
            assert rc == 0
            assert sums.wrong(out, CONFIG["tolerances"]["printed"]) is None
        else:
            assert rc != 0 and "reads\t" not in out


def test_python_decoder_gives_the_same_answer(made, monkeypatch):
    """Without the native library every rANS Nx16 stream takes the
    Python decoder (counted as such) and the answer is the same."""
    from hadoop_bam_tpu.formats import cram_codecs_nx16
    from hadoop_bam_tpu.parallel.pipeline import cram_seq_stats_file
    from hadoop_bam_tpu.utils import native
    from hadoop_bam_tpu.utils.metrics import base_metrics

    written, _ = made
    want = cram_seq_stats_file(written.cram, config=_cfg(written.fasta))
    real = native.available
    # keep the ITF8 batch decoder (the columnar path needs it); refuse
    # only the Nx16 pass, as a host whose library lacks it would
    monkeypatch.setattr(native, "rans_nx16_decode", lambda *a, **k: None)
    monkeypatch.setattr(native, "rans_nx16_decode_batch",
                        lambda p, o: np.full(len(p), -4, np.int32))
    base_metrics().reset()
    got = cram_seq_stats_file(written.cram, config=_cfg(written.fasta))
    c = base_metrics().snapshot()["counters"]
    assert "cram.nx16_native_bytes" not in c
    assert c["cram.nx16_python_bytes"] > 0
    assert got["n_reads"] == want["n_reads"]
    assert got["mean_gc"] == want["mean_gc"]
    assert got["mean_qual"] == want["mean_qual"]
    assert np.array_equal(got["base_hist"], want["base_hist"])
    assert real is native.available and cram_codecs_nx16 is not None


def test_header_read_takes_only_the_header_container(made, monkeypatch):
    """Opening the dataset reads the file definition and the header
    container, not the file."""
    from hadoop_bam_tpu.formats import cramio

    written, _ = made
    calls = []
    real = cramio._read_all
    monkeypatch.setattr(cramio, "_read_all",
                        lambda s: calls.append(s) or real(s))
    header, first = cramio.read_cram_header(written.cram)
    assert not calls and header.ref_names == ["chr20"] and first > 26


# ---------------------------------------------------------------------------
# the reference, as samtools holds one
# ---------------------------------------------------------------------------

FASTA_CASES = {
    "regular": b">a desc\n" + b"ACGTACGTAC\n" * 5 + b"ACG\n>b\nTTTT\nGG\n",
    "no_final_newline": b">a\nACGTA\nCC",
    "crlf": b">a\r\nACGT\r\nACGT\r\nAC\r\n>b\r\nGGGG\r\n",
    "ragged": b">a\nACGTACGT\nACG\nACGTACGTAA\n>b\nTT\nTTTTT\n",
    "blank_lines": b">a\nACGT\n\nACGT\n>b\nAAAA\n",
    "empty_contig": b">a\n>b\nACGT\n",
    "lowercase": b">a\nacgtNNnn\nacgt\n",
}


@pytest.mark.parametrize("case", sorted(FASTA_CASES))
@pytest.mark.parametrize("with_fai", [False, True])
def test_indexed_fasta_matches_a_whole_file_parse(case, with_fai, tmp_path):
    from hadoop_bam_tpu.formats.cram_decode import FastaReferenceSource
    from hadoop_bam_tpu.formats.fasta import parse_fasta

    data = FASTA_CASES[case]
    path = str(tmp_path / "ref.fa")
    with open(path, "wb") as fh:
        fh.write(data)
    if with_fai:
        FastaReferenceSource(path)          # builds and writes the .fai
    src = FastaReferenceSource(path)
    src_bytes = FastaReferenceSource(data)
    whole = {f.contig: f.sequence
             for f in parse_fasta(data, line_fragments=False)}
    for name, seq in whole.items():
        for start in range(0, len(seq) + 3):
            for length in (1, 3, 7, 60):
                want = seq[start - 1:start - 1 + length] if start else \
                    seq[-1:-1 + length]
                assert src.get(name, start, length) == want, (name, start)
                assert src_bytes.get(name, start, length) == want
                assert src.get_bytes(name, start, length).tobytes() == \
                    want.encode()
    regular = case not in ("ragged", "blank_lines")
    assert os.path.exists(path + ".fai") == regular


def test_fai_written_beside_the_fasta_is_samtools_faidx(tmp_path):
    from hadoop_bam_tpu.formats.cram_decode import FastaReferenceSource

    path = str(tmp_path / "ref.fa")
    with open(path, "wb") as fh:
        fh.write(FASTA_CASES["regular"])
    FastaReferenceSource(path)
    with open(path + ".fai") as fh:
        assert fh.read() == "a\t53\t8\t10\t11\nb\t6\t70\t4\t5\n"


def test_missing_contig_is_a_cram_error(tmp_path):
    from hadoop_bam_tpu.formats.cram import CRAMError
    from hadoop_bam_tpu.formats.cram_decode import FastaReferenceSource

    src = FastaReferenceSource(FASTA_CASES["regular"])
    with pytest.raises(CRAMError):
        src.get("chr1", 1, 10)


def test_opening_the_reference_reads_the_index_not_the_genome(made,
                                                              monkeypatch):
    from hadoop_bam_tpu.formats import fasta
    from hadoop_bam_tpu.formats.cram_decode import FastaReferenceSource

    written, _ = made
    monkeypatch.setattr(fasta, "build_fai", lambda *a: pytest.fail(
        "an indexed FASTA was parsed"))
    src = FastaReferenceSource(written.fasta)
    ref = C.genome(SEED)
    got = src.get_bytes("chr20", 1_000_001, 500)
    assert got.tobytes() == ref[1_000_000:1_000_500].tobytes()


# ---------------------------------------------------------------------------
# the generator's pieces
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("order", [0, 1])
@pytest.mark.parametrize("n", [32, 33, 100, 4097])
def test_vectorised_encoder_round_trips(order, n):
    """The lockstep encoder's frames decode through the program's Python
    decoder and the oracle, streams of many lengths at once."""
    from hadoop_bam_tpu.formats.cram_codecs_nx16 import (
        rans_nx16_decode_python,
    )

    rng = np.random.default_rng(n)
    datas = [rng.choice(np.frombuffer(b"ACGT!#", np.uint8), n + k)
             for k in range(5)] + [np.full(n, 7, np.uint8)]
    bodies = C.rans_encode_batch([C._Job(d, order) for d in datas])
    for d, body in zip(datas, bodies):
        frame = bytes([order]) + C.uint7(d.size) + body
        assert rans_nx16_decode_python(frame) == d.tobytes()
        assert _oracle_nx16_full(frame) == d.tobytes()


@pytest.mark.parametrize("values", [[0, 1, 127, 128, 16383, 16384,
                                     (1 << 21) - 1, 1 << 21, (1 << 28) - 1,
                                     1 << 28, -1, -150]])
def test_vectorised_itf8_is_the_programs(values):
    from hadoop_bam_tpu.formats.cram import write_itf8

    assert C.itf8_stream(np.array(values)).tobytes() == b"".join(
        write_itf8(v) for v in values)
    assert [C.itf8(v) for v in values] == [write_itf8(v) for v in values]


def test_eof_container_is_the_programs():
    from hadoop_bam_tpu.formats.cram import EOF_CONTAINER

    assert C.eof_container() == EOF_CONTAINER


def test_block_crc_matches(made):
    written, _ = made
    buf, conts = _blocks(written.cram)
    _off, blocks = conts[0]
    for method, _ct, cid, p, csize, rsize in blocks:
        start = p - 2 - sum(len(C.itf8(v)) for v in (cid, csize, rsize))
        (crc,) = struct.unpack_from("<I", buf, p + csize)
        assert zlib.crc32(buf[start:p + csize]) & 0xFFFFFFFF == crc


@pytest.mark.parametrize("rl,max_len,seq_stride,qual_stride,with_qual", [
    (151, 160, 96, 160, True), (151, 100, 96, 160, True),
    (150, 160, 96, 160, True), (151, 160, 40, 64, True),
    (7, 160, 96, 160, False), (1, 8, 4, 8, True)])
def test_native_read_pack_equals_the_numpy_pack(rl, max_len, seq_stride,
                                                qual_stride, with_qual,
                                                monkeypatch):
    from hadoop_bam_tpu.api import read_datasets as R
    from hadoop_bam_tpu.utils import native

    rng = np.random.default_rng(rl)
    n = 300
    seq = rng.choice(np.frombuffer(b"ACGTNacgtnRY=", np.uint8), n * rl)
    ql = rl if with_qual else 0
    qual = rng.integers(0, 42, n * ql).astype(np.uint8)
    args = (seq.tobytes(), np.full(n, rl), qual.tobytes(), np.full(n, ql),
            seq_stride, qual_stride, max_len)
    got = R.ragged_to_payload_tiles(*args)
    monkeypatch.setattr(native, "available", lambda: False)
    want = R.ragged_to_payload_tiles(*args)
    for a, b in zip(got, want):
        assert np.array_equal(a, b)


def test_explain_prints_the_cram_plan(made):
    written, _ = made
    rc, out = _seq_stats(["explain", "seq-stats", written.cram])
    assert rc == 0
    assert "cram_decode" in out and "seq_stats_reduce" in out


def test_native_copy_runs_refuses_a_run_outside_either_buffer():
    from hadoop_bam_tpu.utils import native

    src = np.arange(100, dtype=np.uint8)
    dst = np.zeros(50, np.uint8)
    assert native.copy_runs(dst, src, np.array([0, 10]), np.array([5, 90]),
                            np.array([3, 4]))
    assert dst[:3].tolist() == [5, 6, 7] and dst[10:14].tolist() == [
        90, 91, 92, 93]
    for d_at, s_at, ln in (([48], [0], [3]), ([0], [98], [3]),
                           ([-1], [0], [1]), ([0], [0], [-1])):
        assert not native.copy_runs(dst, src, np.array(d_at),
                                    np.array(s_at), np.array(ln))
