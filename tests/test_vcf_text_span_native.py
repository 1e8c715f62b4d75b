"""The native text span against the Python composition it stands for.

A BGZF VCF span goes from its compressed bytes to its stats columns in
native calls with the interpreter lock released: the read
(``split/vcf_planners.py::_lease_bgzf_text``, ``hbam_vcf_text_span_read``)
and the columns (``parallel/variant_pipeline.py::span_columns_native``,
``hbam_vcf_span_columns``).  The Python composition — the block-by-block
read (``_inflate_text_python``, ``_prev_block_last_byte``, ``_owned_text``)
and ``pack_variant_tiles_from_text`` with ``_fixed_field_columns`` — is the
oracle: the same bytes, the same columns, the same rows sent to the scalar
parse (but a wide ALT's, whose flags the native pass sets itself), the same
counters.
"""
from __future__ import annotations

import os
import random

import numpy as np
import pytest

import kgp30x_gatk_reference as G
import kgp3_reference as K
import kgp3_vcf_reference as V
from hadoop_bam_tpu.formats import bgzf
from hadoop_bam_tpu.formats.vcf import VCFHeader
from hadoop_bam_tpu.parallel import variant_pipeline as vp
from hadoop_bam_tpu.split import vcf_planners
from hadoop_bam_tpu.split.spans import FileByteSpan
from hadoop_bam_tpu.split.vcf_planners import (
    bgzf_text_span_lines, plan_bgzf_text_spans,
)
from hadoop_bam_tpu.utils import native
from hadoop_bam_tpu.utils.metrics import base_metrics

needs_native = pytest.mark.skipif(not native.available(),
                                  reason="no native library on this host")

_COUNTERS = ("vcf.text_native_records", "vcf.text_bulk_records",
             "vcf.text_scalar_records", "vcf.text_keyed_records",
             "vcf.text_nocall_cells")


def _header(n_samples: int, contigs=("chr1", "chr20")) -> VCFHeader:
    return VCFHeader.from_text(
        "##fileformat=VCFv4.2\n"
        + "".join(f"##contig=<ID={c},length=1000000>\n" for c in contigs)
        + "#CHROM\tPOS\tID\tREF\tALT\tQUAL\tFILTER\tINFO\tFORMAT"
        + "".join(f"\ts{i}" for i in range(n_samples)) + "\n")


def _line(chrom, pos, ref, alt, filt, fmt, cells):
    return "\t".join([chrom, str(pos), ".", ref, alt, "50", filt, "AC=1",
                      fmt] + list(cells))


def _record_lines(text: bytes) -> int:
    return sum(1 for ln in text.split(b"\n")
               if ln and ln[:1] != b"#" and ln.count(b"\t") >= 7)


def _columns(text: bytes, header: VCFHeader, geom, monkeypatch):
    """(native columns, oracle columns, native counters, oracle counters,
    native scalar rows, oracle scalar rows)."""
    seen = []
    real = vp._patch_scalar_rows

    def spy(cols, buf, rows, lines, header, geometry):
        seen.append(sorted(int(r) for r in rows))
        real(cols, buf, rows, lines, header, geometry)

    monkeypatch.setattr(vp, "_patch_scalar_rows", spy)
    table = native.contig_table(header.contigs)
    out = []
    for run in ("native", "native-counted", "oracle"):
        base_metrics().reset()
        if run == "oracle":
            cols = vp.pack_variant_tiles_from_text(text, header, geom)
        else:
            records = _record_lines(text) if run == "native-counted" else -1
            cols = vp.span_columns_native(text, records, header, geom,
                                          table)
        c = base_metrics().snapshot()["counters"]
        out.append((cols, {k: c.get(k, 0) for k in _COUNTERS},
                    seen.pop() if seen else []))
    (nat, nat_c, nat_rows), (cnt, cnt_c, cnt_rows), (ora, ora_c, ora_rows) \
        = out
    for k in ora:
        assert nat[k].dtype == ora[k].dtype and nat[k].shape == ora[k].shape
        assert np.array_equal(nat[k], ora[k]), k
        assert np.array_equal(cnt[k], ora[k]), k
    assert (cnt_c, cnt_rows) == (nat_c, nat_rows)
    return nat, ora, nat_c, ora_c, nat_rows, ora_rows


def _gt_phased(rng, n, s):
    return [_line("chr20", 1000 + i, "A", rng.choice(["G", "C,T"]),
                  rng.choice(["PASS", "LowQual"]), "GT",
                  [rng.choice(["0|0", "0|1", "1|0", "1|1", "0/1"])
                   for _ in range(s)]) for i in range(n)]


def _gatk_keyed(rng, n, s):
    cells = ["0/0:31,0:31:93:0,93,930", "0/1:14,12:26:99:350,0,420",
             "1/1:0,30:30:90:900,90,0", "./.:0,0:0:.:0,0,0", ".", "./.",
             "./1:3,4:7:20:90,0,80"]
    return [_line("chr20", 1000 + i, "A", "C", rng.choice(
        ["PASS", "VQSRTrancheSNP99.80to100.00"]), "GT:AD:DP:GQ:PL",
        [rng.choice(cells) for _ in range(s)]) for i in range(n)]


_WIDE_ALTS = ["A,C,G,T,N,A,C,G,T", "A,C,G,T,N,A,C,G,T,A", "ACGTACGTACGTACGTACG",
              "A,C,G,T,N,A,C,G,T,X", "A,C,G,T,N,A,C,G,,T", "A,C,G,T,N,A,*,G,T",
              "<DEL>,A,C,G,T,N,A,C,G", "A,C,G,T,N,a,C,G,T"]


def _wide_alt(rng, n, s):
    alts = _WIDE_ALTS + ["*", "A,*", "G", "."]
    return [_line("chr20", 1000 + i, rng.choice(["A", "AT"]),
                  alts[i % len(alts)], "PASS", rng.choice(["GT", "GT:DP"]),
                  [rng.choice(["0|1", "1|1"]) for _ in range(s)])
            for i in range(n)]


def _scalar_rows(rng, n, s):
    forms = ["0|1", "10/1", "1", "0", "1/2", "./.", "0|0|1", "1/11"]
    return [_line("chr20", 1000 + i, "A", "C", "PASS", rng.choice(
        ["GT", "GT:DP"]), [rng.choice(forms) for _ in range(s)])
        for i in range(n)]


def _odd_pos(rng, n, s):
    poss = ["00000000012", " 12", "+5", "1_2", "2147483647", "0", "7",
            "0000000001"]
    return [_line("chr20", poss[i % len(poss)], "A", "C", "PASS", "GT",
                  ["0|1"] * s) for i in range(n)]


CONTIGS = [c for c, _ in G.contigs()]


def _many_contigs(rng, n, s):
    names = CONTIGS[:5] + CONTIGS[-5:] + [rng.choice(CONTIGS)
                                          for _ in range(20)]
    names += ["chrUnknown", "", CONTIGS[0] + "x", CONTIGS[0][:-1]]
    return [_line(names[i % len(names)], 1000 + i, "A", "C", "PASS", "GT",
                  ["0|1"] * s) for i in range(n)]


@needs_native
@pytest.mark.parametrize("make,n_samples,contigs", [
    (_gt_phased, 7, ("chr1", "chr20")),
    (_gatk_keyed, 9, ("chr1", "chr20")),
    (_wide_alt, 4, ("chr1", "chr20")),
    (_scalar_rows, 5, ("chr1", "chr20")),
    (_odd_pos, 3, ("chr1", "chr20")),
    (_many_contigs, 3, tuple(CONTIGS)),
    (_gatk_keyed, 0, ("chr20",)),
], ids=["gt-phased", "gatk-keyed", "wide-alt", "scalar-rows", "odd-pos",
        "3366-contigs", "no-samples"])
def test_span_columns_equal_the_python_composition(make, n_samples, contigs,
                                                   monkeypatch):
    """Columns, rows sent to the scalar parse and counters of the native
    pass equal the composition's, and both equal the scalar parse of every
    line; the only rows the native pass keeps from the scalar parse are
    the otherwise bulk rows whose ALT is wider than ``_ALT_W``."""
    header = _header(n_samples, contigs)
    geom = vp.VariantGeometry(n_samples=n_samples)
    lines = make(random.Random(47), 160, n_samples)
    lines[3:3] = ["", "#a comment", "too\tfew\tfields"]
    text = ("\n".join(lines) + "\n").encode()
    nat, ora, nat_c, ora_c, nat_rows, ora_rows = _columns(
        text, header, geom, monkeypatch)
    want = vp._pack_variant_tiles_from_text_scalar(text, header, geom)
    for k in want:
        assert np.array_equal(want[k], nat[k]), k
    records = [ln for ln in lines if ln and ln[0] != "#"
               and ln.count("\t") >= 7]
    wide = {i for i, ln in enumerate(records)
            if len(ln.split("\t")[4]) > vp._ALT_W} - set(nat_rows)
    assert set(ora_rows) == set(nat_rows) | wide
    assert not wide & set(nat_rows)
    keyed_wide = sum(1 for i in wide if records[i].split("\t")[8]
                     .startswith("GT:") and n_samples)
    assert nat_c["vcf.text_native_records"] == len(records)
    assert nat_c["vcf.text_scalar_records"] == len(nat_rows)
    assert nat_c["vcf.text_bulk_records"] == \
        ora_c["vcf.text_bulk_records"] + len(wide)
    assert nat_c["vcf.text_keyed_records"] == \
        ora_c["vcf.text_keyed_records"] + keyed_wide
    if not wide:
        assert nat_c == ora_c
    if make is _wide_alt:
        assert wide and nat["flags"][sorted(wide)].any()
    if make is _gatk_keyed and n_samples:
        assert nat_c["vcf.text_nocall_cells"] > 0
        assert nat_c["vcf.text_keyed_records"] == len(records)
    if make in (_scalar_rows, _odd_pos):
        assert nat_rows and nat_rows == ora_rows
    if make is _many_contigs:
        assert (nat["chrom"] == -1).any()
        assert nat["chrom"].max() == len(CONTIGS) - 1


@needs_native
@pytest.mark.parametrize("pos", ["", "x1", "12a", "2147483648",
                                 "99999999999"])
def test_a_pos_the_scalar_parse_refuses_raises_on_both_paths(pos):
    """An empty POS, a non-digit, a value past int32: the native pass
    refuses the line and the scalar parse raises as it does for the
    composition."""
    header = _header(2)
    geom = vp.VariantGeometry(n_samples=2)
    text = ("\n".join([_line("chr20", 5, "A", "C", "PASS", "GT",
                             ["0|1"] * 2),
                       _line("chr20", pos, "A", "C", "PASS", "GT",
                             ["0|1"] * 2)]) + "\n").encode()
    with pytest.raises((ValueError, OverflowError)) as oracle:
        vp.pack_variant_tiles_from_text(text, header, geom)
    with pytest.raises((ValueError, OverflowError)) as got:
        vp.span_columns_native(text, -1, header, geom,
                               native.contig_table(header.contigs))
    assert got.type is oracle.type


@needs_native
def test_contig_table_is_the_dict_of_the_header():
    """The table built once a scan answers as ``{name: index}`` does: the
    later of two equal names, -1 for a name it lacks, whatever the
    header's size."""
    names = CONTIGS + ["chr1", "chrM"]          # repeats: the later wins
    header = _header(1, names)
    assert header.contigs == names
    want = {c.encode(): i for i, c in enumerate(names)}
    lines = [_line(c, 1, "A", "C", "PASS", "GT", ["0|1"]) for c in
             names[::7] + ["chr1", "chrM", "chrUn_x", "chr", "chr10_"]]
    text = ("\n".join(lines) + "\n").encode()
    cols, refused, _, _ = native.vcf_span_columns(
        text, -1, 1, 8, native.contig_table(names))
    assert refused.shape[0] == 0
    assert cols["chrom"].tolist() == [
        want.get(ln.split("\t")[0].encode(), -1) for ln in lines]
    empty = native.contig_table([])
    cols, _, _, _ = native.vcf_span_columns(text[:len(lines[0]) + 1], -1, 1,
                                            8, empty)
    assert cols["chrom"].tolist() == [-1]


# -- the read ----------------------------------------------------------------

def _write_blocks(path: str, text: bytes, cuts) -> None:
    """``text`` as BGZF blocks cut at ``cuts`` (offsets in the text)."""
    edges = [0] + list(cuts) + [len(text)]
    with open(path, "wb") as f:
        f.write(b"".join(bgzf.deflate_block(text[a:b])
                         for a, b in zip(edges, edges[1:]) if b > a)
                + bgzf.EOF_BLOCK)


def _both_reads(path, spans, monkeypatch):
    """Each span's (text, records) by the native read, the Python read's
    texts, and the native run's counters."""
    base_metrics().reset()
    fast = []
    for s in spans:
        with bgzf_text_span_lines(path, s) as (text, records):
            fast.append((bytes(text), records))
    c = base_metrics().snapshot()["counters"]
    with monkeypatch.context() as m:
        m.setattr(vcf_planners.native, "available", lambda: False)
        slow = []
        for s in spans:
            with bgzf_text_span_lines(path, s) as (text, records):
                assert records == -1
                slow.append(bytes(text))
    assert [t for t, _ in fast] == slow
    for t, records in fast:
        assert records in (-1, _record_lines(t))
    return fast, c


def _text(n_lines: int, width: int, seed: int = 5) -> bytes:
    rng = random.Random(seed)
    head = ("##fileformat=VCFv4.2\n##contig=<ID=chr20,length=64444167>\n"
            "#CHROM\tPOS\tID\tREF\tALT\tQUAL\tFILTER\tINFO\n")
    body = [f"chr20\t{100 + i}\t.\tA\tG\t40\tPASS\t"
            + "X" * rng.randrange(width // 2, width) for i in range(n_lines)]
    return (head + "\n".join(body) + "\n").encode()


@needs_native
@pytest.mark.parametrize("case", ["mid-line", "line-ends-at-block-end",
                                  "header-span", "empty-span",
                                  "line-longer-than-a-block",
                                  "line-longer-than-the-room"])
def test_native_read_is_the_python_read(case, tmp_path, monkeypatch):
    """The owned lines and their record count, span by span, against the
    Python read: spans that start mid-line, lines that end exactly at a
    block's end, the first span with the header, an empty span, a line
    longer than a block (finished by the native read) and a line longer
    than the read's room (finished in Python, counted)."""
    path = str(tmp_path / f"{case}.vcf.gz")
    if case == "line-ends-at-block-end":
        text = _text(80, 300)
        ends = [i + 1 for i, b in enumerate(text) if b == 0x0A]
        _write_blocks(path, text, ends[::3])
    elif case == "line-longer-than-a-block":
        text = _text(12, 150_000)
        _write_blocks(path, text, range(50_000, len(text), 50_000))
    elif case == "line-longer-than-the-room":
        text = _text(3, 2_000_000)
        _write_blocks(path, text, range(60_000, len(text), 60_000))
    else:
        text = _text(300, 400)
        _write_blocks(path, text, range(1_000, len(text), 1_000))
    size = os.path.getsize(path)
    if case == "empty-span":
        spans = [FileByteSpan(path, 0, 0), FileByteSpan(path, size, size)]
    elif case == "header-span":
        spans = plan_bgzf_text_spans(path, num_spans=1)
    else:
        spans = plan_bgzf_text_spans(path, num_spans=9)
    fast, c = _both_reads(path, spans, monkeypatch)
    if case == "empty-span":
        assert fast == [(b"", -1)] * 2
        assert c["vcf.python_read_spans"] == 2
        return
    assert b"".join(t for t, _ in fast) == text
    assert c["vcf.native_read_spans"] == len(spans)
    assert "vcf.python_read_spans" not in c
    tails = c.get("vcf.text_span_tail_spans", 0)
    if case == "line-longer-than-the-room":
        assert tails >= 1
        assert any(r == -1 for _, r in fast)
    else:
        assert tails == 0
        assert all(r == _record_lines(t) for t, r in fast)
    if case == "header-span":
        assert fast[0][0].startswith(b"##fileformat")


def _reference_file(tmp_path, kind: str) -> str:
    path = str(tmp_path / f"{kind}.vcf.gz")
    if kind == "kgp3":
        shape = K.Shape((5, 4, 6), unphased=0.3, missing=0.05,
                        haploid=0.05)
        V.write_vcfgz(path, 3_000_000_019, 2, 96,
                      K.Reference(shape.n_samples), shape=shape)
    else:
        shape = G.SHAPE._replace(pops=(9, 8, 8, 8, 7))
        G.write_vcfgz(path, 3_000_000_019, 2, 64,
                      G.Reference(shape.n_samples), shape=shape)
    return path


def _inflate(path: str) -> bytes:
    """Every block of a BGZF file inflated, in order."""
    raw = open(path, "rb").read()
    out, p = [], 0
    while p < len(raw):
        info = bgzf.parse_block_header(raw, p)
        out.append(bgzf.inflate_block(raw, info))
        p = info.next_coffset
    return b"".join(out)


@needs_native
@pytest.mark.parametrize("kind", ["kgp3", "kgp30x-gatk"])
def test_every_line_once_at_every_span_count(kind, tmp_path, monkeypatch):
    """On the reference writers' files: the union of a plan's spans is
    every line exactly once at several span counts, each span's native
    columns are the composition's, and all of them are the scalar parse
    of the whole file."""
    from hadoop_bam_tpu.api.vcf_dataset import open_vcf

    path = _reference_file(tmp_path, kind)
    header = open_vcf(path).header
    geom = vp.VariantGeometry(n_samples=header.n_samples)
    table = native.contig_table(header.contigs)
    joined = None
    for num_spans in (1, 2, 3, 5, 8, 13):
        spans = plan_bgzf_text_spans(path, num_spans=num_spans)
        fast, _ = _both_reads(path, spans, monkeypatch)
        text = b"".join(t for t, _ in fast)
        assert joined is None or text == joined
        joined = text
        parts = [vp.span_columns_native(t, r, header, geom, table)
                 for t, r in fast]
        oracle = [vp.pack_variant_tiles_from_text(t, header, geom)
                  for t, _ in fast]
        for k in parts[0]:
            got = np.concatenate([p[k] for p in parts])
            assert np.array_equal(got, np.concatenate([o[k] for o in oracle]))
    assert joined == _inflate(path)
    want = vp._pack_variant_tiles_from_text_scalar(joined, header, geom)
    for k in want:
        assert np.array_equal(np.concatenate([p[k] for p in parts]), want[k])
    assert want["flags"].size == 2 * (96 if kind == "kgp3" else 64)


@pytest.mark.parametrize("kind", ["kgp3", "kgp30x-gatk"])
def test_a_host_without_the_library_takes_the_python_path(kind, tmp_path,
                                                         monkeypatch):
    """Without the native library a scan reads and tokenises every span
    by the Python composition and counts it; with the library every span
    is native; the answers are the same."""
    path = _reference_file(tmp_path, kind)
    results, counters = [], []
    for library in (True, False):
        if library and not native.available():
            continue
        with monkeypatch.context() as m:
            if not library:
                m.setattr(native, "load", lambda: None)
            base_metrics().reset()
            results.append(vp.variant_stats_file(path))
            counters.append(base_metrics().snapshot()["counters"])
    for c, library in zip(counters, (True, False)[-len(counters):]):
        n = c["vcf.native_read_spans" if library else "vcf.python_read_spans"]
        assert n >= 1
        if library:
            assert c["vcf.text_span_native_spans"] == n
            assert "vcf.text_span_python_spans" not in c
        else:
            assert c["vcf.text_span_python_spans"] == n
            assert "vcf.text_span_native_spans" not in c
            assert "vcf.native_read_spans" not in c
    for r in results[1:]:
        for k in ("n_variants", "n_snp", "n_pass", "n_af"):
            assert r[k] == results[0][k], k
        assert r["mean_af"] == pytest.approx(results[0]["mean_af"], abs=1e-6)
        assert np.allclose(r["sample_callrate"], results[0]["sample_callrate"])
