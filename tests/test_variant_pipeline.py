"""Variant device feed: dosage tensors + mesh stats on the CPU mesh."""
import random

import numpy as np
import pytest

from hadoop_bam_tpu.formats.vcf import VariantBatch, VCFHeader, VcfRecord
from hadoop_bam_tpu.parallel.variant_pipeline import (
    VariantGeometry, variant_stats_file,
)

N_SAMPLES = 5
HEADER_TEXT = (
    "##fileformat=VCFv4.2\n"
    "##contig=<ID=c1,length=1000000>\n"
    "##contig=<ID=c2,length=500000>\n"
    '##INFO=<ID=DP,Number=1,Type=Integer,Description="Depth">\n'
    '##FILTER=<ID=q10,Description="Quality below 10">\n'
    '##FORMAT=<ID=GT,Number=1,Type=String,Description="Genotype">\n'
    "#CHROM\tPOS\tID\tREF\tALT\tQUAL\tFILTER\tINFO\tFORMAT\t"
    + "\t".join(f"s{i}" for i in range(N_SAMPLES)) + "\n")


def _make_records(n, seed=5):
    rng = random.Random(seed)
    recs = []
    for i in range(n):
        chrom = "c1" if i % 3 else "c2"
        ref = rng.choice("ACGT")
        alt = rng.choice([c for c in "ACGT" if c != ref])
        gts = []
        for _ in range(N_SAMPLES):
            r = rng.random()
            gts.append("./." if r < 0.1 else
                       rng.choice(["0/0", "0/1", "1/1", "0|1"]))
        filt = "PASS" if rng.random() < 0.8 else "q10"
        recs.append(VcfRecord.from_line(
            f"{chrom}\t{100 + i * 7}\t.\t{ref}\t{alt}\t{30 + i % 40}\t"
            f"{filt}\tDP={i}\tGT\t" + "\t".join(gts)))
    return recs


@pytest.fixture(scope="module")
def vcf(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("varpipe") / "v.vcf")
    header = VCFHeader.from_text(HEADER_TEXT)
    recs = _make_records(2000)
    with open(path, "w") as f:
        f.write(HEADER_TEXT)
        for r in recs:
            f.write(r.to_line() + "\n")
    return path, header, recs


def test_dosage_matrix(vcf):
    path, header, recs = vcf
    batch = VariantBatch(recs[:50], header)
    d = batch.dosage_matrix()
    assert d.shape == (50, N_SAMPLES)
    for i in (0, 17, 49):
        for s in range(N_SAMPLES):
            gt = recs[i].genotypes[s].split(":")[0]
            if gt.startswith("."):
                assert d[i, s] == -1
            else:
                expect = sum(1 for a in gt.replace("|", "/").split("/")
                             if int(a) > 0)
                assert d[i, s] == expect


def test_variant_stats_file_matches_oracle(vcf):
    path, header, recs = vcf
    stats = variant_stats_file(path, header=header)
    assert stats["n_variants"] == len(recs)
    n_pass = sum(1 for r in recs if r.filters == ("PASS",))
    assert stats["n_pass"] == n_pass
    assert stats["n_snp"] == len(recs)  # all synthesized records are SNPs
    # oracle AF + callrates
    batch = VariantBatch(recs, header)
    d = batch.dosage_matrix().astype(np.int64)
    called = d >= 0
    af = np.where(called.sum(1) > 0,
                  np.where(called, d, 0).sum(1)
                  / (2.0 * np.maximum(called.sum(1), 1)), 0.0)
    has = called.sum(1) > 0
    assert abs(stats["mean_af"] - af[has].mean()) < 1e-6
    np.testing.assert_allclose(stats["sample_callrate"],
                               called.mean(axis=0), atol=1e-9)


def test_variant_tensor_batches(vcf):
    path, header, recs = vcf
    from hadoop_bam_tpu.api.vcf_dataset import open_vcf
    ds = open_vcf(path)
    g = VariantGeometry(tile_records=512, n_samples=header.n_samples)
    total = 0
    for batch in ds.tensor_batches(geometry=g, num_spans=3):
        counts = np.asarray(batch["n_records"])
        total += int(counts.sum())
        assert batch["dosage"].shape[1:] == (512, g.samples_pad)
        assert batch["chrom"].shape[1:] == (512,)
    assert total == len(recs)


def test_variant_stats_on_bcf(vcf, tmp_path):
    """Same stats through the BCF container (binary codec round-trip)."""
    path, header, recs = vcf
    from hadoop_bam_tpu.api.writers import open_vcf_writer
    out = str(tmp_path / "v.bcf")
    with open_vcf_writer(out, header) as w:
        for r in recs:
            w.write_record(r)
    stats = variant_stats_file(out)
    assert stats["n_variants"] == len(recs)
    assert stats["n_snp"] == len(recs)


def test_fast_tokenizer_matches_generic(vcf):
    """pack_variant_tiles_from_text == VariantBatch-based packing."""
    path, header, recs = vcf
    from hadoop_bam_tpu.api.vcf_dataset import open_vcf
    from hadoop_bam_tpu.parallel.variant_pipeline import (
        pack_variant_tiles, pack_variant_tiles_from_text,
    )
    g = VariantGeometry(n_samples=header.n_samples)
    ds = open_vcf(path)
    for span in ds.spans(3):
        text = ds.read_span_text(span)
        fast = pack_variant_tiles_from_text(text, header, g)
        slow = pack_variant_tiles(
            __import__("hadoop_bam_tpu.formats.vcf",
                       fromlist=["VariantBatch"]).VariantBatch(
                ds.read_span(span), header), g)
        for k in ("chrom", "pos", "flags", "dosage"):
            np.testing.assert_array_equal(fast[k], slow[k], err_msg=k)


def test_bcf_fast_scan_wide_gt_and_half_missing(tmp_path):
    """scan_variant_columns must (a) decode GT vectors the encoder widened
    to int16 (allele index >= 63 -> value 128 > int8 max) and (b) agree
    with VariantBatch.dosage_matrix on half-missing genotypes ('0/.' ->
    -1), across text and binary containers."""
    from hadoop_bam_tpu.api.vcf_dataset import open_vcf
    from hadoop_bam_tpu.api.writers import open_vcf_writer
    from hadoop_bam_tpu.formats.bcf import scan_variant_columns
    from hadoop_bam_tpu.parallel.variant_pipeline import pack_variant_tiles
    from hadoop_bam_tpu.split.vcf_planners import read_bcf_span_bytes

    n_alts = 70  # forces (70+1)<<1 = 142 -> int16 GT encoding
    alts = ",".join("ACGT"[i % 4] * (i // 4 + 2) for i in range(n_alts))
    hdr_text = (
        "##fileformat=VCFv4.2\n"
        "##contig=<ID=c1,length=1000000>\n"
        '##FORMAT=<ID=GT,Number=1,Type=String,Description="Genotype">\n'
        "#CHROM\tPOS\tID\tREF\tALT\tQUAL\tFILTER\tINFO\tFORMAT\t"
        "s0\ts1\ts2\ts3\n")
    header = VCFHeader.from_text(hdr_text)
    lines = [
        f"c1\t100\t.\tA\t{alts}\t30\tPASS\t.\tGT\t0/70\t70/70\t0/0\t./.",
        f"c1\t200\t.\tA\t{alts}\t30\tPASS\t.\tGT\t0/.\t./0\t1/.\t0|70",
        "c1\t300\t.\tA\tC\t30\tPASS\t.\tGT\t0/1\t0|.\t./.\t1/1",
    ]
    recs = [VcfRecord.from_line(ln) for ln in lines]
    out = str(tmp_path / "wide.bcf")
    with open_vcf_writer(out, header) as w:
        for r in recs:
            w.write_record(r)
    ds = open_vcf(out)
    g = VariantGeometry(n_samples=header.n_samples)
    (span,) = ds.spans(1)
    raw = read_bcf_span_bytes(out, span, ds._is_bgzf_bcf)
    fast = scan_variant_columns(raw, header, g.samples_pad)
    # oracle: the generic per-record path
    slow = pack_variant_tiles(VariantBatch(ds.read_span(span), header), g)
    for k in ("chrom", "pos", "flags", "dosage"):
        np.testing.assert_array_equal(fast[k], slow[k], err_msg=k)
    # explicit semantics: int16 GT decoded, half-missing -> -1
    np.testing.assert_array_equal(
        fast["dosage"][:, :4],
        [[1, 2, 0, -1], [-1, -1, -1, 1], [1, -1, -1, 2]])


def test_bcf_fast_scan_matches_generic(vcf, tmp_path):
    """scan_variant_columns == VariantBatch packing for BCF spans."""
    path, header, recs = vcf
    from hadoop_bam_tpu.api.vcf_dataset import open_vcf
    from hadoop_bam_tpu.api.writers import open_vcf_writer
    from hadoop_bam_tpu.formats.bcf import scan_variant_columns
    from hadoop_bam_tpu.parallel.variant_pipeline import pack_variant_tiles
    from hadoop_bam_tpu.split.vcf_planners import read_bcf_span_bytes

    out = str(tmp_path / "scan.bcf")
    with open_vcf_writer(out, header) as w:
        for r in recs:
            w.write_record(r)
    ds = open_vcf(out)
    g = VariantGeometry(n_samples=header.n_samples)
    total = 0
    for span in ds.spans(3):
        raw = read_bcf_span_bytes(out, span, ds._is_bgzf_bcf)
        fast = scan_variant_columns(raw, header, g.samples_pad)
        slow = pack_variant_tiles(VariantBatch(ds.read_span(span), header),
                                  g)
        for k in ("chrom", "pos", "flags", "dosage"):
            np.testing.assert_array_equal(fast[k], slow[k], err_msg=k)
        total += fast["chrom"].shape[0]
    assert total == len(recs)


def _fuzz_lines(rng, n_lines, n_samples):
    """Adversarial VCF lines: multi-allelic ALTs, wide ALTs, polyploid and
    multi-digit genotypes, missing trailing fields, '.' everywhere."""
    alts = ["A", "T", "A,C", "A,C,G,T,A,C,G,T,A",     # > _ALT_W wide
            "AT", "A,TT", ".", "<DEL>", "A,<INS>", "*"]
    gts = ["0/0", "0/1", "1|1", "./.", ".", "0", "2", "10/1", "0/1/1",
           "1", "0|0|1", "./0", "0/.", "", "1/2:99", "0/1:.:3"]
    formats = ["GT", "GT:GQ", "GQ", "GTX"]
    lines = []
    for i in range(n_lines):
        chrom = rng.choice(["chr1", "chrX_alt", "chrUnknown"])
        pos = rng.randint(1, 999999)
        nf = rng.choice([8, 9, 10, 11, 9 + n_samples])
        parts = [chrom, str(pos), ".", rng.choice(["A", "AT"]),
                 rng.choice(alts), "30",
                 rng.choice(["PASS", "q10", "."]), "DP=5"]
        if nf > 8:
            parts.append(rng.choice(formats))
            for _ in range(nf - 9):
                parts.append(rng.choice(gts))
        lines.append("\t".join(parts))
    return lines


def _wide_lines(rng, n_lines, n_samples):
    """A call set's lines at ``n_samples`` — FORMAT ``GT``, every cell
    ``digit sep digit`` — with each irregular kind mixed in: a multi-digit
    allele, a haploid call, ``.`` and ``./.``, ``GT:DP`` subfields, a short
    line, a long one, a wide ALT, no FORMAT at all, a carriage return."""
    regular = ["0|0"] * 12 + ["0|1", "1|0", "1|1", "0/1", "2|1", "0|3"]
    lines = []
    for i in range(n_lines):
        cells = [rng.choice(regular) for _ in range(n_samples)]
        fmt, pos, alt = "GT", str(rng.randint(1, 999999)), "T"
        kind = rng.choice(["regular"] * 6 + [
            "multi-digit", "haploid", "dot", "dot-slash", "subfields",
            "short", "long", "wide-alt", "no-format", "cr"])
        at = rng.randrange(n_samples)
        if kind == "multi-digit":
            cells[at] = "10|1"
        elif kind == "haploid":
            cells[at] = "1"
        elif kind == "dot":
            cells[at] = "."
        elif kind == "dot-slash":
            cells[at] = "./."
        elif kind == "subfields":
            fmt, cells = "GT:DP", [c + ":7" for c in cells]
        elif kind == "short":
            cells = cells[:at]
        elif kind == "long":
            cells.append("0|1")
        elif kind == "wide-alt":
            alt = "A,C,G,T,A,C,G,T,A"
        elif kind == "cr":
            cells[-1] += "\r"
        parts = [rng.choice(["chr1", "chrX_alt"]), pos, ".", "A", alt, "30",
                 rng.choice(["PASS", "q10"]), "DP=5"]
        if kind != "no-format":
            parts += [fmt] + cells
        lines.append("\t".join(parts))
    return lines


@pytest.mark.parametrize("twin", [False, True], ids=["native", "numpy"])
@pytest.mark.parametrize("n_samples,make,n_lines", [
    (3, _fuzz_lines, 400), (2504, _wide_lines, 160), (0, _fuzz_lines, 200)],
    ids=["3-samples-fuzz", "2504-samples-mixed", "sites-only"])
def test_text_tokenizer_vectorized_matches_scalar(n_samples, make, n_lines,
                                                  twin, monkeypatch):
    """Differential fuzz: the bulk tokeniser (the native pass and its
    NumPy twin, + the irregular-row fallback) must match the per-line
    scalar parse byte-for-byte, at 3 samples and at 2,504, with a trailing
    newline and without, and count every record once under
    ``vcf.text_bulk_records`` / ``vcf.text_scalar_records``."""
    import random as _random

    from hadoop_bam_tpu.formats.vcf import VCFHeader
    from hadoop_bam_tpu.parallel.variant_pipeline import (
        VariantGeometry, _pack_variant_tiles_from_text_scalar,
        pack_variant_tiles_from_text,
    )
    from hadoop_bam_tpu.utils import native
    from hadoop_bam_tpu.utils.metrics import base_metrics

    if twin:
        monkeypatch.setattr(native, "load", lambda: None)
    elif native.load() is None:
        pytest.skip("no native library on this host")
    header = VCFHeader.from_text(
        "##fileformat=VCFv4.2\n"
        "##contig=<ID=chr1,length=1000000>\n"
        "##contig=<ID=chrX_alt,length=50000>\n"
        '##FORMAT=<ID=GT,Number=1,Type=String,Description="G">\n'
        "#CHROM\tPOS\tID\tREF\tALT\tQUAL\tFILTER\tINFO\tFORMAT"
        + "".join(f"\ts{i}" for i in range(n_samples)) + "\n")
    lines = make(_random.Random(17), n_lines, n_samples)
    lines[5:5] = ["", "#a comment line", "too\tfew\tfields"]
    text = ("\n".join(lines) + "\n").encode()
    geom = VariantGeometry(n_samples=n_samples)
    want = _pack_variant_tiles_from_text_scalar(text, header, geom)
    assert want["chrom"].shape[0] == n_lines
    base_metrics().reset()
    got = pack_variant_tiles_from_text(text, header, geom)
    for k in want:
        assert (want[k] == got[k]).all(), k
        assert want[k].dtype == got[k].dtype and want[k].shape == got[k].shape
    c = base_metrics().snapshot()["counters"]
    assert c["vcf.text_bulk_records"] + c.get("vcf.text_scalar_records", 0) \
        == n_lines
    assert c["vcf.text_numpy_records" if twin else
             "vcf.text_native_records"] == n_lines
    if make is _wide_lines:     # both paths are really taken
        assert c["vcf.text_bulk_records"] > n_lines // 3
        assert c["vcf.text_scalar_records"] > n_lines // 4
    # and without a trailing newline, and as a view of a larger buffer
    got2 = pack_variant_tiles_from_text(memoryview(text)[:-1], header, geom)
    for k in want:
        assert (want[k] == got2[k]).all(), k


def test_native_pass_and_numpy_twin_return_the_same_arrays():
    """``utils/native.py::vcf_tokenize`` and ``_vcf_tokenize_numpy``,
    array for array (a row with ``bulk`` unset aside: neither promises
    it), lines with a long head included — but for the keyed lines
    (FORMAT ``GT:`` and more keys), which only the native pass reads and
    the twin leaves to the scalar parse."""
    import random as _random

    from hadoop_bam_tpu.parallel.variant_pipeline import (
        _keyed_lines, _vcf_tokenize_numpy,
    )
    from hadoop_bam_tpu.utils import native

    if native.load() is None:
        pytest.skip("no native library on this host")
    rng = _random.Random(23)
    for n_samples, make in ((3, _fuzz_lines), (40, _wide_lines)):
        lines = make(rng, 300, n_samples)
        lines[7] = lines[7].replace("DP=5", "DP=5;X=" + "A" * 5000)
        lines[9] = "chr1\t5\t.\t" + "A" * 3000 + "\tT\t30\tPASS\tDP=5"
        buf = np.frombuffer(("\n".join(lines)).encode(), np.uint8)
        pad = max(8, (n_samples + 7) // 8 * 8)
        a = native.vcf_tokenize(buf, n_samples, pad)
        b = _vcf_tokenize_numpy(buf, n_samples, pad)
        for x, y in zip(a[:3], b[:3]):
            assert x.dtype == y.dtype and x.shape == y.shape
        assert np.array_equal(a[0], b[0]) and np.array_equal(a[1], b[1])
        keyed = _keyed_lines(buf, a[0], a[1])
        assert np.array_equal(a[2] & ~keyed, b[2])
        assert not (b[2] & keyed).any()
        assert a[4] == int((a[2] & keyed).sum())
        assert a[5] == int((a[3][a[2] & keyed, :n_samples] < 0).sum())
        assert np.array_equal(a[3][b[2]], b[3][b[2]])
        assert a[2].any() and not a[2].all()
        assert (a[2] & keyed).any()


def test_variant_geometry_byte_budget_large_cohorts():
    """The auto tile sizing is byte-clamped, not record-floored: a
    100k-sample cohort must stay near the ~8 MB dosage budget instead
    of blowing up to a 4096-record (1.6 GB int32) tile (ADVICE r4)."""
    from hadoop_bam_tpu.parallel.variant_pipeline import VariantGeometry

    g = VariantGeometry(n_samples=100_000)
    assert g.tile_records * g.samples_pad <= (16 << 20)   # ~2x budget max
    assert g.tile_records >= 64
    # small cohorts still get big tiles (dispatch amortization)
    g_small = VariantGeometry(n_samples=3)
    assert g_small.tile_records == 1 << 16
