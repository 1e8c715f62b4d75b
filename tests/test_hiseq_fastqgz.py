"""The ``hiseq-fastqgz-x1`` deployment on the CPU: a seeded HiSeq lane's pair
of single-member ``.fastq.gz`` files (tests/hiseq_fastq_reference.py) through
``hbam seq-stats`` against the plain reference.

The chip compares the same things at the configured size
(benchmark/runners/read_scan.py); here the sizes are small and the timings
mean nothing.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gzip
import io
import json
import os
import re
import zlib

import numpy as np
import pytest

import hiseq_fastq_reference as H

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "benchmark", "configs",
                       "hiseq-fastqgz-x1.json"), encoding="utf-8") as _fh:
    CONFIG = json.load(_fh)
TOL = CONFIG["tolerances"]
SEED = 3_000_000_019


def run_cli(argv) -> str:
    from hadoop_bam_tpu.tools.cli import main as hbam_main

    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = hbam_main(list(argv))
    assert rc == 0
    return out.getvalue()


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    """The configuration's ``tiny`` lane: 4,096 pairs, in chunks small
    enough that tiles and chunks do not line up."""
    d = str(tmp_path_factory.mktemp("hiseq"))
    ref = H.Reference()
    paths = H.write_pair(d, SEED, CONFIG["tiny"]["pairs"], ref,
                         chunk_pairs=1500)
    texts = [gzip.decompress(open(p, "rb").read()) for p in paths]
    return paths, ref, texts


def test_the_benchmarks_generator_is_this_reference_verbatim():
    with open(os.path.join(ROOT, "tests", "hiseq_fastq_reference.py"),
              "rb") as a, open(os.path.join(
                  ROOT, "benchmark", "gen_hiseq_fastq.py"), "rb") as b:
        assert a.read() == b.read()


# -- the shape ---------------------------------------------------------------

def test_each_file_is_one_gzip_member_with_nothing_to_split_by(tiny):
    paths, ref, texts = tiny
    for r, path in enumerate(paths):
        blob = open(path, "rb").read()
        assert blob[:3] == b"\x1f\x8b\x08"
        assert blob[3] == 0             # FLG: no FEXTRA, FNAME, FCOMMENT
        z = zlib.decompressobj(wbits=31)
        text = z.decompress(blob)
        assert z.eof and z.unused_data == b""       # ONE member, no more
        assert text == texts[r]
        assert (len(text), len(blob)) == (ref.text_bytes[r],
                                          ref.gz_bytes[r])
    assert os.path.basename(paths[0]).endswith("_R1_001.fastq.gz")
    assert os.path.basename(paths[1]).endswith("_R2_001.fastq.gz")
    assert H.GZIP_LEVEL == CONFIG["assumed"]["gzip_level"] == 4


def test_records_are_the_sources_shape(tiny):
    from hadoop_bam_tpu.formats.fastq import SequencedFragment

    _paths, _ref, texts = tiny
    pairs = CONFIG["tiny"]["pairs"]
    assert H.READ_LEN == CONFIG["shape"]["read_length"] == 101
    assert CONFIG["sizes"]["pairs"] == 1 << 21
    assert len(H.TILES) == 96 and (H.TILES[0], H.TILES[-1]) == (1101, 2316)
    names = []
    for read, text in enumerate(texts, start=1):
        assert b"\r" not in text and text.endswith(b"\n")
        lines = text.split(b"\n")[:-1]
        assert len(lines) == 4 * pairs
        assert set(lines[2::4]) == {b"+"}
        assert {len(s) for s in lines[1::4]} == {101}
        assert {len(q) for q in lines[3::4]} == {101}
        assert set(b"".join(lines[1::4])) <= set(b"ACGTN")
        frags = [SequencedFragment.from_name(n[1:].decode())
                 for n in lines[0::4]]
        assert {f.read for f in frags} == {read}
        assert {(f.instrument, f.run_number, f.flowcell_id, f.lane,
                 f.control_number, f.index_sequence) for f in frags} \
            == {(H.INSTRUMENT, H.RUN, H.FLOWCELL, H.LANE, 0, H.INDEX)}
        tiles = [f.tile for f in frags]
        assert tiles == sorted(tiles) and set(tiles) == set(H.TILES)
        ys = np.array([f.ypos for f in frags])
        same = np.diff(np.array(tiles)) == 0
        assert (np.diff(ys)[same] >= 0).all()       # y rises within a tile
        failed = np.mean([f.filter_passed is False for f in frags])
        assert 0.005 < failed < 0.03
        names.append([n.split(b" ")[0] for n in lines[0::4]])
    assert names[0] == names[1]                     # mates carry one name
    per_record = len(texts[0]) / pairs
    assert abs(per_record - CONFIG["shape"]["text_bytes_per_record"]) < 1.0


def test_qualities_are_unbinned_and_fall_with_the_cycle():
    g = H.genome(SEED)
    p = H.gen_pairs(SEED, 0, 1 << 16, 1 << 16, g)
    q1 = H.gen_read(SEED, 0, 1, p, g)[1]
    b2, q2 = H.gen_read(SEED, 0, 2, p, g)
    for q in (q1, q2):
        assert (int(q.min()), int(q.max())) == (2, 41)
        assert len(np.unique(q)) == 40              # Q2..Q41, every value
        by_cycle = q.mean(axis=0)
        assert by_cycle[:10].mean() > by_cycle[-10:].mean() + 3
    assert q2.mean() < q1.mean() - 1                # R2 under R1
    tails = (q1[:, -1] == 2) & (q1[:, -5:] == 2).all(axis=1)
    assert 0.02 < tails.mean() < 0.05               # '#' tails
    n = b2 == 4
    assert 0.0005 < n.mean() < 0.002
    assert (q2[n] == 2).all()                       # N is called at Q2
    assert n[~p["edge"]].sum() == 0                 # and only at the edge
    gc = ((b2 == 1) | (b2 == 2)).mean()
    assert abs(gc - 0.41) < 0.01


def test_mates_come_from_one_fragment_on_opposite_strands():
    g = H.genome(SEED)
    p = H.gen_pairs(SEED, 3, 1 << 14, 1 << 12, g)
    b1, _ = H.gen_read(SEED, 3, 1, p, g)
    b2, _ = H.gen_read(SEED, 3, 2, p, g)
    comp = np.array([3, 2, 1, 0, 4], np.uint8)
    agree = 0
    for i in range(200):
        s, ins = int(p["start"][i]), int(p["insert"][i])
        frag = g[s:s + ins]
        head, tail = frag[:101], comp[frag[::-1][:101]]
        first, second = (tail, head) if p["minus"][i] else (head, tail)
        agree += (b1[i] == first).mean() + (b2[i] == second).mean()
    assert agree / 400 > 0.98                       # but for the miscalls


def test_the_reference_is_a_plain_reading_of_the_text(tiny):
    _paths, ref, texts = tiny
    code = {c: i for i, c in enumerate(H.BASE_NAMES)}
    for r, text in enumerate(texts):
        lines = text.decode().split("\n")[:-1]
        n = gc = mq = 0.0
        hist = [0] * 16
        n_ok = 0
        for name, seq, qual in zip(lines[0::4], lines[1::4], lines[3::4]):
            n += 1
            gc += sum(c in "GC" for c in seq) / len(seq)
            mq += sum(ord(c) - 33 for c in qual) / len(qual)
            for c in seq:
                hist[code[c]] += 1
            n_ok += re.search(r" [12]:N:", name) is not None
        want = ref.all[r]
        assert want.n == n and want.hist.tolist() == hist
        assert want.gc == pytest.approx(gc, rel=1e-12)
        assert want.mq == pytest.approx(mq, rel=1e-12)
        assert ref.passed[r].n == n_ok < n
    pair = ref.pair()
    assert pair.n == 2 * CONFIG["tiny"]["pairs"]
    assert pair.hist.sum() == pair.n * 101


def test_round_bf16_is_round_to_nearest_even():
    x = np.array([1.0, 1.0 + 2 ** -8, 1.0 + 3 * 2 ** -8, 0.41, 36.7, 0.0])
    got = H.round_bf16(x)
    assert got[0] == 1.0 and got[5] == 0.0
    assert got[1] == 1.0                    # a tie goes to the even side
    assert got[2] == 1.0 + 2 ** -6
    assert np.all(np.abs(got - x) <= np.abs(x) * 2 ** -8)
    assert np.all(got == H.round_bf16(got))


# -- the verb against the reference ------------------------------------------

def test_seq_stats_on_the_gzip_the_plain_file_and_the_reference_agree(
        tiny, tmp_path):
    paths, ref, texts = tiny
    for r, (path, text) in enumerate(zip(paths, texts)):
        plain = str(tmp_path / f"r{r + 1}.fastq")
        with open(plain, "wb") as fh:
            fh.write(text)
        out_gz, out_plain = run_cli(["seq-stats", path]), \
            run_cli(["seq-stats", plain])
        assert ref.wrong(out_gz, r, TOL["printed"]) is None
        assert ref.wrong(out_plain, r, TOL["printed"]) is None
        assert out_gz.splitlines()[0] == f"reads\t{ref.all[r].n}"
    # the comparison is not vacuous
    other = ref.wrong(run_cli(["seq-stats", paths[0]]), 1, TOL["printed"])
    assert other is not None and "base histogram" in other


@pytest.mark.parametrize("split_size", [None, 1 << 16])
def test_the_answer_does_not_depend_on_who_inflates(tiny, monkeypatch,
                                                    split_size):
    """The tiny lane through the inflate workers (a host with CPUs to
    spare, the native library) and through the one ``zlib`` inflate (the
    library masked out): the same counts and, to the bit, the same two
    means — the tiles the chip sees are the same, however the text is
    cut."""
    from hadoop_bam_tpu.config import DEFAULT_CONFIG
    from hadoop_bam_tpu.parallel.pipeline import fastq_seq_stats_file
    from hadoop_bam_tpu.split import read_planners
    from hadoop_bam_tpu.utils import native
    from hadoop_bam_tpu.utils.metrics import MetricsContext

    if not native.available():
        pytest.skip("no native library")
    paths, ref, _texts = tiny
    cfg = DEFAULT_CONFIG if split_size is None \
        else dataclasses.replace(DEFAULT_CONFIG, split_size=split_size)
    monkeypatch.setattr(read_planners.os, "cpu_count", lambda: 8)
    answers = {}
    for who in ("workers", "one"):
        if who == "one":
            monkeypatch.setattr(native, "load", lambda: None)
        for r, path in enumerate(paths):
            with MetricsContext() as m:
                got = fastq_seq_stats_file(path, config=cfg)
            par = m.get("fastq.inflated_bytes_parallel")
            assert par > 0 if who == "workers" else par == 0
            assert m.get("fastq.inflated_bytes") == ref.text_bytes[r]
            answers[who, r] = (int(got["n_reads"]),
                               [int(c) for c in got["base_hist"]],
                               float(got["mean_gc"]),
                               float(got["mean_qual"]))
    for r in range(2):
        assert answers["workers", r] == answers["one", r]
        assert answers["one", r][:2] == (ref.all[r].n,
                                         ref.all[r].hist.tolist())


def test_the_filter_leaves_the_reads_that_passed(tiny):
    from hadoop_bam_tpu.config import DEFAULT_CONFIG
    from hadoop_bam_tpu.parallel.pipeline import fastq_seq_stats_file

    paths, ref, _texts = tiny
    cfg = dataclasses.replace(DEFAULT_CONFIG, fastq_filter_failed_qc=True)
    for r, path in enumerate(paths):
        got = fastq_seq_stats_file(path, config=cfg)
        want = ref.passed[r]
        assert got["n_reads"] == want.n < ref.all[r].n
        assert [int(c) for c in got["base_hist"]] == want.hist.tolist()
        assert not H.outside((got["mean_gc"], got["mean_qual"]), want,
                             TOL["unrounded"])


def test_the_unrounded_limits_pass_float32_and_refuse_bfloat16(tiny):
    """What the runner's ``verify`` decides at the timed size (PERF.md
    section 6 has the chip's readings; at this size the bfloat16 reading
    is further off than there)."""
    from hadoop_bam_tpu.parallel.pipeline import fastq_seq_stats_file

    paths, ref, _texts = tiny
    for r, path in enumerate(paths):
        want = ref.all[r]
        res = fastq_seq_stats_file(path)
        got = (float(res["mean_gc"]), float(res["mean_qual"]))
        assert H.outside(got, want, TOL["unrounded"]) == []
        bf16 = want.means("bf16")
        assert bf16 != want.means()
        assert "mean_gc" in H.outside(bf16, want, TOL["unrounded"])
    for k in ("mean_gc", "mean_qual"):
        assert TOL["unrounded"][k] < TOL["printed"][k]


def test_the_configuration_says_what_the_generator_does():
    assert CONFIG["architecture"] is None and CONFIG["chips"] == 1
    assert list(CONFIG["reduced"]) == ["records"]
    assert CONFIG["source_records"] == 400_000_000
    a = CONFIG["assumed"]
    assert f"{H.INSTRUMENT}:{H.RUN}:{H.FLOWCELL}" \
        in a["instrument_run_flowcell_lane_index"]
    assert H.INDEX in a["instrument_run_flowcell_lane_index"]
    assert (H.FILTER_FAIL, H.HASH_TAIL, H.MISCALL, H.GC) \
        == (0.015, 0.03, 0.004, 0.41)
    assert H.EDGE_READS * H.EDGE_N == pytest.approx(0.001)
    assert (H.INSERT_MEAN, H.INSERT_SD) == (400.0, 60.0)
    assert H.GENOME_BASES == 1 << 22
