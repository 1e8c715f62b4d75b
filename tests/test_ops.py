"""Device-op tests (run on CPU JAX per conftest): the jnp unpack path must
agree bit-for-bit with the host NumPy reference (formats/bam.BamBatch)."""
import numpy as np
import pytest

from hadoop_bam_tpu.formats import bgzf
from hadoop_bam_tpu.formats.bam import BamBatch, walk_record_offsets
from hadoop_bam_tpu.formats.bamio import BamWriter
from hadoop_bam_tpu.ops import inflate as inflate_ops
from hadoop_bam_tpu.ops.flagstat import flagstat_from_columns, format_flagstat
from hadoop_bam_tpu.ops.seq_decode import decode_qual, decode_seq
from hadoop_bam_tpu.ops.unpack_bam import (
    FIXED_FIELDS, pad_data, pad_offsets, unpack_fixed_fields,
)
from hadoop_bam_tpu.utils import native

from fixtures import make_header, make_records


@pytest.fixture(scope="module")
def decoded_span(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("ops") / "t.bam")
    header = make_header()
    records = make_records(header, 500, seed=9)
    with BamWriter(path, header) as w:
        for r in records:
            w.write_sam_record(r)
    raw = open(path, "rb").read()
    data, ubase = inflate_ops.inflate_span(raw)
    from hadoop_bam_tpu.formats.bam import SAMHeader
    _, after = SAMHeader.from_bam_bytes(data.tobytes())
    offs = walk_record_offsets(data.tobytes(), start=after)
    batch = BamBatch(data, offs, header=header)
    return header, records, data, offs, batch


def test_unpack_fixed_fields_matches_host(decoded_span):
    header, records, data, offs, batch = decoded_span
    cap_d = 1 << 20
    cap_n = 1024
    dev_data = pad_data(data, cap_d)
    dev_offs, n = pad_offsets(offs.astype(np.int32), cap_n)
    cols = unpack_fixed_fields(dev_data, dev_offs)
    for name in FIXED_FIELDS:
        host = getattr(batch, name)
        got = np.asarray(cols[name])[:n]
        np.testing.assert_array_equal(got.astype(np.int64), host,
                                      err_msg=f"column {name}")


def test_flagstat_matches_host(decoded_span):
    header, records, data, offs, batch = decoded_span
    dev_data = pad_data(data, 1 << 20)
    dev_offs, n = pad_offsets(offs.astype(np.int32), 1024)
    cols = unpack_fixed_fields(dev_data, dev_offs)
    valid = np.arange(1024) < n
    stats = {k: int(v) for k, v in
             flagstat_from_columns(cols, valid).items()}
    flags = np.asarray([r.flag for r in records])
    assert stats["total"] == len(records)
    assert stats["mapped"] == int(np.sum((flags & 0x4) == 0))
    assert stats["paired"] == int(np.sum((flags & 0x1) != 0))
    assert stats["properly_paired"] == int(
        np.sum(((flags & 0x2) != 0) & ((flags & 0x1) != 0) & ((flags & 0x4) == 0)))
    text = format_flagstat(stats)
    assert text.splitlines()[0].startswith(f"{len(records)} + 0 in total")


def test_seq_qual_decode_matches_host(decoded_span):
    header, records, data, offs, batch = decoded_span
    n = len(batch)
    max_len = int(batch.l_seq.max())
    dev_data = pad_data(data, 1 << 20)
    seq = np.asarray(decode_seq(dev_data, batch.seq_offset.astype(np.int32),
                                batch.l_seq.astype(np.int32), max_len))
    qual = np.asarray(decode_qual(dev_data, batch.qual_offset.astype(np.int32),
                                  batch.l_seq.astype(np.int32), max_len))
    for i in [0, 5, n - 1]:
        l = int(batch.l_seq[i])
        assert seq[i, :l].tobytes().decode() == batch.seq_string(i)
        assert qual[i, :l].tobytes().decode() == batch.qual_string(i)
        assert not seq[i, l:].any()


@pytest.mark.skipif(not native.available(), reason="native lib not built")
def test_native_inflate_matches_zlib(decoded_span, tmp_path):
    header, records, *_ = decoded_span
    path = str(tmp_path / "t2.bam")
    with BamWriter(path, header) as w:
        for r in records:
            w.write_sam_record(r)
    raw = open(path, "rb").read()
    d1, u1 = inflate_ops.inflate_span(raw, backend="native")
    d2, u2 = inflate_ops.inflate_span(raw, backend="zlib")
    np.testing.assert_array_equal(d1, d2)
    np.testing.assert_array_equal(u1, u2)
    table = inflate_ops.block_table(raw)
    inflate_ops.verify_crcs(raw, table, d1, u1)
    # corrupt one compressed byte -> native inflate or CRC must fail
    bad = bytearray(raw)
    bad[int(table["cdata_off"][0]) + 5] ^= 0xFF
    with pytest.raises(Exception):
        d3, u3 = inflate_ops.inflate_span(bytes(bad), backend="native")
        inflate_ops.verify_crcs(bytes(bad), inflate_ops.block_table(bytes(bad)),
                                d3, u3)


@pytest.mark.skipif(not native.available(), reason="native lib not built")
def test_native_walk_matches_python(decoded_span):
    header, records, data, offs, batch = decoded_span
    from hadoop_bam_tpu.formats.bam import SAMHeader
    _, after = SAMHeader.from_bam_bytes(data.tobytes())
    n_offs, tail = native.walk_bam_records(data, after, cap=10000)
    np.testing.assert_array_equal(n_offs, offs)
    assert tail == data.size
