"""CRAM file orchestration: writer, header reader, record iteration.

File shape [SPEC CRAM 3.0 section 6]: file definition, a first container
holding the SAM header (FILE_HEADER block: i32 text length + text), data
containers (one slice each, cram_encode.py), and the fixed 38-byte EOF
container.

Reference equivalents: htsjdk ``CramContainerIterator`` / CRAM writer as used
by hb/CRAMInputFormat.java, hb/CRAMRecordReader.java and
hb/KeyIgnoringCRAMRecordWriter.java (SURVEY.md sections 2.3/2.4).
"""
from __future__ import annotations

import struct
from collections.abc import Mapping
from typing import BinaryIO, Dict, Iterator, List, Optional, Tuple, Union

from hadoop_bam_tpu.formats.bam import SAMHeader
from hadoop_bam_tpu.formats.cram import (
    Block, CRAMError, COMPRESSION_HEADER, Container, CORE_DATA,
    EOF_CONTAINER, EXTERNAL_DATA, FILE_HEADER, FQZCOMP, FileDefinition,
    GZIP,
    MAPPED_SLICE_HEADER, read_container, scan_container_offsets,
)
from hadoop_bam_tpu.formats.cram_decode import (
    CF_DETACHED, CF_QUAL_STORED, CompressionHeader, CramRecord,
    MATE_REVERSE, MATE_UNMAPPED, ReferenceSource, SliceHeader,
    decode_slice_records,
)
from hadoop_bam_tpu.formats.cram_encode import encode_container
from hadoop_bam_tpu.formats.sam import SamRecord

# Phred -> ASCII(+33) translation table (bulk qual rendering)
_Q33 = bytes(min(q + 33, 255) for q in range(256))

DEFAULT_RECORDS_PER_CONTAINER = 10_000


# ---------------------------------------------------------------------------
# Writer
# ---------------------------------------------------------------------------

class CramWriter:
    """Streaming CRAM writer; buffers records into containers.

    ``write_header``/``write_eof`` knobs mirror the reference's shard-writer
    options (hb/KeyIgnoringCRAMRecordWriter.java): headerless, terminator-less
    shards can later be concatenated by the merger
    (hadoop_bam_tpu/utils/mergers.py).
    """

    def __init__(self, path_or_sink: Union[str, BinaryIO], header: SAMHeader,
                 records_per_container: int = DEFAULT_RECORDS_PER_CONTAINER,
                 write_header: bool = True, write_eof: bool = True,
                 version: Tuple[int, int] = (3, 0)):
        if version not in ((3, 0), (3, 1)):
            raise ValueError(f"unsupported CRAM write version {version}")
        if isinstance(path_or_sink, str):
            self._sink: BinaryIO = open(path_or_sink, "wb")
            self._owns = True
        else:
            self._sink = path_or_sink
            self._owns = False
        self.header = header
        self.version = version
        self.records_per_container = records_per_container
        self._write_eof = write_eof
        self._pending: List[SamRecord] = []
        self._record_counter = 0
        self._closed = False
        if write_header:
            self._sink.write(FileDefinition(
                major=version[0], minor=version[1]).to_bytes())
            self._sink.write(_header_container_bytes(header))

    def write_record(self, rec: SamRecord) -> None:
        self._pending.append(rec)
        if len(self._pending) >= self.records_per_container:
            self.flush_container()

    def write_records(self, recs) -> None:
        for r in recs:
            self.write_record(r)

    def flush_container(self) -> None:
        if not self._pending:
            return
        # split runs so each container's slice is single-ref where possible
        self._sink.write(encode_container(
            self._pending, self.header, self._record_counter,
            version=self.version))
        self._record_counter += len(self._pending)
        self._pending = []

    def close(self) -> None:
        if self._closed:
            return
        self.flush_container()
        if self._write_eof:
            self._sink.write(EOF_CONTAINER)
        if self._owns:
            self._sink.close()
        self._closed = True

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


def _header_container_bytes(header: SAMHeader) -> bytes:
    text = header.to_sam_text().encode("ascii") if hasattr(
        header, "to_sam_text") else header.text.encode("ascii")
    payload = struct.pack("<i", len(text)) + text
    from hadoop_bam_tpu.formats.cram import build_container
    blk = Block(FILE_HEADER, 0, payload, GZIP)
    return build_container([blk], ref_seq_id=-1, start=0, span=0,
                           n_records=0, record_counter=0, bases=0,
                           landmarks=[0])


def write_cram(path_or_sink, header: SAMHeader, records) -> None:
    with CramWriter(path_or_sink, header) as w:
        w.write_records(records)


# ---------------------------------------------------------------------------
# Reader
# ---------------------------------------------------------------------------

def _read_all(source) -> bytes:
    if isinstance(source, (bytes, bytearray)):
        return bytes(source)
    with open(source, "rb") as f:
        return f.read()


def _read_head(path: str) -> bytes:
    """The file definition and the first (header) container of a CRAM on
    disk — the bytes ``read_cram_header`` needs, not the file."""
    from hadoop_bam_tpu.formats.cram import ContainerHeader

    with open(path, "rb") as f:
        buf = f.read(1 << 16)
        try:
            hdr, after = ContainerHeader.from_buffer(buf,
                                                     FileDefinition.SIZE)
        except (IndexError, ValueError, struct.error):
            return buf + f.read()        # let the parser word the error
        need = after + max(0, hdr.length)
        if need > len(buf):
            buf += f.read(need - len(buf))
    return buf


def read_cram_header(source) -> Tuple[SAMHeader, int]:
    """Returns (header, offset of the first data container)."""
    buf = (_read_all(source) if isinstance(source, (bytes, bytearray))
           else _read_head(source))
    FileDefinition.from_bytes(buf)
    cont, after = read_container(buf, FileDefinition.SIZE)
    for blk in cont.blocks:
        if blk.content_type == FILE_HEADER:
            (l_text,) = struct.unpack_from("<i", blk.data, 0)
            text = blk.data[4:4 + l_text].decode("ascii", "replace")
            return SAMHeader.from_sam_text(text.rstrip("\x00")), after
    raise CRAMError("first container carries no FILE_HEADER block")


class ExternalBlocks(Mapping):
    """content id -> one slice's EXTERNAL payload.  A block of a lazily
    read container (``read_container(..., lazy=True)``) is decompressed
    on the first lookup of its id, so a decoder that never asks for an id
    — the columnar stats path and the read names, tags and detached-mate
    series — never pays for it; membership and ``len`` touch nothing."""

    def __init__(self, blocks: Dict[int, Block]):
        self._blocks = blocks

    def __getitem__(self, cid: int) -> bytes:
        return self._blocks[cid].data

    def __contains__(self, cid) -> bool:
        return cid in self._blocks

    def __iter__(self):
        return iter(self._blocks)

    def __len__(self) -> int:
        return len(self._blocks)


def iter_container_slices(cont: Container):
    """(comp, slice_hdr, core, external, codec_rec_lens) for each slice
    of one data container — the shared walk under both the record-object
    and the columnar slice decoders.  ``codec_rec_lens`` maps content id
    -> the block codec's own per-record lengths for codecs that model
    record boundaries (fqzcomp), for the RL-series desync tripwire."""
    if cont.header.is_eof or not cont.blocks:
        return
    if cont.blocks[0].content_type != COMPRESSION_HEADER:
        raise CRAMError("container does not start with a compression header")
    comp = CompressionHeader.from_bytes(cont.blocks[0].data)
    i = 1
    while i < len(cont.blocks):
        blk = cont.blocks[i]
        if blk.content_type != MAPPED_SLICE_HEADER:
            raise CRAMError(f"expected slice header block, got type "
                            f"{blk.content_type}")
        slice_hdr = SliceHeader.from_bytes(blk.data)
        body = cont.blocks[i + 1:i + 1 + slice_hdr.n_blocks]
        if len(body) != slice_hdr.n_blocks:
            raise CRAMError("slice block count overruns container")
        core = b""
        ext_blocks: Dict[int, Block] = {}
        codec_rec_lens: Dict[int, list] = {}
        for b in body:
            if b.content_type == CORE_DATA:
                core = b.data
            elif b.content_type == EXTERNAL_DATA:
                ext_blocks[b.content_id] = b
                if b.method == FQZCOMP and b.aux:
                    codec_rec_lens[b.content_id] = b.aux
        yield (comp, slice_hdr, core, ExternalBlocks(ext_blocks),
               codec_rec_lens)
        i += 1 + slice_hdr.n_blocks


def decode_container_slices(cont: Container, header: SAMHeader,
                            ref_source: Optional[ReferenceSource] = None
                            ) -> List[Tuple[int, List["CramRecord"]]]:
    """Decode one data container into per-slice pre-SAM CramRecord lists
    (features resolved, mates NOT linked), each paired with its slice's
    record-counter base.  The columnar stats path consumes these directly
    — seq/qual/length are final here — skipping mate resolution and
    SamRecord materialization; decode_container builds on this for the
    full SAM view."""
    out: List[Tuple[int, List["CramRecord"]]] = []
    for comp, slice_hdr, core, external, codec_lens \
            in iter_container_slices(cont):
        records = decode_slice_records(comp, slice_hdr, core, external,
                                       header.ref_names, ref_source,
                                       codec_rec_lens=codec_lens)
        out.append((slice_hdr.record_counter, records))
    return out


def decode_container(cont: Container, header: SAMHeader,
                     ref_source: Optional[ReferenceSource] = None
                     ) -> List[SamRecord]:
    """Decode every slice of one data container into SAM records."""
    out: List[SamRecord] = []
    for base, records in decode_container_slices(cont, header, ref_source):
        _resolve_mates(records)      # NF chains never cross slices [SPEC]
        out.extend(_to_sam(r, header, base + j)
                   for j, r in enumerate(records))
    return out


def iter_cram_records(source, header: Optional[SAMHeader] = None,
                      ref_source: Optional[ReferenceSource] = None
                      ) -> Iterator[SamRecord]:
    buf = _read_all(source)
    hdr, pos = read_cram_header(buf)
    header = header or hdr
    n = len(buf)
    while pos < n:
        cont, pos = read_container(buf, pos)
        if cont.header.is_eof:
            break
        yield from decode_container(cont, header, ref_source)


def read_cram(source, ref_source: Optional[ReferenceSource] = None
              ) -> Tuple[SAMHeader, List[SamRecord]]:
    buf = _read_all(source)
    header, _ = read_cram_header(buf)
    return header, list(iter_cram_records(buf, header, ref_source))


# ---------------------------------------------------------------------------
# CramRecord → SamRecord
# ---------------------------------------------------------------------------

def _resolve_mates(records: List[CramRecord]) -> None:
    """Link NF (mate-downstream) chains the way htsjdk does: each record's
    mate is the next in the chain; the last points back to the first."""
    seen = set()
    for i, r in enumerate(records):
        if i in seen or r.next_fragment < 0:
            continue
        chain = [i]
        j = i
        while records[j].next_fragment >= 0:
            j = j + records[j].next_fragment + 1
            if j >= len(records):
                raise CRAMError("NF mate link points past the slice")
            chain.append(j)
        seen.update(chain)
        for k, idx in enumerate(chain):
            mate = records[chain[(k + 1) % len(chain)]]
            rec = records[idx]
            rec.mate_ref_id = mate.ref_id
            rec.mate_pos = mate.pos
            rec.mate_flags = ((1 if mate.bf & 0x10 else 0)
                              | (2 if mate.bf & 0x4 else 0))
        # template size: leftmost..rightmost span, sign by position
        mapped = [records[idx] for idx in chain if not records[idx].bf & 0x4]
        if len(mapped) >= 2:
            starts = [m.pos for m in mapped]
            ends = [m.pos + _cigar_ref_len(m.cigar) - 1 for m in mapped]
            tlen = max(ends) - min(starts) + 1
            leftmost = min(range(len(mapped)), key=lambda k: starts[k])
            for k, m in enumerate(mapped):
                m.template_size = tlen if k == leftmost else -tlen


def _cigar_ref_len(cigar: str) -> int:
    if cigar == "*":
        return 0
    from hadoop_bam_tpu.formats.bam import parse_cigar_string
    return sum(n for n, op in parse_cigar_string(cigar) if op in "MDN=X")


def _to_sam(r: CramRecord, header: SAMHeader, counter: int) -> SamRecord:
    flag = r.bf
    if r.mate_flags & 1:
        flag |= MATE_REVERSE
    if r.mate_flags & 2:
        flag |= MATE_UNMAPPED
    names = header.ref_names
    rname = names[r.ref_id] if 0 <= r.ref_id < len(names) else "*"
    if r.mate_ref_id < 0:
        rnext = "*"
    elif r.mate_ref_id == r.ref_id:
        rnext = "="
    else:
        rnext = names[r.mate_ref_id] if r.mate_ref_id < len(names) else "*"
    if r.cf & CF_QUAL_STORED and r.qual:
        qual = bytes(r.qual).translate(_Q33).decode("latin-1")
    else:
        qual = "*"
    tags = list(r.tags)
    if r.read_group >= 0 and not any(t == "RG" for t, _, _ in tags):
        rg_ids = _rg_ids(header)
        if r.read_group < len(rg_ids):
            tags.append(("RG", "Z", rg_ids[r.read_group]))
    name = r.name.decode("ascii") if r.name else f"cram-{counter}"
    return SamRecord(
        qname=name, flag=flag, rname=rname, pos=r.pos,
        mapq=r.mapq if not r.bf & 0x4 else 0,
        cigar=r.cigar if not r.bf & 0x4 else "*",
        rnext=rnext, pnext=r.mate_pos, tlen=r.template_size,
        seq=r.seq if r.seq else "*", qual=qual, tags=tags)


def _rg_ids(header: SAMHeader) -> List[str]:
    ids = []
    for line in header.text.splitlines():
        if line.startswith("@RG"):
            for f in line.split("\t")[1:]:
                if f.startswith("ID:"):
                    ids.append(f[3:])
    return ids
