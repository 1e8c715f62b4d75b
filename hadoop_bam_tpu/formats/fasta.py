"""FASTA format: ReferenceFragment model and sequence-aligned spans.

Reference equivalents: hb/FastaInputFormat.java + hb/ReferenceFragment.java
(SURVEY.md section 2.3/2.5): reference FASTA split at ``>`` sequence starts;
the value type carries (sequence text, contig name, 1-based position within
the contig) so downstream tasks know where each fragment maps.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Iterator, List, Optional, Tuple


class FastaError(ValueError):
    pass


@dataclass
class ReferenceFragment:
    """One chunk of reference sequence — hb/ReferenceFragment.java."""
    sequence: str
    contig: str
    position: int   # 1-based position of sequence[0] within the contig

    def __len__(self) -> int:
        return len(self.sequence)


def parse_fasta(text: bytes, line_fragments: bool = True
                ) -> List[ReferenceFragment]:
    """Parse FASTA text into fragments.

    ``line_fragments=True`` mirrors the reference reader: one fragment per
    sequence line (with running position); False merges whole contigs."""
    out: List[ReferenceFragment] = []
    contig: Optional[str] = None
    position = 1
    merged: List[str] = []
    for raw in text.split(b"\n"):
        line = raw.strip()
        if not line:
            continue
        if line.startswith(b">"):
            if contig is not None and not line_fragments and merged:
                out.append(ReferenceFragment("".join(merged), contig, 1))
            name_parts = line[1:].split()
            if not name_parts:
                raise FastaError("empty contig name in FASTA header")
            contig = name_parts[0].decode()
            position = 1
            merged = []
            continue
        if contig is None:
            raise FastaError("sequence data before any '>' header")
        seq = line.decode()
        if line_fragments:
            out.append(ReferenceFragment(seq, contig, position))
        else:
            merged.append(seq)
        position += len(seq)
    if contig is not None and not line_fragments and merged:
        out.append(ReferenceFragment("".join(merged), contig, 1))
    return out


def find_sequence_start(buf: bytes, offset: int = 0) -> Optional[int]:
    """Offset of the next ``>`` header-line start at or after ``offset`` —
    the split-snapping rule of hb/FastaInputFormat.getSplits."""
    if offset == 0 and buf[:1] == b">":
        return 0
    pos = max(offset - 1, 0)
    while True:
        hit = buf.find(b"\n>", pos)
        if hit < 0:
            return None
        if hit + 1 >= offset:
            return hit + 1
        pos = hit + 1


def format_fasta(fragments: List[ReferenceFragment], width: int = 60) -> str:
    """Emit FASTA text (contig headers inserted when the name changes)."""
    out: List[str] = []
    last: Optional[str] = None
    for f in fragments:
        if f.contig != last:
            out.append(f">{f.contig}\n")
            last = f.contig
        seq = f.sequence
        for i in range(0, len(seq), width):
            out.append(seq[i:i + width] + "\n")
    return "".join(out)


# ---------------------------------------------------------------------------
# .fai-indexed access (samtools faidx layout): what a CRAM decoder needs of
# a reference — a contig range as bytes — without reading the genome
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class FaiEntry:
    """One line of a samtools ``.fai``: NAME LENGTH OFFSET LINEBASES
    LINEWIDTH."""
    name: str
    length: int
    offset: int        # file offset of the contig's first base
    line_bases: int
    line_width: int    # bytes a full line, its terminator included

    def to_line(self) -> str:
        return (f"{self.name}\t{self.length}\t{self.offset}\t"
                f"{self.line_bases}\t{self.line_width}\n")


def read_fai(path: str) -> List[FaiEntry]:
    out = []
    with open(path, encoding="ascii") as fh:
        for ln in fh:
            f = ln.rstrip("\n").split("\t")
            if len(f) < 5:
                raise FastaError(f"{path}: not a .fai line: {ln!r}")
            out.append(FaiEntry(f[0], int(f[1]), int(f[2]), int(f[3]),
                                int(f[4])))
    return out


def _regular_entry(data, name: str, seq_start: int, end: int
                   ) -> Optional[FaiEntry]:
    """The .fai entry of the contig whose sequence lines fill
    data[seq_start:end), or None when its lines are not all one width
    (the last may be shorter) with one terminator — ragged, blank or
    padded lines, which samtools faidx refuses."""
    import numpy as np

    if end <= seq_start:
        return FaiEntry(name, 0, seq_start, 0, 0)
    region = np.frombuffer(data, np.uint8, end - seq_start, seq_start)
    if np.count_nonzero((region == 32) | (region == 9)):
        return None
    nl = np.flatnonzero(region == 10)
    tail = region.size - (int(nl[-1]) + 1 if nl.size else 0)
    if nl.size == 0:
        if region[-1] == 13:
            return None
        return FaiEntry(name, tail, seq_start, tail, tail + 1)
    first = int(nl[0])
    crlf = first > 0 and region[first - 1] == 13
    lb, lw = first - int(crlf), first + 1
    if lb <= 0:
        return None
    # every line a full one but the last, which may be shorter: the
    # terminators sit on a grid of lw, the last one (without tail bases
    # after it) anywhere after the one before
    n_grid = nl.size if tail else nl.size - 1
    grid = lw - 1 + lw * np.arange(n_grid, dtype=np.int64)
    if not np.array_equal(nl[:n_grid], grid):
        return None
    if tail:
        if tail > lb or region[-1] == 13:
            return None
        last = tail
    else:
        prev = int(nl[-2]) + 1 if nl.size > 1 else 0
        last = int(nl[-1]) - prev - int(crlf)
        if not 0 < last <= lb:
            return None
    if int(np.count_nonzero(region == 13)) != (nl.size if crlf else 0):
        return None
    if crlf and not bool((region[nl - 1] == 13).all()):
        return None
    return FaiEntry(name, n_grid * lb + last, seq_start, lb, lw)


def build_fai(data) -> Tuple[List[FaiEntry], Dict[str, bytes]]:
    """Index FASTA bytes (or a mapping of them) in one pass: the .fai
    entries of regular contigs, and the bases of ragged ones (their lines
    stripped and joined, as ``parse_fasta`` reads them), by name."""
    entries: List[FaiEntry] = []
    ragged: Dict[str, bytes] = {}
    n = len(data)
    pos = 0
    if n and data[0:1] != b">":
        hit = data.find(b"\n>")
        stray = data[:hit if hit >= 0 else n]
        if stray.strip():
            raise FastaError("sequence data before any '>' header")
        pos = hit + 1 if hit >= 0 else n
    while pos < n:
        eol = data.find(b"\n", pos)
        eol = n if eol < 0 else eol
        name_parts = bytes(data[pos + 1:eol]).split()
        if not name_parts:
            raise FastaError("empty contig name in FASTA header")
        name = name_parts[0].decode()
        seq_start = min(eol + 1, n)
        nxt = data.find(b"\n>", eol)
        end = n if nxt < 0 else nxt + 1
        entry = _regular_entry(data, name, seq_start, end)
        if entry is None:
            ragged[name] = b"".join(
                ln.strip() for ln in bytes(data[seq_start:end]).split(b"\n"))
        else:
            entries.append(entry)
        pos = end
    return entries, ragged


class IndexedFasta:
    """A FASTA read the way samtools holds one: its ``.fai`` (read, or
    built in one pass and written beside the file when absent or older
    than the FASTA), the file memory-mapped, and a contig range returned
    as ``uint8`` bases without a Python ``str`` of the contig.  Contigs
    whose lines are ragged (no .fai can describe them) are kept as bytes
    from the same pass."""

    def __init__(self, path_or_bytes):
        import mmap
        import os

        self._ragged: Dict[str, bytes] = {}
        if isinstance(path_or_bytes, (bytes, bytearray)):
            self._data = bytes(path_or_bytes)
            entries, self._ragged = build_fai(self._data)
        else:
            path = os.fspath(path_or_bytes)
            with open(path, "rb") as fh:
                size = os.fstat(fh.fileno()).st_size
                self._data = (mmap.mmap(fh.fileno(), 0,
                                        access=mmap.ACCESS_READ)
                              if size else b"")
            fai = path + ".fai"
            if (os.path.exists(fai)
                    and os.path.getmtime(fai) >= os.path.getmtime(path)):
                entries = read_fai(fai)
            else:
                entries, self._ragged = build_fai(self._data)
                if not self._ragged:
                    _write_fai(fai, entries)
        self._entries = {e.name: e for e in entries}

    def __contains__(self, name: str) -> bool:
        return name in self._entries or name in self._ragged

    def length(self, name: str) -> int:
        if name in self._ragged:
            return len(self._ragged[name])
        return self._entries[name].length

    def fetch(self, name: str, lo: int, hi: int):
        """Bases [lo, hi) (0-based, clipped to the contig) as uint8."""
        import numpy as np

        if name in self._ragged:
            return np.frombuffer(self._ragged[name], np.uint8)[lo:hi]
        e = self._entries[name]
        lo, hi = max(0, lo), min(hi, e.length)
        if hi <= lo:
            return np.zeros(0, np.uint8)
        l0, l1 = lo // e.line_bases, (hi - 1) // e.line_bases
        start = e.offset + l0 * e.line_width
        want = (l1 - l0 + 1) * e.line_width
        raw = np.frombuffer(self._data, np.uint8,
                            min(want, len(self._data) - start), start)
        if raw.size < want:               # the file's last line
            raw = np.concatenate([raw, np.zeros(want - raw.size, np.uint8)])
        rows = raw.reshape(-1, e.line_width)[:, :e.line_bases]
        skip = lo - l0 * e.line_bases
        return rows.reshape(-1)[skip:skip + hi - lo]


def _write_fai(path: str, entries: List[FaiEntry]) -> None:
    """Write the index beside the FASTA as samtools faidx would; a
    read-only directory just means the next open builds it again."""
    import os

    tmp = f"{path}.{os.getpid()}.tmp"
    try:
        with open(tmp, "w", encoding="ascii") as fh:
            fh.writelines(e.to_line() for e in entries)
        os.replace(tmp, path)
    except OSError:
        try:
            os.unlink(tmp)
        except OSError:
            pass
