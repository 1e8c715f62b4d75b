"""Batched BAM record field unpack: bytes + offsets -> SoA columns, on device.

The device-side replacement for htsjdk ``BAMRecordCodec.decode``'s per-record
field parse (the hot loop of hb/BAMRecordReader.java, SURVEY.md section 3.2).
Input is the inflated span bytes (uint8, padded to a static capacity) and the
record start offsets (int32, padded); output is one int32 column per fixed
field [SPEC record layout, formats/bam.py docstring].

``unpack_fixed_fields`` is pure jnp: the single gather
``data[offsets[:, None] + arange(36)]`` pulls each record's fixed 36-byte
prefix into an [N, 36] tile; field extraction is then fused elementwise
arithmetic.

Padding convention: offsets[i] for i >= n_records MUST point at valid bytes
(use 0); consumers mask with ``valid = arange(N) < n_records``.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# column name -> (byte offset in record, byte width, signed)
FIXED_FIELDS: Dict[str, Tuple[int, int, bool]] = {
    "block_size": (0, 4, True),
    "refid": (4, 4, True),
    "pos": (8, 4, True),
    "l_read_name": (12, 1, False),
    "mapq": (13, 1, False),
    "bin": (14, 2, False),
    "n_cigar": (16, 2, False),
    "flag": (18, 2, False),
    "l_seq": (20, 4, True),
    "mate_refid": (24, 4, True),
    "mate_pos": (28, 4, True),
    "tlen": (32, 4, True),
}

PREFIX = 36

ALL_FIELDS: Tuple[str, ...] = tuple(FIXED_FIELDS)

# Pushdown projection for flagstat: only the columns the reduction reads
# cross the host->device link (11 bytes/record instead of 36).
FLAGSTAT_PROJECTION: Tuple[str, ...] = ("flag", "refid", "mate_refid", "mapq")


def projection_row_bytes(fields: Tuple[str, ...]) -> int:
    return sum(FIXED_FIELDS[name][1] for name in fields)


def projection_ranges(fields: Tuple[str, ...]) -> "list[tuple[int, int]]":
    """(src_offset, length) copy ranges for the host row packer, with
    adjacent source ranges merged (the full-field projection collapses to a
    single 36-byte memcpy)."""
    ranges: list[tuple[int, int]] = []
    for name in fields:
        off, width, _ = FIXED_FIELDS[name]
        if ranges and ranges[-1][0] + ranges[-1][1] == off:
            ranges[-1] = (ranges[-1][0], ranges[-1][1] + width)
        else:
            ranges.append((off, width))
    return ranges


def unpack_projected_tile(tile: jnp.ndarray, fields: Tuple[str, ...]
                          ) -> Dict[str, jnp.ndarray]:
    """tile: [N, row_bytes] uint8, rows packed per ``fields`` order ->
    dict of int32 columns (fused elementwise, no gather)."""
    t = tile.astype(jnp.uint32)
    out: Dict[str, jnp.ndarray] = {}
    off = 0
    for name in fields:
        _, width, _signed = FIXED_FIELDS[name]
        acc = t[:, off]
        for k in range(1, width):
            acc = acc | (t[:, off + k] << (8 * k))
        out[name] = acc.astype(jnp.int32)
        off += width
    return out


def unpack_fixed_fields_tile(tile: jnp.ndarray) -> Dict[str, jnp.ndarray]:
    """tile: [N, 36] uint8 -> dict of int32 columns (fused elementwise).

    The dense-tile entry point: when the host packs each record's 36-byte
    fixed prefix contiguously (the columnar transfer layout — ~20x fewer
    bytes over the interconnect than shipping whole inflated spans), field
    extraction is pure strided slicing, no gather at all.  The fixed prefix
    is exactly the all-fields projection: FIXED_FIELDS covers bytes 0..35
    contiguously in declaration order."""
    return unpack_projected_tile(tile, ALL_FIELDS)


@jax.jit
def unpack_fixed_fields(data: jnp.ndarray, offsets: jnp.ndarray
                        ) -> Dict[str, jnp.ndarray]:
    """data: uint8 [D]; offsets: int32 [N] (padded with safe offsets).
    Returns dict of int32 [N] columns for every fixed field."""
    idx = offsets[:, None] + jnp.arange(PREFIX, dtype=offsets.dtype)[None, :]
    tile = data[idx]  # [N, 36] uint8 gather
    return unpack_fixed_fields_tile(tile)


@jax.jit
def gather_record_windows(data: jnp.ndarray, offsets: jnp.ndarray,
                          window: int) -> jnp.ndarray:
    """Gather a fixed-size byte window per record (for payload-stage kernels:
    names, cigar, seq).  Returns uint8 [N, window]."""
    idx = offsets[:, None] + jnp.arange(window, dtype=offsets.dtype)[None, :]
    idx = jnp.minimum(idx, data.shape[0] - 1)
    return data[idx]


def pad_offsets(offsets: np.ndarray, capacity: int) -> Tuple[np.ndarray, int]:
    """Host helper: pad an offsets vector to ``capacity`` with zeros."""
    n = int(offsets.size)
    if n > capacity:
        raise ValueError(f"{n} records exceed capacity {capacity}")
    out = np.zeros(capacity, dtype=np.int32)
    out[:n] = offsets
    return out, n


def pad_data(data: np.ndarray, capacity: int) -> np.ndarray:
    """Host helper: pad span bytes to ``capacity`` (static shape for jit)."""
    if data.size > capacity:
        raise ValueError(f"{data.size} bytes exceed capacity {capacity}")
    out = np.zeros(capacity, dtype=np.uint8)
    out[:data.size] = data
    return out
