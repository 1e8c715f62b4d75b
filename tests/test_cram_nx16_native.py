"""The native rANS Nx16 decoder (``native/hbam_native.cpp::
hbam_rans_nx16_decode`` through ``utils/native.py::rans_nx16_decode``) is
held to the Python decoder of ``formats/cram_codecs_nx16.py``, which stays
the fallback and the oracle: byte for byte on every flag combination, at
N = 4 and X32, on empty, one-byte, short and 256-symbol streams; the same
error class on truncated streams and bad final states; and both paths
counted."""
from __future__ import annotations

import itertools
import random

import numpy as np
import pytest

from hadoop_bam_tpu.formats.cram_codecs import RansError
from hadoop_bam_tpu.formats.cram_codecs_nx16 import (
    NX16_CAT, NX16_ORDER1, NX16_PACK, NX16_RLE, NX16_STRIPE, NX16_X32,
    _encode_order0_core, _read_order1_ctx_tables, rans_nx16_decode,
    rans_nx16_decode_python, rans_nx16_encode, var_get_u32, var_put_u32,
)
from hadoop_bam_tpu.utils import native
from hadoop_bam_tpu.utils.metrics import base_metrics

pytestmark = pytest.mark.skipif(not native.available(),
                                reason="native library not built here")

FLAGS = (NX16_ORDER1, NX16_X32, NX16_STRIPE, NX16_CAT, NX16_RLE, NX16_PACK)
COMBOS = [sum(c) for r in range(len(FLAGS) + 1)
          for c in itertools.combinations(FLAGS, r)]
ALPHABETS = (b"A", b"AC", b"ACGT", b"ACGTN!#", bytes(range(256)))


def _streams(seed: int):
    """(data) cases: empty, 1 byte, <= 16 symbols, 256 symbols, a few
    thousand, in runs and not."""
    rng = random.Random(seed)
    for n in (0, 1, 7, 16, 256, 3001):
        for alpha in ALPHABETS:
            data = bytes(rng.choice(alpha) for _ in range(n))
            yield data
            if n > 1:
                yield bytes(sorted(data))               # long runs


def _native(frame: bytes, size: int) -> bytes:
    out = native.rans_nx16_decode(frame, size)
    assert out is not None, "the native pass refused a stream"
    return out.tobytes()


@pytest.mark.parametrize("flags", COMBOS,
                         ids=[f"0x{f:02x}" for f in COMBOS])
def test_native_equals_python_on_every_flag_combination(flags):
    for data in _streams(flags):
        frame = rans_nx16_encode(data, flags)
        want = rans_nx16_decode_python(frame)
        assert want == data
        assert _native(frame, len(data)) == want


def _order1_with_compressed_tables(data: bytes) -> bytes:
    """An order-1 frame whose context tables are themselves an order-0
    stream (the lead byte's bit 0), rebuilt from the encoder's plain one."""
    frame = rans_nx16_encode(data, NX16_ORDER1)
    assert frame[0] == NX16_ORDER1
    size, pos = var_get_u32(frame, 1)
    lead = frame[pos]
    _f, _c, _s, end = _read_order1_ctx_tables(frame, pos + 1, lead >> 4)
    tables = frame[pos + 1:end]
    comp = _encode_order0_core(tables, 4)
    return (frame[:pos] + bytes([lead | 1]) + var_put_u32(len(tables))
            + var_put_u32(len(comp)) + comp + frame[end:])


def _rle_with_compressed_meta(data: bytes) -> bytes:
    """An RLE frame whose metadata is an order-0 stream (even length
    word), rebuilt from the encoder's raw-metadata one."""
    frame = rans_nx16_encode(data, NX16_RLE)
    assert frame[0] & NX16_RLE
    _size, pos = var_get_u32(frame, 1)
    mlen, p = var_get_u32(frame, pos)
    meta = frame[p:p + (mlen >> 1)]
    comp = _encode_order0_core(meta, 4)
    return (frame[:pos] + var_put_u32(len(meta) << 1)
            + var_put_u32(len(comp)) + comp + frame[p + (mlen >> 1):])


@pytest.mark.parametrize("build", [_order1_with_compressed_tables,
                                   _rle_with_compressed_meta])
@pytest.mark.parametrize("n", [40, 500, 5000])
def test_native_equals_python_on_compressed_metadata(build, n):
    rng = random.Random(n)
    data = bytes(sorted(rng.choice(b"ACGTN") for _ in range(n)))
    frame = build(data)
    assert rans_nx16_decode_python(frame) == data
    assert _native(frame, n) == data


@pytest.mark.parametrize("flags", [0, NX16_ORDER1, NX16_X32,
                                   NX16_PACK | NX16_RLE,
                                   NX16_STRIPE | NX16_ORDER1])
def test_truncated_streams_raise_on_both_paths(flags):
    rng = random.Random(flags)
    data = bytes(rng.choice(b"ACGTN!") for _ in range(2000))
    frame = rans_nx16_encode(data, flags)
    for cut in sorted({1, 2, 5, len(frame) // 3, len(frame) // 2,
                       len(frame) - 3, len(frame) - 1}):
        short = frame[:cut]
        with pytest.raises(RansError):
            rans_nx16_decode_python(short, len(data))
        with pytest.raises(RansError):
            rans_nx16_decode(short, len(data))


@pytest.mark.parametrize("flags", [0, NX16_ORDER1, NX16_X32,
                                   NX16_ORDER1 | NX16_X32])
def test_bad_final_state_raises_on_both_paths(flags):
    """A stream whose last renormalisation word is changed decodes every
    state back to something other than 2^15."""
    rng = random.Random(7 + flags)
    data = bytes(rng.choice(b"ACGTN!") for _ in range(3000))
    frame = bytearray(rans_nx16_encode(data, flags))
    frame[-1] ^= 0x40
    with pytest.raises(RansError):
        rans_nx16_decode_python(bytes(frame))
    with pytest.raises(RansError):
        native.rans_nx16_decode(bytes(frame), len(data))


def test_nosz_needs_a_size_on_both_paths():
    frame = rans_nx16_encode(b"ACGT" * 40, 0x10)
    with pytest.raises(RansError):
        rans_nx16_decode_python(frame)
    with pytest.raises(RansError):
        rans_nx16_decode(frame)
    assert rans_nx16_decode(frame, 160) == b"ACGT" * 40


def test_both_paths_are_counted(monkeypatch):
    data = bytes(range(256)) * 8
    frame = rans_nx16_encode(data, NX16_ORDER1)
    base_metrics().reset()
    assert rans_nx16_decode(frame) == data
    c = base_metrics().snapshot()["counters"]
    assert c["cram.nx16_native_bytes"] == len(data)
    assert "cram.nx16_python_bytes" not in c
    monkeypatch.setattr(native, "load", lambda: None)
    assert rans_nx16_decode(frame) == data
    c = base_metrics().snapshot()["counters"]
    assert c["cram.nx16_python_bytes"] == len(data)


def test_a_refused_stream_takes_the_python_decoder(monkeypatch):
    data = b"QQQQ#QQQ" * 100
    frame = rans_nx16_encode(data, NX16_PACK | NX16_RLE)
    monkeypatch.setattr(native, "rans_nx16_decode", lambda *a, **k: None)
    base_metrics().reset()
    assert rans_nx16_decode(frame) == data
    assert base_metrics().snapshot()["counters"][
        "cram.nx16_python_bytes"] == len(data)


def test_tok3_substreams_go_through_the_native_pass():
    from hadoop_bam_tpu.formats.cram_name_tok3 import tok3_decode, tok3_encode

    names = b"".join(b"IL3:6:1:%d:%04d\0" % (i // 3, i) for i in range(400))
    frame = tok3_encode(names)
    base_metrics().reset()
    assert tok3_decode(frame, len(names)) == names
    c = base_metrics().snapshot()["counters"]
    assert c["cram.nx16_native_bytes"] > 0
    assert "cram.nx16_python_bytes" not in c


def test_native_decode_releases_the_interpreter_lock():
    """While one thread is inside a long native decode, this thread keeps
    running Python: ctypes drops the interpreter lock for the call."""
    import threading

    n = 50_000_000                  # one symbol: no words, states fixed
    big = bytes([0]) + var_put_u32(n) + _encode_order0_core(b"A" * 4, 4)
    out = {}
    t = threading.Thread(target=lambda: out.update(
        r=native.rans_nx16_decode(big, n)))
    spins = 0
    t.start()
    while t.is_alive():
        spins += 1
    t.join()
    assert out["r"][:3].tobytes() == b"AAA" and out["r"].size == n
    assert spins > 1000
