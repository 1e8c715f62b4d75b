"""Build + load the native C++ helper library (ctypes).

The reference reached native code through java.util.zip's JNI; we compile
native/hbam_native.cpp on first use with g++ and bind via ctypes (no pybind11
in this image).  Every caller must tolerate ``load() is None`` — the NumPy /
zlib-module fallbacks keep the framework functional without a compiler —
but a failed build is never silent: the compiler's message is logged and
kept (``build_info()["error"]``).

The artifact is named by a digest of the source bytes, the compile
command and this host's CPU model + feature flags (``-march=native``
bakes the build host's ISA in), so a library built from other source,
with other flags or on another machine is never loaded: a tree copied
between hosts rebuilds from native/hbam_native.cpp.
"""
from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import platform
import subprocess
import threading
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

logger = logging.getLogger(__name__)

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_SRC = os.path.join(_REPO_ROOT, "native", "hbam_native.cpp")
_OUT_DIR = os.path.join(_REPO_ROOT, "native", "build")

# HBAM_NATIVE_SANITIZE=address|thread builds and loads a sanitized variant
# (the reference side got memory safety for free from the JVM; our C++ has
# threads + raw offset arithmetic, so CI exercises it under ASan/TSan —
# SURVEY.md section 5 sanitizers row).  The sanitized .so only loads when
# the runtime (libasan/libtsan) is preloaded; tests spawn a subprocess with
# LD_PRELOAD set (tests/test_native_sanitize.py).
_SANITIZE = os.environ.get("HBAM_NATIVE_SANITIZE", "")

# Build flavours in preference order: libdeflate (~2x zlib inflate
# speed) when the toolchain has it, plain zlib otherwise.
_FLAVOURS: Tuple[Tuple[str, List[str]], ...] = (
    ("libdeflate", ["-DHBAM_USE_LIBDEFLATE", "-lz", "-ldeflate"]),
    ("zlib", ["-lz"]),
)

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_tried = False
_info: Dict[str, Optional[str]] = {"path": None, "flavour": None,
                                   "error": None}


def _host_cpu_signature() -> str:
    """CPU model + ISA feature flags of this host — what ``-march=native``
    compiles for."""
    try:
        with open("/proc/cpuinfo") as f:
            lines = f.read().split("\n\n", 1)[0].splitlines()
        keep = [ln.split(":", 1)[1].strip() for ln in lines
                if ln.split(":", 1)[0].strip() in ("model name", "flags",
                                                   "Features")]
        if keep:
            return "|".join(keep)
    except OSError:
        pass
    return f"{platform.machine()}|{platform.processor()}"


def _cflags() -> List[str]:
    flags = ["-O3", "-march=native", "-shared", "-fPIC", "-pthread"]
    if _SANITIZE:
        flags = [f"-fsanitize={_SANITIZE}", "-fno-omit-frame-pointer",
                 "-g"] + flags
    return flags


def artifact_path(flavour: str, extra: List[str]) -> str:
    """The digest-keyed artifact for one build flavour on this host."""
    h = hashlib.sha256()
    with open(_SRC, "rb") as f:
        h.update(f.read())
    h.update("\0".join(["g++"] + _cflags() + extra).encode())
    h.update(_host_cpu_signature().encode())
    tag = f"_{_SANITIZE}" if _SANITIZE else ""
    return os.path.join(
        _OUT_DIR, f"libhbam_native{tag}-{flavour}-{h.hexdigest()[:16]}.so")


def _compile(out: str, extra: List[str]) -> Optional[str]:
    """Build one flavour to ``out``; None on success, else the
    compiler's message."""
    os.makedirs(_OUT_DIR, exist_ok=True)
    # build under a private name and rename: a concurrent process never
    # loads a half-written library
    tmp = f"{out}.{os.getpid()}.tmp"
    cmd = ["g++"] + _cflags() + [_SRC, "-o", tmp] + extra
    try:
        r = subprocess.run(cmd, capture_output=True, text=True, timeout=300)
    except (OSError, subprocess.TimeoutExpired) as e:
        return f"{type(e).__name__}: {e}"
    if r.returncode != 0:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        return (r.stderr or r.stdout or f"g++ exited {r.returncode}").strip()
    os.replace(tmp, out)
    return None


def _find_or_build() -> Optional[Tuple[str, str]]:
    """(path, flavour) of a library built for THIS source, command and
    CPU — an existing digest-named artifact, else a fresh build."""
    paths = [(artifact_path(name, extra), name, extra)
             for name, extra in _FLAVOURS]
    for path, name, _extra in paths:
        if os.path.exists(path):
            return path, name
    errors = []
    for path, name, extra in paths:
        err = _compile(path, extra)
        if err is None:
            return path, name
        errors.append(f"[{name}] {err}")
    _info["error"] = "\n".join(errors)
    logger.error("native library build failed; host decode degrades to "
                 "Python zlib:\n%s", _info["error"])
    return None


def build_info() -> Dict[str, Optional[str]]:
    """{"path", "flavour" ("libdeflate" | "zlib"), "error" (the
    compiler's message when the build or load failed)} of this process's
    native library, loading it if needed."""
    load()
    return dict(_info)


def load() -> Optional[ctypes.CDLL]:
    """Load (compiling if needed) the native library; None on failure."""
    global _lib, _tried
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        if not os.path.exists(_SRC):
            _info["error"] = f"native source missing: {_SRC}"
            logger.error(_info["error"])
            return None
        found = _find_or_build()
        if found is None:
            return None
        path, flavour = found
        try:
            lib = ctypes.CDLL(path)
        except OSError as e:
            _info["error"] = f"cannot load {path}: {e}"
            logger.error(_info["error"])
            return None
        _info["path"], _info["flavour"] = path, flavour
        i8p = ctypes.POINTER(ctypes.c_uint8)
        i64p = ctypes.POINTER(ctypes.c_int64)
        i32p = ctypes.POINTER(ctypes.c_int32)
        u32p = ctypes.POINTER(ctypes.c_uint32)
        lib.hbam_inflate_batch.restype = ctypes.c_int
        lib.hbam_inflate_batch.argtypes = [
            i8p, i64p, i32p, ctypes.c_int32, i8p, i64p, i32p, ctypes.c_int32]
        lib.hbam_walk_bam_records.restype = ctypes.c_int64
        lib.hbam_walk_bam_records.argtypes = [
            i8p, ctypes.c_int64, ctypes.c_int64, i64p, ctypes.c_int64, i64p]
        lib.hbam_walk_bam_packed.restype = ctypes.c_int64
        lib.hbam_walk_bam_packed.argtypes = [
            i8p, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64, i32p, i32p,
            ctypes.c_int32, ctypes.c_int32, i8p, i64p, ctypes.c_int64, i64p]
        lib.hbam_walk_bam_payload.restype = ctypes.c_int64
        lib.hbam_walk_bam_payload.argtypes = [
            i8p, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
            ctypes.c_int32, ctypes.c_int32, ctypes.c_int32,
            i8p, i8p, i8p, i64p, ctypes.c_int64, i64p]
        for name in ("hbam_rans0_decode", "hbam_rans1_decode"):
            fn = getattr(lib, name)
            fn.restype = ctypes.c_int
            fn.argtypes = [i8p, ctypes.c_int64, ctypes.c_int64,
                           u32p, u32p, i8p, i8p, ctypes.c_int64]
        lib.hbam_pack_reads.restype = None
        lib.hbam_pack_reads.argtypes = [
            i8p, i8p, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64, i8p,
            ctypes.c_int64, i8p, ctypes.c_int64, i8p, ctypes.c_int64]
        lib.hbam_copy_runs.restype = ctypes.c_int64
        lib.hbam_copy_runs.argtypes = [
            i8p, ctypes.c_int64, i8p, ctypes.c_int64, i64p, i64p, i64p,
            ctypes.c_int64]
        lib.hbam_rans_nx16_decode_batch.restype = ctypes.c_int64
        u64p = ctypes.POINTER(ctypes.c_uint64)
        lib.hbam_rans_nx16_decode_batch.argtypes = [
            u64p, i64p, u64p, i64p, i32p, ctypes.c_int64]
        lib.hbam_rans_nx16_decode.restype = ctypes.c_int
        lib.hbam_rans_nx16_decode.argtypes = [
            i8p, ctypes.c_int64, i8p, ctypes.c_int64]
        lib.hbam_itf8_decode_batch.restype = ctypes.c_int64
        lib.hbam_itf8_decode_batch.argtypes = [
            i8p, ctypes.c_int64, ctypes.c_int64, i32p]
        lib.hbam_crc32_batch.restype = ctypes.c_int
        lib.hbam_crc32_batch.argtypes = [
            i8p, i64p, i32p, ctypes.c_int32, u32p, ctypes.c_int32]
        lib.hbam_block_table.restype = ctypes.c_int64
        lib.hbam_block_table.argtypes = [
            i8p, ctypes.c_int64, ctypes.c_int64, i64p, i64p, i32p, i32p,
            ctypes.c_int64, i64p]
        lib.hbam_deflate_batch.restype = ctypes.c_int
        lib.hbam_deflate_batch.argtypes = [
            i8p, i64p, i32p, ctypes.c_int32, i8p, i64p, i32p, i32p,
            ctypes.c_int32, ctypes.c_int32]
        i8sp = ctypes.POINTER(ctypes.c_int8)
        lib.hbam_bcf_gt_dosage.restype = ctypes.c_int64
        lib.hbam_bcf_gt_dosage.argtypes = [
            i8p, ctypes.c_int64, i64p, i64p, ctypes.c_int64, ctypes.c_int32,
            ctypes.c_int32, ctypes.c_int64, i8sp, ctypes.c_int64,
            ctypes.c_int64]
        f32p = ctypes.POINTER(ctypes.c_float)
        i16p = ctypes.POINTER(ctypes.c_int16)
        lib.hbam_bcf_chase.restype = ctypes.c_int64
        lib.hbam_bcf_chase.argtypes = [
            i8p, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64, i64p,
            ctypes.c_int64, i64p, i64p]
        lib.hbam_bcf_span_columns.restype = ctypes.c_int64
        lib.hbam_bcf_span_columns.argtypes = [
            i8p, ctypes.c_int64, i64p, ctypes.c_int64, ctypes.c_int64,
            ctypes.c_int64, ctypes.c_int64, ctypes.c_int64, i32p, i32p, i32p,
            f32p, i16p, i16p, i8p, i8sp, ctypes.c_int64, i64p]
        lib.hbam_bcf_guess.restype = ctypes.c_int64
        lib.hbam_bcf_guess.argtypes = [
            i8p, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
            ctypes.c_int32, ctypes.c_int32, i32p]
        lib.hbam_fastq_count_lines.restype = ctypes.c_int64
        lib.hbam_fastq_count_lines.argtypes = [i8p, ctypes.c_int64]
        lib.hbam_fastq_tokenize.restype = ctypes.c_int32
        lib.hbam_fastq_tokenize.argtypes = [
            i8p, ctypes.c_int64, i8p, ctypes.c_int64, ctypes.c_int64,
            ctypes.c_int32, i8p, ctypes.c_int64, i8p, ctypes.c_int64, i32p,
            ctypes.c_int64]
        lib.hbam_vcf_tokenize.restype = ctypes.c_int64
        lib.hbam_vcf_tokenize.argtypes = [
            i8p, ctypes.c_int64, ctypes.c_int64, i64p, i32p, i8p, i8sp,
            ctypes.c_int64, ctypes.c_int64, i64p]
        lib.hbam_contig_table.restype = ctypes.c_int64
        lib.hbam_contig_table.argtypes = [
            i8p, i64p, ctypes.c_int64, i32p, ctypes.c_int64]
        lib.hbam_vcf_span_columns.restype = ctypes.c_int64
        lib.hbam_vcf_span_columns.argtypes = [
            i8p, ctypes.c_int64, ctypes.c_int64, i8p, i64p, i32p,
            ctypes.c_int64, i32p, i32p, i8p, i8sp, ctypes.c_int64, i64p,
            ctypes.c_int64, i64p]
        lib.hbam_vcf_text_span_read.restype = ctypes.c_int64
        lib.hbam_vcf_text_span_read.argtypes = [
            i8p, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
            ctypes.c_int64, i8p, ctypes.c_int64, i64p]
        lib.hbam_cram_slice_rebuild.restype = ctypes.c_int64
        lib.hbam_cram_slice_rebuild.argtypes = [
            ctypes.c_int64, i32p, i32p, i32p, i64p, i32p, i32p,
            ctypes.c_int64, i8p, i32p, ctypes.c_int64, u64p, i64p, i8p,
            ctypes.c_int32, i8p, ctypes.c_int64, ctypes.c_int64, i8p,
            ctypes.c_int64, i8p, ctypes.c_int64, i64p, i64p, i64p, i64p]
        u16p = ctypes.POINTER(ctypes.c_uint16)
        lib.hbam_deflate_find_block.restype = ctypes.c_int64
        lib.hbam_deflate_find_block.argtypes = [
            i8p, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64]
        lib.hbam_deflate_decode_symbols.restype = ctypes.c_int32
        lib.hbam_deflate_decode_symbols.argtypes = [
            i8p, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
            ctypes.c_int64, ctypes.c_int32, u16p, ctypes.c_int64, i64p, i64p]
        lib.hbam_deflate_resolve.restype = ctypes.c_uint32
        lib.hbam_deflate_resolve.argtypes = [
            u16p, ctypes.c_int64, i8p, i8p, ctypes.c_uint8, i64p]
        lib.hbam_crc32_combine.restype = ctypes.c_uint32
        lib.hbam_crc32_combine.argtypes = [
            ctypes.c_uint32, ctypes.c_uint32, ctypes.c_int64]
        f64p = ctypes.POINTER(ctypes.c_double)
        lib.hbam_grm_finish.restype = ctypes.c_int64
        lib.hbam_grm_finish.argtypes = [
            f32p, ctypes.c_int64, f32p, ctypes.c_double, ctypes.c_int64,
            ctypes.c_int64, f64p]
        if hasattr(lib, "hbam_fused_start"):
            lib.hbam_fused_start.restype = ctypes.c_void_p
            lib.hbam_fused_start.argtypes = [
                i8p, i64p, i32p, i32p, u32p, ctypes.c_int32,
                i8p, i64p, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
                ctypes.c_int32, i32p, i32p, ctypes.c_int32, ctypes.c_int32,
                i8p, i8p, i8p, ctypes.c_int32, ctypes.c_int32,
                ctypes.c_int32, i64p, ctypes.c_int64, ctypes.c_int32,
                ctypes.c_int32]
            lib.hbam_fused_next.restype = ctypes.c_int
            lib.hbam_fused_next.argtypes = [ctypes.c_void_p, i64p, i64p]
            lib.hbam_fused_finish.restype = ctypes.c_int
            lib.hbam_fused_finish.argtypes = [
                ctypes.c_void_p, i64p, i64p, i64p, i64p]
        _lib = lib
        return _lib


def _ptr(arr: np.ndarray, ctype):
    return arr.ctypes.data_as(ctypes.POINTER(ctype))


def inflate_batch(src: np.ndarray, cdata_off: np.ndarray,
                  cdata_len: np.ndarray, dst: np.ndarray,
                  dst_off: np.ndarray, isize: np.ndarray,
                  n_threads: int = 0) -> None:
    """Native batched inflate; raises on corrupt blocks."""
    lib = load()
    assert lib is not None
    if n_threads <= 0:
        n_threads = min(len(cdata_off), os.cpu_count() or 1)
    rc = lib.hbam_inflate_batch(
        _ptr(src, ctypes.c_uint8), _ptr(cdata_off, ctypes.c_int64),
        _ptr(cdata_len, ctypes.c_int32), len(cdata_off),
        _ptr(dst, ctypes.c_uint8), _ptr(dst_off, ctypes.c_int64),
        _ptr(isize, ctypes.c_int32), n_threads)
    if rc:
        raise ValueError(f"native inflate failed at block {rc - 1000}")


def block_table(src: np.ndarray, offset: int = 0
                ) -> "tuple[tuple[np.ndarray, ...], int]":
    """Native walk of the BGZF header chain in ``src[offset:]`` (the
    interpreter lock is released for the call).  Returns ((coffset i64,
    cdata_off i64, cdata_len i32, isize i32), stop): ``stop`` is
    ``src.size`` after a clean walk, else the offset of the first header
    the walk did not accept — the caller's Python parser names the fault
    from there."""
    lib = load()
    assert lib is not None
    n = int(src.size)
    # a BGZF block is seldom under 4 KiB; a table that fills is doubled
    cap = max(64, (n - offset) >> 12)
    parts = []
    stop = np.full(1, offset, dtype=np.int64)
    while True:
        cols = (np.empty(cap, np.int64), np.empty(cap, np.int64),
                np.empty(cap, np.int32), np.empty(cap, np.int32))
        k = int(lib.hbam_block_table(
            _ptr(src, ctypes.c_uint8), n, int(stop[0]),
            _ptr(cols[0], ctypes.c_int64), _ptr(cols[1], ctypes.c_int64),
            _ptr(cols[2], ctypes.c_int32), _ptr(cols[3], ctypes.c_int32),
            cap, _ptr(stop, ctypes.c_int64)))
        parts.append(tuple(c[:k] for c in cols))
        if k < cap or int(stop[0]) >= n:
            break
        cap *= 2
    if len(parts) > 1:
        parts = [tuple(np.concatenate(c) for c in zip(*parts))]
    return parts[0], int(stop[0])


def walk_bam_records(buf: np.ndarray, start: int, cap: int
                     ) -> tuple[np.ndarray, int]:
    """Native record walk; returns (offsets, tail_offset)."""
    lib = load()
    assert lib is not None
    out = np.empty(cap, dtype=np.int64)
    tail = np.zeros(1, dtype=np.int64)
    n = lib.hbam_walk_bam_records(
        _ptr(buf, ctypes.c_uint8), buf.size, start,
        _ptr(out, ctypes.c_int64), cap, _ptr(tail, ctypes.c_int64))
    if n < 0:
        raise ValueError("malformed BAM record chain")
    if n > cap:
        raise ValueError(f"record count {n} exceeds capacity {cap}")
    return out[:n], int(tail[0])


def walk_bam_packed(buf: np.ndarray, start: int, cap: int,
                    sel: "list[tuple[int, int]]", row_stride: int,
                    stop: Optional[int] = None,
                    ) -> tuple[np.ndarray, np.ndarray, int]:
    """Native single-pass walk + columnar row pack.

    ``sel`` is a list of (src_offset, length) ranges within each record's
    fixed prefix, packed back-to-back into ``row_stride``-byte rows.  The
    walk stops at the first record starting at or past ``stop`` (records
    there belong to the next span).  ``cap`` must cover the worst case —
    (stop - start) / 36 + 1 records.
    Returns (rows[n, row_stride], offsets[n], tail_offset).
    """
    lib = load()
    assert lib is not None
    if stop is None:
        stop = buf.size
    sel_off = np.asarray([o for o, _ in sel], dtype=np.int32)
    sel_len = np.asarray([l for _, l in sel], dtype=np.int32)
    rows = np.empty((cap, row_stride), dtype=np.uint8)
    offs = np.empty(cap, dtype=np.int64)
    tail = np.zeros(1, dtype=np.int64)
    n = lib.hbam_walk_bam_packed(
        _ptr(buf, ctypes.c_uint8), buf.size, start, stop,
        _ptr(sel_off, ctypes.c_int32), _ptr(sel_len, ctypes.c_int32),
        len(sel), row_stride, _ptr(rows, ctypes.c_uint8),
        _ptr(offs, ctypes.c_int64), cap, _ptr(tail, ctypes.c_int64))
    if n < 0:
        raise ValueError("malformed BAM record chain")
    if n > cap:
        raise ValueError(f"record count {n} exceeds capacity {cap}")
    return rows[:n], offs[:n], int(tail[0])


def walk_bam_payload(buf: np.ndarray, start: int, cap: int, max_len: int,
                     seq_stride: int, qual_stride: int,
                     stop: Optional[int] = None,
                     ) -> tuple[np.ndarray, np.ndarray, np.ndarray,
                                np.ndarray, int]:
    """Native single-pass walk + prefix/seq/qual tile pack.

    Returns (prefix[n, 36], seq[n, seq_stride] 4-bit packed,
    qual[n, qual_stride], offsets[n], tail_offset).  Rows are zero-padded
    (buffers are allocated zeroed here; the C side only writes payload).
    """
    lib = load()
    assert lib is not None
    if stop is None:
        stop = buf.size
    prefix = np.zeros((cap, 36), dtype=np.uint8)
    seq = np.zeros((cap, seq_stride), dtype=np.uint8)
    qual = np.zeros((cap, qual_stride), dtype=np.uint8)
    offs = np.empty(cap, dtype=np.int64)
    tail = np.zeros(1, dtype=np.int64)
    n = lib.hbam_walk_bam_payload(
        _ptr(buf, ctypes.c_uint8), buf.size, start, stop,
        max_len, seq_stride, qual_stride,
        _ptr(prefix, ctypes.c_uint8), _ptr(seq, ctypes.c_uint8),
        _ptr(qual, ctypes.c_uint8), _ptr(offs, ctypes.c_int64), cap,
        _ptr(tail, ctypes.c_int64))
    if n < 0:
        raise ValueError("malformed BAM record chain")
    if n > cap:
        raise ValueError(f"record count {n} exceeds capacity {cap}")
    return prefix[:n], seq[:n], qual[:n], offs[:n], int(tail[0])


def deflate_raw(payload: bytes, level: int = 6) -> Optional[bytes]:
    """Compress one raw-DEFLATE stream natively (libdeflate when built in).
    Returns None when the result would not beat the stored-block limit —
    callers fall back to an uncompressed block."""
    lib = load()
    assert lib is not None
    src = np.frombuffer(payload, dtype=np.uint8)
    cap = max(len(payload) + 64, 256)
    dst = np.empty(cap, dtype=np.uint8)
    out_len = np.zeros(1, dtype=np.int32)
    rc = lib.hbam_deflate_batch(
        _ptr(src, ctypes.c_uint8),
        _ptr(np.zeros(1, np.int64), ctypes.c_int64),
        _ptr(np.asarray([len(payload)], np.int32), ctypes.c_int32), 1,
        _ptr(dst, ctypes.c_uint8),
        _ptr(np.zeros(1, np.int64), ctypes.c_int64),
        _ptr(np.asarray([cap], np.int32), ctypes.c_int32),
        _ptr(out_len, ctypes.c_int32), level, 1)
    if rc or out_len[0] <= 0:
        return None
    return dst[:int(out_len[0])].tobytes()


def rans_decode(order: int, buf: np.ndarray, ptr: int, freqs: np.ndarray,
                cum: np.ndarray, slot2sym: np.ndarray, out_size: int
                ) -> np.ndarray:
    """Native rANS 4x8 decode loop (tables parsed by the caller).
    Raises on corrupt/truncated streams."""
    lib = load()
    assert lib is not None
    out = np.empty(out_size, dtype=np.uint8)
    fn = lib.hbam_rans1_decode if order else lib.hbam_rans0_decode
    rc = fn(_ptr(buf, ctypes.c_uint8), buf.size, ptr,
            _ptr(freqs, ctypes.c_uint32), _ptr(cum, ctypes.c_uint32),
            _ptr(slot2sym, ctypes.c_uint8), _ptr(out, ctypes.c_uint8),
            out_size)
    if rc != 0:
        from hadoop_bam_tpu.formats.cram_codecs import RansError
        raise RansError(
            "corrupt rANS stream (ran out of bytes)" if rc == -1 else
            "corrupt rANS stream (final-state integrity check failed)")
    return out


_NX16_ERRORS = {-1: "truncated rANS Nx16 stream (ran out of bytes)",
                 -2: "corrupt rANS Nx16 stream (final-state integrity "
                     "check failed)",
                 -3: "corrupt rANS Nx16 stream (malformed)"}


def rans_nx16_decode(payload, out_size: int,
                     into: Optional[bytearray] = None
                     ) -> Optional[np.ndarray]:
    """One whole rANS Nx16 stream (``out_size``: its decoded size) in one
    native call, the interpreter lock released, into a new array or the
    caller's ``into`` (of ``out_size`` bytes; the array returned is a view
    of it); None where the pass refuses the stream (the caller runs the
    Python decoder).  Raises RansError on a truncated or corrupt stream."""
    lib = load()
    assert lib is not None
    src = np.frombuffer(payload, dtype=np.uint8)
    out = (np.empty(out_size, dtype=np.uint8) if into is None
           else np.frombuffer(into, dtype=np.uint8))
    rc = lib.hbam_rans_nx16_decode(_ptr(src, ctypes.c_uint8), src.size,
                                   _ptr(out, ctypes.c_uint8), out_size)
    if rc == 0:
        return out
    if rc in _NX16_ERRORS:
        from hadoop_bam_tpu.formats.cram_codecs import RansError
        raise RansError(_NX16_ERRORS[rc])
    return None


def rans_nx16_decode_batch(payloads: list, outs: list) -> np.ndarray:
    """Many whole rANS Nx16 streams in ONE native call, the interpreter
    lock released: ``payloads[i]`` (bytes-like) decodes into ``outs[i]``
    (a writable buffer of its decoded size).  Returns each stream's code
    (0, or a negative ``hbam_rans_nx16_decode`` code: the caller re-runs
    those one at a time to word the error or take the Python decoder)."""
    lib = load()
    assert lib is not None
    srcs = [np.frombuffer(p, np.uint8) for p in payloads]
    dsts = [np.frombuffer(o, np.uint8) for o in outs]
    addr = lambda arrs: np.array(  # noqa: E731
        [a.ctypes.data for a in arrs], np.uint64)
    size = lambda arrs: np.array([a.size for a in arrs], np.int64)  # noqa
    src_a, src_n, dst_a, dst_n = addr(srcs), size(srcs), addr(dsts), \
        size(dsts)
    rc = np.zeros(len(srcs), np.int32)
    lib.hbam_rans_nx16_decode_batch(
        _ptr(src_a, ctypes.c_uint64), _ptr(src_n, ctypes.c_int64),
        _ptr(dst_a, ctypes.c_uint64), _ptr(dst_n, ctypes.c_int64),
        _ptr(rc, ctypes.c_int32), rc.size)
    return rc


def pack_reads(seq: np.ndarray, qual: np.ndarray, n: int, rl: int,
               ql: int, lut: np.ndarray, max_len: int, seq_out: np.ndarray,
               qual_out: np.ndarray) -> None:
    """n reads of one length into zeroed payload tile rows, one native
    pass (``hbam_pack_reads``), the interpreter lock released."""
    lib = load()
    assert lib is not None
    lib.hbam_pack_reads(
        _ptr(seq, ctypes.c_uint8), _ptr(qual, ctypes.c_uint8), n, rl, ql,
        _ptr(lut, ctypes.c_uint8), max_len, _ptr(seq_out, ctypes.c_uint8),
        seq_out.shape[1], _ptr(qual_out, ctypes.c_uint8),
        qual_out.shape[1])


def copy_runs(dst: np.ndarray, src: np.ndarray, dst_at: np.ndarray,
              src_at: np.ndarray, lens: np.ndarray) -> bool:
    """dst[dst_at[i]:+lens[i]] = src[src_at[i]:+lens[i]] for every run, one
    native call; False (nothing copied past the first bad run) when a run
    falls outside either buffer."""
    lib = load()
    assert lib is not None
    d_at = np.ascontiguousarray(dst_at, np.int64)
    s_at = np.ascontiguousarray(src_at, np.int64)
    ln = np.ascontiguousarray(lens, np.int64)
    src = np.ascontiguousarray(src, np.uint8)
    return lib.hbam_copy_runs(
        _ptr(dst, ctypes.c_uint8), dst.size, _ptr(src, ctypes.c_uint8),
        src.size, _ptr(d_at, ctypes.c_int64), _ptr(s_at, ctypes.c_int64),
        _ptr(ln, ctypes.c_int64), ln.size) == 0


# hbam_cram_slice_rebuild's payload streams, in its order (QS, BA, BS, then
# each byte array's lengths and values, then the D / N lengths) and what it
# returns other than -1 (arguments it cannot take)
CRAM_STREAMS = ("QS", "BA", "BS", "BB_len", "BB", "QQ_len", "QQ", "IN_len",
                "IN", "SC_len", "SC", "DL", "RS")
_CRAM_STREAM_DTYPES = tuple(np.int64 if k.endswith("_len") or k in ("DL", "RS")
                            else np.uint8 for k in CRAM_STREAMS)
CRAM_OK, CRAM_NEED_REF, CRAM_GEOMETRY, CRAM_DECLINED, CRAM_BAD_SUBST = range(5)


def cram_slice_rebuild(bf: np.ndarray, cf: np.ndarray, rl: np.ndarray,
                       pos: np.ndarray, fn: np.ndarray, mq: np.ndarray,
                       fc: np.ndarray, fp: np.ndarray, streams: list,
                       table: np.ndarray, have_source: bool,
                       ref: Optional[np.ndarray], ref_lo: int,
                       seq_out: np.ndarray, qual_out: Optional[np.ndarray]
                       ) -> Optional[tuple]:
    """A CRAM slice's bases and qualities rebuilt from its predecoded
    columns in one native call, the interpreter lock released
    (``hbam_cram_slice_rebuild``): the columns of ``formats/cram_columns.py::
    _rebuild_numpy``.  ``streams`` holds the ``CRAM_STREAMS`` in order, each
    an array (uint8; int64 for the lengths) or ``None`` where the series
    cannot be read at computed offsets.  Returns (code, info, seq_lens,
    qual_lens, mapq): ``CRAM_OK`` with ``seq_out[:info[2]]`` and
    ``qual_out[:info[3]]`` written; ``CRAM_NEED_REF`` with the reference
    window [info[0], info[1]) to fetch and call again with; or the check
    that failed.  ``None`` where the pass refuses its arguments."""
    lib = load()
    assert lib is not None
    i32 = [np.ascontiguousarray(a, np.int32) for a in (bf, cf, rl, fn, mq,
                                                        fp)]
    fc = np.ascontiguousarray(fc, np.uint8)
    pos = np.ascontiguousarray(pos, np.int64)
    n = int(i32[0].size)
    if any(a.size != n for a in (i32[1], i32[2], pos)) \
            or i32[3].size != i32[4].size or fc.size != i32[5].size \
            or len(streams) != len(CRAM_STREAMS):
        return None
    for out in (seq_out, qual_out):
        if out is not None and (out.dtype != np.uint8 or out.ndim != 1
                                or not out.flags.c_contiguous
                                or not out.flags.writeable):
            raise ValueError("cram_slice_rebuild writes contiguous u8 rows")
    if ref is not None:
        ref = np.ascontiguousarray(ref, np.uint8)
    held = [np.ascontiguousarray(a, dt) if a is not None else None
            for a, dt in zip(streams, _CRAM_STREAM_DTYPES)]
    addr = np.array([a.ctypes.data if a is not None else 0 for a in held],
                    np.uint64)
    size = np.array([a.size if a is not None else -1 for a in held],
                    np.int64)
    table = np.ascontiguousarray(table, np.uint8)
    if table.size != 20:
        return None
    seq_lens, qual_lens, mapq = (np.empty(n, np.int64) for _ in range(3))
    info = np.zeros(4, np.int64)
    u8 = ctypes.POINTER(ctypes.c_uint8)
    rc = int(lib.hbam_cram_slice_rebuild(
        n, _ptr(i32[0], ctypes.c_int32), _ptr(i32[1], ctypes.c_int32),
        _ptr(i32[2], ctypes.c_int32), _ptr(pos, ctypes.c_int64),
        _ptr(i32[3], ctypes.c_int32), _ptr(i32[4], ctypes.c_int32),
        int(i32[3].size), _ptr(fc, ctypes.c_uint8),
        _ptr(i32[5], ctypes.c_int32), int(fc.size),
        _ptr(addr, ctypes.c_uint64), _ptr(size, ctypes.c_int64),
        _ptr(table, ctypes.c_uint8), int(bool(have_source)),
        _ptr(ref, ctypes.c_uint8) if ref is not None else u8(),
        int(ref.size) if ref is not None else 0, int(ref_lo),
        _ptr(seq_out, ctypes.c_uint8), int(seq_out.size),
        _ptr(qual_out, ctypes.c_uint8) if qual_out is not None else u8(),
        int(qual_out.size) if qual_out is not None else 0,
        _ptr(seq_lens, ctypes.c_int64), _ptr(qual_lens, ctypes.c_int64),
        _ptr(mapq, ctypes.c_int64), _ptr(info, ctypes.c_int64)))
    if rc < 0:
        return None
    return rc, info, seq_lens, qual_lens, mapq


def itf8_decode_batch(buf: np.ndarray, count: int
                      ) -> "tuple[np.ndarray, int]":
    """Decode ``count`` ITF8 varints from ``buf`` in one native pass.

    Returns (values int32[count], bytes_consumed).  Raises ValueError on
    a truncated stream.  Callers must handle load() failure themselves
    (available() gate) — CRAM's predecode falls back to the per-record
    Python path."""
    lib = load()
    assert lib is not None
    out = np.empty(count, dtype=np.int32)
    buf = np.ascontiguousarray(buf)
    consumed = lib.hbam_itf8_decode_batch(
        _ptr(buf, ctypes.c_uint8), buf.size, count,
        _ptr(out, ctypes.c_int32))
    if consumed < 0:
        raise ValueError("ITF8 stream truncated")
    return out, int(consumed)


def bcf_gt_dosage(buf: np.ndarray, rows: np.ndarray, offs: np.ndarray,
                  typ: int, ploidy: int, n_sample: int,
                  dosage: np.ndarray) -> None:
    """Native GT -> ALT dosage of one layout group of a BCF span (the
    interpreter lock is released for the call): record ``i`` keeps
    ``ploidy x n_sample`` genotypes of BCF int type ``typ`` at
    ``buf[offs[i]]`` and gets its ``n_sample`` int8 dosages in row
    ``rows[i]`` of ``dosage``; columns past ``n_sample`` are left alone.
    The kernel re-checks every extent: a payload or row outside its
    buffer, or a layout it cannot take, raises ``BCFError`` and writes
    nothing."""
    from hadoop_bam_tpu.formats.bcf import BCFError
    lib = load()
    assert lib is not None
    if (buf.dtype != np.uint8 or dosage.dtype != np.int8 or dosage.ndim != 2
            or not buf.flags.c_contiguous or not dosage.flags.c_contiguous):
        raise ValueError("bcf_gt_dosage wants contiguous u8 bytes and an "
                         "i8 [records, samples] matrix")
    offs = np.ascontiguousarray(offs, np.int64)
    rows = np.ascontiguousarray(rows, np.int64)
    if offs.shape != rows.shape or offs.ndim != 1:
        raise ValueError("bcf_gt_dosage wants one offset a row")
    rc = int(lib.hbam_bcf_gt_dosage(
        _ptr(buf, ctypes.c_uint8), int(buf.size),
        _ptr(offs, ctypes.c_int64), _ptr(rows, ctypes.c_int64),
        int(offs.size), int(typ), int(ploidy), int(n_sample),
        _ptr(dosage, ctypes.c_int8), int(dosage.shape[0]),
        int(dosage.shape[1])))
    if rc < 0:
        raise BCFError(f"GT layout (type {typ}, ploidy {ploidy}, "
                       f"{n_sample} samples) cannot be gathered")
    if rc:
        raise BCFError(f"GT vector of record {rc - 1} of its layout group "
                       "overruns the span")


def bcf_chase(buf: np.ndarray, start: int, n0: int
              ) -> "tuple[np.ndarray, int, int]":
    """Native chase over the ``l_shared`` / ``l_indiv`` prefixes of BCF
    records (the interpreter lock is released for the call): from
    ``start``, every record that begins before ``n0`` and lies whole in
    ``buf``.  Returns (their starts, where the chase stopped, need):
    ``need`` is 0, or the length ``buf`` must have for the record at the
    stop to be framed — its 8-byte header, or its whole body; the caller
    grows the buffer and chases on from the stop."""
    lib = load()
    assert lib is not None
    if buf.dtype != np.uint8 or buf.ndim != 1 or not buf.flags.c_contiguous:
        raise ValueError("bcf_chase wants contiguous u8 bytes")
    # a record is 8 bytes at the very least (both blocks empty: the
    # decoder's to refuse); the pages the chase does not write stay
    # untouched
    cap = max(0, min(n0, int(buf.size)) - start) // 8 + 1
    starts = np.empty(cap, dtype=np.int64)
    out = np.zeros(2, dtype=np.int64)
    n = int(lib.hbam_bcf_chase(
        _ptr(buf, ctypes.c_uint8), int(buf.size), int(start), int(n0),
        _ptr(starts, ctypes.c_int64), cap, _ptr(out, ctypes.c_int64),
        _ptr(out[1:], ctypes.c_int64)))
    if n < 0:
        raise ValueError(f"bcf_chase refused its arguments ({n})")
    return starts[:n].copy(), int(out[0]), int(out[1])


# what hbam_bcf_span_columns returns below zero: the check that failed
# (the messages are formats/bcf_columns.py::_cursor_walk's)
_BCF_WALK_ERRORS = {
    -2: "BCF record start out of range",
    -3: "BCF shared block shorter than its fixed fields",
    -4: "truncated BCF record in columnar scan",
    -5: "typed-value descriptor overruns record",
    -6: "extended count overruns record",
    -7: "malformed extended-count scalar",
    -8: "negative typed-value count",
    -9: "unknown typed-value type",
    -10: "typed value overruns record",
    -11: "allele is not a char vector",
    -12: "allele overruns record",
    -13: "FILTER vector overruns record",
    -14: "malformed FORMAT key",
    -15: "FORMAT key overruns record",
    -16: "FORMAT data overruns record",
}


def bcf_span_columns(buf: np.ndarray, starts: np.ndarray, gt_key: int,
                     samples_pad: int, max_allele: int, max_fmt: int,
                     max_ploidy: int
                     ) -> "Optional[tuple[Dict[str, np.ndarray], int]]":
    """A framed BCF span -> (the columns of ``formats/bcf_columns.py::
    decode_bcf_columns``, its records with a GT vector) in one native
    call, the interpreter lock released: the typed-value walk of every
    record and its GT vector reduced to the int8 dosage row, each byte of
    ``dosage`` written once (so it is minted uninitialised).  ``None``
    where the kernel declines the span's geometry (more alleles / FORMAT
    fields / GT ploidy than the bounds given, more samples than
    ``samples_pad``): the record scanner's to read.  Corrupt input raises
    ``BCFError`` naming the check and the record; it is never decoded
    loosely."""
    from hadoop_bam_tpu.formats.bcf import BCFError
    lib = load()
    assert lib is not None
    if buf.dtype != np.uint8 or buf.ndim != 1 or not buf.flags.c_contiguous:
        raise ValueError("bcf_span_columns wants contiguous u8 bytes")
    starts = np.ascontiguousarray(starts, np.int64)
    n = int(starts.size)
    cols = {
        "chrom": np.empty(n, np.int32), "pos": np.empty(n, np.int32),
        "rlen": np.empty(n, np.int32), "qual": np.empty(n, np.float32),
        "n_allele": np.empty(n, np.int16), "n_fmt": np.empty(n, np.int16),
        "flags": np.empty(n, np.uint8),
        "dosage": np.empty((n, samples_pad), np.int8),
    }
    info = np.zeros(2, dtype=np.int64)
    rc = int(lib.hbam_bcf_span_columns(
        _ptr(buf, ctypes.c_uint8), int(buf.size),
        _ptr(starts, ctypes.c_int64), n, int(gt_key), int(max_allele),
        int(max_fmt), int(max_ploidy), _ptr(cols["chrom"], ctypes.c_int32),
        _ptr(cols["pos"], ctypes.c_int32), _ptr(cols["rlen"], ctypes.c_int32),
        _ptr(cols["qual"], ctypes.c_float),
        _ptr(cols["n_allele"], ctypes.c_int16),
        _ptr(cols["n_fmt"], ctypes.c_int16),
        _ptr(cols["flags"], ctypes.c_uint8),
        _ptr(cols["dosage"], ctypes.c_int8), int(samples_pad),
        _ptr(info, ctypes.c_int64)))
    if rc == 1:
        return None
    if rc in _BCF_WALK_ERRORS:
        raise BCFError(f"{_BCF_WALK_ERRORS[rc]} (record {int(info[0])} of "
                       "the span)")
    if rc:
        raise ValueError(f"bcf_span_columns refused its arguments ({rc})")
    return cols, int(info[1])


def bcf_guess(data, first_len: int, n_contigs: int, min_chain: int,
              partial: bool) -> "tuple[int, bool]":
    """The split guesser's candidate test in one native scan with an early
    exit (the interpreter lock is released for the call): the smallest
    offset in ``data[:first_len]`` that passes the plausibility sweep and
    starts a chain of ``min_chain`` valid records — ``split/bcf_guesser.py
    ::_plausible_offsets`` + ``_chain_ok``, offset for offset; -1 where
    there is none.  The flag beside it says the answer leaned on where
    ``data`` ends: without it, more bytes behind ``data`` (and another
    ``partial``) give the same answer."""
    lib = load()
    assert lib is not None
    a = _src_u8(data)
    if a.dtype != np.uint8 or a.ndim != 1 or not a.flags.c_contiguous:
        raise ValueError("bcf_guess wants contiguous u8 bytes")
    edge = ctypes.c_int32(0)
    u = int(lib.hbam_bcf_guess(
        _ptr(a, ctypes.c_uint8), int(a.size), int(first_len),
        int(n_contigs), int(min_chain), int(bool(partial)),
        ctypes.byref(edge)))
    return u, bool(edge.value)


def fastq_tokenize(text, nibble: np.ndarray, seq_stride: int,
                   qual_stride: int, max_len: int, qual_offset: int
                   ) -> "Optional[tuple[np.ndarray, np.ndarray, np.ndarray]]":
    """A FASTQ chunk's ``text`` -> its (seq [n, seq_stride] u8 of
    ``nibble[base]`` codes two a byte, qual [n, qual_stride] u8 re-based
    by ``qual_offset``, lengths [n] i32 = min(read length, max_len)) in
    one native pass over the bytes, the interpreter lock released: the
    tiles of ``api/read_datasets.py::fastq_text_to_payload_tiles``, byte
    for byte.  The pass writes every byte of every row, so the tiles are
    minted uninitialised and nothing else is allocated.  ``None`` where it
    refuses the text — lines that are not 4n, a record without its ``@``
    or ``+``, SEQ and QUAL of unequal length, under ``qual_offset != 33``
    a quality outside Phred 0..93 anywhere in a field — or the arguments
    (the ranges are the pass's to check): the caller's NumPy twin then
    raises what it always raised."""
    lib = load()
    assert lib is not None
    buf = _src_u8(text)
    if nibble.dtype != np.uint8 or nibble.size != 256 \
            or not nibble.flags.c_contiguous or buf.dtype != np.uint8 \
            or buf.ndim != 1 or not buf.flags.c_contiguous:
        raise ValueError("fastq_tokenize wants contiguous u8 text and a "
                         "256-entry u8 code table")
    p_text = _ptr(buf, ctypes.c_uint8)
    lines = int(lib.hbam_fastq_count_lines(p_text, int(buf.size)))
    if lines % 4:
        return None
    n = lines // 4
    seq = np.empty((n, seq_stride), dtype=np.uint8)
    qual = np.empty((n, qual_stride), dtype=np.uint8)
    lengths = np.empty(n, dtype=np.int32)
    rc = int(lib.hbam_fastq_tokenize(
        p_text, int(buf.size), _ptr(nibble, ctypes.c_uint8), int(max_len),
        int(qual_offset), int(qual_offset != 33), _ptr(seq, ctypes.c_uint8),
        int(seq_stride), _ptr(qual, ctypes.c_uint8), int(qual_stride),
        _ptr(lengths, ctypes.c_int32), n))
    return (seq, qual, lengths) if rc == 0 else None


def vcf_tokenize(text, n_sample: int, samples_pad: int) -> tuple:
    """A VCF text span's record lines in one native pass over the bytes,
    the interpreter lock released: (bounds [n, 11] i64 — a line's start,
    its first nine tabs (its end where it has fewer), its end; ntab [n]
    i32, nine at most; bulk [n] bool; dosage [n, samples_pad] i8; keyed;
    nocall).  Where ``bulk`` is set the dosage row is final: -1
    throughout for a line with no ``GT`` FORMAT; the ALT dosages of a line
    whose FORMAT is exactly ``GT`` and whose ``n_sample`` cells are all
    ``digit sep digit``; and of a keyed line — FORMAT ``GT:`` and more
    keys, as GATK writes ``GT:AD:DP:GQ:PL`` — whose ``n_sample`` cells
    each lead with ``digit sep digit``, a half-missing ``./1`` or a
    no-call ``./.`` / ``.`` (-1) before the cell's first ':'.  ``keyed``
    counts those keyed lines, ``nocall`` the no-call cells in them.  The
    other rows are not written: a multi-digit allele, a haploid call, a
    line with too few or too many cells or a FORMAT not led by ``GT`` goes
    to the caller's scalar parse.  The first four arrays are what
    ``parallel/variant_pipeline.py::_vcf_tokenize_numpy`` returns, array
    for array, but for keyed lines, which the twin leaves to the scalar
    parse (``bulk`` unset)."""
    lib = load()
    assert lib is not None
    buf = _src_u8(text)
    if buf.dtype != np.uint8 or buf.ndim != 1 or not buf.flags.c_contiguous:
        raise ValueError("vcf_tokenize wants contiguous u8 text")
    p_text = _ptr(buf, ctypes.c_uint8)
    cap = int(lib.hbam_vcf_tokenize(p_text, int(buf.size), int(n_sample),
                                    None, None, None, None,
                                    int(samples_pad), 0, None))
    if cap < 0:
        raise ValueError(f"vcf_tokenize refused its arguments ({cap})")
    bounds = np.empty((cap, 11), dtype=np.int64)
    ntab = np.empty(cap, dtype=np.int32)
    bulk = np.empty(cap, dtype=np.uint8)
    dosage = np.empty((cap, samples_pad), dtype=np.int8)
    counts = np.zeros(2, dtype=np.int64)
    n = int(lib.hbam_vcf_tokenize(
        p_text, int(buf.size), int(n_sample), _ptr(bounds, ctypes.c_int64),
        _ptr(ntab, ctypes.c_int32), _ptr(bulk, ctypes.c_uint8),
        _ptr(dosage, ctypes.c_int8), int(samples_pad), cap,
        _ptr(counts, ctypes.c_int64)))
    if n != cap:
        raise ValueError(f"vcf_tokenize counted {cap} records and wrote {n}")
    return bounds, ntab, bulk.view(bool), dosage, int(counts[0]), \
        int(counts[1])


class ContigTable(NamedTuple):
    """A header's contigs as ``hbam_vcf_span_columns`` looks CHROM up:
    the names' bytes end to end, where each starts (and the end), and the
    hash slots of ``hbam_contig_table``.  Built once a scan."""
    names: np.ndarray
    offsets: np.ndarray
    slots: np.ndarray


def contig_table(contigs: Sequence[str]) -> ContigTable:
    """The ``{name.encode(): index}`` map of ``contigs`` as a
    ``ContigTable`` (one native call): the later of two equal names wins,
    as it does in the dict."""
    lib = load()
    assert lib is not None
    enc = [c.encode() for c in contigs]
    offsets = np.zeros(len(enc) + 1, np.int64)
    np.cumsum([len(e) for e in enc], out=offsets[1:])
    names = np.frombuffer(b"".join(enc) + b"\0", np.uint8)
    slots = np.empty(1 << (2 * len(enc)).bit_length(), np.int32)
    rc = int(lib.hbam_contig_table(
        _ptr(names, ctypes.c_uint8), _ptr(offsets, ctypes.c_int64),
        len(enc), _ptr(slots, ctypes.c_int32), int(slots.size)))
    if rc:
        raise ValueError(f"contig_table refused its arguments ({rc})")
    return ContigTable(names, offsets, slots)


def vcf_text_span_read(raw, at: int, want: int, file_end: int,
                       out: Optional[np.ndarray]
                       ) -> "tuple[int, tuple[int, ...]]":
    """``hbam_vcf_text_span_read`` on the compressed bytes ``raw``, the
    interpreter lock released: (its return code, its six-entry ``info``
    as ints — total, base_len, lo, hi, records, resume).  ``out`` is the
    buffer the span's text is inflated into, or None to ask the size
    first."""
    lib = load()
    assert lib is not None
    buf = _src_u8(raw)
    info = np.zeros(6, dtype=np.int64)
    rc = int(lib.hbam_vcf_text_span_read(
        _ptr(buf, ctypes.c_uint8), int(buf.size), int(at), int(want),
        int(file_end), None if out is None else _ptr(out, ctypes.c_uint8),
        0 if out is None else int(out.size), _ptr(info, ctypes.c_int64)))
    return rc, tuple(info.tolist())


def vcf_span_columns(text, records: int, n_sample: int, samples_pad: int,
                     contigs: ContigTable) -> tuple:
    """A VCF text span's stats columns in one native pass, the
    interpreter lock released: (cols — chrom [n] i32, pos [n] i32, flags
    [n] u8, dosage [n, samples_pad] i8 —, refused [k, 3] i64 of (row,
    line start, line end), keyed, nocall).  ``records`` is the count of
    record lines the read found, or negative to have one more native
    call count them.  A refused row's columns hold nothing: the caller's
    scalar parse reads its line.  ``keyed`` and ``nocall`` count the keyed
    lines not refused and their no-call cells."""
    lib = load()
    assert lib is not None
    buf = _src_u8(text)
    if buf.dtype != np.uint8 or buf.ndim != 1 or not buf.flags.c_contiguous:
        raise ValueError("vcf_span_columns wants contiguous u8 text")
    p_text = _ptr(buf, ctypes.c_uint8)
    table = (_ptr(contigs.names, ctypes.c_uint8),
             _ptr(contigs.offsets, ctypes.c_int64),
             _ptr(contigs.slots, ctypes.c_int32), int(contigs.slots.size))
    if records < 0:
        records = int(lib.hbam_vcf_span_columns(
            p_text, int(buf.size), int(n_sample), *table, None, None, None,
            None, int(samples_pad), None, 0, None))
        if records < 0:
            raise ValueError(
                f"vcf_span_columns refused its arguments ({records})")
    cols = {"chrom": np.empty(records, np.int32),
            "pos": np.empty(records, np.int32),
            "flags": np.empty(records, np.uint8),
            "dosage": np.empty((records, samples_pad), np.int8)}
    refused = np.empty((records, 3), np.int64)
    counts = np.zeros(3, dtype=np.int64)
    n = int(lib.hbam_vcf_span_columns(
        p_text, int(buf.size), int(n_sample), *table,
        _ptr(cols["chrom"], ctypes.c_int32),
        _ptr(cols["pos"], ctypes.c_int32),
        _ptr(cols["flags"], ctypes.c_uint8),
        _ptr(cols["dosage"], ctypes.c_int8), int(samples_pad),
        _ptr(refused, ctypes.c_int64), records,
        _ptr(counts, ctypes.c_int64)))
    if n != records:
        raise ValueError(f"vcf_span_columns was told {records} records "
                         f"and wrote {n}")
    return cols, refused[:int(counts[2])], int(counts[0]), int(counts[1])


def grm_finish(acc: np.ndarray, r: np.ndarray, c: float, n_grm: int,
               n_samples: int, out: np.ndarray) -> np.ndarray:
    """The GWAS job's A [S, S] float64 written into ``out`` in one native
    pass, the interpreter lock released (``hbam_grm_finish``): bitwise
    ``cohort/gwas.py::_grm_from_accumulators_numpy`` of the float32 ``acc``
    [Sp, Sp] (upper triangle read) and ``r`` [Sp], the scalar ``c`` and the
    site count ``n_grm``.  Returns ``out``."""
    lib = load()
    assert lib is not None
    s = int(n_samples)
    acc = np.ascontiguousarray(acc, np.float32)
    r = np.ascontiguousarray(r, np.float32)
    sp = acc.shape[1] if acc.ndim == 2 else -1
    if acc.shape != (sp, sp) or r.shape[0] < s \
            or out.shape != (s, s) or out.dtype != np.float64 \
            or not out.flags.c_contiguous or not out.flags.writeable:
        raise ValueError("grm_finish wants acc [Sp, Sp] f32, r [Sp] f32 and "
                         "a writable contiguous out [S, S] f64, S <= Sp")
    if lib.hbam_grm_finish(_ptr(acc, ctypes.c_float), sp,
                           _ptr(r, ctypes.c_float), float(c), int(n_grm), s,
                           _ptr(out, ctypes.c_double)) != 0:
        raise ValueError(f"grm_finish refused S = {s} for Sp = {sp}")
    return out


# A DEFLATE window, and the symbols ``deflate_decode_symbols`` writes where
# the window is unknown: 256 + k for its byte k (hbam_native.cpp kGzWindow).
DEFLATE_WINDOW = 32768
DEFLATE_SLACK = 16
_UNKNOWN_WINDOW = np.arange(256, 256 + DEFLATE_WINDOW, dtype=np.uint16)


def _src_u8(src) -> np.ndarray:
    return src if isinstance(src, np.ndarray) \
        else np.frombuffer(src, dtype=np.uint8)


def deflate_find_block(src, from_bit: int, until_bit: int) -> int:
    """The first bit offset in ``[from_bit, until_bit)`` of ``src`` at
    which a non-final dynamic-Huffman DEFLATE block header parses whole
    to two prefix codes inflate would accept (the interpreter lock is
    released for the scan); -1 where there is none.  Stored and fixed
    blocks are not found."""
    lib = load()
    assert lib is not None
    a = _src_u8(src)
    return int(lib.hbam_deflate_find_block(
        _ptr(a, ctypes.c_uint8), int(a.size), int(from_bit),
        int(until_bit)))


def deflate_symbol_buffer(cap: int) -> np.ndarray:
    """Room for ``cap`` symbols of ``deflate_decode_symbols`` behind the
    window it starts from (and the decoder's slack): allocated, not
    touched, so what stays unused costs no memory."""
    return np.empty(DEFLATE_WINDOW + int(cap) + DEFLATE_SLACK, np.uint16)


def deflate_decode_symbols(src, start_bit: int, stop_bit: int,
                           soft_cap: int, window: Optional[bytes],
                           buf: np.ndarray) -> "tuple[int, int, np.ndarray]":
    """Decode DEFLATE blocks of ``src`` from ``start_bit`` (a block's
    first bit) into 16-bit symbols in ``buf`` (``deflate_symbol_buffer``),
    the interpreter lock released.  ``window`` is the text before the
    block, at most ``DEFLATE_WINDOW`` bytes of it (a match may not reach
    further back than it has), or ``None``: unknown — a symbol is then a
    byte, or 256 + k where a match reaches byte k of the unknown window.
    It stops at the first block boundary at or past ``stop_bit`` or with
    ``soft_cap`` symbols written, at the final block's end, or with the
    last whole block where ``src`` or ``buf`` ends inside one.  Returns
    (status, end_bit, symbols): status 1 at the final block's end, 0 at
    another boundary, -1 for data that is no DEFLATE, -2 / -3 where not
    one block fits the room / the input; ``symbols`` is a view of
    ``buf``."""
    lib = load()
    assert lib is not None
    a = _src_u8(src)
    if buf.dtype != np.uint16 or not buf.flags.c_contiguous \
            or buf.size < DEFLATE_WINDOW + DEFLATE_SLACK:
        raise ValueError("deflate_decode_symbols wants a buffer of "
                         "deflate_symbol_buffer")
    if window is None:
        buf[:DEFLATE_WINDOW] = _UNKNOWN_WINDOW
        known = DEFLATE_WINDOW
    else:
        tail = np.frombuffer(window, np.uint8)[-DEFLATE_WINDOW:]
        known = int(tail.size)
        buf[DEFLATE_WINDOW - known:DEFLATE_WINDOW] = tail
    out = np.zeros(2, dtype=np.int64)
    rc = int(lib.hbam_deflate_decode_symbols(
        _ptr(a, ctypes.c_uint8), int(a.size), int(start_bit), int(stop_bit),
        int(soft_cap), known, _ptr(buf, ctypes.c_uint16),
        int(buf.size) - DEFLATE_WINDOW - DEFLATE_SLACK,
        _ptr(out, ctypes.c_int64), _ptr(out[1:], ctypes.c_int64)))
    return rc, int(out[0]), buf[DEFLATE_WINDOW:DEFLATE_WINDOW + int(out[1])]


def deflate_resolve(symbols: np.ndarray, window: Optional[bytes],
                    out: Optional[np.ndarray] = None, eol: int = 0x0A
                    ) -> "tuple[np.ndarray, int, int]":
    """Symbols -> (bytes, their CRC32, how many of them are ``eol``), the
    interpreter lock released: a symbol under 256 is its byte, 256 + k
    is byte k of the ``DEFLATE_WINDOW`` bytes before the symbols' first
    — ``window``, its last bytes where it is shorter (what lies before
    them reads 0), or ``None`` where no symbol is a mark.  The bytes are
    written into ``out`` where given (a view of it is returned)."""
    lib = load()
    assert lib is not None
    if symbols.dtype != np.uint16 or symbols.ndim != 1 \
            or not symbols.flags.c_contiguous:
        raise ValueError("deflate_resolve wants contiguous u16 symbols")
    win = None
    if window:
        win = np.frombuffer(window[-DEFLATE_WINDOW:].rjust(
            DEFLATE_WINDOW, b"\0"), np.uint8)
    if out is None:
        out = np.empty(symbols.size, dtype=np.uint8)
    elif out.dtype != np.uint8 or not out.flags.c_contiguous \
            or out.size < symbols.size:
        raise ValueError("deflate_resolve wants room for a byte a symbol")
    eols = np.zeros(1, dtype=np.int64)
    crc = int(lib.hbam_deflate_resolve(
        _ptr(symbols, ctypes.c_uint16), int(symbols.size),
        None if win is None else _ptr(win, ctypes.c_uint8),
        _ptr(out, ctypes.c_uint8), int(eol), _ptr(eols, ctypes.c_int64)))
    return out[:symbols.size], crc, int(eols[0])


def crc32_combine(crc_a: int, crc_b: int, len_b: int) -> int:
    """CRC32 of A ++ B from CRC32(A), CRC32(B) and len(B)."""
    lib = load()
    assert lib is not None
    return int(lib.hbam_crc32_combine(int(crc_a), int(crc_b), int(len_b)))


def fused_available() -> bool:
    """True when the native library loaded and exposes the fused
    span-decode entry points."""
    lib = load()
    return lib is not None and hasattr(lib, "hbam_fused_start")


# fused pack modes (must mirror HbamFusedJob::mode in hbam_native.cpp)
FUSED_OFFSETS, FUSED_ROWS, FUSED_PAYLOAD = 0, 1, 2


class FusedJob:
    """Handle over one running ``hbam_fused_*`` span decode.

    Thin lifecycle wrapper: pins every borrowed array for the job's
    lifetime, exposes the blocking chunk poll, and guarantees the native
    workers are joined exactly once (``finish``/``close``/GC).  Error
    mapping to the repo taxonomy lives in ``ops/inflate.py`` — this layer
    only reports raw (rc, err_index) pairs.  Single consumer; not
    thread-safe."""

    def __init__(self, src: np.ndarray, cdata_off: np.ndarray,
                 cdata_len: np.ndarray, isize: np.ndarray,
                 expect_crc: Optional[np.ndarray], dst: np.ndarray,
                 ubase: np.ndarray, start: int, stop: int, mode: int,
                 sel_off: Optional[np.ndarray], sel_len: Optional[np.ndarray],
                 row_stride: int, out_rows: Optional[np.ndarray],
                 out_seq: Optional[np.ndarray],
                 out_qual: Optional[np.ndarray], max_len: int,
                 seq_stride: int, qual_stride: int, out_off: np.ndarray,
                 chunk_blocks: int, n_threads: int = 0):
        lib = load()
        assert lib is not None and hasattr(lib, "hbam_fused_start")
        self._lib = lib
        n_blocks = len(cdata_off)
        if n_threads <= 0:
            n_threads = min(
                (n_blocks + chunk_blocks - 1) // max(1, chunk_blocks),
                os.cpu_count() or 1)
        # pin every borrowed buffer until finish()
        self._keep = (src, cdata_off, cdata_len, isize, expect_crc, dst,
                      ubase, sel_off, sel_len, out_rows, out_seq, out_qual,
                      out_off)
        self._h = lib.hbam_fused_start(
            _ptr(src, ctypes.c_uint8), _ptr(cdata_off, ctypes.c_int64),
            _ptr(cdata_len, ctypes.c_int32), _ptr(isize, ctypes.c_int32),
            None if expect_crc is None else _ptr(expect_crc,
                                                ctypes.c_uint32),
            n_blocks, _ptr(dst, ctypes.c_uint8), _ptr(ubase, ctypes.c_int64),
            int(dst.size), int(start), int(stop), int(mode),
            None if sel_off is None else _ptr(sel_off, ctypes.c_int32),
            None if sel_len is None else _ptr(sel_len, ctypes.c_int32),
            0 if sel_off is None else len(sel_off), int(row_stride),
            None if out_rows is None else _ptr(out_rows, ctypes.c_uint8),
            None if out_seq is None else _ptr(out_seq, ctypes.c_uint8),
            None if out_qual is None else _ptr(out_qual, ctypes.c_uint8),
            int(max_len), int(seq_stride), int(qual_stride),
            _ptr(out_off, ctypes.c_int64), int(out_off.size),
            int(chunk_blocks), int(n_threads))
        if not self._h:
            raise ValueError("fused decode rejected its arguments")
        self.rc = 0
        self.tail = int(start)
        self.n_rows = 0
        self.err_index = -1
        # core-nanoseconds the workers spent in inflate + walk + pack;
        # known once they are joined (``finish``)
        self.busy_ns = 0

    def next_chunk(self) -> "Optional[tuple[int, int]]":
        """Block until the next walked row range lands; (row_lo, row_hi),
        or None when the decode is complete.  On error, joins the workers
        and returns None with ``self.rc < 0`` set."""
        if self._h is None:
            return None
        lo = np.zeros(1, dtype=np.int64)
        hi = np.zeros(1, dtype=np.int64)
        rc = self._lib.hbam_fused_next(
            self._h, _ptr(lo, ctypes.c_int64), _ptr(hi, ctypes.c_int64))
        if rc == 1:
            return int(lo[0]), int(hi[0])
        if rc < 0:
            self.finish()
        return None

    def finish(self) -> int:
        """Join + free; idempotent.  Returns the final rc (0 or -kind) and
        populates ``tail``/``n_rows``/``err_index``/``busy_ns``."""
        if self._h is None:
            return self.rc
        out = np.zeros(4, dtype=np.int64)   # tail, n_rows, err_index, busy
        rc = self._lib.hbam_fused_finish(
            self._h, *(_ptr(out[i:], ctypes.c_int64) for i in range(4)))
        self._h = None
        self.rc = int(rc)
        self.tail, self.n_rows, self.err_index, self.busy_ns = \
            (int(v) for v in out)
        return self.rc

    close = finish

    def __del__(self):  # abandoned mid-stream: never leak native threads
        try:
            self.finish()
        except Exception:  # noqa: BLE001 — interpreter teardown
            pass


def available() -> bool:
    return load() is not None
