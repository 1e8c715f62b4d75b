"""Seekable byte sources — positioned reads over files and buffers.

Rebuild of the reference's seekable-stream adapters
(hb/util/WrapSeekable.java: htsjdk SeekableStream over Hadoop
FSDataInputStream; hb/util/SeekableArrayStream.java: over byte[]): every layer
above works against one tiny interface, ``pread(offset, size) -> bytes`` plus
``size``, so local files, in-memory buffers, and (later) object-store
byte-range fetchers are interchangeable.  Positioned reads (not stateful
seeks) are the right primitive for the TPU pipeline: span fetches are
stateless and trivially parallel across threads/hosts.
"""
from __future__ import annotations

import io
import os
import threading
from typing import Callable, Optional, Union

# Resilience hook (utils/resilient.py): when chaos injection or a retry
# wrapper is registered for some path, resilient installs a wrapper here and
# every path-opened source flows through it.  None = zero-overhead fast path.
_SOURCE_WRAPPER: Optional[Callable[["ByteSource"], "ByteSource"]] = None


class ByteSource:
    """Interface: stateless positioned reads."""

    size: int

    def pread(self, offset: int, size: int) -> bytes:
        raise NotImplementedError

    # ``pread_into(offset, buf) -> int``: fill a caller's writable buffer
    # in place and return the bytes read (short only at the end of the
    # source).  None where a source cannot: its callers use ``pread``.
    pread_into = None

    def close(self) -> None:
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


class FileByteSource(ByteSource):
    """Positioned reads over a local file via os.pread (thread-safe, no
    seek state — many fetcher threads can share one fd)."""

    def __init__(self, path: Union[str, os.PathLike]):
        self.path = os.fspath(path)
        self._fd = -1  # set first so __del__ is safe if os.open raises
        self._fd = os.open(self.path, os.O_RDONLY)
        self.size = os.fstat(self._fd).st_size

    def pread(self, offset: int, size: int) -> bytes:
        if offset >= self.size or size <= 0:
            return b""
        try:
            return os.pread(self._fd, size, offset)
        except OSError as e:
            # classify at the policy boundary: a failed positioned read is
            # an environment fault (EIO on network mounts, stale handles),
            # not data corruption — retryable upstream
            from hadoop_bam_tpu.utils.errors import TransientIOError
            raise TransientIOError(
                f"pread({offset}, {size}) failed on {self.path}: {e}"
            ) from e

    def pread_into(self, offset: int, buf) -> int:
        mv = memoryview(buf)
        got = 0
        try:
            while got < len(mv) and offset + got < self.size:
                n = os.preadv(self._fd, [mv[got:]], offset + got)
                if n <= 0:
                    break
                got += n
        except OSError as e:
            from hadoop_bam_tpu.utils.errors import TransientIOError
            raise TransientIOError(
                f"preadv({offset}, {len(mv)}) failed on {self.path}: {e}"
            ) from e
        return got

    def close(self) -> None:
        if self._fd >= 0:
            os.close(self._fd)
            self._fd = -1

    def __del__(self):
        try:
            self.close()
        except OSError:
            pass


class BytesByteSource(ByteSource):
    """Over an in-memory buffer (hb/util/SeekableArrayStream.java analog);
    guessers re-scan fetched windows through this."""

    def __init__(self, data: bytes):
        self._data = data
        self.size = len(data)

    def pread(self, offset: int, size: int) -> bytes:
        return self._data[offset:offset + size]


def as_byte_source(obj) -> ByteSource:
    if isinstance(obj, ByteSource):
        return obj
    if isinstance(obj, (bytes, bytearray, memoryview)):
        return BytesByteSource(bytes(obj))
    if isinstance(obj, (str, os.PathLike)):
        src = FileByteSource(obj)
        return _SOURCE_WRAPPER(src) if _SOURCE_WRAPPER is not None else src
    raise TypeError(f"cannot make a ByteSource from {type(obj)!r}")


class scoped_byte_source:
    """``with scoped_byte_source(obj) as src``: closes ``src`` on exit only
    when this call created it (an already-open ByteSource passes through
    untouched — the caller owns its lifetime)."""

    def __init__(self, obj):
        self._owned = not isinstance(obj, ByteSource)
        self.src = as_byte_source(obj)

    def __enter__(self) -> ByteSource:
        return self.src

    def __exit__(self, *exc):
        if self._owned:
            self.src.close()
