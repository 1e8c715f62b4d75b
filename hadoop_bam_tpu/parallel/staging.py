"""Staging rings + the shared host->device feed pipeline.

Before this module, every driver family (flagstat tiles, BAM payload
stats, FASTQ/QSEQ, CRAM, variant tensors) hand-rolled the same
emit/dispatch loop — and each emit allocated a fresh
``np.zeros((n_dev, cap, w))`` group tile, memset it, copied every
device's rows into it, then synchronously ``device_put`` + stepped it.
That loop is why the pipeline scaled *inversely* with device count:
host group-assembly work (memsets + copies, all O(n_dev)) grew with
every added device while the device waited, serialized behind it.

Two mechanisms replace it:

- **``StagingRing``** — a small ring of preallocated, reusable
  ``[n_dev, cap, w]`` group buffers.  Emit writes each device's rows in
  place; a partial tile zeroes only its own tail (rows
  ``[count, bucket)``), so a full group pays ZERO allocation and ZERO
  memset.  Slots are leased/released: a slot is handed back to the ring
  only after its dispatch completed, and the device arrays a dispatch
  creates ride the slot as IN-FLIGHT handles — the packer waits on
  them after re-leasing, before writing — so an asynchronous
  host->device transfer can never still be reading a buffer the packer
  overwrites (``jax.device_put`` may return before the DMA completes
  on real TPUs), and the dispatch thread never blocks for it.

- **``FeedPipeline``** — a packer thread assembles group *k+1* into one
  ring slot while the caller's thread dispatches group *k* from another
  (depth-2 double buffering).  All JAX calls stay on the caller's
  thread — transfers keep issuing sequentially from one thread — while
  the packing memcpys overlap them.  ``feed()`` drives stats drivers to
  completion;
  ``stream()`` powers the generator-shaped ``tensor_batches`` APIs.

Wall-clock accounting rides along: ``pipeline.feed_wall`` (whole feed),
``pipeline.dispatch_wall`` (host wall inside dispatch calls) and the
``pipeline.dispatch_bytes`` counter — the thread-summed ``METRICS.timer``
values cannot show overlap, the wall spans can.  Each thread's time is
partitioned by spans (all carry a profiler annotation while a recorder
is active): the dispatch thread's by ``feed.wait_group`` +
``pipeline.dispatch_wall``; the packer's by ``feed.wait_rows`` +
``feed.wait_slot`` + ``staging.transfer_wait`` + ``staging.pack``.
``feed.first_dispatch_wait`` is the one wall that starts outside the
feed: from the ``plan.execute`` around it to its first dispatch.
"""
from __future__ import annotations

import collections
import contextlib
import contextvars
import dataclasses
import queue
import threading
import time
from typing import Callable, Iterable, Iterator, List, Optional, Sequence, Tuple

import numpy as np

from hadoop_bam_tpu.config import DEFAULT_CONFIG, HBamConfig
from hadoop_bam_tpu.obs.context import take_execute_start
from hadoop_bam_tpu.obs.trace import active_recorder
from hadoop_bam_tpu.utils.metrics import METRICS


def bucket_cap(count: int, cap: int, block_n: int = 256) -> int:
    """Rows to actually dispatch for a partial tile of ``count`` records.

    Full tiles ship at ``cap``; the FINAL partial tile shrinks to the
    smallest bucket (~cap/16, ~cap/4, cap) that holds it, so a small
    file pays a kernel over ~its own rows instead of the full padded
    tile (the small-input dispatch floor: a 10k-read file inside a
    64k-row tile spent 6x its data in padding).  Buckets are rounded up
    to the Pallas record-block height ``block_n`` (the kernel asserts
    divisibility), and a fixed 3-step ladder bounds jit retraces at two
    extra shapes per step function."""
    for b in (cap // 16, cap // 4):
        b = -(-b // block_n) * block_n       # round up to a block multiple
        if b >= block_n and count <= b < cap:
            return b
    return cap


@dataclasses.dataclass(frozen=True)
class TileSpec:
    """Per-record layout of one array in a tile tuple: trailing shape
    (``()`` for 1-D series), dtype, and the padding value rows beyond a
    device's count are filled with (0 for byte tiles, -1 for dosage,
    NaN for qual columns)."""
    shape: Tuple[int, ...]
    dtype: object
    pad: object = 0

    @classmethod
    def normalize(cls, spec) -> "TileSpec":
        """Accept the legacy ``_iter_tile_tuples`` spec forms too: an int
        width (uint8 [cap, w]) or a (width_or_None, dtype) pair."""
        if isinstance(spec, TileSpec):
            return spec
        if isinstance(spec, (int, np.integer)):
            return cls((int(spec),), np.uint8, 0)
        w, dt = spec
        return cls(() if w is None else (int(w),), dt, 0)


class RingSlot:
    """One leased group buffer set: ``arrays[j]`` is
    [n_dev, cap, *specs[j].shape], ``counts`` is [n_dev] int32.

    ``in_flight`` carries the device arrays the last dispatch created
    from these buffers (any pytree); the packer blocks on them after
    re-leasing the slot and BEFORE writing — so an asynchronous
    host->device transfer can never still be reading a buffer the
    packer overwrites, without the dispatch thread ever waiting.

    ``pin()`` transfers the slot's buffers OUT of the ring permanently:
    a pinned slot's ``release`` parks it (never requeues it) and the
    ring MINTS a fresh replacement slot, so capacity is unchanged while
    the pinned buffers can never be re-leased and overwritten.  This is
    load-bearing on the CPU backend, where ``jax.device_put`` may
    ZERO-COPY alias a numpy buffer — a device array the serve tile
    cache retains would otherwise silently mutate when the ring reuses
    the slot (caught by the test_serve churn proof).  ``unpin()``
    relinquishes a parked slot (its buffers then live exactly as long
    as the device arrays referencing them) or, if called before
    release, cancels the pin so the slot recirculates normally."""
    __slots__ = ("arrays", "counts", "index", "in_flight", "pinned",
                 "parked", "_ring")

    def __init__(self, arrays: List[np.ndarray], counts: np.ndarray,
                 index: int, ring: "StagingRing"):
        self.arrays = arrays
        self.counts = counts
        self.index = index
        self.in_flight = None
        self.pinned = False
        self.parked = False
        self._ring = ring

    def pin(self) -> None:
        self.pinned = True

    def unpin(self) -> None:
        self._ring.unpin(self)

    def release(self) -> None:
        self._ring.release(self)


def _block_in_flight(handles) -> None:
    """Wait for every transfer handle in ``handles`` (a pytree of jax
    arrays, or anything exposing ``block_until_ready``)."""
    import jax

    for leaf in jax.tree_util.tree_leaves(handles):
        if hasattr(leaf, "block_until_ready"):
            leaf.block_until_ready()


class _Cancelled(Exception):
    """Internal: the other side of the pipeline stopped; unwind quietly."""


class StagingRing:
    """A ring of preallocated group buffers, leased and released.

    ``lease`` blocks until a slot is free (with a cancellation event so
    an aborted run can't deadlock the packer); ``release`` hands the
    slot back for reuse.  Buffers are allocated ONCE here — the lint
    rule PF501 exists to keep fresh per-emit group allocations from
    creeping back into the feed paths."""

    def __init__(self, n_dev: int, cap: int, specs: Sequence[TileSpec],
                 slots: int):
        self.n_dev, self.cap = int(n_dev), int(cap)
        self.specs = [TileSpec.normalize(s) for s in specs]
        self.n_slots = max(2, int(slots))
        self._free: "queue.Queue[RingSlot]" = queue.Queue()
        self._next_index = 0
        self.slots: List[RingSlot] = []
        for _ in range(self.n_slots):
            slot = self._fresh_slot()
            self.slots.append(slot)
            self._free.put(slot)

    def _fresh_slot(self) -> RingSlot:
        arrays = [
            np.full((self.n_dev, self.cap) + s.shape, s.pad, dtype=s.dtype)
            for s in self.specs
        ]
        slot = RingSlot(arrays, np.zeros(self.n_dev, np.int32),
                        self._next_index, self)
        self._next_index += 1
        return slot

    def lease(self, cancel: threading.Event) -> RingSlot:
        while True:
            try:
                return self._free.get(timeout=0.05)
            except queue.Empty:
                if cancel.is_set():
                    raise _Cancelled()

    def release(self, slot: RingSlot) -> None:
        if slot.pinned:
            # ownership transfer: the pinned buffers leave the ring FOR
            # GOOD (device arrays made from them may alias the memory on
            # the CPU backend — recycling would corrupt a cached tile);
            # a fresh replacement keeps ring capacity unchanged
            slot.parked = True
            replacement = self._fresh_slot()
            try:
                self.slots[self.slots.index(slot)] = replacement
            except ValueError:
                self.slots.append(replacement)
            self._free.put(replacement)
            return
        self._free.put(slot)

    def unpin(self, slot: RingSlot) -> None:
        """Relinquish a pinned slot.  Parked (already released): a
        replacement was minted at release time, so this only drops the
        ring's bookkeeping — the buffers live exactly as long as the
        device arrays referencing them, and are NEVER re-leased.  Not
        yet released: cancels the pin, the slot recirculates normally on
        release."""
        slot.pinned = False
        slot.parked = False


def _put(q: "queue.Queue", item, cancel: threading.Event) -> None:
    while True:
        try:
            q.put(item, timeout=0.05)
            return
        except queue.Full:
            if cancel.is_set():
                raise _Cancelled()


_SENTINEL = object()


class FeedPipeline:
    """The shared group-assembly + double-buffered dispatch engine.

    Construct with the mesh width, the tile cap, and per-array
    ``TileSpec``s, then either::

        fp.feed(span_arrays_stream, dispatch_fn)      # stats drivers

    or::

        for out in fp.stream(span_arrays_stream, emit_fn):  # datasets
            ...

    ``span_arrays_stream`` yields per-span TUPLES of row arrays in
    lockstep (axis 0 = records; empty spans allowed).  The pipeline
    repacks them across span boundaries into ring-slot group buffers —
    device ``i`` of a group holds rows ``[i*cap, (i+1)*cap)`` of the
    concatenated stream, exactly the tiling of the old serial
    ``_iter_*_tiles`` + emit path (byte-identical, pinned by tests).

    ``dispatch_fn(arrays, counts)`` / ``emit_fn(arrays, counts)`` run on
    the CALLER's thread with ``arrays[j]`` a ``[n_dev, bucket, w]`` view
    of a leased ring slot and ``counts`` the per-device row counts.
    The buffers are BORROWED: valid until the call returns (``feed``)
    or until the generator is advanced (``stream``) — consumers must
    ``device_put``/copy before then, never retain the views.  That
    borrow is what makes the ring safe: the slot is released (and can
    be overwritten by the packer) only after the consumer is done.
    """

    def __init__(self, n_dev: int, cap: int, specs: Sequence[TileSpec],
                 *, block_n: int = 256, fixed_shape: bool = False,
                 balance: bool = False,
                 ring_slots: Optional[int] = None,
                 dispatch_depth: Optional[int] = None,
                 config: Optional[HBamConfig] = None,
                 count_bytes: bool = True,
                 name: str = "pipeline",
                 fmt: Optional[str] = None):
        config = config if config is not None else DEFAULT_CONFIG
        self.n_dev, self.cap = int(n_dev), int(cap)
        self.specs = [TileSpec.normalize(s) for s in specs]
        self.block_n = int(block_n)
        self.fixed_shape = bool(fixed_shape)
        self.balance = bool(balance)
        self.ring_slots = int(ring_slots if ring_slots is not None
                              else getattr(config, "feed_ring_slots", 2))
        self.dispatch_depth = max(1, int(
            dispatch_depth if dispatch_depth is not None
            else getattr(config, "feed_dispatch_depth", 2)))
        # count_bytes=False: the dispatcher transfers a narrower slice
        # of the ring views (coverage's op-width cut) and counts its
        # own pipeline.dispatch_bytes — the view nbytes would overstate
        self.count_bytes = bool(count_bytes)
        self.name = name
        # driver-family taxonomy twin: with fmt="bam" the same walls
        # ALSO land under bam.feed_wall / bam.dispatch_wall, so every
        # driver family reports the same <fmt>.<stage> span set (the
        # shared pipeline.* keys keep the bench contract)
        self.fmt = fmt
        self.dispatches = 0
        self.dispatch_bytes = 0

    # -- packer side (its own thread) ---------------------------------------

    def _pack_loop(self, stream: Iterable[Tuple[np.ndarray, ...]],
                   q: "queue.Queue", cancel: threading.Event,
                   ring: StagingRing) -> None:
        it = iter(stream)
        parts: "collections.deque[Tuple[np.ndarray, ...]]" = \
            collections.deque()
        have = 0
        exhausted = False
        # feed.wait_rows: the packer starved by decode (pool fetch, job
        # start and the native chunk poll all sit under next(it)).  With
        # a recorder active each pull is a span, so it carries a profiler
        # annotation; otherwise two clock reads a pull, summed into one
        # add_wall a group.  Its child feed.head_wait (parallel/pipeline.
        # _iter_windowed) says why: the next unit of the decode window
        # was queued, was running, or was stuck in front of finished ones
        traced = active_recorder() is not None
        waited = 0.0

        def pull_until(need: int) -> None:
            nonlocal exhausted, have, waited
            while not exhausted and have < need:
                if cancel.is_set():
                    raise _Cancelled()
                try:
                    if traced:
                        with METRICS.span("feed.wait_rows"):
                            arrays = next(it)
                    else:
                        t_wait = time.perf_counter()
                        try:
                            arrays = next(it)
                        finally:
                            waited += time.perf_counter() - t_wait
                except StopIteration:
                    exhausted = True
                    return
                arrays = tuple(arrays)
                n = arrays[0].shape[0]
                if n:
                    parts.append(arrays)
                    have += n

        while True:
            # balance needs one group's worth buffered up front (the
            # tail split depends on the total); serial mode pulls
            # lazily so tensor_batches never holds an extra group of
            # decoded spans in memory (its later pulls then sit inside
            # staging.pack, as feed.wait_rows children)
            pull_until(self.n_dev * self.cap if self.balance else 1)
            if waited:
                METRICS.add_wall("feed.wait_rows", waited)
                waited = 0.0
            if not have:
                break
            # feed.wait_slot: the packer held by back-pressure — every
            # ring slot still leased to the dispatch side here, the
            # hand-off queue full below (only with more slots than depth)
            with METRICS.span("feed.wait_slot"):
                slot = ring.lease(cancel)
            if slot.in_flight is not None:
                # the slot's previous dispatch may still be transferring
                # from these buffers: wait HERE, on the packer thread,
                # where the wait overlaps the consumer's next dispatch
                with METRICS.span("staging.transfer_wait"):
                    _block_in_flight(slot.in_flight)
                slot.in_flight = None
            # pack span (packer thread): group assembly occupancy sits
            # next to the consumer thread's dispatch spans in the trace
            # — the double-buffer overlap made visible
            with METRICS.span("staging.pack") as pack_args:
                counts = slot.counts
                counts[:] = 0
                target = self.cap
                if self.balance and exhausted and have < self.n_dev * self.cap:
                    # balanced tail (stats drivers): the serial fill order
                    # would park the whole remainder on the first devices
                    # and leave the rest idle — a small file on an 8-wide
                    # mesh then pays one device's wall time AND a full-cap
                    # padded transfer.  Spreading the tail evenly keeps
                    # every shard busy and lets the bucket ladder shrink
                    # the dispatch.  psum-invariant, so results are
                    # unchanged; tensor_batches keeps the serial order
                    # (balance=False) for byte-stable public batches.
                    target = max(1, -(-have // self.n_dev))
                for dev in range(self.n_dev):
                    filled = 0
                    while filled < target:
                        if not parts:
                            pull_until(1)
                            if not parts:
                                break
                        head = parts[0]
                        k = min(target - filled, head[0].shape[0])
                        for dst, src in zip(slot.arrays, head):
                            dst[dev, filled:filled + k] = src[:k]
                        if k == head[0].shape[0]:
                            parts.popleft()
                        else:
                            parts[0] = tuple(h[k:] for h in head)
                        filled += k
                        have -= k
                    counts[dev] = filled
                    if not parts and exhausted:
                        break
                bucket = self.cap
                if not self.fixed_shape:
                    # per-device bucket caps: the dispatch height is shared
                    # (one shard_map step) but sized by the LARGEST shard,
                    # so the final partial group shrinks to the smallest
                    # bucket holding it (bucket_cap is monotonic in count,
                    # so the max over devices equals bucket_cap(max count))
                    bucket = max(bucket_cap(int(c), self.cap, self.block_n)
                                 for c in counts)
                # zero ONLY the written tail: rows [count, bucket) per
                # device.  Rows past the bucket are never dispatched, and
                # rows under the count are fully overwritten — a full group
                # therefore pays no memset at all.
                for spec, dst in zip(self.specs, slot.arrays):
                    for dev in range(self.n_dev):
                        c = int(counts[dev])
                        if c < bucket:
                            dst[dev, c:bucket] = spec.pad
                pack_args.update(rows=int(counts.sum()), bucket=bucket)
            with METRICS.span("feed.wait_slot"):
                _put(q, (slot, bucket), cancel)

    # -- consumer side (the caller's thread) --------------------------------

    def _slots(self, stream: Iterable[Tuple[np.ndarray, ...]]
               ) -> Iterator[Tuple[RingSlot, Tuple[np.ndarray, ...],
                                   np.ndarray]]:
        """Yield leased ``(slot, bucket_views, counts)`` triples; the
        slot is released when the generator is advanced (or closed) — the
        depth-2 contract lives here."""
        import jax

        # The in-flight handles a dispatch returns are its TRANSFERS.  On
        # an accelerator a finished transfer means the slot's bytes are
        # on the device and the packer may overwrite them.  XLA:CPU may
        # instead zero-copy alias a suitably aligned host buffer: the
        # "transfer" is ready at once while the step launched after it
        # still reads the slot — so there each group is dispatched from
        # a private copy that nothing overwrites.  The slot's COUNTS are
        # ring memory too (a step that ran late read the count the packer
        # had written for a later group and dropped the difference in
        # rows), so they are copied with the rows.
        cpu_backend = jax.default_backend() == "cpu"
        ring = StagingRing(self.n_dev, self.cap, self.specs,
                           self.ring_slots)
        q: "queue.Queue" = queue.Queue(maxsize=max(1,
                                                   self.dispatch_depth - 1))
        cancel = threading.Event()
        errs: List[BaseException] = []

        def pack() -> None:
            try:
                self._pack_loop(stream, q, cancel, ring)
            except _Cancelled:
                return
            except BaseException as e:  # noqa: BLE001 — crosses the thread
                errs.append(e)
            try:
                _put(q, _SENTINEL, cancel)
            except _Cancelled:
                pass

        # the packer runs in a COPY of the caller's context so its spans
        # and walls land in the caller's MetricsContext, not the global
        ctx = contextvars.copy_context()
        packer = threading.Thread(target=lambda: ctx.run(pack),
                                  name="hbam-feed-pack", daemon=True)
        self.dispatches = 0
        self.dispatch_bytes = 0
        t0 = time.perf_counter()
        packer.start()
        try:
            while True:
                # feed.wait_group: the dispatch thread starved by the
                # packer (and, through it, by decode)
                with METRICS.span("feed.wait_group"):
                    item = q.get()
                if item is _SENTINEL:
                    break
                slot, bucket = item
                arrays = tuple(a[:, :bucket] for a in slot.arrays)
                counts = slot.counts
                if cpu_backend:
                    arrays = tuple(np.array(a) for a in arrays)
                    counts = np.array(counts)
                try:
                    yield slot, arrays, counts
                finally:
                    slot.release()
        finally:
            cancel.set()
            packer.join()
            wall = time.perf_counter() - t0
            METRICS.add_wall(f"{self.name}.feed_wall", wall,
                             t0=t0, args={"groups": self.dispatches})
            if self.fmt:
                METRICS.add_wall(f"{self.fmt}.feed_wall", wall)
        if errs:
            raise errs[0]

    @contextlib.contextmanager
    def _account(self, arrays: Tuple[np.ndarray, ...],
                 counts: np.ndarray) -> Iterator[None]:
        """The ``<name>.dispatch_wall`` span around one group's dispatch
        call, and that group's counters once the call returned."""
        n = None
        if self.count_bytes:
            n = sum(int(a.nbytes) for a in arrays) + int(counts.nbytes)
        t_exec = take_execute_start()
        t0 = time.perf_counter()
        with METRICS.span(f"{self.name}.dispatch_wall") as span_args:
            if n is not None:
                span_args["bytes"] = n
            yield
        dt = time.perf_counter() - t0
        if t_exec is not None:
            # plan.execute's start to the first group's dispatch: the
            # plan, a variant feed's peek, the first units' decode and
            # the first pack.  Once an execute (take_execute_start), and
            # never for a feed that no plan.execute stands around
            METRICS.add_wall("feed.first_dispatch_wait", t0 - t_exec,
                             t0=t_exec)
        self.dispatches += 1
        METRICS.count_per_device(f"{self.name}.device_rows", counts)
        if n is not None:
            self.dispatch_bytes += n
            METRICS.count("pipeline.dispatch_bytes", n)
        if self.fmt:
            METRICS.add_wall(f"{self.fmt}.dispatch_wall", dt)
        # per-group dispatch latency distribution: the p99 here is the
        # stall a device feels when the host falls behind — invisible in
        # the summed dispatch_wall
        METRICS.observe("pipeline.dispatch_group_s", dt)

    def stream(self, span_stream: Iterable[Tuple[np.ndarray, ...]],
               emit_fn: Callable) -> Iterator:
        """Generator mode for ``tensor_batches``-shaped APIs: yields
        ``emit_fn(arrays, counts)`` per group.  The borrowed buffers
        stay valid until the generator is advanced for the NEXT group.
        ``emit_fn`` should ``jax.device_put`` the views (plain, NOT
        blocking) and RETURN the resulting device arrays (any pytree):
        the return value is attached to the ring slot as its in-flight
        transfer handle, and the packer waits on it before reusing the
        buffers — asynchronous transfers stay safe without the dispatch
        thread ever blocking."""
        for slot, arrays, counts in self._slots(span_stream):
            with self._account(arrays, counts):
                out = emit_fn(arrays, counts)
            slot.in_flight = out
            yield out

    def feed(self, span_stream: Iterable[Tuple[np.ndarray, ...]],
             dispatch_fn: Callable) -> int:
        """Drive the whole stream through ``dispatch_fn`` (same handle
        contract as ``stream``: return the device arrays made from the
        borrowed buffers); returns the number of dispatched groups."""
        for _ in self.stream(span_stream, dispatch_fn):
            pass
        return self.dispatches
