"""Staging-ring / FeedPipeline suite (parallel/staging.py, r8 tentpole).

Three contracts are pinned here:

1. BYTE IDENTITY — random span-size streams through ``FeedPipeline``
   produce exactly the batches of the old serial emit path (the
   ``_iter_tile_tuples`` + fresh-group-tile loop every driver used to
   hand-roll), so the ring rebuild cannot change a single device byte.
2. NO ALIASING — a leased ring slot is never mutated while its dispatch
   is still in flight (a fake device_put snapshots the buffers, dawdles,
   and re-checks), which is the whole safety argument for reusing
   buffers under a double-buffered packer thread.
3. SHARED POOL / KNOBS — ``utils/pools.py`` hands every driver the same
   executor, honors ``decode_pool_workers`` at creation, and the
   ``set_decode_pool`` injection hook reaches real drivers.

Quick run: ``pytest -m staging``; still part of the tier-1 run.
"""
import concurrent.futures as cf
import threading
import time

import numpy as np
import pytest

from hadoop_bam_tpu.config import HBamConfig
from hadoop_bam_tpu.parallel.staging import (
    FeedPipeline, StagingRing, TileSpec, bucket_cap,
)

pytestmark = pytest.mark.staging


# ---------------------------------------------------------------------------
# the serial reference: the old per-driver emit loop, verbatim semantics
# ---------------------------------------------------------------------------

def serial_reference_groups(span_tuples, n_dev, cap, specs, block_n=16,
                            fixed_shape=False):
    """What every driver's hand-rolled loop used to produce: serial
    cross-span tiling (_iter_tile_tuples) + a fresh padded group tile
    per emit.  The FeedPipeline must match this byte for byte."""
    from hadoop_bam_tpu.parallel.pipeline import _iter_tile_tuples

    specs = [TileSpec.normalize(s) for s in specs]
    legacy = [(s.shape[0] if s.shape else None, s.dtype) for s in specs]
    group, counts, out = [], [], []

    def emit():
        b = cap if fixed_shape else \
            max(bucket_cap(c, cap, block_n) for c in counts)
        cvec = np.zeros((n_dev,), np.int32)
        cvec[:len(counts)] = counts
        stacked = []
        for j, sp in enumerate(specs):
            tile = np.full((n_dev, b) + sp.shape, sp.pad, specs[j].dtype)
            for i, g in enumerate(group):
                tile[i, :counts[i]] = g[j][:counts[i]]
            stacked.append(tile)
        out.append((stacked, cvec))
        group.clear()
        counts.clear()

    for tiles, count in _iter_tile_tuples(span_tuples, cap, legacy):
        group.append(tiles)
        counts.append(count)
        if len(group) == n_dev:
            emit()
    if group:
        emit()
    return out


def random_span_stream(rng, specs, n_spans, max_rows=57):
    """Random per-span row-array tuples (lockstep lengths, incl. empty
    spans) with distinguishable content."""
    specs = [TileSpec.normalize(s) for s in specs]
    seq = 0
    out = []
    for _ in range(n_spans):
        n = int(rng.integers(0, max_rows + 1))
        arrays = []
        for sp in specs:
            shape = (n,) + sp.shape
            if np.issubdtype(np.dtype(sp.dtype), np.floating):
                a = rng.normal(size=shape).astype(sp.dtype)
            else:
                info = np.iinfo(np.dtype(sp.dtype))
                a = (seq + np.arange(np.prod(shape, dtype=np.int64))
                     ).reshape(shape) % int(info.max) + 1
                a = a.astype(sp.dtype)
            arrays.append(a)
        seq += n
        out.append(tuple(arrays))
    return out


SPECS = (TileSpec((7,), np.uint8, 0),       # payload-ish 2-D tile
         TileSpec((3,), np.int8, -1),       # dosage-ish, pad -1
         TileSpec((), np.int32, 0))         # lengths-ish 1-D series


@pytest.mark.parametrize("n_dev,cap,fixed", [(1, 32, False), (3, 32, False),
                                             (8, 64, True), (4, 16, False)])
def test_feed_pipeline_byte_identical_to_serial_emit(n_dev, cap, fixed):
    rng = np.random.default_rng(1234 + n_dev + cap)
    for trial in range(4):
        spans = random_span_stream(rng, SPECS, n_spans=int(
            rng.integers(0, 24)))
        want = serial_reference_groups(iter(spans), n_dev, cap, SPECS,
                                       block_n=8, fixed_shape=fixed)
        fp = FeedPipeline(n_dev, cap, SPECS, block_n=8, fixed_shape=fixed,
                          ring_slots=2, dispatch_depth=2)
        got = []
        fp.feed(iter(spans), lambda arrays, counts: got.append(
            ([a.copy() for a in arrays], counts.copy())))
        assert len(got) == len(want)
        for (ga, gc), (wa, wc) in zip(got, want):
            np.testing.assert_array_equal(gc, wc)
            assert len(ga) == len(wa)
            for g, w in zip(ga, wa):
                assert g.dtype == w.dtype and g.shape == w.shape
                np.testing.assert_array_equal(g, w)


def test_feed_pipeline_property_many_seeds():
    """Wider randomized sweep at one geometry — the property-test body
    of the r8 acceptance: stream -> ring == stream -> serial, always."""
    for seed in range(12):
        rng = np.random.default_rng(seed)
        spans = random_span_stream(rng, SPECS, n_spans=int(
            rng.integers(1, 40)), max_rows=33)
        want = serial_reference_groups(iter(spans), 3, 24, SPECS, block_n=4)
        fp = FeedPipeline(3, 24, SPECS, block_n=4, ring_slots=3,
                          dispatch_depth=2)
        got = []
        fp.feed(iter(spans), lambda a, c: got.append(
            ([x.copy() for x in a], c.copy())))
        assert len(got) == len(want)
        for (ga, gc), (wa, wc) in zip(got, want):
            np.testing.assert_array_equal(gc, wc)
            for g, w in zip(ga, wa):
                np.testing.assert_array_equal(g, w)


def test_leased_slot_never_mutated_during_dispatch():
    """The aliasing contract: while a fake device_put dawdles inside
    dispatch, the packer thread must NOT touch the dispatched buffers —
    entry and exit snapshots are identical, and every snapshot equals
    the serial reference batch."""
    rng = np.random.default_rng(7)
    spans = random_span_stream(rng, SPECS, n_spans=30, max_rows=40)
    n_dev, cap = 2, 16
    want = serial_reference_groups(iter(spans), n_dev, cap, SPECS,
                                   block_n=4)
    # 2 slots + a fast packer: if leasing were broken the packer would
    # overwrite the in-flight slot during the sleep below
    fp = FeedPipeline(n_dev, cap, SPECS, block_n=4, ring_slots=2,
                      dispatch_depth=2)
    snapshots = []

    def fake_device_put_dispatch(arrays, counts):
        entry = [a.copy() for a in arrays] + [counts.copy()]
        time.sleep(0.02)          # the device_put "in flight" window
        for before, now in zip(entry, list(arrays) + [counts]):
            np.testing.assert_array_equal(before, now)
        snapshots.append(entry)

    fp.feed(iter(spans), fake_device_put_dispatch)
    assert len(snapshots) == len(want)
    for snap, (wa, wc) in zip(snapshots, want):
        for g, w in zip(snap[:-1], wa):
            np.testing.assert_array_equal(g, w)
        np.testing.assert_array_equal(snap[-1], wc)


def test_stream_mode_releases_slot_only_after_advance():
    """stream(): the yielded batch's buffers stay valid until the
    consumer asks for the next one (the borrow contract tensor_batches
    relies on)."""
    spans = [(np.full((10, 4), i + 1, np.uint8),) for i in range(12)]
    fp = FeedPipeline(2, 8, (TileSpec((4,), np.uint8),), block_n=4,
                      ring_slots=2)
    it = fp.stream(iter(spans), lambda a, c: (a[0], c))
    tile, counts = next(it)
    first = tile.copy()
    time.sleep(0.05)              # packer has every chance to misbehave
    np.testing.assert_array_equal(tile, first)
    rest = list(it)
    assert rest                   # the stream kept flowing afterwards


def test_in_flight_handles_block_before_slot_reuse():
    """The async-transfer contract: whatever a dispatch returns rides
    the slot as its in-flight handle, and the packer must wait on it
    before overwriting that slot's buffers.  Each fake handle only
    'completes' when the NEXT group is dispatched — so the feed can
    finish at all only if the packer genuinely waited in order."""
    class Handle:
        def __init__(self, i):
            self.i = i
            self.released = threading.Event()

        def block_until_ready(self):
            if not self.released.wait(timeout=10):
                raise RuntimeError(f"handle {self.i} never released")
            waited.append(self.i)

    spans = [(np.full((8, 2), i + 1, np.uint8),) for i in range(6)]
    fp = FeedPipeline(1, 8, (TileSpec((2,), np.uint8),), ring_slots=2,
                      dispatch_depth=2)
    handles, waited = [], []

    def dispatch(arrays, counts):
        h = Handle(len(handles))
        handles.append(h)
        if h.i >= 1:
            handles[h.i - 1].released.set()   # transfer i-1 'completes'
        return h

    assert fp.feed(iter(spans), dispatch) == 6
    # 2-slot ring over 6 groups: slots reused 4 times, each wait honored
    assert waited == [0, 1, 2, 3]
    for h in handles:
        h.released.set()


def test_decode_error_propagates_and_unwinds():
    """An exception in the span stream (the packer thread) re-raises at
    the caller and leaves no stuck threads behind."""
    def bad_stream():
        yield (np.zeros((5, 4), np.uint8),)
        raise RuntimeError("span decode exploded")

    fp = FeedPipeline(2, 8, (TileSpec((4,), np.uint8),), ring_slots=2)
    before = threading.active_count()
    with pytest.raises(RuntimeError, match="exploded"):
        fp.feed(bad_stream(), lambda a, c: None)
    deadline = time.time() + 5
    while threading.active_count() > before and time.time() < deadline:
        time.sleep(0.01)
    assert threading.active_count() <= before


def test_dispatch_error_cancels_packer():
    """The inverse: the consumer's dispatch raising must cancel the
    packer (which may be blocked on a full queue) instead of hanging."""
    spans = [(np.zeros((8, 4), np.uint8),) for _ in range(64)]
    fp = FeedPipeline(1, 8, (TileSpec((4,), np.uint8),), ring_slots=2,
                      dispatch_depth=2)

    def dispatch(arrays, counts):
        raise ValueError("device fell over")

    with pytest.raises(ValueError, match="fell over"):
        fp.feed(iter(spans), dispatch)


def test_empty_and_all_empty_streams_dispatch_nothing():
    fp = FeedPipeline(2, 8, (TileSpec((4,), np.uint8),))
    calls = []
    fp.feed(iter(()), lambda a, c: calls.append(1))
    fp.feed(iter([(np.zeros((0, 4), np.uint8),)] * 3),
            lambda a, c: calls.append(1))
    assert calls == []
    assert fp.dispatches == 0


def test_partial_tail_zeroing_uses_spec_pad():
    """Reused slots must not leak a previous group's rows: with a
    2-slot ring, the third group reuses the first group's slot, and its
    partial tail must carry the SPEC pad (0 / -1), not group 1's 9s."""
    fp = FeedPipeline(1, 8, (TileSpec((2,), np.uint8, 0),
                             TileSpec((2,), np.int8, -1)),
                      block_n=4, ring_slots=2, dispatch_depth=2)
    spans = [
        (np.full((8, 2), 9, np.uint8), np.full((8, 2), 5, np.int8)),
        (np.full((8, 2), 8, np.uint8), np.full((8, 2), 4, np.int8)),
        (np.full((3, 2), 7, np.uint8), np.full((3, 2), 2, np.int8)),
    ]
    batches = []
    fp.feed(iter(spans),
            lambda a, c: batches.append(([x.copy() for x in a], c.copy())))
    assert len(batches) == 3
    (u8, i8), c = batches[-1]
    assert int(c[0]) == 3
    assert u8.shape == (1, 4, 2)      # shrunk to the block_n bucket
    assert (u8[0, :3] == 7).all() and (u8[0, 3:] == 0).all()
    assert (i8[0, :3] == 2).all() and (i8[0, 3:] == -1).all()


def test_config_knobs_reach_the_pipeline():
    cfg = HBamConfig(feed_ring_slots=5, feed_dispatch_depth=3)
    fp = FeedPipeline(2, 8, (TileSpec((4,), np.uint8),), config=cfg)
    assert fp.ring_slots == 5 and fp.dispatch_depth == 3
    # explicit args beat the config
    fp = FeedPipeline(2, 8, (TileSpec((4,), np.uint8),), config=cfg,
                      ring_slots=2, dispatch_depth=2)
    assert fp.ring_slots == 2 and fp.dispatch_depth == 2
    ring = StagingRing(2, 8, (TileSpec((4,), np.uint8),), slots=4)
    assert ring.n_slots == 4 and len(ring.slots) == 4


def test_overlap_accounting_and_dispatch_bytes():
    from hadoop_bam_tpu.utils.metrics import Metrics

    spans = [(np.zeros((16, 4), np.uint8),) for _ in range(8)]
    fp = FeedPipeline(2, 16, (TileSpec((4,), np.uint8),), block_n=4)
    fp.feed(iter(spans), lambda a, c: time.sleep(0.005))
    assert fp.dispatches == 4
    # [2, 16, 4] u8 + [2] i32 per group
    assert fp.dispatch_bytes == 4 * (2 * 16 * 4 + 8)

    # wall_timer union semantics: overlapping same-name spans count once
    m = Metrics()
    with m.wall_timer("x"):
        with m.wall_timer("x"):
            time.sleep(0.02)
    assert m.wall_calls["x"] == 1
    assert 0.015 <= m.wall_timers["x"] < 1.0


# ---------------------------------------------------------------------------
# the shared decode pool
# ---------------------------------------------------------------------------

def test_decode_pool_is_shared_and_sized_by_config():
    from hadoop_bam_tpu.utils import pools

    prev = pools.set_decode_pool(None)
    try:
        cfg = HBamConfig(decode_pool_workers=3)
        p1 = pools.decode_pool(cfg)
        assert pools.decode_pool_size() == 3
        # one process, one pool: later (different) configs get the same
        p2 = pools.decode_pool(HBamConfig(decode_pool_workers=11))
        assert p2 is p1 and pools.decode_pool_size() == 3
        p1.shutdown(wait=True)
    finally:
        pools.set_decode_pool(*prev)


def test_set_decode_pool_injection_reaches_drivers(tmp_path):
    """A driver run decodes through the injected pool — the test hook
    the r8 issue asks for."""
    from hadoop_bam_tpu.parallel.pipeline import fastq_seq_stats_file
    from hadoop_bam_tpu.utils import pools

    class RecordingPool(cf.ThreadPoolExecutor):
        def __init__(self):
            super().__init__(max_workers=2)
            self.submits = 0

        def submit(self, fn, *a, **kw):
            self.submits += 1
            return super().submit(fn, *a, **kw)

    fq = str(tmp_path / "tiny.fastq")
    with open(fq, "w") as f:
        for i in range(50):
            f.write(f"@r{i}\nACGTACGTAC\n+\nIIIIIIIIII\n")
    rec = RecordingPool()
    prev = pools.set_decode_pool(rec, size=2)
    try:
        stats = fastq_seq_stats_file(fq)
        assert stats["n_reads"] == 50
        assert rec.submits > 0
    finally:
        pools.set_decode_pool(*prev)
        rec.shutdown(wait=True)
