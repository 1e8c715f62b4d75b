"""The second cache tier: DEVICE-RESIDENT decoded interval tiles.

The PR-5 ``ChunkCache`` only avoids re-*reading* — a warm query still
pays host_decode-to-columns plus a fresh ``device_put`` every time.
This module keeps the decoded, sharded ``[n_dev, cap]`` interval
columns (``rid``/``pos1``/``end1`` + per-device counts) resident on the
devices, keyed by ``(file_identity, chunk range, projection)``:

- a TILE HIT skips fetch + inflate + host_decode + transfer entirely
  and goes straight to the jitted interval-filter step — the warm
  serving path touches no host decode work at all;
- the budget is in DEVICE bytes, strict LRU, with proactive
  invalidation: putting a tile for a path whose ``file_identity``
  changed purges every tile of the old identity (the identity is also
  in the key, so even un-purged stale entries can never be served);
- tiles are assembled through a small pinned ``StagingRing``
  (``TileBuilder``): slot buffers are PINNED out of ring circulation
  from ``device_put`` until the transfer is committed, so a cached
  device tile can never be backed by host memory the ring re-leases
  and overwrites (the slot-pinning invariant, proof-tested in
  tests/test_serve.py).

Counters: ``serve.tile_hits`` / ``serve.tile_misses`` /
``serve.tile_evictions`` process-wide, plus per-instance ``stats()``
(the bench's hit-rate source, same convention as ``ChunkCache``).
"""
from __future__ import annotations

import dataclasses
import threading
from collections import OrderedDict
from typing import Dict, Hashable, List, Optional, Tuple

import numpy as np

from hadoop_bam_tpu.utils.metrics import METRICS
from hadoop_bam_tpu.utils.stepcache import BoundedStepCache, named_step

# the one projection served today: interval-overlap columns.  Payload
# projections (seq/qual tiles for query-then-analyze fusion) slot in as
# new names without touching the cache.
INTERVAL_PROJECTION = "intervals"


@dataclasses.dataclass
class TileGroup:
    """One sharded device group of a tile set: ``cols`` is the
    (rid, pos1, end1) triple of ``[n_dev, cap]`` int32 device arrays,
    ``counts`` the ``[n_dev]`` int32 per-device row counts (device
    array), ``n`` the live rows in this group."""
    cols: Tuple
    counts: object
    n: int


@dataclasses.dataclass
class TileSet:
    """Every device group of one decoded chunk, plus accounting.
    (Prefetch provenance lives on the HOST chunk in
    ``serve/prefetch.py`` — tiles are always built by the dispatcher.)"""
    groups: List[TileGroup]
    n: int                       # total candidate rows
    nbytes: int                  # device-resident footprint
    ident: Tuple                 # file_identity the tiles decode


def tile_key(ident: Tuple, kind: str, s: int, e: int,
             n_dev: int, cap: int,
             projection: str = INTERVAL_PROJECTION) -> Tuple:
    """(file_identity, region bucket, projection) — plus the mesh/tile
    geometry, because tiles sharded for one mesh shape cannot be served
    to another."""
    return (ident, kind, s, e, projection, n_dev, cap)


class DeviceTileCache:
    """Byte-budgeted LRU of device-resident ``TileSet`` values.

    Thread-safe (serve hits it from the dispatcher thread while stats
    readers poll from transport threads); values are built and consumed
    only on the dispatcher thread, so the lock guards the map, not the
    device arrays."""

    def __init__(self, byte_budget: int = 512 << 20):
        if byte_budget <= 0:
            from hadoop_bam_tpu.utils.errors import PlanError
            raise PlanError(
                f"serve tile cache byte budget must be positive, got "
                f"{byte_budget}")
        self.byte_budget = int(byte_budget)
        self._lock = threading.Lock()
        self._entries: "OrderedDict[Hashable, TileSet]" = OrderedDict()
        self._by_path: Dict[str, set] = {}   # abspath -> live keys
        self._ident_of: Dict[str, Tuple] = {}  # abspath -> newest identity
        self._bytes = 0
        self._hits = 0
        self._misses = 0
        self._evictions = 0
        self._invalidated = 0

    @staticmethod
    def _abspath(key: Hashable) -> str:
        return key[0][0]          # tile_key ident = (abspath, size, mtime)

    def get(self, key: Hashable) -> Optional[TileSet]:
        with self._lock:
            hit = self._entries.get(key)
            if hit is None:
                self._misses += 1
                METRICS.count("serve.tile_misses")
                return None
            self._entries.move_to_end(key)
            self._hits += 1
            METRICS.count("serve.tile_hits")
            return hit

    def put(self, key: Hashable, tiles: TileSet) -> None:
        nbytes = max(0, int(tiles.nbytes))
        path = self._abspath(key)
        with self._lock:
            prev_ident = self._ident_of.get(path)
            if prev_ident is not None and prev_ident != tiles.ident:
                # the file changed on disk: purge every tile of the old
                # identity NOW rather than waiting for LRU pressure —
                # they can never hit again and would squat on the
                # budget.  This runs even when the NEW tile is rejected
                # as oversize below: the stale tiles are dead either way
                self._purge_path_locked(path)
            if nbytes > self.byte_budget:
                METRICS.count("serve.tile_oversize")
                return
            self._ident_of[path] = tiles.ident
            old = self._entries.pop(key, None)
            if old is not None:
                self._bytes -= old.nbytes
            self._entries[key] = tiles
            self._by_path.setdefault(path, set()).add(key)
            self._bytes += nbytes
            while self._bytes > self.byte_budget and len(self._entries) > 1:
                k, v = self._entries.popitem(last=False)
                self._drop_locked(k, v)
                self._evictions += 1
                METRICS.count("serve.tile_evictions")

    def _drop_locked(self, key: Hashable, tiles: TileSet) -> None:
        self._bytes -= tiles.nbytes
        path = self._abspath(key)
        keys = self._by_path.get(path)
        if keys is not None:
            keys.discard(key)
            if not keys:
                self._by_path.pop(path, None)
                self._ident_of.pop(path, None)

    def _purge_path_locked(self, path: str) -> None:
        for k in list(self._by_path.get(path, ())):
            v = self._entries.pop(k, None)
            if v is not None:
                self._drop_locked(k, v)
                self._invalidated += 1
                METRICS.count("serve.tile_invalidations")

    def invalidate_path(self, path: str) -> None:
        """Drop every tile of ``path`` (any identity) — the explicit
        variant of the identity-change purge."""
        import os
        with self._lock:
            self._purge_path_locked(os.path.abspath(path))

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            self._by_path.clear()
            self._ident_of.clear()
            self._bytes = 0

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    @property
    def bytes_used(self) -> int:
        with self._lock:
            return self._bytes

    def stats(self) -> Dict[str, float]:
        with self._lock:
            total = self._hits + self._misses
            return {
                "entries": len(self._entries),
                "bytes": self._bytes,
                "byte_budget": self.byte_budget,
                "hits": self._hits,
                "misses": self._misses,
                "evictions": self._evictions,
                "invalidated": self._invalidated,
                "hit_rate": (self._hits / total) if total else 0.0,
            }


# ---------------------------------------------------------------------------
# device filter step: cached tiles x one query interval
# ---------------------------------------------------------------------------

# bounded (SV801): one entry per (mesh, axis) in live use
_STEP_CACHE = BoundedStepCache(cap=8)


def make_tile_filter_step(mesh, axis: str = "data"):
    """Jitted sharded predicate over a CACHED tile: per-row 1-based
    inclusive overlap of the tile's (rid, pos1, end1) columns against
    ONE query interval ``iv = [rid, beg, end]`` (replicated int32[3]).
    Returns ``(keep, hits)``: the sharded boolean mask and the
    per-device match COUNTS — count-only serving reads just the [n_dev]
    counts (a few bytes off the mesh) and never materializes the mask.

    Unlike ``query.engine.make_overlap_step`` — which bakes the interval
    into per-row columns at pack time — the interval here is a runtime
    argument, so one resident tile serves every query that lands on its
    chunk without repacking or retransferring anything."""
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from hadoop_bam_tpu.parallel.mesh import shard_map

    key = ("serve_tile_filter", tuple(mesh.devices.flat),
           mesh.axis_names, axis)

    def build():
        def per_device(rid, pos1, end1, count, iv):
            rid, pos1, end1, count = rid[0], pos1[0], end1[0], count[0]
            valid = jnp.arange(rid.shape[0], dtype=jnp.int32) < count
            keep = valid & (rid == iv[0]) & (pos1 <= iv[2]) \
                & (end1 >= iv[1])
            hits = keep.sum(dtype=jnp.int32)
            return keep[None], hits[None]

        fn = shard_map(per_device, mesh=mesh,
                       in_specs=(P(axis), P(axis), P(axis), P(axis), P()),
                       out_specs=(P(axis), P(axis)))
        return named_step("tile_filter_step", fn)

    return _STEP_CACHE.get_or_build(key, build)


# ---------------------------------------------------------------------------
# tile assembly through a pinned staging ring
# ---------------------------------------------------------------------------

class TileBuilder:
    """Assembles decoded chunk columns into sharded device ``TileSet``s
    through a ``StagingRing`` with SLOT PINNING: each group's slot is
    pinned before release, which transfers its buffers OUT of ring
    circulation for the lifetime of the device arrays (the ring mints a
    replacement).  That ownership transfer is what makes device-tile
    caching safe at all — on the CPU backend ``jax.device_put`` may
    zero-copy ALIAS the host buffers, so a recycled slot would silently
    rewrite a cached tile (the churn proof in tests/test_serve.py
    catches exactly this).  All methods run on ONE thread (the serve
    dispatcher); jax never gets called from two threads here."""

    def __init__(self, mesh, cap: int, ring_slots: int = 3):
        import jax  # noqa: F401 — fail early if jax is absent
        from jax.sharding import NamedSharding, PartitionSpec as P

        from hadoop_bam_tpu.parallel.staging import StagingRing, TileSpec

        self.mesh = mesh
        self.n_dev = int(np.prod(mesh.devices.shape))
        self.cap = int(cap)
        self.sharding = NamedSharding(mesh, P("data"))
        self.replicated = NamedSharding(mesh, P())
        # rid pads with -1 so a padding row can never match a real
        # reference id even if a bug ever ignored the count mask
        specs = [TileSpec((), np.int32, -1),
                 TileSpec((), np.int32, 0),
                 TileSpec((), np.int32, 0)]
        self._ring = StagingRing(self.n_dev, self.cap, specs,
                                 max(3, int(ring_slots)))
        self._cancel = threading.Event()
        # replicated-interval LRU (SV801-bounded): zipf-hot regions
        # repeat, so the warm path skips even the tiny iv device_put
        self._iv_cache: "OrderedDict[Tuple[int, int, int], object]" = \
            OrderedDict()

    def put_interval(self, iv_arr) -> object:
        """Replicate a ``[rid, beg, end]`` int32 interval across the
        mesh for the filter step (LRU-cached: repeated hot regions pay
        zero transfers)."""
        import jax
        key = (int(iv_arr[0]), int(iv_arr[1]), int(iv_arr[2]))
        hit = self._iv_cache.get(key)
        if hit is not None:
            self._iv_cache.move_to_end(key)
            return hit
        dev = jax.device_put(np.asarray(iv_arr, np.int32),
                             self.replicated)
        while len(self._iv_cache) >= 256:
            self._iv_cache.popitem(last=False)
        self._iv_cache[key] = dev
        return dev

    def build(self, ident: Tuple, cols: Dict[str, object]) -> TileSet:
        """Sharded device tiles from one decoded chunk's host columns
        (the ``rid``/``pos1``/``end1`` arrays of ``QueryEngine._chunk``).
        Rows pack serially: group g, device d holds rows
        ``[g*n_dev*cap + d*cap, ...+cap)`` of the chunk."""
        import jax

        n = int(cols["n"])
        host = (np.asarray(cols["rid"], np.int32),
                np.asarray(cols["pos1"], np.int32),
                np.asarray(cols["end1"], np.int32))
        groups: List[TileGroup] = []
        nbytes = 0
        if n == 0:
            # empty chunks cache as an empty TileSet: the lookup still
            # hits (no re-decode), the filter loop has nothing to do
            return TileSet(groups=[], n=0, nbytes=64, ident=ident)
        with METRICS.span("serve.tile_build_wall", rows=n):
            per_group = self.n_dev * self.cap
            for base in range(0, n, per_group):
                slot = self._ring.lease(self._cancel)
                counts = slot.counts
                counts[:] = 0
                for dev in range(self.n_dev):
                    lo = base + dev * self.cap
                    k = max(0, min(self.cap, n - lo))
                    for dst, src in zip(slot.arrays, host):
                        if k:
                            dst[dev, :k] = src[lo:lo + k]
                    counts[dev] = k
                # pad rows past each device's count (fresh ring slots
                # arrive pre-padded, but a slot that recirculated from
                # an unpinned use may carry stale rows)
                for spec, dst in zip(self._ring.specs, slot.arrays):
                    for dev in range(self.n_dev):
                        c = int(counts[dev])
                        if c < self.cap:
                            dst[dev, c:] = spec.pad
                dev_arrays = jax.device_put(
                    (slot.arrays[0], slot.arrays[1], slot.arrays[2],
                     counts.copy()), self.sharding)
                # ownership transfer: these buffers now belong to the
                # cached tile; the ring replaces the slot and can never
                # hand this memory out again
                slot.pin()
                slot.release()
                g_rows = int(min(n - base, per_group))
                groups.append(TileGroup(cols=dev_arrays[:3],
                                        counts=dev_arrays[3], n=g_rows))
                nbytes += sum(int(a.nbytes) for a in dev_arrays)
        return TileSet(groups=groups, n=n, nbytes=nbytes + 64,
                       ident=ident)

    def close(self) -> None:
        self._cancel.set()
