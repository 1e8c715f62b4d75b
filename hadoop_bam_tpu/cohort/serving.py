"""Cohort-slice serving: "this gene across N samples" from resident tiles.

The serve tier's third projection (after interval tiles and host
chunks): the joined cohort's ``chrom``/``pos``/``n_allele``/``dosage``
columns live as sharded device tiles in the SAME ``DeviceTileCache``
as region tiles, keyed by the **cohort manifest identity** (every
input's ``(abspath, size, mtime_ns)`` digested — rewrite one sample
file and every cached cohort tile self-invalidates).

Request shape on the wire (serve/transport.py)::

    {"id": 7, "cohort": true, "path": "cohort.json",
     "regions": ["chr20:1000000-2000000"], "records": false}

The COLD path runs the full position join (host work, spanned as
``cohort.join_wall`` + ``pipeline.host_decode_wall``) and parks the
joined tiles on the devices; every WARM slice goes straight to the
jitted interval filter — no host decode at all, the same bypass
contract as region serving (pinned by tests: host_decode share ~0 on
repeat slices).
"""
from __future__ import annotations

import threading
from collections import OrderedDict
from typing import Dict, List, Optional, Tuple

import numpy as np

from hadoop_bam_tpu.config import DEFAULT_CONFIG, HBamConfig
from hadoop_bam_tpu.cohort.manifest import CohortManifest, load_manifest
from hadoop_bam_tpu.utils.errors import PlanError
from hadoop_bam_tpu.utils.metrics import METRICS
from hadoop_bam_tpu.utils.stepcache import BoundedStepCache, named_step

COHORT_PROJECTION = "cohort_dosage"

_I32_MAX = int(np.iinfo(np.int32).max)


class _CohortMeta:
    """Resident per-manifest state: ONE ``CohortDataset`` (so the serve
    path shares the exact quarantine policy AND contig space the
    CLI/API build uses — a header-corrupt sample quarantines here too,
    and tile chrom indices can never diverge from the cmap the slice
    resolves against), plus — once built — the tile group row counts
    (so warm lookups know every key to fetch)."""

    __slots__ = ("path", "dataset", "ident", "group_rows", "n_variants")

    def __init__(self, path: str, dataset, ident):
        self.path = path
        self.dataset = dataset
        self.ident = ident
        self.group_rows: Optional[List[int]] = None
        self.n_variants = 0

    @property
    def manifest(self) -> CohortManifest:
        return self.dataset.manifest

    @property
    def contigs(self) -> List[str]:
        return self.dataset.contigs

    @property
    def cmap(self):
        return self.dataset._cmap

    @property
    def n_samples(self) -> int:
        return self.dataset.n_samples

    @property
    def samples_pad(self) -> int:
        return self.dataset.geometry.samples_pad


def make_cohort_slice_step(mesh, axis: str = "data", *,
                           _cache=BoundedStepCache(cap=8)):
    """Jitted sharded slice predicate over a resident cohort tile:
    rows overlapping ONE interval ``iv = [contig, beg, end]``
    (replicated int32[3]).  Returns ``(keep, hits, af, af_sum, af_n)``
    — count-only serving reads just the per-device scalars; ``af`` is
    the per-row diploid ALT allele frequency (records mode)."""
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from hadoop_bam_tpu.parallel.mesh import shard_map

    key = ("cohort_slice", tuple(mesh.devices.flat), mesh.axis_names,
           axis)

    def build():
        def per_device(chrom, pos, dosage, count, iv):
            chrom, pos = chrom[0], pos[0]
            dosage, count = dosage[0], count[0]
            cap = chrom.shape[0]
            valid = jnp.arange(cap, dtype=jnp.int32) < count
            keep = valid & (chrom == iv[0]) & (pos >= iv[1]) \
                & (pos <= iv[2])
            hits = keep.sum(dtype=jnp.int32)
            d = dosage.astype(jnp.int32)
            called = d >= 0
            ncf = called.sum(axis=1).astype(jnp.float32)
            alt = jnp.where(called, d, 0).sum(axis=1).astype(jnp.float32)
            has = ncf > 0
            af = jnp.where(has, alt / (2.0 * jnp.maximum(ncf, 1.0)),
                           jnp.float32(jnp.nan))
            in_mean = keep & has
            af_sum = jnp.where(in_mean, af, 0.0).sum()
            af_n = in_mean.sum(dtype=jnp.int32)
            return (keep[None], hits[None], af[None], af_sum[None],
                    af_n[None])

        fn = shard_map(per_device, mesh=mesh,
                       in_specs=(P(axis),) * 4 + (P(),),
                       out_specs=(P(axis),) * 5)
        return named_step("cohort_slice_step", fn)

    return _cache.get_or_build(key, build)


class CohortServer:
    """The serve tier's cohort plane: owns manifest metadata (bounded
    LRU), builds joined dosage tiles into the shared DeviceTileCache,
    and answers slice requests.  All methods run on the ONE serve
    dispatcher thread — the FeedPipeline jax discipline — so no lock
    guards the device work, only the meta map (stats readers poll)."""

    def __init__(self, mesh, config: HBamConfig = DEFAULT_CONFIG):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        self.mesh = mesh
        self.config = config
        self.n_dev = int(np.prod(mesh.devices.shape))
        self.cap = int(getattr(config, "serve_tile_records", 4096))
        self.sharding = NamedSharding(mesh, P("data"))
        self.replicated = NamedSharding(mesh, P())
        self._lock = threading.Lock()
        self._meta: "OrderedDict[str, _CohortMeta]" = OrderedDict()
        self._meta_cap = max(1, int(getattr(config,
                                            "serve_cohort_manifests", 8)))
        self._jax = jax

    # -- metadata ------------------------------------------------------------

    def _meta_for(self, path: str) -> _CohortMeta:
        import os

        apath = os.path.abspath(path)
        manifest = load_manifest(apath)
        ident = manifest.identity()
        with self._lock:
            meta = self._meta.get(apath)
            if meta is not None and meta.ident == ident:
                self._meta.move_to_end(apath)
                return meta
        # cold or identity-changed: ONE CohortDataset carries the
        # contig space, geometry, and quarantine policy for both the
        # slice resolution below and the tile build — the same code
        # path the CLI/API build runs, so a header-corrupt sample
        # quarantines instead of failing the request, and tile chrom
        # indices always match the cmap slices resolve against
        from hadoop_bam_tpu.cohort.dataset import CohortDataset

        meta = _CohortMeta(apath, CohortDataset(manifest, self.config),
                           ident)
        with self._lock:
            self._meta[apath] = meta
            self._meta.move_to_end(apath)
            while len(self._meta) > self._meta_cap:
                self._meta.popitem(last=False)
        return meta

    # -- tiles ---------------------------------------------------------------

    def _key(self, meta: _CohortMeta, g: int) -> Tuple:
        from hadoop_bam_tpu.serve.tiles import tile_key
        return tile_key(meta.ident, "cohort", g, 0, self.n_dev, self.cap,
                        projection=COHORT_PROJECTION)

    def _build_tiles(self, meta: _CohortMeta) -> List:
        """Run the join and park the cohort on the devices: one sharded
        TileSet per ``n_dev * cap``-row group.  Host arrays here are
        FRESH per build (never ring-recycled), so the CPU backend's
        zero-copy ``device_put`` aliasing is safe by construction.

        Chunks STREAM into the group buffers: the slice path never
        uses the qual column (the largest one — dropped on arrival),
        and at most one group plus one chunk of dosage is held on the
        host at a time, never a second full-cohort copy."""
        from hadoop_bam_tpu.serve.tiles import TileGroup, TileSet

        ds = meta.dataset
        per_group = self.n_dev * self.cap
        sets: List[TileSet] = []
        group = None                # (chrom, pos, nall, dosage) buffers
        fill = 0                    # rows filled in the open group

        def fresh_group():
            return (np.full((per_group,), -1, np.int32),
                    np.zeros((per_group,), np.int32),
                    np.zeros((per_group,), np.int16),
                    np.full((per_group, meta.samples_pad), -1, np.int8))

        def close_group(bufs, rows: int) -> None:
            counts = np.minimum(
                np.maximum(rows - np.arange(self.n_dev) * self.cap, 0),
                self.cap).astype(np.int32)
            shaped = (bufs[0].reshape(self.n_dev, self.cap),
                      bufs[1].reshape(self.n_dev, self.cap),
                      bufs[2].reshape(self.n_dev, self.cap),
                      bufs[3].reshape(self.n_dev, self.cap,
                                      meta.samples_pad))
            dev_arrays = self._jax.device_put(shaped + (counts,),
                                              self.sharding)
            nbytes = sum(int(a.nbytes) for a in dev_arrays)
            sets.append(TileSet(
                groups=[TileGroup(cols=dev_arrays[:4],
                                  counts=dev_arrays[4], n=rows)],
                n=rows, nbytes=nbytes + 64, ident=meta.ident))

        n = 0
        with METRICS.span("cohort.tile_build_wall"):
            for chunk in ds.site_chunks():
                chunk.pop("qual", None)      # slicing never reads it
                m = int(chunk["chrom"].shape[0])
                taken = 0
                while taken < m:
                    if group is None:
                        group, fill = fresh_group(), 0
                    k = min(per_group - fill, m - taken)
                    group[0][fill:fill + k] = chunk["chrom"][taken:taken + k]
                    group[1][fill:fill + k] = chunk["pos"][taken:taken + k]
                    group[2][fill:fill + k] = \
                        chunk["n_allele"][taken:taken + k]
                    group[3][fill:fill + k] = \
                        chunk["dosage"][taken:taken + k]
                    fill += k
                    taken += k
                    n += k
                    if fill == per_group:
                        close_group(group, fill)
                        group = None
            if group is not None and fill:
                close_group(group, fill)
            elif n == 0:
                # empty cohort: one all-padding group so warm lookups
                # and the filter loop have a well-formed (empty) tile
                close_group(fresh_group(), 0)
        meta.n_variants = n
        return sets

    def _tiles(self, meta: _CohortMeta, tiles_cache
               ) -> Tuple[List, int, int]:
        """(tile sets, tile_hits, tile_misses) — warm fetch from the
        shared device cache, or one cold build that parks every group."""
        if meta.group_rows is not None:
            sets = []
            for g in range(len(meta.group_rows)):
                t = tiles_cache.get(self._key(meta, g))
                if t is None:
                    sets = None
                    break
                sets.append(t)
            if sets is not None:
                return sets, len(sets), 0
        built = self._build_tiles(meta)
        for g, t in enumerate(built):
            tiles_cache.put(self._key(meta, g), t)
        meta.group_rows = [t.n for t in built]
        METRICS.count("cohort.tile_builds")
        return built, 0, max(1, len(built))

    # -- the slice -----------------------------------------------------------

    def serve(self, path: str, region: str, tiles_cache, *,
              want_records: bool = False, deadline=None):
        """Answer one cohort-slice request; returns a
        ``serve.loop.ServeResult`` (count = variants in the slice,
        ``extra`` carries the cohort aggregates)."""
        from hadoop_bam_tpu.serve.loop import ServeResult
        from hadoop_bam_tpu.split.intervals import parse_interval

        if deadline is not None:
            deadline.check("cohort resolve")
        meta = self._meta_for(path)
        iv = parse_interval(region)
        rid = meta.cmap.get(iv.rname)
        if rid is None:
            raise PlanError(
                f"cohort slice: contig {iv.rname!r} is in no sample "
                f"header of {path!r}")
        sets, tile_hits, tile_misses = self._tiles(meta, tiles_cache)
        step = make_cohort_slice_step(self.mesh)
        iv_dev = self._jax.device_put(
            np.asarray([rid, min(iv.start, _I32_MAX),
                        min(iv.end, _I32_MAX)], np.int32),
            self.replicated)
        count = 0
        af_sum = 0.0
        af_n = 0
        recs: Optional[List[Dict]] = [] if want_records else None
        with METRICS.span("cohort.slice_wall", region=region):
            # dispatch EVERY group first, drain once: per-group host
            # syncs inside the loop would serialize a device round-trip
            # every n_dev*cap rows
            pending = []
            for t in sets:
                if deadline is not None:
                    deadline.check("cohort slice group")
                for g in t.groups:
                    pending.append(
                        (g, step(*g.cols[:2], g.cols[3], g.counts,
                                 iv_dev)))
            for g, (keep, hits, af, asum, an) in pending:
                count += int(np.asarray(hits).sum())
                af_sum += float(np.asarray(asum).sum())
                af_n += int(np.asarray(an).sum())
                if recs is not None:
                    km = np.asarray(keep)
                    hchrom = np.asarray(g.cols[0])
                    hpos = np.asarray(g.cols[1])
                    hnall = np.asarray(g.cols[2])
                    haf = np.asarray(af)
                    for dev in range(km.shape[0]):
                        for row in np.flatnonzero(km[dev]):
                            a = float(haf[dev, row])
                            recs.append({
                                "chrom": meta.contigs[
                                    int(hchrom[dev, row])],
                                "pos": int(hpos[dev, row]),
                                "n_allele": int(hnall[dev, row]),
                                "af": None if np.isnan(a)
                                else round(a, 6)})
        METRICS.count("cohort.slice_requests")
        extra = {
            "n_samples": meta.n_samples,
            "mean_af": (round(af_sum / af_n, 6) if af_n else None),
        }
        if meta.manifest.quarantined:
            extra["quarantined"] = sorted(meta.manifest.quarantined)
        if recs is not None:
            recs.sort(key=lambda r: (r["chrom"], r["pos"]))
        return ServeResult(region=region, count=count,
                           n_candidates=meta.n_variants,
                           tile_hits=tile_hits, tile_misses=tile_misses,
                           records=recs, extra=extra)

    def stats(self) -> Dict[str, int]:
        with self._lock:
            return {"manifests": len(self._meta)}
