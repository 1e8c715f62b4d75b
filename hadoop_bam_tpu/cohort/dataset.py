"""CohortDataset: the [variants, samples] tensor surface over a manifest.

The cohort twin of ``api.vcf_dataset.VcfDataset``: where that class
tiles ONE file's variants, this one streams k single-sample files
through the position join (cohort/join.py) and tiles the JOINED columns
onto the mesh through the same scan feed (``parallel/scan.py``) and
``FeedPipeline`` machinery — so sentinel padding (-1 dosage / NaN qual), ring-slot
reuse, and the in-flight transfer discipline are all inherited, not
re-implemented.
"""
from __future__ import annotations

from typing import Dict, Iterator, List, Optional, Union

import numpy as np

from hadoop_bam_tpu.config import DEFAULT_CONFIG, HBamConfig
from hadoop_bam_tpu.cohort.join import (
    _JoinState, build_contig_space, guarded_sites, iter_joined_chunks,
    iter_sample_sites,
)
from hadoop_bam_tpu.cohort.manifest import CohortManifest, as_manifest


class CohortDataset:
    """Mesh-tiled access to a cohort of single-sample VCF/BCF files.

    ``tensor_batches`` yields device-resident dicts sharded over the
    mesh's data axis::

        chrom    int32  [n_dev, cap]
        pos      int32  [n_dev, cap]
        n_allele int16  [n_dev, cap]
        dosage   int8   [n_dev, cap, samples_pad]   (-1 missing)
        qual     float32[n_dev, cap, samples_pad]   (NaN missing)
        n_records int32 [n_dev]

    Rows beyond a shard's ``n_records`` carry the missing-value
    sentinels uniformly (dosage -1, qual NaN, 0 elsewhere) — the PR-4
    convention, enforced by the shared TileSpec pads.  Column ``j`` is
    ``manifest.samples[j]``; a sample whose input quarantined mid-join
    is sentinel-filled from the fault onward and listed in
    ``self.manifest.quarantined``.
    """

    def __init__(self, source: Union[str, CohortManifest, List[str]],
                 config: HBamConfig = DEFAULT_CONFIG,
                 journal_path: Optional[str] = None):
        from hadoop_bam_tpu.api.vcf_dataset import VcfDataset
        from hadoop_bam_tpu.parallel.variant_pipeline import VariantGeometry
        from hadoop_bam_tpu.resilience import file_ident, registry
        from hadoop_bam_tpu.utils.errors import (
            CorruptDataError, PLAN, classify_error,
        )
        from hadoop_bam_tpu.utils.metrics import METRICS

        self.config = config
        self.journal_path = journal_path
        self._journal_live = False     # one journaled join at a time
        self.manifest = as_manifest(source)
        quarantine = bool(getattr(config, "cohort_quarantine_inputs",
                                  True))
        # header reads: a MISSING path is configuration (PLAN, raises);
        # a file whose header bytes are corrupt is data — under the
        # quarantine policy its column goes sentinel before the join
        # even starts (the slot is kept as None so sample indices stay
        # stable)
        self._datasets: List = []
        for s in self.manifest.samples:
            try:
                self._datasets.append(VcfDataset(s.path, config))
            except Exception as e:  # noqa: BLE001 — classified below
                if classify_error(e) == PLAN or not quarantine:
                    raise
                registry().domain("cohort", "input", file_ident(s.path),
                                  config=config).record_failure(e)
                self.manifest.record_quarantine(
                    s.sample_id, f"{type(e).__name__}: {e}")
                METRICS.count("cohort.samples_quarantined")
                self._datasets.append(None)
        n_dead = sum(1 for d in self._datasets if d is None)
        max_frac = float(getattr(config, "cohort_max_quarantine_fraction",
                                 0.5))
        if n_dead / max(1, self.manifest.n_samples) > max_frac:
            raise CorruptDataError(
                f"cohort build: {n_dead}/{self.manifest.n_samples} "
                f"sample inputs quarantined at header read — over the "
                f"cohort_max_quarantine_fraction={max_frac} circuit")
        self.contigs = build_contig_space(
            [ds.header for ds in self._datasets if ds is not None])
        self._cmap = {c: i for i, c in enumerate(self.contigs)}
        self.geometry = VariantGeometry(n_samples=self.manifest.n_samples)

    @property
    def n_samples(self) -> int:
        return self.manifest.n_samples

    @property
    def sample_ids(self) -> List[str]:
        return self.manifest.sample_ids

    def contig_index(self, name: str) -> int:
        return self._cmap.get(name, -1)

    # -- host-side joined columns (the serve tier + oracle surface) ----------

    def site_chunks(self) -> Iterator[Dict[str, np.ndarray]]:
        """Stream the joined cohort as host column chunks (up to
        ``config.cohort_chunk_sites`` rows each) — the input of both the
        mesh feed below and the serve tier's tile builder.

        With a ``journal_path`` the join is CRASH-SAFE (jobs/): every
        produced chunk persists to ``<journal>.chunks/chunk-NNNNN.npz``
        and commits a journaled unit (size+CRC+last site key); a
        resumed join replays the verified chunks from disk — identical
        bytes, zero re-join/re-harmonize work — then continues the live
        merge from the last committed key.  Input records are still
        re-streamed for the continuation (a k-way merge needs its
        cursors), so the savings are the join/harmonize/pack work and,
        on a finished job, the entire decode.  A quarantine that was
        caused by a TRANSIENT fault may heal on resume: the journaled
        chunks keep their sentinel columns, the live suffix carries
        real data — recorded as ``quarantine`` events either way."""
        if self.journal_path is not None and self._journal_live:
            # the guard must fire BEFORE stream construction: merely
            # building streams resets every sample's span cursor, which
            # would corrupt the live iteration's reads even if the
            # journal itself were protected further down
            from hadoop_bam_tpu.utils.errors import PlanError
            raise PlanError(
                f"a journaled join over {self.journal_path} is already "
                f"in progress on this dataset — close (exhaust) the "
                f"prior site_chunks() iterator before starting another")
        state = _JoinState(
            self.manifest.n_samples,
            float(getattr(self.config, "cohort_max_quarantine_fraction",
                          0.5)))
        # header-time casualties count toward the fraction circuit
        state.quarantined = sum(1 for d in self._datasets if d is None)
        streams = []
        for ds, sample in zip(self._datasets, self.manifest.samples):
            if ds is None:
                streams.append(iter(()))   # quarantined at header read
                continue
            # every join starts from the file's FIRST span: records()
            # only auto-resets after a fully-exhausted iteration, and a
            # join abandoned mid-stream (early tensor_batches break, a
            # fraction-circuit trip) would otherwise silently RESUME
            # mid-file on the next call and serve a truncated cohort
            ds._next_span = 0
            sites = iter_sample_sites(ds.records(), self._cmap)
            streams.append(guarded_sites(
                sites, sample.sample_id, sample.path, self.manifest,
                state, self.config))
        if self.journal_path is None:
            return iter_joined_chunks(self.manifest, streams,
                                      self.geometry.samples_pad,
                                      self.config)
        return self._journaled_chunks(streams)

    def _journaled_chunks(self, streams) -> Iterator[Dict[str,
                                                          np.ndarray]]:
        """The journal-aware wrapper around ``iter_joined_chunks``
        (``site_chunks`` docstring): replay verified chunks, sweep the
        in-flight chunk's debris, continue past the last committed
        key, commit each fresh chunk before handing it downstream."""
        import os

        from hadoop_bam_tpu.jobs import journal as jj
        from hadoop_bam_tpu.jobs.runner import (
            COHORT_FINGERPRINT_FIELDS, plan_journal_params,
        )
        from hadoop_bam_tpu.utils.metrics import METRICS

        # reentrancy is refused at the top of site_chunks (two live
        # journaled iterations = two writers on one journal, the exact
        # shape replay classifies as corruption; and the second
        # resume's sweep could unlink chunks the first just committed)
        chunks_dir = os.path.abspath(self.journal_path) + ".chunks"

        def load(u):
            with np.load(u["path"]) as z:
                return {kk: z[kk] for kk in ("chrom", "pos", "n_allele",
                                             "dosage", "qual")}

        def gen():
            # EVERYTHING — journal open, lock, replay — happens lazily
            # at first next(): a generator that is created but never
            # started runs no body, so eager setup would leave the
            # dataset permanently locked with an open journal fd
            if self._journal_live:
                from hadoop_bam_tpu.utils.errors import PlanError
                raise PlanError(
                    f"a journaled join over {self.journal_path} is "
                    f"already in progress on this dataset")
            self._journal_live = True
            jr = None
            try:
                anchor, _k, digest = self.manifest.identity()
                jr, state = jj.JobJournal.resume(
                    self.journal_path, kind="cohort_join",
                    inputs=[(anchor or "<inline-manifest>", digest)],
                    output=None,
                    fingerprint=jj.config_fingerprint(
                        self.config, COHORT_FINGERPRINT_FIELDS),
                    config_values=jj.fingerprint_values(
                        self.config, COHORT_FINGERPRINT_FIELDS),
                    # the plan digest rides the params (the IR-level
                    # twin of the spill sort's span plan_digest): a
                    # resume whose compiled plan differs — changed
                    # manifest identity, changed unit-partitioning
                    # knobs — refuses instead of mis-stitching chunks
                    params=plan_journal_params(self.plan(), {
                        "manifest":
                            (os.path.abspath(self.manifest.path)
                             if self.manifest.path else None)}),
                    fsync=bool(getattr(self.config, "journal_fsync",
                                       True)))
                replayed = []
                if state is not None:
                    while True:
                        u = state.unit("chunk", len(replayed))
                        if u is None or not jj.verify_artifact(
                                u.get("path", ""), u.get("size", -1),
                                u.get("crc", "")):
                            break
                        replayed.append(u)
                    jj.sweep_unrecorded(
                        chunks_dir, [u["path"] for u in replayed],
                        counter="jobs.stale_chunks_swept")
                # finished job with every chunk intact: pure replay,
                # the input streams are never touched (zero decode)
                replay_only = (state is not None
                               and state.done is not None
                               and int(state.done.get("chunks", -1))
                               == len(replayed))
                last_key = None
                for u in replayed:
                    METRICS.count("jobs.chunks_replayed")
                    last_key = (int(u.get("key_hi", 0)),
                                int(u.get("key_lo", 0)))
                    yield load(u)
                if replay_only:
                    METRICS.count("jobs.jobs_skipped")
                    return
                if replayed:
                    METRICS.count("jobs.cohort_resumes")
                os.makedirs(chunks_dir, exist_ok=True)
                seen_q = set(self.manifest.quarantined)
                i = len(replayed)
                for chunk in iter_joined_chunks(
                        self.manifest, streams,
                        self.geometry.samples_pad, self.config,
                        skip_through_key=last_key):
                    for sid in sorted(set(self.manifest.quarantined)
                                      - seen_q):
                        # observability, not replayed state: a
                        # deterministic fault re-fires on resume, a
                        # transient one heals (docstring)
                        jr.event("quarantine", sample=sid)
                        seen_q.add(sid)
                    # abspath (chunks_dir is absolute): the unit record
                    # must verify from any cwd `hbam resume` runs in
                    path = os.path.join(chunks_dir,
                                        f"chunk-{i:05d}.npz")
                    np.savez(path, **chunk)
                    size, crc = jj.file_digest(path)
                    jr.unit_done(
                        "chunk", i, path=path, size=size, crc=crc,
                        sites=int(chunk["pos"].shape[0]),
                        # the continuation key: group keys strictly
                        # increase, so the last row's (chrom, pos) IS
                        # the chunk's high-water mark
                        key_hi=int(chunk["chrom"][-1]),
                        key_lo=int(chunk["pos"][-1]))
                    i += 1
                    yield chunk
                jr.job_done(chunks=i)
            finally:
                self._journal_live = False
                if jr is not None:
                    jr.close()

        return gen()

    # -- mesh feed -----------------------------------------------------------

    def plan(self):
        """This cohort's compiled PlanIR (plan/builders.cohort_plan):
        the identity the journal seam records and ``hbam explain
        cohort`` prints."""
        from hadoop_bam_tpu.plan import builders
        return builders.cohort_plan(self.manifest, self.config,
                                    geometry=self.geometry)

    def tensor_batches(self, mesh=None, geometry=None) -> Iterator[Dict]:
        """Yield device-resident joined tensor batches (class
        docstring).  Compiles to a plan and runs through the one
        executor, which owns the feed discipline shared with
        ``VcfDataset.tensor_batches``: ring-slot groups, async
        device_put with in-flight handles, fixed-shape tiles.  Lazy:
        no join work (and no journal open) until first iteration.

        The compiled plan is ALWAYS ``self.plan()`` — the join identity
        the journal seam records: ``site_chunks`` joins with
        ``self.geometry`` regardless of a feed-geometry override here
        (``geometry`` only re-tiles the mesh feed), so the executing
        plan and the journaled plan_digest can never diverge."""
        from hadoop_bam_tpu.plan import executor as plan_executor

        return plan_executor.execute(self.plan(), config=self.config,
                                     mesh=mesh, geometry=geometry,
                                     dataset=self)

    # -- drivers -------------------------------------------------------------

    def gwas(self, phenotype=None, mesh=None) -> Dict[str, np.ndarray]:
        """Per-variant GWAS columns (cohort/gwas.py): allele frequency,
        call rate, HWE chi-square, and — with a phenotype vector — the
        score-test association chi-square."""
        from hadoop_bam_tpu.cohort.gwas import cohort_gwas
        return cohort_gwas(self, phenotype=phenotype, mesh=mesh,
                           config=self.config)


def open_cohort(source: Union[str, CohortManifest, List[str]],
                config: HBamConfig = DEFAULT_CONFIG,
                journal_path: Optional[str] = None) -> CohortDataset:
    """Resolve a manifest (path / object / bare path list) into the
    cohort dataset — the cohort analog of ``api.open_vcf``.
    ``journal_path`` makes the join crash-safe (``site_chunks``)."""
    return CohortDataset(source, config, journal_path=journal_path)
