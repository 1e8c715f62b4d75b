"""The fused preprocessing pipeline: read -> mesh sort exchange ->
markdup -> indexed write, as ONE journaled run.

Composition, not new machinery: the sort half IS the spill byte
exchange from ``parallel/mesh_sort.py`` (same planner, same bucket
boundaries protocol, same framed spill runs and per-bucket k-way
merge), extended in the SAME jitted step with the duplicate-signature
column unpack (prep/markdup.py) so the markdup keys are computed while
the record bytes are already resident on device — records never
re-inflate between stages.  The duplicate bits then ride a second,
columns-only exchange (7 uint32s per record, never the payload), and
the FLAG patch is applied per frame during the shard write, between the
spill merge and the BGZF deflate.

Journal grains (``jobs/``), one per stage:

- ``round``  — each sort round's spilled runs + its signature-column
  sidecar (size+CRC verified on resume; partial rounds swept);
- ``markdup`` — the duplicate bitmap over global record indices;
- ``shard``  — each written output part (ShardedFileWriter's protocol).

A SIGKILL at any stage boundary resumes byte-identically: completed
rounds are not re-decoded, a completed bitmap is not re-exchanged,
committed parts are not re-deflated (``jobs.rounds_skipped`` /
``jobs.markdup_skipped`` / ``jobs.shards_skipped``).

Semantics are pinned byte-for-byte against ``prep.oracle`` — see its
docstrings for the signature/score/patch contract and the documented
deviations from Picard (PARITY.md).
"""
from __future__ import annotations

from typing import List, Optional, Tuple

import numpy as np

from hadoop_bam_tpu.config import DEFAULT_CONFIG, HBamConfig
from hadoop_bam_tpu.formats.bam import SAMHeader
from hadoop_bam_tpu.utils.errors import CorruptDataError, PlanError

DEFAULT_ROUND_RECORDS = 1_000_000


def markdup_bam_mesh(input_path: str, output_path: str, *,
                     mesh=None, config: HBamConfig = DEFAULT_CONFIG,
                     header: Optional[SAMHeader] = None,
                     remove_duplicates: bool = False,
                     library_from: str = "none",
                     round_records: Optional[int] = None,
                     journal_path: Optional[str] = None) -> int:
    """Mark duplicates in ``input_path`` and write the coordinate-sorted
    result to ``output_path`` in one fused mesh pass (module docstring).
    Returns the number of records written.  Byte-identical to
    ``oracle.markdup_bam_oracle`` with the same options.

    Spilled runs, the column sidecars, the duplicate bitmap, and the
    output parts all live in ``<output>.mkdup-spill``; the directory is
    removed on success (or on failure without a journal — with one, the
    completed units ARE the resume state and must survive)."""
    import shutil

    import jax

    from hadoop_bam_tpu.parallel.mesh import make_mesh

    if jax.process_count() > 1:
        raise PlanError(
            "the fused markdup pipeline is single-process for now: the "
            "duplicate bitmap and the journal protocol assume one host; "
            "run under a single process (multi-host markdup needs the "
            "distributed journal protocol first)")
    if mesh is None:
        mesh = make_mesh()
    if round_records is None:
        round_records = DEFAULT_ROUND_RECORDS
    if int(round_records) <= 0:
        raise PlanError(f"round_records must be positive, got "
                        f"{round_records}")
    ok = False
    try:
        n = _markdup_bam_mesh_impl(
            input_path, output_path, mesh=mesh, config=config,
            header=header, remove_duplicates=bool(remove_duplicates),
            library_from=library_from, round_records=int(round_records),
            journal_path=journal_path)
        ok = True
        return n
    finally:
        keep = bool(getattr(config, "debug_keep_spill", False)) \
            or (journal_path is not None and not ok)
        if not keep:
            shutil.rmtree(output_path + ".mkdup-spill",
                          ignore_errors=True)


def _markdup_bam_mesh_impl(input_path: str, output_path: str, *, mesh,
                           config: HBamConfig,
                           header: Optional[SAMHeader],
                           remove_duplicates: bool, library_from: str,
                           round_records: int,
                           journal_path: Optional[str]) -> int:
    import os
    import shutil

    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from hadoop_bam_tpu.formats.bamio import BamWriter, read_bam_header
    from hadoop_bam_tpu.parallel.mesh_sort import (
        _I32_SENTINEL, _buckets, _frame_run, _iter_run_frames, _keys_of,
        _pack_record_rows, _record_lens, _round_up, _sample_bounds,
        check_global_index_ceiling,
    )
    from hadoop_bam_tpu.parallel.pipeline import _decode_span_core
    from hadoop_bam_tpu.prep.markdup import (
        _make_fused_sort_markdup_step, _make_markdup_exchange_step,
        host_kmax,
    )
    from hadoop_bam_tpu.prep.oracle import library_column, library_map
    from hadoop_bam_tpu.split.planners import plan_bam_spans_balanced
    from hadoop_bam_tpu.utils.metrics import METRICS
    from hadoop_bam_tpu.utils.sort import _sorted_header
    from hadoop_bam_tpu.write import (
        ShardedFileWriter, write_bam_shards_concat,
    )

    mesh_devs = list(mesh.devices.ravel())
    n_dev = len(mesh_devs)
    if header is None:
        header, _ = read_bam_header(input_path)
    rg_to_lib = library_map(header, library_from)

    jr = None
    resume = None
    if journal_path is not None:
        from hadoop_bam_tpu.jobs import journal as jj
        from hadoop_bam_tpu.jobs.runner import (
            SORT_FINGERPRINT_FIELDS, plan_journal_params,
        )
        from hadoop_bam_tpu.plan import builders
        plan_ir = builders.mkdup_plan(
            input_path, output_path, config,
            remove_duplicates=remove_duplicates,
            library_from=library_from)
        jr, resume = jj.JobJournal.resume(
            journal_path, kind="mkdup",
            inputs=[(os.path.abspath(input_path),
                     jj.file_identity_digest(input_path))],
            output=os.path.abspath(output_path),
            fingerprint=jj.config_fingerprint(config,
                                              SORT_FINGERPRINT_FIELDS),
            config_values=jj.fingerprint_values(config,
                                                SORT_FINGERPRINT_FIELDS),
            params=plan_journal_params(plan_ir, {
                "input": os.path.abspath(input_path),
                "output": os.path.abspath(output_path),
                "remove_duplicates": bool(remove_duplicates),
                "library_from": library_from,
                "round_records": int(round_records),
                "n_dev": n_dev,
            }),
            fsync=bool(getattr(config, "journal_fsync", True)))
        if resume is not None and resume.done is not None:
            d = resume.done
            if jj.verify_artifact(output_path, d.get("size", -1),
                                  d.get("crc", "")):
                METRICS.count("jobs.jobs_skipped")
                jr.close()
                return int(d.get("records", 0))

    def plan():
        from hadoop_bam_tpu.split.splitting_index import (
            SplittingIndex, build_splitting_index,
        )
        index = SplittingIndex.load_for(input_path)
        fine = max(1, round_records // 8)
        if index is None or (index.granularity or 1) > fine:
            index = build_splitting_index(input_path, granularity=fine)
        n_samples = max(1, len(index.voffsets) - 1)
        if index.total_records > 0:
            total_est = index.total_records
            check_global_index_ceiling(total_est, "fused markdup plan")
        else:
            total_est = n_samples * max(1, index.granularity)
        want = -(-total_est // max(1, round_records))
        want = _round_up(want, n_dev)
        return plan_bam_spans_balanced(input_path, want, header=header,
                                       index=index)

    spans = plan()
    n_rounds = max(1, -(-len(spans) // n_dev))

    shard_dir = output_path + ".mkdup-spill"
    resumed_rounds: dict = {}
    markdup_unit = None
    bounds_ev = None
    if jr is not None:
        pd = jj.plan_digest(spans)
        plan_ev = resume.last_event("plan") if resume is not None else None
        if plan_ev is not None and plan_ev.get("digest") != pd:
            raise PlanError(
                f"refusing to resume {journal_path}: the span plan no "
                f"longer matches the journaled run (journal digest "
                f"{plan_ev.get('digest')!r}, now {pd!r}) — the input's "
                f"splitting-index state changed; delete the journal to "
                f"start over")
        if plan_ev is None:
            jr.event("plan", digest=pd, n_spans=len(spans),
                     n_rounds=int(n_rounds))
        if resume is not None:
            bounds_ev = resume.last_event("bounds")
            for t in range(n_rounds):
                u = resume.unit("round", t)
                if u is None:
                    continue
                runs = list(u.get("runs", []))
                cols = u.get("cols")
                if (all(jj.verify_artifact(p, s, c) for _b, p, s, c
                        in runs)
                        and cols is not None
                        and jj.verify_artifact(*cols)):
                    resumed_rounds[t] = u
            mu = resume.unit("markdup", 0)
            if mu is not None and jj.verify_artifact(
                    mu.get("path", ""), mu.get("size", -1),
                    mu.get("crc", "")):
                markdup_unit = mu
            recorded = [p for u in resumed_rounds.values()
                        for _b, p, s, c in u.get("runs", [])]
            recorded += [u["cols"][0] for u in resumed_rounds.values()]
            if markdup_unit is not None:
                recorded.append(markdup_unit["path"])
            jj.sweep_unrecorded(shard_dir, recorded,
                                counter="jobs.stale_runs_swept")
            if resumed_rounds and bounds_ev is None:
                raise PlanError(
                    f"refusing to resume {journal_path}: completed "
                    f"rounds are recorded but the round-0 bucket "
                    f"boundaries are not — later rounds re-bucketed "
                    f"under fresh boundaries would break the global "
                    f"order; delete the journal to start over")
            spans_skipped = sum(
                min((t + 1) * n_dev, len(spans)) - t * n_dev
                for t in resumed_rounds)
            if resumed_rounds:
                METRICS.count("jobs.rounds_skipped", len(resumed_rounds))
                METRICS.count("jobs.spans_skipped", spans_skipped)
            jr.event("resume_plan", rounds_total=int(n_rounds),
                     rounds_skipped=len(resumed_rounds),
                     spans_skipped=int(spans_skipped))
    if not resumed_rounds and markdup_unit is None:
        shutil.rmtree(shard_dir, ignore_errors=True)
    os.makedirs(shard_dir, exist_ok=True)

    sharding = NamedSharding(mesh, P("data"))
    rep = NamedSharding(mesh, P())

    def sharded(shape, dtype, of_d):
        return jax.make_array_from_single_device_arrays(
            shape, sharding,
            [jax.device_put(np.asarray(of_d(d), dtype=dtype),
                            mesh_devs[d]) for d in range(n_dev)])

    def replicated(arr, dtype):
        arr = np.asarray(arr, dtype=dtype)
        return jax.make_array_from_single_device_arrays(
            arr.shape, rep,
            [jax.device_put(arr, mesh_devs[d]) for d in range(n_dev)])

    # ---------------- stage 1: fused sort exchange + column unpack ----
    step_cache = {}
    bhi = blo = None
    bhi_g = blo_g = None
    prefix_total = 0
    run_files: dict = {}               # bucket -> [run paths]
    col_files: List[str] = []          # per-round signature sidecars

    with METRICS.span("prep.sort_wall"):
        for t in range(n_rounds):
            if t in resumed_rounds:
                u = resumed_rounds[t]
                for b, p, _s, _c in u.get("runs", []):
                    run_files.setdefault(int(b), []).append(p)
                col_files.append(u["cols"][0])
                prefix_total += int(u.get("round_total", 0))
                continue
            decoded = {}
            counts_vec = np.zeros(n_dev, np.int64)
            max_len = 0
            kmax = 0
            his: List[np.ndarray] = []
            los: List[np.ndarray] = []
            for d in range(n_dev):
                s = t * n_dev + d
                if s >= len(spans):
                    continue
                data, offs, _v, _ = _decode_span_core(
                    input_path, spans[s], False, "auto",
                    want_voffs=False)
                lens_ = _record_lens(data, offs)
                libs = library_column(data, offs, lens_, rg_to_lib)
                decoded[d] = (data, offs, lens_, libs)
                counts_vec[d] = offs.size
                if offs.size:
                    max_len = max(max_len, int(lens_.max()))
                    kmax = max(kmax, host_kmax(data, offs))
                if t == 0:
                    h, l = _keys_of(data, offs)
                    his.append(h)
                    los.append(l)

            if bhi is None:
                if bounds_ev is not None:
                    bhi = np.asarray(bounds_ev["bhi"], np.uint32)
                    blo = np.asarray(bounds_ev["blo"], np.uint32)
                else:
                    bhi, blo = _sample_bounds(his, los, n_dev)
                    if jr is not None:
                        jr.event("bounds",
                                 bhi=[int(x) for x in bhi],
                                 blo=[int(x) for x in blo])
                bhi_g = replicated(bhi, jnp.uint32)
                blo_g = replicated(blo, jnp.uint32)

            METRICS.count("mesh_sort.rounds")
            METRICS.count_per_device("mesh_sort.device_rows", counts_vec)
            round_total = int(counts_vec.sum())
            check_global_index_ceiling(prefix_total + round_total,
                                       "fused markdup (mid-run backstop)")
            base_vec = prefix_total + np.concatenate(
                [[0], np.cumsum(counts_vec[:-1])])
            prefix_total += round_total

            records_cap = _round_up(max(int(counts_vec.max()), 1), 1024)
            stride = 1 << max(6, int(max(max_len, 36) - 1).bit_length())
            kpow = 0 if kmax == 0 else 1 << (kmax - 1).bit_length()
            key = (records_cap, stride, kpow)
            if key not in step_cache:
                step_cache[key] = _make_fused_sort_markdup_step(
                    mesh, records_cap, stride, kpow)
            step = step_cache[key]

            _empty = (np.zeros(0, np.uint8), np.zeros(0, np.int64),
                      np.zeros(0, np.int64), np.zeros(0, np.uint32))
            packed = {}
            lib_cols = {}
            for d in range(n_dev):
                data, offs, lens_, libs = decoded.pop(d, _empty)
                packed[d] = _pack_record_rows(data, offs, lens_,
                                              records_cap, stride)
                lc = np.zeros(records_cap, np.uint32)
                lc[:libs.size] = libs
                lib_cols[d] = lc
            del decoded

            rows_g = sharded((n_dev, records_cap, stride), jnp.uint8,
                             lambda d: packed[d][0][None])
            lens_g = sharded((n_dev, records_cap), jnp.int32,
                             lambda d: packed[d][1][None])
            count_g = sharded((n_dev,), jnp.int32,
                              lambda d: np.asarray([counts_vec[d]],
                                                   np.int32))
            base_g = sharded((n_dev,), jnp.int32,
                             lambda d: np.asarray([base_vec[d]],
                                                  np.int32))
            lib_g = sharded((n_dev, records_cap), jnp.uint32,
                            lambda d: lib_cols[d][None])
            (rows_s, lens_s, six_s, k0_s, k1_s, k2_s, k3_s, k4_s,
             score_s, elig_s) = step(rows_g, lens_g, count_g, base_g,
                                     lib_g, bhi_g, blo_g)

            # spill the round's buckets as framed sorted runs (the sort
            # half, identical to mesh_sort's spill protocol)
            b_rows, b_lens, b_six = (_buckets(rows_s), _buckets(lens_s),
                                     _buckets(six_s))
            round_runs: List[Tuple[int, str]] = []
            for b in sorted(b_rows):
                keep = b_six[b] != _I32_SENTINEL
                if not bool(keep.any()):
                    continue
                rows_k = b_rows[b][keep]
                lens_k = b_lens[b][keep]
                six_k = b_six[b][keep]
                hi_k, lo_k = _keys_of(
                    np.ascontiguousarray(rows_k).ravel(),
                    np.arange(rows_k.shape[0], dtype=np.int64)
                    * rows_k.shape[1])
                path = os.path.join(shard_dir, f"b{b:05d}-r{t:05d}.run")
                with open(path, "wb") as f:
                    f.write(_frame_run(rows_k, lens_k, six_k, hi_k,
                                       lo_k))
                run_files.setdefault(b, []).append(path)
                round_runs.append((b, path))

            # spill the round's signature columns (the markdup half):
            # eligible records only — 28 bytes per record, not payload
            cols_d = {n: _buckets(a) for n, a in (
                ("k0", k0_s), ("k1", k1_s), ("k2", k2_s), ("k3", k3_s),
                ("k4", k4_s), ("score", score_s), ("elig", elig_s))}
            parts = {n: [] for n in ("k0", "k1", "k2", "k3", "k4",
                                     "score", "gidx")}
            for d in range(n_dev):
                cnt = int(counts_vec[d])
                el = cols_d["elig"][d][:cnt].astype(bool)
                for n in ("k0", "k1", "k2", "k3", "k4", "score"):
                    parts[n].append(cols_d[n][d][:cnt][el])
                parts["gidx"].append(
                    (base_vec[d] + np.arange(cnt, dtype=np.int64))[el]
                    .astype(np.int32))
            cpath = os.path.join(shard_dir, f"cols-r{t:05d}.npz")
            with open(cpath, "wb") as f:
                np.savez(f, **{n: np.concatenate(v) if v else
                               np.zeros(0, np.uint32)
                               for n, v in parts.items()})
            col_files.append(cpath)

            if jr is not None:
                jr.unit_done(
                    "round", t,
                    runs=[[b, os.path.abspath(p), *jj.file_digest(p)]
                          for b, p in round_runs],
                    cols=[os.path.abspath(cpath),
                          *jj.file_digest(cpath)],
                    round_total=int(round_total))

    total = prefix_total

    # ---------------- stage 2: duplicate-group exchange ---------------
    with METRICS.span("prep.markdup_wall"):
        if markdup_unit is not None:
            dup_bits = np.fromfile(markdup_unit["path"], np.uint8)
            if dup_bits.size != total:
                raise CorruptDataError(
                    f"journaled duplicate bitmap covers {dup_bits.size} "
                    f"records but the plan decodes {total} — the spill "
                    f"state is inconsistent; delete the journal to "
                    f"start over")
            n_dups = int(dup_bits.sum())
            METRICS.count("jobs.markdup_skipped")
        else:
            sig = {n: [] for n in ("k0", "k1", "k2", "k3", "k4",
                                   "score", "gidx")}
            for cpath in col_files:
                with np.load(cpath) as z:
                    for n in sig:
                        sig[n].append(z[n])
            sig = {n: np.concatenate(v) if v else np.zeros(0, np.uint32)
                   for n, v in sig.items()}
            m = int(sig["gidx"].size)
            dup_bits = np.zeros(total, np.uint8)
            if m:
                n_per = -(-m // n_dev)
                cap2 = _round_up(max(n_per, 1), 1024)
                step2 = _make_markdup_exchange_step(mesh, cap2)

                def slice_of(arr, d, dtype):
                    part = arr[d * n_per:min((d + 1) * n_per, m)]
                    out = np.zeros(cap2, dtype)
                    out[:part.size] = part
                    return out[None]

                args2 = [sharded((n_dev, cap2), jnp.uint32,
                                 lambda d, a=sig[n]: slice_of(
                                     a, d, np.uint32))
                         for n in ("k0", "k1", "k2", "k3", "k4",
                                   "score")]
                args2.append(sharded((n_dev, cap2), jnp.int32,
                                     lambda d: slice_of(sig["gidx"], d,
                                                        np.int32)))
                args2.append(sharded(
                    (n_dev,), jnp.int32,
                    lambda d: np.asarray(
                        [max(0, min(n_per, m - d * n_per))], np.int32)))
                six2, dup2 = step2(*args2)
                b_six, b_dup = _buckets(six2), _buckets(dup2)
                for d in range(n_dev):
                    s_arr, du = b_six[d], b_dup[d]
                    okm = s_arr != _I32_SENTINEL
                    dup_bits[s_arr[okm & (du == 1)]] = 1
            n_dups = int(dup_bits.sum())
            dpath = os.path.join(shard_dir, "dupbits.u8")
            with open(dpath, "wb") as f:
                f.write(dup_bits.tobytes())
            if jr is not None:
                jr.unit_done("markdup", 0, path=os.path.abspath(dpath),
                             size=jj.file_digest(dpath)[0],
                             crc=jj.file_digest(dpath)[1],
                             n_dups=n_dups, total=int(total))
        METRICS.count("prep.duplicates_marked", n_dups)

    # ---------------- stage 3: patched per-bucket merge + write -------
    from hadoop_bam_tpu.split.kmerge import kmerge

    out_header = _sorted_header(header, by_name=False)
    written = 0
    with METRICS.span("prep.write_wall"):
        sw = ShardedFileWriter(output_path, n_dev,
                               dir_suffix=".mkdup-spill/parts",
                               resume_state=resume)
        if resume is not None:
            sw.sweep_stale_temps()
        for b in range(n_dev):
            if jr is not None and sw.shard_committed(b):
                written += int(resume.unit("shard", b).get("records", 0))
                continue
            chunks: List[bytes] = []
            n_b = 0
            for (hi, lo, gidx), payload in kmerge(
                    (_iter_run_frames(p)
                     for p in run_files.get(b, [])),
                    key=lambda kv: kv[0]):
                dup = int(dup_bits[gidx])
                if remove_duplicates and dup:
                    continue
                flag = int.from_bytes(payload[18:20], "little")
                nf = (flag & ~0x400) | (0x400 if dup else 0)
                if nf != flag:
                    payload = (payload[:18]
                               + nf.to_bytes(2, "little")
                               + payload[20:])
                chunks.append(payload)
                n_b += 1
            # every bucket writes its part — empty included — so the
            # concatenation sees the full deterministic part set
            with sw.open_shard(b) as f:
                with BamWriter(f, out_header, write_header=False,
                               write_eof=False,
                               level=config.write_compress_level) as w:
                    w.write_raw(b"".join(chunks), n_records=n_b)
            written += n_b
            if jr is not None:
                part = sw.shard_path(b)
                size, crc = jj.file_digest(part)
                jr.unit_done("shard", b, path=os.path.abspath(part),
                             size=size, crc=crc, records=n_b)

        expected = total - (n_dups if remove_duplicates else 0)
        if written != expected:
            raise CorruptDataError(
                f"fused markdup wrote {written} of {expected} records "
                f"— output is invalid")
        sw.concatenate(
            lambda parts: write_bam_shards_concat(
                parts, output_path, out_header, config=config),
            what="fused markdup write", cleanup=False)

    if jr is not None:
        size, crc = jj.file_digest(output_path)
        jr.job_done(records=int(written), size=size, crc=crc)
        jr.close()
    return written
