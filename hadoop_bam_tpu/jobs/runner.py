"""Job-level resume glue: fingerprints, the `hbam resume`/`hbam jobs`
entry points, and the generic job-grain idempotence wrapper.

The journal (jobs/journal.py) is mechanism; this module is policy —
which config fields participate in each job kind's resume contract, how
a journal's header maps back to the pipeline invocation that wrote it,
and what ``hbam jobs`` reports about a directory of journals.
"""
from __future__ import annotations

import dataclasses
import glob
import os
from typing import Callable, Dict, List, Optional, Sequence

from hadoop_bam_tpu.config import resolve_inflate_backend
from hadoop_bam_tpu.jobs import journal as jj
from hadoop_bam_tpu.obs.context import ensure_trace
from hadoop_bam_tpu.utils.errors import PlanError
from hadoop_bam_tpu.utils.metrics import METRICS

# Output-affecting config fields per job kind — the resume contract's
# fingerprint (jobs/journal.config_fingerprint).  Observability /
# scheduling knobs are deliberately absent: changing a trace flag must
# not strand a resumable journal; changing anything that alters the
# published BYTES (or the unit partitioning the journal indexes) must.
SORT_FINGERPRINT_FIELDS = (
    "write_compress_level", "write_header", "write_terminator",
    "write_index_kinds", "splitting_index_granularity",
)
COHORT_FINGERPRINT_FIELDS = (
    "cohort_chunk_sites", "cohort_quarantine_inputs",
    "cohort_max_quarantine_fraction",
)


def plan_journal_params(plan, extra: Optional[Dict] = None) -> Dict:
    """Journal params carrying a compiled plan's IR digest — the
    IR-level twin of ``journal.plan_digest(spans)``.  Where the span
    digest pins the CUT GEOMETRY of a pinned span plan, the plan digest
    pins the compiled workload itself (source identity, op DAG, the
    unit-partitioning knobs the builder folded in), so a resume whose
    plan compiles differently refuses inside ``JobJournal.resume``'s
    params match instead of silently mis-joining units."""
    out = dict(extra or {})
    out["plan_digest"] = plan.digest()
    return out


def sort_job_params(input_path: str, output_path: str, *,
                    exchange: Optional[str],
                    round_records: Optional[int],
                    n_dev: Optional[int] = None) -> Dict:
    """The spill sort's params carry ``n_dev``: round units are cut per
    device position, so resuming on a different mesh size must refuse
    (params mismatch) instead of mis-stitching rounds.  The resident
    modes omit it — their output is byte-identical at any mesh size and
    they only resume at job grain."""
    out = {"input": os.path.abspath(input_path),
           # abspath both endpoints: a job journaled with a relative
           # spelling must resume from `hbam resume` (which re-plans
           # from the journal's params) without a spurious mismatch
           "output": os.path.abspath(output_path),
           "exchange": exchange,
           "round_records": (None if round_records is None
                             else int(round_records))}
    if n_dev is not None:
        out["n_dev"] = int(n_dev)
    return out


def run_job_level(journal_path: str, *, kind: str, config,
                  inputs: Sequence[str], output: str, params: Dict,
                  run: Callable[[], int],
                  fingerprint_fields: Sequence[str] = SORT_FINGERPRINT_FIELDS
                  ) -> int:
    """Idempotence at JOB grain for pipelines whose whole run is one
    unit of work: a journal whose ``job_done`` record matches the
    (verified) output makes the re-run a no-op; anything else re-runs
    ``run()`` and commits the result.  Mismatched identity/fingerprint/
    params refuse inside ``JobJournal.resume``."""
    output = os.path.abspath(output)
    # job start is an entry point: the minted (or joined) trace id is
    # stamped onto every journal line this run writes
    with ensure_trace(op=f"job.{kind}"):
        jr, state = jj.JobJournal.resume(
            journal_path, kind=kind,
            inputs=[(os.path.abspath(p), jj.file_identity_digest(p))
                    for p in inputs],
            output=output,
            fingerprint=jj.config_fingerprint(config, fingerprint_fields),
            config_values=jj.fingerprint_values(config,
                                                fingerprint_fields),
            params=params,
            fsync=bool(getattr(config, "journal_fsync", True)))
        with jr:
            if state is not None and state.done is not None:
                d = state.done
                if jj.verify_artifact(output, d.get("size", -1),
                                      d.get("crc", "")):
                    METRICS.count("jobs.jobs_skipped")
                    return int(d.get("records", 0))
            n = int(run())
            size, crc = jj.file_digest(output)
            jr.job_done(records=n, size=size, crc=crc)
            return n


# ---------------------------------------------------------------------------
# hbam resume
# ---------------------------------------------------------------------------

def resume_job(journal_path: str, config=None) -> Dict:
    """Re-drive the job a journal describes (the ``hbam resume`` verb).

    Reads only the journal HEADER here; all verification (input
    identity, config fingerprint, plan digest, per-unit artifacts)
    happens inside the pipeline itself when it re-opens the journal —
    resume is a plain re-invocation, which is what makes it correct
    under repeated crashes (resuming a resume is the same code path).

    Returns a summary dict: kind, output, records/chunks, and the skip
    counters the resumed run recorded."""
    from hadoop_bam_tpu.config import DEFAULT_CONFIG

    config = DEFAULT_CONFIG if config is None else config
    state = jj.JobJournal.replay(journal_path)
    kind = state.kind
    with ensure_trace(op=f"job.resume.{kind}"):
        return _resume_replayed(journal_path, config, state, kind)


def _resume_replayed(journal_path: str, config, state, kind: str) -> Dict:
    params = dict(state.header.get("params", {}))
    # the header records the fingerprinted field VALUES: reconstruct the
    # job's output-affecting config on top of the caller's, so a job
    # journaled with non-default knobs (a custom compression level, a
    # different chunk size) resumes from the bare CLI instead of
    # refusing on its own fingerprint
    recorded = {k: v for k, v in dict(state.header.get("config",
                                                       {})).items()
                if hasattr(config, k)}
    if recorded:
        config = dataclasses.replace(config, **recorded)
    # a header naming a decode plane this build does not have (one
    # written before PR 30 under inflate_backend="device") refuses with
    # PlanError here instead of resuming on another plane
    resolve_inflate_backend(config)
    if kind in ("mesh_sort_spill", "mesh_sort"):
        from hadoop_bam_tpu.parallel.mesh_sort import sort_bam_mesh

        n = sort_bam_mesh(
            params["input"], params["output"],
            config=config,
            exchange=params.get("exchange"),
            round_records=params.get("round_records"),
            journal_path=journal_path)
        return {"kind": kind, "output": params["output"], "records": n}
    if kind == "mkdup":
        from hadoop_bam_tpu.prep.pipeline import markdup_bam_mesh

        n = markdup_bam_mesh(
            params["input"], params["output"],
            config=config,
            remove_duplicates=bool(params.get("remove_duplicates",
                                              False)),
            library_from=params.get("library_from", "none"),
            round_records=params.get("round_records"),
            journal_path=journal_path)
        return {"kind": kind, "output": params["output"], "records": n}
    if kind == "cohort_join":
        from hadoop_bam_tpu.cohort.dataset import open_cohort

        manifest = params.get("manifest")
        if not manifest:
            raise PlanError(
                f"journal {journal_path} records an inline-manifest "
                f"cohort job — only manifest-file cohort jobs are "
                f"resumable from the CLI; resume through the library "
                f"(CohortDataset(..., journal_path=...))")
        ds = open_cohort(manifest, config=config,
                         journal_path=journal_path)
        sites = 0
        chunks = 0
        for chunk in ds.site_chunks():
            sites += int(chunk["pos"].shape[0])
            chunks += 1
        return {"kind": kind, "output": None, "chunks": chunks,
                "sites": sites,
                "quarantined": sorted(ds.manifest.quarantined)}
    raise PlanError(
        f"journal {journal_path} records job kind {kind!r}, which has "
        f"no CLI resume driver (resumable kinds: mesh_sort_spill, "
        f"mesh_sort, mkdup, cohort_join)")


# ---------------------------------------------------------------------------
# hbam jobs
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class JobInfo:
    path: str
    kind: str
    status: str        # done | resumable | fresh | corrupt
    units: int
    output: Optional[str]
    detail: str = ""
    # machine-readable extras (`hbam jobs --json` / `hbam top`):
    trace_id: Optional[str] = None      # trace that wrote the header
    units_skipped: int = 0              # units a resume verified+skipped
    resumes: int = 0                    # resume events recorded


# the grain a resumed job skips completed work at, per journal kind —
# the `hbam jobs --json` / `hbam top` vocabulary (README crash-recovery
# table is the human-readable twin)
RESUME_GRAINS = {
    "mesh_sort_spill": "round",
    "mesh_sort": "job",
    "mkdup": "round",
    "cohort_join": "chunk",
    "shard_write": "part",
}


def resume_grain(kind: str) -> str:
    return RESUME_GRAINS.get(kind, "job")


def job_info_doc(info: JobInfo) -> Dict:
    """THE machine-readable job row — the one parser ``hbam jobs
    --json``, ``hbam top`` and external schedulers share.  Keys are a
    stable contract: path/kind/status/output, the journal-writing
    trace_id, the resume grain, and units committed/skipped."""
    return {
        "path": info.path,
        "kind": info.kind,
        "status": info.status,
        "output": info.output,
        "detail": info.detail or None,
        "trace_id": info.trace_id,
        "resume_grain": resume_grain(info.kind),
        "units_total": info.units,
        "units_skipped": info.units_skipped,
        "resumes": info.resumes,
    }


def job_status(journal_path: str) -> JobInfo:
    """One journal's summary row, never raising: a corrupt journal is a
    listable fact, not a listing failure."""
    try:
        state = jj.JobJournal.replay(journal_path)
    except Exception as e:  # noqa: BLE001 — report, don't die
        return JobInfo(path=journal_path, kind="?", status="corrupt",
                       units=0, output=None,
                       detail=f"{type(e).__name__}: {e}")
    trace_id = state.header.get("trace")
    resumes = [e for e in state.events if e.get("name") == "resume"]
    # units the LAST resume found committed = what that resume verified
    # and skipped instead of re-running
    units_skipped = int(resumes[-1].get("prior_units", 0)) \
        if resumes else 0
    if state.done is not None:
        output = state.header.get("output")
        if output is None:
            # chunk-replay jobs (cohort join) publish no single output
            # file — their artifacts are the journaled units themselves
            detail = "no published output (unit-replay job)"
        elif jj.verify_artifact(output, state.done.get("size", -1),
                                state.done.get("crc", "")):
            detail = "output verified"
        else:
            detail = "output missing/changed since job_done"
        return JobInfo(
            path=journal_path, kind=state.kind, status="done",
            units=len(state.units), output=output, detail=detail,
            trace_id=trace_id, units_skipped=units_skipped,
            resumes=len(resumes))
    status = "resumable" if state.units else "fresh"
    detail = "torn tail (expected after a crash)" if state.torn_tail \
        else ""
    return JobInfo(path=journal_path, kind=state.kind, status=status,
                   units=len(state.units),
                   output=state.header.get("output"), detail=detail,
                   trace_id=trace_id, units_skipped=units_skipped,
                   resumes=len(resumes))


def list_jobs(directory: str = ".") -> List[JobInfo]:
    """Every ``*.hbam-journal`` under ``directory`` (non-recursive),
    summarized."""
    out = []
    for p in sorted(glob.glob(os.path.join(directory,
                                           "*" + jj.JOURNAL_SUFFIX))):
        out.append(job_status(p))
    return out
