"""Mesh bucketed sort — the MapReduce-shuffle analog on a device mesh.

The reference's CLI ``sort`` keyed records into the MR shuffle and let
Hadoop's distributed external merge do the work (SURVEY.md section 2.9
shuffle row).  This module is that shuffle as XLA collectives:

1. span planning assigns each device a record-balanced slice of the file
   (split/planners.py::plan_bam_spans_balanced);
2. each device extracts sort keys from its records ON DEVICE
   (ops/unpack_bam.py::unpack_fixed_fields over the shard's span tile);
3. keys are range-partitioned into per-device buckets (boundaries from a
   host-side key sample — the planner's job, like split guessing) and
   exchanged with ``lax.all_to_all`` over the data axis;
4. each device sorts its bucket with a multi-key ``lax.sort`` over
   (key_hi, key_lo, global input index) — the index key makes ties
   deterministic, reproducing a stable sort exactly;
5. hosts apply the resulting permutation to the record bytes and write
   bucket 0..n-1 sequentially — byte-identical output to the
   single-process spill-merge sort (utils/sort.py::sort_bam).

Two exchange modes:

``exchange="index"`` (default single-host): only keys + global indices
ride the all_to_all; hosts keep every decoded span resident and apply
the permutation by gathering record bytes locally.  Cheapest on one
host, impossible on many (a bucket's bytes may live on another host).

``exchange="bytes"`` (default multi-host): the record BYTES themselves
ride the all_to_all as fixed-stride rows — the literal MR shuffle.
Each process decodes only the spans owned by its local devices
(broadcast_plan/assign-by-device, parallel/distributed.py), devices
exchange (key, index, row) tuples, sort their bucket, and each host
writes only its devices' buckets as headerless shards which host 0
concatenates via utils/mergers.py — byte-identical to sort_bam.
Requires the input path to be readable from every host (the HDFS
analog) and the shard/output directory to be shared.

Device memory bound, index mode: one span tile + two [n_dev,
records_cap] u32 bucket matrices per device.  Bytes mode: two
[n_dev, records_cap, stride] u8 row matrices per device (send + recv)
— the shuffle's traffic, resident on device instead of host.  Host
memory bound, index mode: the inflated input; bytes mode: only the
process's own spans.

``round_records`` engages the MULTI-ROUND spill exchange (the MR
shuffle's spill-to-disk, _sort_bam_mesh_bytes_spill): the plan is cut
into ~round_records-record spans, each round ships one span per device
through the same all_to_all step, bucket-sorted rows spill to framed
run files, and a final per-bucket k-way merge reconstructs the exact
single-round order — device memory is then bounded by the ROUND tile,
not the file.  The int32 global-index layout still caps the total at
2^31-2 records (~a 150+ GB BAM); beyond that the sort fails over
cleanly to utils/sort.py with a clear error.
"""
from __future__ import annotations

from typing import List, Optional, Tuple

import numpy as np

from hadoop_bam_tpu.config import DEFAULT_CONFIG, HBamConfig
from hadoop_bam_tpu.formats.bam import SAMHeader
from hadoop_bam_tpu.utils.errors import PlanError
from hadoop_bam_tpu.utils.metrics import METRICS
from hadoop_bam_tpu.utils.stepcache import named_step

_I32_SENTINEL = np.int32(2**31 - 1)
GLOBAL_INDEX_CEILING = 2**31 - 2     # int32 global record indices


def check_global_index_ceiling(n_records: int, where: str) -> None:
    """Raise ``PlanError`` when a record count cannot fit the mesh sort's
    int32 global-index layout.  PlanError (never a bare ValueError): a
    too-large input is a configuration fault — the retry policy must
    neither re-attempt it nor quarantine it, and the message has to tell
    the operator what to do instead of letting indices silently wrap."""
    if n_records > GLOBAL_INDEX_CEILING:
        raise PlanError(
            f"{where}: {n_records} records exceed the mesh sort's int32 "
            f"global-index ceiling ({GLOBAL_INDEX_CEILING}). The spill "
            f"exchange (`--run-records N` / round_records=N) bounds "
            f"device memory but shares the same global index — sort the "
            f"input as <2^31-record chunks (each through the spill-mode "
            f"mesh sort), then merge the sorted chunks with "
            f"utils/mergers.py or utils.sort.sort_bam, or run "
            f"utils.sort.sort_bam directly.")


def _round_up(x: int, m: int) -> int:
    return ((max(x, 1) + m - 1) // m) * m


def _keys_of(data: np.ndarray, offs: np.ndarray) -> Tuple[np.ndarray,
                                                          np.ndarray]:
    """(hi, lo) uint32 coordinate keys from raw record bytes on host —
    used only for boundary sampling; the sharded step re-derives keys on
    device.  hi = refid (unmapped -> 2^32-1, sorting last, matching
    utils/sort.py::coordinate_key); lo = pos + 1 in uint32 wraparound."""
    base = offs.astype(np.int64)
    refid = (data[base[:, None] + np.arange(4, 8)]
             .view(np.int32).ravel())
    pos = (data[base[:, None] + np.arange(8, 12)]
           .view(np.int32).ravel())
    hi = np.where(refid < 0, np.uint32(0xFFFFFFFF),
                  refid.astype(np.uint32))
    lo = pos.astype(np.uint32) + np.uint32(1)
    return hi, lo


def _sample_bounds(his: List[np.ndarray], los: List[np.ndarray],
                   n_dev: int, max_sample: int = 1 << 16
                   ) -> Tuple[np.ndarray, np.ndarray]:
    """n_dev - 1 lexicographic (hi, lo) bucket boundaries from a key
    sample: bucket b receives keys in [bound_{b-1}, bound_b)."""
    hi = np.concatenate(his) if his else np.zeros(0, np.uint32)
    lo = np.concatenate(los) if los else np.zeros(0, np.uint32)
    n = hi.size
    if n > max_sample:
        step = n // max_sample
        hi, lo = hi[::step], lo[::step]
        n = hi.size
    order = np.lexsort((lo, hi))
    hi, lo = hi[order], lo[order]
    picks = (np.arange(1, n_dev) * n) // n_dev if n else np.zeros(
        0, np.int64)
    bhi = hi[picks] if n else np.zeros(n_dev - 1, np.uint32)
    blo = lo[picks] if n else np.zeros(n_dev - 1, np.uint32)
    return bhi.astype(np.uint32), blo.astype(np.uint32)


def _device_keys(refid, pos, valid, base, R):
    """(hi, lo, gidx) device sort keys — the single definition of the
    coordinate-key convention (unmapped refid<0 sorts last; pos+1 in
    uint32 wraparound, matching utils/sort.py::coordinate_key), shared
    by both exchange modes so they cannot drift apart."""
    import jax.numpy as jnp

    hi = jnp.where(refid < 0, jnp.uint32(0xFFFFFFFF),
                   refid.astype(jnp.uint32))
    lo = pos.astype(jnp.uint32) + jnp.uint32(1)
    hi = jnp.where(valid, hi, jnp.uint32(0xFFFFFFFF))
    lo = jnp.where(valid, lo, jnp.uint32(0xFFFFFFFF))
    gidx = jnp.where(valid, base + jnp.arange(R, dtype=jnp.int32),
                     _I32_SENTINEL)
    return hi, lo, gidx


def _bucket_pack(hi, lo, bhi, blo, R):
    """Range-partition bucket ids (how many boundaries <= key) plus the
    stable within-bucket scatter coordinates (perm, dest bucket, rank)
    for the per-destination send matrices."""
    import jax.numpy as jnp

    ge = ((hi[:, None] > bhi[None, :])
          | ((hi[:, None] == bhi[None, :])
             & (lo[:, None] >= blo[None, :])))
    bucket = jnp.sum(ge.astype(jnp.int32), axis=1)          # [R] 0..n_dev-1
    perm = jnp.argsort(bucket, stable=True)
    sb = bucket[perm]
    rank = jnp.arange(R, dtype=jnp.int32) - jnp.searchsorted(
        sb, sb, side="left").astype(jnp.int32)
    return perm, sb, rank


def _send_matrices(hi, lo, gidx, perm, sb, rank, n_dev, R):
    """Per-destination [n_dev, R] send matrices for the key triple —
    sentinel-filled so unreceived cells sort last and drop at write
    time.  Shared by both exchange modes (drift here would break their
    byte-identity contract)."""
    import jax.numpy as jnp

    send_hi = jnp.full((n_dev, R), 0xFFFFFFFF, jnp.uint32
                       ).at[sb, rank].set(hi[perm])
    send_lo = jnp.full((n_dev, R), 0xFFFFFFFF, jnp.uint32
                       ).at[sb, rank].set(lo[perm])
    send_ix = jnp.full((n_dev, R), _I32_SENTINEL, jnp.int32
                       ).at[sb, rank].set(gidx[perm])
    return send_hi, send_lo, send_ix


def _make_sort_step(mesh, records_cap: int):
    """shard_map step: tiles -> device keys -> all_to_all bucket exchange
    -> per-device multi-key sort.  Returns per-device sorted global
    indices (sentinel-padded) as a [n_dev, n_dev * records_cap] array."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from hadoop_bam_tpu.parallel.mesh import shard_map

    from hadoop_bam_tpu.ops.unpack_bam import unpack_fixed_fields

    n_dev = int(np.prod(mesh.devices.shape))
    R = records_cap

    def per_device(data, offsets, count, base, bhi, blo):
        data, offsets = data[0], offsets[0]
        count, base = count[0], base[0]
        with jax.named_scope("local_sort"):
            cols = unpack_fixed_fields(data, offsets)
            valid = jnp.arange(R, dtype=jnp.int32) < count
            hi, lo, gidx = _device_keys(cols["refid"], cols["pos"], valid,
                                        base, R)
            perm, sb, rank = _bucket_pack(hi, lo, bhi, blo, R)
            send_hi, send_lo, send_ix = _send_matrices(
                hi, lo, gidx, perm, sb, rank, n_dev, R)

        # the shuffle: row b of each device goes to device b
        with jax.named_scope("exchange"):
            recv_hi = jax.lax.all_to_all(send_hi, "data", 0, 0, tiled=True)
            recv_lo = jax.lax.all_to_all(send_lo, "data", 0, 0, tiled=True)
            recv_ix = jax.lax.all_to_all(send_ix, "data", 0, 0, tiled=True)

        # bucket-local sort; the global-index key makes ties deterministic
        with jax.named_scope("merge"):
            _, _, six = jax.lax.sort(
                (recv_hi.ravel(), recv_lo.ravel(), recv_ix.ravel()),
                num_keys=3)
        return six[None]

    return named_step("sort_step", shard_map(
        per_device, mesh=mesh,
        in_specs=(P("data"), P("data"), P("data"), P("data"), P(), P()),
        out_specs=P("data"), check_vma=False))


def _record_lens(data: np.ndarray, offs: np.ndarray) -> np.ndarray:
    """Per-record total byte lengths (block_size field + its own 4)."""
    base = offs.astype(np.int64)
    return (data[base[:, None] + np.arange(4)].view("<i4").ravel()
            .astype(np.int64) + 4)


def _pack_record_rows(data: np.ndarray, offs: np.ndarray, bs: np.ndarray,
                      records_cap: int, stride: int
                      ) -> Tuple[np.ndarray, np.ndarray]:
    """Zero-padded [records_cap, stride] u8 row tile + per-row lengths
    from walked record offsets + precomputed lengths — the fixed-shape
    unit the byte exchange ships through all_to_all."""
    rows = np.zeros((records_cap, stride), np.uint8)
    lens = np.zeros(records_cap, np.int32)
    n = offs.size
    if not n:
        return rows, lens
    if int(bs.max()) > stride:
        raise ValueError(f"record of {int(bs.max())} bytes exceeds the "
                         f"agreed row stride {stride}")
    lens[:n] = bs
    base = offs.astype(np.int64)
    f = (np.arange(int(bs.sum()), dtype=np.int64)
         - np.repeat(np.cumsum(bs) - bs, bs))
    rows[np.repeat(np.arange(n), bs), f] = data[np.repeat(base, bs) + f]
    return rows, lens


def _make_bytes_sort_step(mesh, records_cap: int, stride: int):
    """shard_map step for the byte exchange: rows -> device keys ->
    all_to_all of (key, index, length, row bytes) -> per-device bucket
    sort -> bucket-sorted rows.  Unlike the index step, the permutation
    is applied ON DEVICE (take along the row axis), so hosts never need
    remote spans."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from hadoop_bam_tpu.parallel.mesh import shard_map

    n_dev = int(np.prod(mesh.devices.shape))
    R = records_cap
    N = n_dev * R

    def le_i32(rows, col):
        b = rows[:, col:col + 4].astype(jnp.uint32)
        v = (b[:, 0] | (b[:, 1] << 8) | (b[:, 2] << 16) | (b[:, 3] << 24))
        return jax.lax.bitcast_convert_type(v, jnp.int32)

    def per_device(rows, lens, count, base, bhi, blo):
        rows, lens = rows[0], lens[0]
        count, base = count[0], base[0]
        with jax.named_scope("local_sort"):
            refid = le_i32(rows, 4)      # BAM fixed fields live at the
            pos = le_i32(rows, 8)        # row head: refID @4, pos @8
            valid = jnp.arange(R, dtype=jnp.int32) < count
            hi, lo, gidx = _device_keys(refid, pos, valid, base, R)
            # capacity is structural (a source holds at most R records,
            # so no (src, dst) send cell can overflow)
            perm, sb, rank = _bucket_pack(hi, lo, bhi, blo, R)
            send_hi, send_lo, send_ix = _send_matrices(
                hi, lo, gidx, perm, sb, rank, n_dev, R)
            send_ln = jnp.zeros((n_dev, R), jnp.int32
                                ).at[sb, rank].set(lens[perm])
            send_rows = jnp.zeros((n_dev, R, stride), jnp.uint8
                                  ).at[sb, rank].set(rows[perm])

        with jax.named_scope("exchange"):
            recv_hi = jax.lax.all_to_all(send_hi, "data", 0, 0,
                                         tiled=True).ravel()
            recv_lo = jax.lax.all_to_all(send_lo, "data", 0, 0,
                                         tiled=True).ravel()
            recv_ix = jax.lax.all_to_all(send_ix, "data", 0, 0,
                                         tiled=True).ravel()
            recv_ln = jax.lax.all_to_all(send_ln, "data", 0, 0,
                                         tiled=True).ravel()
            recv_rows = jax.lax.all_to_all(send_rows, "data", 0, 0,
                                           tiled=True).reshape(N, stride)

        with jax.named_scope("merge"):
            iota = jnp.arange(N, dtype=jnp.int32)
            _, _, six, order = jax.lax.sort(
                (recv_hi, recv_lo, recv_ix, iota), num_keys=3)
            sorted_rows = jnp.take(recv_rows, order, axis=0)
            sorted_ln = jnp.take(recv_ln, order)
        return sorted_rows[None], sorted_ln[None], six[None]

    return named_step("bytes_sort_step", shard_map(
        per_device, mesh=mesh,
        in_specs=(P("data"), P("data"), P("data"), P("data"), P(), P()),
        out_specs=(P("data"), P("data"), P("data")), check_vma=False))


def _buckets(garr) -> dict:
    """Per-device bucket arrays of a sharded step output, keyed by device
    position — the one shard-extraction helper for both exchange
    flavors.  A 1-device mesh yields slice(None) indices: start is 0."""
    return {(sh.index[0].start or 0): np.asarray(sh.data)[0]
            for sh in garr.addressable_shards}


def _agree_round_geometry(counts_vec: np.ndarray, max_len: int,
                          his: List[np.ndarray], los: List[np.ndarray],
                          *, err: Optional[BaseException] = None,
                          want_sample: bool = True,
                          sample_cap: int = 4096,
                          timeout_s: Optional[float] = None):
    """Multi-host agreement on (counts, max record length[, key sample])
    with a decode-failure flag — the ONE collective protocol shared by
    the single-round bytes exchange and every round of the spill
    exchange, so the two paths cannot drift.  A raise on one host
    before the collective would strand the others in it, so a local
    ``err`` ships as a flag and re-raises only after every process has
    reached the allgather.  Single-process calls are a local
    passthrough.  Returns (counts_vec, max_len, shis, slos); the sample
    lists are None when ``want_sample`` is False (``want_sample`` must
    agree across processes — it changes the collective sequence)."""
    import jax

    if jax.process_count() == 1:
        # pure local passthrough, UNSAMPLED: _sample_bounds applies its
        # own (larger) cap, so pre-truncating here would silently
        # coarsen single-host bucket boundaries
        if err is not None:
            raise err
        METRICS.count("mesh_sort.rounds")
        METRICS.count_per_device("mesh_sort.device_rows", counts_vec)
        return (counts_vec, max_len,
                list(his) if want_sample else None,
                list(los) if want_sample else None)

    hi_s = np.concatenate(his) if his else np.zeros(0, np.uint32)
    lo_s = np.concatenate(los) if los else np.zeros(0, np.uint32)
    if hi_s.size > sample_cap:
        step_ = -(-hi_s.size // sample_cap)
        hi_s, lo_s = hi_s[::step_], lo_s[::step_]

    from hadoop_bam_tpu.parallel.distributed import guarded_allgather

    n_proc = jax.process_count()
    n_dev = counts_vec.size
    meta = np.zeros(n_dev + 3, np.int64)
    meta[:n_dev] = counts_vec
    meta[n_dev] = max_len
    meta[n_dev + 1] = hi_s.size
    meta[n_dev + 2] = 0 if err is None else 1
    g_meta = guarded_allgather(meta, "mesh sort: round geometry",
                               timeout_s=timeout_s)
    if err is not None:
        raise err
    if int(g_meta[:, n_dev + 2].max()) > 0:
        raise RuntimeError("mesh sort: decode failed on another host")
    counts_out = g_meta[:, :n_dev].sum(axis=0)
    METRICS.count("mesh_sort.rounds")
    METRICS.count_per_device("mesh_sort.device_rows", counts_out)
    max_out = int(g_meta[:, n_dev].max())
    shis = slos = None
    if want_sample:
        sample = np.full((sample_cap, 2), 0xFFFFFFFF, np.uint32)
        sample[:hi_s.size, 0] = hi_s
        sample[:hi_s.size, 1] = lo_s
        g_sample = guarded_allgather(sample, "mesh sort: key sample",
                                     timeout_s=timeout_s)
        shis = [g_sample[p, :int(g_meta[p, n_dev + 1]), 0]
                .astype(np.uint32) for p in range(n_proc)]
        slos = [g_sample[p, :int(g_meta[p, n_dev + 1]), 1]
                .astype(np.uint32) for p in range(n_proc)]
    return counts_out, max_out, shis, slos


def _frame_run(rows: np.ndarray, lens: np.ndarray, six: np.ndarray,
               hi: np.ndarray, lo: np.ndarray) -> bytes:
    """Serialize one bucket-round's sorted records as framed bytes:
    per record <u32 hi><u32 lo><i32 gidx><i32 len><len payload bytes>.
    The frame carries the full sort key so the cross-round merge never
    re-derives anything from payload bytes."""
    k = int(lens.size)
    if not k:
        return b""
    hdr = np.empty((k, 16), np.uint8)
    hdr[:, 0:4] = hi.astype("<u4")[:, None].view(np.uint8)
    hdr[:, 4:8] = lo.astype("<u4")[:, None].view(np.uint8)
    hdr[:, 8:12] = six.astype("<i4")[:, None].view(np.uint8)
    hdr[:, 12:16] = lens.astype("<i4")[:, None].view(np.uint8)
    lens64 = lens.astype(np.int64)
    total = int(lens64.sum()) + 16 * k
    out = np.empty(total, np.uint8)
    # frame start offsets
    starts = np.cumsum(lens64 + 16) - (lens64 + 16)
    out[(starts[:, None] + np.arange(16)).ravel()] = hdr.ravel()
    body = _ragged_positions(starts + 16, lens64)
    src = _ragged_positions(np.zeros(k, np.int64) + np.arange(k)
                            * rows.shape[1], lens64)
    out[body] = rows.ravel()[src]
    return out.tobytes()


def _ragged_positions(starts: np.ndarray, lens: np.ndarray) -> np.ndarray:
    total = int(lens.sum())
    if not total:
        return np.empty(0, np.int64)
    firsts = np.cumsum(lens) - lens
    flat = np.arange(total, dtype=np.int64) - np.repeat(firsts, lens)
    return np.repeat(starts, lens) + flat


def _iter_run_frames(path: str):
    """Yield ((hi, lo, gidx), payload) frames of one spilled run file."""
    with open(path, "rb") as f:
        buf = f.read()
    pos = 0
    n = len(buf)
    while pos < n:
        hi = int.from_bytes(buf[pos:pos + 4], "little")
        lo = int.from_bytes(buf[pos + 4:pos + 8], "little")
        gidx = int.from_bytes(buf[pos + 8:pos + 12], "little", signed=True)
        ln = int.from_bytes(buf[pos + 12:pos + 16], "little", signed=True)
        pos += 16
        yield (hi, lo, gidx), buf[pos:pos + ln]
        pos += ln


def _merge_bucket_runs(run_paths: List[str]
                       ) -> Tuple[bytes, np.ndarray]:
    """k-way merge of one bucket's per-round sorted runs by the framed
    (hi, lo, gidx) key — the external-merge half of the MR shuffle,
    running on the shared ``split/kmerge.py`` heap core (ties break in
    run order, exactly ``heapq.merge``'s stability, so the extraction
    is byte-identical — re-pinned by tests/test_kmerge.py).
    Returns (concatenated record bytes, per-record lengths) so writers
    can recover record boundaries for index-during-write."""
    from hadoop_bam_tpu.split.kmerge import kmerge

    chunks: List[bytes] = []
    lens: List[int] = []
    for _key, payload in kmerge(
            (_iter_run_frames(p) for p in run_paths),
            key=lambda kv: kv[0]):
        chunks.append(payload)
        lens.append(len(payload))
    return b"".join(chunks), np.asarray(lens, dtype=np.int64)


def _sort_bam_mesh_bytes_spill(input_path: str, output_path: str, *, mesh,
                               config: HBamConfig,
                               header: Optional[SAMHeader],
                               round_records: int,
                               journal_path: Optional[str] = None) -> int:
    """Spill-exchange entry: runs the rounds and removes the
    ``.mesh-spill`` run directory afterwards — success or failure — so
    an exception mid-round/mid-merge cannot strand spilled runs that
    approach the input's size (ADVICE r5).  ``config.debug_keep_spill``
    preserves the directory for post-mortem.

    Under a JOURNAL the failure branch keeps the directory: the spilled
    runs of completed rounds are exactly the artifacts ``hbam resume``
    verifies and reuses — deleting them on an exception would turn
    every recoverable fault into a from-zero re-run (a SIGKILL never
    reaches this finally either way; this aligns the exception path
    with the crash path).  Success still cleans up: once ``job_done``
    is journaled, the runs have served their purpose.

    Multi-host note: removal happens on host 0 only, and every raise
    inside the impl is preceded by the round/merge error-flag
    allgathers, so by the time any host unwinds into this finally all
    hosts have stopped writing — host 0's rmtree cannot race a writer.
    """
    import shutil

    import jax

    ok = False
    try:
        n = _sort_bam_mesh_bytes_spill_impl(
            input_path, output_path, mesh=mesh, config=config,
            header=header, round_records=round_records,
            journal_path=journal_path)
        ok = True
        return n
    finally:
        keep = bool(getattr(config, "debug_keep_spill", False)) \
            or (journal_path is not None and not ok)
        if not keep and jax.process_index() == 0:
            shutil.rmtree(output_path + ".mesh-spill", ignore_errors=True)


def _sort_bam_mesh_bytes_spill_impl(input_path: str, output_path: str, *,
                                    mesh, config: HBamConfig,
                                    header: Optional[SAMHeader],
                                    round_records: int,
                                    journal_path: Optional[str] = None
                                    ) -> int:
    """Multi-round byte exchange (VERDICT r4 #6): device memory bounded
    by the ROUND tile, not the file.

    The plan is cut so each span holds ~``round_records`` records; round
    t ships spans [t*n_dev, (t+1)*n_dev) through the same all_to_all
    bucket step as the single-round path, each host appends its devices'
    bucket-sorted rows to per-(bucket, round) spill runs, and a final
    per-bucket k-way merge of the framed runs (sorted by the full
    (hi, lo, gidx) key) reconstructs exactly the single-round order —
    byte-identical to sort_bam.

    Bucket boundaries are sampled from ROUND 0's keys only (they affect
    balance, never order); a key-skewed first round costs balance, not
    correctness.  HBM per device: two [n_dev, R, stride] tiles with
    R ≈ round_records; host per merge: one bucket's frames.

    With a ``journal_path`` the run is CRASH-SAFE (jobs/journal.py):
    the journal records the job identity (input file identity + the
    output-affecting config fingerprint + a digest of the span plan),
    the round-0 bucket boundaries, and — per completed round — the
    spilled run files with size+CRC.  A resumed run verifies every
    recorded artifact, reuses the journaled boundaries (they were
    sampled from round 0, which may no longer be decoded), skips the
    completed rounds entirely (``jobs.rounds_skipped`` /
    ``jobs.spans_skipped``), sweeps the partial spill files of the
    in-flight round, and re-runs only the remainder — byte-identical
    output, strictly fewer spans decoded.  ``job_done`` records the
    published output's size+CRC so re-running a finished job is a
    verified no-op."""
    import os
    import shutil

    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from hadoop_bam_tpu.formats.bamio import BamWriter, read_bam_header
    from hadoop_bam_tpu.parallel.distributed import (
        broadcast_plan, collective_timeout, guarded_allgather,
    )
    from hadoop_bam_tpu.parallel.pipeline import _decode_span_core
    from hadoop_bam_tpu.split.planners import plan_bam_spans_balanced
    from hadoop_bam_tpu.utils.sort import _sorted_header

    mesh_devs = list(mesh.devices.ravel())
    n_dev = len(mesh_devs)
    pid = jax.process_index()
    n_proc = jax.process_count()
    coll_timeout = collective_timeout(config)
    if header is None:
        header, _ = read_bam_header(input_path)

    jr = None
    resume = None
    if journal_path is not None:
        if n_proc > 1:
            raise PlanError(
                "mesh sort journaling is single-process for now: each "
                "host would need its own journal and a resume barrier "
                "protocol; run without journal_path under "
                "jax.distributed")
        from hadoop_bam_tpu.jobs import journal as jj
        from hadoop_bam_tpu.jobs.runner import (
            SORT_FINGERPRINT_FIELDS, sort_job_params,
        )
        jr, resume = jj.JobJournal.resume(
            journal_path, kind="mesh_sort_spill",
            inputs=[(os.path.abspath(input_path),
                     jj.file_identity_digest(input_path))],
            output=os.path.abspath(output_path),
            fingerprint=jj.config_fingerprint(config,
                                              SORT_FINGERPRINT_FIELDS),
            config_values=jj.fingerprint_values(config,
                                                SORT_FINGERPRINT_FIELDS),
            params=sort_job_params(input_path, output_path,
                                   exchange="bytes",
                                   round_records=int(round_records),
                                   n_dev=n_dev),
            fsync=bool(getattr(config, "journal_fsync", True)))
        if resume is not None and resume.done is not None:
            d = resume.done
            if jj.verify_artifact(output_path, d.get("size", -1),
                                  d.get("crc", "")):
                # committed job: re-running it is a verified no-op
                METRICS.count("jobs.jobs_skipped")
                jr.close()
                return int(d.get("records", 0))
            # output vanished/changed after job_done: fall through and
            # rebuild it from whatever units still verify

    def plan():
        from hadoop_bam_tpu.split.splitting_index import (
            SplittingIndex, build_splitting_index,
        )
        index = SplittingIndex.load_for(input_path)
        # a sidecar coarser than ~round_records/8 cannot cut spans small
        # enough to honor the round memory bound (num_spans is capped at
        # the sample count) — rebuild fine enough for ~8 samples/span
        fine = max(1, round_records // 8)
        if index is None or (index.granularity or 1) > fine:
            index = build_splitting_index(input_path, granularity=fine)
        # a sidecar index samples one voffset per GRANULARITY records:
        # estimate records from total_records (when stored) or samples x
        # granularity — len(voffsets) alone would undercount ~4096x on a
        # standard .sbi and balloon the round tile past the memory bound
        n_samples = max(1, len(index.voffsets) - 1)
        if index.total_records > 0:
            total_est = index.total_records
            # UP-FRONT ceiling check (VERDICT r5 #8): a stored exact
            # record count lets the overflow surface before any round
            # decodes, not 2^31 records into the run
            check_global_index_ceiling(total_est, "mesh spill sort plan")
        else:
            total_est = n_samples * max(1, index.granularity)
        want = -(-total_est // max(1, round_records))
        want = _round_up(want, n_dev)          # whole rounds of n_dev
        return plan_bam_spans_balanced(input_path, want, header=header,
                                       index=index)

    spans = broadcast_plan(plan() if pid == 0 else None,
                           timeout_s=coll_timeout)
    n_rounds = max(1, -(-len(spans) // n_dev))
    local_pos = [d for d, dev in enumerate(mesh_devs)
                 if dev.process_index == pid]
    local_set = set(local_pos)

    shard_dir = output_path + ".mesh-spill"
    resumed_rounds: dict = {}
    bounds_ev = None
    if jr is not None:
        # the plan digest is part of the resume contract: a changed
        # sidecar/planner state would re-cut spans under the recorded
        # rounds and silently mis-join old runs with new ones
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
                if all(jj.verify_artifact(p, s, c) for _b, p, s, c
                       in runs):
                    resumed_rounds[t] = u
            recorded = [p for u in resumed_rounds.values()
                        for _b, p, s, c in u.get("runs", [])]
            # the in-flight round's partial spills (and anything else
            # the journal never committed) are debris, not state
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
    if not resumed_rounds:
        if pid == 0:
            shutil.rmtree(shard_dir, ignore_errors=True)
        if n_proc > 1:
            guarded_allgather(np.zeros(1, np.int32),
                              "mesh spill sort: prepare barrier",
                              timeout_s=coll_timeout)
    os.makedirs(shard_dir, exist_ok=True)

    sharding = NamedSharding(mesh, P("data"))
    rep = NamedSharding(mesh, P())
    step_cache = {}
    bhi = blo = None
    prefix_total = 0
    run_files: dict = {}               # bucket -> [run paths]
    err: Optional[BaseException] = None

    # make_array_from_single_device_arrays grew its dtype kwarg after
    # jax 0.4; casting host-side before device_put is version-portable
    def sharded(shape, dtype, of_d):
        return jax.make_array_from_single_device_arrays(
            shape, sharding,
            [jax.device_put(np.asarray(of_d(d), dtype=dtype),
                            mesh_devs[d]) for d in local_pos])

    def replicated(arr, dtype):
        arr = np.asarray(arr, dtype=dtype)
        return jax.make_array_from_single_device_arrays(
            arr.shape, rep,
            [jax.device_put(arr, mesh_devs[d]) for d in local_pos])

    for t in range(n_rounds):
        if t in resumed_rounds:
            # journal-verified round: its sorted runs are already on
            # disk with matching size+CRC — reuse them, decode nothing
            u = resumed_rounds[t]
            for b, p, _s, _c in u.get("runs", []):
                run_files.setdefault(int(b), []).append(p)
            prefix_total += int(u.get("round_total", 0))
            continue
        # --- decode this round's local spans (streaming: only one
        # round's rows are ever resident) ---
        decoded = {}
        counts_vec = np.zeros(n_dev, np.int64)
        max_len = 0
        his: List[np.ndarray] = []
        los: List[np.ndarray] = []
        try:
            for d in local_pos:
                s = t * n_dev + d
                if s >= len(spans):
                    continue
                data, offs, _v, _ = _decode_span_core(
                    input_path, spans[s], False, "auto", want_voffs=False)
                lens_ = _record_lens(data, offs)
                decoded[d] = (data, offs, lens_)
                counts_vec[d] = offs.size
                if offs.size:
                    max_len = max(max_len, int(lens_.max()))
                if t == 0:
                    h, l = _keys_of(data, offs)
                    his.append(h)
                    los.append(l)
        except Exception as e:  # noqa: BLE001 — must reach the collective
            err = e

        # --- agree on round geometry (and boundaries, round 0) ---
        counts_vec, max_len, shis, slos = _agree_round_geometry(
            counts_vec, max_len, his, los, err=err, want_sample=(t == 0),
            timeout_s=coll_timeout)
        err = None     # consumed: the helper raised if any host failed
        if bhi is None:
            if bounds_ev is not None:
                # resumed run: boundaries MUST be the journaled ones —
                # the completed rounds' runs were bucketed under them,
                # and bucket assignment must agree across rounds for
                # the per-bucket merge to reconstruct the global order
                bhi = np.asarray(bounds_ev["bhi"], np.uint32)
                blo = np.asarray(bounds_ev["blo"], np.uint32)
            else:
                bhi, blo = _sample_bounds(shis, slos, n_dev)
                if jr is not None:
                    jr.event("bounds",
                             bhi=[int(x) for x in bhi],
                             blo=[int(x) for x in blo])
            # boundaries are fixed after round 0: ship them once
            bhi_g = replicated(bhi, jnp.uint32)
            blo_g = replicated(blo, jnp.uint32)

        round_total = int(counts_vec.sum())
        check_global_index_ceiling(prefix_total + round_total,
                                   "mesh spill sort (mid-run backstop)")
        base_vec = prefix_total + np.concatenate(
            [[0], np.cumsum(counts_vec[:-1])])
        prefix_total += round_total

        records_cap = _round_up(max(int(counts_vec.max()), 1), 1024)
        stride = 1 << max(6, int(max(max_len, 36) - 1).bit_length())
        key = (records_cap, stride)
        if key not in step_cache:
            step_cache[key] = _make_bytes_sort_step(mesh, records_cap,
                                                    stride)
        step = step_cache[key]

        _empty = (np.zeros(0, np.uint8), np.zeros(0, np.int64),
                  np.zeros(0, np.int64))
        packed = {}
        for d in local_pos:
            data, offs, lens_ = decoded.pop(d, _empty)
            packed[d] = _pack_record_rows(data, offs, lens_, records_cap,
                                          stride)
        del decoded

        rows_g = sharded((n_dev, records_cap, stride), jnp.uint8,
                         lambda d: packed[d][0][None])
        lens_g = sharded((n_dev, records_cap), jnp.int32,
                         lambda d: packed[d][1][None])
        count_g = sharded((n_dev,), jnp.int32,
                          lambda d: np.asarray([counts_vec[d]], np.int32))
        base_g = sharded((n_dev,), jnp.int32,
                         lambda d: np.asarray([base_vec[d]], np.int32))
        rows_s, lens_s, six_s = step(rows_g, lens_g, count_g, base_g,
                                     bhi_g, blo_g)

        # --- spill this round's local buckets as framed sorted runs ---
        b_rows, b_lens, b_six = (_buckets(rows_s), _buckets(lens_s),
                                 _buckets(six_s))
        round_runs: List[Tuple[int, str]] = []
        try:
            for b in sorted(b_rows):
                keep = b_six[b] != _I32_SENTINEL
                if not bool(keep.any()):
                    continue
                rows_k = b_rows[b][keep]
                lens_k = b_lens[b][keep]
                six_k = b_six[b][keep]
                # the ONE key-convention definition (_keys_of) — packed
                # rows are fixed-stride records, so row starts are the
                # offsets
                hi_k, lo_k = _keys_of(
                    np.ascontiguousarray(rows_k).ravel(),
                    np.arange(rows_k.shape[0], dtype=np.int64)
                    * rows_k.shape[1])
                path = os.path.join(shard_dir, f"b{b:05d}-r{t:05d}.run")
                with open(path, "wb") as f:
                    f.write(_frame_run(rows_k, lens_k, six_k, hi_k, lo_k))
                run_files.setdefault(b, []).append(path)
                round_runs.append((b, path))
        except Exception as e:  # noqa: BLE001 — flagged below
            err = e
        if n_proc > 1:
            ok = np.asarray([0 if err is not None else 1], np.int32)
            g_ok = guarded_allgather(ok, "mesh spill sort: round flag",
                                     timeout_s=coll_timeout)
            if err is not None:
                raise err
            if int(g_ok.min()) == 0:
                raise RuntimeError("mesh spill sort: run write failed on "
                                   "another host")
        elif err is not None:
            raise err
        if jr is not None:
            # the round's COMMIT record: every run file it produced,
            # verified by size+CRC on resume.  Written only after the
            # spills all landed — a crash mid-round leaves the round
            # unrecorded and its partial files get swept on resume
            jr.unit_done(
                "round", t,
                # abspath run files: `hbam resume` may run from a
                # different cwd than the (relative-pathed) killed run
                runs=[[b, os.path.abspath(p), *jj.file_digest(p)]
                      for b, p in round_runs],
                round_total=int(round_total))

    # --- final per-bucket merge ---
    total = prefix_total
    out_header = _sorted_header(header, by_name=False)
    written = 0
    merge_err: Optional[BaseException] = None
    if n_proc == 1:
        from hadoop_bam_tpu.write import write_bam_records

        def bucket_chunks():
            for b in range(n_dev):
                payload, lens = _merge_bucket_runs(run_files.get(b, []))
                if lens.size:
                    yield payload, np.cumsum(lens) - lens
        written = write_bam_records(output_path, out_header,
                                    bucket_chunks(), config=config).records
        # spill-dir removal lives in the caller's finally
        if jr is not None and written == total:
            size, crc = jj.file_digest(output_path)
            jr.job_done(records=int(written), size=size, crc=crc)
            jr.close()
    else:
        from hadoop_bam_tpu.write import (
            ShardedFileWriter, write_bam_shards_concat,
        )
        # parts live inside the existing .mesh-spill run dir (distinct
        # "part-*" names), so the caller's finally removes them with the
        # runs on every failure path
        sw = ShardedFileWriter(output_path, n_dev,
                               dir_suffix=".mesh-spill")
        try:
            for b in sorted(local_pos):
                payload, lens = _merge_bucket_runs(run_files.get(b, []))
                with sw.open_shard(b) as f:
                    with BamWriter(f, out_header, write_header=False,
                                   write_eof=False,
                                   level=config.write_compress_level) as w:
                        w.write_raw(payload, n_records=int(lens.size))
                written += int(lens.size)
        except Exception as e:  # noqa: BLE001 — flagged below
            merge_err = e
        g_written = guarded_allgather(
            np.asarray([written if merge_err is None else -1], np.int64),
            "mesh spill sort: merge counts", timeout_s=coll_timeout)
        if merge_err is not None:
            raise merge_err
        if (g_written < 0).any():
            raise RuntimeError("mesh spill sort: bucket merge failed on "
                               "another host; output is invalid")
        written = int(g_written.sum())
        if written != total:
            raise RuntimeError(
                f"mesh spill sort wrote {written} of {total} records — "
                f"output is invalid")
        final_err = None
        if pid == 0:
            try:
                # spill-dir removal (parts included) lives in the
                # caller's finally, which honors debug_keep_spill
                sw.concatenate(
                    lambda parts: write_bam_shards_concat(
                        parts, output_path, out_header, config=config),
                    what="mesh spill sort", cleanup=False)
            except Exception as e:  # noqa: BLE001 — must reach the barrier
                final_err = e
        ok = np.asarray([0 if final_err is not None else 1], np.int32)
        g_ok = guarded_allgather(ok, "mesh spill sort: publish flag",
                                 timeout_s=coll_timeout)
        if final_err is not None:
            raise final_err
        if int(g_ok.min()) == 0:
            raise RuntimeError("mesh spill sort merge failed on host 0; "
                               "output is invalid")
        return total
    if written != total:
        raise RuntimeError(
            f"mesh spill sort wrote {written} of {total} records — "
            f"output is invalid")
    return total


def _sort_bam_mesh_bytes(input_path: str, output_path: str, *, mesh,
                         config: HBamConfig,
                         header: Optional[SAMHeader]) -> int:
    """Byte-exchange mesh sort: works multi-host.  Each process decodes
    only its devices' spans; record bytes ride the all_to_all; each host
    writes its buckets as headerless shards; host 0 merges."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from hadoop_bam_tpu.formats.bamio import BamWriter, read_bam_header
    from hadoop_bam_tpu.parallel.distributed import broadcast_plan
    from hadoop_bam_tpu.parallel.pipeline import _decode_span_core
    from hadoop_bam_tpu.split.planners import plan_bam_spans_balanced
    from hadoop_bam_tpu.utils.sort import _sorted_header

    mesh_devs = list(mesh.devices.ravel())
    n_dev = len(mesh_devs)
    pid = jax.process_index()
    n_proc = jax.process_count()
    if n_proc > 1:
        from jax.experimental import multihost_utils
    if header is None:
        header, _ = read_bam_header(input_path)

    # host 0 plans once (split guessing does real I/O); everyone receives
    spans = broadcast_plan(
        plan_bam_spans_balanced(input_path, n_dev, header=header)
        if pid == 0 else None)

    # decode ONLY the spans owned by this process's mesh devices
    local_pos = [d for d, dev in enumerate(mesh_devs)
                 if dev.process_index == pid]
    local = {}
    his: List[np.ndarray] = []
    los: List[np.ndarray] = []
    counts_vec = np.zeros(n_dev, np.int64)
    max_len = 0
    decode_err: Optional[BaseException] = None
    try:
        for d in local_pos:
            if d >= len(spans):
                continue
            data, offs, _voffs, _ = _decode_span_core(
                input_path, spans[d], False, "auto", want_voffs=False)
            lens_ = _record_lens(data, offs)
            local[d] = (data, offs, lens_)
            counts_vec[d] = offs.size
            if offs.size:
                max_len = max(max_len, int(lens_.max()))
            h, l = _keys_of(data, offs)
            his.append(h)
            los.append(l)
    except Exception as e:  # noqa: BLE001 — must reach the collective
        decode_err = e

    # agree on global geometry: counts/base, row stride, bucket bounds.
    # Boundary choice only affects balance, never order (buckets are a
    # range partition and every bucket is fully sorted), so a modest
    # fixed-size per-process sample is enough.  Same shared protocol as
    # the spill rounds (_agree_round_geometry), failure flag included.
    counts_vec, max_len, shis, slos = _agree_round_geometry(
        counts_vec, max_len, his, los, err=decode_err)
    total = int(counts_vec.sum())
    check_global_index_ceiling(total, "mesh sort (post-decode backstop)")
    bhi, blo = _sample_bounds(shis, slos, n_dev)

    records_cap = _round_up(int(counts_vec.max()) if total else 1, 8)
    stride = _round_up(max(max_len, 36), 64)
    base_vec = np.zeros(n_dev, np.int64)
    base_vec[1:] = np.cumsum(counts_vec[:-1])

    sharding = NamedSharding(mesh, P("data"))
    rep = NamedSharding(mesh, P())
    _empty = (np.zeros(0, np.uint8), np.zeros(0, np.int64),
              np.zeros(0, np.int64))
    packed = {}
    for d in local_pos:
        data, offs, lens_ = local.pop(d, _empty)
        packed[d] = _pack_record_rows(data, offs, lens_, records_cap,
                                      stride)

    # make_array_from_single_device_arrays grew its dtype kwarg after
    # jax 0.4; casting host-side before device_put is version-portable
    def sharded(shape, dtype, of_d):
        return jax.make_array_from_single_device_arrays(
            shape, sharding,
            [jax.device_put(np.asarray(of_d(d), dtype=dtype),
                            mesh_devs[d]) for d in local_pos])

    def replicated(arr, dtype):
        arr = np.asarray(arr, dtype=dtype)
        return jax.make_array_from_single_device_arrays(
            arr.shape, rep,
            [jax.device_put(arr, mesh_devs[d]) for d in local_pos])

    rows_g = sharded((n_dev, records_cap, stride), jnp.uint8,
                     lambda d: packed[d][0][None])
    lens_g = sharded((n_dev, records_cap), jnp.int32,
                     lambda d: packed[d][1][None])
    count_g = sharded((n_dev,), jnp.int32,
                      lambda d: np.asarray([counts_vec[d]], np.int32))
    base_g = sharded((n_dev,), jnp.int32,
                     lambda d: np.asarray([base_vec[d]], np.int32))
    bhi_g = replicated(bhi, jnp.uint32)
    blo_g = replicated(blo, jnp.uint32)

    step = _make_bytes_sort_step(mesh, records_cap, stride)
    rows_s, lens_s, six_s = step(rows_g, lens_g, count_g, base_g,
                                 bhi_g, blo_g)

    # every host holds ONLY its devices' buckets; bucket order IS the
    # global order
    out_header = _sorted_header(header, by_name=False)

    b_rows, b_lens, b_six = (_buckets(rows_s), _buckets(lens_s),
                             _buckets(six_s))

    def bucket_payload(b):
        """(concatenated record bytes, record start offsets) of one
        bucket — record-aligned chunks the write path indexes."""
        keep = b_six[b] != _I32_SENTINEL
        n = int(keep.sum())
        if not n:
            return b"", np.zeros(0, np.int64)
        rows = b_rows[b][keep]
        lens = b_lens[b][keep].astype(np.int64)
        colmask = np.arange(stride)[None, :] < lens[:, None]
        return rows[colmask].tobytes(), np.cumsum(lens) - lens

    written = 0
    if n_proc == 1:
        # one continuous BGZF stream — byte-identical to sort_bam —
        # through the parallel write path, index sidecars co-written
        from hadoop_bam_tpu.write import write_bam_records

        def chunks():
            for b in sorted(b_rows):
                payload, offs = bucket_payload(b)
                if offs.size:
                    yield payload, offs
        written = write_bam_records(output_path, out_header, chunks(),
                                    config=config).records
    else:
        # parallel headerless shard writes (each host deflates its own
        # buckets), then host 0 re-blocks them into the continuous
        # stream so the merged file still matches sort_bam exactly
        from hadoop_bam_tpu.write import (
            ShardedFileWriter, write_bam_shards_concat,
        )
        sw = ShardedFileWriter(output_path, n_dev,
                               dir_suffix=".mesh-shards")
        if pid == 0:
            # stale parts from an earlier failed run must not survive
            # into this merge; barrier before anyone writes new ones
            sw.prepare()
        multihost_utils.process_allgather(np.zeros(1, np.int32))
        write_err = None
        try:
            for b in sorted(b_rows):
                payload, offs = bucket_payload(b)
                with sw.open_shard(b) as f:
                    with BamWriter(f, out_header, write_header=False,
                                   write_eof=False,
                                   level=config.write_compress_level) as w:
                        w.write_raw(payload, n_records=int(offs.size))
                written += int(offs.size)
        except Exception as e:  # noqa: BLE001 — must reach the collective
            # a raise here on one host only (ENOSPC, EIO, ...) would
            # strand the others in the allgather below; ship written=-1
            # as the failure flag instead
            write_err = e

    if n_proc > 1:
        g_written = np.asarray(multihost_utils.process_allgather(
            np.asarray([written if write_err is None else -1], np.int64)))
        if write_err is not None:
            raise write_err
        if (g_written < 0).any():
            raise RuntimeError("mesh sort shard write failed on another "
                               "host; output is invalid")
        written = int(g_written.sum())
    if written != total:
        raise RuntimeError(
            f"mesh sort wrote {written} of {total} records — bucket "
            f"exchange lost data; output is invalid")
    if n_proc > 1:
        merge_err = None
        if pid == 0:
            try:
                # every device position writes exactly one part (empty
                # buckets included), so a missing part means shared-FS
                # lag or data loss — refuse to merge a truncated file
                sw.concatenate(
                    lambda parts: write_bam_shards_concat(
                        parts, output_path, out_header, config=config),
                    what="mesh sort")
            except Exception as e:  # noqa: BLE001 — must reach the barrier
                merge_err = e
        # barrier doubling as failure broadcast: a raise before this
        # point on one process only would deadlock the others, so host
        # 0 always arrives here and ships ok/failed to everyone
        ok = np.asarray([0 if merge_err is not None else 1], np.int32)
        g_ok = np.asarray(multihost_utils.process_allgather(ok))
        if merge_err is not None:
            raise merge_err
        if int(g_ok.min()) == 0:
            raise RuntimeError("mesh sort merge failed on host 0; "
                               "output is invalid")
    return total


def sort_bam_mesh(input_path: str, output_path: str, *,
                  mesh=None, config: HBamConfig = DEFAULT_CONFIG,
                  header: Optional[SAMHeader] = None,
                  exchange: Optional[str] = None,
                  round_records: Optional[int] = None,
                  journal_path: Optional[str] = None) -> int:
    """Coordinate-sort a BAM over the mesh; byte-identical to
    utils/sort.py::sort_bam(by_name=False).  Returns the record count.

    ``exchange`` picks the shuffle flavor (module docstring): "index"
    (default single-host) or "bytes" (default — and required — when
    ``jax.process_count() > 1``).

    ``round_records`` engages the multi-round spill exchange
    (bytes-mode only): the shuffle streams ~that many records per
    device per round through the all_to_all, appending bucket-sorted
    runs to disk and k-way-merging per bucket at the end — device
    memory is then bounded by the round tile, not the file (the MR
    shuffle's spill, VERDICT r4 #6).  None keeps the single-round
    resident exchange.

    ``journal_path`` makes the sort CRASH-SAFE through a durable job
    journal (jobs/journal.py; ``hbam sort --journal``, resumed by
    ``hbam resume``).  Spill mode resumes at ROUND granularity — a
    SIGKILLed run re-decodes only the rounds whose runs never committed
    (see ``_sort_bam_mesh_bytes_spill_impl``); the resident single-round
    modes get job-level idempotence — a finished job's journal +
    verified output make the re-run a no-op, an unfinished one restarts
    (their whole exchange is one unit of work; use ``round_records``
    for mid-flight resume).  Mismatched input identity / config
    fingerprint / parameters refuse with ``PlanError``.

    Queryname sort keys are variable-length byte strings with no fixed-
    width device representation; use sort_bam for those.
    """
    import jax

    from hadoop_bam_tpu.parallel.mesh import make_mesh

    if round_records is not None and exchange is None:
        exchange = "bytes"
    if exchange is None:
        exchange = "bytes" if jax.process_count() > 1 else "index"
    if exchange not in ("index", "bytes"):
        raise ValueError(f"unknown exchange mode {exchange!r}; "
                         f"expected 'index' or 'bytes'")
    if round_records is not None and exchange != "bytes":
        raise ValueError("round_records (the spill exchange) requires "
                         "exchange='bytes'")
    # UP-FRONT int32 global-index ceiling (VERDICT r5 #8): when a
    # splitting-index sidecar records the exact total, refuse oversized
    # inputs BEFORE planning/decoding instead of wrapping mid-run
    from hadoop_bam_tpu.split.splitting_index import SplittingIndex
    _sidx = SplittingIndex.load_for(input_path)
    if _sidx is not None and _sidx.total_records > 0:
        check_global_index_ceiling(_sidx.total_records, "mesh sort plan")
    if mesh is None:
        mesh = make_mesh()
    if journal_path is not None and jax.process_count() > 1:
        raise PlanError(
            "mesh sort journaling is single-process for now: each host "
            "would need its own journal and a resume barrier protocol; "
            "run without journal_path under jax.distributed")
    if exchange == "bytes" and round_records is not None:
        return _sort_bam_mesh_bytes_spill(
            input_path, output_path, mesh=mesh, config=config,
            header=header, round_records=int(round_records),
            journal_path=journal_path)
    if exchange == "index" and jax.process_count() > 1:
        raise ValueError(
            "exchange='index' keeps every decoded span on the calling "
            "host and cannot run multi-host; use exchange='bytes'")
    if journal_path is not None:
        # resident exchanges are one unit of work: journal at JOB grain
        # (done + verified output -> no-op; anything else -> re-run)
        from hadoop_bam_tpu.jobs.runner import (
            run_job_level, sort_job_params,
        )

        return run_job_level(
            journal_path, kind="mesh_sort", config=config,
            inputs=[input_path], output=output_path,
            params=sort_job_params(input_path, output_path,
                                   exchange=exchange, round_records=None),
            run=lambda: (
                _sort_bam_mesh_bytes(input_path, output_path, mesh=mesh,
                                     config=config, header=header)
                if exchange == "bytes" else
                _sort_bam_mesh_index(input_path, output_path, mesh=mesh,
                                     config=config, header=header)))
    if exchange == "bytes":
        return _sort_bam_mesh_bytes(input_path, output_path, mesh=mesh,
                                    config=config, header=header)
    return _sort_bam_mesh_index(input_path, output_path, mesh=mesh,
                                config=config, header=header)


def _sort_bam_mesh_index(input_path: str, output_path: str, *, mesh,
                         config: HBamConfig,
                         header: Optional[SAMHeader]) -> int:
    """Index-exchange mesh sort (module docstring): only keys + global
    indices ride the all_to_all; the host applies the permutation by
    gathering record bytes from its resident decoded spans.  Single
    process only (the caller enforces it)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from hadoop_bam_tpu.formats.bamio import read_bam_header
    from hadoop_bam_tpu.parallel.pipeline import _decode_span_core
    from hadoop_bam_tpu.split.planners import plan_bam_spans_balanced
    from hadoop_bam_tpu.utils.sort import _sorted_header

    n_dev = int(np.prod(mesh.devices.shape))
    if header is None:
        header, _ = read_bam_header(input_path)

    # one round; each phase a span on the calling thread (PERF.md section 3)
    raw: List[Tuple[np.ndarray, np.ndarray]] = []   # (data, offsets)
    his: List[np.ndarray] = []
    los: List[np.ndarray] = []
    with METRICS.span("sort.read_wall", round=0) as read_args:
        spans = plan_bam_spans_balanced(input_path, n_dev, header=header)
        for s in spans:
            data, offs, _voffs, _ = _decode_span_core(input_path, s, False,
                                                      "auto")
            if data.size > 2**31 - 64:
                raise ValueError(
                    f"span inflates to {data.size} bytes — offsets exceed "
                    f"the device int32 tile layout; use "
                    f"utils.sort.sort_bam for inputs this large")
            raw.append((data, offs.astype(np.int32)))
            h, l = _keys_of(data, offs)
            his.append(h)
            los.append(l)
        counts = [o.size for _, o in raw]
        total = int(sum(counts))
        read_args["records"] = total
    METRICS.count("mesh_sort.rounds")
    METRICS.count_per_device("mesh_sort.device_rows", counts)
    base = np.zeros(n_dev, dtype=np.int32)
    if counts:
        base[1:len(counts)] = np.cumsum(counts[:-1])

    with METRICS.span("sort.pack_wall", round=0, records=total):
        bytes_cap = _round_up(max((d.size for d, _ in raw), default=1), 256)
        records_cap = _round_up(max(counts, default=1), 8)
        datas = np.zeros((n_dev, bytes_cap), np.uint8)
        offsets = np.zeros((n_dev, records_cap), np.int32)
        cvec = np.zeros(n_dev, np.int32)
        for d, (dat, off) in enumerate(raw):
            datas[d, :dat.size] = dat
            offsets[d, :off.size] = off
            cvec[d] = off.size
        bhi, blo = _sample_bounds(his, los, n_dev)

    # launch to ready: step build (a trace + cache load every job, see
    # steps.built.hbam_sort_step), transfers, the exchange, the readback
    with METRICS.span("sort.exchange_wall", round=0, records=total):
        step = _make_sort_step(mesh, records_cap)
        sharding = NamedSharding(mesh, P("data"))
        rep = NamedSharding(mesh, P())
        six = step(jax.device_put(datas, sharding),
                   jax.device_put(offsets, sharding),
                   jax.device_put(cvec, sharding),
                   jax.device_put(base, sharding),
                   jax.device_put(bhi, rep), jax.device_put(blo, rep))
        six = np.asarray(six)                 # [n_dev, n_dev * records_cap]
    del datas, offsets                        # padded copies; raw suffices

    # apply the permutation: buckets in device order ARE the global order.
    # Vectorized per bucket — per-record Python slicing would dominate the
    # whole sort at scale: gather each record's (source span, offset,
    # length), then assemble one contiguous output buffer with the same
    # repeat/arange scatter the decode paths use, and bulk-append it.
    span_of = np.searchsorted(
        np.cumsum(counts), np.arange(total), side="right")
    out_header = _sorted_header(header, by_name=False)
    from hadoop_bam_tpu.write import write_bam_records

    def permute_bucket(idxs):
        """(record bytes, record starts) of one bucket in sorted order."""
        s_arr = span_of[idxs]
        o_arr = np.empty(idxs.size, np.int64)
        ln_arr = np.empty(idxs.size, np.int64)
        for sp in np.unique(s_arr):
            m = s_arr == sp
            data, offs = raw[sp]
            o = offs[idxs[m] - int(base[sp])].astype(np.int64)
            bs = (data[o[:, None] + np.arange(4)]
                  .view("<i4").ravel().astype(np.int64))
            o_arr[m] = o
            ln_arr[m] = bs + 4
        dst0 = np.cumsum(ln_arr) - ln_arr
        out = np.empty(int(ln_arr.sum()), np.uint8)
        for sp in np.unique(s_arr):
            m = s_arr == sp
            data, _ = raw[sp]
            nb = ln_arr[m]
            f = (np.arange(int(nb.sum()), dtype=np.int64)
                 - np.repeat(np.cumsum(nb) - nb, nb))
            out[np.repeat(dst0[m], nb) + f] = \
                data[np.repeat(o_arr[m], nb) + f]
        return out, dst0

    def bucket_chunks():
        for d in range(n_dev):
            idxs = six[d]
            idxs = idxs[idxs != _I32_SENTINEL].astype(np.int64)
            if not idxs.size:
                continue
            # a child of sort.write_wall where the writer pulls chunks on
            # the calling thread; closed before the yield, never across it
            with METRICS.span("sort.permute_wall", round=0,
                              records=int(idxs.size)):
                chunk = permute_bucket(idxs)
            yield chunk

    with METRICS.span("sort.write_wall", round=0, records=total):
        written = write_bam_records(output_path, out_header,
                                    bucket_chunks(), config=config).records
    if written != total:
        raise RuntimeError(
            f"mesh sort wrote {written} of {total} records — bucket "
            f"exchange lost data (capacity bug); output is invalid")
    return total
