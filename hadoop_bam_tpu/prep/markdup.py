"""Device markdup kernels: fused sort exchange + signature columns, and
the signature-hash duplicate exchange.

Two shard_map steps, both riding the mesh-sort machinery
(parallel/mesh_sort.py — ``_device_keys``/``_bucket_pack``/
``_send_matrices`` are imported, not re-derived, so the key conventions
cannot drift):

1. ``_make_fused_sort_markdup_step`` — the byte-exchange sort step
   EXTENDED: before the all_to_all ships the rows away, the device
   unpacks the duplicate-signature columns (unclipped 5' position via a
   masked CIGAR prefix/suffix walk, orientation/pair-class bits, mate
   key, sum-of-quals score) straight from the resident row bytes.  One
   jitted call per round does the shuffle AND the signature unpack —
   records are never re-inflated for a second pass.

2. ``_make_markdup_exchange_step`` — the duplicate grouping: signature
   columns (7 uint32s per record, never the payload) are hash-
   partitioned over the mesh so every signature group lands whole on
   one device, a multi-key ``lax.sort`` over (signature, inverted
   score, global index) clusters each group with its winner first, and
   the duplicate bit is exactly "valid and same signature as the
   previous row" — the segmented best-of-duplicate reduction.

The column definitions mirror ``prep.oracle.record_signature`` /
``record_score`` field for field; tests pin byte identity of the whole
pipeline against the oracle, which would catch any drift here.
"""
from __future__ import annotations

import numpy as np

from hadoop_bam_tpu.parallel.mesh_sort import (
    _I32_SENTINEL, _bucket_pack, _device_keys, _send_matrices,
)
from hadoop_bam_tpu.utils.stepcache import named_step

_U32 = 0xFFFFFFFF
# ineligible flags: unmapped 0x4, secondary 0x100, supplementary 0x800
_INELIGIBLE_MASK = 0x904


def _le_u16(rows, col):
    import jax.numpy as jnp

    b = rows[:, col:col + 2].astype(jnp.uint32)
    return b[:, 0] | (b[:, 1] << 8)


def _le_i32(rows, col):
    import jax
    import jax.numpy as jnp

    b = rows[:, col:col + 4].astype(jnp.uint32)
    v = (b[:, 0] | (b[:, 1] << 8) | (b[:, 2] << 16) | (b[:, 3] << 24))
    return jax.lax.bitcast_convert_type(v, jnp.int32)


def host_kmax(data: np.ndarray, offs: np.ndarray) -> int:
    """Max n_cigar_op over a decoded span (host, cheap): the static
    CIGAR-walk width the fused step compiles for."""
    if not offs.size:
        return 0
    base = offs.astype(np.int64)
    n_cigar = (data[base[:, None] + np.arange(16, 18)]
               .view("<u2").ravel())
    return int(n_cigar.max())


def markdup_columns(rows, lens, valid, lib, kmax: int, stride: int):
    """(k0..k4, score, elig) uint32 signature columns from a row tile,
    on device — the single-definition twin of
    ``oracle.record_signature``/``record_score`` (docstrings there).

    ``kmax`` is the static CIGAR width (host-measured per round);
    ``lib`` the host-joined per-record library column.  Runs on the
    PRE-exchange rows, so each record's columns carry its own global
    index position implicitly (the caller pairs them with
    ``base + arange``)."""
    import jax.numpy as jnp

    R = rows.shape[0]
    flag = _le_u16(rows, 18)
    l_read_name = rows[:, 12].astype(jnp.int32)
    n_cigar = _le_u16(rows, 16).astype(jnp.int32)
    l_seq = _le_i32(rows, 20)
    refid = _le_i32(rows, 4)
    pos = _le_i32(rows, 8)
    nref = _le_i32(rows, 24)
    npos = _le_i32(rows, 28)

    elig = valid & ((flag & _INELIGIBLE_MASK) == 0)

    # --- masked CIGAR walk: leading/trailing clips + reference span ---
    cig_off = 36 + l_read_name
    if kmax > 0:
        karange = jnp.arange(kmax, dtype=jnp.int32)
        kvalid = karange[None, :] < n_cigar[:, None]
        flat = rows.ravel()
        cpos = (jnp.arange(R, dtype=jnp.int32)[:, None] * stride
                + cig_off[:, None] + 4 * karange[None, :])
        cap = R * stride - 1

        def gb(j):
            return jnp.take(flat, jnp.clip(cpos + j, 0, cap)
                            ).astype(jnp.uint32)

        v = gb(0) | (gb(1) << 8) | (gb(2) << 16) | (gb(3) << 24)
        op = v & 0xF
        ln = (v >> 4).astype(jnp.int32)
        is_clip = ((op == 4) | (op == 5)) & kvalid
        # maximal clip prefix / suffix (oracle._cigar_walk): padding
        # counts as clip on the suffix side so variable lengths don't
        # break the right-to-left product
        lead_mask = jnp.cumprod(is_clip.astype(jnp.int32), axis=1)
        clip_or_pad = (is_clip | ~kvalid).astype(jnp.int32)
        suffix = jnp.cumprod(clip_or_pad[:, ::-1], axis=1)[:, ::-1]
        lead = jnp.sum(ln * lead_mask, axis=1)
        trail = jnp.sum(ln * suffix * is_clip.astype(jnp.int32), axis=1)
        is_ref = ((op == 0) | (op == 2) | (op == 3)
                  | (op == 7) | (op == 8)) & kvalid
        ref_sum = jnp.sum(ln * is_ref.astype(jnp.int32), axis=1)
    else:
        lead = trail = ref_sum = jnp.zeros(R, jnp.int32)
    ref_len = jnp.where(n_cigar == 0, l_seq, ref_sum)

    orient = (flag >> 4) & 1
    upos = jnp.where(orient.astype(bool),
                     pos + ref_len - 1 + trail, pos - lead)

    # --- sum of base qualities >= SCORE_MIN_QUAL (oracle.record_score) ---
    qual_off = 36 + l_read_name + 4 * n_cigar + (l_seq + 1) // 2
    cols = jnp.arange(stride, dtype=jnp.int32)[None, :]
    qmask = ((cols >= qual_off[:, None])
             & (cols < (qual_off + l_seq)[:, None])
             & (rows >= 15))
    score = jnp.sum(jnp.where(qmask, rows, 0).astype(jnp.uint32),
                    axis=1)

    pair = ((flag & 0x1) != 0) & ((flag & 0x8) == 0)
    mate_rev = jnp.where(pair, (flag >> 5) & 1, 0)
    k0 = refid.astype(jnp.uint32)
    k1 = (upos + 1).astype(jnp.uint32)
    k2 = ((lib.astype(jnp.uint32) << 3) | (mate_rev << 2)
          | (orient << 1) | pair.astype(jnp.uint32))
    k3 = jnp.where(pair, (nref + 1).astype(jnp.uint32), jnp.uint32(0))
    k4 = jnp.where(pair, (npos + 1).astype(jnp.uint32), jnp.uint32(0))
    return k0, k1, k2, k3, k4, score, elig


def _make_fused_sort_markdup_step(mesh, records_cap: int, stride: int,
                                  kmax: int):
    """The byte-exchange sort step (mesh_sort._make_bytes_sort_step)
    fused with the signature-column unpack: same all_to_all shuffle and
    bucket sort, plus per-source-device (k0..k4, score, elig) columns
    computed from the rows BEFORE they ship.  Returns
    ((sorted_rows, sorted_lens, six), (k0..k4, score, elig))."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from hadoop_bam_tpu.parallel.mesh import shard_map

    n_dev = int(np.prod(mesh.devices.shape))
    R = records_cap
    N = n_dev * R

    def per_device(rows, lens, count, base, lib, bhi, blo):
        rows, lens = rows[0], lens[0]
        count, base, lib = count[0], base[0], lib[0]
        refid = _le_i32(rows, 4)
        pos = _le_i32(rows, 8)
        valid = jnp.arange(R, dtype=jnp.int32) < count
        hi, lo, gidx = _device_keys(refid, pos, valid, base, R)

        # signature columns from the resident pre-exchange rows — the
        # fusion: one pass over bytes that are already on device
        k0, k1, k2, k3, k4, score, elig = markdup_columns(
            rows, lens, valid, lib, kmax, stride)

        perm, sb, rank = _bucket_pack(hi, lo, bhi, blo, R)
        send_hi, send_lo, send_ix = _send_matrices(hi, lo, gidx, perm,
                                                   sb, rank, n_dev, R)
        send_ln = jnp.zeros((n_dev, R), jnp.int32
                            ).at[sb, rank].set(lens[perm])
        send_rows = jnp.zeros((n_dev, R, stride), jnp.uint8
                              ).at[sb, rank].set(rows[perm])

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

        iota = jnp.arange(N, dtype=jnp.int32)
        _, _, six, order = jax.lax.sort(
            (recv_hi, recv_lo, recv_ix, iota), num_keys=3)
        sorted_rows = jnp.take(recv_rows, order, axis=0)
        sorted_ln = jnp.take(recv_ln, order)
        return (sorted_rows[None], sorted_ln[None], six[None],
                k0[None], k1[None], k2[None], k3[None], k4[None],
                score[None], elig.astype(jnp.uint8)[None])

    return named_step("fused_sort_markdup_step", shard_map(
        per_device, mesh=mesh,
        in_specs=(P("data"),) * 5 + (P(), P()),
        out_specs=(P("data"),) * 10, check_vma=False))


def _make_markdup_exchange_step(mesh, cap: int):
    """The duplicate-grouping exchange: hash-partition signature column
    tuples over the mesh, multi-key sort each device's groups with the
    winner first, emit per-record duplicate bits keyed by global index.

    Capacity is structural like the sort exchange: a source holds at
    most ``cap`` eligible records, so no (src, dst) send cell can
    overflow.  Padding cells carry the int32 gidx sentinel and all-ones
    keys; they sort last and are dropped on the host."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from hadoop_bam_tpu.parallel.mesh import shard_map

    n_dev = int(np.prod(mesh.devices.shape))
    R = cap
    N = n_dev * R

    def per_device(k0, k1, k2, k3, k4, score, gidx, count):
        k0, k1, k2, k3, k4 = k0[0], k1[0], k2[0], k3[0], k4[0]
        score, gidx, count = score[0], gidx[0], count[0]
        valid = jnp.arange(R, dtype=jnp.int32) < count

        # deterministic u32 hash mix over the 5 signature keys: equal
        # signatures land on one device regardless of mesh size, which
        # is what makes tie-breaks shard-count-invariant
        h = k0
        for k in (k1, k2, k3, k4):
            h = (h ^ k) * jnp.uint32(0x9E3779B1)
        bucket = jnp.where(valid, (h % jnp.uint32(n_dev)).astype(
            jnp.int32), 0)
        perm = jnp.argsort(bucket, stable=True)
        sb = bucket[perm]
        rank = jnp.arange(R, dtype=jnp.int32) - jnp.searchsorted(
            sb, sb, side="left").astype(jnp.int32)

        def send_u32(x):
            x = jnp.where(valid, x, jnp.uint32(_U32))
            return jnp.full((n_dev, R), _U32, jnp.uint32
                            ).at[sb, rank].set(x[perm])

        sends = [send_u32(k) for k in (k0, k1, k2, k3, k4)]
        # inverted score: ascending sort puts the HIGHEST score first
        inv = jnp.uint32(_U32) - jnp.where(valid, score, jnp.uint32(0))
        sends.append(send_u32(inv))
        gidx_s = jnp.where(valid, gidx, _I32_SENTINEL)
        send_ix = jnp.full((n_dev, R), _I32_SENTINEL, jnp.int32
                           ).at[sb, rank].set(gidx_s[perm])

        recvd = [jax.lax.all_to_all(s, "data", 0, 0, tiled=True).ravel()
                 for s in sends]
        recv_ix = jax.lax.all_to_all(send_ix, "data", 0, 0,
                                     tiled=True).ravel()

        s0, s1, s2, s3, s4, sinv, six = jax.lax.sort(
            (*recvd, recv_ix), num_keys=7)
        ok = six != _I32_SENTINEL
        prev_same = jnp.zeros(N, bool).at[1:].set(
            (s0[1:] == s0[:-1]) & (s1[1:] == s1[:-1])
            & (s2[1:] == s2[:-1]) & (s3[1:] == s3[:-1])
            & (s4[1:] == s4[:-1]) & ok[1:] & ok[:-1])
        dup = (ok & prev_same).astype(jnp.uint8)
        return six[None], dup[None]

    return named_step("markdup_exchange_step", shard_map(
        per_device, mesh=mesh,
        in_specs=(P("data"),) * 8,
        out_specs=(P("data"), P("data")), check_vma=False))
