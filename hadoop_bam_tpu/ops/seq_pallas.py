"""Pallas TPU kernels over packed sequence/quality payload tiles.

The device side of the tensor-batch feed: the host packs each read's 4-bit
encoded bases (2/byte [SPEC section 4.2.3 seq encoding]) and quality bytes
into fixed-stride tiles (native hbam_walk_bam_payload); these kernels unpack
and reduce them entirely in VMEM — one pass, no [N, L] base matrix ever
materialised in HBM for the stats path.

In the reference universe this work does not exist as device compute at all:
per-base access went through htsjdk ``SAMRecord.getReadBases()`` on the JVM
heap (hb/SAMRecordWritable.java consumers).  Here it is the framework's
showcase of intra-record parallelism: VPU lanes process 2 bases/byte across
a whole record tile per grid step.

Nibble convention [SPEC]: the FIRST base of a pair sits in the HIGH nibble.
Codes: 0='=', 1=A, 2=C, 4=G, 8=T, 15=N (4-bit IUPAC subset).
"""
from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

N_CODES = 16

# GC bases: C=2, G=4 (canonical); S (C|G ambiguity) = 6 also counts as GC.
_GC_CODES = (2, 4, 6)


def _interpret() -> bool:
    # any non-TPU target takes the non-Mosaic path (plain-XLA twin, or
    # the Pallas interpreter under force_pallas)
    return jax.default_backend() != "tpu"


def _is_gc(c):
    """GC membership as an explicit compare-or chain — shared by the
    kernel and its plain-XLA twin so the code set cannot drift
    (jnp.isin does not lower inside Pallas)."""
    m = c == _GC_CODES[0]
    for code in _GC_CODES[1:]:
        m = m | (c == code)
    return m


def _seq_stats_kernel(seq_ref, qual_ref, len_ref,
                      gc_ref, mq_ref, hist_ref):
    """One record tile: [TN, SB] packed bases + [TN, QB] quals + [TN, 1]
    lengths -> per-record GC fraction and mean quality, plus a global
    base-code histogram accumulated across the (sequential) TPU grid."""
    i = pl.program_id(0)
    # widen before bit ops: Mosaic cannot legalize shifts on i8 vectors
    seq = seq_ref[:].astype(jnp.int32)
    ln = len_ref[:]                                   # [TN, 1] int32
    hi = seq >> 4                                     # base 2j
    lo = seq & 0xF                                    # base 2j + 1
    jidx = jax.lax.broadcasted_iota(jnp.int32, seq.shape, 1)
    hi_valid = (2 * jidx) < ln
    lo_valid = (2 * jidx + 1) < ln

    denom = jnp.maximum(ln[:, 0], 1).astype(jnp.float32)
    gc_hi = _is_gc(hi) & hi_valid
    gc_lo = _is_gc(lo) & lo_valid
    gc = (gc_hi.sum(axis=1) + gc_lo.sum(axis=1)).astype(jnp.float32)
    gc_ref[:] = (gc / denom)[:, None]

    # Mosaic has no direct u8 -> f32 cast; widen to i32 first
    qual = qual_ref[:].astype(jnp.int32).astype(jnp.float32)
    qidx = jax.lax.broadcasted_iota(jnp.int32, qual.shape, 1)
    qmask = (qidx < ln).astype(jnp.float32)
    mq_ref[:] = ((qual * qmask).sum(axis=1) / denom)[:, None]

    counts = []
    for code in range(N_CODES):
        c = ((hi == code) & hi_valid).sum() + ((lo == code) & lo_valid).sum()
        counts.append(c)
    # i32, not f32: float accumulation loses integer precision past 2^24
    # (one 150bp x 112k-read tile already exceeds 16.7M bases)
    hist = jnp.stack(counts).astype(jnp.int32)[None, :]  # [1, 16]

    @pl.when(i == 0)
    def _init():
        hist_ref[:] = jnp.zeros_like(hist_ref)

    hist_ref[:] += hist


def _seq_stats_jnp(seq_tile: jnp.ndarray, qual_tile: jnp.ndarray,
                   lengths: jnp.ndarray) -> Dict[str, jnp.ndarray]:
    """Plain-XLA twin of _seq_stats_kernel — same math, no Pallas.

    On non-TPU platforms the Pallas interpreter executes the kernel
    block-by-block in Python (~2 s per 64k-read tile on one CPU core,
    the dominant cost of the CPU FASTQ/seq-stats rows and the bench
    scaling children); XLA:CPU compiles this version natively.  The TPU
    path keeps the fused kernel (bases never materialize in HBM)."""
    seq = seq_tile.astype(jnp.int32)
    ln = lengths[:, None]
    hi = seq >> 4
    lo = seq & 0xF
    jidx = jnp.arange(seq.shape[1], dtype=jnp.int32)[None, :]
    hi_valid = (2 * jidx) < ln
    lo_valid = (2 * jidx + 1) < ln

    denom = jnp.maximum(lengths, 1).astype(jnp.float32)
    gc = ((_is_gc(hi) & hi_valid).sum(axis=1)
          + (_is_gc(lo) & lo_valid).sum(axis=1)).astype(jnp.float32)
    qual = qual_tile.astype(jnp.int32).astype(jnp.float32)
    qidx = jnp.arange(qual_tile.shape[1], dtype=jnp.int32)[None, :]
    qmask = (qidx < ln).astype(jnp.float32)
    mq = (qual * qmask).sum(axis=1) / denom
    # scatter-add histogram: two passes over the tile instead of the
    # kernel's 16 per-code masked sums (XLA:CPU doesn't fuse those away)
    hist = (jnp.zeros(N_CODES, jnp.int32)
            .at[hi.ravel()].add(hi_valid.ravel().astype(jnp.int32))
            .at[lo.ravel()].add(lo_valid.ravel().astype(jnp.int32)))
    return {"gc": gc / denom, "mean_qual": mq, "base_hist": hist}


@functools.partial(jax.jit,
                   static_argnames=("block_n", "interpret",
                                    "force_pallas"))
def seq_qual_stats(seq_tile: jnp.ndarray, qual_tile: jnp.ndarray,
                   lengths: jnp.ndarray, block_n: int = 256,
                   interpret: bool | None = None,
                   force_pallas: bool = False
                   ) -> Dict[str, jnp.ndarray]:
    """Fused per-read stats over packed payload tiles.

    seq_tile: [N, SB] uint8, 2 bases/byte; qual_tile: [N, QB] uint8;
    lengths: [N] int32 (0 for padding rows — they contribute nothing).
    N must be a multiple of block_n.  Returns {"gc": [N] f32,
    "mean_qual": [N] f32, "base_hist": [16] i32}.

    ``interpret``: the computation targets a non-TPU device.  None =
    infer from the default backend — pass it explicitly when placing
    the computation on devices that are not the default backend (e.g. a
    virtual CPU mesh under a TPU-default process).  Non-TPU targets use
    the plain-XLA twin (_seq_stats_jnp) instead of the Pallas
    interpreter; ``force_pallas`` keeps the kernel itself testable on
    CPU via the interpreter.
    """
    n = seq_tile.shape[0]
    assert n % block_n == 0, (n, block_n)
    grid = n // block_n
    if interpret is None:
        interpret = _interpret()
    if interpret and not force_pallas:
        return _seq_stats_jnp(seq_tile, qual_tile, lengths)
    gc, mq, hist = pl.pallas_call(
        _seq_stats_kernel,
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((block_n, seq_tile.shape[1]), lambda i: (i, 0)),
            pl.BlockSpec((block_n, qual_tile.shape[1]), lambda i: (i, 0)),
            pl.BlockSpec((block_n, 1), lambda i: (i, 0)),
        ],
        out_specs=(
            pl.BlockSpec((block_n, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_n, 1), lambda i: (i, 0)),
            pl.BlockSpec((1, N_CODES), lambda i: (0, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
            jax.ShapeDtypeStruct((1, N_CODES), jnp.int32),
        ),
        interpret=interpret,
        name="hbam_seq_stats_kernel",
    )(seq_tile, qual_tile, lengths[:, None])
    return {"gc": gc[:, 0], "mean_qual": mq[:, 0], "base_hist": hist[0]}


@functools.partial(jax.jit, static_argnames=("max_len",))
def unpack_bases(seq_tile: jnp.ndarray, max_len: int | None = None
                 ) -> jnp.ndarray:
    """[N, SB] packed nibbles -> [N, 2*SB] base codes (uint8), high nibble
    first [SPEC].  Plain XLA — interleave is a reshape, and downstream
    one-hot/embedding fuses with it; the Pallas path is for fused stats."""
    hi = seq_tile >> 4
    lo = seq_tile & 0xF
    codes = jnp.stack([hi, lo], axis=-1).reshape(seq_tile.shape[0], -1)
    if max_len is not None:
        codes = codes[:, :max_len]
    return codes


# host-side reference implementations (test oracles, NumPy)

def seq_qual_stats_host(seq_tile: np.ndarray, qual_tile: np.ndarray,
                        lengths: np.ndarray) -> Dict[str, np.ndarray]:
    n = seq_tile.shape[0]
    gc = np.zeros(n, dtype=np.float32)
    mq = np.zeros(n, dtype=np.float32)
    hist = np.zeros(N_CODES, dtype=np.int64)
    for i in range(n):
        ln = int(lengths[i])
        packed = seq_tile[i]
        codes = np.empty(packed.size * 2, dtype=np.uint8)
        codes[0::2] = packed >> 4
        codes[1::2] = packed & 0xF
        codes = codes[:ln]
        denom = max(ln, 1)
        gc[i] = float(np.isin(codes, _GC_CODES).sum()) / denom
        mq[i] = float(qual_tile[i, :ln].astype(np.float64).sum()) / denom
        for c in codes:
            hist[c] += 1
    return {"gc": gc, "mean_qual": mq, "base_hist": hist}
