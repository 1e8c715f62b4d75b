"""The two matrix products of ``hbam vcf-gwas`` over int8 dosage: the GRM
accumulation of pass 1 (``hbam_grm_kernel``) and the association product
of pass 2 (``hbam_assoc_kernel``) — the system's first MXU work.

Both fuse the int8 -> operand conversion into the product, so neither the
standardised matrix Z nor a widened copy of the dosage ever exists in HBM,
and both keep float32's accuracy on a bfloat16 MXU the same way: one
operand of each product is the raw dosage (0, 1, 2 or -1: exact in
bfloat16) and the other, a float32 matrix, is split into three bfloat16
parts whose products accumulate in float32 — three passes where
``precision=HIGHEST`` makes six, and nothing float32 would keep is lost.

GRM.  ``Z^T Z`` with ``z_js = s_j (g_js - m_j)`` (``m_j = 2 p_j``, ``s_j =
1 / sqrt(2 p_j (1 - p_j))``) is regrouped so that the dosage itself is an
operand: with ``w_j = s_j^2`` and ``T_js = w_j (g_js - m_j)``

    Z^T Z = T^T G - r 1^T,      r_s = sum_j m_j T_js

so the kernel accumulates ``T^T G`` (T built in VMEM from the transposed
int8 tile and split there) and the caller keeps the vector ``r``.  Only
the blocks on and above the block diagonal are accumulated (A is
symmetric): what lies below it is unspecified, and a reader mirrors the
upper triangle.

Pass 2.  ``G [M, S] x [Y~ | Q] [S, P + C]`` a row block at a time, with the
row sum of squares, the chi-square and its per-trait summaries reduced in
the same kernel: a few KB leave the chip (and the ``[M, P]`` table only
when asked for).

Off the TPU both route to plain-XLA twins, as ``ops/seq_pallas.py`` does;
``force_pallas`` keeps the kernels testable there through the interpreter.
"""
from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128
GRM_BLOCK = 512            # samples a block of A, and sites a step of it
ASSOC_ROWS = 1024          # sites a step of pass 2
V_FLOOR = 1e-6             # chi2 is NaN where v_j <= V_FLOOR |g_j|^2
CHI2_GENOME_WIDE = 29.72   # p < 5e-8 at 1 d.f.
_VMEM_LIMIT = 64 << 20
_HIGHEST = jax.lax.Precision.HIGHEST


def round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def split_bf16(x):
    """float32 ``x`` as three bfloat16 parts that sum to it exactly (a
    float32 mantissa is three bfloat16 mantissas wide).  For a NumPy array
    or inside a Pallas kernel — NOT under plain ``jit`` on a TPU, where XLA
    (``xla_allow_excess_precision``) may drop the float32 -> bfloat16 ->
    float32 round trip and leave the lower parts zero (the chip showed it:
    PERF.md section 6, PR 32)."""
    hi = x.astype(jnp.bfloat16)
    rest = x - hi.astype(jnp.float32)
    mid = rest.astype(jnp.bfloat16)
    lo = (rest - mid.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, mid, lo


def _dot(a, b):
    return jnp.dot(a, b, preferred_element_type=jnp.float32)


def _widen(ref_or_array) -> jnp.ndarray:
    # Mosaic has no direct int8 -> float32 cast: widen to int32 first
    return ref_or_array.astype(jnp.int32).astype(jnp.float32)


# ---------------------------------------------------------------------------
# pass 1: the GRM accumulation
# ---------------------------------------------------------------------------

def _grm_kernel(tt_ref, g_ref, m_ref, w_ref, acc_in_ref, acc_ref):
    """One (i, j, k) step: ``acc[i, j] += T[k, i]^T G[k, j]``.  ``tt_ref``
    [B, B] is the transposed int8 tile (samples of block i x sites of step
    k), ``g_ref`` [B, B] the tile (sites x samples of block j), ``m_ref``
    / ``w_ref`` [1, B] the sites' ``2 p`` and ``1 / (2 p (1 - p))`` (0 for
    a site outside the set)."""
    i, j, k = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(k == 0)
    def _first():
        acc_ref[...] = acc_in_ref[...]

    @pl.when(j >= i)
    def _accumulate():
        t = (_widen(tt_ref[...]) - m_ref[...]) * w_ref[...]
        g = _widen(g_ref[...]).astype(jnp.bfloat16)
        acc_ref[...] += sum(_dot(part, g) for part in split_bf16(t))


def _grm_jnp(acc, tile, m, w):
    g = tile.astype(jnp.float32)
    t = (g - m[:, None]) * w[:, None]
    return acc + jnp.dot(t.T, g, precision=_HIGHEST)


def grm_accumulate(acc: jnp.ndarray, tile: jnp.ndarray, m: jnp.ndarray,
                   w: jnp.ndarray, *, interpret: bool | None = None,
                   force_pallas: bool = False) -> jnp.ndarray:
    """``acc [Sp, Sp] f32 + T^T G`` for one int8 ``tile [Kp, Sp]`` (sites x
    samples, both multiples of ``GRM_BLOCK``; pad rows carry ``w = 0``)
    with per-site ``m`` and ``w`` [Kp].  Call under ``jit`` with ``acc``
    donated: the kernel updates it in place."""
    kp, sp = tile.shape
    assert kp % GRM_BLOCK == 0 and sp % GRM_BLOCK == 0, (kp, sp)
    assert acc.shape == (sp, sp), (acc.shape, sp)
    if interpret is None:
        interpret = _interpret()
    if interpret and not force_pallas:
        return _grm_jnp(acc, tile, m, w)
    b = GRM_BLOCK
    n = sp // b
    return pl.pallas_call(
        _grm_kernel,
        grid=(n, n, kp // b),
        in_specs=[
            pl.BlockSpec((b, b), lambda i, j, k: (i, k)),
            pl.BlockSpec((b, b), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, b), lambda i, j, k: (0, k)),
            pl.BlockSpec((1, b), lambda i, j, k: (0, k)),
            pl.BlockSpec((b, b), lambda i, j, k: (i, j)),
        ],
        out_specs=pl.BlockSpec((b, b), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((sp, sp), jnp.float32),
        input_output_aliases={4: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="hbam_grm_kernel",
    )(tile.T, tile, m[None, :], w[None, :], acc)


# ---------------------------------------------------------------------------
# pass 2: the association product and its summaries
# ---------------------------------------------------------------------------

def _assoc_block(g8, w3, qt, isig, row0, n_sites, *, n_traits: int,
                 n_cov: int, pad_cols: int):
    """One row block of pass 2, shared by the kernel and its twin.

    ``g8`` [R, Sp] int8 rows of the resident matrix (pad columns -1);
    ``w3`` the three bfloat16 parts of ``[Y~ | Q | 0]`` [Sp, Np]; ``qt``
    [8, Sp] f32 the rows of ``Q^T``; ``isig`` [1, Np] ``1 / sigma2_p`` on
    the trait columns.  Returns (chi2 [R, Np] with NaN wherever it is not
    a tested site's trait column, tested [R, 1] bool)."""
    gi = g8.astype(jnp.int32)
    gf = gi.astype(jnp.float32)
    r = sum(_dot(gf.astype(jnp.bfloat16), part) for part in w3)
    col = jax.lax.broadcasted_iota(jnp.int32, r.shape, 1)
    # v = |g|^2 - |Q^T g|^2 taken as |g - Q Q^T g|^2, a sum of squares: a
    # site that lies mostly in the covariates' span (private to one
    # population: v / |g|^2 of a few per cent) would lose float32's last
    # digits to the difference, and chi2 with them
    fitted = sum(
        jnp.where(col == n_traits + c, r, 0.0).sum(axis=1, keepdims=True)
        * qt[c:c + 1, :] for c in range(n_cov))
    # every pad column holds -1 and fits 0: one squared, and one "missing"
    v = ((gf - fitted) ** 2).sum(axis=1, keepdims=True) - float(pad_cols)
    gg = (gf * gf).sum(axis=1, keepdims=True) - float(pad_cols)
    n_missing = (gi < 0).astype(jnp.int32).sum(axis=1, keepdims=True) \
        - pad_cols
    row = row0 + jax.lax.broadcasted_iota(jnp.int32, v.shape, 0)
    tested = (n_missing == 0) & (v > V_FLOOR * gg) & (row < n_sites)
    chi2 = r * r * isig / jnp.where(tested, v, 1.0)
    return jnp.where(tested & (col < n_traits), chi2, jnp.nan), tested


def _fold(chi2, tested, row0, state):
    """Fold one block's chi2 into the running per-trait summaries
    (each [1, Np]: sum f32, max f32, argmax row i32, tested sites i32,
    chi2 over the genome-wide threshold i32)."""
    total, top, top_at, n_tested, hits = state
    ok = ~jnp.isnan(chi2)
    c = jnp.where(ok, chi2, -jnp.inf)
    row = row0 + jax.lax.broadcasted_iota(jnp.int32, chi2.shape, 0)
    block_top = c.max(axis=0, keepdims=True)
    # the first row that reaches the block's max: ties go to file order
    block_at = jnp.where(c == block_top, row, jnp.iinfo(jnp.int32).max) \
        .min(axis=0, keepdims=True)
    better = block_top > top
    return (total + jnp.where(ok, chi2, 0.0).sum(axis=0, keepdims=True),
            jnp.where(better, block_top, top),
            jnp.where(better, block_at, top_at),
            n_tested + tested.astype(jnp.int32).sum(axis=0, keepdims=True),
            hits + (c > CHI2_GENOME_WIDE).astype(jnp.int32)
            .sum(axis=0, keepdims=True))


def _fold_init(n_cols: int):
    return (jnp.zeros((1, n_cols), jnp.float32),
            jnp.full((1, n_cols), -jnp.inf, jnp.float32),
            jnp.zeros((1, n_cols), jnp.int32),
            jnp.zeros((1, n_cols), jnp.int32),
            jnp.zeros((1, n_cols), jnp.int32))


def _assoc_kernel(n_ref, g_ref, w_ref, qt_ref, isig_ref, *out_refs,
                  with_table: bool, **static):
    i = pl.program_id(0)
    refs = out_refs[1:] if with_table else out_refs
    rows = g_ref.shape[0]

    @pl.when(i == 0)
    def _first():
        for ref, init in zip(refs, _fold_init(refs[0].shape[1])):
            ref[...] = init

    chi2, tested = _assoc_block(
        g_ref[...], (w_ref[0], w_ref[1], w_ref[2]), qt_ref[...],
        isig_ref[...], i * rows, n_ref[0], **static)
    if with_table:
        out_refs[0][...] = chi2[:, :out_refs[0].shape[1]]
    state = _fold(chi2, tested, i * rows, tuple(r[...] for r in refs))
    for ref, value in zip(refs, state):
        ref[...] = value


def _assoc_jnp(resident, w3, qt, isig, n_sites, *, with_table: bool,
               tp: int, **static):
    cap, sp = resident.shape
    rows = min(ASSOC_ROWS, cap)
    blocks = resident.reshape(cap // rows, rows, sp)

    def step(state, xs):
        g8, row0 = xs
        chi2, tested = _assoc_block(g8, w3, qt, isig, row0, n_sites,
                                    **static)
        return _fold(chi2, tested, row0, state), \
            (chi2[:, :tp] if with_table else None)

    row0s = jnp.arange(cap // rows, dtype=jnp.int32) * rows
    state, table = jax.lax.scan(step, _fold_init(isig.shape[1]),
                                (blocks, row0s))
    return ((table.reshape(cap, tp),) if with_table else ()) + state


def assoc_scan(resident: jnp.ndarray, w3: jnp.ndarray, isig: jnp.ndarray,
               n_sites: jnp.ndarray, *, n_traits: int, n_cov: int,
               n_samples: int, with_table: bool = False,
               interpret: bool | None = None,
               force_pallas: bool = False) -> Dict[str, jnp.ndarray]:
    """Pass 2 over the resident int8 matrix ``[cap, Sp]`` (``cap`` a
    multiple of ``ASSOC_ROWS`` or smaller than it; pad columns and rows
    -1).  ``w3`` [3, Sp, Np] bf16 is ``split_bf16`` of ``[Y~ | Q | 0]``
    (zero rows for pad samples), split on the host; ``isig`` [Np] f32 is
    ``1 / sigma2_p`` on the trait columns,
    ``n_sites`` [1] i32 the rows that hold sites.  Returns per-trait
    ``sum``, ``max``, ``max_row`` [P], ``tested`` (a scalar), ``hits`` [P]
    and, when asked, ``chi2`` [cap, P] f32."""
    cap, sp = resident.shape
    np_ = w3.shape[2]
    rows = min(ASSOC_ROWS, cap)
    assert cap % rows == 0 and sp % LANE == 0 and np_ % LANE == 0
    tp = round_up(n_traits, LANE)
    static = dict(n_traits=n_traits, n_cov=n_cov, pad_cols=sp - n_samples)
    isig = isig[None, :]
    # the covariates' rows of W^T, reassembled from their exact parts
    qt = jnp.zeros((8, sp), jnp.float32).at[:n_cov].set(
        sum(p.astype(jnp.float32) for p in w3)[:, n_traits:n_traits + n_cov]
        .T)
    if interpret is None:
        interpret = _interpret()
    if interpret and not force_pallas:
        out = _assoc_jnp(resident, w3, qt, isig, n_sites[0],
                         with_table=with_table, tp=tp, **static)
    else:
        small = [jax.ShapeDtypeStruct((1, np_), jnp.float32),
                 jax.ShapeDtypeStruct((1, np_), jnp.float32),
                 jax.ShapeDtypeStruct((1, np_), jnp.int32),
                 jax.ShapeDtypeStruct((1, np_), jnp.int32),
                 jax.ShapeDtypeStruct((1, np_), jnp.int32)]
        small_specs = [pl.BlockSpec(s.shape, lambda i, n: (0, 0))
                       for s in small]
        table, table_spec = [], []
        if with_table:
            table = [jax.ShapeDtypeStruct((cap, tp), jnp.float32)]
            table_spec = [pl.BlockSpec((rows, tp), lambda i, n: (i, 0))]
        out = pl.pallas_call(
            functools.partial(_assoc_kernel, with_table=with_table,
                              **static),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(cap // rows,),
                in_specs=[
                    pl.BlockSpec((rows, sp), lambda i, n: (i, 0)),
                    pl.BlockSpec((3, sp, np_), lambda i, n: (0, 0, 0)),
                    pl.BlockSpec((8, sp), lambda i, n: (0, 0)),
                    pl.BlockSpec((1, np_), lambda i, n: (0, 0)),
                ],
                out_specs=table_spec + small_specs),
            out_shape=table + small,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=_VMEM_LIMIT),
            interpret=interpret,
            name="hbam_assoc_kernel",
        )(n_sites, resident, w3, qt, isig)
    out = list(out)
    res = {}
    if with_table:
        res["chi2"] = out.pop(0)[:, :n_traits]
    total, top, top_at, n_tested, hits = out
    res.update(sum=total[0, :n_traits], max=top[0, :n_traits],
               max_row=top_at[0, :n_traits], tested=n_tested[0, 0],
               hits=hits[0, :n_traits])
    return res
