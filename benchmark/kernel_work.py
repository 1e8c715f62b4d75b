"""The least work a kernel's result needs: operations and bytes counted from
the job's sizes, as the runner reports them in ``observations`` (jobs, sites
M, GRM sites |C|, samples S, traits P, covariates), and from nothing the
program says about itself — so no implementation can read over 100 % of a
roofline, and a faster one reads higher.  ``benchmark/reducers/roofline.py``
divides by the device seconds of the named ops.

Each function returns ``(operations, bytes)`` of ONE job."""
from __future__ import annotations


def grm(sizes: dict):
    """``A = Z^T Z`` over the |C| sites of the GRM's set: the symmetric half,
    ``S (S + 1) / 2`` entries of |C| multiply-adds (2 operations each); the
    int8 dosage of those sites read once and A written once in float32."""
    c, s = int(sizes["grm_sites"]), int(sizes["samples"])
    return c * s * (s + 1), c * s + 4 * s * s


def assoc(sizes: dict):
    """``G [M, S] x [Q | Y~] [S, covariates + P]``: 2 operations a
    multiply-add; G read once as int8, the small operand once in float32,
    and one float32 a site out at least."""
    m, s = int(sizes["sites"]), int(sizes["samples"])
    k = int(sizes["covariates"]) + int(sizes["traits"])
    return 2 * m * s * k, m * s + 4 * s * k + 4 * m


KERNELS = {"grm": grm, "assoc": assoc}
