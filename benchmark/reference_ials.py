"""The plain reference of implicit-feedback ALS (Hu, Koren, Volinsky, ICDM
2008), float64 numpy.  It imports nothing of the program and takes nothing
the program has made, except the factor state a check says it starts from;
it knows nothing of blocks, degree buckets, padding, chunks or which route
solved a row.

With confidence c_ui = 1 + alpha r_ui and preference p_ui = 1 wherever the
user played the song (r_ui > 0) and 0 elsewhere, the row of user u that
minimises  sum_i c_ui (p_ui - x_u . y_i)^2 + lam |x_u|^2  over ALL songs i
solves

    (Y^T Y + sum_{i in Omega_u} alpha r_ui y_i y_i^T + lam I) x_u
        = sum_{i in Omega_u} (1 + alpha r_ui) y_i,

Y the whole other side's factors and Omega_u the songs u played: HKV's
equation 4, with Y^T C^u Y split into Y^T Y + Y^T (C^u - I) Y (their
speed-up, exact).  lam is plain, not scaled by the row's count.  A (user,
song) pair that appears twice counts as two interactions, as the sweep
sums it.
"""

from __future__ import annotations

import numpy as np


def gramian(other, block=1 << 16):
    """Y^T Y in float64 over every row of the other side, in blocks so that
    the float64 copy of a 571,355-row table is never whole."""
    k = other.shape[1]
    out = np.zeros((k, k))
    for s in range(0, other.shape[0], block):
        y = other[s:s + block].astype(np.float64)
        out += y.T @ y
    return out


def hkv_rows(sample, row_of, col_of, plays, other, lam, alpha):
    """The HKV solve of the sampled rows in float64: `row_of`, `col_of`,
    `plays` are the interaction triples seen from this side, `other` the
    other side's factors (n, k)."""
    sel = np.flatnonzero(np.isin(row_of, sample))
    order = sel[np.argsort(row_of[sel], kind="stable")]
    starts = np.searchsorted(row_of[order], sample)
    ends = np.searchsorted(row_of[order], sample, side="right")
    k = other.shape[1]
    base = gramian(other) + lam * np.eye(k)
    out = np.zeros((len(sample), k))
    for n, (s, e) in enumerate(zip(starts, ends)):
        rows = order[s:e]
        y = other[col_of[rows]].astype(np.float64)
        r = plays[rows].astype(np.float64)
        a = base + (y * (alpha * r)[:, None]).T @ y
        out[n] = np.linalg.solve(a, y.T @ (1.0 + alpha * r))
    return out


def objective(x_rows, r_dense, other, lam, alpha):
    """HKV's objective of some rows against a DENSE (rows, n) play matrix,
    every cell counted, the zeros with confidence 1: what `hkv_rows`
    minimises (the tests' brute-force check at a small size)."""
    c = 1.0 + alpha * r_dense
    p = (r_dense > 0).astype(np.float64)
    fit = (c * (p - x_rows @ other.T) ** 2).sum()
    return float(fit + lam * (x_rows ** 2).sum())
