"""The operations and bytes the two parts of an ALS iteration that grow with
the rank need, from the configuration's shape, by `roofline.py`'s rule: the
least any implementation can do.  Both halves of `roofline.als_iter`'s count,
split where the program's scopes split the sweep (`als.contract`,
`als.solve`), so that the two shares add up to no more than the whole
iteration's.  Counted at the SOURCE's ratings and rows, not the program's
padded slots, and without what fusing can spare: a contraction fused with its
gather reads no gathered rows, a solve fused with its assembly reads no A
from HBM.  Neither share can pass 100% whatever a later PR fuses."""

from __future__ import annotations


def als_contract(cfg):
    """Both half-sweeps' normal equations: per rating 2k^2 + 2k operations
    (y y^T into A, r y into b), as `roofline.als_iter` counts them; each
    rating's index and value read once a half (8 B)."""
    nnz, k = cfg["nnz"], cfg["rank"]
    return float(2 * nnz * (2 * k * k + 2 * k)), float(2 * nnz * 8)


def als_solve(cfg):
    """One k x k system a row of either side: k^3/3 + 4k^2 operations for
    Cholesky and the two triangular solves, as `roofline.als_iter` counts
    them; b read and x written (2k floats a system)."""
    n, k = cfg["n_users"] + cfg["n_items"], cfg["rank"]
    return float(n * (k ** 3 / 3 + 4 * k * k)), float(n * 2 * k * 4)
