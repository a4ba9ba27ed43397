"""The operations and bytes one implicit-feedback ALS iteration needs, from
the configuration's shape, by `roofline.py`'s rule: every input read once
for each pass the algorithm makes over it, every output written once, every
multiply-add done once."""

from __future__ import annotations

from benchmark import roofline


def ials_iter(cfg):
    """One full iteration (both half-sweeps) of Hu-Koren-Volinsky ALS:
    `roofline.als_iter`'s count (per interaction 2k^2 + 2k for the weighted
    normal equations, per row k^3/3 + 4k^2 for Cholesky and the two
    triangular solves; 8 B an interaction a half-sweep, each factor table
    read once and written once) plus what implicit mode adds: Y^T Y over the
    whole other side before each half-sweep, 2 n k^2 operations and one more
    read of that table (no row can be solved before the Gramian is whole),
    and per interaction a half-sweep the confidence pair (alpha r and
    1 + alpha r, 2 operations) and the k multiplies that weight y."""
    nnz, n_u, n_i, k = cfg["nnz"], cfg["n_users"], cfg["n_items"], cfg["rank"]
    flops, nbytes = roofline.als_iter(cfg)
    flops += 2 * (n_u + n_i) * k * k + 2 * nnz * (k + 2)
    nbytes += (n_u + n_i) * k * 4
    return float(flops), float(nbytes)
