"""The operations and bytes each kernel's algorithm needs, from shapes.

The least a call can cost: every input read once, every output written
once, every multiply-add done once in whatever precision.  What an
implementation re-reads or materialises on the way is its own business, so
a share of these rooflines cannot pass 100%.
"""

from __future__ import annotations


def als_iter(cfg):
    """One full ALS iteration (both half-sweeps), copied from
    `bench.als_flops_per_iter`: per rating 2k^2 + 2k for the normal
    equations, per row k^3/3 + 4k^2 for Cholesky and two triangular solves.
    Bytes: each half-sweep reads every rating's index and value once (8 B)
    and the other side's factor table once, and writes its own table."""
    nnz, n_u, n_i, k = cfg["nnz"], cfg["n_users"], cfg["n_items"], cfg["rank"]
    flops = 2 * nnz * (2 * k * k + 2 * k) + (n_u + n_i) * (k ** 3 / 3 + 4 * k * k)
    nbytes = 2 * nnz * 8 + 2 * (n_u + n_i) * k * 4
    return float(flops), float(nbytes)


def topk_frame(cfg, batch):
    """One frame of `batch` queries: the catalog read once, the queries
    read, k (score, id) pairs written per query; 2 * rank flops per score."""
    n, r, k = cfg["rows"], cfg["rank"], cfg["k"]
    flops = 2.0 * batch * n * r
    nbytes = n * r * 4 + batch * r * 4 + batch * k * 8
    return float(flops), float(nbytes)
