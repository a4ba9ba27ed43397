"""The operations and bytes one CoCoA outer round needs, from the
configuration's shape, by `roofline.py`'s rule: every input read once for
each pass the algorithm makes over it, every output written once."""

from __future__ import annotations


def cocoa_round(cfg):
    """One round over every chain at the SOURCE's entry count, not the
    program's padded arrays (pad entries are the implementation's own).

    The algorithm passes over the examples twice, once for the round-start
    margins w.x_j and once for Dw = X^T Dalpha: each pass reads an entry's
    feature id and value (8 B) and does one multiply-add.  Between them each
    chain takes `local_iterations` dual steps, each reading one row of the
    chain's Gram matrix (as many floats as the chain has rows) for an AXPY on
    the running margins.  w, alpha are read and written; labels and squared
    norms are read."""
    n, nnz, d = cfg["rows"], cfg["nnz"], cfg["features"]
    chains, steps = cfg["blocks"], cfg["local_iterations"]
    rows = -(-n // chains)
    flops = 2 * 2 * nnz + chains * steps * (2 * rows + 12)
    nbytes = (2 * nnz * 8 + chains * steps * rows * 4
              + 2 * d * 4 + 2 * n * 4 + 2 * n * 4)
    return float(flops), float(nbytes)
