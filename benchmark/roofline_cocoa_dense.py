"""The operations and bytes one CoCoA outer round over DENSE examples needs,
from the configuration's shape, by `roofline.py`'s rule: every input read
once for each pass the algorithm makes over it, every output written once.
Counted at the SOURCE's rows, not the program's padded slots (pad rows are
the implementation's own), so padding can only lower a share."""

from __future__ import annotations

BYTES = 4  # the configuration's float32; a dense row stores no feature ids


def cocoa_dense_pass(cfg):
    """One pass over X: the round-start margins X w, or Dw = X^T Dalpha.
    Every value read once with one multiply-add; the vector along the rows
    (margins or Dalpha) and the one along the features (w or Dw) each read
    or written once."""
    n, d = cfg["rows"], cfg["features"]
    return float(2 * n * d), float(n * d * BYTES + (n + d) * BYTES)


def cocoa_dense_round(cfg):
    """One round over every chain.  The algorithm passes over the examples
    twice (margins, Dw); between the passes each chain takes
    `local_iterations` dual steps, each reading one row of the chain's Gram
    matrix (as many floats as the chain has rows) for an AXPY on the running
    margins.  w and alpha are read and written; labels and squared norms are
    read."""
    n, d = cfg["rows"], cfg["features"]
    chains, steps = cfg["blocks"], cfg["local_iterations"]
    rows = -(-n // chains)
    flops = 2 * 2 * n * d + chains * steps * (2 * rows + 12)
    nbytes = (2 * n * d * BYTES + chains * steps * rows * BYTES
              + 2 * d * BYTES + 2 * n * BYTES + 2 * n * BYTES)
    return float(flops), float(nbytes)
