"""The operations and bytes of one frame of the exact top-k over a matrix
that is allocated at a capacity above its live rows, by `roofline.py`'s rule
(every input read once, every output written once, every multiply-add done
once), from what the program reports of itself."""

from __future__ import annotations


def topk_frame_capacity(cfg, batch, capacity):
    """One frame of `batch` queries over a matrix of `capacity` rows (the
    gauge `tpums_topk_rows_capacity`: live rows and spare ones).  The scan
    reads and scores every row of the matrix, the spare rows too, and masks
    them afterwards: the bytes and operations are counted over the capacity,
    so that headroom shows as work and not as speed.  Otherwise
    `roofline.topk_frame`: the queries read, k (score, id) pairs written per
    query, 2 * rank flops per score."""
    r, k = cfg["rank"], cfg["k"]
    flops = 2.0 * batch * capacity * r
    nbytes = capacity * r * 4 + batch * r * 4 + batch * k * 8
    return float(flops), float(nbytes)
