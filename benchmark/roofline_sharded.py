"""The operations and bytes ONE chip's part of a row-sharded kernel needs,
from shapes, by `roofline.py`'s rule: every input read once, every output
written once, every multiply-add done once."""

from __future__ import annotations


def topk_shard_frame(cfg, batch, shards):
    """One chip's part of one frame of `batch` queries over a catalog whose
    rows are split evenly over `shards` chips: its rows read once and the
    queries read, k (score, id) partials written per query, the partials of
    all shards read for the merge and k pairs written; 2 * rank flops per
    score.  Rows are the configuration's, not the program's padded bucket:
    pad rows are the implementation's own."""
    n, r, k = cfg["rows"], cfg["rank"], cfg["k"]
    mine = -(-n // shards)
    flops = 2.0 * batch * mine * r
    nbytes = (mine * r * 4 + batch * r * 4 + batch * k * 8
              + shards * batch * k * 8 + batch * k * 8)
    return float(flops), float(nbytes)
