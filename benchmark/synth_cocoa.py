"""Sparse labelled documents made from `--seed`, at a configuration's shape.

As `synth.als_problem` does with degrees, the row lengths are ONE sequence
fixed by the configuration (so the program's padded shape, its compiled round
and the work per round are the same for every seed) and the seed decides which
row has which length, which features a row holds, their values, and the labels.
Every law here is the benchmark's (`assumed` in the configuration file), none
the source's: the published vectors cannot be fetched.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

from benchmark.synth import _round_to_total

PARTS = 16  # independent streams, a fixed count: rows do not depend on the machine


def row_lengths(cfg):
    """(rows,) int64, fixed by the configuration and not by the seed:
    log-normal, clipped to [min, max], its location bisected so that the
    clipped lengths sum to `nnz`; the longest row is at the clip."""
    a = cfg["assumed"]
    n, nnz = cfg["rows"], cfg["nnz"]
    lo, hi = a["row_length_min"], a["row_length_clip"]
    raw = np.random.default_rng(a["length_seed"]).lognormal(
        0.0, a["row_length_lognormal_sigma"], n)
    s_lo, s_hi = 1e-3, 1e6
    for _ in range(80):
        mid = (s_lo * s_hi) ** 0.5
        if np.clip(np.floor(raw * mid), lo, hi).sum() > nnz:
            s_hi = mid
        else:
            s_lo = mid
    lens = _round_to_total(raw * s_lo, nnz, lo, hi)
    if lens.max() < hi:
        raise ValueError(f"no row reaches the clip {hi}: the padded width "
                         "would not be the configuration's")
    return lens


def _distinct_ranks(ranks, row_of, pos, n_features):
    """Sorted ranks of each row -> strictly increasing ones: a repeated rank
    moves up to the next free one (and, at the top of the range, down)."""
    big = np.int64(2 * n_features)
    # g_i = i + max_{j <= i}(f_j - j) inside a row; rows are kept apart by
    # an offset larger than any f_j - j can span
    up = np.maximum.accumulate(ranks - pos + row_of * big) - row_of * big + pos
    np.minimum(up, n_features - 1, out=up)
    # the same from the right for what the clip at the top folded together
    # h_i = i + min_{j >= i}(g_j - j): a running maximum of j - g_j along the
    # reversed array, where the rows' offsets increase
    back = pos[::-1] - row_of[::-1] * big
    down = back - np.maximum.accumulate(back - up[::-1])
    return down[::-1]


def _part(cfg, seed, j, indptr, out_idx, out_val, bounds, feature_of):
    """Rows bounds[j]..bounds[j+1]: feature ids and unit-norm values."""
    a = cfg["assumed"]
    d, q = cfg["features"], a["feature_zipf_mandelbrot_q"]
    rng = np.random.default_rng([seed, 5, j])
    lo = int(indptr[bounds[j]])
    mine = np.diff(indptr[bounds[j]:bounds[j + 1] + 1])
    total = int(mine.sum())
    if total == 0:
        return
    starts = np.cumsum(mine) - mine
    row_of = np.repeat(np.arange(len(mine), dtype=np.int64), mine)
    pos = np.arange(total, dtype=np.int64) - starts[row_of]
    # rank x with weight ~ 1 / (x + q): the inverse of the continuous law's
    # distribution function, floored
    u = rng.random(total)
    ranks = np.minimum((q * ((1.0 + d / q) ** u - 1.0)).astype(np.int64), d - 1)
    order = np.argsort(row_of * d + ranks, kind="stable")
    ranks = _distinct_ranks(ranks[order], row_of, pos, d)
    vals = (0.1 + rng.random(total)).astype(np.float32)
    nonempty = mine > 0
    norms = np.sqrt(np.add.reduceat(vals.astype(np.float64) ** 2,
                                    starts[nonempty]))
    vals /= np.repeat(norms, mine[nonempty]).astype(np.float32)
    out_idx[lo:lo + total] = feature_of[ranks]
    out_val[lo:lo + total] = vals


def cocoa_problem(cfg, seed):
    """-> (indptr (rows+1,) int64, indices (nnz,) int32 0-based, values (nnz,)
    float32, labels (rows,) +-1 float64): CSR triples of unit-norm rows.

    Which row has which length, and which feature id holds which frequency
    rank, are permutations from the seed.  Labels: the sign of a planted
    `w* . x` plus noise, cut so that `positive_share` of them are +1."""
    a = cfg["assumed"]
    n, d = cfg["rows"], cfg["features"]
    rng = np.random.default_rng([seed, 5])
    lens = row_lengths(cfg)[rng.permutation(n)]
    if lens.max() > d:
        raise ValueError("a row cannot hold more distinct features than there are")
    feature_of = rng.permutation(d).astype(np.int32)
    indptr = np.concatenate([[0], np.cumsum(lens)]).astype(np.int64)
    indices = np.empty(indptr[-1], np.int32)
    values = np.empty(indptr[-1], np.float32)
    bounds = np.linspace(0, n, PARTS + 1).astype(np.int64)
    with ThreadPoolExecutor(PARTS) as pool:
        list(pool.map(lambda j: _part(cfg, seed, j, indptr, indices, values,
                                      bounds, feature_of), range(PARTS)))
    w_true = rng.standard_normal(d)
    score = np.zeros(n)
    nonempty = lens > 0
    score[nonempty] = np.add.reduceat(w_true[indices] * values,
                                      indptr[:-1][nonempty])
    score += a["label_noise"] * score.std() * rng.standard_normal(n)
    cut = np.quantile(score, 1.0 - a["positive_share"])
    labels = np.where(score > cut, 1.0, -1.0)
    return indptr, indices, values, labels
