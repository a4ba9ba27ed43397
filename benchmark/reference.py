"""Plain numpy references.  Nothing here imports the program or takes
anything the program has made, except the state a check says it starts from.
"""

from __future__ import annotations

import numpy as np


def stratified_rows(degrees, n, rng):
    """`n` rows spread evenly over the degree ranking, the heaviest included,
    so that every degree bucket of the program is looked at."""
    order = np.argsort(degrees, kind="stable")
    n = min(n, len(order))
    pos = np.floor((np.arange(n) + rng.random(n)) * len(order) / n).astype(np.int64)
    pos[-1] = len(order) - 1
    return np.unique(order[np.minimum(pos, len(order) - 1)])


def ridge_rows(sample, row_of, col_of, vals, other, lam):
    """ALS-WR solve of the sampled rows in float64:
    (sum_j y_j y_j^T + lam * n_row * I) x = sum_j r_j y_j  over the row's
    ratings, y_j the other side's factor of rating j."""
    sel = np.flatnonzero(np.isin(row_of, sample))
    order = sel[np.argsort(row_of[sel], kind="stable")]
    starts = np.searchsorted(row_of[order], sample)
    ends = np.searchsorted(row_of[order], sample, side="right")
    k = other.shape[1]
    out = np.zeros((len(sample), k))
    for n, (s, e) in enumerate(zip(starts, ends)):
        rows = order[s:e]
        y = other[col_of[rows]].astype(np.float64)
        a = y.T @ y + lam * len(rows) * np.eye(k)
        out[n] = np.linalg.solve(a, y.T @ vals[rows].astype(np.float64))
    return out


def worst_row_error(got, want):
    """Largest per-row max|got - want| / max|want|."""
    scale = np.maximum(np.abs(want).max(axis=1), 1e-30)
    return float((np.abs(got - want).max(axis=1) / scale).max())


def topk(rows, queries, k, block=1 << 20, spare=8):
    """Exact top-k of queries @ rows.T, blockwise so that it fits the host:
    f32 scores shortlist k + spare rows per block, float64 rescoring of the
    shortlist decides.  -> (ids (Q, k + 1) 0-based, scores (Q, k + 1)); the
    extra column is the runner-up, for the gap at rank k."""
    q32 = np.asarray(queries, np.float32)
    keep = k + 1 + spare
    cand = []
    for s in range(0, rows.shape[0], block):
        scores = q32 @ rows[s:s + block].T
        m = min(keep, scores.shape[1])
        part = np.argpartition(scores, scores.shape[1] - m, axis=1)[:, -m:]
        cand.append(part + s)
    cand = np.concatenate(cand, axis=1)
    q64 = q32.astype(np.float64)
    exact = np.einsum("qck,qk->qc", rows[cand].astype(np.float64), q64)
    order = np.argsort(-exact, axis=1, kind="stable")[:, :k + 1]
    return (np.take_along_axis(cand, order, axis=1),
            np.take_along_axis(exact, order, axis=1))


def compare_topk(got_ids, got_scores, ref_ids, ref_scores, gap):
    """-> (largest |score - reference| by rank, ids that differ at ranks
    whose reference score is more than `gap` from both neighbours)."""
    k = got_scores.shape[1]
    score_err = float(np.abs(got_scores - ref_scores[:, :k]).max())
    below = ref_scores[:, :k] - ref_scores[:, 1:k + 1]
    above = np.concatenate(
        [np.full((len(ref_scores), 1), np.inf),
         ref_scores[:, :k - 1] - ref_scores[:, 1:k]], axis=1)
    clear = (below > gap) & (above > gap)
    wrong = int(((got_ids != ref_ids[:, :k]) & clear).sum())
    return score_err, wrong, int(clear.sum())
