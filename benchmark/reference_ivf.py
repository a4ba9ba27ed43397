"""The plain IVF reference: numpy, float64 where it decides.  Nothing here
imports the program; a check hands it the centroids and the list membership
that the built index reports, and it answers what ANY inverted file over
those lists answers: the exact top-k of the rows whose list is probed.
"""

from __future__ import annotations

import numpy as np


def probe(centroids, queries, nprobe):
    """-> (lists (Q, nprobe), best first, and the margin (Q,) between the
    last list probed and the first one not: a query whose margin is small
    may probe another list in float32)."""
    cs = np.asarray(queries, np.float64) @ np.asarray(centroids, np.float64).T
    order = np.argsort(-cs, axis=1, kind="stable")
    ranked = np.take_along_axis(cs, order, axis=1)
    margin = (ranked[:, nprobe - 1] - ranked[:, nprobe]
              if cs.shape[1] > nprobe else np.full(len(cs), np.inf))
    return order[:, :nprobe], margin


def topk(rows, membership, centroids, queries, nprobe, k, block=1 << 20):
    """Exact top-k by inner product among the rows whose list (`membership`,
    one list id a row) is among the query's `nprobe` best centroids.
    Blockwise so that it fits the host; float32 scores shortlist, float64
    rescoring decides.  -> (ids (Q, k + 1), scores (Q, k + 1), margin (Q,)):
    the extra column is the runner-up, -1 / -inf where the lists held fewer
    rows."""
    q32 = np.asarray(queries, np.float32)
    lists, margin = probe(centroids, q32, nprobe)
    probed = np.zeros((len(q32), len(centroids)), bool)
    np.put_along_axis(probed, lists, True, axis=1)
    keep = k + 1 + 8
    cand, cand_ok = [], []
    for s in range(0, rows.shape[0], block):
        scores = q32 @ rows[s:s + block].T
        mine = probed[:, membership[s:s + block]]
        scores[~mine] = -np.inf
        m = min(keep, scores.shape[1])
        part = np.argpartition(scores, scores.shape[1] - m, axis=1)[:, -m:]
        cand.append(part + s)
        cand_ok.append(np.take_along_axis(mine, part, axis=1))
    cand, cand_ok = np.concatenate(cand, axis=1), np.concatenate(cand_ok, axis=1)
    exact = np.einsum("qck,qk->qc", rows[cand].astype(np.float64),
                      q32.astype(np.float64))
    exact[~cand_ok] = -np.inf
    order = np.argsort(-exact, axis=1, kind="stable")[:, :k + 1]
    ids = np.take_along_axis(cand, order, axis=1)
    scores = np.take_along_axis(exact, order, axis=1)
    return np.where(np.isfinite(scores), ids, -1), scores, margin


def nearest_centroid(rows, centroids, block=1 << 16):
    """The L2-nearest centroid of each row, in float64, blockwise."""
    c = np.asarray(centroids, np.float64)
    half = 0.5 * np.einsum("ck,ck->c", c, c)
    out = np.empty(len(rows), np.int64)
    for s in range(0, len(rows), block):
        x = np.asarray(rows[s:s + block], np.float64)
        out[s:s + block] = np.argmax(x @ c.T - half, axis=1)
    return out


def recall(got_ids, exact_ids):
    """Share of the exact top-k ids (Q, k) found among the answered (Q, k)."""
    hits = sum(len(np.intersect1d(g, e)) for g, e in zip(got_ids, exact_ids))
    return hits / float(exact_ids.size)
