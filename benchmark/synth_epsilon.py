"""Dense labelled rows made from `--seed`, at a configuration's shape.

Every row holds every feature, as LIBSVM's `epsilon_normalized` lists them:
feature ids 0 .. d-1 in order on every line.  The shape is the
configuration's alone, so every seed has one layout and one compiled
program; the seed decides the values and the labels.  Every law here is the
benchmark's (`assumed` in the configuration file), none the source's: the
published vectors cannot be fetched.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

PARTS = 16    # independent streams, a fixed count: rows do not depend on the machine
STRIP = 4096  # rows drawn at a time inside a part: 65 MB of float64 a thread


def _part(seed, j, bounds, values, w_true, score):
    """Rows bounds[j]..bounds[j+1]: z / |z| with z iid standard normal, drawn
    in float64 (numpy's float32 ziggurat returns an exact 0 once in 2^23
    draws, a hundred cells of this matrix) and kept in float32."""
    rng = np.random.default_rng([seed, 7, j])
    strip = np.empty((STRIP, values.shape[1]))  # one buffer a thread, reused
    for lo in range(int(bounds[j]), int(bounds[j + 1]), STRIP):
        hi = min(lo + STRIP, int(bounds[j + 1]))
        z = rng.standard_normal(out=strip[:hi - lo])
        z /= np.sqrt(np.einsum("ij,ij->i", z, z))[:, None]
        values[lo:hi] = z
        z[:] = values[lo:hi]  # the labels are planted on the rows as stored
        score[lo:hi] = z @ w_true


def epsilon_problem(cfg, seed):
    """-> (indptr (rows+1,) int64, indices (rows * features,) int32 0-based,
    values (rows * features,) float32, labels (rows,) +-1 float64): CSR
    triples of unit-norm rows in which every row is full and in feature
    order, which is what a LIBSVM reader makes of a dense file.

    Labels: the sign of a planted `w* . x` plus noise, cut so that
    `positive_share` of them are +1."""
    a = cfg["assumed"]
    n, d = cfg["rows"], cfg["features"]
    rng = np.random.default_rng([seed, 7])
    w_true = rng.standard_normal(d)
    values = np.empty((n, d), np.float32)
    score = np.empty(n)
    bounds = np.linspace(0, n, PARTS + 1).astype(np.int64)
    with ThreadPoolExecutor(PARTS) as pool:
        list(pool.map(lambda j: _part(seed, j, bounds, values, w_true, score),
                      range(PARTS)))
    score += a["label_noise"] * score.std() * rng.standard_normal(n)
    cut = np.quantile(score, 1.0 - a["positive_share"])
    labels = np.where(score > cut, 1.0, -1.0)
    indices = np.tile(np.arange(d, dtype=np.int32), n)
    indptr = np.arange(n + 1, dtype=np.int64) * d
    return indptr, indices, values.reshape(-1), labels
