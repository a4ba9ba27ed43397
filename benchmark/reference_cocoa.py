"""Plain numpy reference for CoCoA rounds of a hinge-loss linear SVM.

float64, on the CSR triples of the examples themselves.  It knows nothing of
the program's padded `(K, rows, L)` arrays, its Gram matrix or how it reduces
the round's update; it is handed what belongs to the configuration and not to
the mathematics: which example sits in which slot of which chain, and which
slot every chain draws at every local step.

    min_w  (lam / 2) |w|^2 + (1 / n) sum_j max(0, 1 - y_j w.x_j)

One outer round (Jaggi et al. 2014, Algorithm 1, with the hinge SDCA step of
Shalev-Shwartz & Zhang as the local solver; CoCoA+ of Ma et al. 2015 for
`mode="add"`): every chain k starts from the shared w and its own alphas,

    d_j   = y_j clip(alpha_j y_j + (1 - y_j w_loc.x_j) lam n / (s |x_j|^2), 0, 1) - alpha_j
    alpha_j += d_j,   w_loc += s d_j x_j / (lam n),   Dw_k += d_j x_j / (lam n)

for its drawn slots in order, and then w += g sum_k Dw_k, alpha += g Dalpha,
with (g, s) = (stepsize / K, 1) when averaging and (stepsize, stepsize * K, or
the given `sigma_prime`) when adding.  alpha_j carries the label's sign.
"""

from __future__ import annotations

import numpy as np


def row_sums(indptr, per_entry):
    """Sum of `per_entry` over each row of the CSR layout; 0 for an empty row."""
    out = np.zeros(len(indptr) - 1)
    nonempty = indptr[1:] > indptr[:-1]
    out[nonempty] = np.add.reduceat(per_entry, indptr[:-1][nonempty])
    return out


def rows_of(indptr, examples):
    """Flat positions of the entries of `examples` (an id < 0 is an empty
    row) and, for each, which of `examples` it belongs to."""
    ok = examples >= 0
    safe = np.where(ok, examples, 0)
    lens = np.where(ok, indptr[safe + 1] - indptr[safe], 0)
    owner = np.repeat(np.arange(len(examples)), lens)
    first = np.cumsum(lens) - lens
    flat = indptr[safe][owner] + np.arange(int(lens.sum())) - first[owner]
    return flat, owner


def cocoa_round(indptr, indices, values, labels, slots, draws, w, alpha, lam,
                mode="avg", stepsize=1.0, sigma_prime=None, chunk=512):
    """One outer round -> (w, alpha) after it, float64.

    `slots` (K, rows): the example id in each slot of each chain, -1 where a
    slot is empty.  `draws` (K, H): the slot each chain visits at each of its
    H local steps.  `alpha` (n,): the signed duals by example."""
    indptr = np.asarray(indptr, np.int64)
    vals = np.asarray(values, np.float64)
    y_all = np.asarray(labels, np.float64)
    w = np.asarray(w, np.float64)
    alpha = np.asarray(alpha, np.float64)
    n, (n_chains, n_steps) = len(y_all), draws.shape
    lam_n = lam * n
    if mode == "avg":
        g, s = stepsize / n_chains, 1.0
    else:
        g = stepsize
        s = stepsize * n_chains if sigma_prime is None else sigma_prime
    sq_norm = row_sums(indptr, vals ** 2)
    dw = np.zeros_like(w)
    a_loc = alpha.copy()  # every example sits in one chain: one shared copy
    for c0 in range(0, n_chains, chunk):
        mine = slots[c0:c0 + chunk]
        m = len(mine)
        u = np.zeros((m, len(w)))  # Dw_k of each chain of the chunk
        chain = np.arange(m)
        for h in range(n_steps):
            ex = mine[chain, draws[c0:c0 + m, h]]
            flat, owner = rows_of(indptr, ex)
            feat, x = indices[flat], vals[flat]
            wx = np.bincount(owner, (w[feat] + s * u[owner, feat]) * x, m)
            ok = (ex >= 0) & (sq_norm[ex] > 0)
            e = ex[ok]
            y, a = y_all[e], a_loc[e]
            new = np.clip(a * y + (1.0 - y * wx[ok]) * lam_n / (s * sq_norm[e]),
                          0.0, 1.0)
            d = np.zeros(m)
            d[ok] = y * new - a
            a_loc[e] += d[ok]  # chains hold disjoint examples
            u[owner, feat] += d[owner] * x / lam_n  # a row's features are distinct
        dw += u.sum(axis=0)
    return w + g * dw, alpha + g * (a_loc - alpha)


def primal_of(indptr, indices, values, alpha, lam, n_features):
    """X^T alpha / (lam n): the w that the dual state stands for."""
    per_entry = np.repeat(np.asarray(alpha, np.float64), np.diff(indptr))
    return np.bincount(indices, per_entry * values, n_features) / (lam * len(alpha))


def objective(indptr, indices, values, labels, w, lam):
    """(lam / 2) |w|^2 + mean hinge loss."""
    w = np.asarray(w, np.float64)
    wx = row_sums(np.asarray(indptr, np.int64), w[indices] * values)
    return float(0.5 * lam * (w @ w) + np.maximum(0.0, 1.0 - labels * wx).mean())


def rel_err(got, want):
    """max|got - want| / max|want|."""
    want = np.asarray(want, np.float64)
    return float(np.abs(np.asarray(got, np.float64) - want).max()
                 / max(np.abs(want).max(), 1e-300))
