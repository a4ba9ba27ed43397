"""Plain numpy reference for CoCoA / CoCoA+ rounds of a hinge-loss linear SVM
over a DENSE example matrix.

float64, on the `(n, d)` matrix of the examples themselves.  It knows nothing
of the program's row layouts, its Gram tensor or how it reduces the round's
update, and it does not reassociate the chain: every chain is a plain SDCA
chain on its own copy of w.  It is handed what belongs to the configuration
and not to the mathematics: which example sits in which slot of which chain,
and which slot every chain draws at every local step.

    min_w  (lam / 2) |w|^2 + (1 / n) sum_j max(0, 1 - y_j w.x_j)

One outer round (`reference_cocoa.py` has the sources): every chain k starts
from w_loc = w and its own alphas and, for its drawn slots in order,

    d_j      = y_j clip(alpha_j y_j + (1 - y_j w_loc.x_j) lam n / (s |x_j|^2), 0, 1) - alpha_j
    alpha_j += d_j,   w_loc += s d_j x_j / (lam n)

then Dw_k = (w_loc - w) / s, w += g sum_k Dw_k, alpha += g Dalpha, with
(g, s) = (stepsize / K, 1) when averaging and (stepsize, stepsize * K, or the
given `sigma_prime`) when adding.  alpha_j carries the label's sign.
"""

from __future__ import annotations

import numpy as np

from benchmark.reference_cocoa import rel_err  # noqa: F401  the same comparison

# examples converted to float64 at a time by the whole-matrix passes, and
# chains a chunk: float64 temporaries of 16-50 MB (larger ones are mapped and
# faulted in anew at every use, which costs more than the arithmetic)
ROWS = 1024
CHUNK = 64


def _strips(X):
    for lo in range(0, len(X), ROWS):
        yield lo, np.asarray(X[lo:lo + ROWS], np.float64)


def cocoa_round(X, labels, slots, draws, w, alpha, lam, mode="avg",
                stepsize=1.0, sigma_prime=None, chunk=CHUNK):
    """One outer round -> (w, alpha) after it, float64.

    `X` (n, d): the examples, any float type (a chunk of chains is converted
    at a time: `chunk` x rows x d float64).  `slots` (K, rows): the example
    id in each slot of each chain, -1 where a slot is empty.  `draws` (K, H):
    the slot each chain visits at each of its H local steps.  `alpha` (n,):
    the signed duals by example."""
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
    dw = np.zeros_like(w)
    a_loc = alpha.copy()  # every example sits in one chain: one shared copy
    rows_buf = np.empty((min(chunk, n_chains), slots.shape[1], len(w)))
    for c0 in range(0, n_chains, chunk):
        mine = slots[c0:c0 + chunk]
        m = len(mine)
        held = mine >= 0
        rows = rows_buf[:m]  # the chunk's examples in float64: (m, rows, d)
        rows[...] = X[np.where(held, mine, 0)]
        rows[~held] = 0.0
        sq_norm = np.einsum("mrd,mrd->mr", rows, rows)
        w_loc = np.tile(w, (m, 1))  # each chain's own copy of w
        chain = np.arange(m)
        for h in range(n_steps):
            at = draws[c0:c0 + m, h]
            x, q = rows[chain, at], sq_norm[chain, at]
            ok = held[chain, at] & (q > 0)
            e = mine[chain, at][ok]
            y, a = y_all[e], a_loc[e]
            margin = np.einsum("md,md->m", w_loc, x)
            new = np.clip(a * y + (1.0 - y * margin[ok]) * lam_n / (s * q[ok]),
                          0.0, 1.0)
            d = np.zeros(m)
            d[ok] = y * new - a
            a_loc[e] += d[ok]  # chains hold disjoint examples
            w_loc += (s * d / lam_n)[:, None] * x
        dw += ((w_loc - w) / s).sum(axis=0)
    return w + g * dw, alpha + g * (a_loc - alpha)


def primal_of(X, alpha, lam):
    """X^T alpha / (lam n): the w that the dual state stands for."""
    alpha = np.asarray(alpha, np.float64)
    out = np.zeros(X.shape[1])
    for lo, rows in _strips(X):
        out += alpha[lo:lo + len(rows)] @ rows
    return out / (lam * len(alpha))


def objective(X, labels, w, lam):
    """(lam / 2) |w|^2 + mean hinge loss."""
    w = np.asarray(w, np.float64)
    hinge = sum(np.maximum(0.0, 1.0 - labels[lo:lo + len(rows)] * (rows @ w)).sum()
                for lo, rows in _strips(X))
    return float(0.5 * lam * (w @ w) + hinge / len(labels))
