"""Inputs made from `--seed`: ratings, init factors, a catalog, query vectors.

Every seed gets the same sizes in another order: the degree sequences of the
ALS problem are fixed by the configuration (so the padded shapes, the compiled
program and the work per iteration are the same for every seed) and the seed
decides which id holds which degree, who rated what, and the rating values.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np


def _round_to_total(expected, total, lo, hi):
    """Whole numbers in [lo, hi] that follow `expected` and sum to `total`."""
    out = np.clip(np.floor(expected), lo, hi).astype(np.int64)
    short = int(total - out.sum())
    if short < 0:
        raise ValueError("degree floor overshoots the total")
    # hand the remainder out one each, largest fractional part first
    room = np.flatnonzero(out < hi)
    frac = (expected - np.floor(expected))[room]
    order = room[np.argsort(-frac, kind="stable")]
    while short > 0:
        take = order[:short]
        out[take] += 1
        short -= len(take)
        order = order[out[order] < hi]
    return out


def als_degrees(cfg):
    """(user degrees, item degrees): fixed by the configuration, not the seed.

    Users: lognormal, clipped to [min, max] and rescaled to the rating total.
    Items: Zipf-Mandelbrot w_i ~ 1/(i + q) over the ranked catalog."""
    a = cfg["assumed"]
    n_users, n_items, nnz = cfg["n_users"], cfg["n_items"], cfg["nnz"]
    rng = np.random.default_rng(a["degree_seed"])
    raw = rng.lognormal(a["user_degree_lognormal_mu"],
                        a["user_degree_lognormal_sigma"], n_users)
    lo, hi = a["user_degree_min"], a["user_degree_max"]
    # bisect the scale at which the clipped degrees reach the total
    s_lo, s_hi = 1e-3, 1e3
    for _ in range(60):
        mid = (s_lo * s_hi) ** 0.5
        if np.clip(np.floor(raw * mid), lo, hi).sum() > nnz:
            s_hi = mid
        else:
            s_lo = mid
    user_deg = _round_to_total(raw * s_lo, nnz, lo, hi)
    w = 1.0 / (np.arange(1, n_items + 1) + a["item_zipf_mandelbrot_q"])
    item_deg = _round_to_total(w / w.sum() * nnz, nnz, 1, nnz)
    return user_deg, item_deg


def pair_dots(a, b, rows, cols, chunk=1 << 21):
    out = np.empty(len(rows), np.float32)
    for s in range(0, len(rows), chunk):
        e = s + chunk
        out[s:e] = np.einsum("nk,nk->n", a[rows[s:e]], b[cols[s:e]])
    return out


def als_problem(cfg, seed):
    """-> users, items, ratings (nnz each), init (user, item) factors.

    A configuration-model pairing of the two fixed degree sequences (a pair
    may repeat: ALS sums it as one more rating), half-star ratings with a
    planted rank-8 signal, uniform(0,1)/sqrt(k) starting factors."""
    a = cfg["assumed"]
    n_users, n_items, k = cfg["n_users"], cfg["n_items"], cfg["rank"]
    user_deg, item_deg = als_degrees(cfg)
    rng = np.random.default_rng(seed)
    users = np.repeat(rng.permutation(n_users).astype(np.int32), user_deg)
    items = np.repeat(rng.permutation(n_items).astype(np.int32), item_deg)
    rng.shuffle(items)
    r = a["planted_rank"]
    u_true = rng.standard_normal((n_users, r), dtype=np.float32)
    v_true = rng.standard_normal((n_items, r), dtype=np.float32)
    raw = (3.0 + 0.35 * pair_dots(u_true, v_true, users, items)
           + 0.3 * rng.standard_normal(len(users), dtype=np.float32))
    ratings = np.clip(np.round(raw * 2) / 2, 0.5, 5.0).astype(np.float32)
    scale = np.float32(1.0 / np.sqrt(k))
    init = (rng.random((n_users, k), dtype=np.float32) * scale,
            rng.random((n_items, k), dtype=np.float32) * scale)
    return users, items, ratings, init


def catalog(cfg, seed, parts=16):
    """-> (ids, rows): the served item catalog, f32, made on the host because
    `bulk_load` takes a host array (and the reference scores the same copy).
    Entries iid normal(0, 1/rank): row norms near 1, not normalised.
    `parts` independent streams fill it in threads (numpy drops the GIL);
    the count is fixed, so the rows do not depend on the machine."""
    n, k = cfg["rows"], cfg["rank"]
    rows = np.empty((n, k), np.float32)
    scale = np.float32(1.0 / np.sqrt(k))
    bounds = np.linspace(0, n, parts + 1).astype(np.int64)

    def fill(j):
        out = rows[bounds[j]:bounds[j + 1]]
        np.random.default_rng([seed, 4, j]).standard_normal(out=out, dtype=np.float32)
        out *= scale

    with ThreadPoolExecutor(parts) as pool:
        list(pool.map(fill, range(parts)))
    ids = list(map(str, range(1, n + 1)))  # 1-based row numbers
    return ids, rows


def queries(seed, pool, rank):
    """`pool` unit-norm query vectors; request i sends vector i % pool."""
    rng = np.random.default_rng([seed, 1])
    q = rng.standard_normal((pool, rank), dtype=np.float32)
    q /= np.linalg.norm(q, axis=1, keepdims=True)
    return q.astype(np.float32)


def query_payload(vec):
    """The TOPKV wire payload; %.9g round-trips an f32."""
    return ";".join("%.9g" % x for x in vec.tolist())
