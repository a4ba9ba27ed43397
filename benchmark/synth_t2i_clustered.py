"""A catalog with cluster structure, from `--seed`, for the cells that serve
from the IVF tier: the source's embeddings cannot be fetched (no network), and
on `synth.catalog`'s iid rows a coarse quantiser has nothing to quantise (IVF
recall@10 reads 0.03 there).

The law, every number of it in the configuration's `assumed.law`: `components`
Gaussian components with weights proportional to 1 / i (Zipf, exponent 1:
the largest holds an eighth of the rows, the smallest a thousandth of that),
centres normal(0, 1/rank) per coordinate (norm near 1), a row = its
component's centre + `sigma_w` * normal(0, 1/rank).  Queries are
`synth.queries`: unit-norm gaussian directions, independent of the base.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

STRIP = 1 << 16  # rows a thread fills at a time: bounds the centres' gather


def component_of(cfg, seed):
    """-> (centres (components, rank) f32, component of each row (rows,))."""
    law = cfg["assumed"]["law"]
    n, k, c = cfg["rows"], cfg["rank"], law["components"]
    rng = np.random.default_rng([seed, 5])
    centres = (rng.standard_normal((c, k), dtype=np.float32)
               * np.float32(1.0 / np.sqrt(k)))
    weights = 1.0 / np.arange(1, c + 1) ** law["zipf_exponent"]
    return centres, rng.choice(c, size=n, p=weights / weights.sum()).astype(np.int32)


def catalog(cfg, seed, parts=16):
    """-> (ids, rows) as `synth.catalog` gives them: f32 rows on the host,
    ids the 1-based row numbers.  `parts` independent noise streams fill the
    rows in threads; the count is fixed, so the rows do not depend on the
    machine."""
    n, k = cfg["rows"], cfg["rank"]
    centres, comp = component_of(cfg, seed)
    scale = np.float32(cfg["assumed"]["law"]["sigma_w"] / np.sqrt(k))
    rows = np.empty((n, k), np.float32)
    bounds = np.linspace(0, n, parts + 1).astype(np.int64)

    def fill(j):
        rng = np.random.default_rng([seed, 6, j])
        for lo in range(bounds[j], bounds[j + 1], STRIP):
            out = rows[lo:min(lo + STRIP, bounds[j + 1])]
            rng.standard_normal(out=out, dtype=np.float32)
            out *= scale
            out += centres[comp[lo:lo + len(out)]]

    with ThreadPoolExecutor(parts) as pool:
        list(pool.map(fill, range(parts)))
    return list(map(str, range(1, n + 1))), rows
