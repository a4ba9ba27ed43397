"""The implicit-feedback problem made from `--seed`: (user, song, play
count) triples and starting factors at the configuration's counts.

As `synth.als_problem`: the two degree sequences are fixed by the
configuration (`synth.als_degrees` reads both laws from its `assumed`), so
every seed has the same padded shapes, one compiled program and the same
work an iteration; the seed decides which id holds which degree, who played
what, and how often.
"""

from __future__ import annotations

import numpy as np

from benchmark import synth


def play_counts(rng, n, tail, most):
    """`n` whole play counts, min(floor(U ** (-1 / tail)), most) with U
    uniform in (0, 1]: a discrete Pareto law, most of it 1."""
    u = 1.0 - rng.random(n, dtype=np.float32)  # (0, 1]
    return np.minimum(np.floor(u ** np.float32(-1.0 / tail)), most).astype(np.float32)


def ials_problem(cfg, seed):
    """-> users, items, plays (nnz each), init (user, item) factors.

    A configuration-model pairing of the two fixed degree sequences (a pair
    may repeat: the sweep sums it as one more interaction), heavy-tailed
    play counts independent of the pair, uniform(0,1)/sqrt(k) starting
    factors."""
    a = cfg["assumed"]
    n_users, n_items, k = cfg["n_users"], cfg["n_items"], cfg["rank"]
    user_deg, item_deg = synth.als_degrees(cfg)
    rng = np.random.default_rng(seed)
    users = np.repeat(rng.permutation(n_users).astype(np.int32), user_deg)
    items = np.repeat(rng.permutation(n_items).astype(np.int32), item_deg)
    rng.shuffle(items)
    plays = play_counts(rng, len(users), a["play_tail"], a["play_max"])
    scale = np.float32(1.0 / np.sqrt(k))
    init = (rng.random((n_users, k), dtype=np.float32) * scale,
            rng.random((n_items, k), dtype=np.float32) * scale)
    return users, items, plays, init
