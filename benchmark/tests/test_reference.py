"""The numpy references at a tiny size, against brute force, and the
lower-precision control of the TOPK comparison."""

import numpy as np

from benchmark import reference, synth


def test_ridge_rows_equals_dense_solve():
    rng = np.random.default_rng(0)
    n_rows, n_cols, k, nnz, lam = 30, 20, 4, 400, 0.05
    row_of = rng.integers(0, n_rows, nnz)
    col_of = rng.integers(0, n_cols, nnz)
    vals = rng.random(nnz).astype(np.float32)
    other = rng.standard_normal((n_cols, k)).astype(np.float32)
    sample = np.array([0, 7, 29])
    got = reference.ridge_rows(sample, row_of, col_of, vals, other, lam)
    for x, r in zip(got, sample):
        mine = row_of == r
        y = other[col_of[mine]].astype(np.float64)
        a = y.T @ y + lam * mine.sum() * np.eye(k)
        np.testing.assert_allclose(x, np.linalg.solve(a, y.T @ vals[mine]), rtol=1e-12)


def test_stratified_rows_cover_the_heaviest():
    deg = np.arange(1000) % 97
    deg[123] = 5000
    rows = reference.stratified_rows(deg, 64, np.random.default_rng(1))
    assert 123 in rows and len(rows) <= 64
    assert deg[rows].min() <= 2  # the light end is looked at too


def test_topk_equals_full_sort():
    rng = np.random.default_rng(2)
    rows = rng.random((5000, 16), dtype=np.float32)
    q = synth.queries(3, 8, 16)
    ids, scores = reference.topk(rows, q, 10, block=1024)
    full = q.astype(np.float64) @ rows.astype(np.float64).T
    want = np.argsort(-full, axis=1)[:, :11]
    assert (ids == want).all()
    np.testing.assert_allclose(scores, np.take_along_axis(full, want, 1), rtol=1e-12)


def test_compare_topk_masks_near_ties_only():
    ref_scores = np.array([[5.0, 4.0, 3.99999, 2.0]])
    ref_ids = np.array([[10, 11, 12, 13]])
    # ranks 1 and 2 are within the gap of each other: swapped ids pass there
    err, wrong, clear = reference.compare_topk(
        np.array([[10, 12, 11]]), np.array([[5.0, 4.0, 3.99999]]),
        ref_ids, ref_scores, 1e-4)
    assert (err, wrong, clear) == (0.0, 0, 1)
    # a wrong id at a clear rank counts
    err, wrong, _ = reference.compare_topk(
        np.array([[99, 11, 12]]), np.array([[5.0, 4.0, 3.99999]]),
        ref_ids, ref_scores, 1e-4)
    assert wrong == 1


def bf16(x):
    """Round-to-nearest-even to bfloat16, kept in float32."""
    b = np.asarray(x, np.float32).view(np.uint32)
    b = (b + 0x7FFF + ((b >> 16) & 1)) & 0xFFFF0000
    return b.view(np.float32)


def test_one_pass_bf16_scoring_fails_the_topk_limits():
    """The control: the reference in the program's place, scored as one bf16
    MXU pass would (bf16 inputs, f32 accumulation).  It has to miss the
    score limit of the committed configuration."""
    import json
    import os

    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "configs", "bigann-t2i-10m.json")) as f:
        cfg = json.load(f)
    cfg = dict(cfg, rows=1 << 16)
    _, rows = synth.catalog(cfg, 5)
    q = synth.queries(5, 16, cfg["rank"])
    ref_ids, ref_scores = reference.topk(rows, q, cfg["k"])
    low = bf16(q) @ bf16(rows).T
    order = np.argsort(-low, axis=1)[:, :cfg["k"]]
    err, _, _ = reference.compare_topk(
        order, np.take_along_axis(low, order, 1), ref_ids, ref_scores,
        cfg["limits"]["topk_gap"])
    assert err > 3 * cfg["limits"]["topk_score_abs_err"]
    sound = q @ rows.T
    order = np.argsort(-sound, axis=1)[:, :cfg["k"]]
    err, wrong, _ = reference.compare_topk(
        order, np.take_along_axis(sound, order, 1), ref_ids, ref_scores,
        cfg["limits"]["topk_gap"])
    assert err < cfg["limits"]["topk_score_abs_err"] / 3 and wrong == 0
