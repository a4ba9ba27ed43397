"""The dense row layout of `ops/svm.py` (PR 37): the rule that picks it from
the data, what `prepare_svm_blocked` builds for it, the compiled round against
the benchmark's plain dense reference (`benchmark/reference_cocoa_dense.py`:
numpy, float64, a plain SDCA chain on a local copy of w) in both combines, and
against the sparse layout forced on the same rows; its scopes, precisions
and gauges; the synth law, the counts and the rehearsal of the cell
`epsilon-cocoa-plus.dense-rounds`."""

import ast
import json
import os
import re
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference_cocoa as ref_csr
from benchmark import reference_cocoa_dense as ref
from benchmark import roofline_cocoa_dense, synth_epsilon
from benchmark.drivers.cocoa_rounds import by_example, slots_of, step_draws
from benchmark.readers import trace_scope_roofline
from flink_ms_tpu.core.formats import SparseData
from flink_ms_tpu.obs import metrics as obs_metrics
from flink_ms_tpu.ops import svm
from flink_ms_tpu.ops.svm import SVMConfig, compile_svm_fit, prepare_svm_blocked
from flink_ms_tpu.parallel.mesh import make_mesh

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = os.path.join(REPO, "benchmark", "tests", "tiny-epsilon", "BENCHMARK.json")
TINY_CELL = "epsilon-tiny.dense-rounds"
SEED = 13
LAM = 1e-3
# float32 state against float64: sums of d = 24 products a row and three
# rounds of steps; bfloat16 (8 bits of mantissa) misses by 100x
TOL = 2e-5
ROUND_SCOPES = ("svm.margins", "svm.steps", "svm.dw", "svm.combine")


def full_rows(n=61, d=24, seed=3, in_order=True):
    """Unit-norm rows that hold every feature, as CSR triples: what a LIBSVM
    reader makes of a dense file (`in_order`), or the same rows with each
    row's entries listed in an order of its own."""
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n, d))
    X /= np.linalg.norm(X, axis=1, keepdims=True)
    ids = np.tile(np.arange(d), (n, 1))
    if not in_order:
        ids = rng.permuted(ids, axis=1)
    labels = np.where(rng.random(n) < 0.5, 1.0, -1.0)
    data = SparseData(labels=labels, indptr=np.arange(n + 1) * d,
                      indices=ids.reshape(-1),
                      values=np.take_along_axis(X, ids, 1).reshape(-1),
                      n_features=d)
    return data, X


def run_program(data, chains, mode, rounds, sigma_prime=None, devices=1,
                inner="gram", dtype=jnp.float32):
    problem = prepare_svm_blocked(data, chains, seed=SEED)
    cfg = SVMConfig(local_iterations=problem.rows_per_block,
                    regularization=LAM, seed=SEED, mode=mode, inner=inner,
                    sigma_prime=sigma_prime, dtype=dtype)
    fit, args = compile_svm_fit(problem, cfg, make_mesh(devices))
    w, alpha = fit(rounds, *args)
    return (problem, args, np.asarray(w).astype(np.float64),
            np.asarray(alpha).astype(np.float64))


def run_reference(X, labels, chains, mode, rounds, sigma_prime=None):
    n = len(labels)
    rows = -(-n // chains)
    slots = slots_of(SEED, n, chains, rows)
    w, alpha = np.zeros(X.shape[1]), np.zeros(n)
    for r in range(rounds):
        w, alpha = ref.cocoa_round(
            X, labels, slots, step_draws(SEED, chains, r, rows, rows), w,
            alpha, LAM, mode=mode, sigma_prime=sigma_prime)
    return slots, w, alpha


# -- the rule -------------------------------------------------------------------

@pytest.mark.parametrize("n, d, nnz, itemsize, dense", [
    (677399, 47236, 49556258, 4, False),   # RCV1: 0.16% dense
    (400000, 2000, 800000000, 4, True),    # epsilon: every cell stored
    (581012, 54, 6940438, 4, False),       # covertype: 22%
    (100, 10, 500, 4, True),               # the boundary: 4000 B either way
    (100, 10, 499, 4, False),
    (100, 10, 334, 2, True),               # bfloat16: 6 B an entry, from 1/3
    (100, 10, 333, 2, False),
    (0, 10, 0, 4, False), (10, 10, 0, 4, False)])  # nothing stored
def test_the_rule_is_a_pure_function_of_the_counts(n, d, nnz, itemsize, dense):
    assert svm.stores_rows_dense(n, d, nnz, itemsize) is dense


def test_full_rows_take_the_dense_layout_and_sparse_rows_the_sparse_one():
    dense, _ = full_rows()
    assert prepare_svm_blocked(dense, 4).dense
    rng = np.random.default_rng(0)
    sparse = SparseData(labels=np.ones(50), indptr=np.arange(51) * 3,
                        indices=np.concatenate(
                            [rng.choice(40, 3, replace=False) for _ in range(50)]),
                        values=rng.random(150) + 0.1, n_features=40)
    problem = prepare_svm_blocked(sparse, 4)
    assert not problem.dense and problem.idx.shape == (4, 13, 3)


# -- the host layout ------------------------------------------------------------

@pytest.mark.parametrize("in_order", [True, False])
def test_prepare_builds_no_id_rectangle_and_keeps_the_sparse_paths_vectors(
        in_order, monkeypatch):
    data, X = full_rows(in_order=in_order)
    dense = prepare_svm_blocked(data, 6, seed=SEED)
    monkeypatch.setattr(svm, "stores_rows_dense", lambda *a: False)
    sparse = prepare_svm_blocked(data, 6, seed=SEED)
    assert dense.idx is None and sparse.idx.shape == (6, 11, 24)
    assert dense.val.shape == (6, 11, 24) and dense.val.dtype == np.float32
    assert np.array_equal(dense.label, sparse.label)
    assert np.array_equal(dense.row_len, sparse.row_len)
    np.testing.assert_allclose(dense.sq_norm, sparse.sq_norm, rtol=2e-7)
    order = np.random.default_rng(SEED).permutation(61)
    flat = dense.val.reshape(66, 24)
    assert np.array_equal(flat[:61], X[order].astype(np.float32))
    assert not flat[61:].any() and not dense.label.reshape(-1)[61:].any()


def test_rows_that_are_mostly_there_are_scattered_to_their_columns():
    rng = np.random.default_rng(5)
    n, d = 40, 16
    X = rng.standard_normal((n, d)) * (rng.random((n, d)) < 0.8)
    X[3] = 0.0  # an empty row
    rows, cols = np.nonzero(X)
    data = SparseData(labels=np.ones(n),
                      indptr=np.concatenate([[0], np.cumsum((X != 0).sum(1))]),
                      indices=cols, values=X[rows, cols], n_features=d)
    problem = prepare_svm_blocked(data, 3, seed=SEED)
    assert problem.dense
    order = np.random.default_rng(SEED).permutation(n)
    assert np.array_equal(problem.val.reshape(-1, d)[:n],
                          X[order].astype(np.float32))
    assert np.array_equal(problem.row_len.reshape(-1)[:n], (X != 0).sum(1)[order])


def test_strips_cover_every_row_once(monkeypatch):
    monkeypatch.setattr(svm, "_STRIP_BYTES", 24 * 8 * 7)  # 7 rows a strip
    data, X = full_rows()
    problem = prepare_svm_blocked(data, 4, seed=SEED)
    order = np.random.default_rng(SEED).permutation(61)
    assert np.array_equal(problem.val.reshape(-1, 24)[:61],
                          X[order].astype(np.float32))
    np.testing.assert_allclose(problem.sq_norm.reshape(-1)[:61], 1.0, rtol=1e-6)


# -- the plain reference ----------------------------------------------------------

def brute_force_round(X, y, slots, draws, w, alpha, lam, mode, sigma_prime):
    """One chain at a time, one step at a time, scalars: the definition."""
    n, K = len(y), len(slots)
    g, s = (1.0 / K, 1.0) if mode == "avg" else (1.0, sigma_prime or float(K))
    w_new, a_new = w.copy(), alpha.copy()
    for k in range(K):
        w_loc, a = w.copy(), alpha.copy()
        for slot in draws[k]:
            j = slots[k, slot]
            if j < 0:
                continue
            margin = float(w_loc @ X[j])
            new = min(max(a[j] * y[j] + (1 - y[j] * margin) * lam * n
                          / (s * float(X[j] @ X[j])), 0.0), 1.0)
            delta = y[j] * new - a[j]
            a[j] += delta
            w_loc += s * delta * X[j] / (lam * n)
        w_new += g * (w_loc - w) / s
        a_new += g * (a - alpha)
    return w_new, a_new


@pytest.mark.parametrize("mode, sigma_prime", [
    ("avg", None), ("add", None), ("add", 2.0)])
def test_dense_reference_against_a_brute_force_chain_loop(mode, sigma_prime):
    rng = np.random.default_rng(1)
    X = rng.standard_normal((14, 7))  # 3 chains x 5 rows, one slot empty
    y = np.where(rng.random(14) < 0.5, 1.0, -1.0)
    slots = slots_of(SEED, 14, 3, 5)
    w, alpha = 0.1 * rng.standard_normal(7), np.zeros(14)
    for r in range(2):  # the second round starts from a state that moved
        draws = step_draws(SEED, 3, r, 6, 5)
        want = brute_force_round(X, y, slots, draws, w, alpha, LAM, mode,
                                 sigma_prime)
        got = ref.cocoa_round(X, y, slots, draws, w, alpha, LAM, mode=mode,
                              sigma_prime=sigma_prime, chunk=2)
        np.testing.assert_allclose(got[0], want[0], rtol=1e-12, atol=1e-14)
        np.testing.assert_allclose(got[1], want[1], rtol=1e-12, atol=1e-14)
        w, alpha = got
    assert np.abs(alpha).max() > 0


@pytest.mark.parametrize("mode, sigma_prime", [
    ("avg", None), ("add", None), ("add", 4.0)])
def test_dense_reference_agrees_with_the_csr_reference(mode, sigma_prime):
    data, X = full_rows(in_order=False)
    slots = slots_of(SEED, 61, 4, 16)
    draws = step_draws(SEED, 4, 0, 16, 16)
    w, alpha = np.zeros(24), np.zeros(61)
    for _ in range(2):
        got = ref.cocoa_round(X, data.labels, slots, draws, w, alpha, LAM,
                              mode=mode, sigma_prime=sigma_prime)
        want = ref_csr.cocoa_round(data.indptr, data.indices, data.values,
                                   data.labels, slots, draws, w, alpha, LAM,
                                   mode=mode, sigma_prime=sigma_prime)
        np.testing.assert_allclose(got[0], want[0], rtol=1e-10, atol=1e-13)
        np.testing.assert_allclose(got[1], want[1], rtol=1e-10, atol=1e-13)
        w, alpha = got
    csr = (data.indptr, data.indices, data.values)
    np.testing.assert_allclose(ref.primal_of(X, alpha, LAM),
                               ref_csr.primal_of(*csr, alpha, LAM, 24), rtol=1e-12)
    assert ref.objective(X, data.labels, w, LAM) == pytest.approx(
        ref_csr.objective(*csr, data.labels, w, LAM), rel=1e-12)


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(REPO, "benchmark", "reference_cocoa_dense.py")) as f:
        tree = ast.parse(f.read())
    names = [n.module if isinstance(n, ast.ImportFrom) else n.names[0].name
             for n in ast.walk(tree) if isinstance(n, (ast.Import, ast.ImportFrom))]
    assert not [m for m in names if m.startswith(("flink_ms_tpu", "jax"))]


# -- the program against the reference -------------------------------------------

@pytest.mark.parametrize("rounds", [1, 3])
@pytest.mark.parametrize("devices", [1, 4])
@pytest.mark.parametrize("mode, sigma_prime", [
    ("avg", None), ("avg", 4.0), ("add", None), ("add", 4.0)])
def test_dense_round_agrees_with_the_dense_reference(mode, sigma_prime, devices,
                                                     rounds):
    data, X = full_rows()
    problem, args, w, alpha = run_program(data, 6, mode, rounds, sigma_prime,
                                          devices)
    assert problem.dense and args[1] is None
    assert args[2].shape == (-(-6 // devices) * devices * 11, 24)  # X, once, flat
    slots, w_ref, a_ref = run_reference(
        X, data.labels, 6, mode, rounds,
        sigma_prime if mode == "add" else None)  # averaging ignores sigma'
    a = by_example(alpha, slots, 61)
    assert ref.rel_err(w, w_ref) < TOL
    assert ref.rel_err(a, a_ref) < TOL
    assert ref.rel_err(w, ref.primal_of(X, a, LAM)) < TOL  # both combines keep it
    ya = data.labels * a
    assert ya.min() >= -1e-7 and ya.max() <= 1 + 1e-6 and np.abs(a).max() > 0


@pytest.mark.parametrize("mode", ["avg", "add"])
@pytest.mark.parametrize("inner", ["gram", "scatter"])
@pytest.mark.parametrize("devices", [1, 4])
def test_dense_layout_agrees_with_the_sparse_layout_forced_on_the_same_rows(
        devices, inner, mode, monkeypatch):
    data, X = full_rows(in_order=False)
    dense, _, w, alpha = run_program(data, 6, mode, 2, devices=devices, inner=inner)
    monkeypatch.setattr(svm, "stores_rows_dense", lambda *a: False)
    sparse, args, w_sparse, a_sparse = run_program(data, 6, mode, 2,
                                                   devices=devices, inner=inner)
    assert dense.dense and not sparse.dense and args[1] is not None
    assert ref.rel_err(w, w_sparse) < TOL
    assert ref.rel_err(alpha, a_sparse) < TOL
    # and the scatter engine on dense rows, which auto picks where the Gram
    # tensor would pass its budget, is the same fit as the reference's
    _, w_ref, _ = run_reference(X, data.labels, 6, mode, 2)
    assert ref.rel_err(w, w_ref) < TOL


def test_auto_falls_to_the_scatter_engine_on_dense_rows_past_the_gram_budget(
        monkeypatch):
    data, X = full_rows()
    monkeypatch.setenv("FLINK_MS_SVM_GRAM_BYTES", "100")
    problem, args, w, _ = run_program(data, 6, "add", 1, inner="auto")
    assert problem.dense and len(args) == 7  # no Gram tensor among them
    assert gauges()["tpums_svm_gram_bytes"] == 0
    _, w_ref, _ = run_reference(X, data.labels, 6, "add", 1)
    assert ref.rel_err(w, w_ref) < TOL


def test_bfloat16_state_misses_the_same_tolerance():
    data, X = full_rows()
    _, _, w, alpha = run_program(data, 6, "add", 1, dtype=jnp.bfloat16)
    slots, w_ref, a_ref = run_reference(X, data.labels, 6, "add", 1)
    assert ref.rel_err(w, w_ref) > 20 * TOL
    assert ref.rel_err(by_example(alpha, slots, 61), a_ref) > 20 * TOL


# -- scopes, precisions, gauges -------------------------------------------------

def lowered_round(inner="gram"):
    problem = prepare_svm_blocked(full_rows()[0], 4, seed=SEED)
    cfg = SVMConfig(local_iterations=problem.rows_per_block,
                    regularization=LAM, seed=SEED, mode="add", inner=inner)
    fit, args = compile_svm_fit(problem, cfg, make_mesh(1))
    _, gram_fn, _ = svm._cached_fit(problem, cfg, make_mesh(1))
    return (jax.jit(lambda *a: fit(1, *a)).lower(*args).as_text(debug_info=True),
            gram_fn.lower(args[2]).as_text(debug_info=True) if gram_fn else "")


@pytest.fixture(scope="module")
def lowered():
    return lowered_round()


@pytest.mark.parametrize("scope", ROUND_SCOPES)
def test_the_dense_round_carries_the_round_scopes(lowered, scope):
    assert scope in lowered[0]


def test_the_dense_gram_build_carries_its_scope(lowered):
    assert "svm.gram" in lowered[1] and "svm.gram" not in lowered[0]


def test_the_dense_scatter_round_has_no_margins_scope():
    text, gram = lowered_round("scatter")
    assert gram == "" and "svm.margins" not in text
    assert all(scope in text for scope in ROUND_SCOPES[1:])


@pytest.mark.parametrize("precision, want", [("highest", "HIGHEST"),
                                             ("default", "DEFAULT")])
def test_every_product_of_the_dense_round_runs_at_the_stated_precision(
        precision, want, monkeypatch):
    """On a TPU a float32 product at the default precision is one bfloat16
    pass; the control `one_pass_products` patches the one constant that
    names the dense products' precision.  A CPU product at default precision
    is full f32, so only the lowered program (and a chip run) shows it."""
    monkeypatch.setattr(svm, "_DENSE_PRECISION", precision)
    svm._FIT_CACHE.clear()
    try:
        texts = lowered_round()
    finally:
        svm._FIT_CACHE.clear()
    for text, products in zip(texts, (2, 1)):  # margins and dw; the Gram build
        found = re.findall(r"precision = \[(\w+), (\w+)\]", text)
        assert len(found) == text.count("stablehlo.dot_general") == products
        assert set(found) == {(want, want)}


def gauges():
    return {g["name"]: g["value"]
            for g in obs_metrics.get_registry().snapshot()["gauges"]
            if g["name"].startswith("tpums_svm_") and not g["labels"]}


@pytest.mark.parametrize("mode, sigma_prime, want_sigma", [
    ("avg", None, 1.0), ("add", None, 6.0), ("add", 2.5, 2.5)])
@pytest.mark.parametrize("devices", [1, 4])
def test_gauges_read_what_the_layout_implies_on_both_layouts(
        devices, mode, sigma_prime, want_sigma, monkeypatch):
    data, _ = full_rows()
    padded_chains = -(-6 // devices) * devices
    run_program(data, 6, mode, 1, sigma_prime, devices)
    got = gauges()
    slots = padded_chains * 11
    assert got["tpums_svm_rows"] == slots and got["tpums_svm_row_width"] == 24
    assert got["tpums_svm_dense_entries"] == slots * 24
    assert got["tpums_svm_pad_entries"] == (slots - 61) * 24  # pad rows x d
    assert got["tpums_svm_gram_bytes"] == padded_chains * 11 * 11 * 4
    assert got["tpums_svm_sigma_prime"] == want_sigma
    assert "dense rows" in svm.layout_report()
    assert f"sigma' {want_sigma:g}" in svm.layout_report()
    monkeypatch.setattr(svm, "stores_rows_dense", lambda *a: False)
    _, args, _, _ = run_program(data, 6, mode, 1, sigma_prime, devices)
    got = gauges()
    # the same rows sparse: 24 entries a row, three steps of 8 in one block
    # of tile rows on every device that holds a chain (of four devices the
    # last holds the two empty ones), no cell held dense
    in_use = [[3]] * min(devices, 3) + [[0]] * (devices - 3)
    assert np.asarray(args[10]).tolist() == in_use
    assert got["tpums_svm_rows"] * got["tpums_svm_row_width"] == pytest.approx(
        3 * min(devices, 3) * svm._TILE_STEP * svm._TILE_ROWS)
    assert got["tpums_svm_dense_entries"] == got["tpums_svm_head_columns"] == 0
    assert got["tpums_svm_sigma_prime"] == want_sigma
    assert "sparse rows" in svm.layout_report()
    assert "in tiles" in svm.layout_report()


def test_svm_train_says_which_layout_served_a_dense_file(tmp_path, capsys):
    from flink_ms_tpu.train import svm_train

    data, _ = full_rows(n=30, d=6)
    path = tmp_path / "dense.libsvm"
    with open(path, "w") as f:
        for i in range(30):
            ids, vals = data.row(i)
            f.write("%+d %s\n" % (data.labels[i], " ".join(
                f"{j + 1}:{v:.9g}" for j, v in zip(ids, vals))))
    svm_train.main(["--training", str(path), "--blocks", "4", "--iteration", "2",
                    "--mode", "add", "--output", str(tmp_path / "w")])
    out = capsys.readouterr().out
    assert "layout dense rows (32 x 6 cells, no ids), sigma' 4" in out


# -- the benchmark's side ---------------------------------------------------------

def cell_config():
    with open(os.path.join(REPO, "benchmark", "configs",
                           "epsilon-cocoa-plus.json")) as f:
        return json.load(f)


def test_the_configuration_states_the_published_shape_uncut():
    cfg = cell_config()
    assert (cfg["rows"], cfg["features"], cfg["nnz"]) == (400000, 2000, 800000000)
    assert cfg["reduced"] == [] and cfg["mode"] == "add" and cfg["sigma_prime"] is None
    assert cfg["local_iterations"] == -(-cfg["rows"] // cfg["blocks"]) == 49
    assert svm.stores_rows_dense(cfg["rows"], cfg["features"], cfg["nnz"], 4)
    assert set(cfg["controls"]) == {"bf16_state", "one_pass_products"}
    assert cfg["controls"]["one_pass_products"]["patch"] == {
        "flink_ms_tpu.ops.svm._DENSE_PRECISION": "default"}
    assert svm._DENSE_PRECISION == "highest"


def test_the_counts_are_at_the_sources_rows_and_charge_no_ids():
    cfg = cell_config()
    flops, nbytes = roofline_cocoa_dense.cocoa_dense_pass(cfg)
    assert flops == 2 * 8e8 and nbytes == 8e8 * 4 + (400000 + 2000) * 4
    flops, nbytes = roofline_cocoa_dense.cocoa_dense_round(cfg)
    assert 6.48e9 < nbytes < 6.49e9 and 3.2e9 < flops < 3.3e9
    assert nbytes / 819e9 > flops / 197e12  # bytes-bound


def test_the_scope_roofline_reader_reads_nothing_without_a_trace():
    class Run:
        trace_path, counts = None, {"iterations": 5}

    assert trace_scope_roofline.read(
        Run(), "svm.dw", list(ROUND_SCOPES), "roofline_cocoa_dense",
        "cocoa_dense_pass", "iterations") is None


def test_the_drivers_first_statement_imports_the_rule():
    """So that a program without the dense layout exits at once on this
    cell, before the sparse path's re-layout of 800M entries starts."""
    with open(os.path.join(REPO, "benchmark", "drivers",
                           "cocoa_dense_rounds.py")) as f:
        body = ast.parse(f.read()).body
    first = body[1]  # after the docstring
    assert isinstance(body[0], ast.Expr) and isinstance(first, ast.ImportFrom)
    assert first.module == "flink_ms_tpu.ops.svm"
    assert [a.name for a in first.names] == ["stores_rows_dense"]


@pytest.fixture(scope="module")
def tiny_cfg():
    with open(os.path.join(REPO, "benchmark", "tests", "tiny-epsilon",
                           "epsilon-tiny.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def two_seeds(tiny_cfg):
    return [synth_epsilon.epsilon_problem(tiny_cfg, seed)
            for seed in (5, 2**31 + 77)]


@pytest.mark.parametrize("which", [0, 1])
def test_synth_rows_are_full_unit_norm_and_nowhere_zero(tiny_cfg, two_seeds, which):
    indptr, indices, values, labels = two_seeds[which]
    n, d = tiny_cfg["rows"], tiny_cfg["features"]
    assert values.dtype == np.float32 and indices.dtype == np.int32
    assert np.array_equal(indptr, np.arange(n + 1) * d)
    assert np.array_equal(indices.reshape(n, d), np.tile(np.arange(d), (n, 1)))
    assert (values != 0).all()
    sq = (values.reshape(n, d).astype(np.float64) ** 2).sum(axis=1)
    np.testing.assert_allclose(sq, 1.0, atol=1e-6)
    assert set(np.unique(labels)) == {-1.0, 1.0}
    assert abs((labels > 0).mean() - tiny_cfg["assumed"]["positive_share"]) < 2e-3


def test_synth_seeds_differ_in_values_and_share_one_shape(tiny_cfg, two_seeds):
    shapes = set()
    for indptr, indices, values, labels in two_seeds:
        data = SparseData(labels=labels, indptr=indptr, indices=indices,
                          values=values, n_features=tiny_cfg["features"])
        problem = prepare_svm_blocked(data, tiny_cfg["blocks"])
        shapes.add((problem.dense, problem.val.shape))
    assert shapes == {(True, (tiny_cfg["blocks"], tiny_cfg["local_iterations"],
                              tiny_cfg["features"]))}
    assert not np.array_equal(two_seeds[0][2], two_seeds[1][2])
    again = synth_epsilon.epsilon_problem(tiny_cfg, 5)
    assert all(np.array_equal(a, b) for a, b in zip(two_seeds[0], again))


# -- the cell's rehearsal -----------------------------------------------------------

def rehearse(trace, *more):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--bench", TINY, "--workload",
         TINY_CELL, "--seed", "3000037019", "--seconds",
         "0.2" if trace else "1",  # a CPU trace of a thousand rounds reads slowly
         "--trace", str(trace), *more],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    shutil.rmtree(os.path.join(REPO, ".benchwork", TINY_CELL), ignore_errors=True)
    assert done.returncode == 0, done.stderr[-2000:]
    return json.loads(done.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_prints_the_contract_line(trace):
    line = rehearse(trace)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"  # a rehearsal says so
    checked = {c["name"] for c in line["checks"]}
    assert {"cocoa_first_w_rel_err", "cocoa_first_alpha_rel_err",
            "cocoa_last_w_rel_err", "cocoa_primal_dual_rel_err",
            "cocoa_box_violation", "cocoa_w_change", "cocoa_objective_drop",
            "tpums_svm_dense_entries"} <= checked
    if trace:
        # no device plane on the CPU: the scope and roofline readers leave
        # their metrics out and do not raise
        assert line["metrics"]["cocoa_dense_share"]["value"] == 100.0
        assert line["metrics"]["cocoa_pad_share"]["value"] == pytest.approx(
            100 * 12 / 1312)
        assert not {"cocoa_dense_round_roofline", "cocoa_dense_margins_roofline",
                    "cocoa_dense_dw_roofline"} & set(line["metrics"])
        assert {"cocoa_gram_build_s", "cocoa_place_s", "cocoa_prepare_s",
                "setup_program_s"} <= set(line["metrics"])
    else:
        assert set(line["metrics"]) == {"train_iter_s", "setup_s"}
        assert line["layers"]["cocoa_dense_share"]["value"] == 100.0


def test_rehearsal_under_the_bf16_state_control_is_not_correct():
    line = rehearse(0, "--control", "bf16_state")
    assert line["correct"] is False
    failed = {c["name"] for c in line["checks"] if not c["ok"]}
    assert {"cocoa_first_w_rel_err", "cocoa_first_alpha_rel_err"} <= failed
    # the control changes the state's type, not the layout
    assert "tpums_svm_dense_entries" not in failed


def test_rehearsal_under_the_one_pass_control_runs_the_patched_program():
    """`correct` is true here and false on the chip: a CPU product at the
    default precision is full f32 (PERF.md section 2), so what the rehearsal
    can show is that the control's patch reaches the program and the cell
    runs under it; the lowered-program test above holds the precision."""
    line = rehearse(0, "--control", "one_pass_products")
    assert line["failed"] == 0 and line["attempted"] > 0
    assert line["layers"]["cocoa_dense_share"]["value"] == 100.0
