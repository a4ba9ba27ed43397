"""Implicit-feedback ALS (Hu, Koren, Volinsky) as the `msd-ials` cell runs it:
the benchmark's plain reference against a brute-force minimiser of HKV's
objective; the program's implicit sweep against that reference on both solve
routes, both solvers and both assemblies (the einsum pair, and the Pallas
kernel's weighted form interpreted); the route decision as a function of sizes; the
scopes, gauges and the counter PR 33 added to `ops/als.py`; the synthetic
play counts; and the cell's rehearsal on the CPU."""

import json
import os
import shutil
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference_ials as ref
from benchmark import synth, synth_ials
from flink_ms_tpu.obs import metrics as obs_metrics
from flink_ms_tpu.ops import als as A
from flink_ms_tpu.parallel.mesh import make_mesh

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = os.path.join(REPO, "benchmark", "tests", "tiny-ials", "BENCHMARK.json")
TINY_CELL = "msd-tiny.ials-retrain"
ALPHA, LAM = 40.0, 0.1
# float32 sums of at most a few hundred products against float64, one solve
# of a system whose condition number is a few thousand (uniform(0, 1) starting
# factors share a large mean direction): 6e-5 at the worst row seen; the
# bfloat16 exchange (8 bits of mantissa) misses by two orders
TOL = 2e-4
V5E_BYTES = 16909336064  # `bytes_limit` of one TPU v5e chip (chip run, PR 33)


def plays_problem(rng, n_users=60, n_items=25, nnz=420, once=False):
    """Triples with play counts that vary (1..30), every id present; some
    fifty name a (user, item) pair a second time, none where ``once``."""
    users = np.concatenate([np.arange(n_users), rng.integers(0, n_users, nnz - n_users)])
    items = np.concatenate([np.arange(n_items), rng.integers(0, n_items, nnz - n_items)])
    rng.shuffle(items)
    plays = np.minimum(np.floor(rng.random(nnz) ** -0.7), 30.0)
    if once:
        first = np.sort(np.unique(users * n_items + items, return_index=True)[1])
        assert set(users[first]) == set(users) and set(items[first]) == set(items)
        return users[first], items[first], plays[first]
    return users, items, plays


# -- the reference ------------------------------------------------------------

@pytest.mark.parametrize("side", ["user", "item"])
def test_reference_minimises_the_dense_hkv_objective(rng, side):
    """30 x 20, every cell of the play matrix counted: the rows `hkv_rows`
    returns are those of the dense normal equations Y^T C Y + lam I over ALL
    columns (no Y^T Y split), and no nearby point has a lower objective."""
    n, m, k = (30, 20, 5) if side == "user" else (20, 30, 5)
    dense = np.where(rng.random((n, m)) < 0.3,
                     np.floor(rng.random((n, m)) ** -0.8), 0.0)
    rows, cols = np.nonzero(dense)
    other = rng.normal(size=(m, k))
    got = ref.hkv_rows(np.arange(n), rows, cols, dense[rows, cols], other, LAM, ALPHA)
    want = np.empty_like(got)
    for u in range(n):
        c = 1.0 + ALPHA * dense[u]
        want[u] = np.linalg.solve((other * c[:, None]).T @ other + LAM * np.eye(k),
                                  other.T @ (c * (dense[u] > 0)))
    np.testing.assert_allclose(got, want, rtol=1e-9, atol=1e-12)
    best = ref.objective(got, dense, other, LAM, ALPHA)
    for _ in range(8):
        nearby = got + 1e-3 * rng.normal(size=got.shape)
        assert ref.objective(nearby, dense, other, LAM, ALPHA) > best


def test_reference_counts_a_repeated_pair_twice_and_reads_the_play_count(rng):
    other = rng.normal(size=(6, 3))
    once = ref.hkv_rows(np.array([0]), np.array([0, 0]), np.array([1, 4]),
                        np.array([2.0, 3.0]), other, LAM, ALPHA)
    twice = ref.hkv_rows(np.array([0]), np.array([0, 0, 0]), np.array([1, 4, 4]),
                         np.array([2.0, 3.0, 3.0]), other, LAM, ALPHA)
    ones = ref.hkv_rows(np.array([0]), np.array([0, 0]), np.array([1, 4]),
                        np.array([1.0, 1.0]), other, LAM, ALPHA)
    assert np.abs(once - twice).max() > 1e-3 and np.abs(once - ones).max() > 1e-3


# -- the program against the reference ----------------------------------------

def one_iteration(rng, monkeypatch, devices, env, memory=None,
                  assembly="einsum", once=False, **config):
    users, items, plays = plays_problem(rng, once=once)
    if assembly == "kernel":
        # the resolver answered as a TPU would, the kernel interpreted; it
        # is not in the sweep's cache key
        real = A.resolve_assembly
        monkeypatch.setattr(A, "resolve_assembly",
                            lambda _, *a, **kw: real("tpu", *a, **kw))
        monkeypatch.setattr(A, "_SWEEP_CACHE", {})
    k = 8
    init = (rng.random((60, k), dtype=np.float32) / np.sqrt(k),
            rng.random((25, k), dtype=np.float32) / np.sqrt(k))
    for name, value in env.items():
        monkeypatch.setenv(name, value)
    if memory is not None:
        monkeypatch.setattr(A, "device_memory", lambda device: memory)
    cfg = A.ALSConfig(num_factors=k, iterations=1, lambda_=LAM, implicit=True,
                      alpha=ALPHA, exchange_dtype=None, **config)
    mesh = make_mesh(devices)
    problem = A.prepare_blocked(users, items, plays, devices)
    model = A.als_fit(users, items, plays, cfg, mesh, problem=problem, init=init)
    want_u = ref.hkv_rows(np.arange(60), users, items, plays, init[1], LAM, ALPHA)
    want_i = ref.hkv_rows(np.arange(25), items, users, plays,
                          model.user_factors, LAM, ALPHA)
    return problem, cfg, mesh, model, want_u, want_i


def row_error(got, want):
    return float((np.abs(got - want).max(axis=1) / np.abs(want).max(axis=1)).max())


@pytest.mark.parametrize("assembly", ["einsum", "kernel"])
@pytest.mark.parametrize("devices", [1, 4])
@pytest.mark.parametrize("solver", ["lax", "pallas"])
@pytest.mark.parametrize("route", ["0", "1"])
def test_implicit_iteration_agrees_with_the_reference(rng, monkeypatch, route,
                                                      solver, devices,
                                                      assembly):
    """Both routes (materialised, per chunk), both solvers, both
    assemblies, chunks forced small enough that the larger buckets run
    under lax.map.  Kernel, Pallas solver, materialised: A arrives
    lane-major (from the kernel itself where a bucket runs straight-line,
    transposed after the map elsewhere) and Y^T Y is added to it there."""
    from flink_ms_tpu.ops import assemble_pallas

    seen = []
    for name in ("assemble_bucket", "assemble_bucket_lanes"):
        fn = getattr(assemble_pallas, name)
        monkeypatch.setattr(
            assemble_pallas, name,
            lambda *a, fn=fn, name=name, **kw: (
                seen.append((name, kw["alpha"])), fn(*a, **kw))[1])
    # the kernel path counts a gathered row of 8 values as the lane tile of
    # 128 it occupies: sixteen times the budget for the same steps
    budget = "4096" if assembly == "einsum" else "65536"
    _, _, _, model, want_u, want_i = one_iteration(
        rng, monkeypatch, devices,
        {"FLINK_MS_ALS_FUSED": route, "FLINK_MS_ALS_SOLVER": solver,
         "FLINK_MS_ALS_ASSEMBLY_CHUNK_BYTES": budget}, assembly=assembly)
    assert row_error(model.user_factors, want_u) < TOL
    assert row_error(model.item_factors, want_i) < TOL
    if assembly == "einsum":
        assert not seen
    else:
        assert {alpha for _, alpha in seen} == {ALPHA}
        lane_major = solver == "pallas" and route == "0"
        assert ("assemble_bucket_lanes" in {n for n, _ in seen}) is lane_major
        if devices == 1:   # where the budget cuts the larger buckets up
            assert "assemble_bucket" in {n for n, _ in seen}


@pytest.mark.parametrize("segments", [2, 3])
@pytest.mark.parametrize("route", ["0", "1"])
@pytest.mark.parametrize("assembly", ["einsum", "kernel"])
def test_a_table_read_in_segments_agrees_with_the_reference_and_the_whole(
        rng, monkeypatch, assembly, route, segments):
    """A fast memory that holds the user table in `segments` pieces (the
    item table in one fewer): the weighted sums over a list's runs, added in
    the solve dtype, are the list's; Y^T Y is the whole table's either
    way.  Against the float64 reference at the tolerance of the uncut
    sweep, and against the uncut sweep to float32 round-off."""
    from flink_ms_tpu.ops.assemble_pallas import _lanes_vmem_limit

    env = {"FLINK_MS_ALS_FUSED": route, "FLINK_MS_ALS_SOLVER": "pallas",
           "FLINK_MS_ALS_ASSEMBLY_CHUNK_BYTES":
               "4096" if assembly == "einsum" else "65536"}
    # each pair played once: the two sweeps are compared to round-off, and
    # which way round two plays of one pair lie is the fill's to choose
    _, _, _, whole, _, _ = one_iteration(rng, monkeypatch, 1, env,
                                         assembly=assembly, once=True)
    rows = 60 + A._PAD_STRIP
    fit = -(-60 // segments) + A._PAD_STRIP
    fast = _lanes_vmem_limit(8) + A._FAST_MEMORY_SLACK + fit * 512
    monkeypatch.setattr(A, "fast_memory", lambda device: fast)
    monkeypatch.setattr(A, "_SWEEP_CACHE", {})
    problem, cfg, mesh, model, want_u, want_i = one_iteration(
        np.random.default_rng(42), monkeypatch, 1, env, assembly=assembly,
        once=True)
    # ... at three the 25 songs' table no longer fits whole either
    assert A._segments(problem, cfg, mesh) == {"u": segments - 1,
                                               "i": segments}
    assert A.table_segments(rows, 8, 4, fast, _lanes_vmem_limit(8)) == segments
    assert row_error(model.user_factors, want_u) < TOL
    assert row_error(model.item_factors, want_i) < TOL
    assert row_error(model.user_factors, whole.user_factors) < TOL / 4
    assert row_error(model.item_factors, whole.item_factors) < TOL / 4
    gauges = als_gauges()
    cut = sum(a.size for (name, _), side in problem.cuts.items()
              for bucket in side.idx for a in bucket)
    assert gauges["tpums_als_segmented_entries"] == cut > 0
    assert gauges["tpums_als_entries"] == cut + (
        sum(a.size for a in problem.u.idx) if segments == 2 else 0)


def test_the_sweep_decides_per_side_and_still_agrees(rng, monkeypatch):
    """A device whose memory holds four of the item side's tensors and not
    four of the user side's: users per chunk, items materialised."""
    users_bytes, items_bytes = ((n + A._PAD_STRIP) * 8 * 8 * 4 for n in (60, 25))
    memory = 4 * items_bytes + 64
    assert 4 * users_bytes > memory
    problem, cfg, mesh, model, want_u, want_i = one_iteration(
        rng, monkeypatch, 1, {}, memory=memory)
    assert A._routes(problem, cfg, mesh) == {"u": True, "i": False}
    assert row_error(model.user_factors, want_u) < TOL
    assert row_error(model.item_factors, want_i) < TOL
    gauges = als_gauges()
    assert gauges["tpums_als_fused_rows"] == problem.u.per_block
    assert gauges["tpums_als_rows"] == problem.u.per_block + problem.i.per_block


@pytest.mark.parametrize("assembly", ["einsum", "kernel"])
def test_a_sweep_that_ignored_the_play_counts_would_fail(rng, monkeypatch,
                                                         assembly):
    seen = {}
    real = ref.hkv_rows

    def spy(sample, row_of, col_of, plays, other, lam, alpha):
        seen.setdefault("first", (sample, row_of, col_of, plays, other))
        return real(sample, row_of, col_of, plays, other, lam, alpha)

    monkeypatch.setattr(ref, "hkv_rows", spy)
    _, _, _, model, _, _ = one_iteration(rng, monkeypatch, 1, {},
                                         assembly=assembly)
    sample, row_of, col_of, plays, other = seen["first"]
    as_ones = real(sample, row_of, col_of, np.ones_like(plays), other, LAM, ALPHA)
    assert plays.max() > 1
    assert row_error(model.user_factors, as_ones) > 50 * TOL


def test_the_bfloat16_exchange_misses_the_same_tolerance(rng, monkeypatch):
    users, items, plays = plays_problem(rng)
    k = 8
    init = (rng.random((60, k), dtype=np.float32) / np.sqrt(k),
            rng.random((25, k), dtype=np.float32) / np.sqrt(k))
    cfg = A.ALSConfig(num_factors=k, iterations=1, lambda_=LAM, implicit=True,
                      alpha=ALPHA, exchange_dtype="bfloat16")
    model = A.als_fit(users, items, plays, cfg, make_mesh(1), init=init)
    want = ref.hkv_rows(np.arange(60), users, items, plays, init[1], LAM, ALPHA)
    assert row_error(model.user_factors, want) > 10 * TOL


# -- the route decision ---------------------------------------------------------

@pytest.mark.parametrize("rows, k, env, memory, want", [
    # als-ml20m: 1.38 GB and 0.27 GB of 16.9: both sides keep their tensor
    (138494, 50, None, V5E_BYTES, False),
    (26745, 50, None, V5E_BYTES, False),
    # msd-ials: the users' 9.36 GB go per chunk, the items' 0.67 GB do not
    (571356, 64, None, V5E_BYTES, True),
    (41141, 64, None, V5E_BYTES, False),
    # either side of a quarter of the memory
    (1000, 64, None, 4 * 1000 * 64 * 64 * 4, False),
    (1000, 64, None, 4 * 1000 * 64 * 64 * 4 - 1, True),
    # a runtime that reports no memory (the CPU) keeps the tensor
    (571356, 64, None, None, False),
    # the override, both ways
    (571356, 64, "0", V5E_BYTES, False),
    (138494, 50, "1", V5E_BYTES, True),
    (138494, 50, "1", None, True),
])
def test_route_is_a_function_of_the_sizes(monkeypatch, rows, k, env, memory, want):
    monkeypatch.delenv("FLINK_MS_ALS_FUSED", raising=False)
    if env is not None:
        monkeypatch.setenv("FLINK_MS_ALS_FUSED", env)
    assert A.solves_per_chunk(rows, k, 4, memory) is want


@pytest.mark.parametrize("value", ["true", "auto", "2"])
def test_an_unknown_route_value_raises(monkeypatch, value):
    monkeypatch.setenv("FLINK_MS_ALS_FUSED", value)
    with pytest.raises(ValueError, match="FLINK_MS_ALS_FUSED"):
        A.solves_per_chunk(10, 4, 4, V5E_BYTES)


def test_the_cpu_reports_no_memory_and_keeps_the_tensor():
    mesh = make_mesh(1)
    assert A.device_memory(mesh.devices.flat[0]) is None


# -- scopes, gauges, the counter ------------------------------------------------

def lowered(implicit, precision="highest"):
    rng = np.random.default_rng(1)
    users, items, plays = plays_problem(rng)
    problem = A.prepare_blocked(users, items, plays, 1)
    cfg = A.ALSConfig(num_factors=4, iterations=1, implicit=implicit,
                      exchange_dtype=None, assembly_precision=precision)
    fit_fn, dev_args = A.compile_fit(problem, cfg, make_mesh(1))
    return fit_fn.lower(jnp.asarray(1, jnp.int32), *dev_args)


@pytest.mark.parametrize("scope", ["als.gram", "als.weight"])
@pytest.mark.parametrize("implicit", [True, False])
def test_implicit_scopes_are_in_the_implicit_program_alone(implicit, scope):
    text = lowered(implicit).as_text(debug_info=True)
    assert (f"{scope}/" in text or f"{scope}\"" in text) is implicit
    for shared in ("als.exchange", "als.assemble", "als.solve", "als.gather",
                   "als.contract"):
        assert f"{shared}/" in text or f"{shared}\"" in text


@pytest.mark.parametrize("precision, want", [("highest", "HIGHEST"),
                                             ("default", "DEFAULT")])
def test_every_product_of_the_implicit_sweep_runs_at_the_stated_precision(
        precision, want):
    """Y^T Y included: on a TPU a product left at the default is one
    bfloat16 pass, which the `bf16_assembly` control turns on for all of
    them and the sound configuration for none.  (On the CPU both are full
    f32, so only a chip run shows the control `correct: false`.)"""
    import re

    text = lowered(True, precision).as_text()
    found = re.findall(r"precision = \[(\w+), (\w+)\]", text)
    assert len(found) == text.count("stablehlo.dot_general") >= 6
    assert {p for pair in found for p in pair} == {want}


def als_gauges():
    return {g["name"]: g["value"]
            for g in obs_metrics.get_registry().snapshot()["gauges"]
            if g["name"].startswith("tpums_als_") and not g["labels"]}


@pytest.mark.parametrize("devices, route, implicit", [
    (1, "0", True), (1, "1", True), (4, "1", False)])
def test_gauges_and_the_counter_read_what_the_layout_implies(
        rng, monkeypatch, devices, route, implicit):
    monkeypatch.setenv("FLINK_MS_ALS_FUSED", route)
    monkeypatch.setenv("FLINK_MS_ALS_ASSEMBLY_CHUNK_BYTES", "4096")
    users, items, plays = plays_problem(rng)
    k = 8
    problem = A.prepare_blocked(users, items, plays, devices)
    cfg = A.ALSConfig(num_factors=k, iterations=1, implicit=implicit,
                      exchange_dtype=None)
    fit_fn, dev_args = A.compile_fit(problem, cfg, make_mesh(devices))
    got = als_gauges()
    rows = devices * (problem.u.per_block + problem.i.per_block)
    stored = sum(a.size for side in (problem.u, problem.i) for a in side.idx)
    assert got["tpums_als_rows"] == rows
    assert got["tpums_als_fused_rows"] == (rows if route == "1" else 0)
    assert got["tpums_als_entries"] == stored
    assert got["tpums_als_pad_entries"] == stored - 2 * len(plays)
    assert got["tpums_als_pad_slots"] == 2 * devices * A._PAD_STRIP
    assert got["tpums_als_normal_eq_bytes"] == (
        max(problem.u.per_block, problem.i.per_block) * k * k * 4)
    # one step a straight-line bucket, several where 4 KiB cuts one up
    buckets = devices * (len(problem.u.widths) + len(problem.i.widths))
    assert got["tpums_als_chunks"] > buckets
    counter = obs_metrics.get_registry().counter("tpums_als_iterations_total")
    before = counter.value
    state = fit_fn(jnp.asarray(2, jnp.int32), *dev_args)
    fit_fn(3, *state, *dev_args[2:])
    assert counter.value - before == 5


# -- the synthetic plays ----------------------------------------------------------

def cell_config():
    with open(os.path.join(REPO, "benchmark", "configs", "msd-ials.json")) as f:
        return json.load(f)


def test_the_cells_degree_laws_keep_the_sources_filters_and_totals():
    cfg = cell_config()
    user_deg, item_deg = synth.als_degrees(cfg)
    assert len(user_deg) == 571355 and len(item_deg) == 41140
    assert user_deg.sum() == item_deg.sum() == cfg["nnz"] == 33_600_000
    assert user_deg.min() == 20      # users with 20 or more songs
    assert item_deg.min() >= 200     # songs with 200 or more listeners
    assert cfg["reduced"] == [] and cfg["rank"] == 64 and cfg["alpha"] == 40.0


def test_play_counts_vary_are_mostly_one_and_stop_at_the_clip():
    a = cell_config()["assumed"]
    plays = synth_ials.play_counts(np.random.default_rng(5), 200_000,
                                   a["play_tail"], a["play_max"])
    assert plays.dtype == np.float32 and (plays == np.floor(plays)).all()
    assert plays.min() == 1 and plays.max() == a["play_max"]
    assert 0.63 < (plays == 1).mean() < 0.66
    assert 2.2 < plays.mean() < 2.6


def test_a_seed_permutes_ids_and_keeps_the_shapes():
    with open(os.path.join(REPO, "benchmark", "tests", "tiny-ials", "msd-tiny.json")) as f:
        cfg = json.load(f)
    one, two = (synth_ials.ials_problem(cfg, s) for s in (3000033001, 2**31 + 77))
    for a, b in zip(one[:3], two[:3]):
        assert a.shape == b.shape == (cfg["nnz"],) and not np.array_equal(a, b)
    for k in (0, 1):
        assert np.array_equal(np.sort(np.bincount(one[k])), np.sort(np.bincount(two[k])))
    again = synth_ials.ials_problem(cfg, 3000033001)
    assert all(np.array_equal(a, b) for a, b in zip(one[:3], again[:3]))


# -- the cell's rehearsal -------------------------------------------------------

def rehearse(trace, *more):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--bench", TINY, "--workload",
         TINY_CELL, "--seed", "3000033019", "--seconds", "1", "--trace",
         str(trace), *more],
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
    assert {"ials_first_user_rel_err", "ials_first_item_rel_err",
            "ials_last_user_rel_err", "ials_last_item_rel_err",
            "ials_item_factor_change"} <= checked
    if trace:
        got = line["metrics"]
        # the gauges' metrics need no chip; scopes and peaks do
        assert got["ials_fused_share"]["value"] == 0.0
        assert got["ials_fused_share"]["tpums_als_iterations_total"] == line["attempted"] + 1
        assert 0 < got["ials_pad_share"]["value"] < 60
        assert {"als_prepare_s", "als_iter_median_s", "als_device_busy_s"} <= set(got)
        assert "ials_iter_roofline" not in got
    else:
        assert set(line["metrics"]) == {"train_iter_s", "setup_s"}


def test_rehearsal_under_the_bf16_exchange_control_is_not_correct():
    line = rehearse(0, "--control", "bf16_exchange")
    assert line["correct"] is False
    failed = {c["name"] for c in line["checks"] if not c["ok"]}
    assert failed and all(n.endswith("_rel_err") for n in failed)
