"""Explicit ALS-WR at rank 100 as `netflix-als-f100.retrain` runs it (PR 44):
the program's sweep against the benchmark's float64 ridge solve on both solve
routes and both assemblies; the assembly rule from rank 65 to 128; steps cut
from a ladder whose widest list alone passes a quarter of the step budget;
every product of the lowered sweep at the stated precision; the gauges and
the `[als] assembly:` line that say which form and tile a fit took; the
configuration's degree laws; and the cell's rehearsal on the CPU."""

import json
import os
import re
import shutil
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference, synth
from flink_ms_tpu.obs import metrics as obs_metrics
from flink_ms_tpu.ops import als as A
from flink_ms_tpu.parallel.mesh import make_mesh

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = os.path.join(REPO, "benchmark", "tests", "tiny-netflix", "BENCHMARK.json")
TINY_CELL = "netflix-tiny.retrain"
K, LAM = 100, 0.05
# float32 sums of up to 240 products against float64 and one solve of a
# system whose condition number is a few dozen (lambda * n on the diagonal
# under uniform(0, 1) factors' mean direction): 4e-6 at the worst row seen;
# the bfloat16 exchange misses by two orders
TOL = 1e-4
V5E_BYTES = 16909336064  # `bytes_limit` of one TPU v5e chip (chip run, PR 33)


def ratings_problem(rng, n_users=70, n_items=30, nnz=1500, longest=0):
    """Half-star ratings, every id present, users from 1 rating up; with
    `longest`, user 0 rates that many (pairs may repeat, as in the cell)."""
    users = np.concatenate([np.arange(n_users),
                            rng.integers(1, n_users, nnz - n_users),
                            np.zeros(longest, np.int64)])
    items = np.concatenate([np.arange(n_items),
                            rng.integers(0, n_items, len(users) - n_items)])
    rng.shuffle(items)
    ratings = np.round(rng.uniform(0.5, 5.0, len(users)) * 2) / 2
    return users, items, ratings.astype(np.float32)


def kernel_on_the_cpu(monkeypatch):
    """The resolver answers as a TPU would and the kernel runs interpreted;
    the resolver is not in the sweep's cache key."""
    real = A.resolve_assembly
    monkeypatch.setattr(A, "resolve_assembly",
                        lambda _, *a, **kw: real("tpu", *a, **kw))
    monkeypatch.setattr(A, "_SWEEP_CACHE", {})


def one_iteration(rng, monkeypatch, env, assembly="einsum", **problem_args):
    users, items, ratings = ratings_problem(rng, **problem_args)
    n_users, n_items = users.max() + 1, items.max() + 1
    if assembly == "kernel":
        kernel_on_the_cpu(monkeypatch)
    init = (rng.random((n_users, K), dtype=np.float32) / np.sqrt(K),
            rng.random((n_items, K), dtype=np.float32) / np.sqrt(K))
    for name, value in env.items():
        monkeypatch.setenv(name, value)
    cfg = A.ALSConfig(num_factors=K, iterations=1, lambda_=LAM,
                      weighted_reg=True, exchange_dtype=None)
    problem = A.prepare_blocked(users, items, ratings, 1)
    model = A.als_fit(users, items, ratings, cfg, make_mesh(1),
                      problem=problem, init=init)
    want_u = reference.ridge_rows(np.arange(n_users), users, items, ratings,
                                  init[1], LAM)
    want_i = reference.ridge_rows(np.arange(n_items), items, users, ratings,
                                  model.user_factors, LAM)
    return problem, model, want_u, want_i


# -- the program against the reference ----------------------------------------

@pytest.mark.parametrize("assembly", ["einsum", "kernel"])
@pytest.mark.parametrize("route", ["0", "1"])
def test_explicit_iteration_at_rank_100_agrees_with_the_reference(
        rng, monkeypatch, route, assembly):
    """Both routes (materialised, per chunk: no cell ran explicit + per
    chunk before this one) and both assemblies, the budget small enough
    that the wider buckets run under lax.map."""
    from flink_ms_tpu.ops import assemble_pallas

    seen = []
    for name in ("assemble_bucket", "assemble_bucket_lanes"):
        fn = getattr(assemble_pallas, name)
        monkeypatch.setattr(
            assemble_pallas, name,
            lambda *a, fn=fn, name=name, **kw: (seen.append(name), fn(*a, **kw))[1])
    # a gathered row of 100 values occupies a lane tile of 128 on the kernel
    # path: the same steps at 1.28 times the budget
    budget = "262144" if assembly == "einsum" else "335544"
    _, model, want_u, want_i = one_iteration(
        rng, monkeypatch,
        {"FLINK_MS_ALS_FUSED": route,
         "FLINK_MS_ALS_ASSEMBLY_CHUNK_BYTES": budget}, assembly=assembly)
    assert reference.worst_row_error(model.user_factors, want_u) < TOL
    assert reference.worst_row_error(model.item_factors, want_i) < TOL
    # the CPU's solver is `lax`, so the kernel hands A over batch-major
    assert set(seen) == ({"assemble_bucket"} if assembly == "kernel" else set())


def test_kernel_hands_rank_100_to_the_pallas_solver_lane_major(rng, monkeypatch):
    """The materialised route as the cell's movie half runs it on the chip:
    the assembly kernel writes (100, 100, n) itself and
    `cholesky_solve_lanes` adds lambda * n on the diagonal in its tile."""
    from flink_ms_tpu.ops import cholesky_pallas

    shapes = []
    real = cholesky_pallas.cholesky_solve_lanes
    monkeypatch.setattr(
        cholesky_pallas, "cholesky_solve_lanes",
        lambda At, bt, d, **kw: (shapes.append(At.shape), real(At, bt, d, **kw))[1])
    _, model, want_u, want_i = one_iteration(
        rng, monkeypatch, {"FLINK_MS_ALS_FUSED": "0",
                           "FLINK_MS_ALS_SOLVER": "pallas"},
        assembly="kernel", n_users=40, n_items=20, nnz=500)
    assert reference.worst_row_error(model.user_factors, want_u) < TOL
    assert reference.worst_row_error(model.item_factors, want_i) < TOL
    assert len(shapes) == 2 and all(s[:2] == (K, K) and s[2] % 128 == 0
                                    for s in shapes)


def test_the_bfloat16_exchange_misses_the_same_tolerance(rng):
    users, items, ratings = ratings_problem(rng)
    init = (rng.random((70, K), dtype=np.float32) / np.sqrt(K),
            rng.random((30, K), dtype=np.float32) / np.sqrt(K))
    cfg = A.ALSConfig(num_factors=K, iterations=1, lambda_=LAM,
                      exchange_dtype="bfloat16")
    model = A.als_fit(users, items, ratings, cfg, make_mesh(1), init=init)
    want = reference.ridge_rows(np.arange(70), users, items, ratings, init[1], LAM)
    assert reference.worst_row_error(model.user_factors, want) > 10 * TOL


# -- steps from a ladder with one very long list --------------------------------

@pytest.mark.parametrize("how,per_chunk,r,w,limit,want", [
    # the cell's movie ladder (PR 44), 2 GiB steps: a list of 218,472 is 112
    # MB in lane tiles, 42 of them three steps of 14; the six of 327,712
    # (1.0 GB) run straight-line
    ("kernel", False, 42, 218472, 2 << 30, 14),
    ("kernel", False, 6, 327712, 2 << 30, None),
    ("einsum", False, 42, 218472, 2 << 30, 21),
    # its users solve per chunk: a step also holds its (C, 100, 100) systems
    ("kernel", True, 14192, 8, 2 << 30, None),
    ("kernel", True, 26432, 16, 2 << 30, 13216),
    # one list alone over a quarter of the budget, over half of it, over all
    # of it: steps of 3, 1 and 1 rows, never 0
    ("einsum", False, 10, 328, 4 * 328 * 100 * 4 - 1, 3),
    ("einsum", False, 10, 328, 2 * 328 * 100 * 4 - 1, 1),
    ("kernel", False, 10, 328, 328 * 128 * 4 - 1, 1),
    ("kernel", True, 10, 328, 1000, 1),
])
def test_steps_hold_whole_rows_and_never_none(monkeypatch, how, per_chunk, r,
                                              w, limit, want):
    monkeypatch.setenv("FLINK_MS_ALS_ASSEMBLY_CHUNK_BYTES", str(limit))
    got = A._chunk_rows(r, w, K, 4, 4, how, False, per_chunk)
    assert got == want
    if got is not None:
        steps = -(-r // got)
        assert got >= 1 and (steps - 1) * got < r <= steps * got


@pytest.mark.parametrize("assembly", ["einsum", "kernel"])
@pytest.mark.parametrize("route", ["0", "1"])
def test_a_fit_whose_longest_list_passes_the_step_budget_solves_every_row_once(
        rng, monkeypatch, route, assembly):
    """User 0 rates 320 times (a bucket of width 328 with one row); the
    budget holds a third of that list, so every step of every bucket holds
    one row or a few: each row still comes out once, in its own slot."""
    row = 328 * (128 if assembly == "kernel" else K) * 4
    problem, model, want_u, want_i = one_iteration(
        rng, monkeypatch,
        {"FLINK_MS_ALS_FUSED": route,
         "FLINK_MS_ALS_ASSEMBLY_CHUNK_BYTES": str(row // 3)},
        assembly=assembly, n_users=24, n_items=16, nnz=200, longest=320)
    assert problem.u.widths[0] == 328 and problem.u.rows[0] == 1
    # the one long list is a step of its own, over the budget as it is
    assert A._chunk_rows(1, 328, K, 4, 4, assembly, False, False) == 1
    narrow = A._chunk_rows(problem.u.rows[-1], problem.u.widths[-1], K, 4, 4,
                           assembly, False, route == "1")
    assert 1 <= narrow < problem.u.rows[-1]   # the narrowest bucket is cut too
    assert reference.worst_row_error(model.user_factors, want_u) < TOL
    # movies rated 13 times on average by users of 8: rank-deficient sums
    # that lean on lambda * n, 1.3e-4 at the worst row seen
    assert reference.worst_row_error(model.item_factors, want_i) < 3 * TOL


# -- the rule above rank 64 -------------------------------------------------------

@pytest.mark.parametrize("k,want", [(64, "kernel"), (65, "kernel"),
                                    (100, "kernel"), (128, "kernel"),
                                    (129, "einsum")])
def test_assembly_rule_from_rank_65_to_128(k, want):
    """Up to 64 the kernel, as the two cells below it were timed; from 65 to
    128 the kernel, as two chip readings at rank 100 set it (the constant's
    comment holds them: 1.5345 s/iter against the einsum pair's 1.9364);
    past 128, which no kernel states, the einsum pair."""
    assert A._KERNEL_MAX_RANK == 128
    assert A.resolve_assembly("tpu", "float32", "float32", k) == want
    assert A.resolve_assembly("cpu", "float32", "float32", k) == "einsum"
    assert A.resolve_assembly("tpu", "bfloat16", "float32", k) == "einsum"


def test_users_go_per_chunk_and_movies_materialise_at_the_cells_size():
    """480,189 x 100 x 100 x 4 = 19.2 GB of 16.9; 17,770: 0.71 GB."""
    assert A.solves_per_chunk(480189 + A._PAD_STRIP, K, 4, V5E_BYTES) is True
    assert A.solves_per_chunk(17770 + A._PAD_STRIP, K, 4, V5E_BYTES) is False


# -- the lowered program ----------------------------------------------------------

def lowered(precision, route, monkeypatch):
    monkeypatch.setenv("FLINK_MS_ALS_FUSED", route)
    monkeypatch.setenv("FLINK_MS_ALS_ASSEMBLY_CHUNK_BYTES", "262144")
    users, items, ratings = ratings_problem(np.random.default_rng(1))
    problem = A.prepare_blocked(users, items, ratings, 1)
    cfg = A.ALSConfig(num_factors=K, iterations=1, lambda_=LAM,
                      exchange_dtype=None, assembly_precision=precision)
    fit_fn, dev_args = A.compile_fit(problem, cfg, make_mesh(1))
    return fit_fn.lower(jnp.asarray(1, jnp.int32), *dev_args)


@pytest.mark.parametrize("route", ["0", "1"])
@pytest.mark.parametrize("precision, want", [("highest", "HIGHEST"),
                                             ("default", "DEFAULT")])
def test_every_product_at_rank_100_runs_at_the_stated_precision(
        monkeypatch, precision, want, route):
    """On a TPU a product left at the default is one bfloat16 pass, which the
    `bf16_assembly` control turns on for all of them and the sound
    configuration for none.  (On the CPU both are full f32, so only a chip
    run shows that control `correct: false`.)"""
    program = lowered(precision, route, monkeypatch)
    text = program.as_text()
    found = re.findall(r"precision = \[(\w+), (\w+)\]", text)
    assert len(found) == text.count("stablehlo.dot_general") >= 4
    assert {p for pair in found for p in pair} == {want}
    scoped = program.as_text(debug_info=True)
    for scope in ("als.exchange", "als.assemble", "als.gather", "als.contract",
                  "als.solve"):
        assert f"{scope}/" in scoped or f"{scope}\"" in scoped


# -- gauges and the line ----------------------------------------------------------

def als_gauges():
    return {(g["name"], g["labels"].get("kind")): g["value"]
            for g in obs_metrics.get_registry().snapshot()["gauges"]
            if g["name"].startswith("tpums_als_")}


@pytest.mark.parametrize("solver,route,k,want", [
    # the CPU's default solver takes no tile
    ("lax", "1", K, (0, 0, 0)),
    # rank 100 on the Pallas solver: whole lane tiles on both entries; the
    # per-chunk route runs both (its straight-line buckets solve lane-major)
    ("pallas", "1", K, (128, 128, 128)),
    ("pallas", "0", K, (128, 128, 0)),
    # rank 64 per chunk, as msd-ials runs: whole tiles there too (the
    # batch-major entry took half a tile at ranks 57-64 until PR 46)
    ("pallas", "1", 64, (128, 128, 128)),
])
def test_gauges_say_the_rank_and_the_solvers_tile(rng, monkeypatch, solver,
                                                  route, k, want):
    monkeypatch.setenv("FLINK_MS_ALS_SOLVER", solver)
    monkeypatch.setenv("FLINK_MS_ALS_FUSED", route)
    monkeypatch.setenv("FLINK_MS_ALS_ASSEMBLY_CHUNK_BYTES", "262144")
    users, items, ratings = ratings_problem(rng)
    problem = A.prepare_blocked(users, items, ratings, 1)
    cfg = A.ALSConfig(num_factors=k, iterations=1, exchange_dtype=None)
    A.compile_fit(problem, cfg, make_mesh(1))
    got = als_gauges()
    assert got["tpums_als_rank", None] == k
    assert (got["tpums_als_solver_tile", None],
            got["tpums_als_solver_tile", "lane_major"],
            got["tpums_als_solver_tile", "batch_major"]) == want


def test_the_assembly_line_ends_in_the_rank_the_form_and_the_tiles(capsys):
    import types

    side = types.SimpleNamespace(widths=(328, 8), rows=(3, 40),
                                 per_block=43 + A._PAD_STRIP)
    problem = types.SimpleNamespace(u=side, i=side)
    A._log_assembly(problem, "einsum", False, K, {"u": True, "i": False},
                    {"batch_major": 128, "lane_major": 128},
                    exchange="bfloat16")
    line = capsys.readouterr().out
    # the parent's line, then what rank 65-128 adds
    assert line.startswith("[als] assembly: u-sweep solve per chunk (")
    assert line.rstrip().endswith(
        "; einsum pair elsewhere; rank 100: einsum pair, solver tile 128 "
        "batch-major, 128 lane-major; exchange bfloat16")
    A._log_assembly(problem, "kernel", True, 50, {"u": False, "i": False},
                    exchange="float32")
    assert capsys.readouterr().out.rstrip().endswith(
        "; rank 50: Pallas kernel; exchange float32")


# -- the configuration ------------------------------------------------------------

def cell_config():
    with open(os.path.join(REPO, "benchmark", "configs",
                           "netflix-als-f100.json")) as f:
        return json.load(f)


def test_the_cells_shape_is_the_sources_to_the_digit():
    cfg = cell_config()
    assert (cfg["n_users"], cfg["n_items"], cfg["nnz"]) == (480189, 17770, 99072112)
    assert cfg["rank"] == 100 and cfg["lambda"] == 0.05
    assert cfg["reduced"] == [] and cfg["exchange_dtype"] is None
    assert cfg["dtype"] == "float32" and cfg["assembly_precision"] == "highest"
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(c for c in bench["configs"] if c["name"] == "netflix-als-f100")
    assert entry["reduced"] == [] and len(entry["source"]) <= 200
    cell = next(w for w in bench["workloads"]
                if w["name"] == "netflix-als-f100.retrain")
    assert cell["chips"] == 1 and cell["traffic"] == "retrain"
    for name in ("als_contract_roofline", "als_solve_roofline",
                 "als_iter_roofline", "train_iter_s"):
        metric = next(m for m in bench["per_layer"] + bench["end_to_end"]
                      if m["name"] == name)
        assert "netflix-als-f100.retrain" in metric["workloads"]


def test_the_cells_degree_laws_keep_the_sources_extremes_and_totals():
    cfg = cell_config()
    user_deg, item_deg = synth.als_degrees(cfg)
    assert len(user_deg) == 480189 and len(item_deg) == 17770
    assert user_deg.sum() == item_deg.sum() == cfg["nnz"]
    assert user_deg.min() == 1 and user_deg.max() == 17653
    assert 90 <= np.median(user_deg) <= 100 and (user_deg < 8).sum() > 10000
    # the top movie holds the source's 232,944 ratings to a quarter percent
    assert abs(item_deg.max() / 232944 - 1) < 0.0025


def test_the_two_parts_rooflines_add_up_to_the_iterations():
    from benchmark import roofline, roofline_als_parts

    cfg = cell_config()
    whole = roofline.als_iter(cfg)
    contract = roofline_als_parts.als_contract(cfg)
    solve = roofline_als_parts.als_solve(cfg)
    assert contract[0] + solve[0] == whole[0]
    assert contract[1] + solve[1] == whole[1]
    assert contract[0] == 2 * 99072112 * (2 * 100 * 100 + 2 * 100)
    assert solve[1] == (480189 + 17770) * 2 * 100 * 4


# -- the cell's rehearsal -------------------------------------------------------

def rehearse(trace, *more):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--bench", TINY, "--workload",
         TINY_CELL, "--seed", "3000044019", "--seconds", "1", "--trace",
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
    assert {"als_first_user_rel_err", "als_first_item_rel_err",
            "als_last_user_rel_err", "als_last_item_rel_err",
            "als_item_factor_change"} <= checked
    if trace:
        got = line["metrics"]
        # the gauges' metric needs no chip; scopes and peaks do
        assert got["als_pad_entries_per_slot"]["value"] > 0
        assert {"als_prepare_s", "als_iter_median_s", "als_device_busy_s"} <= set(got)
        assert not {"als_iter_roofline", "als_contract_roofline",
                    "als_solve_roofline"} & set(got)
    else:
        assert set(line["metrics"]) == {"train_iter_s", "setup_s"}


def test_rehearsal_under_the_bf16_exchange_control_is_not_correct():
    """(`bf16_assembly` reads `correct: true` on the CPU, whose products at
    default precision are full f32: the lowered-program test above holds
    that control's products, the chip run shows it false.)"""
    line = rehearse(0, "--control", "bf16_exchange")
    assert line["correct"] is False
    failed = {c["name"] for c in line["checks"] if not c["ok"]}
    assert {"als_first_user_rel_err", "als_last_user_rel_err"} <= failed
    assert all(n.startswith("als_") for n in failed)
