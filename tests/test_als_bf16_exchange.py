"""The bfloat16 factor exchange as `als-ml20m-bf16x.retrain-bf16x` states it
(PR 49): the opposite side's factors rounded once a half-sweep, f32 sums of
exact products, f32 solve, x kept in f32.  The program's sweep against the
benchmark's numpy reference that rounds where the program rounds; that
reference's rounding against jax's; the one convert a half-sweep and the f32
sums in the lowered program; the two gauges and the two report lines that
say which exchange and contraction a fit got; and the cell's rehearsal."""

import re
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference, reference_ials
from benchmark.reference_als_bf16 import ridge_rows_rounded, round_bf16
# the cell's CPU rehearsal lives with the benchmark (`benchmark/tests` is
# not tier-1): tier-1 collects it from here
from benchmark.tests.test_bf16x_cell import (  # noqa: F401
    test_rehearsal_prints_the_contract_line,
    test_rehearsal_under_each_control_is_not_correct,
)
from flink_ms_tpu.core import formats as F
from flink_ms_tpu.core.params import Params
from flink_ms_tpu.obs import metrics as obs_metrics
from flink_ms_tpu.ops import als as A
from flink_ms_tpu.parallel.mesh import make_mesh
from flink_ms_tpu.train import als_train

K, LAM, ALPHA = 50, 0.05, 40.0
N_USERS, N_ITEMS = 70, 30
# f32 sums of up to a few hundred exact products against float64 and one
# f32 solve: 2e-5 at the worst row seen.  Against the UNROUNDED factors the
# same fit reads 2e-3 and more (bfloat16 keeps 8 bits), two orders above
TOL = 5e-5


def ratings_problem(rng, nnz=1500, counts=False):
    """Half-star ratings (or play counts 1..30), every id present, users
    from 1 rating up; pairs may repeat, as in the cell."""
    users = np.concatenate([np.arange(N_USERS),
                            rng.integers(1, N_USERS, nnz - N_USERS)])
    items = np.concatenate([np.arange(N_ITEMS),
                            rng.integers(0, N_ITEMS, nnz - N_ITEMS)])
    rng.shuffle(items)
    if counts:
        values = np.minimum(np.floor(rng.random(nnz) ** -0.7), 30.0)
    else:
        values = np.round(rng.uniform(0.5, 5.0, nnz) * 2) / 2
    return users, items, values.astype(np.float32)


def starting_factors(rng, k=K):
    return (rng.random((N_USERS, k), dtype=np.float32) / np.sqrt(k),
            rng.random((N_ITEMS, k), dtype=np.float32) / np.sqrt(k))


def states(users, items, values, init, exchange, upto=3, **config):
    """The factor state after 1..upto iterations from `init` (a fit of n
    iterations is the first n of a longer one)."""
    out = [init]
    for n in range(1, upto + 1):
        cfg = A.ALSConfig(num_factors=init[0].shape[1], iterations=n,
                          lambda_=LAM, exchange_dtype=exchange, **config)
        model = A.als_fit(users, items, values, cfg, make_mesh(1), init=init)
        out.append((model.user_factors, model.item_factors))
    return out


def half_sweeps(users, items, state):
    """(name, got, row_of, col_of, n_rows, the other side as that half read
    it) of both halves of the first and of the third iteration."""
    for name, before, after in (("first", state[0], state[1]),
                                ("later", state[2], state[3])):
        yield name + " user", after[0], users, items, N_USERS, before[1]
        yield name + " item", after[1], items, users, N_ITEMS, after[0]


# -- (a) the program against the reference that rounds where it rounds ---------

@pytest.fixture(scope="module")
def explicit():
    rng = np.random.default_rng(49)
    users, items, ratings = ratings_problem(rng)
    init = starting_factors(rng)
    return {exchange: (users, items, ratings,
                       states(users, items, ratings, init, exchange))
            for exchange in ("bfloat16", None)}


HALVES = ["first user", "first item", "later user", "later item"]


@pytest.mark.parametrize("half", HALVES)
def test_a_bf16_exchange_fit_is_the_ridge_solve_of_the_rounded_factors(
        explicit, half):
    users, items, ratings, state = explicit["bfloat16"]
    _, got, row_of, col_of, n, other = next(
        h for h in half_sweeps(users, items, state) if h[0] == half)
    rows = np.arange(n)
    rounded = reference.worst_row_error(
        got, ridge_rows_rounded(rows, row_of, col_of, ratings, other, LAM))
    plain = reference.worst_row_error(
        got, reference.ridge_rows(rows, row_of, col_of, ratings, other, LAM))
    assert rounded < TOL
    assert plain > 100 * rounded and plain > 1e-3


@pytest.mark.parametrize("half", HALVES)
def test_an_f32_exchange_fit_misses_the_rounded_reference(explicit, half):
    """How the cell refuses to time the other route under this
    configuration's name (its control `f32_exchange`)."""
    users, items, ratings, state = explicit[None]
    _, got, row_of, col_of, n, other = next(
        h for h in half_sweeps(users, items, state) if h[0] == half)
    rows = np.arange(n)
    assert reference.worst_row_error(got, ridge_rows_rounded(
        rows, row_of, col_of, ratings, other, LAM)) > 10 * TOL
    assert reference.worst_row_error(got, reference.ridge_rows(
        rows, row_of, col_of, ratings, other, LAM)) < TOL


def hkv_rows_rounded(rows, row_of, col_of, plays, other):
    """Implicit mode under the bf16 exchange, as the sweep computes it: the
    Gramian Y^T Y from the factors where they lie (f32, NOT exchanged), the
    rated rows' weighted sum and the right-hand side from the rounded ones."""
    k = other.shape[1]
    base = reference_ials.gramian(other) + LAM * np.eye(k)
    y_hat = round_bf16(other).astype(np.float64)
    out = np.zeros((len(rows), k))
    for n, row in enumerate(rows):
        sel = np.flatnonzero(row_of == row)
        y, r = y_hat[col_of[sel]], plays[sel].astype(np.float64)
        out[n] = np.linalg.solve(base + (y * (ALPHA * r)[:, None]).T @ y,
                                 y.T @ (1.0 + ALPHA * r))
    return out


def test_an_implicit_bf16_exchange_fit_rounds_the_gathered_rows_alone(rng):
    """`als_train --implicit true` takes the same exchange: the gathered
    rows are rounded, the whole-side Gramian is not.  (Rank 8: with plain
    lambda, Y^T Y over 30 or 70 rows of 50 factors is too ill-conditioned
    to tell the references apart.)"""
    users, items, plays = ratings_problem(rng, counts=True)
    init = starting_factors(rng, k=8)
    state = states(users, items, plays, init, "bfloat16", upto=1,
                   implicit=True, alpha=ALPHA)
    for got, row_of, col_of, n, other in (
            (state[1][0], users, items, N_USERS, init[1]),
            (state[1][1], items, users, N_ITEMS, state[1][0])):
        rows = np.arange(n)
        rounded = reference.worst_row_error(
            got, hkv_rows_rounded(rows, row_of, col_of, plays, other))
        plain, all_rounded = (
            reference.worst_row_error(got, reference_ials.hkv_rows(
                rows, row_of, col_of, plays, y, LAM, ALPHA))
            for y in (other, round_bf16(other)))
        assert rounded < TOL
        assert plain > 100 * rounded and all_rounded > 10 * rounded


# -- (b) the reference's rounding against jax's -------------------------------

def _bits(*patterns):
    return np.array(patterns, np.uint32).view(np.float32)


@pytest.mark.parametrize("name, x", [
    # exactly half way between two bfloat16 values: to the even one
    ("ties", _bits(0x3F808000, 0x3F818000, 0xBF808000, 0xBF818000,
                   0x00008000, 0x00018000, 0x7F7F8000)),
    ("just off a tie", _bits(0x3F807FFF, 0x3F808001, 0x3F817FFF, 0x3F818001)),
    ("subnormals", np.array([1e-40, -1e-40, 9.2e-41, 1.4e-45, 2.0 ** -133,
                             2.0 ** -134, 1.1754942e-38], np.float32)),
    ("zeros", np.array([0.0, -0.0], np.float32)),
    ("large", np.array([3.3895314e38, 3.39e38, 3.4028235e38, -3.4028235e38,
                        np.inf, -np.inf, 65504.0, 1e30], np.float32)),
    ("a factor table", np.random.default_rng(7).random(
        (257, 50), dtype=np.float32) / np.float32(np.sqrt(50))),
    ("any bits", np.random.default_rng(8).integers(
        0, 2 ** 32, 4096, dtype=np.uint64).astype(np.uint32).view(np.float32)),
])
def test_round_bf16_is_jaxs_conversion(name, x):
    finite = x[~np.isnan(x)]
    want = np.asarray(jnp.asarray(finite, jnp.bfloat16).astype(jnp.float32))
    got = round_bf16(finite)
    assert got.dtype == np.float32
    assert np.array_equal(got.view(np.uint32), want.view(np.uint32))
    # and the compiled convert, which is what the sweep runs
    dev = np.asarray(jax.jit(
        lambda a: a.astype(jnp.bfloat16).astype(jnp.float32))(finite))
    assert np.array_equal(got.view(np.uint32), dev.view(np.uint32))
    assert np.isnan(round_bf16(x[np.isnan(x)])).all()


def test_round_bf16_keeps_nan():
    assert np.isnan(round_bf16(_bits(0x7FC00000, 0xFFC00000, 0xFFFFFFFF))).all()


# -- (c) the lowered program ----------------------------------------------------

def _eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (tuple, list)) else (value,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner)


@pytest.mark.parametrize("implicit", [False, True])
def test_one_convert_a_half_sweep_and_f32_sums(rng, implicit):
    users, items, values = ratings_problem(rng, counts=implicit)
    problem = A.prepare_blocked(users, items, values, 1)
    cfg = A.ALSConfig(num_factors=K, iterations=1, lambda_=LAM,
                      implicit=implicit, exchange_dtype="bfloat16")
    fit_fn, dev_args = A.compile_fit(problem, cfg, make_mesh(1))
    one = jnp.asarray(1, jnp.int32)
    # by scope: every f32 -> bf16 convert lies under als.exchange, one a half
    down = [str(e.source_info.name_stack)
            for e in _eqns(jax.make_jaxpr(fit_fn)(one, *dev_args).jaxpr)
            if e.primitive.name == "convert_element_type"
            and e.params["new_dtype"] == jnp.bfloat16]
    assert len(down) == 2 and all("als.exchange" in s for s in down)
    assert sorted(s.split("/")[0] for s in down) == ["als.item_half",
                                                     "als.user_half"]
    # in the lowered text: the same two, and every product summed in f32
    text = fit_fn.lower(one, *dev_args).as_text()
    assert len(re.findall(r"stablehlo\.convert .*-> tensor<[0-9x]*xbf16>",
                          text)) == 2
    dots = re.findall(r"stablehlo\.dot_general .*-> tensor<[0-9x]*x(\w+)>", text)
    n_buckets = len(problem.u.widths) + len(problem.i.widths)
    assert len(dots) == 2 * n_buckets + 2 * implicit
    assert set(dots) == {"f32"}
    # A's contraction reads the rounded rows on both sides
    assert len(re.findall(
        r"stablehlo\.dot_general .*\(tensor<[0-9x]*xbf16>, tensor<[0-9x]*xbf16>\)",
        text)) == (0 if implicit else n_buckets)


# -- (d) the gauges -------------------------------------------------------------

def als_gauges():
    return {g["name"]: g["value"]
            for g in obs_metrics.get_registry().snapshot()["gauges"]
            if g["name"].startswith("tpums_als_") and not g["labels"]}


@pytest.mark.parametrize("exchange, as_tpu, itemsize, on_einsum", [
    (None, False, 4, True),          # every CPU fit: the einsum pair
    ("bfloat16", False, 2, True),
    (None, True, 4, False),          # a TPU's f32 exchange: the kernel
    ("bfloat16", True, 2, True),     # `als_train`'s default on a TPU
    ("auto", False, 4, True),        # "auto" off a TPU: full precision
])
def test_gauges_say_the_exchange_width_and_the_einsum_pairs_entries(
        rng, monkeypatch, exchange, as_tpu, itemsize, on_einsum):
    users, items, ratings = ratings_problem(rng)
    problem = A.prepare_blocked(users, items, ratings, 1)
    if as_tpu:
        # the resolver answers as a TPU would; the kernel runs interpreted
        real = A.resolve_assembly
        monkeypatch.setattr(A, "resolve_assembly",
                            lambda _, *a, **kw: real("tpu", *a, **kw))
        monkeypatch.setattr(A, "_SWEEP_CACHE", {})
    cfg = A.ALSConfig(num_factors=K, iterations=1, lambda_=LAM,
                      exchange_dtype=exchange)
    A.compile_fit(problem, cfg, make_mesh(1))
    got = als_gauges()
    entries = sum(a.size for side in (problem.u, problem.i) for a in side.idx)
    assert got["tpums_als_entries"] == entries
    assert got["tpums_als_exchange_itemsize"] == itemsize
    assert got["tpums_als_einsum_entries"] == (entries if on_einsum else 0)


# -- (e) the lines that name the exchange --------------------------------------

def _mesh_on(platform):
    return types.SimpleNamespace(
        devices=np.array([types.SimpleNamespace(platform=platform)]))


@pytest.mark.parametrize("platform, exchange, want", [
    ("tpu", "auto", "exchange bfloat16, einsum pair"),
    ("tpu", "bfloat16", "exchange bfloat16, einsum pair"),
    ("tpu", None, "exchange float32, Pallas kernel"),
    ("cpu", "auto", "exchange float32, einsum pair"),
    ("cpu", "bfloat16", "exchange bfloat16, einsum pair"),
])
def test_exchange_report_names_what_a_fit_resolves_to(platform, exchange, want):
    cfg = A.ALSConfig(num_factors=K, exchange_dtype=exchange)
    assert A.exchange_report(cfg, _mesh_on(platform)) == want


def test_als_trains_report_line_names_the_exchange(tmp_path, rng, capsys):
    users, items, ratings = ratings_problem(rng, nnz=400)
    path = str(tmp_path / "ratings.csv")
    F.write_ratings(path, users, items, ratings)
    als_train.run(Params.from_args([
        "--input", path, "--ignoreFirstLine", "false", "--iterations", "2",
        "--numFactors", "4", "--devices", "1",
        "--userFactors", str(tmp_path / "uf"),
        "--itemFactors", str(tmp_path / "itf")]))
    line = next(l for l in capsys.readouterr().out.splitlines()
                if l.startswith("[ALS] model-training:"))
    assert re.search(r"train RMSE=[0-9.]+; exchange float32, einsum pair$", line)
