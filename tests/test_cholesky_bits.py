"""The Pallas Cholesky's body against the one it replaced (PR 46): the
elimination on the trailing block gives the full-tile form's x to the bit on
every entry, and does the work and costs the tracer what the counts below
say.  The full-tile body is kept here as the plain reference.  Files of
their own beside `test_cholesky_pallas.py`, this one up to rank 64 and
`test_solver_rank_100.py` and `test_solver_rank_128.py` above it: an
interpreted body takes the CPU compiler half a minute at rank 100 and most
of one at 128, twice a case, and the test runner hands out whole files."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flink_ms_tpu.ops import cholesky_pallas


def _solve_tile_full(M, b, k: int):
    """The plain reference: the body as it was before the elimination shrank
    (PR 46), every step downdating the whole (k, k, T) tile.  Above the
    pivot's sublane group it subtracts 0 from entries nothing reads, so the
    shrunk body owes it x to the bit."""
    rows = jax.lax.broadcasted_iota(jnp.int32, (k, 1), 0)
    cols = []
    for j in range(k):
        d = jax.lax.rsqrt(M[j, j:j + 1, :])
        col = M[:, j, :] * d
        col = jnp.where(rows >= j, col, 0.0)
        cols.append(col)
        M = M - col[:, None, :] * col[None, :, :]
    diag = jnp.concatenate([c[j:j + 1, :] for j, c in enumerate(cols)], axis=0)
    acc = jnp.zeros_like(b)
    zs = []
    for j in range(k):
        z = (b[j:j + 1, :] - acc[j:j + 1, :]) / diag[j:j + 1, :]
        zs.append(z)
        acc = acc + cols[j] * z
    Lrows = jnp.stack([c for c in cols], axis=1)
    acc = jnp.zeros_like(b)
    xs = [None] * k
    for j in reversed(range(k)):
        x = (zs[j] - acc[j:j + 1, :]) / diag[j:j + 1, :]
        xs[j] = x
        acc = acc + Lrows[j, :, :] * x
    return jnp.concatenate(xs, axis=0)


def solver_copy(body=None):
    """A second copy of the solver's module, with ``body`` for its
    ``_solve_tile`` (plain, the lane-major kernel's) and
    ``_solve_tile_shared`` (jitted, the batch-major kernel's) where one is
    given: its jitted functions, and what they have traced, are its own, so
    neither copy is ever answered from the other's trace.  Same file, same
    lines: what the two lower to may be compared as text."""
    import importlib.util

    # a name inside the package, so that the copy's relative imports resolve
    spec = importlib.util.spec_from_file_location(
        cholesky_pallas.__package__ + ".cholesky_pallas_copy",
        cholesky_pallas.__file__)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    if body is not None:
        module._solve_tile = body
        module._solve_tile_shared = jax.jit(body, static_argnames=("k",))
    return module


@pytest.fixture(scope="module")
def full_tile_solver():
    """The solver with the full-tile body on its three entries."""
    return solver_copy(_solve_tile_full)


def assert_x_to_the_bit(rng, full_tile_solver, k, entry):
    """130 systems: two grid steps at a tile of 128, the second nearly all
    identity pad.  A is made unsymmetric in its last bits: both bodies read
    the lower triangle, and only a body that read the upper one could
    differ."""
    n = 130
    G = rng.standard_normal((n, k, k)).astype(np.float32)
    A = G @ G.transpose(0, 2, 1) + 5.0 * np.eye(k, dtype=np.float32)
    A = A + np.triu(A, 1) * np.float32(2.0 ** -20)
    b = rng.standard_normal((n, k)).astype(np.float32)

    def solve(module):
        if entry == "lanes":
            pad = -n % cholesky_pallas.LANES
            d = np.pad(np.linspace(0.5, 20.0, n, dtype=np.float32), (0, pad),
                       constant_values=1.0)
            return np.asarray(module.cholesky_solve_lanes(
                jnp.asarray(np.pad(A.transpose(1, 2, 0),
                                   ((0, 0), (0, 0), (0, pad)))),
                jnp.asarray(np.pad(b.T, ((0, 0), (0, pad)))),
                jnp.asarray(d), interpret=True))
        return np.asarray(module.cholesky_solve_batched(
            jnp.asarray(A), jnp.asarray(b), interpret=True, layout=entry))

    x = solve(cholesky_pallas)
    assert np.isfinite(x).all() and x.any()
    np.testing.assert_array_equal(x, solve(full_tile_solver))
    if entry == "lanes":
        assert not x[:, n:].any()              # pad lanes: x = 0 exactly


ENTRIES = ["lanes", "lane_major", "batch_major"]


@pytest.mark.parametrize("entry", ENTRIES)
@pytest.mark.parametrize("k", [10, 50, 57, 64])
def test_trailing_block_elimination_is_the_full_tile_to_the_bit(
        rng, full_tile_solver, k, entry):
    assert_x_to_the_bit(rng, full_tile_solver, k, entry)


def _rank3_sub_elements(jaxpr):
    return sum(int(np.prod(e.outvars[0].aval.shape)) for e in jaxpr.eqns
               if e.primitive.name == "sub" and e.outvars[0].aval.ndim == 3)


@pytest.mark.parametrize("k", [50, 64, 100])
def test_the_elimination_works_where_the_factor_has_entries(k):
    """What the body asks of the vector unit and of the tracer, counted on
    plain arrays: the downdates' elements fall from k^3 a lane to under
    0.45 k^3 (a sublane group of rows and columns leaves every 8 steps),
    and the statements stay the full-tile body's but for two slices and an
    iota a group and a pad a step."""
    T = 8
    M = jax.ShapeDtypeStruct((k, k, T), jnp.float32)
    b = jax.ShapeDtypeStruct((k, T), jnp.float32)
    shrunk = jax.make_jaxpr(
        lambda M, b: cholesky_pallas._solve_tile(M, b, k))(M, b).jaxpr
    full = jax.make_jaxpr(lambda M, b: _solve_tile_full(M, b, k))(M, b).jaxpr
    assert _rank3_sub_elements(full) == k ** 3 * T
    assert _rank3_sub_elements(shrunk) <= 0.45 * k ** 3 * T
    assert len(shrunk.eqns) <= 1.10 * len(full.eqns)
