"""The Pallas solver from rank 65 to 128 (PR 44), the range
`netflix-als-f100.retrain` opened: `solver_tile`, the one rule that gives
every entry its tile and the VMEM it asks for, and the three entries
interpreted against numpy float64 at the bottom of the range.  The same
comparison at the cell's rank and at the top of the range is in
`test_solver_rank_100.py` and `test_solver_rank_128.py`, beside the
comparison to the bit that interprets the same three programs: an unrolled
body takes the CPU compiler half a minute at rank 100 and most of one at
128, a process compiles a program once, and the test runner hands out whole
files.  The compile for a described v5e, which is what refuses a kernel over
its VMEM, is in `test_cholesky_pallas.py` beside the other chip lowerings."""

import jax.numpy as jnp
import numpy as np
import pytest

from flink_ms_tpu.ops.cholesky_pallas import (
    LANES, cholesky_solve_batched, cholesky_solve_lanes, solver_tile)


# ranks 65-128 (PR 44): the tile is whole and the kernel names its VMEM
# limit; the arithmetic is the one unrolled body at every rank.  130 systems:
# two grid steps, the second nearly all identity pad (the shapes of
# `test_cholesky_bits.assert_x_to_the_bit`: one program serves both)
def assert_matches_numpy(rng, k, entry):
    n = 130
    G = rng.standard_normal((n, k, k)).astype(np.float32)
    A = G @ G.transpose(0, 2, 1) + 5.0 * np.eye(k, dtype=np.float32)
    b = rng.standard_normal((n, k)).astype(np.float32)
    d = rng.uniform(0.5, 20.0, n).astype(np.float32)
    if entry == "lanes":
        n_pad = 2 * LANES
        At = np.zeros((k, k, n_pad), np.float32)
        At[:, :, :n] = A.transpose(1, 2, 0)
        bt = np.zeros((k, n_pad), np.float32)
        bt[:, :n] = b.T
        dp = np.ones(n_pad, np.float32)
        dp[:n] = d
        x = np.asarray(cholesky_solve_lanes(
            jnp.asarray(At), jnp.asarray(bt), jnp.asarray(dp), interpret=True))
        assert (x[:, n:] == 0).all()     # pad lanes: the identity system
        x = x[:, :n].T
        A = A + d[:, None, None] * np.eye(k, dtype=np.float32)
    else:
        x = np.asarray(cholesky_solve_batched(
            jnp.asarray(A), jnp.asarray(b), interpret=True, layout=entry))
    x_ref = np.linalg.solve(A.astype(np.float64),
                            b.astype(np.float64)[..., None])[..., 0]
    np.testing.assert_allclose(x, x_ref, rtol=2e-3, atol=2e-4)


@pytest.mark.parametrize("entry", ["lane_major", "batch_major", "lanes"])
@pytest.mark.parametrize("k", [65])
def test_ranks_above_64_match_numpy(rng, k, entry):
    assert_matches_numpy(rng, k, entry)


@pytest.mark.parametrize("k,layout,want", [
    # up to rank 64 a whole lane tile and no named limit on every entry:
    # the batch-major one took half a tile from rank 57 until PR 46
    (10, "lane_major", (128, None)), (50, "lane_major", (128, None)),
    (64, "lane_major", (128, None)), (50, "batch_major", (128, None)),
    (56, "batch_major", (128, None)), (57, "batch_major", (128, None)),
    (64, "batch_major", (128, None)),
    # above it the tile stays whole and the kernel asks for eight
    # (k rows padded to 8) x k x 128-lane f32 buffers
    (65, "lane_major", (128, 8 * 72 * 65 * 512)),
    (100, "lane_major", (128, 8 * 104 * 100 * 512)),
    (100, "batch_major", (128, 8 * 104 * 100 * 512)),
    (128, "batch_major", (128, 8 * 128 * 128 * 512)),
])
def test_solver_tile_rule(k, layout, want):
    assert solver_tile(k, layout) == want
    tile, limit = want
    if limit is not None:
        # over what the v5e compiler needed (bisected again for PR 46's
        # body: 31 and 50 MiB lane-major at k = 100 and 128, 17 and 50
        # batch-major), with the 7 MiB XLA keeps around a batch-major call
        # inside a lax.map step, and under the chip's 128 MiB of VMEM
        floor = {("lane_major", 100): 31, ("lane_major", 128): 50,
                 ("batch_major", 100): 17 + 7, ("batch_major", 128): 50 + 7}
        assert floor.get((layout, k), 0) << 20 < limit < 96 << 20
