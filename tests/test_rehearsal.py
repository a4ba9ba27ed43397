"""The sharded ALS fit's invariants at a size every test run affords:
each device holds one ``(1, per_block, k)`` block of a factor table and no
more; a routed exchange's plan accounts for its rows consistently (``D - 1``
peers send ``r_max`` rows each, the received table is ``D * r_max`` plus the
local block, the send plan is ``(D, D, r_max)``); and a staged fit resumed
after a simulated restart ends where an uninterrupted one does."""

import os

import numpy as np

from flink_ms_tpu.ops import als
from flink_ms_tpu.ops.als import ALSConfig, als_fit, compile_fit, prepare_blocked
from flink_ms_tpu.parallel.mesh import make_mesh


def _problem(rng, n_users=4_000, n_items=900, nnz=20_000, D=8):
    users = rng.integers(0, n_users, nnz)
    items = rng.integers(0, n_items, nnz)
    ratings = rng.uniform(1, 5, nnz)
    return users, items, ratings, prepare_blocked(users, items, ratings, D)


def test_per_device_shard_shapes_and_exchange_accounting(rng):
    D = 8
    users, items, ratings, problem = _problem(rng, D=D)
    mesh = make_mesh(D)
    k = 8
    cfg = ALSConfig(num_factors=k, iterations=1, lambda_=0.1,
                    exchange_dtype=None)
    fit_fn, dev_args = compile_fit(problem, cfg, mesh)
    # factor shards: one (1, per_block, k) block per device
    uf0 = dev_args[0]
    shapes = [s.data.shape for s in uf0.addressable_shards]
    assert len(shapes) == D
    assert all(s == (1, problem.u.per_block, k) for s in shapes)
    # the exchange plan's accounting is self-consistent
    plan = als._exchange_plan(problem, D)
    for name, opp in (("u", problem.i), ("i", problem.u)):
        r = plan[name]
        if r is not None:
            assert r.net_rows == (D - 1) * r.r_max
            assert r.recv_rows == D * r.r_max + opp.per_block
            assert r.send_idx.shape == (D, D, r.r_max)


def test_staging_resume_across_simulated_restart(rng, tmp_path):
    D = 4
    users, items, ratings, problem = _problem(
        rng, n_users=600, n_items=300, nnz=5_000, D=D)
    mesh = make_mesh(D)
    k = 6
    init = (0.1 * rng.standard_normal((problem.n_users, k)),
            0.1 * rng.standard_normal((problem.n_items, k)))
    stage = str(tmp_path / "stage")
    cfg2 = ALSConfig(num_factors=k, iterations=2, lambda_=0.1,
                     exchange_dtype=None)
    cfg4 = ALSConfig(num_factors=k, iterations=4, lambda_=0.1,
                     exchange_dtype=None)
    # "crash" after two staged iterations
    als_fit(users, items, ratings, cfg2, mesh, problem=problem, init=init,
            temporary_path=stage)
    snaps = [f for f in os.listdir(stage) if f.startswith("iter_")]
    assert snaps, "no iteration snapshots staged"
    # the restarted run resumes and matches an uninterrupted fit
    m_resumed = als_fit(users, items, ratings, cfg4, mesh, problem=problem,
                        init=init, temporary_path=stage)
    m_straight = als_fit(users, items, ratings, cfg4, mesh, problem=problem,
                         init=init)
    np.testing.assert_allclose(
        m_resumed.user_factors, m_straight.user_factors,
        rtol=1e-5, atol=1e-7,
    )
