"""Routed factor exchange (SURVEY §2.3): need-list
all_to_all replacing the full-table all_gather, equivalence-pinned against
the gather path on an 8-device CPU mesh, with exchange-volume accounting
that shrinks as the mesh grows (the property the all_gather lacks)."""

import numpy as np
import pytest

from flink_ms_tpu.ops import als
from flink_ms_tpu.ops.als import (
    ALSConfig,
    _exchange_plan,
    als_fit,
    build_routing,
    prepare_blocked,
)
from flink_ms_tpu.parallel.mesh import make_mesh


def _ratings(n_users=240, n_items=180, nnz=3_000, seed=0):
    rng = np.random.default_rng(seed)
    return (rng.integers(0, n_users, nnz), rng.integers(0, n_items, nnz),
            rng.uniform(1, 5, nnz))


def _pinned_init(problem, k, seed=7):
    rng = np.random.default_rng(seed)
    return (0.1 * rng.standard_normal((problem.n_users, k)),
            0.1 * rng.standard_normal((problem.n_items, k)))


def _fit_with_mode(mode, monkeypatch, implicit=False):
    monkeypatch.setenv("FLINK_MS_ALS_EXCHANGE_MODE", mode)
    mesh = make_mesh(8)
    users, items, ratings = _ratings()
    problem = prepare_blocked(users, items, ratings, 8)
    k = 6
    cfg = ALSConfig(num_factors=k, iterations=3, lambda_=0.1,
                    implicit=implicit, alpha=10.0, exchange_dtype=None)
    model = als_fit(users, items, ratings, cfg, mesh, problem=problem,
                    init=_pinned_init(problem, k))
    return problem, model


def test_routed_equals_gather_explicit(monkeypatch):
    """Routed and gathered sweeps consume identical factor rows in
    identical per-rating order — results agree bitwise."""
    _, m_gather = _fit_with_mode("gather", monkeypatch)
    problem, m_routed = _fit_with_mode("routed", monkeypatch)
    assert _exchange_plan(problem, 8)["u"] is not None  # actually routed
    np.testing.assert_array_equal(m_routed.user_factors, m_gather.user_factors)
    np.testing.assert_array_equal(m_routed.item_factors, m_gather.item_factors)


def test_routed_equals_gather_implicit(monkeypatch):
    _, m_gather = _fit_with_mode("gather", monkeypatch, implicit=True)
    _, m_routed = _fit_with_mode("routed", monkeypatch, implicit=True)
    np.testing.assert_array_equal(m_routed.user_factors, m_gather.user_factors)
    np.testing.assert_array_equal(m_routed.item_factors, m_gather.item_factors)


def test_block_local_ratings_route_almost_nothing():
    """When each user block only references its own item block, the routed
    exchange receives ~opp_pb rows while the all_gather always ships
    (D-1)*opp_pb — the win the design exists for."""
    D, per = 4, 50
    rng = np.random.default_rng(3)
    users = rng.integers(0, D * per, 4_000)
    items = (users // per) * per + rng.integers(0, per, 4_000)
    problem = prepare_blocked(users, items, rng.uniform(1, 5, 4_000), D)
    routed = build_routing(problem.u, problem.i, D)
    gather_rows = (D - 1) * problem.i.per_block
    # self-owned rows never ride the collective, so block-local ratings
    # cross almost nothing: r_max is a handful of stragglers (dense-index
    # blocking need not align perfectly with the id blocks), far below
    # the per-block catalog slice
    assert routed.net_rows < gather_rows / 4
    assert routed.r_max <= max(problem.i.per_block // 4, 2)
    # the diagonal send slots are all the zero strip's (nothing self-shipped)
    strip = problem.i.per_block - als._PAD_STRIP
    np.testing.assert_array_equal(
        routed.send_idx[2, 2],
        strip + np.arange(routed.r_max) % als._PAD_STRIP)


def test_exchange_volume_shrinks_with_mesh_size():
    """Per-device routed receive volume drops as D grows (need-lists thin
    out), while the all_gather volume stays ~flat — the SURVEY §2.3
    scaling property, asserted via the accounting the kernel logs."""
    users, items, ratings = _ratings(n_users=2_000, n_items=2_000,
                                     nnz=4_000, seed=5)
    ratios = []
    for D in (2, 8):
        problem = prepare_blocked(users, items, ratings, D)
        routed = build_routing(problem.u, problem.i, D)
        gather_rows = max((D - 1) * problem.i.per_block, 1)
        ratios.append(routed.net_rows / gather_rows)
    assert ratios[1] < ratios[0]
    assert ratios[1] < 0.7  # at D=8 the routed path is a real win


def test_auto_mode_decides_per_density(monkeypatch):
    monkeypatch.setenv("FLINK_MS_ALS_EXCHANGE_MODE", "auto")
    # saturated: tiny catalogs, many ratings -> gather (skip build)
    users, items, ratings = _ratings(n_users=40, n_items=30, nnz=6_000)
    dense = prepare_blocked(users, items, ratings, 4)
    plan = _exchange_plan(dense, 4)
    assert plan["u"] is None and plan["i"] is None
    # sparse: big catalogs, few ratings -> routed
    users, items, ratings = _ratings(n_users=3_000, n_items=3_000, nnz=2_000)
    sparse = prepare_blocked(users, items, ratings, 4)
    plan = _exchange_plan(sparse, 4)
    assert plan["u"] is not None and plan["i"] is not None
    # plans cache on the problem
    assert _exchange_plan(sparse, 4) is plan


def test_single_device_never_routes(monkeypatch):
    monkeypatch.setenv("FLINK_MS_ALS_EXCHANGE_MODE", "routed")
    users, items, ratings = _ratings(nnz=500)
    problem = prepare_blocked(users, items, ratings, 1)
    plan = _exchange_plan(problem, 1)
    assert plan["u"] is None and plan["i"] is None


def test_bad_mode_env_raises(monkeypatch):
    monkeypatch.setenv("FLINK_MS_ALS_EXCHANGE_MODE", "banana")
    with pytest.raises(ValueError, match="banana"):
        als._exchange_mode_choice()
