"""Pallas ALS assembly kernel: numerics against a float64 einsum in
interpreter mode, in both output layouts (batch-major, and lane-major as
the Pallas solver reads it), the precision it is asked for, end-to-end ALS
parity with the resolver patched onto the kernel (unfused, fused, chunked,
and the lane-major hand-off to the solver), which buckets take that
hand-off, and the resolver's answers — the bf16-exchange and CPU paths must keep
the einsum pair and trace no new kernel, in either mode; implicit mode's
weighted contraction (A = Σ αr·y yᵀ, b = Σ (1 + αr)·y) runs beside the
explicit one in every numerical case.  The TPU
cross-lowering cases are beside the Cholesky kernel's in
``test_cholesky_pallas.py``."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flink_ms_tpu.ops import als as A
from flink_ms_tpu.ops import assemble_pallas
from flink_ms_tpu.ops.assemble_pallas import (
    assemble_bucket, assemble_bucket_lanes, lane_tile_sizes, tile_sizes)
from flink_ms_tpu.parallel.mesh import make_mesh

# rows per width: never a multiple of the kernel's entity tile C, and more
# than one row block where C is small enough to afford it in the interpreter
_ROWS = {8: 13, 24: 13, 64: 13, 144: 53, 328: 29, 1032: 11}


def _bucket(rng, r, w, k):
    y = rng.standard_normal((r, w, k)).astype(np.float32)
    t = rng.uniform(0.5, 5.0, (r, w)).astype(np.float32)
    y[:, w - 3:], t[:, w - 3:] = 0.0, 0.0   # pad entries: zero row, zero rating
    y[1], t[1] = 0.0, 0.0                   # an entity that is all pad
    return y, t


def _einsum64(y, t, alpha=None):
    """Explicit: A = Σ y yᵀ, b = Σ t·y.  Implicit (``alpha``, t the play
    counts): A = Σ αt·y yᵀ, b = Σ (1 + αt)·y."""
    y, t = y.astype(np.float64), t.astype(np.float64)
    if alpha is None:
        return (np.einsum("rwk,rwl->rkl", y, y), np.einsum("rwk,rw->rk", y, t))
    return (np.einsum("rw,rwk,rwl->rkl", alpha * t, y, y),
            np.einsum("rwk,rw->rk", y, 1.0 + alpha * t))


def _dropped_weight_shows(y, t, alpha, want_a, want_b):
    """The ratings vary (0.5 .. 5), so sums that took every weight as one
    constant, or b's as α·t, are far from the weighted ones."""
    flat_a, _ = _einsum64(y, np.full_like(t, t.mean()), alpha)
    _, no_one = _einsum64(y, alpha * t)
    return (np.abs(flat_a - want_a).max() > 1e-2 * np.abs(want_a).max()
            and np.abs(no_one - want_b).max() > 1e-3 * np.abs(want_b).max())


@pytest.mark.parametrize("alpha", [None, 40.0])
@pytest.mark.parametrize("k", [10, 50, 64])
@pytest.mark.parametrize("w", sorted(_ROWS))
def test_kernel_matches_float64_einsum(rng, w, k, alpha):
    """w = 1032 is one full tile of 1024 and a ragged tile of 8: the rows
    the second block reads past the array must not reach A or b (under
    ``alpha`` their row k would be 1, not 0, were y not masked)."""
    r = _ROWS[w]
    c, wt = tile_sizes(w, k)
    assert r % c and (w <= wt or w % wt)
    y, t = _bucket(rng, r, w, k)
    got_a, got_b = assemble_bucket(
        jnp.asarray(y), jnp.asarray(t), precision="highest", interpret=True,
        alpha=alpha)
    want_a, want_b = _einsum64(y, t, alpha)
    assert alpha is None or _dropped_weight_shows(y, t, alpha, want_a, want_b)
    np.testing.assert_allclose(got_a, want_a, rtol=1e-5,
                               atol=1e-6 * np.abs(want_a).max())
    np.testing.assert_allclose(got_b, want_b, rtol=1e-5,
                               atol=1e-6 * np.abs(want_b).max())
    assert not np.asarray(got_a)[1].any() and not np.asarray(got_b)[1].any()


@pytest.mark.parametrize("alpha", [None, 40.0])
@pytest.mark.parametrize("k", [10, 50, 64])
@pytest.mark.parametrize("w", [24, 144, 1032])
@pytest.mark.parametrize("r", [5, 130, 257])
def test_lane_major_kernel_matches_float64_einsum(rng, r, w, k, alpha):
    """The same sums with the batch on the lanes: r under one lane tile,
    ragged over two, and over three; w = 24 whole in one sub-block of 128
    entities, 144 in sub-blocks of 64 (at r = 5 the second one lies wholly
    past the array and is skipped), 1032 tiled over w with a ragged last
    tile in sub-blocks of 8.  Lanes past r, and the all-pad entity, are
    exact zeros: the solver's diagonal operand makes them identity systems."""
    cs, wt = lane_tile_sizes(w, k)
    assert 128 % cs == 0 and (w <= wt or w % wt)
    y, t = _bucket(rng, r, w, k)
    got_a, got_b = (np.asarray(x) for x in assemble_bucket_lanes(
        jnp.asarray(y), jnp.asarray(t), precision="highest", interpret=True,
        alpha=alpha))
    n = -(-r // 128) * 128
    assert got_a.shape == (k, k, n) and got_b.shape == (k, n)
    want_a, want_b = _einsum64(y, t, alpha)
    assert alpha is None or _dropped_weight_shows(y, t, alpha, want_a, want_b)
    np.testing.assert_allclose(got_a[:, :, :r].transpose(2, 0, 1), want_a,
                               rtol=1e-5, atol=1e-6 * np.abs(want_a).max())
    np.testing.assert_allclose(got_b[:, :r].T, want_b, rtol=1e-5,
                               atol=1e-6 * np.abs(want_b).max())
    assert not got_a[:, :, r:].any() and not got_b[:, r:].any()
    assert not got_a[:, :, 1].any() and not got_b[:, 1].any()


@pytest.mark.parametrize("alpha", [None, 40.0])
def test_lane_major_kernel_sums_what_the_batch_major_one_sums(rng, alpha):
    """Same contraction per entity, same order over the w tiles: the two
    layouts hold the same floats."""
    y, t = _bucket(rng, 21, 1032, 50)
    a, b = assemble_bucket(jnp.asarray(y), jnp.asarray(t), alpha=alpha,
                           precision="highest", interpret=True)
    at, bt = assemble_bucket_lanes(jnp.asarray(y), jnp.asarray(t), alpha=alpha,
                                   precision="highest", interpret=True)
    np.testing.assert_array_equal(np.asarray(at)[:, :, :21].transpose(2, 0, 1), a)
    np.testing.assert_array_equal(np.asarray(bt)[:, :21].T, b)


# (w, rows) of the two sides of als-ml20m.retrain (benchmark/synth.py's
# degree sequences through `_side_order`), widest first
_ML20M = {
    "u": [(12784, 1), (8520, 16), (5680, 51), (3784, 151), (2520, 429),
          (1680, 886), (1120, 1935), (744, 3695), (496, 6535), (328, 10033),
          (216, 13236), (144, 16549), (96, 18001), (64, 19890), (40, 18580),
          (24, 28505)],
    "i": [(97096, 7), (64728, 24), (43152, 36), (28768, 53), (19176, 80),
          (12784, 121), (8520, 181), (5680, 271), (3784, 408), (2520, 611),
          (1680, 916), (1120, 1388), (744, 2066), (496, 3172), (328, 4850),
          (216, 7088), (144, 5472)],
}


@pytest.mark.parametrize("w", sorted({w for side in _ML20M.values()
                                      for w, _ in side}))
def test_lane_tiles_on_the_ml20m_ladder(w):
    """Every width of the cell's ladder has a lane-major tiling: sub-blocks
    that divide the lane tile, whole in w up to 1024 and inside the same
    VMEM budget as the batch-major form's input blocks, the batch-major
    form's w tiles beyond."""
    cs, wt = lane_tile_sizes(w, 50)
    assert cs in (8, 16, 32, 64, 128)
    if w <= 1024:
        assert wt == w
        assert cs * assemble_pallas._input_bytes(w, 50) <= 10 << 20
        assert cs == 128 or 2 * cs * assemble_pallas._input_bytes(w, 50) > 10 << 20
    else:
        assert (cs, wt) == tile_sizes(w, 50)


def _ladder_problem():
    import types

    side = {n: types.SimpleNamespace(widths=tuple(w for w, _ in b),
                                     rows=tuple(r for _, r in b),
                                     per_block=sum(r for _, r in b)
                                     + A._PAD_STRIP)
            for n, b in _ML20M.items()}
    return types.SimpleNamespace(u=side["u"], i=side["i"])


@pytest.mark.parametrize("limit,want", [
    # the cell: the largest gather is 658 MB of values, 1.68 GB as the take
    # leaves it (50 values in a lane tile of 128), of the 2 GiB chunk, so
    # all 33 buckets are written lane-major by the kernel itself
    (None, ("on 16 (100.0% of 138493 entities)",
            "on 17 (100.0% of 26744 entities)")),
    # a 1311 MB chunk (512 MB of values): the user side's four largest
    # gathers (w = 216..744) run in lax.map chunks, batch-major, and are
    # transposed after
    (1311 << 20, ("on 12 (75.8% of 138493 entities)",
                  "on 17 (100.0% of 26744 entities)")),
    # 512 MB of lane tiles: most of both sides
    (512 << 20, ("on 6 (34.2% of 138493 entities)",
                 "on 2 (20.5% of 26744 entities)")),
])
def test_which_buckets_hand_off_lane_major(capsys, monkeypatch, limit, want):
    """The rule holds no width and no row count: a bucket's kernel writes
    the solver's layout whenever it runs straight-line, and `_log_assembly`
    prints the share per side."""
    if limit:
        monkeypatch.setenv("FLINK_MS_ALS_ASSEMBLY_CHUNK_BYTES", str(limit))
    keep = {"u": False, "i": False}
    A._log_assembly(_ladder_problem(), "kernel", True, 50, keep,
                    exchange="float32")
    line = capsys.readouterr().out
    u, i = line.split("i-sweep")
    assert "lane-major hand-off " + want[0] in u
    assert "lane-major hand-off " + want[1] in i
    users_gb = (138493 + A._PAD_STRIP) * 50 * 50 * 4 / 1e9
    assert (f"u-sweep solve materialised ({users_gb:.2f} GB of normal "
            "equations)") in u
    A._log_assembly(_ladder_problem(), "kernel", False, 50, keep,
                    exchange="float32")  # lax solver
    assert "hand-off on 0 (0.0%" in capsys.readouterr().out
    # a side on the per-chunk route keeps the batch-major hand-off
    A._log_assembly(_ladder_problem(), "kernel", True, 50,
                    {"u": True, "i": False}, exchange="float32")
    u, i = capsys.readouterr().out.split("i-sweep")
    assert "solve per chunk" in u and "hand-off on 0 (0.0%" in u
    assert "solve materialised" in i and "hand-off " + want[1] in i


@pytest.mark.parametrize("how,implicit,per_chunk,r,w,k,want", [
    # msd-ials' item buckets, 1.09 GB of values and 2.17 GB in lane tiles:
    # two steps on the kernel as on the einsum pair (whose second gigabyte
    # is the weighted copy), straight-line only where neither is counted
    ("kernel", True, False, 2524, 1680, 64, 1262),
    ("einsum", True, False, 2524, 1680, 64, 1262),
    ("einsum", False, False, 2524, 1680, 64, None),
    # its narrowest user bucket solves per chunk: six equal steps, the
    # parent's, whichever path assembles
    ("kernel", True, True, 180614, 24, 64, 30103),
    ("einsum", True, True, 180614, 24, 64, 30103),
    # als-ml20m's largest gather stays whole: 1.68 GB at rank 50
    ("kernel", False, False, 10033, 328, 50, None),
])
def test_a_step_is_budgeted_by_what_its_path_keeps_live(monkeypatch, how,
                                                        implicit, per_chunk,
                                                        r, w, k, want):
    monkeypatch.delenv("FLINK_MS_ALS_ASSEMBLY_CHUNK_BYTES", raising=False)
    assert A._chunk_rows(r, w, k, 4, 4, how, implicit, per_chunk) == want


@pytest.mark.parametrize("alpha", [None, 40.0])
@pytest.mark.parametrize("w", [24, 1032])
def test_default_precision_is_one_bf16_pass(rng, w, alpha):
    """`precision="default"` rounds both operands to bfloat16 and
    accumulates in f32, as the einsum it replaces does on a TPU: the
    benchmark's `bf16_assembly` control must stay wrong, in implicit mode
    too, where what is rounded is the weighted operand (α·t·y, and
    1 + α·t), as the einsum pair rounds `yw`."""
    y, t = _bucket(rng, 9, w, 50)
    got_a, got_b = assemble_bucket(
        jnp.asarray(y), jnp.asarray(t), precision="default", interpret=True,
        alpha=alpha)

    def rounded(x):
        return np.asarray(jnp.asarray(x).astype(jnp.bfloat16).astype(jnp.float32))

    if alpha is None:
        want_a, want_b = _einsum64(rounded(y), rounded(t))
    else:
        yw = rounded(y * (np.float32(alpha) * t)[..., None]).astype(np.float64)
        y64 = rounded(y).astype(np.float64)
        want_a = np.einsum("rwk,rwl->rkl", yw, y64)
        want_b = np.einsum("rwk,rw->rk", y64,
                           rounded(1.0 + np.float32(alpha) * t))
    np.testing.assert_allclose(got_a, want_a, rtol=1e-5,
                               atol=1e-6 * np.abs(want_a).max())
    np.testing.assert_allclose(got_b, want_b, rtol=1e-5,
                               atol=1e-6 * np.abs(want_b).max())
    full_a, _ = _einsum64(y, t, alpha)
    assert np.abs(np.asarray(got_a) - full_a).max() > 1e-4 * np.abs(full_a).max()


def test_unknown_precision_is_refused():
    y = jnp.zeros((8, 8, 4), jnp.float32)
    with pytest.raises(ValueError, match="precision"):
        assemble_bucket(y, y[..., 0], precision="high", interpret=True)


def _kernel_everywhere(platform, y_dtype, dtype, k, precision="highest"):
    return "kernel"


@pytest.mark.parametrize("mode", ["unfused", "fused", "chunked"])
def test_als_fit_with_kernel_matches_einsum(rng, monkeypatch, mode):
    """The whole sweep with every bucket on the (interpreted) kernel against
    the einsum pair: straight-line, inside the fused solve's `post`, and
    inside the `lax.map` chunks."""
    n_users, n_items, k = 40, 30, 4
    full = rng.normal(size=(n_users, k)) @ rng.normal(size=(n_items, k)).T
    u, i = np.nonzero(rng.uniform(size=full.shape) < 0.6)
    r = full[u, i]
    init = (rng.normal(size=(n_users, k)).astype(np.float32),
            rng.normal(size=(n_items, k)).astype(np.float32))
    cfg = A.ALSConfig(num_factors=k, iterations=2, lambda_=0.1)
    mesh = make_mesh(2)
    if mode == "fused":
        monkeypatch.setenv("FLINK_MS_ALS_FUSED", "1")
    if mode == "chunked":
        monkeypatch.setenv("FLINK_MS_ALS_ASSEMBLY_CHUNK_BYTES", "512")
    base = A.als_fit(u, i, r, cfg, mesh, init=init)
    A._SWEEP_CACHE.clear()   # the resolver is not in the sweep's cache key
    monkeypatch.setattr(A, "resolve_assembly", _kernel_everywhere)
    try:
        kernel = A.als_fit(u, i, r, cfg, mesh, init=init)
    finally:
        A._SWEEP_CACHE.clear()
    np.testing.assert_allclose(
        kernel.user_factors, base.user_factors, rtol=1e-3, atol=1e-5)
    np.testing.assert_allclose(
        kernel.item_factors, base.item_factors, rtol=1e-3, atol=1e-5)


def _spy(monkeypatch, module, name, note):
    """`module.name` still runs; `note(args)` sees each call first."""
    fn = getattr(module, name)

    def spy(*a, **kw):
        note(a)
        return fn(*a, **kw)

    monkeypatch.setattr(module, name, spy)


def _count_lane_calls(monkeypatch):
    seen = []
    _spy(monkeypatch, assemble_pallas, "assemble_bucket_lanes",
         lambda a: seen.append(a[0].shape))
    return seen


@pytest.mark.parametrize("mode", ["lanes", "lanes_chunked", "lax_solver"])
def test_als_fit_hands_off_lane_major(rng, monkeypatch, mode):
    """One device, a user bucket of more than 128 rows, the Pallas solver:
    the kernel writes A batch-minor, the solver adds the diagonal, and the
    factors are the einsum path's.  A bucket that chunks keeps the
    batch-major kernel inside its lax.map and is transposed after; with
    the lax solver nothing is lane-major."""
    n_users, n_items, k = 300, 30, 4
    full = rng.normal(size=(n_users, k)) @ rng.normal(size=(n_items, k)).T
    u, i = np.nonzero(rng.uniform(size=full.shape) < 0.6)
    r = full[u, i]
    init = (rng.normal(size=(n_users, k)).astype(np.float32),
            rng.normal(size=(n_items, k)).astype(np.float32))
    cfg = A.ALSConfig(num_factors=k, iterations=2, lambda_=0.1)
    mesh = make_mesh(1)
    base = A.als_fit(u, i, r, cfg, mesh, init=init)
    if mode != "lax_solver":
        monkeypatch.setenv("FLINK_MS_ALS_SOLVER", "pallas")
    if mode == "lanes_chunked":
        # a gathered row of 4 values counts as the lane tile it occupies
        monkeypatch.setenv("FLINK_MS_ALS_ASSEMBLY_CHUNK_BYTES", str(1 << 17))
    A._SWEEP_CACHE.clear()
    monkeypatch.setattr(A, "resolve_assembly", _kernel_everywhere)
    seen = _count_lane_calls(monkeypatch)
    try:
        kernel = A.als_fit(u, i, r, cfg, mesh, init=init)
    finally:
        A._SWEEP_CACHE.clear()
    rows = sorted(shape[0] for shape in seen)
    if mode == "lanes":
        assert len(rows) >= 3 and rows[-1] > 128
    elif mode == "lanes_chunked":
        assert rows and rows[-1] * 16 * 512 <= 1 << 17 < 205 * 24 * 512
    else:
        assert not rows
    np.testing.assert_allclose(
        kernel.user_factors, base.user_factors, rtol=1e-3, atol=1e-5)
    np.testing.assert_allclose(
        kernel.item_factors, base.item_factors, rtol=1e-3, atol=1e-5)


@pytest.mark.parametrize("assembly,solver,chunked,implicit", [
    ("kernel", "pallas", False, False), ("kernel", "pallas", True, False),
    ("kernel", "pallas", False, True), ("kernel", "pallas", True, True),
    ("einsum", "pallas", True, True),    # the einsum pair: ragged as it was
    ("kernel", "lax", True, True),       # no Pallas solver: nothing to tile
])
def test_per_chunk_solve_takes_whole_lane_tiles(rng, monkeypatch, assembly,
                                                solver, chunked, implicit):
    """The per-chunk route, one device: behind the kernel every batch the
    Pallas solver is handed is whole lane tiles of systems (it pads a ragged
    one by a copy of all of A), the steps themselves stay as ragged as
    ``_chunk_rows`` cut them, and the factors are the materialised einsum
    fit's: the systems past a step's rows are solved and dropped."""
    from flink_ms_tpu.ops import cholesky_pallas

    n_users, n_items, k = 300, 30, 4
    u, i = np.nonzero(rng.uniform(size=(n_users, n_items)) < 0.6)
    r = rng.integers(1, 30, len(u)).astype(np.float32)
    init = (rng.random((n_users, k), dtype=np.float32) / 2,
            rng.random((n_items, k), dtype=np.float32) / 2)
    cfg = A.ALSConfig(num_factors=k, iterations=2, lambda_=0.1,
                      implicit=implicit, alpha=2.0)
    mesh = make_mesh(1)
    base = A.als_fit(u, i, r, cfg, mesh, init=init)
    monkeypatch.setenv("FLINK_MS_ALS_FUSED", "1")
    monkeypatch.setenv("FLINK_MS_ALS_SOLVER", solver)
    if chunked:
        monkeypatch.setenv("FLINK_MS_ALS_ASSEMBLY_CHUNK_BYTES", str(1 << 17))
    if assembly == "kernel":
        monkeypatch.setattr(A, "resolve_assembly", _kernel_everywhere)
    A._SWEEP_CACHE.clear()
    batches, steps = [], []
    _spy(monkeypatch, cholesky_pallas, "cholesky_solve_batched",
         lambda a: batches.append(a[0].shape[0]))
    _spy(monkeypatch, assemble_pallas, "assemble_bucket",
         lambda a: steps.append(a[0].shape[0]))
    try:
        got = A.als_fit(u, i, r, cfg, mesh, init=init)
    finally:
        A._SWEEP_CACHE.clear()
    assert (solver == "pallas") == bool(batches)
    assert (assembly == "kernel") == bool(steps)
    if assembly == "kernel":
        assert any(rows % 128 for rows in steps)
        if batches:   # one solve a step, the step's rows rounded up
            assert sorted(batches) == sorted(-(-rows // 128) * 128
                                             for rows in steps)
    else:
        assert any(rows % 128 for rows in batches)
    # f32 sums in another order, through two ill-conditioned rank-4 solves
    np.testing.assert_allclose(
        got.user_factors, base.user_factors, rtol=1e-3, atol=5e-4)
    np.testing.assert_allclose(
        got.item_factors, base.item_factors, rtol=1e-3, atol=5e-4)


@pytest.mark.parametrize("platform,exchange,implicit,on_kernel", [
    ("tpu", "bfloat16", False, False),   # `als_train`'s default on a TPU
    ("tpu", None, True, True),           # implicit f32: the weighted kernel
    ("tpu", None, False, True),          # explicit f32: one kernel a bucket
    ("tpu", "bfloat16", True, False),    # the bf16 exchange, implicit too
    ("cpu", None, True, False),          # a CPU fit, implicit too
])
def test_einsum_sweeps_trace_no_new_kernel(rng, monkeypatch, platform,
                                           exchange, implicit, on_kernel):
    """A sweep on the Pallas solver with the resolver as `platform` would
    answer it: the bf16 exchange and a CPU call no assembly kernel in
    either mode and hand `(n, k, k)` to `cholesky_solve_batched` as before;
    only the kernel path reaches the solver through `cholesky_solve_lanes`,
    implicit mode's with `alpha` and explicit mode's without."""
    from flink_ms_tpu.ops import cholesky_pallas

    n_users, n_items, k = 40, 30, 4
    u, i = np.nonzero(rng.uniform(size=(n_users, n_items)) < 0.6)
    problem = A.prepare_blocked(u, i, np.ones(len(u), np.float32), 1)
    cfg = A.ALSConfig(num_factors=k, iterations=1, implicit=implicit,
                      exchange_dtype=exchange)
    monkeypatch.setenv("FLINK_MS_ALS_SOLVER", "pallas")
    real = A.resolve_assembly
    monkeypatch.setattr(
        A, "resolve_assembly",
        lambda _, *a, **kw: real(platform, *a, **kw))
    calls = {"kernel": [], "batched": [], "lanes": []}
    alphas = []
    real_lanes = assemble_pallas.assemble_bucket_lanes
    monkeypatch.setattr(
        assemble_pallas, "assemble_bucket_lanes",
        lambda *a, **kw: (alphas.append(kw.get("alpha")), real_lanes(*a, **kw))[1])
    for module, name, key in [
            (assemble_pallas, "assemble_bucket", "kernel"),
            (assemble_pallas, "assemble_bucket_lanes", "kernel"),
            (cholesky_pallas, "cholesky_solve_batched", "batched"),
            (cholesky_pallas, "cholesky_solve_lanes", "lanes")]:
        _spy(monkeypatch, module, name,
             lambda a, key=key: calls[key].append(a[0].shape))
    fit_fn, dev_args = A.compile_fit(problem, cfg, make_mesh(1))
    A._SWEEP_CACHE.clear()
    jax.make_jaxpr(fit_fn)(jnp.asarray(1, jnp.int32), *dev_args)
    if on_kernel:
        assert len(calls["kernel"]) == (len(problem.u.widths)
                                        + len(problem.i.widths))
        assert len(calls["lanes"]) == 2 and not calls["batched"]
        assert set(alphas) == ({cfg.alpha} if implicit else {None})
    else:
        assert not calls["kernel"] and not calls["lanes"]
        assert calls["batched"] == [(problem.u.per_block, k, k),
                                    (problem.i.per_block, k, k)]


@pytest.mark.parametrize("platform,y_dtype,implicit,w,k,precision,want", [
    ("tpu", "float32", False, 144, 50, "highest", "kernel"),
    ("tpu", "float32", False, 97096, 50, "highest", "kernel"),
    ("tpu", "float32", False, 144, 64, "default", "kernel"),
    ("tpu", "bfloat16", False, 144, 50, "highest", "einsum"),   # bf16 exchange
    ("tpu", "float32", True, 144, 50, "highest", "kernel"),     # implicit
    ("tpu", "float32", True, 24, 64, "default", "kernel"),      # its control
    ("tpu", "bfloat16", True, 144, 50, "highest", "einsum"),    # bf16, implicit
    ("cpu", "float32", True, 144, 50, "highest", "einsum"),     # CPU, implicit
    ("cpu", "float32", False, 144, 50, "highest", "einsum"),    # CPU mesh
    (None, "float32", False, 144, 50, "highest", "einsum"),
    ("tpu", "float32", False, 144, 50, "high", "einsum"),       # no 3-pass
    ("tpu", "float32", False, 24, 50, "highest", "kernel"),     # every width
    ("tpu", "float32", False, 144, 100, "highest", "kernel"),   # PR 44's cell
    ("tpu", "float32", False, 144, 129, "highest", "einsum"),   # past the rank
    ("tpu", "float32", False, 144, 200, "highest", "einsum"),   # the chip ran
])
def test_resolver_and_what_it_traces(platform, y_dtype, implicit, w, k,
                                     precision, want):
    assert A.resolve_assembly(platform, y_dtype, "float32", k,
                              precision) == want
    # ... and the bucket really traces that: a pallas_call only where the
    # resolver said kernel, the einsum pair's two dot_generals otherwise
    r = 16
    jaxpr = str(jax.make_jaxpr(
        lambda tab, idx, val: A._bucket_normal_eqs(
            tab, idx, val, implicit, 40.0, jnp.float32, precision,
            platform=platform)
    )(jnp.zeros((32, k), y_dtype), jnp.zeros((r, w), jnp.int32),
      jnp.zeros((r, w), jnp.float32)))
    assert ("pallas_call" in jaxpr) == (want == "kernel")
    if want == "einsum":
        assert jaxpr.count("dot_general") == 2
    else:
        # the weights are a static branch of the kernel's body: two f32
        # multiplies under implicit, none (no multiply by ones) without
        assert len(re.findall(r"f32\[[^\]]*\] = mul ", jaxpr)) == 2 * implicit
