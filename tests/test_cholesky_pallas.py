"""Pallas batched Cholesky solve: numerics vs numpy in interpreter mode,
end-to-end ALS parity via FLINK_MS_ALS_SOLVER=pallas (SURVEY.md §4:
kernel unit tests against closed form), and a TPU cross-lowering of every
``pallas_call`` under ``flink_ms_tpu/ops/`` — a kernel the installed jax
cannot lower for the chip fails here, not on the chip."""

import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flink_ms_tpu.obs import metrics as obs_metrics
from flink_ms_tpu.ops.cholesky_pallas import (
    cholesky_solve_batched, cholesky_solve_lanes)
from test_cholesky_bits import solver_copy


@pytest.mark.parametrize("k", [3, 8, 16, 50])
@pytest.mark.parametrize("n", [1, 100, 257])
def test_matches_numpy(rng, k, n):
    G = rng.standard_normal((n, k, k)).astype(np.float32)
    A = G @ G.transpose(0, 2, 1) + 5.0 * np.eye(k, dtype=np.float32)
    b = rng.standard_normal((n, k)).astype(np.float32)
    x = np.asarray(cholesky_solve_batched(
        jnp.asarray(A), jnp.asarray(b), interpret=True))
    x_ref = np.linalg.solve(
        A.astype(np.float64), b.astype(np.float64)[..., None]
    )[..., 0]
    np.testing.assert_allclose(x, x_ref, rtol=2e-3, atol=2e-4)


@pytest.mark.parametrize("k", [8, 50, 64])
@pytest.mark.parametrize("n", [1, 100, 257])
def test_batch_major_matches_lane_major(rng, k, n):
    """The batch-major variant (per-tile VMEM transpose; forced inside
    fused scan bodies) must agree with the lane-major kernel — same
    elimination arithmetic, different operand routing."""
    G = rng.standard_normal((n, k, k)).astype(np.float32)
    A = G @ G.transpose(0, 2, 1) + 5.0 * np.eye(k, dtype=np.float32)
    b = rng.standard_normal((n, k)).astype(np.float32)
    lane = np.asarray(cholesky_solve_batched(
        jnp.asarray(A), jnp.asarray(b), interpret=True,
        layout="lane_major"))
    batch = np.asarray(cholesky_solve_batched(
        jnp.asarray(A), jnp.asarray(b), interpret=True,
        layout="batch_major"))
    np.testing.assert_allclose(batch, lane, rtol=1e-5, atol=1e-6)
    x_ref = np.linalg.solve(
        A.astype(np.float64), b.astype(np.float64)[..., None]
    )[..., 0]
    np.testing.assert_allclose(batch, x_ref, rtol=2e-3, atol=2e-4)


# rank 50 once: every new n retraces its three unrolled k-loops
@pytest.mark.parametrize("n,k", [(n, k) for k in (3, 8, 16)
                                 for n in (1, 100, 257)] + [(257, 50)])
def test_lanes_with_diagonal_matches_numpy(rng, k, n):
    """The solver as the assembly kernel feeds it: A already batch-minor,
    padded to whole lane tiles, and the regularisation as a per-lane
    operand added to the diagonal in VMEM.  Against float64
    solve(A + d·I, b); a `count = 0` lane (A = 0, b = 0, d = 1) and every
    pad lane are identity systems, x = 0 exactly."""
    G = rng.standard_normal((n, k, k)).astype(np.float32)
    A = G @ G.transpose(0, 2, 1)
    b = rng.standard_normal((n, k)).astype(np.float32)
    d = rng.uniform(0.5, 8.0, n).astype(np.float32)
    A[n // 2], b[n // 2], d[n // 2] = 0.0, 0.0, 1.0      # an empty row
    pad = -n % 128
    At = np.pad(A.transpose(1, 2, 0), ((0, 0), (0, 0), (0, pad)))
    bt = np.pad(b.T, ((0, 0), (0, pad)))
    x = np.asarray(cholesky_solve_lanes(
        jnp.asarray(At), jnp.asarray(bt),
        jnp.asarray(np.pad(d, (0, pad), constant_values=1.0)),
        interpret=True))
    assert x.shape == (k, n + pad)
    x_ref = np.linalg.solve(
        A.astype(np.float64) + d[:, None, None] * np.eye(k),
        b.astype(np.float64)[..., None])[..., 0]
    np.testing.assert_allclose(x[:, :n].T, x_ref, rtol=2e-3, atol=2e-4)
    assert not x[:, n:].any() and not x[:, n // 2].any()


def test_diagonal_operand_is_the_xla_add(rng):
    """Same elimination on the same numbers: adding d in the tile gives the
    floats that `A + d·I` in XLA and the operand-free kernel give."""
    n, k = 128, 16
    G = rng.standard_normal((n, k, k)).astype(np.float32)
    A = G @ G.transpose(0, 2, 1)
    b = rng.standard_normal((n, k)).astype(np.float32)
    d = rng.uniform(0.5, 8.0, n).astype(np.float32)
    inside = cholesky_solve_lanes(
        jnp.asarray(A.transpose(1, 2, 0)), jnp.asarray(b.T), jnp.asarray(d),
        interpret=True)
    outside = cholesky_solve_batched(
        jnp.asarray(A) + jnp.asarray(d)[:, None, None] * jnp.eye(k),
        jnp.asarray(b), interpret=True, layout="lane_major")
    np.testing.assert_array_equal(np.asarray(inside).T, np.asarray(outside))


def test_als_fit_with_pallas_solver_matches_default(rng, monkeypatch):
    from flink_ms_tpu.ops import als as A
    from flink_ms_tpu.parallel.mesh import make_mesh

    n_users, n_items, k = 40, 30, 4
    uf = rng.normal(size=(n_users, k))
    itf = rng.normal(size=(n_items, k))
    full = uf @ itf.T
    mask = rng.uniform(size=full.shape) < 0.6
    u, i = np.nonzero(mask)
    r = full[u, i]
    uf0 = rng.normal(size=(n_users, k)).astype(np.float32)
    itf0 = rng.normal(size=(n_items, k)).astype(np.float32)
    cfg = A.ALSConfig(num_factors=k, iterations=2, lambda_=0.1)
    mesh = make_mesh(2)
    base = A.als_fit(u, i, r, cfg, mesh, init=(uf0, itf0))
    monkeypatch.setenv("FLINK_MS_ALS_SOLVER", "pallas")
    pallas = A.als_fit(u, i, r, cfg, mesh, init=(uf0, itf0))
    np.testing.assert_allclose(
        pallas.user_factors, base.user_factors, rtol=1e-3, atol=1e-5
    )
    np.testing.assert_allclose(
        pallas.item_factors, base.item_factors, rtol=1e-3, atol=1e-5
    )


# every pallas_call in ops/ at the shapes the sweeps produce: k=50
# lane-major is the ML-20M default, k=64 batch-major is the fused
# (lax.map) solve of the 10M x 1M configuration
_TPU_LOWERED = {
    "cholesky_pallas.py": [(50, "lane_major"), (64, "batch_major"),
                           (50, "batch_major"), (64, "lane_major"),
                           (100, "lane_major"), (100, "batch_major"),
                           # the first rank whose batch-major entry took
                           # half a tile until PR 46: a whole one lowers
                           (57, "batch_major")],
    # (w, k) of the assembly kernel: the user side's narrowest and a middle
    # bucket of ML-20M, rank 64, and the item side's 64,728-wide bucket,
    # which goes through the tiled-w path with a ragged last tile
    "assemble_pallas.py": [(24, 50), (144, 50), (328, 64), (64728, 50),
                           (8, 100), (744, 100), (19176, 100)],
    # (rows a chain, steps) of the SDCA kernel at the two CoCoA cells,
    # epsilon-cocoa-plus and rcv1-cocoa, and the longest chain it takes
    "sdca_pallas.py": [(49, 49), (83, 83), (113, 113)],
}
# (r, w, k) of the lane-major form at the extremes of als-ml20m.retrain:
# the most rows, the middle of the user side, the widest whole bucket
# (sub-blocks of 8), the first tiled one, and seven entities of 97,096
# ratings (one lane tile, 95 w tiles); and rank 64
_TPU_LOWERED_LANES = [(28505, 24, 50), (13236, 216, 50), (3695, 744, 50),
                      (1935, 1120, 50), (7, 97096, 50), (4850, 328, 64),
                      # netflix-als-f100.retrain's movie half, rank 100: its
                      # two straight-line buckets
                      (1592, 1120, 100), (6, 327712, 100)]


def test_every_ops_pallas_kernel_has_a_lowering_case():
    import flink_ms_tpu.ops as ops_pkg

    ops = pathlib.Path(ops_pkg.__file__).parent
    with_kernels = sorted(
        f.name for f in ops.glob("*.py") if "pallas_call" in f.read_text()
    )
    assert with_kernels == sorted(_TPU_LOWERED)


@pytest.mark.parametrize("k,layout", _TPU_LOWERED["cholesky_pallas.py"])
def test_cholesky_kernel_lowers_for_tpu(k, layout):
    """Mosaic lowering only (no chip here): the compiled, non-interpret
    kernel must lower to a ``tpu_custom_call``."""
    n = 4096
    lowered = jax.jit(
        lambda A, b: cholesky_solve_batched(
            A, b, interpret=False, layout=layout)
    ).trace(
        jax.ShapeDtypeStruct((n, k, k), jnp.float32),
        jax.ShapeDtypeStruct((n, k), jnp.float32),
    ).lower(lowering_platforms=("tpu",))
    assert "tpu_custom_call" in lowered.as_text()


@pytest.mark.parametrize("w,k", _TPU_LOWERED["assemble_pallas.py"])
def test_assembly_kernel_lowers_for_tpu(w, k):
    from flink_ms_tpu.ops.assemble_pallas import assemble_bucket, tile_sizes

    r = 24
    assert (tile_sizes(w, k)[1] < w) == (w > 1024)
    lowered = jax.jit(
        lambda y, t: assemble_bucket(
            y, t, precision="highest", interpret=False)
    ).trace(
        jax.ShapeDtypeStruct((r, w, k), jnp.float32),
        jax.ShapeDtypeStruct((r, w), jnp.float32),
    ).lower(lowering_platforms=("tpu",))
    assert "tpu_custom_call" in lowered.as_text()


@pytest.mark.parametrize("r,w,k", _TPU_LOWERED_LANES)
def test_lane_major_assembly_kernel_lowers_for_tpu(r, w, k):
    from flink_ms_tpu.ops.assemble_pallas import (
        assemble_bucket_lanes, lane_tile_sizes)

    assert (lane_tile_sizes(w, k)[1] < w) == (w > 1024)
    lowered = jax.jit(
        lambda y, t: assemble_bucket_lanes(
            y, t, precision="highest", interpret=False)
    ).trace(
        jax.ShapeDtypeStruct((r, w, k), jnp.float32),
        jax.ShapeDtypeStruct((r, w), jnp.float32),
    ).lower(lowering_platforms=("tpu",))
    assert "tpu_custom_call" in lowered.as_text()
    n = -(-r // 128) * 128
    assert [tuple(o.shape) for o in lowered.out_info] == [(k, k, n), (k, n)]


# (r, w) of the weighted (implicit feedback) form at msd-ials.ials-retrain,
# rank 64: batch-major as the user half's lax.map chunks run it (the
# narrowest bucket's chunk, a middle one, the widest list), lane-major as
# the item half's straight-line buckets do (the most rows, the first
# tiled width, the widest: 196 lists of 12,784 in tiles of 640)
_TPU_LOWERED_WEIGHTED = [
    ("batch", 36123, 24), ("batch", 12134, 216), ("batch", 1, 3784),
    ("lanes", 13366, 328), ("lanes", 3826, 1120), ("lanes", 196, 12784)]


@pytest.mark.parametrize("layout,r,w", _TPU_LOWERED_WEIGHTED)
def test_weighted_assembly_kernel_lowers_for_tpu(layout, r, w):
    """`alpha` is a static of the kernel body: the multiply by the
    confidence row and the 1 + alpha*t of row k must lower in Mosaic at
    the cell's widths, in both output layouts."""
    from flink_ms_tpu.ops.assemble_pallas import (
        assemble_bucket, assemble_bucket_lanes)

    k = 64
    assemble = assemble_bucket_lanes if layout == "lanes" else assemble_bucket
    lowered = jax.jit(
        lambda y, t: assemble(y, t, precision="highest", interpret=False,
                              alpha=40.0)
    ).trace(
        jax.ShapeDtypeStruct((r, w, k), jnp.float32),
        jax.ShapeDtypeStruct((r, w), jnp.float32),
    ).lower(lowering_platforms=("tpu",))
    assert "tpu_custom_call" in lowered.as_text()
    n = -(-r // 128) * 128
    assert [tuple(o.shape) for o in lowered.out_info] == (
        [(k, k, n), (k, n)] if layout == "lanes" else [(r, k, k), (r, k)])


@pytest.mark.parametrize("k,n", [(50, 138496), (64, 4096)])
def test_cholesky_kernel_with_diagonal_lowers_for_tpu(k, n):
    """(50, 50, 138496): the user side of als-ml20m.retrain, padded."""
    lowered = jax.jit(
        lambda At, bt, d: cholesky_solve_lanes(At, bt, d, interpret=False)
    ).trace(
        jax.ShapeDtypeStruct((k, k, n), jnp.float32),
        jax.ShapeDtypeStruct((k, n), jnp.float32),
        jax.ShapeDtypeStruct((n,), jnp.float32),
    ).lower(lowering_platforms=("tpu",))
    assert "tpu_custom_call" in lowered.as_text()


def _f32(*dims):
    return jax.ShapeDtypeStruct(dims, jnp.float32)


@pytest.mark.parametrize("k", [50, 64, 100])
def test_a_side_solved_per_chunk_traces_the_body_once_a_rank(k):
    """Every padded batch is its own ``pallas_call`` and its own trace of
    the kernel function (a side solved per chunk has one a bucket: 19 at
    netflix-als-f100.retrain, 13 at msd-ials.ials-retrain, batch-major
    where the bucket takes several steps, lane-major where it takes one);
    under ``shared`` the elimination inside them is one jaxpr per (k, tile,
    dtype) on both layouts, and a process traces it once.  A call that does
    not say ``shared`` (a materialised side makes one) traces the plain
    body, where it always did."""
    solver = solver_copy()               # nothing traced yet
    traces = obs_metrics.get_registry().counter(
        "tpums_als_solver_body_traces_total")
    before = traces.value

    def trace(n, layout, **shared):
        jax.make_jaxpr(lambda A, b: solver.cholesky_solve_batched(
            A, b, interpret=False, layout=layout, **shared))(
                _f32(n, k, k), _f32(n, k))

    for n in (1408, 2688, 3968):
        trace(n, "batch_major", shared=True)
    trace(640, "lane_major", shared=True)
    assert traces.value - before == 1
    trace(640, "lane_major")
    jax.make_jaxpr(lambda At, bt, d: solver.cholesky_solve_lanes(
        At, bt, d, interpret=False))(_f32(k, k, 512), _f32(k, 512), _f32(512))
    assert traces.value - before == 3


@pytest.mark.parametrize("layout", ["lane_major", "batch_major"])
@pytest.mark.parametrize("k", [16, 50])
def test_the_shared_body_gives_the_plain_body_to_the_bit(rng, k, layout):
    n = 100       # one lane tile: the program the numpy comparisons compiled
    G = rng.standard_normal((n, k, k)).astype(np.float32)
    A = jnp.asarray(G @ G.transpose(0, 2, 1) + 5.0 * np.eye(k, dtype=np.float32))
    b = jnp.asarray(rng.standard_normal((n, k)).astype(np.float32))
    x, x_plain = (np.asarray(cholesky_solve_batched(
        A, b, interpret=True, layout=layout, shared=shared))
        for shared in (True, False))
    assert np.isfinite(x).all() and x.any()
    np.testing.assert_array_equal(x, x_plain)


@pytest.mark.parametrize("k,entry", [(64, "batch_major"), (100, "batch_major"),
                                     (50, "lanes")])
def test_the_shared_body_lowers_to_the_plain_body_for_tpu(k, entry):
    """Pallas inlines the body's ``pjit`` equation when it lowers the
    kernel: the module exported for the chip, Mosaic's kernel and all, is
    the text the plain body gives, so the jit can change neither the
    chip's kernel nor the compile cache's key unseen.  One call site and a
    fresh copy of the module serve each: the kernel's locations name the
    lines that wrote each operation and their callers, which for a shared
    body are those of whoever traced it first."""
    from jax import export

    n = 1408

    def module_text(shared):
        solver = solver_copy()
        if entry == "lanes":
            tile, limit = solver.solver_tile(k, "lane_major")
            fn, args = (lambda At, bt, d: solver._solve_padded(
                At, bt, tile, False, d, vmem_limit=limit, shared=shared)), (
                    _f32(k, k, n), _f32(k, n), _f32(1, n))
        else:
            tile, limit = solver.solver_tile(k, entry)
            fn, args = (lambda Ab, bb: solver._solve_padded_batch_major(
                Ab, bb, tile, False, vmem_limit=limit, shared=shared)), (
                    _f32(n, k, k), _f32(n, k))
        return export.export(jax.jit(fn), platforms=("tpu",))(
            *args).mlir_module()

    text, plain_text = [module_text(shared) for shared in (True, False)]
    assert "tpu_custom_call" in text
    assert text == plain_text


@pytest.mark.parametrize("h_rows,steps", _TPU_LOWERED["sdca_pallas.py"])
def test_sdca_kernel_lowers_for_tpu(h_rows, steps):
    """8192 chains a device, as both cells run."""
    from flink_ms_tpu.ops.sdca_pallas import fits_vmem, sdca_steps_lanes

    assert fits_vmem(h_rows, steps)
    hp, hs, cp = -(-h_rows // 8) * 8, -(-steps // 8) * 8, 8192
    state = jax.ShapeDtypeStruct((hp, cp), jnp.float32)
    lowered = jax.jit(
        lambda j, g, wx, y, q, a: sdca_steps_lanes(
            j, g, wx, y, q, a, steps=steps, lam_n=0.4, sigma_p=8192.0,
            interpret=False)
    ).trace(
        jax.ShapeDtypeStruct((hs, cp), jnp.int32),
        jax.ShapeDtypeStruct((h_rows, hp, cp), jnp.float32),
        state, state, state, state,
    ).lower(lowering_platforms=("tpu",))
    assert "tpu_custom_call" in lowered.as_text()
    assert tuple(lowered.out_info.shape) == (hp, cp)


# -- compiled for a described v5e: what lowering alone cannot refuse ----------

@pytest.fixture(scope="module")
def v5e_2x2():
    """A host of four TPU v5e chips that is described, not attached: the
    installed TPU compiler refuses here what it would refuse on the chip (a
    kernel over its scoped VMEM, which no lowering and no interpreted run
    shows)."""
    import os

    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(v5e_2x2):
    """One chip of the described host."""
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(v5e_2x2.devices[0])


@pytest.mark.parametrize("k,entry", [(100, "lane_major"), (100, "batch_major"),
                                     (100, "lanes"), (128, "lanes"),
                                     # a whole lane tile under the default
                                     # limit at the ranks whose batch-major
                                     # entry took half of one until PR 46
                                     (57, "batch_major"), (64, "batch_major")])
def test_solver_compiles_for_a_v5e(one_chip, k, entry):
    """netflix-als-f100.retrain's rank on all three entries, and the top of
    the stated range on the widest: under Mosaic's default scoped limit
    each of those is refused (30.88 MB of 16 at k = 100, PR 44).  Up to rank
    64 the default has to do (``solver_tile``), for a whole tile too."""
    n = 512

    def shape(*dims):
        return jax.ShapeDtypeStruct(dims, jnp.float32, sharding=one_chip)

    if entry == "lanes":
        fn, args = (lambda At, bt, d: cholesky_solve_lanes(
            At, bt, d, interpret=False)), (shape(k, k, n), shape(k, n), shape(n))
    else:
        fn, args = (lambda A, b: cholesky_solve_batched(
            A, b, interpret=False, layout=entry)), (shape(n, k, k), shape(n, k))
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("layout,r,w,k", [
    # netflix-als-f100.retrain's two straight-line movie buckets, and the
    # top of the stated range where the resident blocks are largest (whole
    # sub-blocks of 128 entities; sub-blocks of 8 and a full tile of w)
    ("lanes", 1592, 1120, 100), ("lanes", 6, 327712, 100),
    ("lanes", 640, 64, 128), ("lanes", 640, 1024, 128),
    # batch-major, as the users' lax.map steps run it: the default limit
    ("batch", 9084, 216, 100), ("batch", 4150, 744, 128)])
def test_assembly_compiles_for_a_v5e_above_rank_64(one_chip, layout, r, w, k):
    """Under the 40 MB the lane-major form names up to rank 64 the compiler
    refuses it at k = 128 (it needs 50 MiB): `_lanes_vmem_limit`."""
    from flink_ms_tpu.ops.assemble_pallas import (
        assemble_bucket, assemble_bucket_lanes)

    assemble = assemble_bucket_lanes if layout == "lanes" else assemble_bucket
    compiled = jax.jit(
        lambda y, t: assemble(y, t, precision="highest", interpret=False)
    ).lower(
        jax.ShapeDtypeStruct((r, w, k), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((r, w), jnp.float32, sharding=one_chip),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("layout", ["one_chip", "v5e_2x2"])
def test_the_update_scatter_owns_its_relayouts_on_a_v5e(
        v5e_2x2, one_chip, layout):
    """`serve/topk._scatter_program` at the serving cells' size (this file
    holds it because one file a run may describe a chip).  The TPU keeps the
    resident matrix column-major, and the program writes the changed rows
    into that buffer as it lies: no `copy` of a matrix-sized value anywhere
    in the module (XLA's own scatter relaid the whole matrix out before it
    and again after it: 27.3 of a frame's 39.7 device ms until PR 55), no
    scratch, and the result aliased to the argument.  Every operation of the
    entry and of the loop's body carries the scope `topk.update_scatter`, so
    that a trace's reading of the scope is the drain.  The row-sharded form
    (four shards of 4,194,304 rows on the fixture's 2x2 mesh) is the same
    loop in a `shard_map`: no collective either."""
    import re

    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from flink_ms_tpu.parallel.mesh import BLOCK_AXIS
    from flink_ms_tpu.serve import topk

    if layout == "one_chip":
        mesh, rows, held, everywhere = None, 5_000_000, one_chip, one_chip
    else:
        mesh = Mesh(np.array(v5e_2x2.devices), (BLOCK_AXIS,))
        rows = 4 * 4_194_304
        held = NamedSharding(mesh, P(BLOCK_AXIS, None))
        everywhere = NamedSharding(mesh, P())

    def shape(dtype, sharding, *dims):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=sharding)

    compiled = topk._scatter_program(mesh).lower(
        shape(jnp.float32, held, rows, 200),
        shape(jnp.int32, everywhere, 1024),
        shape(jnp.float32, everywhere, 1024, 200),
        shape(jnp.int32, everywhere)).compile()
    text = compiled.as_text()
    per_device = rows if mesh is None else rows // 4
    assert f"f32[{per_device},200]" in text
    # no operation relays the matrix out, gathers it or sends it anywhere
    assert not re.findall(
        rf"= f32\[{per_device},200\]\S* (?:copy|scatter|transpose)\(", text), text
    assert not re.findall(
        r"all-gather|all-reduce|all-to-all|collective-permute|reduce-scatter",
        text), text
    body = re.search(r"\n(%\S*region_0\S* .*?\n})", text, re.S).group(1)
    assert "dynamic-update-slice(" in body
    entry = text.split("ENTRY ")[1]
    plumbing = {"parameter", "constant", "get-tuple-element", "tuple",
                "bitcast", "copy"}  # (the one copy is of the scalar 0)
    for part in (body, entry):
        ops = [line for code, line in re.findall(
            r" ([a-z][a-z-]*)\((%.*)", part) if code not in plumbing]
        named = [op for op in ops if re.search(
            r"op_name=\"jit\(scatter_rows\)/(?:shard_map/)?"
            r"topk\.update_scatter/", op)]
        assert ops and len(named) == len(ops), part
    stats = compiled.memory_analysis()
    matrix_bytes = per_device * 200 * 4
    assert stats.temp_size_in_bytes < 0.1e9
    assert matrix_bytes <= stats.alias_size_in_bytes < matrix_bytes + 1e6
    assert stats.output_size_in_bytes < matrix_bytes + 1e6


@pytest.mark.parametrize("layout", ["one_chip", "v5e_2x2"])
def test_the_frame_program_masks_its_spare_rows_inside_the_score_on_a_v5e(
        v5e_2x2, one_chip, layout):
    """`serve/topk`'s exact frame programs at the serving cells' sizes with
    their spare rows (one chip: `mesh.row_capacity(5,000,000)`; the 2x2 mesh:
    four shards of 4,194,304; this file holds it because one file a run may
    describe a chip).  The live count is an operand, so one compile serves
    every live count; the compare is a fusion of its own over a row-number
    vector (one byte a row, not a pass over the scores), and the select
    rides the matmul's output fusion: the `(8, rows)` scores are written
    ONCE, by that fusion, and read once, by the top-k.  A select in a fusion
    of its own would read and write them again: 0.4 ms of the paced cell's
    7 ms frame."""
    import re

    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from flink_ms_tpu.parallel.mesh import BLOCK_AXIS, row_capacity
    from flink_ms_tpu.serve import topk
    from flink_ms_tpu.serve.table import ModelTable

    if layout == "one_chip":
        index = topk.DeviceFactorIndex(ModelTable(), "-I")
        index.bulk_load(["1", "2", "3"], np.eye(3, 8, dtype=np.float32))
        index.topk_many(np.ones((2, 8), np.float32), 2)  # makes the program
        program, rows = index._topk_many_fn, row_capacity(5_000_000)
        per_device = rows
        assert rows == 5_019_648
        held = everywhere = one_chip
    else:
        mesh = Mesh(np.array(v5e_2x2.devices), (BLOCK_AXIS,))
        program, rows, per_device = (
            topk._sharded_topk_program(mesh), 4 * 4_194_304, 4_194_304)
        held = NamedSharding(mesh, P(BLOCK_AXIS, None))
        everywhere = NamedSharding(mesh, P())

    def shape(dtype, sharding, *dims):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=sharding)

    text = program.lower(
        shape(jnp.float32, held, rows, 200), shape(jnp.int32, everywhere),
        shape(jnp.float32, everywhere, 8, 200), 10).compile().as_text()
    entry = text.split("ENTRY ")[1]
    scores = re.findall(rf"(%\S+) = f32\[8,{per_device}\]\S* (\S+?)\(", entry)
    assert len(scores) == 1 and scores[0][1] == "fusion", scores
    fused = re.search(rf"{re.escape(scores[0][0])} = .*?calls=(%[\w.-]+)",
                      entry).group(1)
    body = re.search(rf"\n{re.escape(fused)} .*?\n}}", text, re.S).group(0)
    assert " convolution(" in body and " select(" in body
    # the matrix is read where it lies: no copy, no transpose of it
    assert not re.findall(
        rf"= f32\[{per_device},200\]\S* (?:copy|transpose)\(", text)
    assert re.findall(rf"= pred\[{per_device}\]\S* fusion\(", entry)
