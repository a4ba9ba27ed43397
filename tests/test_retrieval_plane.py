"""Retrieval-plane tiers (round 11): mesh-sharded exact layout parity,
IVF ANN recall contract, tier auto-fallback, zero-host-copy steady path,
and the maintenance observability gauges.

The suite-wide conftest forces 8 virtual host devices, so the sharded
tier is exercised in-process; size floors are overridden per-test (the
production defaults keep tiny catalogs on the single-device layout)."""

import io
import os
import sys
import threading
import time

import numpy as np
import pytest

from flink_ms_tpu.serve import topk as topk_mod
from flink_ms_tpu.serve.table import ModelTable
from flink_ms_tpu.serve.topk import DeviceFactorIndex


def _clustered_rows(n, d, seed=0, n_clusters=16):
    """Mixture-of-gaussians factors — the geometry ALS items actually
    have, and the one IVF recall is calibrated against (isotropic noise
    has no cluster structure for a coarse quantizer to exploit)."""
    rng = np.random.default_rng(seed)
    cents = rng.normal(size=(n_clusters, d)).astype(np.float32) * 3.0
    assign = rng.integers(0, n_clusters, size=n)
    return cents[assign] + rng.normal(size=(n, d)).astype(np.float32) * 0.5


def _fill_table(rows):
    t = ModelTable()
    for i, vec in enumerate(rows):
        t.put(f"it{i}-I", ";".join(f"{v:.6f}" for v in vec))
    return t


def _ids(results):
    return [i for i, _ in results]


@pytest.fixture
def catalog():
    rows = _clustered_rows(3000, 8, seed=7)
    return _fill_table(rows), rows


def _index(table, monkeypatch, *, sharded=None, tier=None, **env):
    if sharded is not None:
        monkeypatch.setenv("TPUMS_TOPK_SHARDED", sharded)
    if tier is not None:
        monkeypatch.setenv("TPUMS_TOPK_TIER", tier)
    for k, v in env.items():
        monkeypatch.setenv(k, str(v))
    return DeviceFactorIndex(table, "-I")


# -- sharded exact tier --------------------------------------------------


def test_sharded_matches_single_device(catalog, monkeypatch):
    table, rows = catalog
    single = _index(table, monkeypatch, sharded="0", tier="exact")
    shard = _index(table, monkeypatch, sharded="1", tier="exact")
    q = np.random.default_rng(1).normal(size=(6, rows.shape[1]))
    q = q.astype(np.float32)
    ref = single.topk_many(q, 17)
    got = shard.topk_many(q, 17)
    assert shard._is_sharded and not single._is_sharded
    for r, g in zip(ref, got):
        assert _ids(r) == _ids(g)
        np.testing.assert_allclose(
            [s for _, s in r], [s for _, s in g], rtol=1e-4)
    # single-query parity too (rides the frame program when sharded)
    r1 = single.topk(q[0], 9)
    g1 = shard.topk(q[0], 9)
    assert _ids(r1) == _ids(g1)


def test_sharded_dirty_scatter_mid_stream(catalog, monkeypatch):
    table, rows = catalog
    single = _index(table, monkeypatch, sharded="0", tier="exact")
    shard = _index(table, monkeypatch, sharded="1", tier="exact")
    d = rows.shape[1]
    probe = np.ones(d, dtype=np.float32)
    single.topk(probe, 5)
    shard.topk(probe, 5)
    # stream an update through the table: BOTH indexes see it via the
    # dirty set and must agree afterwards (in-place scatter, no rebuild)
    table.put("it42-I", ";".join("7.5" for _ in range(d)))
    builds_before = (single.full_builds, shard.full_builds)
    r = single.topk(probe, 5)
    g = shard.topk(probe, 5)
    assert _ids(r)[0] == "it42" and _ids(g)[0] == "it42"
    assert _ids(r) == _ids(g)
    assert (single.full_builds, shard.full_builds) == builds_before
    assert single.inplace_updates >= 1 and shard.inplace_updates >= 1


def test_sharded_pad_rows_never_surface(monkeypatch):
    # 10 rows over 8 shards pads to 64 rows — 54 pad rows with every
    # real score negative: the bias must keep pads out of the top-k
    rows = -np.abs(_clustered_rows(10, 4, seed=3)) - 1.0
    table = _fill_table(rows.astype(np.float32))
    shard = _index(table, monkeypatch, sharded="1", tier="exact")
    res = shard.topk(np.ones(4, dtype=np.float32), 10)
    assert shard._is_sharded and shard._n_pad > 10
    assert len(res) == 10
    assert all(i.startswith("it") for i in _ids(res))


def test_row_bucket_discipline():
    from flink_ms_tpu.parallel.mesh import row_bucket

    assert row_bucket(1000, 8) == 8 * 128
    assert row_bucket(1024, 8) == 8 * 128
    assert row_bucket(1025, 8) == 8 * 256
    assert row_bucket(5, 8, floor=8) == 64  # floor keeps shards top_k-able
    with pytest.raises(ValueError):
        row_bucket(10, 0)


# -- IVF ANN tier --------------------------------------------------------


def test_ivf_recall_parity(monkeypatch):
    rows = _clustered_rows(20_000, 8, seed=11)
    table = _fill_table(rows)
    exact = _index(table, monkeypatch, sharded="0", tier="exact")
    ivf = _index(table, monkeypatch, sharded="0", tier="ivf",
                 TPUMS_ANN_NLIST=64, TPUMS_ANN_NPROBE=16)
    ivf.topk(rows[0], 5)  # first query pays the build (ANN included)
    assert ivf._ann is not None
    assert ivf._ann.recall_probe >= 0.9  # build-time self-probe
    rng = np.random.default_rng(2)
    q = rows[rng.choice(len(rows), size=32, replace=False)]
    k = 50
    hits = total = 0
    for r, g in zip(exact.topk_many(q, k), ivf.topk_many(q, k)):
        hits += len(set(_ids(r)) & set(_ids(g)))
        total += len(r)
    assert hits / total >= 0.9
    # every returned IVF score is EXACT (re-rank reads the same matrix)
    r1, g1 = exact.topk(q[0], k), ivf.topk(q[0], k)
    exact_scores = dict(r1)
    for item, score in g1:
        if item in exact_scores:
            assert abs(score - exact_scores[item]) < 1e-3


def test_ivf_auto_gate_degrades_to_exact(monkeypatch):
    # auto tier + a catalog below the ANN floor: no ANN tier is built
    rows = _clustered_rows(2000, 8, seed=5)
    table = _fill_table(rows)
    idx = _index(table, monkeypatch, sharded="0", tier="auto")
    idx.topk(np.ones(8, dtype=np.float32), 5)
    assert idx._ann is None and not idx.prefers_frames
    # auto tier past the floor but failing the recall gate: degrades too
    monkeypatch.setenv("TPUMS_ANN_MIN_ROWS", "1000")
    monkeypatch.setenv("TPUMS_ANN_RECALL_MIN", "1.01")  # unreachable
    idx2 = _index(table, monkeypatch, sharded="0", tier="auto")
    idx2.topk(np.ones(8, dtype=np.float32), 5)
    assert idx2._ann is None


@pytest.mark.parametrize("tier", ["ivf", "auto"])
def test_failed_ivf_build_is_counted_and_never_silent(monkeypatch, capsys, tier):
    """A build that fails leaves the exact tier serving, as before; it is
    counted, and where the operator forced ``ivf`` stderr says that their
    tier is not the one answering."""
    from flink_ms_tpu.serve.ann import IVFIndex

    rows = _clustered_rows(2000, 8, seed=5)
    table = _fill_table(rows)

    def no_room(*a, **kw):
        raise MemoryError("RESOURCE_EXHAUSTED: 32 GB on a 16 GB chip")

    monkeypatch.setattr(IVFIndex, "build", classmethod(no_room))
    idx = _index(table, monkeypatch, sharded="0", tier=tier,
                 TPUMS_ANN_MIN_ROWS=1000)
    failures = idx._obs_ann_build_failures.value
    errors = idx._obs_device_errors.value
    exact = _index(table, monkeypatch, sharded="0", tier="exact")
    q = np.ones(8, dtype=np.float32)
    assert idx.topk(q, 5) == exact.topk(q, 5)
    assert idx._ann is None and not idx.prefers_frames
    assert idx._obs_ann_build_failures.value == failures + 1
    assert idx._obs_device_errors.value == errors + 1
    assert idx._obs_ann_frames.value == 0 or idx._ann is None
    err = capsys.readouterr().err
    assert "IVF build failed (serving exact)" in err and "32 GB" in err
    assert ("TPUMS_TOPK_TIER=ivf is NOT being served" in err) == (tier == "ivf")


def test_tier_auto_single_device_fallback(catalog, monkeypatch):
    # one visible device: the mesh is None, sharding can't engage even
    # when forced, and auto tier serves single-device exact
    table, rows = catalog
    monkeypatch.setattr(topk_mod, "_index_mesh", lambda: None)
    idx = _index(table, monkeypatch, sharded="1", tier="auto")
    res = idx.topk(np.ones(rows.shape[1], dtype=np.float32), 5)
    assert len(res) == 5
    assert not idx._is_sharded and idx._ann is None
    assert not idx.prefers_frames


# -- zero host copies on the steady sharded path -------------------------


def test_sharded_steady_path_zero_catalog_copies(catalog, monkeypatch):
    table, rows = catalog
    shard = _index(table, monkeypatch, sharded="1", tier="exact")
    q = np.random.default_rng(4).normal(size=(8, rows.shape[1]))
    q = q.astype(np.float32)
    shard.topk_many(q, 10)  # warm: build + compiles off the probe
    matrix_before = shard._matrix
    seen: list = []
    real_to_host = topk_mod._to_host

    def spy(x):
        seen.append(tuple(np.shape(x)))
        return real_to_host(x)

    monkeypatch.setattr(topk_mod, "_to_host", spy)
    for _ in range(5):
        shard.topk_many(q, 10)
    # _to_host is the ONE device->host funnel on the query path: only
    # the merged (B, k) winners may cross, never a catalog-sized array —
    # ONE (B, 2k) array a dispatch, scores and indices side by side
    assert seen == [(8, 20)] * 5, seen
    # and the resident matrix was not re-placed or rebuilt per query
    assert shard._matrix is matrix_before
    # jit-trace check: the compiled program's one output is (B, 2k) —
    # the catalog stays an input, it never flows back out
    import jax

    fn = topk_mod._sharded_topk_program(shard._mesh)
    traced = jax.make_jaxpr(lambda m, b, qs: fn(m, b, qs, 10))(
        shard._matrix, shard._live, q)
    out_shapes = [tuple(v.aval.shape) for v in traced.jaxpr.outvars]
    assert out_shapes == [(8, 20)]


# -- the one-copy fetch --------------------------------------------------


def test_pack_and_unpack_are_a_bit_cast():
    """Scores travel as int32 bit patterns beside the indices: every
    float32 pattern (a NaN's payload, a denormal, -0.0, the pad score)
    and every index (-1 is a masked ANN slot) comes back bit for bit."""
    import jax

    scores = np.array(
        [[1.5, -0.0, np.nan, 1e-45, -np.inf, topk_mod._PAD_SCORE]],
        dtype=np.float32)
    scores.view(np.uint32)[0, 2] = 0x7FC12345  # a NaN with a payload
    idx = np.array([[0, 2**31 - 1, -1, 7, -2**31, 123456789]], np.int32)
    packed = topk_mod._to_host(jax.jit(topk_mod._pack_results)(scores, idx))
    assert packed.shape == (1, 12) and packed.dtype == np.int32
    got_scores, got_idx = topk_mod._unpack_results(packed)
    assert got_scores.dtype == np.float32
    assert got_scores.tobytes() == scores.tobytes()
    assert got_idx.tobytes() == idx.tobytes()


@pytest.mark.parametrize("sharded", ["0", "1"])
def test_one_copy_fetch_returns_the_two_array_forms_bits(
        catalog, monkeypatch, sharded):
    """The packed output is the programs' former pair of outputs, bit for
    bit: the same index answers frames and singles once through the
    one-copy fetch and once with the programs returning (scores, idx) as
    they did, and the (id, float score) rows compare with ``==``.  On the
    sharded tier, where a single rides the frame program, a frame's rows
    are the singles' rows too."""
    table, rows = catalog
    idx = _index(table, monkeypatch, sharded=sharded, tier="exact")
    q = np.random.default_rng(11).normal(size=(8, rows.shape[1]))
    q = q.astype(np.float32)
    many, singles = idx.topk_many(q, 10), [idx.topk(v, 10) for v in q]
    assert idx._is_sharded == (sharded == "1")
    assert all(len(row) == 10 for row in many)
    if idx._is_sharded:
        assert many == singles
    monkeypatch.setattr(topk_mod, "_pack_results", lambda s, i: (s, i))
    monkeypatch.setattr(topk_mod, "_unpack_results", lambda pair: pair)
    monkeypatch.setattr(topk_mod, "_to_host",
                        lambda pair: tuple(np.asarray(a) for a in pair))
    monkeypatch.setattr(topk_mod, "_sharded_program_cache", {})
    idx._topk_fn = idx._topk_many_fn = None  # traced again, unpacked
    assert idx.topk_many(q, 10) == many
    assert [idx.topk(v, 10) for v in q] == singles


# -- observability -------------------------------------------------------


def test_rebuild_counter_and_staleness_gauges(catalog, monkeypatch):
    table, rows = catalog
    idx = _index(table, monkeypatch, sharded="0", tier="exact")
    d = rows.shape[1]
    idx.topk(np.ones(d, dtype=np.float32), 3)
    base = idx._obs_rebuilds.value
    assert base >= 1  # the initial build counted
    # a row of ANOTHER WIDTH is structural (a new id no longer is: it is
    # written into spare capacity): the background rebuild counts
    table.put("brand-new-I", ";".join("1.0" for _ in range(d + 1)))
    idx.topk(np.ones(d, dtype=np.float32), 3)
    deadline = time.time() + 10
    while time.time() < deadline:
        if (idx._rebuild_thread is None
                or not idx._rebuild_thread.is_alive()):
            break
        time.sleep(0.02)
    idx.topk(np.ones(d, dtype=np.float32), 3)
    assert idx._obs_rebuilds.value >= base + 1
    assert idx._obs_dirty_depth.value == 0
    assert idx._obs_staleness.value == 0.0


def test_staleness_tracks_oldest_unabsorbed_update(catalog, monkeypatch):
    table, rows = catalog
    idx = _index(table, monkeypatch, sharded="0", tier="exact")
    d = rows.shape[1]
    idx.topk(np.ones(d, dtype=np.float32), 3)
    # mark dirty WITHOUT querying: staleness must grow until a query
    # drains the backlog
    table.put("it7-I", ";".join("2.0" for _ in range(d)))
    assert idx._oldest_dirty_ts is not None
    time.sleep(0.05)
    with idx._lock:
        idx._observe_health()
    assert idx._obs_staleness.value >= 0.05
    assert idx._obs_dirty_depth.value >= 1
    idx.topk(np.ones(d, dtype=np.float32), 3)  # drains
    with idx._lock:
        idx._observe_health()
    assert idx._obs_staleness.value == 0.0


def test_fleet_signals_surfaces_retrieval_health():
    from flink_ms_tpu.obs.scrape import fleet_signals

    def snap(rebuilds, dirty, stale, recall):
        return {
            "ts": 0,
            "counters": [{"name": "tpums_topk_rebuilds_total",
                          "labels": {}, "value": rebuilds}],
            "gauges": [
                {"name": "tpums_topk_dirty_depth", "labels": {},
                 "value": dirty},
                {"name": "tpums_topk_index_staleness_seconds",
                 "labels": {"pid": "1"}, "value": stale},
                {"name": "tpums_topk_index_staleness_seconds",
                 "labels": {"pid": "2"}, "value": stale / 2},
                {"name": "tpums_ann_recall_probe",
                 "labels": {"pid": "1"}, "value": recall},
                {"name": "tpums_ann_recall_probe",
                 "labels": {"pid": "2"}, "value": recall + 0.02},
            ],
            "histograms": [],
        }
    sig = fleet_signals(snap(2, 0, 0.0, 0.96), snap(7, 12, 3.0, 0.96),
                        dt_s=10.0)
    assert sig["topk_rebuilds_per_s"] == pytest.approx(0.5)
    assert sig["topk_dirty_depth"] == 12
    assert sig["topk_staleness_s"] == 3.0    # max across pids, not sum
    assert sig["ann_recall"] == pytest.approx(0.96)  # min across pids
    # no ANN tier anywhere -> None, not 0.0 (0.0 would page someone)
    empty = {"ts": 0, "counters": [], "gauges": [], "histograms": []}
    assert fleet_signals(empty, empty, dt_s=1.0)["ann_recall"] is None


# -- microbatcher frame handoff ------------------------------------------


def test_batcher_hands_lone_query_to_frame_program(catalog, monkeypatch):
    from flink_ms_tpu.serve.microbatch import TopKBatcher

    table, rows = catalog
    shard = _index(table, monkeypatch, sharded="1", tier="exact")
    assert shard.prefers_frames is False or shard._built_once is False
    q = np.ones(rows.shape[1], dtype=np.float32)
    shard.topk(q, 3)  # build -> sharded layout engages
    assert shard.prefers_frames
    calls = {"topk": 0, "topk_many": 0}
    real_many = shard.topk_many
    monkeypatch.setattr(
        shard, "topk_many",
        lambda *a, **kw: (calls.__setitem__(
            "topk_many", calls["topk_many"] + 1) or real_many(*a, **kw)))
    batcher = TopKBatcher(shard)
    try:
        pending = batcher.submit(q, 3, allow_inline=False)
        res = pending.wait()
        assert _ids(res)[0].startswith("it")
        assert calls["topk_many"] == 1  # lone query rode the frame path
    finally:
        batcher.close()
