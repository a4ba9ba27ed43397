"""The IVF tier (`serve/ann.py`) as PR 39 left it: lists that are runs of
whole blocks of the resident matrix and lose no row, a query program of
static shape that reads them as blocks, held against the benchmark's plain
inverted file (`benchmark/reference_ivf.py`: numpy, float64, nothing of the
block layout); what the index owner does around it (list-ordered ids, an
UPDATE seen by the next query, the tier's counters, phases and scopes); and
the benchmark's side of the cell `bigann-t2i-10m-ivf.topk-paced-recall`.
"""

import json
import os
import shutil
import subprocess
import sys
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference, reference_ivf, roofline_ivf, synth_t2i_clustered
from benchmark.readers import counter_share, run_count, trace_scope_roofline_counted
from flink_ms_tpu.obs import tracing
from flink_ms_tpu.serve import ann as ann_mod
from flink_ms_tpu.serve.ann import IVFIndex, block_count, block_rows
from flink_ms_tpu.serve.table import ModelTable
from flink_ms_tpu.serve.topk import DeviceFactorIndex, _unpack_results

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = os.path.join(REPO, "benchmark", "tests", "tiny-ivf", "BENCHMARK.json")
TINY_CELL = "t2i-tiny-ivf.topk-paced-recall"
CELL = "bigann-t2i-10m-ivf.topk-paced-recall"


def clustered(n, d, seed, clusters=12, spread=0.5):
    rng = np.random.default_rng(seed)
    cents = rng.normal(size=(clusters, d)).astype(np.float32) * 2.0
    return (cents[rng.integers(0, clusters, n)]
            + rng.normal(size=(n, d)).astype(np.float32) * spread)


def lopsided(n, d, seed):
    """A third of the rows one and the same vector (they share a list, and
    the centroids drawn from among them tie, so all but one of those lists
    stay empty), the rest in small clusters."""
    rows = clustered(n, d, seed, clusters=5, spread=0.2)
    rows[: n // 3] = rows[0]
    return rows


def build(rows, nlist, nprobe, seed=0):
    matrix = jax.device_put(rows)
    return IVFIndex.build(rows, matrix, nlist=nlist, nprobe=nprobe, seed=seed)


def member_of(index, position):
    """The list of each catalog row, read off the built layout."""
    return index.membership()[position]


# -- the layout -----------------------------------------------------------------

LAYOUTS = {
    "even": (lambda: clustered(3000, 8, 1), 16, 4),
    "more-lists": (lambda: clustered(2500, 12, 2), 64, 16),
    "lopsided": (lambda: lopsided(3000, 8, 3), 32, 8),
    "few-rows": (lambda: clustered(40, 8, 4), 8, 4),
}


@pytest.fixture(scope="module", params=sorted(LAYOUTS))
def built(request):
    make, nlist, nprobe = LAYOUTS[request.param]
    rows = make()
    index, listed, position = build(rows, nlist, nprobe)
    return SimpleNamespace(name=request.param, rows=rows, index=index,
                           listed=listed, position=position)


def test_every_row_is_in_exactly_one_list(built):
    n = len(built.rows)
    member = built.index.membership()
    assert (member >= 0).sum() == n          # as many list slots as rows
    assert len(np.unique(built.position)) == n
    assert (member[built.position] >= 0).all()
    # the listed matrix holds each row where `position` says, zeros elsewhere
    listed = np.asarray(built.listed)
    assert np.array_equal(listed[built.position], built.rows)
    assert not listed[member < 0].any()
    # a list is one run of positions: whole blocks, the last one padded
    per = built.index.rows_per_block
    real = np.flatnonzero(member >= 0)
    runs = np.flatnonzero(np.diff(member[real]) != 0)
    assert len(runs) + 1 == len(np.unique(member[real]))
    starts = real[np.concatenate(([0], runs + 1))]
    assert (starts % per == 0).all()
    counts = np.bincount(member[real], minlength=built.index.nlist)
    assert np.array_equal(np.asarray(built.index.list_rows), counts)
    assert np.array_equal(np.asarray(built.index.list_blocks), -(-counts // per))


def test_rows_sit_in_their_nearest_centroids_list(built):
    nearest = reference_ivf.nearest_centroid(
        built.rows, np.asarray(built.index.centroids))
    member = member_of(built.index, built.position)
    # a CPU product is full f32: only exact ties may differ
    assert (nearest != member).mean() <= (0.34 if built.name == "lopsided" else 0.01)


def test_shapes_come_from_the_sizes_alone(built):
    n = len(built.rows)
    per = block_rows(n, built.index.nlist)
    assert built.index.rows_per_block == per
    assert built.listed.shape == (block_count(n, built.index.nlist) * per,
                                  built.rows.shape[1])


def test_lopsided_lists_are_lopsided():
    rows = lopsided(3000, 8, 3)
    index, _, _ = build(rows, 32, 8)
    counts = np.asarray(index.list_rows)
    assert counts.max() >= len(rows) // 3    # one list holds a third of the rows
    assert (counts == 0).sum() >= 3          # and several hold none


@pytest.mark.parametrize("n,nlist,per", [
    (5_000_000, 4096, 256), (1_000_000, 4096, 128), (4_194_304, 4096, 256),
    (3000, 16, 128)])
def test_block_rows_rule(n, nlist, per):
    assert block_rows(n, nlist) == per
    blocks = block_count(n, nlist)
    assert blocks % 128 == 0 and blocks >= -(-n // per) + nlist


# -- the query program against the plain reference ----------------------------------


@pytest.mark.parametrize("batch", [1, 2, 8, 32])
def test_frames_match_the_plain_inverted_file(built, batch):
    n, d = built.rows.shape
    k = min(10, n)
    q = np.random.default_rng([5, batch]).normal(size=(batch, d)).astype(np.float32)
    out = np.asarray(built.index.search(built.listed, q, k))
    assert out.shape == (max(batch, 8), 2 * k + 2) and out.dtype == np.int32
    scores, at = _unpack_results(out[:batch, :-2])
    member = member_of(built.index, built.position)
    ref_ids, ref_scores, margin = reference_ivf.topk(
        built.rows, member, np.asarray(built.index.centroids), q,
        built.index.nprobe, k)
    row_at = np.full(built.listed.shape[0], -1)
    row_at[built.position] = np.arange(n)
    got_ids = np.where(at >= 0, row_at[np.maximum(at, 0)], -1)
    clear = margin > 1e-5
    assert clear.any()
    have = np.isfinite(ref_scores[:, :k])
    assert np.array_equal(at >= 0, have)     # short lists: -1 past their rows
    err, wrong, ranks = reference.compare_topk(
        got_ids[clear], np.where(have, scores, 0.0)[clear], ref_ids[clear],
        np.where(np.isfinite(ref_scores), ref_scores, 0.0)[clear], 1e-6)
    assert wrong == 0 and ranks > 0
    assert err <= 1e-5
    # the two counts: rows in the union of the frame's probed lists, and
    # the rows scored for the row's own query (whole blocks)
    lists, _ = reference_ivf.probe(np.asarray(built.index.centroids), q,
                                   built.index.nprobe)
    counts = np.asarray(built.index.list_rows)
    blocks = np.asarray(built.index.list_blocks)
    if clear.all():
        assert (out[:, -2] == counts[np.unique(lists)].sum()).all()
        assert np.array_equal(out[:batch, -1], blocks[lists].sum(axis=1)
                              * built.index.rows_per_block)
    assert (out[batch:] == out[0]).all()     # pad rows repeat the first


def test_probing_every_list_is_the_exact_ranking():
    rows = clustered(2000, 8, 9)
    index, listed, position = build(rows, 16, 16)
    q = np.random.default_rng(3).normal(size=(4, 8)).astype(np.float32)
    _, at = _unpack_results(np.asarray(index.search(listed, q, 10))[:4, :-2])
    row_at = np.full(listed.shape[0], -1)
    row_at[position] = np.arange(len(rows))
    exact_ids, _ = reference.topk(rows, q, 10)
    assert np.array_equal(row_at[at], exact_ids[:, :10])
    assert index.recall_probe == 1.0


def test_pad_rows_of_a_frame_do_not_widen_the_union():
    rows = clustered(3000, 8, 1)
    index, listed, _ = build(rows, 16, 4)
    q = np.random.default_rng(8).normal(size=(1, 8)).astype(np.float32)
    alone = np.asarray(index.search(listed, q, 5))
    twice = np.asarray(index.search(listed, np.repeat(q, 2, 0), 5))
    assert np.array_equal(alone, twice) and (alone == alone[0]).all()


def test_the_program_itself_takes_no_frame_wider_than_the_bits():
    """The owner slices (`test_frames_wider_than_the_program_go_as_slices`);
    the program's own limit stays an assertion."""
    rows = clustered(500, 8, 1)
    index, listed, _ = build(rows, 8, 4)
    assert IVFIndex.max_frame == 32
    with pytest.raises(ValueError, match="up to 32"):
        index.search(listed, np.zeros((64, 8), np.float32), 5)


def test_recall_probe_sees_a_fault_of_the_layout(monkeypatch):
    """The probe's exact side is the plain program over the row-ordered
    matrix: a layout whose positions are wrong loses recall, though every
    list is probed."""
    rows = clustered(2000, 8, 9)
    sound, _, _ = build(rows, 16, 16)
    assert sound.recall_probe == 1.0
    in_order = ann_mod._in_list_order

    def shifted(rows, src, place, dev, **kw):
        return in_order(rows, np.roll(src, 1), place, dev, **kw)

    monkeypatch.setattr(ann_mod, "_in_list_order", shifted)
    faulty, _, _ = build(rows, 16, 16)
    assert faulty.recall_probe < 0.5


@pytest.mark.parametrize("batch", [8, 16, 32])
def test_query_program_lowers_for_tpu_with_its_scopes(batch):
    """Mosaic lowering only (no chip here), at the cell's shapes: both
    kernels lower, every product carries the score precision, and the three
    scopes name the program's parts."""
    n, d, nlist = 5_000_000, 200, 4096
    per, blocks = block_rows(n, nlist), block_count(n, nlist)
    s = jax.ShapeDtypeStruct
    lowered = ann_mod._search_jit().trace(
        s((nlist, d), jnp.float32), s((nlist,), jnp.int32), s((nlist,), jnp.int32),
        s((blocks,), jnp.int32), s((blocks * per, d), jnp.float32),
        s((batch, d), jnp.float32),
        k=10, nprobe=256, rows=per, interpret=False,
    ).lower(lowering_platforms=("tpu",))
    text = lowered.as_text(debug_info=True)
    assert text.count("tpu_custom_call") >= 2
    for scope in ("topk.ivf.probe", "topk.ivf.scan", "topk.ivf.select"):
        assert scope in text
    assert "HIGHEST" in text and "DEFAULT" not in text.replace("DEFAULT_", "")
    assert tuple(lowered.out_info.shape) == (batch, 22)


# -- the index owner ----------------------------------------------------------------


def filled(rows):
    table = ModelTable()
    for i, vec in enumerate(rows):
        table.put(f"it{i}-I", ";".join(f"{v:.6f}" for v in vec))
    return table


@pytest.fixture
def served(monkeypatch):
    monkeypatch.setenv("TPUMS_TOPK_TIER", "ivf")
    monkeypatch.setenv("TPUMS_TOPK_SHARDED", "0")
    monkeypatch.setenv("TPUMS_ANN_NLIST", "16")
    monkeypatch.setenv("TPUMS_ANN_NPROBE", "4")
    rows = clustered(2000, 8, 21)
    table = filled(rows)
    return table, rows, DeviceFactorIndex(table, "-I")


def test_index_serves_list_ordered_ids(served):
    table, rows, index = served
    got = index.topk(rows[5], 5)
    assert index._ann is not None and index.prefers_frames
    member = np.empty(len(rows), np.int64)
    for item, pos in index._id_pos.items():
        member[int(item[2:])] = index._ann.membership()[pos]
    ref_ids, ref_scores, _ = reference_ivf.topk(
        rows, member, np.asarray(index._ann.centroids), rows[:6], 4, 5)
    assert [i for i, _ in got] == [f"it{i}" for i in ref_ids[5, :5]]
    assert [s for _, s in got] == pytest.approx(ref_scores[5, :5].tolist(), abs=1e-4)
    n_pad = index._matrix.shape[0]
    assert len(index._ids) == n_pad and index._n_real == len(rows)
    assert sum(i is not None for i in index._ids) == len(rows)
    for item, pos in list(index._id_pos.items())[:50]:
        assert index._ids[pos] == item
    many = index.topk_many(rows[:6], 5)
    assert [[i for i, _ in r] for r in many] == [
        [f"it{i}" for i in ids[:5]] for ids in ref_ids]
    assert many[0] == index.topk(rows[0], 5)


def test_update_of_an_existing_row_is_seen_by_the_next_query(served):
    table, rows, index = served
    q = rows[7]
    before = index.topk(q, 3)
    builds = index.full_builds
    # the last of the catalog's rows now scores far above everything on q
    far = ";".join(f"{v:.6f}" for v in (q * 50.0))
    member = index._ann.membership()
    probed = reference_ivf.probe(np.asarray(index._ann.centroids), q[None], 4)[0][0]
    target = next(i for i, pos in index._id_pos.items()
                  if member[pos] in probed and i != before[0][0])
    table.put(f"{target}-I", far)
    after = index.topk(q, 3)
    assert after[0][0] == target
    assert after[0][1] == pytest.approx(50.0 * float(q @ q), rel=1e-5)
    assert index.full_builds == builds and index.inplace_updates == 1
    assert index.topk_many(np.stack([q, q]), 3)[1][0][0] == target


def test_tier_counters_count_frames_queries_and_rows(served):
    table, rows, index = served
    index.topk(rows[0], 3)                      # builds
    at = {c: getattr(index, "_obs_ann_" + c).value
          for c in ("frames", "queries", "union_rows", "probed_rows")}
    index.topk_many(rows[:3], 3)                # one frame of 3, padded to 4
    index.topk(rows[9], 3)                      # one frame of 1
    gain = {c: getattr(index, "_obs_ann_" + c).value - v for c, v in at.items()}
    assert gain["frames"] == 2 and gain["queries"] == 4
    cents = np.asarray(index._ann.centroids)
    counts = np.asarray(index._ann.list_rows)
    blocks = np.asarray(index._ann.list_blocks)
    frame, _ = reference_ivf.probe(cents, rows[:3], 4)
    single, _ = reference_ivf.probe(cents, rows[9:10], 4)
    assert gain["union_rows"] == (counts[np.unique(frame)].sum()
                                  + counts[np.unique(single)].sum())
    assert gain["probed_rows"] == index._ann.rows_per_block * (
        blocks[frame].sum() + blocks[single].sum())


@pytest.mark.parametrize("n_queries", [33, 64, 70])
def test_frames_wider_than_the_program_go_as_slices(served, n_queries):
    table, rows, index = served
    q = rows[100:100 + n_queries] + 0.01
    singles = [index.topk(v, 4) for v in q]
    at = {c: getattr(index, "_obs_ann_" + c).value
          for c in ("frames", "queries", "union_rows", "probed_rows")}
    many = index.topk_many(q, 4)
    assert len(many) == n_queries
    for got, want in zip(many, singles):
        assert [i for i, _ in got] == [i for i, _ in want]
        assert [s for _, s in got] == pytest.approx([s for _, s in want], abs=1e-5)
    gain = {c: getattr(index, "_obs_ann_" + c).value - v for c, v in at.items()}
    slices = [q[lo:lo + 32] for lo in range(0, n_queries, 32)]
    assert gain["frames"] == len(slices) and gain["queries"] == n_queries
    cents = np.asarray(index._ann.centroids)
    counts = np.asarray(index._ann.list_rows)
    blocks = np.asarray(index._ann.list_blocks)
    probed = [reference_ivf.probe(cents, part, 4)[0] for part in slices]
    assert gain["union_rows"] == sum(counts[np.unique(p)].sum() for p in probed)
    assert gain["probed_rows"] == index._ann.rows_per_block * sum(
        blocks[p].sum() for p in probed)


def test_the_batcher_serves_frames_over_32_from_the_tier(served):
    """TPUMS_TOPK_BATCH_MAX above the program's frame: a full frame of the
    batcher is answered, not failed."""
    from flink_ms_tpu.serve.microbatch import TopKBatcher

    table, rows, index = served
    index.warm_batch_shapes(4, max_batch=64)     # 1 .. 64: no bucket throws
    batcher = TopKBatcher(index, max_batch=64, max_wait_us=200_000)
    q = rows[300:348] * 1.01
    want = [index.topk(v, 4) for v in q]
    try:
        pending = [batcher.submit(v, 4, allow_inline=False) for v in q]
        got = [p.wait(60) for p in pending]
    finally:
        batcher.close()
    assert batcher.max_batch_seen > 32
    assert [[i for i, _ in r] for r in got] == [[i for i, _ in r] for r in want]


def test_push_rescoring_reaches_a_group_over_32_on_the_tier(served, monkeypatch):
    """The push plane stacks every candidate subscription of one k into one
    `topk_many` and swallows what it raises: 40 of them are re-scored."""
    from flink_ms_tpu.serve.client import QueryClient
    from flink_ms_tpu.serve.server import LookupServer
    from flink_ms_tpu.serve.topk import ALSTopkHandler

    table, rows, index = served
    handler = ALSTopkHandler(table)
    srv = LookupServer({"ALS_MODEL": table}, host="127.0.0.1", port=0,
                       topk_handlers={"ALS_MODEL": handler}).start()
    clients = []
    try:
        q = rows[7] / np.linalg.norm(rows[7])
        payload = ";".join(f"{v:.6f}" for v in q)
        subs = []
        for _ in range(40):
            c = QueryClient("127.0.0.1", srv.port, proto="b2", push=True,
                            timeout_s=20)
            clients.append(c)
            subs.append(c.subscribe_topk("ALS_MODEL", payload, 1))
        assert handler.index._ann is not None
        held = subs[0]["snapshot"].split(":")[0]
        eng = srv._push_engine
        rescored = eng.rescored
        # the held item now scores far higher: every subscription is a candidate
        table.put(f"{held}-I", ";".join(f"{v:.6f}" for v in q * 80.0))
        for c, sub in zip(clients, subs):
            sid, seq, delta = c.next_push(timeout_s=20.0)
            assert (sid, seq) == (sub["sub_id"], 1)
            assert delta.startswith(f"+{held}:")
        assert eng.rescored - rescored == 40
    finally:
        for c in clients:
            c.close()
        srv.stop()
        handler.close()


def test_build_records_the_tiers_phases(served):
    table, rows, index = served
    tracing.clear_phases()
    index.topk(rows[0], 3)
    log = tracing.phase_log()
    ann = next(e for e in log if e["name"] == "topk.build.ann")
    assert ann["parent"] == "topk.build"
    children = [e["name"] for e in tracing.phase_children(ann, log)]
    assert children == ["topk.build.ann.train", "topk.build.ann.assign",
                        "topk.build.ann.lists", "topk.build.ann.recall"]
    names = [e["name"] for e in log]
    assert names.index("topk.build.ids") > names.index("topk.build.ann")


def test_warm_batch_shapes_runs_the_tiers_program_per_bucket(served):
    table, rows, index = served
    index.topk(rows[0], 3)
    frames = index._obs_ann_frames.value
    programs = ann_mod._search_jit()._cache_size()
    index.warm_batch_shapes(3, max_batch=16)
    assert index._obs_ann_frames.value - frames == 5     # 1, 2, 4, 8, 16
    # frames of up to 8 share a program: one more for the frame of 16
    assert ann_mod._search_jit()._cache_size() == programs + 1
    assert index._topk_many_fn is None and index._topk_fn is None


def test_failed_build_is_counted_and_said(served, monkeypatch, capsys):
    table, rows, index = served

    def broken(*a, **kw):
        raise RuntimeError("RESOURCE_EXHAUSTED: no room")

    monkeypatch.setattr(IVFIndex, "build", classmethod(broken))
    failures = index._obs_ann_build_failures.value
    got = index.topk(rows[5], 5)
    exact_ids, _ = reference.topk(rows, rows[5:6], 5)   # the exact tier answers
    assert [i for i, _ in got] == [f"it{i}" for i in exact_ids[0, :5]]
    assert index._ann is None and not index.prefers_frames
    assert index._obs_ann_build_failures.value == failures + 1
    assert len(index._ids) == len(rows) and None not in index._ids  # no pads
    err = capsys.readouterr().err
    assert "IVF build failed" in err and "no room" in err
    assert "TPUMS_TOPK_TIER=ivf is NOT being served" in err


def test_sharded_catalog_keeps_the_sharded_exact_tier(monkeypatch, capsys):
    monkeypatch.setenv("TPUMS_TOPK_TIER", "ivf")
    monkeypatch.setenv("TPUMS_TOPK_SHARDED", "1")
    rows = clustered(2000, 8, 21)
    index = DeviceFactorIndex(filled(rows), "-I")
    exact_ids, _ = reference.topk(rows, rows[5:6], 5)
    assert [i for i, _ in index.topk(rows[5], 5)] == [
        f"it{i}" for i in exact_ids[0, :5]]
    assert index._is_sharded and index._ann is None
    assert "lives on one device" in capsys.readouterr().err


def test_auto_says_once_that_a_sharded_catalog_gets_no_ivf_tier(monkeypatch, capsys):
    """Before PR 39 `auto` built the tier over the sharded matrix above
    TPUMS_ANN_MIN_ROWS; now it serves sharded exact, and says so once."""
    monkeypatch.setenv("TPUMS_TOPK_TIER", "auto")
    monkeypatch.setenv("TPUMS_TOPK_SHARDED", "1")
    monkeypatch.setenv("TPUMS_ANN_MIN_ROWS", "100")
    rows = clustered(2000, 8, 21)
    table = filled(rows)
    index = DeviceFactorIndex(table, "-I")
    index.topk(rows[5], 5)
    assert index._is_sharded and index._ann is None
    assert capsys.readouterr().err.count("lives on one device") == 1
    table.put("it-new-I", ";".join(f"{v:.6f}" for v in rows[0]))   # a rebuild
    index.topk(rows[5], 5)
    with index._lock:
        index._build_locked()
    assert "lives on one device" not in capsys.readouterr().err


def test_exact_tier_builds_nothing_of_the_tier(monkeypatch):
    monkeypatch.setenv("TPUMS_TOPK_TIER", "exact")
    monkeypatch.setenv("TPUMS_TOPK_SHARDED", "0")
    rows = clustered(500, 8, 2)
    index = DeviceFactorIndex(filled(rows), "-I")
    tracing.clear_phases()
    index.topk(rows[1], 3)
    assert index._ann is None and len(index._ids) == len(rows)
    assert not any(e["name"].startswith("topk.build.ann")
                   for e in tracing.phase_log())
    frames = index._obs_ann_frames.value
    index.topk_many(rows[:4], 3)
    assert index._obs_ann_frames.value == frames


# -- the plain reference ----------------------------------------------------------------


def test_reference_topk_is_brute_force_over_the_probed_lists():
    rng = np.random.default_rng(0)
    rows = rng.normal(size=(700, 6)).astype(np.float32)
    cents = rng.normal(size=(9, 6)).astype(np.float32)
    member = rng.integers(0, 9, 700)
    q = rng.normal(size=(5, 6)).astype(np.float32)
    ids, scores, margin = reference_ivf.topk(rows, member, cents, q, 3, 4, block=128)
    for b in range(5):
        cs = q[b].astype(np.float64) @ cents.astype(np.float64).T
        best = np.argsort(-cs)[:3]
        s = np.where(np.isin(member, best),
                     rows.astype(np.float64) @ q[b].astype(np.float64), -np.inf)
        order = np.argsort(-s)[:5]
        assert np.array_equal(ids[b], order)
        assert np.allclose(scores[b], s[order])
        assert margin[b] == pytest.approx(np.sort(cs)[-3] - np.sort(cs)[-4])


def test_reference_marks_what_short_lists_cannot_give():
    rows = np.eye(4, dtype=np.float32)
    ids, scores, margin = reference_ivf.topk(
        rows, np.array([0, 0, 1, 1]), np.array([[1, 0, 0, 0], [0, 0, 1, 0]], np.float32),
        np.array([[1, 0.5, 0, 0]], np.float32), 1, 3)
    assert ids.tolist() == [[0, 1, -1, -1]]
    assert np.isinf(scores[0, 2:]).all() and margin[0] == pytest.approx(1.0)


def test_reference_nearest_centroid_and_recall():
    cents = np.array([[0, 0], [10, 0], [0, 10]], np.float32)
    rows = np.array([[1, 1], [9, 1], [1, 8], [6, 0]], np.float32)
    assert reference_ivf.nearest_centroid(rows, cents, block=2).tolist() == [0, 1, 2, 1]
    assert reference_ivf.recall(np.array([[1, 2, 3], [4, 5, 6]]),
                                np.array([[3, 2, 9], [6, 7, 8]])) == 0.5


# -- the benchmark's side ---------------------------------------------------------------


def load(*parts):
    with open(os.path.join(REPO, *parts)) as f:
        return json.load(f)


def test_clustered_catalog_follows_its_law():
    cfg = {"rows": 20000, "rank": 16,
           "assumed": {"law": {"components": 8, "zipf_exponent": 1.0, "sigma_w": 0.5}}}
    ids, rows = synth_t2i_clustered.catalog(cfg, 5)
    again = synth_t2i_clustered.catalog(cfg, 5)[1]
    other = synth_t2i_clustered.catalog(cfg, 6)[1]
    assert ids[0] == "1" and ids[-1] == "20000" and rows.dtype == np.float32
    assert np.array_equal(rows, again) and not np.array_equal(rows, other)
    centres, comp = synth_t2i_clustered.component_of(cfg, 5)
    share = np.bincount(comp, minlength=8) / len(comp)
    want = 1.0 / np.arange(1, 9)
    assert np.allclose(share, want / want.sum(), atol=0.02)
    noise = rows - centres[comp]
    assert noise.std() == pytest.approx(0.5 / 4.0, rel=0.02)   # sigma_w / sqrt(rank)
    assert np.linalg.norm(centres, axis=1).mean() == pytest.approx(1.0, abs=0.15)


def test_configuration_states_what_the_sizing_rule_gives():
    cfg = load("benchmark", "configs", "bigann-t2i-10m-ivf.json")
    sibling = load("benchmark", "configs", "bigann-t2i-10m.json")
    for key in ("rows", "rank", "k", "dtype", "distance", "score_precision", "reduced"):
        assert cfg[key] == sibling[key]
    assert cfg["nlist"] == IVFIndex.default_nlist(cfg["rows"]) == 4096
    assert cfg["nprobe"] == IVFIndex.default_nprobe(cfg["nlist"]) == 256
    assert cfg["env"] == {"TPUMS_TOPK_TIER": "ivf"}     # no TPUMS_ANN_* knob
    assert cfg["assumed"]["law"]["components"] == cfg["nlist"] // 4
    assert 0.5 <= cfg["assumed"]["law"]["sigma_w"] <= 1.0
    assert cfg["limits"]["ivf_recall_at_10"] == 0.9
    assert cfg["limits"]["topk_score_abs_err"] == sibling["limits"]["topk_score_abs_err"]
    assert cfg["controls"] == sibling["controls"]
    traffic = load("benchmark", "traffic", "topk-paced-recall.json")
    paced = load("benchmark", "traffic", "topk-paced.json")
    assert traffic["driver"] == "topk_open_ivf"
    assert {k: v for k, v in traffic.items() if k not in ("driver", "what")} == \
        {k: v for k, v in paced.items() if k not in ("driver", "what")}


def test_benchmark_gains_one_configuration_and_one_cell():
    bench = load("BENCHMARK.json")
    # the seventh of each list; later PRs append after it (PR 44 did)
    assert [c["name"] for c in bench["configs"]][6] == "bigann-t2i-10m-ivf"
    assert len(bench["configs"]) >= 7 and len(bench["workloads"]) >= 7
    assert bench["workloads"][6] == {
        "name": CELL, "config": "bigann-t2i-10m-ivf",
        "traffic": "topk-paced-recall", "chips": 1,
        "why": bench["workloads"][6]["why"]}
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    # a line of prose in the file holds 1 to 200 characters
    for entry in (bench["configs"][6], bench["workloads"][6]):
        for key in ("why", "source"):
            assert 1 <= len(entry.get(key, "x")) <= 200, (entry["name"], key)
    mine = {m["name"]: m for m in bench["per_layer"] if CELL in m.get("workloads", [])}
    new = {"ivf_probe_ms", "ivf_scan_ms", "ivf_select_ms", "ivf_scan_roofline",
           "ivf_scanned_share", "ivf_recall_at_10", "index_ann_build_s",
           "index_ann_train_s", "index_ann_assign_s", "index_ann_lists_s",
           "index_ann_recall_s"}
    assert new <= set(mine)
    # appended in this order, one after the other (later PRs append after)
    names = [m["name"] for m in bench["per_layer"]]
    first = names.index("ivf_probe_ms")
    assert names[first:first + len(new)] == [
        "ivf_probe_ms", "ivf_scan_ms", "ivf_select_ms", "ivf_scan_roofline",
        "ivf_scanned_share", "ivf_recall_at_10", "index_ann_build_s",
        "index_ann_train_s", "index_ann_assign_s", "index_ann_lists_s",
        "index_ann_recall_s"]
    for name in new:
        assert mine[name]["workloads"] == [CELL]
        assert os.path.exists(os.path.join(REPO, "benchmark", "metrics", name + ".json"))
    # the full scan's model and scopes stay the full scan's
    assert not {"paced_frame_roofline", "paced_score_ms", "paced_select_ms"} & set(mine)
    assert {"paced_dispatch_ms", "paced_batch_size", "index_build_s", "compile_s",
            "setup_program_s", "loadgen_lag_p99_ms", "device_clock_lead_ms"} <= set(mine)
    topk = next(m for m in bench["end_to_end"] if m["name"] == "topk_p50_ms")
    assert topk["workloads"][-1] == CELL and topk["bound"] == 0.03


@pytest.mark.parametrize("union_rows,probed_rows,flops,nbytes", [
    (1000, 2048, 2 * 200 * 2048, 1000 * 800),
    (312500.0, 1.6e6, 2 * 200 * 1.6e6, 312500 * 800),
    (0, 0, 0, 0)])
def test_scan_roofline_counts_by_hand(union_rows, probed_rows, flops, nbytes):
    cfg = {"rank": 200, "nlist": 4096, "rows": 5_000_000}
    assert roofline_ivf.ivf_scan(cfg, union_rows, probed_rows) == (flops, nbytes)


class FakeRun:
    def __init__(self, before, after, counts=None, config=None):
        self.before, self.after = before, after
        self.counts, self.config = counts or {}, config or {}
        self.trace_path = None

    def counter(self, name, at_open=False):
        return (self.before if at_open else self.after).get(name)


def test_counter_readers_read_gains_and_leave_out_what_is_not_there():
    run = FakeRun({"rows": 100, "queries": 10, "frames": 1},
                  {"rows": 100 + 6 * 625, "queries": 16, "frames": 3},
                  counts={"frames": 2, "recall_at_10": 0.93},
                  config={"rows": 10000})
    value, extra = counter_share.read(run, "rows", per=["queries"], of=["rows"],
                                      scale=100.0)
    assert value == pytest.approx(6.25) and extra == {"rows": 3750, "queries": 6}
    assert counter_share.read(run, "absent", per=["queries"]) is None
    assert counter_share.read(run, "rows", per=["absent"]) is None
    still = FakeRun({"rows": 5, "queries": 3}, {"rows": 5, "queries": 3})
    assert counter_share.read(still, "rows", per=["queries"]) is None
    assert run_count.read(run, "recall_at_10") == 0.93
    assert run_count.read(run, "absent") is None
    gains = trace_scope_roofline_counted.gains
    assert gains(run, {"union_rows": "rows"}, "frames") == {"union_rows": 1875.0}
    assert gains(run, {"union_rows": "absent"}, "frames") is None
    assert gains(still, {"union_rows": "rows"}, "frames") is None
    # no trace: the roofline reader returns nothing and does not raise
    assert trace_scope_roofline_counted.read(
        run, "topk.ivf.scan", ["topk.ivf.scan"], "roofline_ivf", "ivf_scan",
        "frames", {"union_rows": "rows", "probed_rows": "rows"}) is None


def test_every_new_benchmark_file_is_listed_for_the_reviewer():
    with open(os.path.join(REPO, "benchmark", "README-ivf.md")) as f:
        listed = f.read()
    for name in ("synth_t2i_clustered.py", "reference_ivf.py", "roofline_ivf.py",
                 "topk_open_ivf.py", "topk-paced-recall.json",
                 "trace_scope_roofline_counted", "counter_share", "run_count",
                 "bigann-t2i-10m-ivf.json", "tiny-ivf"):
        assert name in listed, name


def rehearse(trace, *more):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--bench", TINY, "--workload",
         TINY_CELL, "--seed", "3000000019", "--seconds", "1", "--trace",
         str(trace), *more],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600)
    shutil.rmtree(os.path.join(REPO, ".benchwork", TINY_CELL), ignore_errors=True)
    assert done.returncode == 0, done.stderr[-2000:]
    return json.loads(done.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_prints_the_contract_line(trace):
    line = rehearse(trace)
    assert line["correct"] is True, [c for c in line["checks"] if not c["ok"]]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"  # a rehearsal says so
    checked = {c["name"] for c in line["checks"]}
    assert {"ivf_frames_not_from_the_tier", "ivf_queries_not_from_the_tier",
            "ivf_build_failures", "ivf_rows_not_in_one_list", "ivf_misassigned_share",
            "topk_score_abs_err", "topk_wrong_ids_at_clear_ranks",
            "topk_checked_queries", "ivf_recall_at_10"} <= checked
    if trace:
        got = set(line["metrics"])
        # no device plane on the CPU: the scope readers and the roofline
        # leave their metrics out and do not raise
        assert not {"ivf_probe_ms", "ivf_scan_ms", "ivf_select_ms",
                    "ivf_scan_roofline"} & got
        # (not the `paced_*` host metrics: they are means over batched
        # frames, and on a host that scores a frame inside the mix's 50 ms
        # gap every request is an inline single and there is none)
        assert {"ivf_scanned_share", "ivf_recall_at_10", "index_ann_build_s",
                "index_ann_train_s", "index_ann_assign_s", "index_ann_lists_s",
                "index_ann_recall_s", "index_build_s"} <= got
        # 8 of 32 lists probed, whole blocks of 128 rows scored
        assert 25.0 <= line["metrics"]["ivf_scanned_share"]["value"] <= 100.0
        assert 0.5 <= line["metrics"]["ivf_recall_at_10"]["value"] <= 1.0
    else:
        assert set(line["metrics"]) == {"topk_p50_ms", "setup_s"}


def test_rehearsal_on_the_exact_tier_is_not_correct():
    """Check (e): a run whose frames the exact tier answered does not pass,
    whatever its ids and scores."""
    line = rehearse(0, "--control", "exact_tier")
    assert line["correct"] is False
    failed = {c["name"] for c in line["checks"] if not c["ok"]}
    assert {"ivf_queries_not_from_the_tier", "ivf_tier_built"} <= failed
