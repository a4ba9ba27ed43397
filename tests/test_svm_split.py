"""The column split of the Gram engine's sparse layout (`ops/svm.py`, PR 41):
the rule that sizes the dense head from what a fit can observe, the host
pieces that cut every entry into exactly one of head and tail, the compiled
round against the same fit with no head (the same program without the head's
block, ids and two products) in both combines, the shapes' and the
program's independence of which features a seed drew, the round's scopes, the
gauges and the trainer's line."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src import core as jax_core

from flink_ms_tpu.core.formats import SparseData
from flink_ms_tpu.obs import metrics as obs_metrics
from flink_ms_tpu.ops import svm
from flink_ms_tpu.ops.svm import SVMConfig, compile_svm_fit, prepare_svm_blocked
from flink_ms_tpu.parallel.mesh import make_mesh

V5E_BYTES = 16909336064
LAM = 1e-3
HEADS = (128, 256)


def lengths(n=1500, lo=2, hi=40, seed=31):
    """One fixed sequence of row lengths, log-normal, as the cell's."""
    raw = np.random.default_rng(seed).lognormal(2.3, 0.7, n)
    return np.clip(raw.astype(np.int64), lo, hi)


def heavy_tailed(seed, lens=None, d=600, q=5.0):
    """Sparse rows whose features follow a Zipf-Mandelbrot law, which row has
    which length and which feature id holds which rank permuted by the seed:
    the benchmark's law for `rcv1-cocoa` at a CPU size."""
    rng = np.random.default_rng(seed)
    lens = lengths() if lens is None else lens
    lens = lens[rng.permutation(len(lens))]
    p = 1.0 / (np.arange(d) + q)
    feature_of = rng.permutation(d)
    ids = np.concatenate([
        feature_of[np.sort(rng.choice(d, n, replace=False, p=p / p.sum()))]
        for n in lens])
    indptr = np.concatenate([[0], np.cumsum(lens)])
    vals = 0.1 + rng.random(indptr[-1])
    vals /= np.sqrt(np.repeat(np.add.reduceat(vals ** 2, indptr[:-1]), lens))
    score = np.add.reduceat(rng.standard_normal(d)[ids] * vals, indptr[:-1])
    return SparseData(labels=np.where(score > 0, 1.0, -1.0), indptr=indptr,
                      indices=ids.astype(np.int32), values=vals, n_features=d)


def dense_matrix(data):
    X = np.zeros((data.n_examples, data.n_features))
    rows = np.repeat(np.arange(data.n_examples), np.diff(data.indptr))
    X[rows, data.indices] = data.values
    return X


def config(problem, mode="avg", rounds=3):
    return SVMConfig(iterations=rounds,
                     local_iterations=problem.rows_per_block,
                     regularization=LAM, mode=mode, inner="gram")


def fitted(problem, cfg, mesh, head, rounds=3):
    fit, args = compile_svm_fit(problem, cfg, mesh, head_columns=head)
    w, alpha = fit(rounds, *args)
    return np.asarray(w), np.asarray(alpha)


# -- the rule -------------------------------------------------------------------

# 679,936 slots of f32: a column costs 2.72 MB a pass, what 530 gathered
# entries cost, and a quarter of the chip holds 1,554 of them
SLOTS = 679936
HOT = np.full(4096, 5000)


@pytest.mark.parametrize("counts, memory, want", [
    (HOT, None, 0),                      # a runtime that reports no memory
    (HOT, 0, 0),
    (HOT, V5E_BYTES, 1536),              # stops at the budget, whole tiles
    (HOT[:1280], V5E_BYTES, 1280),       # all columns of an all-hot matrix
    (HOT[:1300], V5E_BYTES, 1280),       # whole lane tiles of 128
    (np.r_[HOT[:700], np.full(3000, 529)], V5E_BYTES, 640),  # the break-even
    (np.r_[HOT[:700], np.full(3000, 531)], V5E_BYTES, 1536),
    (np.full(4096, 100), V5E_BYTES, 0),  # nothing hot enough to stream
    (HOT, V5E_BYTES // 8, 128),          # a small device
])
def test_the_head_rule_is_a_pure_function_of_counts_itemsize_and_memory(
        counts, memory, want):
    assert svm.head_width(counts, SLOTS, 4, memory) == want


def test_the_head_rule_counts_a_narrower_value_as_cheaper_to_stream():
    counts = np.full(8192, 300)  # under f32's break-even of 530 rows
    assert svm.head_width(counts, SLOTS, 4, V5E_BYTES) == 0
    assert svm.head_width(counts, SLOTS, 2, V5E_BYTES) == 3072


def test_a_cpu_fit_takes_no_head_and_a_device_with_memory_takes_one(monkeypatch):
    problem = prepare_svm_blocked(heavy_tailed(1), 8, seed=0)
    mesh = make_mesh(1)
    compile_svm_fit(problem, config(problem), mesh)
    assert gauges()["tpums_svm_head_columns"] == 0
    monkeypatch.setattr(svm, "device_memory", lambda device: 1 << 30)
    compile_svm_fit(problem, config(problem), mesh)
    # 1504 slots of f32: every column a row holds at all is cheaper streamed
    want = int((problem.col_count > 0).sum()) // 128 * 128
    assert gauges()["tpums_svm_head_columns"] == want > 0


def test_bf16_state_on_a_device_with_memory_takes_no_head_and_runs_tiles(
        monkeypatch):
    monkeypatch.setattr(svm, "device_memory", lambda device: 1 << 30)
    problem = prepare_svm_blocked(heavy_tailed(1), 8, seed=0)
    cfg = SVMConfig(local_iterations=problem.rows_per_block,
                    regularization=LAM, inner="gram", dtype=jnp.bfloat16)
    fit, args = compile_svm_fit(problem, cfg, make_mesh(1))
    assert gauges()["tpums_svm_head_columns"] == 0 and len(args) == 11
    # whole rows in tiles: 1,504 slots are two blocks of tile rows, as deep
    # as their longest rows (40 and 8 entries: 5 steps and 1)
    assert args[2].dtype == jnp.bfloat16 and args[2].shape == (
        1, 6, svm._TILE_STEP, svm._TILE_ROWS)
    assert int(np.asarray(args[10])[0, 0]) == 6
    assert np.count_nonzero(np.asarray(args[2], np.float32)) == len(
        heavy_tailed(1).values)
    assert "in tiles" in svm.layout_report()
    w, _ = fit(2, *args)
    assert w.dtype == jnp.bfloat16 and np.abs(np.asarray(w, np.float32)).max() > 0


def test_a_head_wider_than_the_matrix_is_refused():
    problem = prepare_svm_blocked(heavy_tailed(1), 8, seed=0)
    with pytest.raises(ValueError, match="head_columns"):
        compile_svm_fit(problem, config(problem), make_mesh(1),
                        head_columns=problem.n_features + 1)


# -- every entry in exactly one of head and tail ----------------------------------

def test_a_rows_entries_descend_by_how_many_rows_hold_their_column():
    data = heavy_tailed(2)
    problem = prepare_svm_blocked(data, 8, seed=0)
    counts = np.bincount(data.indices, minlength=data.n_features)
    assert (problem.col_count == counts).all()
    assert (np.diff(counts[np.argsort(problem.col_rank)]) <= 0).all()
    idx = problem.idx.reshape(-1, problem.idx.shape[-1])
    ranks = np.where(
        np.arange(idx.shape[1]) < problem.row_len.reshape(-1, 1),
        problem.col_rank[idx], -1)
    assert (np.diff(ranks, axis=1) <= 0).all()


@pytest.fixture(params=["one piece", "many pieces"])
def pieces(request, monkeypatch):
    """The head's entries as one piece into one window (small data), or cut
    as large data is: by entries and by the rows a window spans."""
    if request.param == "many pieces":
        monkeypatch.setattr(svm, "_HEAD_CHUNK", 1024)
        monkeypatch.setattr(svm, "_HEAD_WINDOW", 96)
    return request.param


@pytest.mark.parametrize("head", HEADS + (0, 600))
@pytest.mark.parametrize("devices", [1, 4])
def test_head_and_tail_together_are_the_input_matrix(head, devices, pieces):
    data = heavy_tailed(3)
    problem = prepare_svm_blocked(data, 8, seed=5)
    slots = problem.n_blocks * problem.rows_per_block
    per_device = slots // devices
    row_len = problem.row_len.reshape(-1)
    tail_len = svm._tail_lengths(
        problem.idx.reshape(slots, -1), row_len, problem.col_rank, head)
    brute = (np.where(
        np.arange(problem.idx.shape[-1]) < row_len[:, None],
        problem.col_rank[problem.idx.reshape(slots, -1)], -1) >= head).sum(1)
    assert (tail_len == brute).all()

    ids, val, slot, row0, n_tiles = svm._tail_tiles(
        problem.idx, problem.val, row_len, tail_len, devices)
    start, rows, cols, vals = svm._head_entries(
        problem.idx, problem.val, row_len, tail_len, problem.col_rank, devices,
        min(svm._HEAD_WINDOW, per_device))
    assert (rows.shape[1] > 1) == (pieces == "many pieces" and head > 0)
    assert rows.max() < min(svm._HEAD_WINDOW, per_device)
    rows = rows + start  # a piece's rows count from its window's first
    feature_of = np.argsort(problem.col_rank)
    X = np.zeros((slots, data.n_features))
    for dev in range(devices):
        first = dev * per_device
        used = slice(0, n_tiles[dev, 0])
        # nothing is stored past the tiles the round will loop over
        assert not val[dev, n_tiles[dev, 0]:].any()
        tile_slot = slot[dev][
            row0[dev, used, None] + np.arange(svm._TILE_ROWS)]
        where = np.broadcast_to(tile_slot[:, None, :], val[dev, used].shape)
        np.add.at(X, (first + where, ids[dev, used]), val[dev, used])
        np.add.at(X, (first + rows[dev].reshape(-1),
                      feature_of[cols[dev].reshape(-1)]),
                  vals[dev].reshape(-1))
    assert np.count_nonzero(val) + np.count_nonzero(vals) == len(data.values)
    assert np.count_nonzero(val) == tail_len.sum()
    want = np.zeros_like(X)
    want[:data.n_examples] = dense_matrix(data)[
        np.random.default_rng(5).permutation(data.n_examples)]
    np.testing.assert_allclose(X, want.astype(np.float32), rtol=0, atol=0)


def test_rows_and_blocks_without_a_tail_entry_cost_no_tile(monkeypatch):
    """Rows whose every entry is in the head fall behind the last tile, and
    a layout whose every entry is in the head loops over no tile at all:
    its margins are the head's product alone."""
    monkeypatch.setattr(svm, "_TILE_ROWS", 128)
    data = heavy_tailed(4)
    problem = prepare_svm_blocked(data, 8, seed=0)
    slots = problem.n_blocks * problem.rows_per_block
    row_len = problem.row_len.reshape(-1)
    tail_len = svm._tail_lengths(
        problem.idx.reshape(slots, -1), row_len, problem.col_rank, 256)
    assert 0 < (tail_len == 0).sum() < slots
    *_, row0, n_tiles = svm._tail_tiles(
        problem.idx, problem.val, row_len, tail_len, 1)
    steps = -(-np.sort(tail_len)[::-1][::128] // svm._TILE_STEP)
    assert n_tiles[0, 0] == steps.sum() and steps[-1] == 0
    assert row0[0, :n_tiles[0, 0]].max() == 128 * (np.flatnonzero(steps)[-1])

    cfg = config(problem)
    mesh = make_mesh(1)
    fit, args = compile_svm_fit(problem, cfg, mesh,
                                head_columns=data.n_features)
    assert int(np.asarray(args[10]).sum()) == 0 and not np.asarray(args[2]).any()
    assert gauges()["tpums_svm_row_width"] == 0
    w, alpha = (np.asarray(x) for x in fit(3, *args))
    w0, alpha0 = fitted(problem, cfg, mesh, 0)
    np.testing.assert_allclose(w, w0, rtol=2e-4, atol=1e-6)
    np.testing.assert_allclose(alpha, alpha0, rtol=2e-4, atol=1e-6)


# -- the compiled round ------------------------------------------------------------

@pytest.mark.parametrize("mode", ["avg", "add"])
@pytest.mark.parametrize("head", HEADS)
@pytest.mark.parametrize("devices", [1, 4])
def test_a_fit_with_a_head_agrees_with_the_fit_without(devices, head, mode):
    """Head or no head, the round runs the same update sequence; only the
    order of a row's sum changes: the cross-engine tolerance."""
    data = heavy_tailed(6)
    problem = prepare_svm_blocked(data, 16, seed=0)
    mesh = make_mesh(devices)
    cfg = config(problem, mode)
    w0, alpha0 = fitted(problem, cfg, mesh, 0)
    w, alpha = fitted(problem, cfg, mesh, head)
    assert np.abs(w0).max() > 0
    np.testing.assert_allclose(w, w0, rtol=2e-4, atol=1e-6)
    np.testing.assert_allclose(alpha, alpha0, rtol=2e-4, atol=1e-6)


@pytest.mark.parametrize("devices", [1, 4])
def test_the_head_block_is_the_same_whatever_its_pieces(devices, monkeypatch):
    problem = prepare_svm_blocked(heavy_tailed(6), 16, seed=0)
    mesh = make_mesh(devices)
    _, args = compile_svm_fit(problem, config(problem), mesh, head_columns=256)
    whole = np.asarray(args[11])
    monkeypatch.setattr(svm, "_HEAD_CHUNK", 1024)
    monkeypatch.setattr(svm, "_HEAD_WINDOW", 96)
    _, args = compile_svm_fit(problem, config(problem), mesh, head_columns=256)
    np.testing.assert_array_equal(np.asarray(args[11]), whole)
    assert np.count_nonzero(whole) == gauges()["tpums_svm_head_nonzeros"]


@pytest.mark.parametrize("head", HEADS + (0,))
def test_chained_segments_of_a_split_fit_equal_one_long_fit(head):
    problem = prepare_svm_blocked(heavy_tailed(7), 16, seed=0)
    cfg = config(problem, "add")
    fit, args = compile_svm_fit(problem, cfg, make_mesh(4), head_columns=head)
    w_one, a_one = fit(3, *args)
    w, alpha = args[0], args[5]
    for start in range(3):
        w, alpha = fit(1, w, *args[1:5], alpha, *args[6:], start=start)
    np.testing.assert_array_equal(np.asarray(w), np.asarray(w_one))
    np.testing.assert_array_equal(np.asarray(alpha), np.asarray(a_one))


@pytest.mark.parametrize("head", [128, 0])
def test_two_seeds_of_one_length_sequence_share_shapes_and_the_program(head):
    """Which features a seed drew moves the tail lengths, the tiles in use
    and the head's entries (with no head: the stored ids alone), and none of
    the operands' shapes: one compiled program, one cache entry."""
    mesh = make_mesh(1)
    svm._FIT_CACHE.clear()
    shapes, in_head, ids, programs = [], [], [], []
    for seed in (11, 12):
        problem = prepare_svm_blocked(heavy_tailed(seed), 8, seed=0)
        cfg = config(problem)
        fit, args = compile_svm_fit(problem, cfg, mesh, head_columns=head)
        shapes.append([(a.shape, a.dtype) for a in jax.tree.leaves(args)])
        in_head.append(gauges()["tpums_svm_head_nonzeros"])
        ids.append(np.asarray(args[1]))
        programs.append(jax.jit(lambda *a: fit(1, *a)).lower(*args).as_text())
    # the seeds drew other features
    assert (in_head[0] != in_head[1]) == (head > 0)
    assert not np.array_equal(ids[0], ids[1])
    assert shapes[0] == shapes[1] and programs[0] == programs[1]
    assert len(svm._FIT_CACHE) == 1


# -- scopes ------------------------------------------------------------------------

def ops_by_scope(fit, args):
    """(name stack, primitive) of every equation of one round, the bodies of
    its loops and calls included."""
    found = []

    def walk(jaxpr, prefix):
        for eqn in jaxpr.eqns:
            stack = f"{prefix}/{eqn.source_info.name_stack}"
            found.append((stack, eqn.primitive.name))
            for sub in jax_core.jaxprs_in_params(eqn.params):
                walk(sub, stack)

    walk(jax.make_jaxpr(lambda *a: fit(1, *a))(*args).jaxpr, "")
    return found


def under(ops, scope):
    return [name for stack, name in ops if scope in stack]


@pytest.mark.parametrize("head", [128, 0])
def test_the_split_round_scopes_both_the_product_and_the_tails_loop(head):
    """Each pass is one loop over the tiles, whatever their number (no
    fusion a length bucket), and with a head one product beside it; without
    a head the same round holds no product at all."""
    problem = prepare_svm_blocked(heavy_tailed(8), 8, seed=0)
    fit, args = compile_svm_fit(problem, config(problem), make_mesh(1),
                                head_columns=head)
    ops = ops_by_scope(fit, args)
    for scope, touch in (("svm.margins", "gather"), ("svm.dw", "scatter-add")):
        mine = under(ops, scope)
        assert mine.count("while") == 1
        assert mine.count("dot_general") == (1 if head else 0)
        # the tail's, and with a head the F ids' of w
        assert mine.count(touch) == (2 if head else 1)
    # nothing of either pass escapes its scope
    outside = [name for stack, name in ops
               if not any(s in stack for s in ("svm.margins", "svm.dw",
                                               "svm.steps", "svm.combine"))]
    assert not {"dot_general", "gather", "scatter-add"} & set(outside)


def test_the_other_layouts_rounds_have_no_loop_over_tiles():
    """The dense layout's two passes are products, no gather and no while:
    it lowers as it did before the split."""
    rng = np.random.default_rng(3)
    X = rng.standard_normal((61, 24))
    data = SparseData(
        labels=np.where(rng.random(61) < 0.5, 1.0, -1.0),
        indptr=np.arange(62) * 24, indices=np.tile(np.arange(24), 61),
        values=X.reshape(-1), n_features=24)
    problem = prepare_svm_blocked(data, 4, seed=0)
    assert problem.dense
    fit, args = compile_svm_fit(problem, config(problem), make_mesh(1))
    ops = ops_by_scope(fit, args)
    for scope in ("svm.margins", "svm.dw"):
        mine = under(ops, scope)
        assert "while" not in mine and "dot_general" in mine
        assert not {"gather", "scatter-add"} & set(mine)


# -- gauges and the trainer's line ---------------------------------------------------

def gauges():
    return {g["name"]: g["value"]
            for g in obs_metrics.get_registry().snapshot()["gauges"]
            if g["name"].startswith("tpums_svm_") and not g["labels"]}


@pytest.mark.parametrize("head", [128, 0, None])
@pytest.mark.parametrize("devices", [1, 4])
def test_gauges_read_what_the_split_layout_streams(devices, head):
    """With a head; with `head_columns=0`; and as a CPU fit decides for
    itself (no memory reported: no head)."""
    data = heavy_tailed(9)
    problem = prepare_svm_blocked(data, 8, seed=0)
    mesh = make_mesh(devices)
    _, args = compile_svm_fit(problem, config(problem), mesh, head_columns=head)
    head = head or 0
    got = gauges()
    slots = problem.n_blocks * problem.rows_per_block
    in_head = int((problem.col_rank[data.indices] < head).sum())
    stored = int(np.asarray(args[10]).sum()) * svm._TILE_STEP * svm._TILE_ROWS
    assert got["tpums_svm_head_columns"] == head
    assert got["tpums_svm_nonzeros"] == len(data.values)
    assert got["tpums_svm_head_nonzeros"] == in_head
    assert got["tpums_svm_dense_entries"] == slots * head
    assert got["tpums_svm_rows"] == slots
    assert got["tpums_svm_rows"] * got["tpums_svm_row_width"] == pytest.approx(stored)
    assert got["tpums_svm_pad_entries"] == stored - (len(data.values) - in_head)
    # the tiles hold what the head does not
    assert np.count_nonzero(np.asarray(args[2])) == len(data.values) - in_head
    report = svm.layout_report()
    if head:
        assert np.asarray(args[11]).shape == (slots, 128)
        assert np.asarray(args[12]).shape == (128,)
        assert "split by column (128 head columns" in report
        assert f"{100 * in_head / len(data.values):.1f}% of the non-zeros" in report
    else:
        # no block and no ids among the operands, and the line names none
        assert len(args) == 11
        assert f"sparse rows ({slots} x " in report and "in tiles)" in report


@pytest.mark.parametrize("inner, how", [
    ("gram", "in tiles)"), ("scatter", "one padded rectangle)")])
def test_the_trainers_line_names_how_sparse_rows_without_a_head_are_stored(
        inner, how):
    problem = prepare_svm_blocked(heavy_tailed(9), 8, seed=0)
    cfg = SVMConfig(local_iterations=problem.rows_per_block,
                    regularization=LAM, inner=inner)
    compile_svm_fit(problem, cfg, make_mesh(1))
    assert how in svm.layout_report() and "split" not in svm.layout_report()


def test_svm_train_names_the_split(tmp_path, capsys, monkeypatch):
    from flink_ms_tpu.train import svm_train

    data = heavy_tailed(10, lens=lengths(n=300))
    path = tmp_path / "rows.libsvm"
    with open(path, "w") as f:
        for i in range(data.n_examples):
            ids, vals = data.row(i)
            f.write("%+d %s\n" % (data.labels[i], " ".join(
                f"{j + 1}:{v:.9g}" for j, v in sorted(zip(ids, vals)))))
    monkeypatch.setattr(svm, "device_memory", lambda device: 1 << 30)
    svm_train.main(["--training", str(path), "--blocks", "4", "--iteration",
                    "2", "--output", str(tmp_path / "w")])
    out = capsys.readouterr().out
    assert "sparse rows split by column (" in out and "tail 300 x" in out
