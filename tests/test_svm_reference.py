"""The CoCoA program against the benchmark's plain reference
(`benchmark/reference_cocoa.py`: numpy, float64, CSR, nothing of the padded
arrays, the tiles or the Gram matrix), on rows of unequal length; the Gram
engine's tiles of whole rows (no head: every CPU fit) against the CSR and the
padded rectangles they are cut from; the synthetic documents of
`benchmark/synth_cocoa.py`; and what PR 31 added to `ops/svm.py` for
whoever profiles it: named scopes, gauges, a counter."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference_cocoa as ref
from benchmark import synth_cocoa
from benchmark.drivers.cocoa_rounds import by_example, slots_of, step_draws
from flink_ms_tpu.core.formats import SparseData
from flink_ms_tpu.obs import metrics as obs_metrics
from flink_ms_tpu.ops import svm
from flink_ms_tpu.ops.svm import SVMConfig, compile_svm_fit, prepare_svm_blocked
from flink_ms_tpu.parallel.mesh import make_mesh

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED = 11
LAM = 1e-3
# float32 state against float64: sums of at most a few hundred products a
# row and three rounds of steps; bfloat16 (8 bits of mantissa) misses by 100x
TOL = 2e-5
ROUND_SCOPES = ("svm.margins", "svm.steps", "svm.dw", "svm.combine")


def uneven_documents(n=61, d=300, long_row=120, seed=3):
    """Unit-norm rows of 1..9 distinct features, one of `long_row`, one
    empty: what `prepare_svm_blocked` pads to the longest."""
    rng = np.random.default_rng(seed)
    lens = rng.integers(1, 10, n)
    lens[7], lens[20] = long_row, 0
    indptr = np.concatenate([[0], np.cumsum(lens)]).astype(np.int64)
    indices = np.concatenate([rng.choice(d, l, replace=False) for l in lens])
    values = 0.1 + rng.random(len(indices))
    norms = np.sqrt(np.add.reduceat(values ** 2, indptr[:-1][lens > 0]))
    values /= np.repeat(norms, lens[lens > 0])
    labels = np.where(rng.random(n) < 0.47, 1.0, -1.0)
    return SparseData(labels=labels, indptr=indptr, indices=indices,
                      values=values, n_features=d)


def run_program(data, chains, inner, mode, rounds, dtype=jnp.float32,
                devices=None):
    problem = prepare_svm_blocked(data, chains, seed=SEED)
    cfg = SVMConfig(local_iterations=problem.rows_per_block,
                    regularization=LAM, seed=SEED, mode=mode, inner=inner,
                    dtype=dtype)
    fit, args = compile_svm_fit(
        problem, cfg, make_mesh(devices or min(chains, 4)))
    w, alpha = fit(rounds, *args)
    return (problem, np.asarray(w).astype(np.float64),
            np.asarray(alpha).astype(np.float64))


def run_reference(data, chains, mode, rounds):
    n = data.n_examples
    rows = -(-n // chains)
    slots = slots_of(SEED, n, chains, rows)
    w, alpha = np.zeros(data.n_features), np.zeros(n)
    for r in range(rounds):
        w, alpha = ref.cocoa_round(
            data.indptr, data.indices, data.values, data.labels, slots,
            step_draws(SEED, chains, r, rows, rows), w, alpha, LAM, mode=mode)
    return slots, w, alpha


@pytest.mark.parametrize("rounds", [1, 3])
@pytest.mark.parametrize("mode", ["avg", "add"])
@pytest.mark.parametrize("inner", ["gram", "scatter"])
@pytest.mark.parametrize("chains", [1, 4, 16])
def test_program_agrees_with_the_plain_reference(chains, inner, mode, rounds):
    data = uneven_documents()
    problem, w, alpha = run_program(data, chains, inner, mode, rounds)
    assert problem.idx.shape[-1] == 120  # every row padded to the longest
    slots, w_ref, a_ref = run_reference(data, chains, mode, rounds)
    n = data.n_examples
    a = by_example(alpha, slots, n)
    assert ref.rel_err(w, w_ref) < TOL
    assert ref.rel_err(a, a_ref) < TOL
    # the primal-dual relation both combinations keep, and the box
    primal = ref.primal_of(data.indptr, data.indices, data.values, a, LAM,
                           data.n_features)
    assert ref.rel_err(w, primal) < TOL
    ya = data.labels * a
    assert ya.min() >= -1e-7 and ya.max() <= 1 + 1e-6
    assert a[20] == 0.0 and np.abs(a).max() > 0  # the empty row never moves


@pytest.mark.parametrize("inner", ["gram", "scatter"])
def test_bfloat16_state_misses_the_same_tolerance(inner):
    data = uneven_documents()
    _, w, alpha = run_program(data, 4, inner, "avg", 1, dtype=jnp.bfloat16)
    slots, w_ref, a_ref = run_reference(data, 4, "avg", 1)
    assert ref.rel_err(w, w_ref) > 20 * TOL
    assert ref.rel_err(by_example(alpha, slots, data.n_examples), a_ref) > 20 * TOL


def test_the_scatter_add_agrees_and_carries_its_scope():
    data = uneven_documents()
    problem, w, _ = run_program(data, 4, "gram", "avg", 2)
    _, w_ref, _ = run_reference(data, 4, "avg", 2)
    assert ref.rel_err(w, w_ref) < TOL
    assert "svm.dw" in lowered_round(problem, "gram")


def lowered_round(problem, inner):
    cfg = SVMConfig(local_iterations=problem.rows_per_block,
                    regularization=LAM, seed=SEED, inner=inner)
    fit, args = compile_svm_fit(problem, cfg, make_mesh(1))
    return jax.jit(lambda *a: fit(1, *a)).lower(*args).as_text(debug_info=True)


@pytest.fixture(scope="module")
def lowered():
    problem = prepare_svm_blocked(uneven_documents(), 4, seed=SEED)
    return {inner: lowered_round(problem, inner) for inner in ("gram", "scatter")}


@pytest.mark.parametrize("inner, scope", [
    *(("gram", s) for s in ROUND_SCOPES),
    *(("scatter", s) for s in ROUND_SCOPES[1:])])
def test_the_round_carries_its_named_scopes(lowered, inner, scope):
    assert scope in lowered[inner]


def test_the_scatter_engine_has_no_margins_scope(lowered):
    # its margins are computed inside each step
    assert "svm.margins" not in lowered["scatter"]


def test_the_gram_build_carries_its_scope():
    problem = prepare_svm_blocked(uneven_documents(), 4, seed=SEED)
    cfg = SVMConfig(local_iterations=problem.rows_per_block, inner="gram")
    _, gram_fn, _ = svm._cached_fit(problem, cfg, make_mesh(1))
    text = gram_fn.lower(jnp.asarray(problem.idx), jnp.asarray(problem.val)
                         ).as_text(debug_info=True)
    assert "svm.gram" in text


def gauges():
    return {g["name"]: g["value"]
            for g in obs_metrics.get_registry().snapshot()["gauges"]
            if g["name"].startswith("tpums_svm_") and not g["labels"]}


@pytest.mark.parametrize("inner, chains, devices", [
    ("gram", 6, 4), ("scatter", 6, 4), ("gram", 3, 1)])
def test_gauges_and_round_counter_read_what_the_layout_implies(inner, chains, devices):
    data = uneven_documents()
    problem = prepare_svm_blocked(data, chains, seed=SEED)
    cfg = SVMConfig(local_iterations=5, regularization=LAM, inner=inner)
    fit, args = compile_svm_fit(problem, cfg, make_mesh(devices))
    padded_chains = -(-chains // devices) * devices  # empty chains fill the mesh
    rows = problem.rows_per_block
    got = gauges()
    assert got["tpums_svm_rows"] == padded_chains * rows
    if inner == "gram":
        # what the round streams: the tiles that hold an entry, the pads
        # inside them included; row_width is their mean over the row slots
        assert args[1].shape == args[2].shape == (
            devices, args[9].shape[1], svm._TILE_STEP, svm._TILE_ROWS)
        in_use = np.asarray(args[10])
        # the device with the row of 120 needs 15 steps of 8, the others 2
        # (rows of 9 entries at most), the one of two empty chains none
        assert in_use.shape == (devices, 1) and sorted(in_use[:, 0]) == (
            [0, 2, 2, 15] if devices == 4 else [15])
        assert not np.asarray(args[2])[0, in_use[0, 0]:].any()
        stored = int(in_use.sum()) * svm._TILE_STEP * svm._TILE_ROWS
        assert len(args) == 11 and got["tpums_svm_head_columns"] == 0
    else:
        stored = padded_chains * rows * 120  # every row padded to the longest
        assert args[1].size == args[2].size == stored
        assert got["tpums_svm_row_width"] == 120
    assert got["tpums_svm_rows"] * got["tpums_svm_row_width"] == pytest.approx(
        stored, rel=1e-12)
    assert got["tpums_svm_pad_entries"] == stored - len(data.indices)
    assert got["tpums_svm_gram_bytes"] == (
        padded_chains * rows * rows * 4 if inner == "gram" else 0)
    assert got["tpums_svm_chains_per_device"] == padded_chains // devices
    counter = obs_metrics.get_registry().counter("tpums_svm_rounds_total")
    before = counter.value
    state = fit(2, *args)
    fit(jnp.asarray(3, jnp.int32), state[0], *args[1:5], state[1], *args[6:],
        start=2)
    assert counter.value - before == 5


# -- the Gram engine's tiles of whole rows (no head) ---------------------------

def tiled(data, chains, devices):
    """(problem, ids, val, slot, row0, n_tiles) as `compile_svm_fit` places
    them for a fit without a head, back on the host."""
    problem = prepare_svm_blocked(data, chains, seed=SEED)
    cfg = SVMConfig(local_iterations=problem.rows_per_block,
                    regularization=LAM, seed=SEED, inner="gram")
    _, args = compile_svm_fit(problem, cfg, make_mesh(devices))
    assert len(args) == 11  # no head block, no head ids
    return (problem, *(np.asarray(args[i]) for i in (1, 2, 8, 9, 10)))


def equal_length_documents(n=50, d=40, length=12, seed=5):
    rng = np.random.default_rng(seed)
    indices = np.concatenate([rng.choice(d, length, replace=False)
                              for _ in range(n)])
    values = (0.1 + rng.random(n * length)) / np.sqrt(length)
    return SparseData(labels=np.where(rng.random(n) < 0.5, 1.0, -1.0),
                      indptr=np.arange(0, (n + 1) * length, length),
                      indices=indices, values=values, n_features=d)


@pytest.mark.parametrize("chains, devices", [(1, 1), (4, 1), (6, 4), (16, 4), (1, 4)])
def test_tiles_hold_every_nonzero_once_at_its_rows_slot(chains, devices,
                                                        monkeypatch):
    data = uneven_documents()
    n = data.n_examples
    # blocks of 8 tile rows: several blocks a device, as at the cell's size
    monkeypatch.setattr(svm, "_TILE_ROWS", 8)
    problem, ids, val, slot, row0, n_tiles = tiled(data, chains, devices)
    per_device = -(-chains // devices) * problem.rows_per_block
    example_of = np.full(devices * per_device, -1)
    example_of[:n] = np.random.default_rng(SEED).permutation(n)
    got = []
    for dev in range(devices):
        used = n_tiles[dev, 0]
        # nothing is stored past the tiles the round loops over
        assert not val[dev, used:].any() and not ids[dev, used:].any()
        tile, entry, row = np.nonzero(val[dev, :used])
        examples = example_of[
            dev * per_device + slot[dev, row0[dev, tile] + row]]
        got.append(np.stack([examples, ids[dev, tile, entry, row],
                             val[dev, tile, entry, row].view(np.int32)], axis=1))
        # a stored entry without a value is a pad: id 0
        assert not ids[dev][val[dev] == 0].any()
    got = np.concatenate(got)
    want = np.stack([np.repeat(np.arange(n), np.diff(data.indptr)),
                     data.indices,
                     data.values.astype(np.float32).view(np.int32)], axis=1)
    assert len(got) == len(data.indices)
    assert np.array_equal(got[np.lexsort(got.T[::-1])],
                          want[np.lexsort(want.T[::-1])])
    # the padded rectangle says the same of every slot
    lens = problem.row_len.reshape(-1)
    assert np.array_equal(lens, (problem.val != 0).sum(-1).reshape(-1))


def test_empty_rows_and_chains_cost_no_tile_and_a_block_follows_its_longest_row(
        monkeypatch):
    data = uneven_documents()  # row 20 is empty, row 7 holds 120 entries
    monkeypatch.setattr(svm, "_TILE_ROWS", 8)
    problem, ids, val, slot, row0, n_tiles = tiled(data, 6, 4)
    lens = svm._pad_blocks(problem.row_len, 8).reshape(4, 22)
    assert lens.reshape(-1)[61:].sum() == 0  # 5 pad rows, 2 empty chains
    order = np.random.default_rng(SEED).permutation(data.n_examples)
    # the last device holds the two empty chains: no tile at all
    assert lens[3].sum() == 0 and n_tiles[3, 0] == 0 and not val[3].any()
    for dev in range(4):
        # rows longest first, three blocks of 8: each as deep as its first row
        by_length = np.sort(lens[dev])[::-1]
        assert np.array_equal(lens[dev][slot[dev, :22]], by_length)
        steps = -(-by_length[::8] // 8)
        assert n_tiles[dev, 0] == steps.sum()
        assert np.array_equal(row0[dev, :steps.sum()],
                              np.repeat(np.arange(3) * 8, steps))
    # the row of 120 leads its device's first block, 15 steps deep, alone
    # past the second step; the other devices' rows hold 9 entries at most
    dev, local = divmod(np.flatnonzero(order == 7)[0], 22)
    assert slot[dev, 0] == local and (n_tiles[:3, 0] >= 15).sum() == 1
    assert (val[dev, 2:15] != 0).any(axis=(0, 1)).sum() == 1
    # the empty document sorts behind every row that holds an entry
    dev, local = divmod(np.flatnonzero(order == 20)[0], 22)
    assert np.flatnonzero(slot[dev, :22] == local)[0] >= (lens[dev] > 0).sum()


@pytest.mark.parametrize("length", [12, 8, 1])
def test_rows_of_one_length_store_no_position_past_the_next_multiple_of_8(
        length):
    data = equal_length_documents(length=length)
    problem, ids, val, slot, row0, n_tiles = tiled(data, 5, 1)
    depth = -(-length // 8)
    assert n_tiles[0, 0] == depth == ids.shape[1]  # one block of 50 rows
    assert np.array_equal(slot[0, :50], np.arange(50))  # nothing to reorder
    stored = val[0].reshape(depth * 8, -1)
    assert np.array_equal(stored[:length, :50],
                          problem.val.reshape(50, length).T)
    assert np.array_equal(ids[0].reshape(depth * 8, -1)[:length, :50],
                          problem.idx.reshape(50, length).T)
    assert not stored[length:].any() and not stored[:, 50:].any()
    _, w_gram, a_gram = run_program(data, 5, "gram", "avg", 2, devices=1)
    _, w_padded, a_padded = run_program(data, 5, "scatter", "avg", 2, devices=1)
    assert ref.rel_err(w_gram, w_padded) < TOL
    assert ref.rel_err(a_gram, a_padded) < TOL


def test_the_cells_lengths_keep_their_padding_under_a_sixteenth():
    """Whole rows in tiles, longest first: 4.7% of the stored positions are
    pads at the cell's lengths (the ladder of length buckets that the tiles
    replaced stored 10.7%, the rectangle padded to 256 71.5%)."""
    with open(os.path.join(REPO, "benchmark", "configs", "rcv1-cocoa.json")) as f:
        cfg = json.load(f)
    lens = synth_cocoa.row_lengths(cfg)
    chains = cfg["blocks"]
    slots = np.zeros(chains * -(-len(lens) // chains), np.int64)
    slots[:len(lens)] = lens
    stored = svm._tile_steps(slots).sum() * svm._TILE_STEP * svm._TILE_ROWS
    assert 1 - lens.sum() / stored < 0.0625 < 0.12 < 0.715


@pytest.mark.parametrize("mode", ["avg", "add"])
@pytest.mark.parametrize("devices", [1, 4])
@pytest.mark.parametrize("chains", [1, 4, 16])
def test_tiled_round_agrees_with_the_reference_and_the_padded_round(
        chains, devices, mode):
    data = uneven_documents()
    _, w, alpha = run_program(data, chains, "gram", mode, 2, devices=devices)
    slots, w_ref, a_ref = run_reference(data, chains, mode, 2)
    assert ref.rel_err(w, w_ref) < TOL
    assert ref.rel_err(by_example(alpha, slots, data.n_examples), a_ref) < TOL
    # the scatter engine reads the padded rectangles, row by row
    _, w_padded, a_padded = run_program(data, chains, "scatter", mode, 2,
                                        devices=devices)
    assert ref.rel_err(w, w_padded) < TOL
    assert ref.rel_err(alpha, a_padded) < TOL


@pytest.mark.parametrize("mode", ["avg", "add"])
@pytest.mark.parametrize("devices", [1, 4])
def test_a_round_over_several_blocks_of_tile_rows_agrees_with_the_reference(
        devices, mode, monkeypatch):
    """61 rows are one block of 1,024 tile rows; in blocks of 8 the round's
    loop moves from block to block (`row0`), as it does at the cell's size."""
    monkeypatch.setattr(svm, "_TILE_ROWS", 8)
    data = uneven_documents()
    _, w, alpha = run_program(data, 4, "gram", mode, 2, devices=devices)
    slots, w_ref, a_ref = run_reference(data, 4, mode, 2)
    assert ref.rel_err(w, w_ref) < TOL
    assert ref.rel_err(by_example(alpha, slots, data.n_examples), a_ref) < TOL


def boundary_sizes(jaxpr, d):
    """Indices of every gather from, and scatter-add into, (d,) vectors."""
    found = {"gather": 0, "scatter-add": 0}

    def walk(j):
        for eqn in j.eqns:
            if (eqn.primitive.name in found
                    and eqn.invars[0].aval.shape[-1:] == (d,)):
                found[eqn.primitive.name] += int(np.prod(
                    eqn.invars[1].aval.shape[:-1]))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jaxpr)
    return found


@pytest.mark.parametrize("inner", ["gram", "scatter"])
def test_the_rounds_gather_and_scatter_run_over_the_stored_entries(inner):
    data = uneven_documents()
    problem = prepare_svm_blocked(data, 4, seed=SEED)
    cfg = SVMConfig(local_iterations=problem.rows_per_block,
                    regularization=LAM, seed=SEED, inner=inner)
    fit, args = compile_svm_fit(problem, cfg, make_mesh(1))
    sizes = boundary_sizes(
        jax.make_jaxpr(lambda *a: fit(1, *a))(*args).jaxpr,
        data.n_features)
    if inner == "gram":
        # one gather and one scatter-add, each a tile wide, in a loop over
        # the tiles in use: together the stored entries
        tile = svm._TILE_STEP * svm._TILE_ROWS
        assert sizes == {"gather": tile, "scatter-add": tile}
        stored = int(np.asarray(args[10]).sum()) * tile
        assert stored == gauges()["tpums_svm_rows"] * gauges()["tpums_svm_row_width"]
        assert len(data.indices) <= stored
    else:
        # one row of every chain a step, each of the padded width
        assert sizes["gather"] == sizes["scatter-add"] == 4 * 120


# -- the synthetic documents ---------------------------------------------------

@pytest.fixture(scope="module")
def tiny_cfg():
    with open(os.path.join(REPO, "benchmark", "tests", "tiny-cocoa",
                           "rcv1-tiny.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def two_seeds(tiny_cfg):
    return [synth_cocoa.cocoa_problem(tiny_cfg, seed) for seed in (5, 3000000019)]


def test_row_lengths_are_the_configurations_not_the_seeds(tiny_cfg, two_seeds):
    lens = synth_cocoa.row_lengths(tiny_cfg)
    a = tiny_cfg["assumed"]
    assert lens.sum() == tiny_cfg["nnz"]
    assert lens.min() >= a["row_length_min"] and lens.max() == a["row_length_clip"]
    for indptr, indices, _, _ in two_seeds:
        assert len(indices) == tiny_cfg["nnz"] == indptr[-1]
        assert np.array_equal(np.sort(np.diff(indptr)), np.sort(lens))
    assert not np.array_equal(np.diff(two_seeds[0][0]), np.diff(two_seeds[1][0]))


def test_two_seeds_give_one_padded_shape(tiny_cfg, two_seeds):
    shapes = set()
    for indptr, indices, values, labels in two_seeds:
        data = SparseData(labels=labels, indptr=indptr, indices=indices,
                          values=values, n_features=tiny_cfg["features"])
        shapes.add(prepare_svm_blocked(data, tiny_cfg["blocks"]).idx.shape)
    assert shapes == {(tiny_cfg["blocks"], tiny_cfg["local_iterations"],
                       tiny_cfg["assumed"]["row_length_clip"])}


@pytest.mark.parametrize("which", [0, 1])
def test_rows_are_unit_norm_with_distinct_features(tiny_cfg, two_seeds, which):
    indptr, indices, values, labels = two_seeds[which]
    assert values.dtype == np.float32 and values.min() > 0
    sq = np.add.reduceat(values.astype(np.float64) ** 2, indptr[:-1])
    np.testing.assert_allclose(sq, 1.0, atol=1e-6)
    row_of = np.repeat(np.arange(tiny_cfg["rows"]), np.diff(indptr))
    keys = row_of * tiny_cfg["features"] + indices
    assert len(np.unique(keys)) == len(keys)
    assert 0 <= indices.min() and indices.max() < tiny_cfg["features"]
    assert set(np.unique(labels)) == {-1.0, 1.0}
    assert abs((labels > 0).mean() - tiny_cfg["assumed"]["positive_share"]) < 2e-3


def test_the_same_seed_gives_the_same_documents(tiny_cfg, two_seeds):
    again = synth_cocoa.cocoa_problem(tiny_cfg, 5)
    for a, b in zip(two_seeds[0], again):
        assert np.array_equal(a, b)


def test_repeated_ranks_move_to_the_next_free_one():
    ranks = np.array([0, 0, 0, 5, 5, 9, 9, 9, 3, 3], np.int64)
    row_of = np.array([0, 0, 0, 0, 0, 0, 0, 0, 1, 1], np.int64)
    pos = np.array([0, 1, 2, 3, 4, 5, 6, 7, 0, 1], np.int64)
    got = synth_cocoa._distinct_ranks(ranks, row_of, pos, 10)
    assert got.tolist() == [0, 1, 2, 5, 6, 7, 8, 9, 3, 4]
