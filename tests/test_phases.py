"""Set-up phases (``obs/tracing.phase``): the primitive (parents, self time,
a thread's own root, the bound, no jax needed, the registry series), the
named phases of the three set-up paths at CPU sizes, and the window guard:
nothing a window repeats (iterations, rounds, frames) ends a phase."""

import os
import re
import subprocess
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flink_ms_tpu.core import formats as F
from flink_ms_tpu.obs import metrics as obs_metrics
from flink_ms_tpu.obs import tracing as T
from flink_ms_tpu.parallel.mesh import make_mesh

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def log():
    """The phase log emptied before the test -> a function that returns
    what ended since, as {name: entry} (the last of a name wins)."""
    T.clear_phases()
    yield lambda: {e["name"]: e for e in T.phase_log()}
    T.clear_phases()


def _dur(e):
    return e["end"] - e["start"]


# -- the primitive ----------------------------------------------------------

def test_nesting_gives_parents_and_self_time(log):
    with T.phase("root"):
        time.sleep(0.03)
        with T.phase("root.a"):
            time.sleep(0.02)
            with T.phase("root.a.deep"):
                time.sleep(0.01)
        with T.phase("root.b"):
            time.sleep(0.02)
    entries = T.phase_log()
    # an entry is written when its phase ends: children before parents
    assert [e["name"] for e in entries] == [
        "root.a.deep", "root.a", "root.b", "root"]
    by = log()
    assert by["root"]["parent"] is None
    assert by["root.a"]["parent"] == by["root.b"]["parent"] == "root"
    assert by["root.a.deep"]["parent"] == "root.a"
    assert {e["thread"] for e in entries} == {threading.get_ident()}
    children = T.phase_children(by["root"], entries)
    assert [c["name"] for c in children] == ["root.a", "root.b"]  # direct only
    self_s = _dur(by["root"]) - sum(_dur(c) for c in children)
    assert 0.03 <= self_s < 0.03 + 0.02  # the root's own sleep, not its children's
    assert _dur(by["root.a"]) >= 0.03 and _dur(by["root.a.deep"]) >= 0.01
    for c in children:
        assert by["root"]["start"] <= c["start"] <= c["end"] <= by["root"]["end"]
    # start and end are perf_counter instants
    assert abs(by["root"]["end"] - time.perf_counter()) < 5.0


def test_a_phase_on_a_second_thread_is_a_root_of_its_own(log):
    def work():
        with T.phase("rebuild"):
            with T.phase("rebuild.child"):
                pass

    with T.phase("main"):
        worker = threading.Thread(target=work)
        worker.start()
        worker.join(timeout=30)
        assert not worker.is_alive()
    by = log()
    assert by["rebuild"]["parent"] is None and by["main"]["parent"] is None
    assert by["rebuild.child"]["parent"] == "rebuild"
    assert by["rebuild"]["thread"] != by["main"]["thread"]
    # lying inside main's interval does not make it main's child
    assert T.phase_children(by["main"], T.phase_log()) == []


def test_an_exception_still_ends_the_phase_and_is_not_swallowed(log):
    with pytest.raises(KeyError):
        with T.phase("outer"):
            with T.phase("outer.fails"):
                raise KeyError("kept")
    by = log()
    assert by["outer.fails"]["parent"] == "outer"
    with T.phase("after"):
        pass
    assert log()["after"]["parent"] is None  # the stack was unwound


def test_the_log_is_bounded(log):
    for i in range(T._PHASE_CAP + 50):
        with T.phase("many"):
            pass
    entries = T.phase_log()
    assert len(entries) == T._PHASE_CAP
    assert entries[0]["start"] < entries[-1]["start"]  # the oldest went first


def test_phase_without_jax_records_the_entry_and_opens_no_stage():
    probe = (
        "import sys\n"
        "from flink_ms_tpu.obs import tracing\n"
        "from flink_ms_tpu.obs.metrics import get_registry\n"
        "with tracing.phase('als.prepare') as p:\n"
        "    assert p._stage is tracing._NO_STAGE\n"
        "    with tracing.phase('als.prepare.fill'):\n"
        "        pass\n"
        "assert 'jax' not in sys.modules, 'phase() imported jax'\n"
        "log = tracing.phase_log()\n"
        "assert [(e['name'], e['parent']) for e in log] == [\n"
        "    ('als.prepare.fill', 'als.prepare'), ('als.prepare', None)], log\n"
        "h = get_registry().histogram('tpums_phase_seconds', kind='als.prepare')\n"
        "assert h.count == 1\n"
        "print(tracing.phase_report())\n"
    )
    done = subprocess.run([sys.executable, "-c", probe], cwd=REPO,
                          capture_output=True, text=True, timeout=60)
    assert done.returncode == 0, done.stderr
    assert re.fullmatch(
        r"als\.prepare \d+\.\d\ds \(als\.prepare\.fill \d+\.\d\d\)",
        done.stdout.strip())
    # with jax in the process the phase opens the stage of its name
    with T.phase("x") as p:
        assert isinstance(p._stage, jax.profiler.TraceAnnotation)


def test_phase_seconds_series_passes_the_hygiene_lint(log):
    reg = obs_metrics.get_registry()
    before = reg.histogram("tpums_phase_seconds", kind="lint.me")
    n0, s0 = before.count, before.sum
    with T.phase("lint.me"):
        time.sleep(0.005)
    mine = [h for h in reg.snapshot()["histograms"]
            if h["name"] == "tpums_phase_seconds"
            and h["labels"] == {"kind": "lint.me"}]
    assert len(mine) == 1
    assert re.match(obs_metrics.NAME_PATTERN, mine[0]["name"])
    assert set(mine[0]["labels"]) <= obs_metrics.LABEL_VOCABULARY
    assert mine[0]["count"] == n0 + 1
    assert mine[0]["sum"] - s0 == pytest.approx(_dur(log()["lint.me"]))


def test_report_sums_phases_of_one_name_and_brackets_children(log):
    for _ in range(2):
        with T.phase("topk.build"):
            with T.phase("topk.build.ids"):
                pass
    with T.phase("topk.warm"):
        pass
    assert re.fullmatch(
        r"topk\.build \d+\.\d\ds \(topk\.build\.ids \d+\.\d\d\), "
        r"topk\.warm \d+\.\d\ds", T.phase_report())
    assert T.phase_report([]) == "none"


def test_spans_sit_on_one_clock_and_nest(log):
    T.clear_events()
    with T.trace_span() as tid:
        with T.span("outer"):
            with T.span("inner"):
                time.sleep(0.002)
    outer, = T.recent_events(tid=tid, kind="outer")
    inner, = T.recent_events(tid=tid, kind="inner")
    assert outer["t0"] <= inner["t0"]
    assert inner["t0"] + inner["dur_s"] <= outer["t0"] + outer["dur_s"]
    # wall time by the process's one offset, read at import
    assert T.wall_offset() == T._WALL_OFFSET
    assert outer["t0"] == pytest.approx(time.time() - outer["dur_s"], abs=1.0)
    assert outer["t0"] - T.wall_offset() <= time.perf_counter()


# -- the three set-up paths -------------------------------------------------

def _als_setup():
    from flink_ms_tpu.ops.als import ALSConfig, compile_fit, prepare_blocked

    rng = np.random.default_rng(1)
    n = 600
    problem = prepare_blocked(rng.integers(0, 40, n), rng.integers(0, 30, n),
                              rng.uniform(1, 5, n).astype(np.float32), 1)
    fit_fn, dev_args = compile_fit(
        problem, ALSConfig(num_factors=4, iterations=1), make_mesh(n_devices=1))
    return fit_fn, dev_args


def _topk_setup(monkeypatch, sharded, n_items=4096, width=8):
    from flink_ms_tpu.serve.table import ModelTable
    from flink_ms_tpu.serve.topk import DeviceFactorIndex

    monkeypatch.setenv("TPUMS_TOPK_SHARDED", "1" if sharded else "0")
    rng = np.random.default_rng(2)
    index = DeviceFactorIndex(ModelTable(2), "-I")
    index.bulk_load([f"{i}-I" for i in range(n_items)],
                    rng.normal(size=(n_items, width)).astype(np.float32))
    index.warm_batch_shapes(5, 4)
    return index


def _svm_data(rng, n=256, d=64, nnz_row=6):
    idx = np.stack([rng.choice(d, nnz_row, replace=False) for _ in range(n)])
    return F.SparseData(
        labels=np.where(rng.random(n) < 0.5, -1.0, 1.0),
        indptr=np.arange(0, (n + 1) * nnz_row, nnz_row),
        indices=idx.ravel(), values=rng.normal(size=n * nnz_row),
        n_features=d)


def _svm_setup():
    from flink_ms_tpu.ops.svm import (SVMConfig, compile_svm_fit,
                                      prepare_svm_blocked)

    problem = prepare_svm_blocked(_svm_data(np.random.default_rng(3)), 8)
    config = SVMConfig(iterations=1, local_iterations=problem.rows_per_block,
                       regularization=1e-3, inner="gram")
    return compile_svm_fit(problem, config, make_mesh(n_devices=1))


ALS_TREE = {"als.prepare": None, "als.prepare.order": "als.prepare",
            "als.prepare.fill": "als.prepare",
            "als.prepare.fill.sort": "als.prepare.fill", "als.place": None,
            "als.sweep": None}
TOPK_TREE = {"topk.build": None, "topk.build.place": "topk.build",
             "topk.build.warm_scatter": "topk.build",
             "topk.build.ids": "topk.build", "topk.warm": None}
SVM_TREE = {"svm.prepare": None, "svm.gram_build": None, "svm.place": None,
            "svm.bucket": "svm.place"}


@pytest.mark.parametrize("path, tree", [
    ("als", ALS_TREE),
    # one device: the rows' one put, then their join with the spare rows
    ("topk", dict(TOPK_TREE, **{"topk.build.spare": "topk.build"})),
    ("topk-sharded", dict(TOPK_TREE, **{"topk.build.pad": "topk.build"})),
    ("svm-gram", SVM_TREE),
])
def test_setup_paths_record_their_named_phases(path, tree, log, monkeypatch):
    if path == "als":
        _als_setup()
    elif path == "svm-gram":
        _svm_setup()
    else:
        _topk_setup(monkeypatch, sharded=path == "topk-sharded")
    by = log()
    by.pop("device.backend", None)  # whichever test acquires first has it
    assert {n: e["parent"] for n, e in by.items()} == tree
    entries = T.phase_log()
    assert len([e for e in entries if e["name"] != "device.backend"]) \
        == len(tree), "a phase ran twice"
    if path.startswith("topk"):
        # the dict is made before the first wait (one device: under the transfer)
        assert by["topk.build.place"]["end"] <= by["topk.build.ids"]["start"]
        assert by["topk.build.ids"]["end"] <= by.get(
            "topk.build.spare", by["topk.build.warm_scatter"])["start"] \
            <= by["topk.build.warm_scatter"]["start"]
    for name, entry in by.items():
        children = T.phase_children(entry, entries)
        assert {c["name"] for c in children} == {
            n for n, parent in tree.items() if parent == name}
        assert sum(_dur(c) for c in children) <= _dur(entry)


@pytest.mark.parametrize("sharded", [False, True])
def test_an_update_of_a_bulk_loaded_id_lands_in_place(sharded, monkeypatch):
    """`id_pos`, made before the build's first wait, holds every id at its
    row: an UPDATE after the build is one scatter into it, no rebuild."""
    from flink_ms_tpu.serve.table import ModelTable
    from flink_ms_tpu.serve.topk import DeviceFactorIndex

    monkeypatch.setenv("TPUMS_TOPK_SHARDED", "1" if sharded else "0")
    monkeypatch.setenv("TPUMS_TOPK_TIER", "exact")
    rng = np.random.default_rng(7)
    n, width = 1000, 4  # 8 shards of 128: the last one 104 real rows
    ids = [f"item{i}" for i in range(n)]
    rows = rng.normal(size=(n, width)).astype(np.float32)
    table = ModelTable(2)
    index = DeviceFactorIndex(table, "-I")
    index.bulk_load(ids, rows)
    assert index._is_sharded == sharded
    assert index._id_pos == {id_: i for i, id_ in enumerate(ids)}
    for pos in (0, 613, n - 1):
        table.put(f"item{pos}-I", ";".join(["9.5"] * width))
    top = index.topk(np.ones(width, np.float32), 3)
    assert sorted(item for item, _ in top) == ["item0", "item613", "item999"]
    assert [score for _, score in top] == [38.0] * 3
    assert (index.full_builds, index.inplace_updates) == (1, 3)
    want = rows.copy()
    want[[0, 613, n - 1]] = 9.5
    assert np.array_equal(np.asarray(index._matrix)[:n], want)
    assert not np.asarray(index._matrix)[n:].any()


def test_routing_tables_are_host_prep_with_a_root_of_their_own(
        log, monkeypatch):
    from flink_ms_tpu.ops.als import ALSConfig, compile_fit, prepare_blocked

    monkeypatch.setenv("FLINK_MS_ALS_EXCHANGE_MODE", "routed")
    rng = np.random.default_rng(5)
    n = 600
    problem = prepare_blocked(rng.integers(0, 40, n), rng.integers(0, 30, n),
                              rng.uniform(1, 5, n).astype(np.float32), 2)
    compile_fit(problem, ALSConfig(num_factors=4, iterations=1),
                make_mesh(n_devices=2))
    routes = [e for e in T.phase_log() if e["name"] == "als.prepare.route"]
    assert len(routes) == 2  # one a half-sweep
    assert all(e["parent"] is None for e in routes)  # not under als.place
    assert routes[-1]["end"] <= log()["als.place"]["start"]


def test_table_path_and_background_rebuild_snapshot_under_their_own_root(
        log, monkeypatch):
    from flink_ms_tpu.serve import topk
    from flink_ms_tpu.serve.table import ModelTable
    from flink_ms_tpu.serve.topk import DeviceFactorIndex

    # a new id starts a rebuild, as on the IVF tier (in place otherwise)
    monkeypatch.setattr(topk, "_INSERTS_IN_PLACE", False)

    table = ModelTable(2)
    index = DeviceFactorIndex(table, "-I")
    for i in range(32):
        table.put(f"{i}-I", ";".join(["0.5"] * 4))
    assert len(index.topk(np.ones(4, np.float32), 3)) == 3  # the full build
    by = log()
    assert by["topk.build.snapshot"]["parent"] is None
    assert by["topk.build"]["thread"] == threading.get_ident()
    T.clear_phases()
    table.put("new-I", ";".join(["0.25"] * 4))  # unknown id: one rebuild
    index.topk(np.ones(4, np.float32), 3)
    index._rebuild_thread.join(timeout=60)
    assert not index._rebuild_thread.is_alive()
    by = log()
    assert by["topk.build"]["parent"] is None  # the rebuild thread's own root
    assert by["topk.build"]["thread"] != threading.get_ident()
    assert by["topk.build.snapshot"]["thread"] == by["topk.build"]["thread"]


# -- the window guard -------------------------------------------------------

def _ten_iterations():
    fit_fn, dev_args = _als_setup()
    one = jnp.asarray(1, jnp.int32)
    state, static = dev_args[:2], dev_args[2:]

    def window():
        nonlocal state
        for _ in range(10):
            state = fit_fn(one, *state, *static)
        jax.block_until_ready(state)

    return window


def _ten_rounds():
    fit, dev_args = _svm_setup()
    args = list(dev_args)

    def window():
        for r in range(10):
            args[0], args[5] = fit(1, *args, start=r)
        jax.block_until_ready(args[0])

    return window


def _frames(monkeypatch):
    from flink_ms_tpu.serve.microbatch import TopKBatcher

    index = _topk_setup(monkeypatch, sharded=False)
    batcher = TopKBatcher(index, max_batch=4, max_wait_us=200_000)
    rng = np.random.default_rng(4)

    def window():
        try:
            for _ in range(5):
                pending = [batcher.submit(
                    rng.normal(size=8).astype(np.float32), 5,
                    allow_inline=False) for _ in range(4)]
                batcher.flush()
                assert all(len(p.wait(timeout=60)) == 5 for p in pending)
        finally:
            batcher.close()

    return window


@pytest.mark.parametrize("what", ["als-iterations", "cocoa-rounds",
                                  "topk-frames"])
def test_window_guard_nothing_a_window_repeats_ends_a_phase(
        what, log, monkeypatch):
    window = {"als-iterations": _ten_iterations, "cocoa-rounds": _ten_rounds,
              "topk-frames": lambda: _frames(monkeypatch)}[what]()
    at_open = len(T.phase_log())
    assert at_open > 0  # set-up did record
    window()
    assert len(T.phase_log()) == at_open
