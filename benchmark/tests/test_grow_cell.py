"""The cells `bigann-t2i-10m-ycsb-d.serve-grow` and
`bigann-t2i-10m.topk-poisson` at a size the CPU holds: the rehearsals through
the real command, the plain reference against a dict replay, what counts as
a stale answer when ids are new, the latest law, the Poisson schedule, and
the new reader and model."""

import json
import os
import shutil
import subprocess
import sys
import types

import numpy as np
import pytest

from benchmark import loadgen_poisson, reference, reference_grow, roofline
from benchmark import roofline_grow, synth, synth_grow, synth_updates
from benchmark.readers import trace_roofline_gauged
from benchmark.tests.conftest import REPO

TINY = os.path.join(REPO, "benchmark", "tests", "tiny-ycsb-d", "BENCHMARK.json")
CELL = "t2i-tiny-ycsb-d.serve-grow"
POISSON = os.path.join(REPO, "benchmark", "tests", "tiny-poisson",
                       "BENCHMARK.json")
POISSON_CELL = "t2i-tiny.topk-poisson"


def command(bench, cell, trace, *more):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--bench", bench, "--workload",
         cell, "--seed", "3000000019", "--seconds", "2", "--trace", str(trace),
         *more],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600)
    shutil.rmtree(os.path.join(REPO, ".benchwork", cell), ignore_errors=True)
    assert done.returncode == 0, done.stderr[-2000:]
    return json.loads(done.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_prints_the_contract_line(trace):
    line = command(TINY, CELL, trace)
    assert line["correct"] is True, [c for c in line["checks"] if not c["ok"]]
    assert line["failed"] == 0 and line["attempted"] == 600
    assert line["device"]["platform"] == "cpu"  # a rehearsal says so
    checks = {c["name"]: c["value"] for c in line["checks"]}
    assert {"topk_score_abs_err", "topk_wrong_ids_at_clear_ranks",
            "grow_catalog_change", "grow_stale_answers",
            "grow_freshness_checked", "grow_inserts_lost",
            "grow_inserts_refused", "grow_rebuilds", "grow_rows_not_live",
            "grow_puts_not_from_journal"} <= set(checks)
    assert checks["grow_freshness_checked"] > 10
    got = line["metrics"] if trace else line["layers"]
    assert {"grow_insert_visible_p50_ms", "grow_insert_visible_p99_ms",
            "grow_in_place_share", "grow_rebuilds", "grow_capacity_share",
            "mix_consume_lag_ms"} <= set(got)
    assert got["grow_in_place_share"]["value"] == 1.0
    assert got["grow_rebuilds"]["value"] == 0.0
    # 20,000 loaded rows and the window's inserts, over row_capacity(20,000)
    assert 20000 / 21504 < got["grow_capacity_share"]["value"] < 20100 / 21504
    assert (got["grow_insert_visible_p50_ms"]["value"]
            <= got["grow_insert_visible_p99_ms"]["value"])
    if trace:
        # the roofline share needs a published peak and is left out
        assert "grow_frame_roofline" not in got
    if trace and "paced_dispatch_ms" in got:
        # the stages are host spans, which a CPU trace holds; they are read
        # per batched frame, and at 300 /s a quick CPU may answer every
        # request of a 2 s window as an inline single: no frame, no reading
        assert {"mix_maintain_ms", "mix_parse_ms", "mix_scatter_enqueue_ms",
                "grow_insert_ms"} <= set(got)
        assert (got["grow_insert_ms"]["value"] + got["mix_parse_ms"]["value"]
                + got["mix_scatter_enqueue_ms"]["value"]
                <= got["mix_maintain_ms"]["value"])
    else:
        assert set(line["metrics"]) == {"topk_p50_ms", "setup_s"}


@pytest.mark.parametrize("control, fails", [
    # the index is never told: (a), (b) and (c) all see it
    ("lost_inserts", {"topk_wrong_ids_at_clear_ranks", "grow_stale_answers",
                      "grow_inserts_lost", "grow_rows_not_live"}),
    # the behaviour before this cell's PR, a rebuild for every new id: at
    # this size a rebuild is quick, so (c) alone is sure to see it
    ("rebuild_on_insert", {"grow_inserts_lost", "grow_rebuilds"}),
])
def test_rehearsal_under_each_control_is_not_correct(control, fails):
    line = command(TINY, CELL, 0, "--control", control)
    assert line["correct"] is False
    assert fails <= {c["name"] for c in line["checks"] if not c["ok"]}


def test_poisson_rehearsal_prints_the_contract_line():
    line = command(POISSON, POISSON_CELL, 0)
    assert line["correct"] is True, [c for c in line["checks"] if not c["ok"]]
    assert line["failed"] == 0
    # a Poisson count: 1,200 expected over 2 s at 600 /s, sd 35
    assert 1000 < line["attempted"] < 1400 and line["attempted"] != 1200
    assert set(line["metrics"]) == {"topk_p50_ms", "setup_s"}
    assert {"paced_p95_ms", "paced_p99_ms", "loadgen_lag_p99_ms"} <= set(
        line["layers"])


# -- the reference -------------------------------------------------------------


def log_of(ids, rows, t_end):
    t_end = np.asarray(t_end, np.float64)
    return reference_grow.Log(np.asarray(ids, np.int64),
                              np.asarray(rows, np.float32), t_end - 0.001, t_end)


def test_replay_is_a_dict_that_grows():
    rng = np.random.default_rng(5)
    base = rng.standard_normal((50, 4), dtype=np.float32)
    ids = rng.integers(0, 70, 200)      # rows 50..69 are new ids
    rows = rng.standard_normal((200, 4), dtype=np.float32)
    grown = reference_grow.replay(base, log_of(ids, rows, np.arange(200.0)))
    table = {i: base[i] for i in range(50)}
    for id_, row in zip(ids.tolist(), rows):
        table[id_] = row
    assert all(np.array_equal(grown.base[i], table[i]) for i in range(50))
    assert not np.shares_memory(grown.base, base)
    assert sorted(grown.new_ids.tolist()) == sorted(k for k in table if k >= 50)
    # new ids in the order they first came
    first = list(dict.fromkeys(i for i in ids.tolist() if i >= 50))
    assert grown.new_ids.tolist() == first
    assert all(np.array_equal(row, table[id_])
               for id_, row in zip(grown.new_ids.tolist(), grown.new_rows))
    # a log of new ids alone leaves the loaded rows as they are, uncopied
    alone = reference_grow.replay(base, log_of([50, 51], rows[:2], [1.0, 2.0]))
    assert alone.base is base
    # the text of a row parses to the row (%.9g round-trips an f32)
    lines = synth_updates.journal_lines(ids, rows)
    log = reference_grow.read_log(lines, np.zeros(150), np.zeros(150))
    assert len(log.ids) == 150  # as far as the writer got
    assert np.array_equal(log.ids, ids[:150])
    assert np.array_equal(log.rows.view(np.uint32), rows[:150].view(np.uint32))


def test_final_topk_is_the_brute_force_over_the_grown_catalog():
    rng = np.random.default_rng(6)
    base = rng.standard_normal((3000, 8), dtype=np.float32)
    q = synth.queries(6, 5, 8)
    ids = np.concatenate([3000 + np.arange(20), rng.integers(0, 3000, 10)])
    rows = rng.standard_normal((30, 8), dtype=np.float32) * 2
    log = log_of(ids, rows, np.arange(30.0))
    got_ids, got_scores = reference_grow.final_topk(base, log, q, 10)
    whole = np.concatenate([base, np.zeros((20, 8), np.float32)])
    for id_, row in zip(ids.tolist(), rows):
        whole[id_] = row
    scores = q.astype(np.float64) @ whole.astype(np.float64).T
    order = np.argsort(-scores, axis=1, kind="stable")[:, :11]
    assert np.array_equal(got_ids, order)
    assert np.allclose(got_scores, np.take_along_axis(scores, order, axis=1),
                       rtol=0, atol=1e-12)
    assert (got_ids >= 3000).any()      # the new rows do rank
    # no puts: the plain reference
    none = log_of([], np.zeros((0, 8)), [])
    assert np.array_equal(reference_grow.final_topk(base, none, q, 10)[0],
                          reference.topk(base, q, 10)[0])


def test_a_new_id_has_no_version_before_its_first_put():
    base = np.zeros((4, 2), np.float32)
    rows = np.array([[1, 0], [2, 0], [3, 0]], np.float32)
    log = log_of([9, 2, 9], rows, [10.0, 11.0, 12.0])
    new = reference_grow.versions(base, log, 9)
    assert [(v[0], since, until) for v, since, until in new] == [
        (1.0, 9.999, 12.0), (3.0, 11.999, np.inf)]
    loaded = reference_grow.versions(base, log, 2)
    assert [(v[0], since, until) for v, since, until in loaded] == [
        (0.0, -np.inf, 11.0), (2.0, 10.999, np.inf)]
    assert reference_grow.versions(base, log, 7) == []


def test_what_counts_as_a_stale_answer_when_ids_are_new():
    base = np.array([[0.5, 0], [0.4, 0], [0.3, 0], [0.2, 0]], np.float32)
    q = np.array([1.0, 0.0], np.float32)
    log = log_of([4], [[0.9, 0.0]], [10.0])     # id 4 is new, acknowledged at 10
    old, new = [(0, 0.5), (1, 0.4)], [(4, 0.9), (0, 0.5)]

    def said(reply, sent, done, within=0.3):
        return reference_grow.stale_answer(
            base, log, q, [0], reply, sent, done, within, 1e-5)

    assert said(old, 9.0, 9.5) is None          # before the insert
    assert said(old, 10.2, 10.25) is None       # too fresh to be owed
    assert "missing" in said(old, 10.4, 10.45)  # owed: the bound has run out
    assert said(new, 10.4, 10.45) is None
    assert said(new, 9.99, 10.05) is None       # readable from its append's call
    # a row nobody had written yet, or a score that is no version's
    assert "no version" in said(new, 9.0, 9.5)
    assert "no version" in said([(4, 0.8), (0, 0.5)], 10.4, 10.45)
    assert "no version" in said([(7, 0.9), (0, 0.5)], 10.4, 10.45)
    # a marker that scores under the last returned row need not rank
    low = log_of([4], [[0.1, 0.0]], [10.0])
    assert reference_grow.stale_answer(
        base, low, q, [0], old, 10.4, 10.45, 0.3, 1e-5) is None


# -- the traffic ------------------------------------------------------------------


TRAFFIC = {"rate_per_s": 300, "insert_rate_per_s": 15.8,
           "insert_offset_gaps": 0.5, "insert_pull": 0.5, "read_zipf": 0.99,
           "pool": 4096}


def test_inserts_are_new_ids_near_one_pool_query():
    vectors = synth.queries(7, 4096, 200)
    ids, toward, values = synth_grow.inserts(TRAFFIC, 7, 10_000, vectors, 300)
    assert ids.tolist() == list(range(10_000, 10_300))
    assert values.dtype == np.float32 and values.shape == (300, 200)
    own = values - np.float32(0.5) * vectors[toward]
    assert abs(float(own.std()) - 200 ** -0.5) < 0.003   # the catalog's law
    scores = np.einsum("nk,nk->n", values, vectors[toward])
    assert 0.25 < scores.min() and abs(scores.mean() - 0.5) < 0.02
    assert len(set(toward.tolist())) > 280               # uniform over the pool
    again = synth_grow.inserts(TRAFFIC, 7, 10_000, vectors, 300)
    assert np.array_equal(again[2], values)              # the seed's
    lines = synth_updates.journal_lines(ids, values)
    assert lines[0].startswith("10001,I,") and lines[-1].startswith("10300,I,")


def test_reads_follow_the_latest_law():
    n_rows, count, n_reads = 10_000_000, 336, 6300
    toward = np.random.default_rng(8).integers(0, 4096, count)
    slots = synth_grow.latest_slots(TRAFFIC, 8, n_rows, toward, n_reads)
    assert slots.shape == (n_reads,) and 0 <= slots.min() and slots.max() < 4096
    assert np.array_equal(
        slots, synth_grow.latest_slots(TRAFFIC, 8, n_rows, toward, n_reads))
    t_read = np.arange(n_reads) / 300.0
    t_ins = synth_grow.insert_times(TRAFFIC, count)
    assert t_ins[0] == 0.5 / 300 and np.allclose(np.diff(t_ins), 1 / 15.8)
    written = np.searchsorted(t_ins, t_read, side="left")
    newest = toward[np.maximum(written - 1, 0)]
    late = written > 50            # past the lead-in: fifty ranks are inserts
    # zipfian 0.99 over 10M records: rank 0 takes 1 / H = 5.5%
    share = float(np.mean(slots[late] == newest[late]))
    assert 0.04 < share < 0.07, share
    # and a third of the reads, less what the early ones lack, aim at an
    # insert of the run: their slot is one some insert was written toward
    aimed = np.isin(slots[late], toward)
    assert 0.25 < float(aimed.mean()) < 0.45
    # before any insert a read can only ask for a loaded row
    assert written[0] == 0 and (slots[:1] < 4096).all()


def test_poisson_arrivals_have_the_rate_and_the_spread():
    at = loadgen_poisson.arrivals(3000000021, 600.0, 100.0, 121.0)
    assert at[0] > 100.0 and at[-1] < 121.0 and (np.diff(at) > 0).all()
    assert abs(len(at) - 12_600) < 5 * 12_600 ** 0.5
    gaps = np.diff(at)
    assert abs(gaps.mean() * 600 - 1) < 0.03
    assert abs(gaps.std() / gaps.mean() - 1) < 0.05      # exponential: cv 1
    assert np.array_equal(at, loadgen_poisson.arrivals(3000000021, 600.0,
                                                       100.0, 121.0))
    other = loadgen_poisson.arrivals(3000000022, 600.0, 100.0, 121.0)
    assert len(other) != len(at) or not np.array_equal(other, at)


# -- the model and its reader ---------------------------------------------------


def test_the_frame_is_counted_over_the_capacity():
    cfg = {"rows": 10_000_000, "rank": 200, "k": 10}
    flops, nbytes = roofline_grow.topk_frame_capacity(cfg, 6.0, 10_039_296)
    assert flops == 2.0 * 6 * 10_039_296 * 200
    assert nbytes == 10_039_296 * 800 + 6 * 800 + 6 * 80
    # at capacity == rows it is the sibling's model to the digit
    assert roofline_grow.topk_frame_capacity(cfg, 6.0, 10_000_000) \
        == roofline.topk_frame(cfg, 6.0)


def fake_run(gauges, kind="TPU v5 lite", busy=1.0, frames=100, batch=(600.0, 100)):
    snap = {"gauges": [{"name": k, "value": v, "labels": {}}
                       for k, v in gauges.items()]}
    run = types.SimpleNamespace(
        config={"rows": 10_000_000, "rank": 200, "k": 10},
        counts={"frames": frames}, snap_after=snap,
        devices=[types.SimpleNamespace(device_kind=kind, platform=(
            "cpu" if kind == "cpu" else "tpu"))],
        reduced_trace=lambda: {"busy_s": busy},
        hist_delta=lambda name: batch)
    with open(os.path.join(REPO, "benchmark", "peaks.json")) as f:
        peaks = json.load(f)
    run.load = lambda *parts: peaks
    return run, peaks


ARGS = {"module": "roofline_grow", "model": "topk_frame_capacity",
        "per": "frames", "batch_from": "tpums_topk_batch_size",
        "gauges": {"capacity": "tpums_topk_rows_capacity"}}


def test_the_gauged_roofline_reads_the_capacity_from_the_program():
    run, peaks = fake_run({"tpums_topk_rows_capacity": 10_039_296.0})
    value, extra = trace_roofline_gauged.read(run, **ARGS)
    nbytes = 10_039_296 * 800 + 6 * 800 + 6 * 80
    assert extra["bound"] == "bytes" and extra["capacity"] == 10_039_296.0
    assert extra["batch"] == 6.0 and extra["bytes"] == nbytes
    want = 100.0 * (nbytes / peaks["TPU v5 lite"]["hbm_bytes_per_s"]) / 0.01
    assert value == pytest.approx(want)
    assert 0 < value < 100


@pytest.mark.parametrize("missing", ["gauge", "trace", "batch", "cpu"])
def test_the_gauged_roofline_says_nothing_where_there_is_nothing(missing):
    gauges = {} if missing == "gauge" else {"tpums_topk_rows_capacity": 1e7}
    run, _ = fake_run(gauges, kind="cpu" if missing == "cpu" else "TPU v5 lite",
                      busy=0.0 if missing == "trace" else 1.0,
                      batch=(0.0, 0) if missing == "batch" else (600.0, 100))
    assert trace_roofline_gauged.read(run, **ARGS) is None
