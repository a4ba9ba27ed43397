"""The readers of PR 25: the clock lead, device idle time by stage, and
device time by named scope — on hand-made planes, on two traces recorded on
the TPU v5e (`small.xplane.pb`: no scopes, no stage spans; `scoped.xplane.pb`:
three `topk.frame`s with `topk.enqueue` / `topk.fetch` around a scoped
matmul + top_k, then two dispatches of a scoped loop), and in the traced CPU
rehearsal."""

import os

import pytest

from benchmark import run as harness
from benchmark import trace_reduce
from benchmark.readers import trace_clock, trace_scope, trace_starved
from benchmark.tests.conftest import REPO, TINY
from benchmark.tests.test_trace_reduce import Line, Plane, ev

HERE = os.path.dirname(__file__)
SMALL = os.path.join(HERE, "small.xplane.pb")
SCOPED = os.path.join(HERE, "scoped.xplane.pb")
LEAD = 600  # ns the hand-made device clock shows an instant early


def shifted_planes():
    """Two runs on a device whose clock leads the host's by LEAD.  On the
    host's clock: run 1 busy 1000..2000, run 2 busy 2800..3600, so the device
    idles 2000..2800, all of it under stage `topk.scatter` (1900..2900)."""
    host = Line("dispatcher", [
        ev(trace_reduce.WINDOW, 900, 2800),             # 900..3700
        ev("topk.frame", 950, 1960),                    # 950..2910
        ev("topk.fetch", 960, 930),                     # 960..1890
        ev("topk.scatter", 1900, 1000),                 # 1900..2900
        ev("topk.coalesce", 2920, 700),
    ])
    runtime = Line("runtime", [
        ev("DoEnqueueProgram", 990, 5, run_id=7),       # 10 before run 1
        ev("CompleteCallbacks", 2010, 20, run_id=7),    # 10 after it
        ev("DoEnqueueProgram", 2700, 5, run_id=8),      # run 2 queued early
        ev("CompleteCallbacks", 3650, 20, run_id=8),
        ev("CompleteCallbacks", 5000, 20, run_id=99),   # a run not traced
    ])
    modules = Line("XLA Modules", [
        ev("jit_f", 1000 - LEAD, 1000, run_id=7),
        ev("jit_f", 2800 - LEAD, 800, run_id=8)])
    ops = Line("XLA Ops", [ev("%fusion.1", 1000 - LEAD, 1000),
                           ev("%fusion.1", 2800 - LEAD, 800)])
    return [Plane("/device:TPU:0", [modules, ops]),
            Plane("/host:CPU", [host, runtime])]


# -- trace_clock ------------------------------------------------------------

def test_clock_lead_on_the_recorded_traces():
    for path, n in ((SMALL, 4), (SCOPED, 5)):
        lo, hi, runs = trace_clock.bounds(trace_clock.profile(path).planes)
        assert lo <= hi and runs == n
        assert 1.0e6 < (lo + hi) / 2 < 1.6e6  # ns


def test_clock_lead_recovers_a_known_shift():
    lo, hi, runs = trace_clock.bounds(shifted_planes())
    # run 1 is pinned from both sides; run 2 was enqueued 100 early and
    # seen done 50 late, so it widens nothing
    assert (lo, hi, runs) == (LEAD - 10, LEAD + 10, 2)
    assert trace_clock.lead_ns(shifted_planes()) == LEAD


def test_no_pair_or_crossed_bounds_give_no_estimate():
    planes = shifted_planes()
    assert trace_clock.bounds(planes[1:]) is None          # no device plane
    crossed = Line("runtime", [ev("DoEnqueueProgram", 1500, 5, run_id=7),
                               ev("CompleteCallbacks", 1600, 5, run_id=7)])
    assert trace_clock.bounds(
        [planes[0], Plane("/host:CPU", [crossed])]) is None


# -- trace_starved ----------------------------------------------------------

def test_the_lead_moves_a_gap_from_one_stage_to_its_neighbour():
    idle, by_stage = trace_starved.starved(shifted_planes(), LEAD, "topk.")
    # 900..1000 before run 1 (50 of it before any stage span), the gap
    # 2000..2800, and 3600..3700 after run 2 (the coalesce span ends at 3620)
    assert idle == 100 + 800 + 100
    assert by_stage == {"topk.frame": 10, "topk.fetch": 40,
                        "topk.scatter": 800, "topk.coalesce": 20}
    # uncorrected, the same gap sits 600 earlier: most of it under the fetch
    _, raw = trace_starved.starved(shifted_planes(), 0, "topk.")
    assert raw["topk.fetch"] == 1890 - 1400 and raw["topk.scatter"] == 300


def test_pieces_name_overlaps_by_the_shortest_span_open():
    spans = [(0, 100, "topk.coalesce"), (10, 50, "topk.frame"),
             (20, 30, "topk.fetch"), (200, 210, "topk.frame")]
    assert trace_starved.pieces(spans) == [
        (0, 10, "topk.coalesce"), (10, 20, "topk.frame"),
        (20, 30, "topk.fetch"), (30, 50, "topk.frame"),
        (50, 100, "topk.coalesce"), (200, 210, "topk.frame")]


def test_starved_on_the_recorded_trace_puts_the_first_frame_in_its_fetch():
    planes = list(trace_clock.profile(SCOPED).planes)
    lead = trace_clock.lead_ns(planes)
    idle, by_stage = trace_starved.starved(planes, lead, "topk.")
    # three frames of 0.66 ms and two loops of 0.14 in a 20.5 ms window
    assert idle == pytest.approx(20.47e6 - 3 * 0.66e6 - 2 * 0.141e6, rel=0.01)
    # with the lead taken out every frame's device time lies inside its own
    # topk.fetch, so the fetch spans' idle part is their length less 0.66 ms
    assert by_stage["topk.fetch"] == pytest.approx(
        (2.677 + 2.091 + 2.106 - 3 * 0.66) * 1e6, rel=0.01)
    assert by_stage["topk.enqueue"] == pytest.approx(
        (0.424 + 0.275 + 0.354) * 1e6, rel=0.01)
    assert trace_starved.starved(
        list(trace_clock.profile(SMALL).planes), lead, "topk.") is None


# -- trace_scope ------------------------------------------------------------

@pytest.mark.parametrize("path", [SMALL, SCOPED])
def test_the_wire_reader_agrees_with_profile_data(path):
    want = trace_reduce.device_events(list(trace_clock.profile(path).planes))
    got = trace_scope.device_ops(path)
    assert got.keys() == want.keys()
    for plane, ops in got.items():  # ProfileData rounds to whole ns
        assert [name for _, _, name, _ in ops] == [n for _, _, n in want[plane]]
        assert [t for op in ops for t in op[:2]] == pytest.approx(
            [t for op in want[plane] for t in op[:2]], abs=2)


def test_scope_paths_come_from_the_event_metadata():
    paths = {tf_op for ops in trace_scope.device_ops(SCOPED).values()
             for _, _, _, tf_op in ops}
    assert "jit(f)/topk.score/dot_general:" in paths
    assert "jit(f)/topk.select/top_k:" in paths
    assert "" in paths  # copies and the while carry none


def test_innermost_scope_and_unscoped():
    scopes = ["als.exchange", "als.assemble", "als.solve"]
    assert trace_scope.innermost(
        "jit(fit)/while/body/als.user_half/als.assemble/als.solve/cholesky:",
        scopes) == "als.solve"
    assert trace_scope.innermost(
        "jit(fit)/while/body/als.item_half/als.assemble/jit(_take)/gather:",
        scopes) == "als.assemble"
    assert trace_scope.innermost("jit(fit)/while/body/div:", scopes) == "unscoped"
    assert trace_scope.innermost("", scopes) == "unscoped"
    assert trace_scope.innermost("jit(f)/xals.solve/add:", scopes) == "unscoped"


def test_an_operation_the_compiler_made_counts_as_unscoped():
    # whatever runs beside it: no guess by neighbours
    ops = {"/device:TPU:0": [
        (0, 100, "%while", "jit(f)/while:"),               # holds the rest
        (10, 20, "%fusion.1", "jit(f)/while/body/als.assemble/gather:"),
        (20, 50, "%dynamic-update-slice.9", "jit(f)/while:"),  # the compiler's
        (50, 60, "%fusion.2", "jit(f)/while/body/als.assemble/dot_general:"),
        (60, 70, "%copy.3", ""),
        (70, 90, "%custom-call.1", "jit(f)/while/body/als.solve/solve:")]}
    got = trace_scope.seconds_by_scope(ops, ["als.assemble", "als.solve"])
    assert {k: round(v * 1e9) for k, v in got.items()} == {
        "als.assemble": 20, "als.solve": 20, "unscoped": 60}


def test_seconds_by_scope_on_the_recorded_trace():
    ops = trace_scope.device_ops(SCOPED)
    topk = trace_scope.seconds_by_scope(ops, ["topk.score", "topk.select"])
    assert topk["topk.score"] == pytest.approx(3 * 0.532e-3, rel=0.01)
    assert topk["topk.select"] == pytest.approx(3 * 0.1279e-3, rel=0.01)
    als = trace_scope.seconds_by_scope(
        ops, ["als.exchange", "als.assemble", "als.solve"])
    assert als["als.exchange"] == 0.0 and als["als.solve"] > als["als.assemble"] > 0
    busy = sum(e - s for s, e in trace_reduce.merge(
        [op[:2] for op in ops["/device:TPU:0"]])) / 1e9
    # self times share out the busy time: nothing is counted twice or lost
    for total in (topk, als):
        assert sum(total.values()) == pytest.approx(busy, rel=1e-3)
    # a window clips: the first frame's device time lies before this one
    first = ops["/device:TPU:0"][0][0]
    clipped = trace_scope.seconds_by_scope(
        ops, ["topk.score", "topk.select"], (first + 1e6, first + 30e6))
    assert clipped["topk.score"] == pytest.approx(2 * 0.532e-3, rel=0.01)


def test_a_trace_without_the_scopes_reads_nothing():
    assert trace_scope.seconds_by_scope(
        trace_scope.device_ops(SMALL), ["topk.score", "topk.select"]) is None
    assert trace_scope.seconds_by_scope({}, ["topk.score"]) is None


# -- the traced rehearsal ---------------------------------------------------

def tiny_with_the_new_metrics():
    """The tests' tiny benchmark plus every per-layer metric the real one
    has and the tiny one lacks, on the tiny cells."""
    tiny, real = harness.load_json(TINY), harness.load_json(REPO, "BENCHMARK.json")
    have = {m["name"] for m in tiny["per_layer"]}
    cells = {"als-ml20m.retrain": "als-tiny.retrain",
             "bigann-t2i-10m.topk-paced": "t2i-tiny.topk-paced"}
    tiny["per_layer"] += [
        dict(m, workloads=[cells[c] for c in m["workloads"]])
        for m in real["per_layer"] if m["name"] not in have]
    return tiny


COUNTERS = {"paced_fetch_ms", "paced_turnaround_ms", "paced_reply_ms"}
FROM_THE_DEVICE = {"paced_starved_ms", "paced_score_ms", "paced_select_ms",
                   "als_exchange_s", "als_assemble_s", "als_solve_s",
                   "device_clock_lead_ms"}


def test_every_new_metric_has_its_file_and_its_entry():
    real = harness.load_json(REPO, "BENCHMARK.json")
    entries = {m["name"]: m for m in real["per_layer"]}
    assert COUNTERS | FROM_THE_DEVICE <= set(entries)
    for name in COUNTERS | FROM_THE_DEVICE:
        meta = harness.load_json(REPO, "benchmark", "metrics", name + ".json")
        assert (meta["unit"], meta["layer"], meta["moves"]) == tuple(
            entries[name][key] for key in ("unit", "layer", "moves"))
    # appended, so that what was there reads as unchanged
    assert {m["name"] for m in real["per_layer"][-10:]} == COUNTERS | FROM_THE_DEVICE


def test_traced_paced_rehearsal_reads_the_counters_not_the_device(monkeypatch):
    # a 20 ms coalescing window, so that the CPU's sub-millisecond frames
    # hold a dozen queries and some come back to a queue that is not empty
    monkeypatch.setenv("TPUMS_TOPK_BATCH_WAIT_US", "20000")
    line = harness.run_cell(tiny_with_the_new_metrics(),
                            "t2i-tiny.topk-paced", 3000000023, 2.0, 1)
    assert line["correct"] is True
    assert COUNTERS <= set(line["metrics"])
    assert not FROM_THE_DEVICE & set(line["metrics"])
    fetch, dispatch = (line["metrics"][name]["value"]
                       for name in ("paced_fetch_ms", "paced_dispatch_ms"))
    assert 0 < fetch <= dispatch
    assert line["metrics"]["paced_fetch_ms"]["n"] == \
        line["metrics"]["paced_dispatch_ms"]["n"]
    # the dispatcher's stages name the gaps with no edit to trace_reduce
    assert any(name.startswith("topk.")
               for name, _ in line["breakdown"]["idle_gaps"])


def test_traced_als_rehearsal_leaves_the_device_metrics_out():
    line = harness.run_cell(tiny_with_the_new_metrics(),
                            "als-tiny.retrain", 3000000029, 1.0, 1)
    assert line["correct"] is True
    assert "als_device_busy_s" in line["metrics"]
    assert not FROM_THE_DEVICE & set(line["metrics"])
