"""The cell `rcv1-cocoa.cocoa-rounds` (PR 31): its entries found by name, the
counts of its roofline worked by hand, a CPU rehearsal through the real
command (`tiny-cocoa/BENCHMARK.json`, the configuration's laws at 1,300 rows),
the lower-precision control, a round broken underneath, and the new readers
where the program has nothing for them."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import roofline_cocoa
from benchmark import run as harness
from benchmark.readers import gauge_ratio, trace_roofline_of
from benchmark.tests.conftest import REPO

TINY = os.path.join(REPO, "benchmark", "tests", "tiny-cocoa", "BENCHMARK.json")
CELL = "rcv1-cocoa.cocoa-rounds"
TINY_CELL = "rcv1-tiny.cocoa-rounds"
NEW = {"cocoa_prepare_s", "cocoa_build_s", "cocoa_round_median_s",
       "cocoa_device_busy_s", "cocoa_margins_s", "cocoa_steps_s", "cocoa_dw_s",
       "cocoa_combine_s", "cocoa_pad_share", "cocoa_round_roofline"}
NEEDS_A_DEVICE_PLANE = {"cocoa_margins_s", "cocoa_steps_s", "cocoa_dw_s",
                        "cocoa_combine_s", "cocoa_round_roofline"}
COMPARED = {"cocoa_first_w_rel_err", "cocoa_first_alpha_rel_err",
            "cocoa_last_w_rel_err", "cocoa_primal_dual_rel_err",
            "cocoa_box_violation", "cocoa_w_change", "cocoa_objective_drop"}


def by_name(entries):
    return {e["name"]: e for e in entries}


# -- the entries, by name ----------------------------------------------------

def test_the_cell_its_configuration_and_its_metrics_are_declared():
    bench = harness.load_json(REPO, "BENCHMARK.json")
    cell = by_name(bench["workloads"])[CELL]
    assert (cell["chips"], cell["config"], cell["traffic"]) == (
        1, "rcv1-cocoa", "cocoa-rounds")
    assert len(cell["why"]) <= 200
    entry = by_name(bench["configs"])[cell["config"]]
    cfg = harness.load_json(REPO, entry["file"])
    assert len(entry["source"]) <= 200 and entry["source"] == cfg["source"]
    assert entry["reduced"] == cfg["reduced"] == []
    # the source's shape, uncut
    assert (cfg["rows"], cfg["features"], cfg["nnz"], cfg["regularization"]) == (
        677399, 47236, 49556258, 1e-6)
    assert cfg["blocks"] * cfg["local_iterations"] == 679936  # 83 rows a chain
    assert -(-cfg["rows"] // cfg["blocks"]) == cfg["local_iterations"] == 83
    assert cfg["controls"] == {"bf16_state": {"overrides": {"dtype": "bfloat16"}}}
    assert {k for k in cfg["limits"]} == {
        n + "_min" if n in ("cocoa_w_change", "cocoa_objective_drop") else n
        for n in COMPARED}
    traffic = harness.load_json(REPO, "benchmark", "traffic", cell["traffic"] + ".json")
    assert traffic["driver"] == "cocoa_rounds"
    end_to_end = {m["name"] for m in harness.metrics_of(bench, CELL, "end_to_end")}
    assert end_to_end == {"train_iter_s", "setup_s"}
    per_layer = by_name(harness.metrics_of(bench, CELL, "per_layer"))
    assert NEW | {"backend_init_s", "compile_s"} <= set(per_layer)
    for name in NEW:
        meta = harness.load_json(REPO, "benchmark", "metrics", name + ".json")
        assert (meta["unit"], meta["layer"], meta["moves"]) == tuple(
            per_layer[name][key] for key in ("unit", "layer", "moves"))
        assert per_layer[name]["workloads"] == [CELL]
    # the tiny benchmark rehearses every metric the cell reports
    tiny = harness.load_json(TINY)
    assert set(by_name(harness.metrics_of(tiny, TINY_CELL, "per_layer"))) == set(per_layer)
    small = harness.load_json(REPO, tiny["configs"][0]["file"])
    for key in ("regularization", "stepsize", "mode", "inner", "dtype", "limits",
                "controls", "guarantees"):
        assert small[key] == cfg[key], key  # the same laws and the same limits


def test_roofline_counts_the_sources_entries_not_the_padded_arrays():
    cfg = harness.load_json(REPO, "benchmark", "configs", "rcv1-cocoa.json")
    flops, nbytes = roofline_cocoa.cocoa_round(cfg)
    # two passes over 49,556,258 entries of 8 B; 8192 chains x 83 steps x one
    # Gram row of 83 floats; w read and written; alpha read and written,
    # labels and norms read, 677,399 floats each
    assert nbytes == (2 * 49556258 * 8 + 8192 * 83 * 83 * 4
                      + 2 * 47236 * 4 + 4 * 677399 * 4)
    assert flops == 4 * 49556258 + 8192 * 83 * (2 * 83 + 12)
    assert 1.0e9 < nbytes < 1.1e9
    # bytes-bound on a v5e: 1.26 ms against 1.6 us
    assert nbytes / 819e9 > 500 * flops / 197e12
    # the clip moves the program's arrays, not the algorithm's bytes
    wider = dict(cfg, assumed=dict(cfg["assumed"], row_length_clip=1024))
    assert roofline_cocoa.cocoa_round(wider) == (flops, nbytes)


# -- the rehearsal -----------------------------------------------------------

def command(trace, *more):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--bench", TINY, "--workload",
         TINY_CELL, "--seed", "3000000019", "--seconds", "1", "--trace", str(trace),
         *more],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_prints_the_contract_line(trace):
    done = command(trace)
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"
    assert COMPARED <= {c["name"] for c in line["checks"]}
    read = line["metrics"] if trace else line["layers"]
    without_a_trace = NEW - NEEDS_A_DEVICE_PLANE - {"cocoa_device_busy_s"}
    assert without_a_trace <= set(read)
    assert not NEEDS_A_DEVICE_PLANE & set(read)  # no device plane on the host
    pad = read["cocoa_pad_share"]
    # 1,300 rows in 16 chains of 82: 1,312 slots of 96, 26,000 real entries
    assert (pad["tpums_svm_rows"], pad["tpums_svm_row_width"]) == (1312, 96)
    assert pad["tpums_svm_pad_entries"] == 1312 * 96 - 26000
    assert pad["value"] == pytest.approx(100 * (1 - 26000 / (1312 * 96)))
    # one round is enqueued ahead of the last one counted
    assert pad["tpums_svm_rounds_total"] == line["attempted"] + 1
    assert read["cocoa_round_median_s"]["n"] == line["attempted"]
    if trace:
        assert "cocoa_device_busy_s" in read and line["device"]["busy_s"] > 0
    else:
        assert set(line["metrics"]) == {"train_iter_s", "setup_s"}
        assert line["metrics"]["train_iter_s"]["n"] == line["attempted"]


def test_control_bf16_state_is_not_correct():
    done = command(0, "--control", "bf16_state")
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] is False
    failed = {c["name"] for c in line["checks"] if not c["ok"]}
    assert {"cocoa_first_w_rel_err", "cocoa_first_alpha_rel_err"} <= failed
    assert all(n.endswith("_rel_err") for n in failed)


def test_a_round_that_returns_its_state_unchanged_is_not_correct(monkeypatch):
    from flink_ms_tpu.ops import svm

    real = svm.compile_svm_fit

    def broken(*a, **kw):
        fit, dev_args = real(*a, **kw)
        calls = []

        def lazy(rounds, *args, start=0):
            calls.append(start)
            if len(calls) <= 2:  # set-up's two rounds are sound
                return fit(rounds, *args, start=start)
            return args[0], args[5]

        return lazy, dev_args

    monkeypatch.setattr(svm, "compile_svm_fit", broken)
    line = harness.run_cell(harness.load_json(TINY), TINY_CELL, 5, 0.2, 0)
    failed = {c["name"] for c in line["checks"] if not c["ok"]}
    assert line["correct"] is False
    assert {"cocoa_last_w_rel_err", "cocoa_w_change"} <= failed
    assert "cocoa_first_w_rel_err" not in failed


# -- the readers where there is nothing to read --------------------------------

class FakeRun:
    def __init__(self, busy_s=None, kind="TPU v5 lite", platform="tpu", gauges=()):
        self.red = {"busy_s": busy_s} if busy_s else None
        self.counts = {"iterations": 2}
        self.config = harness.load_json(REPO, "benchmark", "configs", "rcv1-cocoa.json")
        self.devices = [type("D", (), {"device_kind": kind, "platform": platform})()]
        self.snap_after = {"gauges": [
            {"name": n, "labels": {}, "value": v} for n, v in gauges]}

    def reduced_trace(self):
        return self.red

    def load(self, *parts):
        return harness.load_json(REPO, "benchmark", *parts)

    def counter(self, name, at_open=False):
        return 3 if at_open else 11


ROOFLINE = dict(module="roofline_cocoa", model="cocoa_round", per="iterations")


def test_roofline_share_is_the_bytes_time_over_the_busy_time_of_a_round():
    share, extra = trace_roofline_of.read(FakeRun(busy_s=5.6), **ROOFLINE)
    nbytes = roofline_cocoa.cocoa_round(FakeRun().config)[1]
    assert extra["bound"] == "bytes" and extra["bytes"] == nbytes
    assert share == pytest.approx(100 * (nbytes / 819e9) / 2.8)
    assert share < 0.1


def test_roofline_reader_returns_nothing_without_a_trace_or_a_peak():
    assert trace_roofline_of.read(FakeRun(), **ROOFLINE) is None
    assert trace_roofline_of.read(
        FakeRun(busy_s=1.0, kind="cpu", platform="cpu"), **ROOFLINE) is None
    with pytest.raises(ValueError, match="no published peak"):
        trace_roofline_of.read(FakeRun(busy_s=1.0, kind="TPU v9"), **ROOFLINE)


def test_pad_share_reads_the_gauges_or_nothing():
    meta = harness.load_json(REPO, "benchmark", "metrics", "cocoa_pad_share.json")
    run = FakeRun(gauges=[("tpums_svm_pad_entries", 124507358.0),
                          ("tpums_svm_rows", 679936.0),
                          ("tpums_svm_row_width", 256.0)])
    value, extra = gauge_ratio.read(run, **meta["args"])
    assert value == pytest.approx(100 * (1 - 49556258 / (679936 * 256)))
    assert extra["tpums_svm_rounds_total"] == 8
    # the parent's program has no such gauge: the metric is left out
    assert gauge_ratio.read(FakeRun(), **meta["args"]) is None
