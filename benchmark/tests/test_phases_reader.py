"""The `phases` reader on a hand-made phase log, under a program without
one, and in the CPU rehearsal of one ALS and one TOPK cell
(`tiny-phases/BENCHMARK.json`: the tiny cells with the set-up metrics of
PR 35 on their lists)."""

import json
import os
import subprocess
import sys
import types

import pytest

from benchmark import run as harness
from benchmark.readers import phases
from benchmark.tests.conftest import REPO
from flink_ms_tpu.obs import tracing

BENCH = os.path.join(REPO, "benchmark", "tests", "tiny-phases", "BENCHMARK.json")


def entry(name, start, end, parent=None, thread=1):
    return {"name": name, "start": start, "end": end, "parent": parent,
            "thread": thread}


LOG = [
    entry("device.backend", 1.0, 9.0),
    entry("als.prepare.order", 10.0, 12.0, "als.prepare"),
    entry("als.prepare.fill", 12.0, 19.5, "als.prepare"),
    entry("als.prepare", 10.0, 20.0),
    entry("als.place", 20.0, 23.0),
    entry("topk.build", 30.0, 31.0, thread=2),   # a rebuild thread's root
    entry("topk.build", 33.0, 35.0, thread=2),
    entry("late.child", 96.0, 99.0, "late"),     # ended before the window...
    entry("late", 95.0, 101.0),                  # ...its parent after it opened
    entry("in.window", 102.0, 103.0),
]


@pytest.fixture
def run(monkeypatch):
    monkeypatch.setattr(tracing, "phase_log", lambda: list(LOG))
    return types.SimpleNamespace(window=(100.0, None), clock={"setup_s": 99.0})


def test_a_named_phase_reads_its_seconds_self_time_and_children(run):
    value, extra = phases.read(run, name="als.prepare")
    assert value == 10.0
    assert extra == {"n": 1, "self_s": 0.5, "children": {
        "als.prepare.order": 2.0, "als.prepare.fill": 7.5}}
    value, extra = phases.read(run, name="topk.build")  # two builds: summed
    assert (value, extra["n"], extra["children"]) == (3.0, 2, {})


def test_a_phase_that_ends_after_the_window_opened_is_left_out(run):
    assert phases.read(run, name="late") is None
    assert phases.read(run, name="in.window") is None
    assert phases.read(run, name="late.child")[0] == 3.0  # it did end in set-up
    assert phases.read(run, name="no.such.phase") is None


def test_roots_leave_the_backend_out_and_account_for_all_of_setup(run):
    value, extra = phases.read(run, roots=True)
    # als.prepare 10 + als.place 3 + topk.build 3; a child whose parent has
    # not ended is no root
    assert value == 16.0
    assert extra["by_phase"] == {"device.backend": 8.0, "als.prepare": 10.0,
                                 "als.place": 3.0, "topk.build": 3.0}
    assert value + extra["by_phase"]["device.backend"] + extra["outside_s"] \
        == run.clock["setup_s"]


def test_a_program_without_a_phase_log_reads_nothing(run, monkeypatch):
    monkeypatch.delattr(tracing, "phase_log")
    assert phases.read(run, name="als.prepare") is None
    assert phases.read(run, roots=True) is None


def test_a_process_in_which_no_phase_ended_reads_nothing(run, monkeypatch):
    monkeypatch.setattr(tracing, "phase_log", lambda: [])
    assert phases.read(run, roots=True) is None


def test_every_setup_metric_of_pr35_has_its_file_and_its_entry():
    bench = harness.load_json(REPO, "BENCHMARK.json")
    by_name = {m["name"]: m for m in bench["per_layer"]}
    cells = [w["name"] for w in bench["workloads"]]
    als = [c for c in cells if c.endswith("retrain")]
    topk = [c for c in cells if ".topk-" in c]
    wanted = {
        "trace_s": cells, "lower_s": cells, "cache_load_s": cells,
        "setup_program_s": cells,
        "als_order_s": als, "als_fill_s": als, "als_place_s": als,
        "index_place_s": topk, "index_ids_s": topk,
        "index_warm_scatter_s": topk, "index_warm_programs_s": topk,
        "index_pad_s": [c for c in topk if c.endswith("4chip")],
        "cocoa_gram_build_s": ["rcv1-cocoa.cocoa-rounds"],
        "cocoa_place_s": ["rcv1-cocoa.cocoa-rounds"],
    }
    for name, on in wanted.items():
        meta = harness.load_json(REPO, "benchmark", "metrics", name + ".json")
        spec = by_name[name]
        assert sorted(spec["workloads"]) == sorted(on), name
        assert spec["moves"] == meta["moves"] == "setup_s"
        assert spec["layer"] == meta["layer"] and spec["unit"] == meta["unit"] == "s"
        assert meta["reader"] in ("phases", "metrics_diff")


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", ["als-tiny.retrain", "t2i-tiny.topk-paced"])
def test_rehearsal_splits_setup_by_the_programs_phases(cell, trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--bench", BENCH, "--workload",
         cell, "--seed", "3000000035", "--seconds", "1", "--trace", str(trace)],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] is True
    # the readers need no trace: an untraced run has them under `layers`
    got = line["metrics"] if trace else line["layers"]
    wanted = {m["name"] for m in harness.metrics_of(
        harness.load_json(BENCH), cell, "per_layer")}
    assert wanted == set(got)
    setup = got["setup_program_s"]
    assert setup["value"] > 0 and setup["outside_s"] >= 0
    if not trace:
        assert (setup["value"] + setup["by_phase"]["device.backend"]
                + setup["outside_s"]) == pytest.approx(
                    line["metrics"]["setup_s"]["value"], abs=1e-9)
    assert got["backend_init_s"]["value"] >= setup["by_phase"]["device.backend"]
    for name in ("trace_s", "lower_s"):
        assert got[name]["value"] > 0
    assert got["cache_load_s"]["value"] == 0  # no persistent cache on the host
    if cell.startswith("als"):
        parts = got["als_order_s"]["value"] + got["als_fill_s"]["value"]
        assert parts <= got["als_prepare_s"]["value"]
        assert set(setup["by_phase"]) == {
            "device.backend", "als.prepare", "als.place", "als.sweep"}
    else:
        parts = sum(got[n]["value"] for n in (
            "index_place_s", "index_ids_s", "index_warm_scatter_s"))
        assert parts <= got["index_build_s"]["value"]
        assert set(setup["by_phase"]) == {
            "device.backend", "topk.build", "topk.warm"}
