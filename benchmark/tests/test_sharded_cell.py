"""The four-chip cell `bigann-t2i-100m-1of6.topk-paced-4chip` (PR 27): its
entries found by name, a four-device CPU rehearsal through the real command
(`tiny4/BENCHMARK.json`), the new readers on hand-made four-plane traces, and
the lower-precision control against the committed limits."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark import reference, roofline, roofline_sharded, synth
from benchmark import run as harness
from benchmark.readers import gauge_ratio, trace_shard_roofline, trace_shard_skew
from benchmark.tests.conftest import REPO
from benchmark.tests.test_reference import bf16

TINY4 = os.path.join(REPO, "benchmark", "tests", "tiny4", "BENCHMARK.json")
CELL = "bigann-t2i-100m-1of6.topk-paced-4chip"
TINY_CELL = "t2i-tiny-4dev.topk-paced-4chip"
SHARDED = {"sharded_score_ms", "sharded_select_ms", "sharded_merge_ms",
           "sharded_skew_ms", "sharded_frame_roofline", "sharded_pad_share"}
FIRST, LAST = "topk.shard_score", "topk.merge"


def by_name(entries):
    return {e["name"]: e for e in entries}


# -- the entries, by name ----------------------------------------------------

def test_the_cell_its_configuration_and_its_metrics_are_declared():
    bench = harness.load_json(REPO, "BENCHMARK.json")
    cell = by_name(bench["workloads"])[CELL]
    assert (cell["chips"], cell["config"], cell["traffic"]) == (
        4, "bigann-t2i-100m-1of6", "topk-paced-4chip")
    assert len(cell["why"]) <= 200
    entry = by_name(bench["configs"])[cell["config"]]
    cfg = harness.load_json(REPO, entry["file"])
    assert len(entry["source"]) <= 200 and entry["source"] == cfg["source"]
    assert entry["reduced"] == cfg["reduced"] == ["rows"]
    assert cfg["rows"] == -(-cfg["published_rows"] // 6) == 16666667
    same = harness.load_json(REPO, "benchmark", "configs", "bigann-t2i-10m.json")
    for key in ("rank", "k", "dtype", "distance", "score_precision", "env",
                "guarantees", "assumed", "check_queries", "controls"):
        assert cfg[key] == same[key], key  # widths and laws uncut
    traffic = harness.load_json(REPO, "benchmark", "traffic", cell["traffic"] + ".json")
    assert (traffic["driver"], traffic["connections"], traffic["pool"]) == (
        "topk_open", 64, 4096)
    end_to_end = {m["name"] for m in harness.metrics_of(bench, CELL, "end_to_end")}
    assert end_to_end == {"topk_p50_ms", "setup_s"}
    per_layer = by_name(harness.metrics_of(bench, CELL, "per_layer"))
    assert SHARDED <= set(per_layer)
    # one chip's bandwidth against the whole catalog would read past 100%
    assert "paced_frame_roofline" not in per_layer
    for name in SHARDED:
        meta = harness.load_json(REPO, "benchmark", "metrics", name + ".json")
        assert (meta["unit"], meta["layer"], meta["moves"]) == tuple(
            per_layer[name][key] for key in ("unit", "layer", "moves"))
        assert per_layer[name]["workloads"] == [CELL]


# -- the rehearsal -----------------------------------------------------------

@pytest.mark.parametrize("trace", [0, 1])
def test_four_device_rehearsal_prints_the_contract_line(trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    done = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--bench", TINY4, "--workload",
         TINY_CELL, "--seed", "3000000019", "--seconds", "2", "--trace", str(trace)],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert (line["device"]["platform"], line["device"]["count"]) == ("cpu", 4)
    read = line["metrics"] if trace else line["layers"]
    pad = read["sharded_pad_share"]
    # 4,099 rows: 1,025 a shard, padded to 2,048
    assert pad["tpums_topk_shards"] == 4 and pad["tpums_topk_shard_rows"] == 2048
    assert pad["tpums_topk_pad_rows"] == 4 * 2048 - 4099
    assert pad["value"] == pytest.approx(100 * 4093 / 8192)
    assert pad["tpums_topk_sharded_frames_total"] > 0
    # no device plane on the host: the trace readers leave their metrics out
    assert not (SHARDED - {"sharded_pad_share"}) & set(read)
    if trace:
        assert {"paced_fetch_ms", "paced_dispatch_ms", "index_build_s"} <= set(read)
    else:
        assert set(line["metrics"]) == {"topk_p50_ms", "setup_s"}


# -- the readers, on hand-made planes ----------------------------------------

def op(start, dur, scope):
    path = f"jit(sharded_topk)/shard_map/{scope}/op:" if scope else "jit(sharded_topk)/copy:"
    return (float(start), float(start + dur), "%" + (scope or "copy"), path)


def chip(offset, score=4600, select=1200, merge=100, wait=0, frames=3, period=10000):
    """One plane: `frames` launches, each a copy, then score, select, a wait
    for the others inside the collective, and the merge."""
    ops = []
    for f in range(frames):
        t = 1000 + f * period + offset
        ops += [op(t - 50, 20, None), op(t, score, FIRST),
                op(t + score, select, "topk.shard_select"),
                op(t + score + select + wait, merge, LAST),
                op(t + score + select + wait + merge, 10, None)]
    return ops


def test_skew_is_the_span_less_the_mean_busy_time():
    # chips start 0, 100, 200, 300 ns apart; the early ones wait in the
    # merge for the last, so every merge ends at the same instant
    per_device = {f"/device:TPU:{i}": chip(100 * i, wait=300 - 100 * i)
                  for i in range(4)}
    rows = trace_shard_skew.matched(per_device, FIRST, LAST)
    assert len(rows) == 3 and all(len(row) == 4 for row in rows)
    for row in rows:
        span = max(f[1] for f in row) - min(f[0] for f in row)
        assert span == 4600 + 1200 + 300 + 100
        assert [f[2] for f in row] == [5900.0] * 4  # the wait is not busy time
    # a window that cuts the first chip's first frame drops that frame on all
    cut = trace_shard_skew.matched(per_device, FIRST, LAST, window=(1050, 40000))
    assert len(cut) == 2 and cut[0][0][0] == 11000


def test_frames_are_matched_by_time_not_by_position():
    per_device = {f"/device:TPU:{i}": chip(100 * i) for i in range(4)}
    # the trace caught one launch more on the last chip, before the others' first
    per_device["/device:TPU:3"] = chip(300 - 10000, frames=4)
    rows = trace_shard_skew.matched(per_device, FIRST, LAST)
    assert len(rows) == 3
    assert [row[3][0] - row[0][0] for row in rows] == [300.0] * 3


def test_one_plane_or_no_scope_reads_nothing():
    assert trace_shard_skew.matched({"/device:TPU:0": chip(0)}, FIRST, LAST) == []
    plain = [op(1000, 500, None), op(2000, 500, None)]
    assert trace_shard_skew.matched(
        {"/device:TPU:0": plain, "/device:TPU:1": plain}, FIRST, LAST) == []
    # the parent's program: the single-device scopes, not the sharded ones
    old = [op(1000, 500, "topk.score"), op(1500, 100, "topk.select")]
    assert trace_shard_skew.plane_frames(old, FIRST, LAST) == []


class FakeRun:
    """What the two readers take from a `Run`."""

    def __init__(self, per_device, gauges=(), config=None, batch=6.0):
        self.trace_path = "made-up"
        self.per_device = per_device
        self.snap_after = {"gauges": [
            {"name": n, "labels": {}, "value": v} for n, v in gauges]}
        self.config = config
        self.batch = batch
        self.devices = [type("D", (), {"device_kind": "TPU v5 lite", "platform": "tpu"})()]

    def load(self, *parts):
        return harness.load_json(REPO, "benchmark", *parts)

    def hist_delta(self, name):
        return (self.batch * 10, 10)

    def counter(self, name, at_open=False):
        return 5 if at_open else 25


def test_the_readers_on_a_four_plane_trace(monkeypatch):
    per_device = {f"/device:TPU:{i}": chip(100 * i, score=4600 + 50 * i,
                                           wait=300 - 150 * i if i < 2 else 0)
                  for i in range(4)}
    monkeypatch.setattr(trace_shard_skew, "frames_of",
                        lambda run, first, last: trace_shard_skew.matched(
                            run.per_device, first, last))
    monkeypatch.setattr(trace_shard_skew, "launch_spread_ms", lambda run: 0.05)
    cfg = harness.load_json(REPO, "benchmark", "configs", "bigann-t2i-100m-1of6.json")
    run = FakeRun(per_device, config=cfg)
    skew, extra = trace_shard_skew.read(run, FIRST, LAST)
    assert (extra["planes"], extra["n"]) == (4, 3)
    assert extra["start_spread_ms"] == pytest.approx(300e-6)
    assert extra["slowest_busy_ms"] == pytest.approx((4750 + 1200 + 100) * 1e-6)
    assert skew == pytest.approx(extra["span_ms"] - extra["busy_ms"]) and skew > 0
    share, extra = trace_shard_roofline.read(
        run, "topk_shard_frame", FIRST, LAST, "tpums_topk_batch_size")
    flops, nbytes = roofline_sharded.topk_shard_frame(cfg, 6.0, 4)
    assert extra["bound"] == "bytes" and extra["shards"] == 4
    assert share == pytest.approx(100 * nbytes / 819e9 / 6050e-9)
    # one chip's bytes, not the catalog's: a quarter of `roofline.topk_frame`'s
    whole = roofline.topk_frame(cfg, 6.0)[1]
    assert nbytes == pytest.approx(whole / 4, rel=1e-4)
    assert nbytes > 4166667 * 200 * 4


def test_pad_share_reads_the_gauges_or_nothing():
    args = dict(part="tpums_topk_pad_rows",
                of=["tpums_topk_shards", "tpums_topk_shard_rows"], scale=100.0,
                counters=["tpums_topk_sharded_frames_total"])
    run = FakeRun({}, gauges=[("tpums_topk_pad_rows", 110549.0),
                              ("tpums_topk_shards", 4.0),
                              ("tpums_topk_shard_rows", 4194304.0)])
    value, extra = gauge_ratio.read(run, **args)
    assert value == pytest.approx(100 * 110549 / 16777216)
    assert extra["tpums_topk_sharded_frames_total"] == 20
    assert gauge_ratio.read(FakeRun({}), **args) is None  # the parent: no gauge
    empty = FakeRun({}, gauges=[("tpums_topk_pad_rows", 0.0), ("tpums_topk_shards", 1.0),
                                ("tpums_topk_shard_rows", 0.0)])
    assert gauge_ratio.read(empty, **args) is None


# -- the control -------------------------------------------------------------

def test_one_pass_bf16_scoring_fails_this_configurations_limits():
    """`bf16_score` at a size the CPU holds: the reference in the program's
    place, scored as one bf16 MXU pass would, over a catalog padded and
    biased as the sharded layout pads it.  It has to miss the committed
    score limit; full f32 has to sit well inside it."""
    cfg = harness.load_json(REPO, "benchmark", "configs", "bigann-t2i-100m-1of6.json")
    assert cfg["controls"]["bf16_score"]["patch"] == {
        "flink_ms_tpu.serve.topk._SCORE_PRECISION": "default"}
    cfg = dict(cfg, rows=(1 << 16) + 3)
    _, rows = synth.catalog(cfg, 7)
    q = synth.queries(7, 16, cfg["rank"])
    ref_ids, ref_scores = reference.topk(rows, q, cfg["k"])
    padded = np.zeros((4 * (1 << 15), cfg["rank"]), np.float32)
    padded[:len(rows)] = rows
    bias = np.where(np.arange(len(padded)) < len(rows), 0.0, -1e30).astype(np.float32)
    lim = cfg["limits"]
    for scores, sound in ((bf16(q) @ bf16(padded).T + bias, False),
                          (q @ padded.T + bias, True)):
        order = np.argsort(-scores, axis=1)[:, :cfg["k"]]
        err, wrong, _ = reference.compare_topk(
            order, np.take_along_axis(scores, order, 1), ref_ids, ref_scores,
            lim["topk_gap"])
        assert order.max() < len(rows)
        if sound:
            assert err < lim["topk_score_abs_err"] / 3 and wrong == 0
        else:
            assert err > 3 * lim["topk_score_abs_err"]


# -- the one-chip traced rehearsals, with cells mapped by name ----------------
# `test_trace_readers.tiny_with_the_new_metrics` maps every cell of a real
# metric's `workloads` through a two-entry dict, so it raises KeyError since
# this cell's name was appended to those lists (ROADMAP D12).  The same two
# rehearsals, dropping the cells the tiny benchmark has no copy of:

ONE_CHIP = {"als-ml20m.retrain": "als-tiny.retrain",
            "bigann-t2i-10m.topk-paced": "t2i-tiny.topk-paced"}


def tiny_with_every_metric_it_can_run():
    from benchmark.tests.conftest import TINY

    tiny, real = harness.load_json(TINY), harness.load_json(REPO, "BENCHMARK.json")
    have = set(by_name(tiny["per_layer"]))
    for m in real["per_layer"]:
        cells = [ONE_CHIP[c] for c in m["workloads"] if c in ONE_CHIP]
        if m["name"] not in have and cells:
            tiny["per_layer"].append(dict(m, workloads=cells))
    return tiny


@pytest.mark.parametrize("cell, wanted", [
    ("t2i-tiny.topk-paced", {"paced_fetch_ms", "paced_turnaround_ms", "paced_reply_ms"}),
    ("als-tiny.retrain", {"als_device_busy_s"})])
def test_one_chip_traced_rehearsals_still_read_their_metrics(monkeypatch, cell, wanted):
    monkeypatch.setenv("TPUMS_TOPK_BATCH_WAIT_US", "20000")  # frames of a dozen
    bench = tiny_with_every_metric_it_can_run()
    assert not SHARDED & {m["name"] for m in harness.metrics_of(bench, cell, "per_layer")}
    line = harness.run_cell(bench, cell, 3000000031, 2.0, 1)
    assert line["correct"] is True
    assert wanted <= set(line["metrics"])
    from_the_device = {m["name"] for m in bench["per_layer"]
                       if m["source"] == "device_trace"} - {"als_device_busy_s"}
    assert not from_the_device & set(line["metrics"])  # no device plane here
