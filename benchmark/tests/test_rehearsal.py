"""CPU rehearsals of every driver through the real command, the device
rule, the controls, and a timed path broken underneath."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import run as harness
from benchmark.tests.conftest import REPO, TINY

CELLS = ["als-tiny.retrain", "t2i-tiny.topk-paced"]


def command(cell, trace, env_extra, *more):
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    env.update(env_extra)
    return subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--bench", TINY, "--workload",
         cell, "--seed", "3000000019", "--seconds", "2", "--trace", str(trace),
         *more],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_prints_the_contract_line(cell, trace):
    done = command(cell, trace, {"JAX_PLATFORMS": "cpu"})
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"  # a rehearsal says so
    assert {"kind", "count", "memory_peak_bytes"} <= set(line["device"])
    bench = harness.load_json(TINY)
    kind = "per_layer" if trace else "end_to_end"
    wanted = {m["name"] for m in harness.metrics_of(bench, cell, kind)}
    rooflines = {n for n in wanted if n.endswith("_roofline")}  # no CPU peak
    assert wanted - rooflines <= set(line["metrics"]) <= wanted
    for m in line["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    if trace:
        assert line["device"]["busy_s"] > 0 and line["device"]["window_s"] > 0
        assert len(line["breakdown"]["device_ops"]) <= 10
        assert len(line["breakdown"]["idle_gaps"]) <= 10


def test_no_accelerator_and_no_ask_for_the_host_fails():
    done = command(CELLS[0], 0, {"JAX_PLATFORMS": ""})
    assert done.returncode != 0
    assert not done.stdout.strip()  # no result line


def test_als_control_bf16_exchange_is_not_correct():
    done = command(CELLS[0], 0, {"JAX_PLATFORMS": "cpu"}, "--control", "bf16_exchange")
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] is False
    failed = {c["name"] for c in line["checks"] if not c["ok"]}
    assert failed and all(n.endswith("_rel_err") for n in failed)


def test_a_step_that_returns_its_state_unchanged_is_not_correct(monkeypatch):
    from flink_ms_tpu.ops import als

    real = als.compile_fit

    def broken(*a, **kw):
        fit_fn, dev_args = real(*a, **kw)
        calls = []

        def fit(iterations, uf, itf, *rest):
            calls.append(1)
            if len(calls) <= 2:  # set-up's two iterations are sound
                return fit_fn(iterations, uf, itf, *rest)
            return uf, itf

        return fit, dev_args

    monkeypatch.setattr(als, "compile_fit", broken)
    line = harness.run_cell(harness.load_json(TINY), CELLS[0], 7, 1.0, 0)
    assert line["correct"] is False
    failed = {c["name"] for c in line["checks"] if not c["ok"]}
    assert "als_last_user_rel_err" in failed and "als_item_factor_change" in failed


@pytest.mark.parametrize("cell", CELLS[1:])
def test_an_answer_altered_where_it_is_produced_is_not_correct(monkeypatch, cell):
    from flink_ms_tpu.serve import topk

    real = topk._format_topk  # every reply, single-query or frame, passes it

    def altered(results):
        results = list(results)
        return real([(results[1][0], results[0][1])] + results[1:])

    monkeypatch.setattr(topk, "_format_topk", altered)
    line = harness.run_cell(harness.load_json(TINY), cell, 9, 1.0, 0)
    assert line["correct"] is False
    failed = {c["name"] for c in line["checks"] if not c["ok"]}
    assert "topk_wrong_ids_at_clear_ranks" in failed
