"""The cell `als-ml20m-bf16x.retrain-bf16x` (PR 49): its declaration, the
gather's byte model, and its rehearsal on the CPU at a tiny size
(`tiny-bf16x/`), sound and under each control.  `tests/test_als_bf16_exchange.py`
(tier-1) collects the rehearsal tests from here."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark import roofline_als_gather

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
TINY = os.path.join(REPO, "benchmark", "tests", "tiny-bf16x", "BENCHMARK.json")
TINY_CELL = "als-tiny-bf16x.retrain-bf16x"
CELL, SIBLING = "als-ml20m-bf16x.retrain-bf16x", "als-ml20m.retrain"


def load(*parts):
    with open(os.path.join(REPO, *parts)) as f:
        return json.load(f)


# -- the declaration ----------------------------------------------------------

def test_the_cell_is_the_siblings_shape_under_the_bfloat16_exchange():
    mine, sibling = (load("benchmark", "configs", name + ".json")
                     for name in ("als-ml20m-bf16x", "als-ml20m"))
    same = ("n_users", "n_items", "nnz", "rank", "lambda", "dtype",
            "assembly_precision", "assumed", "check_rows", "reduced")
    assert all(mine[key] == sibling[key] for key in same)
    assert (mine["exchange_dtype"], sibling["exchange_dtype"]) == ("bfloat16", None)
    assert mine["controls"] == {
        "bf16_state": {"overrides": {"dtype": "bfloat16"}},
        "f32_exchange": {"overrides": {"exchange_dtype": None}}}
    assert load("benchmark", "traffic", "retrain-bf16x.json")["driver"] == \
        "als_iterate_bf16x"


def test_the_cell_reports_what_the_sibling_reports_and_the_two_new_metrics():
    bench = load("BENCHMARK.json")
    cell = next(c for c in bench["workloads"] if c["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "als-ml20m-bf16x", "retrain-bf16x", 1)
    of = {m["name"]: m.get("workloads") for m in bench["per_layer"]}
    for name, cells in of.items():
        if cells and SIBLING in cells:
            assert CELL in cells, name
    assert CELL in of["als_contract_roofline"] and CELL in of["als_solve_roofline"]
    assert set(of["als_gather_roofline"]) == {SIBLING, CELL}
    assert [m["name"] for m in bench["per_layer"][-2:]] == [
        "als_einsum_share", "als_gather_roofline"]
    for entry in bench["per_layer"][-2:]:
        meta = load("benchmark", "metrics", entry["name"] + ".json")
        assert (meta["unit"], meta["layer"], meta["moves"]) == (
            entry["unit"], "ALS sweep", "train_iter_s")
    train = next(m for m in bench["end_to_end"] if m["name"] == "train_iter_s")
    assert CELL in train["workloads"]


def test_the_gathers_bytes_follow_the_stated_exchange():
    mine, sibling = (load("benchmark", "configs", name + ".json")
                     for name in ("als-ml20m-bf16x", "als-ml20m"))
    # each rating's opposite row and its index, once a half
    assert roofline_als_gather.als_gather(mine) == (
        0.0, 2 * 20_000_000 * (50 * 2 + 4))
    assert roofline_als_gather.als_gather(sibling) == (
        0.0, 2 * 20_000_000 * (50 * 4 + 4))


# -- the rehearsal ----------------------------------------------------------------

def rehearse(trace, *more, env=()):
    done = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--bench", TINY, "--workload",
         TINY_CELL, "--seed", "3000049019", "--seconds", "1", "--trace",
         str(trace), *more],
        cwd=REPO, env=dict(os.environ, JAX_PLATFORMS="cpu", **dict(env)),
        capture_output=True, text=True, timeout=300)
    shutil.rmtree(os.path.join(REPO, ".benchwork", TINY_CELL), ignore_errors=True)
    assert done.returncode == 0, done.stderr[-2000:]
    return json.loads(done.stdout.strip().splitlines()[-1])


ERRORS = {"als_first_user_rel_err", "als_first_item_rel_err",
          "als_last_user_rel_err", "als_last_item_rel_err"}


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_prints_the_contract_line(trace):
    line = rehearse(trace)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"  # a rehearsal says so
    assert ERRORS | {"als_item_factor_change"} <= {c["name"] for c in line["checks"]}
    if trace:
        got = line["metrics"]
        # the gauges' metrics need no chip; scopes and peaks do
        assert got["als_einsum_share"]["value"] == 100.0
        assert got["als_einsum_share"]["tpums_als_einsum_entries"] > 0
        assert got["als_segmented_share"]["value"] == 0.0
        assert {"als_prepare_s", "als_iter_median_s", "als_device_busy_s"} <= set(got)
        assert not {"als_iter_roofline", "als_contract_roofline",
                    "als_solve_roofline", "als_gather_roofline",
                    "als_exchange_s"} & set(got)
    else:
        assert set(line["metrics"]) == {"train_iter_s", "setup_s"}


@pytest.mark.parametrize("control, env", [
    ("f32_exchange", {}),
    # a CPU's own solver (LAPACK) takes no bfloat16: the chip's, interpreted
    ("bf16_state", {"FLINK_MS_ALS_SOLVER": "pallas"}),
])
def test_rehearsal_under_each_control_is_not_correct(control, env):
    line = rehearse(0, "--control", control, env=env)
    assert line["correct"] is False
    failed = {c["name"] for c in line["checks"] if not c["ok"]}
    assert {"als_first_user_rel_err", "als_last_user_rel_err"} <= failed <= ERRORS
