"""Bench harness integrity: the section dispatch table in bench.py must
reference real functions in bench_sections.py, the tiny-config serving
pipeline must produce its metric keys without error keys, and the device
policy holds: no accelerator and no ask for the host -> failure; an unknown
device kind -> no peak; a failed section -> the line, then a non-zero exit."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def test_section_table_names_resolve():
    import ast

    import bench_sections

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    tree = ast.parse(open(os.path.join(root, "bench.py")).read())
    names = [
        n.value for n in ast.walk(tree)
        if isinstance(n, ast.Constant) and isinstance(n.value, str)
        and n.value.startswith("run_") and n.value.endswith("_section")
    ]
    assert names, "section table not found in bench.py"
    for fn_name in names:
        assert callable(getattr(bench_sections, fn_name, None)), fn_name


@pytest.mark.slow
def test_stdout_is_exactly_one_json_line(tmp_path):
    """bench.py stdout is THE artifact, and a log tail must hold it: exactly
    one line, parseable, COMPACT, naming its device, with the full section
    detail in the BENCH_DETAIL.json sidecar."""
    import json
    import subprocess

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    detail = tmp_path / "BENCH_DETAIL.json"
    ambient = {k: v for k, v in os.environ.items()
               if not k.startswith("BENCH_")}
    env = dict(ambient,
               BENCH_SECTIONS="als,svm,serving,svmserve",
               BENCH_DETAIL_PATH=str(detail),
               JAX_PLATFORMS="cpu", BENCH_SMALL="1", BENCH_SKIP_CPU="1",
               BENCH_NNZ="2000", BENCH_USERS="100", BENCH_ITEMS="50",
               BENCH_RANK="4", BENCH_SVM_EXAMPLES="400",
               BENCH_SVM_FEATURES="60", BENCH_SVM_ROUNDS="2",
               BENCH_SERVE_USERS="40", BENCH_SERVE_ITEMS="30",
               BENCH_SERVE_K="4", BENCH_SERVE_QUERIES="10",
               BENCH_SERVE_TOPK_QUERIES="2", BENCH_SGD_RATINGS="10",
               BENCH_MSE_RATINGS="10", BENCH_SHARD_WORKERS="2",
               BENCH_SVMSERVE_FEATURES="50", BENCH_SVMSERVE_QUERIES="5")
    proc = subprocess.run(
        [sys.executable, "bench.py"], cwd=root, env=env,
        capture_output=True, text=True, timeout=1200,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    assert len(lines) == 1, f"stdout polluted: {lines[:5]}"
    assert len(lines[0]) <= 1800, (
        f"compact line {len(lines[0])}B outgrew the driver tail window"
    )
    parsed = json.loads(lines[0])
    assert "metric" in parsed and "value" in parsed
    # JAX_PLATFORMS=cpu asked for the host: every line says so, and no
    # device metric is computed from it
    assert parsed["platform"] == "cpu" and parsed["device_kind"] == "cpu"
    assert parsed["mfu"] is None
    full = json.loads(detail.read_text())
    assert parsed["detail"] == "BENCH_DETAIL.json"
    # the sidecar is a superset of the compact line
    for k, v in parsed.items():
        if k not in ("detail", "section_errors"):
            assert full[k] == v, k
    assert "serving_get_p50_ms" in full  # detail-only key


def test_emit_artifact_compact_even_when_result_is_huge(tmp_path, monkeypatch):
    """A result dict far bigger than the driver's stdout-tail window must
    still render to a short parseable line, with everything in the sidecar."""
    import json

    import bench

    monkeypatch.setattr(bench, "_DETAIL_PATH", str(tmp_path / "d.json"))
    result = {"metric": "als_ml20m_sec_per_iter", "value": 1.0,
              "unit": "s/iter", "vs_baseline": 2.0, "platform": "tpu"}
    result.update({f"extra_key_{i}": i * 0.123 for i in range(200)})
    result["svm_error"] = "boom\n" * 50
    line = bench.emit_artifact(result)
    assert len(line) <= 1800
    parsed = json.loads(line)
    assert parsed["metric"] == "als_ml20m_sec_per_iter"
    assert parsed["section_errors"] == ["svm_error"]
    full = json.loads((tmp_path / "d.json").read_text())
    assert full["extra_key_199"] == 199 * 0.123


@pytest.mark.slow
def test_tiny_als_section_records_resolved_knobs(monkeypatch):
    """The ALS section artifact records RESOLVED kernel knobs (solver,
    exchange dtype) — not raw 'auto' markers — and the exchange A/B
    sections stay off on CPU runs."""
    for k, v in {
        "BENCH_USERS": "300", "BENCH_ITEMS": "200", "BENCH_NNZ": "5000",
        "BENCH_RANK": "4", "BENCH_ITERS": "2", "BENCH_SKIP_CPU": "1",
        "BENCH_SKIP_QUALITY": "1",
    }.items():
        monkeypatch.setenv(k, v)
    import jax

    from bench import run_als_section

    out = run_als_section(jax.devices()[:1], "cpu", True)
    assert out["als_solver"] == "lax"
    assert out["als_exchange_dtype"] == "f32"
    assert out["value"] > 0
    for key in ("als_bf16_sec_per_iter", "als_f32_sec_per_iter",
                "als_exchange_ab_error"):
        assert key not in out, key


@pytest.mark.slow
def test_tiny_serving_section_clean(monkeypatch):
    """Serving section at a tiny config: all metric families present, no
    *_error keys."""
    for k, v in {
        "BENCH_SERVE_USERS": "60", "BENCH_SERVE_ITEMS": "40",
        "BENCH_SERVE_K": "4", "BENCH_SERVE_QUERIES": "20",
        "BENCH_SERVE_TOPK_QUERIES": "4", "BENCH_SGD_RATINGS": "20",
        "BENCH_MSE_RATINGS": "30", "BENCH_SHARD_WORKERS": "2",
    }.items():
        monkeypatch.setenv(k, v)
    from bench_sections import run_serving_section

    out = run_serving_section(small=True)
    errors = {k: v for k, v in out.items() if k.endswith("_error")}
    assert not errors, errors
    for prefix in (
        "gen_rows_per_sec", "ingest_rows_per_sec", "serving_get_p50_ms",
        "serving_mget_p50_ms", "serving_topk_p50_ms",
        "sgd_ratings_per_sec", "mse_live_value",
        "serving_native_mget_p50_ms", "serving_shard_mget_p50_ms",
    ):
        assert prefix in out, (prefix, sorted(out))
    # the live MSE runs against a bounded-factor plane: predictions land in
    # [0,5), so against 1..5 ratings the value is a bounded sanity signal
    # (the r2 artifact recorded 9.5e154 off the heavy-tailed plane)
    import math

    assert math.isfinite(out["mse_live_value"])
    assert 0.0 <= out["mse_live_value"] < 30.0, out["mse_live_value"]
    # the real gate: the live served value must match
    # the offline ground truth computed from the same model files.  The two
    # paths read identical text rows but compute at different precisions
    # (offline scores through f32 jax _predict_dense, live through f64
    # numpy dots), so the tolerance allows per-prediction f32 rounding —
    # abs ~1e-5 bounds it at any MSE magnitude — while a serving-plane
    # corruption (wrong rows, truncated payloads, silently missed keys)
    # moves the live value by far more
    assert out["mse_live_value"] == pytest.approx(
        out["mse_offline_value"], rel=1e-4, abs=1e-5
    ), (out["mse_live_value"], out["mse_offline_value"])


def test_host_reference_op_is_quick_and_stable():
    """Every artifact carries a fixed host-op timing so cross-round
    throughput swings are attributable to environment vs regression."""
    import time as _time

    import bench

    bench.host_reference_ms()  # first matmul in a process spins up BLAS
    t0 = _time.time()
    a = bench.host_reference_ms()
    b = bench.host_reference_ms()
    assert _time.time() - t0 < 30
    assert 0.1 < a < 10_000 and 0.1 < b < 10_000
    # medians of 5 on the same box: same order of magnitude
    assert max(a, b) / min(a, b) < 5, (a, b)


@pytest.mark.slow
def test_als_quality_anchor_small(monkeypatch):
    """The quality anchor must produce a small bench-vs-f64 RMSE delta at
    toy scale (equal iterations, same init) and survive the x64 subprocess
    round trip."""
    import jax
    import numpy as np

    import bench
    from flink_ms_tpu.ops.als import ALSConfig, prepare_blocked
    from flink_ms_tpu.parallel.mesh import make_mesh

    monkeypatch.setenv("BENCH_RMSE_REF_NNZ", "3000")
    monkeypatch.setenv("BENCH_RMSE_REF_ITERS", "3")
    monkeypatch.delenv("BENCH_SKIP_CPU", raising=False)
    rng = np.random.default_rng(0)
    users = rng.integers(0, 50, 3000)
    items = rng.integers(0, 40, 3000)
    ratings = rng.uniform(1, 5, 3000)
    mesh = make_mesh(1)
    problem = prepare_blocked(users, items, ratings, 1)
    cfg = ALSConfig(num_factors=4, iterations=1, lambda_=0.1, seed=42)
    out = bench.als_quality_anchor(
        mesh, problem, users, items, ratings, cfg, iters=3)
    assert out["als_rmse_iters"] == 3
    assert 0.0 < out["als_rmse_at_iters"] < 5.0
    # f32 bench config vs f64 reference: sub-percent at toy scale
    assert abs(out["als_rmse_ref_delta"]) < 0.01, out


def test_peak_flops_raises_on_unknown_device_kind():
    """A device that is not in the peaks table is an error, not a default:
    an MFU against a guessed (or zero) peak is not a measurement."""
    import types

    import bench

    v5e = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    assert bench.peak_flops_per_device(v5e) == 197e12
    unknown = types.SimpleNamespace(platform="tpu", device_kind="TPU v99x")
    with pytest.raises(ValueError, match="v99x"):
        bench.peak_flops_per_device(unknown)
    host = types.SimpleNamespace(platform="cpu", device_kind="cpu")
    with pytest.raises(ValueError):
        bench.peak_flops_per_device(host)


def _run_bench(env_extra, drop=()):
    import subprocess

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("BENCH_") and k not in drop}
    env.update(env_extra)
    return subprocess.run([sys.executable, "bench.py"], cwd=root, env=env,
                          capture_output=True, text=True, timeout=600)


def test_bench_exits_nonzero_after_printing_a_failed_section(tmp_path):
    """A section that raises records its *_error key, the artifact line is
    still printed (and says cpu, as asked), and the exit code is 1."""
    import json

    proc = _run_bench({
        "JAX_PLATFORMS": "cpu", "BENCH_SECTIONS": "svm", "BENCH_SMALL": "1",
        "BENCH_SVM_EXAMPLES": "not-a-number",
        "BENCH_DETAIL_PATH": str(tmp_path / "d.json"),
    })
    assert proc.returncode == 1, proc.stderr[-2000:]
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    assert len(lines) == 1
    parsed = json.loads(lines[0])
    assert parsed["section_errors"] == ["svm_error"]
    assert parsed["platform"] == "cpu"
    assert "FAILED sections: svm_error" in proc.stderr


def test_bench_fails_without_an_accelerator_unless_asked_for_cpu(tmp_path):
    """No chip and no JAX_PLATFORMS=cpu: the device rule's message, a
    non-zero exit, and NO artifact line — never a quiet host run."""
    proc = _run_bench({"BENCH_SECTIONS": "svm", "BENCH_SMALL": "1",
                       "BENCH_DETAIL_PATH": str(tmp_path / "d.json")},
                      drop=("JAX_PLATFORMS",))
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "another process already holds the chip" in proc.stderr
    assert not (tmp_path / "d.json").exists()
