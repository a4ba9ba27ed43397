"""``chip_smoke.py``: the command is debugged here, on the CPU at a tiny
shape (``--tiny``: the chip's Pallas solver, interpreted), so chip time is
not spent on it — and it must FAIL when a child fails, when a reply is
wrong, when it is asked to pass on the host, or outside a checkout."""

import json
import os
import shutil
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(ROOT, "chip_smoke.py")


def _smoke(args=(), env_extra=None, cwd=ROOT, script=SMOKE):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)  # one host device, like a one-chip machine
    env.update(env_extra or {})
    return subprocess.run([sys.executable, script, *args], env=env, cwd=cwd,
                          capture_output=True, text=True, timeout=600)


def _result_lines(proc):
    return [ln for ln in proc.stdout.splitlines() if ln.startswith("{")]


def test_tiny_passes_on_cpu_and_says_cpu():
    proc = _smoke(["--tiny"])
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    last = proc.stdout.strip().splitlines()[-1]
    assert json.loads(last) == {
        "ok": True,
        "device": {"platform": "cpu", "kind": "cpu", "count": 1}}
    out = proc.stdout
    assert "trainer ran on platform: cpu" in out
    assert "server ran on platform: cpu" in out
    assert "nnz 6000" in out and "phase seconds:" in out
    assert "swallowed device errors 0" in out


def test_fails_when_a_child_fails():
    # an unparseable knob stops the trainer child; nothing downstream runs
    proc = _smoke(["--tiny"], {"FLINK_MS_ALS_BUCKET_RATIO": "not-a-number"})
    assert proc.returncode != 0
    assert not _result_lines(proc)
    assert "als_train exited 1" in proc.stderr
    assert "server ran on" not in proc.stdout


def test_fails_when_a_topk_reply_is_wrong():
    # a forced one-probe IVF tier returns a truncated shortlist: the reply
    # no longer equals the numpy argsort of the served factors
    proc = _smoke(["--tiny"], {"TPUMS_TOPK_TIER": "ivf",
                               "TPUMS_ANN_NLIST": "64",
                               "TPUMS_ANN_NPROBE": "1"})
    assert proc.returncode != 0
    assert not _result_lines(proc)
    assert "FAILED: TOPK" in proc.stderr


def test_full_size_refuses_the_host_and_prints_no_result():
    proc = _smoke()  # no --tiny, JAX_PLATFORMS=cpu: immediate refusal
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "needs the accelerator" in proc.stderr


def test_fails_in_a_directory_that_holds_nothing_else(tmp_path):
    alone = shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    proc = _smoke(["--tiny"], cwd=str(tmp_path), script=str(alone))
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no flink_ms_tpu package" in proc.stderr
