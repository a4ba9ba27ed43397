"""The device rule (``parallel.mesh.acquire_devices``): one function every
device-touching entry point calls.  It raises when jax quietly fell back to
the host, honours an explicit ask for the host, says where the job runs,
and places the compile cache at a fixed path."""

import os
import re
import subprocess
import sys
import types

import jax
import numpy as np
import pytest

from flink_ms_tpu.parallel import mesh as M

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def fresh_rule(monkeypatch):
    """The once-per-process half of the rule (log line, cache, listeners)
    armed again, with the listeners it registers removed afterwards."""
    monkeypatch.setattr(M, "_acquired", False)
    monkeypatch.setattr(M, "_count_compiles", lambda: None)
    saved = {name: getattr(jax.config, name) for name in (
        "jax_compilation_cache_dir",
        "jax_compilation_cache_include_metadata_in_key")}
    yield
    for name, value in saved.items():
        jax.config.update(name, value)


def _a_chip(monkeypatch):
    """jax.devices() as a one-chip host reports it."""
    chip = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    monkeypatch.setattr(jax, "devices", lambda *a: [chip])


def test_raises_on_a_cpu_backend_nobody_asked_for(monkeypatch):
    monkeypatch.delenv("JAX_PLATFORMS")
    with pytest.raises(RuntimeError) as err:
        M.acquire_devices()
    msg = str(err.value)
    assert "no TPU is attached" in msg
    assert "another process already holds the chip" in msg
    assert "JAX_PLATFORMS=cpu" in msg
    # "tpu,cpu" with the host answering is not an ask for the host either
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    with pytest.raises(RuntimeError):
        M.acquire_devices()


def test_honours_an_explicit_ask_and_logs_where_it_runs(fresh_rule, capfd):
    assert os.environ["JAX_PLATFORMS"] == "cpu"  # conftest's ask
    devices = M.acquire_devices()
    assert devices == jax.devices()
    err = capfd.readouterr().err
    assert re.search(
        rf"^\[mesh\] platform=cpu device_kind=cpu devices={len(devices)}$",
        err, re.M), err
    M.acquire_devices()  # one line per process, not per call
    assert "[mesh]" not in capfd.readouterr().err


def test_index_host_pin_is_an_ask_too(monkeypatch):
    monkeypatch.delenv("JAX_PLATFORMS")
    devices = M.acquire_devices(host_pinned=True)
    assert devices and all(d.platform == "cpu" for d in devices)


def test_cache_untouched_on_the_host_and_when_the_operator_chose(
        fresh_rule, monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    jax.config.update("jax_compilation_cache_dir", None)
    M.acquire_devices()  # cpu backend: no cache is set in code
    assert jax.config.jax_compilation_cache_dir is None
    # on the chip with JAX_COMPILATION_CACHE_DIR set, jax reads the
    # variable itself and the code sets nothing
    monkeypatch.setattr(M, "_acquired", False)
    _a_chip(monkeypatch)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/operator/choice")
    M.acquire_devices()
    assert jax.config.jax_compilation_cache_dir is None


def test_cache_at_the_fixed_checkout_path_on_the_chip(
        fresh_rule, monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    _a_chip(monkeypatch)
    assert M.acquire_devices()[0].platform == "tpu"
    assert jax.config.jax_compilation_cache_dir == os.path.join(
        ROOT, ".jax_cache")


def test_cache_key_holds_the_metadata_on_the_chip_only(
        fresh_rule, monkeypatch):
    """A hit otherwise hands back the executable with the named scopes and
    source lines of whoever compiled it first, and a profile of today's
    code names yesterday's."""
    jax.config.update("jax_compilation_cache_include_metadata_in_key", False)
    M.acquire_devices()  # the host: no cache, nothing set
    assert not jax.config.jax_compilation_cache_include_metadata_in_key
    monkeypatch.setattr(M, "_acquired", False)
    _a_chip(monkeypatch)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/operator/choice")
    M.acquire_devices()  # wherever the cache lives
    assert jax.config.jax_compilation_cache_include_metadata_in_key


def test_cache_path_is_fixed_and_gitignored():
    """The path is part of the cache key: the same string in every
    process, with no temp dir, pid or time in it."""
    path = M.repo_cache_dir()
    assert path == os.path.join(ROOT, ".jax_cache")
    other = subprocess.run(
        [sys.executable, "-c",
         "from flink_ms_tpu.parallel.mesh import repo_cache_dir;"
         "print(repo_cache_dir())"],
        cwd=ROOT, capture_output=True, text=True, check=True,
        env=dict(os.environ, TMPDIR="/tmp/elsewhere"),
    ).stdout.strip()
    assert other == path
    assert str(os.getpid()) not in path and "tmp" not in path.lower()
    assert ".jax_cache/" in open(os.path.join(ROOT, ".gitignore")).read()


def test_compile_counters_feed_the_report(monkeypatch):
    from flink_ms_tpu.obs.metrics import get_registry

    # hook the listeners up again: they hold the counters of the registry
    # as it was when this process first acquired, and an earlier test file
    # in the same worker may have reset it since (test_native_protocol does)
    monkeypatch.setattr(M, "_acquired", False)
    M.acquire_devices()
    secs = get_registry().counter("tpums_jax_compile_seconds_total")
    before = secs.value
    jax.block_until_ready(jax.jit(lambda x: x * 3 + 1)(np.arange(7.0)))
    assert secs.value > before
    assert re.match(
        r"compile \d+\.\d\ds, persistent cache \d+ hit\(s\) / \d+ miss\(es\)$",
        M.compile_report())


def test_host_draw_needs_no_cpu_backend(monkeypatch):
    """Under JAX_PLATFORMS=tpu jax initialises no cpu backend and a "cpu"
    lookup raises; init_factors then draws on the default device — the
    same threefry values the parity tests pin."""
    from flink_ms_tpu.ops.als import init_factors

    key = jax.random.PRNGKey(3)
    pinned = np.asarray(init_factors(64, 5, key, np.float32))

    def no_cpu_backend(backend=None):
        raise RuntimeError("Unknown backend cpu")

    monkeypatch.setattr(jax, "local_devices", no_cpu_backend)
    assert M.host_device() is None
    np.testing.assert_array_equal(
        np.asarray(init_factors(64, 5, key, np.float32)), pinned)


def test_trainer_and_server_die_with_the_rule_on_a_chipless_host(tmp_path):
    """No accelerator, JAX_PLATFORMS unset: als_train exits non-zero before
    it reads its input; an ALS serving job refuses to start."""
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    env["PYTHONPATH"] = ROOT
    train = subprocess.run(
        [sys.executable, "-m", "flink_ms_tpu.train.als_train",
         "--input", str(tmp_path / "never-read.csv")],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=300)
    assert train.returncode != 0
    assert "another process already holds the chip" in train.stderr
    assert "never-read.csv" not in train.stderr  # failed before the parse
    serve = subprocess.run(
        [sys.executable, "-m", "flink_ms_tpu.serve.als_consumer",
         "--journalDir", str(tmp_path / "bus"), "--topic", "t",
         "--host", "127.0.0.1", "--port", "0"],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=300)
    assert serve.returncode != 0
    assert "another process already holds the chip" in serve.stderr
