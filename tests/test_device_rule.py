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

from flink_ms_tpu.obs.metrics import get_registry
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
    # listening, whichever test file ran before (once a process: the
    # listeners look their counters up when an event fires, so a registry
    # an earlier test file reset loses nothing)
    monkeypatch.setattr(M, "_acquired", False)
    M.acquire_devices()
    secs = get_registry().counter("tpums_jax_compile_seconds_total")
    before = secs.value
    jax.block_until_ready(jax.jit(lambda x: x * 3 + 1)(np.arange(7.0)))
    assert secs.value > before
    assert re.match(
        r"compile \d+\.\d\ds, persistent cache \d+ hit\(s\) / \d+ miss\(es\); "
        r"trace \d+\.\d\ds, lower \d+\.\d\ds, cache load \d+\.\d\ds; "
        r"costliest: (\S+ \d+\.\d\ds)(, \S+ \d+\.\d\ds){0,2}$",
        M.compile_report())


def _seconds(kind=None):
    """The four seconds series of the device rule, as they stand: the
    unlabelled totals, or one function's children."""
    reg = get_registry()
    labels = {} if kind is None else {"kind": kind}
    return {name: reg.counter(f"tpums_jax_{name}_seconds_total", **labels).value
            for name in ("trace", "lower", "cache_load", "compile")}


def test_a_cold_jit_raises_its_functions_children_and_a_cached_call_none(
        monkeypatch):
    monkeypatch.setattr(M, "_acquired", False)
    M.acquire_devices()  # listening, whichever test file ran before

    def inner_helper(x):
        return x * 2

    def a_function_named_for_this_test(x):
        # the nested jit's trace lies inside this one's: counted once
        return jax.jit(inner_helper)(x) + 1

    fn = jax.jit(a_function_named_for_this_test)
    totals, mine = _seconds(), _seconds("a_function_named_for_this_test")
    assert set(mine.values()) == {0}
    jax.block_until_ready(fn(np.arange(5.0)))
    cold, cold_totals = _seconds("a_function_named_for_this_test"), _seconds()
    for series in ("trace", "lower", "compile"):
        assert cold[series] > 0, series
        # the total moved by what the outermost function reports, no more
        assert cold_totals[series] - totals[series] == pytest.approx(
            cold[series], abs=1e-9), series
    assert _seconds("inner_helper")["trace"] == 0  # inside its caller's
    assert cold["cache_load"] == 0  # no persistent cache on the host
    jax.block_until_ready(fn(np.arange(5.0)))  # cached: jax fires nothing
    assert _seconds("a_function_named_for_this_test") == cold
    assert _seconds() == cold_totals
    assert any(c["labels"].get("kind") == "a_function_named_for_this_test"
               for c in get_registry().snapshot()["counters"])


def test_a_cache_load_is_booked_to_the_function_being_compiled(monkeypatch):
    """jax names no function on the cache's retrieval event; it fires
    inside the backend-compile event of the function being loaded."""
    monkeypatch.setattr(M, "_acquired", False)
    M.acquire_devices()
    before, total = _seconds("loaded_fn"), _seconds()
    jax.monitoring.record_event_duration_secs(M._CACHE_LOAD_EVENT, 0.25)
    jax.monitoring.record_event_duration_secs(
        M._BACKEND_COMPILE_EVENT, 0.5, fun_name="jit(loaded_fn)")
    jax.monitoring.record_event_duration_secs(
        M._BACKEND_COMPILE_EVENT, 0.5, fun_name="jit(compiled_fn)")
    after = _seconds("loaded_fn")
    assert after["cache_load"] - before["cache_load"] == pytest.approx(0.25)
    assert after["compile"] - before["compile"] == pytest.approx(0.5)
    assert _seconds("compiled_fn")["cache_load"] == 0
    assert _seconds()["cache_load"] - total["cache_load"] == pytest.approx(0.25)
    assert _seconds()["compile"] - total["compile"] == pytest.approx(1.0)


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
