"""The host's heartbeat (``obs/hostbeat.py``): a process stopped from outside
is logged as one stall whose interval brackets the stop; a thread that keeps
the interpreter's lock shows as lateness and sleeping does not; the parsers
of the kernel's files on made-up trees, a missing file giving no series; the
``cause`` rule; the log's bound; one thread a process and none where metrics
are off; the operator's ``[host]`` line; and the benchmark's readers
``stalls`` and ``counter_delta`` on a hand-made run."""

import json
import os
import re
import signal
import subprocess
import sys
import threading
import time
import types
from collections import deque

import pytest

from flink_ms_tpu.obs import hostbeat as H
from flink_ms_tpu.obs import metrics as obs_metrics
from flink_ms_tpu.obs import tracing as T

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# a process of its own (no jax): it starts the beat, says "ready", does what
# the lines it is sent say, and on "dump" prints what the beat saw
CHILD = r"""
import json, sys, threading, time
from flink_ms_tpu.obs import hostbeat, tracing
from flink_ms_tpu.obs.metrics import get_registry
if len(sys.argv) > 1:  # a tree without the kernel's files: cpu_s alone speaks
    hostbeat._ROOT = sys.argv[1]
started = tracing.start_heartbeat(watch="main")
print("ready", flush=True)

def late():
    h = get_registry().histogram("tpums_host_beat_late_seconds")
    return h.sum, h.count

phases = {}
for line in sys.stdin:
    verb = line.strip()
    if verb == "sleep":
        s0, n0 = late()
        end = time.perf_counter() + 1.0
        while time.perf_counter() < end:
            time.sleep(0.001)
        s1, n1 = late()
        phases["sleep"] = [s1 - s0, n1 - n0]
    elif verb == "busy":
        sys.setswitchinterval(0.2)
        s0, n0 = late()
        end = time.perf_counter() + 1.5
        x = 0
        while time.perf_counter() < end:
            x += 1
        s1, n1 = late()
        sys.setswitchinterval(0.005)
        phases["busy"] = [s1 - s0, n1 - n0]
    elif verb == "dump":
        snap = get_registry().snapshot()
        print(json.dumps({
            "started": started,
            "threads": [t.name for t in threading.enumerate()],
            "stalls": tracing.stall_log(),
            "readings": tracing.thread_readings(),
            "report": tracing.host_report(),
            "phases": phases,
            "counters": {c["name"]: c["value"] for c in snap["counters"]},
        }), flush=True)
        break
"""


class Child:
    def __init__(self, *argv, **env):
        self.proc = subprocess.Popen(
            [sys.executable, "-c", CHILD, *argv], cwd=REPO, text=True,
            stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            env={**os.environ, **env})
        assert self.proc.stdout.readline().strip() == "ready"

    def say(self, verb):
        self.proc.stdin.write(verb + "\n")
        self.proc.stdin.flush()

    def dump(self):
        try:
            self.say("dump")
            out = json.loads(self.proc.stdout.readline())
            assert self.proc.wait(timeout=30) == 0
            return out
        finally:
            if self.proc.poll() is None:
                self.proc.kill()
                self.proc.wait()


# -- a stop from outside ----------------------------------------------------

def test_a_stopped_process_logs_one_stall_that_brackets_the_stop(tmp_path):
    child = Child(str(tmp_path))
    time.sleep(0.3)
    t_stop = time.perf_counter()
    os.kill(child.proc.pid, signal.SIGSTOP)
    try:
        time.sleep(0.3)
    finally:
        t_cont = time.perf_counter()
        os.kill(child.proc.pid, signal.SIGCONT)
    time.sleep(0.2)
    seen = child.dump()
    # perf_counter is the machine's monotonic clock: one clock for both
    long = [e for e in seen["stalls"] if e["end"] - e["start"] >= 0.25]
    assert len(long) == 1, seen["stalls"]
    (stall,) = long
    assert 0.25 <= stall["end"] - stall["start"] <= 0.6
    # the beat was due at most one period after the stop fell
    assert t_stop - 0.02 <= stall["start"] <= t_stop + 0.05
    assert t_cont <= stall["end"] <= t_cont + 0.2
    # nobody of the process ran: not its own doing, and no file says whose
    assert stall["cpu_s"] < 0.1 and stall["cause"] == "unknown"
    assert stall["since_s"] > 0 and stall["deltas"] == {}
    assert seen["counters"]["tpums_host_stalls_total"] == len(seen["stalls"])
    assert seen["counters"]["tpums_host_stall_seconds_total"] == pytest.approx(
        sum(e["end"] - e["start"] for e in seen["stalls"]))
    assert re.match(r"stalls \d+ \(\d+\.\d\ds\), longest 0\.\d{3}s \(\w+\); "
                    r"throttled .+; beat late \d+\.\d{3}ms mean of \d+$",
                    seen["report"]), seen["report"]
    # the stall took a reading of the watched main thread with it
    at_stall = [r for r in seen["readings"] if r["t"] == stall["end"]]
    assert at_stall and "frames" in at_stall[0]["threads"]["main"]


@pytest.fixture(scope="module")
def lock_and_sleep(tmp_path_factory):
    child = Child(str(tmp_path_factory.mktemp("no-kernel-files")))
    child.say("sleep")
    child.say("busy")
    return child.dump()


@pytest.mark.parametrize("what", ["sleep", "busy"])
def test_a_held_interpreter_lock_is_lateness_and_sleeping_is_not(
        lock_and_sleep, what):
    total, n = lock_and_sleep["phases"][what]
    assert n > 0
    mean = total / n
    if what == "sleep":
        # a thread that sleeps hands the lock over at once
        assert n > 30 and mean < 0.02, (mean, n)
    else:
        # a bytecode loop gives it up once a switch interval (0.2 s here)
        assert mean > 0.1, (mean, n)
        long = [e for e in lock_and_sleep["stalls"]
                if e["end"] - e["start"] > 0.1]
        assert long
        # the process was on a cpu meanwhile (a stopped one is not): half of
        # the time and more on an idle machine, less beside five other
        # workers; and by the rule that is what `busy` means
        assert sum(e["cpu_s"] for e in long) > 0.25 * sum(
            e["end"] - e["start"] for e in long), long
        for e in long:
            want = ("busy" if e["cpu_s"] >= (e["end"] - e["start"]) / 2
                    else "unknown")
            assert e["cause"] == want, e


def test_metrics_off_starts_no_thread():
    seen = Child(TPUMS_METRICS="0").dump()
    assert seen["started"] is False
    assert H.THREAD_NAME not in seen["threads"]
    assert seen["report"] == "no heartbeat"
    assert Child().dump()["threads"].count(H.THREAD_NAME) == 1


def test_acquire_devices_twice_starts_one_beat_and_watches_its_caller(
        monkeypatch, capsys):
    from flink_ms_tpu.parallel import mesh as M

    for _ in range(2):
        monkeypatch.setattr(M, "_acquired", False)
        M.acquire_devices()
    names = [t.name for t in threading.enumerate()]
    assert names.count(H.THREAD_NAME) == 1
    me = threading.current_thread()
    assert H._watched[me.name][0] is me


# -- the kernel's files -----------------------------------------------------

V2_CPU_STAT = ("usage_usec 912345678\nuser_usec 800000000\n"
               "system_usec 112345678\nnr_periods 5000\nnr_throttled 120\n"
               "throttled_usec 3400000\nnr_bursts 0\nburst_usec 0\n")
V1_CPU_STAT = "nr_periods 5000\nnr_throttled 7\nthrottled_time 1250000000\n"
PRESSURE = ("some avg10=1.74 avg60=2.14 avg300=1.45 total=414650088\n"
            "full avg10=0.00 avg60=0.00 avg300=0.00 total=7\n")
PROC_STAT = ("cpu  494211 0 32470 9288370 2836 0 1790 1152 0 0\n"
             "cpu0 1 2 3 4 5 6 7 8 9 10\nintr 0 0\n")
SELF_STAT = ("287 (python3 -m x) y) S 285 284 1 0 0 0 0 0 42 0 673 11 0 0 20 "
             "0 9 0 2237 104955904 5294 18446744073709551615 0 0 0\n")
TICK = H._TICK


@pytest.mark.parametrize("parse, text, want", [
    (H.parse_cpu_stat, V2_CPU_STAT,
     {"throttled_s": 3.4, "throttled_periods": 120}),
    (H.parse_cpu_stat, V1_CPU_STAT,
     {"throttled_s": 1.25, "throttled_periods": 7}),
    # a v2 root's cpu.stat counts usage and no throttling
    (H.parse_cpu_stat, "usage_usec 5\nuser_usec 3\nsystem_usec 2\n", None),
    (H.parse_cpu_stat, None, None),
    (H.parse_pressure, PRESSURE, 414.650088),
    (H.parse_pressure, "full avg10=0.00 total=7\n", None),
    (H.parse_steal, PROC_STAT, 1152 / TICK),
    # a sandbox's kernel that shows the file and accounts nothing
    (H.parse_steal, "cpu  0 0 0 0 0 0 0 0 0 0\ncpu0 0 0 0 0\n", None),
    (H.parse_stat, SELF_STAT, {"state": "S", "major_faults": 42,
                               "utime_s": 673 / TICK, "stime_s": 11 / TICK}),
    (H.parse_stat, "287 (python3) R 285\n", None),
    (H.parse_schedstat, "542178000 48567000 19\n", (0.542178, 0.048567)),
    (H.parse_schedstat, "", None),
], ids=["cpu.stat-v2", "cpu.stat-v1", "cpu.stat-root", "cpu.stat-missing",
        "pressure", "pressure-no-some", "steal", "steal-all-zero", "stat",
        "stat-short", "schedstat", "schedstat-missing"])
def test_the_parsers(parse, text, want):
    got = parse(text)
    assert got == (pytest.approx(want) if want is not None else None)


def _tree(root, files):
    for path, text in files.items():
        full = root / path
        full.parent.mkdir(parents=True, exist_ok=True)
        full.write_text(text)


TREES = {
    # cgroup v2, the container's own cgroup at the path /proc names
    "v2": ({"proc/self/cgroup": "0::/kube/pod7\n",
            "sys/fs/cgroup/kube/pod7/cpu.stat": V2_CPU_STAT,
            "sys/fs/cgroup/kube/pod7/cpu.pressure": PRESSURE,
            "sys/fs/cgroup/kube/pod7/memory.pressure": PRESSURE,
            "proc/pressure/cpu": "some avg10=0 total=1\n",
            "proc/pressure/io": PRESSURE,
            "proc/stat": PROC_STAT, "proc/self/stat": SELF_STAT},
           {"throttled_s": 3.4, "throttled_periods": 120,
            "pressure_cpu_s": 414.650088, "pressure_memory_s": 414.650088,
            "pressure_io_s": 414.650088, "steal_s": 1152 / TICK,
            "major_faults": 42, "utime_s": 673 / TICK, "stime_s": 11 / TICK}),
    # cgroup v1 whose mount shows the container's cgroup at its root (the
    # path /proc names is the host's), no pressure files, a dead /proc/stat
    "v1": ({"proc/self/cgroup": "2:cpuacct:/job\n1:cpu:/job\n",
            "sys/fs/cgroup/cpu/cpu.stat": V1_CPU_STAT,
            "proc/stat": "cpu  0 0 0 0 0 0 0 0 0 0\n",
            "proc/self/stat": SELF_STAT},
           {"throttled_s": 1.25, "throttled_periods": 7, "major_faults": 42,
            "utime_s": 673 / TICK, "stime_s": 11 / TICK}),
    "nothing": ({}, {}),
}


@pytest.fixture
def private(monkeypatch):
    """A registry, a stall log and readings of the test's own."""
    reg = obs_metrics.MetricsRegistry()
    monkeypatch.setattr(H._metrics, "get_registry", lambda: reg)
    monkeypatch.setattr(H, "_stall_log", deque(maxlen=H._STALL_CAP))
    monkeypatch.setattr(H, "_readings", deque(maxlen=H._READING_CAP))
    monkeypatch.setattr(H, "_watched", {})
    return reg


@pytest.mark.parametrize("tree", sorted(TREES))
def test_a_source_gives_a_series_and_a_missing_one_gives_none(
        tree, tmp_path, monkeypatch, private):
    files, want = TREES[tree]
    _tree(tmp_path, files)
    monkeypatch.setattr(H, "_ROOT", str(tmp_path))
    beat = H._Beat()
    assert beat.sources.read() == pytest.approx(want)
    beat.account(1.0)  # the first reading: every series at 0
    if "throttled_s" in want:
        (tmp_path / beat.sources.cpu_stat).write_text(
            "nr_throttled 130\nthrottled_usec 3900000\n" if tree == "v2"
            else "nr_throttled 17\nthrottled_time 1750000000\n")
    deltas, since = beat.account(2.5)
    assert since == 1.5
    counters = {c["name"]: c["value"]
                for c in private.snapshot()["counters"]}
    assert set(counters) == {H._SERIES[k] for k in want if k in H._SERIES}
    if "throttled_s" in want:
        assert deltas["throttled_s"] == pytest.approx(0.5)
        assert counters[H._SERIES["throttled_s"]] == pytest.approx(0.5)
        assert counters[H._SERIES["throttled_periods"]] == 10
        assert deltas["utime_s"] == 0
    else:
        assert deltas == {}


def test_the_watched_threads_are_read_and_the_dead_dropped(
        tmp_path, monkeypatch, private):
    monkeypatch.setattr(H, "_ROOT", str(tmp_path))
    H.watch_thread("main")
    native = threading.get_native_id()
    _tree(tmp_path, {
        f"proc/self/task/{native}/schedstat": "2000000000 250000000 9\n",
        f"proc/self/task/{native}/stat": SELF_STAT})
    release = threading.Event()
    other = threading.Thread(
        target=lambda: (H.watch_thread("main"), release.wait(10)))
    other.start()
    while len(H._watched) < 2:
        time.sleep(0.001)
    second = next(n for n in H._watched if n != "main")
    assert second == f"main.{other.native_id}"  # the name was taken
    beat = H._Beat()
    beat.read_threads(1.0)
    (tmp_path / f"proc/self/task/{native}/schedstat").write_text(
        "2100000000 300000000 9\n")
    release.set()
    other.join(10)
    beat.read_threads(2.0)
    first, last = H.thread_readings()
    assert set(first["threads"]) == {"main", second}
    assert set(last["threads"]) == {"main"} and second not in H._watched
    mine = last["threads"]["main"]
    assert (mine["cpu_s"], mine["wait_s"], mine["state"]) == (2.1, 0.3, "S")
    # innermost first; here the reader runs on the thread it reads
    assert mine["frames"][0].endswith(" read_threads")
    assert mine["frames"][1].endswith(
        "test_the_watched_threads_are_read_and_the_dead_dropped")
    assert len(mine["frames"]) == H._FRAMES
    # no schedstat for the other thread: no run-queue seconds, frames only
    assert "wait_s" not in first["threads"][second]
    assert private.counter(H.RUNQUEUE_SERIES).value == pytest.approx(0.05)


def test_reading_a_threads_frames_keeps_none_of_its_locals_alive(
        tmp_path, monkeypatch, private):
    """A function that returns while another thread holds its frame object
    hands its locals over to that object, and only the cyclic collector
    frees them then: a loop of device calls kept one iteration's arrays a
    reading (PERF.md, PR 51).  The reader lets the frames go before any
    call that gives the interpreter's lock away."""
    import gc

    monkeypatch.setattr(H, "_ROOT", str(tmp_path))

    class State:  # an iteration's device arrays
        alive = 0

        def __init__(self):
            State.alive += 1

        def __del__(self):
            State.alive -= 1

    def step(prev):
        def inner(p):
            made = State()
            time.sleep(0.0002)
            return made
        return inner(prev)

    H.watch_thread("main")
    beat, done = H._Beat(), threading.Event()

    def read():
        while not done.is_set():
            beat.read_threads(time.perf_counter())
            time.sleep(0.002)

    reader = threading.Thread(target=read)
    gc.collect()
    gc.disable()  # a tight loop of device calls hardly ever triggers it
    try:
        reader.start()
        state, end = State(), time.perf_counter() + 1.0
        while time.perf_counter() < end:
            state = step(state)
        done.set()
        reader.join(10)
        assert len(H.thread_readings()) > 50
        assert State.alive == 1
    finally:
        done.set()
        gc.enable()


@pytest.mark.parametrize("late, cpu_s, deltas, want", [
    (0.12, 0.0, {"throttled_s": 0.07, "pressure_cpu_s": 0.5}, "throttled"),
    (0.12, 0.0, {"throttled_s": 0.05, "pressure_cpu_s": 0.06}, "cpu_pressure"),
    (0.12, 0.0, {"pressure_memory_s": 0.06, "pressure_io_s": 0.2}, "memory"),
    (0.12, 0.2, {"pressure_io_s": 0.06, "major_faults": 900}, "io"),
    (0.12, 0.06, {"throttled_s": 0.059}, "busy"),
    (3.4, 0.4, {"throttled_s": 0.0, "steal_s": 3.0, "utime_s": 0.4}, "unknown"),
    (0.12, 0.0, {}, "unknown"),
])
def test_the_cause_rule(late, cpu_s, deltas, want):
    assert H.stall_cause(late, cpu_s, deltas) == want


def test_the_stall_log_is_bounded_and_counts_every_stall(
        tmp_path, monkeypatch, private):
    monkeypatch.setattr(H, "_ROOT", str(tmp_path))
    beat = H._Beat()
    n = H._STALL_CAP + 7
    for i in range(n):
        beat.stall(float(i), i + 0.25, 0.2)
    log = T.stall_log()
    assert len(log) == H._STALL_CAP and log[0]["start"] == 7.0
    assert log[-1] == {"start": n - 1.0, "end": n - 0.75, "cpu_s": 0.2,
                       "cause": "busy", "since_s": pytest.approx(
                           n - 0.75 - (n - 1.75)), "deltas": {}}
    assert private.counter(H.STALLS_SERIES).value == n
    assert private.counter(H.STALL_SECONDS_SERIES).value == pytest.approx(
        0.25 * n)


def test_the_operators_line(private):
    assert T.host_report() == "no heartbeat"
    for late in (0.0, 0.001, 0.001, 0.002):
        private.histogram(H.BEAT_SERIES).observe(late)
    assert T.host_report() == ("stalls none; throttled not counted on this "
                               "host; beat late 1.000ms mean of 4")
    private.counter(H._SERIES["throttled_s"]).inc(1.5)
    private.counter(H.STALLS_SERIES).inc(2)
    private.counter(H.STALL_SECONDS_SERIES).inc(0.3)
    H._stall_log.extend([
        {"start": 1.0, "end": 1.09, "cause": "unknown"},
        {"start": 5.0, "end": 5.21, "cause": "throttled"}])
    assert T.host_report() == (
        "stalls 2 (0.30s), longest 0.210s (throttled); throttled 1.50s; "
        "beat late 1.000ms mean of 4")


def test_als_train_prints_the_host_line(tmp_path, rng, capsys):
    import numpy as np

    from flink_ms_tpu.core import formats as F
    from flink_ms_tpu.core.params import Params
    from flink_ms_tpu.train import als_train

    u, i = np.nonzero(rng.uniform(size=(12, 9)) < 0.6)
    path = str(tmp_path / "ratings.csv")
    F.write_ratings(path, u, i, rng.uniform(1, 5, len(u)))
    als_train.run(Params.from_args([
        "--input", path, "--ignoreFirstLine", "false", "--iterations", "2",
        "--numFactors", "3", "--devices", "1",
        "--userFactors", str(tmp_path / "uf"),
        "--itemFactors", str(tmp_path / "itf")]))
    lines = capsys.readouterr().out.splitlines()
    at = next(n for n, line in enumerate(lines) if line.startswith("[phases]"))
    assert re.match(r"\[host\] stalls (none|\d+ \(.+\)); throttled .+; "
                    r"beat late \d+\.\d{3}ms mean of \d+$", lines[at + 1])


# -- the benchmark's readers ------------------------------------------------

def _stall(start, end, cause="unknown", cpu_s=0.0):
    return {"start": start, "end": end, "cause": cause, "cpu_s": cpu_s,
            "since_s": 0.8, "deltas": {"utime_s": 0.01}}


def _snap(counters=(), beats=0):
    return {"counters": [{"name": n, "labels": {}, "value": v}
                         for n, v in counters],
            "histograms": [{"name": H.BEAT_SERIES, "labels": {},
                            "sum": beats * 1e-4, "count": beats}]}


def _run(before, after, window=(100.0, 120.0)):
    from benchmark import run as harness

    run = types.SimpleNamespace(window=window, snap_before=before,
                                snap_after=after, trace_path=None)
    run.counter = types.MethodType(harness.Run.counter, run)
    run.hist_delta = types.MethodType(harness.Run.hist_delta, run)
    return run


READINGS = [
    {"t": 99.0, "threads": {"main": {"cpu_s": 1.0, "state": "R", "frames": []}}},
    {"t": 101.0, "threads": {"main": {"cpu_s": 3.0, "wait_s": 0.1, "state": "R",
                                      "frames": ["a.py:1 f"]}}},
    {"t": 102.0, "threads": {"main": {"cpu_s": 3.1, "wait_s": 0.4, "state": "S",
                                      "frames": ["api.py:9 block_until_ready"]}}},
    {"t": 119.0, "threads": {"main": {"cpu_s": 9.0, "wait_s": 0.5, "state": "R",
                                      "frames": ["a.py:1 f"]}}},
    {"t": 121.0, "threads": {"main": {"cpu_s": 99.0, "wait_s": 9.0}}},
]


@pytest.mark.parametrize("log, want", [
    # inside the window, beside one that ended before it opened
    ([_stall(90.0, 90.3), _stall(105.0, 105.12, "busy", 0.11)],
     (120.0, 1, [5.0])),
    # straddling either edge: the whole stall counts, and it is in
    ([_stall(99.9, 100.2), _stall(119.95, 121.0), _stall(121.0, 122.0)],
     (1050.0, 2, [-0.1, 19.95])),
    # the beat ran and saw none
    ([], (0.0, 0, [])),
], ids=["inside", "straddling", "none"])
def test_the_stalls_reader(monkeypatch, log, want):
    from benchmark.readers import stalls

    monkeypatch.setattr(T, "stall_log", lambda: list(log))
    monkeypatch.setattr(T, "thread_readings", lambda: list(READINGS))
    value, extra = stalls.read(_run(_snap(beats=10), _snap(beats=1990)))
    longest, n, offsets = want
    assert value == pytest.approx(longest)
    assert extra["n"] == n
    assert [s["offset_s"] for s in extra["stalls"]] == pytest.approx(offsets)
    assert extra["total_ms"] == pytest.approx(
        sum(s["late_ms"] for s in extra["stalls"]))
    if n == 1:
        assert extra["stalls"][0] == {
            "offset_s": pytest.approx(5.0), "late_ms": pytest.approx(120.0),
            "cause": "busy", "cpu_ms": pytest.approx(110.0), "since_s": 0.8,
            "deltas": {"utime_s": 0.01}}
    # the watched thread over the window's readings, and its idlest second
    assert extra["threads"] == {"main": {
        "readings": 3, "cpu_s": pytest.approx(6.0),
        "wait_s": pytest.approx(0.4),
        "idlest": {"offset_s": 2.0, "over_s": 1.0,
                   "cpu_s": pytest.approx(0.1), "state": "S",
                   "frames": ["api.py:9 block_until_ready"]}}}


@pytest.mark.parametrize("case", ["no-log", "no-beat", "window-open"])
def test_the_stalls_reader_reads_nothing(monkeypatch, case):
    from benchmark.readers import stalls

    run = _run(_snap(beats=10), _snap(beats=1990))
    if case == "no-log":  # a program from before the beat
        monkeypatch.delattr(T, "stall_log")
    elif case == "no-beat":  # metrics off, or the thread died
        run = _run(_snap(beats=10), _snap(beats=10))
    else:
        run.window = (100.0, None)
    assert stalls.read(run) is None


def test_the_stalls_reader_lays_a_marker_over_the_device_plane():
    from benchmark.readers import stalls

    def event(name, start, dur, stats=()):
        return types.SimpleNamespace(name=name, start_ns=start, duration_ns=dur,
                                     stats=list(stats))

    def plane(name, **lines):
        return types.SimpleNamespace(name=name, lines=[
            types.SimpleNamespace(name=n.replace("_", " "), events=ev)
            for n, ev in lines.items()])

    ms = 1_000_000
    planes = [
        plane("/device:TPU:0", XLA_Ops=[
            event("fusion.1", 0, 40 * ms), event("fusion.2", 100 * ms, 20 * ms),
            event("fusion.3", 290 * ms, 50 * ms)]),
        plane("/host:CPU", python=[
            event("topk.frame", 5 * ms, 2 * ms),
            event("host.stall", 300 * ms, 1500, [("late_ms", 250.0)])]),
    ]
    # the stall is [50, 300] ms: the device ran 100-120 and 290-300 of it
    assert stalls.device_idle(planes) == [(250.0, pytest.approx(220.0))]
    assert stalls.device_idle(planes[1:]) == []


@pytest.mark.parametrize("before, after, want", [
    ([("tpums_x_total", 2.5)], [("tpums_x_total", 4.0)], 1500.0),
    ([], [("tpums_x_total", 4.0)], 4000.0),   # it first moved in the window
    ([("tpums_x_total", 4.0)], [("tpums_x_total", 4.0)], 0.0),
    ([], [], None),                           # no such series: nothing
], ids=["gain", "born-in-the-window", "unmoved", "no-series"])
def test_the_counter_delta_reader(before, after, want):
    from benchmark.readers import counter_delta

    got = counter_delta.read(_run(_snap(before), _snap(after)),
                             name="tpums_x_total", scale=1000.0)
    assert got == (pytest.approx(want) if want is not None else None)


def test_the_new_series_pass_the_hygiene_lint():
    names = [H.BEAT_SERIES, H.STALLS_SERIES, H.STALL_SECONDS_SERIES,
             H.RUNQUEUE_SERIES, *H._SERIES.values()]
    assert len(set(names)) == len(names)
    for name in names:
        assert re.match(obs_metrics.NAME_PATTERN, name)
        assert name.endswith("_total") or name == H.BEAT_SERIES
