"""The host's heartbeat: what the operating system and the container did to
this process while nothing of the program ran.

``span``, ``stage`` and ``phase`` (``obs/tracing.py``) time work that runs.
A stall is the absence of work: a process frozen by its sandbox, a
container out of CPU quota, a thread that waits for the interpreter's lock
or for a core.  No block of the program is open then, so no block can time
it.  The beat times no block.  One daemon thread a process
(``tpums-heartbeat``, started by ``parallel/mesh.acquire_devices`` wherever
``obs.metrics.metrics_enabled()`` is true, with no switch of its own)
sleeps ``_BEAT_S`` and reads ``time.perf_counter()`` either side: what the
sleep took beyond its period is what the scheduler and the interpreter's
lock added before THIS thread ran again, and any other thread of the
process that became runnable in that moment waited as long.

- Every beat observes ``tpums_host_beat_late_seconds`` (sum / count over a
  window: the mean hand-off delay a thread of this process sees).
- A beat later than ``_STALL_S`` is a **stall**: ``tpums_host_stalls_total``
  and ``tpums_host_stall_seconds_total`` move, one entry goes to a bounded
  in-memory log (``stall_log()``: ``start`` / ``end`` on ``perf_counter``,
  the clock of ``tracing.phase_log()``), and a ``stage("host.stall",
  late_ms=...)`` is opened and closed at once, so that a profile holds a
  marker on the profiler's clock from which a reader rebuilds the interval
  ``[t - late, t]`` and lays it over the device planes.  An ordinary beat
  opens no stage: a 10 ms span always open would name every longer gap of
  a trace.
- Every ``_ACCOUNT_EVERY`` beats, and once more at each stall, the same
  thread reads the kernel's own accounting (never on a path of the
  program): the container's ``cpu.stat`` (``nr_throttled`` and
  ``throttled_usec``, or cgroup v1's ``throttled_time``), the pressure
  files (``some total``; the cgroup's own ``*.pressure`` where present,
  else ``/proc/pressure/*``), ``/proc/stat``'s ``steal`` and
  ``/proc/self/stat``'s ``majflt``, ``utime``, ``stime``.  Each becomes a
  monotonic series counted from the thread's first reading (``_SERIES``);
  a source the host lacks gives no series, not a zero.
- At the same readings, for each thread registered by ``watch_thread``
  (the ones that feed the device: the TOPK dispatcher, a trainer's or a
  driver's main thread), ``/proc/self/task/<id>/schedstat`` (seconds on a
  CPU, seconds runnable and waiting for one; where the host has no
  ``schedstat`` the CPU seconds come from the task's ``stat``), its state
  letter and its innermost Python frames; the last ``_READING_CAP``
  readings are kept (``thread_readings()``).  This is for the stall that
  freezes one thread and not the process: the beat is then on time and
  sees nothing.  No verdict is drawn from them here: a main thread asleep
  in ``block_until_ready`` is doing its job.

A stall's entry carries ``cpu_s``, the CPU seconds the whole process gained
across the late beat (``time.process_time()`` either side of the sleep:
near ``late`` or above where the process's own threads held the lock or
the cores, near 0 where the process stood still as a whole), the deltas of
the accounting since the reading before it (``deltas``, over ``since_s``
seconds), and a ``cause`` by one rule over them (``stall_cause``).  The raw
numbers stay in the entry; the rule is a reading aid.
"""

from __future__ import annotations

import os
import sys
import threading
import time
from collections import deque
from typing import Deque, Dict, List, Optional, Tuple

from . import metrics as _metrics

THREAD_NAME = "tpums-heartbeat"
_BEAT_S = 0.010
# under half the smallest wall PERF.md reports (0.118 s)
_STALL_S = 0.050
_ACCOUNT_EVERY = 100
_STALL_CAP = 512
# one reading a second, and the benchmark's checks keep a process alive for
# a minute after the window its readers then ask about
_READING_CAP = 256
_FRAMES = 3
_TICK = float(os.sysconf("SC_CLK_TCK")) if hasattr(os, "sysconf") else 100.0

# tests point this at a made-up tree
_ROOT = "/"

BEAT_SERIES = "tpums_host_beat_late_seconds"
STALLS_SERIES = "tpums_host_stalls_total"
STALL_SECONDS_SERIES = "tpums_host_stall_seconds_total"
RUNQUEUE_SERIES = "tpums_host_runqueue_wait_seconds_total"
# key of a reading -> its series; ``utime_s`` / ``stime_s`` have none and
# live in the stall entries' deltas only
_SERIES = {
    "throttled_s": "tpums_host_cpu_throttled_seconds_total",
    "throttled_periods": "tpums_host_cpu_throttled_periods_total",
    "pressure_cpu_s": "tpums_host_pressure_cpu_seconds_total",
    "pressure_memory_s": "tpums_host_pressure_memory_seconds_total",
    "pressure_io_s": "tpums_host_pressure_io_seconds_total",
    "steal_s": "tpums_host_steal_seconds_total",
    "major_faults": "tpums_host_major_faults_total",
}
# the rule of ``stall_cause``, in its order: cause <- the delta that names it
_CAUSES = (("throttled", "throttled_s"), ("cpu_pressure", "pressure_cpu_s"),
           ("memory", "pressure_memory_s"), ("io", "pressure_io_s"))

# deque.append is atomic; entries are appended whole and never edited
_stall_log: Deque[dict] = deque(maxlen=_STALL_CAP)
_readings: Deque[dict] = deque(maxlen=_READING_CAP)
# name -> (thread, ident, native id); each thread registers itself, the beat
# thread drops the dead
_watched: Dict[str, Tuple[threading.Thread, int, int]] = {}
_start_lock = threading.Lock()
_thread: Optional[threading.Thread] = None


# ---------------------------------------------------------------------------
# the kernel's files
# ---------------------------------------------------------------------------

def _text(path: str) -> Optional[str]:
    try:
        with open(os.path.join(_ROOT, path)) as f:
            return f.read()
    except OSError:
        return None


def parse_cpu_stat(text: Optional[str]) -> Optional[dict]:
    """A cgroup's ``cpu.stat`` -> ``{throttled_s, throttled_periods}``, from
    ``throttled_usec`` (v2) or ``throttled_time`` (v1, nanoseconds); None
    where the file counts no throttling (a v2 root's has ``usage_usec``
    alone)."""
    fields = dict(pair for pair in map(str.split, (text or "").splitlines())
                  if len(pair) == 2)
    try:
        if "throttled_usec" in fields:
            secs = int(fields["throttled_usec"]) / 1e6
        else:
            secs = int(fields["throttled_time"]) / 1e9
        return {"throttled_s": secs,
                "throttled_periods": int(fields["nr_throttled"])}
    except (KeyError, ValueError):
        return None


def parse_pressure(text: Optional[str]) -> Optional[float]:
    """A pressure file's ``some ... total=<usec>`` -> seconds in which at
    least one task waited for the resource."""
    for line in (text or "").splitlines():
        if line.startswith("some"):
            for field in line.split():
                if field.startswith("total="):
                    try:
                        return int(field[6:]) / 1e6
                    except ValueError:
                        return None
    return None


def parse_steal(text: Optional[str]) -> Optional[float]:
    """``/proc/stat`` -> seconds the hypervisor ran somebody else on this
    guest's cpus.  None where the ``cpu`` line is all zeros: a sandbox's
    kernel that shows the file and accounts nothing."""
    first = (text or "").split("\n", 1)[0].split()
    try:
        ticks = [int(x) for x in first[1:]]
    except ValueError:
        return None
    if len(ticks) < 8 or first[0] != "cpu" or not any(ticks):
        return None
    return ticks[7] / _TICK


def parse_stat(text: Optional[str]) -> Optional[dict]:
    """``/proc/<pid>/stat`` or a task's -> state letter, major faults and
    CPU seconds (fields 3, 12, 14 and 15, counted from the command's
    closing bracket, which may itself hold spaces)."""
    rest = (text or "").rpartition(")")[2].split()
    try:
        return {"state": rest[0], "major_faults": int(rest[9]),
                "utime_s": int(rest[11]) / _TICK,
                "stime_s": int(rest[12]) / _TICK}
    except (IndexError, ValueError):
        return None


def parse_schedstat(text: Optional[str]) -> Optional[Tuple[float, float]]:
    """A task's ``schedstat`` -> (seconds on a cpu, seconds runnable and
    waiting for one)."""
    parts = (text or "").split()
    try:
        return int(parts[0]) / 1e9, int(parts[1]) / 1e9
    except (IndexError, ValueError):
        return None


def _cgroup_dirs() -> List[str]:
    """Where this process's cgroup may show its files, most specific first:
    under the v2 mount at the path ``/proc/self/cgroup`` gives and at its
    root (inside a cgroup namespace the root IS the container's cgroup),
    then the same under a hybrid host's ``unified`` mount and under the v1
    ``cpu`` controller."""
    v2 = v1 = ""
    for line in (_text("proc/self/cgroup") or "").splitlines():
        controllers, _, path = line.partition(":")[2].partition(":")
        if not controllers:
            v2 = path
        elif "cpu" in controllers.split(","):
            v1 = path
    out: List[str] = []
    for mount, path in (("", v2), ("unified", v2),
                        ("cpu,cpuacct", v1), ("cpu", v1)):
        for sub in (path.strip("/"), ""):
            where = os.path.normpath(
                os.path.join("sys/fs/cgroup", mount, sub))
            if where not in out:
                out.append(where)
    return out


class Sources:
    """Which of the kernel's files this host has, found once; ``read()`` ->
    their totals now, under the keys of ``_SERIES`` plus ``utime_s`` /
    ``stime_s``.  A file that is missing or counts nothing gives no key."""

    def __init__(self):
        dirs = _cgroup_dirs()
        self.cpu_stat = next(
            (p for p in (os.path.join(d, "cpu.stat") for d in dirs)
             if parse_cpu_stat(_text(p)) is not None), None)
        self.pressure = {}
        for what in ("cpu", "memory", "io"):
            paths = [os.path.join(d, what + ".pressure") for d in dirs]
            paths.append("proc/pressure/" + what)
            found = next((p for p in paths
                          if parse_pressure(_text(p)) is not None), None)
            if found is not None:
                self.pressure[what] = found
        self.steal = parse_steal(_text("proc/stat")) is not None

    def read(self) -> dict:
        out: dict = {}
        if self.cpu_stat is not None:
            out.update(parse_cpu_stat(_text(self.cpu_stat)) or {})
        for what, path in self.pressure.items():
            secs = parse_pressure(_text(path))
            if secs is not None:
                out[f"pressure_{what}_s"] = secs
        if self.steal:
            secs = parse_steal(_text("proc/stat"))
            if secs is not None:
                out["steal_s"] = secs
        stat = parse_stat(_text("proc/self/stat")) or {}
        out.update((k, v) for k, v in stat.items() if k != "state")
        return out


def stall_cause(late: float, cpu_s: float, deltas: dict) -> str:
    """One rule, a reading aid: the first of ``throttled`` (the container's
    quota), ``cpu_pressure``, ``memory``, ``io`` (the pressure files) whose
    delta since the reading before the stall covers at least half of
    ``late``; else ``busy`` where the process's own CPU seconds across the
    late beat do (its threads held the interpreter's lock or the cores: the
    program's own doing); else ``unknown``, which with ``cpu_s`` near 0 is
    a process that stood still as a whole and no file of this host says
    why.  The deltas cover up to ``_ACCOUNT_EVERY`` beats before the stall
    too, so steady pressure can claim a short stall it did not cause: read
    ``deltas`` beside ``since_s``."""
    half = late / 2
    for cause, key in _CAUSES:
        if deltas.get(key, 0.0) >= half:
            return cause
    return "busy" if cpu_s >= half else "unknown"


# ---------------------------------------------------------------------------
# the thread
# ---------------------------------------------------------------------------

def _innermost(frame) -> List[str]:
    out = []
    while frame is not None and len(out) < _FRAMES:
        code = frame.f_code
        out.append(f"{os.path.basename(code.co_filename)}:{frame.f_lineno} "
                   f"{code.co_name}")
        frame = frame.f_back
    return out


class _Beat:
    """The state of the one thread.  Series are looked up when they move,
    so a registry reset (tests) loses nothing after it."""

    def __init__(self):
        self.sources = Sources()
        self.totals: dict = {}
        self.read_at = time.perf_counter()
        self.waited: Dict[str, float] = {}

    def account(self, now: float) -> Tuple[dict, float]:
        """Read the kernel's accounting and the watched threads; -> (deltas
        since the reading before, the seconds they cover)."""
        totals = self.sources.read()
        deltas = {k: max(v - self.totals[k], 0) for k, v in totals.items()
                  if k in self.totals}
        since = now - self.read_at
        self.totals, self.read_at = totals, now
        counter = _metrics.get_registry().counter
        for key in totals:
            if key in _SERIES:
                counter(_SERIES[key]).inc(deltas.get(key, 0))
        self.read_threads(now)
        return deltas, since

    def read_threads(self, now: float) -> None:
        if not _watched:
            return
        # the frames are read and let go in one stretch of bytecode with no
        # blocking call in it, so no other thread runs Python meanwhile: a
        # function that returns while somebody holds its frame object hands
        # its locals over to it, and what then frees them is the cyclic
        # collector, which a loop of device calls hardly ever triggers (one
        # iteration's device arrays stayed alive a reading: PERF.md, PR 51)
        frames = sys._current_frames()
        stacks = {name: _innermost(frames.get(ident))
                  for name, (_, ident, _) in list(_watched.items())}
        del frames
        threads = {}
        for name, (thread, ident, native) in list(_watched.items()):
            if not thread.is_alive():
                _watched.pop(name, None)
                self.waited.pop(name, None)
                continue
            task = f"proc/self/task/{native}/"
            got: dict = {}
            stat = parse_stat(_text(task + "stat"))
            sched = parse_schedstat(_text(task + "schedstat"))
            if sched is not None:
                got["cpu_s"], got["wait_s"] = sched
                gained = sched[1] - self.waited.get(name, sched[1])
                self.waited[name] = sched[1]
                _metrics.get_registry().counter(RUNQUEUE_SERIES).inc(
                    max(gained, 0.0))
            elif stat is not None:
                got["cpu_s"] = stat["utime_s"] + stat["stime_s"]
            if stat is not None:
                got["state"] = stat["state"]
            got["frames"] = stacks.get(name, [])
            threads[name] = got
        if threads:
            _readings.append({"t": now, "threads": threads})

    def stall(self, due: float, woke: float, cpu_s: float) -> None:
        from .tracing import stage

        late = woke - due
        deltas, since = self.account(woke)
        _stall_log.append({
            "start": due, "end": woke, "cpu_s": cpu_s,
            "cause": stall_cause(late, cpu_s, deltas),
            "since_s": since, "deltas": deltas})
        counter = _metrics.get_registry().counter
        counter(STALLS_SERIES).inc()
        counter(STALL_SECONDS_SERIES).inc(late)
        with stage("host.stall", late_ms=late * 1e3):
            pass

    def run(self) -> None:
        clock, cpu, sleep = time.perf_counter, time.process_time, time.sleep
        registry = _metrics.get_registry()
        # a scrape reads 0, not nothing, before the first stall
        registry.counter(STALLS_SERIES)
        registry.counter(STALL_SECONDS_SERIES)
        self.account(clock())  # what every series is counted from
        beats = 0
        while True:
            t0, c0 = clock(), cpu()
            sleep(_BEAT_S)
            t1, c1 = clock(), cpu()
            if not _metrics.metrics_enabled():
                continue
            late = t1 - t0 - _BEAT_S
            registry.histogram(BEAT_SERIES).observe(max(late, 0.0))
            beats += 1
            if late > _STALL_S:
                self.stall(t0 + _BEAT_S, t1, c1 - c0)
            elif beats % _ACCOUNT_EVERY == 0:
                self.account(t1)


def start_heartbeat(watch: Optional[str] = None) -> bool:
    """Start the process's one beat thread unless it runs or metrics are
    off; ``watch`` also registers the calling thread under that name.
    -> whether a beat runs."""
    global _thread
    if not _metrics.metrics_enabled():
        return False
    if watch is not None:
        watch_thread(watch)
    with _start_lock:
        # a forked child inherits the handle and not the thread
        if _thread is None or not _thread.is_alive():
            _thread = threading.Thread(
                target=_Beat().run, name=THREAD_NAME, daemon=True)
            _thread.start()
    return True


def watch_thread(name: str) -> None:
    """Register the calling thread among those the beat thread reads at
    each accounting reading (``thread_readings()``).  A name in use by
    another live thread gets the native id appended."""
    me = threading.current_thread()
    native = threading.get_native_id()
    held = _watched.get(name)
    if held is not None and held[0] is not me and held[0].is_alive():
        name = f"{name}.{native}"
    _watched[name] = (me, threading.get_ident(), native)


def stall_log() -> List[dict]:
    """The stalls seen, oldest first (the last ``_STALL_CAP``)."""
    return list(_stall_log)


def thread_readings() -> List[dict]:
    """The readings of the watched threads, oldest first (the last
    ``_READING_CAP``): ``{t, threads: {name: {cpu_s, wait_s, state,
    frames}}}``, ``t`` on ``perf_counter``, the seconds totals since the
    thread began; ``wait_s`` only where the host has ``schedstat``."""
    return list(_readings)


def host_report() -> str:
    """One line for an operator, beside ``[phases]``: stalls seen with the
    longest and its cause, seconds throttled, mean beat lateness."""
    snap = _metrics.get_registry().snapshot()
    beat = next((h for h in snap["histograms"]
                 if h["name"] == BEAT_SERIES and not h["labels"]), None)
    if beat is None or not beat["count"]:
        return "no heartbeat"
    counters = {c["name"]: c["value"] for c in snap["counters"]
                if not c["labels"]}
    n = counters.get(STALLS_SERIES, 0)
    if n:
        longest = max(_stall_log, key=lambda e: e["end"] - e["start"],
                      default=None)
        stalls = f"stalls {n} ({counters.get(STALL_SECONDS_SERIES, 0.0):.2f}s)"
        if longest is not None:
            stalls += (f", longest {longest['end'] - longest['start']:.3f}s "
                       f"({longest['cause']})")
    else:
        stalls = "stalls none"
    throttled = counters.get(_SERIES["throttled_s"])
    return (f"{stalls}; throttled "
            + ("not counted on this host" if throttled is None
               else f"{throttled:.2f}s")
            + f"; beat late {beat['sum'] / beat['count'] * 1e3:.3f}ms mean "
            f"of {beat['count']}")
