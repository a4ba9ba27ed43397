"""The host's stalls inside the window, as the program's own heartbeat saw
them (`flink_ms_tpu/obs/hostbeat.py`, through `obs/tracing`): a thread that
sleeps 10 ms at a time and logs every sleep that came back more than 50 ms
late, with the kernel's accounting since the reading before it.

Value: the longest stall whose interval `[start, end]` meets `run.window`,
in ms (the whole stall, not the part inside); **0.0 with `n: 0` where the
beat ran and saw none**.  `extra`: `n`, `total_ms`, `stalls` (each with
`offset_s` from the window's start, `late_ms`, `cause`, `cpu_ms` the whole
process gained across it, the accounting's `deltas` over `since_s`), and
`threads`: for each thread the program registered (`tracing.watch_thread`),
from the beat thread's readings inside the window, the CPU seconds and,
where the host has `schedstat`, the run-queue seconds it gained between the
first and the last of them, and the reading before which it gained the
least CPU (`idlest`: offset, the seconds to the reading before, its gain,
state letter and innermost frames).

In a traced run each stall also gets `device_idle_ms`: the time inside
`[t - late, t]` in which no operation ran on the first device plane, `t`
being the program's `host.stall` marker in the trace.  Device and host
events are laid together uncorrected: the device clock's lead of 0.5-2 ms
(`device_clock_lead_ms`) is noise against a stall of 50 ms or more.

Nothing is returned where the program has no `stall_log` (a program from
before the beat) or the beat observed nothing inside the window (metrics
off, or the thread died)."""

from benchmark import trace_reduce

MARKER = "host.stall"
BEAT = "tpums_host_beat_late_seconds"


def markers(planes):
    """[(t_ns, late_ms)] of the program's stall markers, by time."""
    out = []
    for plane in planes:
        if plane.name != "/host:CPU":
            continue
        for ln in plane.lines:
            for e in ln.events:
                if e.name != MARKER:
                    continue
                late = next((v for key, v in e.stats if key == "late_ms"), None)
                if late is not None:
                    out.append((e.start_ns, float(late)))
    return sorted(out)


def device_idle(planes):
    """[(late_ms, idle ms of the first device plane inside the stall)]."""
    planes = list(planes)
    per_device = trace_reduce.device_events(planes)
    if not per_device:
        return []
    busy = trace_reduce.merge(next(iter(per_device.values())))
    out = []
    for t, late_ms in markers(planes):
        lo = t - late_ms * 1e6
        ran = sum(min(e, t) - max(s, lo) for s, e in busy if e > lo and s < t)
        out.append((late_ms, late_ms - ran / 1e6))
    return out


def threads_in(readings, t0, t1):
    inside = [r for r in readings if t0 <= r["t"] <= t1]
    out = {}
    for name in sorted({n for r in inside for n in r["threads"]}):
        mine = [(r["t"], r["threads"][name]) for r in inside
                if "cpu_s" in r["threads"].get(name, ())]
        if len(mine) < 2:
            continue
        got = {"readings": len(mine),
               "cpu_s": mine[-1][1]["cpu_s"] - mine[0][1]["cpu_s"]}
        if "wait_s" in mine[-1][1] and "wait_s" in mine[0][1]:
            got["wait_s"] = mine[-1][1]["wait_s"] - mine[0][1]["wait_s"]
        (t_a, a), (t_b, b) = min(
            zip(mine, mine[1:]),
            key=lambda pair: pair[1][1]["cpu_s"] - pair[0][1]["cpu_s"])
        got["idlest"] = {"offset_s": t_b - t0, "over_s": t_b - t_a,
                         "cpu_s": b["cpu_s"] - a["cpu_s"],
                         "state": b.get("state"), "frames": b.get("frames")}
        out[name] = got
    return out


def read(run):
    from flink_ms_tpu.obs import tracing

    log = getattr(tracing, "stall_log", None)
    if log is None or not run.window or run.window[1] is None:
        return None
    if run.hist_delta(BEAT)[1] <= 0:
        return None
    t0, t1 = run.window
    stalls = [{"offset_s": e["start"] - t0,
               "late_ms": (e["end"] - e["start"]) * 1e3,
               "cause": e["cause"], "cpu_ms": e["cpu_s"] * 1e3,
               "since_s": e["since_s"], "deltas": e["deltas"]}
              for e in log() if e["end"] > t0 and e["start"] < t1]
    if run.trace_path:
        from benchmark.readers import trace_clock

        idle = device_idle(trace_clock.profile(run.trace_path).planes)
        for s in stalls:
            near = min(idle, key=lambda m: abs(m[0] - s["late_ms"]),
                       default=None)
            if near is not None and abs(near[0] - s["late_ms"]) < 1e-3:
                s["device_idle_ms"] = near[1]
    lates = [s["late_ms"] for s in stalls]
    return max(lates, default=0.0), {
        "n": len(stalls), "total_ms": sum(lates), "stalls": stalls,
        "threads": threads_in(tracing.thread_readings(), t0, t1)}
