"""How far the device's clock leads the host's in one trace, from launches
both sides saw.

A program's run on the device (an event of the `XLA Modules` line) and two
host events of the same launch carry one `run_id`: the runtime's
`DoEnqueueProgram`, which hands the run to the device, and
`CompleteCallbacks`, which starts once the device reported it done.  A run
cannot start on the device before the first began nor end after the second
began, so with `lead` = host time - device time of one instant,

    lead >= DoEnqueueProgram start - device start     (each run; lo = the max)
    lead <= CompleteCallbacks start - device end      (each run; hi = the min)

Back-to-back launches on an idle device make both tight.  The names are
libtpu 0.0.34's; where no pair is found (a CPU rehearsal) or the bounds
cross, there is no estimate and the reader returns nothing.
"""

import functools

LAUNCH, DONE = "DoEnqueueProgram", "CompleteCallbacks"


@functools.lru_cache(maxsize=1)
def profile(path):
    """The trace file, read once per process however many readers ask."""
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


def window_in(hosts):
    """(start, end) of the harness's window annotation among
    `trace_reduce.host_lines`, or None."""
    from benchmark import trace_reduce

    return next(((starts[i], ends[i]) for starts, ends, names in hosts
                 for i, name in enumerate(names) if name == trace_reduce.WINDOW),
                None)


def _run_id(event):
    return next((v for key, v in event.stats if key == "run_id"), None)


def bounds(planes):
    """-> (lo_ns, hi_ns, runs paired) of the first device plane, or None."""
    planes = list(planes)  # ProfileData hands out a one-shot iterator
    runs = {}
    for plane in planes:
        if plane.name.startswith("/device:") and not runs:
            for ln in plane.lines:
                if ln.name == "XLA Modules":
                    for e in ln.events:
                        runs[_run_id(e)] = (e.start_ns, e.start_ns + e.duration_ns)
    runs.pop(None, None)
    lo, hi, paired = [], [], set()
    for plane in planes:
        if plane.name != "/host:CPU":
            continue
        for ln in plane.lines:
            for e in ln.events:
                if e.name not in (LAUNCH, DONE):
                    continue
                run = runs.get(_run_id(e))
                if run is None:
                    continue
                paired.add(run)
                if e.name == LAUNCH:
                    lo.append(e.start_ns - run[0])
                else:
                    hi.append(e.start_ns - run[1])
    if not lo or not hi or max(lo) > min(hi):
        return None
    return max(lo), min(hi), len(paired)


def lead_ns(planes):
    """The midpoint of the bounds: add it to a device timestamp to put it
    on the host's clock.  None where there is no estimate."""
    got = bounds(planes)
    return None if got is None else (got[0] + got[1]) / 2


def read(run):
    if not run.trace_path:
        return None
    got = bounds(profile(run.trace_path).planes)
    if got is None:
        return None
    lo, hi, n = got
    return (lo + hi) / 2e6, {"lo": lo / 1e6, "hi": hi / 1e6, "n": n}
