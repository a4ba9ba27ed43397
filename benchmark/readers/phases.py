"""The program's own set-up phases (`flink_ms_tpu/obs/tracing.phase`): the
entries of `tracing.phase_log()` that ended at or before the window opened.

`name`: the summed duration of the phases of that name, in seconds; `extra`
has their count `n`, `self_s` (the duration less the children's) and
`children` (name -> seconds).  `roots: true`: the summed duration of the
phases with no parent other than `device.backend` (the runtime's start, not
the program's work); `extra.by_phase` has every root, `extra.outside_s` is
`setup_s` less all of them: imports, the benchmark's own synthesis and
reference work, the first iterations the driver runs, the load generator's
lead-in.  Nothing is returned where the program has no `phase_log` (a
program from before the phases existed) or no such phase ran."""

BACKEND = "device.backend"


def read(run, name=None, roots=False):
    from flink_ms_tpu.obs import tracing

    log = getattr(tracing, "phase_log", None)
    if log is None or not run.window:
        return None
    entries = [e for e in log() if e["end"] <= run.window[0]]
    if roots:
        by_phase = tracing.phase_seconds(
            e for e in entries if e["parent"] is None)
        if not by_phase:
            return None
        value = sum(s for n, s in by_phase.items() if n != BACKEND)
        extra = {"by_phase": by_phase}
        if "setup_s" in run.clock:
            extra["outside_s"] = run.clock["setup_s"] - sum(by_phase.values())
        return value, extra
    mine = [e for e in entries if e["name"] == name]
    if not mine:
        return None
    value = sum(e["end"] - e["start"] for e in mine)
    children = tracing.phase_seconds(
        c for e in mine for c in tracing.phase_children(e, entries))
    return value, {"n": len(mine), "self_s": value - sum(children.values()),
                   "children": children}
