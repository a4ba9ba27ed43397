"""From a profiler trace (`.xplane.pb`) to busy time, the device operations
that took most time, and the idle gaps named by what the host was doing.

Read with `jax.profiler.ProfileData` alone.  Device operations are the events
of the `XLA Ops` line of each `/device:` plane; on a backend without device
planes (the CPU rehearsal) they are the host events that carry an `hlo_op`.
"""

from __future__ import annotations

import bisect
import re
from collections import defaultdict

TOP = 10
WINDOW = "benchmark_window"  # the harness's TraceAnnotation around the window
_MAX_GAPS = 4000      # the longest gaps carry the idle time
_MAX_SCAN = 4000


def clean(name):
    return re.sub(r"[^A-Za-z0-9_.:-]+", "_", name.lstrip("%"))[:64]


def device_events(planes):
    """-> {plane name: [(start_ns, end_ns, name)]}, sorted by start."""
    out = {}
    for plane in planes:
        if not plane.name.startswith("/device:"):
            continue
        lines = list(plane.lines)
        ops = [ln for ln in lines if ln.name == "XLA Ops"] or lines
        events = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                  for ln in ops for e in ln.events]
        if events:
            out[plane.name] = sorted(events)
    if out:
        return out
    events = []
    for plane in planes:
        if plane.name != "/host:CPU":
            continue
        for ln in plane.lines:
            for e in ln.events:
                if any(key == "hlo_op" for key, _ in e.stats):
                    events.append((e.start_ns, e.start_ns + e.duration_ns, e.name))
    return {"/host:CPU": sorted(events)} if events else {}


def host_lines(planes):
    """Host TraceMe events per thread: [(starts, ends, names)] each sorted."""
    out = []
    for plane in planes:
        if plane.name != "/host:CPU":
            continue
        for ln in plane.lines:
            ev = sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                        for e in ln.events if e.duration_ns > 0)
            if ev:
                out.append(([s for s, _, _ in ev], [e for _, e, _ in ev],
                            [n for _, _, n in ev]))
    return out


def merge(events):
    """Union of (start, end, ...) intervals, sorted by start -> [[start, end]]."""
    merged = []
    for ev in events:
        s, e = ev[0], ev[1]
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return merged


def self_times(events):
    """Seconds per operation name, a nested operation's time taken out of the
    one around it (a `while` holds its body's operations)."""
    total = defaultdict(float)
    stack = []  # (end, name, index into own)
    own = []
    for s, e, name in events:
        while stack and stack[-1][0] <= s:
            stack.pop()
        if stack:
            own[stack[-1][2]][1] -= min(e, stack[-1][0]) - s
        own.append([name, e - s])
        stack.append((e, name, len(own) - 1))
    for name, ns in own:
        total[clean(name)] += max(ns, 0.0) / 1e9
    return total


def innermost_host_event(lines, t):
    """Name of the shortest host event open at instant `t`, or None."""
    best = None
    for starts, ends, names in lines:
        i = bisect.bisect_right(starts, t) - 1
        for j in range(i, max(i - _MAX_SCAN, -1), -1):
            if ends[j] >= t and names[j] != WINDOW:
                if best is None or ends[j] - starts[j] < best[0]:
                    best = (ends[j] - starts[j], names[j])
                break
    return None if best is None else best[1]


def clip(events, lo, hi):
    return [(max(s, lo), min(e, hi), n) for s, e, n in events if e > lo and s < hi]


def top(seconds_by_name):
    ranked = sorted(seconds_by_name.items(), key=lambda kv: -kv[1])
    return [[name, sec] for name, sec in ranked[:TOP]]


def reduce_planes(planes, window_s):
    """`window_s` is the host's own measure of the traced window, used where
    the trace does not hold the harness's window annotation."""
    planes = list(planes)
    per_device = device_events(planes)
    hosts = host_lines(planes)
    for starts, ends, names in hosts:
        if WINDOW in names:
            i = names.index(WINDOW)
            per_device = {p: clip(ev, starts[i], ends[i])
                          for p, ev in per_device.items()}
            per_device = {p: ev for p, ev in per_device.items() if ev}
            window_s = (ends[i] - starts[i]) / 1e9
            break
    if not per_device:
        return {"busy_s": 0.0, "window_s": window_s, "n_devices": 0,
                "device_ops": [], "idle_gaps": []}
    busy = []
    ops = defaultdict(float)
    for events in per_device.values():
        busy.append(sum(e - s for s, e in merge(events)) / 1e9)
        for name, sec in self_times(events).items():
            ops[name] += sec / len(per_device)
    first = merge(next(iter(per_device.values())))
    gaps = sorted(((b[0] - a[1], a[1], b[0]) for a, b in zip(first, first[1:])),
                  reverse=True)[:_MAX_GAPS]
    named = defaultdict(float)
    for length, s, e in gaps:
        name = innermost_host_event(hosts, (s + e) / 2)
        named[clean(name) if name else "_no_host_span_"] += length / 1e9
    return {"busy_s": sum(busy) / len(busy), "window_s": window_s,
            "n_devices": len(per_device), "device_ops": top(ops),
            "idle_gaps": top(named)}


def reduce_file(path, window_s):
    from jax.profiler import ProfileData

    return reduce_planes(ProfileData.from_file(path).planes, window_s)
