"""Device idle time per unit of work, split by the program's own stage
spans (`obs/tracing.stage`: `topk.coalesce`, `topk.frame` and its children),
with the device's events first put on the host's clock (`trace_clock`).

A gap is shared out by overlap, not by its middle: each piece of it goes to
the shortest stage span open then (a child before its `topk.frame`, an
inline single on a handler thread before the dispatcher's `topk.coalesce`);
what no stage span covers is `unnamed`.  The window's two edges count as
idle too.  Nothing is returned where the trace holds no stage span (a
program from before they existed) or the clock lead has no estimate.
"""

from collections import defaultdict

from benchmark import trace_reduce
from benchmark.readers import trace_clock


def pieces(spans):
    """(start, end, name) spans, nested or overlapping -> pieces that do not
    overlap, each named by the shortest span open over it."""
    edges = sorted([(s, 1, i) for i, (s, _, _) in enumerate(spans)]
                   + [(e, 0, i) for i, (_, e, _) in enumerate(spans)])
    out, active, prev = [], set(), None
    for t, opening, i in edges:
        if active and t > prev:
            s, e, name = min((spans[j] for j in active),
                             key=lambda span: span[1] - span[0])
            out.append((prev, t, name))
        (active.add if opening else active.discard)(i)
        prev = t
    return out


def share_out(gaps, named):
    """Nanoseconds of the sorted `gaps` [(start, end)] under each name of the
    sorted, non-overlapping `named` pieces."""
    out = defaultdict(float)
    first = 0
    for g_start, g_end in gaps:
        while first < len(named) and named[first][1] <= g_start:
            first += 1
        for start, end, name in named[first:]:
            if start >= g_end:
                break
            out[name] += min(g_end, end) - max(g_start, start)
    return out


def starved(planes, lead_ns, prefix):
    """-> (idle ns, {stage: ns}) of the first device plane inside the
    harness's window, or None where there is nothing to read."""
    planes = list(planes)
    hosts = trace_reduce.host_lines(planes)
    spans = [(s, e, n) for starts, ends, names in hosts
             for s, e, n in zip(starts, ends, names) if n.startswith(prefix)]
    per_device = trace_reduce.device_events(planes)
    if not spans or not per_device:
        return None
    events = [(s + lead_ns, e + lead_ns, n)
              for s, e, n in next(iter(per_device.values()))]
    window = trace_clock.window_in(hosts) or (events[0][0], events[-1][1])
    busy = trace_reduce.merge(trace_reduce.clip(events, *window))
    marks = [window[0]] + [t for interval in busy for t in interval] + [window[1]]
    gaps = [(a, b) for a, b in zip(marks[::2], marks[1::2]) if b > a]
    return sum(b - a for a, b in gaps), dict(share_out(gaps, pieces(spans)))


def read(run, per, prefix):
    n = run.counts.get(per, 0)
    if not run.trace_path or n <= 0:
        return None
    planes = list(trace_clock.profile(run.trace_path).planes)
    lead = trace_clock.lead_ns(planes)
    got = None if lead is None else starved(planes, lead, prefix)
    if got is None:
        return None
    idle, by_stage = got
    ms = 1e-6 / n
    return idle * ms, {
        "by_stage": {name: ns * ms for name, ns in sorted(by_stage.items())},
        "unnamed": (idle - sum(by_stage.values())) * ms,
        "lead_ms": lead / 1e6, "n": n}
