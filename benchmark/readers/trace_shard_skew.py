"""What a frame of a program that runs on several chips at once costs
beyond a chip's own work: the launches that do not start together and the
wait inside the collective.

Each device plane's operations are cut into frames by the program's named
scopes: a frame runs from the first operation under `first`
(`topk.shard_score`) that follows an operation under `last` (`topk.merge`)
to the end of the last operation under `last` before the next such start;
its busy time is the union of the plane's operations in between, the
compiler's unscoped copies included.  The chips' frames are matched by
time (`same_launch`), and per frame

    span  = the last chip's end - the first chip's start
    skew  = span - the chips' mean busy time

so a frame whose chips start together and never wait reads 0.  The device
planes of one host share one clock in the profiler's file.  On the TPU v5e
the chips' first operations start within microseconds of each other although
their launches do not: a chip launched early waits inside its program before
its first operation, where no operation shows it.  `extra.launch_spread_ms`
is that wait's bound, the last minus the first start of the chips' `XLA
Modules` events per launch.  Nothing is returned where no operation carries
both scopes (a program from before they existed, a CPU rehearsal) or there
is one plane only.
"""

import bisect
import functools

from benchmark import trace_reduce
from benchmark.readers import trace_clock, trace_scope


def plane_frames(ops, first, last):
    """One plane's [(start_ns, end_ns, busy_ns)], `ops` sorted by start as
    `trace_scope.device_ops` gives them."""
    frames, current, closing = [], None, True
    for start, end, _, tf_op in ops:
        scope = trace_scope.innermost(tf_op, (first, last))
        if scope == first and closing:
            if current:
                frames.append(current)
            current, closing = [], False
        elif scope == last:
            closing = True
        if current is not None:
            current.append((start, end, scope))
    if current:
        frames.append(current)
    out = []
    for events in frames:
        ends = [e for _, e, scope in events if scope == last]
        if not ends:
            continue  # cut off before its merge
        whole = [(s, e) for s, e, _ in events if e <= max(ends)]
        busy = sum(e - s for s, e in trace_reduce.merge(whole))
        out.append((events[0][0], max(ends), busy))
    return out


def same_launch(planes):
    """Sorted [(start, end, ...)] of each plane -> [[one of each plane]] for
    every interval of the first plane that the nearest interval of every
    other plane overlaps: the k-th launch of one chip is the k-th of the
    others, but a trace or a window may cut a chip's first or last."""
    out = []
    starts = [[f[0] for f in frames] for frames in planes[1:]]
    for frame in planes[0]:
        row = [frame]
        for frames, at in zip(planes[1:], starts):
            i = bisect.bisect_left(at, frame[0])
            near = min((j for j in (i - 1, i) if 0 <= j < len(frames)),
                       key=lambda j: abs(at[j] - frame[0]))
            if frames[near][0] < frame[1] and frames[near][1] > frame[0]:
                row.append(frames[near])
        if len(row) == len(planes):
            out.append(row)
    return out


def matched(per_device, first, last, window=None):
    """-> [[(start, end, busy) of each plane] per frame seen on every plane]."""
    planes = [plane_frames(ops, first, last) for ops in per_device.values()]
    if window:
        planes = [[f for f in frames if f[0] >= window[0] and f[1] <= window[1]]
                  for frames in planes]
    if len(planes) < 2 or not all(planes):
        return []
    return same_launch(planes)


def window_of(path):
    return trace_clock.window_in(
        trace_reduce.host_lines(trace_clock.profile(path).planes))


@functools.lru_cache(maxsize=1)
def _frames(path, first, last):
    return matched(trace_scope.device_ops(path), first, last, window_of(path))


def frames_of(run, first, last):
    """The matched frames inside the harness's window, or []; cut once per
    trace however many readers ask."""
    return _frames(run.trace_path, first, last) if run.trace_path else []


def launch_spread_ms(run):
    """Mean over the window's launches of the last chip's program start less
    the first's, from the planes' `XLA Modules` events.  None without them."""
    window = window_of(run.trace_path)
    runs = [sorted((e.start_ns, e.start_ns + e.duration_ns)
                   for ln in plane.lines if ln.name == "XLA Modules"
                   for e in ln.events)
            for plane in trace_clock.profile(run.trace_path).planes
            if plane.name.startswith("/device:")]
    runs = [r for r in runs if r]  # the file also holds planes with no line
    if window:
        runs[:1] = [[r for r in runs[0] if window[0] <= r[0] < window[1]]]
    rows = same_launch(runs) if len(runs) > 1 else []
    spreads = [max(r[0] for r in row) - min(r[0] for r in row) for row in rows]
    return sum(spreads) / len(spreads) / 1e6 if spreads else None


def read(run, first, last):
    rows = frames_of(run, first, last)
    if not rows:
        return None
    n = len(rows)
    span = sum(max(f[1] for f in row) - min(f[0] for f in row) for row in rows)
    busy = sum(sum(f[2] for f in row) / len(row) for row in rows)
    start = sum(max(f[0] for f in row) - min(f[0] for f in row) for row in rows)
    slowest = sum(max(f[2] for f in row) for row in rows)
    ms = 1e-6 / n
    return (span - busy) * ms, {
        "span_ms": span * ms, "busy_ms": busy * ms,
        "slowest_busy_ms": slowest * ms, "start_spread_ms": start * ms,
        "launch_spread_ms": launch_spread_ms(run),
        "planes": len(rows[0]), "n": n}
