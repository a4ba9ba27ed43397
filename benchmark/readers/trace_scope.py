"""Device self time per unit of work under one of the program's named
scopes (`jax.named_scope` in `ops/als.py` and `serve/topk.py`).

On this libtpu the scope path of a device operation (`jit(f)/topk.score/
dot_general:`) is the `tf_op` stat of its event's METADATA in the
`.xplane.pb`; `jax.profiler.ProfileData` shows an event's own stats only,
so this reader walks the file's protobuf wire format itself (XSpace ->
XPlane -> XLine -> XEvent, XEventMetadata, XStat; field numbers from
tsl/profiler/protobuf/xplane.proto) for the `/device:` planes alone.  An
operation counts under the innermost of the listed scopes in its path (a
fusion carries its root's), and as `unscoped` where it has none of them:
the compiler makes operations of its own that carry the loop's path alone
(in the ALS sweep ten `dynamic-update-slice`s, the concatenation of the
per-bucket normal equations).  Self time and the window are
`trace_reduce`'s.  Nothing is returned where no operation carries any of
the listed scopes: a program from before they existed, a CPU rehearsal, or
an executable that a compile cache handed back with older metadata.
"""

import functools

from benchmark import trace_reduce
from benchmark.readers import trace_clock

UNSCOPED = "unscoped"


def _varint(buf, i):
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, value) of one message: an int for a varint, bytes for
    a length-delimited field, None for a fixed-width one."""
    i, end = 0, len(buf)
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            value, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"wire type {wire} in an xplane file")
        yield key >> 3, value


def _message(buf):
    """A message whose fields appear once each -> {field number: value}."""
    return dict(_fields(buf))


def _plane_ops(plane):
    """One XPlane -> (name, [(start_ns, end_ns, op name, tf_op)]) of its
    `XLA Ops` line."""
    name, lines, stat_names, event_meta = "", [], {}, {}
    for field, value in _fields(plane):
        if field == 2:
            name = value.decode()
            if not name.startswith("/device:"):
                return name, []
        elif field == 3:
            lines.append(value)
        elif field == 4:  # map<int64, XEventMetadata>
            event_meta.update([_map_entry(value)])
        elif field == 5:  # map<int64, XStatMetadata>
            key, meta = _map_entry(value)
            stat_names[key] = _message(meta).get(2, b"").decode()
    tf_op = next((k for k, n in stat_names.items() if n == "tf_op"), None)
    named = {}
    for key, meta in event_meta.items():
        op, scope = "", ""
        for field, value in _fields(meta):
            if field == 2:
                op = value.decode()
            elif field == 5:  # XStat: metadata_id = 1, str_value = 5
                stat = _message(value)
                if stat.get(1) == tf_op:
                    scope = stat.get(5, b"").decode()
        named[key] = (op, scope)
    ops = []
    for line in lines:
        line = list(_fields(line))
        head = {field: value for field, value in line if field != 4}
        if head.get(2) != b"XLA Ops":  # XLine: name 2, timestamp_ns 3, events 4
            continue
        for field, value in line:
            if field == 4:  # XEvent: metadata_id 1, offset_ps 2, duration_ps 3
                ev = _message(value)
                start = head.get(3, 0) + ev.get(2, 0) / 1e3
                ops.append((start, start + ev.get(3, 0) / 1e3,
                            *named.get(ev.get(1), ("", ""))))
    return name, sorted(ops)


def _map_entry(buf):
    entry = _message(buf)
    return entry.get(1, 0), entry.get(2, b"")


@functools.lru_cache(maxsize=1)
def device_ops(path):
    """-> {device plane name: [(start_ns, end_ns, op name, tf_op)]}."""
    with open(path, "rb") as f:
        space = f.read()
    out = {}
    for field, plane in _fields(space):
        if field == 1:
            name, ops = _plane_ops(plane)
            if ops:
                out[name] = ops
    return out


def innermost(tf_op, scopes):
    """The last component of the scope path that is one of `scopes`."""
    for part in reversed(tf_op.split("/")):
        if part in scopes:
            return part
    return UNSCOPED


def seconds_by_scope(per_device, scopes, window=None):
    """Self seconds under each scope and `unscoped`, the mean over the
    device planes; None where no operation carries any of `scopes`."""
    total = dict.fromkeys((*scopes, UNSCOPED), 0.0)
    for ops in per_device.values():
        events = [(s, e, innermost(tf_op, scopes)) for s, e, _, tf_op in ops]
        if window:
            events = trace_reduce.clip(events, *window)
        for name, sec in trace_reduce.self_times(events).items():
            total[name] += sec / len(per_device)
    return total if any(total[s] for s in scopes) else None


def read(run, scope, among, per, scale=1.0):
    n = run.counts.get(per, 0)
    if not run.trace_path or n <= 0:
        return None
    window = trace_clock.window_in(
        trace_reduce.host_lines(trace_clock.profile(run.trace_path).planes))
    total = seconds_by_scope(device_ops(run.trace_path), among, window)
    if total is None:
        return None
    return total[scope] / n * scale, {
        UNSCOPED: total[UNSCOPED] / n * scale, "n": n}
