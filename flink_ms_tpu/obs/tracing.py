"""Dapper-style request tracing over the tab-separated wire protocol.

A trace is a 16-hex-char id that a client stamps onto a request as a
trailing ``tid=<id>`` tab field, the server echoes back, and every hop in
between (shard fan-out threads, HA failover retries, the microbatch
dispatcher) records against as structured **events**: one JSON object per
event with ``ts``/``tid``/``kind`` plus free-form span fields (queue wait,
batch size, device seconds).  Reconstructing one slow request end to end
is then a filter of the event log by tid.

On top of the flat events sits a **span** layer: an event that also
carries ``sid`` (8-hex span id), ``psid`` (parent span id), ``t0`` (wall
start) and ``dur_s`` is a timed node in the request's causal tree.  A
thread-local span stack parents nested spans automatically; crossing a
process boundary, the wire tid field widens to ``tid=<tid>/<sid>`` so the
server's spans parent under the client RPC that caused them (the bare
``tid=<tid>`` form stays accepted, and servers echo the raw value so old
clients' exact-suffix unstamp keeps working).  ``obs/forensics.py``
assembles the per-process JSONL spills back into trees and diffs the
slow ones against the fast ones.

Wire compatibility is the hard constraint: the seed protocol's servers
validate field counts strictly (``len(parts) == 3`` etc.), so the tid
field is ONLY appended while a trace context is active — untraced traffic
stays byte-identical in both directions, and old servers never see the
extra field unless an operator opts a client in.

Context is thread-local because the serving stack is thread-per-connection
and the sharded clients fan out on pool threads; ``call_with_trace``
captures the submitting thread's tid so pool workers inherit it
explicitly (thread-locals do not cross ``ThreadPoolExecutor.submit``).

Event sinks, controlled by ``TPUMS_TRACE``:

- unset/``0`` — events still go to a small in-process ring buffer (cheap:
  one dict + deque append), which is what the in-process tests read;
- a path — additionally appended as JSONL to that file (``-`` = stderr),
  which is what ``scripts/chaos_kill.py`` and multi-process smoke runs
  use to correlate across processes.  The file sink rotates at
  ``TPUMS_TRACE_MAX_BYTES`` (keeping ``TPUMS_TRACE_KEEP`` old files) so a
  long soak cannot fill the disk.

``TPUMS_TRACE_SAMPLE`` (0..1) is the head-sampling knob: ``sample_trace``
rolls it once per would-be trace root, so span cost scales with the
sample rate, not the request rate.

**Three ways to time a block, and which one a new call site takes.**

- a piece of one REQUEST's path (a verb, a fan-out leg, a retry) ->
  ``span``: head-sampled, on the wall clock, parented across threads and
  processes by the wire tid, spilled to JSONL, free when untraced;
- work done once a FRAME or once a CALL on a path that repeats (the
  dispatcher's stages, a fit's enqueue) -> ``stage``: an interval on the
  PROFILER's clock, beside the device's timeline, recorded nowhere unless
  somebody profiles;
- work done once a FIT, once a BUILD or once a PROCESS (set-up) ->
  ``phase``: a ``stage`` that is also kept, in ``phase_log()`` and in
  ``tpums_phase_seconds{kind}``, always on.

There is no fourth, and none is to be added.  The one thing here that
times NO block is the host's heartbeat (``obs/hostbeat.py``, re-exported
below as ``start_heartbeat``, ``watch_thread``, ``stall_log``,
``thread_readings``, ``host_report``): a stall is the absence of work, so
no block is open to time it.
"""

from __future__ import annotations

import contextlib
import json
import os
import random
import secrets
import sys
import threading
import time
from collections import deque
from typing import Callable, Deque, Dict, List, Optional

from . import metrics as _metrics
from .hostbeat import (  # noqa: F401  (the beat's readers live here)
    host_report,
    stall_log,
    start_heartbeat,
    thread_readings,
    watch_thread,
)

TID_FIELD = "tid="
_RING_CAP = 4096
_DEFAULT_MAX_BYTES = 64 << 20
_DEFAULT_KEEP = 3

class _TraceLocal(threading.local):
    # Class-level defaults so the untraced read is a plain attribute hit:
    # getattr(local, "tid", None) on a thread that never traced otherwise
    # raises-and-catches AttributeError internally (~0.5us), and
    # current_trace()/current_span_id() run on every request's hot path.
    tid = None
    spans = None


_local = _TraceLocal()

# Cross-thread stage registry for the sampling profiler (obs/profiler.py).
# The span stack above is thread-LOCAL (only the owning thread can read
# it), but the profiler samples from its own timer thread, so spans
# additionally publish their stage *kind* here, keyed by thread ident.
# Mutation discipline: each thread touches only its own ident's list, the
# sampler only reads — under the GIL that makes the plain dict safe, and a
# rare torn read costs one mis-attributed sample, never corruption.  Cost
# rides the TRACED path only (span enter/exit); untraced requests never
# touch it.
_thread_stages: Dict[int, List[str]] = {}


def push_stage(kind: str) -> None:
    """Mark this thread as inside ``kind`` for the profiler's sampler.
    Span enter does this automatically; bare call sites (benches, the
    server dispatch choke point) may use ``profiler.prof_stage``."""
    ident = threading.get_ident()
    stack = _thread_stages.get(ident)
    if stack is None:
        stack = _thread_stages[ident] = []
    stack.append(kind)


def pop_stage() -> None:
    ident = threading.get_ident()
    stack = _thread_stages.get(ident)
    if stack:
        stack.pop()
        if not stack:
            _thread_stages.pop(ident, None)


def thread_stages() -> Dict[int, str]:
    """Sampler view: thread ident -> innermost active stage name.  Copies
    under the GIL; threads that are outside any stage are absent."""
    out: Dict[int, str] = {}
    for ident, stack in list(_thread_stages.items()):
        try:
            if stack:
                out[ident] = stack[-1]
        except IndexError:  # racing pop on the owner thread
            continue
    return out


# what ``stage`` hands out where there is no profiler to write to
_NO_STAGE = contextlib.nullcontext()


def stage(name: str, **fields):
    """``with stage("topk.fetch"):`` — a named interval on the PROFILER's
    clock: a ``jax.profiler.TraceAnnotation``, so it lands in the same
    trace, on the same host clock, as the runtime's own events and the
    device timeline (an operator's ``--profileDir`` trace, the benchmark's
    traced window).  ``fields`` become the event's stats.  With no profiler
    session open it costs one constructor and a flag test in C++; in a
    process that never imported jax (clients, the load generator) it is a
    shared no-op object, and jax is not imported for its sake.

    Not a ``span``: those are per-request, head-sampled, on the wall clock
    and spill to JSONL; a stage is per-thread wall time for whoever is
    profiling, and records nothing otherwise (``phase`` is the stage that
    also records)."""
    jax = sys.modules.get("jax")
    if jax is None:
        return _NO_STAGE
    return jax.profiler.TraceAnnotation(name, **fields)


# ---------------------------------------------------------------------------
# set-up phases
# ---------------------------------------------------------------------------

# One wall offset for the process, read at import: every instant this module
# hands out as wall time is ``perf_counter() + wall_offset()``, so a span, its
# parent and the batcher's stamps sit on one clock and nest without the drift
# of a fresh wall-clock reading each.
_WALL_OFFSET = time.time() - time.perf_counter()
_PHASE_CAP = 1024


def wall_offset() -> float:
    """Wall time minus ``time.perf_counter()``, as read when this module was
    imported."""
    return _WALL_OFFSET


class _PhaseLocal(threading.local):
    stack = None  # names of the phases open on this thread, outermost first


_phases = _PhaseLocal()
# deque.append is atomic; entries are appended whole on exit and never edited
_phase_log: Deque[dict] = deque(maxlen=_PHASE_CAP)


class phase:
    """``with phase("als.prepare.fill"):`` — a named piece of SET-UP, timed
    where it happens and kept.  On exit one entry ``{name, start, end,
    parent, thread}`` goes to a bounded in-memory log (``phase_log()``):
    ``start`` / ``end`` on ``time.perf_counter()``, ``parent`` the name of
    the innermost phase open on the same thread (None for a root; a thread
    started inside a phase begins a root of its own).  The same enter / exit
    opens the ``stage`` of that name, so a profile shows the phase on the
    profiler's clock, and observes ``tpums_phase_seconds{kind=<name>}``, so
    a METRICS scrape shows what each retrain wave or index rebuild spent
    where.  There is no switch: a phase is always recorded.

    A phase belongs at the boundaries of work done once a fit, once a
    build or once a process (tens of entries a process), never on a
    per-iteration, per-round, per-frame or per-request path (``stage`` is
    for those).  It adds no wait of its own: around asynchronous work
    (a ``device_put``) it times the enqueue only."""

    __slots__ = ("name", "_parent", "_stage", "_t0")

    def __init__(self, name: str):
        self.name = name

    def __enter__(self) -> "phase":
        stack = _phases.stack
        if stack is None:
            stack = _phases.stack = []
        self._parent = stack[-1] if stack else None
        stack.append(self.name)
        self._stage = stage(self.name)
        self._stage.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        end = time.perf_counter()
        self._stage.__exit__(exc_type, exc, tb)
        _phases.stack.pop()
        _phase_log.append({"name": self.name, "start": self._t0, "end": end,
                           "parent": self._parent,
                           "thread": threading.get_ident()})
        _metrics.get_registry().histogram(
            "tpums_phase_seconds", kind=self.name).observe(end - self._t0)


def phase_log() -> List[dict]:
    """The phases that have ended, oldest first (the last ``_PHASE_CAP``)."""
    return list(_phase_log)


def clear_phases() -> None:
    _phase_log.clear()


def phase_children(entry: dict, entries: List[dict]) -> List[dict]:
    """The entries opened directly under ``entry``: same thread, its name as
    their parent, inside its interval.  Phases of one thread nest, so the
    children do not overlap: self time is the duration minus their sum."""
    return [e for e in entries
            if e["parent"] == entry["name"] and e["thread"] == entry["thread"]
            and e["start"] >= entry["start"] and e["end"] <= entry["end"]]


def phase_seconds(entries) -> Dict[str, float]:
    """name -> summed seconds of the given entries, in the order met."""
    out: Dict[str, float] = {}
    for e in entries:
        out[e["name"]] = out.get(e["name"], 0.0) + e["end"] - e["start"]
    return out


def phase_report(entries: Optional[List[dict]] = None) -> str:
    """One line for an operator: every root by name with its seconds and,
    in brackets, its children's, e.g. ``als.prepare 9.73s (als.prepare.order
    2.10, als.prepare.fill 7.60), als.place 1.20s``.  Phases of one name
    (two rebuilds) are summed."""
    entries = phase_log() if entries is None else entries
    roots = [e for e in entries if e["parent"] is None]
    parts = []
    for name, secs in phase_seconds(roots).items():
        inner = ", ".join(f"{n} {s:.2f}" for n, s in phase_seconds(
            c for e in roots if e["name"] == name
            for c in phase_children(e, entries)).items())
        parts.append(f"{name} {secs:.2f}s" + (f" ({inner})" if inner else ""))
    return ", ".join(parts) if parts else "none"


_ring_lock = threading.Lock()
_ring: Deque[dict] = deque(maxlen=_RING_CAP)
_file_lock = threading.Lock()
_file_handle = None
_file_path_cached: Optional[str] = None
_file_bytes = 0
_file_max_bytes = _DEFAULT_MAX_BYTES


def new_trace_id() -> str:
    """16 hex chars — wide enough to never collide within a bench run,
    short enough to cost one small tab field on the wire."""
    return secrets.token_hex(8)


def new_span_id() -> str:
    """8 hex chars — unique within one trace, not globally."""
    return secrets.token_hex(4)


_sample_cache = ("", 0.0)  # (raw env string, parsed rate)


def trace_sample_rate() -> float:
    """``TPUMS_TRACE_SAMPLE`` clamped to [0, 1]; 0 when unset/garbage.
    Parsed once per distinct env value — workload drivers roll this per
    request root, so the steady-state cost is one dict lookup and a
    string compare, not a float parse (the 3% hot-path bar counts it)."""
    global _sample_cache
    raw = os.environ.get("TPUMS_TRACE_SAMPLE") or "0"
    cached_raw, cached = _sample_cache
    if raw is cached_raw or raw == cached_raw:
        return cached
    try:
        rate = max(0.0, min(1.0, float(raw)))
    except ValueError:
        rate = 0.0
    _sample_cache = (raw, rate)
    return rate


def sample_trace() -> Optional[str]:
    """Roll the sampling dice once: a fresh trace id with probability
    ``TPUMS_TRACE_SAMPLE``, else None.  Workload drivers and the update
    plane call this at trace-root points so span volume follows the knob
    instead of the request rate."""
    r = trace_sample_rate()
    if r <= 0.0:
        return None
    if r < 1.0 and random.random() >= r:
        return None
    return new_trace_id()


# ---------------------------------------------------------------------------
# thread-local context
# ---------------------------------------------------------------------------

def current_trace() -> Optional[str]:
    return getattr(_local, "tid", None)


def set_trace(tid: Optional[str]) -> Optional[str]:
    """Install ``tid`` as this thread's trace context -> previous value."""
    prev = getattr(_local, "tid", None)
    _local.tid = tid
    return prev


class trace_span:
    """``with trace_span() as tid:`` — installs a (fresh or given) trace id
    for the block and restores the previous context on exit."""

    __slots__ = ("tid", "_prev")

    def __init__(self, tid: Optional[str] = None):
        self.tid = tid or new_trace_id()
        self._prev = None

    def __enter__(self) -> str:
        self._prev = set_trace(self.tid)
        return self.tid

    def __exit__(self, *exc) -> None:
        set_trace(self._prev)


def current_span_id() -> Optional[str]:
    """Innermost open span on this thread, or None outside any span."""
    stack = getattr(_local, "spans", None)
    return stack[-1] if stack else None


def current_context() -> Optional[str]:
    """The value to hand ``call_with_trace`` when fanning out to a pool:
    ``tid/sid`` while a span is open (so the worker's spans parent under
    it), the bare tid otherwise, None when untraced."""
    tid = current_trace()
    if tid is None:
        return None
    sid = current_span_id()
    return f"{tid}/{sid}" if sid else tid


class span:
    """``with span("stage", op=...):`` — one timed node in the request's
    causal tree.  Allocates a span id, parents under the innermost open
    span on this thread, and emits a single event carrying
    ``sid``/``psid``/``t0``/``dur_s`` on exit: both instants are
    ``perf_counter`` readings, ``t0`` moved to wall time by the process's one
    ``wall_offset()``, so a child's ``t0 + dur_s`` never passes its
    parent's.  A no-op (no id, no event) when no trace context is active,
    so instrumented code pays one thread-local read on the untraced path."""

    __slots__ = ("kind", "fields", "tid", "sid", "_psid", "_t0")

    def __init__(self, kind: str, tid: Optional[str] = None, **fields):
        self.kind = kind
        self.fields = fields
        self.tid = tid
        self.sid = None

    def __enter__(self) -> "span":
        tid = self.tid if self.tid is not None else current_trace()
        if tid is None:
            return self
        self.tid = tid
        self.sid = new_span_id()
        self._psid = current_span_id()
        stack = getattr(_local, "spans", None)
        if stack is None:
            stack = _local.spans = []
        stack.append(self.sid)
        push_stage(self.kind)
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        if self.sid is None:
            return
        _local.spans.pop()
        pop_stage()
        if exc_type is not None:
            self.fields.setdefault("error", repr(exc))
        dur_s = time.perf_counter() - self._t0
        event(self.kind, tid=self.tid, sid=self.sid, psid=self._psid,
              t0=self._t0 + _WALL_OFFSET, dur_s=dur_s, **self.fields)


def span_event(kind: str, tid: Optional[str] = None,
               dur_s: Optional[float] = None, t0: Optional[float] = None,
               sid: Optional[str] = None, psid: Optional[str] = None,
               **fields) -> Optional[dict]:
    """One-shot span record for call sites that already know the duration
    (client RPCs, server replies, synthesized microbatch stages).  None
    when untraced."""
    tid = tid if tid is not None else current_trace()
    if tid is None:
        return None
    return event(kind, tid=tid, sid=sid if sid is not None else new_span_id(),
                 psid=psid if psid is not None else current_span_id(),
                 t0=t0, dur_s=dur_s, **fields)


def call_with_trace(tid: Optional[str], fn: Callable, *args, **kwargs):
    """Run ``fn`` with ``tid`` installed — the pool-submit adapter used by
    the sharded/HA fan-out (``pool.submit(call_with_trace, tid, fn, ...)``)
    so worker threads inherit the submitting request's context.  ``tid``
    may be the composite ``tid/sid`` from ``current_context()``: the sid
    seeds the worker thread's span stack so its spans parent under the
    caller's open span."""
    if tid is None:
        return fn(*args, **kwargs)
    base, psid = split_tid(tid)
    prev = set_trace(base)
    prev_stack = getattr(_local, "spans", None)
    _local.spans = [psid] if psid else []
    try:
        return fn(*args, **kwargs)
    finally:
        set_trace(prev)
        _local.spans = prev_stack if prev_stack is not None else []


# ---------------------------------------------------------------------------
# wire helpers
# ---------------------------------------------------------------------------

def stamp(request: str, tid: Optional[str] = None) -> str:
    """Append ``\\ttid=<id>`` when a trace is active; otherwise return the
    request untouched (the byte-compatibility guarantee lives here)."""
    tid = tid if tid is not None else current_trace()
    if tid is None:
        return request
    return f"{request}\t{TID_FIELD}{tid}"


def unstamp_reply(reply: str, tid: str) -> str:
    """Strip the server's tid echo off a reply.  Only the exact suffix for
    the id we sent is removed, so payloads that legitimately contain tabs
    (MGET) cannot be corrupted."""
    suffix = f"\t{TID_FIELD}{tid}"
    if reply.endswith(suffix):
        return reply[: -len(suffix)]
    return reply


def pop_tid(parts: List[str]) -> Optional[str]:
    """Server side: remove and return a trailing ``tid=`` field from a
    split request line (mutates ``parts``); None when untraced.  The
    returned value is the RAW wire form — possibly ``tid/sid`` — so the
    server can echo it verbatim; split with ``split_tid``."""
    if len(parts) >= 2 and parts[-1].startswith(TID_FIELD):
        return parts.pop()[len(TID_FIELD):]
    return None


def wire_tid(tid: str, sid: Optional[str] = None) -> str:
    """The wire form of a trace context: ``tid/sid`` when the caller has
    an open span for this RPC, the bare tid otherwise."""
    return f"{tid}/{sid}" if sid else tid


def split_tid(raw: Optional[str]):
    """Split a raw wire tid into ``(trace_id, parent_span_id)`` — the
    parent is None for the bare pre-span form."""
    if raw and "/" in raw:
        base, _, psid = raw.partition("/")
        return base, (psid or None)
    return raw, None


# ---------------------------------------------------------------------------
# event log
# ---------------------------------------------------------------------------

def _trace_file() -> Optional[str]:
    v = os.environ.get("TPUMS_TRACE", "").strip()
    if v in ("", "0", "1"):
        return None
    return v


def trace_file_path() -> Optional[str]:
    """The active JSONL spill path (None when TPUMS_TRACE is off or the
    stderr sink ``-``) — where forensics should collect from."""
    p = _trace_file()
    return None if p == "-" else p


def event(kind: str, tid: Optional[str] = None, **fields) -> dict:
    """Record one structured event.  Always lands in the in-process ring;
    additionally appended as one JSON line to ``TPUMS_TRACE`` when that is
    a path.  Returns the event dict (chaos_kill prints it)."""
    ev: Dict = {"ts": time.time(),
                "tid": tid if tid is not None else current_trace(),
                "kind": kind}
    ev.update(fields)
    if "sid" in ev:
        # span record: count it so fleet_signals can rate the span volume
        _metrics.get_registry().counter("tpums_trace_spans_total").inc()
    elif "psid" not in ev:
        # point event inside an open span parents under it automatically,
        # so retries/fan-out markers land in the assembled tree
        psid = current_span_id()
        if psid is not None:
            ev["psid"] = psid
    with _ring_lock:
        _ring.append(ev)
    path = _trace_file()
    if path is not None:
        line = json.dumps(ev, separators=(",", ":"), default=str)
        if path == "-":
            print(line, file=sys.stderr)
        else:
            _append_line(path, line)
    return ev


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, "") or default)
    except ValueError:
        return default


def _append_line(path: str, line: str) -> None:
    global _file_handle, _file_path_cached, _file_bytes, _file_max_bytes
    with _file_lock:
        if _file_handle is None or _file_path_cached != path:
            if _file_handle is not None:
                try:
                    _file_handle.close()
                except OSError:
                    pass
            _file_handle = open(path, "a", buffering=1)
            _file_path_cached = path
            try:
                _file_bytes = os.path.getsize(path)
            except OSError:
                _file_bytes = 0
            # rotation knobs are read once per open — cheap appends, and a
            # test that re-points TPUMS_TRACE re-reads them naturally
            _file_max_bytes = _env_int("TPUMS_TRACE_MAX_BYTES",
                                       _DEFAULT_MAX_BYTES)
        if _file_bytes >= _file_max_bytes > 0:
            _rotate_locked(path)
        _file_handle.write(line + "\n")
        _file_bytes += len(line) + 1


def _rotate_locked(path: str) -> None:
    """Size-capped keep-K rotation: path -> path.1 -> ... -> path.K, the
    oldest dropped.  Caller holds ``_file_lock``."""
    global _file_handle, _file_bytes
    try:
        _file_handle.close()
    except OSError:
        pass
    keep = max(0, _env_int("TPUMS_TRACE_KEEP", _DEFAULT_KEEP))
    try:
        if keep == 0:
            os.remove(path)
        else:
            for i in range(keep - 1, 0, -1):
                src = f"{path}.{i}"
                if os.path.exists(src):
                    os.replace(src, f"{path}.{i + 1}")
            os.replace(path, f"{path}.1")
    except OSError:
        pass  # cross-process rotation race: the loser just keeps appending
    _file_handle = open(path, "a", buffering=1)
    _file_bytes = 0


def recent_events(tid: Optional[str] = None,
                  kind: Optional[str] = None) -> List[dict]:
    """Snapshot the ring buffer, optionally filtered by tid and/or kind —
    the in-process way to reconstruct a request chain."""
    with _ring_lock:
        evs = list(_ring)
    if tid is not None:
        evs = [e for e in evs if e.get("tid") == tid]
    if kind is not None:
        evs = [e for e in evs if e.get("kind") == kind]
    return evs


def clear_events() -> None:
    with _ring_lock:
        _ring.clear()


def load_events(path: str) -> List[dict]:
    """Parse a JSONL event file (cross-process correlation: chaos runs,
    obs_smoke).  Malformed lines are skipped, not fatal — the file is
    append-shared across processes."""
    out: List[dict] = []
    try:
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    out.append(json.loads(line))
                except ValueError:
                    continue
    except OSError:
        pass
    return out


def events_counter(kind: str, **labels) -> None:
    """Event + matching counter in one call — supervisor transitions use
    this so 'respawn happened' is both a countable series and a
    reconstructable timeline entry."""
    event(kind, **labels)
    _metrics.get_registry().counter(
        "tpums_events_total", kind=kind).inc()
