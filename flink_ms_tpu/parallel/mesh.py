"""Device-mesh bootstrap and sharding helpers.

The reference distributes work over Flink TaskManager slots (`setBlocks` /
`setParallelism`); the TPU-native equivalent is a `jax.sharding.Mesh` whose
single "blocks" axis plays the role of the reference's block/parallelism
count (SURVEY.md §2.3).  Intra-slice exchanges ride ICI via XLA collectives
(`all_gather` for factor broadcast, `psum` for CoCoA averaging); multi-host
scaling layers DCN on top through `jax.distributed` without code changes
here — the mesh simply spans more devices.
"""

from __future__ import annotations

import os
import sys
import threading
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..obs import metrics as obs_metrics
from ..obs import tracing

BLOCK_AXIS = "blocks"

_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"
_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_CACHE_LOAD_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# The four costs of getting a program, each a duration event of jax's:
# tracing the Python body, lowering the jaxpr to a module, loading the
# executable from the persistent cache, and the backend's compile, which
# CONTAINS the load (jax times the whole compile-or-load call), so on a
# warm cache ``compile`` is mostly ``cache load``.
_SECONDS_SERIES = {
    _TRACE_EVENT: "tpums_jax_trace_seconds_total",
    _LOWER_EVENT: "tpums_jax_lower_seconds_total",
    _CACHE_LOAD_EVENT: "tpums_jax_cache_load_seconds_total",
    _BACKEND_COMPILE_EVENT: "tpums_jax_compile_seconds_total",
}

_acquire_lock = threading.Lock()
_acquired = False
_listening = False


def repo_cache_dir() -> str:
    """The fixed compile-cache location: ``<checkout>/.jax_cache``.  The
    path is part of the cache key, so it never carries a pid, a time or a
    temp dir."""
    checkout = os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    return os.path.join(checkout, ".jax_cache")


def _compile_counters() -> tuple:
    """(seconds, persistent-cache hits, misses) of this process's compiles."""
    reg = obs_metrics.get_registry()
    return (
        reg.counter("tpums_jax_compile_seconds_total"),
        reg.counter("tpums_jax_compile_cache_hits_total"),
        reg.counter("tpums_jax_compile_cache_misses_total"),
    )


def _count_compiles() -> None:
    """Feed jax's own compile events into the metrics registry, once a
    process, so a trainer's summary line and a server's METRICS reply both
    say what the process spent tracing, lowering, loading and compiling,
    for which function, and whether the persistent cache answered.  Each
    seconds series keeps its unlabelled total and gains one
    ``{kind=<function>}`` child per function (jax's ``fun_name``, without
    the ``jit(...)`` the lowering and the compile wrap it in).  jax fires
    these only when it traces, lowers, loads or compiles: never on a cached
    call.  Counters are looked up when an event fires, so a registry reset
    (tests) loses nothing after it."""
    global _listening
    if _listening:
        return
    _listening = True
    counter = obs_metrics.get_registry().counter
    for name in _SECONDS_SERIES.values():
        counter(name)  # a scrape reads 0, not nothing, before the first event
    events = {_CACHE_HIT_EVENT: "tpums_jax_compile_cache_hits_total",
              _CACHE_MISS_EVENT: "tpums_jax_compile_cache_misses_total"}
    # per thread: how many trace events are open (a jit called while another
    # is traced reports its own trace inside the caller's: only the
    # outermost counts, or the total would pass the wall), and the seconds
    # the cache's load event reported since the last compile event (jax
    # names no function on it; it fires inside the backend-compile event of
    # the function being loaded)
    local = threading.local()

    def on_start(event, _start, **_kw):
        if event == _TRACE_EVENT:
            local.tracing = getattr(local, "tracing", 0) + 1

    def on_duration(event, duration, fun_name=None, **_kw):
        series = _SECONDS_SERIES.get(event)
        if series is None:
            return
        if event == _TRACE_EVENT:
            local.tracing = max(getattr(local, "tracing", 1) - 1, 0)
            if local.tracing:
                return
        counter(series).inc(duration)
        if event == _CACHE_LOAD_EVENT:
            local.loaded = getattr(local, "loaded", 0.0) + duration
            return
        if fun_name is None:
            return
        if fun_name.startswith("jit(") and fun_name.endswith(")"):
            fun_name = fun_name[4:-1]
        counter(series, kind=fun_name).inc(duration)
        if event == _BACKEND_COMPILE_EVENT and getattr(local, "loaded", 0.0):
            counter(_SECONDS_SERIES[_CACHE_LOAD_EVENT],
                    kind=fun_name).inc(local.loaded)
            local.loaded = 0.0

    def on_event(event, **_kw):
        name = events.get(event)
        if name is not None:
            counter(name).inc()

    jax.monitoring.register_scalar_listener(on_start)
    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)


def compile_report() -> str:
    """One-line summary of what this process spent getting its programs so
    far, and the three functions that cost most (trace + lower + compile,
    the load being part of the compile)."""
    secs, hits, misses = _compile_counters()
    snap = obs_metrics.get_registry().snapshot()["counters"]
    total = {c["name"]: c["value"] for c in snap if not c["labels"]}
    trace, lower, load = (
        total.get(_SECONDS_SERIES[e], 0.0)
        for e in (_TRACE_EVENT, _LOWER_EVENT, _CACHE_LOAD_EVENT))
    costed = {_SECONDS_SERIES[e] for e in (
        _TRACE_EVENT, _LOWER_EVENT, _BACKEND_COMPILE_EVENT)}
    by_fun: dict = {}
    for c in snap:
        kind = c["labels"].get("kind")
        if kind is not None and c["name"] in costed:
            by_fun[kind] = by_fun.get(kind, 0.0) + c["value"]
    top = sorted(by_fun.items(), key=lambda kv: -kv[1])[:3]
    return (f"compile {secs.value:.2f}s, persistent cache "
            f"{hits.value} hit(s) / {misses.value} miss(es); "
            f"trace {trace:.2f}s, lower {lower:.2f}s, cache load {load:.2f}s; "
            "costliest: "
            + (", ".join(f"{k} {v:.2f}s" for k, v in top) or "none"))


def acquire_devices(host_pinned: bool = False) -> list:
    """THE device rule: every entry point that touches a device calls this
    first.  It returns the devices the process computes on, and

    - raises when the default backend is ``cpu`` and nobody asked for it.
      jax's built-in ``tpu`` factory fails quietly, so a process that finds
      no chip — or finds it held by another process — would otherwise
      train or serve from the host without a word.  ``JAX_PLATFORMS=cpu``
      is the explicit ask; ``host_pinned`` is the serving index's
      ``TPUMS_TOPK_PLATFORM=cpu`` pin, which returns the host devices even
      when a chip is present;
    - logs one line — platform, device_kind, device count — which is how an
      operator (and ``chip_smoke.py``) learns where a job ran, and records
      the backend's start as the phase ``device.backend``;
    - turns the persistent compile cache on when the backend is ``tpu``:
      at ``$JAX_COMPILATION_CACHE_DIR`` when that is set (jax reads it
      itself; nothing is set in code), else at ``repo_cache_dir()``, with
      the programs' metadata (named scopes, source locations) in the key.

    Call it after ``jax.distributed.initialize`` in multi-process jobs."""
    global _acquired
    if _acquired:
        devices = jax.devices()
    else:
        # the process's first look at its devices starts the backend
        with tracing.phase("device.backend"):
            devices = jax.devices()
    backend = devices[0].platform
    asked_for_cpu = os.environ.get("JAX_PLATFORMS", "").strip() == "cpu"
    if backend == "cpu" and not (asked_for_cpu or host_pinned):
        raise RuntimeError(
            "flink_ms_tpu: jax's default backend is 'cpu' but this process "
            "was not asked to run on the host.  Likely cause: no TPU is "
            "attached, or another process already holds the chip (a chip "
            "belongs to one process at a time).  Set JAX_PLATFORMS=cpu to "
            "run on the host on purpose."
        )
    if host_pinned and backend != "cpu":
        devices = jax.devices("cpu")
    with _acquire_lock:
        if not _acquired:
            _acquired = True
            _count_compiles()
            # the host's heartbeat, with the caller (a trainer's or a driver's
            # main thread) among the threads it reads
            tracing.start_heartbeat(watch=threading.current_thread().name)
            if backend == "tpu":
                if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
                    jax.config.update(
                        "jax_compilation_cache_dir", repo_cache_dir())
                # named scopes and source lines live in the metadata that
                # the cache key leaves out by default, and a hit hands back
                # the executable with the metadata of whoever compiled it
                # first.  On a machine that keeps its cache (the chip
                # tool's does) a profile of today's code then carries
                # yesterday's names, or none: the TOPK programs of PR 25
                # came back without their scopes (PERF.md, PR 25).  The
                # price: an edit that moves a line jax records for a
                # traced operation, or a checkout at another path,
                # compiles once more.
                jax.config.update(
                    "jax_compilation_cache_include_metadata_in_key", True)
            print(
                f"[mesh] platform={devices[0].platform} "
                f"device_kind={devices[0].device_kind} "
                f"devices={len(devices)}",
                file=sys.stderr, flush=True,
            )
    return devices


def host_device():
    """A CPU device for host-side draws, or None (= the default device)
    when this process has no CPU backend: under ``JAX_PLATFORMS=tpu`` jax
    initialises only ``tpu`` and a ``"cpu"`` lookup raises.
    ``local_devices``, not ``devices``: in a multi-process run the global
    list starts with process 0's device, which other processes cannot
    address."""
    try:
        return jax.local_devices(backend="cpu")[0]
    except RuntimeError:
        return None


def make_mesh(n_devices: Optional[int] = None, devices: Optional[Sequence] = None) -> Mesh:
    """1-D mesh over the first `n_devices` devices (default: all)."""
    if devices is None:
        devices = acquire_devices()
    if n_devices is not None:
        if n_devices > len(devices):
            raise ValueError(
                f"requested {n_devices} devices but only {len(devices)} available"
            )
        devices = devices[:n_devices]
    return Mesh(np.asarray(devices), (BLOCK_AXIS,))


def mesh_for_blocks(
    blocks: Optional[int], n_devices: Optional[int] = None
) -> Mesh:
    """Pick the mesh for a ``--blocks``/``--parallelism`` request.

    - an explicit ``--devices`` count wins;
    - multi-process runs always span every global device: a mesh capped
      below the process count could own no devices on some process, which
      would wedge that process's collectives (each process must
      participate in every mesh it is part of);
    - ``blocks <= devices``: a mesh of exactly ``blocks`` devices;
    - ``blocks > devices``: all devices — the kernels stack the extra
      logical blocks per device (the SVM kernel vmaps ceil(K/D) SDCA
      chains per device; the ALS solver is row-exact, so any logical
      block count partitions onto D device blocks without changing the
      result).
    """
    if n_devices is not None:
        return make_mesh(n_devices)
    if jax.process_count() > 1 or blocks is None:
        return make_mesh()
    avail = len(acquire_devices())
    if blocks > avail:
        print(
            f"[mesh] --blocks {blocks} exceeds the {avail} visible "
            f"device(s); running the logical blocks on {avail} device "
            "block(s) (SVM stacks chains per device; ALS partitioning is "
            "row-exact)"
        )
    return make_mesh(min(blocks, avail))


def block_sharding(mesh: Mesh, *, rank: int = 2) -> NamedSharding:
    """Shard the leading axis over the block axis, replicate the rest."""
    return NamedSharding(mesh, P(BLOCK_AXIS, *([None] * (rank - 1))))


def row_bucket(n: int, n_shards: int, floor: int = 8) -> int:
    """Pad a row count to the next power-of-two PER-SHARD bucket.

    The same pad-to-bucket discipline as the ALS degree buckets and the
    top-k batch shapes (``warm_batch_shapes``): a catalog that grows row
    by row must not recompile its sharded programs per row, so the padded
    total is ``n_shards * 2^ceil(log2(ceil(n / n_shards)))`` — every shard
    holds the same power-of-two row count and XLA sees a handful of
    distinct shapes over the catalog's whole growth curve.  ``floor``
    bounds the per-shard size from below so tiny catalogs still give each
    shard enough rows for a local ``top_k``."""
    if n_shards < 1:
        raise ValueError("need n_shards >= 1")
    per_shard = max((max(n, 1) + n_shards - 1) // n_shards, floor)
    return n_shards * (1 << (per_shard - 1).bit_length())


def row_capacity(n: int, floor: int = 1024) -> int:
    """Rows a matrix of ``n`` live rows on ONE device is allocated at: the
    live rows and spare positions for ids that arrive later (the top-k
    index writes a new id into the next of them, ``serve/topk.py``).  Over
    a mesh ``row_bucket``'s pad rows are that capacity already.

    The spare rows are scanned by every query like the live ones, so they
    are what every reader pays for a writer's new ids: ``n / 256`` of them
    (0.4% of a scan; a power of two as ``row_bucket`` takes would be 68%
    at 10,000,000 rows, and a sixteenth, 6.25%, more than the serving
    cells' whole bound), at least ``floor`` (one whole drain of the
    index's ``apply_cap`` new ids always fits), the total a multiple of
    1024 rows.  A catalog that outgrows them is copied to the capacity of
    its new size where the device has room for both."""
    spare = max(max(n, 0) >> 8, floor)
    return -(-(max(n, 0) + spare) // 1024) * 1024


def device_memory(device) -> Optional[int]:
    """One device's memory in bytes as the runtime reports it, None where
    it reports none (the CPU backend; a device that is only described)."""
    try:
        stats = device.memory_stats()
    except Exception:  # a described topology has no runtime to ask
        return None
    return (stats or {}).get("bytes_limit")


# On-chip vector memory (VMEM) of one TensorCore by ``device_kind``: the
# space XLA marks ``S(1)`` in a compiled program's layouts.  No runtime
# reports it; a kind that is not listed has none to plan with.
_FAST_MEMORY_BYTES = {"TPU v5 lite": 128 << 20}


def fast_memory(device) -> Optional[int]:
    """One device's fast on-chip memory in bytes, None where none is known
    (the CPU backend, a TPU generation nobody has read)."""
    return _FAST_MEMORY_BYTES.get(device.device_kind)


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def num_blocks(mesh: Mesh) -> int:
    return mesh.shape[BLOCK_AXIS]
