"""Top-k recommendation serving from the live model table.

The reference serves only point lookups; top-k over a 26k..1M-item catalog
would need one RPC per item.  TPU-native serving instead keeps a
device-resident mirror of the item-factor matrix and answers top-k with one
jitted matmul + ``lax.top_k`` — the BASELINE.json config
"flink-queryable-client top-k recommendation serving from ALS factors".

Index maintenance is INCREMENTAL: the table pushes changed keys into the
index's dirty set (``add_change_listener``), and at query time

- updates to rows already in the index are applied in place on device (the
  m changed rows written into the resident matrix's own buffer, which is
  donated to the program — O(m), not O(catalog), on the device too), so a
  streaming online-SGD load never forces full rebuilds on the query path;
- a genuinely new item id is applied by the same drain: the matrix is
  allocated at a CAPACITY above its live rows (``mesh.row_capacity`` on one
  device, ``mesh.row_bucket``'s pad rows over a mesh), the new row is
  written at the next free position, and the query programs are told how
  many rows are live (a device scalar compared with the row number in the
  score's epilogue, so one compile serves every live count of a capacity
  and a spare row can never reach an answer).  No rebuild, and ids already
  served keep their positions;
- when no free position is left the matrix is copied on the device into one
  of the next capacity, off the lock, and swapped in as a rebuild is; where
  the device has no room for both, new ids WAIT (counted, said once on
  stderr) and everything already served keeps being served and updated;
- a rebuild (a changed row width, a matrix lost to a failed update, a
  writer that outran the query path, a new id on the IVF tier, which keeps
  no spare positions) runs on ONE background thread while queries keep
  answering from the current (briefly stale) index, and swaps in
  atomically.  It takes the rows the index serves overlaid by the table's,
  so a catalog installed by ``bulk_load`` does not revert to the table.

The first query after startup pays the initial build (the benchmark's
``index_build_s``).

RETRIEVAL TIERS (round 11).  Two levers lift the catalog ceiling from the
~1M rows the single-array exact scan tops out at:

- **Sharded exact tier** — on a multi-device host the factor matrix is
  laid out as a permanently mesh-resident array, row-sharded over
  ``make_mesh()``'s block axis and padded to the shared power-of-two
  bucket discipline (``mesh.row_bucket``; pad rows score ``-1e30`` by
  the live-row compare above so they can never surface; the padded matrix
  exists on the devices only, each shard put from its own rows,
  ``_pack``).  A batched
  TOPK is then ONE compiled ``shard_map`` program per batch-shape
  bucket: each device scores and ``top_k``'s its own row slice, an
  ``all_gather`` of the (D, B, k) partials feeds a tiny cross-shard
  merge, and only the final (B, k) winners ever reach the host — zero
  host round-trips on the steady path.  The dirty-row scatter and
  background rebuild run against the sharded array too (each shard walks
  the drain's batch and writes the rows of its own range into its own
  buffer, ``_scatter_program``), so streaming SGD never forces full
  rebuilds here either.  Engages automatically past
  ``TPUMS_TOPK_SHARD_MIN_ROWS`` when the mesh has >1 device;
  ``TPUMS_TOPK_SHARDED=1|0`` forces/disables.

- **IVF ANN tier** (``serve/ann.py``) — a coarse k-means quantizer over
  the item factors (trained on-device, refreshed by the same background
  rebuild thread) makes retrieval cost sublinear in the catalog: a query
  probes the ``TPUMS_ANN_NPROBE`` nearest centroid lists and the rows of
  those lists are scored EXACTLY, so the only approximation is a missing
  candidate — which the build-time recall probe measures and gates on
  (``TPUMS_ANN_RECALL_MIN``).  While the tier serves, the resident matrix
  itself lies in list order (a list is a run of whole blocks of rows,
  read as blocks; ``_ids`` / ``_id_pos`` follow, pad positions hold no
  id), so the catalog still exists once and an UPDATE still lands where
  the next query reads.  One device only: a mesh-sharded catalog keeps
  the sharded exact tier.
  ``TPUMS_TOPK_TIER`` picks: ``exact``, ``ivf``, or ``auto`` (default —
  IVF past ``TPUMS_ANN_MIN_ROWS`` while the measured recall holds the
  gate, exact otherwise, so the approximation is a contract, not a
  hope).
"""

from __future__ import annotations

import itertools
import os
import sys
import threading
import time
from collections import deque
from typing import Deque, List, Optional, Tuple

import numpy as np

from ..obs import metrics as obs_metrics
from ..obs.tracing import phase, stage
from .table import ModelTable


def _tier_mode() -> str:
    """TPUMS_TOPK_TIER: ``exact`` | ``ivf`` | ``auto`` (default).  Unknown
    values degrade to ``auto`` (the safe tier: exact until the catalog is
    big enough AND the measured recall holds the gate)."""
    tier = os.environ.get("TPUMS_TOPK_TIER", "auto").strip().lower()
    return tier if tier in ("exact", "ivf", "auto") else "auto"


def _index_platform() -> str:
    """TPUMS_TOPK_PLATFORM: ``""`` (default — the index lives on the
    process's default backend, the chip) or ``cpu``, the explicit host
    pin for a process that holds the chip for something else and wants
    its index host-resident.  A worker that must never touch the chip
    (a chip belongs to one process) is started with ``JAX_PLATFORMS=cpu``
    instead."""
    return os.environ.get("TPUMS_TOPK_PLATFORM", "")


_warm_started = False
_warm_lock = threading.Lock()


def _device_errors():
    """Device-side failures the index survived by serving what it had
    (warm-up, IVF build, background rebuild).  Production keeps
    answering; ``chip_smoke.py`` reads this as zero."""
    return obs_metrics.get_registry().counter(
        "tpums_topk_device_errors_total")


def _warm_jit_async() -> None:
    """Pay JAX's cold-pipeline cost off the query path, once per process.

    The first jit in a fresh process costs ~8 s (backend init + compiler
    warm-up) and the first scatter another ~3 s — measured on the CPU
    backend; a same-structure compile at the real shapes afterwards is
    ~1 s.  Serving workers answer their first TOPK/TOPKV within a client's
    5 s queryTimeout only if that cold cost is paid at startup, so this
    runs tiny dummy-shape compiles of exactly the two programs the index
    uses (matmul+top_k, row scatter) on a daemon thread.  The devices are
    acquired by the caller first: the device rule raises on the
    constructing thread, not inside this one."""
    global _warm_started
    dev = _target_device()
    with _warm_lock:
        if _warm_started:
            return
        _warm_started = True

    def warm():
        try:
            import jax

            m = jax.device_put(np.zeros((8, 4), np.float32), dev)
            q = jax.device_put(np.zeros((4,), np.float32), dev)
            jax.jit(lambda a, b: jax.lax.top_k(a @ b, 2))(m, q)
            pos = np.zeros((4,), dtype=np.int32)
            vec = np.zeros((4, 4), np.float32)
            _scatter_rows(m, pos, vec, 0).block_until_ready()
        except Exception as e:  # pragma: no cover - best-effort warm-up
            _device_errors().inc()
            print(f"[topk] jit warm-up failed: {e}", file=sys.stderr)

    threading.Thread(target=warm, name="topk-jit-warm", daemon=True).start()


_index_devices_cache: dict = {}


def _index_devices() -> list:
    """Devices the index lives on, by the device rule
    (``mesh.acquire_devices``): the default backend, or the host under
    the TPUMS_TOPK_PLATFORM=cpu pin.  Raises when the process expected
    the chip and got the host.  Cached per knob value — the decision is
    fixed for the life of the process."""
    platform = _index_platform()
    devices = _index_devices_cache.get(platform)
    if devices is None:
        from ..parallel.mesh import acquire_devices

        devices = acquire_devices(host_pinned=platform == "cpu")
        _index_devices_cache[platform] = devices
    return devices


def _target_device():
    return _index_devices()[0]


_index_mesh_cache: dict = {}


def _index_mesh():
    """Mesh over every device of the index's platform, or None when only
    one device is visible (the sharded tier has nothing to shard over).
    Cached per platform knob, like the devices."""
    platform = _index_platform()
    if platform in _index_mesh_cache:
        return _index_mesh_cache[platform]
    from ..parallel.mesh import make_mesh

    devices = _index_devices()
    mesh = make_mesh(devices=devices) if len(devices) > 1 else None
    _index_mesh_cache[platform] = mesh
    return mesh


def _to_host(x) -> np.ndarray:
    """The ONE funnel through which query results reach the host.  On the
    steady path exactly one (B, 2k) array passes through per dispatch —
    the zero-host-copy test monkeypatches this to prove no catalog-sized
    array ever does."""
    return np.asarray(x)


def _pack_results(scores, idx):
    """The last step of every query program (traced): ``(..., k)`` f32
    scores and int32 row indices -> ONE ``(..., 2k)`` int32 array, the
    scores bit-cast beside the indices.  Two outputs are two buffers to
    allocate at every launch and two device->host copies, the second
    issued only when the first is back (0.6 ms of a blocked dispatcher a
    frame on a TPU v5e, PERF.md §6); one output is one of each.  Integers,
    so no bit pattern is a NaN or a denormal anywhere on the way."""
    import jax
    import jax.numpy as jnp

    return jnp.concatenate(
        [jax.lax.bitcast_convert_type(scores, jnp.int32), idx], axis=-1)


def _unpack_results(packed: np.ndarray):
    """Host side of ``_pack_results`` -> ``(scores, idx)`` views of the
    one host array, bit for bit what the program computed."""
    k = packed.shape[-1] // 2
    return packed[..., :k].view(np.float32), packed[..., k:]


# Scores are full-f32 products on every backend.  The TPU's default matmul
# precision is one bf16 pass (~1e-2 absolute error on factor dot products):
# enough to reorder near-equal neighbours and to make the same TOPK differ
# between a chip and a host replica.  The scan is memory-bound, so the
# extra MXU passes are not what a query waits for.
_SCORE_PRECISION = "highest"

# the score of a row that is not live (spare capacity, a shard's pad rows,
# a masked ANN candidate slot), so that it can never win a top-k over any
# real row; float32-safe margin below any realistic factor dot product
_PAD_SCORE = np.float32(-1e30)

# A new id is written into spare capacity by the drain that finds it.  False
# is the behaviour before PR 57, a background rebuild for every new id:
# nothing in the program sets it; the benchmark's control
# ``rebuild_on_insert`` patches it to show that its limits tell the two apart.
_INSERTS_IN_PLACE = True

# rows of one device-to-host strip when a rebuild reads back the rows the
# index serves and the table lacks (0.21 GB of 200-wide rows)
_FETCH_STRIP = 1 << 18
# ONE put of more than 4 GiB took 26 s on a TPU v5e where 4.0 GB take 0.4
# (``ann._PUT_STRIP``'s reason): a placement of more than ``_ONE_PUT_BYTES``
# goes in strips of ``_PUT_STRIP`` rows
_ONE_PUT_BYTES = 4 << 30
_PUT_STRIP = 1 << 20

# rows the index remembers in ``apply_log`` (a 20 s window at 600 rows/s is
# 12,000; an entry is a key and three numbers)
_APPLY_LOG_CAP = 1 << 16

_scatter_program_cache: dict = {}


def _scatter_program(mesh=None):
    """The update scatter: the first ``count`` rows of the batch ``vec``
    written over the rows of ``matrix`` at ``pos``, in the matrix's own
    buffer and in the layout the runtime gave it.  One jitted program under
    the named scope ``topk.update_scatter``, so that a device trace tells it
    from the score (``benchmark/readers/trace_scope.py``): a ``fori_loop``
    whose trip count is the traced scalar ``count`` and whose body is one
    ``dynamic_update_slice`` of one row.  Rows are applied in batch order (a
    position that occurs twice ends with the later row) and rows from
    ``count`` on are not read at all, so the batch keeps ONE static shape,
    ``apply_cap`` rows, whatever a drain holds: one compile an index, warmed
    at build time with ``count`` 0.

    The matrix IS donated: the result is the argument's buffer (the compiled
    program aliases them and holds no copy of the matrix, no relayout and no
    scratch: ``tests/test_cholesky_pallas.py`` compiles it for a described
    v5e), and the handle that was passed in is deleted when the call
    returns.  A caller rebinds its name to the result and keeps no other
    reference.  XLA's own scatter (``matrix.at[pos].set(vec)``) wants the
    matrix row-major, the TPU keeps an ``(n, 200)`` f32 matrix column-major,
    and the two whole-matrix relayouts between them were 27.3 of a frame's
    39.7 device ms where 28 rows of 800 B changed (PERF.md §6, PR 55).

    ``mesh``: None for a matrix on one device; for the row-sharded layout
    its mesh, and the same loop then runs inside a ``shard_map`` over it:
    every shard walks the whole batch, writes the rows whose position falls
    in its own range (its offset subtracted) and rewrites one of its own
    rows with itself for the others.  No collective, no relayout."""
    fn = _scatter_program_cache.get(mesh)
    if fn is not None:
        return fn
    from functools import partial

    import jax
    import jax.numpy as jnp

    def write_rows(matrix, pos, vec, count):
        with jax.named_scope("topk.update_scatter"):
            n = matrix.shape[0]  # the shard's rows inside a shard_map
            lo = 0 if mesh is None else jax.lax.axis_index(BLOCK_AXIS) * n

            def write(i, m):
                row = jax.lax.dynamic_slice_in_dim(vec, i, 1)
                at = pos[i] - lo
                if mesh is not None:
                    mine = (at >= 0) & (at < n)
                    at = jnp.clip(at, 0, n - 1)
                    row = jnp.where(
                        mine, row, jax.lax.dynamic_slice_in_dim(m, at, 1))
                return jax.lax.dynamic_update_slice(m, row, (at, 0))

            return jax.lax.fori_loop(0, count, write, matrix)

    if mesh is not None:
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        from ..parallel.mesh import BLOCK_AXIS

        write_rows = shard_map(
            write_rows, mesh=mesh,
            in_specs=(P(BLOCK_AXIS, None), P(None), P(None, None), P()),
            out_specs=P(BLOCK_AXIS, None), check_vma=False)

    @partial(jax.jit, donate_argnums=0)
    def scatter_rows(matrix, pos, vec, count):
        return write_rows(matrix, pos, vec, count)

    _scatter_program_cache[mesh] = scatter_rows
    return scatter_rows


def _scatter_rows(matrix, pos, vec, count):
    """``_scatter_program`` for this matrix, chosen on its sharding alone
    (a mesh of more than one device: the ``shard_map`` form).  ``matrix`` is
    donated: use the result."""
    mesh = getattr(matrix.sharding, "mesh", None)
    if mesh is not None and mesh.size == 1:
        mesh = None
    return _scatter_program(mesh)(matrix, pos, vec, np.int32(count))


def _mask_spare_rows(scores, live, first=0):
    """Traced, the score's epilogue in every exact query program: the
    scores of rows from ``live`` on (spare capacity, pad rows) become
    ``_PAD_SCORE``.  ``scores``: ``(..., rows)``, row ``first`` the first of
    them (a shard's offset); ``live``: the traced int32 count of live rows,
    so one compile serves every live count.  On a TPU the compare is one
    small fusion over a row-number vector and the select rides the matmul's
    own output fusion: no pass over the scores of its own."""
    import jax
    import jax.numpy as jnp

    at = first + jax.lax.broadcasted_iota(
        jnp.int32, scores.shape, scores.ndim - 1)
    return jnp.where(at < live, scores, _PAD_SCORE)


_build_programs: Optional[tuple] = None


def _build_jits():
    """-> ``(place, grow, strip)``, the three programs that move whole rows
    of one device's matrix, compiled once a shape.  ``place(matrix, rows,
    start)``: ``rows`` written into the DONATED ``matrix`` from row
    ``start`` -> the matrix and one element of it, a token to wait on once
    the matrix itself has been donated to the next call.  ``grow(matrix,
    capacity)``: a zero matrix of ``capacity`` rows with ``matrix`` as its
    first rows (not donated: the old one serves until the swap).  ``strip(matrix, start, rows)``: ``rows`` rows of
    ``matrix`` from ``start``, for the way back to the host."""
    global _build_programs
    if _build_programs is None:
        from functools import partial

        import jax
        import jax.numpy as jnp

        @partial(jax.jit, donate_argnums=0)
        def place(matrix, rows, start):
            matrix = jax.lax.dynamic_update_slice_in_dim(
                matrix, rows, start, axis=0)
            return matrix, matrix[0, 0]

        @partial(jax.jit, static_argnums=1)
        def grow(matrix, capacity):
            return jax.lax.dynamic_update_slice_in_dim(
                jnp.zeros((capacity, matrix.shape[1]), matrix.dtype),
                matrix, 0, axis=0)

        @partial(jax.jit, static_argnums=2)
        def strip(matrix, start, rows):
            return jax.lax.dynamic_slice_in_dim(matrix, start, rows, axis=0)

        _build_programs = (place, grow, strip)
    return _build_programs


def _free_bytes(dev) -> Optional[int]:
    """Bytes ``dev`` has free by its runtime's own count, None where the
    backend reports no memory (the CPU): whoever asks then has room."""
    stats = dev.memory_stats() or {}
    if "bytes_limit" not in stats:
        return None
    return stats["bytes_limit"] - stats.get("bytes_in_use", 0)


def _one_put_serves(rows: np.ndarray, capacity: int, dev) -> bool:
    """Whether ``rows`` go to ``dev`` in ONE put, to be copied into the zero
    matrix of ``capacity`` rows there (``_build_jits``' ``grow``): up to
    ``_ONE_PUT_BYTES``, where the device has room for the rows beside the
    matrix.  One put returns at once and flies under the caller's next work
    (3.3 s of id dict at 5M rows); strips hold the caller a transfer each
    (1.15 s for 4 GB, PERF.md §6)."""
    if not 0 < rows.nbytes <= _ONE_PUT_BYTES:
        return False
    free = _free_bytes(dev)
    return free is None or rows.nbytes + capacity * rows.shape[1] * 4 <= free


def _place_in_strips(rows: np.ndarray, capacity: int, dev):
    """``rows`` as the first rows of a zero ``(capacity, k)`` matrix on
    ``dev``, without a padded copy on the host and with no put of more than
    ``_PUT_STRIP`` rows: strips of one size (the last one starts early
    enough to be whole and rewrites what the one before it gave) are put
    from views of ``rows`` and written into the donated buffer.  Those in
    flight take no more than half of what the device has free beside the
    matrix (five of a 10M x 200 catalog's ten on a 16 GB chip), two where
    the backend reports no memory."""
    import jax
    import jax.numpy as jnp

    from .ann import _whole_chunks

    place = _build_jits()[0]
    n, k = rows.shape
    free = _free_bytes(dev)
    matrix = jnp.zeros((capacity, k), jnp.float32, device=dev)
    if not n:
        return matrix
    strip, starts = _whole_chunks(n, _PUT_STRIP)
    in_flight = 2 if free is None else max(
        2, int((free - capacity * k * 4) // (2 * strip * k * 4)))
    placed = []
    for lo in starts:
        if len(placed) == in_flight:
            placed.pop(0).block_until_ready()
        matrix, token = place(
            matrix, jax.device_put(rows[lo:lo + strip], dev), np.int32(lo))
        placed.append(token)
    return matrix


_sharded_program_cache: dict = {}


def _sharded_topk_program(mesh):
    """One jitted shard_map top-k per mesh (jax re-specializes per
    (n_pad, B, k) shape bucket): every device scores its own row slice
    against the whole query batch, takes a LOCAL top-k, globalizes the
    row indices by its shard offset (rows from ``live`` on, the pad rows,
    score ``_PAD_SCORE``: ``_mask_spare_rows``), and an ``all_gather`` of the
    (D, B, k_local) partials feeds the final merge ``top_k`` — O(D*k)
    work replicated on every shard, tiny next to the O(n/D) scan.  The
    catalog never moves: only the merged (B, k) winners leave the
    program, as one ``_pack_results`` array."""
    fn = _sharded_program_cache.get(mesh)
    if fn is not None:
        return fn
    from functools import partial

    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from ..parallel.mesh import BLOCK_AXIS

    @partial(jax.jit, static_argnums=3)
    def sharded_topk(matrix, live, qs, k):
        @partial(
            shard_map, mesh=mesh,
            in_specs=(P(BLOCK_AXIS, None), P(), P(None, None)),
            out_specs=P(None, None),
            check_vma=False,
        )
        def run(m, n_live, q):
            with jax.named_scope("topk.shard_score"):
                first = jax.lax.axis_index(BLOCK_AXIS) * m.shape[0]
                # (B, n/D) scores of this shard's rows
                scores = _mask_spare_rows(
                    jnp.matmul(q, m.T, precision=_SCORE_PRECISION),
                    n_live, first)
            with jax.named_scope("topk.shard_select"):
                k_local = min(k, m.shape[0])
                s, i = jax.lax.top_k(scores, k_local)
                gi = (i + first).astype(jnp.int32)
            with jax.named_scope("topk.merge"):
                s_all = jax.lax.all_gather(s, BLOCK_AXIS)  # (D, B, k_local)
                g_all = jax.lax.all_gather(gi, BLOCK_AXIS)
                s_cat = jnp.moveaxis(s_all, 0, 1).reshape(q.shape[0], -1)
                g_cat = jnp.moveaxis(g_all, 0, 1).reshape(q.shape[0], -1)
                ms, mi = jax.lax.top_k(s_cat, k)  # k <= D*k_local == n_pad
                return _pack_results(
                    ms, jnp.take_along_axis(g_cat, mi, axis=1))

        return run(matrix, live, qs)

    _sharded_program_cache[mesh] = sharded_topk
    return sharded_topk


class DeviceFactorIndex:
    def __init__(self, table: ModelTable, factor_suffix: str = "-I"):
        self.table = table
        self.suffix = factor_suffix
        # acquires the index's devices on THIS thread: a process that
        # expected the chip and got the host dies here, at construction
        _warm_jit_async()
        self._obs_device_errors = _device_errors()
        self._lock = threading.Lock()
        self._ids: List[str] = []
        self._id_pos: dict = {}   # id -> row index in the device matrix
        self._matrix = None  # (n_pad, k) device array (maybe mesh-sharded)
        self._n_real = 0     # live rows: positions below it hold an id
        self._k_real = 0  # real factor width
        self._topk_fn = None
        self._topk_many_fn = None
        # each thread's last dispatch, stamped by _fetch (last_fetch)
        self._stamps = threading.local()
        self._built_once = False
        # retrieval tiers (module docstring): sharded exact layout +
        # optional IVF ANN shortlist.  Knobs are read once per index; the
        # background rebuild re-evaluates the SIZE thresholds each swap,
        # so a catalog growing past them upgrades tiers without restarts.
        self.tier = _tier_mode()
        self._shard_mode = os.environ.get("TPUMS_TOPK_SHARDED", "auto")
        self._shard_min_rows = int(
            os.environ.get("TPUMS_TOPK_SHARD_MIN_ROWS", 100_000))
        self._ann_min_rows = int(
            os.environ.get("TPUMS_ANN_MIN_ROWS", 200_000))
        self._ann_recall_min = float(
            os.environ.get("TPUMS_ANN_RECALL_MIN", 0.95))
        self._is_sharded = False
        self._mesh = None        # set when the sharded layout engages
        self._live = None        # n_real as a device scalar, for the programs
        self._n_pad = 0          # the matrix's rows: the capacity
        self._ann = None         # serve.ann.IVFIndex when the tier is built
        self._said_ann_unsharded = False
        # retrieval-plane health (obs/scrape.fleet_signals): rebuild rate,
        # dirty backlog depth, and how stale the serving matrix is
        # relative to the oldest unabsorbed update
        reg = obs_metrics.get_registry()
        self._obs_rebuilds = reg.counter("tpums_topk_rebuilds_total")
        self._obs_dirty_depth = reg.gauge("tpums_topk_dirty_depth")
        # staleness is labeled per-process: the fleet merge SUMS
        # same-labeled gauges, and a sum of stalenesses means nothing —
        # distinct series let fleet_signals take the max
        self._obs_staleness = reg.gauge(
            "tpums_topk_index_staleness_seconds", pid=str(os.getpid()))
        self._obs_ann_recall = reg.gauge(
            "tpums_ann_recall_probe", pid=str(os.getpid()))
        # which tier answered: frames the IVF program ran and the queries
        # in them, the rows in the union of a frame's probed lists and the
        # rows scored summed over its queries, the pad rows of their blocks
        # among them (both counted by the program, fetched with the
        # results), and the builds that failed and left the exact tier
        # serving
        self._obs_ann_frames = reg.counter("tpums_ann_frames_total")
        self._obs_ann_queries = reg.counter("tpums_ann_queries_total")
        self._obs_ann_union_rows = reg.counter("tpums_ann_union_rows_total")
        self._obs_ann_probed_rows = reg.counter(
            "tpums_ann_probed_rows_total")
        self._obs_ann_build_failures = reg.counter(
            "tpums_ann_build_failures_total")
        # the installed layout (set at every swap): devices the rows are
        # split over (1 = the single-device layout), rows a device holds,
        # pad rows among them; and the frames the shard_map program ran
        self._obs_shards = reg.gauge("tpums_topk_shards")
        self._obs_shard_rows = reg.gauge("tpums_topk_shard_rows")
        self._obs_pad_rows = reg.gauge("tpums_topk_pad_rows")
        self._obs_sharded_frames = reg.counter(
            "tpums_topk_sharded_frames_total")
        # catalog bytes the last build copied on the host (``_pack``)
        self._obs_host_copy_bytes = reg.gauge(
            "tpums_topk_build_host_copy_bytes")
        # the update path (put -> dirty set -> drain -> scatter): rows
        # scattered in place, drains that scattered any, those of them
        # whose program wrote into the buffer it was given, puts of a key that
        # was still waiting for its drain (last writer wins: one row is
        # applied for them all), and put -> scatter enqueued per applied row
        self._obs_updates_applied = reg.counter(
            "tpums_topk_updates_applied_total")
        self._obs_update_drains = reg.counter(
            "tpums_topk_update_drains_total")
        self._obs_update_drains_in_place = reg.counter(
            "tpums_topk_update_drains_in_place_total")
        self._obs_updates_coalesced = reg.counter(
            "tpums_topk_updates_coalesced_total")
        self._obs_update_visible = reg.histogram(
            "tpums_topk_update_visible_seconds")
        # new ids: rows written into spare capacity by a drain, their own
        # put -> scatter enqueued (the histogram above sees updates of ids
        # the index knew), ids that found no free position and no room for
        # a larger matrix (each waiting id counted once), and the copies
        # into a larger one; live rows and the rows of the matrix
        self._obs_inserts_applied = reg.counter(
            "tpums_topk_inserts_applied_total")
        self._obs_inserts_refused = reg.counter(
            "tpums_topk_inserts_refused_total")
        self._obs_grows = reg.counter("tpums_topk_grows_total")
        self._obs_insert_visible = reg.histogram(
            "tpums_topk_insert_visible_seconds")
        self._obs_rows_live = reg.gauge("tpums_topk_rows_live")
        self._obs_rows_capacity = reg.gauge("tpums_topk_rows_capacity")
        # key -> (first put time, puts) of the new ids that wait for a
        # matrix with room (under self._lock); the operator is told once an
        # exhaustion
        self._unplaced: dict = {}
        self._said_full = False
        # (key, t_put, t_applied, puts) of the last applied rows
        # (``apply_log``); deque.append is atomic
        self._apply_log: Deque[tuple] = deque(maxlen=_APPLY_LOG_CAP)
        # perf_counter instant of the oldest put still waiting
        self._oldest_dirty_ts: Optional[float] = None
        # dirty-key plumbing: the table's writer thread appends, the query
        # path drains.  Tables without listener support (none in-tree) fall
        # back to counter-triggered full rebuilds.  key -> (perf_counter
        # instant of its FIRST put since the last drain, puts since then).
        self._dirty_lock = threading.Lock()
        self._dirty: dict = {}
        # rows absorbed from replay-scale batches that are pending a full
        # rebuild (a count, not keys: storing 1M keys per cold-start chunk
        # in the dirty set was measured ingest overhead with zero value —
        # the rebuild snapshots the whole table anyway)
        self._replay_backlog = 0
        self._rebuild_thread: Optional[threading.Thread] = None
        self._counter_mode = not hasattr(table, "add_change_listener")
        self._built_at = -1
        if not self._counter_mode:
            try:
                # batched registration: ingest chunks notify once per chunk
                # (one dirty-lock acquisition), not once per row
                table.add_change_listener(self._on_put, self._on_put_many)
            except TypeError:  # older table: per-key contract only
                table.add_change_listener(self._on_put)
        # per-query work bound: at most this many dirty rows are parsed and
        # scattered on the query path; a backlog beyond the rebuild
        # threshold (a writer outrunning the query rate) is absorbed by ONE
        # background rebuild instead, so query latency stays O(cap) no
        # matter the write rate
        self.apply_cap = int(os.environ.get("TPUMS_TOPK_APPLY_CAP", 1024))
        self.rebuild_backlog = 8 * self.apply_cap
        # keys already peek-applied while the current rebuild runs: an
        # unchanged backlog must not be re-parsed on every query
        self._peek_applied: set = set()
        self.full_builds = 0       # observability / test hooks
        self.inplace_updates = 0

    # -- change tracking ----------------------------------------------------

    def _on_put(self, key: str) -> None:  # writer thread, table lock held
        if key.endswith(self.suffix) and not key.startswith("MEAN"):
            self._mark_dirty((key,))

    def _mark_dirty(self, keys) -> None:
        """Enter relevant keys into the dirty set under ONE lock hold.  A
        key already waiting keeps its first put time and counts as
        coalesced: the drain reads the table, so it applies the last value
        once."""
        now = time.perf_counter()
        coalesced = 0
        with self._dirty_lock:
            dirty = self._dirty
            for key in keys:
                waiting = dirty.get(key)
                if waiting is None:
                    dirty[key] = (now, 1)
                else:
                    dirty[key] = (waiting[0], waiting[1] + 1)
                    coalesced += 1
            if self._oldest_dirty_ts is None:
                self._oldest_dirty_ts = now
        if coalesced:
            self._obs_updates_coalesced.inc(coalesced)

    def _on_put_many(self, keys) -> None:  # writer thread, table lock held
        """Batched change notification: the dirty lock is taken ONCE per
        ingest chunk — the per-key lock acquisition was half the
        listener-path ingest cost at replay scale.

        Small batches run the exact suffix filter (one C-level
        comprehension).  Replay-scale batches skip even that per-key pass:
        a batch this size pushes the backlog past the rebuild threshold by
        itself, so only a COUNT is recorded — the next query triggers one
        background rebuild whose table snapshot (filtered by suffix there)
        absorbs every absorbed row.  Filtering or storing 100k keys per
        chunk at ingest would be pure wasted time on the writer thread."""
        if len(keys) >= self.rebuild_backlog:
            with self._dirty_lock:
                self._replay_backlog += len(keys)
                if self._oldest_dirty_ts is None:
                    self._oldest_dirty_ts = time.perf_counter()
            return
        suffix = self.suffix
        relevant = [
            k for k in keys
            if k.endswith(suffix) and not k.startswith("MEAN")
        ]
        if relevant:
            self._mark_dirty(relevant)

    def _drain_dirty(self, limit: Optional[int] = None) -> dict:
        """Take up to ``limit`` keys out of the dirty set, those entered
        first first -> key -> (first put time, puts)."""
        with self._dirty_lock:
            if limit is None or len(self._dirty) <= limit:
                dirty, self._dirty = self._dirty, {}
                if not self._replay_backlog:
                    self._oldest_dirty_ts = None
                return dirty
            dirty = {key: self._dirty.pop(key) for key in
                     list(itertools.islice(self._dirty, limit))}
            # the backlog's age is the oldest key's that is still waiting
            # (a replay batch absorbed by count keeps the stamp it set)
            if not self._replay_backlog:
                self._oldest_dirty_ts = min(
                    t_put for t_put, _ in self._dirty.values())
            return dirty

    def _put_back(self, waiting: dict) -> None:
        """Keys a drain took and could not apply re-enter the dirty set
        with the stamps they had.  A key put again meanwhile waits under the
        earlier stamp for all its puts, and one of them now counts as
        coalesced."""
        if not waiting:
            return
        merged = 0
        with self._dirty_lock:
            for key, (t_put, puts) in waiting.items():
                again = self._dirty.get(key)
                if again is None:
                    self._dirty[key] = (t_put, puts)
                else:
                    self._dirty[key] = (t_put, puts + again[1])
                    merged += 1
            oldest = min(t_put for t_put, _ in waiting.values())
            if self._oldest_dirty_ts is None or oldest < self._oldest_dirty_ts:
                self._oldest_dirty_ts = oldest
        if merged:
            self._obs_updates_coalesced.inc(merged)

    def apply_log(self) -> List[tuple]:
        """``(key, t_put, t_applied, puts)`` of the last rows scattered in
        place (at most ``_APPLY_LOG_CAP``; updates of known ids and new ids
        alike), oldest first: ``perf_counter``
        instants of the key's first put since its last drain and of the
        scatter's enqueue, after which every dispatch reads the row, and
        the puts of the key that this one row stands for (all but one of
        them coalesced; a key's entries cover its puts in order)."""
        return list(self._apply_log)

    def update_stats(self) -> dict:
        """The update path in numbers, for ``ServingJob.health``: rows
        scattered in place (new ids among them), puts coalesced, drains,
        the mean put -> visible of updates; new ids written into spare
        capacity, new ids that wait for room, live rows and capacity."""
        seen = self._obs_update_visible
        return {
            "applied": int(self._obs_updates_applied.value),
            "coalesced": int(self._obs_updates_coalesced.value),
            "drains": int(self._obs_update_drains.value),
            "visible_mean_ms": (1e3 * seen.sum / seen.count
                                if seen.count else None),
            "inserted": int(self._obs_inserts_applied.value),
            "waiting_for_room": len(self._unplaced),
            "rows_live": self._n_real,
            "rows_capacity": self._n_pad,
        }

    # -- building -----------------------------------------------------------

    def _snapshot_rows(self, serving: bool = False):
        """-> (ids, rows ndarray (n, width), width): the table's rows and,
        for a rebuild of an index that is ``serving``, the rows it serves
        whose ids the table does not hold (``_rows_the_table_lacks``: a
        catalog that ``bulk_load`` installed), after the table's.

        Width policy: the index width is the MODAL separator count across
        the snapshot (cheap C-level ``str.count``), so a single truncated
        or over-long payload is dropped rather than poisoning the build —
        and because rows are pre-filtered by token count, a reshape can
        never misalign rows (compensating short/long pairs are filtered
        out, not averaged away by a total-size check).  The rows the index
        serves vote with their own width, so one odd payload cannot turn
        a bulk-loaded catalog out either; a table whose rows outvote them
        at another width does, and the index says so.

        Fast path: join the width-consistent payloads and parse ONCE with
        numpy's C float parser — ~25x less Python-loop work than
        per-token float() at 1M rows.  Non-numeric tokens make the parse
        come up short, which the size check detects; the robust per-row
        path then also drops those rows."""
        ids, payloads = [], []
        for key, payload in self.table.items():
            if not key.endswith(self.suffix) or key.startswith("MEAN"):
                continue
            ids.append(key[: -len(self.suffix)])
            payloads.append(payload.rstrip(";"))
        held_ids, held = (self._rows_the_table_lacks(set(ids)) if serving
                          else ([], None))
        if not ids and not held_ids:
            return [], np.zeros((0, 0), np.float32), None
        counts = np.fromiter(
            (p.count(";") + 1 for p in payloads),
            dtype=np.int64, count=len(payloads),
        )
        votes = np.bincount(counts, minlength=held.shape[1] + 1
                            if held_ids else 0)
        if held_ids:
            votes[held.shape[1]] += len(held_ids)
        width = int(votes.argmax())
        if held_ids and held.shape[1] != width:
            print(f"[topk] the table's rows are {width} wide: {len(held_ids)} "
                  f"rows of width {held.shape[1]} that only the index held "
                  "are dropped", file=sys.stderr)
            held_ids, held = [], None
        keep = counts == width
        if not keep.all():
            ids = [i for i, k in zip(ids, keep) if k]
            payloads = [p for p, k in zip(payloads, keep) if k]
        if held_ids:
            t_ids, t_rows, _ = self._parse_rows(ids, payloads, width)
            if not t_ids:
                return held_ids, held, width
            return t_ids + held_ids, np.concatenate([t_rows, held]), width
        if not ids or width <= 0:
            return [], np.zeros((0, 0), np.float32), None
        return self._parse_rows(ids, payloads, width)

    @staticmethod
    def _parse_rows(ids, payloads, width):
        """Payloads of ``width`` tokens each -> (ids, rows, width), without
        the rows a token of which is no number."""
        if not ids:
            return [], np.zeros((0, width), np.float32), width
        try:
            # one C-level parse of every payload (np.array over one big
            # split — same pattern as formats.parse_svm_range_payload;
            # np.fromstring's text mode is deprecated and its removal
            # would have silently dropped this vectorized path into the
            # 25x-slower per-row fallback below)
            flat = np.array(";".join(payloads).split(";"), dtype=np.float64)
            if flat.size == len(ids) * width:
                return ids, flat.reshape(len(ids), width).astype(np.float32), width
        except Exception:
            pass
        # robust path: per-row parse, drop rows with non-numeric tokens
        out_ids, rows = [], []
        for id_, payload in zip(ids, payloads):
            try:
                vec = [float(t) for t in payload.split(";") if t]
            except ValueError:
                continue
            if len(vec) != width:
                continue
            out_ids.append(id_)
            rows.append(vec)
        return (out_ids, np.asarray(rows, dtype=np.float32).reshape(-1, width),
                width)

    def _rows_the_table_lacks(self, table_ids: set):
        """-> (ids, rows) that the index serves and the table does not hold,
        read back from the device; ([], None) where there are none, or no
        matrix to read (none built yet, or lost to a failed update: such a
        rebuild has the table alone).  Called by the rebuild thread WITHOUT
        the index lock: each strip of ``_FETCH_STRIP`` rows that holds such
        a row is sliced under the lock (the matrix is donated to every
        drain, so no handle of it may be kept across one) and copied to the
        host outside it.  Such rows never change under the rebuild: a write
        reaches the index through the table only."""
        with self._lock:
            if self._matrix is None or self._matrix.is_deleted():
                return [], None
            ids, n_pad = list(self._ids), self._n_pad
            n_shards = (self._matrix.sharding.num_devices
                        if self._is_sharded else 1)
            width = self._matrix.shape[1]
        absent = np.fromiter(
            (pos for pos, id_ in enumerate(ids)
             if id_ is not None and id_ not in table_ids), dtype=np.int64)
        if not len(absent):
            return [], None
        from .ann import _whole_chunks

        strip_of = _build_jits()[2]
        held = np.empty((len(absent), width), np.float32)
        per = n_pad // n_shards
        rows, starts = _whole_chunks(per, _FETCH_STRIP)
        for first in range(0, n_pad, per):
            done = first
            for lo in starts:
                a, b = np.searchsorted(
                    absent, [max(first + lo, done), first + lo + rows])
                done = first + lo + rows
                if a == b:
                    continue
                with self._lock:
                    matrix = self._matrix
                    if (matrix is None or matrix.is_deleted()
                            or matrix.shape[0] != n_pad):
                        raise RuntimeError(
                            "the index was replaced under its rebuild")
                    data = next(
                        shard.data for shard in matrix.addressable_shards
                        if (shard.index[0].start or 0) == first)
                    part = strip_of(data, np.int32(lo), rows)
                    del data, matrix
                held[a:b] = np.asarray(part)[absent[a:b] - first - lo]
        return [ids[pos] for pos in absent.tolist()], held

    def _mesh_if_sharding(self, n_rows: int):
        """The mesh to shard over, or None for the single-device layout.
        ``TPUMS_TOPK_SHARDED``: ``auto`` (default — shard past the row
        floor when >1 device is visible), ``1`` force, ``0`` off."""
        mode = self._shard_mode
        if mode == "0":
            return None
        mesh = _index_mesh()
        if mesh is None:
            return None
        if mode != "1" and n_rows < self._shard_min_rows:
            return None
        return mesh

    def _pack(self, rows, spare: bool = True):
        """Place the factor rows on device ->
        ``(matrix, n_pad, is_sharded)``, ``n_pad`` the matrix's rows: the
        live rows first, then zero rows that the query programs mask
        (``_mask_spare_rows``) and a new id is written into.

        Single-device: ``mesh.row_capacity`` rows.  Where one put serves
        (``_one_put_serves``) the rows alone are put here and ``_assemble``
        joins the spare rows on the device once the id dict is made;
        otherwise strips into a zero matrix (``_place_in_strips``).
        Without ``spare`` (the IVF tier's build, which reorders the rows
        into a matrix of its own) the exact array in one put and
        ``n_pad`` its rows.  Sharded: rows are
        padded to the shared power-of-two per-shard bucket
        (``mesh.row_bucket``) and laid out row-sharded over the mesh's
        block axis — the padding keeps XLA at a handful of compiled shapes
        over the catalog's whole growth curve.

        The padded matrix exists on the devices only.  Each device's row
        range is read from the sharding's own index map; a shard that
        lies wholly inside the real rows is put from a view of ``rows``
        (no host copy), and only a shard that straddles the last real row
        or lies beyond it gets a host buffer (its rows, then zeros).
        ``tpums_topk_build_host_copy_bytes`` is the bytes of those
        buffers (0 on one device).

        ONE transfer at a time: a put to the next device waits for the
        one before it.  Four shards of 3.36 GB enqueued side by side, as
        a ``device_put`` under the ``NamedSharding`` enqueues them, land
        in 32 s on a host of four TPU v5e chips; one after the other in
        1.7 (PERF.md §6, PR 36).  So here ``topk.build.place`` holds the
        transfers themselves; the single-device put stays asynchronous
        and ``_assemble`` builds ``id_pos`` under it (all but the last two
        strips awaited)."""
        import jax

        rows = np.asarray(rows, dtype=np.float32)
        mesh = self._mesh_if_sharding(rows.shape[0])
        if mesh is None:
            from ..parallel.mesh import row_capacity

            dev = _target_device()
            n_pad = row_capacity(rows.shape[0]) if spare else rows.shape[0]
            with phase("topk.build.place"):
                matrix = (_place_in_strips(rows, n_pad, dev)
                          if spare and not _one_put_serves(rows, n_pad, dev)
                          else jax.device_put(rows, dev))
            self._obs_host_copy_bytes.set(0)
            return matrix, n_pad, False
        from jax.sharding import NamedSharding, PartitionSpec as P

        from ..parallel.mesh import BLOCK_AXIS, num_blocks, row_bucket

        self._mesh = mesh
        n, k = rows.shape
        n_pad = row_bucket(n, num_blocks(mesh))
        sharding = NamedSharding(mesh, P(BLOCK_AXIS, None))
        with phase("topk.build.pad"):
            blocks, copied = {}, 0
            for dev, index in sharding.addressable_devices_indices_map(
                    (n_pad, k)).items():
                lo, hi, _ = index[0].indices(n_pad)
                if hi <= n:
                    blocks[dev] = rows[lo:hi]
                else:
                    blocks[dev] = np.zeros((hi - lo, k), np.float32)
                    blocks[dev][:max(n - lo, 0)] = rows[lo:n]
                    copied += blocks[dev].nbytes
        with phase("topk.build.place"):
            matrix = jax.make_array_from_single_device_arrays(
                (n_pad, k), sharding,
                [jax.device_put(block, dev).block_until_ready()
                 for dev, block in blocks.items()])
        self._obs_host_copy_bytes.set(copied)
        return matrix, n_pad, True

    def _live_scalar(self, n_real: int, matrix):
        """``n_real`` as the int32 device scalar the query programs compare
        row numbers with: on ``matrix``'s device, or on every device of its
        mesh.  Put when the count changes (a swap, a drain that held new
        ids), never per query."""
        import jax

        sharding = matrix.sharding
        if getattr(sharding, "mesh", None) is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            sharding = NamedSharding(sharding.mesh, P())
        return jax.device_put(np.int32(n_real), sharding)

    def _wants_ann(self, n: int) -> bool:
        """Whether a build of ``n`` rows tries the IVF tier: the tier knob
        and, under ``auto``, the size threshold."""
        if self.tier == "exact" or n == 0:
            return False
        return self.tier == "ivf" or n >= self._ann_min_rows

    def _maybe_build_ann(self, rows, matrix):
        """Build the IVF tier for this catalog snapshot -> ``(ann, listed,
        position)`` (``serve/ann.IVFIndex.build``), or None where the exact
        tier serves: the build failed, or under ``auto`` its recall probe
        missed the gate.  Runs OFF the index lock on the rebuild path
        (k-means + list assignment is the expensive half of a swap); a
        failed build degrades to the exact tier rather than poisoning the
        swap, is counted (``tpums_ann_build_failures_total``) and, where
        the operator forced ``ivf``, says that their tier is not served."""
        tier = self.tier
        try:
            from .ann import IVFIndex

            with phase("topk.build.ann"):
                built = IVFIndex.build(
                    np.asarray(rows, dtype=np.float32), matrix)
        except Exception as e:
            self._obs_device_errors.inc()
            self._obs_ann_build_failures.inc()
            print(f"[topk] IVF build failed (serving exact): {e}",
                  file=sys.stderr)
            if tier == "ivf":
                print("[topk] TPUMS_TOPK_TIER=ivf is NOT being served: every "
                      "answer comes from the exact tier until a rebuild "
                      "succeeds", file=sys.stderr)
            return None
        ann = built[0]
        self._obs_ann_recall.set(ann.recall_probe)
        if tier == "auto" and ann.recall_probe < self._ann_recall_min:
            # the recall contract failed on THIS catalog's geometry: auto
            # degrades to exact (forced tier=ivf serves anyway — the
            # operator asked for it — but the probe gauge shows the miss)
            print(
                f"[topk] IVF recall probe {ann.recall_probe:.3f} < "
                f"{self._ann_recall_min} gate; serving exact",
                file=sys.stderr,
            )
            return None
        return built

    def _assemble(self, ids, rows, width) -> dict:
        """The expensive half of a (re)build — device placement, ANN
        training, scatter warm-up — safe to run OFF the index lock.  The
        result swaps in atomically via ``_swap_locked``.

        One phase ``topk.build`` a build, whoever asks for it (``bulk_load``,
        the first query's full build, the background rebuild on its own
        thread), with children ``.place`` (the ``device_put``s: the
        enqueue on one device, the awaited transfers of the sharded
        layout, ``_pack``), ``.pad`` (sharded layout only: the host buffers
        of the shards that are not wholly real rows, before the layout's
        ``.place``), ``.ids`` (the ``id_pos`` dict, made before the
        first wait so that one device's transfer flies under it; where the
        IVF tier is tried, after it, since the tier decides the positions),
        ``.ann`` (only where the tier builds; its own children ``.train``,
        ``.assign``, ``.lists``, ``.recall``), ``.spare`` (one device, where
        one put served: the rows' copy into the zero matrix that has the
        spare rows, and what is left of the transfer) and ``.warm_scatter`` (a
        compile or load of the update scatter at this matrix's shape, run
        over no row, and what is left of one device's transfer)."""
        with phase("topk.build"):
            matrix = live = ann = id_pos = None
            n_pad, sharded = 0, False
            n_real = len(ids)
            tries_ann = self._wants_ann(len(rows))
            if len(rows):
                # the IVF tier takes the exact rows and keeps no spare ones
                matrix, n_pad, sharded = self._pack(rows, spare=not tries_ann)
            if tries_ann and sharded:
                tries_ann = False
                if not self._said_ann_unsharded:
                    # once an index, under ``auto`` too: the setting asks
                    # for a tier that this layout does not serve
                    self._said_ann_unsharded = True
                    print(f"[topk] TPUMS_TOPK_TIER={self.tier}: the IVF tier "
                          "lives on one device; this mesh-sharded catalog "
                          "serves the sharded exact tier", file=sys.stderr)
            if tries_ann:
                built = self._maybe_build_ann(rows, matrix)
                if built is not None:
                    # the list-ordered matrix is the resident one from here
                    # (the row-ordered one is dropped); ids follow it
                    ann, matrix, position = built
                    n_pad = matrix.shape[0]
                    with phase("topk.build.ids"):
                        listed = np.full((n_pad,), None, dtype=object)
                        listed[position] = ids
                        id_pos = dict(zip(ids, position.tolist()))
                        ids = listed.tolist()
            if id_pos is None:
                with phase("topk.build.ids"):
                    # a comprehension, not ``dict(zip(...))``: the C-level
                    # form is 2-5% faster (the inserts are the cost, PERF.md
                    # §6) and would hold the interpreter lock for all of it,
                    # against the threads that answer queries while a
                    # rebuild runs
                    id_pos = {id_: i for i, id_ in enumerate(ids)}
            if matrix is not None and matrix.shape[0] < n_pad:
                # one put of the rows alone has flown under the id dict:
                # now their copy into the zero matrix with the spare rows
                with phase("topk.build.spare"):
                    matrix = _build_jits()[1](matrix, n_pad)
            if len(rows) and not self._counter_mode:
                # warm the fixed-shape update scatter at the NEW matrix
                # shape so the first streaming update never pays a compile
                # on the query path.  Zero real rows: nothing is written,
                # and the program hands the donated matrix back
                with phase("topk.build.warm_scatter"):
                    pos = np.zeros((self.apply_cap,), dtype=np.int32)
                    vec = np.zeros(
                        (self.apply_cap, matrix.shape[1]),
                        dtype=np.float32)
                    matrix = _scatter_rows(matrix, pos, vec, 0)
                    matrix.block_until_ready()
            if matrix is not None:
                live = self._live_scalar(n_real, matrix)
            return {
                "ids": ids, "id_pos": id_pos,
                "n_real": n_real, "k_real": width, "matrix": matrix,
                "live": live, "n_pad": n_pad, "sharded": sharded, "ann": ann,
            }

    def _swap_locked(self, a: dict) -> None:
        """Install an assembled index state (under self._lock)."""
        self._ids = a["ids"]
        self._id_pos = a["id_pos"]
        self._n_real = a["n_real"]
        self._k_real = a["k_real"]
        self._matrix = a["matrix"]
        self._live = a["live"]
        self._n_pad = a["n_pad"]
        self._is_sharded = a["sharded"]
        self._ann = a["ann"]
        self._observe_rows_locked()
        self._built_once = True
        self.full_builds += 1
        self._obs_rebuilds.inc()
        self._peek_applied.clear()
        self._readmit_unplaced_locked()

    def _observe_rows_locked(self) -> None:
        """The installed layout's gauges, after a swap, a copy into a
        larger matrix or a drain that held new ids."""
        n_shards = (self._matrix.sharding.num_devices
                    if self._is_sharded else 1)
        self._obs_shards.set(n_shards)
        self._obs_shard_rows.set(self._n_pad // n_shards)
        self._obs_pad_rows.set(self._n_pad - self._n_real)
        self._obs_rows_live.set(self._n_real)
        self._obs_rows_capacity.set(self._n_pad)

    def _readmit_unplaced_locked(self) -> None:
        """A new matrix is in: the new ids that waited for room re-enter the
        dirty set with the stamps they had, and the next exhaustion is said
        again."""
        waiting, self._unplaced = self._unplaced, {}
        self._said_full = False
        self._put_back(waiting)

    def _build_locked(self) -> None:
        """Full build, called under self._lock."""
        _target_device()  # resolve platform pins before first backend touch

        # keys changed while we snapshot stay dirty for the next query
        self._drain_dirty()
        with self._dirty_lock:
            self._replay_backlog = 0  # full build absorbs the replay rows
        with phase("topk.build.snapshot"):
            ids, rows, width = self._snapshot_rows()
        self._swap_locked(self._assemble(ids, rows, width))

    def bulk_load(self, ids, rows) -> None:
        """Install a pre-parsed catalog directly — semantically a full
        build whose table snapshot parsed to exactly ``(ids, rows)``.
        ``benchmark/drivers/topk_open.py`` uses it to stand up 5M–17M-row
        catalogs without materializing as many payload strings through
        the table; later writes via the table flow through the normal
        dirty-set maintenance: a known id is overwritten in place, an
        unknown id is written into spare capacity, and a rebuild, when one
        does run, takes the rows the index serves overlaid by the table's,
        so the installed catalog stays served whether or not the table
        holds it.  (A matrix LOST to a failed update is the exception: its
        rows are gone with it, and that rebuild has the table alone.)

        An f32 ``rows`` is not copied on the host: the device matrix is
        put from views of the caller's array (one device: in strips; the
        sharded layout: every shard that is all real rows).  The caller
        leaves it unmodified while the index holds this catalog: the
        transfers read it after ``device_put`` has returned, and a CPU
        backend's shard may go on sharing its memory."""
        rows = np.asarray(rows, dtype=np.float32)
        if rows.ndim != 2 or len(ids) != rows.shape[0]:
            raise ValueError("bulk_load needs ids aligned with (n, k) rows")
        with self._lock:
            _target_device()
            self._drain_dirty()
            with self._dirty_lock:
                self._replay_backlog = 0
            self._swap_locked(
                self._assemble(list(ids), rows,
                               rows.shape[1] if rows.size else None))

    def _apply_updates_locked(self, dirty: dict, allow_rebuild: bool = True) -> bool:
        """In-place device update (``dirty``: key -> (first put time,
        puts)): a row of an indexed id is written over, a row of a new id is
        written at the next free position of the matrix's spare capacity,
        which then becomes live.  Both ride ONE scatter of the drain, in
        the drain's order.  False where the device refused the scatter:
        nothing was applied and no id was registered.

        A new id that finds no free position: where the device has room
        for a larger matrix beside this one, a background copy into the
        next capacity starts (``_start_rebuild_locked(grow=True)``) and the
        id waits in the dirty set for the swap; where it has not, the id
        waits in ``_unplaced`` (counted once, said once on stderr) until a
        matrix with room is installed.  On the IVF tier, which keeps no
        spare positions, a new id starts a rebuild as it always did.

        The payload parse is vectorized: all rows of the batch are joined
        and parsed with ONE numpy C float pass into a (B, k) matrix, then
        scattered into the device matrix in a single op — per-row
        ``float()`` loops only run on the fallback path (payloads with
        empty/non-numeric tokens), preserving its exact semantics.

        Stages ``topk.maintain.parse`` (table reads, the join and the float
        parse), ``topk.maintain.scatter`` (the padded batch and the
        scatter's enqueue) and, in a drain that held new ids,
        ``topk.maintain.insert`` (their ids registered, the live count
        put), all inside the caller's ``topk.maintain``."""
        suffix = self.suffix
        suffix_len = len(suffix)
        k_real = self._k_real
        takes_new = (_INSERTS_IN_PLACE and self._ann is None
                     and self._matrix is not None)
        # (pos or None for a new id, payload, key, (t_put, puts)) of the
        # rows to scatter
        candidates: list = []
        slow: list = []  # the same, needing the per-row parse
        structural = False
        with stage("topk.maintain.parse"):
            for key, waited in dirty.items():
                if not key.endswith(suffix) or key.startswith("MEAN"):
                    continue  # foreign key from an unfiltered replay batch
                payload = self.table.get(key)
                if payload is None:
                    continue
                pos = self._id_pos.get(key[:-suffix_len])
                if pos is None and not takes_new:
                    structural = True  # new item: needs rebuild
                    continue
                p = payload.rstrip(";")
                if p.count(";") + 1 == k_real and p:
                    candidates.append((pos, p, key, waited))
                else:
                    slow.append((pos, payload, key, waited))
            updates_pos, updates_vec, applied = [], [], []
            if candidates:
                try:
                    flat = np.array(
                        ";".join(c[1] for c in candidates).split(";"),
                        dtype=np.float32)
                    updates_pos = [c[0] for c in candidates]
                    updates_vec = list(flat.reshape(len(candidates), k_real))
                    applied = [c[2:] for c in candidates]
                except ValueError:
                    # an empty/garbled token somewhere in the batch: re-route
                    # every candidate through the exact per-row path
                    slow.extend(candidates)
            for pos, payload, key, waited in slow:
                vec = [float(t) for t in payload.split(";") if t]
                if len(vec) != k_real:
                    structural = True  # width change: needs rebuild
                    continue
                updates_pos.append(pos)
                updates_vec.append(vec)
                applied.append((key, waited))
            new_ids, crowded = self._seat_new_ids(
                updates_pos, updates_vec, applied)
        if len(updates_pos) and self._matrix is not None:
            try:
                with stage("topk.maintain.scatter"):
                    in_place = self._scatter_rows_locked(
                        updates_pos, updates_vec)
            except Exception as e:
                # the scatter failed: the caller keeps the rows waiting,
                # and this frame answers from the matrix it has, if the
                # failed call left it one (``_maintain_locked``)
                self._obs_device_errors.inc()
                print(f"[topk] update scatter failed, {len(applied)} rows "
                      f"wait for the next frame: {e}", file=sys.stderr)
                return False
            t_applied = time.perf_counter()
            if new_ids:
                with stage("topk.maintain.insert"):
                    self._id_pos.update(
                        zip(new_ids, range(self._n_real,
                                           self._n_real + len(new_ids))))
                    self._ids.extend(new_ids)
                    self._n_real += len(new_ids)
                    self._live = self._live_scalar(self._n_real, self._matrix)
                    self._observe_rows_locked()
                    self._obs_inserts_applied.inc(len(new_ids))
            self.inplace_updates += len(applied)
            self._obs_updates_applied.inc(len(applied))
            self._obs_update_drains.inc()
            if in_place:
                self._obs_update_drains_in_place.inc()
            first_new = self._n_real - len(new_ids)
            for pos, (key, (t_put, puts)) in zip(updates_pos, applied):
                (self._obs_insert_visible if pos >= first_new
                 else self._obs_update_visible).observe(t_applied - t_put)
                self._apply_log.append((key, t_put, t_applied, puts))
        if crowded:
            self._hold_crowded_locked(crowded, allow_rebuild)
        if structural and allow_rebuild:
            self._start_rebuild_locked()
        return True

    def _seat_new_ids(self, updates_pos, updates_vec, applied):
        """Give the batch's new ids (position None) the free positions from
        ``_n_real`` on, in batch order -> (their ids, in that order; the
        ``{key: waited}`` of those that found none, taken out of the three
        lists).  Nothing is registered here: the caller does that once the
        scatter is enqueued."""
        free = self._n_pad - self._n_real
        new_ids, crowded, keep = [], {}, []
        for j, pos in enumerate(updates_pos):
            if pos is None:
                key, waited = applied[j]
                if len(new_ids) == free:
                    crowded[key] = waited
                    continue
                updates_pos[j] = self._n_real + len(new_ids)
                new_ids.append(key[:-len(self.suffix)])
            keep.append(j)
        if crowded:
            for column in (updates_pos, updates_vec, applied):
                column[:] = [column[j] for j in keep]
        return new_ids, crowded

    def _room_to_grow(self, n_pad: int) -> bool:
        """Whether the matrix's device can hold a matrix of ``n_pad`` rows
        BESIDE the one it holds, with a quarter of it over for the frames'
        scores.  A backend that reports no memory (the CPU) has room."""
        free = _free_bytes(self._matrix.devices().pop())
        return free is None or free >= 1.25 * n_pad * self._matrix.shape[1] * 4

    def _hold_crowded_locked(self, crowded: dict, allow_rebuild: bool) -> None:
        """New ids of a drain that found no free position.  One device with
        room for a larger matrix: they go back to the dirty set and a copy
        into the next capacity starts (``allow_rebuild`` false: one is in
        flight, they wait for its swap).  A mesh: a rebuild, which lays the
        rows out anew at the next bucket.  No room: they wait in
        ``_unplaced`` until some swap brings a matrix with room, each id
        counted once, and the operator is told once."""
        from ..parallel.mesh import row_capacity

        if not allow_rebuild:
            return  # peeked, not drained: still in the dirty set
        if self._is_sharded:
            self._put_back(crowded)
            self._start_rebuild_locked()
            return
        n_pad = row_capacity(self._n_pad)
        if self._room_to_grow(n_pad):
            self._put_back(crowded)
            self._start_rebuild_locked(grow=n_pad)
            return
        fresh = 0
        for key, (t_put, puts) in crowded.items():
            waiting = self._unplaced.get(key)
            fresh += waiting is None
            self._unplaced[key] = (
                (t_put, puts) if waiting is None
                else (waiting[0], waiting[1] + puts))
        self._obs_inserts_refused.inc(fresh)
        if not self._said_full:
            self._said_full = True
            gb = n_pad * self._matrix.shape[1] * 4 / 1e9
            print(f"[topk] the index is FULL: all {self._n_pad} rows of its "
                  f"device matrix hold an id, and the device has no room for "
                  f"a larger one beside it ({gb:.1f} GB more). New ids are "
                  "NOT served and wait (tpums_topk_inserts_refused_total "
                  "counts them); every id already served stays served and "
                  "takes updates. To take them: restart or bulk_load this "
                  "catalog where it has room (a larger device, or a mesh of "
                  "devices: the row-sharded layout).", file=sys.stderr)

    def _scatter_rows_locked(self, updates_pos, updates_vec) -> bool:
        """Write ≤apply_cap changed rows into the device matrix at ONE
        static shape: the batch is padded to apply_cap by repeating its
        first row and the program is told how many rows are real (it reads
        no further), so XLA compiles exactly one scatter per index
        (``_scatter_rows``), warmed at build time — steady-state updates
        never pay a compile.

        The matrix is donated to the program and ``self._matrix`` rebound
        to the result, under ``self._lock`` like every reader of it
        (``topk``, ``topk_many``, ``_dispatch_frame_locked``, the IVF
        ``search``), each of which waits for its own result before it lets
        the lock go: no host reference to a donated buffer outlives a
        drain.  -> whether the program took the buffer it was given (the
        old handle is deleted): the drain was in place."""
        count = len(updates_pos)
        pad = self.apply_cap - count
        updates_pos = list(updates_pos) + [updates_pos[0]] * pad
        updates_vec = list(updates_vec) + [updates_vec[0]] * pad
        pos = np.asarray(updates_pos, dtype=np.int32)
        vec = np.asarray(updates_vec, dtype=np.float32)
        given = self._matrix
        self._matrix = _scatter_rows(given, pos, vec, count)
        return given.is_deleted()

    def _start_rebuild_locked(self, grow: int = 0) -> None:
        """Start the ONE background thread that replaces the matrix: a
        rebuild from the rows the index serves overlaid by the table's, or,
        with ``grow`` (one device only), a copy of the matrix as it lies
        into one of ``grow`` rows.  While it runs queries peek the dirty
        set and never drain it, so what they apply meanwhile is applied
        again to the new matrix after the swap."""
        if self._rebuild_thread is not None and self._rebuild_thread.is_alive():
            return  # one rebuild in flight; later dirt re-triggers after swap

        def grow_matrix():
            try:
                with phase("topk.grow"):
                    with self._lock:
                        self._raise_if_matrix_lost_locked()
                        # enqueued under the lock: after every drain so far,
                        # before any to come (which goes to the old matrix
                        # and, peeked, again to this one after the swap)
                        matrix = _build_jits()[1](self._matrix, grow)
                    pos = np.zeros((self.apply_cap,), dtype=np.int32)
                    vec = np.zeros((self.apply_cap, matrix.shape[1]),
                                   dtype=np.float32)
                    matrix = _scatter_rows(matrix, pos, vec, 0)
                    matrix.block_until_ready()
                    with self._lock:
                        self._matrix, self._n_pad = matrix, grow
                        self._observe_rows_locked()
                        self._obs_grows.inc()
                        self._peek_applied.clear()
                        self._readmit_unplaced_locked()
            except Exception as e:
                with self._lock:
                    self._peek_applied.clear()
                self._obs_device_errors.inc()
                print(f"[topk] the copy into a matrix of {grow} rows failed; "
                      f"new ids wait: {e}", file=sys.stderr)

        def rebuild():
            drained = {}
            replay_snap = 0
            try:
                # drain BEFORE the snapshot: every drained key's latest
                # value is then included in the snapshot by construction,
                # while keys put during the snapshot re-enter the dirty set
                # and survive the swap.  (Queries peek, never drain, while
                # this thread is alive.)  The replay counter resets at the
                # same moment: replay batches landing after this point
                # re-arm it and trigger a follow-up rebuild.
                drained = self._drain_dirty()
                with self._dirty_lock:
                    replay_snap = self._replay_backlog
                    self._replay_backlog = 0
                with phase("topk.build.snapshot"):
                    ids, rows, width = self._snapshot_rows(serving=True)
                # device placement, scatter warm-up, and the (potentially
                # seconds-long) IVF k-means all run OFF the index lock —
                # queries keep answering from the current index meanwhile
                assembled = self._assemble(ids, rows, width)
                with self._lock:
                    self._swap_locked(assembled)
            except Exception as e:  # pragma: no cover - defensive
                # the drained updates must not be lost: put them back so
                # the next query re-applies them and (for the structural
                # keys) re-triggers a rebuild
                self._put_back(drained)
                with self._dirty_lock:
                    self._replay_backlog += replay_snap
                with self._lock:
                    self._peek_applied.clear()
                self._obs_device_errors.inc()
                print(f"[topk] background rebuild failed: {e}",
                      file=sys.stderr)

        self._rebuild_thread = threading.Thread(
            target=grow_matrix if grow else rebuild,
            name="topk-grow" if grow else "topk-rebuild", daemon=True
        )
        self._rebuild_thread.start()

    # -- querying -----------------------------------------------------------

    def _observe_health(self) -> None:
        """Publish the retrieval-plane health gauges (dirty backlog depth
        and how long the oldest unabsorbed update has been waiting) —
        what ``obs/scrape.fleet_signals`` surfaces to the autoscaler/SLO
        layer as ``topk_dirty_depth`` / ``topk_staleness_s``."""
        with self._dirty_lock:
            depth = len(self._dirty) + self._replay_backlog
            oldest = self._oldest_dirty_ts
        if self._unplaced:
            # new ids that wait for room are unabsorbed writes too
            depth += len(self._unplaced)
            first = min(t_put for t_put, _ in self._unplaced.values())
            oldest = first if oldest is None else min(oldest, first)
        self._obs_dirty_depth.set(depth)
        self._obs_staleness.set(
            max(time.perf_counter() - oldest, 0.0)
            if oldest is not None else 0.0)

    def _maintain_locked(self) -> None:
        """Index maintenance shared by the single and batched query paths
        (called under self._lock): (re)build on first use / counter tick,
        then drain-or-peek the dirty set exactly as the class docstring
        describes.  A batched query pays this ONCE for the whole batch."""
        self._observe_health()
        if self._counter_mode:
            if self.table.puts != self._built_at:
                built_at = self.table.puts
                self._build_locked()
                self._built_at = built_at
        elif not self._built_once:
            self._build_locked()
        else:
            self._raise_if_matrix_lost_locked()
            rebuilding = (
                self._rebuild_thread is not None
                and self._rebuild_thread.is_alive()
            )
            with self._dirty_lock:
                backlog = len(self._dirty)
            if rebuilding:
                # PEEK, don't drain: a key drained now but missing from
                # the in-flight rebuild's snapshot would lose its update
                # at swap time.  Applying from the live table is
                # idempotent, so re-applying after the swap is safe —
                # but keys applied once during THIS rebuild are skipped
                # (cleared at swap), so an unchanged backlog is free.
                with self._dirty_lock:
                    dirty = dict(itertools.islice(
                        (item for item in self._dirty.items()
                         if item[0] not in self._peek_applied),
                        self.apply_cap,
                    ))
                if dirty and self._apply_updates_locked(
                        dirty, allow_rebuild=False):
                    self._peek_applied |= dirty.keys()
            elif self._replay_backlog or backlog > self.rebuild_backlog:
                # writer is outrunning the query path (or a replay-scale
                # batch was absorbed by count): one background rebuild
                # absorbs the whole backlog off-path (its snapshot reads
                # current values; the peeked set stays for idempotent
                # re-apply)
                self._start_rebuild_locked()
            else:
                dirty = self._drain_dirty(limit=self.apply_cap)
                if dirty and not self._apply_updates_locked(
                        dirty, allow_rebuild=True):
                    self._put_back(dirty)
            self._raise_if_matrix_lost_locked()

    def _raise_if_matrix_lost_locked(self) -> None:
        """A call that donates its argument and then fails may have taken
        the buffer with it: the matrix's handle then says ``is_deleted``.
        The table is the source of truth, so a background rebuild starts
        (one at a time), and until it swaps in every frame fails as the
        frames of a failed build do, by raising to its callers; the rows
        the failed drain held are back in the dirty set (``_put_back``) and
        land after the swap."""
        if self._matrix is not None and self._matrix.is_deleted():
            self._start_rebuild_locked()
            raise RuntimeError(
                "the index lost its device matrix to a failed update; a "
                "rebuild from the table is under way")

    @property
    def prefers_frames(self) -> bool:
        """True when the index's fast path is the batched frame program
        (sharded layout and/or ANN shortlist): the microbatcher then
        routes even a lone query through ``topk_many`` instead of the
        legacy single-query program, so there is exactly ONE compiled
        query program per batch bucket."""
        return self._is_sharded or self._ann is not None

    def _dispatch_frame_locked(self, q: np.ndarray, k_eff: int,
                               n_queries: int):
        """One device dispatch for a ``(B, n_factors)`` query frame, its
        first ``n_queries`` rows real ->
        ``(scores, idx)`` host arrays of shape (B, k_eff) — the tier
        router.  ANN (when built and gated in) probes centroid lists and
        scores their rows, read as blocks of the SAME resident matrix (one
        dispatch a slice of 32 real rows; at least ``n_queries`` rows back);
        the sharded exact tier runs the shard_map partial-top-k + merge;
        otherwise the legacy single-device batched program.  Every branch
        funnels through ``_to_host`` with one (B, 2k) array only (the IVF
        program's carries two counts beside it) — the catalog never leaves
        the device."""
        if self._ann is not None:
            # the tier's program takes up to ``wide`` queries: a wider frame
            # (the push plane's group, TPUMS_TOPK_BATCH_MAX above it) goes
            # as slices of its real rows, all enqueued before the first wait
            wide = self._ann.max_frame
            with stage("topk.enqueue"):
                real = q[:n_queries]
                packed = [self._ann.search(self._matrix, real[lo:lo + wide], k_eff)
                          for lo in range(0, n_queries, wide)]
            scores, idx, counts = self._fetch(packed, 2)
            self._obs_ann_frames.inc(len(packed))
            self._obs_ann_queries.inc(n_queries)
            # a slice's union is the same in each of its rows
            self._obs_ann_union_rows.inc(int(counts[::wide, 0].sum()))
            self._obs_ann_probed_rows.inc(int(counts[:n_queries, 1].sum()))
            return scores, idx
        with stage("topk.enqueue"):
            if self._is_sharded:
                fn = _sharded_topk_program(self._mesh)
                packed = fn(self._matrix, self._live, q, k_eff)
                self._obs_sharded_frames.inc()
            else:
                if self._topk_many_fn is None:
                    from functools import partial

                    import jax
                    import jax.numpy as jnp

                    @partial(jax.jit, static_argnums=3)
                    def topk_many_fn(matrix, live, qs, k):
                        with jax.named_scope("topk.score"):
                            scores = _mask_spare_rows(jnp.matmul(  # (B, n_pad)
                                qs, matrix.T, precision=_SCORE_PRECISION), live)
                        with jax.named_scope("topk.select"):
                            return _pack_results(*jax.lax.top_k(scores, k))

                    self._topk_many_fn = topk_many_fn
                packed = self._topk_many_fn(
                    self._matrix, self._live, q, k_eff)
        return self._fetch(packed)

    def _fetch(self, packed, counts: int = 0):
        """The wait for the device and the one result copy, between two
        stamped instants: the one stage of a dispatch that contains the
        device's work.  -> ``(scores, idx)`` host arrays and, where the
        program put ``counts`` int32 columns of its own behind them,
        those.  A list of arrays (the slices of a frame wider than the IVF
        program's) is fetched in order and stacked."""
        t_enqueued = time.perf_counter()
        with stage("topk.fetch"):
            host = (np.concatenate([_to_host(p) for p in packed])
                    if isinstance(packed, list) else _to_host(packed))
            out = _unpack_results(host[..., :-counts] if counts else host)
        self._stamps.last = (t_enqueued, time.perf_counter())
        return (*out, host[..., -counts:]) if counts else out

    def last_fetch(self) -> Optional[Tuple[float, float]]:
        """``perf_counter`` instants of the CALLING thread's last dispatch:
        (the jitted call returned, the results on the host), or
        None.  Per thread, so that the microbatcher, which reads them into
        ``tpums_topk_fetch_seconds`` / ``_turnaround_seconds`` once the
        index call is back and its lock released, never reads the stamps
        of another caller of the same index (the push plane, a warm-up)."""
        return getattr(self._stamps, "last", None)

    def _format_rows(self, scores, idx, n_rows: int):
        """(B_pad, k) score/index arrays -> B result lists of (id, score).
        Negative indices are masked ANN slots (shortlist came up short of
        k — only possible when nprobe lists held < k real rows); they are
        dropped rather than surfaced."""
        ids = self._ids
        return [
            [
                (ids[int(i)], float(s))
                for i, s in zip(idx[b], scores[b])
                if i >= 0
            ]
            for b in range(n_rows)
        ]

    def topk(self, user_factors: np.ndarray, k: int) -> List[Tuple[str, float]]:
        with self._lock:
            with stage("topk.maintain"):
                self._maintain_locked()
            if self._matrix is None:
                return []
            n = self._n_real
            k_eff = min(k, n)
            q = np.asarray(user_factors, dtype=np.float32)
            n_fac = self._k_real
            if q.shape[0] != n_fac:
                raise ValueError(
                    f"query has {q.shape[0]} factors, index has {n_fac}"
                )
            if self.prefers_frames:
                # sharded / ANN tiers only compile the frame program; a
                # lone query rides it as a (1, k) frame
                scores, idx = self._dispatch_frame_locked(
                    q[None, :], k_eff, 1)
                with stage("topk.format"):
                    return self._format_rows(scores, idx, 1)[0]
            with stage("topk.enqueue"):
                if self._topk_fn is None:
                    from functools import partial

                    import jax
                    import jax.numpy as jnp

                    @partial(jax.jit, static_argnums=3)
                    def topk_fn(matrix, live, query, k):
                        with jax.named_scope("topk.score"):
                            scores = _mask_spare_rows(jnp.matmul(  # (n_pad,)
                                matrix, query, precision=_SCORE_PRECISION), live)
                        with jax.named_scope("topk.select"):
                            return _pack_results(*jax.lax.top_k(scores, k))

                    self._topk_fn = topk_fn
                packed = self._topk_fn(self._matrix, self._live, q, k_eff)
            scores, idx = self._fetch(packed)
            with stage("topk.format"):
                return [
                    (self._ids[int(i)], float(s)) for i, s in zip(idx, scores)
                ]

    def topk_many(
        self, queries: np.ndarray, k: int
    ) -> List[List[Tuple[str, float]]]:
        """Batched top-k: ONE device dispatch scores every row of the
        ``(B, n_factors)`` query matrix against the catalog — the catalog
        is read from memory once for the whole batch instead of once per
        query, and the fixed dispatch cost amortizes B-fold (the
        cross-request microbatching lever, see ``microbatch.py``).

        Returns a list of B result lists; row i equals ``topk(queries[i],
        k)`` over the same index state (maintenance — dirty-row scatter /
        rebuild kick — runs once up front for the whole batch, so batched
        queries see streaming updates exactly like single queries do).

        B is padded up to the next power of two by repeating the first
        row (rows are scored independently, so pad rows cannot perturb
        real rows' results and their outputs are sliced off) — the same
        pad-to-bucket idiom as the ALS degree buckets and the update
        scatter's fixed shape: XLA compiles a handful of batch shapes,
        not one per in-flight batch size."""
        with self._lock:
            with stage("topk.maintain"):
                self._maintain_locked()
            with stage("topk.pack"):
                # a list of vectors (the microbatcher's group) stacks here
                q = np.atleast_2d(np.asarray(queries, dtype=np.float32))
                n_queries = q.shape[0]
                if self._matrix is None:
                    return [[] for _ in range(n_queries)]
                if q.shape[1] != self._k_real:
                    raise ValueError(
                        f"queries have {q.shape[1]} factors, index has "
                        f"{self._k_real}"
                    )
                k_eff = min(k, self._n_real)
                b_pad = (1 << (n_queries - 1).bit_length()
                         if n_queries > 1 else 1)
                if b_pad != n_queries:
                    q = np.concatenate([q, np.broadcast_to(
                        q[:1], (b_pad - n_queries, q.shape[1]))])
            scores, idx = self._dispatch_frame_locked(q, k_eff, n_queries)
            with stage("topk.format"):
                return self._format_rows(scores, idx, n_queries)

    def warm_batch_shapes(self, k: int, max_batch: int = 32) -> None:
        """Pre-compile every padded-bucket batched program (power-of-two
        batch shapes up to ``max_batch``) for the given ``k``.  First use
        of a bucket otherwise pays its XLA compile inside a live dispatch,
        charging tens of milliseconds to every request sharing that batch
        — a one-time cost per process that belongs at build time, not in
        the serving tail.  Phase ``topk.warm``."""
        with phase("topk.warm"):
            with self._lock:
                self._maintain_locked()
                if self._matrix is None:
                    return
                width = self._k_real
            b = 1
            while b <= max_batch:
                self.topk_many(np.zeros((b, width), dtype=np.float32), k)
                b *= 2


class ALSTopkHandler:
    """Lookup-server top-k handlers over a table's item factors.

    ``by_user`` answers the TOPK verb (user factors resolved from the same
    table, key ``<id>-U``); ``by_vector`` answers TOPKV (query factors
    supplied by the caller) — the verb sharded serving uses to fan a top-k
    out across workers that each hold only a slice of the catalog (the
    user's row lives on exactly one worker, so peers cannot resolve it
    locally).

    Scoring routes through the cross-request microbatcher
    (``microbatch.TopKBatcher``) unless ``TPUMS_TOPK_BATCH=0``: concurrent
    TOPK/TOPKV requests coalesce into one batched device dispatch instead
    of serializing on the index lock.  ``batching`` can be flipped live."""

    def __init__(self, table: ModelTable, batcher=None):
        self.table = table
        self.index = DeviceFactorIndex(table, "-I")
        if batcher is None:
            from .microbatch import TopKBatcher, batching_enabled

            if batching_enabled():
                batcher = TopKBatcher(self.index)
        self.batcher = batcher
        self.batching = batcher is not None

    def __call__(self, user_id: str, k: int) -> Optional[str]:  # TOPK verb
        payload = self.table.get(f"{user_id}-U")
        if payload is None:
            return None
        return self.by_vector(payload, k)

    def by_vector(self, factors_payload: str, k: int) -> str:  # TOPKV verb
        return self.submit_query("TOPKV", factors_payload, k)()

    def submit_query(self, verb: str, query_arg: str, k: int,
                     burst: int = 1):
        """Enqueue one TOPK/TOPKV query NOW; returns a zero-arg callable
        resolving to the wire payload (``item:score;...``) or None for an
        unknown user.  The split lets the server submit every query of a
        pipelined burst before parking on any result, so a single
        connection's in-flight window coalesces into one dispatch just
        like concurrent connections do.  ``burst`` (the read-burst line
        count) disables the batcher's idle inline path for burst members —
        the rest of the burst is already in hand and must share the
        dispatch.  Parse errors raise here, at submit time (the server
        maps them to an E reply)."""
        if verb == "TOPK":
            payload = self.table.get(f"{query_arg}-U")
            if payload is None:
                return lambda: None
        else:
            payload = query_arg
        # numpy parses the token list at C speed (same idiom as the index
        # build); float()-per-token costs ~2x on the hot path
        vec = np.array(
            [t for t in payload.split(";") if t], dtype=np.float32
        )
        if self.batching and self.batcher is not None:
            pending = self.batcher.submit(vec, k, allow_inline=(burst <= 1))
            resolver = lambda: _format_topk(pending.wait())  # noqa: E731
            # the server's trace epilogue reads the microbatcher's span
            # fields (queue wait / batch size / device time) off the
            # resolver when the request carried a tid
            resolver.pending = pending
            return resolver
        return lambda: _format_topk(self.index.topk(vec, k))

    def close(self) -> None:
        if self.batcher is not None:
            self.batcher.close()


def _format_topk(results) -> str:
    return ";".join(f"{item}:{score}" for item, score in results)


def make_als_topk_handler(table: ModelTable) -> ALSTopkHandler:
    """Handler for the lookup-server TOPK/TOPKV commands."""
    return ALSTopkHandler(table)
