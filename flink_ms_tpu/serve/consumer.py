"""Serving job — counterpart of ``ALSKafkaConsumer`` / ``SVMKafkaConsumer``
(``als-ms/.../qs/ALSKafkaConsumer.java``, ``svm-ms/.../qs/SVMKafkaConsumer.java``).

Pipeline parity (ALSKafkaConsumer.java:26-92):

    journal topic  ->  poll  ->  parse row  ->  keyed put into the sharded
    model table    ->  table is queryable through the lookup server

with the reference's operational envelope re-built natively:

- periodic checkpointing, max 1 concurrent (:44-46): a timer thread writes
  (table snapshot, journal offset) through the selected state backend;
- fixed-delay restart (3 attempts, 10 s — :48-51): the consume loop is
  wrapped in a restart supervisor that restores the last checkpoint and
  replays the journal from the committed offset (at-least-once; duplicate
  rows are last-writer-wins like ``ValueState``);
- state backends (:53-65): ``memory`` (snapshots held in RAM),
  ``fs`` (snapshot dirs under --checkpointDataUri), ``rocksdb`` (the C++
  persistent store when built, otherwise falls back to ``fs`` with a
  warning — same selection flag surface).

Key derivation:
- ALS rows ``id,T,factors`` -> key ``"<id>-<T>"`` (ALSKafkaConsumer.java:75-82)
- SVM rows ``first,rest``   -> key = raw first CSV token (featureID or
  bucket — SVMKafkaConsumer.java:74-82)
"""

from __future__ import annotations

import os
import sys
import tempfile
import threading
import time
import uuid
from typing import Callable, List, Optional, Tuple

from ..core.formats import CHUNK_ALS, CHUNK_SVM, split_journal_chunk
from ..core.params import Params
from ..obs import metrics as obs_metrics
from ..obs import profiler as obs_profiler
from ..obs import tracing as obs_tracing
from . import snapshot as snapshot_mod
from .journal import Journal, OffsetTruncatedError
from .server import LookupServer
from .table import ModelTable, _fnv1a_batch

ALS_STATE = "ALS_MODEL"
SVM_STATE = "SVM_MODEL"


def parse_als_record(line: str) -> Tuple[str, str]:
    id_, typ, payload = line.split(",", 2)
    return f"{id_}-{typ}", payload


def parse_svm_record(line: str) -> Tuple[str, str]:
    key, _, payload = line.partition(",")
    return key, payload


# native bulk-ingest mode ids (tpums_ingest_buf mirrors these parsers
# byte-for-byte; tests pin the parity)
parse_als_record.native_mode = 0
parse_svm_record.native_mode = 1
# columnar chunk-parse mode ids (core.formats.split_journal_chunk mirrors
# these parsers line-for-line; tests pin the parity).  A parse_fn without
# this attribute (custom parsers) always takes the scalar per-line path.
parse_als_record.columnar_mode = CHUNK_ALS
parse_svm_record.columnar_mode = CHUNK_SVM


# ---------------------------------------------------------------------------
# state backends
# ---------------------------------------------------------------------------

class MemoryStateBackend:
    """Snapshots kept in process RAM — survives consume-loop restarts inside
    the job, lost on process death (MemoryStateBackend parity)."""

    kind = "memory"

    def __init__(self):
        self._snap: Optional[Tuple[int, List[dict]]] = None

    def snapshot(self, table: ModelTable, offset: int) -> None:
        if not hasattr(table, "_shards"):
            # arena table: the rows live in the mmap'd file and survive a
            # consume-loop restart on their own — the offset marker is the
            # whole snapshot (replay from it is LWW-idempotent)
            table.flush()
            self._snap = (offset, None)
            return
        with table._lock:
            self._snap = (offset, [dict(s) for s in table._shards])

    def restore(self, table: ModelTable) -> Optional[int]:
        if self._snap is None:
            return None
        offset, shards = self._snap
        if shards is None:
            return offset
        with table._lock:
            table._shards = [dict(s) for s in shards]
        return offset


class FsStateBackend:
    """Snapshot dirs under the checkpoint URI (FsStateBackend parity)."""

    kind = "fs"

    def __init__(self, checkpoint_uri: str):
        import os

        self.dir = checkpoint_uri
        os.makedirs(self.dir, exist_ok=True)

    def snapshot(self, table: ModelTable, offset: int) -> None:
        table.snapshot(self.dir, offset)

    def restore(self, table: ModelTable) -> Optional[int]:
        return table.restore(self.dir)


def make_backend(kind: str, checkpoint_uri: Optional[str]):
    if kind == "memory":
        return MemoryStateBackend()
    if kind == "fs":
        if not checkpoint_uri:
            raise ValueError("fs state backend requires --checkpointDataUri")
        return FsStateBackend(checkpoint_uri)
    if kind == "rocksdb":
        if not checkpoint_uri:
            raise ValueError("rocksdb state backend requires --checkpointDataUri")
        from .native_store import StoreLockedError

        try:
            from .native_store import NativeStateBackend

            return NativeStateBackend(checkpoint_uri)
        except StoreLockedError:
            # another serving job owns this store dir — degrading to fs
            # snapshots in the SAME dir would silently fork the state
            raise
        except Exception as e:
            # toolchain missing / build failed: fs snapshots still honor the
            # checkpoint contract
            print(
                f"[serve] native store unavailable ({e}); rocksdb mode "
                "falling back to fs snapshots",
                file=sys.stderr,
            )
            return FsStateBackend(checkpoint_uri)
    raise ValueError(f"unknown state backend: {kind} (use rocksdb|fs|memory)")


# ---------------------------------------------------------------------------
# the job
# ---------------------------------------------------------------------------

class ServingJob:
    def __init__(
        self,
        journal: Journal,
        state_name: str,
        parse_fn: Callable[[str], Tuple[str, str]],
        backend,
        n_shards: int = 8,
        checkpoint_interval_ms: int = 60_000,
        poll_interval_s: float = 0.1,
        host: str = "0.0.0.0",
        port: int = 6123,
        job_id: Optional[str] = None,
        restart_attempts: int = 3,
        restart_delay_s: float = 10.0,
        native_server: bool = False,
        start_from: str = "earliest",
        ingest_mode: Optional[str] = None,
        topk_index: bool = True,
        replica_of: Optional[str] = None,
        replica_index: Optional[int] = None,
        topology_group: Optional[str] = None,
        generation: Optional[int] = None,
        snapshots: Optional[bool] = None,
        snapshot_min_bytes: Optional[int] = None,
        compact: Optional[bool] = None,
        table: Optional[str] = None,
    ):
        if start_from not in ("earliest", "latest"):
            raise ValueError("start_from must be earliest|latest")
        # journal->state application strategy (TPUMS_INGEST_MODE / CLI
        # --ingestMode): "columnar" splits whole byte chunks with numpy and
        # applies them through put_many_columns; "scalar" is the per-line
        # reference path; "auto" (default) picks columnar whenever the
        # parser advertises a columnar_mode.  The native C++ bulk path
        # (no listeners + rocksdb table) outranks both.
        if ingest_mode is None:
            ingest_mode = os.environ.get("TPUMS_INGEST_MODE", "auto")
        if ingest_mode not in ("auto", "columnar", "scalar"):
            raise ValueError("ingest_mode must be auto|columnar|scalar")
        self.ingest_mode = ingest_mode
        self.journal = journal
        self.state_name = state_name
        self.host = host
        self.parse_fn = parse_fn
        self.backend = backend
        # which table implementation holds the factors (--table /
        # TPUMS_TABLE): "dict" is the in-RAM sharded ModelTable (or the
        # backend's own durable table for rocksdb); "arena" is the
        # shared-memory mmap arena (serve/arena.py) the C++ server and
        # the snapshotter read zero-copy.  Fleet members — sharded
        # (shard_filter), HA replicas (replica_of), elastic topologies
        # (topology_group/generation) — DEFAULT to arena now that its
        # write path is native (ROADMAP item 1); TPUMS_TABLE=dict opts
        # out.  Standalone jobs and make_table backends (rocksdb owns
        # its durable table) keep their existing default.
        _sf = getattr(parse_fn, "shard_filter", None)
        if table is None:
            table = os.environ.get("TPUMS_TABLE")
        if table is None:
            fleet = (_sf is not None or replica_of is not None
                     or topology_group is not None
                     or generation is not None)
            table = "arena" if fleet and not hasattr(
                backend, "make_table") else "dict"
        if table not in ("dict", "arena"):
            raise ValueError("table must be dict|arena")
        self.table_kind = table
        self._snap_owner = (int(_sf[0]), int(_sf[1])) if _sf else (0, 1)
        if table == "arena":
            from .arena import ArenaModelTable

            # one writer per arena (flock): the dir is disambiguated along
            # every axis a fleet multiplies on over a shared journal —
            # state name, worker shard, replica index, topology generation
            arena_dir = os.path.join(
                journal.dir,
                "{}.arena-{}-w{}of{}-r{}-g{}".format(
                    journal.topic, state_name, self._snap_owner[0],
                    self._snap_owner[1], replica_index or 0,
                    generation or 0),
            )
            self.table = ArenaModelTable(n_shards, dir=arena_dir)
        # the native (rocksdb-parity) backend provides its own durable table;
        # memory/fs back a plain in-RAM sharded table
        elif hasattr(backend, "make_table"):
            self.table = backend.make_table(n_shards)
        else:
            self.table = ModelTable(n_shards)
        self.checkpoint_interval_s = checkpoint_interval_ms / 1000.0
        self.poll_interval_s = poll_interval_s
        self.job_id = job_id or uuid.uuid4().hex
        self.restart_attempts = restart_attempts
        self.restart_delay_s = restart_delay_s
        # Kafka auto.offset.reset parity for a consumer with no committed
        # checkpoint: earliest replays the whole retained topic, latest
        # serves only rows published after this job came up (aligned to
        # the last record boundary — a producer mid-append must not make
        # the first poll start inside its torn line).  A restored
        # checkpoint always wins (start() overwrites).
        self.offset = (
            journal.aligned_end_offset() if start_from == "latest" else 0
        )
        # the supervised-restart fallback replays from here when no
        # checkpoint exists yet: a startFrom=latest job must not reset to 0
        # and replay the whole retained backlog it was configured to skip
        self._seed_offset = self.offset
        self.parse_errors = 0
        # ingest-plane observability: which path ran last, how many rows /
        # chunks it applied, and the wall time spent inside state
        # application (parse + put + listener fan-out); ingest_stats()
        # reports them
        self.ingest_path = "idle"
        self.ingest_rows = 0
        self.ingest_batches = 0
        self.ingest_apply_s = 0.0
        self.checkpoints_deferred = 0
        # snapshot-shipped bootstrap (serve/snapshot.py): durable columnar
        # per-shard snapshot artifacts published at checkpoint cadence; a
        # (re)starting job bulk-loads the newest valid one and replays only
        # the journal tail behind it — O(state) recovery instead of
        # O(history) replay.  The native (rocksdb) table IS its own durable
        # O(state) artifact, so snapshots apply to the in-RAM tables only.
        if snapshots is None:
            snapshots = os.environ.get("TPUMS_SNAPSHOTS", "1") != "0"
        self._snapshots_on = bool(snapshots) and (
            hasattr(self.table, "_shards") or self.table_kind == "arena"
        )
        self._snap_root = snapshot_mod.snapshot_root(journal.dir, journal.topic)
        if snapshot_min_bytes is None:
            try:
                snapshot_min_bytes = int(
                    os.environ.get("TPUMS_SNAPSHOT_MIN_BYTES", 1 << 20)
                )
            except ValueError:
                snapshot_min_bytes = 1 << 20
        self._snap_min_bytes = max(int(snapshot_min_bytes), 1)
        self._last_snap_offset = 0
        self.bootstrap_source = "replay"
        self.bootstrap_seconds: Optional[float] = None
        self._bootstrap_t0: Optional[float] = None
        # background journal compactor (serve/compact.py): the journal is
        # shared, so exactly one member per fleet folds it — worker 0 of
        # replica 0 (a solo job qualifies).  Elastic jobs additionally
        # stand the thread down per-tick unless their generation is the
        # group's ACTIVE one (_compactor_active): during a cutover, gen g
        # and the warming gen g+1 both have a worker 0 on the same journal
        if compact is None:
            from .compact import compact_enabled

            compact = compact_enabled()
        self._compact_on = (
            bool(compact)
            and self._snap_owner[0] == 0
            and replica_index in (None, 0)
        )
        self._compactor = None
        # registry instruments (obs/): the ingest plane as scrapeable
        # series — labeled by state name only (a replica fleet is one job
        # per process; in-process test jobs share series and assert deltas)
        reg = obs_metrics.get_registry()
        st = state_name
        self._obs_rows = reg.counter("tpums_ingest_rows_total", state=st)
        self._obs_batches = reg.counter(
            "tpums_ingest_batches_total", state=st)
        self._obs_parse_errors = reg.counter(
            "tpums_ingest_parse_errors_total", state=st)
        self._obs_apply = reg.histogram(
            "tpums_ingest_apply_seconds", state=st)
        self._obs_backlog = reg.gauge(
            "tpums_journal_backlog_bytes", state=st)
        self._obs_rows_per_s = reg.gauge("tpums_ingest_rows_per_s", state=st)
        self._obs_ckpt = reg.counter("tpums_checkpoints_total", state=st)
        self._obs_ckpt_deferred = reg.gauge(
            "tpums_checkpoints_deferred", state=st)
        self._obs_ready_flips = reg.counter(
            "tpums_ready_transitions_total", state=st)
        # bootstrap/snapshot plane: how long a (re)start took to ready,
        # which source fed it, restore failures that used to be swallowed
        self._obs_restore_fail = reg.counter(
            "tpums_checkpoint_restore_failures_total", state=st)
        self._obs_bootstrap_s = reg.histogram(
            "tpums_bootstrap_seconds", state=st)
        self._obs_snap_age = reg.gauge(
            "tpums_snapshot_age_seconds", state=st)
        self._obs_snap_pub = reg.counter(
            "tpums_snapshots_published_total", state=st)
        self._obs_snap_restore_fail = reg.counter(
            "tpums_snapshot_restore_failures_total", state=st)
        self._obs_truncated = reg.counter(
            "tpums_journal_truncated_total", state=st)
        # HA plane (serve/ha.py): membership in a replica set, announced
        # through the registry so clients and supervisors can resolve the
        # whole set by the logical shard-group id
        self.replica_of = replica_of
        self.replica_index = replica_index
        # elastic plane (serve/elastic.py): a worker belonging to topology
        # generation `generation` of group `topology_group` advertises both
        # through HEALTH, plus the group's ACTIVE generation as observed at
        # heartbeat time — clients use active != ours as the re-resolve
        # hint without any new wire verb (the HEALTH JSON is the channel)
        self.topology_group = topology_group
        self.generation = generation
        self._observed_topology_gen: Optional[int] = generation
        # readiness gate: False until the consume loop has replayed the
        # journal backlog that existed when it came up — a rejoining
        # replica must never be routed traffic over a half-replayed table
        self._ready = threading.Event()
        self._hb_lock = threading.Lock()
        self._stopped = False
        self._stop = threading.Event()
        self._consumer_thread: Optional[threading.Thread] = None
        self._hb_thread: Optional[threading.Thread] = None
        self._native_arena = None
        if native_server:
            # C++ epoll data plane reading the persistent store directly —
            # requires the native (rocksdb) backend, which owns the store,
            # OR the shared-memory arena table, which the server maps
            # read-only (zero per-row pushes; tag-dispatched handle)
            from .native_store import NativeLookupServer

            if self.table_kind == "arena":
                from .native_store import NativeArena

                self._native_arena = NativeArena(self.table.dir)
                serve_handle = self._native_arena
            elif hasattr(backend, "store"):
                serve_handle = backend.store
            else:
                # either the wrong backend kind was requested, or rocksdb WAS
                # requested but degraded to fs because the native build is
                # unavailable (make_backend printed the cause)
                raise ValueError(
                    "--nativeServer needs the native (rocksdb) store, but the "
                    f"active backend is '{backend.kind}' — pass --stateBackend "
                    "rocksdb, and if you did, the native store failed to load "
                    "(see the warning above for the build error)"
                )
            self.server = NativeLookupServer(
                serve_handle, state_name, job_id=self.job_id,
                host=host, port=port,
                # ALS planes serve the full verb set natively: TOPK/TOPKV
                # score the "-I" catalog straight from the store (the
                # Python plane's DeviceFactorIndex analog, C++-side)
                topk_suffixes=("-I", "-U") if state_name == ALS_STATE
                else None,
            )
        else:
            topk_handlers = {}
            if state_name == ALS_STATE and topk_index:
                # device-scored top-k over the live item factors (serve/topk.py)
                from .topk import make_als_topk_handler

                topk_handlers[state_name] = make_als_topk_handler(self.table)
            self.server = LookupServer(
                {state_name: self.table},
                host=host,
                port=port,
                job_id=self.job_id,
                topk_handlers=topk_handlers,
                health_fn=self.health,
                staleness_fn=self._staleness,
            )
        self.port = self.server.port

    def _staleness(self):
        """Replication staleness for st=-opted reads: the follower
        replicator's journal-dir status record (serve/georepl.py), or None
        (-> 0.000 on the wire) when this journal is not a geo follower."""
        from . import georepl

        return georepl.staleness_of(self.journal.dir, self.journal.topic)

    # -- lifecycle ---------------------------------------------------------

    def start(self) -> "ServingJob":
        self._bootstrap_t0 = time.monotonic()
        restored = None
        try:
            restored = self.backend.restore(self.table)
        except Exception as e:
            # a corrupt/missing checkpoint is a counted event, not a crash:
            # bootstrap falls down the chain (snapshot, else full replay)
            self._obs_restore_fail.inc()
            print(
                f"[serve:{self.state_name}] checkpoint restore failed "
                f"({e}); falling back to snapshot/replay bootstrap",
                file=sys.stderr,
            )
        if restored is not None:
            self.offset = restored
            self.bootstrap_source = "checkpoint"
            print(
                f"[serve:{self.state_name}] restored {len(self.table)} rows, "
                f"journal offset {self.offset}",
                file=sys.stderr,
            )
        # snapshot overlay: a published snapshot AHEAD of the checkpoint
        # (or of offset 0) replaces that much replay with one columnar
        # bulk-load; last-writer-wins overlay keeps a checkpoint-restored
        # table convergent
        info = self._try_snapshot_bootstrap(min_offset=self.offset + 1)
        if info is not None:
            self.offset = info["offset"]
            self._last_snap_offset = info["offset"]
            self.bootstrap_source = "snapshot"
            if info.get("age_s") is not None:
                self._obs_snap_age.set(info["age_s"])
            print(
                f"[serve:{self.state_name}] snapshot bootstrap: "
                f"{info['rows']} rows from {info['members']} member(s), "
                f"tail replay from offset {self.offset}",
                file=sys.stderr,
            )
        self.server.start()
        # continuous profiling is part of serving (Google-Wide-Profiling
        # stance): the process-wide sampler starts with the first job and
        # is shared by all of them; TPUMS_PROF=0 is the kill switch
        obs_profiler.ensure_started()
        # announce jobId -> endpoint so clients resolve this job without
        # explicit port wiring (the reference's JobManager lookup,
        # QueryClientHelper.java:82-92; best-effort by design), with a
        # heartbeat contract: the entry promises a refresh within the TTL,
        # so readers can treat a silent job as dead (serve/ha.py)
        self._heartbeat_now()
        self._consumer_thread = threading.Thread(
            target=self._supervised_consume, name="journal-consumer", daemon=True
        )
        self._consumer_thread.start()
        self._hb_thread = threading.Thread(
            target=self._heartbeat_loop, name="registry-heartbeat", daemon=True
        )
        self._hb_thread.start()
        if self._compact_on:
            from .compact import CompactorThread

            # shares this job's stop event, so it stands down with stop()
            self._compactor = CompactorThread(
                self.journal, self.parse_fn, stop_event=self._stop,
                active_fn=self._compactor_active,
            )
            self._compactor.start()
        return self

    # -- liveness / readiness (HA plane surface) ---------------------------

    @property
    def ready(self) -> bool:
        """True once the consume loop has caught up with the journal end
        observed at (re)start — the gate a rejoining replica passes before
        it may serve traffic."""
        return self._ready.is_set()

    def wait_ready(self, timeout: Optional[float] = None) -> bool:
        return self._ready.wait(timeout)

    def backlog_bytes(self) -> int:
        """Unconsumed journal bytes behind the producer's end offset."""
        try:
            return max(self.journal.end_offset() - self.offset, 0)
        except OSError:
            return 0

    def health(self) -> dict:
        """The HEALTH verb's payload (key count is added server-side)."""
        ready = self.ready
        payload = {
            "state": self.state_name,
            "job_id": self.job_id,
            "ready": ready,
            "status": "ready" if ready else "replaying",
            "backlog_bytes": self.backlog_bytes(),
            "offset": self.offset,
            "ingest_path": self.ingest_path,
            "replica_of": self.replica_of,
            "replica": self.replica_index,
            "topology_group": self.topology_group,
            "generation": self.generation,
            "topology_gen": self._observed_topology_gen,
            "bootstrap_source": self.bootstrap_source,
            "bootstrap_seconds": self.bootstrap_seconds,
        }
        alerts = self._alert_hint()
        if alerts is not None:
            # same opt-in discipline as tn=/tid=: the fields appear ONLY
            # when a watcher has published a fresh alert record (and the
            # TPUMS_WATCH_HEALTH_HINT kill switch is not thrown), so a
            # fleet without a watch loop keeps its HEALTH bytes unchanged
            payload["alerts_firing"] = alerts["firing"]
            payload["alerts_max_severity"] = alerts["max_severity"]
        return payload

    # HEALTH is a hot poll path (supervisors, elastic clients): cache the
    # registry alert-record read for ~1s rather than hitting the
    # filesystem per reply
    _ALERT_HINT_TTL_S = 1.0

    def _alert_hint(self) -> Optional[dict]:
        if os.environ.get("TPUMS_WATCH_HEALTH_HINT", "1") == "0":
            return None
        now = time.time()
        cached = getattr(self, "_alert_hint_cache", None)
        if cached is not None and now - cached[0] < self._ALERT_HINT_TTL_S:
            return cached[1]
        from . import registry

        try:
            rec = registry.resolve_alerts()
        except Exception:  # noqa: BLE001 - hint must never break HEALTH
            rec = None
        self._alert_hint_cache = (now, rec)
        return rec

    # -- snapshot bootstrap / publication (serve/snapshot.py) --------------

    def _try_snapshot_bootstrap(
        self, min_offset: int = 0, max_offset: Optional[int] = None
    ) -> Optional[dict]:
        """Bulk-load the newest valid snapshot covering this worker's key
        slice (fallback chain: bad checksum -> older snapshot -> None, and
        the caller replays the journal instead).  Corrupt members are
        counted in ``tpums_snapshot_restore_failures_total``."""
        if not self._snapshots_on:
            return None
        try:
            return snapshot_mod.bootstrap(
                self.table,
                self._snap_root,
                owner=self._snap_owner,
                min_offset=min_offset,
                max_offset=max_offset,
                on_corrupt=lambda m: self._obs_snap_restore_fail.inc(),
            )
        except Exception as e:
            # never let the bootstrap fast path kill a job that could have
            # replayed its way up instead
            print(
                f"[serve:{self.state_name}] snapshot bootstrap failed "
                f"({e}); replaying journal",
                file=sys.stderr,
            )
            return None

    def _maybe_publish_snapshot(self) -> None:
        """Publish a snapshot artifact at the current (table, offset) —
        called between chunks (same consistency point as a checkpoint) once
        at least ``snapshot_min_bytes`` of journal landed since the last
        one."""
        if not self._snapshots_on or self.offset <= 0:
            return
        if self.offset - self._last_snap_offset < self._snap_min_bytes:
            return
        try:
            manifest = snapshot_mod.publish(
                self._snap_root,
                self.table,
                self.offset,
                shard=self._snap_owner[0],
                num_shards=self._snap_owner[1],
                group=self.topology_group,
                gen=self.generation,
                topic=self.journal.topic,
            )
        except Exception as e:
            print(
                f"[serve:{self.state_name}] snapshot publish failed ({e})",
                file=sys.stderr,
            )
            return
        self._last_snap_offset = self.offset
        self._obs_snap_pub.inc()
        self._obs_snap_age.set(0.0)
        obs_tracing.event(
            "snapshot_published", state=self.state_name, job_id=self.job_id,
            offset=self.offset, rows=manifest["rows"],
            shard=self._snap_owner[0], num_shards=self._snap_owner[1])

    def _recover_truncated(self, err: OffsetTruncatedError) -> int:
        """The consume loop hit journal history that no longer exists
        byte-for-byte.  Returns the offset to resume from; the table stays
        convergent on every path (last-writer-wins re-application)."""
        self._obs_truncated.inc()
        if err.lossless:
            # a fold replaced bytes we were mid-way through: re-reading the
            # compacted prefix from its base is a last-writer-wins superset
            # of what we already applied — zero loss
            self.journal.compacted_rereads += 1
            print(
                f"[serve:{self.state_name}] journal compacted under us at "
                f"{err.offset}; re-reading fold from {err.resume_offset}",
                file=sys.stderr,
            )
            return err.resume_offset
        # rows below resume_offset are GONE (retention); only a snapshot
        # that reaches the retained region (offset >= resume_offset) covers
        # the hole with zero loss.  One below resume_offset must NOT be
        # resumed from — its offset points back into the hole, so the next
        # read re-raises this same truncation and the loop livelocks
        info = self._try_snapshot_bootstrap(min_offset=err.resume_offset)
        if info is not None:
            self._last_snap_offset = max(
                self._last_snap_offset, info["offset"])
            print(
                f"[serve:{self.state_name}] offset {err.offset} expired; "
                f"snapshot covers through {info['offset']}",
                file=sys.stderr,
            )
            return info["offset"]
        # a snapshot strictly inside the hole can't be resumed from, but
        # bulk-loading it still narrows the loss: state through its offset
        # is covered, and only (snapshot offset, resume_offset) is gone
        info = self._try_snapshot_bootstrap(
            min_offset=err.offset + 1, max_offset=err.resume_offset)
        if info is not None:
            self._last_snap_offset = max(
                self._last_snap_offset, info["offset"])
        base = info["offset"] if info is not None else err.offset
        # resume with an explicit, counted gap — the pre-typed-error
        # journal behavior, now impossible to hit silently
        lost = err.resume_offset - base
        self.journal.expired_bytes_skipped += lost
        print(
            f"[serve:{self.state_name}] offset {err.offset} expired; no "
            f"snapshot reaches retained offset {err.resume_offset}; "
            f"skipping {lost} lost bytes (state covered through {base})",
            file=sys.stderr,
        )
        return err.resume_offset

    def _compactor_active(self) -> bool:
        """Per-tick compactor gate (CompactorThread ``active_fn``): True
        when this worker's topology generation is the group's ACTIVE one,
        as observed at heartbeat time.  During an elastic cutover both
        gen g and the warming gen g+1 have a worker 0 on the shared
        journal; the warming fleet stands down until its generation is
        published active (and the retired fleet stands down right after),
        keeping the one-compactor-per-journal invariant.  Non-elastic
        jobs always qualify."""
        if self.topology_group is None or self.generation is None:
            return True
        obs = self._observed_topology_gen
        return obs is None or int(obs) == int(self.generation)

    def _heartbeat_now(self) -> None:
        from . import registry

        # the lock makes read-ready + register atomic: without it the
        # heartbeat thread can read ready=False, lose the CPU, and write
        # that stale value AFTER the consume loop registered ready=True —
        # readiness must be monotone once flipped.  The stop check under
        # the same lock pairs with the locked unregister in stop(): the
        # consume loop's ready-flip refresh must not resurrect an entry a
        # concurrent shutdown just removed
        with self._hb_lock:
            if self._stop.is_set():
                return
            registry.register(
                self.job_id, self.host, self.port, self.state_name,
                replica_of=self.replica_of, replica=self.replica_index,
                ready=self.ready, ttl_s=registry.replica_ttl_s(),
            )
        if self.topology_group:
            # piggyback on the heartbeat cadence: one small registry read
            # keeps the generation-changed hint served by HEALTH fresh
            # within a heartbeat interval of a cutover
            try:
                topo = registry.resolve_topology(self.topology_group)
                if topo is not None:
                    self._observed_topology_gen = int(topo["gen"])
            except Exception:
                pass
        set_health = getattr(self.server, "set_health", None)
        if set_health is not None:
            # native plane: the C++ server has no callback into this job,
            # so the HEALTH report is PUSHED on the heartbeat cadence (the
            # ready flip triggers an immediate heartbeat, so readiness
            # reaches the wire without waiting out an interval); the server
            # splices in the live key count and metrics_uri itself
            try:
                import json as _json

                set_health(_json.dumps(self.health()))
            except Exception:
                pass

    def _heartbeat_loop(self) -> None:
        from . import registry

        interval = registry.heartbeat_interval_s()
        while not self._stop.wait(interval):
            if self._stop.is_set():
                break
            self._heartbeat_now()

    def stop(self) -> None:
        # idempotent: wait() calls stop() on every exit path (SIGTERM
        # handler, KeyboardInterrupt, supervisor give-up), and callers may
        # also stop() explicitly
        if self._stopped:
            return
        self._stopped = True
        self._stop.set()
        # join the heartbeat BEFORE unregistering, or an in-flight refresh
        # could resurrect the entry we just removed (it would linger until
        # TTL expiry instead of vanishing with the job)
        if self._hb_thread:
            self._hb_thread.join(timeout=5)
        from . import registry

        # under _hb_lock: the consumer thread is NOT joined yet, and its
        # ready-flip heartbeat would otherwise race this removal
        with self._hb_lock:
            registry.unregister(self.job_id)
        if self._consumer_thread:
            self._consumer_thread.join(timeout=10)
        self.server.stop()
        if self._native_arena is not None:
            # after server.stop(): no reader thread may touch the mapping
            self._native_arena.close()
        if self.table_kind == "arena" and (
            self._consumer_thread is None
            or not self._consumer_thread.is_alive()
        ):
            # releases the writer flock; a wedged consumer thread leaks the
            # mapping instead (the flock dies with the process)
            self.table.close()
        if hasattr(self.backend, "close"):
            # never free the native store under a still-running consumer
            # thread (use-after-free); a wedged thread leaks the handle
            # instead, and the flock dies with the process
            if self._consumer_thread is None or not self._consumer_thread.is_alive():
                self.backend.close()
            else:
                print(
                    f"[serve:{self.state_name}] consumer thread still busy; "
                    "leaving native store open",
                    file=sys.stderr,
                )

    def wait(self) -> None:
        # CLI foreground mode: translate SIGTERM into an orderly stop()
        # so the registry entry and backing store are released (a killed
        # job would otherwise leave a stale jobId -> port entry; clients
        # then see a refused connect instead of a clean miss)
        import signal

        try:
            signal.signal(signal.SIGTERM, lambda *_: self.stop())
        except ValueError:
            pass  # not the main thread: caller owns signal handling
        try:
            while not self._stop.is_set():
                time.sleep(0.5)
        except KeyboardInterrupt:
            pass
        # every exit path releases the registry entry and backing store
        # (idempotent — a SIGTERM-handler stop() already ran is a no-op);
        # this also covers the supervisor's give-up path, which sets
        # _stop without the full teardown
        self.stop()

    # -- consume loop with fixed-delay restart -----------------------------

    def _supervised_consume(self) -> None:
        attempts = 0
        while not self._stop.is_set():
            try:
                self._consume_loop()
                return  # clean stop
            except Exception as e:
                attempts += 1
                obs_tracing.events_counter(
                    "consume_restart" if attempts <= self.restart_attempts
                    else "consume_giveup",
                    state=self.state_name, job_id=self.job_id,
                    attempt=attempts, error=str(e))
                if attempts > self.restart_attempts:
                    print(
                        f"[serve:{self.state_name}] giving up after "
                        f"{self.restart_attempts} restarts: {e}",
                        file=sys.stderr,
                    )
                    # a dead job must not stay resolvable: drop the
                    # registry entry here too — embedded (non-CLI) jobs
                    # have no wait() to run the full stop() for them.
                    # _stop is set FIRST so the heartbeat loop stands down
                    # (a refresh racing this unregister would linger only
                    # until TTL expiry — the registry's backstop)
                    self._stop.set()
                    from . import registry

                    with self._hb_lock:
                        registry.unregister(self.job_id)
                    return
                print(
                    f"[serve:{self.state_name}] consume loop failed ({e}); "
                    f"restart {attempts}/{self.restart_attempts} in "
                    f"{self.restart_delay_s}s",
                    file=sys.stderr,
                )
                if self._stop.wait(self.restart_delay_s):
                    return
                try:
                    restored = self.backend.restore(self.table)
                    self.offset = (
                        restored if restored is not None else self._seed_offset
                    )
                except Exception as re:
                    # a corrupt/missing checkpoint must not kill the
                    # supervisor thread; continue from the in-memory state
                    # (at-least-once replay keeps the table convergent)
                    self._obs_restore_fail.inc()
                    print(
                        f"[serve:{self.state_name}] checkpoint restore failed "
                        f"({re}); continuing from in-memory state at offset "
                        f"{self.offset}",
                        file=sys.stderr,
                    )

    # a wall-clock checkpoint is deferred while a replay backlog is live
    # (every poll still moving >= half a chunk cap of bytes), but never past
    # this many checkpoint intervals — bounds the at-least-once replay debt
    # a crash mid-replay can accumulate
    CHECKPOINT_MAX_DEFER_INTERVALS = 5.0
    # one ingest poll's byte budget (both native and columnar paths): caps
    # how long one state-application critical section can run
    CHUNK_CAP = 2 << 20

    def _consume_loop(self) -> None:
        last_checkpoint = time.time()
        chunk_cap = self.CHUNK_CAP
        # readiness target: the journal end when this loop came up.  Until
        # the offset passes it, the table is mid-replay and the job reports
        # "replaying" (registry ready=False) so no failover routes here.
        # A supervised RESTART inside a live process keeps ready set — the
        # table stayed warm and the server kept answering throughout.
        ready_target = self.journal.end_offset() if not self.ready else 0
        while not self._stop.is_set():
            # native fast path: rocksdb-parity table + a standard parser +
            # no change listeners -> the whole chunk (parse, key-derive,
            # put) runs in ONE C++ call; listeners (top-k dirty tracking)
            # force the Python path so they keep seeing every key.  The
            # chunk is capped at 2 MiB (~15k rows) because the ingest call
            # holds the store mutex the C++ lookup server's reads take —
            # same starvation bound as the Python path's row-sliced chunks.
            native_mode = getattr(self.parse_fn, "native_mode", None)
            columnar_mode = getattr(self.parse_fn, "columnar_mode", None)
            rows_before = self.ingest_rows
            errs_before = self.parse_errors
            t0 = time.perf_counter()
            try:
                if (
                    native_mode is not None
                    and hasattr(self.table, "ingest_lines")
                    and not getattr(self.table, "_listeners", True)
                ):
                    self.ingest_path = "native"
                    chunk, next_offset = self.journal.read_bytes_from(
                        self.offset, max_bytes=chunk_cap
                    )
                    got_any = bool(chunk)
                    if chunk:
                        rows, errs = self.table.ingest_lines(
                            chunk, native_mode)
                        self.parse_errors += errs
                        self.ingest_rows += rows
                        self.ingest_batches += 1
                elif columnar_mode is not None and self.ingest_mode != "scalar":
                    # columnar path: numpy splits the whole byte chunk into
                    # key/value columns, ownership filtering and shard routing
                    # are vectorized, and listeners get ONE batched callback
                    self.ingest_path = "columnar"
                    chunk, next_offset = self.journal.read_bytes_from(
                        self.offset, max_bytes=chunk_cap
                    )
                    got_any = bool(chunk)
                    if chunk:
                        self._apply_chunk_columnar(chunk, columnar_mode)
                        self.ingest_batches += 1
                else:
                    self.ingest_path = "scalar"
                    lines, next_offset = self.journal.read_from(
                        self.offset, max_bytes=chunk_cap
                    )
                    got_any = bool(lines)
                    if lines:
                        self._apply_lines(lines)
                        self.ingest_batches += 1
            except OffsetTruncatedError as err:
                # our offset points at folded or expired history: recover
                # (compacted re-read / snapshot / counted gap) and poll again
                self.offset = self._recover_truncated(err)
                continue
            if got_any:
                dt = time.perf_counter() - t0
                self.ingest_apply_s += dt
                if obs_metrics.metrics_enabled():
                    rows = self.ingest_rows - rows_before
                    self._obs_rows.inc(rows)
                    self._obs_batches.inc(1)
                    self._obs_parse_errors.inc(
                        self.parse_errors - errs_before)
                    self._obs_apply.observe(dt)
                    if dt > 0:
                        self._obs_rows_per_s.set(rows / dt)
            bytes_advanced = next_offset - self.offset
            self.offset = next_offset
            if got_any and obs_metrics.metrics_enabled():
                # journal lag behind the producer's end offset — the gauge
                # a scrape reads to see a replica falling behind.  Only
                # polls that ingested re-stat the journal: backlog can
                # only change when the producer appends, and the very
                # next poll reads that — an idle caught-up loop pays no
                # per-poll stat (it would steal GIL slices from the
                # serving threads for a gauge that cannot have moved)
                self._obs_backlog.set(self.backlog_bytes())
            if not self._ready.is_set() and (
                not got_any or self.offset >= ready_target
            ):
                # caught up with the backlog that existed at start: flip to
                # ready and push the flag to the registry immediately (the
                # heartbeat cadence would otherwise delay failback by up to
                # one interval)
                self._ready.set()
                if self._bootstrap_t0 is not None:
                    # cold-path bookkeeping, once per process lifetime: how
                    # long start()->ready took and which source fed it
                    self.bootstrap_seconds = (
                        time.monotonic() - self._bootstrap_t0
                    )
                    self._bootstrap_t0 = None
                    self._obs_bootstrap_s.observe(self.bootstrap_seconds)
                    obs_metrics.get_registry().counter(
                        "tpums_bootstrap_total", state=self.state_name,
                        kind=self.bootstrap_source).inc()
                self._heartbeat_now()
                self._obs_ready_flips.inc()
                obs_tracing.event(
                    "ready", state=self.state_name, job_id=self.job_id,
                    offset=self.offset, replica_of=self.replica_of,
                    replica=self.replica_index,
                    source=self.bootstrap_source)
                # a fresh snapshot right at ready makes the NEXT joiner's
                # bootstrap O(state) even before a checkpoint interval
                # elapses (min-bytes gated, so a snapshot-fed start that
                # replayed a short tail won't immediately republish)
                self._maybe_publish_snapshot()
            now = time.time()
            if now - last_checkpoint >= self.checkpoint_interval_s:
                # a full-chunk poll means we're inside a cold-start replay
                # backlog: snapshotting the whole table now would stall
                # ingest behind a multi-second critical section and commit
                # an offset we'll blow past within milliseconds — defer,
                # bounded so a crash can't replay unboundedly
                backlog = got_any and bytes_advanced >= chunk_cap // 2
                overdue = now - last_checkpoint >= (
                    self.checkpoint_interval_s
                    * self.CHECKPOINT_MAX_DEFER_INTERVALS
                )
                if backlog and not overdue:
                    self.checkpoints_deferred += 1
                    self._obs_ckpt_deferred.set(self.checkpoints_deferred)
                else:
                    self.backend.snapshot(self.table, self.offset)
                    last_checkpoint = now
                    self._obs_ckpt.inc()
                    self._maybe_publish_snapshot()
            if not got_any:
                self._stop.wait(self.poll_interval_s)

    def _apply_lines(self, lines) -> None:
        batch = []
        for line in lines:
            if not line:
                continue
            try:
                parsed = self.parse_fn(line)
            except ValueError:
                # the reference would fail the task and burn a restart on
                # a malformed row; skip-and-count is the deliberate fix
                # (SURVEY.md Appendix C decision)
                self.parse_errors += 1
                continue
            if parsed is None:
                continue  # row owned by another sharded worker
            batch.append(parsed)
        # one lock acquisition per chunk, not per row — but chunked so
        # a cold-start replay of a big journal can't starve concurrent
        # queries behind one multi-second critical section
        for s in range(0, len(batch), 10_000):
            self.table.put_many(batch[s:s + 10_000])
        self.ingest_rows += len(batch)

    def _apply_chunk_columnar(self, chunk: bytes, mode: int) -> None:
        """Vectorized equivalent of read_from + _apply_lines: same skipped
        rows, same parse-error counts, same last-writer-wins table state
        (tests pin byte-identical parity against the scalar path).  The
        shard-routing hashes ride along from the chunk parser so neither
        the ownership filter nor the table re-hashes the keys."""
        keys, values, errs, hashes = split_journal_chunk(
            chunk, mode, with_hashes=True
        )
        self.parse_errors += errs
        shard_filter = getattr(self.parse_fn, "shard_filter", None)
        if shard_filter is not None and keys:
            # sharded worker: vectorized ownership filter replaces the
            # per-row "parsed is None" checks of the scalar wrapper
            worker_index, num_workers = shard_filter
            import numpy as np

            if hashes is None:
                hashes = _fnv1a_batch(keys)
            mine = hashes % num_workers == worker_index
            if not mine.all():
                keys = np.asarray(keys, dtype=object)[mine].tolist()
                values = np.asarray(values, dtype=object)[mine].tolist()
                hashes = hashes[mine]
        # row-sliced like the scalar path so one chunk can't starve
        # concurrent queries behind a single table-lock hold (the
        # vectorized apply is ~5x faster per row, hence the larger slice)
        for s in range(0, len(keys), 50_000):
            self.table.put_many_columns(
                keys[s:s + 50_000], values[s:s + 50_000],
                hashes=None if hashes is None else hashes[s:s + 50_000],
            )
        self.ingest_rows += len(keys)

    def ingest_stats(self) -> dict:
        """Ingest-plane counters for benches and monitoring."""
        return {
            "path": self.ingest_path,
            "rows": self.ingest_rows,
            "batches": self.ingest_batches,
            "apply_s": self.ingest_apply_s,
            "parse_errors": self.parse_errors,
            "checkpoints_deferred": self.checkpoints_deferred,
            "offset": self.offset,
        }


# ---------------------------------------------------------------------------
# CLIs
# ---------------------------------------------------------------------------

def _resolve_journal_dir(params: Params) -> str:
    """Accept both the native ``--journalDir`` and the reference's Kafka
    connection flags (``--bootstrap.servers``, ``--zookeeper.connect``,
    ``--group.id`` — ALSKafkaConsumer.java:30-35) so a reference-shaped
    invocation runs unchanged.  ``bootstrap.servers`` naming a filesystem
    path maps to the journal dir (the journal IS the broker here); a
    ``host:port`` value is acknowledged and ignored with a note."""
    if params.has("journalDir"):
        return params.get_required("journalDir")
    bootstrap = params.get("bootstrap.servers")
    looks_like_path = bool(bootstrap) and "://" not in bootstrap and (
        os.path.isdir(bootstrap)
        or bootstrap.startswith(("/", "./", "../"))
    )  # broker URLs (PLAINTEXT://host:9092, host:9092/chroot) fall through
    if looks_like_path:
        print(
            f"[serve] mapping --bootstrap.servers {bootstrap} to the local "
            "journal directory",
            file=sys.stderr,
        )
        return bootstrap
    fallback = os.environ.get(
        "TPUMS_JOURNAL_DIR",
        os.path.join(tempfile.gettempdir(), "flink_ms_tpu_journal"),
    )
    if bootstrap:
        print(
            f"[serve] --bootstrap.servers {bootstrap} names a broker, not a "
            f"path; there is no Kafka here — journal dir: {fallback} "
            "(override with --journalDir or TPUMS_JOURNAL_DIR)",
            file=sys.stderr,
        )
        return fallback
    return params.get_required("journalDir")  # raises the canonical error


def _run_consumer_cli(params: Params, state_name: str, parse_fn) -> ServingJob:
    for ignored in ("zookeeper.connect", "group.id"):
        if params.has(ignored):
            # accepted for drop-in CLI parity; journal offsets replace
            # ZooKeeper coordination and consumer-group bookkeeping
            print(f"[serve] --{ignored} accepted and ignored", file=sys.stderr)
    # retrieval-plane knobs ride the environment (the index reads them at
    # construction, including inside rebuilds); CLI flags win over an
    # inherited env so one launcher line fully describes the worker
    for flag, env in (("topkTier", "TPUMS_TOPK_TIER"),
                      ("topkSharded", "TPUMS_TOPK_SHARDED"),
                      ("annNlist", "TPUMS_ANN_NLIST"),
                      ("annNprobe", "TPUMS_ANN_NPROBE")):
        if params.has(flag):
            os.environ[env] = str(params.get(flag))
    journal = Journal(_resolve_journal_dir(params), params.get_required("topic"))
    backend = make_backend(
        params.get("stateBackend", "memory"), params.get("checkpointDataUri")
    )
    job = ServingJob(
        journal,
        state_name,
        parse_fn,
        backend,
        n_shards=params.get_int("shards", 8),
        checkpoint_interval_ms=params.get_int("checkPointInterval", 60_000),
        host=params.get("host", "0.0.0.0"),
        port=params.get_int("port", 6123),
        job_id=params.get("jobId"),
        native_server=params.get_bool("nativeServer", False),
        start_from=params.get("startFrom", "earliest"),
        ingest_mode=params.get("ingestMode"),
        snapshots=(
            params.get_bool("snapshots") if params.has("snapshots") else None
        ),
        snapshot_min_bytes=params.get_int("snapshotMinBytes"),
        compact=params.get_bool("compact") if params.has("compact") else None,
        table=params.get("table"),  # dict (default) | arena; TPUMS_TABLE env
    )
    print(
        f"[serve] {state_name} serving topic '{journal.topic}' on port "
        f"{job.port}, jobId={job.job_id}"
    )
    return job.start()


def als_main(argv=None) -> None:
    params = Params.from_args(sys.argv[1:] if argv is None else argv)
    _run_consumer_cli(params, ALS_STATE, parse_als_record).wait()


def svm_main(argv=None) -> None:
    params = Params.from_args(sys.argv[1:] if argv is None else argv)
    _run_consumer_cli(params, SVM_STATE, parse_svm_record).wait()
