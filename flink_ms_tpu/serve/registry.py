"""Job location registry — the jobId->endpoint resolution the reference
gets from its JobManager, grown into the HA
plane's liveness store.

The reference's clients never name a server port: ``QueryClientHelper``
connects to the JobManager (``--jobManagerHost``/``--jobManagerPort``) and
resolves *any* running job's queryable state by ``--jobId``
(``QueryClientHelper.java:82-92,121`` — ``client.getKvState(jobId, ...)``).
Here the control plane is a registry DIRECTORY: every ``ServingJob``
registers ``<jobId>.json`` (host, port, state, pid) on start and removes it
on stop, and clients resolve ``--jobId`` through it when no explicit
``--jobManagerPort`` is given.  Multiple serving jobs on one machine (or a
shared filesystem) are therefore addressable by jobId alone, like the
reference — no operator port wiring.

Liveness (the HA subsystem, serve/ha.py): an entry may carry a heartbeat
contract — ``ttl_s`` promises the writer refreshes ``heartbeat`` at least
that often (``ServingJob`` re-registers on the ``TPUMS_HEARTBEAT_S``
cadence).  Readers treat an entry whose heartbeat is past its promised TTL
as dead, exactly like a locally-recorded pid that no longer exists; dead
entries are garbage-collected on the next ``resolve()`` / ``list_jobs()``
pass instead of lingering forever.  Entries WITHOUT ``ttl_s`` (manual
registrations, older writers) are never TTL-checked — liveness there
remains pid-based only, the pre-HA behavior.

Replica sets: a replicated shard worker registers with ``replica_of`` (the
logical shard group id, e.g. ``"mysvc/shard-0"``), ``replica`` (its index
in the set) and ``ready`` (False while it is still replaying the journal —
the readiness gate clients honor during failover).  ``resolve_replicas``
returns the live members of a group.

Topology records (the elastic plane, serve/elastic.py): a job GROUP's
active shape lives in one ``kind="topology"`` record — ``(gen, shards,
replicas)`` plus a bounded history of superseded generations.  Publishes
are atomic (tmp + rename under a short-lived lock file) and CAS-guarded:
a publisher naming ``expect_gen`` that no longer matches loses with
``TopologyConflict`` instead of silently rolling the fleet back.  Unlike
endpoint registration, topology publish is NOT best-effort — a controller
that cannot record a cutover must know.  ``gc_generation_entries`` reaps
DEAD worker entries of superseded generations immediately (the TTL would
get them eventually; a cutover shouldn't leave corpses for readers to
re-judge until then).  A controller LEASE (``acquire_controller_lease``)
makes rescaling single-writer per group: the second controller refuses —
or defers, its choice — unless the holder's pid/heartbeat shows it dead,
in which case the lease is stolen with the same TOCTOU guard as entry
reaping.

Location: ``TPUMS_REGISTRY_DIR`` (deployment/shared-FS override), else
``<tmpdir>/flink_ms_tpu_registry`` — the same host-local convention as the
journal's default bus directory.  Registration is best-effort: registry
I/O failures never take down a serving job (a client then needs the
explicit port, which is exactly today's behavior).
"""

from __future__ import annotations

import json
import os
import tempfile
import time
from typing import Dict, List, Optional, Tuple

from ..core.params import Params


def registry_dir() -> str:
    return os.environ.get("TPUMS_REGISTRY_DIR") or os.path.join(
        tempfile.gettempdir(), "flink_ms_tpu_registry"
    )


def heartbeat_interval_s() -> float:
    """Registry heartbeat cadence (``TPUMS_HEARTBEAT_S``, default 1 s)."""
    try:
        return max(float(os.environ.get("TPUMS_HEARTBEAT_S", 1.0)), 0.05)
    except ValueError:
        return 1.0


def replica_ttl_s() -> float:
    """Staleness TTL for heartbeat-bearing entries (``TPUMS_REPLICA_TTL_S``,
    default 5x the heartbeat interval).  The TTL must comfortably exceed
    the heartbeat cadence or a GC'd entry flaps on every scheduler hiccup."""
    try:
        v = os.environ.get("TPUMS_REPLICA_TTL_S")
        if v is not None:
            return max(float(v), 0.1)
    except ValueError:
        pass
    return 5.0 * heartbeat_interval_s()


def _load_json_retry(path: str, strict: bool = False):
    """Shared torn-read guard for every registry file read.

    Writers are atomic (tmp + rename/link), but a reader can still open a
    file mid-replacement on filesystems whose rename visibility is not a
    single point (NFS attribute caching, overlayfs copy-up), or catch a
    non-registry writer mid-write.  A JSON decode failure is therefore
    ambiguous: torn-mid-write or an actual corpse.  ONE short re-read
    disambiguates — a concurrent writer's rename lands within the backoff,
    so a live record is never judged dead off a single torn read.  A
    missing file stays an immediate None (no entry is not a torn entry).

    ``strict=True`` (the elastic client's topology refresh) re-raises the
    final failure instead of returning None, so callers can tell "no
    record" from "the registry is unreadable right now" and keep serving
    their last known state rather than silently treating an I/O blip as a
    dropped topology."""
    last_err: Optional[Exception] = None
    for attempt in (0, 1):
        try:
            with open(path) as f:
                return json.load(f)
        except FileNotFoundError:
            return None
        except (OSError, ValueError) as e:
            last_err = e
            if attempt == 0:
                time.sleep(0.002)
    if strict and last_err is not None:
        raise last_err
    return None


def _entry_path(job_id: str) -> str:
    # jobIds are caller-chosen strings: sanitize for the filesystem, and
    # append a short digest of the RAW id so distinct ids can never map to
    # one file (sanitizing alone would let "als/prod" overwrite or delete
    # "als_prod"'s live registration)
    import hashlib

    safe = "".join(c if c.isalnum() or c in "-_." else "_" for c in job_id)
    digest = hashlib.sha1(job_id.encode("utf-8")).hexdigest()[:8]
    return os.path.join(registry_dir(), f"{safe[:80]}-{digest}.json")


def register(
    job_id: str,
    host: str,
    port: int,
    state_name: str,
    *,
    replica_of: Optional[str] = None,
    replica: Optional[int] = None,
    ready: Optional[bool] = None,
    ttl_s: Optional[float] = None,
) -> None:
    """Record a serving job's endpoint (atomic write; best-effort).

    Re-registering IS the heartbeat: a writer that passed ``ttl_s`` calls
    this again on its heartbeat cadence (full-entry atomic rewrite — no
    read-modify-write race with a concurrent reaper)."""
    try:
        os.makedirs(registry_dir(), exist_ok=True)
        path = _entry_path(job_id)
        tmp = f"{path}.{os.getpid()}.tmp"
        import socket

        entry = {
            "job_id": job_id, "host": host, "port": int(port),
            "state": state_name, "pid": os.getpid(),
            # pid_host scopes the pid-liveness check: on a shared-FS
            # registry a pid is only meaningful on the machine that
            # recorded it (a wildcard bind says nothing about where)
            "pid_host": socket.gethostname(),
        }
        if replica_of is not None:
            entry["replica_of"] = replica_of
        if replica is not None:
            entry["replica"] = int(replica)
        if ready is not None:
            entry["ready"] = bool(ready)
        if ttl_s is not None:
            entry["ttl_s"] = float(ttl_s)
            entry["heartbeat"] = time.time()
        with open(tmp, "w") as f:
            json.dump(entry, f)
        os.replace(tmp, path)
    except OSError:
        pass


def unregister(job_id: str) -> None:
    try:
        os.unlink(_entry_path(job_id))
    except OSError:
        pass


def entry_is_dead(entry: dict, now: Optional[float] = None) -> bool:
    """True when this entry's job is provably gone: a locally-recorded pid
    that no longer exists, or a heartbeat contract (``ttl_s``) the writer
    has broken.  Entries without either signal are presumed alive."""
    pid = entry.get("pid")
    if isinstance(pid, int) and _pid_is_ours_and_dead(entry):
        return True
    ttl = entry.get("ttl_s")
    hb = entry.get("heartbeat")
    if isinstance(ttl, (int, float)) and isinstance(hb, (int, float)):
        if (time.time() if now is None else now) - hb > ttl:
            return True
    return False


def _reap_if_unchanged(path: str, entry: dict) -> Optional[dict]:
    """GC a dead entry, guarding the reap TOCTOU: a supervisor may have
    re-registered the job at this path since our read — only unlink if the
    file still carries the same (pid, heartbeat) we judged dead.  Returns
    the FRESH entry when one replaced the dead one, else None."""
    current = _load_json_retry(path)
    if current is None:
        return None
    if (
        isinstance(current, dict)
        and current.get("pid") == entry.get("pid")
        and current.get("heartbeat") == entry.get("heartbeat")
    ):
        try:
            os.unlink(path)
        except OSError:
            pass
        return None
    if isinstance(current, dict) and "port" in current \
            and not entry_is_dead(current):
        return current
    return None


def resolve(job_id: str) -> Optional[dict]:
    """-> the registered entry for job_id, or None.

    A SIGKILL'd ServingJob never runs its unregister cleanup, so an entry
    recorded by THIS machine (pid_host matches) whose pid is dead — or any
    entry whose heartbeat contract has lapsed — is treated as no-entry
    (and reaped): clients then fall back to the explicit-port defaults
    instead of getting connection-refused on a stale endpoint.  Entries
    recorded elsewhere (shared-FS registry) are never pid-checked: the pid
    is meaningless across machines; their TTL still applies."""
    path = _entry_path(job_id)
    entry = _load_json_retry(path)
    if not isinstance(entry, dict) or "port" not in entry:
        return None
    if entry_is_dead(entry):
        return _reap_if_unchanged(path, entry)
    return entry


def list_jobs(gc: bool = True) -> List[dict]:
    """Every live entry in the registry (GC'ing dead ones on the way,
    unless ``gc=False``).  The ops/discovery surface: replica resolution,
    supervisors, and the chaos harness all build on this scan."""
    out: List[dict] = []
    try:
        names = os.listdir(registry_dir())
    except OSError:
        return out
    for name in names:
        if not name.endswith(".json"):
            continue
        path = os.path.join(registry_dir(), name)
        entry = _load_json_retry(path)
        if not isinstance(entry, dict) or "port" not in entry:
            continue
        if entry_is_dead(entry):
            if gc:
                fresh = _reap_if_unchanged(path, entry)
                if fresh is not None:
                    out.append(fresh)
            continue
        out.append(entry)
    return out


def resolve_replicas(replica_of: str) -> List[dict]:
    """Live members of a replica group, sorted by replica index.  Entries
    whose ``ready`` flag is False are included (callers that must not send
    traffic to a replaying replica filter on ``ready`` themselves — a
    supervisor, by contrast, needs to see them to NOT respawn them)."""
    members = [
        e for e in list_jobs() if e.get("replica_of") == replica_of
    ]
    members.sort(key=lambda e: (e.get("replica", 0), e.get("job_id", "")))
    return members


# ---------------------------------------------------------------------------
# tenant namespaces (the multi-tenant fleet, serve/rollout.py + admission)
# ---------------------------------------------------------------------------

# A tenant is a NAME PREFIX on group/job identifiers: ``acme::als`` is
# tenant "acme"'s serving group "als".  Everything derived from the group
# string — worker job ids, replica groups, generation groups, topology
# records, controller leases, snapshot scopes — inherits the prefix, so
# two tenants' fleets coexist in one registry directory with zero shared
# records and per-tenant GC that provably cannot touch a neighbor.

TENANT_SEP = "::"


def default_tenant() -> Optional[str]:
    """The ambient tenant (``TPUMS_TENANT``), or None for the shared
    (un-prefixed) namespace — the single-tenant deployments' default."""
    t = os.environ.get("TPUMS_TENANT", "").strip()
    return t or None


def qualify_group(group: str, tenant: Optional[str] = None) -> str:
    """Tenant-scope a group name -> ``<tenant>::<group>``.

    ``tenant=None`` uses the ambient ``TPUMS_TENANT``; an explicit empty
    string pins the shared namespace regardless of environment.  Already
    qualified names pass through unchanged (idempotent, so controllers
    and clients can both call it on the same name)."""
    if TENANT_SEP in group:
        return group
    t = default_tenant() if tenant is None else (tenant.strip() or None)
    if not t:
        return group
    if TENANT_SEP in t or "/" in t or "\t" in t or "\n" in t:
        raise ValueError(f"bad tenant name: {t!r}")
    return f"{t}{TENANT_SEP}{group}"


def split_tenant(name: str) -> Tuple[Optional[str], str]:
    """``"acme::als@g3/shard-0"`` -> ("acme", "als@g3/shard-0");
    un-prefixed names -> (None, name)."""
    if TENANT_SEP in name:
        t, _, base = name.partition(TENANT_SEP)
        return (t or None), base
    return None, name


def tenant_of(name: str) -> Optional[str]:
    return split_tenant(name)[0]


def _entry_tenant(entry: dict) -> Optional[str]:
    return tenant_of(entry.get("replica_of") or entry.get("job_id") or "")


def list_tenants() -> List[str]:
    """Tenants with any registry presence (live worker entries or
    topology records), sorted.  The shared namespace is not a tenant and
    is never listed."""
    seen = set()
    for e in list_jobs(gc=False):
        t = _entry_tenant(e)
        if t:
            seen.add(t)
    try:
        names = os.listdir(registry_dir())
    except OSError:
        names = []
    for name in names:
        if not name.endswith(".topo.json"):
            continue
        rec = _read_record(os.path.join(registry_dir(), name), "topology")
        if rec:
            t = tenant_of(rec.get("group") or "")
            if t:
                seen.add(t)
    return sorted(seen)


def list_tenant_jobs(tenant: Optional[str], gc: bool = True) -> List[dict]:
    """Live entries belonging to one tenant's namespace (``tenant=None``
    selects the shared namespace)."""
    return [e for e in list_jobs(gc=gc) if _entry_tenant(e) == tenant]


def gc_tenant_entries(tenant: str) -> int:
    """Reap DEAD worker entries of ONE tenant -> count reaped.

    The isolation guarantee of the namespace scheme, stated as an
    operation: this can only ever unlink entries whose identifiers carry
    ``<tenant>::`` — other tenants and the shared namespace are
    structurally out of reach.  Raw dir scan for the same reason as
    ``gc_generation_entries``."""
    if not tenant:
        raise ValueError("gc_tenant_entries needs a tenant name")
    reaped = 0
    try:
        names = os.listdir(registry_dir())
    except OSError:
        return 0
    for name in names:
        if not name.endswith(".json"):
            continue
        path = os.path.join(registry_dir(), name)
        entry = _load_json_retry(path)
        if not isinstance(entry, dict) or "port" not in entry:
            continue
        if _entry_tenant(entry) != tenant:
            continue
        if entry_is_dead(entry) and _reap_if_unchanged(path, entry) is None:
            reaped += 1
    return reaped


# ---------------------------------------------------------------------------
# region namespaces (the geo-distributed plane, serve/georepl.py)
# ---------------------------------------------------------------------------

# A region is the OUTERMOST name prefix on group/job identifiers:
# ``eu@@acme::als`` is region "eu"'s view of tenant "acme"'s serving group
# "als".  Same discipline as tenant namespaces, one level further out:
# every id derived from a region-qualified group — worker job ids, replica
# groups, generation groups, topology records, controller leases, snapshot
# scopes, alert scopes — inherits the prefix, so a follower fleet in one
# region shares zero registry records with the home fleet, and region GC
# structurally cannot touch another region's entries.

REGION_SEP = "@@"


def default_region() -> Optional[str]:
    """The ambient region (``TPUMS_GEO_REGION``), or None for the
    unscoped namespace — single-region deployments' default."""
    r = os.environ.get("TPUMS_GEO_REGION", "").strip()
    return r or None


def qualify_region(name: str, region: Optional[str] = None) -> str:
    """Region-scope a group/job name -> ``<region>@@<name>``.

    ``region=None`` uses the ambient ``TPUMS_GEO_REGION``; an explicit
    empty string pins the unscoped namespace regardless of environment.
    Already region-qualified names pass through unchanged (idempotent).
    Applied OUTSIDE tenant qualification: ``eu@@acme::als``."""
    if REGION_SEP in name:
        return name
    r = default_region() if region is None else (region.strip() or None)
    if not r:
        return name
    if (REGION_SEP in r or TENANT_SEP in r or "/" in r
            or "\t" in r or "\n" in r):
        raise ValueError(f"bad region name: {r!r}")
    return f"{r}{REGION_SEP}{name}"


def split_region(name: str) -> Tuple[Optional[str], str]:
    """``"eu@@acme::als@g3/shard-0"`` -> ("eu", "acme::als@g3/shard-0");
    unscoped names -> (None, name)."""
    if REGION_SEP in name:
        r, _, base = name.partition(REGION_SEP)
        return (r or None), base
    return None, name


def region_of(name: str) -> Optional[str]:
    return split_region(name)[0]


def _entry_region(entry: dict) -> Optional[str]:
    return region_of(entry.get("replica_of") or entry.get("job_id") or "")


def list_regions() -> List[str]:
    """Regions with any registry presence (live worker entries or topology
    records), sorted.  The unscoped namespace is not a region."""
    seen = set()
    for e in list_jobs(gc=False):
        r = _entry_region(e)
        if r:
            seen.add(r)
    try:
        names = os.listdir(registry_dir())
    except OSError:
        names = []
    for name in names:
        if not name.endswith(".topo.json"):
            continue
        rec = _read_record(os.path.join(registry_dir(), name), "topology")
        if rec:
            r = region_of(rec.get("group") or "")
            if r:
                seen.add(r)
    return sorted(seen)


def list_region_jobs(region: Optional[str], gc: bool = True) -> List[dict]:
    """Live entries belonging to one region's namespace (``region=None``
    selects the unscoped namespace)."""
    return [e for e in list_jobs(gc=gc) if _entry_region(e) == region]


# ---------------------------------------------------------------------------
# edge-proxy namespace (serve/edge.py)
# ---------------------------------------------------------------------------

EDGE_PREFIX = "edge/"


def edge_group(group: str, region: Optional[str] = None) -> str:
    """The registry replica-group carrying a serving group's EDGE PROXY
    endpoints — ``edge/<region>@@<tenant>::<group>``.

    Proxies register under it with ``replica_of=edge_group(g)`` (one
    entry per proxy, ``replica=<index>``) and re-register on the
    heartbeat cadence like any worker, so ``resolve_replicas`` is the
    one discovery path clients, smokes and the scraper all share.
    Distinct from the group's shard topology record: the edge tier is
    stateless and has no generations — proxies follow the data plane's
    topology record, they never appear in it."""
    return f"{EDGE_PREFIX}{qualify_region(qualify_group(group), region)}"


def gc_region_entries(region: str) -> int:
    """Reap DEAD worker entries of ONE region -> count reaped.  Same
    structural-isolation statement as ``gc_tenant_entries``: only entries
    whose identifiers carry ``<region>@@`` are reachable."""
    if not region:
        raise ValueError("gc_region_entries needs a region name")
    reaped = 0
    try:
        names = os.listdir(registry_dir())
    except OSError:
        return 0
    for name in names:
        if not name.endswith(".json"):
            continue
        path = os.path.join(registry_dir(), name)
        entry = _load_json_retry(path)
        if not isinstance(entry, dict) or "port" not in entry:
            continue
        if _entry_region(entry) != region:
            continue
        if entry_is_dead(entry) and _reap_if_unchanged(path, entry) is None:
            reaped += 1
    return reaped


def _pid_is_ours_and_dead(entry: dict) -> bool:
    import socket

    if entry.get("pid_host") != socket.gethostname():
        return False  # recorded by another machine (or a pre-pid_host
        # entry): liveness is unknowable here, keep the entry
    try:
        os.kill(entry["pid"], 0)
    except ProcessLookupError:
        return True
    except OSError:
        pass  # EPERM etc.: the process exists, just not ours
    return False


# ---------------------------------------------------------------------------
# topology records + controller lease (the elastic plane, serve/elastic.py)
# ---------------------------------------------------------------------------

TOPOLOGY_HISTORY = 8  # superseded generations kept in the record


class TopologyConflict(RuntimeError):
    """A CAS publish lost: the group's generation moved under the caller."""


def _group_path(group: str, suffix: str) -> str:
    import hashlib

    safe = "".join(c if c.isalnum() or c in "-_." else "_" for c in group)
    digest = hashlib.sha1(group.encode("utf-8")).hexdigest()[:8]
    return os.path.join(registry_dir(), f"{safe[:80]}-{digest}.{suffix}")


def _topology_path(group: str) -> str:
    # distinct suffix so a JOB registered under the group's name can never
    # collide with the group's topology record (both end in .json; readers
    # of either kind validate the payload, not the filename)
    return _group_path(group, "topo.json")


def _read_record(path: str, kind: str, strict: bool = False
                 ) -> Optional[dict]:
    record = _load_json_retry(path, strict=strict)
    if not isinstance(record, dict) or record.get("kind") != kind:
        return None
    return record


def resolve_topology(group: str, strict: bool = False) -> Optional[dict]:
    """The group's active topology record ``{gen, shards, replicas, ...}``,
    or None when no generation was ever published.  ``strict=True`` raises
    the underlying ``OSError``/``ValueError`` when the record exists but
    cannot be read — clients refreshing a topology must distinguish "gone"
    (rebuild against defaults) from "unreadable" (keep the generation they
    have)."""
    return _read_record(_topology_path(group), "topology", strict=strict)


class _GroupLock:
    """Short-lived O_EXCL lock file serializing read-modify-write of one
    group's records.  A lock older than ``stale_s`` is presumed abandoned
    (its holder crashed between create and unlink) and broken."""

    def __init__(self, path: str, timeout_s: float = 2.0,
                 stale_s: float = 5.0):
        self.path = path + ".lock"
        self.timeout_s = timeout_s
        self.stale_s = stale_s

    def __enter__(self):
        deadline = time.time() + self.timeout_s
        while True:
            try:
                fd = os.open(self.path, os.O_CREAT | os.O_EXCL | os.O_WRONLY)
                os.close(fd)
                return self
            except FileExistsError:
                try:
                    if time.time() - os.path.getmtime(self.path) > self.stale_s:
                        os.unlink(self.path)
                        continue
                except OSError:
                    continue  # holder released between stat and unlink
                if time.time() > deadline:
                    raise TimeoutError(
                        f"group lock busy: {self.path}") from None
                time.sleep(0.01)

    def __exit__(self, *exc):
        try:
            os.unlink(self.path)
        except OSError:
            pass


def publish_topology(
    group: str,
    shards: int,
    replicas: int = 1,
    *,
    expect_gen: Optional[int] = None,
    controller: Optional[str] = None,
    extra: Optional[dict] = None,
) -> dict:
    """Atomically publish the group's next topology generation -> record.

    The new generation is always ``current + 1`` (1 for a fresh group).
    ``expect_gen`` is the CAS guard: a controller that planned the cutover
    against generation G passes ``expect_gen=G``, and if some other writer
    advanced the record meanwhile this raises ``TopologyConflict`` instead
    of overwriting the newer topology.  The superseded generation joins a
    bounded ``history`` (stale-generation GC: the record never grows past
    ``TOPOLOGY_HISTORY`` entries).  NOT best-effort: I/O failures raise.

    ``extra``: additional record fields (cannot shadow the protocol
    fields).  The rollout controller binds the generation's MODEL here
    (``{"model": {journal_dir, topic, model_id, ...}}``); a generation's
    model binding follows it into ``history``, which is what makes
    one-command rollback possible (serve/rollout.py)."""
    if shards < 1 or replicas < 1:
        raise ValueError("need shards >= 1 and replicas >= 1")
    os.makedirs(registry_dir(), exist_ok=True)
    path = _topology_path(group)
    import socket

    with _GroupLock(path):
        current = _read_record(path, "topology")
        cur_gen = int(current["gen"]) if current else 0
        if expect_gen is not None and cur_gen != int(expect_gen):
            raise TopologyConflict(
                f"group {group!r} is at generation {cur_gen}, "
                f"publisher expected {expect_gen}"
            )
        history = list(current.get("history", ())) if current else []
        if current:
            superseded = {
                "gen": current["gen"], "shards": current["shards"],
                "replicas": current["replicas"],
                "published_at": current.get("published_at"),
            }
            if "model" in current:
                superseded["model"] = current["model"]
            history.append(superseded)
            history = history[-TOPOLOGY_HISTORY:]
        record = {
            "kind": "topology", "group": group, "gen": cur_gen + 1,
            "shards": int(shards), "replicas": int(replicas),
            "published_at": time.time(),
            "controller": controller
            or f"{socket.gethostname()}:{os.getpid()}",
            "history": history,
        }
        if extra:
            for k, v in extra.items():
                record.setdefault(k, v)
        tmp = f"{path}.{os.getpid()}.tmp"
        with open(tmp, "w") as f:
            json.dump(record, f)
        os.replace(tmp, path)
    return record


def drop_topology(group: str) -> None:
    """Remove the group's topology record (teardown; best-effort)."""
    try:
        os.unlink(_topology_path(group))
    except OSError:
        pass


# ---------------------------------------------------------------------------
# push-plane subscription epochs (serve/push.py)
# ---------------------------------------------------------------------------

def _push_epoch_path(scope: str) -> str:
    return _group_path(f"pushes/{scope}", "push.json")


def next_push_epoch(scope: str) -> int:
    """Atomically claim the scope's next subscription epoch -> int >= 1.

    Every push engine (one per serving process that ever accepts a
    SUBSCRIBE) claims one epoch at startup and mints subscription ids as
    ``<epoch>-<n>``, so ids stay globally unique across replica restarts,
    reshards and failovers — the property the zero-miss/zero-dup sequence
    audit leans on: a RESUME that lands on a replica which never saw the
    subscription can only answer with a FRESH id + snapshot, never reuse
    the old id with a colliding sequence space.  Same read-modify-write
    discipline as ``publish_topology`` (group lock + tmp + rename)."""
    os.makedirs(os.path.dirname(_push_epoch_path(scope)) or ".",
                exist_ok=True)
    path = _push_epoch_path(scope)
    with _GroupLock(path):
        current = _read_record(path, "push_epoch")
        epoch = (int(current["epoch"]) if current else 0) + 1
        record = {"kind": "push_epoch", "scope": scope, "epoch": epoch,
                  "claimed_at": time.time(), "pid": os.getpid()}
        tmp = f"{path}.{os.getpid()}.tmp"
        with open(tmp, "w") as f:
            json.dump(record, f)
        os.replace(tmp, path)
    return epoch


# ---------------------------------------------------------------------------
# snapshot manifests (serve/snapshot.py publishes, fleet scrape reads)
# ---------------------------------------------------------------------------

def snapshot_scope(
    group: Optional[str], topic: Optional[str], num_shards: int, shard: int
) -> str:
    """One registry record per (group-or-topic, sharding, shard): the
    LATEST published snapshot for that slice."""
    return f"snap/{group or topic or 'default'}/{num_shards}/{shard}"


def _snapshot_path(scope: str) -> str:
    return _group_path(scope, "snap.json")


def publish_snapshot(scope: str, manifest: dict) -> None:
    """Register the slice's latest snapshot manifest.  Best-effort by
    design: bootstrap resolves snapshots from the data dirs (which survive
    a wiped registry); this record only feeds fleet observability."""
    os.makedirs(registry_dir(), exist_ok=True)
    path = _snapshot_path(scope)
    record = {"kind": "snapshot", "scope": scope,
              "published_at": time.time(), "manifest": dict(manifest)}
    tmp = f"{path}.{os.getpid()}.tmp"
    try:
        with open(tmp, "w") as f:
            json.dump(record, f)
        os.replace(tmp, path)
    except OSError:
        try:
            os.unlink(tmp)
        except OSError:
            pass


def resolve_snapshot(scope: str) -> Optional[dict]:
    """The slice's latest registered snapshot manifest, or None."""
    record = _read_record(_snapshot_path(scope), "snapshot")
    return record.get("manifest") if record else None


# ---------------------------------------------------------------------------
# alert records (obs/watch.py publishes, HEALTH hints and fleet_signals
# read) — same best-effort file-per-record shape as snapshot manifests.
# Records carry their own TTL so a dead watcher's last word expires
# instead of pinning stale alerts onto every HEALTH reply forever.
# ---------------------------------------------------------------------------

def _alerts_path(scope: str) -> str:
    return _group_path(f"alerts/{scope}", "alerts.json")


def publish_alerts(scope: str, summary: dict, ttl_s: float = 15.0) -> None:
    """Publish a watcher's alert summary (``RulesEngine.summary()`` shape:
    ``{"firing", "max_severity", "max_severity_level", "alerts"}``) under
    ``scope`` (a group name, or ``"fleet"`` for a whole-fleet watcher)."""
    os.makedirs(registry_dir(), exist_ok=True)
    path = _alerts_path(scope)
    record = {"kind": "alerts", "scope": scope,
              "published_at": time.time(), "ttl_s": float(ttl_s),
              "summary": dict(summary)}
    tmp = f"{path}.{os.getpid()}.tmp"
    try:
        with open(tmp, "w") as f:
            json.dump(record, f)
        os.replace(tmp, path)
    except OSError:
        try:
            os.unlink(tmp)
        except OSError:
            pass


def resolve_alerts(scope: Optional[str] = None) -> Optional[dict]:
    """The current alert summary: one scope's fresh record, or — with no
    scope — every fresh record merged (firing counts sum, severities take
    the max).  Expired records are GC'd on the way past.  None when no
    watcher has published anything fresh."""
    if scope is not None:
        record = _read_record(_alerts_path(scope), "alerts")
        if record is None:
            return None
        if time.time() - record.get("published_at", 0) > \
                record.get("ttl_s", 15.0):
            drop_alerts(scope)
            return None
        return record.get("summary")
    merged: Optional[dict] = None
    try:
        names = os.listdir(registry_dir())
    except OSError:
        return None
    now = time.time()
    for fname in names:
        if not fname.startswith("alerts_") or \
                not fname.endswith(".alerts.json"):
            continue
        path = os.path.join(registry_dir(), fname)
        record = _read_record(path, "alerts")
        if record is None:
            continue
        if now - record.get("published_at", 0) > record.get("ttl_s", 15.0):
            try:
                os.unlink(path)
            except OSError:
                pass
            continue
        s = record.get("summary", {})
        if merged is None:
            merged = {"firing": 0, "max_severity": None,
                      "max_severity_level": 0, "alerts": []}
        merged["firing"] += int(s.get("firing", 0))
        merged["alerts"].extend(s.get("alerts", []))
        if s.get("max_severity_level", 0) > merged["max_severity_level"]:
            merged["max_severity_level"] = s["max_severity_level"]
            merged["max_severity"] = s.get("max_severity")
    return merged


def drop_alerts(scope: str) -> None:
    """Remove a scope's alert record (watcher teardown; best-effort)."""
    try:
        os.unlink(_alerts_path(scope))
    except OSError:
        pass


def generation_of(entry: dict, group: str, gen_sep: str = "@g"
                  ) -> Optional[int]:
    """Parse the topology generation out of a worker entry's shard-group id
    (``<group>@g<gen>/shard-<i>``); None for entries outside ``group``."""
    replica_of = entry.get("replica_of") or ""
    prefix = f"{group}{gen_sep}"
    if not replica_of.startswith(prefix):
        return None
    gen_s = replica_of[len(prefix):].split("/", 1)[0]
    try:
        return int(gen_s)
    except ValueError:
        return None


def gc_generation_entries(group: str, active_gen: int) -> int:
    """Reap DEAD worker entries of generations < ``active_gen`` -> count.

    Live old-generation workers are left alone — a cutover drains them
    deliberately (serve/elastic.py), and a worker that outlives its drain
    window still answers in-flight clients.  Dead ones would be TTL-GC'd
    eventually; after a cutover they are provably garbage NOW.

    Scans the raw registry dir (NOT ``list_jobs``, which filters dead
    entries out of its result whether or not it GCs them)."""
    reaped = 0
    try:
        names = os.listdir(registry_dir())
    except OSError:
        return 0
    for name in names:
        if not name.endswith(".json"):
            continue
        path = os.path.join(registry_dir(), name)
        entry = _load_json_retry(path)
        if not isinstance(entry, dict) or "port" not in entry:
            continue
        gen = generation_of(entry, group)
        if gen is None or gen >= active_gen:
            continue
        if entry_is_dead(entry) and _reap_if_unchanged(path, entry) is None:
            reaped += 1
    return reaped


def _controller_path(group: str) -> str:
    return _group_path(group, "ctl.json")


def acquire_controller_lease(group: str, ttl_s: Optional[float] = None
                             ) -> Optional[str]:
    """Try to become the group's single scaling controller -> lease token,
    or None while another live controller holds the lease.

    The lease is a registry-style heartbeat contract: the holder refreshes
    within ``ttl_s`` (default: the replica TTL) or is presumed dead, and a
    dead holder's lease (pid gone, or heartbeat lapsed) is STOLEN —
    serialized through a link-based steal lock so two stealers cannot
    both win one corpse.

    Acquisition is link-based so the lease file appears ATOMICALLY with
    its full contents: an O_EXCL create would expose an empty file for
    the duration of the winner's write, and a concurrent acquirer reading
    that window judged the record a torn-write corpse and claimed it too
    — two winners for one fresh lease."""
    import socket
    import uuid

    os.makedirs(registry_dir(), exist_ok=True)
    path = _controller_path(group)
    token = uuid.uuid4().hex
    entry = {
        "kind": "controller", "group": group, "token": token,
        "pid": os.getpid(), "pid_host": socket.gethostname(),
        "heartbeat": time.time(),
        "ttl_s": replica_ttl_s() if ttl_s is None else float(ttl_s),
    }
    data = json.dumps(entry)
    tmp = f"{path}.{os.getpid()}.{token[:8]}.tmp"
    try:
        with open(tmp, "w") as f:
            f.write(data)
        try:
            os.link(tmp, path)
            return token
        except FileExistsError:
            pass
        current = _read_record(path, "controller")
        if current is not None and not entry_is_dead(current):
            return None
        # unreadable/foreign record (atomic creation means the normal
        # path can no longer produce one) OR a dead holder's lease:
        # exactly ONE claimant recovers it.  Renaming ``path`` aside
        # cannot be the mutual exclusion — the first winner re-creates
        # ``path``, which a second stealer holding a stale read of the
        # corpse would then rename aside again.  Instead a link-based
        # steal LOCK serializes recovery: one claimant creates it,
        # re-judges the record under the lock, and replaces atomically.
        # A lock orphaned by a claimant dying mid-steal goes stale
        # after the lease TTL and is cleared for the next attempt.
        lock = f"{path}.steal"
        try:
            os.link(tmp, lock)
        except FileExistsError:
            try:
                if time.time() - os.stat(lock).st_mtime > entry["ttl_s"]:
                    os.unlink(lock)
            except OSError:
                pass
            return None
        try:
            check = _read_record(path, "controller")
            if check is not None and not entry_is_dead(check):
                return None
            os.replace(tmp, path)
            return token
        finally:
            try:
                os.unlink(lock)
            except OSError:
                pass
    except OSError:
        return None
    finally:
        try:
            os.unlink(tmp)
        except OSError:
            pass


def refresh_controller_lease(group: str, token: str) -> bool:
    """Heartbeat the lease -> True while this token still holds it."""
    path = _controller_path(group)
    current = _read_record(path, "controller")
    if current is None or current.get("token") != token:
        return False
    current["heartbeat"] = time.time()
    tmp = f"{path}.{os.getpid()}.hb.tmp"
    try:
        with open(tmp, "w") as f:
            json.dump(current, f)
        os.replace(tmp, path)
    except OSError:
        return False
    return True


def release_controller_lease(group: str, token: str) -> None:
    """Drop the lease iff this token still holds it (best-effort)."""
    path = _controller_path(group)
    current = _read_record(path, "controller")
    if current is not None and current.get("token") == token:
        try:
            os.unlink(path)
        except OSError:
            pass


def merge_endpoint(entry: Optional[dict], explicit_host: Optional[str],
                   default_host: str = "localhost",
                   default_port: int = 6123) -> Tuple[str, int]:
    """Merge a registry entry with a caller-supplied host into (host, port).

    The single place that encodes the precedence both client surfaces
    (flag-based CLIs and positional REPLs) share: an explicit host always
    wins; a registered wildcard bind (0.0.0.0) is reached via the explicit
    host or loopback default; no entry means the reference defaults."""
    host = explicit_host or default_host
    if entry is None:
        return host, default_port
    reg_host = entry.get("host") or ""
    if explicit_host is None and reg_host and reg_host != "0.0.0.0":
        host = reg_host
    return host, int(entry["port"])


def resolve_endpoint(params: Params, default_port: int = 6123
                     ) -> Tuple[str, int]:
    """(host, port) for a client CLI, with JobManager-style jobId routing.

    Precedence mirrors the reference's surface: an EXPLICIT
    ``--jobManagerPort`` wins (direct wiring always works); otherwise
    ``--jobId`` resolves through the registry like ``getKvState(jobId,...)``
    through the JobManager; otherwise the reference's defaults
    (localhost:6123)."""
    explicit_host = (
        params.get("jobManagerHost") if params.has("jobManagerHost") else None
    )
    if params.has("jobManagerPort"):
        return (explicit_host or "localhost",
                params.get_int("jobManagerPort", default_port))
    job_id = params.get("jobId")
    entry = resolve(job_id) if job_id else None
    return merge_endpoint(entry, explicit_host, default_port=default_port)
