"""High-availability serving plane (serve/ha.py): heartbeat-TTL liveness
and registry GC, the HEALTH verb's readiness gating (a rejoining replica
never serves a half-replayed table), client failover across a replica set
with zero client-visible errors on a mid-stream kill, and supervised
respawn with journal catch-up."""

import json
import pathlib
import signal
import threading
import time

import numpy as np
import pytest

from flink_ms_tpu.core import formats as F
from flink_ms_tpu.serve import registry
from flink_ms_tpu.serve.client import QueryClient, RetryPolicy
from flink_ms_tpu.serve.consumer import (
    ALS_STATE,
    ServingJob,
    make_backend,
    parse_als_record,
)
from flink_ms_tpu.serve.ha import (
    HAShardedClient,
    ReplicaSupervisor,
    resolve_shard_endpoints,
    shard_group,
)
from flink_ms_tpu.serve.journal import Journal

# registry isolation comes from conftest.py's autouse fixture (every test
# gets a private TPUMS_REGISTRY_DIR)


# ---------------------------------------------------------------------------
# retry policy (satellite: shared by _roundtrip and the failover path)
# ---------------------------------------------------------------------------

def test_retry_policy_delays_bounded_and_jittered():
    p = RetryPolicy(attempts=6, backoff_s=0.1, max_backoff_s=0.5, jitter=0.25)
    for i in range(20):
        d = p.delay_s(i)
        base = min(0.1 * 2 ** i, 0.5)
        assert base <= d <= base * 1.25 + 1e-9
    # zero backoff never sleeps (the pre-HA immediate-reconnect default)
    assert RetryPolicy().delay_s(0) == 0.0
    assert RetryPolicy().attempts == 2  # one reconnect, like before
    with pytest.raises(ValueError):
        RetryPolicy(attempts=0)
    with pytest.raises(ValueError):
        RetryPolicy(jitter=2.0)


def test_roundtrip_retries_through_server_restart(tmp_path):
    """A client with a retry budget survives its server restarting on the
    same port (the fixed-delay-restart story _roundtrip always absorbed,
    now policy-driven); attempts=1 turns retries off."""
    from flink_ms_tpu.serve.server import LookupServer
    from flink_ms_tpu.serve.table import ModelTable

    table = ModelTable(2)
    table.put("k", "v")
    srv = LookupServer({ALS_STATE: table}, host="127.0.0.1", port=0).start()
    port = srv.port
    c = QueryClient("127.0.0.1", port, timeout_s=5,
                    retry=RetryPolicy(attempts=5, backoff_s=0.02))
    c_noretry = QueryClient("127.0.0.1", port, timeout_s=5,
                            retry=RetryPolicy(attempts=1))
    try:
        assert c.query_state(ALS_STATE, "k") == "v"
        assert c_noretry.query_state(ALS_STATE, "k") == "v"
        srv.stop()
        srv = LookupServer(
            {ALS_STATE: table}, host="127.0.0.1", port=port).start()
        # dead socket -> reconnect+retry inside the policy budget
        assert c.query_state(ALS_STATE, "k") == "v"
        with pytest.raises((ConnectionError, OSError)):
            c_noretry.query_state(ALS_STATE, "k")
    finally:
        c.close()
        c_noretry.close()
        srv.stop()


# ---------------------------------------------------------------------------
# registry liveness: heartbeat TTL + GC (satellite)
# ---------------------------------------------------------------------------

def _backdate(job_id, seconds):
    path = pathlib.Path(registry._entry_path(job_id))
    entry = json.loads(path.read_text())
    entry["heartbeat"] -= seconds
    path.write_text(json.dumps(entry))
    return path


def test_heartbeat_ttl_expiry_reaps_entry():
    registry.register("hb-job", "127.0.0.1", 7100, ALS_STATE, ttl_s=5.0)
    assert registry.resolve("hb-job")["port"] == 7100
    path = _backdate("hb-job", 60.0)
    assert registry.resolve("hb-job") is None
    assert not path.exists(), "stale entry not GC'd on resolve()"


def test_entry_without_ttl_is_never_ttl_checked():
    # pre-HA writers (manual registrations) carry no heartbeat contract:
    # they must not expire, no matter how old
    registry.register("manual-job", "127.0.0.1", 7101, ALS_STATE)
    entry = registry.resolve("manual-job")
    assert entry is not None and "ttl_s" not in entry


def test_list_jobs_gcs_stale_and_dead_entries():
    import subprocess
    import sys

    registry.register("live-a", "127.0.0.1", 7102, ALS_STATE)
    registry.register("stale-b", "127.0.0.1", 7103, ALS_STATE, ttl_s=5.0)
    _backdate("stale-b", 60.0)
    child = subprocess.Popen([sys.executable, "-c", "pass"])
    child.wait()
    registry.register("dead-c", "127.0.0.1", 7104, ALS_STATE)
    dead_path = pathlib.Path(registry._entry_path("dead-c"))
    entry = json.loads(dead_path.read_text())
    entry["pid"] = child.pid
    dead_path.write_text(json.dumps(entry))

    jobs = registry.list_jobs()
    assert [e["job_id"] for e in jobs] == ["live-a"]
    files = list(pathlib.Path(registry.registry_dir()).iterdir())
    assert len(files) == 1, "stale/dead entries not GC'd on list_jobs()"


def test_resolve_replicas_sorted_and_ready_fallback():
    group = "g/shard-0"
    registry.register("r2", "127.0.0.1", 7202, ALS_STATE,
                      replica_of=group, replica=2, ready=False)
    registry.register("r0", "127.0.0.1", 7200, ALS_STATE,
                      replica_of=group, replica=0, ready=False)
    registry.register("r1", "127.0.0.1", 7201, ALS_STATE,
                      replica_of=group, replica=1, ready=True)
    registry.register("other", "127.0.0.1", 7300, ALS_STATE,
                      replica_of="g/shard-1", replica=0, ready=True)
    members = registry.resolve_replicas(group)
    assert [e["replica"] for e in members] == [0, 1, 2]
    # readiness-gated resolution: only the ready replica gets traffic
    assert resolve_shard_endpoints("g", 0) == [("127.0.0.1", 7201)]
    # ...but with NO ready replica the live set is the last resort
    registry.register("r1", "127.0.0.1", 7201, ALS_STATE,
                      replica_of=group, replica=1, ready=False)
    assert len(resolve_shard_endpoints("g", 0)) == 3


# ---------------------------------------------------------------------------
# HEALTH verb + readiness gating (satellites + tentpole contract)
# ---------------------------------------------------------------------------

def test_health_verb_readiness_gates_replay(tmp_path):
    """The FIRST ready HEALTH report must already see the whole journal
    replayed: ready == half-replayed is exactly the bug the gate exists
    to prevent."""
    journal = Journal(str(tmp_path / "bus"), "t")
    n = 500
    journal.append([F.format_als_row(i, "U", [0.5, float(i)])
                    for i in range(n)])
    job = ServingJob(
        journal, ALS_STATE, parse_als_record, make_backend("memory", None),
        host="127.0.0.1", port=0, poll_interval_s=0.01, job_id="health-e2e",
        replica_of="hg/shard-0", replica_index=0,
    ).start()
    try:
        with QueryClient("127.0.0.1", job.port, timeout_s=10) as c:
            deadline = time.time() + 30
            while time.time() < deadline:
                h = c.health(ALS_STATE)
                if h["ready"]:
                    break
                assert h["status"] == "replaying"
                time.sleep(0.005)
            assert h["ready"] and h["status"] == "ready"
            # the readiness gate: ready implies the FULL backlog is applied
            assert h["keys"] == n
            assert h["backlog_bytes"] == 0
            assert h["state"] == ALS_STATE
            assert h["replica_of"] == "hg/shard-0" and h["replica"] == 0
        # the registry entry mirrors readiness and carries the heartbeat
        # contract (supervisors watch this without a HEALTH round trip).
        # HEALTH answers from the server thread, the registry write happens
        # on the consume/heartbeat threads — poll past that gap
        deadline = time.time() + 30
        entry = registry.resolve("health-e2e")
        while not (entry and entry.get("ready")) and time.time() < deadline:
            time.sleep(0.02)
            entry = registry.resolve("health-e2e")
        assert entry["ready"] is True
        assert entry["replica_of"] == "hg/shard-0"
        assert "heartbeat" in entry and entry["ttl_s"] > 0
    finally:
        job.stop()
    assert registry.resolve("health-e2e") is None


def test_bare_lookup_server_health_is_ready():
    from flink_ms_tpu.serve.server import LookupServer
    from flink_ms_tpu.serve.table import ModelTable

    table = ModelTable(2)
    table.put("a", "1")
    srv = LookupServer({ALS_STATE: table}, host="127.0.0.1", port=0).start()
    try:
        with QueryClient("127.0.0.1", srv.port) as c:
            h = c.health(ALS_STATE)
            assert h["ready"] is True and h["keys"] == 1
            with pytest.raises(RuntimeError):
                c.health("NO_SUCH_STATE")
    finally:
        srv.stop()


def test_heartbeat_refreshes_registry(monkeypatch):
    monkeypatch.setenv("TPUMS_HEARTBEAT_S", "0.05")
    monkeypatch.setenv("TPUMS_REPLICA_TTL_S", "10")
    journal_dir = registry.registry_dir()  # any tmp-ish dir works
    job = ServingJob(
        Journal(journal_dir + "-bus", "t"), ALS_STATE, parse_als_record,
        make_backend("memory", None), host="127.0.0.1", port=0,
        poll_interval_s=0.01, job_id="hb-refresh",
    ).start()
    try:
        first = registry.resolve("hb-refresh")["heartbeat"]
        deadline = time.time() + 10
        while time.time() < deadline:
            entry = registry.resolve("hb-refresh")
            if entry and entry["heartbeat"] > first:
                break
            time.sleep(0.02)
        assert entry["heartbeat"] > first, "heartbeat never refreshed"
    finally:
        job.stop()


# ---------------------------------------------------------------------------
# client failover (in-process replica set: fast + deterministic)
# ---------------------------------------------------------------------------

def _seed_journal(tmp_path, n_users=12, n_items=16, k=3, seed=0):
    journal = Journal(str(tmp_path / "bus"), "models")
    rng = np.random.default_rng(seed)
    uf = rng.normal(size=(n_users, k))
    itf = rng.normal(size=(n_items, k))
    rows = [F.format_als_row(u, "U", uf[u]) for u in range(n_users)]
    rows += [F.format_als_row(i, "I", itf[i]) for i in range(n_items)]
    journal.append(rows)
    return journal, uf, itf


def _inprocess_replica(journal, group, replica):
    return ServingJob(
        journal, ALS_STATE, parse_als_record, make_backend("memory", None),
        host="127.0.0.1", port=0, poll_interval_s=0.01,
        job_id=f"ha:s0r{replica}", replica_of=shard_group(group, 0),
        replica_index=replica, topk_index=False,
    ).start()


def test_failover_absorbs_dead_replica_with_zero_errors(tmp_path):
    """Kill one of two in-process replicas mid-query-stream (server socket
    torn down WITHOUT unregistering — the crash shape): every query in the
    stream must still succeed, and the failover must land on the sibling."""
    journal, uf, _ = _seed_journal(tmp_path)
    jobs = [_inprocess_replica(journal, "ha", r) for r in range(2)]
    try:
        for job in jobs:
            assert job.wait_ready(30)
        client = HAShardedClient(
            1, job_group="ha",
            retry=RetryPolicy(attempts=5, backoff_s=0.01, max_backoff_s=0.2),
            timeout_s=5,
        )
        with client:
            keys = [f"{u}-U" for u in range(len(uf))]
            for key in keys:  # warm: stick to one replica
                assert client.query_state(ALS_MODEL := ALS_STATE, key)
            # crash the data plane of the replica the client sticks to
            # (replica 0, unless the warm-up already failed over under
            # load): its registry entry stays (pid is alive), so the client
            # must discover deadness the hard way — refused connects — and
            # fail over anyway
            stuck_to = client._shards[0].prefer
            next(j for j in jobs if j.port == stuck_to[1]).server.stop()
            errors = []
            for _ in range(3):
                for key in keys:
                    try:
                        v = client.query_state(ALS_MODEL, key)
                        assert v is not None
                    except Exception as e:  # pragma: no cover
                        errors.append((key, e))
            assert errors == [], f"client-visible errors: {errors[:3]}"
            assert client.failovers > 0
            # batched + fan-out paths ride the same failover machinery
            got = client.query_states(ALS_MODEL, keys)
            assert all(v is not None for v in got)
            assert client.total_count(ALS_MODEL) == len(uf) + 16
    finally:
        for job in jobs:
            job.stop()


def test_failover_exhausts_budget_when_all_replicas_dead(tmp_path):
    journal, _, _ = _seed_journal(tmp_path)
    job = _inprocess_replica(journal, "solo", 0)
    assert job.wait_ready(30)
    client = HAShardedClient(
        1, job_group="solo",
        retry=RetryPolicy(attempts=3, backoff_s=0.01, max_backoff_s=0.05),
        timeout_s=2,
    )
    with client:
        assert client.query_state(ALS_STATE, "0-U") is not None
        job.stop()  # clean stop unregisters: the set resolves empty
        t0 = time.monotonic()
        with pytest.raises((ConnectionError, OSError)):
            client.query_state(ALS_STATE, "0-U")
        # bounded: the retry budget, not an unbounded spin
        assert time.monotonic() - t0 < 5.0


# ---------------------------------------------------------------------------
# supervised recovery e2e (real processes, SIGKILL, respawn, readiness)
# ---------------------------------------------------------------------------

def test_supervisor_kill_respawn_readiness_e2e(tmp_path, monkeypatch):
    """The acceptance scenario: R=2, SIGKILL one replica during a sustained
    query stream -> zero client-visible errors; the supervisor detects the
    death, respawns the replica, the rejoin replays the journal and passes
    the HEALTH readiness check; the registry again shows 2 ready
    replicas."""
    monkeypatch.setenv("TPUMS_HEARTBEAT_S", "0.2")
    # generous TTL: SIGKILL detection here goes through proc.poll() and the
    # registry's pid-liveness check, not heartbeat expiry (that path has its
    # own test above) — a tight TTL lets a loaded CI machine starve BOTH
    # replicas' heartbeats past expiry and flake the zero-errors assert
    monkeypatch.setenv("TPUMS_REPLICA_TTL_S", "30")
    journal, uf, _ = _seed_journal(tmp_path, seed=3)
    sup = ReplicaSupervisor(
        num_workers=1, replication=2,
        journal_dir=str(tmp_path / "bus"), topic="models",
        port_dir=str(tmp_path / "ports"),
        state_backend="memory",
        check_interval_s=0.2, respawn_delay_s=0.1,
    )
    with sup.start():
        assert sup.wait_all_ready(90), "replica set never became ready"
        keys = [f"{u}-U" for u in range(len(uf))]
        errors = []
        stop_stream = threading.Event()
        served = [0]

        def stream():
            client = sup.client(retry=RetryPolicy(
                attempts=6, backoff_s=0.02, max_backoff_s=0.5), timeout_s=10)
            with client:
                while not stop_stream.is_set():
                    for key in keys:
                        try:
                            if client.query_state(ALS_STATE, key) is None:
                                errors.append((key, "missing"))
                        except Exception as e:
                            errors.append((key, repr(e)))
                        served[0] += 1

        t = threading.Thread(target=stream, daemon=True)
        t.start()
        deadline = time.time() + 10
        while served[0] < 50 and time.time() < deadline:
            time.sleep(0.02)
        victim = sup.procs[(0, 0)]
        victim.send_signal(signal.SIGKILL)
        victim.wait(timeout=10)
        # sustain the stream across the kill + detection window
        mark = served[0]
        deadline = time.time() + 10
        while served[0] < mark + 100 and time.time() < deadline:
            time.sleep(0.02)
        stop_stream.set()
        t.join(timeout=30)
        assert errors == [], f"client-visible errors: {errors[:5]}"

        # supervised recovery: a NEW process for (0, 0), journal replayed,
        # readiness passed, registry whole again
        assert sup.wait_all_ready(90), "killed replica never rejoined ready"
        # the rejoining replica registers ready on its own; the monitor
        # thread may still be inside its respawn bookkeeping (procs/ports/
        # respawns) when wait_all_ready returns — settle on it
        deadline = time.time() + 30
        while (sup.respawns < 1 or sup.procs[(0, 0)].pid == victim.pid) \
                and time.time() < deadline:
            time.sleep(0.05)
        assert sup.respawns >= 1
        respawned = sup.procs[(0, 0)]
        assert respawned.pid != victim.pid
        new_port = sup.ports[(0, 0)]
        with QueryClient("127.0.0.1", new_port, timeout_s=10) as direct:
            h = direct.health(ALS_STATE)
            assert h["ready"] is True and h["status"] == "ready"
            assert h["keys"] > 0  # the rejoined table really replayed
        actions = [e["action"] for e in sup.events]
        assert "respawn" in actions
