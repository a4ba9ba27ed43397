"""Continuous-training autopilot (serve/autopilot.py): journal windowing
with LWW dedupe and crash-safe offsets, lease-gated single-controller
discipline, drift-triggered rollback with the re-arm latch, and the full
unattended flywheel — ratings stream in, warm-started retrain, candidate
beats incumbent on held-out MSE, automatic rollout with zero failed
queries, injected regression drives automatic rollback restoring the
previous answers.

Tier-1 sizing: JAX_PLATFORMS=cpu via conftest, tiny factor models, and no
sleeps longer than the (sub-second) autopilot cadence under test.
"""

import json
import os
import threading
import time

import numpy as np
import pytest

from flink_ms_tpu.core import formats as F
from flink_ms_tpu.obs.metrics import get_registry
from flink_ms_tpu.serve import registry
from flink_ms_tpu.serve.autopilot import (
    AutopilotController,
    PHASES,
    autopilot_scope,
)
from flink_ms_tpu.serve.consumer import ALS_STATE
from flink_ms_tpu.serve.elastic import ElasticClient
from flink_ms_tpu.serve.journal import Journal
from flink_ms_tpu.serve.rollout import RolloutController
from flink_ms_tpu.serve.update_plane import UpdatePlaneClient

# registry isolation comes from conftest.py's autouse fixture


class _StubRollout:
    """Just enough controller surface for windowing/drift unit tests —
    no workers are ever spawned."""

    def __init__(self, group="stub", topo=None):
        self.group = group
        self.topo = topo
        self.rollbacks = 0

    def current(self):
        return self.topo

    def rollback(self):
        self.rollbacks += 1
        return {"gen": 99, "model": {"model_id": "restored"}}


def _pilot(tmp_path, **kw):
    kw.setdefault("rollout", _StubRollout())
    kw.setdefault("partitions", 2)
    kw.setdefault("min_window", 4)
    kw.setdefault("interval_s", 0.05)
    kw.setdefault("num_factors", 3)
    kw.setdefault("iterations", 1)
    return AutopilotController(
        "stub", str(tmp_path / "bus"), str(tmp_path / "work"), **kw)


def test_autopilot_scope_is_not_the_group_lease():
    # rollout() takes the GROUP lease internally: the autopilot must
    # lease a different scope or deadlock against its own rollout
    assert autopilot_scope("g") != "g"
    assert autopilot_scope("acme::g") != "acme::g"


def test_windowing_lww_offsets_and_restart(tmp_path):
    up = UpdatePlaneClient(str(tmp_path / "bus"), "models", partitions=2)
    up.submit_many([(1, 1, 1.0), (1, 2, 2.0), (2, 1, 3.0)], flush=True)
    up.submit(1, 1, 5.0)  # LWW overwrite of (1, 1)
    up.sync()
    p = _pilot(tmp_path, min_window=100)
    assert p._tail_ratings() == 4
    assert p._acc[(1, 1)] == 5.0 and len(p._acc) == 3
    # offsets persisted only on seal/save; idempotent within a process
    assert p._tail_ratings() == 0
    version, users, items, ratings = p._seal_window()
    assert version == 1 and len(ratings) == 3
    assert os.path.exists(p._window_path(1))
    # a fresh controller (crash restart) restores the SAME window and
    # resumes the offsets — re-reads nothing, loses nothing
    p2 = _pilot(tmp_path, min_window=100)
    assert p2._acc == p._acc
    assert p2.state["offsets"] == p.state["offsets"]
    assert p2._tail_ratings() == 0
    up.submit(3, 1, 4.0)
    up.sync()
    assert p2._tail_ratings() == 1
    v2, _, _, ratings2 = p2._seal_window()
    assert v2 == 2 and len(ratings2) == 4
    # the superseded window file is GC'd (the LWW set subsumes it)
    assert not os.path.exists(p2._window_path(1))


def test_tick_is_standby_without_the_lease(tmp_path):
    p1 = _pilot(tmp_path)
    p2 = _pilot(tmp_path)
    assert p1._ensure_lease()
    out = p2.tick()
    assert out["state"] == "standby"
    assert p2.state["phase"] == "idle"  # standby never mutates the record
    p1.release_lease()
    # released lease -> the standby peer takes over on its next tick
    assert p2._ensure_lease()
    p2.release_lease()


def test_drift_alert_and_gauge_sources_with_rearm_latch(tmp_path):
    stub = _StubRollout()
    live = [0.1]
    p = _pilot(tmp_path, rollout=stub, drift_source="both",
               drift_factor=1.5, live_mse=lambda: live[0])
    p.state["drift_armed"] = True
    p.state["rollout_probe_mse"] = 0.2
    # healthy live score, no alert -> nothing fires
    assert p._drift_fired() is None
    # gauge source: live MSE regresses past factor x probe
    live[0] = 0.5
    assert "live_mse" in p._drift_fired()
    # alert source wins even with a healthy gauge
    live[0] = 0.1
    registry.publish_alerts("fleet", {
        "firing": 1, "max_severity": "warn", "max_severity_level": 1,
        "alerts": [{"rule": "model_drift", "severity": "warn"}]},
        ttl_s=30.0)
    assert p._drift_fired() == "alert:model_drift"
    out = p.tick()
    assert stub.rollbacks == 1 and "rollback" in out
    assert p.state["incumbent_model_id"] == "restored"
    # the latch: disarmed after rollback, the still-firing alert does not
    # ping-pong a second rollback
    assert p.state["drift_armed"] is False
    assert p._drift_fired() is None
    p.tick()
    assert stub.rollbacks == 1
    registry.drop_alerts("fleet")
    p.release_lease()


def test_drift_source_off_and_validation(tmp_path):
    p = _pilot(tmp_path, drift_source="off", live_mse=lambda: 1e9)
    p.state["drift_armed"] = True
    p.state["rollout_probe_mse"] = 1e-9
    assert p._drift_fired() is None
    with pytest.raises(ValueError, match="drift_source"):
        _pilot(tmp_path, drift_source="bogus")


def test_state_record_is_atomic_and_versioned(tmp_path):
    p = _pilot(tmp_path)
    p._set_phase("training")
    with open(p.state_path) as f:
        rec = json.load(f)
    assert rec["kind"] == "autopilot" and rec["phase"] == "training"
    assert rec["phase"] in PHASES
    # a corrupt record never wedges a restart — it resets to genesis
    with open(p.state_path, "w") as f:
        f.write("{torn")
    p2 = _pilot(tmp_path)
    assert p2.state["window_version"] == 0
    assert p2.state["phase"] == "idle"


def test_unattended_flywheel_rollout_then_drift_rollback(
        tmp_path, monkeypatch):
    """The acceptance rehearsal, sized for CI: bootstrap a weak v0, stream
    the full ratings set through the update plane, one tick retrains
    warm-started / wins on held-out MSE / rolls out automatically with
    zero failed queries; an injected live-MSE regression then rolls back
    to v0 — the previous answers return, no human in the loop."""
    monkeypatch.setenv("TPUMS_HEARTBEAT_S", "0.2")
    monkeypatch.setenv("TPUMS_REPLICA_TTL_S", "30")
    from flink_ms_tpu.ops.als import ALSConfig, als_fit
    from flink_ms_tpu.parallel.mesh import make_mesh

    rng = np.random.default_rng(0)
    n_u, n_i, k = 20, 15, 3
    U, V = rng.normal(size=(n_u, k)), rng.normal(size=(n_i, k))
    uu, ii = np.meshgrid(np.arange(n_u), np.arange(n_i), indexing="ij")
    uu, ii = uu.ravel(), ii.ravel()
    rr = np.sum(U[uu] * V[ii], axis=1)
    # v0 incumbent: undertrained on 30% of the ratings
    sel = rng.random(len(uu)) < 0.3
    m0 = als_fit(uu[sel], ii[sel], rr[sel],
                 ALSConfig(num_factors=k, iterations=2, lambda_=0.1),
                 make_mesh(1))
    j0 = Journal(str(tmp_path / "v0"), "models")
    j0.append([F.format_als_row(int(u), "U", f)
               for u, f in zip(m0.user_ids, m0.user_factors)]
              + [F.format_als_row(int(i), "I", f)
                 for i, f in zip(m0.item_ids, m0.item_factors)])

    ctl = RolloutController("auto", port_dir=str(tmp_path / "ports"),
                            journal_dir=j0.dir, topic="models",
                            ready_timeout_s=90)
    errors = []
    served = [0]
    stop = threading.Event()
    try:
        ctl.rollout(j0.dir, "models", model_id="v0", shards=1)

        keys = [f"{u}-U" for u in range(n_u)]
        probe = ElasticClient("auto", timeout_s=10)
        v0_answers = probe.query_states(ALS_STATE, keys)
        assert all(v is not None for v in v0_answers)

        def stream():
            from flink_ms_tpu.serve.client import RetryPolicy
            c = ElasticClient("auto",
                              retry=RetryPolicy(attempts=6, backoff_s=0.02,
                                                max_backoff_s=0.5),
                              timeout_s=10)
            with c:
                while not stop.is_set():
                    for key in keys:
                        try:
                            if c.query_state(ALS_STATE, key) is None:
                                errors.append((key, "missing"))
                        except Exception as e:
                            errors.append((key, repr(e)))
                        served[0] += 1

        t = threading.Thread(target=stream, daemon=True)
        t.start()

        up = UpdatePlaneClient(str(tmp_path / "bus"), "models",
                               partitions=2)
        up.submit_many([(int(u), int(i), float(r))
                        for u, i, r in zip(uu, ii, rr)], flush=True)

        live = [None]
        pilot = AutopilotController(
            "auto", str(tmp_path / "bus"), str(tmp_path / "work"),
            rollout=ctl, partitions=2, min_window=50, interval_s=0.05,
            iterations=3, num_factors=k, drift_source="gauge",
            drift_factor=1.5, live_mse=lambda: live[0])
        out = pilot.tick()
        assert out["win"] is True and out["warm_start"] is True, out
        assert out["candidate_mse"] < out["incumbent_mse"]
        assert "rollout_gen" in out, out
        topo = registry.resolve_topology("auto")
        assert topo["model"]["model_id"].startswith("auto-v")
        # retrain + rollout surfaced through the metrics registry
        snap_counters = {
            c["name"] for c in get_registry().snapshot()["counters"]}
        assert "tpums_autopilot_retrains_total" in snap_counters
        assert "tpums_autopilot_rollouts_total" in snap_counters

        mark = served[0]
        deadline = time.time() + 10
        while served[0] < mark + 40 and time.time() < deadline:
            time.sleep(0.02)
        v1_answers = probe.query_states(ALS_STATE, keys)
        assert v1_answers != v0_answers  # a genuinely different model

        # injected live regression (the canary's gauge, shortcut through
        # the callable hook) -> automatic rollback, v0's answers return
        live[0] = 100.0 * out["candidate_mse"] + 1.0
        out2 = pilot.tick()
        assert "rollback" in out2, out2
        assert pilot.state["drift_armed"] is False
        mark = served[0]
        deadline = time.time() + 10
        while served[0] < mark + 40 and time.time() < deadline:
            time.sleep(0.02)
        assert probe.query_states(ALS_STATE, keys) == v0_answers
        probe.close()

        # crash restart: a fresh controller resumes the persisted record
        pilot.release_lease()
        pilot2 = AutopilotController(
            "auto", str(tmp_path / "bus"), str(tmp_path / "work"),
            rollout=ctl, partitions=2, min_window=50, interval_s=0.05,
            iterations=3, num_factors=k, drift_source="gauge")
        assert pilot2.state["retrains"] == 1
        assert pilot2.state["rollbacks"] == 1
        out3 = pilot2.tick()
        assert out3.get("new_ratings") == 0  # offsets survived the crash
        pilot2.release_lease()

        stop.set()
        t.join(timeout=30)
        assert errors == [], f"client-visible errors: {errors[:5]}"
    finally:
        stop.set()
        ctl.stop(drop_topology=True)
