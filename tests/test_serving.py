"""Serving layer tests: journal at-least-once replay, sharded table
snapshot/restore, serving job checkpoint + fixed-delay restart, and the full
producer -> journal -> consumer -> lookup-server -> client loop over a real
socket (the reference's only quality gates are operational — SURVEY.md §4 —
so these reproduce them as automated tests)."""

import os
import threading
import time

import numpy as np
import pytest

from flink_ms_tpu.core import formats as F
from flink_ms_tpu.core.params import Params
from flink_ms_tpu.eval import mse as mse_mod
from flink_ms_tpu.serve import producer
from flink_ms_tpu.serve.client import QueryClient
from flink_ms_tpu.serve.consumer import (
    ALS_STATE,
    SVM_STATE,
    FsStateBackend,
    MemoryStateBackend,
    ServingJob,
    parse_als_record,
    parse_svm_record,
)
from flink_ms_tpu.serve.journal import Journal
from flink_ms_tpu.serve.table import ModelTable


def _wait_until(pred, timeout=10.0, interval=0.02):
    t0 = time.time()
    while time.time() - t0 < timeout:
        if pred():
            return True
        time.sleep(interval)
    return False


# -- journal ----------------------------------------------------------------

def test_journal_append_and_poll(tmp_path):
    j = Journal(str(tmp_path), "models")
    end = j.append(["a,U,1.0", "b,I,2.0"])
    lines, off = j.read_from(0)
    assert lines == ["a,U,1.0", "b,I,2.0"]
    assert off == end == j.end_offset()
    # nothing new
    lines2, off2 = j.read_from(off)
    assert lines2 == [] and off2 == off


def test_journal_torn_tail_not_consumed(tmp_path):
    j = Journal(str(tmp_path), "t")
    j.append(["complete"])
    with open(j.path, "a") as f:
        f.write("torn-no-newline")
    lines, off = j.read_from(0)
    assert lines == ["complete"]
    # finish the line -> now visible
    with open(j.path, "a") as f:
        f.write("\n")
    lines2, off2 = j.read_from(off)
    assert lines2 == ["torn-no-newline"]


def test_journal_rejects_embedded_newline(tmp_path):
    j = Journal(str(tmp_path), "t")
    with pytest.raises(ValueError):
        j.append(["bad\nrow"])


# -- table ------------------------------------------------------------------

def test_table_put_get_shard_stability(tmp_path):
    t = ModelTable(n_shards=4)
    for i in range(100):
        t.put(f"{i}-U", f"payload-{i}")
    assert len(t) == 100
    assert t.get("7-U") == "payload-7"
    assert t.get("missing") is None
    # last-writer-wins
    t.put("7-U", "updated")
    assert t.get("7-U") == "updated"


def test_table_snapshot_restore_roundtrip(tmp_path):
    t = ModelTable(n_shards=3)
    for i in range(50):
        t.put(str(i), f"v{i}")
    t.snapshot(str(tmp_path), offset=12345)
    t2 = ModelTable(n_shards=3)
    off = t2.restore(str(tmp_path))
    assert off == 12345
    assert len(t2) == 50
    assert t2.get("49") == "v49"


def test_table_snapshot_prunes_old(tmp_path):
    t = ModelTable(n_shards=1)
    t.put("k", "v")
    for i in range(4):
        t.snapshot(str(tmp_path), offset=i)
        time.sleep(0.002)
    chks = [d for d in os.listdir(str(tmp_path)) if d.startswith("chk-")]
    assert len(chks) == 2  # keeps latest 2
    assert t.restore(str(tmp_path)) == 3


def test_table_restore_shard_mismatch(tmp_path):
    t = ModelTable(n_shards=2)
    t.put("k", "v")
    t.snapshot(str(tmp_path), offset=0)
    with pytest.raises(ValueError):
        ModelTable(n_shards=5).restore(str(tmp_path))


# -- record parsing ---------------------------------------------------------

def test_parse_records():
    assert parse_als_record("42,U,1.0;2.0") == ("42-U", "1.0;2.0")
    assert parse_als_record("MEAN,I,0.5") == ("MEAN-I", "0.5")
    assert parse_svm_record("17,0.25") == ("17", "0.25")
    assert parse_svm_record("3,100:1.5;101:0") == ("3", "100:1.5;101:0")
    with pytest.raises(ValueError):
        parse_als_record("no-commas")


# -- end-to-end serving loop ------------------------------------------------

@pytest.fixture
def als_job(tmp_path):
    journal = Journal(str(tmp_path / "journal"), "als_models")
    job = ServingJob(
        journal,
        ALS_STATE,
        parse_als_record,
        MemoryStateBackend(),
        checkpoint_interval_ms=100,
        poll_interval_s=0.01,
        host="127.0.0.1",
        port=0,  # ephemeral
    )
    job.start()
    yield job, journal, tmp_path
    job.stop()


def test_produce_serve_query_loop(als_job):
    job, journal, tmp_path = als_job
    model_file = str(tmp_path / "model")
    F.write_lines(
        model_file,
        [
            F.format_als_row(1, "U", [0.5, 1.5]),
            F.format_als_row(2, "I", [2.0, -1.0]),
            F.format_mean_row("U", [0.1, 0.2]),
        ],
    )
    n = producer.run(
        Params.from_args(
            ["--input", model_file, "--journalDir", str(tmp_path / "journal"),
             "--topic", "als_models"]
        )
    )
    assert n == 3
    assert _wait_until(lambda: len(job.table) == 3)

    with QueryClient("127.0.0.1", job.port) as c:
        assert c.query_state(ALS_STATE, "1-U") == "0.5;1.5"
        assert c.query_state(ALS_STATE, "2-I") == "2.0;-1.0"
        assert c.query_state(ALS_STATE, "MEAN-U") == "0.1;0.2"
        assert c.query_state(ALS_STATE, "999-U") is None  # Optional.empty
        with pytest.raises(RuntimeError):
            c.query_state("NO_SUCH_STATE", "1-U")
        assert c.ping().startswith("PONG\t")


def test_online_update_overwrites_served_row(als_job):
    """The closed loop: a new row for an existing key replaces the served
    value (last-writer-wins ValueState semantics)."""
    job, journal, _ = als_job
    journal.append([F.format_als_row(7, "U", [1.0])])
    assert _wait_until(lambda: job.table.get("7-U") == "1.0")
    journal.append([F.format_als_row(7, "U", [9.0])])  # online update
    assert _wait_until(lambda: job.table.get("7-U") == "9.0")


def test_checkpoint_restart_replays_from_offset(tmp_path):
    """Kill the consume loop; a restart must restore the checkpoint and
    re-consume only from the committed offset (at-least-once)."""
    journal = Journal(str(tmp_path / "j"), "t")
    backend = FsStateBackend(str(tmp_path / "chk"))
    job = ServingJob(
        journal, ALS_STATE, parse_als_record, backend,
        checkpoint_interval_ms=50, poll_interval_s=0.01,
        host="127.0.0.1", port=0, restart_delay_s=0.05,
    )
    job.start()
    try:
        journal.append([F.format_als_row(i, "U", [float(i)]) for i in range(20)])
        assert _wait_until(lambda: len(job.table) == 20)
        assert _wait_until(lambda: backend.restore(ModelTable(8)) is not None)

        # simulate a task failure by making the next poll raise once
        # (read_bytes_from is the shared byte-level read under BOTH the
        # scalar and columnar ingest paths)
        original = journal.read_bytes_from
        calls = {"n": 0}

        def flaky(offset, max_bytes=1 << 24):
            if calls["n"] == 0:
                calls["n"] += 1
                raise OSError("injected failure")
            return original(offset, max_bytes)

        journal.read_bytes_from = flaky
        journal.append([F.format_als_row(100, "U", [4.2])])
        assert _wait_until(lambda: job.table.get("100-U") == "4.2", timeout=15)
        assert len(job.table) == 21
    finally:
        job.stop()


def test_latest_restart_without_checkpoint_keeps_seed_offset(tmp_path):
    """a startFrom=latest consumer that fails before its first
    checkpoint must restart from the seeded end-of-journal offset, not 0 —
    resetting to 0 replays the whole backlog the job was configured to
    skip."""
    journal = Journal(str(tmp_path / "j"), "t")
    journal.append(
        [F.format_als_row(i, "U", [1.0]) for i in range(10)]
    )  # pre-existing backlog this job must never serve
    job = ServingJob(
        journal, ALS_STATE, parse_als_record, MemoryStateBackend(),
        host="127.0.0.1", port=0, poll_interval_s=0.01,
        restart_delay_s=0.05, start_from="latest",
    )
    original = journal.read_bytes_from
    calls = {"n": 0}

    def flaky(offset, max_bytes=1 << 24):
        if calls["n"] == 0:
            calls["n"] += 1
            raise OSError("injected failure")
        return original(offset, max_bytes)

    journal.read_bytes_from = flaky
    job.start()
    try:
        journal.append([F.format_als_row(99, "U", [4.2])])
        assert _wait_until(lambda: job.table.get("99-U") is not None,
                           timeout=15)
        assert calls["n"] == 1  # the failure (and restart) really happened
        assert job.table.get("0-U") is None, "skipped backlog was replayed"
        assert len(job.table) == 1
    finally:
        job.stop()


def test_restart_budget_exhaustion_stops_job(tmp_path):
    journal = Journal(str(tmp_path / "j"), "t")
    job = ServingJob(
        journal, ALS_STATE, parse_als_record, MemoryStateBackend(),
        host="127.0.0.1", port=0,
        restart_attempts=2, restart_delay_s=0.01, poll_interval_s=0.01,
    )
    journal.read_bytes_from = (
        lambda *a, **k: (_ for _ in ()).throw(OSError("down"))
    )
    job.start()
    assert _wait_until(lambda: job._stop.is_set(), timeout=5)
    job.stop()


def test_malformed_rows_counted_not_fatal(als_job):
    job, journal, _ = als_job
    journal.append(["garbage-without-commas", F.format_als_row(1, "U", [1.0])])
    assert _wait_until(lambda: job.table.get("1-U") == "1.0")
    assert job.parse_errors == 1


def test_mse_live_against_serving(als_job, rng):
    """Reference deployment shape: MSE batch job queries the live model."""
    job, journal, tmp_path = als_job
    k = 3
    uf = rng.normal(size=(8, k))
    itf = rng.normal(size=(6, k))
    rows = [F.format_als_row(u + 1, "U", uf[u]) for u in range(8)]
    rows += [F.format_als_row(i + 1, "I", itf[i]) for i in range(6)]
    journal.append(rows)
    assert _wait_until(lambda: len(job.table) == 14)

    u, i = np.nonzero(rng.uniform(size=(8, 6)) < 0.7)
    r = (uf @ itf.T)[u, i]
    ratings_path = str(tmp_path / "ratings.tsv")
    with open(ratings_path, "w") as f:
        f.write("header\n")
        for a, b, c in zip(u + 1, i + 1, r):
            f.write(f"{a}\t{b}\t{c}\n")

    out = mse_mod.run(
        Params.from_args(
            ["--input", ratings_path, "--jobManagerHost", "127.0.0.1",
             "--jobManagerPort", str(job.port), "--jobId", job.job_id]
        )
    )
    assert out == pytest.approx(0.0, abs=1e-9)


def test_consumer_accepts_reference_kafka_flags(tmp_path):
    """A reference-shaped invocation (the exact flag set of
    ALSKafkaConsumer.java:30-35, no --journalDir) must run: bootstrap.servers
    naming a path maps to the journal dir, zookeeper.connect/group.id are
    accepted and ignored."""
    from flink_ms_tpu.serve.consumer import _run_consumer_cli

    journal = Journal(str(tmp_path / "bus"), "models")
    journal.append([F.format_als_row(7, "U", [1.0, 2.0])])
    params = Params.from_args(
        ["--topic", "models",
         "--bootstrap.servers", str(tmp_path / "bus"),
         "--zookeeper.connect", "localhost:2181",
         "--group.id", "als-serving",
         "--checkpointDataUri", str(tmp_path / "chk"),
         "--stateBackend", "fs",
         "--port", "0"]
    )
    job = _run_consumer_cli(params, ALS_STATE, parse_als_record)
    try:
        assert _wait_until(lambda: job.table.get("7-U") == "1.0;2.0")
    finally:
        job.stop()


def test_consumer_broker_bootstrap_falls_back_to_env_journal(tmp_path, monkeypatch):
    """host:port bootstrap.servers (a real broker address) can't be a journal
    path; TPUMS_JOURNAL_DIR provides the location."""
    from flink_ms_tpu.serve.consumer import _resolve_journal_dir

    monkeypatch.setenv("TPUMS_JOURNAL_DIR", str(tmp_path / "env-bus"))
    params = Params.from_args(
        ["--topic", "models", "--bootstrap.servers", "broker-1:9092"]
    )
    assert _resolve_journal_dir(params) == str(tmp_path / "env-bus")


def test_mget_python_server():
    """MGET on the contract (Python) server: order preserved, one request,
    missing keys -> None, empty values survive."""
    from flink_ms_tpu.serve.server import LookupServer

    table = ModelTable(2)
    table.put("1-U", "0.5;1.5")
    table.put("2-I", "")
    srv = LookupServer({ALS_STATE: table}, host="127.0.0.1", port=0).start()
    try:
        with QueryClient("127.0.0.1", srv.port) as c:
            before = srv.requests
            assert c.query_states(ALS_STATE, ["2-I", "gone", "1-U"]) == \
                ["", None, "0.5;1.5"]
            assert srv.requests == before + 1
            with pytest.raises(ValueError):
                c.query_states(ALS_STATE, ["has,comma"])
            with pytest.raises(RuntimeError):
                c.query_states("NO_STATE", ["1-U"])
    finally:
        srv.stop()


def test_sparse_dot_python_server(rng):
    """DOT verb: the whole sparse query answered server-side in ONE round
    trip — exact against client-side computation, missing buckets
    reported, coherent after a bucket republish, loud errors."""
    import pytest

    from flink_ms_tpu.serve.server import LookupServer

    table = ModelTable(2)
    w = np.arange(1, 13, dtype=float) * 0.25
    for line in F.format_svm_range_rows(w, 4):
        k, v = parse_svm_record(line)
        table.put(k, v)
    srv = LookupServer({SVM_STATE: table}, host="127.0.0.1", port=0).start()
    try:
        with QueryClient("127.0.0.1", srv.port) as c:
            vec = {1: 2.0, 2: -1.0, 7: 0.5, 9: 4.0, 999: 3.0}
            before = srv.requests
            dot, missing = c.sparse_dot(SVM_STATE, 4, vec)
            assert srv.requests == before + 1  # one round trip, whole query
            expected = sum(w[f - 1] * v for f, v in vec.items()
                           if f <= len(w))
            assert dot == pytest.approx(expected, rel=1e-12)
            assert missing == [999 // 4]
            # empty query: zero dot, nothing missing
            assert c.sparse_dot(SVM_STATE, 4, {}) == (0.0, [])
            # coherence: republishing a bucket must be visible immediately
            # (the parse cache keys on the payload STRING, not the bucket)
            table.put("1", "5:10.0")
            dot2, _ = c.sparse_dot(SVM_STATE, 4, {5: 1.0, 7: 1.0})
            assert dot2 == pytest.approx(10.0)  # fid 7 gone -> weight 0
            # loud errors, not silent zeros
            with pytest.raises(RuntimeError):
                c.sparse_dot("NO_STATE", 4, {1: 1.0})
            with pytest.raises(RuntimeError):
                c.sparse_dot(SVM_STATE, 0, {1: 1.0})
            assert c._roundtrip(
                f"DOT\t{SVM_STATE}\t4\t1:oops").startswith("E\t")
    finally:
        srv.stop()


def test_mse_live_batched_one_roundtrip_per_group(als_job, rng):
    """Live MSE with MGET costs one request per user group (vs one per
    rating + one per group in the reference, MSE.java:129-158), with skip
    semantics intact: group 9 has an unknown user, item 99 is unknown."""
    job, journal, tmp_path = als_job
    k = 2
    rows = [F.format_als_row(u, "U", [1.0, float(u)]) for u in range(3)]
    rows += [F.format_als_row(i, "I", [0.5, float(i)]) for i in range(3)]
    journal.append(rows)
    assert _wait_until(lambda: len(job.table) == 6)

    ratings_path = str(tmp_path / "r.tsv")
    with open(ratings_path, "w") as f:
        f.write("header\n")
        for u in range(3):
            for i in range(3):
                f.write(f"{u}\t{i}\t{1.0}\n")
        f.write("9\t0\t1.0\n")   # unknown user: whole group skipped
        f.write("0\t99\t1.0\n")  # unknown item: one rating skipped
    before = job.server.requests
    out = mse_mod.run(
        Params.from_args(
            ["--input", ratings_path, "--jobManagerHost", "127.0.0.1",
             "--jobManagerPort", str(job.port), "--jobId", job.job_id]
        )
    )
    # 4 user groups (0,1,2,9) -> 4 MGETs, nothing else
    assert job.server.requests - before == 4
    expected = float(np.mean(
        [(1.0 - (1.0 * 0.5 + u * i)) ** 2 for u in range(3) for i in range(3)]
    ))
    assert out == pytest.approx(expected)

def test_fnv1a_batch_matches_scalar():
    from flink_ms_tpu.serve.table import _fnv1a, _fnv1a_batch

    keys = ["1-U", "12345-I", "MEAN-U", "", "x" * 40, "bucket", "7",
            "ünïcödé-I"]
    batch = _fnv1a_batch(keys)
    for k, h in zip(keys, batch):
        assert int(h) == _fnv1a(k), k

def test_start_from_latest_skips_backlog(tmp_path):
    """--startFrom latest (auto.offset.reset=latest parity): a consumer
    with no checkpoint serves only rows published after it came up."""
    from flink_ms_tpu.serve.consumer import (
        ALS_STATE, MemoryStateBackend, ServingJob, parse_als_record,
    )
    from flink_ms_tpu.serve.journal import Journal

    bus = str(tmp_path)
    j = Journal(bus, "m")
    j.append(["1,U,old-row"], flush=True)
    job = ServingJob(
        Journal(bus, "m"), ALS_STATE, parse_als_record, MemoryStateBackend(),
        host="127.0.0.1", port=0, poll_interval_s=0.01, start_from="latest",
    ).start()
    try:
        j.append(["2,U,new-row"], flush=True)
        assert _wait_until(lambda: job.table.get("2-U") == "new-row")
        assert job.table.get("1-U") is None  # backlog skipped
    finally:
        job.stop()
