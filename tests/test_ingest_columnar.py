"""Vectorized ingest plane (ISSUE 2): the columnar chunk parser must be
byte-identical to the per-line scalar path — same keys, same values, same
parse-error counts, same shard routing — and the batched change-notification
path must leave the top-k index in the same state the per-key path would."""

import time

import numpy as np
import pytest

from flink_ms_tpu.core import formats as F
from flink_ms_tpu.core.formats import (
    CHUNK_ALS,
    CHUNK_SVM,
    split_journal_chunk,
)
from flink_ms_tpu.serve.consumer import (
    ALS_STATE,
    MemoryStateBackend,
    ServingJob,
    parse_als_record,
    parse_svm_record,
)
from flink_ms_tpu.serve.journal import Journal
from flink_ms_tpu.serve.sharded import sharded_parse
from flink_ms_tpu.serve.table import ModelTable, _fnv1a


def _wait_until(pred, timeout=30.0, interval=0.02):
    t0 = time.time()
    while time.time() - t0 < timeout:
        if pred():
            return True
        time.sleep(interval)
    return False


def _scalar_reference(data: bytes, parse_fn):
    """The pre-columnar semantics, verbatim: decode + splitlines + per-line
    parse, empty lines skipped, ValueError -> skip-and-count."""
    pairs, errors = [], 0
    for line in data.decode("utf-8").splitlines():
        if not line:
            continue
        try:
            pairs.append(parse_fn(line))
        except ValueError:
            errors += 1
    return pairs, errors


def _assert_chunk_parity(data: bytes, mode: int, parse_fn):
    keys, values, errs = split_journal_chunk(data, mode)
    ref_pairs, ref_errs = _scalar_reference(data, parse_fn)
    assert list(zip(keys, values)) == ref_pairs, data
    assert errs == ref_errs, data
    k2, v2, e2, hashes = split_journal_chunk(data, mode, with_hashes=True)
    assert (k2, v2, e2) == (keys, values, errs)
    if hashes is None:
        hashes = np.array([], np.uint32) if not keys else None
    assert hashes is not None, "hash fast path must cover normal keys"
    assert [int(h) for h in hashes] == [_fnv1a(k) for k in keys], data


# -- columnar chunk parser: unit parity -------------------------------------

ALS_CASES = [
    b"",
    b"\n",
    b"\n\n\n",
    b"1,U,0.5;1.5\n2,I,2.5;3.5\n",
    b"1,U,0.5;1.5\r\n2,I,2.5;3.5\r\n",          # CRLF
    b"garbage\n1,U,0.5\nalso,bad\n",            # <2 commas -> skip+count
    b"nocommas\n\nstill none\n",                # all-error chunk
    b"1,U,a,b,c\n",                             # payload keeps its commas
    b"\xc3\xa9,U,0.5\n\xe6\x97\xa5,I,1;2\n",    # unicode ids
    b"1,U,\n2,I,x\n",                           # empty / odd payloads
    b"9,I,0.25\n9,I,0.75\n",                    # last-writer-wins order
    b"1,U,0.5",                                 # no trailing newline
]

SVM_CASES = [
    b"",
    b"\n",
    b"f1,0.5;3\nf2,1.5;7\n",
    b"lonely\nf1,0.5\nalso-lonely\n",           # comma-less -> (line, "")
    b"lonely\r\nf1,0.5\r\n",                    # CRLF + loner
    b"a,1\n\nb,2\n\nloner\n",                   # order across loners
    b"\xc3\xa9,0.5\nno-comma-\xe6\x97\xa5\n",   # unicode loner
    b"k,v,w,x\n",                               # payload keeps its commas
    b"f1,0.5",                                  # no trailing newline
]


@pytest.mark.parametrize("data", ALS_CASES)
def test_columnar_als_parity(data):
    _assert_chunk_parity(data, CHUNK_ALS, parse_als_record)


@pytest.mark.parametrize("data", SVM_CASES)
def test_columnar_svm_parity(data):
    _assert_chunk_parity(data, CHUNK_SVM, parse_svm_record)


def test_columnar_fuzz_parity():
    """Random chunks over a hostile alphabet (separators, unicode, empty
    fields) must match the scalar reference row for row in both modes."""
    rng = np.random.default_rng(7)
    alphabet = ["a", "1", ",", ";", "-", "é", "日", ""]
    for trial in range(60):
        lines = []
        for _ in range(int(rng.integers(0, 12))):
            lines.append("".join(
                alphabet[int(i)]
                for i in rng.integers(0, len(alphabet), rng.integers(0, 9))
            ))
        sep = "\r\n" if trial % 3 == 0 else "\n"
        data = sep.join(lines).encode("utf-8")
        if trial % 2:
            data += sep.encode()
        _assert_chunk_parity(data, CHUNK_ALS, parse_als_record)
        _assert_chunk_parity(data, CHUNK_SVM, parse_svm_record)


def test_columnar_oversized_key_hash_falls_back():
    """Keys longer than the vectorized hasher's padded-width bound must
    still hash correctly (per-key fallback), not crash or go quiet."""
    big = "x" * 400
    data = f"{big},U,1.0\n".encode()
    keys, values, errs, hashes = split_journal_chunk(
        data, CHUNK_ALS, with_hashes=True)
    assert keys == [f"{big}-U"] and errs == 0
    if hashes is not None:  # None = caller recomputes; both are valid
        assert int(hashes[0]) == _fnv1a(keys[0])


# -- batched table writes ---------------------------------------------------

def test_put_many_columns_matches_per_key_put():
    rng = np.random.default_rng(3)
    keys = [f"{int(i)}-I" for i in rng.integers(0, 200, 500)]  # dup-heavy
    values = [f"{float(v):.3f}" for v in rng.random(500)]
    a, b = ModelTable(8), ModelTable(8)
    seen_a, seen_b = [], []
    a.add_change_listener(seen_a.append)
    b.add_change_listener(seen_b.append, lambda ks: seen_b.extend(ks))
    for k, v in zip(keys, values):
        a.put(k, v)
    b.put_many_columns(keys, values)
    assert a._shards == b._shards  # byte-identical incl. last-writer-wins
    assert seen_a == seen_b == keys
    assert a.puts == b.puts == 500
    # precomputed hashes route identically
    c = ModelTable(8)
    c.put_many_columns(
        keys, values,
        hashes=np.array([_fnv1a(k) for k in keys], np.uint32))
    assert c._shards == a._shards


# -- end-to-end: columnar vs scalar ServingJob ------------------------------

def _mixed_journal(tmp_path, n=3000):
    j = Journal(str(tmp_path / "bus"), "models")
    rng = np.random.default_rng(11)
    rows, bad = [], 0
    for i in range(n):
        r = int(rng.integers(0, 20))
        if r == 0:
            rows.append("malformed-no-commas")
            bad += 1
        elif r == 1:
            rows.append(f"{i},onlyone")
            bad += 1
        else:
            rows.append(F.format_als_row(
                i % (n // 3), "I" if i % 2 else "U",
                rng.random(4) - 0.5))
    j.append(rows)
    return j, n, bad


@pytest.mark.parametrize("mode", ["columnar", "scalar"])
def test_serving_job_modes_reach_same_state(tmp_path, mode):
    journal, n, bad = _mixed_journal(tmp_path)
    job = ServingJob(
        journal, ALS_STATE, parse_als_record, MemoryStateBackend(),
        host="127.0.0.1", port=0, poll_interval_s=0.01,
        ingest_mode=mode, topk_index=False,
    ).start()
    try:
        assert _wait_until(lambda: job.parse_errors + job.ingest_rows >= n)
        stats = job.ingest_stats()
        assert stats["path"] == mode
        assert job.parse_errors == bad
        # the reference state, computed scalar-side
        expect = ModelTable(job.table.n_shards)
        with open(journal.path, "rb") as f:
            pairs, errs = _scalar_reference(f.read(), parse_als_record)
        for k, v in pairs:
            expect.put(k, v)
        assert errs == bad
        assert job.table._shards == expect._shards
    finally:
        job.stop()


def test_sharded_columnar_ownership_matches_scalar(tmp_path):
    """Vectorized ownership filtering (hash % W on the raw chunk) must give
    each worker exactly the slice the scalar shard filter would."""
    journal, n, bad = _mixed_journal(tmp_path, n=1200)
    slices = {}
    for mode in ("columnar", "scalar"):
        for w in range(2):
            job = ServingJob(
                journal, ALS_STATE, sharded_parse(parse_als_record, w, 2),
                MemoryStateBackend(), host="127.0.0.1", port=0,
                poll_interval_s=0.01, ingest_mode=mode, topk_index=False,
            ).start()
            try:
                assert _wait_until(
                    lambda: job.ingest_stats()["offset"]
                    >= journal.end_offset())
                assert job.ingest_stats()["path"] == mode
                # sharded fleet members default to the arena table now —
                # rebuild the per-shard view through the table contract
                # instead of reaching into dict-table internals
                t = job.table
                if hasattr(t, "_shards"):
                    slices[(mode, w)] = [dict(s) for s in t._shards]
                else:
                    shards = [dict() for _ in range(t.n_shards)]
                    for k, v in t.items():
                        shards[t.shard_of(k)][k] = v
                    slices[(mode, w)] = shards
            finally:
                job.stop()
    for w in range(2):
        assert slices[("columnar", w)] == slices[("scalar", w)]
    union = {}
    for w in range(2):
        for shard in slices[("columnar", w)]:
            assert not (set(shard) & set(union)), "owners must be disjoint"
            union.update(shard)
    with open(journal.path, "rb") as f:
        pairs, _ = _scalar_reference(f.read(), parse_als_record)
    assert union == dict(pairs)


# -- batched listener -> top-k index ----------------------------------------

def test_small_batch_keeps_exact_dirty_set():
    from flink_ms_tpu.serve.topk import DeviceFactorIndex

    table = ModelTable(4)
    index = DeviceFactorIndex(table, "-I")
    table.put_many_columns(
        ["1-I", "2-U", "MEAN-I", "3-I"],
        ["0.1", "0.2", "0.3", "0.4"])
    assert index._dirty == {"1-I", "3-I"}
    assert index._replay_backlog == 0


def test_bulk_replay_triggers_rebuild_and_correct_topk(monkeypatch):
    """A replay-scale batch through the columnar path must (a) not stall
    the writer on per-key dirty tracking, (b) be absorbed by ONE background
    rebuild, and (c) leave the index returning exactly the brute-force
    top-k."""
    monkeypatch.setenv("TPUMS_TOPK_APPLY_CAP", "2")  # rebuild_backlog=16
    from flink_ms_tpu.serve.topk import DeviceFactorIndex

    table = ModelTable(4)
    index = DeviceFactorIndex(table, "-I")
    k = 4
    rng = np.random.default_rng(5)
    seed = rng.random((4, k)) - 0.5
    for i, row in enumerate(seed):
        table.put(f"{i}-I", ";".join(f"{x:.6f}" for x in row))
    q = np.ones(k, np.float32)
    index.topk(q, 2)  # initial build
    builds0 = index.full_builds

    mat = rng.random((40, k)) - 0.5
    keys = [f"{100 + i}-I" for i in range(40)]
    values = [";".join(f"{x:.6f}" for x in row) for row in mat]
    table.put_many_columns(keys, values)
    assert index._replay_backlog >= 40  # counted, not stored
    assert len(index._dirty) == 0

    index.topk(q, 2)  # kicks the background rebuild
    t = index._rebuild_thread
    assert t is not None
    t.join(timeout=60)
    assert index.full_builds > builds0

    got = index.topk(q, 5)
    all_ids = [str(i) for i in range(4)] + [str(100 + i) for i in range(40)]
    all_rows = np.vstack([seed, mat])
    # parse exactly what the table stores — the index scores the stored
    # text, so the expectation must too
    stored = np.array([
        [float(tok) for tok in table.get(f"{i}-I").split(";")]
        for i in all_ids
    ], np.float32)
    assert stored.shape == all_rows.shape
    scores = stored @ q
    want = [all_ids[i] for i in np.argsort(-scores)[:5]]
    assert [gid for gid, _ in got] == want


# -- checkpoint deferral during replay backlog ------------------------------

def test_checkpoints_deferred_while_replaying(tmp_path, monkeypatch):
    monkeypatch.setattr(ServingJob, "CHUNK_CAP", 4096)
    journal = Journal(str(tmp_path / "bus"), "models")
    rows = [F.format_als_row(i, "I", [0.5] * 8) for i in range(2000)]
    journal.append(rows)  # ~100 KB >> 4 KB chunks: a real backlog
    backend = MemoryStateBackend()
    job = ServingJob(
        journal, ALS_STATE, parse_als_record, backend,
        host="127.0.0.1", port=0, poll_interval_s=0.01,
        checkpoint_interval_ms=1, topk_index=False,
    ).start()
    try:
        assert _wait_until(lambda: job.ingest_rows >= 2000)
        assert job.checkpoints_deferred >= 1
        # once drained, the wall-clock checkpoint goes through again (an
        # earlier one may have gone through mid-replay on a loaded host:
        # deferral is bounded by CHECKPOINT_MAX_DEFER_INTERVALS)
        assert _wait_until(lambda: backend._snap is not None
                           and backend._snap[0] == journal.end_offset())
    finally:
        job.stop()


# -- mode selection ---------------------------------------------------------

def test_ingest_mode_validation_and_env(tmp_path, monkeypatch):
    journal = Journal(str(tmp_path / "bus"), "models")
    with pytest.raises(ValueError):
        ServingJob(
            journal, ALS_STATE, parse_als_record, MemoryStateBackend(),
            host="127.0.0.1", port=0, ingest_mode="bogus")
    monkeypatch.setenv("TPUMS_INGEST_MODE", "scalar")
    job = ServingJob(
        journal, ALS_STATE, parse_als_record, MemoryStateBackend(),
        host="127.0.0.1", port=0)
    assert job.ingest_mode == "scalar"
    explicit = ServingJob(
        journal, ALS_STATE, parse_als_record, MemoryStateBackend(),
        host="127.0.0.1", port=0, ingest_mode="columnar")
    assert explicit.ingest_mode == "columnar"
