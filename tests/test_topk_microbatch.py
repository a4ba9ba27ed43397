"""Cross-request top-k microbatching: batched scoring is result-identical
to the single-query path, concurrent load actually coalesces (dispatches <
requests), streaming dirty-set updates stay visible to batched queries,
and a lone request's extra latency is bounded by the coalescing window."""

import threading
import time

import numpy as np
import pytest

from flink_ms_tpu.obs import metrics as obs_metrics
from flink_ms_tpu.serve.client import QueryClient
from flink_ms_tpu.serve.microbatch import TopKBatcher
from flink_ms_tpu.serve.server import LookupServer
from flink_ms_tpu.serve.table import ModelTable
from flink_ms_tpu.serve.topk import ALSTopkHandler, DeviceFactorIndex

STATE = "ALS_MODEL"


def _fill(table, n_items, k, rng, n_users=8):
    for u in range(n_users):
        table.put(
            f"{u}-U", ";".join(repr(float(x)) for x in rng.normal(size=k))
        )
    vecs = rng.normal(size=(n_items, k))
    for i in range(n_items):
        table.put(f"{i}-I", ";".join(repr(float(x)) for x in vecs[i]))
    return vecs


# -- result parity ----------------------------------------------------------

def test_topk_many_matches_single_queries(rng):
    """Every row of a batched dispatch returns the same item ids and
    scores as the single-query program (the microbatcher must be a pure
    throughput lever, invisible in results)."""
    table = ModelTable(4)
    k = 6
    _fill(table, 300, k, rng)
    index = DeviceFactorIndex(table, "-I")
    for batch_size in (1, 2, 5, 8, 13):
        qs = rng.normal(size=(batch_size, k)).astype(np.float32)
        single = [index.topk(q, 7) for q in qs]
        batched = index.topk_many(qs, 7)
        for s, b in zip(single, batched):
            assert [it for it, _ in s] == [it for it, _ in b]
            np.testing.assert_allclose(
                [sc for _, sc in s], [sc for _, sc in b],
                rtol=1e-6, atol=1e-6,
            )


def test_server_batched_replies_match_unbatched(rng):
    """Wire-level parity: the same TOPK queries answered with batching on
    (pipelined burst -> shared dispatch) and off produce identical reply
    payloads, so batching is invisible at the protocol layer."""
    table = ModelTable(4)
    _fill(table, 200, 5, rng)
    handler = ALSTopkHandler(table, batcher=TopKBatcher(
        DeviceFactorIndex(table, "-I"), max_batch=16, max_wait_us=10_000,
    ))
    handler.index = handler.batcher.index  # one index for both arms
    srv = LookupServer(
        {STATE: table}, host="127.0.0.1", port=0,
        topk_handlers={STATE: handler},
    ).start()
    try:
        uids = [str(u) for u in range(8)]
        with QueryClient("127.0.0.1", srv.port, timeout_s=30) as c:
            batched = c.topk_pipelined(STATE, uids, 5)
            handler.batching = False
            unbatched = [c.topk(STATE, u, 5) for u in uids]
        assert [[it for it, _ in r] for r in batched] == \
               [[it for it, _ in r] for r in unbatched]
        for rb, ru in zip(batched, unbatched):
            np.testing.assert_allclose(
                [sc for _, sc in rb], [sc for _, sc in ru],
                rtol=1e-6, atol=1e-6,
            )
        assert handler.batcher.max_batch_seen > 1  # the burst DID coalesce
    finally:
        srv.stop()


# -- coalescing -------------------------------------------------------------

def test_concurrent_submitters_coalesce(rng):
    """N threads submitting at a barrier must share dispatches: the
    dispatch count stays strictly below the request count (the whole point
    of the scheduler), and every thread still gets its own correct rows."""
    table = ModelTable(4)
    k = 5
    _fill(table, 150, k, rng)
    index = DeviceFactorIndex(table, "-I")
    index.topk(np.zeros(k, np.float32), 1)  # warm build off the clock
    batcher = TopKBatcher(index, max_batch=32, max_wait_us=20_000)
    n_threads = 24
    qs = rng.normal(size=(n_threads, k)).astype(np.float32)
    expected = [index.topk(q, 4) for q in qs]
    results = [None] * n_threads
    barrier = threading.Barrier(n_threads)

    def worker(i):
        barrier.wait()
        results[i] = batcher.score(qs[i], 4, timeout=60)

    threads = [
        threading.Thread(target=worker, args=(i,)) for i in range(n_threads)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    batcher.close()
    assert batcher.submitted == n_threads
    assert batcher.dispatches < batcher.submitted
    assert batcher.max_batch_seen > 1
    for got, want in zip(results, expected):
        assert [it for it, _ in got] == [it for it, _ in want]


def test_mixed_k_and_bad_width_fail_only_their_own(rng):
    """A batch mixing k values splits into per-k dispatches; a query whose
    width mismatches the index errors alone without poisoning the batch."""
    table = ModelTable(2)
    k = 4
    _fill(table, 60, k, rng)
    index = DeviceFactorIndex(table, "-I")
    batcher = TopKBatcher(index, max_batch=8, max_wait_us=50_000)
    good_a = batcher.submit(rng.normal(size=k).astype(np.float32), 3)
    good_b = batcher.submit(rng.normal(size=k).astype(np.float32), 5)
    bad = batcher.submit(rng.normal(size=k + 2).astype(np.float32), 3)
    assert len(good_a.wait(timeout=60)) == 3
    assert len(good_b.wait(timeout=60)) == 5
    with pytest.raises(ValueError):
        bad.wait(timeout=60)
    batcher.close()


# -- streaming updates ------------------------------------------------------

def test_dirty_updates_visible_to_batched_queries(rng):
    """An in-place row update lands before the next batched dispatch
    scores (maintenance runs once per batch), with no full rebuild."""
    table = ModelTable(4)
    k = 6
    _fill(table, 80, k, rng)
    index = DeviceFactorIndex(table, "-I")
    qs = rng.normal(size=(3, k)).astype(np.float32)
    index.topk_many(qs, 5)  # initial build
    assert index.full_builds == 1

    target = qs[1] * 100.0
    table.put("33-I", ";".join(repr(float(x)) for x in target))
    got = index.topk_many(qs, 3)
    assert got[1][0][0] == "33"
    assert got[1][0][1] == pytest.approx(float(qs[1] @ target), rel=1e-4)
    assert index.full_builds == 1  # scatter, not rebuild
    assert index.inplace_updates >= 1


# -- latency bound ----------------------------------------------------------

def test_lone_query_latency_bounded_by_wait_window(rng):
    """At concurrency 1 the scheduler may add AT MOST the coalescing
    window (plus scheduling noise) on top of the unbatched query time —
    the knob is a strict bound, not a hint."""
    table = ModelTable(4)
    k = 5
    _fill(table, 100, k, rng)
    index = DeviceFactorIndex(table, "-I")
    q = rng.normal(size=k).astype(np.float32)
    index.topk(q, 5)  # build + compile off the clock
    max_wait_s = 0.15
    batcher = TopKBatcher(index, max_batch=16, max_wait_us=max_wait_s * 1e6)
    batcher.score(q, 5, timeout=60)  # dispatcher thread warm

    def p50(fn, n=7):
        xs = []
        for _ in range(n):
            t0 = time.perf_counter()
            fn()
            xs.append(time.perf_counter() - t0)
        return sorted(xs)[n // 2]

    single = p50(lambda: index.topk(q, 5))
    batched = p50(lambda: batcher.score(q, 5, timeout=60))
    batcher.close()
    # generous absolute slack for a loaded single-core CI box; the bound
    # still rejects any design that waits a multiple of the window
    assert batched <= single + max_wait_s + 0.25, (single, batched)


# -- the window's origin ----------------------------------------------------

class _SleepyIndex:
    """A stub whose frame takes ``sleep_s``: long enough in flight for the
    next frame's head to outlive the coalescing window behind it."""

    prefers_frames = True  # a lone query rides topk_many too

    def __init__(self, sleep_s):
        self.sleep_s = sleep_s

    def topk_many(self, vecs, k):
        time.sleep(self.sleep_s)
        return [[("0", 0.0)] for _ in vecs]

    def last_fetch(self):
        return None


def _window_held():
    h = obs_metrics.get_registry().histogram("tpums_topk_window_held")
    return h.count, h.sum


@pytest.mark.parametrize("case", ["backlog", "idle_arrival", "flush"])
def test_window_counts_from_the_heads_arrival(case):
    """The coalescing window is an arrival's chance to find companions,
    counted from ITS arrival: a head that queued behind a running frame
    has used it up; an arrival at an idle batcher gets all of it, and a
    later companion does not re-arm it; ``flush()`` still ends it."""
    wait_s = {"backlog": 0.8, "idle_arrival": 0.4, "flush": 5.0}[case]
    batcher = TopKBatcher(_SleepyIndex(1.0 if case == "backlog" else 0.0),
                          max_batch=8, max_wait_us=wait_s * 1e6)
    q = np.zeros(4, np.float32)

    def frame_of_one():
        p = batcher.submit(q, 1, allow_inline=False)
        batcher.flush()
        return p

    try:
        if case == "backlog":
            first = frame_of_one()
            time.sleep(0.05)  # the frame is in flight for 1.0 s
            second = batcher.submit(q, 1, allow_inline=False)
            first.wait(timeout=60)
            n0, held0 = _window_held()
            second.wait(timeout=60)
            # picked up when the first frame came back, not wait_s later
            assert second.queue_wait_s >= wait_s
            assert second.t_dispatch - first.t_done < wait_s / 2
            assert _window_held() == (n0 + 1, held0)
        elif case == "idle_arrival":
            frame_of_one().wait(timeout=60)  # the dispatcher is up and idle
            n0, held0 = _window_held()
            head = batcher.submit(q, 1, allow_inline=False)
            time.sleep(0.1)
            companion = batcher.submit(q, 1, allow_inline=False)
            head.wait(timeout=60)
            companion.wait(timeout=60)
            assert head.batch_size == companion.batch_size == 2
            assert wait_s <= head.queue_wait_s < wait_s + 0.5
            assert companion.queue_wait_s < head.queue_wait_s - 0.05
            assert _window_held() == (n0 + 1, held0 + 1)
        else:
            n0, _ = _window_held()
            lone = frame_of_one()
            lone.wait(timeout=60)
            assert lone.queue_wait_s < wait_s / 2
            assert _window_held()[0] == n0 + 1
    finally:
        batcher.close()


# -- client pipelining ------------------------------------------------------

def test_pipeline_preserves_order_and_mixed_verbs(rng):
    """Pipelined replies map positionally onto requests across mixed
    verbs, including error replies for bad lines."""
    table = ModelTable(2)
    _fill(table, 40, 4, rng)
    handler = ALSTopkHandler(table)
    srv = LookupServer(
        {STATE: table}, host="127.0.0.1", port=0,
        topk_handlers={STATE: handler},
    ).start()
    try:
        with QueryClient("127.0.0.1", srv.port, timeout_s=30) as c:
            reqs = [
                f"GET\t{STATE}\t0-U",
                "PING",
                "NONSENSE",
                f"GET\t{STATE}\tmissing-key",
                f"TOPK\t{STATE}\t1\t3",
            ]
            replies = c.pipeline(reqs, window=5)
        assert replies[0].startswith("V\t")
        assert replies[1].startswith("PONG\t")
        assert replies[2].startswith("E\t")
        assert replies[3] == "N"
        assert replies[4].startswith("V\t")
        # and the batched reply parses into exactly k items
        assert len(QueryClient._parse_topk_reply(replies[4])) == 3
    finally:
        srv.stop()


def test_server_stop_closes_batcher(rng):
    table = ModelTable(2)
    _fill(table, 30, 4, rng)
    handler = ALSTopkHandler(table)
    assert handler.batcher is not None  # default-on
    srv = LookupServer(
        {STATE: table}, host="127.0.0.1", port=0,
        topk_handlers={STATE: handler},
    ).start()
    with QueryClient("127.0.0.1", srv.port, timeout_s=30) as c:
        assert c.topk(STATE, "1", 3)
    srv.stop()
    with pytest.raises(RuntimeError):
        handler.batcher.submit(np.zeros(4, np.float32), 1)
