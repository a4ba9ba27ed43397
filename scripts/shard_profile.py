#!/usr/bin/env python
"""Sharded-plane latency attribution: where do the
3-worker MGET/TOPK percentiles go vs single-worker — client routing, pool
dispatch, per-worker service time, or merge?

Builds one single-worker plane and one W-worker plane over the same
generated model, then times:
  - single MGET / sharded MGET (pooled fan-out vs sequential)
  - single TOPK / per-worker TOPKV serial / pooled fan-out topk
Run host-side; no accelerator needed (the serving plane is host-resident).

Measurement hazard on small hosts (this box: 1 CPU core): the first
seconds after worker-process startup carry intermittent ~10-100 ms
scheduler stalls that dominate short windows — a 50-query run can sit
entirely inside them (observed 20 ms p50) while a 500-query run on the
same plane settles to 0.07 ms p50.  Keep PROF_QUERIES >= 300 and trust
p50 over the tail percentiles here; on multi-core serving hosts this
artifact does not exist.
"""

import os
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from flink_ms_tpu.core.params import Params  # noqa: E402
from flink_ms_tpu.gen import als_model_generator  # noqa: E402
from flink_ms_tpu.serve import producer  # noqa: E402
from flink_ms_tpu.serve.client import QueryClient  # noqa: E402
from flink_ms_tpu.serve.consumer import (  # noqa: E402
    ALS_STATE,
    MemoryStateBackend,
    ServingJob,
    parse_als_record,
)
from flink_ms_tpu.serve.journal import Journal  # noqa: E402
from flink_ms_tpu.serve.sharded import (  # noqa: E402
    ShardedQueryClient,
    spawn_worker_procs,
    stop_worker_procs,
)

N_USERS = int(os.environ.get("PROF_USERS", 30_000))
N_ITEMS = int(os.environ.get("PROF_ITEMS", 300_000))
K = int(os.environ.get("PROF_K", 16))
W = int(os.environ.get("PROF_WORKERS", 3))
N_Q = int(os.environ.get("PROF_QUERIES", 500))
TOPK_K = 10


def pcts(xs):
    xs = sorted(xs)
    return {q: round(xs[min(int(len(xs) * q / 100), len(xs) - 1)], 3)
            for q in (50, 95, 99)}


def timed(fn, n=N_Q, seed=1):
    rng = np.random.default_rng(seed)

    def draw():
        return (int(rng.integers(1, N_USERS + 1)),
                int(rng.integers(1, N_ITEMS + 1)))

    # active warmup, uncounted: the seconds after worker startup carry a
    # scheduler/cache transient on small hosts (observed ~20 ms p50 for a
    # measurement window that sits entirely inside it vs 0.07 ms after);
    # warm until the path is demonstrably settled or 3 s, whichever first
    deadline = time.time() + 3.0
    fast = 0
    while time.time() < deadline and fast < 20:
        u, i = draw()
        t0 = time.perf_counter()
        fn(u, i)
        fast = fast + 1 if (time.perf_counter() - t0) < 0.001 else 0
    out = []
    for _ in range(n):
        u, i = draw()
        t0 = time.perf_counter()
        fn(u, i)
        out.append((time.perf_counter() - t0) * 1000.0)
    return pcts(out)


def main():
    tmp = tempfile.mkdtemp(prefix="shard_prof_")
    t0 = time.time()
    als_model_generator.run(Params.from_dict({
        "numUsers": N_USERS, "numItems": N_ITEMS, "latentFactors": K,
        "parallelism": 4, "output": os.path.join(tmp, "model"),
    }))
    producer.run(Params.from_dict({
        "journalDir": os.path.join(tmp, "bus"), "topic": "als-models",
        "input": os.path.join(tmp, "model"),
    }))
    print(f"gen+produce: {time.time() - t0:.1f}s", file=sys.stderr)

    total = N_USERS + N_ITEMS
    journal = Journal(os.path.join(tmp, "bus"), "als-models")
    single = ServingJob(
        journal, ALS_STATE, parse_als_record, MemoryStateBackend(),
        host="127.0.0.1", port=0, poll_interval_s=0.01,
    ).start()
    # REAL worker processes — the deployment shape; in-process workers
    # share one GIL + XLA runtime and serialize the TOPKV fan-out
    procs, ports = spawn_worker_procs(
        W, os.path.join(tmp, "bus"), "als-models", port_dir=tmp,
    )

    try:
        sc = QueryClient("127.0.0.1", single.port, timeout_s=600)
        shc = ShardedQueryClient([("127.0.0.1", pt) for pt in ports],
                                 timeout_s=600)
        wc = [QueryClient("127.0.0.1", pt, timeout_s=600) for pt in ports]
        deadline = time.time() + 600
        while time.time() < deadline:
            if (len(single.table) >= total
                    and shc.total_count(ALS_STATE) >= total):
                break
            time.sleep(0.2)
        print(f"ingest done: {time.time() - t0:.1f}s", file=sys.stderr)

        print("MGET-2  single :", timed(
            lambda u, i: sc.query_states(ALS_STATE, [f"{u}-U", f"{i}-I"])))
        print("MGET-2  sharded:", timed(
            lambda u, i: shc.query_states(ALS_STATE, [f"{u}-U", f"{i}-I"])))

        def seq_mget(u, i):
            for key in (f"{u}-U", f"{i}-I"):
                wc[shc.owner(key)].query_states(ALS_STATE, [key])
        print("MGET-2  seq-direct:", timed(seq_mget))

        # topk warm (index builds)
        t0 = time.time()
        sc.topk(ALS_STATE, "1", TOPK_K)
        print(f"single index build: {time.time() - t0:.1f}s", file=sys.stderr)
        t0 = time.time()
        shc.topk(ALS_STATE, "1", TOPK_K)
        print(f"sharded index build: {time.time() - t0:.1f}s", file=sys.stderr)

        print("TOPK    single :", timed(
            lambda u, i: sc.topk(ALS_STATE, str(u), TOPK_K), n=60))
        print("TOPK    sharded:", timed(
            lambda u, i: shc.topk(ALS_STATE, str(u), TOPK_K), n=60))

        payload = sc.query_state(ALS_STATE, "1-U")
        for widx, c in enumerate(wc):
            ms = []
            for _ in range(60):
                t0 = time.perf_counter()
                c.topk_by_vector(ALS_STATE, payload, TOPK_K)
                ms.append((time.perf_counter() - t0) * 1000.0)
            print(f"TOPKV   worker{widx} direct:", pcts(ms))

        def serial_fan(u, i):
            up = shc.query_state(ALS_STATE, f"{u}-U")
            if up is None:
                return
            merged = []
            for c in wc:
                r = c.topk_by_vector(ALS_STATE, up, TOPK_K)
                merged.extend(r)
            merged.sort(key=lambda it: -it[1])
            merged[:TOPK_K]
        print("TOPK    serial-fanout:", timed(serial_fan, n=60))

        for c in (sc, shc, *wc):
            c.close()
    finally:
        single.stop()
        stop_worker_procs(procs)


if __name__ == "__main__":
    main()
