#!/usr/bin/env python
"""Update-plane microbench: ratings -> published factor rows/sec,
per-rating vs batched vs co-located sharded arms (ISSUE 9).

Measures the SGD apply path in isolation (in-process table, no serving
fleet) so regressions in the rating->rows pipeline are visible outside
the full bench:

- ``perrating``  — the reference shape (SGD.java): one lookup round trip
  and one scalar update per rating;
- ``batched``    — one MGET + the vectorized ``SGDStep.process_batch``
  per chunk (online/sgd.py --batchSize);
- ``colocated``  — the sharded plane (serve/update_plane.py): ratings
  hash-routed into per-partition logs, N co-located UpdateWorkers
  applying through the same batched step, owned reads local, cross-shard
  item reads through the coalesced MGET cache.

All arms run a duplicate-free stream (each user/item once), so the rows
they emit must be BYTE-IDENTICAL; the parity assert covers v1, v0 and
bias semantics before any timing arm runs.

Run host-side (no accelerator needed):

    python scripts/update_profile.py [--ratings 50000] [--k 8] \
        [--workers 4] [--batchSize 256] [--partitions 16]
"""

import os
import random
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

from flink_ms_tpu.core.params import Params  # noqa: E402
from flink_ms_tpu.online.sgd import SGDStep  # noqa: E402
from flink_ms_tpu.serve import update_plane as up  # noqa: E402
from flink_ms_tpu.serve.table import ModelTable  # noqa: E402


def build_table(n: int, k: int, seed: int = 7) -> ModelTable:
    rng = random.Random(seed)
    table = ModelTable(8)
    for i in range(n):
        table.put(f"{i}-U", ";".join(
            f"{rng.uniform(-1, 1):.6f}" for _ in range(k)))
        table.put(f"{i}-I", ";".join(
            f"{rng.uniform(-1, 1):.6f}" for _ in range(k)))
    return table


def build_ratings(n: int, seed: int = 3):
    """Duplicate-free: each user and each item exactly once, so every arm
    computes from the same base vectors and rows are comparable."""
    rng = random.Random(seed)
    items = list(range(n))
    rng.shuffle(items)
    return [(u, items[u], round(rng.uniform(0.5, 5.0), 3)) for u in range(n)]


class TableClient:
    """The co-located arm's 'fleet': MGET answered from the shared table
    (models the cross-shard item fetch without network noise)."""

    def __init__(self, table):
        self.table = table

    def query_states(self, state, keys):
        return [self.table.get(k) for k in keys]

    def close(self):
        pass


def run_perrating(table, ratings, k, version, bias):
    zero = ";".join(["0.0"] * k)
    step = SGDStep(table.get, zero, zero, version=version, update_bias=bias)
    rows = []
    t0 = time.perf_counter()
    for u, i, r in ratings:
        rows.extend(step.process(u, i, r))
    return rows, time.perf_counter() - t0


def run_batched(table, ratings, k, batch_size, version, bias):
    zero = ";".join(["0.0"] * k)
    step = SGDStep(
        table.get, zero, zero, version=version, update_bias=bias,
        lookup_many=lambda keys: [table.get(key) for key in keys],
    )
    rows = []
    t0 = time.perf_counter()
    for s in range(0, len(ratings), batch_size):
        rows.extend(step.process_batch(ratings[s:s + batch_size]))
    return rows, time.perf_counter() - t0


def run_colocated(table, ratings, k, workers, partitions, batch_size,
                  version, bias):
    with tempfile.TemporaryDirectory() as tmp:
        cli = up.UpdatePlaneClient(tmp, "models", partitions=partitions)
        fleet = [up.UpdateWorker(
            tmp, "models", w, workers, table=table,
            client_factory=lambda: TableClient(table),
            partitions=partitions, batch_size=batch_size, poll_s=0.001,
            dim=k, version=version, update_bias=bias,
            visibility_probe=False,
        ).start() for w in range(workers)]
        t0 = time.perf_counter()
        cli.submit_many(ratings)
        deadline = t0 + 300
        while time.perf_counter() < deadline:
            wm = up.applied_watermarks(tmp, "models", partitions)
            if sum(wm.values()) >= len(ratings):
                break
            time.sleep(0.002)
        dt = time.perf_counter() - t0
        for w in fleet:
            w.stop()
        rows = []
        from flink_ms_tpu.serve.journal import Journal
        for p in range(partitions):
            for ln in up._read_all_lines(
                    Journal(tmp, up.apply_topic("models", p))):
                fields = ln.split("\t", 3)
                if len(fields) > 3 and fields[3]:
                    rows.extend(fields[3].split("|"))
        audit = up.audit_partitions(tmp, "models", partitions)
        assert audit["clean"], f"PARITY FAILURE: audit not clean: {audit}"
        return rows, dt


def main(argv=None) -> None:
    params = Params.from_args(sys.argv[1:] if argv is None else argv)
    n = params.get_int("ratings", 50_000)
    k = params.get_int("k", 8)
    workers = params.get_int("workers", 4)
    batch_size = params.get_int("batchSize", 256)
    partitions = params.get_int("partitions", 16)

    # -- parity first: all three arms, byte-identical rows, all semantics --
    print("[update-profile] parity check (v1 / v0 / bias)...",
          file=sys.stderr)
    ptable = build_table(512, k)
    pratings = build_ratings(512)
    for version, bias in (("v1", False), ("v0", False), ("v1", True)):
        ref, _ = run_perrating(ptable, pratings, k, version, bias)
        bat, _ = run_batched(ptable, pratings, k, 64, version, bias)
        col, _ = run_colocated(ptable, pratings, k, workers, partitions,
                               64, version, bias)
        assert sorted(bat) == sorted(ref), \
            f"PARITY FAILURE: batched != per-rating ({version} bias={bias})"
        assert sorted(col) == sorted(ref), \
            f"PARITY FAILURE: co-located != per-rating ({version} bias={bias})"
    print("[update-profile] parity OK", file=sys.stderr)

    # -- timing arms (v1, unbiased — the default closed-loop shape) --
    table = build_table(n, k)
    ratings = build_ratings(n)
    res = {}
    rows, dt = run_perrating(table, ratings, k, "v1", False)
    res["perrating"] = n / dt
    print(f"{'perrating':>10}: {n / dt:>12,.0f} ratings/s "
          f"({len(rows)} rows, {dt:.2f}s)")
    rows, dt = run_batched(table, ratings, k, batch_size, "v1", False)
    res["batched"] = n / dt
    print(f"{'batched':>10}: {n / dt:>12,.0f} ratings/s "
          f"({len(rows)} rows, batch={batch_size}, {dt:.2f}s)")
    rows, dt = run_colocated(table, ratings, k, workers, partitions,
                             batch_size, "v1", False)
    res["colocated"] = n / dt
    print(f"{'colocated':>10}: {n / dt:>12,.0f} ratings/s "
          f"({len(rows)} rows, {workers} workers x {partitions} "
          f"partitions, {dt:.2f}s)")
    print(f"colocated vs perrating: "
          f"{res['colocated'] / res['perrating']:.2f}x | vs batched: "
          f"{res['colocated'] / res['batched']:.2f}x")


if __name__ == "__main__":
    main()
