"""Open loop: TOPKV requests on a constant-gap schedule at a fixed rate.
This process owns the chip and runs the program's lookup server in-process;
`benchmark/loadgen.py` is its client.

Set-up makes the catalog from the seed, installs it with
`DeviceFactorIndex.bulk_load`, warms every batch shape, starts
`LookupServer`, and starts the load generator, which runs `lead_s` of load
before the window opens.  After the window a seeded sample of the queries it
answered is answered again by `reference.topk` over the host copy.
"""

from __future__ import annotations

import contextlib
import json
import os
import subprocess
import sys
import time

import numpy as np

from benchmark import reference, synth

STATE = "ALS_MODEL"
REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def run(run):
    with serving(run) as (server, rows):
        out = offer(run, server)
    check(run, run.config, rows, out)


@contextlib.contextmanager
def serving(run):
    """Set-up: catalog from the seed, index, warm programs, lookup server.
    Yields (server, the host copy of the catalog) and tears both down."""
    cfg = run.config
    os.environ.update(cfg.get("env", {}))  # read by the index at construction
    run.acquire()
    run.apply_patches()
    from flink_ms_tpu.serve.server import LookupServer
    from flink_ms_tpu.serve.table import ModelTable
    from flink_ms_tpu.serve.topk import make_als_topk_handler

    k, rank = cfg["k"], cfg["rank"]
    with run.span("catalog_synth_s"):
        ids, rows = synth.catalog(cfg, run.seed)
    table = ModelTable()
    handler = make_als_topk_handler(table)
    server = None
    try:
        with run.span("index_build_s"):
            handler.index.bulk_load(ids, rows)
        del ids
        with run.span("warm_s"):
            # every program the batcher can pick: the single-query one and
            # the padded frames 1, 2, 4 ... max_batch
            handler.index.topk(np.zeros(rank, np.float32), k)
            handler.index.warm_batch_shapes(k, handler.batcher.max_batch)
        server = LookupServer({STATE: table}, host="127.0.0.1", port=0,
                              topk_handlers={STATE: handler}).start()
        yield server, rows
    finally:
        if server is not None:
            server.stop()
        handler.close()


def offer(run, server):
    """One window of load from the child process -> path of its records."""
    cfg, traffic = run.config, run.traffic
    spec = dict(traffic, host="127.0.0.1", port=server.port,
                state=STATE, k=cfg["k"], rank=cfg["rank"], seed=run.seed,
                seconds=run.seconds,
                out=os.path.join(run.work_dir, "samples.npz"))
    spec_path = os.path.join(run.work_dir, "loadgen.json")
    with open(spec_path, "w") as f:
        json.dump(spec, f)
    env = {key: v for key, v in os.environ.items() if not key.startswith("JAX")}
    child = subprocess.Popen(
        [sys.executable, "-m", "benchmark.loadgen", spec_path],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
        cwd=REPO,
        env=env)
    try:
        if child.stdout.readline().strip() != "ready":
            raise RuntimeError("load generator did not come up")
        run.start_trace()
        t_open = time.perf_counter() + traffic["lead_s"] + 0.2
        child.stdin.write(f"{t_open!r}\n")
        child.stdin.flush()
        time.sleep(max(0.0, t_open - time.perf_counter()))
        run.begin_window(at=t_open)
        t_close = t_open + run.seconds
        time.sleep(max(0.0, t_close - time.perf_counter()))
        run.end_window(at=t_close)
        rc = child.wait(timeout=traffic["drain_s"] + 30)
        if rc != 0:
            raise RuntimeError(f"load generator exited {rc}")
    finally:
        if child.poll() is None:
            child.kill()
            child.wait()
    run.counts["frames"] = run.hist_delta("tpums_topk_device_seconds")[1]
    reduce_samples(run, spec["out"])
    return spec["out"]


def reduce_samples(run, path):
    """Raw per-request records -> the series the readers take statistics of.
    A request belongs to the window by its intended send time."""
    s = np.load(path)
    t_open, t_close = run.window
    mine = (s["intended"] >= t_open) & (s["intended"] < t_close)
    answered = mine & (s["done"] > 0) & (s["ok"] == 1)
    run.attempted = int(mine.sum())
    run.failed = int((mine & ~answered).sum())
    run.series["latency_ms"] = (s["done"] - s["intended"])[answered] * 1e3
    run.series["lag_ms"] = (s["sent"] - s["intended"])[mine] * 1e3


def check(run, cfg, rows, path):
    """`check_queries` of the pool's vectors that the window answered, drawn
    from the seed, against the blockwise numpy top-k of the host catalog."""
    lim = cfg["limits"]
    with open(path + ".replies.json") as f:
        replies = json.load(f)
    vectors = synth.queries(run.seed, run.traffic["pool"], cfg["rank"])
    rng = np.random.default_rng([run.seed, 3])
    answered = np.array(sorted(int(i) for i in replies))
    picked = rng.choice(answered, min(cfg["check_queries"], len(answered)),
                        replace=False)
    got_ids, got_scores = [], []
    for i in picked:
        pairs = [t.rpartition(":") for t in replies[str(i)][2:].split(";")]
        got_ids.append([int(item) - 1 for item, _, _ in pairs])
        got_scores.append([float(score) for _, _, score in pairs])
    ref_ids, ref_scores = reference.topk(rows, vectors[picked], cfg["k"])
    err, wrong, clear = reference.compare_topk(
        np.array(got_ids), np.array(got_scores), ref_ids, ref_scores,
        lim["topk_gap"])
    run.counts["checked_ranks"] = clear
    run.check("topk_score_abs_err", err, lim["topk_score_abs_err"])
    run.check("topk_wrong_ids_at_clear_ranks", wrong, 0)
    run.check("topk_checked_queries", len(picked), 1, at_least=True)
