"""Open loop with independent arrivals: TOPKV requests as a Poisson process
of a fixed mean rate.  `topk_open.py`'s set-up, reduction and check as they
stand; only the window's child is `benchmark/loadgen_poisson.py` (the
sibling's `offer` names its load generator inline, and a PR may not edit
it)."""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

from benchmark.drivers.topk_open import (
    REPO, STATE, check, reduce_samples, serving)


def run(run):
    with serving(run) as (server, rows):
        out = offer(run, server)
    check(run, run.config, rows, out)


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
        [sys.executable, "-m", "benchmark.loadgen_poisson", spec_path],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True, cwd=REPO,
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
