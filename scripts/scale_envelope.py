#!/usr/bin/env python
"""Scale-envelope measurement: the BASELINE.json "model-generator synthetic
(10M x 1M, rank=64)" config — generation + block-ALS throughput at a
catalog whose normal-equation tensor (10M x 64 x 64 x 4 B = 163 GB) can
never materialize in HBM.  Requires FLINK_MS_ALS_FUSED=1 (forced on here):
fused assembly+solve bounds the transient at the chunk size instead.

Run on the chip (one command through the chip tool); start with the
defaults below (half-scale) before attempting SCALE_USERS=10000000.

  SCALE_USERS=5000000 SCALE_ITEMS=500000 SCALE_NNZ=50000000 SCALE_RANK=64 \
      python scripts/scale_envelope.py

Prints one JSON line: prep_s, sec_per_iter, gen_rows_per_sec (device-RNG
rating synthesis), hbm-relevant config echo.
"""

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ["FLINK_MS_ALS_FUSED"] = "1"

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from flink_ms_tpu.ops.als import ALSConfig, compile_fit, prepare_blocked  # noqa: E402
from flink_ms_tpu.parallel.mesh import make_mesh  # noqa: E402

N_USERS = int(os.environ.get("SCALE_USERS", 5_000_000))
N_ITEMS = int(os.environ.get("SCALE_ITEMS", 500_000))
NNZ = int(os.environ.get("SCALE_NNZ", 50_000_000))
RANK = int(os.environ.get("SCALE_RANK", 64))
ITERS = int(os.environ.get("SCALE_ITERS", 2))


def main():
    out = {"users": N_USERS, "items": N_ITEMS, "nnz": NNZ, "rank": RANK}
    t0 = time.time()
    rng = np.random.default_rng(0)
    users = rng.integers(0, N_USERS, NNZ)
    items = rng.integers(0, N_ITEMS, NNZ)
    ratings = rng.uniform(1.0, 5.0, NNZ)
    out["gen_rows_per_sec"] = round(NNZ / (time.time() - t0))

    mesh = make_mesh()  # by the device rule: every chip, or JAX_PLATFORMS=cpu
    out["platform"] = mesh.devices.flat[0].platform
    out["device_kind"] = mesh.devices.flat[0].device_kind
    out["n_devices"] = int(mesh.devices.size)

    t0 = time.time()
    problem = prepare_blocked(users, items, ratings, mesh.devices.size)
    out["prep_s"] = round(time.time() - t0, 1)
    print(f"prepare_blocked: {out['prep_s']}s", file=sys.stderr)

    cfg = ALSConfig(num_factors=RANK, iterations=1, lambda_=0.1, seed=3)
    fit, dev_args = compile_fit(problem, cfg, mesh)

    def run(trip):
        t = time.time()
        uf, _ = fit(jnp.asarray(trip, jnp.int32), *dev_args)
        jax.block_until_ready(uf)
        return time.time() - t

    run(1)  # compile + warmup
    t1, tn = run(1), run(max(ITERS, 2))
    out["sec_per_iter"] = round(
        max((tn - t1) / (max(ITERS, 2) - 1), 1e-9), 4
    )
    print(json.dumps(out))


if __name__ == "__main__":
    main()
