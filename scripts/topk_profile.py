#!/usr/bin/env python
"""Profile the top-k scoring engine on the current backend.

Times the XLA matmul + ``jax.lax.top_k`` path at serving-relevant catalog
sizes (26k ≈ ML-20M items, 1M ≈ BASELINE scale envelope) on whichever
backend the device rule hands out (the chip, or JAX_PLATFORMS=cpu).

  python scripts/topk_profile.py [--items N ...] [--rank K] [--topk T]
"""

import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--items", type=int, nargs="*", default=[26_744, 1_000_000])
    ap.add_argument("--rank", type=int, default=50)
    ap.add_argument("--topk", type=int, default=10)
    ap.add_argument("--reps", type=int, default=20)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from flink_ms_tpu.parallel.mesh import acquire_devices

    acquire_devices()

    rng = np.random.default_rng(0)
    for n in args.items:
        k = args.rank
        matrix = rng.standard_normal((n, k)).astype(np.float32)
        md = jnp.asarray(matrix)

        @jax.jit
        def xla_topk(m, q):
            scores = m @ q
            return jax.lax.top_k(scores, args.topk)

        def run_xla(q):
            t0 = time.time()
            s, _ = xla_topk(md, q)
            jax.block_until_ready(s)
            return time.time() - t0

        qs = [jnp.asarray(rng.standard_normal(k).astype(np.float32))
              for _ in range(args.reps)]
        run_xla(qs[0])  # warmup/compile
        tx = sorted(run_xla(q) for q in qs)[len(qs) // 2]
        print(f"items={n:>9,} rank={k}: xla {tx * 1e3:7.3f} ms/query")


if __name__ == "__main__":
    main()
