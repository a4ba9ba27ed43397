#!/usr/bin/env python
"""ALS kernel microbenchmark: assembly + solve variants on the current
backend.

Times one full compiled sweep (steady-state, hard-sync barrier) across the
solver (unrolled vs lax) and assembly-precision (highest/high/default)
axes, at a configurable scale.  Used to pick kernel defaults on real
hardware; safe to run on CPU for smoke.

  python scripts/als_microbench.py [--small] [--nnz N] [--rank K]
"""

import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def run_breakdown(A_mod, problem, cfg, mesh, dev_args):
    """Time the user half-sweep's phases separately on one device: the
    opposite-factor gather, the full normal-equation assembly, and the
    batched Cholesky solve.  Isolates where a sweep's wall-clock goes so
    kernel work targets the real bottleneck (single-device layout: dev_args
    leading block axis is 1)."""
    import jax
    import jax.numpy as jnp

    k = cfg.num_factors
    n_u_buckets = len(problem.u.widths)
    itf0 = dev_args[1]
    u_flat = dev_args[2:2 + 2 * n_u_buckets + 1]
    *bucket_args, counts = u_flat
    y_all = itf0[0]
    platform = mesh.devices.flat[0].platform

    @jax.jit
    def gather_only(y_all, *bs):
        # one pass of the raw opposite-factor gathers, reduced to force
        # materialization — row chunked with the SAME bound and transient
        # factor _bucket_normal_eqs uses, so the probe's scan overhead
        # matches the assembly row it is compared against (a full-bucket
        # gather at ML-20M scale RESOURCE_EXHAUSTs a 16 GB chip)
        limit = A_mod._assembly_chunk_bytes()
        transients = 2 if cfg.implicit else 1
        tot = jnp.zeros((), y_all.dtype)
        for j in range(n_u_buckets):
            idx = bs[2 * j]
            w = idx.shape[1]
            C = max(
                min(int(limit // (transients * w * k * 4)), idx.shape[0]), 1
            )
            tot = tot + jax.lax.map(
                lambda ic: jnp.take(y_all, ic, axis=0).sum(),
                idx, batch_size=C,
            ).sum()
        return tot

    @jax.jit
    def assemble_only(y_all, *bs):
        bl = [(bs[2 * j], bs[2 * j + 1])
              for j in range(n_u_buckets)]
        A, b = A_mod._assemble_normal_eqs(
            y_all, bl, cfg.implicit, cfg.alpha, cfg.dtype,
            precision=cfg.assembly_precision,
        )
        return A.sum() + b.sum()

    @jax.jit
    def solve_only(A, b, counts):
        x = A_mod._solve_factors(
            A, b, counts, cfg.lambda_, cfg.weighted_reg, cfg.dtype,
            platform,
        )
        return x

    @jax.jit
    def assemble_full(y_all, *bs):
        bl = [(bs[2 * j], bs[2 * j + 1])
              for j in range(n_u_buckets)]
        return A_mod._assemble_normal_eqs(
            y_all, bl, cfg.implicit, cfg.alpha, cfg.dtype,
            precision=cfg.assembly_precision,
        )

    flat_bufs = [a[0] for a in bucket_args]

    def timeit(fn, *args_):
        jax.block_until_ready(fn(*args_))
        reps = 5
        t0 = time.time()
        for _ in range(reps):
            out = fn(*args_)
        jax.block_until_ready(out)
        return (time.time() - t0) / reps

    t_gather = timeit(gather_only, y_all, *flat_bufs)
    t_asm = timeit(assemble_only, y_all, *flat_bufs)
    A, b = assemble_full(y_all, *flat_bufs)
    jax.block_until_ready(A)
    t_solve = timeit(solve_only, A, b, counts[0])
    print(
        f"user half-sweep breakdown (k={k}):\n"
        f"  gather-only   : {t_gather * 1e3:9.2f} ms\n"
        f"  assembly (A,b): {t_asm * 1e3:9.2f} ms  (incl. gather)\n"
        f"  solve         : {t_solve * 1e3:9.2f} ms  "
        f"(batch {int(counts.shape[1])})"
    )


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--small", action="store_true")
    ap.add_argument("--nnz", type=int, default=None)
    ap.add_argument("--users", type=int, default=None)
    ap.add_argument("--items", type=int, default=None)
    ap.add_argument("--rank", type=int, default=None)
    ap.add_argument("--breakdown", action="store_true",
                    help="time gather/assembly/solve phases separately")
    ap.add_argument("--solvers", default="unrolled,lax")
    ap.add_argument("--precisions", default="highest,high,default")
    ap.add_argument("--exchange", default="f32", choices=["f32", "bf16"],
                    help="factor-exchange dtype (bf16 halves gather bytes)")
    args = ap.parse_args()

    small = args.small
    nnz = args.nnz or (500_000 if small else 20_000_000)
    n_users = args.users or (20_000 if small else 138_493)
    n_items = args.items or (2_000 if small else 26_744)
    rank = args.rank or (16 if small else 50)

    import jax
    import jax.numpy as jnp

    from flink_ms_tpu.ops import als as A
    from flink_ms_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(1)  # by the device rule: the chip, or JAX_PLATFORMS=cpu

    rng = np.random.default_rng(0)
    users = rng.integers(0, n_users, nnz)
    items = rng.integers(0, n_items, nnz)
    ratings = rng.uniform(1.0, 5.0, nnz)
    t0 = time.time()
    problem = A.prepare_blocked(users, items, ratings, 1)
    print(f"prepare_blocked: {time.time() - t0:.1f}s  "
          f"(u widths={problem.u.widths}, i widths={problem.i.widths})")

    # dev_args depend only on (problem, dtype): upload once, reuse across
    # all solver/precision variants (only the compiled sweep differs)
    base_cfg = A.ALSConfig(num_factors=rank, iterations=1, lambda_=0.1)
    _, dev_args = A.compile_fit(problem, base_cfg, mesh)

    if args.breakdown:
        run_breakdown(A, problem, base_cfg, mesh, dev_args)

    def steady(cfg):
        fit_fn = A._cached_sweep(problem, cfg, mesh)

        def run(trip):
            t = time.time()
            uf, _ = fit_fn(jnp.asarray(trip, jnp.int32), *dev_args)
            jax.block_until_ready(uf)
            return time.time() - t

        run(1), run(4)  # compile + warmup
        iters = 4
        while run(iters) < 0.5 and iters < 20_000:
            iters *= 4
        samples = sorted(
            max((run(iters) - run(1)) / (iters - 1), 1e-9) for _ in range(3)
        )
        return samples[1]

    valid_solvers = {"unrolled", "panel", "lax", "pallas", "auto"}
    solvers = args.solvers.split(",")
    unknown = [s for s in solvers if s not in valid_solvers]
    if unknown:
        ap.error(f"unknown solver(s) {unknown}; choose from {sorted(valid_solvers)}")
    for solver in solvers:
        os.environ["FLINK_MS_ALS_SOLVER"] = solver
        for precision in args.precisions.split(","):
            cfg = A.ALSConfig(
                num_factors=rank, iterations=1, lambda_=0.1,
                assembly_precision=precision,
                exchange_dtype=(
                    "bfloat16" if args.exchange == "bf16" else None
                ),
            )
            spi = steady(cfg)
            flops = 2 * nnz * (2 * rank * rank + 2 * rank) + (
                n_users + n_items
            ) * (rank ** 3 / 3 + 4 * rank * rank)
            print(
                f"solver={solver:8s} precision={precision:8s} "
                f"exch={args.exchange}: "
                f"{spi * 1e3:9.2f} ms/iter  "
                f"({flops / spi / 1e12:6.2f} TFLOP/s analytic)"
            )


if __name__ == "__main__":
    main()
