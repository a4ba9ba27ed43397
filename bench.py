#!/usr/bin/env python
"""Headline benchmark: blocked-ALS training throughput (sec/iter) at
MovieLens-20M scale, rank 50 — the BASELINE.md north-star config — plus
roofline (MFU) accounting.

Prints ONE COMPACT JSON line (headline keys only):
  {"metric": "als_ml20m_sec_per_iter", "value": N, "unit": "s/iter",
   "vs_baseline": R, "mfu": F, "platform": "...", "device_kind": "...", ...}
and writes every section key to the BENCH_DETAIL.json sidecar next to this
file (the compact line stays small enough for a log tail; the sidecar
carries the rest).

Device policy: the benchmark runs where ``parallel.mesh.acquire_devices``
says — it FAILS on a host with no accelerator unless ``JAX_PLATFORMS=cpu``
asked for the host, in which case every line says ``cpu`` and no device
metric (MFU) is reported.  A failed section records its ``<name>_error``
key, the line is still printed, and the exit code is non-zero.

The reference publishes no numbers (BASELINE.md), so the comparison baseline
is measured in-process: the identical XLA program on the host CPU backend
(all cores — the single-machine stand-in for the reference's TaskManager
cluster), timed at a reduced nnz and scaled linearly to the full config.
vs_baseline > 1 means the TPU path is that many times faster. Override via
env BENCH_BASELINE_SEC_PER_ITER to pin an externally measured Flink baseline.

Env knobs: BENCH_NNZ, BENCH_USERS, BENCH_ITEMS, BENCH_RANK, BENCH_ITERS,
BENCH_SMALL=1 (quick sanity config), BENCH_SKIP_CPU=1,
BENCH_SECTIONS (comma list: als,svm,serving,svmserve,serving_ingest,
serving_ha,serving_elastic,serving_rehearsal,serving_bootstrap,
serving_native,serving_update_plane,serving_rollout,serving_ann,
serving_watch,serving_autopilot,serving_forensics,serving_geo,
serving_arena,serving_arena_ingest,serving_edge,serving_profiler,
serving_push; default all),
BENCH_PUSH_UPDATES / BENCH_PUSH_FANOUT / BENCH_PUSH_TOPK_SUBS /
BENCH_PUSH_SEL_UPDATES (push plane: update->push p99, edge fan-out
amplification, TOPK re-score selectivity under zipf updates),
BENCH_ANN_ROWS_EXACT / BENCH_ANN_ROWS_IVF / BENCH_ANN_ARM_TIMEOUT_S
(retrieval-plane A/B arm sizes: sharded-exact question at 1M rows,
IVF question at 10M, recall@100 >= 0.95 gate recorded),
BENCH_UPDATE_USERS / BENCH_UPDATE_FLEET_RATINGS / BENCH_UPDATE_BATCH /
BENCH_UPDATE_PROBES (online update plane: fleet updates/s vs the
single-consumer baseline, 2->4 reshard audit, submit->queryable p99),
BENCH_NATIVE_KEYS / BENCH_NATIVE_GETS / BENCH_NATIVE_TOPKS /
BENCH_NATIVE_ITEMS (serving-native tab-vs-B2 wire protocol A/B scale),
BENCH_INGEST_ROWS /
BENCH_INGEST_K / BENCH_INGEST_PROP_PROBES (serving-ingest replay scale),
BENCH_HA_USERS / BENCH_HA_DURATION_S / BENCH_HA_WORKERS /
BENCH_HA_HEARTBEAT_S / BENCH_HA_TTL_S (serving-HA kill-a-replica arms),
BENCH_ELASTIC_USERS / BENCH_ELASTIC_WINDOW_S (serving-elastic live
2->4 rescale: p50/p99 before/during/after + cutover duration),
BENCH_HA_RATE_QPS / BENCH_ELASTIC_RATE_QPS (open-loop pacing of the
HA/elastic query arms; latency recorded from intended send time),
BENCH_REHEARSAL_* (closed-loop SLO rehearsal: SHARDS / REPLICATION /
USERS / BASE_QPS / PEAK_QPS / BURST_QPS / THREADS / AUTOSCALE / KILL /
OUT — emits SLO_REPORT.json, see obs/workload.py),
BENCH_BOOTSTRAP_* (KEYS / BASE_ROWS / MULTS / DIM: snapshot-shipped
bootstrap flatness — cold replay-vs-snapshot, elastic 2->4 cutover
with snapshots on/off, HA respawn recovery, each at MULTS x journal),
BENCH_ALS_PRECISION / BENCH_ALS_EXCHANGE (kernel-config A/B),
BENCH_SKIP_QUALITY=1 / BENCH_RMSE_REF_NNZ / BENCH_RMSE_REF_ITERS (ALS
quality anchor), BENCH_SVM_TARGET / BENCH_SVM_REF_ROUNDS / BENCH_SVM_FLIP
(SVM anchor + label noise), BENCH_DETAIL_PATH (sidecar).
"""

import contextlib
import json
import os
import sys
import time
import traceback

import numpy as np


def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# roofline model
# ---------------------------------------------------------------------------

# bf16 MXU peak per chip (the systolic-array ceiling MFU is judged against;
# fp32 work lowers to bf16 passes on the MXU, so this is the honest
# denominator).  Keyed by substring of jax device_kind, first match wins.
_PEAK_FLOPS_BY_KIND = (
    ("v6", 918e12),       # Trillium / v6e
    ("v5p", 459e12),
    ("v5 lite", 197e12),  # v5e reports "TPU v5 lite"
    ("v5e", 197e12),
    ("v5", 459e12),
    ("v4", 275e12),
    ("v3", 123e12),
    ("v2", 45e12),
)


def peak_flops_per_device(device) -> float:
    """Published bf16 peak of one device, by ``device_kind``.  A kind that
    is not in the table is an error, not a default: an MFU against a
    guessed peak is not a measurement."""
    kind = device.device_kind.lower()
    for sub, peak in _PEAK_FLOPS_BY_KIND:
        if sub in kind:
            return peak
    raise ValueError(
        f"no published peak for device_kind {device.device_kind!r}; add it "
        "to _PEAK_FLOPS_BY_KIND with its source"
    )


def als_flops_per_iter(nnz: int, n_users: int, n_items: int, k: int) -> float:
    """Analytic FLOPs of one full ALS iteration (both half-sweeps).

    Per half-sweep over the opposite-side factors Y:
      assembly  A_u += y yᵀ, b_u += r·y per rating: 2k² + 2k flops per nnz
      solve     per entity: Cholesky k³/3 + two triangular solves 2·2k²
    Both orientations touch every rating once, and every user and item row
    gets one solve per iteration."""
    assembly = 2 * nnz * (2 * k * k + 2 * k)
    solves = (n_users + n_items) * (k ** 3 / 3 + 4 * k * k)
    return float(assembly + solves)


# ---------------------------------------------------------------------------
# ALS section
# ---------------------------------------------------------------------------

def synth_ratings(n_users, n_items, nnz, seed=0, skew=None):
    """Synthetic ratings.  BENCH_SKEW=zipf (or skew="zipf") draws item
    popularity and user activity from heavy-tailed marginals (Zipf-like
    s~1, the real MovieLens-20M shape — wide degree spread stresses the
    kernel's bucket padding); default is uniform (the round-2 recorded
    workload)."""
    rng = np.random.default_rng(seed)
    if skew is None:
        skew = os.environ.get("BENCH_SKEW", "")
    if skew == "zipf":
        # bounded zipf via inverse-CDF over the ranked catalog
        def zipf_draw(n_ids, size, s=1.0):
            w = 1.0 / np.arange(1, n_ids + 1) ** s
            cdf = np.cumsum(w)
            cdf /= cdf[-1]  # exact 1.0 at the end: no out-of-range draw
            return np.searchsorted(cdf, rng.uniform(size=size)).astype(np.int64)

        users = zipf_draw(n_users, nnz, s=0.7)   # user activity: milder tail
        items = zipf_draw(n_items, nnz, s=1.0)   # item popularity: zipf-1
    else:
        users = rng.integers(0, n_users, nnz)
        items = rng.integers(0, n_items, nnz)
    ratings = rng.uniform(1.0, 5.0, nnz)
    return users, items, ratings


def time_fit(mesh, problem, cfg_base, iters, repeats=5):
    """Steady-state sec/iter on the compiled sweep with device-resident
    inputs: same executable (dynamic trip count) timed at 1 iteration and at
    `iters`; the difference isolates per-iter cost from dispatch overhead.
    Host<->device transfer happens once, outside the timed region; every
    timed call ends in ``block_until_ready``.  Median over `repeats`."""
    import jax
    import jax.numpy as jnp

    from flink_ms_tpu.ops.als import compile_fit

    iters = max(iters, 2)  # need two points to isolate per-iter cost
    fit_fn, dev_args = compile_fit(problem, cfg_base, mesh)

    def run(trip):
        t0 = time.time()
        uf, itf = fit_fn(jnp.asarray(trip, jnp.int32), *dev_args)
        jax.block_until_ready(uf)
        return time.time() - t0

    # same executable for every trip count (dynamic while_loop bound), so
    # amplify until the timed region dwarfs dispatch noise (>= 0.5 s)
    run(1), run(iters)  # compile + warmup
    while run(iters) < 0.5 and iters < 20_000:
        iters *= 4
    samples = []
    for _ in range(repeats):
        t1 = run(1)
        tn = run(iters)
        samples.append(max((tn - t1) / (iters - 1), 1e-9))
    samples.sort()
    return samples[len(samples) // 2]


def run_als_section(devices, platform, small: bool) -> dict:
    import jax

    from flink_ms_tpu.ops.als import (ALSConfig,
                                      _exchange_plan as _exchange_plan_fn,
                                      prepare_blocked, resolve_exchange,
                                      resolve_solver)
    from flink_ms_tpu.parallel.mesh import host_device, make_mesh

    n_users = int(os.environ.get("BENCH_USERS", 20_000 if small else 138_493))
    n_items = int(os.environ.get("BENCH_ITEMS", 2_000 if small else 26_744))
    nnz = int(os.environ.get("BENCH_NNZ", 500_000 if small else 20_000_000))
    rank = int(os.environ.get("BENCH_RANK", 16 if small else 50))
    iters = int(os.environ.get("BENCH_ITERS", 3 if small else 5))

    skew = os.environ.get("BENCH_SKEW", "") or "uniform"
    users, items, ratings = synth_ratings(n_users, n_items, nnz)
    # kernel-config A/B knobs (the solver knob is FLINK_MS_ALS_SOLVER, read
    # inside the kernel): the on-chip sweep flips these per run, and the
    # quality anchor inherits them so a flipped default is convergence-
    # checked in the same artifact that times it
    exch_env = os.environ.get("BENCH_ALS_EXCHANGE") or "auto"
    if exch_env.lower() in ("f32", "float32", "none", "full"):
        exch_env = None  # explicit full precision (jnp.dtype("f32") would
        # otherwise fail at trace time deep inside the sweep)
    elif exch_env.lower() == "bf16":
        exch_env = "bfloat16"
    cfg = ALSConfig(
        num_factors=rank, iterations=1, lambda_=0.1, seed=42,
        assembly_precision=os.environ.get("BENCH_ALS_PRECISION", "highest"),
        exchange_dtype=exch_env,
    )
    mesh = make_mesh(devices=devices)
    _log(f"[bench] ALS devices: {devices}, nnz={nnz}, rank={rank}")

    t0 = time.time()
    problem = prepare_blocked(users, items, ratings, mesh.devices.size)
    _log(f"[bench] prepare_blocked: {time.time() - t0:.1f}s")

    sec_per_iter = time_fit(mesh, problem, cfg, iters)
    _log(f"[bench] {platform} steady-state: {sec_per_iter:.3f} s/iter")

    flops = als_flops_per_iter(nnz, n_users, n_items, rank)
    mfu = None  # a device metric: never computed from a host run
    if platform != "cpu":
        peak = peak_flops_per_device(devices[0]) * len(devices)
        mfu = (flops / sec_per_iter) / peak
        _log(f"[bench] {flops / 1e9:.1f} GFLOP/iter -> "
             f"{flops / sec_per_iter / 1e12:.2f} TFLOP/s, MFU {mfu:.4f}")

    # the host stand-ins need a CPU backend in this process; under
    # JAX_PLATFORMS=tpu there is none, which reads as BENCH_SKIP_CPU=1
    cpu_dev = host_device()
    skip_cpu = os.environ.get("BENCH_SKIP_CPU") == "1" or cpu_dev is None
    baseline_env = os.environ.get("BENCH_BASELINE_SEC_PER_ITER")
    if baseline_env:
        baseline = float(baseline_env)
    elif skip_cpu or platform == "cpu":
        baseline = sec_per_iter  # vs_baseline = 1.0, no comparison available
    else:
        # CPU stand-in baseline at reduced nnz, scaled linearly to full nnz
        cpu_nnz = min(nnz, 2_000_000)
        cpu_mesh = make_mesh(devices=[cpu_dev])
        cu, ci, cr = users[:cpu_nnz], items[:cpu_nnz], ratings[:cpu_nnz]
        cpu_problem = prepare_blocked(cu, ci, cr, 1)
        cpu_spi = time_fit(cpu_mesh, cpu_problem, cfg, 2, repeats=3)
        baseline = cpu_spi * (nnz / cpu_nnz)
        _log(
            f"[bench] CPU stand-in: {cpu_spi:.3f} s/iter @ {cpu_nnz} nnz "
            f"-> scaled {baseline:.3f} s/iter @ {nnz}"
        )

    out = {
        "metric": "als_ml20m_sec_per_iter" if not small else "als_small_sec_per_iter",
        "value": round(sec_per_iter, 6),
        "unit": "s/iter",
        "vs_baseline": round(baseline / sec_per_iter, 3),
        "mfu": round(mfu, 5) if mfu is not None else None,
        "als_flops_per_iter": flops,
        "als_tflops_per_sec": round(flops / sec_per_iter / 1e12, 3),
        "als_nnz": nnz,
        "als_rank": rank,
        "workload_skew": skew,
        # kernel config forensics: which solver/precision/ladder produced
        # this number (env-driven knobs, baked in at trace time)
        "als_solver": resolve_solver(platform),
        "als_assembly_precision": cfg.assembly_precision,
        "als_bucket_ratio": os.environ.get("FLINK_MS_ALS_BUCKET_RATIO", "1.5"),
        "als_fused": os.environ.get("FLINK_MS_ALS_FUSED", "0"),
        "als_exchange_dtype": resolve_exchange(cfg.exchange_dtype, platform) or "f32",
        # per-half-sweep exchange plan (routed all_to_all vs gather)
        "als_exchange_mode": {
            name: ("routed" if r is not None else "gather")
            for name, r in _exchange_plan_fn(problem, len(devices)).items()
        },
    }

    # BASELINE.json config "als-ms implicit-feedback ALS (confidence-
    # weighted) on MovieLens-20M": same problem layout, HKV mode (psum'd
    # Gramian + confidence-weighted assembly).  Skipped in BENCH_SMALL
    # sanity mode — the key names the ML-20M config and the extra timed
    # section would double the quick run's wall-clock.
    if not small:
        try:
            import dataclasses as _dc

            cfg_imp = _dc.replace(cfg, implicit=True, alpha=40.0)
            spi_imp = time_fit(mesh, problem, cfg_imp, iters)
            out["als_implicit_sec_per_iter"] = round(spi_imp, 6)
            _log(f"[bench] implicit mode: {spi_imp:.3f} s/iter")
        except Exception:
            _log(traceback.format_exc())
            out["als_implicit_error"] = traceback.format_exc(limit=3)

    # exchange-dtype A/B (accelerator runs only, BENCH_ALS_BF16_AB=0 to
    # skip): time the OPPOSITE exchange dtype of whatever the timed config
    # resolved to — with the bf16-on-TPU default this records the f32
    # comparison (and under BENCH_ALS_EXCHANGE=bfloat16... the reverse),
    # so every chip artifact carries both sides of the default-flip
    # evidence; the quality anchor records the matching RMSE deltas
    if (not small and platform != "cpu"
            and os.environ.get("BENCH_ALS_BF16_AB", "1") != "0"):
        try:
            import dataclasses as _dc

            resolved = resolve_exchange(cfg.exchange_dtype, platform)
            alt = None if resolved else "bfloat16"
            alt_name = "f32" if alt is None else "bf16"
            cfg_alt = _dc.replace(cfg, exchange_dtype=alt)
            spi_alt = time_fit(mesh, problem, cfg_alt, max(2, iters - 2))
            out[f"als_{alt_name}_sec_per_iter"] = round(spi_alt, 6)
            _log(f"[bench] {alt_name} exchange variant: {spi_alt:.3f} "
                 f"s/iter (timed default: {sec_per_iter:.3f})")
        except Exception:
            _log(traceback.format_exc())
            out["als_exchange_ab_error"] = traceback.format_exc(limit=3)

    # quality anchor: the timed config's convergence, full scale + parity
    # delta vs the f64 reference (skippable: BENCH_SKIP_QUALITY=1)
    if os.environ.get("BENCH_SKIP_QUALITY") != "1":
        try:
            out.update(als_quality_anchor(
                mesh, problem, users, items, ratings, cfg, iters))
        except Exception:
            _log(traceback.format_exc())
            out["als_quality_error"] = traceback.format_exc(limit=3)

    # BASELINE.json config "flink-als explicit ALS rank=10 on
    # MovieLens-100K (single-node CPU)": the reference's own smallest
    # config shape, timed on one host-CPU device as the single-node
    # reference point
    if not small and not skip_cpu:
        try:
            # always uniform: this key mirrors the fixed BASELINE.json
            # reference shape regardless of BENCH_SKEW
            mu, mi, mr = synth_ratings(943, 1_682, 100_000, seed=1,
                                       skew="uniform")
            cfg100 = ALSConfig(num_factors=10, iterations=1, lambda_=0.1)
            cpu_mesh = make_mesh(devices=[cpu_dev])
            p100 = prepare_blocked(mu, mi, mr, 1)
            spi100 = time_fit(cpu_mesh, p100, cfg100, 3, repeats=3)
            out["als_ml100k_cpu_sec_per_iter"] = round(spi100, 6)
            _log(f"[bench] ML-100K rank-10 single-node CPU: {spi100:.4f} s/iter")
        except Exception:
            _log(traceback.format_exc())
            out["als_ml100k_error"] = traceback.format_exc(limit=3)

    return out


# ---------------------------------------------------------------------------
# ALS quality anchor: the north star is faster *at identical
# RMSE* — record the timed config's train RMSE, and its delta vs a float64
# reference solve on the same data + init at a capped parity scale
# ---------------------------------------------------------------------------

def run_rmse_ref(npz_path: str) -> None:
    """`bench.py --rmse-ref problem.npz`: float64 CPU reference fit.

    Runs in a subprocess because float64 needs jax_enable_x64, which must
    not leak into the benchmark process (it changes promotion semantics
    everywhere).  The caller sets JAX_ENABLE_X64=1 and JAX_PLATFORMS=cpu:
    this child is host-plane by the explicit ask, so it never contends
    for the chip its parent holds.  Prints one JSON line {"rmse_ref": x}."""
    import jax
    import jax.numpy as jnp

    from flink_ms_tpu.ops.als import ALSConfig, als_fit, rmse
    from flink_ms_tpu.parallel.mesh import make_mesh

    assert jax.config.jax_enable_x64, "--rmse-ref requires JAX_ENABLE_X64=1"
    d = np.load(npz_path)
    cfg = ALSConfig(
        num_factors=int(d["k"]), iterations=int(d["iters"]),
        lambda_=float(d["lam"]), dtype=jnp.float64,
        assembly_precision="highest", exchange_dtype=None,
    )
    os.environ["FLINK_MS_ALS_SOLVER"] = "unrolled"  # the spec-tested solver
    mesh = make_mesh(1)
    model = als_fit(
        d["users"], d["items"], d["ratings"], cfg, mesh,
        init=(d["u0"].astype(np.float64), d["i0"].astype(np.float64)),
    )
    val = rmse(model, d["users"], d["items"], d["ratings"])
    print(json.dumps({"rmse_ref": val}), flush=True)


def als_quality_anchor(mesh, problem, users, items, ratings, cfg_base,
                       iters: int) -> dict:
    """-> {als_rmse_at_iters, als_rmse_ref_delta, ...}.

    als_rmse_at_iters: train RMSE of the TIMED configuration after the
    timed iteration count at full scale — the number that would move if a
    solver/precision/exchange default silently regressed convergence.

    als_rmse_ref_delta: relative RMSE gap, bench config vs the float64
    reference solve (same data slice, same init, equal iterations) at a
    capped parity scale (BENCH_RMSE_REF_NNZ; a full-scale f64 CPU fit
    would cost the round minutes for no extra signal)."""
    import dataclasses
    import subprocess
    import tempfile

    from flink_ms_tpu.ops.als import ALSConfig, als_fit, prepare_blocked, rmse

    out = {}
    k = cfg_base.num_factors
    t0 = time.time()
    cfg_n = dataclasses.replace(cfg_base, iterations=iters)
    model = als_fit(users, items, ratings, cfg_n, mesh, problem=problem)
    out["als_rmse_at_iters"] = round(rmse(model, users, items, ratings), 6)
    out["als_rmse_iters"] = iters
    _log(f"[bench] train RMSE after {iters} iters: "
         f"{out['als_rmse_at_iters']} ({time.time() - t0:.1f}s)")

    if os.environ.get("BENCH_SKIP_CPU") == "1":
        return out
    ref_nnz = min(int(os.environ.get("BENCH_RMSE_REF_NNZ", 1_000_000)),
                  len(ratings))
    iters_p = min(iters, int(os.environ.get("BENCH_RMSE_REF_ITERS", 3)))
    ru, ri, rr = users[:ref_nnz], items[:ref_nnz], ratings[:ref_nnz]
    p_bench = prepare_blocked(ru, ri, rr, mesh.devices.size)
    rng = np.random.default_rng(cfg_base.seed)
    init = (0.1 * rng.standard_normal((p_bench.n_users, k)),
            0.1 * rng.standard_normal((p_bench.n_items, k)))
    cfg_p = dataclasses.replace(cfg_n, iterations=iters_p)
    t0 = time.time()
    m_bench = als_fit(ru, ri, rr, cfg_p, mesh, problem=p_bench, init=init)
    rmse_bench = rmse(m_bench, ru, ri, rr)
    _log(f"[bench] parity fit (bench cfg, {ref_nnz} nnz, {iters_p} iters): "
         f"RMSE {rmse_bench:.6f} ({time.time() - t0:.1f}s)")

    with tempfile.TemporaryDirectory(prefix="bench_rmse_") as td:
        npz = os.path.join(td, "problem.npz")
        np.savez(npz, users=ru, items=ri, ratings=rr, u0=init[0], i0=init[1],
                 k=k, lam=cfg_base.lambda_, iters=iters_p)
        env = dict(os.environ)
        # host-plane child: this process holds the chip
        env.update(JAX_ENABLE_X64="1", JAX_PLATFORMS="cpu")
        t0 = time.time()
        sub = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--rmse-ref", npz],
            capture_output=True, text=True, env=env,
            timeout=float(os.environ.get("BENCH_RMSE_REF_TIMEOUT_S", 900)),
            cwd=os.path.dirname(os.path.abspath(__file__)),
        )
    if sub.returncode != 0:
        raise RuntimeError(
            f"rmse-ref subprocess rc={sub.returncode}: {sub.stderr[-800:]}"
        )
    rmse_ref = json.loads(sub.stdout.strip().splitlines()[-1])["rmse_ref"]
    out["als_rmse_ref_delta"] = round((rmse_bench - rmse_ref) / rmse_ref, 6)
    out["als_rmse_ref_nnz"] = ref_nnz
    _log(f"[bench] f64 reference RMSE {rmse_ref:.6f} "
         f"({time.time() - t0:.1f}s) -> delta {out['als_rmse_ref_delta']}")

    # exchange-dtype quality side of the A/B (mirrors run_als_section's
    # speed A/B): the same parity fit with the OPPOSITE exchange dtype of
    # whatever the timed config resolved to, against the SAME f64
    # reference — the delta pair is the evidence a default flip needs
    platform_q = mesh.devices.flat[0].platform
    if (platform_q != "cpu"
            and os.environ.get("BENCH_ALS_BF16_AB", "1") != "0"):
        try:
            from flink_ms_tpu.ops.als import resolve_exchange

            resolved = resolve_exchange(cfg_base.exchange_dtype, platform_q)
            alt = None if resolved else "bfloat16"
            alt_name = "f32" if alt is None else "bf16"
            cfg_alt = dataclasses.replace(cfg_p, exchange_dtype=alt)
            m_alt = als_fit(ru, ri, rr, cfg_alt, mesh, problem=p_bench,
                            init=init)
            delta_alt = (rmse(m_alt, ru, ri, rr) - rmse_ref) / rmse_ref
            out[f"als_{alt_name}_rmse_ref_delta"] = round(delta_alt, 6)
            _log(f"[bench] {alt_name}-exchange parity fit -> delta "
                 f"{out[f'als_{alt_name}_rmse_ref_delta']}")
        except Exception:
            _log(traceback.format_exc())
            out["als_exchange_ab_quality_error"] = traceback.format_exc(
                limit=3)
    return out


# ---------------------------------------------------------------------------
# main
# ---------------------------------------------------------------------------

_DETAIL_PATH = os.environ.get("BENCH_DETAIL_PATH") or os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "BENCH_DETAIL.json"
)

# stdout-artifact keys, in emit order.  Everything else lives only in the
# sidecar.  Budget: the driver's observed stdout-tail window is ~2 KB; this
# set renders well under half of it at realistic values.
_COMPACT_KEYS = (
    "metric", "value", "unit", "vs_baseline", "mfu", "platform",
    "device_kind", "n_devices",
    "als_nnz", "als_rank", "als_tflops_per_sec", "als_solver",
    "als_rmse_at_iters", "als_rmse_ref_delta",
    "svm_rcv1_sec_per_round", "svm_rcv1_vs_baseline", "svm_secs_to_target",
    "serving_mget_p50_ms", "serving_topk_p50_ms", "serving_shard_mget_p50_ms",
    "serving_topk_batched_c64_qps", "serving_topk_batched_speedup_c64",
    "serving_ingest_columnar_rows_per_sec", "serving_ingest_speedup",
    "serving_ingest_columnar_prop_p99_ms",
    "serving_ha_r2_availability", "serving_ha_r2_recovery_s",
    "serving_elastic_cutover_s", "serving_elastic_during_p99_ms",
    "serving_elastic_errors",
    "serving_native_get_b2_c64_p50_us", "serving_native_get_b2_speedup_c64",
    "serving_native_topk_b2_speedup_c64", "serving_native_cutover_errors",
    "serving_ann_sharded_speedup", "serving_ann_ivf_speedup",
    "serving_ann_recall_at_100", "serving_ann_gate_recall_ok",
    "serving_watch_overhead_pct", "serving_watch_mse_abs_diff",
    "serving_watch_drift_fired", "serving_watch_detect_s",
    "serving_watch_unattributed_page",
    "serving_autopilot_retrains", "serving_autopilot_win_rate",
    "serving_autopilot_mse_monotone", "serving_autopilot_warm_beats_cold",
    "serving_autopilot_rollback_detect_s",
    "serving_forensics_stage1", "serving_forensics_stage1_share",
    "serving_forensics_diff_ok", "serving_forensics_alert_fired",
    "serving_forensics_exemplar_tids",
    "serving_forensics_incident_names_stage", "serving_forensics_ok",
    "serving_geo_repl_lag_p50_ms", "serving_geo_repl_lag_p99_ms",
    "serving_geo_stale_reads", "serving_geo_staleness_max_s",
    "serving_geo_failover_ms", "serving_geo_errors", "serving_geo_ok",
    "serving_edge_overhead_p99_us", "serving_edge_coalesce_hit_rate",
    "serving_edge_hedge_p999_ratio", "serving_edge_idle_kb_per_conn",
    "serving_edge_core_starved", "serving_edge_errors", "serving_edge_ok",
    "serving_profiler_top_frame", "serving_profiler_top_share",
    "serving_profiler_diff_ok", "serving_profiler_alert_fired",
    "serving_profiler_page_names_frame", "serving_profiler_replicas",
    "serving_profiler_native_stacks", "serving_profiler_ok",
    "serving_push_latency_p99_ms", "serving_push_fanout_amplification",
    "serving_push_selectivity", "serving_push_core_starved",
    "serving_push_ok",
    "mse_live_value", "host_ref_ms",
)


def emit_artifact(result: dict) -> str:
    """Write the full result to the BENCH_DETAIL.json sidecar and return the
    compact single-line JSON for stdout."""
    try:
        with open(_DETAIL_PATH, "w") as f:
            json.dump(result, f, indent=1, sort_keys=True)
            f.write("\n")
        result["detail"] = os.path.basename(_DETAIL_PATH)
    except OSError as e:
        result["detail"] = f"unwritable: {e}"
    compact = {k: result[k] for k in _COMPACT_KEYS if k in result}
    err_keys = failed_sections(result)
    if err_keys:
        compact["section_errors"] = err_keys
    compact["detail"] = result["detail"]
    line = json.dumps(compact)
    if len(line) > 1800:  # keep the line inside a log tail
        for k in ("section_errors", "als_solver",
                  "serving_shard_mget_p50_ms", "serving_topk_p50_ms"):
            compact.pop(k, None)
        line = json.dumps(compact)
    return line


def failed_sections(result: dict) -> list:
    """The ``*_error`` keys a run recorded — any makes the exit code 1."""
    return sorted(k for k in result if k.endswith("_error"))


def main() -> None:
    # stdout is the artifact: exactly ONE compact JSON line.  Section code
    # calls CLI mains in-process (producer, SGD, MSE) whose job summaries
    # print to stdout — reroute everything but the artifact line to stderr.
    real_stdout = sys.stdout
    with contextlib.redirect_stdout(sys.stderr):
        result = _run_all()
        line = emit_artifact(result)
    print(line, file=real_stdout, flush=True)
    failed = failed_sections(result)
    if failed:
        _log(f"[bench] FAILED sections: {', '.join(failed)}")
        sys.exit(1)


def host_reference_ms() -> float:
    """Fixed host workload timed into every artifact (closed-loop SGD throughput halved between rounds with nothing in the
    artifact separating a busier host from a regression).  One 1024x1024
    f32 matmul plus a 200k-step Python loop — BLAS and interpreter speed
    in one number; median of 5.  Cross-round throughput comparisons
    divide by the ratio of the two artifacts' values."""
    rng = np.random.default_rng(0)
    a = rng.standard_normal((1024, 1024)).astype(np.float32)
    times = []
    for _ in range(5):
        t0 = time.perf_counter()
        float((a @ a).sum())
        acc = 0
        for i in range(200_000):
            acc += i & 7
        times.append((time.perf_counter() - t0) * 1000.0)
    times.sort()
    return round(times[2], 2)


def _run_all() -> dict:
    small = os.environ.get("BENCH_SMALL") == "1"
    sections = os.environ.get(
        "BENCH_SECTIONS",
        "als,svm,serving,svmserve,serving_ingest,serving_ha,"
        "serving_elastic,serving_rehearsal,serving_bootstrap,"
        "serving_native,serving_update_plane,serving_rollout,serving_ann,"
        "serving_watch,serving_autopilot,serving_forensics,serving_geo,"
        "serving_arena,serving_arena_ingest,serving_edge,serving_profiler,"
        "serving_push"
    ).split(",")
    result: dict = {}

    from flink_ms_tpu.parallel.mesh import acquire_devices

    # the device rule: raises on a host with no accelerator unless
    # JAX_PLATFORMS=cpu asked for the host
    devices = acquire_devices()
    platform = devices[0].platform
    result["platform"] = platform
    result["n_devices"] = len(devices)
    result["device_kind"] = devices[0].device_kind
    result["host_ref_ms"] = host_reference_ms()
    _log(f"[bench] host reference op: {result['host_ref_ms']} ms")

    try:
        if "als" in sections:
            result.update(run_als_section(devices, platform, small))
    except Exception:
        _log(traceback.format_exc())
        result["als_error"] = traceback.format_exc(limit=3)

    # a failed section records its <name>_error key without costing the
    # others their metrics; main() turns any such key into a non-zero exit
    extra = (
        ("svm", "run_svm_section", lambda f: f(devices, platform, small)),
        ("serving", "run_serving_section", lambda f: f(small)),
        ("svmserve", "run_svm_serving_section", lambda f: f(small)),
        ("serving_ingest", "run_serving_ingest_section", lambda f: f(small)),
        ("serving_ha", "run_serving_ha_section", lambda f: f(small)),
        ("serving_elastic", "run_serving_elastic_section",
         lambda f: f(small)),
        ("serving_rehearsal", "run_serving_rehearsal_section",
         lambda f: f(small)),
        ("serving_bootstrap", "run_serving_bootstrap_section",
         lambda f: f(small)),
        ("serving_native", "run_serving_native_section",
         lambda f: f(small)),
        ("serving_update_plane", "run_serving_update_plane_section",
         lambda f: f(small)),
        ("serving_rollout", "run_serving_rollout_section",
         lambda f: f(small)),
        ("serving_ann", "run_serving_ann_section",
         lambda f: f(small)),
        ("serving_watch", "run_serving_watch_section",
         lambda f: f(small)),
        ("serving_autopilot", "run_serving_autopilot_section",
         lambda f: f(small)),
        ("serving_forensics", "run_serving_forensics_section",
         lambda f: f(small)),
        ("serving_geo", "run_serving_geo_section",
         lambda f: f(small)),
        ("serving_arena", "run_serving_arena_section",
         lambda f: f(small)),
        ("serving_arena_ingest", "run_serving_arena_ingest_section",
         lambda f: f(small)),
        ("serving_edge", "run_serving_edge_section",
         lambda f: f(small)),
        ("serving_profiler", "run_serving_profiler_section",
         lambda f: f(small)),
        ("serving_push", "run_serving_push_section",
         lambda f: f(small)),
    )
    for name, fn_name, call in extra:
        if name not in sections:
            continue
        try:
            import bench_sections
        except ImportError:
            result[f"{name}_error"] = "bench_sections module not available"
            continue
        fn = getattr(bench_sections, fn_name, None)
        if fn is None:
            result[f"{name}_error"] = f"bench_sections.{fn_name} missing"
            continue
        # bracket the section with registry snapshots: the sidecar detail
        # record carries what the section actually exercised (counters
        # moved, histogram mass added) next to its latency numbers.
        # In-process series only — sections that spawn worker SUBPROCESSES
        # contribute their client-side half here; worker-side series are
        # scraped live via obs.scrape, not captured post-mortem.
        snap_before = None
        try:
            from flink_ms_tpu.obs.metrics import diff_snapshots, get_registry

            snap_before = get_registry().snapshot()
        except Exception:
            pass
        try:
            result.update(call(fn))
        except Exception:
            _log(traceback.format_exc())
            result[f"{name}_error"] = traceback.format_exc(limit=3)
        if snap_before is not None:
            try:
                delta = diff_snapshots(
                    snap_before, get_registry().snapshot())
                if any(delta.values()):
                    result[f"{name}_metrics_delta"] = delta
            except Exception:
                pass
    # headline section failed or not requested: the line still carries the
    # four headline keys
    result.setdefault("metric", "als_ml20m_sec_per_iter")
    result.setdefault("value", None)
    result.setdefault("unit", "s/iter")
    result.setdefault("vs_baseline", None)

    return result


if __name__ == "__main__":
    if "--rmse-ref" in sys.argv:
        run_rmse_ref(sys.argv[sys.argv.index("--rmse-ref") + 1])
    else:
        main()
