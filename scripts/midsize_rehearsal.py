#!/usr/bin/env python
"""Mid-size multi-device rehearsal: the evidence layer
between the toy-shape dryrun and real multi-chip hardware.

One 8-device CPU-mesh run at ~1M-nnz ALS and ~100k-example SVM that PINS,
not just exercises:
  - per-device factor-shard shapes and the per-device device-arg memory
    footprint (the numbers that decide whether a config fits HBM),
  - exchange-volume accounting under the routed all_to_all (net rows per
    device crossing the interconnect, vs what the all_gather would ship),
  - staging resume across a simulated restart (iteration-boundary
    snapshots, second run resumes instead of recomputing, final factors
    identical to an uninterrupted fit),
  - SVM chain stacking (K > D) with convergence at scale,
  - a serving-plane SLO rehearsal on the closed-loop workload engine
    (obs/workload.py): zipfian mixed-verb load + autoscaler + replica
    kill, report must be schema-valid with zero unattributed errors
    (gate with REHEARSAL_SERVING=0; knobs REHEARSAL_SERVING_SHARDS /
    _REPLICATION / _USERS / _BASE_QPS / _PEAK_QPS / _BURST_QPS /
    _THREADS / _AUTOSCALE / _KILL).

Writes one JSON artifact (default REHEARSAL_r05.json next to the repo
root; override with REHEARSAL_OUT) and exits non-zero on any violated
invariant.  Runtime on one CPU core is minutes — this is a rehearsal, not
a benchmark; sec/iter numbers in the artifact are CPU-mesh numbers and
say nothing about chip performance.
"""

import json
import os
import sys
import time

N_DEV = int(os.environ.get("REHEARSAL_DEVICES", 8))

os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + f" --xla_force_host_platform_device_count={N_DEV}"
    ).strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

ART = {"devices": N_DEV, "checks": []}


def check(name, ok, **info):
    ART["checks"].append({"name": name, "ok": bool(ok), **info})
    status = "OK " if ok else "FAIL"
    print(f"[rehearsal] {status} {name} {info}", flush=True)
    return ok


def main() -> int:
    import jax

    from flink_ms_tpu.ops import als
    from flink_ms_tpu.ops.als import (
        ALSConfig, als_fit, compile_fit, prepare_blocked, rmse,
    )
    from flink_ms_tpu.parallel.mesh import BLOCK_AXIS, make_mesh

    mesh = make_mesh(N_DEV)
    ok = True

    # -- ALS at ~1M nnz ----------------------------------------------------
    n_users = int(os.environ.get("REHEARSAL_USERS", 200_000))
    n_items = int(os.environ.get("REHEARSAL_ITEMS", 40_000))
    nnz = int(os.environ.get("REHEARSAL_NNZ", 1_000_000))
    k = int(os.environ.get("REHEARSAL_RANK", 16))
    rng = np.random.default_rng(11)
    users = rng.integers(0, n_users, nnz)
    items = rng.integers(0, n_items, nnz)
    ratings = rng.uniform(1.0, 5.0, nnz)

    t0 = time.time()
    problem = prepare_blocked(users, items, ratings, N_DEV)
    ART["als"] = {
        "nnz": nnz, "n_users": problem.n_users, "n_items": problem.n_items,
        "rank": k, "users_per_block": problem.u.per_block,
        "items_per_block": problem.i.per_block,
        "prepare_s": round(time.time() - t0, 2),
    }

    # exchange accounting under the routed all_to_all (auto mode decides
    # per half-sweep; at this density the user side must route)
    plan = als._exchange_plan(problem, N_DEV)
    exch = {}
    for name, opp in (("u", problem.i), ("i", problem.u)):
        r = plan[name]
        gather_rows = (N_DEV - 1) * opp.per_block
        exch[name] = {
            "mode": "routed" if r is not None else "gather",
            "gather_rows_per_device": gather_rows,
            "net_rows_per_device": (
                r.net_rows if r is not None else gather_rows
            ),
            "net_bytes_per_device_f32": 4 * k * (
                r.net_rows if r is not None else gather_rows
            ),
        }
    ART["als"]["exchange"] = exch
    # the i-sweep exchanges the big USER factor table (200k rows) — that
    # is the side whose need-lists are sparse enough to route; the u-sweep
    # gathers the small saturated item catalog and correctly stays gather
    ok &= check(
        "als_user_factor_exchange_routes", plan["i"] is not None,
        net=exch["i"]["net_rows_per_device"],
        gather=exch["i"]["gather_rows_per_device"],
    )
    if plan["i"] is not None:
        ok &= check(
            "als_routed_crosses_less",
            exch["i"]["net_rows_per_device"]
            < exch["i"]["gather_rows_per_device"],
            ratio=round(exch["i"]["net_rows_per_device"]
                        / exch["i"]["gather_rows_per_device"], 3),
        )

    # per-device shard shapes + device-arg memory footprint
    cfg = ALSConfig(num_factors=k, iterations=1, lambda_=0.1,
                    exchange_dtype=None)
    fit_fn, dev_args = compile_fit(problem, cfg, mesh)
    uf0 = dev_args[0]
    shard_shapes = {
        str(d.id): s.data.shape for s in uf0.addressable_shards
        for d in [s.device]
    }
    want = (1, problem.u.per_block, k)
    ok &= check(
        "als_factor_shard_shape",
        all(s == want for s in shard_shapes.values())
        and len(shard_shapes) == N_DEV,
        shape=list(want), n_shards=len(shard_shapes),
    )
    per_dev_bytes = 0
    for a in dev_args:
        spec = getattr(a.sharding, "spec", None)
        sharded = bool(spec) and len(spec) > 0 and spec[0] == BLOCK_AXIS
        per_dev_bytes += a.nbytes // (N_DEV if sharded else 1)
    ART["als"]["per_device_arg_bytes"] = int(per_dev_bytes)
    ok &= check("als_per_device_bytes_accounted", per_dev_bytes > 0,
                mib=round(per_dev_bytes / 2**20, 1))

    # one timed step (CPU-mesh number, for the record only)
    import jax.numpy as jnp

    t0 = time.time()
    uf, itf = fit_fn(jnp.asarray(1, jnp.int32), *dev_args)
    jax.block_until_ready(uf)
    ART["als"]["first_iter_incl_compile_s"] = round(time.time() - t0, 2)
    t0 = time.time()
    uf, itf = fit_fn(jnp.asarray(2, jnp.int32), *dev_args)
    jax.block_until_ready(uf)
    ART["als"]["two_iter_steady_s"] = round(time.time() - t0, 2)

    # -- staging resume across a simulated restart -------------------------
    import shutil
    import tempfile

    stage = tempfile.mkdtemp(prefix="rehearsal_stage_")
    try:
        init = (0.1 * rng.standard_normal((problem.n_users, k)),
                0.1 * rng.standard_normal((problem.n_items, k)))
        cfg4 = ALSConfig(num_factors=k, iterations=4, lambda_=0.1,
                         exchange_dtype=None)
        cfg2 = ALSConfig(num_factors=k, iterations=2, lambda_=0.1,
                         exchange_dtype=None)
        # "crash" after 2 staged iterations...
        t0 = time.time()
        als_fit(users, items, ratings, cfg2, mesh, problem=problem,
                init=init, temporary_path=stage)
        staged_after_crash = sorted(os.listdir(stage))
        # ...then a NEW run to 4 iterations resumes from the snapshot:
        # it must be faster than 4 cold iterations and bitwise-match the
        # uninterrupted fit
        t_resume0 = time.time()
        m_resumed = als_fit(users, items, ratings, cfg4, mesh,
                            problem=problem, init=init,
                            temporary_path=stage)
        resume_s = time.time() - t_resume0
        m_straight = als_fit(users, items, ratings, cfg4, mesh,
                             problem=problem, init=init)
        ok &= check(
            "als_staging_resume_snapshots", len(staged_after_crash) >= 1,
            files=staged_after_crash[-2:],
        )
        same = np.allclose(m_resumed.user_factors, m_straight.user_factors,
                           rtol=1e-5, atol=1e-7)
        ok &= check("als_staging_resume_matches_straight_fit", same,
                    resume_s=round(resume_s, 2))
        ART["als"]["rmse_after_4_iters"] = round(
            rmse(m_straight, users, items, ratings), 6)
    finally:
        shutil.rmtree(stage, ignore_errors=True)

    # -- SVM at ~100k examples --------------------------------------------
    from flink_ms_tpu.core.formats import SparseData
    from flink_ms_tpu.ops.svm import SVMConfig, prepare_svm_blocked, svm_fit

    n_ex = int(os.environ.get("REHEARSAL_SVM_EXAMPLES", 100_000))
    n_feat = int(os.environ.get("REHEARSAL_SVM_FEATURES", 5_000))
    nnz_row = 12
    indptr = np.arange(n_ex + 1) * nnz_row
    indices = rng.integers(0, n_feat, n_ex * nnz_row).astype(np.int64)
    values = rng.normal(size=n_ex * nnz_row)
    w_true = rng.normal(size=n_feat)
    scores = np.add.reduceat(values * w_true[indices], indptr[:-1])
    labels = np.where(scores >= 0, 1.0, -1.0)
    flip = rng.random(n_ex) < 0.05
    labels[flip] = -labels[flip]
    data = SparseData(labels=labels, indices=indices, values=values,
                      indptr=indptr, n_features=n_feat)

    K = int(os.environ.get("REHEARSAL_SVM_K", 1024))
    # the RCV1 bench configuration family: CoCoA+ add mode with the
    # aggressive sigma' regime (BASELINE.md K-sweep) — avg mode at K=1024
    # divides every round's progress by K and barely moves at 5 rounds
    svm_cfg = SVMConfig(iterations=5, local_iterations=10,
                        regularization=1e-4, mode="add", sigma_prime=8.0)
    t0 = time.time()
    svm_problem = prepare_svm_blocked(data, K, seed=svm_cfg.seed)
    prep_s = time.time() - t0
    t0 = time.time()
    model0 = svm_fit(data, svm_cfg, mesh, problem=svm_problem)
    fit_s = time.time() - t0
    h5 = model0.hinge_loss(data, svm_cfg.regularization)
    import dataclasses as dc

    h15 = svm_fit(
        data, dc.replace(svm_cfg, iterations=15), mesh, problem=svm_problem
    ).hinge_loss(data, svm_cfg.regularization)
    ART["svm"] = {
        "examples": n_ex, "features": n_feat, "chains": K,
        "chains_per_device": -(-K // N_DEV),
        "prepare_s": round(prep_s, 2), "fit5_s": round(fit_s, 2),
        "hinge_after_5": round(h5, 6), "hinge_after_15": round(h15, 6),
    }
    ok &= check("svm_converges_with_rounds", h15 < h5 < 1.0,
                h5=round(h5, 4), h15=round(h15, 4))
    ok &= check("svm_chains_stack_per_device", K > N_DEV,
                chains_per_device=-(-K // N_DEV))

    # -- multi-process DCN rehearsal: 2 procs x 4 devices over gloo --------
    # (the distributed code path — parallel/distributed.py,
    # gloo collectives, single-writer staging, process-0-authoritative
    # resume — must carry the routed exchange and staging-resume at ~1M
    # nnz, not just the in-process 8-device mesh.)  Stand-in for the
    # multi-host run this environment cannot provide.
    if os.environ.get("REHEARSAL_MULTIPROC", "1") != "0":
        import socket as _socket
        import subprocess

        from flink_ms_tpu.core import formats as F

        repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        mp_dir = tempfile.mkdtemp(prefix="rehearsal_mp_")
        try:
            csv = os.path.join(mp_dir, "ratings.csv")
            F.write_ratings(csv, users, items, ratings)

            def _run_group(tag, argv_for, extra_env=None, n_procs=2,
                           dev_per_proc=4):
                """Launch an n-process CLI group over a fresh coordinator
                port.  stdout goes to FILES, not pipes: sequentially
                draining piped children deadlocks if a later one fills
                its 64 KB pipe mid-collective while we wait on an earlier
                one.  A hung/failed member must not orphan its siblings
                while the cleanup below deletes its working dir."""
                with _socket.socket() as s:
                    s.bind(("127.0.0.1", 0))
                    port = s.getsockname()[1]
                procs, handles, logs = [], [], []
                try:
                    for pid in range(n_procs):
                        log_path = os.path.join(mp_dir, f"{tag}-p{pid}.log")
                        logs.append(log_path)
                        fh = open(log_path, "wb")
                        handles.append(fh)
                        procs.append(subprocess.Popen(
                            argv_for(pid, port),
                            env={**os.environ, "JAX_PLATFORMS": "cpu",
                                 "XLA_FLAGS":
                                 "--xla_force_host_platform_device_count="
                                 f"{dev_per_proc}",
                                 **(extra_env or {})},
                            cwd=repo_root, stdout=fh,
                            stderr=subprocess.STDOUT))
                    deadline = time.time() + 1800
                    rcs = [p.wait(timeout=max(1.0, deadline - time.time()))
                           for p in procs]
                except Exception:
                    for p in procs:
                        if p.poll() is None:
                            p.kill()
                            p.wait(timeout=30)
                    raise
                finally:
                    for fh in handles:
                        fh.close()
                outs = [open(lp, errors="replace").read() for lp in logs]
                return rcs, outs

            def _als_argv(iterations, tag):
                def argv_for(pid, port):
                    out_dir = os.path.join(mp_dir, f"{tag}-p{pid}")
                    return [sys.executable, "-m",
                            "flink_ms_tpu.train.als_train",
                            "--input", csv, "--ignoreFirstLine", "false",
                            "--iterations", str(iterations),
                            "--numFactors", str(k), "--lambda", "0.1",
                            "--coordinatorAddress", f"127.0.0.1:{port}",
                            "--numProcesses", "2", "--processId", str(pid),
                            "--temporaryPath",
                            os.path.join(mp_dir, f"stage{pid}"),
                            "--userFactors", os.path.join(out_dir, "uf"),
                            "--itemFactors", os.path.join(out_dir, "itf")]
                return argv_for

            # pin the routed path on: auto may pick gather for one side,
            # and this section exists to prove routing across processes
            _routed = {"FLINK_MS_ALS_EXCHANGE_MODE": "routed"}

            t0 = time.time()
            rcs_a, outs_a = _run_group("runA", _als_argv(2, "runA"),
                                      _routed)  # "crash" after 2 iters
            wall_a = round(time.time() - t0, 1)
            ok &= check("mp_als_2proc_crash_run_exits_zero",
                        rcs_a == [0, 0], wall_s=wall_a,
                        tail="" if rcs_a == [0, 0] else outs_a[0][-400:])
            stage0 = os.path.join(mp_dir, "stage0")
            pre = sorted(os.listdir(stage0)) if os.path.isdir(stage0) else []
            t0 = time.time()
            rcs_b, outs_b = _run_group("runB", _als_argv(4, "runB"),
                                      _routed)  # new run resumes
            wall_b = round(time.time() - t0, 1)
            ok &= check("mp_als_resume_run_exits_zero", rcs_b == [0, 0],
                        wall_s=wall_b,
                        tail="" if rcs_b == [0, 0] else outs_b[0][-400:])
            post = sorted(os.listdir(stage0)) if os.path.isdir(stage0) \
                else []
            # the staging dir prunes to a trailing window, so final file
            # listings cannot distinguish resume from cold rerun — the
            # als_fit resume marker on process 0's stdout can
            resumed = "[ALS] staging: resuming from iteration 2" in outs_b[0]
            ok &= check("mp_als_resume_marker_on_process0", resumed,
                        pre=pre[:4], post=post[:6])
            # process-0 output of the resumed run must match an in-process
            # single-process 4-iteration fit (same CLI defaults: seed 42
            # init, lambda 0.1) across the CSV round trip
            if rcs_b == [0, 0]:
                cfg_cli = ALSConfig(num_factors=k, iterations=4, lambda_=0.1)
                ref = als_fit(users, items, ratings, cfg_cli, mesh,
                              problem=problem)
                ids, kinds, rows = F.read_als_model(
                    os.path.join(mp_dir, "runB-p0", "uf"))
                got = {int(i): r for i, kk, r in zip(ids, kinds, rows)}
                nan_row = np.full(k, np.nan)
                match = len(got) == len(ref.user_ids) and all(
                    np.allclose(got.get(int(uid), nan_row), row,
                                rtol=1e-4, atol=1e-5)
                    for uid, row in zip(ref.user_ids, ref.user_factors)
                )
                ok &= check("mp_als_resumed_matches_inprocess_fit", match,
                            users=len(got))
            else:
                ok &= check("mp_als_resumed_matches_inprocess_fit", False,
                            skipped="resume run failed")
            ART["multiproc"] = {
                "processes": 2, "devices_per_process": 4,
                "backend": "gloo", "nnz": nnz, "rank": k,
                "exchange_mode": "routed",
                "crash_run_2it_s": wall_a, "resume_run_4it_s": wall_b,
            }

            # CoCoA SVM over the same 2-proc x 4-device gloo mesh: chains
            # split by the deterministic layout, deltas psum'd over DCN —
            # process-0 output must equal the in-process fit
            svm_lines = []
            for r in range(n_ex):
                lo, hi = indptr[r], indptr[r + 1]
                tok = " ".join(f"{int(j) + 1}:{v}" for j, v in
                               zip(indices[lo:hi], values[lo:hi]))
                svm_lines.append(f"{int(labels[r])} {tok}")  # +-1 labels:
                # a 0/1 encoding would alias -1 onto sign(0) -> +1 in
                # prepare_svm_blocked
            svm_train_path = os.path.join(mp_dir, "svm_train.libsvm")
            with open(svm_train_path, "w") as f:
                f.write("\n".join(svm_lines) + "\n")
            def _svm_argv(pid, port):
                return [sys.executable, "-m",
                        "flink_ms_tpu.train.svm_train",
                        "--training", svm_train_path,
                        "--blocks", "64", "--iteration", "3",
                        "--localIterations", "20",
                        "--coordinatorAddress", f"127.0.0.1:{port}",
                        "--numProcesses", "2", "--processId", str(pid),
                        "--output", os.path.join(mp_dir, f"svm-w{pid}")]

            t0 = time.time()
            sv_rcs, sv_outs = _run_group("svm", _svm_argv)
            wall_svm = round(time.time() - t0, 1)
            ok &= check("mp_svm_2proc_exits_zero", sv_rcs == [0, 0],
                        wall_s=wall_svm,
                        tail="" if sv_rcs == [0, 0] else sv_outs[0][-400:])
            if sv_rcs == [0, 0]:
                sp = prepare_svm_blocked(data, 64, seed=0)
                ref_cfg = SVMConfig(iterations=3, local_iterations=20,
                                    regularization=1.0)
                ref_w = svm_fit(data, ref_cfg, mesh, problem=sp).weights
                got_w = F.read_svm_model(
                    os.path.join(mp_dir, "svm-w0"), n_features=n_feat)
                ok &= check(
                    "mp_svm_matches_inprocess_fit",
                    np.allclose(got_w, ref_w, rtol=1e-4, atol=1e-6),
                    d=n_feat,
                )
                # single-writer output contract across processes
                ok &= check("mp_svm_single_writer",
                            not os.path.exists(
                                os.path.join(mp_dir, "svm-w1")))
                ART["multiproc"]["svm_2proc_3rounds_s"] = wall_svm
            else:
                ok &= check("mp_svm_matches_inprocess_fit", False,
                            skipped="svm pair failed")

            # N>2 process group: 4 procs x
            # 2 devices over gloo — same 8 global devices, so the
            # blocked layout and the in-process reference fit are
            # unchanged; what varies is process count, per-process
            # addressable shards, and the routed exchange now crossing
            # three process boundaries.
            def _als4_argv(pid, port):
                out_dir = os.path.join(mp_dir, f"run4-p{pid}")
                return [sys.executable, "-m",
                        "flink_ms_tpu.train.als_train",
                        "--input", csv, "--ignoreFirstLine", "false",
                        "--iterations", "2",
                        "--numFactors", str(k), "--lambda", "0.1",
                        "--coordinatorAddress", f"127.0.0.1:{port}",
                        "--numProcesses", "4", "--processId", str(pid),
                        "--userFactors", os.path.join(out_dir, "uf"),
                        "--itemFactors", os.path.join(out_dir, "itf")]

            t0 = time.time()
            rcs4, outs4 = _run_group("run4", _als4_argv, _routed,
                                     n_procs=4, dev_per_proc=2)
            wall4 = round(time.time() - t0, 1)
            ok &= check("mp_als_4proc_exits_zero", rcs4 == [0] * 4,
                        wall_s=wall4,
                        tail="" if rcs4 == [0] * 4 else outs4[0][-400:])
            if rcs4 == [0] * 4:
                cfg2_cli = ALSConfig(num_factors=k, iterations=2,
                                     lambda_=0.1)
                ref2 = als_fit(users, items, ratings, cfg2_cli, mesh,
                               problem=problem)
                ids, kinds, rows = F.read_als_model(
                    os.path.join(mp_dir, "run4-p0", "uf"))
                got = {int(i): r for i, kk, r in zip(ids, kinds, rows)}
                nan_row = np.full(k, np.nan)
                match4 = len(got) == len(ref2.user_ids) and all(
                    np.allclose(got.get(int(uid), nan_row), row,
                                rtol=1e-4, atol=1e-5)
                    for uid, row in zip(ref2.user_ids, ref2.user_factors))
                ok &= check("mp_als_4proc_matches_inprocess_fit", match4,
                            users=len(got))
            else:
                ok &= check("mp_als_4proc_matches_inprocess_fit", False,
                            skipped="4-proc run failed")
            ART["multiproc"]["als_4proc_2dev_2it_s"] = wall4
            ART["multiproc"]["groups"] = [
                {"processes": 2, "devices_per_process": 4},
                {"processes": 4, "devices_per_process": 2},
            ]
        except Exception as e:
            # a crashed harness must still land its earlier checks in the
            # artifact (ok=false), not lose them to an unhandled traceback
            ok &= check("mp_section_completes", False,
                        error=f"{type(e).__name__}: {e}")
        finally:
            shutil.rmtree(mp_dir, ignore_errors=True)

    # -- serving-plane rehearsal on the closed-loop workload engine -------
    # (obs/workload.py + obs/slo.py): zipfian mixed-verb open-loop load
    # against a live sharded group with autoscaler + one replica kill, SLO
    # accounting from the fleet scrape.  The hand-rolled query loop this
    # script used to need lives in the engine now — this stage just sets
    # knobs and checks the report.
    if os.environ.get("REHEARSAL_SERVING", "1") != "0":
        from flink_ms_tpu.obs.slo import validate_report
        from flink_ms_tpu.obs.workload import run_rehearsal

        serving_out = os.path.join(
            tempfile.mkdtemp(prefix="rehearsal_serving_"),
            "SLO_REPORT.json")
        try:
            report = run_rehearsal(
                out_path=serving_out,
                shards=int(os.environ.get("REHEARSAL_SERVING_SHARDS", 2)),
                replication=int(
                    os.environ.get("REHEARSAL_SERVING_REPLICATION", 2)),
                users=int(os.environ.get("REHEARSAL_SERVING_USERS", 400)),
                base_qps=float(
                    os.environ.get("REHEARSAL_SERVING_BASE_QPS", 120)),
                peak_qps=float(
                    os.environ.get("REHEARSAL_SERVING_PEAK_QPS", 240)),
                burst_qps=float(
                    os.environ.get("REHEARSAL_SERVING_BURST_QPS", 480)),
                warm_s=2.0, ramp_s=3.0, burst_s=4.0, cool_s=2.0,
                threads=int(
                    os.environ.get("REHEARSAL_SERVING_THREADS", 4)),
                autoscale=os.environ.get(
                    "REHEARSAL_SERVING_AUTOSCALE", "live"),
                kill=os.environ.get("REHEARSAL_SERVING_KILL", "1") != "0",
                seed=0,
            )
            problems = validate_report(report)
            ok &= check("serving_slo_report_schema_valid", not problems,
                        problems=problems[:3])
            ok &= check("serving_zero_unattributed_errors",
                        report["errors"]["unattributed"] == 0,
                        errors=report["errors"]["total"])
            unattr_breaches = [
                b for b in report["breaches"] if not b.get("attribution")]
            ok &= check("serving_breaches_attributed", not unattr_breaches,
                        breaches=len(report["breaches"]))
            wl = report["workload"]
            ok &= check("serving_open_loop_kept_schedule",
                        wl["completed"] == wl["scheduled"],
                        scheduled=wl["scheduled"], completed=wl["completed"],
                        max_lag_s=wl["max_sched_lag_s"])
            ART["serving"] = {
                "ok": report["ok"],
                "scheduled": wl["scheduled"],
                "achieved_qps": wl["achieved_qps"],
                "errors": report["errors"]["total"],
                "breaches": len(report["breaches"]),
                "kills": sum(1 for e in report["timeline"]
                             if "kill" in e.get("kind", "")),
                "verbs": {v: {"availability": d["availability"],
                              "p99_ms": d["p99_ms"],
                              "burn_rate": d["burn_rate"]}
                          for v, d in report["verbs"].items()},
            }
        except Exception as e:
            ok &= check("serving_rehearsal_completes", False,
                        error=f"{type(e).__name__}: {e}")
        finally:
            shutil.rmtree(os.path.dirname(serving_out), ignore_errors=True)

    ART["ok"] = bool(ok)
    out_path = os.environ.get("REHEARSAL_OUT") or os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "REHEARSAL_r05.json",
    )
    with open(out_path, "w") as f:
        json.dump(ART, f, indent=1)
        f.write("\n")
    print(f"[rehearsal] artifact -> {out_path} ok={ok}", flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
