#!/usr/bin/env python3
"""Prove that train -> publish -> serve still starts on the accelerator.

Drives the loop a user types, once, at MovieLens-20M shape (138,493 x
26,744, 20M ratings, rank 50) with ratings generated from a seed:

    als_train -> als_producer (x2) -> als_consumer -> QueryClient

and checks results, not exit codes: train RMSE falls across iterations and
equals a numpy RMSE of the written factor files; GET/MGET return the rows
of those files; TOPK/TOPKV replies equal a numpy argsort of the same
factors (ids exact, scores to 1e-3); every child ran on the accelerator.

One process per chip: this parent never imports jax.  The trainer runs as
a child that exits (releasing the chip), then the serving job runs as a
child that owns the chip and is queried with the JAX-free client.  Data
lives in a temporary directory outside the checkout; only the compile
cache path is fixed (``parallel/mesh.py``).

    python chip_smoke.py                          # on the chip
    JAX_PLATFORMS=cpu python chip_smoke.py --tiny # debug the command here

The last stdout line is ``{"ok": true, "device": {...}}`` with the device
as the children's jax reported it.  Any failed child, failed check, or
child on ``cpu`` (outside ``--tiny``) exits non-zero without that line.
"""

import argparse
import json
import os
import re
import signal
import socket
import subprocess
import sys
import tempfile
import time

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
if not os.path.isdir(os.path.join(REPO, "flink_ms_tpu")):
    sys.exit("chip_smoke.py: no flink_ms_tpu package beside this script; "
             "run it from the root of a checkout")
sys.path.insert(0, REPO)

from flink_ms_tpu.core import formats as F  # noqa: E402
from flink_ms_tpu.serve.client import QueryClient  # noqa: E402

FULL = dict(n_users=138_493, n_items=26_744, nnz=20_000_000, rank=50)
TINY = dict(n_users=300, n_items=200, nnz=6_000, rank=8)
ITERATIONS = 3
SEED = 20
STATE = "ALS_MODEL"
TOPIC = "als-models"
TOPK_K = 10
N_PROBES = 8

_MESH_RE = re.compile(
    r"^\[mesh\] platform=(\S+) device_kind=(.+) devices=(\d+)$", re.M)
_RMSE_RE = re.compile(r"train RMSE=([0-9.]+)")


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"[smoke] FAILED: {what}")


def pair_dots(uf, itf, users, items) -> np.ndarray:
    """uf[u] . itf[i] per (user, item) pair, in chunks that bound the two
    gathered (chunk, k) transients."""
    out = np.empty(len(users), np.result_type(uf, itf))
    for s in range(0, len(users), 2_000_000):
        e = s + 2_000_000
        out[s:e] = np.einsum("nk,nk->n", uf[users[s:e]], itf[items[s:e]])
    return out


def synth_ratings(n_users, n_items, nnz, seed):
    """Uniform (user, item) pairs over every id, half-star ratings with a
    planted rank-8 signal so ALS has something to fit."""
    rng = np.random.default_rng(seed)
    users = rng.integers(0, n_users, nnz)
    items = rng.integers(0, n_items, nnz)
    users[:n_users] = np.arange(n_users)   # every id occurs: the factor
    items[:n_items] = np.arange(n_items)   # tables have the full shape
    u_true = rng.standard_normal((n_users, 8)).astype(np.float32)
    v_true = rng.standard_normal((n_items, 8)).astype(np.float32)
    raw = (3.0 + 0.35 * pair_dots(u_true, v_true, users, items)
           + 0.3 * rng.standard_normal(nnz))
    return users, items, np.clip(np.round(raw * 2) / 2, 0.5, 5.0)


def numpy_rmse(uf, itf, users, items, ratings) -> float:
    err = ratings - pair_dots(uf, itf, users, items)
    return float(np.sqrt(np.mean(err * err)))


def child_env(tiny: bool) -> dict:
    env = dict(os.environ, PYTHONUNBUFFERED="1")
    prior = env.get("PYTHONPATH", "")
    env["PYTHONPATH"] = REPO + (os.pathsep + prior if prior else "")
    if tiny:
        # the chip's solver, interpreted: the same Pallas kernel runs here
        env["FLINK_MS_ALS_SOLVER"] = "pallas"
    return env


def run_module(module: str, args, log_path: str, env: dict) -> str:
    """Run ``python -m module args`` to completion -> its combined output."""
    with open(log_path, "w") as out:
        rc = subprocess.call(
            [sys.executable, "-m", module, *args],
            stdout=out, stderr=subprocess.STDOUT, env=env, cwd=REPO)
    text = open(log_path).read()
    if rc != 0:
        sys.stderr.write(text[-4000:])
    check(rc == 0, f"{module} exited {rc} (log: {log_path})")
    return text


def reported_device(text: str, who: str, tiny: bool) -> dict:
    m = _MESH_RE.search(text)
    check(m is not None, f"{who} logged no '[mesh] platform=...' line")
    dev = {"platform": m.group(1), "kind": m.group(2),
           "count": int(m.group(3))}
    log(f"{who} ran on platform: {dev['platform']}, "
        f"device_kind: {dev['kind']}, devices: {dev['count']}")
    check(tiny or dev["platform"] != "cpu",
          f"{who} ran on cpu; the smoke needs the accelerator")
    return dev


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def counter(snapshot: dict, name: str) -> float:
    return sum(c["value"] for c in snapshot.get("counters", [])
               if c["name"] == name)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--tiny", action="store_true",
                    help="small shape, interpret-mode kernel; the only mode "
                         "allowed to run on cpu")
    tiny = ap.parse_args().tiny
    if not tiny and os.environ.get("JAX_PLATFORMS", "").strip() == "cpu":
        sys.exit("chip_smoke.py: JAX_PLATFORMS=cpu asks for the host; the "
                 "smoke needs the accelerator (use --tiny to debug on cpu)")
    shape = TINY if tiny else FULL
    rank = shape["rank"]
    env = child_env(tiny)
    phases = {}

    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
        # -- generate ---------------------------------------------------
        t0 = time.time()
        users, items, ratings = synth_ratings(
            shape["n_users"], shape["n_items"], shape["nnz"], SEED)
        ratings_path = os.path.join(tmp, "ratings.csv")
        F.write_ratings(ratings_path, users, items, ratings)
        phases["generate"] = time.time() - t0
        log(f"shape {shape['n_users']} x {shape['n_items']}, "
            f"nnz {len(ratings)}, rank {rank}, {ITERATIONS} iterations; "
            f"ratings file {os.path.getsize(ratings_path) >> 20} MiB")

        # -- train (child 1: holds the chip, then exits) ----------------
        t0 = time.time()
        uf_path = os.path.join(tmp, "userFactors")
        itf_path = os.path.join(tmp, "itemFactors")
        stage = os.path.join(tmp, "stage")
        text = run_module(
            "flink_ms_tpu.train.als_train",
            ["--input", ratings_path, "--ignoreFirstLine", "false",
             "--numFactors", str(rank), "--iterations", str(ITERATIONS),
             "--lambda", "0.05", "--temporaryPath", stage,
             "--userFactors", uf_path, "--itemFactors", itf_path],
            os.path.join(tmp, "train.log"), env)
        phases["train"] = time.time() - t0
        for line in text.splitlines():
            if line.startswith(("[ALS]", "[als", "[mesh]")):
                log(f"trainer: {line}")
        train_dev = reported_device(text, "trainer", tiny)

        # -- check the model ---------------------------------------------
        t0 = time.time()
        u_ids, _, uf = F.read_als_model(uf_path)
        i_ids, _, itf = F.read_als_model(itf_path)
        check(uf.shape == (shape["n_users"], rank)
              and itf.shape == (shape["n_items"], rank),
              f"factor shapes {uf.shape} / {itf.shape}")
        check(bool(np.isfinite(uf).all() and np.isfinite(itf).all()),
              "non-finite factors")
        check(u_ids == [str(i) for i in range(shape["n_users"])]
              and i_ids == [str(i) for i in range(shape["n_items"])],
              "factor files do not list ids 0..n-1 in order")
        # the staged snapshots give the iterations: the last one IS the
        # factor files, the one before it must score worse
        def staged(it):
            with np.load(os.path.join(stage, f"iter_{it:05d}.npz")) as z:
                return z["user_factors"], z["item_factors"]

        uf_last, itf_last = staged(ITERATIONS)
        check(np.allclose(uf, uf_last, atol=1e-6)
              and np.allclose(itf, itf_last, atol=1e-6),
              "factor files differ from the last staged iteration")
        rmse_prev = numpy_rmse(*staged(ITERATIONS - 1), users, items, ratings)
        rmse_files = numpy_rmse(uf, itf, users, items, ratings)
        m = _RMSE_RE.search(text)
        check(m is not None, "trainer printed no train RMSE")
        rmse_trainer = float(m.group(1))
        log(f"train RMSE (numpy): iteration {ITERATIONS - 1} "
            f"{rmse_prev:.4f}, iteration {ITERATIONS} = factor files "
            f"{rmse_files:.4f}; trainer said {rmse_trainer:.4f}")
        check(rmse_files < rmse_prev,
              "train RMSE did not fall across iterations")
        check(abs(rmse_files - rmse_trainer) < 1e-3,
              "trainer's RMSE and numpy's over the factor files disagree")
        phases["check_model"] = time.time() - t0

        # -- publish -----------------------------------------------------
        t0 = time.time()
        bus = os.path.join(tmp, "bus")
        for name, path, n in (("user", uf_path, shape["n_users"]),
                              ("item", itf_path, shape["n_items"])):
            out = run_module(
                "flink_ms_tpu.serve.als_producer",
                ["--input", path, "--journalDir", bus, "--topic", TOPIC],
                os.path.join(tmp, f"publish_{name}.log"), env)
            check(f"{n} rows" in out, f"producer did not load {n} {name} rows")
        phases["publish"] = time.time() - t0

        # -- serve (child 2: owns the chip until stopped) ----------------
        t0 = time.time()
        port = free_port()
        serve_log = os.path.join(tmp, "serve.log")
        with open(serve_log, "w") as out:
            server = subprocess.Popen(
                [sys.executable, "-m", "flink_ms_tpu.serve.als_consumer",
                 "--journalDir", bus, "--topic", TOPIC, "--table", "dict",
                 "--host", "127.0.0.1", "--port", str(port)],
                stdout=out, stderr=subprocess.STDOUT, env=env, cwd=REPO)
        try:
            n_rows = shape["n_users"] + shape["n_items"]
            client = QueryClient("127.0.0.1", port, timeout_s=600)
            deadline = time.time() + 600
            while True:
                check(server.poll() is None,
                      "server exited early:\n" + open(serve_log).read()[-4000:])
                check(time.time() < deadline, "server never became ready")
                try:
                    health = client.health(STATE)
                    if health["ready"] and client.count(STATE) == n_rows:
                        break
                except OSError:
                    client.close()
                time.sleep(0.2)
            phases["serve_ready"] = time.time() - t0
            serve_dev = reported_device(
                open(serve_log).read(), "server", tiny)
            check(serve_dev == train_dev,
                  f"trainer and server disagree on the device: "
                  f"{train_dev} vs {serve_dev}")

            rng = np.random.default_rng(SEED + 1)
            probe_u = rng.choice(shape["n_users"], N_PROBES, replace=False)
            probe_i = rng.choice(shape["n_items"], N_PROBES, replace=False)

            def parse(payload):
                return np.array(payload.rstrip(";").split(";"), np.float64)

            # GET / MGET: the rows of the factor files
            t0 = time.time()
            for u in probe_u:
                got = client.query_state(STATE, f"{u}-U")
                check(got is not None and np.allclose(parse(got), uf[u]),
                      f"GET {u}-U does not match the factor file")
            keys = [f"{i}-I" for i in probe_i]
            got = client.query_states(STATE, keys)
            check(len(got) == len(keys) and all(
                g is not None and np.allclose(parse(g), itf[i])
                for g, i in zip(got, probe_i)),
                "MGET does not match the factor file")
            check(client.query_state(STATE, "no-such-U") is None,
                  "GET of a missing key returned a value")
            phases["get_mget"] = time.time() - t0

            # TOPK / TOPKV: numpy argsort of the same factors
            def check_topk(verb, reply, query):
                scores = itf @ query
                want = np.argsort(-scores, kind="stable")[:TOPK_K]
                got_ids = [int(item) for item, _ in reply]
                got_scores = np.array([s for _, s in reply])
                check(got_ids == want.tolist(),
                      f"{verb} ids {got_ids} != numpy argsort "
                      f"{want.tolist()}")
                check(bool(np.abs(got_scores - scores[want]).max() < 1e-3),
                      f"{verb} scores off by "
                      f"{np.abs(got_scores - scores[want]).max():.2e}")

            t0 = time.time()
            check_topk("TOPK", client.topk(STATE, str(probe_u[0]), TOPK_K),
                       uf[probe_u[0]])
            phases["first_topk"] = time.time() - t0
            t0 = time.time()
            for u in probe_u[1:]:
                check_topk("TOPK", client.topk(STATE, str(u), TOPK_K), uf[u])
            for u in probe_u:
                q = (uf[u] * 0.5).astype(np.float32)
                payload = ";".join(repr(float(x)) for x in q)
                check_topk("TOPKV",
                           client.topk_by_vector(STATE, payload, TOPK_K),
                           q.astype(np.float64))
            # one pipelined window: the server coalesces it into a batched
            # frame, the program concurrent users are answered by
            frame = client.topk_pipelined(
                STATE, [str(u) for u in probe_u], TOPK_K)
            for u, reply in zip(probe_u, frame):
                check_topk("TOPK (pipelined frame)", reply, uf[u])
            check(client.topk(STATE, "no-such", TOPK_K) is None,
                  "TOPK of an unknown user returned a value")
            phases["topk_topkv"] = time.time() - t0

            metrics = client.metrics()
            batches = [h for h in metrics.get("histograms", [])
                       if h["name"] == "tpums_topk_batch_size"]
            check(bool(batches) and batches[0]["sum"] > batches[0]["count"],
                  "the pipelined window never rode a batched frame")
            errors = counter(metrics, "tpums_topk_device_errors_total")
            log("server: compile {:.2f}s, persistent cache {:.0f} hit(s) / "
                "{:.0f} miss(es); swallowed device errors {:.0f}".format(
                    counter(metrics, "tpums_jax_compile_seconds_total"),
                    counter(metrics, "tpums_jax_compile_cache_hits_total"),
                    counter(metrics, "tpums_jax_compile_cache_misses_total"),
                    errors))
            check(errors == 0, f"the index swallowed {errors:.0f} device "
                               "error(s):\n" + open(serve_log).read()[-4000:])
            client.close()
        finally:
            # stop what we started, whatever happened above
            if server.poll() is None:
                server.send_signal(signal.SIGTERM)
                try:
                    server.wait(60)
                except subprocess.TimeoutExpired:
                    server.kill()
                    server.wait()
        check(server.returncode == 0,
              f"server exited {server.returncode} on SIGTERM:\n"
              + open(serve_log).read()[-4000:])

    log("phase seconds: " + ", ".join(
        f"{k} {v:.1f}" for k, v in phases.items()))
    print(json.dumps({"ok": True, "device": train_dev}), flush=True)


if __name__ == "__main__":
    main()
