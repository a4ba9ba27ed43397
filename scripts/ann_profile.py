#!/usr/bin/env python
"""Retrieval-plane microbench: exact host index vs mesh-sharded exact vs
IVF ANN, with a recall parity assert (ISSUE 11).

Arms (all over the same clustered synthetic catalog — a mixture of
gaussians, the geometry ALS item factors actually have, and the one IVF's
recall contract is calibrated against):

- ``build``    — time to stand up each tier (device placement + scatter
  warm-up; for IVF also k-means training, the full assignment pass, and
  the build-time recall probe);
- ``probe``    — batched TOPK qps through each tier's steady-state frame
  program (the microbatcher's dispatch path);
- ``re-rank``  — the IVF shortlist re-rank in isolation (probe minus
  coarse quantizer), to show where the ANN milliseconds go.

Parity: IVF results are compared against the exact tier's on the same
query frames — recall@k must clear ``--recallMin`` (default 0.95) or the
script exits non-zero.  Sharded-exact results must match single-device
results EXACTLY (same ids, scores to float tolerance): sharding is a
layout change, not an approximation.

Run host-side (no accelerator needed; the mesh is forced host devices):

    python scripts/ann_profile.py [--rows 200000] [--k 16] [--devices 8] \
        [--frame 16] [--topk 100] [--nlist 0] [--nprobe 0] \
        [--trials 30] [--json false]

``--json true`` prints one machine-readable result object on stdout
(human lines go to stderr) — the ``serving_ann`` bench section consumes
this.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")


def _force_devices(n: int) -> None:
    """Must run before jax import: the sharded arm needs a multi-device
    host mesh, which on CPU exists only via this XLA flag."""
    flag = f"--xla_force_host_platform_device_count={n}"
    prior = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in prior:
        os.environ["XLA_FLAGS"] = (prior + " " + flag).strip()


def make_catalog(n: int, d: int, seed: int = 0):
    """Clustered item factors + user-like queries.  Items are a mixture
    of gaussians (ALS factor geometry: items cluster by taste dimension);
    queries are smooth mixtures of cluster directions (users straddle
    tastes) — the harder case for IVF, and the one served in production."""
    import numpy as np

    rng = np.random.default_rng(seed)
    n_clusters = max(16, min(256, n // 2000))
    cents = rng.normal(size=(n_clusters, d)).astype(np.float32) * 3.0
    assign = rng.integers(0, n_clusters, size=n)
    rows = cents[assign] + rng.normal(size=(n, d)).astype(np.float32) * 0.6
    w = rng.dirichlet(np.ones(4), size=512).astype(np.float32)
    picks = rng.integers(0, n_clusters, size=(512, 4))
    queries = np.einsum("qm,qmd->qd", w, cents[picks]).astype(np.float32)
    queries += rng.normal(size=queries.shape).astype(np.float32) * 0.2
    return rows, queries


def build_index(rows, ids, env: dict):
    """One DeviceFactorIndex under the given knob env, bulk-loaded with
    the catalog -> (index, build_seconds)."""
    from flink_ms_tpu.serve.table import ModelTable
    from flink_ms_tpu.serve.topk import DeviceFactorIndex

    prior = {k: os.environ.get(k) for k in env}
    os.environ.update(env)
    try:
        t0 = time.perf_counter()
        idx = DeviceFactorIndex(ModelTable(), "-I")
        idx.bulk_load(ids, rows)
        build_s = time.perf_counter() - t0
    finally:
        for k, v in prior.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    return idx, build_s


def measure_qps(idx, queries, frame: int, k: int, trials: int):
    """Steady-state batched qps through ``topk_many`` -> (qps, p50_ms,
    p99_ms).  Frames rotate through the query pool so caching can't
    flatter the number."""
    import numpy as np

    frames = [
        queries[(i * frame) % (len(queries) - frame):][:frame]
        for i in range(trials + 3)
    ]
    for f in frames[:3]:
        idx.topk_many(f, k)  # warm the (frame, k) program
    lat = []
    t0 = time.perf_counter()
    for f in frames[3:]:
        t1 = time.perf_counter()
        idx.topk_many(f, k)
        lat.append(time.perf_counter() - t1)
    dt = time.perf_counter() - t0
    return (
        trials * frame / dt,
        float(np.percentile(lat, 50) * 1e3),
        float(np.percentile(lat, 99) * 1e3),
    )


def recall_vs(exact_idx, ann_idx, queries, k: int) -> float:
    hits = total = 0
    for q0 in range(0, min(len(queries), 128), 32):
        batch = queries[q0:q0 + 32]
        ref = exact_idx.topk_many(batch, k)
        got = ann_idx.topk_many(batch, k)
        for r, g in zip(ref, got):
            ref_ids = {i for i, _ in r}
            hits += len(ref_ids & {i for i, _ in g})
            total += len(ref_ids)
    return hits / max(total, 1)


def main(argv=None) -> int:
    from flink_ms_tpu.core.params import Params

    params = Params.from_args(sys.argv[1:] if argv is None else argv)
    rows_n = params.get_int("rows", 200_000)
    d = params.get_int("k", 16)
    devices = params.get_int("devices", 8)
    frame = params.get_int("frame", 16)
    topk = params.get_int("topk", 100)
    nlist = params.get_int("nlist", 0)
    nprobe = params.get_int("nprobe", 0)
    trials = params.get_int("trials", 30)
    recall_min = float(params.get("recallMin", "0.95"))
    as_json = params.get_bool("json", False)
    _force_devices(devices)

    import numpy as np  # noqa: F401  (after XLA_FLAGS is set)

    say = lambda m: print(m, file=sys.stderr)  # noqa: E731
    say(f"[ann-profile] catalog: {rows_n} rows x {d} dims, "
        f"{devices} forced host devices")
    rows, queries = make_catalog(rows_n, d)
    ids = [f"it{i}" for i in range(rows_n)]
    if nlist:
        os.environ["TPUMS_ANN_NLIST"] = str(nlist)
    if nprobe:
        os.environ["TPUMS_ANN_NPROBE"] = str(nprobe)

    result = {"rows": rows_n, "dims": d, "devices": devices,
              "frame": frame, "topk": topk}

    # -- arm 1: single-device exact (the current host path — baseline) --
    exact_idx, b_s = build_index(
        rows, ids, {"TPUMS_TOPK_SHARDED": "0", "TPUMS_TOPK_TIER": "exact"})
    qps, p50, p99 = measure_qps(exact_idx, queries, frame, topk, trials)
    result.update(exact_build_s=b_s, exact_qps=qps,
                  exact_p50_ms=p50, exact_p99_ms=p99)
    say(f"[ann-profile] exact/host:    build {b_s:6.2f}s  "
        f"{qps:>9,.0f} qps  p50 {p50:.2f}ms p99 {p99:.2f}ms")

    # -- arm 2: mesh-sharded exact --
    shard_idx, b_s = build_index(
        rows, ids, {"TPUMS_TOPK_SHARDED": "1", "TPUMS_TOPK_TIER": "exact"})
    assert shard_idx._is_sharded, "sharded arm did not engage the mesh"
    qps, p50, p99 = measure_qps(shard_idx, queries, frame, topk, trials)
    result.update(sharded_build_s=b_s, sharded_qps=qps,
                  sharded_p50_ms=p50, sharded_p99_ms=p99,
                  sharded_speedup=qps / max(result["exact_qps"], 1e-9))
    say(f"[ann-profile] exact/sharded: build {b_s:6.2f}s  "
        f"{qps:>9,.0f} qps  p50 {p50:.2f}ms p99 {p99:.2f}ms  "
        f"({result['sharded_speedup']:.2f}x vs host)")
    # layout parity: same ids, same scores (sharding is not approximate)
    ref = exact_idx.topk_many(queries[:8], 10)
    got = shard_idx.topk_many(queries[:8], 10)
    for r, g in zip(ref, got):
        assert [i for i, _ in r] == [i for i, _ in g], \
            "PARITY FAILURE: sharded ids differ from single-device"
        assert all(abs(a - b) < 1e-3 for (_, a), (_, b) in zip(r, g)), \
            "PARITY FAILURE: sharded scores differ from single-device"

    # -- arm 3: IVF ANN (forced tier; probe+re-rank timed inside) --
    ann_idx, b_s = build_index(
        rows, ids, {"TPUMS_TOPK_SHARDED": "0", "TPUMS_TOPK_TIER": "ivf"})
    assert ann_idx._ann is not None, "IVF arm did not build an ANN tier"
    ann = ann_idx._ann
    qps, p50, p99 = measure_qps(ann_idx, queries, frame, topk, trials)
    recall = recall_vs(exact_idx, ann_idx, queries, topk)
    result.update(
        ivf_build_s=b_s, ivf_qps=qps, ivf_p50_ms=p50, ivf_p99_ms=p99,
        ivf_speedup=qps / max(result["exact_qps"], 1e-9),
        ivf_nlist=ann.nlist, ivf_nprobe=ann.nprobe,
        ivf_list_len=ann.list_len, ivf_dropped=ann.dropped,
        ivf_recall_probe=ann.recall_probe, recall_at_k=recall,
        recall_min=recall_min,
    )
    say(f"[ann-profile] ivf:           build {b_s:6.2f}s  "
        f"{qps:>9,.0f} qps  p50 {p50:.2f}ms p99 {p99:.2f}ms  "
        f"({result['ivf_speedup']:.2f}x vs exact)  "
        f"nlist={ann.nlist} nprobe={ann.nprobe} "
        f"recall@{topk}={recall:.3f} (probe {ann.recall_probe:.3f})")

    # -- re-rank arm: shortlist scoring in isolation (coarse probe cost =
    # ivf total minus this) --
    import jax

    mat = ann_idx._matrix
    q_dev = jax.device_put(queries[:frame])
    ann.search(mat, q_dev, topk).block_until_ready()  # warm
    t0 = time.perf_counter()
    for _ in range(trials):
        ann.search(mat, q_dev, topk).block_until_ready()
    rr = (time.perf_counter() - t0) / trials
    result["ivf_search_kernel_ms"] = rr * 1e3
    say(f"[ann-profile] ivf kernel:    {rr * 1e3:.2f}ms/frame "
        f"(probe+gather+re-rank, host formatting excluded)")

    ok = recall >= recall_min
    result["recall_ok"] = ok
    if as_json:
        print(json.dumps(result))
    if not ok:
        say(f"[ann-profile] RECALL GATE FAILED: {recall:.3f} < {recall_min}")
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
