"""Open loop over the IVF tier: `topk_open`'s load (`offer`) on a catalog
with cluster structure, and a check that holds the tier to what an inverted
file promises.

Set-up makes the catalog from the seed (`synth_t2i_clustered`), installs it
with `DeviceFactorIndex.bulk_load` under the configuration's `env`
(`TPUMS_TOPK_TIER=ivf`), warms every batch shape and starts `LookupServer`.
After the window the centroids and the list membership are fetched from the
built index, and a seeded sample of the answered queries is answered again
by `reference_ivf.topk` over those lists and by `reference.topk` over the
whole host catalog.  A tree whose tier has no list-ordered layout is turned
away below, in seconds, before it touches a device: its build of this
catalog would run the device out of memory and leave the exact tier serving.
"""

from __future__ import annotations

import contextlib
import json
import os

import numpy as np

from benchmark import reference, reference_ivf, synth, synth_t2i_clustered
from benchmark.drivers.topk_open import STATE, offer
from benchmark.readers.counter_share import gain
from flink_ms_tpu.serve import ann as tier

if not hasattr(tier, "block_rows"):
    raise SystemExit("topk_open_ivf: this tree's IVF tier (serve/ann.py) has no "
                     "list-ordered block layout; the cell cannot run on it")

FRAMES = "tpums_ann_frames_total"
QUERIES = "tpums_ann_queries_total"


def run(run):
    with serving(run) as (server, rows, index):
        out = offer(run, server)
        built = fetch_lists(index, len(rows))
    check(run, run.config, rows, out, built)


@contextlib.contextmanager
def serving(run):
    """`topk_open.serving` on the clustered catalog; also yields the index."""
    cfg = run.config
    os.environ.update(cfg.get("env", {}))  # read by the index at construction
    run.acquire()
    run.apply_patches()
    from flink_ms_tpu.serve.server import LookupServer
    from flink_ms_tpu.serve.table import ModelTable
    from flink_ms_tpu.serve.topk import make_als_topk_handler

    k, rank = cfg["k"], cfg["rank"]
    with run.span("catalog_synth_s"):
        ids, rows = synth_t2i_clustered.catalog(cfg, run.seed)
    table = ModelTable()
    handler = make_als_topk_handler(table)
    server = None
    try:
        with run.span("index_build_s"):
            handler.index.bulk_load(ids, rows)
        del ids
        with run.span("warm_s"):
            handler.index.topk(np.zeros(rank, np.float32), k)
            handler.index.warm_batch_shapes(k, handler.batcher.max_batch)
        server = LookupServer({STATE: table}, host="127.0.0.1", port=0,
                              topk_handlers={STATE: handler}).start()
        yield server, rows, handler.index
    finally:
        if server is not None:
            server.stop()
        handler.close()


def fetch_lists(index, n):
    """What the built index says of itself -> centroids (nlist, rank), the
    list of each catalog row (-1: in none), how often each row occurs in the
    lists, nprobe; None where the exact tier serves."""
    ann = index._ann
    if ann is None:
        return None
    at = ann.membership()                     # list of each matrix position
    holds = np.fromiter((int(i) - 1 if i is not None else -1 for i in index._ids),
                        np.int64, len(index._ids))    # row at each position
    listed = (at >= 0) & (holds >= 0)
    member = np.full(n, -1, np.int64)
    member[holds[listed]] = at[listed]
    return {"centroids": np.asarray(ann.centroids), "member": member,
            "times_listed": np.bincount(holds[listed], minlength=n),
            "stray": int(((at >= 0) != (holds >= 0)).sum()),
            "nprobe": ann.nprobe, "nlist": ann.nlist}


def answered(run, cfg, path):
    """`check_queries` of the pool's vectors that the window answered ->
    (vectors, answered ids (Q, k) 0-based, answered scores)."""
    with open(path + ".replies.json") as f:
        replies = json.load(f)
    vectors = synth.queries(run.seed, run.traffic["pool"], cfg["rank"])
    rng = np.random.default_rng([run.seed, 3])
    seen = np.array(sorted(int(i) for i in replies))
    picked = rng.choice(seen, min(cfg["check_queries"], len(seen)), replace=False)
    got_ids, got_scores = [], []
    for i in picked:
        pairs = [t.rpartition(":") for t in replies[str(i)][2:].split(";")]
        got_ids.append([int(item) - 1 for item, _, _ in pairs])
        got_scores.append([float(score) for _, _, score in pairs])
    return vectors[picked], np.array(got_ids), np.array(got_scores)


def check(run, cfg, rows, path, built):
    lim = cfg["limits"]
    gained = gain(run, FRAMES) or 0
    # (e) a frame in flight when the window opens or closes is counted on one
    # side only; single queries answered inline count as IVF frames alone
    run.check("ivf_frames_not_from_the_tier", run.counts["frames"] - gained, 1)
    # the same by queries, which also sees a run whose requests were all
    # answered inline (no batched frame at all): every answered request is
    # one query through the tier, but for those in flight at the window's
    # edges, at most one a connection
    run.check("ivf_queries_not_from_the_tier",
              run.attempted - run.failed - (gain(run, QUERIES) or 0),
              run.traffic["connections"])
    run.check("ivf_build_failures",
              run.counter("tpums_ann_build_failures_total") or 0, 0)
    if built is None:
        run.check("ivf_tier_built", 0, 1, at_least=True)
        return
    n, k = len(rows), cfg["k"]
    # (c) the lists are a partition of the rows; a seeded sample of rows
    # against their float64-nearest centroid (the program assigns with a
    # product at the device's default precision: one bf16 pass on a TPU)
    run.check("ivf_rows_not_in_one_list",
              int((built["times_listed"] != 1).sum()) + built["stray"], 0)
    sample = np.random.default_rng([run.seed, 7]).choice(
        n, min(cfg["assign_check_rows"], n), replace=False)
    nearest = reference_ivf.nearest_centroid(rows[sample], built["centroids"])
    run.check("ivf_misassigned_share",
              float((nearest != built["member"][sample]).mean()),
              lim["ivf_misassigned_share"])
    vectors, got_ids, got_scores = answered(run, cfg, path)
    # (a) every returned score is that row's score
    true = np.einsum("qck,qk->qc", rows[got_ids].astype(np.float64),
                     vectors.astype(np.float64))
    run.check("topk_score_abs_err", float(np.abs(got_scores - true).max()),
              lim["topk_score_abs_err"])
    # (b) ids against the plain inverted file over the same lists, where the
    # probe's boundary is clear
    ref_ids, ref_scores, margin = reference_ivf.topk(
        rows, built["member"], built["centroids"], vectors, built["nprobe"], k)
    clear = margin > lim["topk_gap"]
    _, wrong, ranks = reference.compare_topk(
        got_ids[clear], got_scores[clear], ref_ids[clear], ref_scores[clear],
        lim["topk_gap"])
    run.counts["checked_ranks"] = ranks
    run.counts["probe_unclear_queries"] = int((~clear).sum())
    run.check("topk_wrong_ids_at_clear_ranks", wrong, 0)
    run.check("topk_checked_queries", int(clear.sum()), 1, at_least=True)
    # (d) recall against the exact ranking of the whole catalog
    exact_ids, _ = reference.topk(rows, vectors, k)
    run.counts["recall_at_10"] = reference_ivf.recall(got_ids, exact_ids[:, :k])
    run.counts["reference_recall_at_10"] = reference_ivf.recall(
        ref_ids[:, :k], exact_ids[:, :k])
    run.check("ivf_recall_at_10", run.counts["recall_at_10"],
              lim["ivf_recall_at_10"], at_least=True)
