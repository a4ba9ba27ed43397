"""A catalog that grows while it is read: TOPKV requests that follow recency
and inserts of NEW item ids side by side, each an open loop on a
constant-gap schedule (YCSB core workload D).

This process owns the chip and hosts the program's `ServingJob`
(`serve/consumer.py`: journal consumer, table, top-k index, lookup server).
The catalog arrives by `bulk_load` into the job's index, as the siblings'
does; every insert of the window takes the program's normal path: a writer
child appends item rows of ids the index has never seen to the job's topic
(`benchmark/loadgen_grow.py`), the job's consumer polls them into the table,
the table tells the index, and the next frame writes them into the spare
capacity of the device matrix.  No test hook, no direct `table.put`.

After the window the job is let run dry, `check_queries` of the queries that
were written toward are asked again, and `benchmark/reference_grow.py` (the
writer's own log replayed over a host copy that grows) decides `correct`:
(a) the read-back equals the grown catalog's exact top-k, (b) no answer of
the window lacks a new id that was acknowledged longer ago than
`visible_within_ms`, and none holds a row that nobody had written yet, (c)
no insert was lost, refused or rebuilt for, the index's live rows are the
loaded rows and the inserts, the table's puts are the journal's rows, and
the inserts did change what the checked queries are told.
"""

from __future__ import annotations

# first, so that a program from before the index could grow fails at once
from flink_ms_tpu.parallel.mesh import row_capacity  # noqa: F401

import contextlib
import json
import os
import subprocess
import sys
import time
import types

import numpy as np

from benchmark import reference, reference_grow, synth, synth_grow, synth_updates
from benchmark.drivers.topk_open import REPO, STATE, reduce_samples
from benchmark.drivers.topk_serve_mix import TOPIC, ask, join_logs, parse_reply

# the accounting's counters, by the name the checks give their gain
SERIES = {"applied": "tpums_topk_inserts_applied_total",
          "refused": "tpums_topk_inserts_refused_total",
          "grows": "tpums_topk_grows_total",
          "rebuilds": "tpums_topk_rebuilds_total",
          "device_errors": "tpums_topk_device_errors_total"}


def run(run):
    with serving(run) as live:
        offer(run, live)
        read_back(run, live)
    check(run, live)


class Live:
    """What set-up hands to the window and the window to the check."""

    def __init__(self, job, index, rows, vectors):
        self.job, self.index, self.rows, self.vectors = job, index, rows, vectors
        # the inserts: new id (0-based), pool slot aimed at, journal row
        self.ids = self.toward = self.lines = None
        self.slots = None                      # pool slot of each read
        self.samples = self.writer_log = None  # the children's files
        self.asked = self.answers = None  # (a): pool slots asked again, replies
        self.counted = {}                 # registry counters and the like
        self.apply_log = None


def counters(live):
    """What the accounting reads, now."""
    from flink_ms_tpu.obs import metrics as obs_metrics

    snap = obs_metrics.get_registry().snapshot()
    named = {c["name"]: c["value"] for c in snap["counters"] if not c["labels"]}
    out = {key: named.get(name, 0) for key, name in SERIES.items()}
    out["rows_live"] = next(g["value"] for g in snap["gauges"]
                            if g["name"] == "tpums_topk_rows_live")
    out["puts"] = live.job.table.puts
    out["consumed"] = live.job.ingest_rows
    return out


@contextlib.contextmanager
def serving(run):
    """Set-up: catalog from the seed, the serving job, its index loaded and
    warm, the inserts as journal rows and the reads' pool slots in files,
    the job started last (its checkpoint cadence, 60 s, then lies outside a
    20 s window)."""
    cfg, traffic = run.config, run.traffic
    os.environ.update(cfg.get("env", {}))  # read by the index at construction
    # the job announces itself in the program's registry: keep that inside
    # the checkout too
    os.environ["TPUMS_REGISTRY_DIR"] = os.path.join(run.work_dir, "registry")
    run.acquire()
    run.apply_patches()
    from flink_ms_tpu.serve import topk
    from flink_ms_tpu.serve.consumer import (
        MemoryStateBackend, ServingJob, parse_als_record)
    from flink_ms_tpu.serve.journal import Journal

    if (run.control or {}).get("drop") == "change_notices":
        # the control `lost_inserts`: the table no longer tells the index
        topk.DeviceFactorIndex._on_put = lambda self, key: None
        topk.DeviceFactorIndex._on_put_many = lambda self, keys: None
    k, rank = cfg["k"], cfg["rank"]
    with run.span("catalog_synth_s"):
        ids, rows = synth.catalog(cfg, run.seed)
        vectors = synth.queries(run.seed, traffic["pool"], rank)
    journal = Journal(os.path.join(run.work_dir, "journal"), TOPIC)
    job = ServingJob(journal, STATE, parse_als_record, MemoryStateBackend(),
                     poll_interval_s=traffic["poll_interval_s"],
                     host="127.0.0.1", port=0)
    handler = job.server.topk_handlers[STATE]
    live = Live(job, handler.index, rows, vectors)
    started = False
    try:
        with run.span("index_build_s"):
            handler.index.bulk_load(ids, rows)
        del ids
        with run.span("warm_s"):
            handler.index.topk(np.zeros(rank, np.float32), k)
            handler.index.warm_batch_shapes(k, handler.batcher.max_batch)
        with run.span("inserts_synth_s"):
            span = traffic["lead_s"] + run.seconds
            count = int(np.ceil(span * traffic["insert_rate_per_s"])) + 1
            live.ids, live.toward, values = synth_grow.inserts(
                traffic, run.seed, len(rows), vectors, count)
            live.lines = synth_updates.journal_lines(live.ids, values)
            with open(os.path.join(run.work_dir, "inserts.txt"), "w") as f:
                f.write("".join(line + "\n" for line in live.lines))
            live.slots = synth_grow.latest_slots(
                traffic, run.seed, len(rows), live.toward,
                int(np.ceil(span * traffic["rate_per_s"])))
            np.save(os.path.join(run.work_dir, "slots.npy"), live.slots)
        job.start()
        started = True
        if not job.wait_ready(30.0):
            raise RuntimeError("the serving job did not become ready")
        yield live
    finally:
        if started:
            job.stop()  # its lookup server closes the handler
        else:
            handler.close()


def child(run, role, live, **more):
    """Start one load generator; -> (process, path of its records)."""
    cfg = run.config
    out = os.path.join(run.work_dir, role + ".npz")
    spec = dict(run.traffic, role=role, host="127.0.0.1", port=live.job.port,
                state=STATE, k=cfg["k"], rank=cfg["rank"], seed=run.seed,
                seconds=run.seconds, out=out, **more)
    spec_path = os.path.join(run.work_dir, role + ".json")
    with open(spec_path, "w") as f:
        json.dump(spec, f)
    env = {key: v for key, v in os.environ.items() if not key.startswith("JAX")}
    proc = subprocess.Popen(
        [sys.executable, "-m", "benchmark.loadgen_grow", spec_path],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True, cwd=REPO,
        env=env)
    return proc, out


def offer(run, live):
    """One window of both streams from the two children."""
    traffic = run.traffic
    live.counted["before"] = counters(live)
    procs = [
        child(run, "reads", live,
              slots=os.path.join(run.work_dir, "slots.npy")),
        # `loadgen_mix.writes`' names for what it is told
        child(run, "writes", live,
              updates=os.path.join(run.work_dir, "inserts.txt"),
              update_rate_per_s=traffic["insert_rate_per_s"],
              update_offset_gaps=traffic["insert_offset_gaps"],
              journal_dir=live.job.journal.dir, topic=TOPIC)]
    try:
        for proc, _ in procs:
            if proc.stdout.readline().strip() != "ready":
                raise RuntimeError("a load generator did not come up")
        run.start_trace()
        t_open = time.perf_counter() + traffic["lead_s"] + 0.2
        for proc, _ in procs:
            proc.stdin.write(f"{t_open!r}\n")
            proc.stdin.flush()
        time.sleep(max(0.0, t_open - time.perf_counter()))
        run.begin_window(at=t_open)
        t_close = t_open + run.seconds
        time.sleep(max(0.0, t_close - time.perf_counter()))
        run.end_window(at=t_close)
        for proc, _ in procs:
            rc = proc.wait(timeout=traffic["drain_s"] + 30)
            if rc != 0:
                raise RuntimeError(f"a load generator exited {rc}")
    finally:
        for proc, _ in procs:
            if proc.poll() is None:
                proc.kill()
                proc.wait()
    run.counts["frames"] = run.hist_delta("tpums_topk_device_seconds")[1]
    live.samples, live.writer_log = procs[0][1], procs[1][1]
    reduce_samples(run, live.samples)


def read_back(run, live, patience_s=30.0):
    """(a)'s half on the live job: wait until it has consumed the journal
    to its end, let one more frame drain the index, then ask `check_queries`
    of the queries that the window's inserts were aimed at once more."""
    cfg, job = run.config, live.job
    log = np.load(live.writer_log)
    t_open, t_close = run.window
    give_up = time.perf_counter() + patience_s
    while job.offset < job.journal.end_offset():
        if time.perf_counter() > give_up:
            raise RuntimeError("the serving job never reached the journal's end")
        time.sleep(0.01)
    n = len(log["t_end"])
    in_window = (log["t_end"] >= t_open) & (log["t_end"] < t_close)
    aimed_at = np.unique(live.toward[:n][in_window])
    rng = np.random.default_rng([run.seed, 3])
    live.asked = rng.choice(aimed_at, min(cfg["check_queries"], len(aimed_at)),
                            replace=False)

    def line(slot):
        payload = synth.query_payload(live.vectors[slot])
        return f"TOPKV\t{STATE}\t{cfg['k']}\t{payload}\n".encode()

    ask(job.port, [line(0)])  # the frame that drains what is left
    live.answers = ask(job.port, [line(slot) for slot in live.asked])
    live.counted["after"] = counters(live)
    live.apply_log = live.index.apply_log()


def check(run, live):
    cfg, lim = run.config, run.config["limits"]
    k, err = cfg["k"], lim["topk_score_abs_err"]
    times = np.load(live.writer_log)
    log = reference_grow.read_log(live.lines, times["t_start"], times["t_end"])
    appended = len(log.ids)
    toward = live.toward[:appended]
    t_open, t_close = run.window
    run.counts["inserts_appended"] = appended

    # (a) read-back after quiescence, against the grown catalog
    queries = live.vectors[live.asked]
    got = [parse_reply(r) for r in live.answers]
    ref_ids, ref_scores = reference_grow.final_topk(live.rows, log, queries, k)
    score_err, wrong, clear = reference.compare_topk(
        np.array([[row for row, _ in g] for g in got]),
        np.array([[score for _, score in g] for g in got]),
        ref_ids, ref_scores, lim["topk_gap"])
    run.counts["checked_ranks"] = clear
    run.check("topk_score_abs_err", score_err, err)
    run.check("topk_wrong_ids_at_clear_ranks", wrong, 0)
    run.check("topk_checked_queries", len(live.asked), 1, at_least=True)
    # a run whose inserts change nothing must not pass: the same queries
    # over the catalog as it was loaded
    old_ids, _ = reference.topk(live.rows, queries, k)
    changed = np.mean([set(a[:k]) != set(b[:k]) for a, b in zip(ref_ids, old_ids)])
    run.check("grow_catalog_change", changed, lim["grow_catalog_change"],
              at_least=True)

    # (b) freshness inside the window: the answers that can show a late
    # insert, and a seeded sample of the others
    within = lim["visible_within_ms"] / 1e3
    s = np.load(live.samples)
    with open(live.samples + ".replies.txt") as f:
        replies = f.read().splitlines()
    slots = live.slots
    mine = (s["intended"] >= t_open) & (s["intended"] < t_close)
    answered = np.flatnonzero(mine & (s["done"] > 0) & (s["ok"] == 1))
    markers = {}
    for u, slot in enumerate(toward.tolist()):
        markers.setdefault(slot, []).append(u)
    telling = [i for i in answered
               if any(within <= s["sent"][i] - log.t_end[u] <= 4 * within
                      for u in markers.get(slots[i], ()))]
    others = np.setdiff1d(answered, telling)
    rng = np.random.default_rng([run.seed, 7])
    sample = rng.choice(others, min(cfg["freshness_sample"], len(others)),
                        replace=False)
    stale = 0
    for i in (*telling, *sample.tolist()):
        said = reference_grow.stale_answer(
            live.rows, log, live.vectors[slots[i]], markers.get(slots[i], ()),
            parse_reply(replies[i]), s["sent"][i], s["done"][i], within, err)
        if said:
            stale += 1
            if stale <= 5:
                print(f"[stale] request {i} (pool slot {slots[i]}): {said}",
                      file=sys.stderr, flush=True)
    for i in np.flatnonzero(mine & (s["ok"] != 1))[:3]:
        print(f"[failed] request {i}: {replies[i][:300]!r}", file=sys.stderr,
              flush=True)
    run.counts["freshness_checked"] = len(telling) + len(sample)
    run.counts["freshness_telling"] = len(telling)
    run.check("grow_stale_answers", stale, 0)
    run.check("grow_freshness_checked", len(telling), 1, at_least=True)

    # (c) accounting, from before the first insert to after the read-back
    before, after = live.counted["before"], live.counted["after"]
    gained = {name: after[name] - before[name] for name in after}
    run.check("grow_inserts_lost", appended - gained["applied"], 0)
    run.check("grow_inserts_refused", gained["refused"], 0)
    run.check("grow_rebuilds", gained["rebuilds"] + gained["grows"], 0)
    # no write made the index drop a row it served: the loaded rows and the
    # inserts are all live
    run.check("grow_rows_not_live",
              abs(len(live.rows) + appended - after["rows_live"]), 0)
    run.counts["inserts_applied"] = gained["applied"]
    if appended:
        run.counts["in_place_share"] = gained["applied"] / appended
    run.counts["device_errors"] = gained["device_errors"]
    if gained["device_errors"]:
        print(f"[note] the index survived {gained['device_errors']} device "
              "error(s)", file=sys.stderr, flush=True)
    run.check("grow_puts_not_from_journal",
              abs(gained["puts"] - gained["consumed"])
              + abs(gained["consumed"] - appended), 0)

    # the update path's delays, for the rows appended inside the window
    if live.apply_log:
        # `join_logs` reads the written rows as `.keys`
        t_put, t_applied = join_logs(
            types.SimpleNamespace(keys=log.ids), live.apply_log)
        mine = (log.t_end >= t_open) & (log.t_end < t_close)
        seen, first = mine & ~np.isnan(t_applied), mine & ~np.isnan(t_put)
        run.series["insert_visible_ms"] = (t_applied - log.t_end)[seen] * 1e3
        if first.any():
            run.counts["consume_lag_mean_ms"] = float(
                (t_put - log.t_end)[first].mean() * 1e3)
        if seen.any():
            # what `visible_within_ms` is read against
            run.counts["insert_visible_max_ms"] = float(
                run.series["insert_visible_ms"].max())
            print(f"[note] insert_visible_max_ms = "
                  f"{run.counts['insert_visible_max_ms']:.3f} over "
                  f"{int(seen.sum())} rows", file=sys.stderr, flush=True)

