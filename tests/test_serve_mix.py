"""A catalog that is written while it is read (PR 54): a `ServingJob` whose
writes arrive through the journal only, held to `benchmark/reference_live.py`
(the writer's log replayed over a host copy) at a size the CPU holds; the
update path's counters, log and stages; the jitted scatter; and the cell
`bigann-t2i-10m-ycsb-a.serve-mix` rehearsed at 20,000 rows.
"""

import json
import os
import shutil
import socket
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from benchmark import reference, reference_live, synth, synth_updates
from flink_ms_tpu.obs import metrics as obs_metrics
from flink_ms_tpu.serve import topk
from flink_ms_tpu.serve.consumer import (
    ALS_STATE, MemoryStateBackend, ServingJob, parse_als_record)
from flink_ms_tpu.serve.journal import Journal
from flink_ms_tpu.serve.table import ModelTable

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = os.path.join(REPO, "benchmark", "tests", "tiny-ycsb-a", "BENCHMARK.json")
TINY_CELL = "t2i-tiny-ycsb-a.serve-mix"
ROWS, RANK, K, POOL, SEED = 20_000, 16, 10, 64, 54
# over the top-10 threshold of a unit query over 20,000 rows of
# normal(0, 1/16) (about 0.82): the written row enters its query's top-10
MIX = {"update_zipf": 0.99, "update_pull": 1.5}


def counter(name):
    snap = obs_metrics.get_registry().snapshot()
    return next(c["value"] for c in snap["counters"]
                if c["name"] == name and not c["labels"])


class Mix:
    """A serving job over a bulk-loaded catalog, a writer that reaches it
    through the journal alone, and the writer's own log."""

    def __init__(self, work_dir):
        cfg = {"rows": ROWS, "rank": RANK}
        ids, self.base = synth.catalog(cfg, SEED)
        self.vectors = synth.queries(SEED, POOL, RANK)
        self.journal = Journal(os.path.join(work_dir, "journal"), "als_models")
        self.job = ServingJob(
            Journal(self.journal.dir, "als_models"), ALS_STATE,
            parse_als_record, MemoryStateBackend(), poll_interval_s=0.02,
            host="127.0.0.1", port=0)
        self.index = self.job.server.topk_handlers[ALS_STATE].index
        # the registry's series are the process's: other indexes feed them
        self.stats_before = self.index.update_stats()
        self.index.bulk_load(ids, self.base)
        self.index.topk(np.zeros(RANK, np.float32), K)
        self.index.warm_batch_shapes(K, 4)
        self.job.start()
        assert self.job.wait_ready(30.0)
        self.lines, self.toward, self.t_start, self.t_end = [], [], [], []
        self.drawn = 0

    def updates(self, count):
        """The next `count` updates of the seed's stream, not yet written."""
        rows, toward, values = synth_updates.updates(
            MIX, SEED + self.drawn, self.base, self.vectors, count)
        self.drawn += 1
        return synth_updates.journal_lines(rows, values), toward.tolist()

    def write(self, line, toward):
        self.t_start.append(time.perf_counter())
        self.journal.append([line], flush=False)
        self.t_end.append(time.perf_counter())
        self.lines.append(line)
        self.toward.append(toward)

    def log(self):
        return reference_live.read_log(self.lines, self.t_start, self.t_end)

    def consumed(self, patience_s=20.0):
        give_up = time.perf_counter() + patience_s
        while self.job.offset < self.journal.end_offset():
            assert time.perf_counter() < give_up, "the job never caught up"
            time.sleep(0.005)

    def ask(self, slots):
        """TOPKV over the lookup server -> [(sent, done, [(row, score)])]."""
        out = []
        with socket.create_connection(("127.0.0.1", self.job.port), 10.0) as s:
            s.settimeout(30.0)
            reader = s.makefile("rb")
            for slot in slots:
                payload = synth.query_payload(self.vectors[slot])
                sent = time.perf_counter()
                s.sendall(f"TOPKV\t{ALS_STATE}\t{K}\t{payload}\n".encode())
                reply = reader.readline().decode().rstrip("\n")
                done = time.perf_counter()
                assert reply.startswith("V\t"), reply
                pairs = [t.rpartition(":") for t in reply[2:].split(";")]
                out.append((sent, done, [(int(i) - 1, float(v))
                                         for i, _, v in pairs]))
        return out


@pytest.fixture(scope="module")
def mix(tmp_path_factory):
    registry = os.environ.get("TPUMS_REGISTRY_DIR")
    work = tmp_path_factory.mktemp("serve_mix")
    os.environ["TPUMS_REGISTRY_DIR"] = str(work / "registry")
    live = Mix(str(work))
    try:
        yield live
    finally:
        live.job.stop()
        if registry is None:
            os.environ.pop("TPUMS_REGISTRY_DIR", None)
        else:
            os.environ["TPUMS_REGISTRY_DIR"] = registry


def test_quiesced_answers_equal_the_replayed_catalog(mix):
    lines, toward = mix.updates(300)
    for line, slot in zip(lines, toward):
        mix.write(line, slot)
    mix.consumed()
    mix.ask([0])  # the frame that drains what the last poll left
    slots = sorted(set(toward))[:16]
    got = mix.ask(slots)
    log = mix.log()
    ref_ids, ref_scores = reference_live.final_topk(
        mix.base, log, mix.vectors[slots], K)
    err, wrong, clear = reference.compare_topk(
        np.array([[row for row, _ in reply] for _, _, reply in got]),
        np.array([[score for _, score in reply] for _, _, reply in got]),
        ref_ids, ref_scores, 1e-4)
    assert err <= 1e-5 and wrong == 0 and clear > 100
    # and the writes did change what these queries are told
    old_ids, _ = reference.topk(mix.base, mix.vectors[slots], K)
    changed = [set(a[:K]) != set(b[:K]) for a, b in zip(ref_ids, old_ids)]
    assert np.mean(changed) >= 0.8
    # the reference itself against a dict of last writes
    last = dict(zip(log.keys.tolist(), log.rows))
    final = reference_live.replay(mix.base, log)
    for key, row in last.items():
        assert np.array_equal(final[key], row)
    untouched = np.setdiff1d(np.arange(ROWS), log.keys)
    assert np.array_equal(final[untouched], mix.base[untouched])


def test_an_answer_in_flight_is_never_staler_than_the_bound(mix):
    lines, toward = mix.updates(400)
    first = len(mix.lines)
    stop = threading.Event()

    def writer():
        for line, slot in zip(lines, toward):
            if stop.is_set():
                return
            mix.write(line, slot)
            time.sleep(0.002)

    thread = threading.Thread(target=writer)
    thread.start()
    try:
        answers = []
        while thread.is_alive():
            answers += [(slot, a) for slot, a in
                        zip(range(POOL), mix.ask(range(POOL)))]
    finally:
        stop.set()
        thread.join(30)
    assert not thread.is_alive() and len(answers) > 2 * POOL
    log = mix.log()
    markers = {}
    for u in range(first, len(mix.toward)):
        markers.setdefault(mix.toward[u], []).append(u)
    within = 0.5  # a shared CPU: twenty-five polls
    owed = 0
    for slot, (sent, done, reply) in answers:
        mine = markers.get(slot, [])
        owed += sum(sent - log.t_end[u] > within for u in mine)
        assert reference_live.stale_answer(
            mix.base, log, mix.vectors[slot], mine, reply, sent, done,
            within, 1e-5) is None
    assert owed > 10  # the bound was put to the test
    # the same answers held to a bound no system keeps: stale ones show
    assert any(reference_live.stale_answer(
        mix.base, log, mix.vectors[slot], markers.get(slot, []), reply,
        sent + 10.0, done + 10.0, 0.0, 1e-5) for slot, (sent, done, reply)
        in answers[:POOL])


def test_a_key_written_twice_before_one_drain_is_applied_once(mix):
    mix.consumed()
    mix.ask([0])  # nothing waits
    names = ("tpums_topk_updates_applied_total",
             "tpums_topk_updates_coalesced_total",
             "tpums_topk_update_drains_total")
    before = [counter(n) for n in names]
    row = 4321
    # (pulled harder than the stream's rows: slot 5 has a dozen of those)
    first = mix.base[row] + np.float32(3.0) * mix.vectors[3]
    second = mix.base[row] + np.float32(3.0) * mix.vectors[5]
    for value, slot in ((first, 3), (second, 5)):
        mix.write(synth_updates.journal_lines([row], [value])[0], slot)
    mix.consumed()  # both puts are in; no query has drained yet
    (_, _, reply), = mix.ask([5])
    assert [counter(n) - b for n, b in zip(names, before)] == [1, 1, 1]
    key, t_put, t_applied, puts = mix.index.apply_log()[-1]
    assert key == f"{row + 1}-I" and puts == 2 and t_put <= t_applied
    score = float(second.astype(np.float64) @ mix.vectors[5].astype(np.float64))
    assert (row, pytest.approx(score, abs=1e-5)) in [
        (r, s) for r, s in reply]  # the LAST value, read back


def test_applied_plus_coalesced_is_appended_and_nothing_is_rebuilt(mix):
    for line, slot in zip(*mix.updates(300)):
        mix.write(line, slot)
    mix.consumed()
    mix.ask([0])
    stats = mix.index.update_stats()
    gained = {key: stats[key] - mix.stats_before[key]
              for key in ("applied", "coalesced", "drains")}
    assert gained["applied"] + gained["coalesced"] == len(mix.lines) >= 300
    assert gained["coalesced"] > 0  # zipfian keys: hot rows are rewritten
    assert gained["drains"] > 0 and stats["visible_mean_ms"] > 0
    assert mix.index.full_builds == 1  # bulk_load, and no rebuild since
    assert mix.job.table.puts == mix.job.ingest_rows == len(mix.lines)
    covered = sum(puts for _, _, _, puts in mix.index.apply_log())
    assert covered == len(mix.lines)


def test_health_and_metrics_carry_the_update_path(mix):
    health = mix.job.health()
    assert health["index_updates"] == mix.index.update_stats()
    with socket.create_connection(("127.0.0.1", mix.job.port), 10.0) as s:
        s.sendall(b"METRICS\n")
        reply = s.makefile("rb").readline().decode()
    assert reply.startswith("J\t")
    for name in ("tpums_topk_updates_applied_total",
                 "tpums_topk_update_drains_total",
                 "tpums_topk_updates_coalesced_total",
                 "tpums_topk_update_visible_seconds"):
        assert name in reply, name


def resident(rows, layout):
    """`rows` on one device, or row-sharded over a mesh of 4 of the suite's
    8 CPU devices as `DeviceFactorIndex._pack` lays them out."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from flink_ms_tpu.parallel.mesh import BLOCK_AXIS, make_mesh

    if layout == "one_device":
        return jax.device_put(rows, jax.devices()[0])
    mesh = make_mesh(devices=jax.devices()[:4])
    return jax.device_put(rows, NamedSharding(mesh, P(BLOCK_AXIS, None)))


def test_the_jitted_scatter_is_the_eager_one_to_the_bit_and_compiles_once():
    import jax.numpy as jnp

    rng = np.random.default_rng(1)
    host = rng.standard_normal((4096, 24), dtype=np.float32)
    matrix = topk._scatter_rows(
        resident(host, "one_device"), np.zeros(8, np.int32),
        np.zeros((8, 24), np.float32), 0)
    compiled = topk._scatter_program()._cache_size()
    for count in (8, 3, 5):
        pos = rng.integers(0, 4096, 8).astype(np.int32)
        vec = rng.standard_normal((8, 24), dtype=np.float32)
        want = np.array(jnp.asarray(host).at[pos[:count]].set(vec[:count]))
        assert not np.array_equal(want, host)
        # (a host view of the matrix itself would hold its buffer, and the
        # CPU runtime would then copy in place of taking the donation)
        got = topk._scatter_rows(matrix, pos, vec, count)
        assert matrix.is_deleted()  # donated: the result is its buffer
        host = np.array(got)
        assert np.array_equal(host, want)
        matrix = got
        del got
    assert topk._scatter_program()._cache_size() == compiled


@pytest.mark.parametrize("layout", ["one_device", "mesh_of_4"])
@pytest.mark.parametrize("case", ["whole_batch", "count_below_the_shape",
                                  "a_position_twice", "count_zero"])
def test_the_update_program_writes_the_real_rows_in_batch_order(layout, case):
    """`_scatter_program` against `matrix.at[pos].set(vec)` over the real
    rows: rows past the count are not written, a position that occurs twice
    ends with the later row, a count of 0 (the warm-up) hands the same
    values back; on one device and in the `shard_map` form, which the
    matrix's sharding alone picks."""
    rng = np.random.default_rng(7)
    n, width, cap = 4096, 24, 32
    for _ in range(3):
        base = rng.standard_normal((n, width), dtype=np.float32)
        pos = rng.choice(n, cap, replace=False).astype(np.int32)
        vec = rng.standard_normal((cap, width), dtype=np.float32)
        count = {"whole_batch": cap, "count_below_the_shape": 11,
                 "a_position_twice": 11, "count_zero": 0}[case]
        if case == "a_position_twice":
            pos[7] = pos[2]  # both real: row 7 of the batch stays
            pos[20] = pos[3]  # past the count: never written
        want = base.copy()
        for i in range(count):
            want[pos[i]] = vec[i]
        matrix = resident(base, layout)
        sharding = matrix.sharding
        got = topk._scatter_rows(matrix, pos, vec, count)
        assert matrix.is_deleted()
        assert got.sharding == sharding
        assert np.array_equal(np.asarray(got), want)
        if case == "count_zero":
            assert np.array_equal(np.asarray(got), base)
        else:
            assert not np.array_equal(want, base)
    if layout == "mesh_of_4":
        assert topk._scatter_program(sharding.mesh) \
            is not topk._scatter_program()


@pytest.mark.parametrize("layout", ["one_device", "mesh_of_4"])
def test_donation_is_taken_on_the_cpu_without_a_word(layout, recwarn):
    """The host-pinned replicas and this suite run the program on the CPU
    backend: the argument is donated there too, and jax says nothing (a
    backend that cannot take a donation warns and copies)."""
    rng = np.random.default_rng(8)
    matrix = resident(rng.standard_normal((512, 8), dtype=np.float32), layout)
    pos, vec = np.arange(4, dtype=np.int32), np.ones((4, 8), np.float32)
    out = topk._scatter_rows(matrix, pos, vec, 4)
    out.block_until_ready()
    assert matrix.is_deleted() and not out.is_deleted()
    assert not [w for w in recwarn if "donat" in str(w.message).lower()]
    lowered = topk._scatter_program(
        None if layout == "one_device" else out.sharding.mesh).lower(
        out, pos, vec, np.int32(4)).as_text()
    assert "tf.aliasing_output = 0" in lowered or "jax.buffer_donor" in lowered


@pytest.mark.parametrize("sharded", ["0", "1"])
def test_the_old_handle_is_deleted_and_the_index_still_answers(
        monkeypatch, sharded):
    """Three drains, each donating the matrix it found: the handle the index
    held before is deleted every time, and a TOPK afterwards is the
    reference's over the replayed rows."""
    monkeypatch.setenv("TPUMS_TOPK_SHARDED", sharded)
    monkeypatch.setenv("TPUMS_TOPK_TIER", "exact")
    table = ModelTable()
    index = topk.DeviceFactorIndex(table)
    rng = np.random.default_rng(9)
    base = rng.standard_normal((256, 8), dtype=np.float32)
    index.bulk_load([str(i + 1) for i in range(256)], base)
    assert index._is_sharded == (sharded == "1")
    drains = counter("tpums_topk_update_drains_total")
    in_place = counter("tpums_topk_update_drains_in_place_total")
    q = rng.standard_normal(8).astype(np.float32)
    replayed = base.copy()
    for drain in range(3):
        held = index._matrix
        for row in rng.choice(256, 5, replace=False):
            replayed[row] = rng.standard_normal(8).astype(np.float32) * 3
            table.put(f"{row + 1}-I",
                      ";".join("%.9g" % x for x in replayed[row]))
        got = index.topk(q, 10)
        assert held.is_deleted() and not index._matrix.is_deleted()
        scores = replayed @ q
        order = np.argsort(-scores)[:10]
        assert [item for item, _ in got] == [str(i + 1) for i in order]
        assert np.allclose([s for _, s in got], scores[order], atol=1e-5)
    # every drain went through the aliasing program, in either layout
    assert counter("tpums_topk_update_drains_total") == drains + 3
    assert counter("tpums_topk_update_drains_in_place_total") == in_place + 3
    assert index.full_builds == 1


def test_the_warm_up_of_a_build_leaves_row_0_as_loaded():
    """`_assemble` warms the scatter on the NEW matrix, which the program
    takes: a warm-up that wrote would zero row 0, and one that threw its
    result away would delete the matrix."""
    index = topk.DeviceFactorIndex(ModelTable())
    base = np.random.default_rng(10).standard_normal(
        (64, 8), dtype=np.float32)
    assembled = index._assemble([str(i + 1) for i in range(64)], base, 8)
    assert not assembled["matrix"].is_deleted()
    assert np.array_equal(np.asarray(assembled["matrix"])[:64], base)
    assert not np.asarray(assembled["matrix"])[64:].any()  # spare rows
    index.bulk_load([str(i + 1) for i in range(64)], base)
    assert index.topk(base[0], 1)[0] == (
        "1", pytest.approx(float(base[0] @ base[0]), rel=1e-6))


def test_in_place_drains_are_all_the_drains_of_a_live_job(mix):
    """`mix_in_place_share`'s two counters on one device: every drain of the
    module's job, whatever the tests before this one wrote, took its
    matrix."""
    lines, toward = mix.updates(12)
    for line, slot in zip(lines, toward):
        mix.write(line, slot)
    mix.consumed()
    mix.ask([0])
    drains = counter("tpums_topk_update_drains_total")
    assert drains > 0
    assert counter("tpums_topk_update_drains_in_place_total") == drains


def test_a_partial_drain_leaves_the_oldest_remaining_stamp():
    index = topk.DeviceFactorIndex(ModelTable())
    for key in ("1-I", "2-I", "3-I"):
        index._on_put_many([key, "1-U"])  # users are not the index's
        time.sleep(0.005)
    stamps = {key: t for key, (t, _) in index._dirty.items()}
    assert list(stamps) == ["1-I", "2-I", "3-I"]
    assert index._oldest_dirty_ts == stamps["1-I"]
    assert list(index._drain_dirty(limit=1)) == ["1-I"]  # the oldest first
    assert index._oldest_dirty_ts == stamps["2-I"]
    index._on_put("2-I")  # written again while it waits: its first stamp stays
    assert index._dirty["2-I"] == (stamps["2-I"], 2)
    assert list(index._drain_dirty(limit=1)) == ["2-I"]
    assert index._oldest_dirty_ts == stamps["3-I"]
    index._observe_health()
    assert 0.0 < index._obs_staleness.value < 5.0
    index._drain_dirty()
    assert index._oldest_dirty_ts is None and not index._dirty


def test_a_written_row_enters_its_query_top_ten_at_the_real_shape():
    """`assumed.update_values` of the configuration: the tenth largest of
    5,000,000 scores normal(0, 1/200) is about 0.33, and a row pulled 0.5
    toward the query scores 0.5 + normal(0, 1/200)."""
    rng = np.random.default_rng(3)
    sd = 1.0 / np.sqrt(200)
    tenth = [np.partition(rng.standard_normal(5_000_000, dtype=np.float32) * sd,
                          -10)[-10] for _ in range(3)]
    assert 0.30 < min(tenth) and max(tenth) < 0.36
    enters = np.mean(0.5 + rng.standard_normal(100_000) * sd > max(tenth))
    assert enters > 0.97


def test_update_keys_follow_the_zipfian_law():
    n, draws = 5_000_000, 200_000
    rows = synth_updates.zipf_rows(7, n, 0.99, draws)
    assert rows.min() >= 0 and rows.max() < n
    weights = np.arange(1, n + 1, dtype=np.float64) ** -0.99
    share = weights / weights.sum()
    ids, counts = np.unique(rows, return_counts=True)
    hottest = counts.max() / draws
    assert hottest == pytest.approx(share[0], rel=0.1)  # 5.8% of the writes
    # the hundred hottest rows take their mass, and they lie anywhere
    top = ids[np.argsort(-counts)[:100]]
    assert np.sort(counts)[-100:].sum() / draws == pytest.approx(
        share[:100].sum(), rel=0.05)
    assert top.min() > 100 or top.max() > n // 2
    assert np.array_equal(rows, synth_updates.zipf_rows(7, n, 0.99, draws))


def rehearse(trace, *more):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--bench", TINY, "--workload",
         TINY_CELL, "--seed", "3000000019", "--seconds", "2", "--trace",
         str(trace), *more],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600)
    shutil.rmtree(os.path.join(REPO, ".benchwork", TINY_CELL), ignore_errors=True)
    assert done.returncode == 0, done.stderr[-2000:]
    return json.loads(done.stdout.strip().splitlines()[-1])


def test_rehearsal_prints_the_contract_line():
    line = rehearse(0)
    assert line["correct"] is True, [c for c in line["checks"] if not c["ok"]]
    assert line["failed"] == 0 and line["attempted"] == 1200
    assert line["device"]["platform"] == "cpu"  # a rehearsal says so
    assert set(line["metrics"]) == {"topk_p50_ms", "setup_s"}
    checks = {c["name"]: c["value"] for c in line["checks"]}
    assert {"topk_score_abs_err", "topk_wrong_ids_at_clear_ranks",
            "mix_catalog_change", "mix_stale_answers", "mix_freshness_checked",
            "mix_updates_lost", "mix_rebuilds",
            "mix_puts_not_from_journal"} <= set(checks)
    assert checks["mix_freshness_checked"] > 20
    # what needs no trace shows under `layers`
    assert {"mix_update_visible_p50_ms", "mix_update_visible_p99_ms",
            "mix_consume_lag_ms", "mix_rows_per_drain", "mix_drain_share",
            "paced_dispatch_ms"} <= set(line["layers"])
    assert 1.0 <= line["layers"]["mix_rows_per_drain"]["value"] <= 64.0
    assert 0.0 < line["layers"]["mix_drain_share"]["value"] <= 100.0


def test_rehearsal_whose_index_is_not_told_of_writes_is_not_correct():
    line = rehearse(0, "--control", "lost_updates")
    assert line["correct"] is False
    failed = {c["name"] for c in line["checks"] if not c["ok"]}
    assert {"topk_wrong_ids_at_clear_ranks", "mix_stale_answers",
            "mix_updates_lost"} <= failed  # (a), (b) and (c)


def test_every_new_benchmark_file_is_listed_for_the_reviewer():
    with open(os.path.join(REPO, "benchmark", "README-serve-mix.md")) as f:
        listed = f.read()
    for name in ("bigann-t2i-10m-ycsb-a.json", "serve-mix.json",
                 "topk_serve_mix.py", "loadgen_mix.py", "synth_updates.py",
                 "reference_live.py", "roofline_update_scatter.py",
                 "trace_stage", "tiny-ycsb-a", "mix_update_scatter_roofline"):
        assert name in listed, name


def test_the_contract_gained_one_configuration_and_one_cell():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(c for c in bench["configs"]
                 if c["name"] == "bigann-t2i-10m-ycsb-a")
    cells = [w for w in bench["workloads"] if w["config"] == entry["name"]]
    assert len(cells) == 1
    cell = cells[0]
    assert cell == {**cell, "name": "bigann-t2i-10m-ycsb-a.serve-mix",
                    "traffic": "serve-mix", "chips": 1}
    with open(os.path.join(REPO, entry["file"])) as f:
        cfg = json.load(f)
    assert cfg["source"] == entry["source"]
    assert cfg["reduced"] == entry["reduced"] == ["rows"]
    assert (cfg["rows"], cfg["rank"], cfg["k"]) == (5_000_000, 200, 10)
    with open(os.path.join(REPO, "benchmark", "traffic", "serve-mix.json")) as f:
        traffic = json.load(f)
    # the issue's table, to the digit
    assert {key: traffic[key] for key in (
        "connections", "rate_per_s", "pool", "lead_s", "drain_s",
        "update_rate_per_s", "update_offset_gaps", "update_zipf",
        "update_pull", "poll_interval_s")} == {
        "connections": 64, "rate_per_s": 600, "pool": 4096, "lead_s": 1.0,
        "drain_s": 10.0, "update_rate_per_s": 600, "update_offset_gaps": 0.5,
        "update_zipf": 0.99, "update_pull": 0.5, "poll_interval_s": 0.02}
    assert cfg["poll_interval_s"] == traffic["poll_interval_s"]
    # every metric the sibling reports, the new cell reports too
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if "bigann-t2i-10m.topk-paced" in metric.get("workloads", ()):
            assert cell["name"] in metric["workloads"], metric["name"]


def test_a_scatter_the_device_refuses_loses_no_row_and_fails_no_query(
        monkeypatch, capsys):
    """A scatter that fails before it has taken its argument (the refusal
    PR 54 met was a second matrix with no room): the rows wait for the next
    frame, the frame answers from the matrix it has, and the failure is
    counted."""
    table = ModelTable()
    index = topk.DeviceFactorIndex(table)
    rng = np.random.default_rng(2)
    base = rng.standard_normal((64, 8), dtype=np.float32)
    index.bulk_load([str(i + 1) for i in range(64)], base)
    real, calls = topk._scatter_rows, []

    def refuse_once(matrix, pos, vec, count):
        calls.append(1)
        if len(calls) == 1:
            raise RuntimeError("RESOURCE_EXHAUSTED: no room for 4.0G")
        return real(matrix, pos, vec, count)

    q = np.zeros(8, np.float32)
    q[0] = 1.0
    row = base[6].copy()
    row[0] = 50.0
    table.put("7-I", ";".join("%.9g" % x for x in row))
    assert index.topk(q, 1)[0] == ("7", pytest.approx(50.0))
    errors = counter("tpums_topk_device_errors_total")
    applied = counter("tpums_topk_updates_applied_total")
    coalesced = counter("tpums_topk_updates_coalesced_total")
    # refused: the frame answers from the matrix it has ...
    monkeypatch.setattr(topk, "_scatter_rows", refuse_once)
    row[0] = 70.0
    table.put("7-I", ";".join("%.9g" % x for x in row))
    assert index.topk(q, 1)[0] == ("7", pytest.approx(50.0))
    assert len(calls) == 1
    assert counter("tpums_topk_device_errors_total") == errors + 1
    assert "update scatter failed" in capsys.readouterr().err
    stamp = index._dirty["7-I"]
    assert stamp[1] == 1 and index._oldest_dirty_ts == stamp[0]
    # ... the row is written again while it waits, and the next frame
    # applies the last value once, for both puts
    row[0] = 90.0
    table.put("7-I", ";".join("%.9g" % x for x in row))
    assert index._dirty["7-I"] == (stamp[0], 2)
    assert index.topk(q, 1)[0] == ("7", pytest.approx(90.0))
    assert counter("tpums_topk_updates_applied_total") == applied + 1
    assert counter("tpums_topk_updates_coalesced_total") == coalesced + 1
    assert index.apply_log()[-1][0] == "7-I" and index.apply_log()[-1][3] == 2


def test_a_rebuild_that_fails_puts_its_keys_back_with_their_counts(
        monkeypatch, capsys):
    """The background rebuild drains the dirty set before its snapshot.  If
    it fails, the keys go back as `_put_back` puts them: a key written again
    meanwhile waits once, for all its puts, so that applied + coalesced
    still equals what was put."""
    table = ModelTable()
    index = topk.DeviceFactorIndex(table)
    base = np.random.default_rng(4).standard_normal((64, 8), dtype=np.float32)
    index.bulk_load([str(i + 1) for i in range(64)], base)
    row = base[6].copy()

    def write(first):
        row[0] = first
        table.put("7-I", ";".join("%.9g" % x for x in row))

    def snapshot_fails(serving=False):
        write(70.0)  # written again after the drain, before the failure
        raise RuntimeError("no room for the snapshot")

    applied = counter("tpums_topk_updates_applied_total")
    coalesced = counter("tpums_topk_updates_coalesced_total")
    write(50.0)
    stamp = index._dirty["7-I"][0]
    monkeypatch.setattr(index, "_snapshot_rows", snapshot_fails)
    with index._lock:
        index._start_rebuild_locked()
    index._rebuild_thread.join(10.0)
    assert "background rebuild failed" in capsys.readouterr().err
    assert index._dirty["7-I"] == (stamp, 2)
    assert index._oldest_dirty_ts == stamp
    q = np.zeros(8, np.float32)
    q[0] = 1.0
    assert index.topk(q, 1)[0] == ("7", pytest.approx(70.0))
    assert counter("tpums_topk_updates_applied_total") == applied + 1
    assert counter("tpums_topk_updates_coalesced_total") == coalesced + 1


def test_a_scatter_that_fails_with_the_matrix_starts_a_rebuild_and_loses_no_key(
        monkeypatch, capsys):
    """The matrix is donated to the scatter.  A call that raises after it
    has taken its argument leaves the index a deleted handle: the keys of
    the drain go back, one device error is counted, a rebuild from the
    table starts, the frame fails as one of a failed build does, and the
    frame after the swap reads the written row."""
    table = ModelTable()
    index = topk.DeviceFactorIndex(table)
    base = np.random.default_rng(5).standard_normal((64, 8), dtype=np.float32)
    for i, row in enumerate(base):
        table.put(f"{i + 1}-I", ";".join("%.9g" % x for x in row))
    q = np.zeros(8, np.float32)
    q[0] = 1.0
    row = base[6].copy()
    row[0] = 50.0
    table.put("7-I", ";".join("%.9g" % x for x in row))
    assert index.topk(q, 1)[0] == ("7", pytest.approx(50.0))  # the build
    assert index.full_builds == 1
    errors = counter("tpums_topk_device_errors_total")
    applied = counter("tpums_topk_updates_applied_total")

    real, calls = topk._scatter_rows, []

    def take_and_fail_once(matrix, pos, vec, count):
        calls.append(count)
        if len(calls) > 1:  # the rebuild's warm-up
            return real(matrix, pos, vec, count)
        matrix.delete()
        raise RuntimeError("INTERNAL: the program failed in flight")

    monkeypatch.setattr(topk, "_scatter_rows", take_and_fail_once)
    row[0] = 70.0
    table.put("7-I", ";".join("%.9g" % x for x in row))
    stamp = index._dirty["7-I"]
    with pytest.raises(RuntimeError, match="lost its device matrix"):
        index.topk(q, 1)
    assert "update scatter failed" in capsys.readouterr().err
    assert counter("tpums_topk_device_errors_total") == errors + 1
    assert counter("tpums_topk_updates_applied_total") == applied
    index._rebuild_thread.join(30.0)
    assert not index._rebuild_thread.is_alive() and calls == [1, 0]
    assert index.full_builds == 2 and not index._matrix.is_deleted()
    assert index.topk(q, 1)[0] == ("7", pytest.approx(70.0))
    # the key went back with the stamp it had, or the rebuild's own drain
    # took it: either way no put is waiting unseen
    assert index._dirty.get("7-I", stamp) == stamp
    assert counter("tpums_topk_device_errors_total") == errors + 1
