"""The query path and the ALS sweep on the profiler's clock: the
dispatcher thread's stages tile each frame in a real (CPU) profiler trace,
the three counters are taken where the work happens, a traced request's
``mb_*`` events sit on the stamped instants, the device scopes reach the
lowered programs, and ``stage()`` costs nothing where nobody profiles."""

import glob
import os
import subprocess
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flink_ms_tpu.obs import metrics as obs_metrics
from flink_ms_tpu.obs import tracing as T
from flink_ms_tpu.serve.client import QueryClient
from flink_ms_tpu.serve.microbatch import TopKBatcher
from flink_ms_tpu.serve.server import LookupServer
from flink_ms_tpu.serve.table import ModelTable
from flink_ms_tpu.serve.topk import ALSTopkHandler, DeviceFactorIndex

STATE = "ALS_MODEL"
CHILDREN = ["topk.maintain", "topk.pack", "topk.enqueue", "topk.fetch",
            "topk.format", "topk.scatter"]
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _hist(name):
    h = obs_metrics.get_registry().histogram(name)
    return h.count, h.sum


def _index(rng, n_items=40_000, width=32):
    index = DeviceFactorIndex(ModelTable(2), "-I")
    index.bulk_load([f"{i}-I" for i in range(n_items)],
                    rng.normal(size=(n_items, width)).astype(np.float32))
    return index


def _burst(batcher, rng, n, width=32, k=5):
    """n queries in one frame: enqueued while the window is open, then
    flushed, as the server does for a pipelined burst."""
    pending = [batcher.submit(rng.normal(size=width).astype(np.float32), k,
                              allow_inline=False) for _ in range(n)]
    batcher.flush()
    return [p.wait(timeout=60) for p in pending], pending


# -- the primitive ----------------------------------------------------------

def test_stage_is_a_noop_without_jax_and_outside_a_profiler_session():
    probe = (
        "import sys\n"
        "from flink_ms_tpu.obs import tracing\n"
        "s = tracing.stage('topk.frame', n=1)\n"
        "assert 'jax' not in sys.modules, 'stage() imported jax'\n"
        "assert s is tracing.stage('other')  # one shared no-op object\n"
        "with s:\n"
        "    pass\n"
        "try:\n"
        "    with tracing.stage('x'):\n"
        "        raise KeyError('kept')\n"
        "except KeyError:\n"
        "    print('ok')\n"
    )
    done = subprocess.run([sys.executable, "-c", probe], cwd=REPO,
                          capture_output=True, text=True, timeout=60)
    assert done.returncode == 0 and done.stdout.strip() == "ok", done.stderr
    # with jax in the process and no session open it is a TraceAnnotation
    # that records nothing and swallows nothing
    assert isinstance(T.stage("topk.frame", n=6, b_pad=8, seq=0),
                      jax.profiler.TraceAnnotation)
    with pytest.raises(KeyError):
        with T.stage("x"):
            raise KeyError("kept")


# -- the dispatcher's loop, tiled -------------------------------------------

@pytest.fixture(scope="module")
def traced_frames(tmp_path_factory):
    """A CPU profiler trace of five batched frames -> (events of the
    dispatcher thread as (start, end, name, stats), dispatches traced)."""
    rng = np.random.default_rng(7)
    index = _index(rng)
    batcher = TopKBatcher(index, max_batch=8, max_wait_us=200_000)
    _burst(batcher, rng, 4)  # starts the thread, compiles the 4-program
    before = batcher.dispatches
    out = str(tmp_path_factory.mktemp("trace"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(out, profiler_options=opts)
    try:
        for _ in range(5):
            _burst(batcher, rng, 4)
    finally:
        jax.profiler.stop_trace()
    batcher.close()
    path, = glob.glob(os.path.join(out, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    lines = [
        sorted((e.start_ns, e.start_ns + e.duration_ns, e.name, dict(e.stats))
               for e in ln.events if e.name.startswith("topk."))
        for plane in jax.profiler.ProfileData.from_file(path).planes
        if plane.name == "/host:CPU" for ln in plane.lines]
    lines = [ev for ev in lines if ev]
    assert len(lines) == 1, "only the dispatcher thread carries stages"
    return lines[0], batcher.dispatches - before


def test_one_frame_span_per_dispatch_with_its_fields(traced_frames):
    events, dispatches = traced_frames
    frames = [e for e in events if e[2] == "topk.frame"]
    assert len(frames) == dispatches == 5
    assert [f[3]["n"] for f in frames] == [4] * 5
    assert [f[3]["b_pad"] for f in frames] == [4] * 5
    seqs = [f[3]["seq"] for f in frames]
    assert seqs == list(range(seqs[0], seqs[0] + 5))


def test_six_children_tile_each_frame(traced_frames):
    events, _ = traced_frames
    frames = [e for e in events if e[2] == "topk.frame"]
    gaps, spans = [], []
    for f_start, f_end, _, _ in frames:
        inside = [e for e in events
                  if e[2] != "topk.frame" and f_start <= e[0] and e[1] <= f_end]
        assert [e[2] for e in inside] == CHILDREN
        edges = [f_start] + [t for e in inside for t in e[:2]] + [f_end]
        assert edges == sorted(edges), "children overlap"
        gaps.append(sum(b - a for a, b in zip(edges[::2], edges[1::2])))
        spans.append(f_end - f_start)
    # the sum over the frames, less the one frame with the widest gap: a
    # thread the scheduler took away between two children (six test workers
    # on eight cores) is not a hole in the tiling, and a hole is in every
    # frame
    worst = gaps.index(max(gaps))
    del gaps[worst], spans[worst]
    assert sum(gaps) < 0.05 * sum(spans), (gaps, spans)


def test_one_coalesce_span_between_frames(traced_frames):
    events, _ = traced_frames
    frames = [e for e in events if e[2] == "topk.frame"]
    for a, b in zip(frames, frames[1:]):
        between = [e[2] for e in events if a[1] <= e[0] and e[1] <= b[0]]
        assert between == ["topk.coalesce"]


# -- the three counters -----------------------------------------------------

def test_fetch_is_observed_once_per_frame_inside_the_dispatch_wall(rng):
    """And so is enqueue: picked up -> the jitted call returned, then that
    -> the results on the host, two pieces of one dispatch wall, in the
    batched frames only."""
    batcher = TopKBatcher(_index(rng, 5_000), max_batch=8,
                          max_wait_us=200_000)
    names = ("tpums_topk_enqueue_seconds", "tpums_topk_fetch_seconds",
             "tpums_topk_device_seconds")
    before = [_hist(name) for name in names]
    for n in (3, 1, 8):  # a lone query rides the single-query program
        _burst(batcher, rng, n)
    (enq_n, enq_s), (fetch_n, fetch_s), (wall_n, wall_s) = [
        (n1 - n0, s1 - s0)
        for (n0, s0), (n1, s1) in zip(before, map(_hist, names))]
    assert enq_n == fetch_n == wall_n == 3
    assert 0 < enq_s and 0 < fetch_s and enq_s + fetch_s <= wall_s
    # an arrival at the idle batcher scores inline: no frame, no observation
    batcher.score(rng.normal(size=32).astype(np.float32), 5, timeout=60)
    batcher.close()
    assert batcher.inline_singles == 1
    assert [_hist(name)[0] - n0 for name, (n0, _) in zip(names, before)] \
        == [3, 3, 3]


def test_fetch_stamps_are_the_calling_threads_own(rng):
    """The batcher reads them after the index call is back and its lock
    released: another caller of the same index (the push plane, a warm-up)
    must not be able to slip its own in between."""
    index = _index(rng, 5_000)
    q = rng.normal(size=(2, 32)).astype(np.float32)
    assert index.last_fetch() is None
    index.topk_many(q, 5)
    mine = index.last_fetch()
    assert mine[0] <= mine[1]
    theirs = []
    other = threading.Thread(target=lambda: (
        theirs.append(index.last_fetch()), index.topk(q[0], 5),
        theirs.append(index.last_fetch())))
    other.start()
    other.join(timeout=60)
    assert theirs[0] is None and theirs[1][0] >= mine[1]
    assert index.last_fetch() == mine
    # so a frame's fetch is the frame's: a foreign call is not observed
    batcher = TopKBatcher(index, max_batch=8, max_wait_us=200_000)
    _burst(batcher, rng, 3)
    n0, sum0 = _hist("tpums_topk_fetch_seconds")
    index.topk_many(q, 5)
    _burst(batcher, rng, 3)
    n1, sum1 = _hist("tpums_topk_fetch_seconds")
    batcher.close()
    assert n1 - n0 == 1 and 0 < sum1 - sum0 < 60


def test_an_empty_index_answers_with_no_fetch_to_observe(rng):
    index = DeviceFactorIndex(ModelTable(2), "-I")
    batcher = TopKBatcher(index, max_batch=8, max_wait_us=200_000)
    fetch0, device0 = _hist("tpums_topk_fetch_seconds"), \
        _hist("tpums_topk_device_seconds")
    results, _ = _burst(batcher, rng, 3)
    batcher.close()
    assert results == [[], [], []] and index.last_fetch() is None
    assert _hist("tpums_topk_fetch_seconds")[0] == fetch0[0]
    assert _hist("tpums_topk_device_seconds")[0] == device0[0] + 1


def test_turnaround_is_observed_only_for_a_frame_that_found_a_backlog(rng):
    index = _index(rng, 5_000)
    batcher = TopKBatcher(index, max_batch=2, max_wait_us=200_000)
    _burst(batcher, rng, 2)  # the thread is up, the 2-program compiled
    n0, _ = _hist("tpums_topk_turnaround_seconds")
    _burst(batcher, rng, 2)  # came back to an empty queue, as did the first
    assert _hist("tpums_topk_turnaround_seconds")[0] == n0
    # six queries while the index is held: three frames of two, and the
    # second and third find the queue not empty when the one before them
    # comes back; the third leaves it empty
    with index._lock:
        pending = [batcher.submit(rng.normal(size=32).astype(np.float32), 5,
                                  allow_inline=False) for _ in range(6)]
    for p in pending:
        p.wait(timeout=60)
    n1, total = _hist("tpums_topk_turnaround_seconds")
    assert n1 - n0 == 2 and total > 0
    _burst(batcher, rng, 2)
    assert _hist("tpums_topk_turnaround_seconds")[0] == n1
    batcher.close()


@pytest.fixture
def served(rng):
    table = ModelTable(4)
    width = 6
    for u in range(8):
        table.put(f"{u}-U",
                  ";".join(repr(float(x)) for x in rng.normal(size=width)))
    for i in range(300):
        table.put(f"{i}-I",
                  ";".join(repr(float(x)) for x in rng.normal(size=width)))
    handler = ALSTopkHandler(table, batcher=TopKBatcher(
        DeviceFactorIndex(table, "-I"), max_batch=16, max_wait_us=50_000))
    handler.index = handler.batcher.index
    seen = []
    submit = handler.batcher.submit

    def recording(*a, **kw):
        seen.append(submit(*a, **kw))
        return seen[-1]

    handler.batcher.submit = recording
    srv = LookupServer({STATE: table}, host="127.0.0.1", port=0,
                       topk_handlers={STATE: handler}).start()
    try:
        with QueryClient("127.0.0.1", srv.port, timeout_s=30) as client:
            yield client, seen
    finally:
        srv.stop()
        handler.close()


def test_reply_is_observed_once_per_batched_request(served):
    client, seen = served
    client.topk(STATE, "0", 5)  # builds; the dispatcher thread starts
    n0, _ = _hist("tpums_topk_reply_seconds")
    assert len(client.topk_pipelined(STATE, [str(u) for u in range(8)], 5)) == 8
    n1, total = _hist("tpums_topk_reply_seconds")
    assert n1 - n0 == 8 and total > 0
    assert all(p.t_done is not None and p.t_done >= p.t_dispatch >= p.t_enqueue
               for p in seen[-8:])
    client.topk(STATE, "1", 5)  # an inline single: no frame, no observation
    assert seen[-1].t_done is None and seen[-1].t_dispatch is not None
    assert _hist("tpums_topk_reply_seconds")[0] == n1


def test_traced_mb_events_sit_on_the_stamped_instants(served):
    client, seen = served
    client.topk(STATE, "0", 5)
    T.clear_events()
    with T.trace_span() as tid:
        client.topk_pipelined(STATE, ["2", "3", "4"], 5)
    wall = T.wall_offset()  # the one offset every traced instant carries
    pending = sorted(seen[-3:], key=lambda p: p.t_enqueue)
    waits = sorted(T.recent_events(tid=tid, kind="mb_queue_wait"),
                   key=lambda e: e["t0"])
    devices = sorted(T.recent_events(tid=tid, kind="mb_device"),
                     key=lambda e: e["t0"])
    replies = {e["sid"] for e in T.recent_events(tid=tid, kind="server_reply")}
    assert len(waits) == len(devices) == 3
    for p, wait, dev in zip(pending, waits, devices):  # one frame: the
        # three share t_dispatch, so any order of `devices` pairs up
        assert wait["psid"] in replies and dev["psid"] in replies
        assert wait["t0"] == pytest.approx(p.t_enqueue + wall, abs=1e-6)
        assert wait["dur_s"] == pytest.approx(p.t_dispatch - p.t_enqueue,
                                              abs=1e-8)
        assert dev["t0"] == pytest.approx(p.t_dispatch + wall, abs=1e-6)
        assert dev["dur_s"] == pytest.approx(p.t_done - p.t_dispatch, abs=1e-8)
        assert dev["batch_size"] == p.batch_size == 3
    # queue wait ends where the dispatch starts: nothing is invented between
    for wait in waits:
        assert wait["t0"] + wait["dur_s"] == pytest.approx(
            devices[0]["t0"], abs=1e-5)


# -- device scopes ----------------------------------------------------------

def _lowered_als():
    from flink_ms_tpu.ops.als import ALSConfig, compile_fit, prepare_blocked
    from flink_ms_tpu.parallel.mesh import make_mesh

    rng = np.random.default_rng(1)
    n = 600
    problem = prepare_blocked(rng.integers(0, 40, n), rng.integers(0, 30, n),
                              rng.uniform(1, 5, n).astype(np.float32), 1)
    mesh = make_mesh(n_devices=1)
    fit_fn, dev_args = compile_fit(
        problem, ALSConfig(num_factors=4, iterations=1), mesh)
    return fit_fn.lower(jnp.asarray(1, jnp.int32), *dev_args)


def _lowered_topk(which):
    index = _index(np.random.default_rng(2), 64, 8)
    q = np.ones((4, 8), np.float32)
    if which == "single":
        index.topk(q[0], 3)
        return index._topk_fn.lower(index._matrix, index._live, q[0], 3)
    index.topk_many(q, 3)
    return index._topk_many_fn.lower(index._matrix, index._live, q, 3)


@pytest.mark.parametrize("program, scopes", [
    ("als", ["als.user_half", "als.item_half", "als.exchange",
             "als.assemble", "als.solve"]),
    ("single", ["topk.score", "topk.select"]),
    ("many", ["topk.score", "topk.select"]),
])
def test_lowered_programs_carry_the_scope_names(program, scopes):
    lowered = _lowered_als() if program == "als" else _lowered_topk(program)
    text = lowered.as_text(debug_info=True)
    for scope in scopes:
        assert f"{scope}/" in text or f"{scope}\"" in text, scope
