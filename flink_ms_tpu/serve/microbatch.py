"""Cross-request top-k microbatching — the serving plane's adaptive
batching lever (the Clipper / TF-Serving idea): TOPK/TOPKV requests
arriving on the thread-per-client lookup server enqueue into a coalescing
queue; ONE dispatcher thread drains up to ``max_batch`` waiting queries
(after at most a ``max_wait_us`` coalescing window) and executes a single
batched matmul + ``top_k`` over the catalog (``DeviceFactorIndex
.topk_many``), then scatters per-query results back to the parked handler
threads.

Why: the unbatched path scores one query vector per device dispatch, so B
concurrent requests serialize on the index lock and re-read the whole
catalog from memory B times.  Batching reads the catalog once per
dispatch and amortizes the fixed dispatch cost B-fold — throughput scales
with concurrency instead of flat-lining at 1/dispatch-latency.

The wire protocol is unchanged; batching is server-internal (the native
C++ plane's byte-parity contract is untouched).  Knobs, read once per
batcher at construction:

- ``TPUMS_TOPK_BATCH``          "1" (default) enable, "0" disable
- ``TPUMS_TOPK_BATCH_MAX``      max queries per device dispatch (default 32)
- ``TPUMS_TOPK_BATCH_WAIT_US``  coalescing window in microseconds
                                (default 200), counted from the ARRIVAL of
                                the queue's head — the worst-case latency a
                                lone request pays for the chance to share
                                a dispatch.  While a dispatch executes,
                                new arrivals queue up naturally and their
                                window runs out meanwhile, so a backlog is
                                picked up as it stands, without waiting.
"""

from __future__ import annotations

import os
import threading
import time
from collections import deque
from typing import List, Optional, Tuple

import numpy as np

from ..obs import metrics as obs_metrics
from ..obs.tracing import stage, watch_thread


def batching_enabled() -> bool:
    return os.environ.get("TPUMS_TOPK_BATCH", "1") != "0"


class PendingTopK:
    """One enqueued query: the submitting handler thread parks on
    ``wait()`` while the dispatcher scores the coalesced batch and
    scatters results (or the per-group error) back.

    Span fields (filled in by the dispatcher, read by the server's
    epilogue): ``queue_wait_s`` — enqueue to dispatch pick-up;
    ``batch_size`` — queries sharing the dispatch; ``device_s`` — the WALL
    of the group's whole index call (maintenance, stack and pad, enqueue,
    the device's work, the result copy, ``_format_rows``), not device
    time: the part that contains the device's work is
    ``tpums_topk_fetch_seconds``.  The instants behind them, on
    ``time.perf_counter()``: ``t_enqueue``; ``t_dispatch`` — the group
    was picked up (``queue_wait_s = t_dispatch - t_enqueue``); ``t_done``
    — the index call returned (``device_s = t_done - t_dispatch``),
    stamped once per frame on every member and left None for an inline
    single, whose reply time is the verb latency less ``device_s``."""

    __slots__ = ("vec", "k", "result", "error", "_event",
                 "t_enqueue", "t_dispatch", "t_done",
                 "queue_wait_s", "batch_size", "device_s")

    def __init__(self, vec: np.ndarray, k: int):
        self.vec = vec
        self.k = k
        self.result: Optional[List[Tuple[str, float]]] = None
        self.error: Optional[BaseException] = None
        self._event = threading.Event()
        self.t_enqueue = time.perf_counter()
        self.t_dispatch: Optional[float] = None
        self.t_done: Optional[float] = None
        self.queue_wait_s: Optional[float] = None
        self.batch_size: Optional[int] = None
        self.device_s: Optional[float] = None

    def _finish(self, result=None, error=None) -> None:
        self.result = result
        self.error = error
        self._event.set()

    def wait(self, timeout: Optional[float] = None):
        if not self._event.wait(timeout):
            raise TimeoutError("batched top-k still queued at deadline")
        if self.error is not None:
            raise self.error
        return self.result


class TopKBatcher:
    """Coalesces concurrent top-k queries into shared device dispatches.

    ``submit(vec, k)`` is non-blocking (returns a :class:`PendingTopK`);
    ``score(vec, k)`` is the blocking submit-and-wait convenience.  The
    dispatcher thread starts lazily on first submit and groups drained
    queries by ``(k, vector shape)`` — ``k`` is a static argument of the
    jitted program and mixed widths cannot stack — so a pathological mix
    degrades to several smaller dispatches, never to an error for the
    well-formed queries sharing the batch.

    Adaptive idle fast path: once the dispatcher exists, a submit that
    finds the batcher fully idle (empty queue, nothing executing) scores
    inline in the caller's thread via the single-query program — zero
    added latency at concurrency 1, where a coalescing window could never
    pay off anyway.  Under queuing pressure (a dispatch in flight or a
    window already open) arrivals enqueue and coalesce as usual.

    Observability (test hooks, bench counters): ``submitted`` /
    ``dispatches`` / ``batched_queries`` / ``max_batch_seen`` /
    ``inline_singles``.  ``dispatches < submitted`` is the signature of
    coalescing actually happening.
    """

    def __init__(self, index, max_batch: Optional[int] = None,
                 max_wait_us: Optional[float] = None):
        self.index = index
        self.max_batch = int(
            os.environ.get("TPUMS_TOPK_BATCH_MAX", 32)
            if max_batch is None else max_batch
        )
        if self.max_batch < 1:
            raise ValueError("max_batch must be >= 1")
        self.max_wait_s = float(
            os.environ.get("TPUMS_TOPK_BATCH_WAIT_US", 200)
            if max_wait_us is None else max_wait_us
        ) / 1e6
        self._queue: deque = deque()
        self._cond = threading.Condition()
        self._thread: Optional[threading.Thread] = None
        self._closed = False
        self._flush = False
        self._executing = 0  # in-flight scorings: dispatcher + inline
        self.submitted = 0
        self.dispatches = 0
        self.batched_queries = 0
        self.max_batch_seen = 0
        self.inline_singles = 0
        # registry instruments (shared process-wide series; the ad-hoc
        # ints above remain the zero-cost test hooks)
        reg = obs_metrics.get_registry()
        self._obs_queue_wait = reg.histogram("tpums_topk_queue_wait_seconds")
        self._obs_batch_size = reg.histogram(
            "tpums_topk_batch_size", bounds=obs_metrics.SIZE_BUCKETS)
        self._obs_device = reg.histogram("tpums_topk_device_seconds")
        # per frame, from the two instants the index stamps around its
        # wait for the device: the enqueue returned -> the results on the
        # host.  device_seconds less this is host work inside the
        # dispatch.
        self._obs_fetch = reg.histogram("tpums_topk_fetch_seconds")
        # per frame that found queries already waiting when the frame
        # before it came back: that frame's results on the host -> this
        # frame enqueued, the device starved by the host
        self._obs_turnaround = reg.histogram("tpums_topk_turnaround_seconds")
        # per frame: the group picked up -> the jitted call returned
        # (maintain, stack and pad, the frame's transfer, the launch): the
        # host's serial work before the device can start
        self._obs_enqueue = reg.histogram("tpums_topk_enqueue_seconds")
        # per frame, 0 or 1: 1 when the dispatcher waited on the coalescing
        # window before taking the frame's batch, 0 when the head's age had
        # used the window up; sum / count is the share of frames that paid it
        self._obs_window_held = reg.histogram("tpums_topk_window_held")
        self._backlog_since: Optional[float] = None

    # -- submit side --------------------------------------------------------

    def submit(self, vec: np.ndarray, k: int,
               allow_inline: bool = True) -> PendingTopK:
        """``allow_inline=False`` forces enqueueing even when idle — the
        server passes it for every member of a multi-line pipelined burst,
        where the NEXT submit is already in hand (an inline execution
        would serialize the burst back into singles)."""
        pending = PendingTopK(np.asarray(vec, dtype=np.float32), int(k))
        inline = False
        with self._cond:
            if self._closed:
                raise RuntimeError("batcher is closed")
            if self._thread is None:
                self._thread = threading.Thread(
                    target=self._run, name="topk-batcher", daemon=True
                )
                self._thread.start()
            elif allow_inline and not self._queue and self._executing == 0:
                # idle fast path: nothing to coalesce WITH, so the window
                # could only add latency — score in the caller's thread
                # via the (bit-identical) single-query program
                inline = True
                self._executing += 1
            self.submitted += 1
            if not inline:
                self._queue.append(pending)
                self._cond.notify_all()
        if inline:
            try:
                self.inline_singles += 1
                t0 = time.perf_counter()
                with stage("topk.frame", n=1, b_pad=1, seq=-1):
                    result = self.index.topk(pending.vec, pending.k)
                pending.t_dispatch = t0
                pending.queue_wait_s = 0.0
                pending.batch_size = 1
                # the wall of the whole index call, as in _dispatch
                # (PendingTopK says what that holds); t_done stays None
                pending.device_s = time.perf_counter() - t0
                # no registry observation here: an inline single's queue
                # wait is 0 and its dispatch wall is within a constant of
                # the verb latency the server already histograms, while
                # even one extra locked observation is measurable on a
                # ~0.1 ms round trip (README overhead A/B).  The span
                # fields above still feed traced requests; batched
                # dispatches — where these series carry information —
                # record all three in _dispatch.
                pending._finish(result=result)
            except BaseException as e:
                pending._finish(error=e)
            finally:
                with self._cond:
                    self._executing -= 1
        return pending

    def score(self, vec: np.ndarray, k: int,
              timeout: Optional[float] = None):
        return self.submit(vec, k).wait(timeout)

    def flush(self) -> None:
        """Hint that the submitting burst is complete: the dispatcher
        stops holding the coalescing window open and dispatches what is
        queued right now (new arrivals still coalesce into later
        batches)."""
        with self._cond:
            self._flush = True
            self._cond.notify_all()

    def close(self) -> None:
        """Stop the dispatcher (drains the queue first so no submitter is
        left parked forever).  Idempotent."""
        with self._cond:
            self._closed = True
            self._cond.notify_all()
        if self._thread is not None:
            self._thread.join(timeout=10)

    # -- dispatcher ---------------------------------------------------------

    def _run(self) -> None:
        # every instant of this thread lies in one named stage (obs/
        # tracing.stage): ``topk.coalesce`` here, ``topk.frame`` and its
        # children in _dispatch and in the index, so whoever profiles the
        # process can name what the host was doing in each device gap
        watch_thread("topk-batcher")
        while True:
            with stage("topk.coalesce"), self._cond:
                while not self._queue and not self._closed:
                    self._cond.wait()
                if not self._queue and self._closed:
                    return
                # coalescing window: an arrival gets max_wait_s, counted
                # from ITS OWN arrival, for companions to share its
                # dispatch.  A head that queued while the frame before it
                # ran has used the window up, so a backlog is taken as it
                # stands; an arrival at an idle batcher gets all of it.
                # Never holds a full batch.  (t_enqueue is perf_counter.)
                held = 0
                while (len(self._queue) < self.max_batch
                       and not self._closed and not self._flush):
                    remaining = (self._queue[0].t_enqueue + self.max_wait_s
                                 - time.perf_counter())
                    if remaining <= 0:
                        break
                    held = 1
                    self._cond.wait(remaining)
                self._flush = False
                batch = [
                    self._queue.popleft()
                    for _ in range(min(len(self._queue), self.max_batch))
                ]
                # arrivals during the dispatch must enqueue (to coalesce
                # into the NEXT batch), not take the idle fast path
                self._executing += 1
            try:
                self._dispatch(batch, held)
            except BaseException as e:  # the loop must survive anything —
                # a dead dispatcher would park every future submitter
                for p in batch:
                    if not p._event.is_set():
                        p._finish(error=e)
            finally:
                with self._cond:
                    self._executing -= 1

    def _dispatch(self, batch: List[PendingTopK], held: int) -> None:
        groups: dict = {}
        for p in batch:
            groups.setdefault((p.k, p.vec.shape), []).append(p)
        for (k, _shape), group in groups.items():
            n = len(group)
            with stage("topk.frame", n=n,
                       b_pad=1 << (n - 1).bit_length(), seq=self.dispatches):
                self._dispatch_group(group, k, held)

    def _dispatch_group(self, group: List[PendingTopK], k: int,
                        held: int) -> None:
        t_disp = time.perf_counter()
        try:
            if len(group) == 1 and not getattr(
                self.index, "prefers_frames", False
            ):
                # a lone query runs the exact single-query program, so
                # sequential traffic is BIT-identical to the unbatched
                # path (the native plane's byte-parity tests replay
                # one-at-a-time queries through here).  Sharded/ANN
                # indexes prefer whole frames: there the batched
                # program IS the only compiled program, so a lone
                # query rides it as a (1, k) frame instead.
                results = [self.index.topk(group[0].vec, k)]
            else:
                # the whole frame goes down in ONE stacked dispatch —
                # on the sharded tier this is the shard_map program
                # (per-device partial top-k + merge) over the frame.
                # The index stacks the vectors, inside its topk.pack
                results = self.index.topk_many([p.vec for p in group], k)
        except Exception as e:
            # a bad group (e.g. width mismatch vs the index) fails its
            # own members; other groups in the batch still score
            for p in group:
                p._finish(error=e)
            return
        t_done = time.perf_counter()
        with stage("topk.scatter"):
            # the wall of the whole index call: PendingTopK says what
            # that holds beside the device's work
            device_s = t_done - t_disp
            self.dispatches += 1
            self.batched_queries += len(group)
            if len(group) > self.max_batch_seen:
                self.max_batch_seen = len(group)
            metrics_on = obs_metrics.metrics_enabled()
            # the index stamps its wait for the device, per calling thread;
            # stamps older than this call belong to an earlier frame, since
            # an empty index answers without dispatching
            t_enqueued, t_fetched = self.index.last_fetch() or (None, None)
            stamped = t_enqueued is not None and t_enqueued >= t_disp
            if metrics_on:
                self._obs_batch_size.observe(len(group))
                self._obs_device.observe(device_s)
                self._obs_window_held.observe(held)
                if stamped:
                    self._obs_enqueue.observe(t_enqueued - t_disp)
                    self._obs_fetch.observe(t_fetched - t_enqueued)
                    if self._backlog_since is not None:
                        self._obs_turnaround.observe(
                            t_enqueued - self._backlog_since)
            self._backlog_since = (
                t_fetched if stamped and self._queue else None)
            for p, result in zip(group, results):
                p.t_dispatch = t_disp
                p.t_done = t_done
                p.queue_wait_s = t_disp - p.t_enqueue
                p.batch_size = len(group)
                p.device_s = device_s
                if metrics_on:
                    self._obs_queue_wait.observe(p.queue_wait_s)
                p._finish(result=result)
