#!/usr/bin/env python
"""Ingest-plane microbench: journal bytes -> queryable table rows/sec,
scalar per-line path vs columnar chunk path, by chunk size (ISSUE 2).

Measures the LISTENER path — the one every ALS serving job actually runs
(the top-k index registers a change listener, which disables the native
C++ bulk ingest) — so regressions in the parse/put/notify pipeline are
visible outside the full bench.  The two paths are also cross-checked:
table contents must be byte-identical and parse-error counts equal.

Run host-side (no accelerator needed):

    python scripts/ingest_profile.py [--rows 1000000] [--k 16] \
        [--chunkKiB 256,2048,8192] [--listener dirty|topk|none] [--svm false]

Output: one line per (path, chunk size) with rows/sec — per-row ``put()``
baseline, batched scalar, and columnar — plus the columnar speedup vs each.
"""

import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

from flink_ms_tpu.core import formats as F  # noqa: E402
from flink_ms_tpu.core.formats import split_journal_chunk  # noqa: E402
from flink_ms_tpu.core.params import Params  # noqa: E402
from flink_ms_tpu.serve.consumer import (  # noqa: E402
    ALS_STATE,
    SVM_STATE,
    parse_als_record,
    parse_svm_record,
)
from flink_ms_tpu.serve.journal import Journal  # noqa: E402
from flink_ms_tpu.serve.table import ModelTable  # noqa: E402


def build_journal(tmp: str, rows: int, k: int, svm: bool) -> Journal:
    journal = Journal(tmp, "ingest-profile")
    batch = []
    for i in range(rows):
        if svm:
            batch.append(f"{i % (rows // 2 + 1)},{i % 97}.5;{i % 13}")
        else:
            vec = [((i * 31 + j * 17) % 1000) / 500.0 - 1.0 for j in range(k)]
            typ = "I" if i % 3 else "U"
            batch.append(F.format_als_row(i % (rows // 2 + 1), typ, vec))
        if len(batch) >= 100_000:
            journal.append(batch)
            batch = []
    if batch:
        journal.append(batch)
    return journal


class DirtySink:
    """Stand-in for the top-k index's listener cost profile: per-key dirty
    marking under a lock (scalar) vs one locked batch update (columnar)."""

    def __init__(self):
        import threading

        self.dirty = set()
        self.lock = threading.Lock()

    def on_put(self, key):
        with self.lock:
            self.dirty.add(key)

    def on_put_many(self, keys):
        with self.lock:
            self.dirty.update(keys)


def run_path(journal: Journal, parse_fn, path: str, chunk_bytes: int,
             listener: str):
    """Replay the whole journal into a fresh table; -> (table, sink,
    rows, errors, seconds).

    ``path``:
    - ``perrow``   — the seed baseline: per-line parse, one ``put()``
      (lock + per-key listener call) per row;
    - ``scalar``   — per-line parse, chunked ``put_many`` (per-key
      listener calls, batched lock);
    - ``columnar`` — the vectorized plane (chunk split + hashed columns
      + one batched listener call per slice).
    """
    table = ModelTable(8)
    sink = None
    if listener == "dirty":
        sink = DirtySink()
        table.add_change_listener(
            sink.on_put, sink.on_put_many if path == "columnar" else None
        )
    elif listener == "topk":
        from flink_ms_tpu.serve.topk import make_als_topk_handler

        make_als_topk_handler(table)
    offset, rows, errors = 0, 0, 0
    t0 = time.perf_counter()
    while True:
        if path == "columnar":
            chunk, next_offset = journal.read_bytes_from(
                offset, max_bytes=chunk_bytes
            )
            if not chunk:
                break
            keys, values, errs, hashes = split_journal_chunk(
                chunk, parse_fn.columnar_mode, with_hashes=True
            )
            errors += errs
            for s in range(0, len(keys), 50_000):
                table.put_many_columns(
                    keys[s:s + 50_000], values[s:s + 50_000],
                    hashes=None if hashes is None else hashes[s:s + 50_000],
                )
            rows += len(keys)
        else:
            lines, next_offset = journal.read_from(
                offset, max_bytes=chunk_bytes
            )
            if not lines:
                break
            batch = []
            for line in lines:
                if not line:
                    continue
                try:
                    batch.append(parse_fn(line))
                except ValueError:
                    errors += 1
            if path == "perrow":
                for key, value in batch:
                    table.put(key, value)
            else:
                for s in range(0, len(batch), 10_000):
                    table.put_many(batch[s:s + 10_000])
            rows += len(batch)
        offset = next_offset
    dt = time.perf_counter() - t0
    return table, sink, rows, errors, dt


def main(argv=None) -> None:
    params = Params.from_args(sys.argv[1:] if argv is None else argv)
    rows = params.get_int("rows", 1_000_000)
    k = params.get_int("k", 16)
    svm = params.get_bool("svm", False)
    listener = params.get("listener", "dirty")  # dirty | topk | none
    chunk_kib = [
        int(c) for c in params.get("chunkKiB", "256,2048,8192").split(",")
    ]
    parse_fn = parse_svm_record if svm else parse_als_record
    state = SVM_STATE if svm else ALS_STATE

    if listener == "topk":
        # pay the once-per-process JIT warm-up BEFORE the timed replays so
        # the warm thread doesn't compete with the path under measurement
        import threading

        from flink_ms_tpu.serve import topk as _topk

        _topk._warm_jit_async()
        for t in threading.enumerate():
            if t.name == "topk-jit-warm":
                t.join()

    with tempfile.TemporaryDirectory() as tmp:
        print(f"[ingest-profile] building {rows} {state} rows (k={k})...",
              file=sys.stderr)
        journal = build_journal(tmp, rows, k, svm)
        ref_table = None
        for kib in chunk_kib:
            chunk_bytes = kib << 10
            res = {}
            for path in ("perrow", "scalar", "columnar"):
                table, sink, n, errs, dt = run_path(
                    journal, parse_fn, path, chunk_bytes, listener
                )
                res[path] = (n / dt, dt)
                print(
                    f"chunk {kib:>6} KiB  {path:>8}: "
                    f"{n / dt:>12,.0f} rows/s  ({n} rows, {errs} errors, "
                    f"{dt:.2f}s, dirty={len(sink.dirty) if sink else '-'})"
                )
                if ref_table is None:
                    ref_table = table
                else:
                    assert table._shards == ref_table._shards, \
                        "PARITY FAILURE: table contents differ between paths"
            print(
                f"chunk {kib:>6} KiB  columnar vs perrow: "
                f"{res['columnar'][0] / res['perrow'][0]:.2f}x | "
                f"vs scalar: {res['columnar'][0] / res['scalar'][0]:.2f}x"
            )


if __name__ == "__main__":
    main()
