"""Model loader — counterpart of ``ALSKafkaProducer`` / ``SVMKafkaProducer``
(``als-ms/.../qs/ALSKafkaProducer.java``, ``svm-ms/.../qs/SVMKafkaProducer.java``).

Streams model text files (file or nested directory, matching
``TextInputFormat(nested=true)`` — ALSKafkaProducer.java:24-26) into a
journal topic with fsync'd appends (at-least-once, the analog of
``setFlushOnCheckpoint(true)`` — :35-37).

Flush cadence: the reference flushes its Kafka
producer on EVERY checkpoint (default 60 s), so a crash mid-load loses at
most one checkpoint interval of buffered rows.  ``--flushInterval`` (ms,
default 60000 — the reference's checkpoint interval) fsyncs the journal on
the same cadence during the load; ``--flushInterval 0`` disables the
periodic flush and keeps only the end-of-stream fsync.

One module serves both ALS and SVM (the reference's two producers are
copies; SVMKafkaProducer.java:40 even kept the "[ALS]" job name —
SURVEY.md Appendix C #2).
"""

from __future__ import annotations

import sys
import time

from ..core import formats as F
from ..core.params import Params
from .journal import Journal

_BATCH = 10_000


def run(params: Params, label: str = "ALS") -> int:
    # optional Kafka-parity log bounding: --segmentBytes rolls the topic
    # into sealed segments, --retainSegments deletes the oldest beyond N
    seg = params.get_int("segmentBytes", 0) or None
    retain = params.get_int("retainSegments", 0) or None
    journal = Journal(
        params.get_required("journalDir"), params.get_required("topic"),
        segment_bytes=seg, retain_segments=retain,
    )
    input_path = params.get_required("input")
    flush_interval_s = params.get_int("flushInterval", 60_000) / 1000.0
    next_flush = time.monotonic() + flush_interval_s
    n = 0
    batch = []
    for line in F.iter_lines(input_path):
        batch.append(line)
        # the flush deadline is checked per line, not only when a 10k
        # batch fills: a source slower than _BATCH lines per interval must
        # still bound crash loss to one interval (flushOnCheckpoint parity)
        flush_now = flush_interval_s > 0 and time.monotonic() >= next_flush
        if len(batch) >= _BATCH or flush_now:
            journal.append(batch, flush=flush_now)
            if flush_now:
                next_flush = time.monotonic() + flush_interval_s
            n += len(batch)
            batch = []
    if batch:
        n += len(batch)
    journal.append(batch, flush=True)  # final fsync = the checkpoint flush
    print(f"[{label}] model-loading: {n} rows -> topic '{journal.topic}'")
    return n


def als_main(argv=None) -> None:
    run(Params.from_args(sys.argv[1:] if argv is None else argv), label="ALS")


def svm_main(argv=None) -> None:
    run(Params.from_args(sys.argv[1:] if argv is None else argv), label="SVM")
