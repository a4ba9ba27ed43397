"""Metric-name hygiene lint (obs/metrics.py contract): after an
end-to-end serving smoke — journal -> ServingJob -> queries -> profiler
flush -> fleet scrape — every live series must be ``tpums_``-prefixed
(``NAME_PATTERN``), every label key must come from the fixed
``LABEL_VOCABULARY``, and every counter name must end ``_total``.

The smoke runs in a SUBPROCESS: the registry is process-global, so an
in-process walk would lint whatever series earlier suite tests happened
to mint (including deliberately weird test series) instead of what the
serving stack itself emits."""

import json
import os
import re
import subprocess
import sys

from flink_ms_tpu.obs.metrics import LABEL_VOCABULARY, NAME_PATTERN

_SMOKE = r"""
import json, os, sys, tempfile, time
import numpy as np

tmp = tempfile.mkdtemp(prefix="tpums_hygiene_")
os.environ["TPUMS_REGISTRY_DIR"] = os.path.join(tmp, "registry")
os.environ["TPUMS_PROF"] = "1"
os.environ["TPUMS_PROF_HZ"] = "200"

from flink_ms_tpu.core import formats as F
from flink_ms_tpu.obs import profiler as P
from flink_ms_tpu.obs import tracing as T
from flink_ms_tpu.obs.metrics import get_registry
from flink_ms_tpu.obs.scrape import fleet_signals, scrape_fleet
from flink_ms_tpu.serve.client import QueryClient
from flink_ms_tpu.serve.consumer import (ALS_STATE, ServingJob,
                                         make_backend, parse_als_record)
from flink_ms_tpu.serve.journal import Journal

rng = np.random.default_rng(0)
journal = Journal(os.path.join(tmp, "bus"), "models")
journal.append([F.format_als_row(u, "U", rng.normal(size=4))
                for u in range(50)])
job = ServingJob(journal, ALS_STATE, parse_als_record,
                 make_backend("memory", None),
                 host="127.0.0.1", port=0, poll_interval_s=0.01).start()
try:
    assert job.wait_ready(120)
    with QueryClient("127.0.0.1", job.port, timeout_s=30) as c:
        tid = T.new_trace_id()
        with T.trace_span(tid):
            for u in range(30):
                c.query_state(ALS_STATE, f"{u}-U")
        c.query_state(ALS_STATE, "no-such-key-U")
        c.query_states(ALS_STATE, ["1-U", "2-U"])
    prof = P.get_profiler()
    if prof is not None:
        prof.flush()
    s0 = scrape_fleet()
    time.sleep(0.05)
    fleet_signals(s0, scrape_fleet())
    print(json.dumps(get_registry().snapshot()))
finally:
    job.stop()
# the lint subject is the snapshot printed above; skip interpreter
# teardown, which can SIGABRT ("terminate called without an active
# exception") when a runtime-library worker thread is still joinable
sys.stdout.flush()
os._exit(0)
"""


def test_live_registry_passes_hygiene_lint(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu", TMPDIR=str(tmp_path))
    out = subprocess.run([sys.executable, "-c", _SMOKE], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-4000:]
    snap = json.loads(out.stdout.strip().splitlines()[-1])

    entries = (snap.get("counters", []) + snap.get("gauges", [])
               + snap.get("histograms", []))
    assert len(snap.get("counters", [])) > 0
    assert len(snap.get("histograms", [])) > 0

    name_re = re.compile(NAME_PATTERN)
    bad_names = sorted({e["name"] for e in entries
                        if not name_re.match(e["name"])})
    assert bad_names == [], f"non-conforming series names: {bad_names}"

    bad_labels = sorted({(e["name"], k) for e in entries
                         for k in e.get("labels", {})
                         if k not in LABEL_VOCABULARY})
    assert bad_labels == [], f"label keys outside vocabulary: {bad_labels}"

    bad_counters = sorted({c["name"] for c in snap.get("counters", [])
                           if not c["name"].endswith("_total")})
    assert bad_counters == [], \
        f"counters without _total suffix: {bad_counters}"


def test_vocabulary_is_frozen_and_prefix_pins_namespace():
    # the contract itself: additions are deliberate, not drive-by
    assert "verb" in LABEL_VOCABULARY and "tenant" in LABEL_VOCABULARY
    assert isinstance(LABEL_VOCABULARY, frozenset)
    assert NAME_PATTERN.startswith("^tpums_")
