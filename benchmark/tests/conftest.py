"""The benchmark's own tests run on the host: JAX_PLATFORMS=cpu is the
explicit ask the program's device rule needs.  Run from the repo root:

    python3 -m pytest benchmark/tests -q
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)
TINY = os.path.join(REPO, "benchmark", "tests", "tiny", "BENCHMARK.json")
