"""Read a cell's compared numbers over many seeds in one process, for
setting a limit: sound runs, or with `--control` the lower-precision control.

    python3 -m benchmark.limits --workload <cell> --seeds 1,2,3 --seconds 3 \
        [--control <name>]

One process pays backend start-up and compilation once.  Sound runs and a
control never share a process: a control's patch stays set.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys

from benchmark import run as harness


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--control", default=None)
    ap.add_argument("--bench", default=os.path.join(harness.REPO, "BENCHMARK.json"))
    args = ap.parse_args(argv)
    bench = harness.load_json(args.bench)
    for seed in (int(s) for s in args.seeds.split(",")):
        line = harness.run_cell(bench, args.workload, seed, args.seconds, 0,
                                args.control)
        print(json.dumps({
            "seed": seed, "control": args.control, "correct": line["correct"],
            "checks": {c["name"]: c["value"] for c in line["checks"]},
            "metrics": {k: v["value"] for k, v in line["metrics"].items()},
        }), flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
