#!/usr/bin/env python
"""Compile a benchmark cell's ALS sweep for a described TPU v5e, without a
chip, and say what XLA made of its row gathers.

    python scripts/als_compiled_layout.py \
        [msd-ials|als-ml20m|netflix-als-f100] [out.hlo]

An iteration's speed hangs on two choices of the compiler that no line of
``ops/als.py`` states and that a small change to the sweep can flip
(PERF.md section 6, PR 42): whether the smaller factor table is kept in the
fast memory space (``S(1)`` on its layout: a take from it then reads 1.33 ns
a row on the chip, from HBM 3.95), and which form each take's custom fusion
got (``"integer":"0"`` beside a table in S(1); ``"256"`` is the 3.95 ns
form, ``"128"`` read 9.8).  What this prints matched the chip in every
program PR 42 ran both ways.  Also printed: each bucket's steps, the passes
XLA runs over a step's ``(C, k, k)`` systems, the compiler's own memory
count.  About three minutes and 6 GB on eight cores (netflix-als-f100, 99M
ratings and 19 unrolled solver bodies at rank 100: four minutes, 10 GB);
JAX_PLATFORMS is set to cpu here, the chip is only described."""

import collections
import json
import os
import re
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import topologies
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from benchmark import synth, synth_ials
from flink_ms_tpu.ops import als as A

V5E_BYTES = 16909336064   # one chip's bytes_limit as the runtime reports it


def main(cell="msd-ials", out=None):
    cfg = json.load(open(os.path.join(ROOT, "benchmark/configs", cell + ".json")))
    make = synth_ials.ials_problem if cell == "msd-ials" else synth.als_problem
    users, items, values, _ = make(cfg, 1)
    problem = A.prepare_blocked(users, items, values, 1)
    implicit = cell == "msd-ials"
    k = cfg["rank"]
    als = A.ALSConfig(
        num_factors=k, iterations=1, lambda_=cfg["lambda"], implicit=implicit,
        alpha=cfg.get("alpha", 1.0), dtype=jnp.float32,
        assembly_precision=cfg["assembly_precision"],
        exchange_dtype=cfg["exchange_dtype"])
    A.device_memory = lambda device: V5E_BYTES   # a described chip reports none
    topology = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    mesh = Mesh(np.array(topology.devices[:1]), (A.BLOCK_AXIS,))
    _, how = A._exchange_and_assembly(als, "tpu")
    routes = A._routes(problem, als, mesh)
    for name, side in (("u", problem.u), ("i", problem.i)):
        for w, r in zip(side.widths, side.rows):
            C = A._chunk_rows(r, w, k, 4, 4, how, implicit, routes[name])
            print(f"{name} w {w:6d} rows {r:7d} steps "
                  f"{1 if C is None else -(-r // C)} of {C or r}")
    s3 = NamedSharding(mesh, P(A.BLOCK_AXIS, None, None))
    s2 = NamedSharding(mesh, P(A.BLOCK_AXIS, None))

    def spec(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype,
                                    sharding=s3 if a.ndim == 3 else s2)

    args = [jax.ShapeDtypeStruct((1, side.per_block, k), jnp.float32,
                                 sharding=s3)
            for side in (problem.u, problem.i)]
    for side in (problem.u, problem.i):
        args += [spec(a) for a in A._flat_side_args(side, np.float32)]
    compiled = A._make_sweep(problem, als, mesh).lower(
        jax.ShapeDtypeStruct((), jnp.int32, sharding=NamedSharding(mesh, P())),
        *args).compile()
    text = compiled.as_text()
    if out:
        open(out, "w").write(text)
    print(compiled.memory_analysis())
    takes = collections.Counter()
    for line in text.splitlines():
        if "kind=kCustom" in line and "als.gather" in line:
            half = "user half" if "als.user_half" in line else "item half"
            form = re.search(r'"integer":"(\d+)"', line)
            takes[half, form.group(1) if form else "?"] += 1
    for (half, form), n in sorted(takes.items()):
        print(f"{half}: {n} takes of form {form}")
    for side, name in ((problem.u, "user"), (problem.i, "item")):
        table = rf"f32\[{side.per_block},{k}\]\{{1,0:T\(8,128\)"
        print(f"{name} table: {len(re.findall(table + r'S\(1\)', text))} "
              f"mentions in S(1), {len(re.findall(table + '[}]', text))} in HBM")
    passes = collections.Counter(
        re.sub(r"[.\d]+$", "", m.group(1)) for m in re.finditer(
            rf"^\s*%([\w\-.]+) = f32\[\d+,{k},{k}\]\S* fusion\(", text, re.M))
    print("fusions that write a step's (C, k, k):", dict(passes))


if __name__ == "__main__":
    main(*sys.argv[1:3])
