#!/usr/bin/env python
"""Compile a benchmark cell's ALS sweep for a described TPU v5e, without a
chip, and say what XLA made of its row gathers.

    python scripts/als_compiled_layout.py \
        [msd-ials|als-ml20m|netflix-als-f100] [out.hlo]

An iteration's speed hangs on two choices of the compiler that no line of
``ops/als.py`` states and that a small change to the sweep can flip
(PERF.md section 6, PR 42): whether a factor table is kept in the fast
memory space (``S(1)`` on its layout: a take from it then reads 1.33 ns a
row on the chip, from HBM 3.95), and which form each take's custom fusion
got (``"integer":"0"`` beside a table in S(1); ``"256"`` is the 3.95 ns
form, ``"128"`` read 9.8).  What this prints matched the chip in every
program PRs 42 and 45 ran both ways.  Printed per half: into how many
segments its table is cut (``ops/als.table_segments``; a described v5e
reports its fast memory through its ``device_kind``) and their rows, each
call's steps, the takes' forms per segment, the ``S(1)`` mentions of the
whole tables and of the segments' buffers; then the passes XLA runs over a
step's ``(C, k, k)`` systems and the compiler's own memory count.

The third form cannot be steered by step size (PR 45, the parent's sweep
compiled here under other ``FLINK_MS_ALS_ASSEMBLY_CHUNK_BYTES``): it is
erratic in the take's shape.  From the 480,317-row table in HBM
``f32[3049408,100]`` gets ``"128"`` and ``f32[3048984,100]`` ``"256"``; at
1 GiB steps netflix-als-f100 reads one take ``"128"`` (``f32[1841152,100]``,
64 x 28,768) and fourteen ``"256"``, while the same setting at msd-ials
cures the widest bucket (``f32[1252832,64]`` -> ``"256"``) and breaks
another (``f32[1445840,64]``, 2,915 x 496 -> ``"128"``).  Every take from a
table in S(1), at any width and step in the three cells, has form ``"0"``:
so a table too large for the fast memory is read through segments that fit
it, and this script is the gate that says whether the compiler placed them
there.  It also says where it did not: a 61.5 MB segment carried through a
``lax.map`` stays in HBM (netflix-als-f100's segment 0, whose thirteen
chunked pieces therefore run their steps unrolled).

About two minutes and 6 GB on eight cores (netflix-als-f100, 99M ratings
and 19 unrolled solver bodies at rank 100: five minutes, 12 GB);
JAX_PLATFORMS is set to cpu here, the chip is only described."""

import collections
import json
import os
import re
import sys
import time

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
    t0 = time.perf_counter()
    cuts = A._cuts(problem, als, mesh)
    print(f"cutting the lists took {time.perf_counter() - t0:.1f} s")
    for name, side in (("u", problem.u), ("i", problem.i)):
        cut = cuts[name]
        print(f"{name} half: table in "
              + (f"{cut.segments} segments of {cut.seg_rows} rows"
                 if cut else "1 segment"))
        for bucket in A._calls(side, cut, routes[name]):
            for r, w in bucket:
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
    for name, side in (("u", problem.u), ("i", problem.i)):
        args += [spec(a) for a in A._flat_side_args(side, np.float32,
                                                    cut=cuts[name])]
    t0 = time.perf_counter()
    lowered = A._make_sweep(problem, als, mesh).lower(
        jax.ShapeDtypeStruct((), jnp.int32, sharding=NamedSharding(mesh, P())),
        *args)
    t1 = time.perf_counter()
    compiled = lowered.compile()
    print(f"trace + lower {t1 - t0:.1f} s, compile "
          f"{time.perf_counter() - t1:.1f} s")
    text = compiled.as_text()
    if out:
        open(out, "w").write(text)
    print(compiled.memory_analysis())
    takes = collections.Counter()
    for line in text.splitlines():
        if "kind=kCustom" in line and "als.gather" in line:
            half = "user half" if "als.user_half" in line else "item half"
            segment = re.search(r"als\.segment(\d+)", line)
            if segment:
                half += ", segment " + segment.group(1)
            form = re.search(r'"integer":"(\d+)"', line)
            takes[half, form.group(1) if form else "?"] += 1
    for (half, form), n in sorted(takes.items()):
        print(f"{half}: {n} takes of form {form}")

    def mentions(rows):
        table = rf"f32\[{rows},{k}\]\{{1,0:T\(8,128\)"
        return (f"{len(re.findall(table + r'S\(1\)', text))} mentions in "
                f"S(1), {len(re.findall(table + '[}]', text))} in HBM")

    for side, name, reader in ((problem.u, "user", "i"),
                               (problem.i, "item", "u")):
        print(f"{name} table ({side.per_block} rows): "
              + mentions(side.per_block))
        if cuts[reader]:
            rows = cuts[reader].seg_rows + A._PAD_STRIP
            print(f"{name} table's segments ({rows} rows each): "
                  + mentions(rows))
    passes = collections.Counter(
        re.sub(r"[.\d]+$", "", m.group(1)) for m in re.finditer(
            rf"^\s*%([\w\-.]+) = f32\[\d+,{k},{k}\]\S* fusion\(", text, re.M))
    print("fusions that write a step's (C, k, k):", dict(passes))


if __name__ == "__main__":
    main(*sys.argv[1:3])
