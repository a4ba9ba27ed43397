"""Synthetic ALS model generator — counterpart of ``ALSModelGenerator``
(``model-generator/src/main/scala/de/tub/it4bi/ALSModelGenerator.scala``).

"Only for testing the latency and throughput. Not for quality."
(ALSModelGenerator.scala:12).  Row format and id conventions match the
reference: ids 1..numUsers typed U then 1..numItems typed I, factor entries
drawn from the same heavy-tailed ratio distribution
``nextDouble()/nextDouble() * latentFactors`` (ALSModelGenerator.scala:28-32).

``--parallelism p`` (default 2, reference parity) writes a directory of
p part files named "1".."p" exactly like Flink's parallel ``writeAsText``
— each part generated by its own worker process over a contiguous id
slice (numpy RNG, one stream per (side, part) like the reference's
per-subtask Random instances).  The single-process path (p <= 1, or
stdout) generates in jitted JAX batches.
"""

from __future__ import annotations

import os
import sys
from typing import Iterator

import jax
import jax.numpy as jnp
import numpy as np

from ..core import formats as F
from ..core.params import Params

_BATCH = 1 << 16


def _random_factor_batch(key, n_rows: int, latent: int,
                         distribution: str = "ratio") -> np.ndarray:
    a, b = jax.random.split(key)
    num = jax.random.uniform(a, (n_rows, latent), dtype=jnp.float32)
    if distribution == "bounded":
        # entries uniform in [0, sqrt(5/latent)): dot products land in
        # [0, 5) like real ratings — a model whose served predictions (and
        # therefore a live MSE against 1..5 ratings) are sanity-checkable,
        # unlike the reference distribution below whose tails overflow any
        # bound.  Opt-in; latency/throughput sections keep reference shape.
        return np.asarray(num * np.sqrt(5.0 / latent), dtype=np.float64)
    den = jax.random.uniform(b, (n_rows, latent), dtype=jnp.float32)
    # same shape as the reference's nextDouble()/nextDouble() * latentFactors:
    # ratio of uniforms, scaled (heavy-tailed; occasionally huge)
    return np.asarray(num / jnp.maximum(den, 1e-12) * latent, dtype=np.float64)


def generate_rows(
    n: int, category: str, latent: int, seed: int = 0,
    distribution: str = "ratio",
) -> Iterator[str]:
    """Rows ``id,U|I,f1;...`` for ids 1..n (reference ids are 1-based —
    ALSModelGenerator.scala:47-53)."""
    from ..parallel.mesh import acquire_devices

    acquire_devices()
    key = jax.random.PRNGKey(seed)
    done = 0
    while done < n:
        m = min(_BATCH, n - done)
        key, sub = jax.random.split(key)
        block = _random_factor_batch(sub, m, latent, distribution)
        for j in range(m):
            yield F.format_als_row(done + j + 1, category, block[j])
        done += m


def _write_parallel(path: str, rows: Iterator[str], parallelism: int) -> None:
    if parallelism <= 1:
        F.write_lines(path, rows)
        return
    os.makedirs(path, exist_ok=True)
    files = [open(os.path.join(path, str(i + 1)), "w") for i in range(parallelism)]
    try:
        for n, row in enumerate(rows):
            f = files[n % parallelism]
            f.write(row)
            f.write("\n")
    finally:
        for f in files:
            f.close()


def _part_worker(args) -> int:
    """One subtask: generate a contiguous id slice of each side into its
    part file (Flink's parallel ``writeAsText``: subtask i owns file i+1).

    numpy-only on purpose: workers are SPAWNED processes whose module
    import pulls jax in but whose body never initializes a backend (init
    is lazy) — a fork of a jax-initialized parent would deadlock on
    inherited runtime mutexes, and a chip belongs to one process, so a
    worker that DID init the accelerator would fail or hang behind the
    parent.  Same ratio-of-uniforms distribution,
    one independent RNG stream per (side, part) like the reference's
    per-subtask Random instances."""
    (path, part, parallelism, num_users, num_items, latent, seed,
     distribution) = args
    n_written = 0
    with open(os.path.join(path, str(part + 1)), "w") as f:
        for total, category, base in (
            (num_users, F.USER, 0),
            (num_items, F.ITEM, 1),
        ):
            lo = part * total // parallelism
            hi = (part + 1) * total // parallelism
            rng = np.random.default_rng(seed + base * 1_000_003 + part)
            done = 0
            while done < hi - lo:
                m = min(_BATCH, hi - lo - done)
                num = rng.uniform(size=(m, latent))
                if distribution == "bounded":
                    block = num * np.sqrt(5.0 / latent)
                else:
                    den = np.maximum(rng.uniform(size=(m, latent)), 1e-12)
                    block = num / den * latent
                for j in range(m):
                    f.write(F.format_als_row(lo + done + j + 1, category,
                                             block[j]))
                    f.write("\n")
                    n_written += 1
                done += m
    return n_written


def run(params: Params) -> None:
    num_users = int(params.get_required("numUsers"))
    num_items = int(params.get_required("numItems"))
    latent = int(params.get_required("latentFactors"))
    p = params.get_int("parallelism", 2)
    seed = params.get_int("seed", 0)
    # "ratio" (reference parity, heavy-tailed) | "bounded" (predictions in
    # [0,5) — for quality-checkable live-MSE harnesses)
    distribution = params.get("distribution", "ratio")
    if distribution not in ("ratio", "bounded"):
        raise ValueError("distribution must be ratio|bounded")

    def all_rows():
        yield from generate_rows(num_users, F.USER, latent, seed,
                                 distribution)
        yield from generate_rows(num_items, F.ITEM, latent, seed + 1,
                                 distribution)

    if params.has("output"):
        path = params.get_required("output")
        if p <= 1:
            _write_parallel(path, all_rows(), p)
        else:
            # REAL parallelism (the reference's generator runs one subtask
            # per Flink slot): p worker processes, each formatting and
            # writing a contiguous id slice into its own part file — row
            # formatting is the CPU cost and is GIL-bound in-process
            import multiprocessing as mp

            # pool size caps at the core count — all p parts are still
            # produced, fewer run concurrently.  Below 2 slots the pool
            # buys nothing: it serializes every part through one spawned
            # worker that still pays interpreter + module import (and its
            # CPU burn bleeds into whatever the caller does next —
            # observed polluting latency harnesses on a 1-core host), so
            # the in-process writer produces the same p part files instead.
            n_workers = max(min(p, os.cpu_count() or 1), 1)
            if n_workers < 2:
                _write_parallel(path, all_rows(), p)
                return

            os.makedirs(path, exist_ok=True)
            jobs = [
                (path, part, p, num_users, num_items, latent, seed,
                 distribution)
                for part in range(p)
            ]
            try:
                # spawn, not fork: the parent may have a live jax runtime
                # whose mutexes a fork would inherit mid-locked
                with mp.get_context("spawn").Pool(n_workers) as pool:
                    counts = pool.map(_part_worker, jobs)
                total = num_users + num_items
                if sum(counts) != total:
                    raise RuntimeError(
                        f"workers wrote {sum(counts)} rows, expected {total}"
                    )
            except Exception as e:
                # pool failure: LOUD single-process fallback preserving
                # the output contract (a silent fallback would mask real
                # worker bugs as mysteriously slow generation)
                print(
                    f"[gen] parallel generation failed ({e!r}); "
                    "regenerating single-process",
                    file=sys.stderr,
                )
                _write_parallel(path, all_rows(), p)
    else:
        print("Printing results to stdout. Use --output to specify output location")
        for row in all_rows():
            print(row)


def main(argv=None) -> None:
    run(Params.from_args(sys.argv[1:] if argv is None else argv))


if __name__ == "__main__":
    main()
