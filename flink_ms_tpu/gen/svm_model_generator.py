"""Synthetic SVM model generator — counterpart of ``SVMModelGenerator``
(``model-generator/src/main/scala/de/tub/it4bi/SVMModelGenerator.scala``).

Emits range-partitioned rows ``bucket,idx:w;...`` for buckets
0..numFeatures/range inclusive, each bucket covering keys
``bucket*range .. bucket*range + range-1`` (0-based, reference parity —
SVMModelGenerator.scala:27-40; note this differs from SVMImpl's 1-based
trained-model indices, a reference quirk preserved as-is).  ~50% of weights
are exactly 0 (``nextBoolean`` gate :32-35), the rest uniform in (-10, 10)
(stand-in for the reference's dyadic-bisection sampler :45-52 — both are
symmetric about 0 and bounded; the generator is documented "Not for
quality" :12).
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


def generate_bucket_rows(num_features: int, range_: int, seed: int = 0) -> Iterator[str]:
    from ..parallel.mesh import acquire_devices

    acquire_devices()
    n_buckets = num_features // range_ + 1
    key = jax.random.PRNGKey(seed)
    for bucket in range(n_buckets):
        key, kz, kw = jax.random.split(key, 3)
        zero = np.asarray(jax.random.bernoulli(kz, 0.5, (range_,)))
        w = np.asarray(
            jax.random.uniform(kw, (range_,), minval=-10.0, maxval=10.0)
        )
        start = bucket * range_
        parts = []
        for j in range(range_):
            v = 0 if bool(zero[j]) else float(w[j])
            parts.append(f"{start + j}:{_fmt(v)}")
        yield f"{bucket}," + ";".join(parts)


def _fmt(v) -> str:
    # reference prints Scala Int 0 for zeroed weights ("i:0"), doubles otherwise
    return "0" if v == 0 else repr(float(v))


def run(params: Params) -> None:
    num_features = int(params.get_required("numFeatures"))
    range_ = int(params.get_required("range"))
    p = params.get_int("parallelism", 2)
    seed = params.get_int("seed", 0)

    rows = generate_bucket_rows(num_features, range_, seed)
    if params.has("output"):
        from .als_model_generator import _write_parallel

        _write_parallel(params.get_required("output"), rows, p)
    else:
        print("Printing results to stdout. Use --output to specify output location")
        for row in rows:
            print(row)


def main(argv=None) -> None:
    run(Params.from_args(sys.argv[1:] if argv is None else argv))


if __name__ == "__main__":
    main()
