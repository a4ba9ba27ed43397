"""The plain reference of explicit ALS-WR under a bfloat16 factor exchange,
numpy alone: no jax, no `ml_dtypes`, nothing of the program, nothing the
program has made except the factor state a check says it starts from.

For a half-sweep over rows u with ratings Omega_u and opposite factors y_j
(float32, as the previous half-sweep left them):

    y^_j = bf16(y_j)                        every entry, once, before the gather
    A_u  = sum_{j in Omega_u} y^_j y^_j^T   a product of two bf16 values is
                                            exact in f32; the sum is f32
    b_u  = sum_{j in Omega_u} r_uj y^_j     r in f32 (half stars: exact)
    x_u  = (A_u + lam |Omega_u| I)^-1 b_u   x_u stays f32: it is rounded only
                                            on its way out, next half

Departures from the f32 reference (`reference.ridge_rows`), one a line:
- the other side's factors are rounded to bfloat16 before anything reads them;
- nothing else: the sums, the ridge term and the solve are float64 as there.
"""

from __future__ import annotations

import numpy as np

from benchmark import reference


def round_bf16(x):
    """float32 -> the nearest bfloat16, ties to even, returned as float32:
    add 0x7FFF plus the lowest kept bit to the uint32 view, drop the low 16
    bits.  Subnormals, +-0 and +-inf come out of the same arithmetic; a
    value past bfloat16's largest rounds to inf; NaN stays NaN."""
    x = np.ascontiguousarray(x, np.float32)
    bits = x.view(np.uint32)
    kept = (bits + (np.uint32(0x7FFF) + ((bits >> 16) & np.uint32(1)))
            ) & np.uint32(0xFFFF0000)
    return np.where(np.isnan(x), x, kept.view(np.float32))


def ridge_rows_rounded(sample, row_of, col_of, vals, other, lam):
    """`reference.ridge_rows` (float64) from `round_bf16(other)`: the
    ALS-WR solve of the sampled rows under the bfloat16 exchange."""
    return reference.ridge_rows(sample, row_of, col_of, vals,
                                round_bf16(other), lam)
