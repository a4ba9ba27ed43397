"""The bytes the row gather of an ALS iteration needs, from the
configuration's shape, by `roofline.py`'s rule: the least any gather can
read.  Counted at the SOURCE's ratings, not the program's padded slots, and
at the width the configuration states for the exchanged factors
(`exchange_dtype`; none stated = the solve dtype's), so that two cells of
one shape that differ in the exchange alone can be read side by side.  What
the program moves beside it (rows landing in 128-lane tiles, the bucket
ladder's pads, a table copied into segments) is its own business: the share
cannot pass 100%."""

from __future__ import annotations

import numpy as np


def als_gather(cfg):
    """Both half-sweeps' gathers: no operations; each rating's opposite row
    (k entries at the exchange width) and its index (4 B), once a half."""
    name = cfg.get("exchange_dtype") or cfg["dtype"]
    # numpy alone knows no bfloat16
    itemsize = 2 if name == "bfloat16" else np.dtype(name).itemsize
    return 0.0, float(2 * cfg["nnz"] * (cfg["rank"] * itemsize + 4))
