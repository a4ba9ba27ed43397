"""Statistics of the load generator's raw per-request records."""

import numpy as np


def read(run, series, percentile):
    values = run.series.get(series)
    if values is None or len(values) == 0:
        return None
    # the nearest-rank percentile of ALL samples: no interpolation ladder
    ranked = np.sort(values)
    rank = min(len(ranked) - 1, int(np.ceil(percentile / 100.0 * len(ranked))) - 1)
    return float(ranked[max(rank, 0)]), {"n": len(ranked)}
