"""A host-clock reading: a named span, the window's wall per unit of work,
or the median of a timed series."""

import statistics


def read(run, span=None, window_per=None, series=None):
    if span is not None:
        return run.clock.get(span)
    if window_per is not None:  # all the time of the window over all its work
        n = run.counts.get(window_per, 0)
        if n <= 0 or not run.window or run.window[1] is None:
            return None
        return (run.window[1] - run.window[0]) / n, {"n": n}
    values = run.series.get(series)
    if values is None or len(values) == 0:
        return None
    return statistics.median(values.tolist()), {"n": len(values)}
