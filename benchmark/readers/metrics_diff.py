"""A program counter: a registry histogram's sum / count over the window
(never a bucket quantile), or a counter's total when the window opened."""


def read(run, name, scale=1.0, counter_at_start=False):
    if counter_at_start:
        value = run.counter(name, at_open=True)
        return None if value is None else value * scale
    total, n = run.hist_delta(name)
    if n <= 0:
        return None
    return total / n * scale, {"n": n}
