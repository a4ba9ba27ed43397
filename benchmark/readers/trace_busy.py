"""Device-busy seconds of the traced window, per unit of work."""


def read(run, per=None):
    red = run.reduced_trace()
    if not red or red["busy_s"] <= 0:
        return None
    n = run.counts.get(per, 0) if per else 1
    if n <= 0:
        return None
    return red["busy_s"] / n, {"n": n}
