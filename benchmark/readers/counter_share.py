"""A registry counter's gain over the window as a share: over the product
of the gains of the counters in `per` and of the configuration's numbers in
`of`, times `scale`.  Nothing where the program has none of these counters
(a program from before they existed) or one of them did not move."""

import math


def gain(run, name):
    """What a registry counter gained over the window; None where the
    program has no such counter."""
    after = run.counter(name)
    if after is None:
        return None
    return after - (run.counter(name, at_open=True) or 0)


def read(run, part, per=(), of=(), scale=1.0):
    top, below = gain(run, part), [gain(run, name) for name in per]
    if top is None or any(g is None or g <= 0 for g in below):
        return None
    whole = math.prod(below) * math.prod(run.config[key] for key in of)
    return scale * top / whole, {part: top, **dict(zip(per, below))}
