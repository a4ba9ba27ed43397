"""One of the program's registry gauges over the product of others, as
they stood when the window closed, times `scale`; `counters` names registry
counters whose gain over the window is reported beside it.  Nothing is
returned where the program has no such gauge (a program from before it
existed) or the product is 0 (nothing installed)."""

import math


def read(run, part, of, scale=1.0, counters=()):
    gauges = {g["name"]: g["value"] for g in run.snap_after["gauges"]
              if not g["labels"]}
    if part not in gauges or not all(name in gauges for name in of):
        return None
    whole = math.prod(gauges[name] for name in of)
    if whole <= 0:
        return None
    extra = {name: gauges[name] for name in (part, *of)}
    for name in counters:
        extra[name] = ((run.counter(name) or 0)
                       - (run.counter(name, at_open=True) or 0))
    return scale * gauges[part] / whole, extra
