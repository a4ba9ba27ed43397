"""`trace_scope_roofline` for a scope whose work is not fixed by the
configuration: the model (`module`.`model`, a file of the benchmark beside
`roofline.py`) is called with the configuration and, by keyword, the gain of
each registry counter in `counters` over the window per unit of work, the
same unit (`per`) that `trace_scope` divides the scope's seconds by.
Nothing where `trace_scope` reads nothing (no trace, a CPU rehearsal, a
program without the scope), where the program has no such counter or it did
not move, or, on a CPU rehearsal, where there is no published peak."""

import importlib

from benchmark.readers import trace_scope
from benchmark.readers.counter_share import gain


def gains(run, counters, per):
    """keyword -> counter gain over the window per unit, or None."""
    n = run.counts.get(per, 0)
    out = {key: gain(run, name) for key, name in counters.items()}
    if n <= 0 or any(g is None or g <= 0 for g in out.values()):
        return None
    return {key: g / n for key, g in out.items()}


def read(run, scope, among, module, model, per, counters):
    got = trace_scope.read(run, scope, among, per)
    counted = gains(run, counters, per)
    if got is None or got[0] <= 0 or counted is None:
        return None
    peaks = run.load("peaks.json")
    dev = run.devices[0]
    if dev.device_kind not in peaks:
        if dev.platform == "cpu":
            return None  # a rehearsal: no peak, no share
        raise ValueError(f"no published peak for {dev.device_kind!r} in peaks.json")
    peak = peaks[dev.device_kind]
    count = getattr(importlib.import_module("benchmark." + module), model)
    flops, nbytes = count(run.config, **counted)
    t_flops, t_bytes = flops / peak["flops_per_s"], nbytes / peak["hbm_bytes_per_s"]
    return 100.0 * max(t_flops, t_bytes) / got[0], {
        "bound": "flops" if t_flops > t_bytes else "bytes",
        "flops": flops, "bytes": nbytes, "scope_s": got[0], "n": got[1]["n"],
        **counted}
