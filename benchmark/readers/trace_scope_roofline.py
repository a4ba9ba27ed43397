"""One named scope's share of its own roofline: `trace_scope`'s device self
time per unit of work under `scope` (the innermost of `among` in an
operation's path), against what `module`.`model` says the scope's work needs
(a file of the benchmark beside `roofline.py`, called with the configuration
alone), as `trace_roofline_of` sets the whole busy time against a whole
iteration's count.  The larger of operations over peak FLOP/s and bytes over
peak bytes/s, over the scope's seconds.  Nothing where `trace_scope` reads
nothing (no trace, a CPU rehearsal, a program without the scopes), where the
scope took no time, or, on a CPU rehearsal, where there is no published
peak."""

import importlib

from benchmark.readers import trace_scope


def read(run, scope, among, module, model, per):
    got = trace_scope.read(run, scope, among, per)
    if got is None or got[0] <= 0:
        return None
    peaks = run.load("peaks.json")
    dev = run.devices[0]
    if dev.device_kind not in peaks:
        if dev.platform == "cpu":
            return None  # a rehearsal: no peak, no share
        raise ValueError(f"no published peak for {dev.device_kind!r} in peaks.json")
    peak = peaks[dev.device_kind]
    count = getattr(importlib.import_module("benchmark." + module), model)
    flops, nbytes = count(run.config)
    t_flops, t_bytes = flops / peak["flops_per_s"], nbytes / peak["hbm_bytes_per_s"]
    return 100.0 * max(t_flops, t_bytes) / got[0], {
        "bound": "flops" if t_flops > t_bytes else "bytes",
        "flops": flops, "bytes": nbytes, "scope_s": got[0], "n": got[1]["n"]}
