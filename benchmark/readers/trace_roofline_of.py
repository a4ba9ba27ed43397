"""`trace_roofline` for a model whose operations and bytes are kept in
another file of the benchmark than `roofline.py`, which later PRs may not
edit: `module` names that file (`roofline_cocoa`), `model` the function in
it, called with the configuration alone.  The larger of operations over
peak FLOP/s and bytes over peak bytes/s, over the traced device time per
unit of work; nothing where the run has no trace or, on a CPU rehearsal, no
published peak."""

import importlib

from benchmark.readers import trace_busy


def read(run, module, model, per):
    got = trace_busy.read(run, per)
    if got is None:
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
    bound = "flops" if t_flops > t_bytes else "bytes"
    return 100.0 * max(t_flops, t_bytes) / got[0], {
        "bound": bound, "flops": flops, "bytes": nbytes}
