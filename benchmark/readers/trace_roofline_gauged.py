"""`trace_roofline` for a model that needs what the program reports of its
own layout: `module`.`model` (a file of the benchmark beside `roofline.py`)
is called with the configuration, the mean queries per frame from the
histogram `batch_from`, and, by keyword, the value each registry gauge in
`gauges` had when the window closed.  The larger of operations over peak
FLOP/s and bytes over peak bytes/s, over the traced device time per unit of
work (`per`).  Nothing where the run has no trace, the histogram did not
move, the program has no such gauge (a program from before it), or, on a CPU
rehearsal, where there is no published peak."""

import importlib

from benchmark.readers import metrics_diff, trace_busy


def read(run, module, model, per, batch_from, gauges):
    got = trace_busy.read(run, per)
    batch = metrics_diff.read(run, batch_from)
    if got is None or batch is None:
        return None
    held = {g["name"]: g["value"] for g in run.snap_after["gauges"]
            if not g["labels"]}
    if not all(name in held and held[name] > 0 for name in gauges.values()):
        return None
    peaks = run.load("peaks.json")
    dev = run.devices[0]
    if dev.device_kind not in peaks:
        if dev.platform == "cpu":
            return None  # a rehearsal: no peak, no share
        raise ValueError(f"no published peak for {dev.device_kind!r} in peaks.json")
    peak = peaks[dev.device_kind]
    counted = {key: held[name] for key, name in gauges.items()}
    count = getattr(importlib.import_module("benchmark." + module), model)
    flops, nbytes = count(run.config, batch[0], **counted)
    t_flops, t_bytes = flops / peak["flops_per_s"], nbytes / peak["hbm_bytes_per_s"]
    return 100.0 * max(t_flops, t_bytes) / got[0], {
        "bound": "flops" if t_flops > t_bytes else "bytes",
        "flops": flops, "bytes": nbytes, "batch": batch[0], **counted}
