"""A kernel's share of its roofline: the larger of operations over peak
FLOP/s and bytes over peak bytes/s, over the traced device time per call."""

from benchmark import roofline
from benchmark.readers import trace_busy


def read(run, model, per, batch_from=None):
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
    args = (run.config,)
    if batch_from:  # mean queries per frame, from the program's histogram
        from benchmark.readers import metrics_diff

        batch = metrics_diff.read(run, batch_from)
        if batch is None:
            return None
        args += (batch[0],)
    flops, nbytes = getattr(roofline, model)(*args)
    t_flops, t_bytes = flops / peak["flops_per_s"], nbytes / peak["hbm_bytes_per_s"]
    bound = "flops" if t_flops > t_bytes else "bytes"
    return 100.0 * max(t_flops, t_bytes) / got[0], {"bound": bound}
