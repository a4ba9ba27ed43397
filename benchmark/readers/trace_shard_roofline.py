"""One chip's share of its roofline in a program that runs on several chips
at once: `roofline_sharded.<model>` for one chip over one chip's published
peak and the slowest chip's traced busy time per frame
(`trace_shard_skew`'s frames, so nothing is returned where the program
carries no such scopes).  `roofline.topk_frame` over `trace_busy` would set
the whole catalog's bytes against one chip's bandwidth."""

from benchmark import roofline_sharded
from benchmark.readers import metrics_diff, trace_shard_skew


def read(run, model, first, last, batch_from):
    rows = trace_shard_skew.frames_of(run, first, last)
    batch = metrics_diff.read(run, batch_from)
    if not rows or batch is None:
        return None
    peaks = run.load("peaks.json")
    dev = run.devices[0]
    if dev.device_kind not in peaks:
        if dev.platform == "cpu":
            return None  # a rehearsal: no peak, no share
        raise ValueError(f"no published peak for {dev.device_kind!r} in peaks.json")
    peak = peaks[dev.device_kind]
    shards = len(rows[0])
    seconds = sum(max(f[2] for f in row) for row in rows) / len(rows) / 1e9
    flops, nbytes = getattr(roofline_sharded, model)(run.config, batch[0], shards)
    t_flops, t_bytes = flops / peak["flops_per_s"], nbytes / peak["hbm_bytes_per_s"]
    bound = "flops" if t_flops > t_bytes else "bytes"
    return 100.0 * max(t_flops, t_bytes) / seconds, {
        "bound": bound, "shards": shards, "slowest_busy_ms": seconds * 1e3,
        "n": len(rows)}
