"""The operations and bytes the IVF tier's list scan needs, by
`roofline.py`'s rule (every input read once, every multiply-add done once),
from what the program counted over the window."""

from __future__ import annotations


def ivf_scan(cfg, union_rows, probed_rows):
    """One frame's scan.  Bytes: the rows in the UNION of the frame's probed
    lists, once (`tpums_ann_union_rows_total` a frame: real rows, no pads;
    an implementation that reads a list once for every query that probes it
    reads more, one that shares it cannot read less).  The centroids are
    not the scan's: `topk.ivf.probe` reads them, outside this scope.
    Operations: 2 * rank a scored row, `tpums_ann_probed_rows_total` a frame
    (the rows summed over the frame's queries, the pad rows of their blocks
    among them: a tenth too many, where bytes bound the scan by a factor of
    hundreds)."""
    r = cfg["rank"]
    flops = 2.0 * r * probed_rows
    nbytes = union_rows * r * 4
    return float(flops), float(nbytes)
