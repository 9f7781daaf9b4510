"""Roofline share of the int8 dequantize-and-aggregate kernel
(``kernels/fused_weighted_agg.py`` ``fused_dequant_cohort_agg``): the larger
of bytes over HBM bandwidth and operations over the int8 peak (both from
``counts.dequant_agg_cost``), over the device time of its events."""
from benchmarks.chip import trace as tr

PATTERNS = ("dequant_cohort",)


def read(ctx):
    cost = ctx["info"].get("dequant_agg")
    if not cost:
        return None
    events = tr.op_events(ctx["trace"], PATTERNS, ctx["lo"], ctx["hi"])
    if not events:
        return None
    ops, nbytes = cost
    p = ctx["peaks"]
    least = max(nbytes / p["hbm_bytes_per_s"], ops / p["int8_ops"])
    spent = sum(e.end - e.start for e in events) / 1e9
    return 100.0 * least * len(events) / spent
