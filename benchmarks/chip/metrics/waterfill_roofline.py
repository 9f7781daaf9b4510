"""Roofline share of the sampler's water-filling kernel
(``kernels/sharded_waterfill.py``): the least time its calls could take, the
larger of bytes over HBM bandwidth and operations over the bf16 peak (both
from ``counts.waterfill_cost``), over the device time of its events."""
from benchmarks.chip import trace as tr

PATTERNS = ("waterfill",)


def read(ctx):
    cost = ctx["info"].get("waterfill")
    if not cost:
        return None
    events = tr.op_events(ctx["trace"], PATTERNS, ctx["lo"], ctx["hi"])
    if not events:
        return None
    ops, nbytes = cost
    p = ctx["peaks"]
    least = max(nbytes / p["hbm_bytes_per_s"], ops / p["bf16_flops"])
    spent = sum(e.end - e.start for e in events) / 1e9
    return 100.0 * least * len(events) / spent
