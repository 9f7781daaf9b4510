"""Roofline share of the SSD scan: the least time a round's scans could take,
the larger of bytes over HBM bandwidth and FLOPs over the bf16 peak (the
family's ``ssd_cost``: the chunked scan's FLOPs at the published chunk,
forward and backward, and its inputs and output moved once a pass), over the
scan's device time a round (``ssd_scan_ms``).  None where the family counts
no scan."""
from benchmarks.chip import named_ops, references

SCOPE = "ssm.scan"


def read(ctx):
    ms = named_ops.component_ms(ctx, SCOPE)
    if not ms or "reference" not in ctx["cfg"]:
        return None
    cost = getattr(references.family(ctx["cfg"]), "ssd_cost", None)
    if cost is None:
        return None
    flops, nbytes = cost(ctx["cfg"], ctx["traffic"])
    p = ctx["peaks"]
    least = max(nbytes / p["hbm_bytes_per_s"], flops / p["bf16_flops"])
    return 100.0 * least / (ms / 1e3)
