"""Device time per round of the sort and top-k operations in the traced
window: the sampler's ISP solve sorts its (N,) scores and the cohort
selection takes the top C of N priorities."""
from benchmarks.chip import trace as tr

PATTERNS = ("sort", "topk", "top-k", "top_k")


def read(ctx):
    rounds = ctx["raw"]["rounds"]
    ns = tr.op_ns(ctx["trace"], PATTERNS, ctx["lo"], ctx["hi"])
    if ns <= 0 or rounds <= 0:
        return None
    return ns / 1e6 / rounds
