"""Device time per round of aggregation: the self time of the ops under the
program's ``round.aggregate`` scope (``program_trace``): the weighted delta
sum, quantization where the round compresses, and the params update."""
from benchmarks.chip import program_trace as pt

SCOPES = ("round.aggregate",)


def read(ctx):
    return pt.layer_ms(ctx, SCOPES)
