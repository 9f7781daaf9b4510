"""Device time per round of cohort selection and gather: the self time of
the ops under the program's ``round.select`` (client weights, the top-k
cohort) and ``round.gather`` (batch keys, the cohort's batches) scopes
(``program_trace``)."""
from benchmarks.chip import program_trace as pt

SCOPES = ("round.select", "round.gather")


def read(ctx):
    return pt.layer_ms(ctx, SCOPES)
