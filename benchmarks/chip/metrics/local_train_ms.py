"""Device time per round of the cohort's local training: the self time of
the ops under the program's ``round.local_train`` scope (``program_trace``)."""
from benchmarks.chip import program_trace as pt

SCOPES = ("round.local_train",)


def read(ctx):
    return pt.layer_ms(ctx, SCOPES)
