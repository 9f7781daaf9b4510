"""Device time per round of the sampler: the self time of the ops under the
program's ``round.solve``, ``round.draw`` and ``round.sampler_update`` scopes
(``program_trace``), the water-filling solve and the ISP sort included."""
from benchmarks.chip import program_trace as pt

SCOPES = ("round.solve", "round.draw", "round.sampler_update")


def read(ctx):
    return pt.layer_ms(ctx, SCOPES)
