"""Device time per round outside every layer of the round: the self time of
the ops under no ``round.*`` scope (``program_trace``), that is the segment
loop's key derivation, loop control, metric stitch and relayouts, and the
other programs the window runs.  With the four layer metrics it adds up to
the device's busy time per round."""
from benchmarks.chip import program_trace as pt

SCOPES = ("",)


def read(ctx):
    return pt.layer_ms(ctx, SCOPES)
