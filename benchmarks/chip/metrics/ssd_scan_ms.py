"""Device time per round of the SSD scan of the state-space layers: the self
time of the ops under the program's ``ssm.scan`` scope (the chunked scan in
forward, its transpose and its recomputation under remat), per device.  The
scope opens inside ``round.local_train``, so this time is a part of
``local_train_ms``."""
from benchmarks.chip import named_ops

SCOPE = "ssm.scan"


def read(ctx):
    return named_ops.component_ms(ctx, SCOPE)
