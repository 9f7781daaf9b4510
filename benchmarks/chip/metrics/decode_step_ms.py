"""Median device time of one run of the decode program (the ``XLA Modules``
events of the engine's jitted ``_decode``) in the traced window."""
import statistics

PATTERN = "_decode"


def read(ctx):
    runs = [m for m in ctx["trace"].modules
            if PATTERN in m.name and ctx["lo"] <= m.start and m.end <= ctx["hi"]]
    if not runs:
        return None
    return statistics.median((m.end - m.start) / 1e6 for m in runs)
