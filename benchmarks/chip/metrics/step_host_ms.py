"""Median host time of one serve decode step outside its wait on the device:
each ``repro.serve.step`` span of the engine in the traced window, less its
``repro.serve.step.wait`` child (``program_trace``).  What is left is the
host's key split, scalar transfers and decode dispatch, per token."""
from benchmarks.chip import program_trace as pt


def read(ctx):
    waits = pt.spans_in(ctx, "repro.serve.step.wait")
    own = []
    for step in pt.spans_in(ctx, "repro.serve.step"):
        inner = sum(w.end - w.start for w in waits
                    if step.start <= w.start and w.end <= step.end)
        own.append(step.end - step.start - inner)
    return pt.median_ms(own)
