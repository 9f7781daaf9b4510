"""Median host time of one weight swap of the serve engine: the
``repro.serve.swap`` spans in the traced window (``program_trace``), the
signature check and the device_put of the new weights."""
from benchmarks.chip import program_trace as pt


def read(ctx):
    return pt.median_ms(s.end - s.start for s in pt.spans_in(ctx, "repro.serve.swap"))
