"""Median of the harness's ``bench.start`` spans in the traced window: each
spans ``ServeEngine.start`` (prefill and the first token) until the first
tokens are ready."""
import statistics


def read(ctx):
    spans = [s for s in ctx["trace"].spans
             if s.name == "bench.start" and ctx["lo"] <= s.start and s.end <= ctx["hi"]]
    if not spans:
        return None
    return statistics.median((s.end - s.start) / 1e6 for s in spans)
