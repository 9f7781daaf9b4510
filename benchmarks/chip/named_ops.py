"""Device self time of the ops that a name component of their ``op_name``
picks out, such as a model-level scope of the program (``repro.obs``
``MODEL_SCOPES``) that opens inside a ``round.*`` scope.

``program_trace`` files each op under its first ``round.*`` component only;
a metric of a layer inside a round scope reads the whole ``op_name`` here
instead.  It uses ``program_trace``'s reader of the trace's stored HLO
(``hlo_op_names``) and its self times (``self_times``), so these times add
up with the round layers' as theirs do.
"""
from __future__ import annotations

import bisect
import collections
import re
import sys

from benchmarks.chip import program_trace as pt

__all__ = ["component_ms"]

_cache: dict = {}


def _named(ctx) -> list:
    """``[(op_name, self_ns)]`` of the device ops of the window of ``ctx``;
    empty where the trace holds no op names."""
    key = (ctx["lo"], ctx["hi"])
    if key in _cache:
        return _cache[key]
    out = []
    try:
        _, path = pt._find(*key)
        if path is not None:
            with open(path, "rb") as f:
                tables = pt.hlo_op_names(f.read())
            runs = collections.defaultdict(list)
            for m in ctx["trace"].modules:
                runs[m.device].append(m)
            for ms in runs.values():
                ms.sort(key=lambda m: m.start)
            starts = {d: [m.start for m in ms] for d, ms in runs.items()}
            for op, own in pt.self_times(ctx["trace"].ops, *key):
                ms = runs.get(op.device, [])
                k = bisect.bisect_right(starts.get(op.device, []), op.start) - 1
                if k >= 0 and op.end <= ms[k].end:
                    out.append((tables.get(ms[k].name, {}).get(op.short, ""), own))
    except Exception as e:  # a metric left out, not a failed run
        print(f"named_ops: {type(e).__name__}: {e}", file=sys.stderr, flush=True)
    _cache.clear()
    _cache[key] = out
    return out


def component_ms(ctx, component: str) -> float | None:
    """Device self time per round and device of the ops whose ``op_name``
    holds ``component`` as a whole component, under any transform
    (``jvp(...)``, ``transpose(...)``, ``rematted_computation``): None where
    no op holds it, as in a program without that name."""
    pat = re.compile(rf"(?<![\w.]){re.escape(component)}(?![\w.])")
    ns = [own for name, own in _named(ctx) if pat.search(name)]
    rounds = ctx["raw"].get("rounds", 0)
    if not ns or rounds <= 0:
        return None
    return sum(ns) / max(ctx["trace"].devices, 1) / 1e6 / rounds
