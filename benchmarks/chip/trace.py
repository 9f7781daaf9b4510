"""Reduction of a JAX profiler trace to the benchmark's device numbers.

``load(trace_dir)`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote and
keeps three things: the device operations of every TPU (the ``XLA Ops`` line
of each ``/device:TPU:<n>`` plane), the runs of whole programs (its ``XLA
Modules`` line) and the benchmark's own host spans (events whose name starts
with ``bench.``, from ``jax.profiler.TraceAnnotation``).
Both are on the profiler's one clock, in nanoseconds.

Everything else here is arithmetic on intervals, so it can be checked on a
trace recorded on the CPU (``tests/test_selfcheck.py``):

* ``busy_ns``: the union of the device-op intervals inside a window, averaged
  over the devices the run used; idle share = 1 - busy / window;
* ``op_ns``: summed device time of the operations whose instruction name
  holds a pattern (a Pallas kernel's name, ``sort``);
* ``top_ops`` and ``idle_gaps``: the ``breakdown`` of the result line, the
  operations that took most device time and the idle time between them,
  named by the innermost host span open over each gap.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os

__all__ = ["Op", "Span", "Trace", "load", "union", "busy_ns", "op_ns",
           "op_events", "leaf_ops", "top_ops", "idle_gaps"]

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."


@dataclasses.dataclass(frozen=True)
class Op:
    start: float
    end: float
    name: str  # on the TPU, the HLO instruction: "%sort.8 = (f32[...]) sort(...)"
    device: int = 0

    @property
    def short(self) -> str:
        """The instruction's own name ("sort.8", "waterfill_level_stats.9"):
        a Pallas kernel's instruction is named after the kernel."""
        return self.name.split(" = ", 1)[0].lstrip("%")

    @property
    def label(self) -> str:
        """The name with the result's type: "copy.45 bf16[1000000,8,60]"."""
        rest = self.name.split(" = ", 1)
        return self.short + (" " + rest[1].split("{", 1)[0][:60] if len(rest) > 1 else "")


@dataclasses.dataclass(frozen=True)
class Span:
    start: float
    end: float
    name: str


@dataclasses.dataclass
class Trace:
    ops: list  # [Op], every device
    spans: list  # [Span], host
    devices: int
    modules: list = dataclasses.field(default_factory=list)  # [Op], one per program run

    def window(self, name: str = "bench.window") -> tuple[float, float]:
        found = [s for s in self.spans if s.name == name]
        if not found:
            raise ValueError(f"trace holds no host span {name!r}")
        return min(s.start for s in found), max(s.end for s in found)


def load(trace_dir: str) -> Trace:
    """The newest ``.xplane.pb`` under ``trace_dir``."""
    import jax

    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                           recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = jax.profiler.ProfileData.from_file(files[-1])
    ops, spans, modules, devices = [], [], [], set()
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            dev = int(plane.name[len(DEVICE_PREFIX):].split()[0].split(":")[0])
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    modules.extend(Op(float(ev.start_ns), float(ev.end_ns), ev.name, dev)
                                   for ev in line.events)
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    devices.add(dev)
                    ops.append(Op(float(ev.start_ns), float(ev.end_ns), ev.name, dev))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append(Span(float(ev.start_ns), float(ev.end_ns), ev.name))
    return Trace(ops=ops, spans=spans, devices=max(len(devices), 1), modules=modules)


def union(intervals) -> list[tuple[float, float]]:
    """Merged, sorted ``(start, end)`` intervals."""
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def busy_ns(trace: Trace, lo: float, hi: float) -> float:
    """Union of device-op time inside [lo, hi], averaged over devices."""
    per_dev = collections.defaultdict(list)
    for op in trace.ops:
        per_dev[op.device].append((op.start, op.end))
    total = 0.0
    for ivs in per_dev.values():
        total += sum(e - s for s, e in union(_clip(ivs, lo, hi)))
    return total / max(trace.devices, 1)


def _matches(op: Op, patterns) -> bool:
    short = op.short.lower()
    return any(p.lower() in short for p in patterns)


def op_events(trace: Trace, patterns, lo: float, hi: float) -> list[Op]:
    """Device ops inside [lo, hi] whose instruction name holds a pattern."""
    return [op for op in trace.ops
            if op.start >= lo and op.end <= hi and _matches(op, patterns)]


def op_ns(trace: Trace, patterns, lo: float, hi: float) -> float:
    """Summed device time of the matching ops, per device."""
    return sum(op.end - op.start for op in op_events(trace, patterns, lo, hi)) / max(
        trace.devices, 1)


def leaf_ops(ops) -> list[Op]:
    """The ops that hold no other op: a ``while`` or ``call`` event spans the
    ops of its body, which are on the same line."""
    out = []
    by_dev = collections.defaultdict(list)
    for op in ops:
        by_dev[op.device].append(op)
    for evs in by_dev.values():
        evs.sort(key=lambda o: (o.start, -o.end))
        for a, b in zip(evs, evs[1:] + [None]):
            if b is None or not (b.start < a.end and b.end <= a.end):
                out.append(a)
    return out


def top_ops(trace: Trace, lo: float, hi: float, k: int = 10) -> list:
    """``[[name, seconds], ...]``: the k leaf ops, by instruction name and
    result type, with most device time (per device)."""
    tot = collections.Counter()
    for op in leaf_ops([o for o in trace.ops if o.start >= lo and o.end <= hi]):
        tot[op.label] += (op.end - op.start) / max(trace.devices, 1)
    return [[name, ns / 1e9] for name, ns in tot.most_common(k)]


def idle_gaps(trace: Trace, lo: float, hi: float, k: int = 10) -> list:
    """``[[host span, seconds], ...]``: device idle time inside the window of
    device 0, summed by the innermost ``bench.*`` span (other than the window
    itself) open at each gap's middle; the k largest."""
    dev0 = min((op.device for op in trace.ops), default=0)
    busy = union(_clip([(o.start, o.end) for o in trace.ops if o.device == dev0], lo, hi))
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    spans = [s for s in trace.spans if s.name != "bench.window"]
    tot = collections.Counter()
    for s, e in zip(edges[0::2], edges[1::2]):
        if e <= s:
            continue
        mid = 0.5 * (s + e)
        open_spans = [sp for sp in spans if sp.start <= mid <= sp.end]
        name = (min(open_spans, key=lambda sp: sp.end - sp.start).name
                if open_spans else "no host span")
        tot[name] += (e - s) / 1e9
    return [[name, sec] for name, sec in tot.most_common(k)]
