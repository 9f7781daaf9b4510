"""The program's own names in a run's trace: its host spans and layer scopes.

``trace.load`` keeps what the harness names: the device ops by instruction
and the harness's ``bench.*`` host spans.  The program names its layers too
(``src/repro/obs.py``): host spans ``repro.*`` (``TraceAnnotation``) and
device scopes ``round.*`` (``jax.named_scope``), which land in the
``op_name`` metadata of each HLO instruction.  A TPU's op events carry the
instruction's name and no ``op_name``; the profiler stores the HLO of every
loaded program, metadata included, in the trace's ``/host:metadata`` plane
(one event metadata per program, named like the program's ``XLA Modules``
events, with a serialized ``HloProto`` stat).  This module reads both from
the ``.xplane.pb`` the harness recorded.

``read(ctx)`` finds that file: ``run.py`` traces into a
``bench_trace_*`` directory of the temp directory and removes it after the
readers ran, so the file is the one there whose ``bench.window`` span is the
run's window.  It returns a ``Program``, cached per window:

* ``spans``: the ``repro.*`` host spans;
* ``ops``: ``(op, scope, self_ns)`` for each device op of the window, its
  scope the first ``round.*`` component of its ``op_name`` (``""`` for none)
  and ``self_ns`` its time inside the window less the time of the ops it
  holds (a ``while`` holds its body's ops), so the self times of a device add
  up to its busy time and nothing is counted twice.

Where the program has none of these names, as a program older than them
has not, ``spans`` is empty and no op has a scope, and the readers return
nothing.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os
import re
import statistics
import sys
import tempfile

from benchmarks.chip import trace as tr

__all__ = ["Program", "read", "build", "host_spans", "hlo_op_names", "scope_of",
           "self_times", "layer_ms", "spans_in", "median_ms"]

TRACE_DIRS = "bench_trace_*"  # run.py's tempfile.mkdtemp(prefix="bench_trace_")
SPAN_PREFIX = "repro."
METADATA_PLANE = "/host:metadata"
HLO_STAT = "Hlo Proto"
_SCOPE = re.compile(r"(?<![\w.])round\.[a-z_]+")


@dataclasses.dataclass
class Program:
    spans: list  # [tr.Span], the program's host spans
    ops: list  # [(tr.Op, scope, self_ns)], device ops of the window

    @property
    def scoped(self) -> bool:
        return any(scope for _, scope, _ in self.ops)


def scope_of(op_name: str) -> str:
    """The first ``round.*`` component of an ``op_name``, or ``""``.  Under
    a transform the scope reads ``vmap(round.local_train)``; an instruction
    XLA merged from several carries their names joined by ``;``."""
    m = _SCOPE.search(op_name)
    return m.group(0) if m else ""


# -- the HLO of each program, from the xplane's metadata plane ---------------
#
# A minimal protobuf wire reader: the xplane and HLO messages are read field
# by field, by number (tsl/profiler/protobuf/xplane.proto, xla/service/hlo.proto),
# so no generated proto module is needed.


def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf, start=0, end=None):
    """``(number, value)`` of each field of the message in ``buf[start:end]``:
    an int for a varint, a ``(start, end)`` pair for a length-delimited
    field, ``None`` for a fixed-width one."""
    i = start
    end = len(buf) if end is None else end
    while i < end:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            n, i = _varint(buf, i)
            value = (i, i + n)
            i += n
        elif kind == 1:
            value, i = None, i + 8
        elif kind == 5:
            value, i = None, i + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {kind}")
        yield key >> 3, value


def _text(buf, span):
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _instruction_op_names(buf, module, out):
    # HloModuleProto.computations = 3; HloComputationProto.instructions = 2;
    # HloInstructionProto.name = 1, .metadata = 7; OpMetadata.op_name = 2.
    for num, comp in _fields(buf, *module):
        if num != 3:
            continue
        for cnum, inst in _fields(buf, *comp):
            if cnum != 2:
                continue
            name = op_name = None
            for inum, val in _fields(buf, *inst):
                if inum == 1:
                    name = _text(buf, val)
                elif inum == 7:
                    for mnum, mval in _fields(buf, *val):
                        if mnum == 2:
                            op_name = _text(buf, mval)
                if inum >= 7:  # fields come in number order
                    break
            if name is not None and op_name:
                out[name] = op_name


def hlo_op_names(blob: bytes) -> dict:
    """``{program: {instruction: op_name}}`` from a serialized XSpace, for
    every program whose HLO the profiler stored, under the name its ``XLA
    Modules`` events carry (``jit_scan_segment(123)``)."""
    buf = memoryview(blob)
    out: dict = {}
    # XSpace.planes = 1; XPlane.name = 2, .event_metadata = 4 (map entry:
    # key 1, value 2), .stat_metadata = 5; XEventMetadata.name = 2,
    # .stats = 5; XStat.metadata_id = 1, .bytes_value = 6; XStatMetadata.id
    # = 1, .name = 2; HloProto.hlo_module = 1.
    for num, plane in _fields(buf):
        if num != 1:
            continue
        fields = list(_fields(buf, *plane))
        if not any(n == 2 and _text(buf, v) == METADATA_PLANE for n, v in fields):
            continue
        hlo_ids = set()
        for n, entry in fields:
            if n != 5:
                continue
            for en, ev in _fields(buf, *entry):
                if en == 2:
                    meta = dict(_fields(buf, *ev))
                    if 2 in meta and _text(buf, meta[2]) == HLO_STAT:
                        hlo_ids.add(meta.get(1, 0))
        for n, entry in fields:
            if n != 4:
                continue
            for en, ev in _fields(buf, *entry):
                if en != 2:
                    continue
                name, protos = "", []
                for mn, mv in _fields(buf, *ev):
                    if mn == 2:
                        name = _text(buf, mv)
                    elif mn == 5:
                        stat = dict(_fields(buf, *mv))
                        if stat.get(1, 0) in hlo_ids and 6 in stat:
                            protos.append(stat[6])
                for proto in protos:
                    ops: dict = {}
                    for hn, module in _fields(buf, *proto):
                        if hn == 1:
                            _instruction_op_names(buf, module, ops)
                    out.setdefault(name, {}).update(ops)
    return out


# -- device ops with their scope and self time -------------------------------


def self_times(ops, lo: float, hi: float) -> list:
    """``[(op, self_ns)]``: each op's time inside [lo, hi] less that of the
    ops it holds, per device.  An op that starts inside another and ends
    after it takes the overlap from it, so the self times of a device add up
    to the union of its ops."""
    by_dev = collections.defaultdict(list)
    for op in ops:
        if op.end > lo and op.start < hi:
            by_dev[op.device].append(op)
    out = []
    for evs in by_dev.values():
        evs.sort(key=lambda o: (o.start, -o.end))
        stack: list = []
        for op in evs:
            while stack and stack[-1][0].end <= op.start:
                out.append(tuple(stack.pop()))
            start = max(op.start, lo)
            if stack:
                stack[-1][1] -= max(0.0, min(op.end, stack[-1][0].end, hi) - start)
            stack.append([op, max(0.0, min(op.end, hi) - start)])
        out.extend(tuple(s) for s in stack)
    return [(op, max(ns, 0.0)) for op, ns in out]


def build(trace, lo: float, hi: float, spans, op_names: dict) -> Program:
    """The ``Program`` of a trace, its host spans and its programs' op names."""
    runs = collections.defaultdict(list)
    for m in trace.modules:
        runs[m.device].append(m)
    for ms in runs.values():
        ms.sort(key=lambda m: m.start)
    starts = {d: [m.start for m in ms] for d, ms in runs.items()}

    def module(op):
        ms = runs.get(op.device, [])
        k = bisect.bisect_right(starts.get(op.device, []), op.start) - 1
        return ms[k].name if k >= 0 and op.end <= ms[k].end else None

    ops = []
    for op, own in self_times(trace.ops, lo, hi):
        table = op_names.get(module(op), {})
        ops.append((op, scope_of(table.get(op.short, "")), own))
    return Program(spans=list(spans), ops=ops)


# -- the run's trace file -----------------------------------------------------

_cache: dict = {}


def host_spans(data, prefix: str = SPAN_PREFIX) -> list:
    """The host events of a ``ProfileData`` whose name starts with
    ``prefix``, as ``trace.Span``s."""
    out = []
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(prefix):
                        out.append(tr.Span(float(ev.start_ns), float(ev.end_ns), ev.name))
    return out


def _find(lo, hi):
    """(ProfileData, path) of the run's trace, or (None, None)."""
    import jax

    pattern = os.path.join(tempfile.gettempdir(), TRACE_DIRS, "**", "*.xplane.pb")
    for path in sorted(glob.glob(pattern, recursive=True), key=os.path.getmtime,
                       reverse=True):
        data = jax.profiler.ProfileData.from_file(path)
        window = [s for s in host_spans(data, "bench.window") if s.name == "bench.window"]
        if window and (min(s.start for s in window), max(s.end for s in window)) == (lo, hi):
            return data, path
    return None, None


def read(ctx) -> Program:
    """The program's spans and scoped ops in the window of ``ctx``."""
    key = (ctx["lo"], ctx["hi"])
    if key not in _cache:
        spans, names = [], {}
        try:
            data, path = _find(*key)
            if data is not None:
                spans = host_spans(data, SPAN_PREFIX)
                with open(path, "rb") as f:
                    names = hlo_op_names(f.read())
        except Exception as e:  # a metric left out, not a failed run
            print(f"program_trace: {type(e).__name__}: {e}", file=sys.stderr, flush=True)
        _cache.clear()
        _cache[key] = build(ctx["trace"], ctx["lo"], ctx["hi"], spans, names)
    return _cache[key]


# -- what the readers compute ------------------------------------------------


def layer_ms(ctx, scopes) -> float | None:
    """Device self time per round of the ops in ``scopes`` (``""`` for the
    ops in none), per device; None where the program has no scopes."""
    prog = read(ctx)
    rounds = ctx["raw"].get("rounds", 0)
    if not prog.scoped or rounds <= 0:
        return None
    ns = sum(own for _, scope, own in prog.ops if scope in scopes)
    return ns / max(ctx["trace"].devices, 1) / 1e6 / rounds


def spans_in(ctx, name: str) -> list:
    """The program's host spans called ``name`` inside the window."""
    return [s for s in read(ctx).spans
            if s.name == name and ctx["lo"] <= s.start and s.end <= ctx["hi"]]


def median_ms(values_ns) -> float | None:
    values = list(values_ns)
    return statistics.median(values) / 1e6 if values else None
