"""Self-check of the readers of the program's own spans and scopes
(``program_trace.py`` and the metrics that use it), on the CPU.

    JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip/tests

* the HLO op names read from a recorded trace's metadata plane equal the
  compiled program's own text;
* the run's trace file is found by its window among other traces;
* each new reader against a value worked out by hand on a hand-built trace,
  the layer metrics adding up to the busy time;
* the readers that were there read the same values after the new ones ran;
* an idle gap is named by ``repro.serve.step.prep`` where that span is the
  innermost one open.
"""
from __future__ import annotations

import glob
import importlib.util
import json
import os
import re
import tempfile

import jax
import jax.numpy as jnp
import pytest

from benchmarks.chip import counts, program_trace as pt, trace as tr

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(HERE))
NEW = ("sampler_ms", "cohort_ms", "local_train_ms", "aggregate_ms", "unscoped_ms",
       "step_host_ms", "swap_ms")
MS = 1e6


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        "p_" + name.replace(".", "_"), os.path.join(HERE, "metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _scoped_fn():
    def f(x):
        with jax.named_scope("round.solve"):
            y = jnp.sort(x)
        with jax.named_scope("round.draw"):
            return y * 2 + 1

    return jax.jit(f)


def test_hlo_op_names_equal_the_compiled_text(tmp_path):
    f, x = _scoped_fn(), jnp.arange(64.0)[::-1]
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    f(x).block_until_ready()
    jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    with open(path, "rb") as fh:
        names = pt.hlo_op_names(fh.read())
    text = f.lower(x).compile().as_text()
    want = dict(re.findall(r'%([\w.-]+) = .*metadata=\{op_name="([^"]*)"', text))
    (program,) = [k for k in names if k.startswith("jit_f(")]
    assert {k: v for k, v in names[program].items() if k in want} == want
    scopes = {pt.scope_of(v) for v in want.values()}
    assert {"round.solve", "round.draw"} <= scopes
    assert pt.hlo_op_names(b"") == {}


def test_scope_of_reads_the_first_round_component():
    assert pt.scope_of("jit(s)/while/body/closed_call/vmap(round.local_train)/jvp()/dot") == (
        "round.local_train")
    assert pt.scope_of("jit(s)/round.solve/jit(_isp_solve)/mul;round.solve/jit") == "round.solve"
    assert pt.scope_of("jit(s)/while/body/add") == ""
    assert pt.scope_of("jit(s)/around.solve/add") == ""


def _record_run(tmp_path):
    """A trace like the harness's: a ``bench.window`` and program spans."""
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("repro.serve.swap"):
            jnp.ones(3).block_until_ready()
    jax.profiler.stop_trace()
    t = tr.load(str(tmp_path))
    return t, t.window()


def test_the_run_trace_is_found_by_its_window(tmp_path, monkeypatch):
    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
    pt._cache.clear()
    _record_run(tmp_path / "bench_trace_older")
    t, (lo, hi) = _record_run(tmp_path / "bench_trace_run")
    _record_run(tmp_path / "elsewhere")  # not a harness trace directory
    ctx = {"trace": t, "lo": lo, "hi": hi, "raw": {"rounds": 1}}
    prog = pt.read(ctx)
    assert [s.name for s in prog.spans] == ["repro.serve.swap"]
    assert lo <= prog.spans[0].start <= prog.spans[0].end <= hi
    assert _reader("swap_ms")(ctx) == pytest.approx(
        (prog.spans[0].end - prog.spans[0].start) / 1e6)
    assert _reader("sampler_ms")(ctx) is None  # a CPU trace has no device ops
    pt._cache.clear()
    assert pt.read({**ctx, "lo": lo - 1}).spans == []  # no trace has that window


def _op(start, end, name, device=0):
    return tr.Op(start * MS, end * MS, f"%{name} = f32[8]{{0}} op()", device)


def _hand_built():
    """Two rounds of one program ``jit_seg(7)``, by hand (ms):

    0-10 while.1 holding sort.8 1-3 (solve), sort.5 3-4 (select), copy.45
    4-8 (no scope) and fusion.3 8-9 (local_train): the while's own time is
    10 - 8 = 2, under no scope; 12-13 add.2 (aggregate) in a second run of
    the program; 21-22 add.2 outside any program run, so under no scope.
    Busy 12 ms over 2 rounds: 6 ms a round."""
    ops = [_op(0, 10, "while.1"), _op(1, 3, "sort.8"), _op(3, 4, "sort.5"),
           _op(4, 8, "copy.45"), _op(8, 9, "fusion.3"), _op(12, 13, "add.2"),
           _op(21, 22, "add.2")]
    modules = [tr.Op(0, 10 * MS, "jit_seg(7)"), tr.Op(11 * MS, 14 * MS, "jit_seg(7)")]
    spans = [tr.Span(0, 30 * MS, "bench.window"), tr.Span(0, 30 * MS, "bench.step")]
    op_names = {"jit_seg(7)": {
        "while.1": "jit(seg)/while",
        "sort.8": "jit(seg)/while/body/closed_call/round.solve/jit(_isp_solve)/sort",
        "sort.5": "jit(seg)/while/body/closed_call/round.select/sort",
        "copy.45": "jit(seg)/while/body/closed_call/data",
        "fusion.3": "jit(seg)/while/body/closed_call/vmap(round.local_train)/add",
        "add.2": "jit(seg)/while/body/closed_call/round.aggregate/add"}}
    program_spans = [
        tr.Span(0.1 * MS, 2.1 * MS, "repro.serve.step"),  # own 2.0 - 1.0 = 1.0
        tr.Span(1.1 * MS, 2.1 * MS, "repro.serve.step.wait"),
        tr.Span(3 * MS, 7 * MS, "repro.serve.step"),  # own 4 - 1 = 3
        tr.Span(3.2 * MS, 4.2 * MS, "repro.serve.step.prep"),
        tr.Span(6 * MS, 7 * MS, "repro.serve.step.wait"),
        tr.Span(10 * MS, 12 * MS, "repro.serve.step"),  # own 2 - 0.5 = 1.5
        tr.Span(11.5 * MS, 12 * MS, "repro.serve.step.wait"),
        tr.Span(8 * MS, 8.5 * MS, "repro.serve.swap"),
        tr.Span(9 * MS, 9.2 * MS, "repro.serve.swap"),
        tr.Span(25 * MS, 40 * MS, "repro.serve.swap"),  # ends after the window
    ]
    t = tr.Trace(ops=ops, spans=spans, devices=1, modules=modules)
    lo, hi = t.window()
    ctx = {"trace": t, "lo": lo, "hi": hi, "window_s": (hi - lo) / 1e9,
           "busy_s": tr.busy_ns(t, lo, hi) / 1e9, "raw": {"rounds": 2},
           "info": {"train_flops_per_round": 1e9, "serve_flops": 2e9,
                    "waterfill": counts.waterfill_cost(4096, 128)},
           "peaks": counts.peaks("TPU v5 lite"), "cfg": {}, "traffic": {}}
    pt._cache.clear()
    pt._cache[(lo, hi)] = pt.build(t, lo, hi, program_spans, op_names)
    return ctx


def test_new_readers_by_hand():
    ctx = _hand_built()
    got = {name: _reader(name)(ctx) for name in NEW}
    want = {"sampler_ms": 1.0, "cohort_ms": 0.5, "local_train_ms": 0.5,
            "aggregate_ms": 0.5, "unscoped_ms": 3.5, "step_host_ms": 1.5, "swap_ms": 0.35}
    assert got == pytest.approx(want)
    layers = sum(got[k] for k in NEW[:5])
    assert layers == pytest.approx(ctx["busy_s"] * 1e3 / ctx["raw"]["rounds"])


def test_self_times_add_up_to_busy_per_device():
    ops = [_op(0, 10, "while.1"), _op(1, 3, "sort.8"), _op(2, 3, "fusion.1"),
           _op(5, 12, "copy.2"), _op(0, 4, "add.1", device=1)]
    own = {(op.short, op.device): ns for op, ns in pt.self_times(ops, 0, 11 * MS)}
    assert own == pytest.approx({("while.1", 0): 3 * MS, ("sort.8", 0): 1 * MS,
                                 ("fusion.1", 0): 1 * MS, ("copy.2", 0): 6 * MS,
                                 ("add.1", 1): 4 * MS})
    t = tr.Trace(ops=ops, spans=[], devices=2)
    assert sum(own.values()) == pytest.approx(2 * tr.busy_ns(t, 0, 11 * MS))


def test_program_without_names_reads_nothing():
    ctx = _hand_built()
    t = ctx["trace"]
    pt._cache[(ctx["lo"], ctx["hi"])] = pt.build(t, ctx["lo"], ctx["hi"], [], {})
    assert {name: _reader(name)(ctx) for name in NEW} == dict.fromkeys(NEW)


def test_existing_readers_read_the_same_after_the_new_ones():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        existing = [m["name"] for m in json.load(f)["per_layer"] if m["name"] not in NEW]
    ctx = _hand_built()
    ops, spans = list(ctx["trace"].ops), list(ctx["trace"].spans)
    before = {name: _reader(name)(ctx) for name in existing}
    assert before["idle_pct.train"] == pytest.approx(100 * (1 - 12 / 30))
    assert before["sampler_sort_ms"] == pytest.approx(1.5)
    for name in NEW:
        _reader(name)(ctx)
    assert {name: _reader(name)(ctx) for name in existing} == before
    assert ctx["trace"].ops == ops and ctx["trace"].spans == spans


def test_idle_gap_named_by_the_innermost_program_span():
    ms = MS
    ops = [tr.Op(0, 4 * ms, "%fusion.1 = f32[8]{0} fusion()"),
           tr.Op(7 * ms, 16 * ms, "%fusion.2 = f32[8]{0} fusion()")]
    bench = [tr.Span(0, 16 * ms, "bench.window"), tr.Span(3.5 * ms, 12 * ms, "bench.step")]
    program = [tr.Span(3.6 * ms, 11.9 * ms, "repro.serve.step"),
               tr.Span(3.7 * ms, 6.5 * ms, "repro.serve.step.prep"),
               tr.Span(6.5 * ms, 6.9 * ms, "repro.serve.step.dispatch")]
    alone = dict(tr.idle_gaps(tr.Trace(ops=ops, spans=bench, devices=1), 0, 16 * ms))
    assert alone == {"bench.step": pytest.approx(3e-3)}
    both = dict(tr.idle_gaps(tr.Trace(ops=ops, spans=bench + program, devices=1), 0, 16 * ms))
    assert both == {"repro.serve.step.prep": pytest.approx(3e-3)}
