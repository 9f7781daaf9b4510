#!/usr/bin/env python3
"""Run one benchmark cell once on the chips of this machine.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name: the cell in ``BENCHMARK.json`` names its
configuration (a file of sizes under ``configs/``, whose ``reference`` key
names its family module, ``references/<family>.py``: the weights, the FLOP
counts, the check of the program's sizes and the plain reference) and its
traffic (``traffic/<traffic>.json``, whose ``generator`` key names the
module under ``generators/`` that builds the system under test and drives
its traffic); each per-layer metric is read by ``metrics/<metric>.py``.
Adding a cell, a configuration, a model family, a traffic mix or a metric
adds files; this file holds no list of them.

A run: set-up (weights and traffic from ``--seed``, the compile cache, the
first rounds or requests that the check compares), then ``--seconds`` of
measured work, then the check against the plain reference.  The last line of
standard output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer metrics from a profiler trace), ``device``, ``breakdown`` (traced
runs) and ``checks``, each compared number with its limit.  Without a TPU, or
with fewer chips than the cell asks for, it exits 3 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def _load_json(path):
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, bench: dict | None = None):
    """(cell entry, configuration dict, traffic dict, benchmark dict)."""
    bench = bench or _load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has {sorted(cells)}")
    cell = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cfg = _load_json(os.path.join(ROOT, conf["file"]))
    traffic = _load_json(os.path.join(HERE, "traffic", cell["traffic"] + ".json"))
    return cell, cfg, traffic, bench


def metrics_for(bench: dict, cell: str, kind: str) -> list:
    return [m for m in bench[kind] if "workloads" not in m or cell in m["workloads"]]


def _reader(name: str):
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"),
        os.path.join(HERE, "metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _span(name):
    import jax

    return jax.profiler.TraceAnnotation(name)


def _log(msg):
    print(msg, file=sys.stderr, flush=True)


class _CompileCount:
    """Counts traces and backend compiles (a persistent-cache hit traces too),
    and for the log the cache's hits and misses and the compile seconds."""

    def __init__(self):
        import collections

        import jax

        self.n = 0
        self.events = collections.Counter()
        self.compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_event)

    def _on(self, event, secs, **_kw):
        if event.endswith(("/jaxpr_trace_duration", "/backend_compile_duration")):
            self.n += 1
        if event.endswith("/backend_compile_duration"):
            self.compile_s += secs

    def _on_event(self, event, **_kw):
        if event.endswith(("/cache_hits", "/cache_misses")):
            self.events[event.rsplit("/", 1)[1]] += 1


def configure(cfg: dict) -> None:
    """Run the program in the precision its configuration states: a float32
    configuration names ``matmul_precision`` "highest", since on a TPU a
    float32 matmul at JAX's default precision is one bfloat16 pass."""
    import jax

    if "matmul_precision" in cfg:
        jax.config.update("jax_default_matmul_precision", cfg["matmul_precision"])


def run(args, *, require_chip: bool = True, bench: dict | None = None,
        cfg: dict | None = None, traffic: dict | None = None) -> dict:
    """One run; returns the result object.  ``require_chip=False`` and the
    explicit ``cfg``/``traffic`` are for the tests, which drive the rest of a
    run on the CPU at a small size."""
    cell, cfg0, traffic0, bench = load_cell(args.workload, bench)
    cfg, traffic = cfg or cfg0, traffic or traffic0
    import jax

    devices = jax.devices()
    if require_chip and (devices[0].platform != "tpu" or len(devices) < int(cell["chips"])):
        raise SystemExit(3)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.compile_cache import use_compile_cache

    if require_chip:
        use_compile_cache()
        # Every program of the cell is served from the cache after the first
        # run, the small ones too.
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    configure(cfg)
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    gen = importlib.import_module("benchmarks.chip.generators." + traffic["generator"])
    dev = devices[0]
    from benchmarks.chip import counts

    peaks = counts.peaks(dev.device_kind) if require_chip else None

    compiles = _CompileCount()
    sim = gen.Cell(cfg, traffic, args.seed)
    sim.setup(_span)
    seconds = float(args.seconds)
    if args.trace:
        seconds = min(seconds, float(traffic.get("trace_seconds", seconds)))
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        jax.profiler.start_trace(trace_dir)
    _log(f"set-up: {dict(compiles.events)} in the persistent compile cache, "
         f"{compiles.compile_s:.1f} s of backend compiles")
    n_before = compiles.n
    raw = sim.window(seconds, _span)
    in_window = compiles.n - n_before
    if args.trace:
        jax.profiler.stop_trace()
    setup_s = raw["wall0"] - T_START
    stats = dev.memory_stats() or {}
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices[: int(cell["chips"])])
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": int(cell["chips"]), "memory_peak_bytes": peak,
              "memory_limit_bytes": int(stats.get("bytes_limit", 0))}
    result = {"attempted": raw["attempted"], "failed": raw["failed"]}
    info = sim.info(raw)
    if args.trace:
        from benchmarks.chip import trace as tr

        t = tr.load(trace_dir)
        lo, hi = t.window()
        window_s = (hi - lo) / 1e9
        busy_s = tr.busy_ns(t, lo, hi) / 1e9
        device.update(busy_s=busy_s, window_s=window_s)
        ctx = {"trace": t, "lo": lo, "hi": hi, "window_s": window_s, "busy_s": busy_s,
               "raw": raw, "info": info, "peaks": peaks, "cfg": cfg, "traffic": traffic}
        metrics = {}
        for m in metrics_for(bench, cell["name"], "per_layer"):
            value = _reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["breakdown"] = {"device_ops": tr.top_ops(t, lo, hi),
                               "idle_gaps": tr.idle_gaps(t, lo, hi)}
        shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        values = dict(sim.end_to_end(raw), setup_s=setup_s)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in metrics_for(bench, cell["name"], "end_to_end")}
    _log(f"setup_s {setup_s!r}; window {raw['t1'] - raw['t0']!r} s; "
         f"traces or compiles inside the window: {in_window}")
    sim.release()
    numbers = sim.check()
    limits = traffic["limits"]
    for k in sorted(set(numbers) - set(limits)):
        _log(f"reading {k} {numbers[k]!r} (not compared)")
    checks = {k: {"value": numbers[k], "limit": lim} for k, lim in limits.items()}
    correct = in_window == 0 and all(c["value"] <= c["limit"] for c in checks.values())
    result.update(correct=bool(correct), metrics=metrics, device=device, checks=checks)
    for k, c in checks.items():
        _log(f"check {k} {c['value']!r} limit {c['limit']!r}")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        _log("the system under test (src/repro) is not in this checkout")
        return 3
    try:
        result = run(args)
    except SystemExit as e:
        if e.code == 3:
            _log("no TPU, or fewer chips than the cell asks for; no result")
        raise
    for c in result["checks"].values():  # a reading of NaN is no JSON number
        if not math.isfinite(c["value"]):
            c["value"] = repr(c["value"])
    order = ["correct", "attempted", "failed", "metrics", "device", "breakdown", "checks"]
    print(json.dumps({k: result[k] for k in order if k in result}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
