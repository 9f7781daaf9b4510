#!/usr/bin/env python3
"""Readings that the limits of a cell's check are set from.

    python3 benchmarks/chip/calibrate.py --workload <cell> --seeds 1 2 3 \\
        --control-seeds 11 12 13 --out readings.json

For each of ``--seeds``: the system under test through set-up and a short
window at the cell's own load (``calibrate_seconds``), then the check's
numbers against the plain reference: the lower readings.  For each of ``--control-seeds``: the
reference computed in the configuration's ``control_mode`` (the next
precision below the one it states) put in the program's place, and each of
the traffic's planted ``faults`` (the reference with that fault), against the
plain reference: the upper readings.  Everything runs in this one process,
so each program compiles once; nothing here is timed.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def _nospan(_name):
    return contextlib.nullcontext()


def _served(gen, cfg, traffic, seed):
    """A cell after set-up and a short window at its own load, released."""
    cell = gen.Cell(cfg, traffic, seed)
    cell.setup(_nospan)
    cell.window(float(traffic.get("calibrate_seconds", 0.0)), _nospan)
    cell.release()
    return cell


def program_reading(gen, cfg, traffic, seed):
    out = _served(gen, cfg, traffic, seed).check()
    gc.collect()
    return out


def upper_readings(gen, cfg, traffic, seed):
    """{"control": numbers, <fault>: numbers, ...} of one seed."""
    cell = _served(gen, cfg, traffic, seed)
    out = {}
    for name in ["control"] + list(traffic.get("faults", [])):
        out[name] = cell.upper(name)
        gc.collect()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    sys.path.insert(0, ROOT)
    import importlib

    import jax

    from benchmarks.chip import run as bench_run
    from repro.launch.compile_cache import use_compile_cache

    if jax.devices()[0].platform != "tpu":
        print("calibrate: no TPU", file=sys.stderr)
        return 3
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    _, cfg, traffic, _ = bench_run.load_cell(args.workload)
    bench_run.configure(cfg)
    gen = importlib.import_module("benchmarks.chip.generators." + traffic["generator"])
    out = {"workload": args.workload, "device": jax.devices()[0].device_kind,
           "program": {}, "upper": {}}
    for seed in args.seeds:
        t = time.time()
        out["program"][seed] = program_reading(gen, cfg, traffic, seed)
        print(f"program seed {seed} ({time.time() - t:.0f}s): {out['program'][seed]}",
              flush=True)
        _write(args.out, out)
    for seed in args.control_seeds:
        t = time.time()
        out["upper"][seed] = upper_readings(gen, cfg, traffic, seed)
        print(f"upper seed {seed} ({time.time() - t:.0f}s): {out['upper'][seed]}", flush=True)
        _write(args.out, out)
    return 0


def _write(path, out):
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    with open(path, "w") as f:
        json.dump(out, f, indent=1, default=float)


if __name__ == "__main__":
    sys.exit(main())
