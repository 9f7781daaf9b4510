"""End-to-end federated training driver: any zoo architecture x any sampler.

The canonical run description is ``repro.api.ExperimentSpec`` — the CLI
flags below are a thin shim that is parsed INTO a spec
(``build_spec_from_args``), and the spec is what actually runs:

  # flags -> spec -> run
  PYTHONPATH=src python -m repro.launch.train --arch smollm-360m --reduced \
      --rounds 8 --clients 32 --budget 6 --sampler kvib --seq 64 --ckpt /tmp/fl

  # print the spec a flag set denotes (no training), then run it verbatim
  PYTHONPATH=src python -m repro.launch.train [flags...] --dump-spec > exp.json
  PYTHONPATH=src python -m repro.launch.train --spec exp.json

The two invocations are equivalent by construction: ``--spec`` consumes
exactly what ``--dump-spec`` emits and reproduces the flag-driven run's
final parameters bit-for-bit (tests/test_launchers.py).  The checkpoint
manifest's ``config_fingerprint`` derives from ``spec.to_dict()`` — ANY
spec field change refuses to resume an old run's checkpoints.

The driver is the deployable realization of Algorithm 1, in two modes:

* default (host loop): per-round Python dispatch —
    host: sampler state, ISP draw, cohort selection/padding via the shared
          ``repro.fed.cohort`` contract (probabilities solved ONCE per round,
          unbiased |S|/C overflow rescaling, inert zero padding)
    device: the jitted federated round step (local SGD + cohort-width
            weighted aggregation + feedback norms in one program)
* ``--compiled``: the run executes as jitted ``lax.scan`` *segments* over
  rounds (``fed.round.build_fed_scan_segment`` driven by
  ``fed.state.run_segmented``) on the host mesh from ``repro.launch.mesh`` —
  draw, selection, device-side batch gather, sharded round step, and sampler
  update all inside the trace; both modes consume the identical key stream,
  so they train on the same draws and batches.  ``--ckpt-every N`` cuts the
  horizon into N-round segments (bitwise-neutral) and, with ``--ckpt DIR``,
  publishes the full ``TrainState`` — params, sampler's learned state, metric
  buffers, round index, RNG key — through a ``CheckpointManager`` at every
  boundary; ``--resume`` restarts a SIGKILL'd run from the manifest and
  reproduces the uninterrupted run's results exactly
  (tests/test_launchers.py).  ``--resume`` without the compiled path is an
  error: host-loop checkpoints hold params+sampler only (no RNG key, no
  round index) and cannot be resumed.
"""
from __future__ import annotations

import argparse
import json
import os
import signal
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.api import (
    CompressionSpec,
    ExecutionSpec,
    ExperimentSpec,
    FaultSpec,
    FederationSpec,
    SamplerSpec,
    TaskSpec,
    build,
)
from repro.checkpoint import CheckpointManager, config_fingerprint, save_checkpoint
from repro.core import estimator
from repro.core.samplers import sampler_names
from repro.fed import cohort as fed_cohort
from repro.fed.round import build_fed_scan_segment, build_round_step
from repro.fed.state import run_segmented
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models import transformer


def make_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        description="Federated training of a zoo arch; flags are a shim over "
        "repro.api.ExperimentSpec (--dump-spec shows the spec they denote)"
    )
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--sampler", default="kvib", choices=sampler_names())
    ap.add_argument("--rounds", type=int, default=8)
    ap.add_argument("--clients", type=int, default=32)
    ap.add_argument("--budget", type=int, default=6)
    ap.add_argument("--cohort", type=int, default=8, help="padded cohort buffer C")
    ap.add_argument("--local-steps", type=int, default=2)
    ap.add_argument("--local-batch", type=int, default=2)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--local-lr", type=float, default=0.05)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt", default="")
    ap.add_argument(
        "--ckpt-every", type=int, default=0,
        help="checkpoint every N rounds; with --compiled this is the scan "
        "segment length (bitwise-neutral) and checkpoints go to the "
        "<ckpt>_ckpts/ CheckpointManager directory.  WITHOUT --compiled the "
        "host loop saves params+sampler snapshots only — no RNG key or round "
        "index — which are NOT resumable",
    )
    ap.add_argument(
        "--compiled", action="store_true",
        help="run the rounds as jitted lax.scan segments on the host mesh "
        "(fed.round.build_fed_scan_segment); default is the per-round host loop",
    )
    ap.add_argument(
        "--resume", action="store_true",
        help="with --compiled --ckpt --ckpt-every: resume from the newest "
        "committed step in <ckpt>_ckpts/manifest.json (fresh start if none). "
        "Errors without the compiled path: host-loop checkpoints are not "
        "resumable",
    )
    ap.add_argument(
        "--shard-sampler", default="", metavar="AXIS",
        help="shard every sampler (N,)-axis tensor over this mesh axis "
        "(e.g. 'data') — the million-client switch: the budget solve, draw, "
        "and feedback update run shard-local (ExecutionSpec.sampler_axis)",
    )
    ap.add_argument(
        "--faults", default="", metavar="JSON",
        help="deployment-realism fault layer as a FaultSpec JSON object, "
        "e.g. '{\"availability\": \"markov\", \"availability_kwargs\": "
        "{\"p_on\": 0.7, \"p_off\": 0.2}, \"deadline\": 1.0}' — availability "
        "processes, deadline stragglers (unbiased reweighting), and "
        "buffered-async aggregation.  Requires --compiled (the fault state "
        "lives in the scan carry)",
    )
    ap.add_argument(
        "--delta-dtype", default="", choices=["", "int8", "fp8"],
        help="quantize client deltas to this width inside the traced round "
        "(CompressionSpec.delta_dtype): the (C, D) stacked buffer lives in "
        "HBM at quantized width with per-(slot, block) fp32 scales and a "
        "server-side error-feedback residual in the carry.  Requires "
        "--compiled (the residual lives in the scan carry)",
    )
    ap.add_argument(
        "--no-error-feedback", action="store_true",
        help="with --delta-dtype: drop the error-feedback residual "
        "(ablation — quantization error then accumulates round over round)",
    )
    ap.add_argument(
        "--spec", default="",
        help="load the experiment from an ExperimentSpec JSON file (as "
        "emitted by --dump-spec); the experiment flags above are ignored",
    )
    ap.add_argument(
        "--dump-spec", action="store_true",
        help="print the ExperimentSpec JSON these flags denote and exit "
        "without training",
    )
    ap.add_argument(
        "--lint", action="store_true",
        help="statically lint the spec before training "
        "(repro.analysis.lint.run_suite: sampler scan-safety, round-body "
        "dtype hygiene, cohort-width) and abort with exit code 1 on any "
        "finding — no training happens on a spec that fails its contracts",
    )
    return ap


def build_spec_from_args(args) -> ExperimentSpec:
    """The flags->spec projection: the ONE place CLI flags acquire meaning.

    ``--spec``/``--dump-spec``/``--ckpt``/``--resume`` are not part of the
    experiment (they say where to run / persist it, not what it is) and do
    not appear in the spec."""
    return ExperimentSpec(
        task=TaskSpec(
            kind="zoo",
            name=args.arch,
            reduced=args.reduced,
            dataset="synthetic_tokens",
            dataset_kwargs={"n_clients": args.clients, "seq_len": args.seq},
        ),
        sampler=SamplerSpec(
            name=args.sampler,
            kwargs=(
                {"horizon": args.rounds} if args.sampler in ("kvib", "vrb") else {}
            ),
        ),
        federation=FederationSpec(
            rounds=args.rounds,
            budget=args.budget,
            cohort=args.cohort,
            local_steps=args.local_steps,
            batch_size=args.local_batch,
            local_lr=args.local_lr,
        ),
        execution=ExecutionSpec(
            seed=args.seed,
            compiled=args.compiled,
            ckpt_every=args.ckpt_every,
            sampler_axis=args.shard_sampler or None,
        ),
        fault=(
            FaultSpec(**json.loads(args.faults)) if args.faults else FaultSpec()
        ),
        compression=CompressionSpec(
            delta_dtype=args.delta_dtype or None,
            error_feedback=not args.no_error_feedback,
        ),
    )


def run_spec(spec: ExperimentSpec, *, ckpt: str = "", resume: bool = False) -> None:
    """Execute a zoo ExperimentSpec with launcher ergonomics (per-round
    prints, checkpoint publishing, kill/resume hooks).  The construction —
    arch config, dataset, sampler, RoundSpec, key stream — comes from
    ``repro.api.build``, so this trains the identical run ``repro.api.run``
    would."""
    built = build(spec)
    cfg, ds, sampler = built.arch_config, built.dataset, built.sampler
    rspec = built.round_spec
    fed, ex = built.spec.federation, spec.execution
    rounds, ckpt_every = fed.rounds, ex.ckpt_every
    lam = np.asarray(ds.lam)

    key = jax.random.PRNGKey(ex.seed)
    params = transformer.init_params(cfg, key)
    n_params = transformer.param_count(params)
    print(f"arch={cfg.name} params={n_params/1e6:.1f}M clients={ds.n_clients} "
          f"K={fed.budget} cohort={rspec.cohort} sampler={spec.sampler.name}")

    s_state = sampler.init()

    if ex.compiled:
        mesh = make_host_mesh()
        print(f"compiled scan on mesh {dict(mesh.shape)} ({len(mesh.devices.flat)} devices)")
        segment, make_state = build_fed_scan_segment(cfg, rspec, sampler, ds, mesh=mesh)
        # Identical key stream to the host loop below: per round
        # (key, k_draw, k_data) chained splits, derived in-trace segment by
        # segment from the TrainState's chain key.
        state = make_state(params, s_state, key, rounds)

        manager = None
        if resume and not (ckpt and ckpt_every):
            print("warning: --resume needs --ckpt AND --ckpt-every; starting fresh")
        if ckpt and ckpt_every:
            # The spec IS the run configuration: its canonical serialization
            # is what the manifest fingerprints, so resuming under ANY
            # changed spec field raises instead of silently mixing runs.
            fingerprint = config_fingerprint(spec.to_dict())
            manager = CheckpointManager(f"{ckpt}_ckpts", fingerprint=fingerprint)
            # Drop the spec next to the manifest BEFORE training: a serving
            # process following this directory (repro.launch.serve --follow)
            # reconstructs the full run configuration — and the matching
            # fingerprint — from this file alone.
            os.makedirs(manager.directory, exist_ok=True)
            spec.save(os.path.join(manager.directory, "spec.json"))
            if resume:
                state, start = manager.restore_or_init(state)
                if start:
                    print(f"resumed from checkpoint step {start} "
                          f"({rounds - start} rounds remaining)")

        # Test hook: self-SIGKILL after N published segments — how the
        # kill/resume integration test simulates a preemption that strikes
        # between segment boundaries.
        kill_after = int(os.environ.get("REPRO_KILL_AFTER_SEGMENTS", "0"))
        segments_done = []

        def on_segment(st, rounds_done):
            segments_done.append(rounds_done)
            if manager is not None:
                print(f"checkpoint step {rounds_done} -> {manager.directory}")
            if kill_after and len(segments_done) >= kill_after:
                print(f"REPRO_KILL_AFTER_SEGMENTS={kill_after}: SIGKILL", flush=True)
                os.kill(os.getpid(), signal.SIGKILL)

        start_round = int(state.round)
        t0 = time.time()
        state = run_segmented(
            state, rounds, segment,
            ckpt_every=ckpt_every, manager=manager, on_segment=on_segment,
        )
        jax.block_until_ready(state)
        wall = time.time() - t0
        params, s_state = state.params, state.sampler
        losses = np.asarray(state.metrics["loss"])
        cohorts = np.asarray(state.metrics["cohort_size"])
        for t in range(rounds):
            print(f"round {t:>3} loss={losses[t]:.4f} cohort={int(cohorts[t])}")
        n_disp = len(segments_done)
        disp = "one dispatch" if n_disp == 1 else f"{n_disp} dispatches"
        print(f"{rounds - start_round} rounds in {disp}: {wall:.1f}s "
              f"({wall / max(rounds - start_round, 1):.2f}s/round)")
        dropped_total = int(np.sum(np.asarray(state.metrics["dropped"])))
        if dropped_total:
            print(f"cohort overflow drops: {dropped_total}")
        if "deadline_dropped" in state.metrics:
            dd = int(np.sum(np.asarray(state.metrics["deadline_dropped"])))
            print(f"deadline straggler drops: {dd}")
        if ckpt:
            f = save_checkpoint(ckpt, {"params": params, "sampler": s_state})
            print("final checkpoint ->", f)
        return

    if rspec.faults is not None:
        raise SystemExit(
            "fault injection (FaultSpec enabled) requires --compiled: the "
            "fault state (availability chain, stale-delta buffer) lives in "
            "the scan carry, which the per-round host loop does not thread"
        )
    if rspec.compression is not None:
        raise SystemExit(
            "delta compression (--delta-dtype) requires --compiled: the "
            "error-feedback residual lives in the scan carry, which the "
            "per-round host loop does not thread"
        )
    round_step = jax.jit(build_round_step(cfg, rspec), donate_argnums=(0,))

    dropped_total = 0
    for t in range(rounds):
        t0 = time.time()
        key, k_draw, k_data = jax.random.split(key, 3)
        # Solve the sampling probabilities ONCE per round; the draw and the
        # log line both reuse this vector (the old loop solved 3x: sample +
        # two probabilities() calls in the print).
        p = sampler.probabilities(s_state)
        draw = sampler.sample_from(p, k_draw)
        w_full = estimator.client_weights(
            draw, jnp.asarray(lam), sampler.procedure, sampler.budget
        )
        # Shared padded-cohort contract: uniform overflow drop with |S|/C
        # weight rescaling (unbiased), inert zero padding — fed/cohort.py.
        sel = fed_cohort.select_cohort(
            draw.mask, w_full, rspec.cohort, jax.random.fold_in(k_draw, 1)
        )
        dropped_total += int(sel.n_dropped)

        # gather cohort batches (C, R, B, S); padding slots stay zero
        tokens, targets = fed_cohort.host_gather_cohort_batches(
            ds, sel, k_data, rspec.local_steps, rspec.local_batch
        )

        params, norms, loss = round_step(params, tokens, targets, sel.weights)

        # feedback: pi_t(i) = lambda_i ||g_i|| for the clients actually trained
        ids, valid = np.asarray(sel.ids), np.asarray(sel.valid)
        fb = np.zeros(ds.n_clients, np.float32)
        fb[ids[valid]] = lam[ids[valid]] * np.asarray(norms)[valid]
        s_state = sampler.update(s_state, draw, jnp.asarray(fb))

        print(
            f"round {t:>3} loss={float(loss):.4f} cohort={int(valid.sum())} "
            f"p[min/max]={float(jnp.min(p)):.3f}/{float(jnp.max(p)):.3f} "
            f"({time.time()-t0:.1f}s)"
        )
        if ckpt and ckpt_every and (t + 1) % ckpt_every == 0:
            # Host-loop snapshot: params+sampler ONLY (not resumable — no
            # RNG key or round index; use --compiled for real resume).
            f = save_checkpoint(f"{ckpt}_r{t+1}", {"params": params, "sampler": s_state})
            print("  checkpoint ->", f)

    if dropped_total:
        print(f"cohort overflow drops: {dropped_total}")
    if ckpt:
        f = save_checkpoint(ckpt, {"params": params, "sampler": s_state})
        print("final checkpoint ->", f)


def main(argv=None) -> None:
    ap = make_parser()
    args = ap.parse_args(argv)
    use_compile_cache()

    if args.spec:
        spec = ExperimentSpec.load(args.spec)
    else:
        spec = build_spec_from_args(args)

    if args.dump_spec:
        print(spec.to_json())
        return

    if args.lint:
        from repro.analysis.lint import run_suite

        report = run_suite(spec)
        print(report.render(), flush=True)
        if not report.ok:
            raise SystemExit(1)

    if args.resume and not spec.execution.compiled:
        ap.error(
            "--resume requires the compiled path (--compiled, or "
            '"execution": {"compiled": true} in --spec): host-loop '
            "checkpoints hold params+sampler only — no RNG key or round "
            "index — and cannot be resumed"
        )

    run_spec(spec, ckpt=args.ckpt, resume=args.resume)


if __name__ == "__main__":
    main()
