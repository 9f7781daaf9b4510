"""Benchmark harness — one entry per paper table/figure + framework benches.

Emits ``name,us_per_call,derived`` CSV rows.  Experiment-derived rows read
the JSON artifacts produced by the example drivers (results/*.json); compute
benches time the hot paths on this host.  The federated benches construct
their experiment pieces through ``repro.api`` specs (``api.build``), so the
benchmarked configuration is the same serializable description every other
front door consumes.

  PYTHONPATH=src python -m benchmarks.run [--filter substr]
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

RESULTS = os.environ.get("REPRO_RESULTS", "results")
ROWS: list[tuple[str, float, str]] = []


def row(name: str, us: float, derived: str = "") -> None:
    ROWS.append((name, us, derived))
    print(f"{name},{us:.2f},{derived}", flush=True)


def _timeit(fn, *args, reps=20, warmup=3):
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e6


# ---------------------------------------------------------------------------
# Table: sampler solver scaling (paper Appendix G — O(N log N) claim)
# ---------------------------------------------------------------------------


def bench_solver_scaling() -> None:
    from repro.core import solver

    for n in (1_000, 10_000, 100_000, 1_000_000):
        a = jax.random.uniform(jax.random.PRNGKey(0), (n,)) + 1e-3
        f = jax.jit(lambda a, n=n: solver.isp_probabilities(a, n // 10))
        us = _timeit(f, a)
        row(f"kvib_solver_n{n}", us, f"probabilities for N={n} clients")


# ---------------------------------------------------------------------------
# Table: server aggregation (fused kernel vs two-pass reference)
# ---------------------------------------------------------------------------


def bench_fused_aggregation() -> None:
    from repro.kernels import ref
    from repro.kernels.fused_weighted_agg import fused_weighted_agg

    c, d = 16, 1 << 20
    g = jax.random.normal(jax.random.PRNGKey(0), (c, d), jnp.float32)
    w = jax.random.uniform(jax.random.PRNGKey(1), (c,))

    us_ref = _timeit(jax.jit(ref.weighted_agg_reference), g, w, reps=5)
    row("weighted_agg_reference", us_ref, f"two-output jnp path C={c} D={d}")
    us_k = _timeit(
        lambda g, w: fused_weighted_agg(g, w, block_d=4096, interpret=True), g, w,
        reps=1, warmup=1,
    )
    row("fused_weighted_agg_interp", us_k, "Pallas kernel (interpret mode; TPU target)")


# ---------------------------------------------------------------------------
# Table: federated round step (paper's Algorithm 1 at simulation scale)
# ---------------------------------------------------------------------------


def bench_round_step() -> None:
    from repro.configs import get_config
    from repro.fed.round import RoundSpec, build_round_step
    from repro.models import transformer

    cfg = get_config("smollm-360m").reduced(n_layers=2, d_model=128, d_ff=256, vocab=256)
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    c, r, b, s = 4, 2, 2, 64
    tok = jax.random.randint(jax.random.PRNGKey(1), (c, r, b, s), 0, cfg.vocab)
    w = jnp.full((c,), 0.25)
    step = jax.jit(build_round_step(cfg, RoundSpec(cohort=c, local_steps=r, local_lr=0.05)))
    us = _timeit(step, params, tok, tok, w, reps=3)
    tokens = c * r * b * s
    row("fl_round_step_reduced", us, f"{tokens} tokens/round client_parallel")


# ---------------------------------------------------------------------------
# Table: compiled scan loop vs per-round Python dispatch (fed/server.py)
# ---------------------------------------------------------------------------


def bench_fed_round_scan() -> None:
    """Whole-run lax.scan vs the per-round reference loop at N=100, T=50.

    The Python path pays 1 jit dispatch + 5 host transfers per round (loss,
    cohort, sq-error, cost, opt-cost); the scan path pays 1 dispatch + 1
    transfer for the ENTIRE run — 6T vs 2 host round-trips (150x fewer at
    T=50).  Both execute the identical round body."""
    import jax.numpy as jnp

    from repro import api
    from repro.fed import server as fed_server

    n, t_rounds = 100, 50
    spec = api.ExperimentSpec(
        task=api.TaskSpec(
            name="logreg", dataset="synthetic_classification",
            dataset_kwargs=dict(n_clients=n, total=200 * n, seed=0),
        ),
        sampler=api.SamplerSpec(name="kvib", kwargs=dict(horizon=t_rounds)),
        federation=api.FederationSpec(
            rounds=t_rounds, budget=10, local_steps=1, batch_size=8,
        ),
    )
    built = api.build(spec)
    task, ds, sampler, cfg = built.task, built.dataset, built.sampler, built.fed_config
    body = fed_server._build_round_body(task, ds, sampler, cfg, None)

    key = jax.random.PRNGKey(0)
    params = task.init(key)
    opt = cfg.server_opt.init(params)
    ss = sampler.init()
    keys = jax.random.split(key, t_rounds * 2).reshape(t_rounds, 2, 2)
    ts = jnp.arange(t_rounds, dtype=jnp.int32)

    @jax.jit
    def scan_all(params, opt, ss, keys):
        return jax.lax.scan(body, (params, opt, ss), (ts, keys[:, 0], keys[:, 1]))

    step = jax.jit(body)

    us_scan = _timeit(scan_all, params, opt, ss, keys, reps=5, warmup=2) / t_rounds

    def python_loop(params, opt, ss, keys):
        carry = (params, opt, ss)
        for t in range(t_rounds):
            carry, m = step(carry, (ts[t], keys[t, 0], keys[t, 1]))
            # The reference loop's per-round host syncs.
            for v in m.values():
                float(jnp.sum(v))
        return carry

    us_py = _timeit(python_loop, params, opt, ss, keys, reps=5, warmup=2) / t_rounds

    row("fed_round_scan", us_scan, f"compiled lax.scan N={n} T={t_rounds}; 2 host round-trips/run")
    row(
        "fed_round_python",
        us_py,
        f"per-round dispatch; {6 * t_rounds} host round-trips/run ({us_py / us_scan:.2f}x slower/round)",
    )


# ---------------------------------------------------------------------------
# Table: segmented compiled horizon vs monolithic scan (preemption-safety tax)
# ---------------------------------------------------------------------------


def bench_fed_scan_segmented() -> None:
    """What does cutting the compiled horizon into checkpointable segments
    cost?  Runs the same T-round horizon (fed/server.py segment runner,
    identical results by construction) as ONE segment vs segments of
    ``ckpt_every=50`` rounds — the overhead is purely the extra host
    dispatches and the metric-buffer stitching, NOT checkpoint I/O (no
    manager attached), which is the steady-state tax a preemption-safe run
    pays every round.  Target: <10% us/round at ckpt_every=50.  Emits
    ``RESULTS/BENCH_fed_scan_segmented.json`` with the lower-is-better
    segmented/monolithic ratio for the regression gate."""
    from repro import api
    from repro.fed import server as fed_server
    from repro.fed.state import run_segmented

    n, t_rounds, every = 100, 100, 50
    spec = api.ExperimentSpec(
        task=api.TaskSpec(
            name="logreg", dataset="synthetic_classification",
            dataset_kwargs=dict(n_clients=n, total=40 * n, seed=0),
        ),
        sampler=api.SamplerSpec(name="kvib", kwargs=dict(horizon=t_rounds)),
        federation=api.FederationSpec(
            rounds=t_rounds, budget=10, local_steps=1, batch_size=8,
        ),
    )
    built = api.build(spec)
    # donate=False: _timeit re-runs from the same initial state, which
    # donation would invalidate on accelerator backends.
    segment, state0 = fed_server.build_segment_runner(
        built.task, built.dataset, built.sampler, built.fed_config, None,
        donate=False,
    )

    def run_with(ckpt_every):
        def go():
            out = run_segmented(state0, t_rounds, segment, ckpt_every=ckpt_every)
            jax.block_until_ready(out.metrics)
        return go

    modes = (("monolithic", 0), (f"ckpt{every}", every))
    goes = {mode: run_with(ckpt_every) for mode, ckpt_every in modes}
    for go in goes.values():  # compile both segment lengths up front
        go()
    # Interleaved best-of-k: the ratio is the payload, and a mean would let a
    # load spike during one mode's window masquerade as segmentation cost.
    best = {mode: float("inf") for mode in goes}
    for _ in range(8):
        for mode, go in goes.items():
            t0 = time.perf_counter()
            go()
            best[mode] = min(best[mode], time.perf_counter() - t0)
    us = {mode: b / t_rounds * 1e6 for mode, b in best.items()}
    for mode, ckpt_every in modes:
        row(
            f"fed_scan_segmented_{mode}", us[mode],
            f"us/round, N={n} T={t_rounds} "
            + ("one segment" if ckpt_every == 0 else f"{t_rounds // ckpt_every} segments"),
        )
    ratio = us[f"ckpt{every}"] / us["monolithic"]
    row("fed_scan_segmented_overhead", 0,
        f"segmented/monolithic us-per-round ratio: {ratio:.3f}x (target < 1.10)")
    os.makedirs(RESULTS, exist_ok=True)
    with open(os.path.join(RESULTS, "BENCH_fed_scan_segmented.json"), "w") as f:
        json.dump(
            {
                "bench": "fed_scan_segmented",
                "entries": [{
                    "n": n, "rounds": t_rounds, "ckpt_every": every,
                    "monolithic_us_per_round": us["monolithic"],
                    "segmented_us_per_round": us[f"ckpt{every}"],
                }],
                # regression-gate ratios: LOWER is better
                "ratios": {f"segmented_ckpt{every}_over_monolithic": ratio},
            },
            f, indent=2,
        )


# ---------------------------------------------------------------------------
# Table: deployable cohort-only round vs oracle all-clients round (O(C) vs O(N))
# ---------------------------------------------------------------------------


def bench_fed_round_cohort() -> None:
    """us/round vs N at fixed K for the two metric fidelities of fed/server.py:
    oracle (trains all N clients, O(N) local-update compute) vs deployable
    (trains only the static C-slot cohort, O(C) local-update compute plus
    O(N) sampler/scatter bookkeeping).  Oracle grows linearly in N; the
    deployable curve should stay roughly flat.  Emits the per-N pairs to
    ``RESULTS/BENCH_fed_round_cohort.json`` so the perf trajectory records
    deployable-mode us/round across PRs."""
    from repro import api
    from repro.fed import server as fed_server

    k, c = 10, 20

    def spec_for(n, oracle):
        return api.ExperimentSpec(
            task=api.TaskSpec(
                name="logreg", dataset="synthetic_classification",
                dataset_kwargs=dict(n_clients=n, total=40 * n, seed=0),
            ),
            sampler=api.SamplerSpec(name="kvib", kwargs=dict(horizon=100)),
            federation=api.FederationSpec(
                budget=k, local_steps=1, batch_size=16,
                cohort=None if oracle else c,
            ),
            execution=api.ExecutionSpec(oracle_metrics=oracle),
        )

    entries = []
    for n in (64, 256, 1024):
        us = {}
        params = None
        for mode, oracle in (("oracle", True), ("deployable", False)):
            built = api.build(spec_for(n, oracle))
            task, ds, sampler, cfg = (
                built.task, built.dataset, built.sampler, built.fed_config,
            )
            if params is None:
                params = task.init(jax.random.PRNGKey(0))
            xs = (jnp.zeros((), jnp.int32), jax.random.PRNGKey(1), jax.random.PRNGKey(2))
            body = fed_server._build_round_body(task, ds, sampler, cfg, None)
            carry = (params, cfg.server_opt.init(params), sampler.init())
            us[mode] = _timeit(jax.jit(body), carry, xs, reps=10, warmup=2)
            row(f"fed_round_cohort_n{n}_{mode}", us[mode], f"K={k} C={c} one round body")
        entries.append(
            {"n": n, "budget": k, "cohort": c,
             "oracle_us": us["oracle"], "deployable_us": us["deployable"],
             "oracle_over_deployable": us["oracle"] / us["deployable"]}
        )
    os.makedirs(RESULTS, exist_ok=True)
    with open(os.path.join(RESULTS, "BENCH_fed_round_cohort.json"), "w") as f:
        json.dump(
            {
                "bench": "fed_round_cohort",
                "entries": entries,
                # regression-gate ratios: LOWER is better (benchmarks/check_regression.py)
                "ratios": {
                    "deployable_over_oracle_n1024":
                        entries[-1]["deployable_us"] / entries[-1]["oracle_us"],
                },
            },
            f, indent=2,
        )


# ---------------------------------------------------------------------------
# Table: cohort-width deployable round — us/round and live bytes flat in N
# ---------------------------------------------------------------------------


def bench_fed_cohort_width() -> None:
    """The tentpole claim of the cohort-width fast path: at fixed K/C the
    deployable round's cost must NOT grow with the client population N.

    Times the deployable round body in both aggregation widths — the default
    O(C*D) cohort-width path and the legacy O(N*D) scatter path
    (``exact_oracle_equiv=True``) — across N, and records the compiled
    round's peak live bytes.  Emits ``RESULTS/BENCH_fed_cohort_width.json``
    with lower-is-better flatness ratios for the regression gate.

    Design notes: the task is the MLP (D ~ 26k params) so the O(*D) costs
    dominate the O(N) sampler-vector ops, as they do at real scale; client
    sizes are uniform (``power=0.0``) so the padded dataset's max-client size
    stays constant in N — under the default power law s_max grows with N and
    the batch *gather* walks a multi-GB array, a simulation-harness artifact
    that would otherwise be billed to the round."""
    from repro import api
    from repro.fed import server as fed_server

    k, c = 10, 20
    entries = []
    for n in (64, 256, 1024):
        spec = api.ExperimentSpec(
            task=api.TaskSpec(
                name="mlp",
                kwargs=dict(dim=60, n_classes=10, hidden=128, depth=2),
                dataset="synthetic_classification",
                dataset_kwargs=dict(n_clients=n, total=40 * n, power=0.0, seed=0),
            ),
            sampler=api.SamplerSpec(name="kvib", kwargs=dict(horizon=100)),
            federation=api.FederationSpec(
                budget=k, local_steps=1, batch_size=16, cohort=c,
            ),
            execution=api.ExecutionSpec(oracle_metrics=False),
        )
        built = api.build(spec)
        task, ds, sampler = built.task, built.dataset, built.sampler
        base = built.fed_config
        params = task.init(jax.random.PRNGKey(0))
        xs = (jnp.zeros((), jnp.int32), jax.random.PRNGKey(1), jax.random.PRNGKey(2))
        entry = {"n": n, "budget": k, "cohort": c}
        for mode, cfg in (
            ("cohort_width", base),
            ("scatter", dataclasses.replace(base, exact_oracle_equiv=True)),
        ):
            body = fed_server._build_round_body(task, ds, sampler, cfg, None)
            carry = (params, cfg.server_opt.init(params), sampler.init())
            jitted = jax.jit(body)  # one wrapper: _timeit and memory_analysis share the compile
            entry[f"{mode}_us"] = _timeit(jitted, carry, xs, reps=20, warmup=3)
            row(f"fed_cohort_width_n{n}_{mode}", entry[f"{mode}_us"],
                f"K={k} C={c} deployable round body")
            try:
                ma = jitted.lower(carry, xs).compile().memory_analysis()
                entry[f"{mode}_peak_bytes"] = int(
                    ma.argument_size_in_bytes + ma.output_size_in_bytes
                    + ma.temp_size_in_bytes
                )
            except Exception:
                entry[f"{mode}_peak_bytes"] = None
        entries.append(entry)
    flat = entries[-1]["cohort_width_us"] / entries[0]["cohort_width_us"]
    slope = entries[-1]["scatter_us"] / entries[0]["scatter_us"]
    row("fed_cohort_width_flatness", 0,
        f"cohort-width N=64->1024: {flat:.2f}x (scatter path: {slope:.2f}x)")
    os.makedirs(RESULTS, exist_ok=True)
    with open(os.path.join(RESULTS, "BENCH_fed_cohort_width.json"), "w") as f:
        json.dump(
            {
                "bench": "fed_cohort_width",
                "entries": entries,
                # regression-gate ratios: LOWER is better
                "ratios": {"cohort_width_n1024_over_n64": flat},
            },
            f, indent=2,
        )


# ---------------------------------------------------------------------------
# Table: million-client sampler round — per-client cost flat in N
# ---------------------------------------------------------------------------


def bench_fed_sampler_scale() -> None:
    """The tentpole claim of the sharded sampler stack: at fixed budget K the
    full sampler round — sharded water-filling solve, Poisson draw, feedback
    update — costs O(N/S) per device with a CONSTANT per-client price.

    Times the jitted sampler round at N = 10^4..10^6 (no model — the sampler
    is the only N-sized object, which is exactly the point) and records the
    compiled round's live bytes.  The gate ratios normalize per client:
    us/client and bytes/client from N=10^4 to N=10^6 must stay <= 1.5x
    (lower-is-better flatness, ``benchmarks/check_regression.py``).  CPU CI
    runs the degenerate S=1 mesh; per-client normalization makes the gate
    mesh-size independent — on an S-shard mesh every shard holds N/S clients
    at the same per-client price."""
    from repro.core import make_sampler
    from repro.launch.mesh import ShardSpec

    k = 64
    entries = []
    for n in (10_000, 100_000, 1_000_000):
        sampler = dataclasses.replace(
            make_sampler("kvib", n=n, budget=k, horizon=100),
            shard=ShardSpec(),
        )

        @jax.jit
        def sampler_round(state, key, sampler=sampler):
            p = sampler.probabilities(state)
            draw = sampler.sample_from(p, key)
            return sampler.update(state, draw, draw.mask * p)

        state = sampler.init()
        key = jax.random.PRNGKey(0)
        reps = 3 if n >= 1_000_000 else 10
        us = _timeit(sampler_round, state, key, reps=reps, warmup=2)
        entry = {
            "n": n, "budget": k,
            "us": us, "us_per_client": us / n,
        }
        try:
            ma = sampler_round.lower(state, key).compile().memory_analysis()
            live = int(
                ma.argument_size_in_bytes + ma.output_size_in_bytes
                + ma.temp_size_in_bytes
            )
            entry["live_bytes"] = live
            entry["bytes_per_client"] = live / n
        except Exception:
            entry["live_bytes"] = None
        row(f"fed_sampler_scale_n{n}", us,
            f"K={k} sharded sampler round (solve+draw+update)")
        entries.append(entry)
    time_flat = entries[-1]["us_per_client"] / entries[0]["us_per_client"]
    ratios = {"per_client_us_n1e6_over_n1e4": time_flat}
    derived = f"us/client N=1e4->1e6: {time_flat:.2f}x"
    if entries[0].get("live_bytes") and entries[-1].get("live_bytes"):
        bytes_flat = (
            entries[-1]["bytes_per_client"] / entries[0]["bytes_per_client"]
        )
        ratios["per_client_bytes_n1e6_over_n1e4"] = bytes_flat
        derived += f" (bytes/client: {bytes_flat:.2f}x)"
    row("fed_sampler_scale_flatness", 0, derived)
    os.makedirs(RESULTS, exist_ok=True)
    with open(os.path.join(RESULTS, "BENCH_fed_sampler_scale.json"), "w") as f:
        json.dump(
            {
                "bench": "fed_sampler_scale",
                "entries": entries,
                # regression-gate ratios: LOWER is better
                "ratios": ratios,
            },
            f, indent=2,
        )


# ---------------------------------------------------------------------------
# Table: fault-realism layer cost + convergence under churn
# ---------------------------------------------------------------------------


def bench_fed_fault_overhead() -> None:
    """What does deployment realism cost inside the traced round body?

    Times the deployable compiled segment (fed/server.py) for the SAME spec
    with the full fault layer on (Markov availability + deadline stragglers +
    buffered-async) vs off — the fault layer is a build-time branch, so the
    clean program is literally the pre-fault one and the ratio is the whole
    story.  Target: faulted/clean us-per-round < 1.10.  Also records
    convergence-under-churn: kvib vs uniform_isp loss curves at 30% Bernoulli
    availability (the adaptive sampler's variance edge must survive churn).
    Emits ``RESULTS/BENCH_fed_fault_overhead.json`` for the regression gate.
    """
    from repro import api
    from repro.fed import server as fed_server
    from repro.fed.state import run_segmented

    n, t_rounds = 128, 50

    def spec_with(fault, sampler="kvib", rounds=t_rounds, seed=0):
        return api.ExperimentSpec(
            task=api.TaskSpec(
                name="logreg", dataset="synthetic_classification",
                dataset_kwargs=dict(n_clients=n, total=40 * n, seed=0),
            ),
            sampler=api.SamplerSpec(
                name=sampler,
                kwargs=dict(horizon=rounds) if sampler == "kvib" else {},
            ),
            federation=api.FederationSpec(
                rounds=rounds, budget=16, local_steps=1, batch_size=8,
            ),
            execution=api.ExecutionSpec(seed=seed),
            fault=fault,
        )

    faulted_fault = api.FaultSpec(
        availability="markov",
        availability_kwargs={"p_on": 0.7, "p_off": 0.2},
        deadline=1.0, latency_kwargs={"scale": 0.5},
        async_buffer=4, staleness_discount=0.5,
    )
    goes = {}
    for mode, fault in (("clean", api.FaultSpec()), ("faulted", faulted_fault)):
        built = api.build(spec_with(fault))
        # donate=False: re-runs start from the same initial state
        segment, state0 = fed_server.build_segment_runner(
            built.task, built.dataset, built.sampler, built.fed_config, None,
            donate=False,
        )

        def go(segment=segment, state0=state0):
            out = run_segmented(state0, t_rounds, segment)
            jax.block_until_ready(out.metrics)

        goes[mode] = go
        go()  # compile up front
    # Interleaved best-of-k (the ratio is the payload; a mean would let a
    # load spike during one mode's window masquerade as fault-layer cost).
    best = {mode: float("inf") for mode in goes}
    for _ in range(8):
        for mode, go in goes.items():
            t0 = time.perf_counter()
            go()
            best[mode] = min(best[mode], time.perf_counter() - t0)
    us = {mode: b / t_rounds * 1e6 for mode, b in best.items()}
    for mode in goes:
        row(f"fed_fault_overhead_{mode}", us[mode],
            f"us/round, N={n} T={t_rounds} deployable compiled")
    ratio = us["faulted"] / us["clean"]
    row("fed_fault_overhead", 0,
        f"faulted/clean us-per-round ratio: {ratio:.3f}x (target < 1.10)")

    # Convergence under churn: 30% Bernoulli availability, adaptive vs
    # uniform — the paper's variance-reduction claim must survive churn.
    churn = api.FaultSpec(availability="bernoulli", availability_kwargs={"q": 0.3})
    curves = {}
    for sampler in ("kvib", "uniform_isp"):
        hist = api.run(spec_with(churn, sampler=sampler, rounds=40, seed=1))
        curves[sampler] = [float(x) for x in hist.train_loss]
        row(f"fed_fault_churn_{sampler}", 0,
            f"final loss @30% availability: {curves[sampler][-1]:.4f}")

    os.makedirs(RESULTS, exist_ok=True)
    with open(os.path.join(RESULTS, "BENCH_fed_fault_overhead.json"), "w") as f:
        json.dump(
            {
                "bench": "fed_fault_overhead",
                "entries": [{
                    "n": n, "rounds": t_rounds,
                    "clean_us_per_round": us["clean"],
                    "faulted_us_per_round": us["faulted"],
                    "churn_availability_q": 0.3,
                    "churn_loss_curves": curves,
                }],
                # regression-gate ratios: LOWER is better
                "ratios": {"faulted_over_clean_us_per_round": ratio},
            },
            f, indent=2,
        )


# ---------------------------------------------------------------------------
# Table: compressed client deltas — delta width on the zoo LM round
# ---------------------------------------------------------------------------


def bench_fed_lm_delta_width() -> None:
    """The delta-width win: int8 client deltas vs f32 on the zoo LM round.

    Three costs, one spec pair (identical except ``compression``):

    * **aggregation buffer bytes** — the HBM-resident stacked cohort buffer
      the aggregate consumes, from aval sizes (``jax.eval_shape`` over
      ``quantize_stacked``): (C, D_pad) int8 + (C, nb) f32 scales vs (C, D)
      f32.  Target: >= 3.5x smaller.
    * **us/round** — the compiled segmented scan, interleaved best-of-k (the
      quantize/dequant work must not eat the bandwidth win).
    * **checkpoint bytes** — with the buffered-async ring on, the carried
      (B, D) stale-delta buffer is quantized too, so the on-disk
      ``TrainState`` shrinks; measured from a real ``CheckpointManager``
      step directory.

    Emits ``RESULTS/BENCH_fed_lm_delta_width.json`` with lower-is-better
    int8/f32 ratios for the regression gate.
    """
    import tempfile

    from repro import api
    from repro.checkpoint import CheckpointManager
    from repro.fed.round import build_fed_scan_segment
    from repro.fed.state import run_segmented
    from repro.kernels.fused_weighted_agg import quantize_stacked
    from repro.models import transformer

    rounds, n, c = 6, 24, 6
    ring_fault = api.FaultSpec(
        async_buffer=4, staleness_discount=0.5,
        latency="exponential", latency_kwargs={"scale": 2.0},
    )

    def spec_with(compression):
        return api.ExperimentSpec(
            task=api.TaskSpec(
                kind="zoo", name="smollm-360m", reduced=True,
                kwargs=dict(
                    n_layers=2, d_model=128, d_ff=256, vocab=256,
                    round_mode="client_parallel",
                ),
                dataset="synthetic_tokens",
                dataset_kwargs=dict(
                    n_clients=n, seq_len=32, vocab=256, total_seqs=40 * n,
                    seed=0,
                ),
            ),
            sampler=api.SamplerSpec(name="kvib", kwargs=dict(horizon=rounds)),
            federation=api.FederationSpec(
                rounds=rounds, budget=c, cohort=c, local_steps=1, batch_size=8,
            ),
            execution=api.ExecutionSpec(seed=0, ckpt_every=rounds // 2),
            fault=ring_fault,
            compression=compression,
        )

    modes = {
        "f32": api.CompressionSpec(),
        "int8": api.CompressionSpec(delta_dtype="int8"),
    }
    entry: dict = {"n": n, "cohort": c, "rounds": rounds}
    goes = {}
    for mode, comp in modes.items():
        spec = spec_with(comp)
        built = api.build(spec)
        key = jax.random.PRNGKey(spec.execution.seed)
        params = transformer.init_params(built.arch_config, key)
        d_dim = sum(int(x.size) for x in jax.tree_util.tree_leaves(params))
        # aggregation buffer bytes, straight from aval sizes
        if not comp.enabled:
            agg_bytes = c * d_dim * 4
        else:
            q_aval, s_aval = jax.eval_shape(
                lambda f: quantize_stacked(
                    f, dtype=comp.delta_dtype, scale_block=comp.scale_block
                ),
                jax.ShapeDtypeStruct((c, d_dim), jnp.float32),
            )
            agg_bytes = (
                q_aval.size * q_aval.dtype.itemsize
                + s_aval.size * s_aval.dtype.itemsize
            )
        entry[f"{mode}_agg_buffer_bytes"] = int(agg_bytes)
        # donate=False: the interleaved re-runs reuse the round-0 state
        segment, make_state = build_fed_scan_segment(
            built.arch_config, built.round_spec, built.sampler, built.dataset,
            donate=False,
        )
        state0 = make_state(params, built.sampler.init(), key, rounds)

        def go(segment=segment, state0=state0):
            jax.block_until_ready(run_segmented(state0, rounds, segment))

        goes[mode] = go
        go()  # compile up front
        # checkpoint bytes: a real manager step dir, async ring included
        with tempfile.TemporaryDirectory() as tmp:
            mgr = CheckpointManager(os.path.join(tmp, "ck"), keep_last=1)
            run_segmented(
                state0, rounds, segment,
                ckpt_every=spec.execution.ckpt_every, manager=mgr,
            )
            ck_bytes = sum(
                os.path.getsize(os.path.join(root, f))
                for root, _, files in os.walk(tmp)
                for f in files
            )
        entry[f"{mode}_ckpt_bytes"] = int(ck_bytes)
    best = {mode: float("inf") for mode in goes}
    for _ in range(6):
        for mode, go in goes.items():
            t0 = time.perf_counter()
            go()
            best[mode] = min(best[mode], time.perf_counter() - t0)
    for mode in goes:
        entry[f"{mode}_us_per_round"] = best[mode] / rounds * 1e6
        row(
            f"fed_lm_delta_width_{mode}", entry[f"{mode}_us_per_round"],
            f"us/round, agg buffer {entry[f'{mode}_agg_buffer_bytes']} B, "
            f"ckpt {entry[f'{mode}_ckpt_bytes']} B",
        )
    ratios = {
        "int8_over_f32_agg_buffer_bytes": entry["int8_agg_buffer_bytes"]
        / entry["f32_agg_buffer_bytes"],
        "int8_over_f32_ckpt_bytes": entry["int8_ckpt_bytes"]
        / entry["f32_ckpt_bytes"],
        "int8_over_f32_us_per_round": entry["int8_us_per_round"]
        / entry["f32_us_per_round"],
    }
    row(
        "fed_lm_delta_width", 0,
        f"agg bytes {1 / ratios['int8_over_f32_agg_buffer_bytes']:.2f}x smaller "
        f"(target >= 3.5x), ckpt {1 / ratios['int8_over_f32_ckpt_bytes']:.2f}x, "
        f"time ratio {ratios['int8_over_f32_us_per_round']:.3f}x",
    )
    os.makedirs(RESULTS, exist_ok=True)
    with open(os.path.join(RESULTS, "BENCH_fed_lm_delta_width.json"), "w") as f:
        json.dump(
            {
                "bench": "fed_lm_delta_width",
                "entries": [entry],
                # regression-gate ratios: LOWER is better
                "ratios": ratios,
            },
            f, indent=2,
        )


# ---------------------------------------------------------------------------
# Paper figures from experiment artifacts
# ---------------------------------------------------------------------------


def table_synthetic() -> None:
    path = os.path.join(RESULTS, "synthetic.json")
    if not os.path.exists(path):
        row("fig2_synthetic", 0, "MISSING - run examples/synthetic_regret.py")
        return
    data = json.load(open(path))
    t = data["config"]["rounds"]
    for name, runs in data["runs"].items():
        if name == "kvib_gamma":
            continue
        reg = np.mean([r["regret"][-1] / t for r in runs])
        err = np.mean([np.mean(r["sq_error"][t // 3 :]) for r in runs])
        row(f"fig2_regretT_{name}", 0, f"dynamic regret/T={reg:.5f} est.var={err:.6f}")


def table_budget() -> None:
    path = os.path.join(RESULTS, "budget.json")
    if not os.path.exists(path):
        row("fig3b_budget", 0, "MISSING - run examples/budget_sweep.py")
        return
    data = json.load(open(path))
    for name, by_k in data["regret_per_round"].items():
        ks = sorted(by_k, key=int)
        speedup = by_k[ks[0]] / max(by_k[ks[-1]], 1e-9)
        row(
            f"fig3b_{name}",
            0,
            f"regret/T K={ks[0]}:{by_k[ks[0]]:.4f} -> K={ks[-1]}:{by_k[ks[-1]]:.4f} ({speedup:.0f}x)",
        )


def table_femnist() -> None:
    path = os.path.join(RESULTS, "femnist.json")
    if not os.path.exists(path):
        row("fig4_femnist", 0, "MISSING - run examples/femnist_style.py")
        return
    data = json.load(open(path))
    for level, lv in data["levels"].items():
        for name, run in lv["samplers"].items():
            tta = run.get("rounds_to_target")
            row(
                f"fig4_{level}_{name}",
                0,
                f"acc={run['acc'][-1]:.3f} t@target={tta} est.var={np.mean(run['sq_error']):.5f}",
            )


def table_fed_lm() -> None:
    path = os.path.join(RESULTS, "fed_lm.json")
    if not os.path.exists(path):
        row("fig5_fed_lm", 0, "MISSING - run examples/fed_lm.py")
        return
    data = json.load(open(path))
    for name, run in data["runs"].items():
        row(f"fig5_lm_{name}", 0, f"loss {run['loss'][0]:.3f}->{run['loss'][-1]:.3f}")


# ---------------------------------------------------------------------------
# Table: train-to-serve — decode throughput under checkpoint hot-swaps
# ---------------------------------------------------------------------------


def bench_fed_serve_swap() -> None:
    """Decode tokens/sec under continuous weight swaps vs a static server,
    and the paged prefill/decode split vs the old whole-sequence recompute.

    Three servers on the reduced zoo config, identical traffic:

    * **static** — ``repro.serve.ServeEngine``, one prefill + T paged decode
      steps, weights never change.
    * **swap** — the same engine geometry, but ``swap_params`` installs an
      alternating candidate every ``swap_every`` decode steps (the serving
      loop's steady state under a fast trainer; candidates pre-restored, as
      the watcher restores off the decode path).  The compile-once contract
      makes this nearly free: target swap/static us-per-token <= 1.11
      (i.e. >= 0.9x the static token rate), with the decode jit cache at
      exactly ONE entry across all swaps.
    * **recompute** — the pre-serve launcher's whole-sequence path: a full
      ``transformer.forward`` over the (B, max_seq) buffer per generated
      token (compiled once; O(S) redundant work per token vs the O(1)
      decode step).

    Emits ``RESULTS/BENCH_fed_serve_swap.json`` with both lower-is-better
    ratios for the regression gate.
    """
    from repro.configs import get_config
    from repro.models import transformer
    from repro.serve import ServeEngine

    b, plen, page, t_steps, swap_every = 4, 16, 16, 96, 16
    max_seq = plen + t_steps
    cfg = get_config("smollm-360m").reduced(
        n_layers=4, d_model=192, d_ff=512, vocab=256
    )
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    params = transformer.init_params(cfg, k1)
    variant = transformer.init_params(cfg, k2)
    prompts = jax.random.randint(jax.random.PRNGKey(3), (b, plen), 0, cfg.vocab)

    engine = ServeEngine(cfg, params, batch=b, max_seq=max_seq, page_size=page)

    def run_decode(swapping: bool) -> float:
        """One full batch: prefill + t_steps decode; us per generated token."""
        if swapping:
            # Start each swapping rep from the SAME incumbent so reps are
            # identical programs (the swap itself is the measured cost).
            engine.swap_params(params)
        engine.start(prompts)
        engine.decode_tokens = 0
        engine.decode_seconds = 0.0
        done = 0
        while done < t_steps:
            done += engine.step(swap_every)
            if swapping:
                engine.swap_params(variant if done % (2 * swap_every) else params)
        return engine.decode_seconds / engine.decode_tokens * 1e6

    # The recompute server: full forward over the padded buffer per token.
    fwd = jax.jit(lambda p, toks: transformer.forward(p, cfg, toks)[0])

    def run_recompute() -> float:
        buf = jnp.zeros((b, max_seq), jnp.int32).at[:, :plen].set(prompts)
        fwd(params, buf)  # warm (compile outside the timed window)
        t0 = time.perf_counter()
        for i in range(plen, plen + t_steps):
            logits = fwd(params, buf)
            buf = buf.at[:, i].set(jnp.argmax(logits[:, i - 1], -1).astype(jnp.int32))
        jax.block_until_ready(buf)
        return (time.perf_counter() - t0) / (t_steps * b) * 1e6

    # Warm both engine entry points, then interleaved best-of-k (the ratio
    # is the payload; interleaving keeps host-load noise symmetric).
    run_decode(False)
    run_decode(True)
    best = {"static": float("inf"), "swap": float("inf"), "recompute": float("inf")}
    for _ in range(6):
        best["static"] = min(best["static"], run_decode(False))
        best["swap"] = min(best["swap"], run_decode(True))
        best["recompute"] = min(best["recompute"], run_recompute())

    cache_entries = engine.decode_cache_entries()
    assert cache_entries == 1, (
        f"decode jit cache grew to {cache_entries} under swaps (compile-once)"
    )
    assert engine.swaps >= 2, engine.swaps

    row("fed_serve_swap_static", best["static"],
        f"us/token, B={b} paged decode (page={page}), static weights")
    row("fed_serve_swap_swapping", best["swap"],
        f"us/token with a hot swap every {swap_every} steps "
        f"({engine.swaps} swaps total, {cache_entries} decode compile)")
    row("fed_serve_swap_recompute", best["recompute"],
        f"us/token, whole-sequence recompute server (S={max_seq})")
    swap_ratio = best["swap"] / best["static"]
    paged_ratio = best["static"] / best["recompute"]
    row("fed_serve_swap", 0,
        f"swap/static us-per-token ratio: {swap_ratio:.3f}x (target <= 1.11, "
        f"i.e. >= 0.9x static tokens/sec); paged/recompute: {paged_ratio:.3f}x")

    os.makedirs(RESULTS, exist_ok=True)
    with open(os.path.join(RESULTS, "BENCH_fed_serve_swap.json"), "w") as f:
        json.dump(
            {
                "bench": "fed_serve_swap",
                "entries": [{
                    "arch": cfg.name, "batch": b, "prompt_len": plen,
                    "page_size": page, "decode_steps": t_steps,
                    "swap_every": swap_every, "n_swaps": engine.swaps,
                    "decode_jit_cache_entries": cache_entries,
                    "static_us_per_token": best["static"],
                    "swap_us_per_token": best["swap"],
                    "recompute_us_per_token": best["recompute"],
                }],
                # regression-gate ratios: LOWER is better
                "ratios": {
                    "swap_over_static_us_per_token": swap_ratio,
                    "paged_over_recompute_us_per_token": paged_ratio,
                },
            },
            f, indent=2,
        )


def table_roofline() -> None:
    from repro.analysis.roofline import HW

    ddir = os.path.join(RESULTS, "dryrun")
    if not os.path.isdir(ddir):
        row("roofline", 0, "MISSING - run python -m repro.launch.dryrun --all")
        return
    hw = HW()
    for f in sorted(os.listdir(ddir)):
        if not f.endswith(".json"):
            continue
        r = json.load(open(os.path.join(ddir, f)))
        if r.get("status") != "ok" or r.get("multi_pod"):
            continue
        comp = r["flops"] / hw.peak_flops
        mem = r["bytes_accessed"] / hw.hbm_bw
        coll = r["collective_bytes"] / hw.ici_bw
        dom = max((comp, "compute"), (mem, "memory"), (coll, "collective"))[1]
        row(
            f"roofline_{r['arch']}_{r['shape']}",
            0,
            f"compute={comp:.3f}s memory={mem:.3f}s collective={coll:.3f}s dominant={dom}",
        )


BENCHES = {
    "solver": bench_solver_scaling,
    "fused_agg": bench_fused_aggregation,
    "round_step": bench_round_step,
    "fed_round_scan": bench_fed_round_scan,
    "fed_scan_segmented": bench_fed_scan_segmented,
    "fed_round_cohort": bench_fed_round_cohort,
    "fed_cohort_width": bench_fed_cohort_width,
    "fed_sampler_scale": bench_fed_sampler_scale,
    "fed_fault_overhead": bench_fed_fault_overhead,
    "fed_lm_delta_width": bench_fed_lm_delta_width,
    "fed_serve_swap": bench_fed_serve_swap,
    "fig2": table_synthetic,
    "fig3b": table_budget,
    "fig4": table_femnist,
    "fig5": table_fed_lm,
    "roofline": table_roofline,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--filter", default="")
    args = ap.parse_args()
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    print("name,us_per_call,derived")
    for name, fn in BENCHES.items():
        if args.filter and args.filter not in name:
            continue
        fn()


if __name__ == "__main__":
    main()
