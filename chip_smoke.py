#!/usr/bin/env python3
"""Bring-up smoke: the federated trainer and the serve engine on a TPU.

    python chip_smoke.py               # one chip: every phase below
    python chip_smoke.py --four-chips  # four chips: the mesh phases only

One process drives every phase, so it alone holds the chip.  Nothing falls
back to the CPU: without a TPU the script exits nonzero before any phase.
Weights and data are random, made from fixed seeds.

One chip, smollm-360m at its published widths (960/15H/5KV/2560/49152,
32 layers, bf16):

* ``kernels`` — the four main-path Pallas kernels, each compiled for the
  chip (its HLO must hold ``tpu_custom_call``) and compared with its jnp
  reference at real width.
* ``train`` — ``repro.api.run`` on the compiled segmented path with a
  ``CheckpointManager``: 4 rounds, checkpoints every 2, N=32 clients,
  seq 512, local batch 4, 2 local steps, the kvib sampler.
* ``train_int8`` — 2 rounds of the same setup with int8 client deltas.
* ``serve`` — ``ServeEngine`` restores the step-2 checkpoint, prefills,
  decodes 32 tokens, swaps in step 4 and decodes 32 more on one compiled
  decode program; its paged math is checked against the full forward.
* ``sampler`` — a deployable logreg run at N=10^6, K=64 with the sampler's
  client axis on the mesh, so the cohort-width aggregation kernel and the
  10^6-client water-filling solve both run; the kernel solve is checked
  against the plain solve.

Four chips (``--four-chips``): the sharded ISP solve at N=10^6 over
``data=4`` against the one-device solve, and one smollm-360m
``client_parallel`` round with its cohort over ``data=4`` against the same
round on one chip.

Device kind, cohort sizes, program bytes and phase wall times go on earlier
lines; the last line is the JSON result.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import shutil
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
# Checkpoints (0.7 GB each) stay in the checkout, out of git and out of
# anything copied back from a chip machine.
OUT_DIR = os.path.join(ROOT, ".smoke_out")


@dataclasses.dataclass(frozen=True)
class Setup:
    """Sizes of every phase; the defaults are what the script runs."""

    arch: str = "smollm-360m"
    n_clients: int = 32
    seq: int = 512
    local_batch: int = 4
    local_steps: int = 2
    rounds: int = 4
    ckpt_every: int = 2
    # Cohort slots C, sized from memory_analysis() of the round program
    # compiled for a described v5e (15.75 GiB of HBM, donation on):
    # C=4 needs 11.1 GiB, C=5 13.8 GiB, C=6 does not fit.  The int8 round
    # also holds the (C, D) f32 flat deltas and their quantization passes:
    # C=2 needs 10.7 GiB, C=3 14.7 GiB, C=4 does not fit.  The smaller
    # value of each pair leaves room for what else the process keeps on
    # the device (serve params, the dataset, the restored checkpoints).
    cohort: int = 4
    cohort_int8: int = 2
    int8_rounds: int = 2
    serve_batch: int = 4
    prompt_len: int = 128
    decode_tokens: int = 32
    page_size: int = 16
    sampler_clients: int = 1_000_000
    sampler_budget: int = 64
    sampler_samples_per_client: int = 8
    sampler_rounds: int = 3
    waterfill_shard: int = 250_000  # N=10^6 over four shards

    def zoo_task(self):
        """The model at its published widths (never ``reduced``)."""
        from repro import api

        return api.TaskSpec(
            kind="zoo",
            name=self.arch,
            dataset="synthetic_tokens",
            dataset_kwargs={"n_clients": self.n_clients, "seq_len": self.seq},
        )


def log(msg: str) -> None:
    print(msg, flush=True)


def check(cond, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


@jax.jit
def _deviation(got, want, rtol, atol):
    diff = jnp.abs(got.astype(jnp.float32) - want.astype(jnp.float32))
    excess = diff - (atol + rtol * jnp.abs(want.astype(jnp.float32)))
    return jnp.max(diff), jnp.max(excess)


def allclose(name, got, want, *, rtol, atol) -> None:
    """``numpy.testing.assert_allclose``'s rule, reduced on the device: the
    model-width vectors here would take tens of GB as host float64."""
    err, excess = _deviation(jnp.asarray(got), jnp.asarray(want), rtol, atol)
    log(f"  {name}: max |got - ref| = {float(err):.3e}")
    check(float(excess) <= 0.0, f"{name}: outside rtol={rtol} atol={atol}")


def has_kernel(jitted, *args) -> bool:
    return "tpu_custom_call" in jitted.lower(*args).compile().as_text()


def ran_program(segment, state, n_rounds):
    """The compiled program a segment function ran, for inspection: lowering
    it again hits JAX's caches, so nothing is compiled a second time."""
    t0 = time.perf_counter()
    compiled = segment.lower(state, n_rounds).compile()
    log(f"  round program fetched in {time.perf_counter() - t0:.1f}s (wall)")
    return compiled


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------


def param_dim(setup: Setup) -> int:
    """Flattened parameter count of the configured model."""
    from repro import api
    from repro.models import transformer

    cfg = api.build(train_spec(setup)).arch_config
    shapes = jax.eval_shape(
        lambda: transformer.init_params(cfg, jax.random.PRNGKey(0))
    )
    return sum(int(np.prod(s.shape)) for s in jax.tree_util.tree_leaves(shapes))


def phase_kernels(setup: Setup) -> None:
    from repro.kernels.fused_weighted_agg import (
        dequant_block_d,
        dequant_cohort_agg_reference,
        fused_cohort_agg_and_error,
        fused_dequant_cohort_agg,
        fused_multi_weighted_agg,
        quantize_stacked,
    )
    from repro.kernels.ref import waterfill_stats_reference
    from repro.kernels.sharded_waterfill import waterfill_level_stats

    hi = jax.lax.Precision.HIGHEST
    d_model = param_dim(setup)
    block = 2048
    d = -(-d_model // block) * block
    log(f"  smollm flattened D={d_model} (padded {d})")
    keys = jax.random.split(jax.random.PRNGKey(7), 6)

    # Aggregation kernels at the model's D (C=2, the int8 round's cohort)
    # and at the logreg cohort width of the sampler phase (C=128, D=640).
    for c, width in ((setup.cohort_int8, d), (2 * setup.sampler_budget, 640)):
        g = jax.random.normal(keys[0], (c, width), jnp.float32)
        w = jax.random.uniform(keys[1], (c,), jnp.float32, 0.1, 2.0)
        lam = jax.random.uniform(keys[2], (c,), jnp.float32, 0.0, 0.3)
        bd = min(width, block)

        multi = jax.jit(lambda g, w2: fused_multi_weighted_agg(g, w2, block_d=bd))
        w2 = jnp.stack([w, w - lam])
        check(has_kernel(multi, g, w2), "fused_multi_weighted_agg: no tpu_custom_call")
        ref = jax.jit(lambda g, w2: jnp.matmul(w2, g, precision=hi))(g, w2)
        allclose(f"fused_multi_weighted_agg C={c} D={width}", multi(g, w2), ref,
                 rtol=1e-5, atol=1e-5)

        cohort = jax.jit(
            lambda g, w, lam: fused_cohort_agg_and_error(g, w, lam, block_d=bd)
        )
        check(has_kernel(cohort, g, w, lam),
              "fused_cohort_agg_and_error: no tpu_custom_call")
        d_got, err_got = cohort(g, w, lam)
        allclose(f"fused_cohort_agg_and_error estimate C={c} D={width}",
                 d_got, ref[0], rtol=1e-5, atol=1e-5)
        allclose(f"fused_cohort_agg_and_error err_sq C={c} D={width}",
                 err_got, jnp.sum(ref[1] ** 2), rtol=1e-4, atol=0)
        del g, ref, d_got

    c = setup.cohort_int8
    bq = dequant_block_d(d_model, 128)
    d_q = -(-d_model // bq) * bq
    flat = jax.random.normal(keys[3], (c, d_q), jnp.float32)
    q, scales = jax.jit(lambda f: quantize_stacked(f, dtype="int8"))(flat)
    del flat
    w = jax.random.uniform(keys[4], (c,), jnp.float32, 0.1, 2.0)
    lam = jax.random.uniform(keys[5], (c,), jnp.float32, 0.0, 0.3)
    dq = jax.jit(fused_dequant_cohort_agg)
    check(has_kernel(dq, q, scales, w, lam), "fused_dequant_cohort_agg: no tpu_custom_call")
    got = dq(q, scales, w, lam)
    want = jax.jit(dequant_cohort_agg_reference)(q, scales, w, lam)
    for name, a, b, rtol in zip(("estimate", "err_sq", "sq_norms"), got, want,
                                (1e-5, 1e-4, 1e-4)):
        allclose(f"fused_dequant_cohort_agg int8 {name} C={c} D={d_q}", a, b,
                 rtol=rtol, atol=1e-5 if name == "estimate" else 0)
    del q, scales, got, want

    m = setup.waterfill_shard
    scores = jax.random.exponential(keys[0], (m,), jnp.float32)
    levels = jnp.exp2(jnp.linspace(-6.0, 4.0, 128, dtype=jnp.float32))
    floors = levels * jnp.float32(1e-3)
    wf = jax.jit(waterfill_level_stats)
    check(has_kernel(wf, scores, levels, floors), "waterfill_level_stats: no tpu_custom_call")
    got = wf(scores, levels, floors)
    want = jax.jit(waterfill_stats_reference)(scores, levels, floors)
    for name, a, b in zip(("n_below", "n_floor", "mid_sum"), got, want):
        allclose(f"waterfill_level_stats {name} M={m}", a, b, rtol=1e-5, atol=0)


def train_spec(setup: Setup, *, int8: bool = False):
    from repro import api

    cohort = setup.cohort_int8 if int8 else setup.cohort
    rounds = setup.int8_rounds if int8 else setup.rounds
    return api.ExperimentSpec(
        task=setup.zoo_task(),
        sampler=api.SamplerSpec(name="kvib", kwargs={"horizon": rounds}),
        federation=api.FederationSpec(
            rounds=rounds,
            budget=max(1, cohort // 2),
            cohort=cohort,
            local_steps=setup.local_steps,
            batch_size=setup.local_batch,
            local_lr=0.05,
        ),
        execution=api.ExecutionSpec(
            seed=0, ckpt_every=0 if int8 else setup.ckpt_every
        ),
        compression=api.CompressionSpec(delta_dtype="int8" if int8 else None),
    )


def run_training(spec, *, int8: bool, manager=None):
    from repro import api

    built = api.build(spec)
    hist = api.run(spec, ckpt_manager=manager, built=built)
    losses = np.asarray(hist.train_loss)
    log(f"  losses {losses.tolist()} cohort sizes {hist.cohort_size}")
    check(len(losses) == spec.federation.rounds, "missing rounds")
    check(np.all(np.isfinite(losses)), f"non-finite loss {losses}")
    compiles = hist.segment._cache_size()
    check(compiles == 1, f"segment compiled {compiles} times (compile-once contract)")
    c = spec.federation.cohort
    compiled = ran_program(
        hist.segment, api.restore_template(spec, built=built),
        spec.execution.ckpt_every or spec.federation.rounds,
    )
    ma = compiled.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    kernel = "tpu_custom_call" in compiled.as_text()
    limit = (jax.devices()[0].memory_stats() or {}).get("bytes_limit", 0)
    log(f"  cohort C={c} round program bytes={total} (device limit {limit})")
    check(not limit or total < limit, f"C={c} round program does not fit")
    check(kernel == int8, f"int8 round must (and only it may) hold the "
          f"dequant kernel; tpu_custom_call={kernel}")


def phase_train(setup: Setup) -> None:
    from repro.checkpoint import CheckpointManager, config_fingerprint

    spec = train_spec(setup)
    shutil.rmtree(OUT_DIR, ignore_errors=True)
    manager = CheckpointManager(
        os.path.join(OUT_DIR, "train_ckpts"),
        fingerprint=config_fingerprint(spec.to_dict()),
    )
    run_training(spec, int8=False, manager=manager)
    steps = manager.read_manifest()["steps"]
    check(steps == [setup.ckpt_every, setup.rounds], f"checkpoint steps {steps}")


def phase_train_int8(setup: Setup) -> None:
    run_training(train_spec(setup, int8=True), int8=True)


def phase_serve(setup: Setup) -> None:
    from repro import api
    from repro.checkpoint import CheckpointManager, config_fingerprint
    from repro.models import transformer
    from repro.serve import ServeEngine

    spec = train_spec(setup)
    built = api.build(spec)
    cfg = built.arch_config
    manager = CheckpointManager(
        os.path.join(OUT_DIR, "train_ckpts"),
        fingerprint=config_fingerprint(spec.to_dict()),
    )
    template = api.restore_template(spec, built=built)
    params = {
        step: manager.restore(template, step).params
        for step in (setup.ckpt_every, setup.rounds)
    }
    del template
    max_seq = setup.prompt_len + 2 * setup.decode_tokens + setup.page_size
    max_seq = -(-max_seq // setup.page_size) * setup.page_size
    engine = ServeEngine(
        cfg, params[setup.ckpt_every], batch=setup.serve_batch,
        max_seq=max_seq, page_size=setup.page_size, temperature=0.0,
    )
    prompts = jax.random.randint(
        jax.random.PRNGKey(11), (setup.serve_batch, setup.prompt_len), 0, cfg.vocab
    )
    engine.start(prompts)
    check(engine.step(setup.decode_tokens) == setup.decode_tokens, "short decode")
    engine.swap_params(params[setup.rounds])
    check(engine.step(setup.decode_tokens) == setup.decode_tokens, "short decode")
    out = np.asarray(engine.generated())
    log(f"  generated {out.shape} tokens; swaps={engine.swaps}")
    check(out.shape == (setup.serve_batch, 2 * setup.decode_tokens + 1), "shape")
    check(np.all((out >= 0) & (out < cfg.vocab)), "token out of vocab")
    check(engine.decode_cache_entries() == 1,
          f"decode compiled {engine.decode_cache_entries()} times across a swap")
    check(engine.prefill_cache_entries() == 1, "prefill recompiled")

    # The paged serving math against the training forward on the served
    # step-4 weights (teacher forcing on the prompt).
    p4 = params[setup.rounds]
    s, extra = setup.prompt_len - 4, 4
    full = jax.jit(lambda p, t: transformer.forward(p, cfg, t)[0])(p4, prompts)
    pre = jax.jit(lambda p, t: transformer.prefill(
        p, cfg, t, max_seq=max_seq, page_size=setup.page_size))
    dec = jax.jit(lambda p, t, c, i: transformer.decode_step(p, cfg, t, c, i))
    logits, caches = pre(p4, prompts[:, :s])
    got = [logits[:, 0]]
    for i in range(extra - 1):
        logits, caches = dec(p4, prompts[:, s + i:s + i + 1], caches,
                             jnp.asarray(s + i, jnp.int32))
        got.append(logits[:, 0])
    got = np.asarray(jnp.stack(got, 1), np.float32)
    want = np.asarray(full[:, s - 1:s - 1 + extra], np.float32)
    rel = float(np.linalg.norm(got - want) / np.linalg.norm(want))
    log(f"  paged prefill/decode vs forward logits: relative error {rel:.3e}")
    # bf16 through 32 layers: the two orders of the same sums differ by
    # about 1e-2 (7e-3 at 8 layers); a wrong cache position or page differs
    # by order 1.
    check(rel < 5e-2, f"paged serving logits drift from the forward: {rel}")


def phase_sampler(setup: Setup) -> None:
    from repro import api
    from repro.core.solver import isp_probabilities
    from repro.launch.mesh import ShardSpec

    n, k = setup.sampler_clients, setup.sampler_budget
    spec = api.ExperimentSpec(
        task=api.TaskSpec(
            name="logreg",
            dataset="synthetic_classification",
            dataset_kwargs=dict(
                n_clients=n, total=setup.sampler_samples_per_client * n,
                power=0.0, seed=0,
            ),
        ),
        sampler=api.SamplerSpec(name="kvib", kwargs={"horizon": setup.sampler_rounds}),
        federation=api.FederationSpec(
            rounds=setup.sampler_rounds, budget=k, local_steps=1, batch_size=8
        ),
        execution=api.ExecutionSpec(oracle_metrics=False, sampler_axis="data"),
    )
    t0 = time.perf_counter()
    built = api.build(spec)
    log(f"  dataset N={n} built in {time.perf_counter() - t0:.1f}s (set-up)")
    hist = api.run(spec, built=built)
    losses = np.asarray(hist.train_loss)
    log(f"  losses {losses.tolist()} cohort sizes {hist.cohort_size}")
    check(np.all(np.isfinite(losses)), f"non-finite loss {losses}")
    check(all(0 < c <= 2 * k for c in hist.cohort_size), "empty cohort")
    compiles = hist.segment._cache_size()
    check(compiles == 1, f"segment compiled {compiles} times (compile-once contract)")
    hlo = ran_program(
        hist.segment, api.restore_template(spec, built=built), setup.sampler_rounds
    ).as_text()
    n_kernels = hlo.count("tpu_custom_call")
    log(f"  round program holds {n_kernels} tpu_custom_call sites")
    check(n_kernels >= 2, "cohort aggregation and water-filling kernels missing")

    scores = jax.random.exponential(jax.random.PRNGKey(5), (n,), jnp.float32)
    p_kernel = isp_probabilities(scores, k, shard=ShardSpec())
    p_plain = isp_probabilities(scores, k)
    allclose(f"sharded (kernel) vs plain ISP solve N={n}", p_kernel, p_plain,
             rtol=0, atol=1e-6)


def phase_four_chips(setup: Setup) -> None:
    from repro import api
    from repro.core.solver import isp_probabilities
    from repro.fed.round import build_fed_scan_segment
    from repro.launch.mesh import ShardSpec, make_host_mesh, make_mesh

    mesh4 = make_host_mesh()
    log(f"  host mesh {dict(mesh4.shape)}")
    check(dict(mesh4.shape) == {"data": 4, "model": 1}, "four chips must give data=4")

    n, k = setup.sampler_clients, setup.sampler_budget
    scores = jax.random.exponential(jax.random.PRNGKey(5), (n,), jnp.float32)
    p_plain = np.asarray(isp_probabilities(scores, k))
    p_sharded = isp_probabilities(scores, k, shard=ShardSpec.from_mesh(mesh4))
    allclose(f"ISP solve N={n} over data=4 vs one device", p_sharded, p_plain,
             rtol=0, atol=1e-6)

    # One client_parallel round, cohort C=4 (one client per chip) against
    # the same round on one chip.  A budget of 2C fills every slot, so each
    # chip trains a client and the aggregate needs all four.
    spec = train_spec(dataclasses.replace(setup, rounds=1, ckpt_every=1))
    c = spec.federation.cohort
    spec = dataclasses.replace(
        spec, federation=dataclasses.replace(spec.federation, budget=2 * c)
    )
    built = api.build(spec)
    mesh1 = make_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])
    results = {}
    for name, mesh in (("data=4", mesh4), ("one chip", mesh1)):
        segment, _ = build_fed_scan_segment(
            built.arch_config, built.round_spec, built.sampler, built.dataset,
            mesh=mesh, donate=False,
        )
        state = api.restore_template(spec, built=built)
        t0 = time.perf_counter()
        out = jax.block_until_ready(segment(state, 1))
        log(f"  {name}: round in {time.perf_counter() - t0:.1f}s (wall, incl. compile)")
        check(int(out.metrics["cohort_size"][0]) == c, "a cohort slot is empty")
        if mesh is mesh4:
            hlo = ran_program(segment, state, 1).as_text()
            check("all-reduce" in hlo, "cohort aggregation never crosses chips")
        # The aggregated update the round applied, in f32 on the host.
        update = np.concatenate([
            np.asarray(a, np.float32).ravel() - np.asarray(b, np.float32).ravel()
            for a, b in zip(jax.tree_util.tree_leaves(out.params),
                            jax.tree_util.tree_leaves(state.params))
        ])
        results[name] = (float(out.metrics["loss"][0]), update)
        del state, out, segment
        gc.collect()
    (l4, u4), (l1, u1) = results["data=4"], results["one chip"]
    allclose("round loss data=4 vs one chip", l4, l1, rtol=1e-3, atol=0)
    rel = float(np.linalg.norm(u4 - u1) / np.linalg.norm(u1))
    log(f"  update data=4 vs one chip: relative error {rel:.3e} "
        f"(|update| {float(np.linalg.norm(u1)):.3e})")
    # Most bf16 parameters move by under half an ulp in one round, so the
    # update is sparse and coarse.  With one of the four slots zeroed the
    # same comparison reads 0.26 (2 layers at these widths on 4 virtual
    # CPU devices, where the sound reading is exactly 0).
    check(rel < 0.1, f"aggregated update differs across chips: {rel}")


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--four-chips", action="store_true",
        help="run only the four-chip mesh phases (needs four TPU chips)",
    )
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found (JAX platform {dev.platform!r}); "
                 "this smoke runs on the chip only")
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        sys.exit(f"chip_smoke: needs {want} TPU chips, found {len(devices)}")

    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.compile_cache import use_compile_cache

    log(f"device kind={dev.device_kind} count={len(devices)} "
        f"compile cache={use_compile_cache()}")
    setup = Setup()
    phases = (
        [("four_chips", phase_four_chips)] if args.four_chips else [
            ("kernels", phase_kernels),
            ("train", phase_train),
            ("train_int8", phase_train_int8),
            ("serve", phase_serve),
            ("sampler", phase_sampler),
        ]
    )
    for name, fn in phases:
        log(f"phase {name}")
        t0 = time.perf_counter()
        fn(setup)
        log(f"phase {name} ok in {time.perf_counter() - t0:.1f}s (wall)")
        gc.collect()
    print(json.dumps({
        "ok": True,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(devices)},
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
