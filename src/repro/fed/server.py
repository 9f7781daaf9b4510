"""Federated server loop (Algorithm 1) — simulation-scale driver.

The canonical way to describe and launch a run is the declarative
``repro.api.ExperimentSpec`` (``repro.api.run(spec)`` dispatches here for
simulation tasks and builds the exact ``(task, dataset, sampler, FedConfig)``
tuple ``run_federated`` takes — bitwise-identical by construction, pinned by
tests/test_api_spec.py).  ``run_federated`` remains the stable programmatic
entry point underneath.

Two execution modes share ONE round body (``_build_round_body``):

* ``compiled=True`` (default): the training run — all-clients local update,
  sampler probabilities/sample/update, unbiased aggregation, server optimizer
  apply, and metric accumulation (loss, estimator squared error, cohort size,
  per-round online costs ``l_t(p^t)`` / ``min_p l_t(p)``) — executes as a
  host-driven loop over jitted ``lax.scan`` *segments* of
  ``FedConfig.ckpt_every`` rounds (``ckpt_every=0``: one segment, the
  monolithic scan) with the carry round-tripping through the canonical
  ``repro.fed.state.TrainState`` pytree.  Segmentation is a pure reshaping of
  the horizon — results are bitwise identical for ANY ``ckpt_every``
  (tests/test_segmented_scan.py) — but each boundary is an escape hatch where
  a ``repro.checkpoint.CheckpointManager`` can publish the full state, so
  long horizons survive preemption with the sampler's learned probabilities
  intact.  Metrics live in on-device (T,)-preallocated buffers stitched
  segment by segment and the ``History`` is materialized once at the end:
  zero host round-trips per round instead of the reference loop's 5+.
* ``compiled=False``: the same body is jitted and dispatched one round at a
  time from Python with per-round host syncs — the debuggable reference loop
  (prints, breakpoints, and per-round inspection work).

Because both modes run the identical traced computation, they produce
bit-identical parameters and metrics (see tests/test_scan_server.py).

Two metric fidelities:

* ``oracle_metrics=True``: every round computes *all* clients' local updates
  (vmapped) so the paper's diagnostics — dynamic regret (eq. 8), estimator
  variance (eq. 2), sampling quality — are exact.  This is how the paper's
  figures are generated (the oracle is a property of the simulation, not of
  the deployed server).
* ``oracle_metrics=False`` (deployable mode): the round trains ONLY a static
  C-slot cohort (``FedConfig.cohort``) selected from the ISP draw inside the
  traced body via ``fed.cohort.select_cohort`` — local-update compute is
  O(C) per round instead of O(N), which is the whole point of expected-K
  client sampling.  Overflow (``|S| > C``) drops to a uniform size-C subset
  with weights rescaled by ``|S|/C`` so the estimate stays unbiased.
  Aggregation is C-width by default (``estimator.aggregate_and_error_cohort``
  — O(C*D), no (N, D) buffer exists anywhere in the round body), which
  matches the oracle computation to float tolerance; setting
  ``FedConfig.exact_oracle_equiv=True`` restores the (N, D) scatter path,
  bit-identical to the full-mask computation whenever ``|S| <= C``
  (tests/test_scan_server.py; fed/cohort.py "Aggregation width").
  Diagnostics requiring full feedback are skipped; ``train_loss`` is the
  importance-weighted cohort estimate of the full weighted loss (unbiased,
  but noisier than the oracle's exact value), ``cohort_size`` counts the
  clients actually contacted (post-drop), and ``History.cohort_dropped``
  records the per-round overflow drops.

The pod-scale distributed round lives in ``repro.fed.round`` and
``repro.launch`` — this module is the algorithmic reference loop and is what
validates the paper's claims on CPU.
"""
from __future__ import annotations

import dataclasses
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import estimator, regret, samplers, stragglers
from repro.core.regret import RegretTracker
from repro.fed import client as fed_client
from repro.fed import cohort as fed_cohort
from repro.fed.state import (
    TrainState,
    build_placement,
    init_metric_buffers,
    make_segment_fn,
    run_segmented,
)
from repro.fed.tasks import Task
from repro.optim.fedopt import FedAvgServer, ServerOptimizer

__all__ = [
    "FedConfig",
    "History",
    "build_segment_runner",
    "round_body_for_lint",
    "run_federated",
]


@dataclasses.dataclass(frozen=True)
class FedConfig:
    rounds: int = 100
    budget: int = 10
    local_steps: int = 1
    batch_size: int = 64
    local_lr: float = 0.02
    server_opt: ServerOptimizer = FedAvgServer(lr=1.0)
    seed: int = 0
    eval_every: int = 5
    eval_batches: int = 4
    oracle_metrics: bool = True
    compiled: bool = True  # False: per-round Python dispatch (debug/reference)
    # Deployable-mode (oracle_metrics=False) static cohort buffer size C;
    # None -> min(2 * budget, n_clients).  Ignored in oracle mode.
    cohort: int | None = None
    # Deployable-mode aggregation width.  False (default): aggregate directly
    # over the (C, ...) cohort deltas — O(C*D) per round, no (N, D) buffer,
    # allclose to the oracle path (the reduction order differs).  True:
    # scatter the cohort back to (N, ...) buffers and reuse the oracle
    # contraction — bitwise equal to the oracle path when |S| <= C, at O(N*D)
    # memory cost.  Ignored in oracle mode.
    exact_oracle_equiv: bool = False
    # Oracle-mode (T, N) per-round score history buffer for the regret
    # diagnostics.  Pure diagnostic weight at large T*N; turn off to drop it
    # from the on-device metrics (regret costs are still tracked).
    track_scores: bool = True
    # Explicit size guard for that (T, N) buffer: build_segment_runner raises
    # (instead of silently OOMing the device at large N) when the buffer
    # would exceed this many bytes and host offload is off.
    score_history_bytes_limit: int = 1 << 30
    # Chunked host offload for the score history: the device buffer shrinks
    # to (ckpt_every, N) — a ring the segment stitch wraps into — and every
    # segment boundary drains it to host memory, where the full (T, N)
    # history is assembled for the regret diagnostics.  Requires the
    # compiled path with ckpt_every > 0.
    score_history_host_offload: bool = False
    # Compiled-path segment length: the scan runs in jitted segments of this
    # many rounds so a CheckpointManager can publish the full TrainState at
    # every boundary.  0 = whole horizon as one segment (the monolithic
    # scan).  Bitwise-neutral: any value yields identical results.
    ckpt_every: int = 0
    # Deployment-realism fault layer: a ``repro.api.FaultSpec`` (duck-typed —
    # anything with its fields works) or None.  None (default) builds the
    # exact pre-fault round body, so existing runs stay bitwise.  When set,
    # the round body threads the availability process / deadline-straggler
    # dropout / buffered-async aggregation from ``repro.core.stragglers``
    # through the traced round, with the fault state carried in
    # ``TrainState.faults``.
    faults: object | None = None
    # Delta-width compression layer: a ``repro.api.CompressionSpec`` (duck-
    # typed) or None.  None (default) builds the exact pre-compression round
    # body.  When set, client deltas are quantized to int8/fp8 with
    # per-(slot, block) fp32 scales inside the traced round and aggregated by
    # the fused dequantize-in-VMEM kernel; with ``error_feedback`` the server
    # carries a (D,) f32 residual in ``TrainState.compression``.
    compression: object | None = None

    def cohort_slots(self, n_clients: int) -> int:
        c = 2 * self.budget if self.cohort is None else int(self.cohort)
        return max(1, min(c, n_clients))


@dataclasses.dataclass
class History:
    rounds: list = dataclasses.field(default_factory=list)
    train_loss: list = dataclasses.field(default_factory=list)
    test_accuracy: list = dataclasses.field(default_factory=list)
    estimator_sq_error: list = dataclasses.field(default_factory=list)
    cohort_size: list = dataclasses.field(default_factory=list)
    cohort_dropped: list = dataclasses.field(default_factory=list)  # deployable
    # Per-round count of clients that missed the FaultSpec deadline (faulted
    # runs with deadline set; empty otherwise).
    deadline_dropped: list = dataclasses.field(default_factory=list)
    regret: RegretTracker | None = None
    wall_time_s: float = 0.0
    final_params: object = None  # trained parameter pytree (trajectory probe)
    # The compiled path's segment function, for probes of the program that
    # ran: ``segment._cache_size()``, ``segment.lower(state, n).compile()``.
    segment: object = None

    def summary(self) -> dict:
        out = {
            "final_loss": self.train_loss[-1] if self.train_loss else None,
            "final_acc": self.test_accuracy[-1] if self.test_accuracy else None,
            "mean_sq_error": float(np.mean(self.estimator_sq_error))
            if self.estimator_sq_error
            else None,
            "mean_cohort": float(np.mean(self.cohort_size)) if self.cohort_size else None,
            "wall_time_s": self.wall_time_s,
        }
        if self.regret is not None and self.regret.costs:
            out["final_dynamic_regret_per_round"] = float(
                self.regret.dynamic_regret()[-1] / len(self.regret.costs)
            )
        return out


def _build_client_step(task: Task, cfg: FedConfig):
    """One client's local update: (params, rows, row index, (R, 2) batch keys)
    -> (delta, loss, update norm), ``rows`` a ``FederatedDataset`` holding the
    client at that row.  Shared by the oracle and deployable paths so
    their per-client numerics cannot drift apart — cross-mode bit-identity
    (tests/test_scan_server.py) depends on this being a single definition."""

    def one_client(params, rows, i, ks):
        def get_batch(k):
            return rows.client_batch(i, k, cfg.batch_size)

        with jax.named_scope("round.gather"):
            batches = jax.vmap(get_batch)(ks)
        with jax.named_scope("round.local_train"):
            delta, loss = fed_client.local_update(
                params, task.loss, batches, cfg.local_lr
            )
            return delta, loss, fed_client.update_norm(delta)

    return one_client


def _split_batch_keys(key, n: int, local_steps: int):
    """(N, R, 2) per-client batch keys — the one key stream both paths index."""
    return jax.random.split(key, n * local_steps).reshape(n, local_steps, 2)


def _build_all_clients(task: Task, dataset, cfg: FedConfig, lam):
    """All-clients local-update step (oracle mode): vmapped over clients."""

    n = dataset.n_clients
    one_client = _build_client_step(task, cfg)

    def all_clients(params, key):
        with jax.named_scope("round.gather"):
            keys = _split_batch_keys(key, n, cfg.local_steps)
        deltas, losses, norms = jax.vmap(
            lambda i, ks: one_client(params, dataset, i, ks)
        )(jnp.arange(n), keys)
        feedback = lam * norms  # pi_t(i) = lambda_i ||g_i||
        return deltas, losses, feedback

    return all_clients


def _build_cohort_clients(task: Task, dataset, cfg: FedConfig):
    """Cohort-only local-update step (deployable mode): vmapped over the C
    selected slots.  Batch keys are split for all N clients exactly as in
    ``_build_all_clients`` and then gathered by client id, so a cohort
    client's batches — and therefore its delta/loss/norm — are bit-identical
    to what the oracle path computes for that client (key material is O(N)
    but cheap; the O(N * local-train) compute is what this path removes).
    The cohort's data rows are gathered once, outside the per-slot ``vmap``
    (``FederatedDataset.rows``): the dataset is never relayouted."""

    n = dataset.n_clients
    one_client = _build_client_step(task, cfg)

    def cohort_clients(params, key, cohort_ids):
        with jax.named_scope("round.gather"):
            keys = _split_batch_keys(key, n, cfg.local_steps)[cohort_ids]
            rows = dataset.rows(cohort_ids)
        slots = jnp.arange(cohort_ids.shape[0])
        return jax.vmap(lambda j, ks: one_client(params, rows, j, ks))(slots, keys)

    return cohort_clients


def _build_round_body(
    task: Task, dataset, sampler: samplers.Sampler, cfg: FedConfig, eval_data,
    lam=None,
):
    """One federated round as a scan body: (carry, (t, k_data, k_sample)) ->
    (carry, per-round metrics dict).  Pure and shape-static, so it runs
    identically under ``lax.scan`` and under per-round ``jit`` dispatch.

    ``lam`` (default ``dataset.lam``) is the client weight vector.  The
    compiled paths pass the dataset and ``lam`` in as jit arguments
    (``_round_data``), ``lam`` computed once outside: computed inside each
    program, its sum would round differently from one program to another.

    Oracle mode trains all N clients; deployable mode (oracle_metrics=False)
    trains only the C-slot cohort selected from the draw and aggregates at
    cohort width — O(C*D) with no (N, D) buffer — unless
    ``cfg.exact_oracle_equiv`` asks for the legacy N-width scatter, which
    reuses the oracle contraction and is bit-identical to it when
    ``|S| <= C`` (module docstring; fed/cohort.py "Aggregation width").

    ``cfg.faults`` (a ``repro.api.FaultSpec``) switches on the deployment-
    realism layer at BUILD time — carry grows a trailing fault-state element
    and the body threads ``core.stragglers``: the availability process
    intersects the draw (composed ``q * p`` correction, so the estimator
    stays unbiased), deadline stragglers are masked out after local training
    with survivor weights rescaled by ``1 / P(latency <= deadline)``, and
    buffered-async mode routes the round's aggregate through a carried
    (B, D) stale-delta ring instead of applying it immediately.  With
    ``faults=None`` the built body is the exact pre-fault program.

    ``cfg.compression`` (a ``repro.api.CompressionSpec``) likewise switches
    at BUILD time: the stacked client deltas are quantized inside the round
    (``estimator.aggregate_compressed``), sampler feedback norms come from
    the dequantized values, and with error feedback the carry grows a
    trailing ``{"resid": (D,) f32}`` element — the applied update is
    ``d_hat + resid`` and the residual absorbs the fresh quantization error
    ``d_true - d_hat`` so errors telescope across rounds.  With
    ``compression=None`` the built body is the exact pre-compression
    program."""

    if lam is None:
        lam = dataset.lam
    n = dataset.n_clients
    if cfg.oracle_metrics:
        all_clients = _build_all_clients(task, dataset, cfg, lam)
    else:
        c_slots = cfg.cohort_slots(n)
        cohort_clients = _build_cohort_clients(task, dataset, cfg)

    faults = cfg.faults
    fault_on = faults is not None
    avail_on = fault_on and faults.availability is not None
    deadline_on = fault_on and faults.deadline is not None
    async_on = fault_on and int(faults.async_buffer) > 0
    # Static build-time survival probability: the unbiasedness rescale for
    # deadline survivors (raises if the deadline is unsatisfiable).
    surv = stragglers.deadline_survival(faults) if deadline_on else 1.0

    comp = cfg.compression
    comp_on = comp is not None
    ef_on = comp_on and bool(comp.error_feedback)
    if comp_on and not cfg.oracle_metrics and cfg.exact_oracle_equiv:
        raise ValueError(
            "compression is incompatible with exact_oracle_equiv: the N-width "
            "scatter path exists to reproduce the oracle contraction bitwise, "
            "which quantization cannot; use the cohort-width aggregation "
            "(exact_oracle_equiv=False)"
        )

    def body(carry, xs):
        c_state = {}
        if ef_on:
            carry, c_state = carry[:-1], carry[-1]
        if fault_on:
            params, opt_state, s_state, f_state = carry
        else:
            params, opt_state, s_state = carry
            f_state = {}
        t, k_data, k_sample = xs

        # Solve p~ once; reuse it for the draw AND the regret diagnostics
        # (the seed loop solved twice and diagnosed off draw.marginals).
        with jax.named_scope("round.solve"):
            p_marg = sampler.probabilities(s_state)
        with jax.named_scope("round.draw"):
            draw = sampler.sample_from(p_marg, k_sample)
        if avail_on:
            # Availability intersects the draw; composing q into the draw's
            # probabilities makes the plain client_weights call below the
            # availability-corrected (1/(q p)) estimator.  Distinct fold_in
            # streams (101/102/103) keep the sampler's own key untouched.
            with jax.named_scope("round.faults"):
                avail_mask, q_t, new_chain = stragglers.availability_step(
                    faults,
                    f_state.get("chain"),
                    t,
                    jax.random.fold_in(k_sample, 101),
                    n,
                )
                avail_mask = sampler.shard_constrain(avail_mask)
                q_t = sampler.shard_constrain(q_t)
                draw = stragglers.available_draw(draw, avail_mask, q_t)
                if "chain" in f_state:
                    f_state = {**f_state, "chain": sampler.shard_constrain(new_chain)}
        with jax.named_scope("round.select"):
            weights = estimator.client_weights(
                draw, lam, sampler.procedure, sampler.budget
            )

        deadline_dropped = jnp.zeros((), jnp.int32)
        if cfg.oracle_metrics:
            deltas, losses, feedback_full = all_clients(params, k_data)
            feedback_full = sampler.shard_constrain(feedback_full)
            active = draw.mask
            if deadline_on:
                # Per-client latency; clients past the deadline report
                # nothing this round.  Survivor weights / surv keeps the
                # estimate unbiased (E[1{survive}] = surv, independent of
                # the draw).
                with jax.named_scope("round.faults"):
                    lat = stragglers.latency_draw(
                        faults, (n,), jax.random.fold_in(k_sample, 102)
                    )
                    late = jnp.logical_and(
                        draw.mask, lat > jnp.float32(faults.deadline)
                    )
                    active = jnp.logical_and(draw.mask, ~late)
                    weights = jnp.where(late, 0.0, weights * jnp.float32(1.0 / surv))
                    deadline_dropped = jnp.sum(late.astype(jnp.int32))
            feedback = feedback_full * active
            train_loss = jnp.sum(lam * losses)
            cohort_size = (
                jnp.sum(active.astype(jnp.int32)) if deadline_on else draw.size
            )
            if comp_on:
                # Compressed width: quantize the (N, ...) stacked deltas and
                # aggregate via the fused dequant kernel; the sampler's
                # feedback norms are recomputed from the dequantized values
                # (the regret signal is what the estimator actually saw), and
                # with error feedback the applied estimate is d_hat + resid.
                with jax.named_scope("round.aggregate"):
                    d_est, sq_err, norms_dq, new_resid = (
                        estimator.aggregate_compressed(
                            deltas, weights, lam, comp, c_state.get("resid")
                        )
                    )
                feedback_full = sampler.shard_constrain(lam * norms_dq)
                feedback = feedback_full * active
                if ef_on:
                    c_state = {"resid": new_resid}
            else:
                # sq_err shares the one pass over the stacked (N, ...) deltas.
                with jax.named_scope("round.aggregate"):
                    d_est, sq_err = estimator.aggregate_and_error(deltas, weights, lam)
        else:
            # Deployable: select C slots from the draw (fold_in keeps the
            # draw's key stream untouched) and train only those clients.
            with jax.named_scope("round.select"):
                sel = fed_cohort.select_cohort(
                    draw.mask, weights, c_slots, jax.random.fold_in(k_sample, 1)
                )
            overflow_dropped = sel.n_dropped
            deltas_c, losses_c, norms_c = cohort_clients(params, k_data, sel.ids)
            if deadline_on:
                # Deadline dropout AFTER local training is scheduled: the C
                # slots' compute already ran; late slots are demoted to inert
                # padding (weight/validity/feedback zeroed) and survivors are
                # rescaled by 1/surv — the O(C*D) aggregation below is
                # untouched (fed/cohort.py mask_selection).
                with jax.named_scope("round.faults"):
                    lat_c = stragglers.latency_draw(
                        faults, (c_slots,), jax.random.fold_in(k_sample, 102)
                    )
                    late_c = jnp.logical_and(
                        sel.valid, lat_c > jnp.float32(faults.deadline)
                    )
                    sel = fed_cohort.mask_selection(sel, ~late_c, 1.0 / surv)
                    deadline_dropped = jnp.sum(late_c.astype(jnp.int32))
            # Sampler feedback is an (N,)-vector scatter of a (C,) vector —
            # the sampler state is legitimately N-sized; only the (N, D)
            # delta pytree scatter is the scale problem.  (Compressed rounds
            # scatter the dequantized norms instead, below.)
            if not comp_on:
                with jax.named_scope("round.sampler_update"):
                    feedback = sampler.shard_constrain(
                        fed_cohort.scatter_cohort(
                            jnp.where(sel.valid, lam[sel.ids] * norms_c, 0.0), sel, n
                        )
                    )
            # Unbiased cohort estimate of the full weighted loss sum_i lam_i l_i.
            train_loss = jnp.sum(jnp.where(sel.valid, sel.weights * losses_c, 0.0))
            # The clients actually contacted (post-overflow-drop), not |S|.
            cohort_size = jnp.sum(sel.valid.astype(jnp.int32))
            if cfg.exact_oracle_equiv:
                # Scatter back to (N, ...) buffers and reuse the oracle
                # contraction: bitwise equal to the oracle path when |S| <= C
                # (inserted zero terms cannot change the partial sums), at
                # O(N*D) memory cost.
                with jax.named_scope("round.aggregate"):
                    deltas = fed_cohort.scatter_cohort(deltas_c, sel, n)
                    agg_weights = fed_cohort.scatter_cohort(sel.weights, sel, n)
                    d_est, sq_err = estimator.aggregate_and_error(
                        deltas, agg_weights, lam
                    )
            elif comp_on:
                # Compressed cohort width: the (C, D) stacked buffer lives at
                # quantized width in HBM and is widened per VMEM tile inside
                # the fused dequant-aggregate kernel.  Feedback norms come
                # from the same pass (dequantized values); error feedback
                # applies/updates the carried residual.
                with jax.named_scope("round.aggregate"):
                    lam_c = jnp.where(sel.valid, lam[sel.ids], 0.0)
                    d_est, sq_err, norms_dq, new_resid = (
                        estimator.aggregate_compressed(
                            deltas_c, sel.weights, lam_c, comp, c_state.get("resid")
                        )
                    )
                with jax.named_scope("round.sampler_update"):
                    feedback = sampler.shard_constrain(
                        fed_cohort.scatter_cohort(
                            jnp.where(sel.valid, lam[sel.ids] * norms_dq, 0.0), sel, n
                        )
                    )
                if ef_on:
                    c_state = {"resid": new_resid}
            else:
                # Cohort-width aggregation: O(C*D), no (N, D) buffer exists
                # anywhere in the round (tests assert this on the jaxpr).
                # Same value as the scatter path in exact arithmetic; allclose
                # on hardware (fed/cohort.py "Aggregation width").
                with jax.named_scope("round.aggregate"):
                    lam_c = jnp.where(sel.valid, lam[sel.ids], 0.0)
                    d_est, sq_err = estimator.aggregate_and_error_cohort(
                        deltas_c, sel.weights, lam_c
                    )
        # sq_err is recorded only in oracle mode; the deployable branches'
        # error row is dead code and fused away.
        if async_on:
            # Buffered-async: the round's aggregate enters the carried (B, D)
            # stale-delta ring; the server applies only the staleness-
            # discounted deltas whose arrival round has come (possibly none).
            with jax.named_scope("round.faults"):
                u_vec = stragglers.tree_to_vec(d_est)
                new_buf, apply_vec, _ = stragglers.async_step(
                    faults,
                    f_state["buf"],
                    u_vec,
                    t,
                    jax.random.fold_in(k_sample, 103),
                    compression=comp,
                )
                f_state = {**f_state, "buf": new_buf}
                d_est = stragglers.vec_to_tree(apply_vec, d_est)
        with jax.named_scope("round.aggregate"):
            params, opt_state = cfg.server_opt.apply(params, d_est, opt_state)

        # The server only observes sampled feedback (Theorem 5.2's partial
        # feedback): masked to the cohort it actually contacted.
        with jax.named_scope("round.sampler_update"):
            s_state = sampler.update(s_state, draw, feedback)

        metrics = {
            "train_loss": train_loss,
            "cohort_size": cohort_size,
        }
        if deadline_on:
            metrics["deadline_dropped"] = deadline_dropped
        if not cfg.oracle_metrics:
            metrics["dropped"] = overflow_dropped
        if cfg.oracle_metrics:
            if sampler.procedure == "isp":
                p_eff = p_marg
            else:
                # K x per-draw distribution approximates the inclusion
                # marginal; clip to (0, 1] so degenerate draws (K q_i > 1)
                # cannot corrupt the regret/quality-gap diagnostics.
                p_eff = jnp.clip(sampler.budget * draw.draw_probs, 1e-30, 1.0)
            cost, opt_cost = regret.round_costs(feedback_full, p_eff, sampler.budget)
            metrics.update(sq_error=sq_err, cost=cost, opt_cost=opt_cost)
            if cfg.track_scores:
                # (T, N) stacked across the scan — pure diagnostic weight at
                # large T*N; opt out via FedConfig.track_scores=False.
                metrics["scores"] = feedback_full
        if eval_data is not None:
            do_eval = (t % cfg.eval_every == 0) | (t == cfg.rounds - 1)
            with jax.named_scope("round.eval"):
                metrics["accuracy"] = jax.lax.cond(
                    do_eval,
                    lambda p: task.accuracy(p, eval_data).astype(jnp.float32),
                    lambda p: jnp.full((), jnp.nan, jnp.float32),
                    params,
                )
        out = (params, opt_state, s_state)
        if fault_on:
            out = out + (f_state,)
        if ef_on:
            out = out + (c_state,)
        return out, metrics

    return body


def _round_data(dataset):
    """``(dataset, lam)``: what the compiled round programs take as jit
    arguments, so no client data is a constant of the program."""
    return dataset, dataset.lam


def round_body_for_lint(
    task: Task,
    dataset,
    sampler: samplers.Sampler,
    cfg: FedConfig,
    eval_data: tuple | None = None,
):
    """Lintable handle on the built round body: ``(body, (carry, xs))``.

    ``carry``/``xs`` are ShapeDtypeStruct pytrees shaped exactly as the
    compiled paths trace the body (``build_segment_runner``'s scan and the
    reference loop's per-round jit) — no arrays are materialized, so the
    static checkers in ``repro.analysis.lint`` can ``jax.make_jaxpr(body)``
    the real program without touching data or devices."""
    body = _build_round_body(task, dataset, sampler, cfg, eval_data)
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    params = jax.eval_shape(task.init, key)
    opt_state = jax.eval_shape(cfg.server_opt.init, params)
    s_state = sampler.abstract_state()
    carry = (params, opt_state, s_state)
    if cfg.faults is not None:
        carry = carry + (
            stragglers.abstract_fault_state(
                cfg.faults,
                dataset.n_clients,
                stragglers.flat_dim(params),
                cfg.compression,
            ),
        )
    if cfg.compression is not None and cfg.compression.error_feedback:
        carry = carry + (
            {
                "resid": jax.ShapeDtypeStruct(
                    (stragglers.flat_dim(params),), jnp.float32
                )
            },
        )
    xs = (jax.ShapeDtypeStruct((), jnp.int32), key, key)
    return body, (carry, xs)


def _materialize_history(metrics: dict, cfg: FedConfig, has_eval: bool) -> History:
    """One host transfer at the end of the run: stacked device buffers ->
    the History lists the analysis/plotting code expects."""
    hist = History(regret=RegretTracker(budget=cfg.budget))
    hist.rounds = list(range(cfg.rounds))
    hist.train_loss = [float(x) for x in np.asarray(metrics["train_loss"])]
    hist.cohort_size = [int(x) for x in np.asarray(metrics["cohort_size"])]
    if "dropped" in metrics:
        hist.cohort_dropped = [int(x) for x in np.asarray(metrics["dropped"])]
    if "deadline_dropped" in metrics:
        hist.deadline_dropped = [
            int(x) for x in np.asarray(metrics["deadline_dropped"])
        ]
    if cfg.oracle_metrics:
        hist.estimator_sq_error = [float(x) for x in np.asarray(metrics["sq_error"])]
        hist.regret = RegretTracker.from_arrays(
            cfg.budget, metrics["cost"], metrics["opt_cost"], metrics.get("scores")
        )
    if has_eval:
        acc = np.asarray(metrics["accuracy"])
        hist.test_accuracy = [float(a) for a in acc[~np.isnan(acc)]]
    return hist


def _score_history_plan(cfg: FedConfig, n_clients: int):
    """Size-guard the oracle (T, N) score-history buffer and pick its device
    shape.

    Returns the number of buffer rows to allocate on device: ``cfg.rounds``
    normally, ``cfg.ckpt_every`` when host offload is on (the segment stitch
    wraps the shorter buffer as a ring and ``run_federated`` drains it to host
    every segment boundary).  Raises instead of silently OOMing the device
    when the full-horizon buffer would exceed
    ``cfg.score_history_bytes_limit``."""
    if not (cfg.oracle_metrics and cfg.track_scores):
        return None
    full_bytes = int(cfg.rounds) * int(n_clients) * 4  # f32 rows
    if cfg.score_history_host_offload:
        if cfg.ckpt_every <= 0:
            raise ValueError(
                "score_history_host_offload=True needs ckpt_every > 0 (the "
                "device ring holds one segment of score rows); got "
                f"ckpt_every={cfg.ckpt_every}"
            )
        return min(int(cfg.ckpt_every), int(cfg.rounds))
    if full_bytes > cfg.score_history_bytes_limit:
        raise ValueError(
            f"track_scores=True would allocate a ({cfg.rounds}, {n_clients}) "
            f"f32 score-history buffer ({full_bytes / 2**20:.0f} MiB) on "
            f"device, over score_history_bytes_limit="
            f"{cfg.score_history_bytes_limit / 2**20:.0f} MiB.  Set "
            "score_history_host_offload=True (chunked host drain), raise the "
            "limit, or set track_scores=False."
        )
    return int(cfg.rounds)


def _flush_async(params, opt_state, f_state, cfg: FedConfig):
    """End-of-horizon flush of the buffered-async stale-delta ring: apply the
    staleness-discounted sum of every still-pending delta through the server
    optimizer, once, after the last round.  Deterministic in the carried
    buffer state — a preempted-and-resumed run reaches the identical buffer
    and therefore the identical flush (mid-run segment boundaries do NOT
    flush; the buffer rides the carry)."""
    buf = f_state["buf"]
    if not np.asarray(buf["valid"]).any():
        return params
    pending = stragglers.flush_pending(
        buf, cfg.rounds, float(cfg.faults.staleness_discount)
    )
    d_pend = stragglers.vec_to_tree(pending, params)
    params, _ = cfg.server_opt.apply(params, d_pend, opt_state)
    return params


def _derive_keys_step(k, _):
    """One link of the reference loop's chained per-round key derivation:
    ``key, k_data, k_sample = split(key, 3)``.  Both execution paths (and the
    pre-scan history of this repo) consume this identical randomness stream,
    and the segmented runner advances the SAME chain segment by segment."""
    k, kd, ks = jax.random.split(k, 3)
    return k, jnp.stack([kd, ks])


def build_segment_runner(
    task: Task,
    dataset,
    sampler: samplers.Sampler,
    cfg: FedConfig,
    eval_data: tuple | None = None,
    *,
    donate: bool = True,
):
    """The segment-shaped compiled loop: ``(segment_fn, init_state)``.

    ``init_state`` is the canonical ``TrainState`` at round 0 — params/opt/
    sampler freshly initialized from ``cfg.seed``, metric buffers zero-
    preallocated for the full ``cfg.rounds`` horizon — and is also the
    restore template for ``CheckpointManager.restore_or_init``.

    ``segment_fn(state, n_rounds)`` comes from the shared
    ``fed.state.make_segment_fn`` machinery: it derives the next ``n_rounds``
    key pairs from ``state.key`` along the chained split sequence, scans the
    round body over them, and stitches the stacked per-round metrics into the
    (T,)-buffers at offset ``state.round``.  Because the bodies see the same
    carries, keys, and round indices under any segmentation, results are
    bitwise identical for every ``n_rounds`` schedule — a segment boundary is
    pure escape hatch, not a numeric event.

    ``donate=False`` keeps the input state alive across calls (benchmarks
    re-time the same state; donation would invalidate it on non-CPU
    backends)."""
    def build_body(data):
        ds, lam = data
        return _build_round_body(task, ds, sampler, cfg, eval_data, lam)

    body = _build_round_body(task, dataset, sampler, cfg, eval_data)
    fault_on = cfg.faults is not None
    ef_on = cfg.compression is not None and bool(cfg.compression.error_feedback)

    key = jax.random.PRNGKey(cfg.seed)
    key, init_key = jax.random.split(key)
    params = task.init(init_key)
    opt_state = cfg.server_opt.init(params)
    s_state = sampler.init()
    f_state = (
        stragglers.fault_state_init(
            cfg.faults, dataset.n_clients, stragglers.flat_dim(params), cfg.compression
        )
        if fault_on
        else ()
    )
    c_state = (
        {"resid": jnp.zeros((stragglers.flat_dim(params),), jnp.float32)}
        if ef_on
        else ()
    )

    carry0 = (params, opt_state, s_state)
    if fault_on:
        carry0 = carry0 + (f_state,)
    if ef_on:
        carry0 = carry0 + (c_state,)
    metrics = init_metric_buffers(
        body,
        carry0,
        (jnp.zeros((), jnp.int32), key, key),
        cfg.rounds,
    )
    score_rows = _score_history_plan(cfg, dataset.n_clients)
    if score_rows is not None and score_rows != cfg.rounds:
        # Host-offload ring: one segment of score rows on device; the rem
        # stitch in make_segment_fn wraps writes into it and run_federated
        # drains it to host at every segment boundary.
        metrics["scores"] = jnp.zeros(
            (score_rows,) + metrics["scores"].shape[1:],
            metrics["scores"].dtype,
        )

    init_state = TrainState(
        params=params,
        opt_state=opt_state,
        sampler=s_state,
        metrics=metrics,
        round=jnp.zeros((), jnp.int32),
        key=key,
        faults=f_state,
        compression=c_state,
    )
    placement = (
        build_placement(init_state, sampler, sampler.shard.mesh())
        if sampler.shard is not None
        else None
    )
    segment = make_segment_fn(
        build_body, _derive_keys_step,
        with_opt_state=True, with_round_index=True, with_faults=fault_on,
        with_compression=ef_on, donate=donate, placement=placement,
        data=_round_data(dataset),
    )
    return segment, init_state


def run_federated(
    task: Task,
    dataset,
    sampler: samplers.Sampler,
    cfg: FedConfig,
    eval_data: tuple | None = None,
    *,
    ckpt_manager=None,
) -> History:
    """Run Algorithm 1; see the module docstring for the execution modes.

    ``ckpt_manager`` (a ``repro.checkpoint.CheckpointManager``, compiled path
    only): restore-or-init from its manifest before running, and publish the
    full ``TrainState`` at every ``cfg.ckpt_every`` segment boundary — a
    preempted run re-invoked with the same config and manager continues from
    the last committed round and produces the identical ``History``."""
    t0 = time.time()

    if cfg.compiled:
        if ckpt_manager is not None and cfg.ckpt_every <= 0:
            # One whole-horizon segment would mean zero mid-run checkpoints —
            # the manager could never protect anything before the final round.
            raise ValueError(
                "run_federated(ckpt_manager=...) needs cfg.ckpt_every > 0; "
                f"got ckpt_every={cfg.ckpt_every}"
            )
        segment, state = build_segment_runner(task, dataset, sampler, cfg, eval_data)
        if ckpt_manager is not None:
            state, _ = ckpt_manager.restore_or_init(state)

        on_segment = None
        offload = (
            cfg.oracle_metrics and cfg.track_scores and cfg.score_history_host_offload
        )
        if offload:
            # Chunked host drain of the (ckpt_every, N) device ring: segments
            # start at multiples of ckpt_every, so each segment's rows sit at
            # the front of the ring.  Rounds executed before a restore (by an
            # earlier process) stay zero — the offloaded history covers this
            # process's rounds.
            scores_host = np.zeros(
                (cfg.rounds, dataset.n_clients),
                np.dtype(state.metrics["scores"].dtype),
            )
            drained_to = int(state.round)

            def on_segment(st, done):
                nonlocal drained_to
                rows = np.asarray(st.metrics["scores"])[: done - drained_to]
                scores_host[drained_to:done] = rows
                drained_to = done

        state = run_segmented(
            state,
            cfg.rounds,
            segment,
            ckpt_every=cfg.ckpt_every,
            manager=ckpt_manager,
            on_segment=on_segment,
        )
        jax.block_until_ready(state)
        params = state.params
        if cfg.faults is not None and int(cfg.faults.async_buffer) > 0:
            params = _flush_async(params, state.opt_state, state.faults, cfg)
        metrics = jax.tree_util.tree_map(np.asarray, state.metrics)
        if offload:
            metrics["scores"] = scores_host
    else:
        key = jax.random.PRNGKey(cfg.seed)
        key, init_key = jax.random.split(key)
        params = task.init(init_key)
        opt_state = cfg.server_opt.init(params)
        s_state = sampler.init()
        fault_on = cfg.faults is not None
        ef_on = cfg.compression is not None and bool(cfg.compression.error_feedback)
        f_state = (
            stragglers.fault_state_init(
                cfg.faults,
                dataset.n_clients,
                stragglers.flat_dim(params),
                cfg.compression,
            )
            if fault_on
            else ()
        )
        c_state = (
            {"resid": jnp.zeros((stragglers.flat_dim(params),), jnp.float32)}
            if ef_on
            else ()
        )

        # Per-round (k_data, k_sample) pairs, derived up front along the same
        # chained-split sequence the segmented runner walks.
        @functools.partial(jax.jit, static_argnames=("rounds",))
        def derive_keys(key, rounds):
            _, pairs = jax.lax.scan(_derive_keys_step, key, None, length=rounds)
            return pairs

        round_keys = derive_keys(key, cfg.rounds)  # (T, 2, key_dim)
        ts = jnp.arange(cfg.rounds, dtype=jnp.int32)

        # The dataset is an argument, as in the segmented path: both paths
        # compile the same program form, and no data is a program constant.
        def step_fn(carry, xs, data):
            ds, lam = data
            return _build_round_body(task, ds, sampler, cfg, eval_data, lam)(carry, xs)

        round_data = _round_data(dataset)
        donate = jax.default_backend() != "cpu"
        step = jax.jit(step_fn, donate_argnums=(0,) if donate else ())
        per_round = []
        for t in range(cfg.rounds):
            carry_in = (params, opt_state, s_state)
            if fault_on:
                carry_in = carry_in + (f_state,)
            if ef_on:
                carry_in = carry_in + (c_state,)
            carry, m = step(
                carry_in,
                (ts[t], round_keys[t, 0], round_keys[t, 1]),
                round_data,
            )
            if ef_on:
                carry, c_state = carry[:-1], carry[-1]
            if fault_on:
                params, opt_state, s_state, f_state = carry
            else:
                params, opt_state, s_state = carry
            # Host sync every round — the reference loop's defining trait.
            per_round.append(jax.tree_util.tree_map(np.asarray, m))
        if fault_on and int(cfg.faults.async_buffer) > 0 and cfg.rounds > 0:
            params = _flush_async(params, opt_state, f_state, cfg)
        if per_round:
            metrics = {k: np.stack([m[k] for m in per_round]) for k in per_round[0]}
        else:
            metrics = {"train_loss": np.zeros(0), "cohort_size": np.zeros(0, np.int32)}
            if fault_on and cfg.faults.deadline is not None:
                metrics["deadline_dropped"] = np.zeros(0, np.int32)
            if not cfg.oracle_metrics:
                metrics["dropped"] = np.zeros(0, np.int32)
            if cfg.oracle_metrics:
                metrics.update(
                    sq_error=np.zeros(0), cost=np.zeros(0), opt_cost=np.zeros(0)
                )
                if cfg.track_scores:
                    metrics["scores"] = np.zeros((0, dataset.n_clients))
            if eval_data is not None:
                metrics["accuracy"] = np.zeros(0)

    hist = _materialize_history(metrics, cfg, has_eval=eval_data is not None)
    if cfg.compiled:
        hist.segment = segment
    hist.final_params = jax.tree_util.tree_map(np.asarray, params)
    hist.wall_time_s = time.time() - t0
    return hist
