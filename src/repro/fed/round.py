"""Pod-scale federated round steps (the distributed Algorithm 1).

Two cohort execution modes (DESIGN.md section 3):

* client_parallel — cohort members vmapped across the batch ('data'/'pod')
  mesh axes; per-client diverged params live concurrently (C copies, each
  tensor-sharded over 'model').  Round latency ~= one client's local run.
* cohort_sequential — lax.scan over cohort members; each member's batch is
  itself data-parallel and params are FSDP-sharded over (batch x model)
  axes; only ONE diverged copy + the accumulator exist at a time, which is
  what lets llama3-405b / arctic-480b run true R-step local training.

Both produce:
  new_params  — x^{t+1} = x^t - eta_g * d^t with the unbiased ISP estimate
                d^t = sum_c w_c * (x^t - x_c^{t,R}),  w_c = m_c lambda_c / p~_c
  feedback    — pi_t(c) = ||delta_c||  (weights applied by the server, which
                knows lambda; the norm rides the aggregation pass)
  mean loss over the active (w != 0) cohort slots — padding is inert.

The round consumes a *static padded cohort* of size C with the inclusion
mask folded into w (w_c = 0 for padding) — ISP's stochastic |S^t| maps onto
fixed TPU shapes this way.  Selection/padding/weight semantics live in
``repro.fed.cohort`` (the shared contract with the compiled server loop and
the launcher); this module is the device-side consumer of that contract.

``RoundSpec`` is this stack's low-level knob set; the canonical experiment
description is ``repro.api.ExperimentSpec``, whose zoo dispatch
(``repro.api.run`` / ``repro.launch.train``) projects its ``FederationSpec``
onto a ``RoundSpec`` and drives ``build_fed_scan_segment``.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable

import jax
import jax.numpy as jnp

from repro.fed.cohort import mask_selection, select_cohort, weighted_delta_sum
from repro.fed.state import (
    TrainState,
    build_placement,
    init_metric_buffers,
    make_segment_fn,
)
from repro.models import transformer
from repro.models.common import ArchConfig

__all__ = [
    "RoundSpec",
    "build_round_step",
    "build_fed_scan",
    "build_fed_scan_segment",
    "scan_body_for_lint",
]


@dataclasses.dataclass(frozen=True)
class RoundSpec:
    cohort: int  # padded cohort size C
    local_steps: int  # R
    local_lr: float = 0.02
    server_lr: float = 1.0
    local_batch: int = 2  # B_local (used by the compiled scan's device gather)
    # Deployment-realism fault layer (a ``repro.api.FaultSpec`` or None —
    # see ``FedConfig.faults``).  None builds the exact pre-fault scan body;
    # enabled faults require the segment-shaped runner
    # (``build_fed_scan_segment``) — the monolithic ``build_fed_scan`` and
    # the host launcher loop raise.
    faults: object | None = None
    # Delta-width compression (a ``repro.api.CompressionSpec`` or None — see
    # ``FedConfig.compression``).  None builds the exact pre-compression scan
    # body.  Only ``client_parallel`` supports it (cohort_sequential never
    # materializes a (C, D) stacked buffer to compress); enabled compression
    # requires the segment-shaped runner, like faults.
    compression: object | None = None


def _tree_sq_norm(delta):
    sq = jax.tree_util.tree_map(
        lambda x: jnp.sum(jnp.square(x.astype(jnp.float32))), delta
    )
    return jax.tree_util.tree_reduce(jnp.add, sq)


def _local_train(params, cfg: ArchConfig, batches, lr: float):
    """R local SGD steps on one client. batches: pytree with leading R axis.

    Returns (delta = x0 - xR, last-step loss)."""

    def step(p, batch):
        loss, grads = jax.value_and_grad(lambda q: transformer.loss_fn(q, cfg, batch))(p)
        p = jax.tree_util.tree_map(lambda w, g: w - lr * g.astype(w.dtype), p, grads)
        return p, loss

    final, losses = jax.lax.scan(step, params, batches)
    delta = jax.tree_util.tree_map(lambda a, b: (a - b).astype(a.dtype), params, final)
    return delta, losses[-1]


def build_round_step(cfg: ArchConfig, spec: RoundSpec, constrain=None) -> Callable:
    """Returns round_step(params, tokens, targets, weights[, aux_embeds]).

    tokens/targets: (C, R, B_local, S) int32 — each cohort member's R local
    batches.  aux_embeds (multimodal archs): (C, R, B_local, S_front, F).
    weights: (C,) f32 — m_c * lambda_c / p~_c (zero for cohort padding).
    constrain: optional fn(param-like pytree) -> pytree applying sharding
    constraints — REQUIRED at scale for cohort_sequential so the f32
    estimate accumulator stays FSDP-sharded instead of being replicated and
    all-reduced every cohort step (EXPERIMENTS.md section Perf, qwen3 iter 1).
    """
    mode = cfg.round_mode
    if constrain is None:
        constrain = lambda tree: tree
    comp = spec.compression
    comp_on = comp is not None
    if comp_on and mode != "client_parallel":
        raise ValueError(
            f"RoundSpec.compression needs round_mode='client_parallel' (got "
            f"{mode!r}): cohort_sequential accumulates per-member deltas one "
            "at a time and never materializes the (C, D) stacked buffer that "
            "delta-width compression shrinks"
        )

    def per_client(params, tok, tgt, aux):
        batches = (tok, tgt) if aux is None else (tok, tgt, aux)
        delta, loss = _local_train(params, cfg, batches, spec.local_lr)
        return delta, loss, jnp.sqrt(_tree_sq_norm(delta))

    def cohort_mean_loss(losses, weights):
        # Padding slots (w == 0) hold inert all-zero batches; their loss is
        # meaningless and must not pollute the round's reported loss.
        active = weights != 0.0
        return jnp.sum(jnp.where(active, losses, 0.0)) / jnp.maximum(
            jnp.sum(active.astype(jnp.float32)), 1.0
        )

    if mode == "client_parallel":
        if comp_on:
            from repro.core import estimator

            def round_step(
                params, tokens, targets, weights, aux_embeds=None, resid=None
            ):
                def one(tok, tgt, aux):
                    return per_client(params, tok, tgt, aux)

                with jax.named_scope("round.local_train"):
                    if aux_embeds is None:
                        deltas, losses, _ = jax.vmap(
                            lambda tok, tgt: one(tok, tgt, None)
                        )(tokens, targets)
                    else:
                        deltas, losses, _ = jax.vmap(one)(tokens, targets, aux_embeds)
                # Compressed aggregation: the stacked cohort deltas are
                # quantized and reduced by the fused dequant kernel; passing
                # ``weights`` for lam_cohort zeroes the (unused here) error
                # row.  Feedback norms come from the dequantized values.
                with jax.named_scope("round.aggregate"):
                    d, _, norms, new_resid = estimator.aggregate_compressed(
                        deltas, weights, weights, comp, resid
                    )
                    new_params = jax.tree_util.tree_map(
                        lambda p, g: p - spec.server_lr * g.astype(p.dtype), params, d
                    )
                return new_params, norms, cohort_mean_loss(losses, weights), new_resid

            return round_step

        def round_step(params, tokens, targets, weights, aux_embeds=None):
            def one(tok, tgt, aux):
                return per_client(params, tok, tgt, aux)

            with jax.named_scope("round.local_train"):
                if aux_embeds is None:
                    deltas, losses, norms = jax.vmap(
                        lambda tok, tgt: one(tok, tgt, None)
                    )(tokens, targets)
                else:
                    deltas, losses, norms = jax.vmap(one)(tokens, targets, aux_embeds)
            with jax.named_scope("round.aggregate"):
                d = weighted_delta_sum(deltas, weights)
                new_params = jax.tree_util.tree_map(
                    lambda p, g: p - spec.server_lr * g.astype(p.dtype), params, d
                )
            return new_params, norms, cohort_mean_loss(losses, weights)

        return round_step

    if mode == "cohort_sequential":

        def round_step(params, tokens, targets, weights, aux_embeds=None):
            with jax.named_scope("round.aggregate"):
                acc0 = constrain(
                    jax.tree_util.tree_map(
                        lambda p: jnp.zeros(p.shape, jnp.float32), params
                    )
                )

            def body(acc, inp):
                if aux_embeds is None:
                    tok, tgt, w = inp
                    aux = None
                else:
                    tok, tgt, w, aux = inp
                # Members train and accumulate in turn; each half takes its
                # own scope inside the unscoped scan.
                with jax.named_scope("round.local_train"):
                    delta, loss, norm = per_client(params, tok, tgt, aux)
                with jax.named_scope("round.aggregate"):
                    delta = constrain(delta)
                    acc = jax.tree_util.tree_map(
                        lambda a, dl: a + w * dl.astype(jnp.float32), acc, delta
                    )
                    acc = constrain(acc)
                return acc, (loss, norm)

            xs = (
                (tokens, targets, weights)
                if aux_embeds is None
                else (tokens, targets, weights, aux_embeds)
            )
            d, (losses, norms) = jax.lax.scan(body, acc0, xs)
            with jax.named_scope("round.aggregate"):
                new_params = jax.tree_util.tree_map(
                    lambda p, g: p - spec.server_lr * g.astype(p.dtype), params, d
                )
            return new_params, norms, cohort_mean_loss(losses, weights)

        return round_step

    raise ValueError(f"unknown round_mode {mode!r}")


def build_fed_scan(
    cfg: ArchConfig,
    spec: RoundSpec,
    sampler,
    dataset,
    *,
    mesh=None,
    constrain=None,
) -> Callable:
    """Compiled multi-round federated training: ONE jitted ``lax.scan`` whose
    per-round body is this module's pod-scale ``build_round_step`` — the
    mesh-parallel counterpart of the single-host scan loop in ``fed/server.py``
    and the compiled form of the ``repro.launch.train`` host loop.

    Per round, entirely inside the trace: probabilities solved once, ISP/RSP
    draw, padded-cohort selection (shared ``fed.cohort`` contract, unbiased
    |S|/C overflow rescaling), device-side cohort batch gather (keys derived
    by ``fold_in(k_data, client_id)`` — the identical stream to
    ``host_gather_cohort_batches``, so the compiled and host loops train on
    the same batches), the round step's local training + cohort-width
    aggregation, feedback scatter, sampler update.  Every buffer with a
    parameter axis is C-wide; the sampler state and feedback are the only
    N-sized tensors, and they are (N,)-vectors.

    With ``mesh`` (from ``repro.launch.mesh``), cohort batches carry sharding
    constraints: client_parallel spreads the C cohort members across the
    mesh's batch axes, cohort_sequential spreads each member's local batch —
    one dispatch drives the whole sharded multi-round run.

    Returns ``run(params, s_state, round_keys)`` with ``round_keys`` (T, 2, 2)
    stacked (k_draw, k_data) pairs; yields (params, s_state, metrics) where
    metrics are (T,)-stacked ``loss`` / ``cohort_size`` / ``dropped``.

    For the preemption-safe segment-shaped form of the same computation, see
    ``build_fed_scan_segment``.
    """
    if spec.faults is not None:
        raise ValueError(
            "RoundSpec.faults requires the segment-shaped runner "
            "(build_fed_scan_segment): the fault state (availability chain, "
            "stale-delta buffer) lives in the TrainState carry, which the "
            "monolithic build_fed_scan signature cannot thread"
        )
    if spec.compression is not None:
        raise ValueError(
            "RoundSpec.compression requires the segment-shaped runner "
            "(build_fed_scan_segment): the error-feedback residual lives in "
            "the TrainState carry, which the monolithic build_fed_scan "
            "signature cannot thread"
        )
    body = _build_scan_body(cfg, spec, sampler, dataset, mesh, constrain)

    donate = (0,) if jax.default_backend() != "cpu" else ()

    @functools.partial(jax.jit, donate_argnums=donate)
    def run(params, s_state, round_keys):
        (params, s_state), metrics = jax.lax.scan(
            body, (params, s_state), round_keys
        )
        return params, s_state, metrics

    return run


def _build_scan_body(cfg, spec, sampler, dataset, mesh, constrain):
    """The per-round scan body shared by ``build_fed_scan`` (monolithic) and
    ``build_fed_scan_segment``: (params, s_state) carry, (2, key) xs.

    With ``spec.faults`` set the body grows the deployment-realism layer
    (``repro.core.stragglers``; same semantics as the simulation stack's
    ``fed.server._build_round_body``): carry becomes
    ``(params, s_state, f_state)`` and xs ``(t, k_draw, k_data)`` — the round
    index feeds the availability process and the async ring."""
    from repro.core import estimator, stragglers

    lam = dataset.lam
    n = dataset.n_clients
    round_step = build_round_step(cfg, spec, constrain)

    faults = spec.faults
    fault_on = faults is not None
    avail_on = fault_on and faults.availability is not None
    deadline_on = fault_on and faults.deadline is not None
    async_on = fault_on and int(faults.async_buffer) > 0
    surv = stragglers.deadline_survival(faults) if deadline_on else 1.0
    comp = spec.compression
    comp_on = comp is not None
    ef_on = comp_on and bool(comp.error_feedback)

    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec

        from repro.launch.mesh import batch_axes

        baxes = batch_axes(mesh)
        # (C, R, B, S) batches: client_parallel shards cohort members,
        # cohort_sequential scans members and shards their local batch.
        spec_nd = (
            PartitionSpec(baxes)
            if cfg.round_mode == "client_parallel"
            else PartitionSpec(None, None, baxes)
        )

        def shard_batches(x):
            return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec_nd))

    else:

        def shard_batches(x):
            return x

    def gather_cohort(sel, k_data):
        """(C, R, B, ...) device gather; padding slots zeroed (inert).  The
        cohort's rows are gathered once, then each slot samples its own row
        (``FederatedDataset.rows``)."""
        rows = dataset.rows(sel.ids)

        def one(slot, cid):
            keys = jax.random.split(
                jax.random.fold_in(k_data, cid), spec.local_steps
            )
            return jax.vmap(
                lambda kr: rows.client_batch(slot, kr, spec.local_batch)
            )(keys)

        feats, labs = jax.vmap(one)(jnp.arange(sel.ids.shape[0]), sel.ids)

        def zero_pad(leaf):
            keep = sel.valid.reshape((-1,) + (1,) * (leaf.ndim - 1))
            return jnp.where(keep, leaf, jnp.zeros((), leaf.dtype))

        return shard_batches(zero_pad(feats)), shard_batches(zero_pad(labs))

    def body(carry, xs):
        c_state = {}
        if ef_on:
            carry, c_state = carry[:-1], carry[-1]
        if fault_on:
            params, s_state, f_state = carry
            t, k_draw, k_data = xs
        else:
            params, s_state = carry
            f_state = {}
            t = None
            k_draw, k_data = xs[0], xs[1]
        with jax.named_scope("round.solve"):
            p = sampler.probabilities(s_state)
        with jax.named_scope("round.draw"):
            draw = sampler.sample_from(p, k_draw)
        if avail_on:
            # Same fold_in streams (101/102/103) as the simulation stack, off
            # the draw key; the draw's own key material is untouched.
            with jax.named_scope("round.faults"):
                avail_mask, q_t, new_chain = stragglers.availability_step(
                    faults,
                    f_state.get("chain"),
                    t,
                    jax.random.fold_in(k_draw, 101),
                    n,
                )
                avail_mask = sampler.shard_constrain(avail_mask)
                q_t = sampler.shard_constrain(q_t)
                draw = stragglers.available_draw(draw, avail_mask, q_t)
                if "chain" in f_state:
                    f_state = {**f_state, "chain": sampler.shard_constrain(new_chain)}
        with jax.named_scope("round.select"):
            w_full = estimator.client_weights(
                draw, lam, sampler.procedure, sampler.budget
            )
            sel = select_cohort(
                draw.mask, w_full, spec.cohort, jax.random.fold_in(k_draw, 1)
            )
        overflow_dropped = sel.n_dropped
        deadline_dropped = jnp.zeros((), jnp.int32)
        if deadline_on:
            # Local training below still runs for every C slot (the server
            # already scheduled it); late slots are demoted to inert padding
            # so only the aggregation weights / feedback / loss see the drop,
            # with survivors rescaled by 1/surv for unbiasedness.
            with jax.named_scope("round.faults"):
                lat_c = stragglers.latency_draw(
                    faults, (sel.valid.shape[0],), jax.random.fold_in(k_draw, 102)
                )
                late_c = jnp.logical_and(
                    sel.valid, lat_c > jnp.float32(faults.deadline)
                )
                sel = mask_selection(sel, ~late_c, 1.0 / surv)
                deadline_dropped = jnp.sum(late_c.astype(jnp.int32))
        with jax.named_scope("round.gather"):
            tokens, targets = gather_cohort(sel, k_data)
        # round_step opens its own round.local_train and round.aggregate.
        if comp_on:
            new_params, norms, loss, new_resid = round_step(
                params, tokens, targets, sel.weights, resid=c_state.get("resid")
            )
            if ef_on:
                c_state = {"resid": new_resid}
        else:
            new_params, norms, loss = round_step(params, tokens, targets, sel.weights)
        if async_on:
            # round_step already applied x - server_lr * d; recover the
            # update u = server_lr * d, route it through the carried (B, D)
            # stale-delta ring, and apply only what arrived this round.
            with jax.named_scope("round.faults"):
                u = jax.tree_util.tree_map(lambda a, b: a - b, params, new_params)
                new_buf, apply_vec, _ = stragglers.async_step(
                    faults,
                    f_state["buf"],
                    stragglers.tree_to_vec(u),
                    t,
                    jax.random.fold_in(k_draw, 103),
                    compression=comp,
                )
                f_state = {**f_state, "buf": new_buf}
                d_apply = stragglers.vec_to_tree(apply_vec, params)
                params = jax.tree_util.tree_map(lambda a, g: a - g, params, d_apply)
        else:
            params = new_params
        # Sampler feedback: (N,)-vector scatter of the (C,) cohort norms,
        # constrained back onto the sampler's (N,)-shard layout so the
        # scatter result never materializes replicated at scale.
        with jax.named_scope("round.sampler_update"):
            fb = sampler.shard_constrain(
                jnp.zeros((n,), jnp.float32).at[sel.ids].add(
                    jnp.where(sel.valid, lam[sel.ids] * norms, 0.0)
                )
            )
            s_state = sampler.update(s_state, draw, fb)
        metrics = {
            "loss": loss,
            "cohort_size": jnp.sum(sel.valid.astype(jnp.int32)),
            "dropped": overflow_dropped,
        }
        if deadline_on:
            metrics["deadline_dropped"] = deadline_dropped
        out = (params, s_state)
        if fault_on:
            out = out + (f_state,)
        if ef_on:
            out = out + (c_state,)
        return out, metrics

    return body


def scan_body_for_lint(
    cfg: ArchConfig,
    spec: RoundSpec,
    sampler,
    dataset,
    *,
    mesh=None,
    constrain=None,
):
    """Lintable handle on the pod-scale scan body: ``(body, (carry, xs))``.

    ``carry``/``xs`` are ShapeDtypeStruct pytrees matching what
    ``build_fed_scan``/``build_fed_scan_segment`` scan the body with — the
    model parameters come from ``jax.eval_shape`` of ``transformer.
    init_params``, so no weights are materialized and the static checkers in
    ``repro.analysis.lint`` can trace the real round program for free."""
    from repro.core import stragglers

    body = _build_scan_body(cfg, spec, sampler, dataset, mesh, constrain)
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    params = jax.eval_shape(lambda k: transformer.init_params(cfg, k), key)
    carry = (params, sampler.abstract_state())
    xs = jax.eval_shape(lambda k: jnp.stack([k, k]), key)
    if spec.faults is not None:
        carry = carry + (
            stragglers.abstract_fault_state(
                spec.faults,
                dataset.n_clients,
                stragglers.flat_dim(params),
                spec.compression,
            ),
        )
        xs = (jax.ShapeDtypeStruct((), jnp.int32), key, key)
    if spec.compression is not None and spec.compression.error_feedback:
        carry = carry + (
            {
                "resid": jax.ShapeDtypeStruct(
                    (stragglers.flat_dim(params),), jnp.float32
                )
            },
        )
    return body, (carry, xs)


def build_fed_scan_segment(
    cfg: ArchConfig,
    spec: RoundSpec,
    sampler,
    dataset,
    *,
    mesh=None,
    constrain=None,
    donate: bool = True,
) -> tuple:
    """Segment-shaped ``build_fed_scan``: ``(segment_fn, make_state)``.

    The same per-round body as ``build_fed_scan``, cut for the host-driven
    segmented horizon (``repro.fed.state.run_segmented``) so
    ``repro.launch.train --compiled`` can publish a checkpoint every
    ``--ckpt-every`` rounds and survive preemption:

    * ``make_state(params, s_state, key, total_rounds)`` builds the canonical
      ``TrainState`` at round 0 — ``key`` is the launcher's chain key, from
      which each round's ``key, k_draw, k_data = split(key, 3)`` derives (the
      identical stream the host loop and the monolithic ``build_fed_scan``
      caller consume), and the ``loss``/``cohort_size``/``dropped`` metric
      buffers are zero-preallocated for the FULL horizon.  It is also the
      restore template for ``CheckpointManager.restore_or_init``.
    * ``segment_fn(state, n_rounds)`` comes from the shared
      ``fed.state.make_segment_fn`` machinery: it derives the next
      ``n_rounds`` key pairs in-trace, scans the round body, and stitches the
      stacked metrics into the buffers at offset ``state.round`` — bitwise
      identical to the monolithic scan under any segmentation
      (tests/test_segmented_scan.py).

    The launcher round step is stateless on the server side (``server_lr``
    applied directly), so ``TrainState.opt_state`` is ``()``.
    """
    from repro.core import stragglers

    body = _build_scan_body(cfg, spec, sampler, dataset, mesh, constrain)
    fault_on = spec.faults is not None
    ef_on = spec.compression is not None and bool(spec.compression.error_feedback)

    def derive_step(k, _):
        k, k_draw, k_data = jax.random.split(k, 3)
        return k, jnp.stack([k_draw, k_data])

    def fault_init(params):
        return stragglers.fault_state_init(
            spec.faults,
            dataset.n_clients,
            stragglers.flat_dim(params),
            spec.compression,
        )

    def comp_init(params):
        return {"resid": jnp.zeros((stragglers.flat_dim(params),), jnp.float32)}

    def make_state(params, s_state, key, total_rounds: int) -> TrainState:
        f_state = fault_init(params) if fault_on else ()
        c_state = comp_init(params) if ef_on else ()
        carry0 = (params, s_state) + ((f_state,) if fault_on else ())
        if ef_on:
            carry0 = carry0 + (c_state,)
        xs0 = (
            (jnp.zeros((), jnp.int32), key, key)
            if fault_on
            else jnp.stack([key, key])
        )
        return TrainState(
            params=params,
            opt_state=(),
            sampler=s_state,
            metrics=init_metric_buffers(body, carry0, xs0, total_rounds),
            round=jnp.zeros((), jnp.int32),
            key=key,
            faults=f_state,
            compression=c_state,
        )

    placement = None
    if mesh is not None or getattr(sampler, "shard", None) is not None:
        # The body's sharding constraints commit its outputs to the mesh; a
        # canonical placement gives fresh, carried and restored states the
        # same layout, so the segment compiles once.
        # Shape-only template: the metrics dict's structure (and its lack of
        # any (N,)-axis buffer) is the same for every horizon length, so a
        # 1-round buffer set is enough to derive the placement pytree.
        key_s = jax.eval_shape(lambda: jax.random.PRNGKey(0))
        params_s = jax.eval_shape(lambda k: transformer.init_params(cfg, k), key_s)
        f_state_s = jax.eval_shape(fault_init, params_s) if fault_on else ()
        c_state_s = jax.eval_shape(comp_init, params_s) if ef_on else ()
        carry_s = (params_s, sampler.abstract_state()) + (
            (f_state_s,) if fault_on else ()
        )
        if ef_on:
            carry_s = carry_s + (c_state_s,)
        xs_s = (
            (jax.ShapeDtypeStruct((), jnp.int32), key_s, key_s)
            if fault_on
            else jax.eval_shape(lambda k: jnp.stack([k, k]), key_s)
        )
        template = TrainState(
            params=params_s,
            opt_state=(),
            sampler=sampler.abstract_state(),
            metrics=init_metric_buffers(body, carry_s, xs_s, 1),
            round=jax.ShapeDtypeStruct((), jnp.int32),
            key=key_s,
            faults=f_state_s,
            compression=c_state_s,
        )
        placement = build_placement(
            template, sampler, mesh if mesh is not None else sampler.shard.mesh()
        )

    segment = make_segment_fn(
        body, derive_step,
        with_opt_state=False, with_round_index=fault_on, with_faults=fault_on,
        with_compression=ef_on, donate=donate, placement=placement,
    )
    return segment, make_state
