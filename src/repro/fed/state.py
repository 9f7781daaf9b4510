"""Canonical compiled-horizon carry (``TrainState``) + the segmented driver.

The K-Vib sampler's value is its *online* state: the cumulative feedback it
accumulates over the horizon is what drives the variance-reduced regret bound
(PAPER.md section 4), so a preempted server that loses sampler state loses the
learned sampling probabilities, not just wall-clock.  This module is the
preemption-safety layer for the compiled execution paths: instead of running
the whole horizon as one opaque ``lax.scan``, the horizon is cut into jitted
scan *segments* of ``ckpt_every`` rounds driven from a host loop that can
publish a checkpoint (``repro.checkpoint.CheckpointManager``) at every
segment boundary.

What must be in the carry
-------------------------

``TrainState`` is the single canonical pytree that round-trips through
segment boundaries AND through checkpoints.  Everything a resumed process
needs to continue the run bit-for-bit must live here as an *array* leaf:

* ``params``     — model parameters (pytree of arrays).
* ``opt_state``  — server-optimizer state (``()`` for stateless FedAvg).
* ``sampler``    — the sampler's online state (``core.samplers.SamplerState``
                   contract: flat pytree of arrays, no Python scalars).
* ``metrics``    — dict of on-device ``(T, ...)`` per-round metric buffers,
                   preallocated for the FULL horizon and stitched segment by
                   segment via ``lax.dynamic_update_slice`` — a resumed run's
                   ``History`` therefore covers the whole horizon, including
                   rounds executed before the preemption.
* ``round``      — scalar int32: the next round to execute (also the write
                   offset into the metric buffers and the checkpoint step).
* ``key``        — the PRNG key from which the remaining rounds' per-round
                   keys derive.  Each segment advances it by exactly
                   ``n_rounds`` chained splits, so any segmentation of the
                   horizon consumes the identical key stream.
* ``faults``     — the fault layer's carried state when a
                   ``repro.api.FaultSpec`` is enabled (Markov availability
                   chain, buffered-async stale-delta ring —
                   ``core.stragglers.fault_state_init``); ``()`` otherwise.
                   Living here is what makes a SIGKILL'd faulted run resume
                   bit-for-bit and keeps async segmentation bitwise-neutral
                   (pending deltas ride the boundary instead of flushing).
* ``compression``— the delta-compression layer's carried state when a
                   ``repro.api.CompressionSpec`` with error feedback is
                   enabled: ``{"resid": (D,) f32}``, the server-side
                   error-feedback residual.  Riding the carry keeps the
                   quantization-error telescope exact across segment
                   boundaries, SIGKILL/resume, and mesh re-shapes;
                   ``()`` otherwise.

Segmentation is a pure reshaping of the horizon: for any ``ckpt_every`` the
per-round bodies see the same carries, keys, and round indices, so results
are bitwise identical to the monolithic scan (tests/test_segmented_scan.py
pins this at ``ckpt_every`` in {1, 7, T}).

Restore is template-shaped: build the fresh round-0 state, then refill it
from the checkpoint.  ``repro.api.restore_template(spec)`` constructs that
template for either stack straight from the declarative
``repro.api.ExperimentSpec`` — the same spec whose
``config_fingerprint(spec.to_dict())`` guards the manifest.
"""
from __future__ import annotations

import dataclasses
import functools
import itertools
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro import obs

__all__ = [
    "TrainState",
    "build_placement",
    "make_segment_fn",
    "init_metric_buffers",
    "run_segmented",
]


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class TrainState:
    """The canonical compiled-horizon carry (see module docstring)."""

    params: Any
    opt_state: Any
    sampler: Any
    metrics: Any
    round: jax.Array  # scalar int32 — next round to execute
    key: jax.Array  # PRNG key for the remaining rounds' key derivation
    faults: Any = ()  # fault-layer carry (FaultSpec enabled) or ()
    compression: Any = ()  # error-feedback residual carry (CompressionSpec) or ()

    def tree_flatten(self):
        children = (
            self.params,
            self.opt_state,
            self.sampler,
            self.metrics,
            self.round,
            self.key,
            self.faults,
            self.compression,
        )
        return children, None

    @classmethod
    def tree_unflatten(cls, _, children):
        return cls(*children)


def init_metric_buffers(body, carry, xs_example, total_rounds: int):
    """Zero-preallocated full-horizon ``(T, ...)`` metric buffers, shaped by
    ``jax.eval_shape`` of the round body's per-round metrics output — the
    buffers a segment stitches into at offset ``state.round``."""
    _, metric_shapes = jax.eval_shape(body, carry, xs_example)
    return jax.tree_util.tree_map(
        lambda s: jnp.zeros((int(total_rounds),) + s.shape, s.dtype), metric_shapes
    )


def build_placement(template: TrainState, sampler, mesh) -> TrainState:
    """Canonical ``TrainState`` device-placement pytree over ``mesh`` — the
    one mesh the round body is constrained to and the sampler shard (if any)
    lives on — handed to ``make_segment_fn(placement=...)``.

    ``template`` only needs shapes/dtypes — concrete arrays and
    ``ShapeDtypeStruct`` pytrees both work.  Rule: sampler-state leaves with
    a leading (N,) axis live split along ``sampler.shard``'s mesh axis;
    metric buffers with a trailing (N,) axis (the oracle score history)
    split that axis the same way; every other leaf — params, optimizer
    state, scalar metrics, round counter, key — is explicitly replicated.
    Without a sampler shard every leaf is replicated over ``mesh``.
    Making the whole carry's placement explicit (not just the sharded
    leaves) is what keeps the jit cache at one entry: fresh states, carried
    outputs, and numpy-round-tripped restores all ``device_put`` onto this
    exact layout before entering the jitted segment.

    When N is not divisible by the shard count, the at-rest placement falls
    back to replicated for the affected leaves — ``device_put`` cannot
    express an uneven split, while the in-trace sharding constraints can
    (GSPMD pads internally), so compute stays sharded either way."""
    shard = sampler.shard
    if shard is not None and shard.mesh() != mesh:
        raise ValueError(
            f"sampler shard mesh {shard.axes} differs from the placement mesh "
            f"{dict(mesh.shape)}"
        )
    rep = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    n = sampler.n
    divisible = shard is not None and n % shard.num_shards == 0
    row = shard.named_sharding(mesh) if shard is not None else rep

    def sampler_rule(leaf):
        if divisible and leaf.ndim >= 1 and leaf.shape[0] == n:
            return row
        return rep

    def metric_rule(leaf):
        if divisible and leaf.ndim >= 2 and leaf.shape[-1] == n:
            spec = jax.sharding.PartitionSpec(
                *([None] * (leaf.ndim - 1)), shard.axis
            )
            return jax.sharding.NamedSharding(mesh, spec)
        return rep

    return TrainState(
        params=jax.tree_util.tree_map(lambda _: rep, template.params),
        opt_state=jax.tree_util.tree_map(lambda _: rep, template.opt_state),
        sampler=jax.tree_util.tree_map(sampler_rule, template.sampler),
        metrics=jax.tree_util.tree_map(metric_rule, template.metrics),
        round=rep,
        key=rep,
        # The fault carry follows the sampler rule: the (N,) Markov
        # availability chain lives split along the sampler's mesh axis, the
        # (B, D) stale-delta buffer (B != N) falls through to replicated.
        faults=jax.tree_util.tree_map(sampler_rule, template.faults),
        # The error-feedback residual is (D,)-shaped — D could coincidentally
        # equal N, so it gets an explicit replicated rule, not sampler_rule.
        compression=jax.tree_util.tree_map(lambda _: rep, template.compression),
    )


def make_segment_fn(
    body,
    derive_step,
    *,
    with_opt_state: bool,
    with_round_index: bool,
    with_faults: bool = False,
    with_compression: bool = False,
    donate: bool = True,
    placement=None,
    data=None,
):
    """The ONE implementation of a jitted scan segment over ``TrainState``.

    Both compiled paths — ``fed.server.build_segment_runner`` and
    ``fed.round.build_fed_scan_segment`` — get their segment function here,
    so the bitwise-neutrality contract (key-chain advance, metric-buffer
    stitch offset, round accounting, donation gating) lives in exactly one
    place.  The returned ``segment(state, n_rounds)`` (jitted, ``n_rounds``
    static):

    1. derives the next ``n_rounds`` key pairs by scanning ``derive_step``
       (one chained-split link, returning ``(key, stacked pair)``) from
       ``state.key``;
    2. scans ``body`` over them — carry ``(params, opt_state, sampler)``
       when ``with_opt_state`` else ``(params, sampler)``, with
       ``state.faults`` appended as a trailing carry element when
       ``with_faults`` (the fault layer's availability chain / stale-delta
       buffer advance inside the scan exactly like the sampler state), and
       ``state.compression`` (the error-feedback residual) appended after
       it when ``with_compression``; xs
       ``(ts, pairs[:, 0], pairs[:, 1])`` with ``ts = round + arange`` when
       ``with_round_index`` else the raw ``pairs``;
    3. stitches the stacked per-round metrics into the full-horizon buffers
       at offset ``state.round`` via ``dynamic_update_slice``;
    4. returns the advanced ``TrainState`` (``round + n_rounds``, new key).

    ``donate=False`` keeps the input state alive across calls (benchmarks
    re-time from the same state; donation would invalidate it on non-CPU
    backends — the CPU backend never donates).

    ``placement`` (a pytree of ``Sharding``s matching ``TrainState``, built
    by the caller when the sampler's (N,) axis is mesh-sharded) makes the
    carry's device layout canonical at the host boundary: every call first
    ``device_put``s the state to that placement.  Without it, the first call
    (uncommitted fresh state) and every later call (committed outputs
    carrying the in-body sharding constraints) present different input
    shardings to the jit cache and the second call pays a full recompile —
    with it, fresh states, carried states, and numpy-round-tripped restores
    all hit the single compiled entry (the compile-once contract,
    ``analysis.lint.audit_compile_once``).  Re-placing an already-placed
    carry is a no-op dispatch, not a copy.

    The stitch offset into the ``(T, ...)`` metric buffers is
    ``round mod T_buf`` — identity for full-horizon buffers (``round < T``,
    so this stays bitwise-neutral), a ring write for shorter host-offload
    buffers (``fed.server`` score-history offload allocates
    ``(ckpt_every, N)`` and drains to host every segment).

    ``data`` (a pytree of arrays, e.g. the ``FederatedDataset``) enters the
    jit as an argument, and ``body`` is then ``body(data) -> scan body``:
    the arrays stay device buffers the program reads instead of constants
    compiled into it (a 10^6-client dataset is gigabytes of constant).

    ``segment.lower(state, n_rounds)`` lowers the program the calls run
    (its ``.compile()`` reuses their executable): ``memory_analysis()``
    and the HLO of what actually ran.
    """
    donate_argnums = (0,) if donate and jax.default_backend() != "cpu" else ()

    @functools.partial(jax.jit, static_argnums=(2,), donate_argnums=donate_argnums)
    def scan_segment(state: TrainState, data, n_rounds: int) -> TrainState:
        scan_body = body if data is None else body(data)
        key, pairs = jax.lax.scan(derive_step, state.key, None, length=n_rounds)
        if with_opt_state:
            carry = (state.params, state.opt_state, state.sampler)
        else:
            carry = (state.params, state.sampler)
        if with_faults:
            carry = carry + (state.faults,)
        if with_compression:
            carry = carry + (state.compression,)
        if with_round_index:
            ts = state.round + jnp.arange(n_rounds, dtype=jnp.int32)
            xs = (ts, pairs[:, 0], pairs[:, 1])
        else:
            xs = pairs
        carry, stacked = jax.lax.scan(scan_body, carry, xs)
        if with_compression:
            carry, c_state = carry[:-1], carry[-1]
        else:
            c_state = state.compression
        if with_faults:
            carry, f_state = carry[:-1], carry[-1]
        else:
            f_state = state.faults
        if with_opt_state:
            params, opt_state, s_state = carry
        else:
            (params, s_state), opt_state = carry, state.opt_state
        metrics = jax.tree_util.tree_map(
            lambda buf, seg: jax.lax.dynamic_update_slice(
                buf,
                seg,
                (jax.lax.rem(state.round, jnp.int32(buf.shape[0])),)
                + (0,) * (buf.ndim - 1),
            ),
            state.metrics,
            stacked,
        )
        return TrainState(
            params=params,
            opt_state=opt_state,
            sampler=s_state,
            metrics=metrics,
            round=state.round + n_rounds,
            key=key,
            faults=f_state,
            compression=c_state,
        )

    lint_info = {
        "body": body,
        "derive_step": derive_step,
        "with_opt_state": with_opt_state,
        "with_round_index": with_round_index,
        "with_faults": with_faults,
        "with_compression": with_compression,
        "donate": donate,
        "donate_argnums": donate_argnums,
        "placement": placement,
    }

    def place(state: TrainState) -> TrainState:
        return state if placement is None else jax.device_put(state, placement)

    calls = itertools.count()

    def segment(state: TrainState, n_rounds: int) -> TrainState:
        call = next(calls)
        with obs.span("train.place", call=call):
            state = place(state)
        with obs.span("train.dispatch", call=call, rounds=n_rounds):
            return scan_segment(state, data, n_rounds)

    segment._cache_size = scan_segment._cache_size
    segment.lower = lambda state, n_rounds: scan_segment.lower(
        place(state), data, n_rounds
    )

    # Lintable handles for the static checkers (repro.analysis.lint):
    # audit_compile_once reads the declared donation setup from here and the
    # jit cache counter from the PjitFunction itself, so the compile-once /
    # donation contract is checkable without re-deriving how the segment was
    # built.
    segment._lint = lint_info
    return segment


def run_segmented(
    state: TrainState,
    total_rounds: int,
    segment_fn: Callable[[TrainState, int], TrainState],
    *,
    ckpt_every: int = 0,
    manager=None,
    on_segment: Callable[[TrainState, int], None] | None = None,
    max_segments: int | None = None,
    publish: Callable[[TrainState, int], None] | None = None,
) -> TrainState:
    """Host-driven loop over jitted scan segments of ``ckpt_every`` rounds.

    Starts from ``state.round`` (0 for a fresh state, later for one restored
    from a checkpoint) and calls ``segment_fn(state, n_rounds)`` — a function
    jitted with a *static* segment length — until ``total_rounds`` is reached.
    ``ckpt_every <= 0`` runs the remainder as ONE segment (the monolithic
    scan, now merely the degenerate segmentation).

    After each segment, in order: ``manager.save(state, step=rounds_done)``
    publishes a checkpoint (atomic npz + manifest — the manifest write is the
    commit point), then ``publish(state, rounds_done)`` announces the
    boundary, then ``on_segment(state, rounds_done)`` runs (progress
    printing, cooperative-preemption hooks).  ``max_segments`` stops the loop
    early after that many segments — cooperative preemption for time-limited
    schedulers, and what the resume tests use to simulate a mid-horizon kill.

    ``publish`` is the train side of the train-to-serve loop
    (``repro.serve``): because it fires strictly AFTER the manifest commit,
    a serving process notified at (or polling around) that moment is
    guaranteed to observe the step via ``CheckpointManager.wait_for_next`` —
    the hook requires ``manager`` (without one there is no committed
    artifact to announce).

    Returns the final (or preempted) state; ``int(state.round)`` tells the
    caller how far it got.
    """
    if publish is not None and manager is None:
        raise ValueError(
            "run_segmented(publish=...) requires a manager: the publish hook "
            "announces COMMITTED checkpoint boundaries, and only the "
            "manager's manifest write commits one"
        )
    done = int(state.round)
    if done > total_rounds:
        raise ValueError(
            f"state.round={done} is past the horizon total_rounds={total_rounds}"
        )
    seg = int(ckpt_every) if ckpt_every and ckpt_every > 0 else int(total_rounds)
    n_segments = 0
    while done < total_rounds:
        n = min(seg, total_rounds - done)
        state = segment_fn(state, n)
        done += n
        if manager is not None:
            with obs.span("train.ckpt_save", step=done):
                manager.save(state, step=done)
            if publish is not None:
                with obs.span("train.publish", step=done):
                    publish(state, done)
        if on_segment is not None:
            on_segment(state, done)
        n_segments += 1
        if max_segments is not None and n_segments >= max_segments:
            break
    return state
