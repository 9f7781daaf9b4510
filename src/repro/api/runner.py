"""``run(spec)``: the one facade over both execution stacks.

``build(spec)`` resolves an ``ExperimentSpec``'s registry names into the
concrete objects the stacks consume — task/arch config, federated dataset,
sampler, ``FedConfig``/``RoundSpec`` — and ``run(spec)`` dispatches:

* ``task.kind == "task"`` — the simulation stack:
  ``fed.server.run_federated(task, dataset, sampler, fed_config)``.  The
  spec layer builds the identical objects the legacy call takes, so the two
  entry points are bitwise-equal (tests/test_api_spec.py golden tests).
* ``task.kind == "zoo"`` — the pod-scale compiled stack:
  ``fed.round.build_fed_scan_segment`` on the host mesh, driven by
  ``fed.state.run_segmented`` — the same construction (and key stream) as
  ``repro.launch.train --compiled``.

Both paths accept a ``repro.checkpoint.CheckpointManager`` whose manifest
fingerprint should be ``config_fingerprint(spec.to_dict())`` — the spec IS
the run configuration, so resuming under a changed spec raises.
``restore_template(spec)`` exposes the matching restore template (the fresh
round-0 ``TrainState``) for out-of-band checkpoint surgery.
"""
from __future__ import annotations

import dataclasses
import json
import time
from typing import Any

import jax
import numpy as np

from repro.api.spec import (
    ExperimentSpec,
    _dataset_registry,
    _task_registry,
    dataset_names,
    task_names,
)
from repro.core.samplers import make_sampler
from repro.fed.server import FedConfig, History, build_segment_runner, run_federated

__all__ = ["BuiltExperiment", "build", "run", "restore_template"]


# Dataset construction is memoized per process: sweeps (budget grids, sampler
# panels) re-reference the identical (factory, kwargs) cell many times, and
# the factories are deterministic pure functions of their kwargs (the
# register_dataset contract), so rebuilding the arrays is pure waste.  The
# cache is tiny — a sweep touches one or two datasets at a time.
_DATASET_CACHE: dict = {}
_DATASET_CACHE_MAX = 4


def _build_dataset(name: str, factory, kwargs: dict):
    key = (name, id(factory), json.dumps(kwargs, sort_keys=True, default=repr))
    if key not in _DATASET_CACHE:
        if len(_DATASET_CACHE) >= _DATASET_CACHE_MAX:
            _DATASET_CACHE.pop(next(iter(_DATASET_CACHE)))
        _DATASET_CACHE[key] = factory(**kwargs)
    return _DATASET_CACHE[key]


@dataclasses.dataclass(frozen=True)
class BuiltExperiment:
    """The resolved pieces of one spec; which fields are set depends on kind.

    kind="task": ``task`` (fed.tasks.Task), ``dataset``, ``sampler``,
    ``fed_config`` — exactly the legacy ``run_federated`` argument tuple.
    kind="zoo": ``arch_config`` (models.common.ArchConfig), ``dataset``,
    ``sampler``, ``round_spec`` — the ``launch.train`` construction set.
    """

    spec: ExperimentSpec
    kind: str
    dataset: Any
    sampler: Any
    task: Any = None  # simulation Task (kind="task")
    fed_config: FedConfig | None = None  # kind="task"
    arch_config: Any = None  # kind="zoo"
    round_spec: Any = None  # kind="zoo"


def _sampler_shard(spec: ExperimentSpec):
    """The ``ShardSpec`` that ``spec.execution.sampler_axis`` denotes (or
    ``None``): the sampler's (N,)-axis layout over the run's mesh — the same
    mesh ``_make_mesh`` hands the zoo stack, so one ``sampler_axis`` switch
    shards the solve/draw/update on both stacks."""
    axis = spec.execution.sampler_axis
    if axis is None:
        return None
    from repro.launch.mesh import ShardSpec

    return ShardSpec.from_mesh(_make_mesh(spec), axis=axis)


def _build_task(spec: ExperimentSpec) -> BuiltExperiment:
    tasks = _task_registry()
    if spec.task.name not in tasks:
        raise ValueError(
            f"unknown task {spec.task.name!r}; registered: {task_names()} "
            "(repro.api.register_task adds custom factories)"
        )
    datasets = _dataset_registry()
    if spec.task.dataset not in datasets:
        raise ValueError(
            f"unknown dataset {spec.task.dataset!r}; registered: {dataset_names()} "
            "(repro.api.register_dataset adds custom factories)"
        )
    task = tasks[spec.task.name](**dict(spec.task.kwargs))
    ds = _build_dataset(
        spec.task.dataset,
        datasets[spec.task.dataset],
        dict(spec.task.dataset_kwargs),
    )
    sampler = make_sampler(
        spec.sampler.name,
        n=ds.n_clients,
        budget=spec.federation.budget,
        shard=_sampler_shard(spec),
        **dict(spec.sampler.kwargs),
    )
    return BuiltExperiment(
        spec=spec,
        kind="task",
        dataset=ds,
        sampler=sampler,
        task=task,
        fed_config=spec.fed_config(),
    )


def _build_zoo(spec: ExperimentSpec) -> BuiltExperiment:
    from repro.configs import get_config, list_archs
    from repro.configs.registry import has_arch

    if not has_arch(spec.task.name):
        raise ValueError(
            f"unknown zoo arch {spec.task.name!r}; options: {list_archs()}"
        )
    cfg = get_config(spec.task.name)
    if spec.task.reduced:
        cfg = cfg.reduced(**dict(spec.task.kwargs))

    datasets = _dataset_registry()
    if spec.task.dataset not in datasets:
        raise ValueError(
            f"unknown dataset {spec.task.dataset!r}; registered: {dataset_names()}"
        )
    ds_kw = dict(spec.task.dataset_kwargs)
    if spec.task.dataset == "synthetic_tokens":
        # The launcher's defaults: vocab from the arch, seed from the run
        # seed, total_seqs sized to the client count.
        ds_kw.setdefault("vocab", cfg.vocab)
        ds_kw.setdefault("seed", spec.execution.seed)
        if "n_clients" in ds_kw:
            ds_kw.setdefault("total_seqs", max(32 * int(ds_kw["n_clients"]), 512))
    ds = _build_dataset(spec.task.dataset, datasets[spec.task.dataset], ds_kw)

    sampler = make_sampler(
        spec.sampler.name,
        n=ds.n_clients,
        budget=spec.federation.budget,
        shard=_sampler_shard(spec),
        **dict(spec.sampler.kwargs),
    )
    fed = spec.federation
    if fed.cohort is None:
        fed = dataclasses.replace(
            fed, cohort=max(1, min(2 * fed.budget, ds.n_clients))
        )
        spec = dataclasses.replace(spec, federation=fed)
    return BuiltExperiment(
        spec=spec,
        kind="zoo",
        dataset=ds,
        sampler=sampler,
        arch_config=cfg,
        round_spec=spec.round_spec(),
    )


def build(spec: ExperimentSpec) -> BuiltExperiment:
    """Resolve a spec's registry names into the concrete experiment objects.

    Pure construction — no training, no device state beyond dataset arrays.
    ``run(spec, built=...)`` accepts the result so drivers that need the
    dataset up front (e.g. to derive eval batches) build exactly once."""
    if spec.task.kind == "zoo":
        return _build_zoo(spec)
    return _build_task(spec)


def _specs_compatible(a: ExperimentSpec, b: ExperimentSpec) -> bool:
    """Equality modulo the one build-time resolution: ``cohort=None`` may
    have been replaced by its concrete default in a built spec."""
    fa, fb = a.federation, b.federation
    if fa.cohort is None or fb.cohort is None:
        fa = dataclasses.replace(fa, cohort=None)
        fb = dataclasses.replace(fb, cohort=None)
    return (a.task, a.sampler, fa, a.execution, a.fault, a.compression, a.serve) == (
        b.task,
        b.sampler,
        fb,
        b.execution,
        b.fault,
        b.compression,
        b.serve,
    )


def _make_mesh(spec: ExperimentSpec):
    from repro.launch.mesh import make_host_mesh, make_mesh

    shape = spec.execution.mesh_shape
    if shape is None:
        return make_host_mesh()
    axes = ("pod", "data", "model") if len(shape) == 3 else ("data", "model")
    return make_mesh(shape, axes)


def _zoo_segment_and_state(built: BuiltExperiment):
    """(segment_fn, round-0 TrainState) for the zoo stack — the identical
    construction (and chain-key reuse) as ``repro.launch.train --compiled``."""
    from repro.fed.round import build_fed_scan_segment
    from repro.models import transformer

    spec = built.spec
    key = jax.random.PRNGKey(spec.execution.seed)
    params = transformer.init_params(built.arch_config, key)
    segment, make_state = build_fed_scan_segment(
        built.arch_config,
        built.round_spec,
        built.sampler,
        built.dataset,
        mesh=_make_mesh(spec),
    )
    state = make_state(params, built.sampler.init(), key, spec.federation.rounds)
    return segment, state


def _run_zoo(built: BuiltExperiment, ckpt_manager, publish=None) -> History:
    from repro.fed.state import run_segmented

    spec = built.spec
    t0 = time.time()
    ckpt_every = spec.execution.ckpt_every
    if ckpt_manager is not None and ckpt_every <= 0:
        raise ValueError(
            "run(spec, ckpt_manager=...) needs execution.ckpt_every > 0; "
            f"got ckpt_every={ckpt_every}"
        )
    segment, state = _zoo_segment_and_state(built)
    if ckpt_manager is not None:
        state, _ = ckpt_manager.restore_or_init(state)
    state = run_segmented(
        state,
        spec.federation.rounds,
        segment,
        ckpt_every=ckpt_every,
        manager=ckpt_manager,
        publish=publish,
    )
    jax.block_until_ready(state)

    params = state.params
    fault = spec.fault
    if fault.enabled and int(fault.async_buffer) > 0:
        # End-of-horizon flush of still-pending stale deltas (mid-run segment
        # boundaries keep the buffer in the carry — core.stragglers).
        from repro.core import stragglers

        buf = state.faults["buf"]
        if np.asarray(buf["valid"]).any():
            pending = stragglers.flush_pending(
                buf, spec.federation.rounds, float(fault.staleness_discount)
            )
            d_pend = stragglers.vec_to_tree(pending, params)
            params = jax.tree_util.tree_map(lambda p, g: p - g, params, d_pend)

    hist = History()
    hist.rounds = list(range(spec.federation.rounds))
    hist.train_loss = [float(x) for x in np.asarray(state.metrics["loss"])]
    hist.cohort_size = [int(x) for x in np.asarray(state.metrics["cohort_size"])]
    hist.cohort_dropped = [int(x) for x in np.asarray(state.metrics["dropped"])]
    if "deadline_dropped" in state.metrics:
        hist.deadline_dropped = [
            int(x) for x in np.asarray(state.metrics["deadline_dropped"])
        ]
    hist.final_params = jax.tree_util.tree_map(np.asarray, params)
    hist.segment = segment
    hist.wall_time_s = time.time() - t0
    return hist


def run(
    spec: ExperimentSpec,
    *,
    eval_data: tuple | None = None,
    ckpt_manager=None,
    built: BuiltExperiment | None = None,
    publish=None,
) -> History:
    """Execute a spec end to end; the one front door for both stacks.

    ``eval_data`` — optional (x, y) evaluation batch for the simulation
    stack's accuracy curve (``FederationSpec.eval_every`` schedule).
    ``ckpt_manager`` — a ``repro.checkpoint.CheckpointManager``: restore-or-
    init before running, publish the full ``TrainState`` at every
    ``execution.ckpt_every`` segment boundary.  Its fingerprint should be
    ``config_fingerprint(spec.to_dict())``.
    ``built`` — a prior ``build(spec)`` result to reuse (must be from an
    equal spec).
    ``publish`` — ``(state, rounds_done)`` callback fired after each
    boundary's manifest commit (zoo stack; needs ``ckpt_manager``): the
    train side of the ``repro.serve`` hand-off."""
    if built is None:
        built = build(spec)
    elif not _specs_compatible(built.spec, spec):
        raise ValueError("run(built=...) got a BuiltExperiment from a different spec")
    if ckpt_manager is not None and getattr(ckpt_manager, "layout", None) is None:
        # Record the run's sampler (N,)-axis layout in the manifest
        # (provenance only — restore never validates it).
        ckpt_manager.layout = built.sampler.shard
    if built.kind == "zoo":
        if eval_data is not None:
            raise ValueError(
                "eval_data is only supported on the simulation stack "
                "(kind='task'); the zoo stack's metrics are train loss / "
                "cohort size / drops"
            )
        return _run_zoo(built, ckpt_manager, publish)
    if publish is not None:
        raise ValueError(
            "run(spec, publish=...) is a zoo-stack feature (kind='zoo'): "
            "the serve hand-off follows the segmented TrainState manager"
        )
    return run_federated(
        built.task,
        built.dataset,
        built.sampler,
        built.fed_config,
        eval_data=eval_data,
        ckpt_manager=ckpt_manager,
    )


def restore_template(
    spec: ExperimentSpec, *, built: BuiltExperiment | None = None
):
    """The fresh round-0 ``TrainState`` a checkpoint of this spec restores
    into (``CheckpointManager.restore(template)``) — for either stack.

    ``run(spec, ckpt_manager=...)`` constructs this internally; it is exposed
    for out-of-band checkpoint inspection/surgery."""
    if built is None:
        built = build(spec)
    if built.kind == "zoo":
        _, state = _zoo_segment_and_state(built)
        return state
    cfg = built.fed_config
    if not cfg.compiled:
        raise ValueError(
            "restore templates exist only for the compiled execution path "
            "(execution.compiled=False has no checkpointable TrainState)"
        )
    _, state = build_segment_runner(
        built.task, built.dataset, built.sampler, cfg, None
    )
    return state
