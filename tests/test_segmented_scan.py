"""The segmented compiled horizon must be a pure reshaping of the monolithic
scan: for ANY ``ckpt_every`` the per-round bodies see the same carries, keys,
and round indices, so params, sampler state, and ``History`` are bitwise
identical — and a segment boundary is a preemption-safe escape hatch where the
canonical ``TrainState`` round-trips through a ``CheckpointManager`` and a
restarted process continues the run exactly.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager
from repro.core import make_sampler
from repro.data import synthetic_classification, synthetic_tokens
from repro.fed import (
    FedConfig,
    build_segment_runner,
    logistic_regression,
    run_federated,
    run_segmented,
)


@pytest.fixture(scope="module")
def tiny_ds():
    return synthetic_classification(n_clients=12, total=600, seed=7)


def _histories_equal(a, b):
    assert a.train_loss == b.train_loss
    assert a.cohort_size == b.cohort_size
    assert a.cohort_dropped == b.cohort_dropped
    assert a.estimator_sq_error == b.estimator_sq_error
    assert a.test_accuracy == b.test_accuracy
    assert a.rounds == b.rounds
    if a.regret is not None and a.regret.costs:
        assert a.regret.costs == b.regret.costs
        assert a.regret.opt_costs == b.regret.opt_costs
        if a.regret.score_history:
            np.testing.assert_array_equal(
                np.stack(a.regret.score_history), np.stack(b.regret.score_history)
            )
    for x, y in zip(
        jax.tree_util.tree_leaves(a.final_params),
        jax.tree_util.tree_leaves(b.final_params),
    ):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _run(ds, name, **cfg_kw):
    cfg = FedConfig(
        rounds=10, budget=4, local_steps=2, batch_size=16, local_lr=0.05, seed=11,
        **cfg_kw,
    )
    sampler = make_sampler(
        name, n=ds.n_clients, budget=cfg.budget,
        **({"horizon": cfg.rounds} if name in ("kvib", "vrb") else {}),
    )
    ev = ds.batch_all_clients(jax.random.PRNGKey(99), 4)
    ev = (ev[0].reshape(-1, ev[0].shape[-1]), ev[1].reshape(-1))
    return run_federated(logistic_regression(), ds, sampler, cfg, eval_data=ev)


@pytest.mark.parametrize("ckpt_every", [1, 7, 10])
def test_segmented_bitwise_identical_to_monolithic(tiny_ds, ckpt_every):
    """Acceptance: ckpt_every in {1, 7, T} reproduces the monolithic scan's
    params, sampler-driven draws, metric buffers, and eval schedule exactly
    (T=10: segmentations of 10x1, 7+3, and the degenerate single segment)."""
    h_mono = _run(tiny_ds, "kvib", ckpt_every=0)
    h_seg = _run(tiny_ds, "kvib", ckpt_every=ckpt_every)
    _histories_equal(h_seg, h_mono)


@pytest.mark.parametrize("name", ["vrb", "uniform_rsp"])
def test_segmented_identity_rsp_procedures(tiny_ds, name):
    """The identity holds across sampling procedures (RSP draw paths have
    their own key-consumption pattern inside the body)."""
    _histories_equal(
        _run(tiny_ds, name, ckpt_every=3), _run(tiny_ds, name, ckpt_every=0)
    )


def test_segmented_identity_deployable_cohort(tiny_ds):
    """Deployable mode (cohort-only training, C-width aggregation, overflow
    drops) is segmentation-invariant too — including the dropped counters."""
    kw = dict(oracle_metrics=False, cohort=4)
    _histories_equal(
        _run(tiny_ds, "kvib", ckpt_every=3, **kw),
        _run(tiny_ds, "kvib", ckpt_every=0, **kw),
    )


def test_segment_runner_state_advances(tiny_ds):
    """The TrainState carry advances round/key and stitches metric buffers
    in place: after k rounds, exactly the first k buffer slots are written."""
    cfg = FedConfig(rounds=6, budget=4, local_steps=1, batch_size=16, seed=3)
    sampler = make_sampler("kvib", n=tiny_ds.n_clients, budget=4, horizon=6)
    segment, state0 = build_segment_runner(
        logistic_regression(), tiny_ds, sampler, cfg
    )
    assert int(state0.round) == 0
    st = segment(state0, 2)
    assert int(st.round) == 2
    assert not np.array_equal(np.asarray(st.key), np.asarray(state0.key))
    loss = np.asarray(st.metrics["train_loss"])
    assert loss.shape == (6,)
    assert np.all(loss[:2] != 0.0) and np.all(loss[2:] == 0.0)
    st = segment(st, 4)
    assert int(st.round) == 6
    assert np.all(np.asarray(st.metrics["train_loss"]) != 0.0)


def test_segment_takes_dataset_as_argument(tiny_ds):
    """The round program reads the client data from its arguments: no
    dataset array is a constant of the program, and ``run_federated``'s
    ``History.segment`` lowers the program that ran without compiling it
    again."""
    cfg = FedConfig(rounds=4, budget=4, local_steps=1, batch_size=16, seed=3,
                    oracle_metrics=False, cohort=4)
    sampler = make_sampler("kvib", n=tiny_ds.n_clients, budget=4, horizon=4)
    hist = run_federated(logistic_regression(), tiny_ds, sampler, cfg)
    segment = hist.segment
    assert segment._cache_size() == 1
    _, state = build_segment_runner(logistic_regression(), tiny_ds, sampler, cfg)
    lowered = segment.lower(state, cfg.rounds)
    text = lowered.as_text()
    feats = "x".join(str(d) for d in tiny_ds.features.shape)
    assert f"tensor<{feats}xf32>" in text  # a parameter of the program
    assert not any(
        "stablehlo.constant" in line and feats in line
        for line in text.splitlines()
    )
    assert lowered.compile().memory_analysis() is not None
    assert segment._cache_size() == 1


def test_preempt_checkpoint_resume_bitwise(tiny_ds, tmp_path):
    """Preemption simulation, in-process: run 2 of 5 segments with a manager,
    'restart' by restoring the latest committed step into a fresh template,
    finish the horizon, and compare the FULL TrainState — params, sampler
    state, every metric buffer slot (including pre-preemption rounds), round
    index, and RNG key — bitwise against an uninterrupted run."""
    cfg = FedConfig(rounds=10, budget=4, local_steps=1, batch_size=16, seed=5,
                    ckpt_every=2)
    task = logistic_regression()

    def runner():
        sampler = make_sampler("kvib", n=tiny_ds.n_clients, budget=4, horizon=10)
        return build_segment_runner(task, tiny_ds, sampler, cfg)

    segment, state0 = runner()
    full = run_segmented(state0, cfg.rounds, segment, ckpt_every=cfg.ckpt_every)

    mgr = CheckpointManager(str(tmp_path / "ck"), keep_last=2)
    segment_b, state0_b = runner()
    preempted = run_segmented(
        state0_b, cfg.rounds, segment_b, ckpt_every=cfg.ckpt_every,
        manager=mgr, max_segments=2,
    )
    assert int(preempted.round) == 4
    assert mgr.latest() == 4

    # "process restart": fresh template, fresh jitted segment, restore.
    segment_c, template = runner()
    restored, step = mgr.restore_or_init(template)
    assert step == 4 and int(restored.round) == 4
    resumed = run_segmented(
        restored, cfg.rounds, segment_c, ckpt_every=cfg.ckpt_every, manager=mgr
    )
    assert int(resumed.round) == cfg.rounds
    for a, b in zip(
        jax.tree_util.tree_leaves(resumed), jax.tree_util.tree_leaves(full)
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_run_federated_resumes_from_manager(tiny_ds, tmp_path):
    """run_federated(ckpt_manager=...) end to end: a run preempted at the
    driver level and re-invoked with the same manager yields the identical
    History as a never-interrupted run — including pre-preemption rounds."""
    cfg = FedConfig(rounds=8, budget=4, local_steps=1, batch_size=16, seed=5,
                    ckpt_every=3)
    task = logistic_regression()

    def sampler():
        return make_sampler("kvib", n=tiny_ds.n_clients, budget=4, horizon=8)

    h_full = run_federated(task, tiny_ds, sampler(), cfg)

    # Preempt: run only the first segment (3 rounds) with a manager.
    mgr = CheckpointManager(str(tmp_path / "ck"))
    segment, state0 = build_segment_runner(task, tiny_ds, sampler(), cfg)
    run_segmented(state0, cfg.rounds, segment, ckpt_every=cfg.ckpt_every,
                  manager=mgr, max_segments=1)
    assert mgr.latest() == 3

    h_resumed = run_federated(task, tiny_ds, sampler(), cfg, ckpt_manager=mgr)
    _histories_equal(h_resumed, h_full)
    assert mgr.latest() == 8


def test_run_federated_rejects_manager_without_segments(tiny_ds, tmp_path):
    """A manager with ckpt_every=0 would publish nothing before the final
    round — a silent no-protection configuration; it must raise instead."""
    cfg = FedConfig(rounds=4, budget=2, local_steps=1, batch_size=8)
    sampler = make_sampler("uniform_isp", n=tiny_ds.n_clients, budget=2)
    with pytest.raises(ValueError, match="ckpt_every"):
        run_federated(
            logistic_regression(), tiny_ds, sampler, cfg,
            ckpt_manager=CheckpointManager(str(tmp_path / "ck")),
        )


def test_fed_scan_segment_matches_monolithic():
    """fed/round.py: the segment-shaped pod-scale scan reproduces the
    monolithic build_fed_scan bitwise for ckpt_every in {1, 2, T} — identical
    key chain (in-trace derivation == host-side stacking), identical round
    bodies, identical metric values."""
    from repro.configs import get_config
    from repro.fed.round import RoundSpec, build_fed_scan, build_fed_scan_segment

    cfg = get_config("smollm-360m").reduced(n_layers=2, d_model=64, d_ff=128, vocab=128)
    ds = synthetic_tokens(n_clients=8, seq_len=16, vocab=cfg.vocab, total_seqs=256, seed=3)
    spec = RoundSpec(cohort=3, local_steps=2, local_lr=0.05, local_batch=2)
    sampler = make_sampler("kvib", n=ds.n_clients, budget=2, horizon=4)
    rounds = 4

    from repro.models import transformer

    key = jax.random.PRNGKey(5)
    params0 = transformer.init_params(cfg, key)

    # Monolithic reference: host-derived key pairs, one scan.
    k = key
    pairs = []
    for _ in range(rounds):
        k, k_draw, k_data = jax.random.split(k, 3)
        pairs.append(jnp.stack([k_draw, k_data]))
    run = build_fed_scan(cfg, spec, sampler, ds)
    p_mono, s_mono, m_mono = run(
        jax.tree_util.tree_map(jnp.copy, params0), sampler.init(), jnp.stack(pairs)
    )

    segment, make_state = build_fed_scan_segment(cfg, spec, sampler, ds)
    for every in (1, 2, rounds):
        state = make_state(
            jax.tree_util.tree_map(jnp.copy, params0), sampler.init(), key, rounds
        )
        state = run_segmented(state, rounds, segment, ckpt_every=every)
        assert int(state.round) == rounds
        for name, ref in m_mono.items():
            np.testing.assert_array_equal(
                np.asarray(state.metrics[name]), np.asarray(ref), err_msg=name
            )
        for a, b in zip(
            jax.tree_util.tree_leaves(state.params), jax.tree_util.tree_leaves(p_mono)
        ):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(
            jax.tree_util.tree_leaves(state.sampler), jax.tree_util.tree_leaves(s_mono)
        ):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
