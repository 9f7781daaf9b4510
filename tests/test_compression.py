"""Compressed client deltas: quantization properties, the fused
dequant-aggregate kernel vs its jnp oracle (interpret=True on CPU),
error-feedback convergence, spec plumbing, and the segmented/resume
contract under int8 delta width."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import CompressionSpec, ExperimentSpec
from repro.core import estimator, make_sampler, sampler_names
from repro.data import synthetic_classification
from repro.fed import FedConfig, logistic_regression, run_federated
from repro.kernels.fused_weighted_agg import (
    _QMAX,
    dequant_cohort_agg_reference,
    dequantize_stacked,
    fused_dequant_cohort_agg,
    quantize_stacked,
)

DTYPES = ["int8", "fp8"]


@pytest.fixture(scope="module")
def tiny_ds():
    return synthetic_classification(n_clients=12, total=600, seed=7)


# ---------------------------------------------------------------- quantizer


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("c,d,sb", [(4, 640, 128), (7, 123, 128), (3, 256, 64)])
def test_quantize_roundtrip_error_bound(dtype, c, d, sb):
    """Blockwise symmetric quantization: padded shapes, per-block fp32
    scales, and a per-element reconstruction error bounded by the block's
    quantization step."""
    flat = jax.random.normal(jax.random.PRNGKey(c * d), (c, d), jnp.float32) * 3.0
    q, scales = quantize_stacked(flat, dtype=dtype, scale_block=sb)
    nb = -(-d // sb)
    assert q.shape == (c, nb * sb) and scales.shape == (c, nb)
    assert scales.dtype == jnp.float32
    assert np.all(np.asarray(scales) > 0)
    deq = np.asarray(dequantize_stacked(q, scales))
    # padding region dequantizes to exact zero
    assert np.array_equal(deq[:, d:], np.zeros((c, nb * sb - d), np.float32))
    err = np.abs(deq[:, :d] - np.asarray(flat))
    step = np.repeat(np.asarray(scales), sb, axis=1)[:, :d]
    if dtype == "int8":
        # round-to-nearest on a scale-wide grid: error <= scale/2 everywhere
        assert np.all(err <= step / 2 + 1e-7)
    else:
        # fp8 e4m3: 3 mantissa bits -> relative error <= 2**-4 of the block max
        assert np.all(err <= step * _QMAX["fp8"] * 2**-4 + 1e-7)


def test_quantize_zero_rows_and_saturation():
    """All-zero slots quantize to zero with the safe scale 1.0 (no NaN/inf on
    dequant), and block abs-max values land exactly on the saturation code."""
    flat = jnp.zeros((2, 256), jnp.float32)
    flat = flat.at[1, 3].set(5.0)
    q, scales = quantize_stacked(flat, dtype="int8", scale_block=128)
    assert np.asarray(scales)[0].tolist() == [1.0, 1.0]
    assert int(np.abs(np.asarray(q)).max()) == 127
    deq = np.asarray(dequantize_stacked(q, scales))
    assert np.all(np.isfinite(deq))
    np.testing.assert_allclose(deq[1, 3], 5.0, rtol=1e-6)
    assert np.array_equal(deq[0], np.zeros(256, np.float32))


# ------------------------------------------------------------ fused kernel


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize(
    "c,d,sb,bd",
    [
        (4, 4096, 128, 1024),
        (3, 2048, 128, 2048),
        (8, 1024, 64, 256),
        (2, 512, 128, 512),
    ],
)
def test_fused_dequant_agg_matches_reference(dtype, c, d, sb, bd):
    """The Pallas kernel (interpret=True) and the jnp oracle are the same
    computation: estimate chunk, squared-error scalar, and per-slot
    dequantized squared norms all agree to f32 accumulation tolerance."""
    key = jax.random.PRNGKey(hash((c, d, sb)) % 2**31)
    ks = jax.random.split(key, 3)
    flat = jax.random.normal(ks[0], (c, d), jnp.float32)
    q, scales = quantize_stacked(flat, dtype=dtype, scale_block=sb)
    w = jax.random.uniform(ks[1], (c,), jnp.float32, 0.1, 2.0)
    lam = jax.random.uniform(ks[2], (c,), jnp.float32, 0.0, 0.3)
    got = fused_dequant_cohort_agg(q, scales, w, lam, block_d=bd, interpret=True)
    want = dequant_cohort_agg_reference(q, scales, w, lam)
    for g, r in zip(got, want):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(r), rtol=1e-5, atol=1e-5
        )


def test_fused_dequant_agg_close_to_f32_aggregate():
    """End to end, the compressed estimate tracks the uncompressed weighted
    sum within the blockwise quantization error budget."""
    c, d = 5, 2048
    flat = jax.random.normal(jax.random.PRNGKey(0), (c, d), jnp.float32)
    w = jnp.linspace(0.2, 1.4, c)
    q, scales = quantize_stacked(flat, dtype="int8", scale_block=128)
    d_hat, _, sqn = fused_dequant_cohort_agg(
        q, scales, w, jnp.zeros((c,)), block_d=512, interpret=True
    )
    d_true = np.asarray(w @ flat)
    np.testing.assert_allclose(np.asarray(d_hat), d_true, atol=0.05, rtol=0.05)
    true_norms = np.linalg.norm(np.asarray(flat), axis=1)
    np.testing.assert_allclose(np.sqrt(np.asarray(sqn)), true_norms, rtol=0.01)


def test_aggregate_compressed_error_feedback_residual():
    """aggregate_compressed carries the exact quantization error: the applied
    update is d_hat + resid_in and the returned residual is d_true - d_hat,
    so consecutive rounds telescope."""
    c, d = 4, 300
    flat = jax.random.normal(jax.random.PRNGKey(3), (c, d), jnp.float32)
    updates = {"w": flat.reshape(c, 30, 10)}
    w = jnp.linspace(0.5, 1.5, c)
    lam = jnp.full((c,), 0.25)
    comp = CompressionSpec(delta_dtype="int8")
    resid_in = jax.random.normal(jax.random.PRNGKey(4), (d,), jnp.float32) * 0.01
    agg, sq, norms, new_resid = estimator.aggregate_compressed(
        updates, w, lam, comp, resid_in
    )
    d_true = np.asarray(w @ flat)
    applied = np.asarray(agg["w"]).reshape(-1)
    # applied - resid_in is the raw dequantized estimate; adding back the
    # returned residual must reconstruct the exact f32 aggregate
    d_hat = applied - np.asarray(resid_in)
    np.testing.assert_allclose(
        d_hat + np.asarray(new_resid), d_true, rtol=1e-5, atol=1e-5
    )
    assert np.asarray(new_resid).shape == (d,)
    assert float(sq) >= 0.0
    np.testing.assert_allclose(
        np.asarray(norms), np.linalg.norm(flat, axis=1), rtol=0.01
    )


# ------------------------------------------------------------ spec plumbing


def test_compression_spec_roundtrip_and_old_json():
    from repro.api import FederationSpec

    spec = ExperimentSpec(
        federation=FederationSpec(cohort=4),
        compression=CompressionSpec(delta_dtype="int8"),
    )
    d = spec.to_dict()
    assert d["compression"]["delta_dtype"] == "int8"
    back = ExperimentSpec.from_dict(d)
    assert back.compression == spec.compression
    # pre-compression JSONs have no "compression" section -> default disabled
    legacy = spec.to_dict()
    del legacy["compression"]
    old = ExperimentSpec.from_dict(legacy)
    assert old.compression == CompressionSpec()
    assert not old.compression.enabled
    assert old.fed_config().compression is None
    assert old.round_spec().compression is None


def test_compression_spec_validation():
    with pytest.raises(ValueError):
        CompressionSpec(delta_dtype="int4")
    with pytest.raises(ValueError):
        CompressionSpec(delta_dtype="int8", scale_block=0)
    assert not CompressionSpec().enabled
    assert CompressionSpec(delta_dtype="int8").enabled


def test_exact_oracle_equiv_rejects_compression(tiny_ds):
    from repro.fed import server as fed_server

    cfg = FedConfig(
        rounds=2, budget=4, local_steps=1, batch_size=16, seed=0,
        oracle_metrics=False, exact_oracle_equiv=True,
        compression=CompressionSpec(delta_dtype="int8"),
    )
    sampler = make_sampler("uniform_isp", n=tiny_ds.n_clients, budget=4)
    with pytest.raises(ValueError, match="exact_oracle_equiv"):
        run_federated(logistic_regression(), tiny_ds, sampler, cfg)


# ----------------------------------------------------- federated behaviour


def _run(ds, name, rounds=6, compiled=True, **cfg_kw):
    cfg = FedConfig(
        rounds=rounds, budget=4, local_steps=2, batch_size=16, local_lr=0.05,
        seed=11, compiled=compiled, **cfg_kw,
    )
    sampler = make_sampler(
        name, n=ds.n_clients, budget=cfg.budget,
        **({"horizon": cfg.rounds} if name in ("kvib", "vrb") else {}),
    )
    return run_federated(logistic_regression(), ds, sampler, cfg)


@pytest.mark.parametrize("name", sampler_names())
def test_feedback_norms_tolerance_registry_sweep(tiny_ds, name):
    """Registry sweep: with int8 deltas every sampler's feedback signal (the
    dequantized norms driving its score updates) stays within quantization
    tolerance of the f32 run.  Round-1 cohorts are identical (feedback has
    not entered yet), so the post-feedback scores are directly comparable."""
    h32 = _run(tiny_ds, name, rounds=2)
    h8 = _run(tiny_ds, name, rounds=2,
              compression=CompressionSpec(delta_dtype="int8"))
    s32 = np.stack(h32.regret.score_history)
    s8 = np.stack(h8.regret.score_history)
    assert s32.shape == s8.shape
    np.testing.assert_allclose(s8, s32, rtol=0.05, atol=1e-4)
    # losses diverge only by the quantization perturbation
    np.testing.assert_allclose(
        np.asarray(h8.train_loss), np.asarray(h32.train_loss), rtol=0.02, atol=5e-3
    )


def test_error_feedback_recovers_f32_loss(tiny_ds):
    """The acceptance bound: int8 + error feedback lands allclose to the f32
    final loss (the residual telescopes, leaving one round's error), while
    disabling EF accumulates a random walk that is measurably worse.

    The walk is measured where it lives, in the parameters: near the optimum
    the loss is flat to first order, so a final-loss gap understates the
    drift and sits within noise of the EF run's one-round error (about 2x at
    25 rounds).  Over 100 rounds the walk has had time to accumulate."""
    rounds = 100
    h32 = _run(tiny_ds, "uniform_isp", rounds=rounds)
    h_ef = _run(tiny_ds, "uniform_isp", rounds=rounds,
                compression=CompressionSpec(delta_dtype="int8"))
    h_no = _run(tiny_ds, "uniform_isp", rounds=rounds,
                compression=CompressionSpec(delta_dtype="int8",
                                            error_feedback=False))
    np.testing.assert_allclose(h_ef.train_loss[-1], h32.train_loss[-1],
                               rtol=0, atol=2e-3)

    def drift(h):
        return float(np.sqrt(sum(
            np.sum((np.asarray(a) - np.asarray(b)) ** 2)
            for a, b in zip(jax.tree_util.tree_leaves(h.final_params),
                            jax.tree_util.tree_leaves(h32.final_params))
        )))

    ef_err, no_err = drift(h_ef), drift(h_no)
    assert no_err > 10 * ef_err, (
        f"EF off should drift measurably: |ef|={ef_err:.2e} |no-ef|={no_err:.2e}"
    )


@pytest.mark.parametrize("oracle", [True, False])
def test_compiled_matches_reference_compressed(tiny_ds, oracle):
    """Both execution stacks trace the same compressed round body: compiled
    scan == Python reference loop bitwise, with the EF residual in the carry."""
    kw = dict(rounds=4, oracle_metrics=oracle,
              compression=CompressionSpec(delta_dtype="int8"))
    h_scan = _run(tiny_ds, "kvib", **kw)
    h_py = _run(tiny_ds, "kvib", compiled=False, **kw)
    assert h_scan.train_loss == h_py.train_loss
    assert h_scan.estimator_sq_error == h_py.estimator_sq_error
    for a, b in zip(
        jax.tree_util.tree_leaves(h_scan.final_params),
        jax.tree_util.tree_leaves(h_py.final_params),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_compressed_segmented_resume_bitwise(tiny_ds, tmp_path):
    """The EF residual is checkpoint state: a compressed run preempted at a
    segment boundary and restored through a CheckpointManager finishes
    bitwise identical to the uninterrupted run."""
    from repro.checkpoint import CheckpointManager
    from repro.fed import build_segment_runner, run_segmented

    cfg = FedConfig(
        rounds=8, budget=4, local_steps=1, batch_size=16, seed=5, ckpt_every=2,
        compression=CompressionSpec(delta_dtype="int8"),
    )
    task = logistic_regression()

    def runner():
        sampler = make_sampler("kvib", n=tiny_ds.n_clients, budget=4, horizon=8)
        return build_segment_runner(task, tiny_ds, sampler, cfg)

    segment, state0 = runner()
    full = run_segmented(state0, cfg.rounds, segment, ckpt_every=cfg.ckpt_every)
    assert full.compression and "resid" in full.compression
    assert np.any(np.asarray(full.compression["resid"]) != 0.0)

    mgr = CheckpointManager(str(tmp_path / "ck"), keep_last=2)
    segment_b, state0_b = runner()
    run_segmented(state0_b, cfg.rounds, segment_b, ckpt_every=cfg.ckpt_every,
                  manager=mgr, max_segments=2)
    segment_c, template = runner()
    restored, step = mgr.restore_or_init(template)
    assert step == 4
    resumed = run_segmented(restored, cfg.rounds, segment_c,
                            ckpt_every=cfg.ckpt_every, manager=mgr)
    for a, b in zip(
        jax.tree_util.tree_leaves(resumed), jax.tree_util.tree_leaves(full)
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_disabled_compression_is_inert(tiny_ds):
    """compression=None and an explicit disabled CompressionSpec build the
    SAME program: fed_config() maps disabled -> None, and run histories are
    bitwise equal (the round body has no compression branch to enter)."""
    spec = ExperimentSpec(compression=CompressionSpec())
    assert spec.fed_config().compression is None
    h_none = _run(tiny_ds, "vrb", rounds=4)
    h_off = _run(tiny_ds, "vrb", rounds=4, compression=None)
    assert h_none.train_loss == h_off.train_loss
    for a, b in zip(
        jax.tree_util.tree_leaves(h_none.final_params),
        jax.tree_util.tree_leaves(h_off.final_params),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_round_step_rejects_sequential_compression():
    from repro.configs import get_config
    from repro.fed.round import RoundSpec, build_round_step

    cfg = get_config("smollm-360m").reduced(n_layers=2, d_model=64, d_ff=128,
                                            vocab=128)
    cfg = dataclasses.replace(cfg, round_mode="cohort_sequential")
    spec = RoundSpec(cohort=4, local_steps=1, local_lr=0.05,
                     compression=CompressionSpec(delta_dtype="int8"))
    with pytest.raises(ValueError, match="client_parallel"):
        build_round_step(cfg, spec)


def test_zoo_round_step_compressed_matches_f32():
    """The client_parallel zoo round step under int8: same cohort, params
    close to the f32 step within quantization error, EF residual returned."""
    from repro.configs import get_config
    from repro.fed.round import RoundSpec, build_round_step
    from repro.models import transformer

    cfg = get_config("smollm-360m").reduced(n_layers=2, d_model=64, d_ff=128,
                                            vocab=128)
    cfg = dataclasses.replace(cfg, round_mode="client_parallel")
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    c, r, b, s = 4, 2, 2, 16
    tokens = jax.random.randint(jax.random.PRNGKey(1), (c, r, b, s), 0, cfg.vocab)
    targets = jax.random.randint(jax.random.PRNGKey(2), (c, r, b, s), 0, cfg.vocab)
    weights = jnp.array([0.5, 0.0, 1.25, 0.8], jnp.float32)

    step32 = build_round_step(cfg, RoundSpec(cohort=c, local_steps=r,
                                             local_lr=0.05))
    p32, n32, l32 = jax.jit(step32)(params, tokens, targets, weights)

    spec8 = RoundSpec(cohort=c, local_steps=r, local_lr=0.05,
                      compression=CompressionSpec(delta_dtype="int8"))
    step8 = build_round_step(cfg, spec8)
    d_dim = sum(x.size for x in jax.tree_util.tree_leaves(params))
    resid = jnp.zeros((d_dim,), jnp.float32)
    p8, n8, l8, new_resid = jax.jit(step8)(
        params, tokens, targets, weights, resid=resid
    )
    assert float(l8) == float(l32)  # loss is computed pre-aggregation
    np.testing.assert_allclose(np.asarray(n8), np.asarray(n32), rtol=0.02,
                               atol=1e-5)
    assert new_resid.shape == (d_dim,)
    for a, b in zip(jax.tree_util.tree_leaves(p8),
                    jax.tree_util.tree_leaves(p32)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-3,
                                   rtol=5e-3)
