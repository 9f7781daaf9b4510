"""Per-architecture smoke tests: REDUCED variants (<=2-ish pattern groups,
d_model<=128, <=4 experts) run one forward + one train-grad step + a decode
step on CPU, asserting shapes and no NaNs.  Full configs are exercised only
via the dry-run (ShapeDtypeStructs, no allocation)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, list_archs
from repro.models import transformer

ARCHS = list_archs()


def _reduced(name):
    cfg = get_config(name).reduced()
    return cfg


def _batch(cfg, key, b=2, s=32):
    ks = jax.random.split(key, 3)
    tokens = jax.random.randint(ks[0], (b, s), 0, cfg.vocab)
    targets = jax.random.randint(ks[1], (b, s), 0, cfg.vocab)
    if cfg.frontend:
        fd = cfg.frontend_dim or cfg.d_model
        aux = jax.random.normal(ks[2], (b, cfg.frontend_seq, fd), jnp.float32)
        return (tokens, targets, aux)
    return (tokens, targets)


@pytest.mark.parametrize("name", ARCHS)
def test_forward_shapes_and_finite(name):
    cfg = _reduced(name)
    key = jax.random.PRNGKey(0)
    params = transformer.init_params(cfg, key)
    batch = _batch(cfg, key)
    logits, aux = transformer.forward(
        params, cfg, batch[0], batch[2] if len(batch) > 2 else None
    )
    assert logits.shape == (2, 32, cfg.vocab)
    assert np.isfinite(np.asarray(logits, np.float32)).all(), name
    assert np.isfinite(float(aux))


@pytest.mark.parametrize("name", ARCHS)
def test_train_step_no_nans(name):
    cfg = _reduced(name)
    key = jax.random.PRNGKey(1)
    params = transformer.init_params(cfg, key)
    batch = _batch(cfg, key)

    loss, grads = jax.value_and_grad(lambda p: transformer.loss_fn(p, cfg, batch))(params)
    assert np.isfinite(float(loss)), name
    flat = jax.tree_util.tree_leaves(grads)
    assert all(np.isfinite(np.asarray(g, np.float32)).all() for g in flat), name
    # SGD step changes params
    new = jax.tree_util.tree_map(lambda p, g: p - 0.1 * g.astype(p.dtype), params, grads)
    l2 = transformer.loss_fn(new, cfg, batch)
    assert np.isfinite(float(l2))


@pytest.mark.parametrize("name", ARCHS)
def test_prefill_then_decode_matches_forward(name):
    """Prefill + single decode step must agree with the full forward on the
    next-token logits (the serving path is consistent with training math)."""
    cfg = _reduced(name)
    key = jax.random.PRNGKey(2)
    params = transformer.init_params(cfg, key)
    b, s = 2, 16
    tokens = jax.random.randint(key, (b, s + 1), 0, cfg.vocab)
    aux = None
    if cfg.frontend:
        fd = cfg.frontend_dim or cfg.d_model
        aux = jax.random.normal(key, (b, cfg.frontend_seq, fd), jnp.float32)

    # ground truth: full forward over s+1 tokens; logits at position s-1
    # predict token s.
    logits_full, _ = transformer.forward(params, cfg, tokens, aux)

    logits_pre, caches = transformer.prefill(params, cfg, tokens[:, :s], aux, max_seq=s + 4)
    np.testing.assert_allclose(
        np.asarray(logits_pre[:, 0], np.float32),
        np.asarray(logits_full[:, s - 1], np.float32),
        rtol=2e-2,
        atol=2e-2,
    )

    logits_dec, caches = transformer.decode_step(
        params, cfg, tokens[:, s : s + 1], caches, jnp.asarray(s, jnp.int32)
    )
    np.testing.assert_allclose(
        np.asarray(logits_dec[:, 0], np.float32),
        np.asarray(logits_full[:, s], np.float32),
        rtol=2e-2,
        atol=2e-2,
    )


def test_long_variant_exists_for_llama1b():
    from repro.configs.llama3_2_1b import SW_CONFIG

    assert SW_CONFIG.block_pattern == ("attn_local",)
    assert SW_CONFIG.sliding_window == 8192


def test_param_counts_full_configs():
    """Full configs must hit their nameplate scale (+-35%) — catches config
    transcription errors without allocating (eval_shape only)."""
    import jax

    expectations = {
        "llama3-405b": 405e9,
        "qwen3-moe-235b-a22b": 235e9,
        "arctic-480b": 480e9,
        "gemma2-27b": 27e9,
        "llama3.2-1b": 1.2e9,
        "smollm-360m": 360e6,
        "xlstm-125m": 125e6,
        "zamba2-1.2b": 1.2e9,
        "llama-3.2-vision-11b": 11e9,
        "whisper-small": 240e6,
        "granite-4.0-h-micro": 3.19e9,
    }
    for name, want in expectations.items():
        cfg = get_config(name)
        shapes = jax.eval_shape(
            lambda c=cfg: transformer.init_params(c, jax.random.PRNGKey(0))
        )
        n = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(shapes))
        assert 0.65 * want < n < 1.45 * want, f"{name}: {n/1e9:.2f}B vs {want/1e9:.2f}B"
