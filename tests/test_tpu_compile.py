"""The main-path Pallas kernels compile for a TPU v5e at real widths.

Interpret mode accepts kernels that the chip's compiler refuses (a scalar
store to VMEM, a block not aligned to the (8, 128) tiling), so each kernel
is compiled here for a *described* v5e — no chip attached — and its HLO
must hold the Mosaic custom call.  The topology is described inside a
fixture, never at import: only one process at a time may load the TPU
compiler library.
"""
import importlib
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core import estimator
from repro.data import FederatedDataset
from repro.fed import FedConfig, logistic_regression
from repro.fed import server as fed_server
from repro.models import transformer

fwa = importlib.import_module("repro.kernels.fused_weighted_agg")
swf = importlib.import_module("repro.kernels.sharded_waterfill")

SMOKE_COHORT_INT8 = 2  # chip_smoke.Setup.cohort_int8
SAMPLER_COHORT = 128  # 2 * K at K=64, the million-client logreg run
LOGREG_D = 60 * 10 + 10


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler library here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # A described chip cannot read a persistent-cache entry back; keep the
    # cache out of these compiles.
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def smollm_d():
    """smollm-360m's flattened parameter count (shapes only)."""
    cfg = get_config("smollm-360m")
    shapes = jax.eval_shape(
        lambda: transformer.init_params(cfg, jax.random.PRNGKey(0))
    )
    return sum(s.size for s in jax.tree_util.tree_leaves(shapes))


def _hlo(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def _pad(d, block):
    return -(-d // block) * block


def test_fused_cohort_agg_and_error_compiles(one_chip, smollm_d):
    spec = lambda shape: jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    for c, d in ((SAMPLER_COHORT, _pad(LOGREG_D, 128)),
                 (SMOKE_COHORT_INT8, _pad(smollm_d, 2048))):
        hlo = _hlo(
            lambda g, w, lam: fwa.fused_cohort_agg_and_error(
                g, w, lam, block_d=min(d, 2048)),
            spec((c, d)), spec((c,)), spec((c,)),
        )
        assert "tpu_custom_call" in hlo, (c, d)


def test_fused_dequant_cohort_agg_compiles(one_chip, smollm_d):
    c, sb = SMOKE_COHORT_INT8, 128
    d_pad = _pad(smollm_d, fwa.dequant_block_d(smollm_d, sb))
    args = (
        jax.ShapeDtypeStruct((c, d_pad), jnp.int8, sharding=one_chip),
        jax.ShapeDtypeStruct((c, d_pad // sb), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((c,), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((c,), jnp.float32, sharding=one_chip),
    )
    assert "tpu_custom_call" in _hlo(fwa.fused_dequant_cohort_agg, *args)


def test_fused_multi_weighted_agg_compiles(one_chip, smollm_d):
    c, d = SMOKE_COHORT_INT8, _pad(smollm_d, 2048)
    hlo = _hlo(
        lambda g, w: fwa.fused_multi_weighted_agg(g, w, block_d=2048),
        jax.ShapeDtypeStruct((c, d), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((2, c), jnp.float32, sharding=one_chip),
    )
    assert "tpu_custom_call" in hlo


def test_waterfill_level_stats_compiles(one_chip):
    n_shard = 250_000  # N=10^6 clients over four shards
    hlo = _hlo(
        swf.waterfill_level_stats,
        jax.ShapeDtypeStruct((n_shard,), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((128,), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((128,), jnp.float32, sharding=one_chip),
    )
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("path", ["cohort", "compressed"])
def test_estimator_runs_kernel_at_any_width_on_tpu(one_chip, monkeypatch, path):
    """On TPU the estimator pads D to whole kernel chunks instead of falling
    back to jnp: logreg's D=610 is no multiple of 128.  The described chip
    cannot steer ``jax.default_backend()``, so the test steers the
    estimator's backend check."""
    from repro.api import CompressionSpec

    monkeypatch.setattr(estimator, "_on_tpu", lambda: True)
    c = SAMPLER_COHORT
    spec = lambda shape: jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    updates = {"w": spec((c, 60, 10)), "b": spec((c, 10))}
    if path == "cohort":
        fn = estimator.aggregate_and_error_cohort
    else:
        comp = CompressionSpec(delta_dtype="int8")
        fn = lambda u, w, lam: estimator.aggregate_compressed(u, w, lam, comp)
    assert "tpu_custom_call" in _hlo(fn, updates, spec((c,)), spec((c,)))


@pytest.mark.parametrize("precision", ["highest", None])
def test_cohort_step_leaves_the_dataset_in_place(one_chip, precision):
    """The deployable cohort step of the million-client logreg run gathers
    its cohort's rows and never relayouts the dataset.  Sampled per slot
    straight from the (N, 8, 60) dataset, the batches are one point gather
    that wants another layout, so every call copied the whole dataset into a
    temporary padded to 128 features: 4.1 GB at the run's "highest" matmul
    precision, 2.0 GB as bfloat16 at the default one."""
    n, s, dim = 1_000_000, 8, 60
    spec = lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    task = logistic_regression(dim=dim, n_classes=10)
    cfg = FedConfig(budget=64, cohort=SAMPLER_COHORT, local_steps=1, batch_size=8,
                    local_lr=0.01, oracle_metrics=False)
    ds = FederatedDataset(features=spec((n, s, dim)), labels=spec((n, s), jnp.int32),
                          sizes=spec((n,), jnp.int32))
    params = jax.tree_util.tree_map(
        lambda a: spec(a.shape, a.dtype), jax.eval_shape(task.init, jax.random.PRNGKey(0)))

    def step(ds, params, key, cohort_ids):
        return fed_server._build_cohort_clients(task, ds, cfg)(params, key, cohort_ids)

    with jax.default_matmul_precision(precision):
        compiled = jax.jit(step).lower(
            ds, params, spec((2,), jnp.uint32), spec((SAMPLER_COHORT,), jnp.int32)).compile()
    # "%name = f32[N,8,60]{layout} opcode(": what produces a whole-dataset value
    produced = re.compile(rf"%\S+ = \w+\[{n},{s},{dim}\](?:\{{[^}}]*\}})? ([\w-]+)\(")
    made = [m.group(0) for m in map(produced.search, compiled.as_text().splitlines())
            if m and m.group(1) != "parameter"]
    assert not made, made
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


def _kernel_args(name, spec):
    if name == "rmsnorm":
        return (spec((256, 256)), spec((256,))), {}
    if name == "flash_attention":
        return (spec((2, 256, 128)),) * 3, {}
    if name == "waterfill_level_stats":
        return (spec((4096,)), spec((128,)), spec((128,))), {}
    if name == "fused_dequant_cohort_agg":
        return (spec((2, 16384), jnp.int8), spec((2, 128)), spec((2,)), spec((2,))), {}
    if name == "fused_multi_weighted_agg":
        return (spec((2, 4096)), spec((2, 2))), {"block_d": 2048}
    return (spec((8, 4096)), spec((8,))) + ((spec((8,)),) if name.endswith("error") else ()), {
        "block_d": 2048}


KERNELS = {
    "rmsnorm": "repro.kernels.rmsnorm",
    "flash_attention": "repro.kernels.flash_attention",
    "waterfill_level_stats": "repro.kernels.sharded_waterfill",
    "fused_weighted_agg": "repro.kernels.fused_weighted_agg",
    "fused_multi_weighted_agg": "repro.kernels.fused_weighted_agg",
    "fused_cohort_agg_and_error": "repro.kernels.fused_weighted_agg",
    "fused_dequant_cohort_agg": "repro.kernels.fused_weighted_agg",
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_names_its_pallas_call(one_chip, name):
    """Each ``pallas_call`` is named after its kernel: the Mosaic call carries
    that ``kernel_name``, and the trace shows the compiled instruction under
    it, where the benchmark's readers match it (``waterfill``,
    ``dequant_cohort``).  ``ssd_scan`` runs in interpret mode only: its
    ``cumsum`` has no TPU lowering."""
    fn = getattr(importlib.import_module(KERNELS[name]), name)
    spec = lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    args, kw = _kernel_args(name, spec)
    lowered = jax.jit(lambda *a: fn(*a, **kw)).lower(*args)
    assert f'kernel_name = "{name}"' in lowered.as_text()
    calls = [line.split(" = ", 1)[0].split()[-1].lstrip("%")
             for line in lowered.compile().as_text().splitlines()
             if "custom_call_target=\"tpu_custom_call\"" in line]
    assert calls and all(c.rsplit(".", 1)[0] == name for c in calls), calls
