"""Self-check of the benchmark's own arithmetic, on the CPU at small sizes.

    JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip/tests

* the trace reduction: the loader on a trace recorded here, the busy union,
  the idle share, kernel-name matching and the idle-gap attribution on a
  hand-built trace, and roofline shares that stay at or under 100 %;
* the peaks table: a device kind it does not hold is an error;
* the FLOP counts against a count by hand;
* the plain references against the system under test at a tiny size: the
  llama loss and gradient, the ISP solve and K-Vib probabilities, the cohort
  selection, the int8 aggregation with error feedback, logistic regression;
* the device-side Synthetic(1,1) generator against the program's host
  generator: marginals at small N.
"""
from __future__ import annotations

import glob
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.chip import counts, datasets, trace as tr
from benchmarks.chip.references import fedavg, llama, logreg, quant, sampler

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        "m_" + name.replace(".", "_"), os.path.join(HERE, "metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# -- trace reduction ------------------------------------------------------


def test_loader_reads_host_spans_of_a_recorded_trace(tmp_path):
    f = jax.jit(lambda x: jnp.sort(x) @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.segment"):
                y = f(x)
        y.block_until_ready()
    jax.profiler.stop_trace()
    assert glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    t = tr.load(str(tmp_path))
    names = [s.name for s in t.spans]
    assert names.count("bench.segment") == 3 and "bench.window" in names
    lo, hi = t.window()
    assert hi > lo
    segs = [s for s in t.spans if s.name == "bench.segment"]
    assert all(lo <= s.start <= s.end <= hi for s in segs)


def _trace():
    ms = 1e6
    ops = [tr.Op(0 * ms, 4 * ms, "%while.2 = (f32[8]) while(%t), body=%b"),
           tr.Op(0 * ms, 4 * ms, "%fusion.1 = f32[8]{0} fusion(%sort.4)"),  # in the while
           tr.Op(2 * ms, 5 * ms, "%convolution.3 = bf16[8]{0} convolution(%a, %b)"),
           tr.Op(6 * ms, 7 * ms, "%waterfill_level_stats.9 = f32[3]{0} custom-call(%x)"),
           tr.Op(8 * ms, 9 * ms, "%sort.4 = f32[8]{0} sort(%y)"),
           tr.Op(12 * ms, 20 * ms, "%fusion.1 = f32[8]{0} fusion(%sort.4)")]
    spans = [tr.Span(0, 16 * ms, "bench.window"),
             tr.Span(4.5 * ms, 7.2 * ms, "bench.segment"),
             tr.Span(9 * ms, 12 * ms, "bench.sync")]
    return tr.Trace(ops=ops, spans=spans, devices=1)


def test_busy_union_and_idle_share():
    t = _trace()
    lo, hi = t.window()
    assert (lo, hi) == (0, 16e6)
    assert tr.union([(0, 4), (2, 5), (6, 7), (7, 8)]) == [(0, 5), (6, 8)]
    busy = tr.busy_ns(t, lo, hi)
    assert busy == pytest.approx((5 + 1 + 1 + 4) * 1e6)
    ctx = {"busy_s": busy / 1e9, "window_s": (hi - lo) / 1e9}
    assert _reader("idle_pct.train")(ctx) == pytest.approx(100 * (1 - 11 / 16))


def test_two_devices_are_averaged():
    t = _trace()
    t.ops.append(tr.Op(0, 16e6, "%fusion.1 = f32[8]{0} fusion()", device=1))
    t.devices = 2
    assert tr.busy_ns(t, 0, 16e6) == pytest.approx((11e6 + 16e6) / 2)


def test_kernel_name_matching():
    t = _trace()
    lo, hi = t.window()
    assert [o.short for o in tr.op_events(t, ("waterfill",), lo, hi)] == [
        "waterfill_level_stats.9"]
    # an operand named sort.4 does not make its consumer a sort
    assert tr.op_ns(t, ("sort",), lo, hi) == pytest.approx(1e6)
    assert tr.op_events(t, ("dequant_cohort",), lo, hi) == []
    top = dict(tr.top_ops(t, lo, hi))
    assert "while.2 (f32[8])" not in top  # it holds fusion.1
    assert top["fusion.1 f32[8]"] == pytest.approx(4e-3)
    assert top["convolution.3 bf16[8]"] == pytest.approx(3e-3)


def test_idle_gaps_named_by_innermost_host_span():
    t = _trace()
    gaps = dict(tr.idle_gaps(t, *t.window()))
    # 5-6 ms under bench.segment, 7-8 ms in the window only, 9-12 ms under
    # bench.sync; 16 ms is the window's end, so the op at 12-20 ms counts to it.
    assert gaps["bench.segment"] == pytest.approx(1e-3)
    assert gaps["no host span"] == pytest.approx(1e-3)
    assert gaps["bench.sync"] == pytest.approx(3e-3)


def test_roofline_shares_stay_within_100_percent():
    peaks = counts.peaks("TPU v5 lite")
    m = 250_000
    ops, nbytes = counts.waterfill_cost(m, 128)
    least = max(nbytes / peaks["hbm_bytes_per_s"], ops / peaks["bf16_flops"])
    t = tr.Trace(ops=[tr.Op(0, least * 1e9, "%waterfill_level_stats.1 = f32[3]{0} custom-call()"),
                      tr.Op(1e6, 1e6 + 4 * least * 1e9,
                            "%waterfill_level_stats.1 = f32[3]{0} custom-call()")],
                 spans=[tr.Span(0, 1e9, "bench.window")], devices=1)
    ctx = {"trace": t, "lo": 0, "hi": 1e9, "info": {"waterfill": (ops, nbytes)},
           "peaks": peaks}
    share = _reader("waterfill_roofline")(ctx)
    assert share == pytest.approx(100 * 2 / 5)
    assert 0 < share <= 100
    ctx["info"] = {}
    assert _reader("waterfill_roofline")(ctx) is None  # nothing to read


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        counts.peaks("TPU v9 imaginary")
    assert counts.peaks("TPU v5 lite")["bf16_flops"] == 197e12


def test_flop_counts_by_hand():
    m = {"hidden_size": 8, "intermediate_size": 16, "num_hidden_layers": 2,
         "vocab_size": 32, "head_dim": 2, "num_attention_heads": 4,
         "num_key_value_heads": 2}
    per_layer = 8 * 8 + 2 * 8 * 4 + 8 * 8 + 3 * 8 * 16
    assert llama.matmul_params(m) == 2 * per_layer + 8 * 32
    # a round of 10 tokens in sequences of 5
    traffic = {"federation": {"cohort": 1, "local_steps": 1, "batch_size": 2},
               "data": {"seq_len": 5}}
    assert llama.train_flops(m, traffic) == 10 * (
        6 * (2 * per_layer + 256) + 3 * 4 * 2 * 4 * 2 * 5)


# -- plain references against the system under test -----------------------


TINY = {"hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "vocab_size": 256, "rope_theta": 10000.0, "rms_norm_eps": 1e-6,
        "torch_dtype": "float32", "model": "llama", "reference": "references/llama.py"}


def test_llama_reference_matches_the_program():
    from repro.configs import get_config
    from repro.models import transformer

    from benchmarks.chip import weights

    cfg = get_config("smollm-360m").reduced(
        n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, d_ff=128, vocab=256,
        param_dtype=jnp.float32)
    p = weights.make(TINY, 3)
    shapes = jax.eval_shape(lambda: transformer.init_params(cfg, jax.random.PRNGKey(0)))
    assert jax.tree_util.tree_structure(shapes) == jax.tree_util.tree_structure(p)
    tok = jax.random.randint(jax.random.PRNGKey(1), (2, 24), 0, 256)
    tgt = jnp.roll(tok, -1, axis=1)
    with jax.default_matmul_precision("highest"):
        want, gw = jax.value_and_grad(lambda q: transformer.loss_fn(q, cfg, (tok, tgt)))(p)
    items = tuple(sorted(TINY.items()))
    got, gg = llama.grad(p, tok, tgt, items, "f32")
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(gg), jax.tree_util.tree_leaves(gw)):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=1e-6)
    _, g8 = llama.grad(p, tok, tgt, items, "fp8")  # the control differs
    gap = max(float(jnp.max(jnp.abs(a - b))) for a, b in
              zip(jax.tree_util.tree_leaves(g8), jax.tree_util.tree_leaves(gw)))
    assert gap > 1e-4


def test_isp_and_kvib_probabilities_match_the_program():
    from repro.core.samplers import KVib
    from repro.core.solver import isp_probabilities

    a = jax.random.exponential(jax.random.PRNGKey(0), (5000,)) ** 2
    for k in (1, 64, 1000):
        np.testing.assert_allclose(sampler.isp(a, jnp.float32(k)),
                                   isp_probabilities(a, k), rtol=2e-5, atol=1e-9)
    kv = KVib(n=5000, budget=64, horizon=1000)
    st = kv.init()
    st = st.__class__(stats=a, aux=jnp.full((5000,), 0.3), t=st.t + 1)
    th = sampler.theta(5000, 64, 1000)
    np.testing.assert_allclose(sampler.probabilities(a, jnp.float32(0.3), 64, th),
                               kv.probabilities(st), rtol=2e-5, atol=1e-9)


def test_cohort_selection_matches_the_program():
    from repro.fed.cohort import select_cohort

    key = jax.random.PRNGKey(4)
    for n_drawn in (3, 40):
        mask = jnp.zeros((100,), bool).at[jnp.arange(n_drawn) * 2].set(True)
        w = jnp.where(mask, jax.random.uniform(key, (100,)) + 0.5, 0.0)
        sel = select_cohort(mask, w, 8, key)
        ids, wk, n_inc = sampler.select(mask, w, 8, key)
        got = sorted(zip(np.asarray(sel.ids)[np.asarray(sel.valid)].tolist(),
                         np.asarray(sel.weights)[np.asarray(sel.valid)].tolist()))
        assert [i for i, _ in got] == sorted(ids)
        np.testing.assert_allclose([x for _, x in got],
                                   [x for _, x in sorted(zip(ids, wk))], rtol=1e-6)
        assert n_inc == int(sel.n_included)


def test_int8_aggregation_matches_the_program():
    from repro.api import CompressionSpec
    from repro.core import estimator

    key = jax.random.PRNGKey(2)
    like = {"a": jnp.zeros((3, 50), jnp.float32), "b": jnp.zeros((300,), jnp.float32)}
    deltas = [jax.tree_util.tree_map(
        lambda x, i=i: jax.random.normal(jax.random.fold_in(key, i), x.shape) * (i + 1), like)
        for i in range(3)]
    w = np.array([0.7, 1.3, 0.2], np.float32)
    resid = jax.random.normal(key, (450,)) * 0.01
    comp = CompressionSpec(delta_dtype="int8")
    stacked = jax.tree_util.tree_map(lambda *x: jnp.stack(x), *deltas)
    d_p, _, norms_p, resid_p = estimator.aggregate_compressed(
        stacked, jnp.asarray(w), jnp.asarray(w), comp, resid)
    d_r, norms_r, resid_r = quant.aggregator({"delta_dtype": "int8"})(deltas, w, resid, like)
    for a, b in zip(jax.tree_util.tree_leaves(d_r), jax.tree_util.tree_leaves(d_p)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(norms_r, norms_p, rtol=1e-5)
    np.testing.assert_allclose(resid_r, resid_p, rtol=1e-5, atol=1e-5)


def test_logreg_reference_matches_the_program():
    from repro.fed.tasks import logistic_regression

    task = logistic_regression(60, 10)
    p = task.init(jax.random.PRNGKey(0))
    p = {"w": p["w"] * 50, "b": p["b"] + 0.1}
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 60))
    y = jax.random.randint(jax.random.PRNGKey(2), (8,), 0, 10)
    with jax.default_matmul_precision("highest"):
        want, gw = jax.value_and_grad(task.loss)(p, (x, y))
    got, gg = logreg.grad(p, x, y, "f32")
    assert float(got) == pytest.approx(float(want), rel=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(gg), jax.tree_util.tree_leaves(gw)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7)


def test_leaf_gap_rule():
    ref = {"update": {"a": 1.0, "b": 2.0, "c": 1e-6}, "change": {"a": 1.0, "b": 2.0, "c": 0.0},
           "loss": [2.0], "stats": [1.0], "mismatch": 0}
    run = {"update": {"a": 1.1, "b": 2.0, "c": 5.0}, "change": {"a": 0.0, "b": 2.0, "c": 9.0},
           "loss": [2.2], "stats": [1.0]}
    got = fedavg.compare(run, ref)
    # leaf c moves by round-off alone in the reference: left out
    assert got["update_gap"] == pytest.approx(0.1 / 1.5)
    assert got["change_gap"] == pytest.approx(1.0 / 1.5)
    assert got["loss_gap"] == pytest.approx(0.1)


# -- the device-side generator --------------------------------------------


def test_synthetic_generator_marginals_match_the_host_generator():
    from repro.data.pipeline import synthetic_classification

    n, s = 3000, 8
    mine = datasets.synthetic(n, s, seed=5, block=512)
    host = synthetic_classification(n_clients=n, total=n * s, power=0.0, seed=5)
    xa = np.asarray(mine.features).reshape(-1, 60)
    xb = np.asarray(host.features)[:, :s].reshape(-1, 60)
    np.testing.assert_allclose(xa.mean(0), xb.mean(0), atol=0.15)
    np.testing.assert_allclose(xa.std(0), xb.std(0), rtol=0.1)
    ha = np.bincount(np.asarray(mine.labels).ravel(), minlength=10) / (n * s)
    hb = np.bincount(np.asarray(host.labels)[:, :s].ravel(), minlength=10) / (n * s)
    np.testing.assert_allclose(ha, hb, atol=0.03)
    assert (np.asarray(mine.sizes) == s).all()


def test_token_generator_pattern_and_seed():
    a = datasets.tokens(4, 3, 16, 100, seed=2**33 + 1)
    b = datasets.tokens(4, 3, 16, 100, seed=2**33 + 1)
    c = datasets.tokens(4, 3, 16, 100, seed=2**33 + 2)
    f = np.asarray(a.features)
    assert (f == np.asarray(b.features)).all() and not (f == np.asarray(c.features)).all()
    assert (f[:, :, 1::2] == (f[:, :, 0::2] + 1) % 100).all()
    assert (np.asarray(a.labels)[:, :, :-1] == f[:, :, 1:]).all()
