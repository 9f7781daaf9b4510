"""granite-4.0-h-micro, a Mamba-2 + NoPE-attention hybrid, against its plain
float32 reference (``benchmarks/chip/references/granite.py``) at a small size
on the CPU, with seeded random weights:

* the loss and gradient of ``transformer.loss_fn``, and the gradient of
  the chunked attention path against the einsum path;
* the program's chunked SSD against the reference's token-by-token
  recurrence;
* per-block remat against no remat;
* a zoo round through ``build_fed_scan_segment`` against ``fedavg.follow``
  (the harness's set-up and check), and the control failing a limit;
* the ``ssm.scan`` scope inside ``round.local_train`` in the round's HLO;
* the configuration's program check and the published parameter count.
"""
import contextlib
import dataclasses
import json
import os
import re
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.chip import references
from benchmarks.chip.generators import fl_round
from repro import obs
from repro.configs import get_config
from repro.models import ssm, transformer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# The small size: every width cut and every scalar kept; a period of two
# layers (Mamba-2, then attention) holds both kinds of layer at half the
# compile time of the published ten.
SMALL = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
             intermediate_size=128, shared_intermediate_size=128, vocab_size=256,
             mamba_d_state=16, mamba_d_head=16, mamba_n_heads=8, num_hidden_layers=2,
             layer_types=["mamba", "attention"])
PROGRAM = dict(n_layers=2, block_pattern=("mamba2_mlp", "attn"), d_model=64, n_heads=4,
               n_kv_heads=2, d_ff=128, vocab=256, ssm_state=16, ssm_head_dim=16)


def _load(kind, name):
    with open(os.path.join(ROOT, "benchmarks", "chip", kind, name + ".json")) as f:
        return json.load(f)


def _small(dtype="float32"):
    """(configuration dict, program ArchConfig, family module)."""
    cfg = dict(_load("configs", "granite-4.0-h-micro"), **SMALL, torch_dtype=dtype)
    arch = get_config(cfg["program_arch"]).reduced(**PROGRAM)
    return cfg, arch, references.family(cfg)


def _batch(s, seed=1, vocab=256):
    tok = jax.random.randint(jax.random.PRNGKey(seed), (2, s), 0, vocab)
    return tok, jnp.roll(tok, -1, axis=1)


def _rel(a, b):
    """Largest gap of ``a`` against ``b``, over ``b``'s largest magnitude."""
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30))


def test_loss_and_gradient_follow_the_reference():
    """Both sides in float32.  Tolerances: the loss to 1e-6 relative and each
    gradient leaf to 1e-4 of its largest entry.  The program's chunked SSD
    sums in another order than the reference's recurrence, and the CPU's
    float32 round-off leaves gaps near 2e-5; the reference rounded to fp8
    (the control) moves a projection's gradient by more than 1e-2."""
    cfg, arch, fam = _small()
    p = fam.weights(cfg, jax.random.PRNGKey(0))
    tok, tgt = _batch(64)
    loss, g = jax.jit(jax.value_and_grad(
        lambda q: transformer.loss_fn(q, arch, (tok, tgt))))(p)
    ref_loss, ref_g = fam.grad(p, tok, tgt, fl_round._items(cfg), "f32")
    assert float(loss) == pytest.approx(float(ref_loss), rel=1e-6)
    gaps = {jax.tree_util.keystr(k): _rel(a, b) for (k, a), b in zip(
        jax.tree_util.tree_leaves_with_path(g), jax.tree_util.tree_leaves(ref_g))}
    assert max(gaps.values()) < 1e-4, gaps
    _, ctl_g = fam.grad(p, tok, tgt, fl_round._items(cfg), "fp8")
    assert _rel(ctl_g["stacks"][0]["ssm"]["in_proj"], ref_g["stacks"][0]["ssm"]["in_proj"]) > 1e-2


def test_chunked_attention_gradient_follows_einsum():
    """granite's attention path (NoPE, scale 1/64, online softmax over KV
    blocks, each block recomputed in backward) against the einsum path, at
    1024 positions (two blocks): values and gradients to 1e-5 of their
    largest entry (the online softmax sums in another order)."""
    from repro.models import attention

    _, arch, _ = _small()
    ks = jax.random.split(jax.random.PRNGKey(5), 4)
    q = jax.random.normal(ks[0], (1, 1024, 2, 2, 16))
    k, v = (jax.random.normal(kk, (1, 1024, 2, 16)) for kk in ks[1:3])
    w = jax.random.normal(ks[3], q.shape)
    mask = attention._causal_mask(1024, 1024, None)

    def f(impl):
        a = dataclasses.replace(arch, attn_impl=impl)
        return jax.jit(jax.value_and_grad(
            lambda *x: jnp.sum(w * attention._sdpa(a, *x, mask)), argnums=(0, 1, 2)))(q, k, v)

    (val, grads), (want, want_g) = f("chunked"), f("einsum")
    assert arch.attn_impl == "chunked" and arch.position_embedding == "nope"
    assert float(val) == pytest.approx(float(want), rel=1e-5)
    for a, b in zip(grads, want_g):
        assert _rel(a, b) < 1e-5


@pytest.mark.parametrize("chunk", [8, 32])
def test_chunked_ssd_follows_the_recurrence(chunk):
    """``ssd_chunked`` against the reference's token-by-token recurrence
    (float32 both; 1e-5 of the largest output: the chunked form sums in
    another order)."""
    ks = jax.random.split(jax.random.PRNGKey(4), 6)
    b, s, h, pd, n = 2, 64, 4, 8, 16
    x = jax.random.normal(ks[0], (b, s, h, pd))
    dt = jax.random.normal(ks[1], (b, s, h)) - 2.0
    a_log = jnp.log(jax.random.uniform(ks[2], (h,), minval=1.0, maxval=16.0))
    bm, cm = jax.random.normal(ks[3], (b, s, n)), jax.random.normal(ks[4], (b, s, n))
    d_skip = jax.random.normal(ks[5], (h,))
    y = ssm.ssd_chunked(x, dt, a_log, bm, cm, d_skip, chunk=chunk)
    delta = jax.nn.softplus(dt)
    a = jnp.broadcast_to(-jnp.exp(a_log), delta.shape)
    cfg, _, fam = _small()
    want = jax.jit(fam._recurrence, static_argnums=5)(x, delta, a, bm, cm, "f32")
    want = want + d_skip[:, None] * x
    assert _rel(y, want) < 1e-5


def test_per_block_remat_gives_the_gradient_of_no_remat():
    """The checkpointed blocks recompute the same operations: the gradients
    agree to float32 round-off (1e-6 of each leaf's largest entry)."""
    cfg, arch, fam = _small()
    p = fam.weights(cfg, jax.random.PRNGKey(0))
    tok, tgt = _batch(32)

    def grads(a):
        return jax.jit(jax.grad(lambda q: transformer.loss_fn(q, a, (tok, tgt))))(p)

    full, none = grads(arch), grads(dataclasses.replace(arch, remat="none"))
    assert arch.remat == "full"
    for a, b in zip(jax.tree_util.tree_leaves(full), jax.tree_util.tree_leaves(none)):
        assert _rel(a, b) < 1e-6


def _cell(dtype="bfloat16"):
    """The cell at the small size: its traffic and limits, 128-token
    sequences, the program in the configuration's type."""
    cfg, _, _ = _small(dtype)
    cfg["program_reduced"] = dict(PROGRAM, param_dtype=dtype)
    traffic = _load("traffic", "fl_round.seq4k")
    traffic["data"].update(seq_len=128, seqs_per_client=4)
    return cfg, traffic


@pytest.fixture(scope="module")
def served():
    """The cell at the small size after the harness's set-up and a short
    window: ``(cell, traffic, the round program's HLO text)``.  The HLO is
    that of the executable the calls ran (``segment.lower``)."""
    cfg, traffic = _cell()
    cell = fl_round.Cell(cfg, traffic, 2**31 + 17)
    cell.setup(lambda _name: contextlib.nullcontext())
    text = cell.segment.lower(cell.state, 1).compile().as_text()
    cell.window(0.0, lambda _name: contextlib.nullcontext())
    cell.release()
    return cell, traffic, text


def test_zoo_round_follows_the_reference_and_the_control_does_not(served):
    """A zoo round at the small size: set-up drives the compiled segment
    (``build_fed_scan_segment``, ``client_parallel``) for ``check_rounds``
    rounds and ``fedavg.follow`` follows them with the family's reference;
    every compared number is inside the cell's limits.  The control (the
    reference in fp8 in the program's place) fails at least one."""
    cell, traffic, _ = served
    limits = traffic["limits"]
    sound = cell.check()
    assert all(sound[k] <= lim for k, lim in limits.items()), sound
    control = cell.upper("control")
    assert any(not control[k] <= lim for k, lim in limits.items()), control


def test_scan_scope_lies_inside_local_training(served):
    """Every instruction of the SSD carries ``ssm.scan`` after the round's
    ``round.local_train`` in its op_name (forward, transpose and remat), so
    ``scope_of`` still files it under local training."""
    from benchmarks.chip import program_trace

    names = [n for name in re.findall(r'op_name="([^"]*)"', served[2]) for n in name.split(";")]
    scanned = [n for n in names if "ssm.scan" in n]
    assert obs.MODEL_SCOPES == (ssm.SCAN_SCOPE,)
    scopes = [program_trace.scope_of(n) for n in scanned]
    # A few instructions of the transposed checkpoint lose the outer scopes
    # (``checkpoint/ssm.scan/...``); every other one lies in local training.
    assert set(scopes) <= {"round.local_train", ""}
    assert scopes.count("round.local_train") >= 0.9 * len(scanned) > 100
    assert all(n.index("round.local_train") < n.index("ssm.scan")
               for n in scanned if "round.local_train" in n)
    for form in ("/jvp()/", "/transpose(jvp())/", "/rematted_computation/"):
        assert any(form in n for n in scanned), form


def test_program_check_passes_and_raises():
    cfg = _load("configs", "granite-4.0-h-micro")
    fam = references.family(cfg)
    arch = get_config(cfg["program_arch"])
    fam.check_program(cfg, arch)
    for key, wrong in (("residual_multiplier", 0.25), ("attention_multiplier", 0.125),
                       ("position_embedding_type", "rope"), ("mamba_d_state", 64)):
        with pytest.raises(ValueError, match=key):
            fam.check_program(dict(cfg, **{key: wrong}), arch)
    with pytest.raises(ValueError, match="layer_types"):
        fam.check_program(cfg, dataclasses.replace(
            arch, block_pattern=arch.block_pattern[1:] + arch.block_pattern[:1]))
    small_cfg, small_arch, _ = _small()
    fam.check_program(small_cfg, small_arch)


def test_published_parameter_count():
    """granite-4.0-h-micro: 3.19e9 parameters at 40 layers; the benchmark's
    cut is one period (746.5M) and half of the tied embedding (102.8M)."""
    def count(name):
        shapes = jax.eval_shape(lambda: transformer.init_params(
            get_config(name), jax.random.PRNGKey(0)))
        return sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(shapes))

    assert count("granite-4.0-h-micro") == pytest.approx(3.19e9, rel=2e-3)
    assert count("granite-4.0-h-micro-p1") == pytest.approx(746.5e6 + 102.76e6, rel=1e-3)


def test_served_tokens_follow_the_reference_across_a_swap():
    """``ServeEngine`` on the small model: prefill, paged decode with the
    Mamba-2 state and the NoPE attention's KV pages side by side, and a hot
    swap after three steps.  Each greedy token is the reference's best at
    its position, with the weights active there (``served_gaps``), to 1e-4
    of a logit: float32 both, summed in another order."""
    from repro.serve import ServeEngine

    cfg, arch, fam = _small()
    p = fam.weights(cfg, jax.random.PRNGKey(0))
    q = fam.weights(cfg, jax.random.PRNGKey(1))
    prompt, steps, swap_at = 8, 6, 3
    eng = ServeEngine(arch, p, batch=2, max_seq=16, page_size=4)
    eng.start(_batch(prompt, seed=2)[0])
    eng.step(swap_at)
    eng.swap_params(q)
    eng.step(steps - swap_at)
    tokens = jnp.concatenate([_batch(prompt, seed=2)[0], eng.generated()], axis=1)
    use_q = jnp.arange(tokens.shape[1]) >= prompt + swap_at
    gaps, _, _ = fam.served_gaps(p, q, use_q, tokens, tokens, fl_round._items(cfg), "f32")
    served = np.asarray(gaps)[:, prompt - 1:]
    assert served.shape == (2, steps + 1) and float(np.max(np.abs(served))) < 1e-4
    held = eng.counters()
    # Per sequence: one attention layer's K and V pages (16 positions, 2 KV
    # heads of 16), and one Mamba-2 layer's conv tail and SSM state.
    assert held["kv_page_bytes"] == 2 * 2 * 16 * 2 * 16 * 4
    assert held["recurrent_state_bytes"] == 2 * 4 * (3 * (128 + 2 * 16) + 8 * 16 * 16)


def test_scan_readers_by_hand():
    """``ssd_scan_ms`` and ``ssd_scan_roofline`` on a hand-built window: the
    ops whose op_name holds ``ssm.scan`` under any transform, and no other,
    per round; the share is the family's least time over that."""
    import importlib.util

    from benchmarks.chip import counts, named_ops

    def reader(name):
        path = os.path.join(ROOT, "benchmarks", "chip", "metrics", name + ".py")
        spec = importlib.util.spec_from_file_location("m_" + name, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read

    ms = 1e6
    named = [
        ("jit(seg)/while/body/round.local_train/vmap()/jvp()/ssm.scan/exp", 3 * ms),
        ("jit(seg)/while/body/round.local_train/transpose(jvp())/checkpoint/"
         "rematted_computation/ssm.scan/dot_general", 4 * ms),
        ("checkpoint/ssm.scan/reduce_sum", 1 * ms),
        ("jit(seg)/while/body/round.local_train/vmap()/jvp()/mlp/dot_general", 50 * ms),
        ("jit(seg)/while/body/ssm.scanner/add", 7 * ms),
    ]
    cfg = _load("configs", "granite-4.0-h-micro")
    traffic = _load("traffic", "fl_round.seq4k")
    ctx = {"lo": 0.0, "hi": 1.0, "raw": {"rounds": 2}, "trace": types.SimpleNamespace(devices=1),
           "cfg": cfg, "traffic": traffic, "peaks": counts.peaks("TPU v5 lite")}
    named_ops._cache.clear()
    named_ops._cache[(0.0, 1.0)] = named
    assert reader("ssd_scan_ms")(ctx) == pytest.approx(4.0)
    flops, nbytes = references.family(cfg).ssd_cost(cfg, traffic)
    least = max(flops / 197e12, nbytes / 819e9)
    assert reader("ssd_scan_roofline")(ctx) == pytest.approx(100 * least / 4e-3)
    assert flops / 16384 / 3 / 9 == pytest.approx(4.26e6, rel=1e-3)
    named_ops._cache[(0.0, 1.0)] = named[3:]
    assert reader("ssd_scan_ms")(ctx) is None and reader("ssd_scan_roofline")(ctx) is None
    named_ops._cache.clear()
