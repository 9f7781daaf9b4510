"""The program's tracing: layer scopes in the round programs, host spans in
the serve engine, the segment loop and the checkpoint save, and the counters
of the engine and the checkpoint manager.

* Every ``round.*`` scope a configuration exercises is in the ``op_name``
  metadata of its compiled round program, and no ``round.*`` scope opens
  inside another (``repro.obs`` keeps them one level deep).
* A profiler trace recorded here holds the ``repro.*`` host spans, each
  child inside its parent, read back by the benchmark's ``program_trace``.
* The counters count what was done, a refused swap included.

The scopes change no numbers: the bitwise tests of the compiled paths
(``test_segmented_scan.py``, ``test_scan_server.py``) run on the scoped
programs unchanged.
"""
import dataclasses
import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api, obs
from repro.api import ExecutionSpec, ExperimentSpec, FederationSpec, SamplerSpec, TaskSpec
from repro.checkpoint import CheckpointManager
from repro.configs import get_config
from repro.models import transformer
from repro.serve import ServeEngine
from repro.serve.session import ServeSummary

SCOPE = re.compile(r"(?<![\w.])round\.[a-z_]+")
TRAINING = {"round.solve", "round.draw", "round.select", "round.gather",
            "round.local_train", "round.aggregate", "round.sampler_update"}
FAULTS = api.FaultSpec(
    availability="markov", availability_kwargs={"p_on": 0.7, "p_off": 0.2},
    deadline=1.0, latency="exponential", latency_kwargs={"scale": 0.5},
    async_buffer=3, staleness_discount=0.5,
)


def _zoo_spec(fault=None, round_mode="client_parallel"):
    return ExperimentSpec(
        task=TaskSpec(kind="zoo", name="smollm-360m", reduced=True,
                      kwargs={"n_layers": 2, "d_model": 64, "d_ff": 128, "vocab": 128,
                              "round_mode": round_mode},
                      dataset="synthetic_tokens",
                      dataset_kwargs={"n_clients": 8, "seq_len": 16, "total_seqs": 256}),
        sampler=SamplerSpec(name="kvib", kwargs={"horizon": 4}),
        federation=FederationSpec(rounds=4, budget=2, cohort=3, local_steps=2,
                                  batch_size=2, local_lr=0.05),
        execution=ExecutionSpec(seed=5, compiled=True),
        fault=fault or api.FaultSpec(),
    )


def _sim_spec(fault=None, oracle=False):
    return ExperimentSpec(
        task=TaskSpec(name="logreg", kwargs={"dim": 6, "n_classes": 3},
                      dataset="synthetic_classification",
                      dataset_kwargs={"n_clients": 12, "total": 600, "dim": 6,
                                      "n_classes": 3, "seed": 0}),
        sampler=SamplerSpec(name="kvib", kwargs={"horizon": 6}),
        federation=FederationSpec(rounds=6, budget=4, cohort=None if oracle else 6,
                                  local_steps=1, batch_size=8, local_lr=0.05),
        execution=ExecutionSpec(seed=3, oracle_metrics=oracle),
        fault=fault or api.FaultSpec(),
    )


def _segment(spec, eval_data=None):
    built = api.build(spec)
    if spec.task.kind == "zoo":
        from repro.api.runner import _zoo_segment_and_state

        return _zoo_segment_and_state(built)
    from repro.fed.server import build_segment_runner

    return build_segment_runner(built.task, built.dataset, built.sampler,
                                built.fed_config, eval_data)


def _op_names(segment, state):
    return re.findall(r'op_name="([^"]*)"', segment.lower(state, 1).compile().as_text())


CASES = {
    "zoo": (lambda: _zoo_spec(), TRAINING),
    "zoo_sequential": (lambda: _zoo_spec(round_mode="cohort_sequential"), TRAINING),
    "zoo_faults": (lambda: _zoo_spec(FAULTS), TRAINING | {"round.faults"}),
    "sim": (lambda: _sim_spec(), TRAINING | {"round.eval"}),
    "sim_oracle_faults": (lambda: _sim_spec(FAULTS, oracle=True),
                          TRAINING | {"round.faults", "round.eval"}),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_round_program_holds_each_scope_once(case):
    make_spec, want = CASES[case]
    spec = make_spec()
    eval_data = None
    if spec.task.kind != "zoo":
        ds = api.build(spec).dataset
        x, y = ds.batch_all_clients(jax.random.PRNGKey(9), 2)
        eval_data = (x.reshape(-1, x.shape[-1]), y.reshape(-1))
    names = _op_names(*_segment(spec, eval_data))
    found = {m for name in names for m in SCOPE.findall(name)}
    assert found == want, sorted(found ^ want)
    assert found <= set(obs.DEVICE_SCOPES)
    # XLA joins the op_names of instructions it merged with ";": each of
    # them lies in at most one scope.
    nested = [n for name in names for n in name.split(";") if len(SCOPE.findall(n)) > 1]
    assert nested == []
    # The segment loop itself (key derivation, metric stitch) is unscoped.
    assert any(not SCOPE.search(n) for n in names)


def _record(tmp_path, fn):
    from benchmarks.chip import program_trace as pt

    jax.profiler.start_trace(str(tmp_path))
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    return out, pt.host_spans(jax.profiler.ProfileData.from_file(path))


def _inside(child, parents):
    return any(p.start <= child.start and child.end <= p.end for p in parents)


def test_serve_spans_nest_and_counters_count(tmp_path):
    cfg = get_config("smollm-360m").reduced(n_layers=2, d_model=64, d_ff=128, vocab=64)
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    eng = ServeEngine(cfg, params, batch=2, max_seq=32, page_size=8)
    prompts = jnp.ones((2, 8), jnp.int32)
    eng.start(prompts).block_until_ready()  # compile outside the trace
    eng.step(1)

    def serve():
        eng.start(prompts)
        eng.step(1)
        eng.swap_params(jax.tree_util.tree_map(jnp.copy, params))
        eng.step(1)

    _, spans = _record(tmp_path, serve)
    by = {}
    for s in spans:
        by.setdefault(s.name, []).append(s)
    counts = {k: len(v) for k, v in by.items()}
    assert counts == {"repro.serve.start": 1, "repro.serve.step": 2,
                      "repro.serve.step.prep": 2, "repro.serve.step.dispatch": 2,
                      "repro.serve.step.wait": 2, "repro.serve.swap": 1}
    for child in ("prep", "dispatch", "wait"):
        assert all(_inside(s, by["repro.serve.step"]) for s in by["repro.serve.step." + child])
    for a, b in zip(by["repro.serve.step.prep"], by["repro.serve.step.dispatch"]):
        assert a.end <= b.start

    # Two layers' K and V pools of (batch 2 x 4 pages, page 8, 2 KV heads,
    # head size 16) float32; a dense model holds no recurrent state.
    assert eng.counters() == {"prefills": 2, "host_syncs": 3, "swaps": 1,
                              "swaps_rejected": 0, "decode_tokens": 6,
                              "decode_seconds": eng.decode_seconds,
                              "kv_page_bytes": 2 * 2 * (8 * 8 * 2 * 16 * 4),
                              "recurrent_state_bytes": 0}
    rogue = dict(params, rogue=jnp.zeros((3,)))
    with pytest.raises(ValueError, match="treedef"):
        eng.swap_params(rogue)
    drift = dict(params, embed=np.asarray(params["embed"], np.float16))
    with pytest.raises(ValueError, match="aval drift"):
        eng.swap_params(drift)
    assert (eng.swaps, eng.swaps_rejected) == (1, 2)
    eng.decode_tokens, eng.decode_seconds = 0, 0.0  # assignable, as callers reset them
    assert eng.counters()["decode_tokens"] == 0


def test_segment_and_checkpoint_spans_and_manager_counters(tmp_path):
    from repro.fed.state import run_segmented

    seg, state = _segment(_sim_spec())
    seg(state, 2).round.block_until_ready()  # compile outside the trace
    mgr = CheckpointManager(str(tmp_path / "ck"))
    published = []

    final, spans = _record(tmp_path / "trace", lambda: run_segmented(
        state, 6, seg, ckpt_every=2, manager=mgr,
        publish=lambda st, step: published.append(step)))
    assert int(final.round) == 6 and published == [2, 4, 6]
    by = {}
    for s in spans:
        by.setdefault(s.name, []).append(s)
    for name in ("train.place", "train.dispatch", "train.ckpt_save", "train.publish",
                 "ckpt.fetch", "ckpt.write"):
        assert len(by["repro." + name]) == 3, name
    for name in ("ckpt.fetch", "ckpt.write"):
        assert all(_inside(s, by["repro.train.ckpt_save"]) for s in by["repro." + name])
    assert mgr.saves == 3
    files = glob.glob(str(tmp_path / "ck" / "state_*"))
    # retention keeps the newest three: all that was written is still there
    assert mgr.bytes_written == sum(os.path.getsize(f) for f in files) > 0


def test_span_names_come_from_one_list():
    assert set(obs.HOST_SPANS) >= {"serve.step", "serve.step.wait", "ckpt.write"}
    with obs.span("serve.swap", swap=3):
        pass
    with pytest.raises(ValueError, match="unknown host span"):
        obs.span("serve.nap")


def test_serve_summary_renders_the_engine_counters():
    summary = ServeSummary(tokens=8, tokens_per_sec=4.0, promotions=1, rollbacks=0,
                           swaps=1, last_step=2, batches_served=1, host_syncs=5,
                           swaps_rejected=2)
    line = summary.render()
    assert line.startswith("serve summary: promotions=1 ")
    assert line.endswith("host_syncs=5 swaps_rejected=2")
    assert dataclasses.replace(summary, swaps_rejected=0).render().endswith("swaps_rejected=0")


def test_compile_cache_key_holds_the_scopes(monkeypatch, tmp_path):
    """A program that differs from a cached one only in its metadata (a
    scope) must not run the cached executable, whose profile would show the
    old names; source files are keyed relative to the checkout."""
    from repro.launch import compile_cache

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    keep = (jax.config.jax_compilation_cache_include_metadata_in_key,
            jax.config.jax_hlo_source_file_canonicalization_regex)
    try:
        assert compile_cache.use_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_include_metadata_in_key
        regex = jax.config.jax_hlo_source_file_canonicalization_regex
        here = os.path.abspath(__file__)
        assert re.sub(regex, "", here) == os.path.relpath(here, compile_cache.ROOT)
    finally:
        jax.config.update("jax_compilation_cache_include_metadata_in_key", keep[0])
        jax.config.update("jax_hlo_source_file_canonicalization_regex", keep[1])
