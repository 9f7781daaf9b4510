"""The check fails where it must: a whole run, on the CPU at a tiny size.

    JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip/tests

Each test drives ``run.run`` past its look for a chip, at a tiny version of a
cell's configuration and traffic but with the cell's own limits.  A sound run
comes out ``correct``; with the timed path broken underneath (a round that
leaves the parameters unchanged, half of each local batch left out with the
mean taken over the rest, one label altered where a batch is produced, one
served token altered where it is sampled) it comes out not correct.  The
control (the reference in the configuration's next lower precision, in the
program's place) fails at least one limit too.  On the chip the same
readings at the cells' own sizes come from ``calibrate.py``.
"""
from __future__ import annotations

import json
import os
import types

import jax.numpy as jnp
import pytest

from benchmarks.chip import calibrate, run as bench_run
from benchmarks.chip.generators import fl_round, serve

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(kind, name):
    with open(os.path.join(HERE, kind, name + ".json")) as f:
        return json.load(f)


def _tiny_llama():
    cfg = _load("configs", "smollm-360m")
    cfg.update(hidden_size=64, intermediate_size=128, num_hidden_layers=2,
               num_attention_heads=4, num_key_value_heads=2, head_dim=16, vocab_size=256,
               program_reduced={"n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
                                "d_ff": 128, "vocab": 256, "param_dtype": "bfloat16"})
    return cfg


def _zoo(traffic="fl_round.bf16"):
    t = _load("traffic", traffic)
    t["data"].update(seq_len=32, seqs_per_client=8)
    return "smollm-360m." + traffic, _tiny_llama(), t


def _sim():
    cfg = _load("configs", "logreg-n1m")
    cfg["data"]["n_clients"] = 20_000
    return "logreg-n1m.fl_round.kvib", cfg, _load("traffic", "fl_round.kvib")


def _serve():
    t = _load("traffic", "serve.ctx2k_swap16")
    t.update(batch=2, prompt_len=32, new_tokens=8, swap_every=4, pool=4, check_sequences=4)
    return "smollm-360m.serve.ctx2k_swap16", _tiny_llama(), t


CELLS = {"zoo": _zoo, "sim": _sim, "int8": lambda: _zoo("fl_round.int8_light"),
         "serve": _serve}


def _bench(name, traffic):
    """BENCHMARK.json, with a cell whose traffic file is held out of it (the
    int8 round, PERF.md section 6) added back for the test."""
    with open(os.path.join(os.path.dirname(os.path.dirname(HERE)), "BENCHMARK.json")) as f:
        bench = json.load(f)
    if name not in {w["name"] for w in bench["workloads"]}:
        bench["workloads"].append({"name": name, "config": name.split(".")[0],
                                   "traffic": traffic, "chips": 1, "why": "held out"})
        for m in bench["end_to_end"]:
            if m["name"] == "round_ms":
                m["workloads"].append(name)
    return bench


def _run(cell, seed=2**31 + 17):
    name, cfg, traffic = CELLS[cell]()
    args = types.SimpleNamespace(workload=name, seed=seed, seconds=0.5, trace=0)
    return bench_run.run(args, require_chip=False, bench=_bench(name, name.split(".", 1)[1]),
                         cfg=cfg, traffic=traffic)


def _unchanged(monkeypatch, cell):
    if cell in ("zoo", "int8"):
        from repro.fed import round as fr

        real = fr._local_train
        monkeypatch.setattr(fr, "_local_train", lambda p, cfg, b, lr: (
            lambda d, loss: (jax_zeros(d), loss))(*real(p, cfg, b, lr)))
    else:
        from repro.fed import client

        real = client.local_update
        monkeypatch.setattr(client, "local_update", lambda p, f, b, lr: (
            lambda d, loss: (jax_zeros(d), loss))(*real(p, f, b, lr)))


def jax_zeros(tree):
    import jax

    return jax.tree_util.tree_map(jnp.zeros_like, tree)


def _half_batch(monkeypatch, cell):
    if cell in ("zoo", "int8"):
        from repro.models import transformer

        real = transformer.loss_fn
        monkeypatch.setattr(transformer, "loss_fn", lambda p, cfg, batch: real(
            p, cfg, tuple(x[: x.shape[0] // 2] for x in batch)))
    else:
        from repro.fed import tasks

        real = tasks._xent
        monkeypatch.setattr(tasks, "_xent", lambda lg, y: real(
            lg[: lg.shape[0] // 2], y[: y.shape[0] // 2]))


def _token(monkeypatch, cell):
    if cell == "serve":
        from repro.serve import engine

        real = engine._sample_token

        def altered(logits, key, temperature):
            tok = real(logits, key, temperature)
            return tok.at[0, 0].set((tok[0, 0] + 1) % 7)

        monkeypatch.setattr(engine, "_sample_token", altered)
        return
    from repro.data.pipeline import FederatedDataset

    real = FederatedDataset.client_batch

    def altered(self, client, key, batch_size):
        x, y = real(self, client, key, batch_size)
        return x, y.at[0].set((y[0] + 1) % 7)

    monkeypatch.setattr(FederatedDataset, "client_batch", altered)


FAULTS = {"unchanged": _unchanged, "half_batch": _half_batch, "token": _token}
# The faults each cell can have: one local step of batch 1 has no half batch
# to leave out; serving has no training step.
CELL_FAULTS = [("zoo", f) for f in FAULTS] + [("sim", f) for f in FAULTS] + [
    ("int8", "unchanged"), ("int8", "token"), ("serve", "token")]
END_TO_END = {"serve": {"serve_tokens_per_s", "itl_ms_p95", "setup_s"}}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_sound_run_is_correct(cell):
    res = _run(cell)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == END_TO_END.get(cell, {"round_ms", "setup_s"})


@pytest.mark.parametrize("cell,fault", CELL_FAULTS)
def test_planted_fault_is_not_correct(monkeypatch, cell, fault):
    FAULTS[fault](monkeypatch, cell)
    res = _run(cell)
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("cell", sorted(set(CELLS) - {"serve"}))
def test_control_fails_a_limit(cell):
    name, cfg, traffic = CELLS[cell]()
    got = calibrate.upper_readings(fl_round, cfg, dict(traffic, faults=[]), seed=7)
    limits = traffic["limits"]
    assert any(not v <= limits[k] for k, v in got["control"].items() if k in limits), got


def test_serve_control_reads_far_above_a_sound_run():
    """A two-layer model's greedy tokens seldom flip in fp8, so at this size
    the control cannot reach the limit set at the cell's own size (32 layers,
    2048-token prompts, where it reads about 200 times the sound runs; see
    ``PERF.md``).  Here it must still read far above a sound run."""
    _, cfg, traffic = CELLS["serve"]()
    sound = calibrate.program_reading(serve, cfg, traffic, seed=7)["logit_gap"]
    got = calibrate.upper_readings(serve, cfg, dict(traffic, faults=[]), seed=7)
    assert got["control"]["logit_gap"] > max(10 * sound, 1e-3), (sound, got)
