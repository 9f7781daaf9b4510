"""A model family is files: the contract of ``references/<family>.py``.

    JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip/tests

A configuration names its family module in ``reference``; the harness loads
it by path and knows no family by name.  These tests check that every
configuration of ``BENCHMARK.json`` resolves to a module with the functions
its cells call; that the weights, gradients and FLOP counts are those the
harness gave before the families became files (pinned below); that no
generic file of the harness names a family; and that a family added only as
a new file, outside the repository, runs a whole cell to ``correct``.
"""
from __future__ import annotations

import glob
import hashlib
import json
import os
import re
import shutil
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.chip import references, run as bench_run, weights
from benchmarks.chip.generators import fl_round, serve
from benchmarks.chip.tests import test_control as tc

CHIP = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(CHIP))

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)

# The functions a cell's generator calls on its configuration's family.
NEEDS = {"fl_round": ("weights", "train_flops", "grad"),
         "serve": ("weights", "serve_flops", "served_gaps")}


def _config(name):
    conf = {c["name"]: c for c in BENCH["configs"]}[name]
    with open(os.path.join(ROOT, conf["file"])) as f:
        return json.load(f)


def _digest(tree):
    h = hashlib.sha256()
    for x in jax.tree_util.tree_leaves(tree):
        a = np.asarray(x)
        h.update(f"{a.dtype}{a.shape}".encode())
        h.update(a.tobytes())
    return h.hexdigest()[:16]


# -- (a) every configuration resolves to a family with the contract ----------


@pytest.mark.parametrize("name", sorted(c["name"] for c in BENCH["configs"]))
def test_configuration_resolves_to_its_family(name):
    cfg = _config(name)
    fam = references.family(cfg)
    assert os.path.samefile(fam.__file__, os.path.join(CHIP, cfg["reference"]))
    need = set()
    for w in BENCH["workloads"]:
        if w["config"] == name:
            need.update(NEEDS[tc._load("traffic", w["traffic"])["generator"]])
    if cfg["stack"] == "zoo":
        need.add("check_program")
    assert need and not [f for f in sorted(need) if not callable(getattr(fam, f, None))]


# -- (b) the same numbers as before the move ----------------------------------

SEED = 2**31 + 17

# Digests of the weight bytes (dtype, shape and bytes of every leaf in tree
# order), taken with the harness before the families became files.
WEIGHTS = {("zoo", 2): "badda9523608ad4b", ("zoo", 4): "9c2b5c3a567c7db5",
           ("sim", 2): "579bd91b6633e80f"}


@pytest.mark.parametrize("cell,stream", sorted(WEIGHTS))
def test_weights_are_pinned(cell, stream):
    _, cfg, _ = tc.CELLS[cell]()
    assert _digest(weights.make(cfg, SEED, stream)) == WEIGHTS[cell, stream]


def _train_flops(cfg, traffic):
    cell = fl_round.Cell(cfg, {k: v for k, v in traffic.items() if k != "compression"}, SEED)
    n = cfg.get("data", {}).get("n_clients", 0)
    cell.built = types.SimpleNamespace(sampler=types.SimpleNamespace(n=n))
    return cell.info({"rounds": 1})["train_flops_per_round"]


def _serve_flops(cfg, traffic):
    return serve.Cell(cfg, traffic, SEED).info({"batches": 1})["serve_flops"]


# ``info()``'s FLOPs a round (training) or a batch (serving), as the harness
# counted them before the move: at test_control's tiny sizes and at the
# cells' own.
FLOPS = {"zoo": 603979776.0, "int8": 37748736.0, "sim": 3686400.0, "serve": 15233024.0,
         "smollm-360m.fl_round.bf16": 38654705664000.0,
         "logreg-n1m.fl_round.kvib": 3686400.0,
         "smollm-360m.serve.ctx2k_swap16": 14416965795840.0}


@pytest.mark.parametrize("case", sorted(FLOPS))
def test_flop_counts_are_pinned(case):
    if case in tc.CELLS:
        _, cfg, traffic = tc.CELLS[case]()
    else:
        cell = {w["name"]: w for w in BENCH["workloads"]}[case]
        cfg, traffic = _config(cell["config"]), tc._load("traffic", cell["traffic"])
    count = _serve_flops if traffic["generator"] == "serve" else _train_flops
    assert count(cfg, traffic) == FLOPS[case]


# (loss, per-leaf gradient norms) of the family's ``grad`` at tiny sizes,
# before the move.  Held to float32 round-off, not to the bit: the CPU's
# matmul kernels may differ between hosts.
GRADS = {
    ("zoo", "f32"): (5.539470672607422, [
        1.864975929260254, 0.023306861519813538, 0.08993341028690338, 0.44420263171195984,
        0.08513862639665604, 0.4770611524581909, 0.03315284475684166, 0.02178122103214264,
        0.29536762833595276, 0.2332562953233719, 0.2095528095960617]),
    ("zoo", "fp8"): (5.540321350097656, [
        1.16972815990448, 0.01796686463057995, 0.0, 0.19600462913513184, 0.0,
        0.06902576982975006, 0.0, 0.0, 0.13671875, 0.08323154598474503,
        0.07511282712221146]),
    ("sim", "f32"): (2.3104450702667236, [0.2964946925640106, 2.6677327156066895]),
    ("sim", "bf16"): (2.3104958534240723, [0.29657110571861267, 2.6701314449310303]),
}


@pytest.mark.parametrize("cell,mode", sorted(GRADS))
def test_gradients_are_pinned(cell, mode):
    _, cfg, _ = tc.CELLS[cell]()
    if cell == "zoo":
        cfg = dict(cfg, torch_dtype="float32")
        p = weights.make(cfg, 3)
        tok = jax.random.randint(jax.random.PRNGKey(1), (2, 24), 0, 256)
        loss, g = references.family(cfg).grad(p, tok, jnp.roll(tok, -1, axis=1),
                                             fl_round._items(cfg), mode)
    else:
        x = jax.random.normal(jax.random.PRNGKey(1), (8, 60))
        y = jax.random.randint(jax.random.PRNGKey(2), (8,), 0, 10)
        loss, g = references.family(cfg).grad(weights.make(cfg, SEED), x, y, mode)
    want_loss, want_norms = GRADS[cell, mode]
    assert float(loss) == pytest.approx(want_loss, rel=1e-6)
    norms = [float(jnp.linalg.norm(x)) for x in jax.tree_util.tree_leaves(g)]
    assert norms == pytest.approx(want_norms, rel=1e-5, abs=1e-9)


# -- (c) no generic file names a family ---------------------------------------


def _family_names():
    names = set()
    for path in glob.glob(os.path.join(CHIP, "configs", "*.json")):
        with open(path) as f:
            cfg = json.load(f)
        names.add(cfg["model"].lower())
        names.add(os.path.splitext(os.path.basename(cfg["reference"]))[0].lower())
    return names


def _generic_files():
    """Every file of the harness but the configurations, the tests and the
    family modules the configurations name."""
    family_files = set()
    for path in glob.glob(os.path.join(CHIP, "configs", "*.json")):
        with open(path) as f:
            family_files.add(os.path.normpath(os.path.join(CHIP, json.load(f)["reference"])))
    out = []
    for path in glob.glob(os.path.join(CHIP, "**", "*"), recursive=True):
        rel = os.path.relpath(path, CHIP)
        if (os.path.isfile(path) and rel.split(os.sep)[0] not in ("configs", "tests")
                and "__pycache__" not in rel and os.path.normpath(path) not in family_files):
            out.append(path)
    return sorted(out)


def test_no_generic_file_names_a_family():
    """A name counts where no letter or digit adjoins it (``llama_grad``
    and ``references.llama`` name a family; a longer word holding a short
    family name does not)."""
    words = {n: re.compile(rf"(?<![a-z0-9]){re.escape(n)}(?![a-z0-9])")
             for n in sorted(_family_names())}
    files = _generic_files()
    assert {"run.py", "weights.py", "counts.py", "serve.py", "train_mfu.py"} <= {
        os.path.basename(p) for p in files}
    found = []
    for path in files:
        with open(path, errors="replace") as f:
            text = f.read().lower()
        found += [(os.path.relpath(path, CHIP), n) for n, w in words.items() if w.search(text)]
    assert not found


# -- (d) a family added only as a file -----------------------------------------


@pytest.mark.parametrize("cell", ["zoo", "serve"])
def test_a_family_added_as_a_file_runs_to_correct(tmp_path, cell):
    """A copy of the decoder family under another name, outside the
    repository, named by a tiny configuration's ``reference``: the harness
    loads it and the whole run comes out ``correct``."""
    path = tmp_path / "decoder.py"
    shutil.copy(os.path.join(CHIP, "references", "llama.py"), path)
    name, cfg, traffic = tc.CELLS[cell]()
    cfg.update(model="decoder", reference=str(path))
    args = types.SimpleNamespace(workload=name, seed=SEED, seconds=0.5, trace=0)
    res = bench_run.run(args, require_chip=False, bench=tc._bench(name, name.split(".", 1)[1]),
                        cfg=cfg, traffic=traffic)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert sys.modules["benchmarks.chip.references.decoder"].__file__ == str(path)
