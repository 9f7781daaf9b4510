"""Plain references, independent of the system under test.

A configuration names its family module in ``reference``, a path relative
to ``benchmarks/chip`` (``references/<family>.py``), and that module is the
one place that knows the family.  ``family(cfg)`` loads it by path.  Every
family module exports plain functions of the configuration dict:

* ``weights(cfg, key)``: the parameters, in the layout the program is fed
  and the type it serves them in, made from ``key`` in one jitted call;
* ``train_flops(cfg, traffic)``: model FLOPs of one round's local training
  (a ``fl_round`` traffic dict), recomputation not counted;
* ``grad``: (loss, gradient) in float32, or in a lower ``mode``
  (``precision.cast``) for the control; a ``zoo`` stack family takes
  ``(params, tokens, targets, cfg_items, mode)``, a ``task`` stack family
  ``(params, x, y, mode)``;
* ``check_program(cfg, arch)``, ``zoo`` stack families: raises where the
  program's ``ArchConfig`` differs from the configuration file;
* ``serve_flops(cfg, batch, prompt_len, new_tokens)`` and ``served_gaps``,
  families that can be served: one batch's prefill and decode FLOPs, and
  the reference's logit gaps of served tokens (``generators/serve.py``).

The other modules here (``fedavg``, ``sampler``, ``quant``, ``precision``)
are shared by every family.
"""
from __future__ import annotations

import importlib.util
import os
import sys

__all__ = ["family"]

_CHIP = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def family(cfg: dict):
    """The module that ``cfg["reference"]`` names, loaded once by path.  It
    is a module of this package by name, so its relative imports of the
    shared modules resolve wherever its file lies."""
    path = os.path.normpath(os.path.join(_CHIP, cfg["reference"]))
    name = __name__ + "." + os.path.splitext(os.path.basename(path))[0]
    mod = sys.modules.get(name)
    if mod is None or os.path.normpath(mod.__file__) != path:
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        sys.modules[name] = mod
    return mod
