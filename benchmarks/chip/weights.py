"""The benchmark's weights, made on the device from the seed in one jitted call.

``make(cfg, seed, stream)`` is the one entry point: it calls the
``weights(cfg, key)`` of the configuration's family module (the file its
``reference`` key names) with the key of ``seed`` and ``stream``.  Both the
system under test and the references start from these arrays; the program's
own initialisers are not used.
"""
from __future__ import annotations

from . import datasets, references

__all__ = ["make"]


def make(cfg: dict, seed: int, stream: int = 2):
    """The weights of ``cfg`` from ``seed``; another ``stream`` gives an
    independent set (the serving cells swap between two)."""
    return references.family(cfg).weights(cfg, datasets.seed_key(seed, stream))
