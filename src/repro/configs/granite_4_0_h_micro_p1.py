"""granite-4.0-h-micro-p1 — one chip's share of granite-4.0-h-micro at its
published widths: one period of the layer pattern (ten layers: nine Mamba2,
one attention), a stage of a four-stage pipeline over the published 40
layers, and half of the tied embedding/head's rows (50176 of 100352), a
vocabulary-parallel layout over two chips."""
import dataclasses

from repro.configs.granite_4_0_h_micro import CONFIG as _FULL

CONFIG = dataclasses.replace(_FULL, name="granite-4.0-h-micro-p1", n_layers=10, vocab=50176)
