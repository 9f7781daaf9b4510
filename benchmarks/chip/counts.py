"""Operations and bytes of kernels from shapes, and the peaks they are divided by.

A model's FLOPs are its family's to count (``train_flops`` / ``serve_flops``
of the module that a configuration's ``reference`` names).  Everything is
counted from shapes, never from the compiled program: a program that
recomputes (``remat``) or pads does more work than these counts, and that
extra work does not count as useful.
"""
from __future__ import annotations

import json
import os

__all__ = ["peaks", "dequant_agg_cost", "waterfill_cost"]

_HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> dict:
    """Published per-chip peaks of ``device_kind`` (``peaks.json``).  A
    device that is not in the table is an error, not a default."""
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def dequant_agg_cost(c: int, d: int, scale_block: int) -> tuple[float, float]:
    """(ops, bytes) of one ``fused_dequant_cohort_agg`` call over a (c, d)
    int8 buffer with one f32 scale per ``scale_block`` codes: every code is
    read once and widened (1 op), scaled (1), weighted into the estimate and
    the error row (2 multiply-adds, 4 ops) and squared into its slot's norm
    (2); the (d,) f32 estimate is written once."""
    ops = 8.0 * c * d
    nbytes = c * d * 1 + c * (d // scale_block) * 4 + d * 4
    return ops, float(nbytes)


def waterfill_cost(m: int, levels: int) -> tuple[float, float]:
    """(ops, bytes) of one ``waterfill_level_stats`` call over ``m`` sorted f32
    scores and ``levels`` water levels: each (score, level) pair is compared
    twice and added into the middle sum (4 ops); the scores are read once."""
    return 4.0 * m * levels, 4.0 * m + 3 * 4.0 * levels
