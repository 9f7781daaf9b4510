"""Operations and bytes from shapes, and the peaks they are divided by.

Everything is counted from the configuration's sizes, never from the
compiled program: a program that recomputes (``remat``) or pads does more
work than these counts, and that extra work does not count as useful.
"""
from __future__ import annotations

import json
import os

__all__ = ["peaks", "llama_matmul_params", "llama_train_flops", "logreg_train_flops",
           "llama_forward_flops", "llama_decode_flops", "dequant_agg_cost",
           "waterfill_cost"]

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


def llama_matmul_params(m: dict) -> int:
    """Weights that take part in a matmul per token: the attention and MLP
    projections of every layer and the LM head (tied or not).  The embedding
    lookup is a gather and costs no FLOPs."""
    d, f, L, V = m["hidden_size"], m["intermediate_size"], m["num_hidden_layers"], m["vocab_size"]
    hd = m["head_dim"]
    h, kv = m["num_attention_heads"], m["num_key_value_heads"]
    per_layer = d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * f
    return L * per_layer + d * V


def _attn_flops_per_token(m: dict, ctx: int) -> int:
    """Forward score and value products of one token against ``ctx``
    positions, every layer: 2 matmuls x 2 FLOPs x heads x head size x ctx."""
    return 4 * m["num_hidden_layers"] * m["num_attention_heads"] * m["head_dim"] * ctx


def llama_train_flops(m: dict, tokens: int, seq: int) -> float:
    """Model FLOPs of forward and backward over ``tokens`` tokens in sequences
    of ``seq``: 6 x matmul params per token plus 3 x the forward attention
    products at the full sequence length (the PaLM appendix B count).
    Recomputation under ``remat`` is not counted."""
    return float(tokens) * (6 * llama_matmul_params(m) + 3 * _attn_flops_per_token(m, seq))


def logreg_train_flops(m: dict, samples: int) -> float:
    """Forward and backward of ``x W + b`` over ``samples`` rows: 6 FLOPs per
    weight per row (the bias and the softmax are not counted)."""
    return 6.0 * samples * m["dim"] * m["n_classes"]


def llama_forward_flops(m: dict, batch: int, seq: int) -> float:
    """Prefill: forward over ``batch`` prompts of ``seq`` tokens, attention
    over the causal half of the (seq, seq) products."""
    tokens = batch * seq
    return float(tokens) * 2 * llama_matmul_params(m) + batch * _attn_flops_per_token(
        m, seq) * seq / 2


def llama_decode_flops(m: dict, batch: int, ctx: int) -> float:
    """One decode step of ``batch`` sequences with ``ctx`` cached positions."""
    return float(batch) * (2 * llama_matmul_params(m) + _attn_flops_per_token(m, ctx))


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
