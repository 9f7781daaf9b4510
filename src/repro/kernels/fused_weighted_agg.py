"""Fused ISP-weighted aggregation + feedback norms — the paper's server hot loop.

Algorithm 1 lines 12+14 need, per round, BOTH the global estimate
``d = sum_i (m_i lambda_i / p_i) g_i`` AND the per-client feedback
``pi_i^2 = ||g_i||^2``.  Done naively that is two full HBM passes over the
stacked client updates (the largest tensor the server touches).  This kernel
produces both in ONE pass:

  grid = (n_chunks,)                 chunks over the flattened param dim
  g block   (C, BD)  VMEM            stacked client-update chunk
  w block   (C, 1)   VMEM            estimator weights (m lambda / p)
  d out     (1, BD)                  weighted aggregate chunk
  sq scratch (C, 128) f32            per-client partial squared norms,
                                     accumulated across chunks, emitted last

Oracle: ref.weighted_agg_reference.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "fused_weighted_agg",
    "fused_multi_weighted_agg",
    "fused_cohort_agg_and_error",
    "quantize_stacked",
    "dequantize_stacked",
    "dequant_cohort_agg_reference",
    "dequant_block_d",
    "fused_dequant_cohort_agg",
]

# Saturation point of each supported delta width: int8 symmetric round-to-
# nearest keeps +-127 (the -128 code is unused so the grid is symmetric);
# float8_e4m3fn's largest finite value is 448.
_QMAX = {"int8": 127.0, "fp8": 448.0}

# The weight contractions run in full f32: the estimate is a weighted sum
# whose terms can cancel, and a one-pass bf16 MXU product would round the
# weights to 8 mantissa bits.
_HIGHEST = jax.lax.Precision.HIGHEST


def quant_dtype(name: str):
    """jnp dtype for a delta-width name ('int8' | 'fp8')."""
    if name == "int8":
        return jnp.int8
    if name == "fp8":
        return jnp.float8_e4m3fn
    raise ValueError(f"unknown delta dtype {name!r}")


def quantize_stacked(flat: jax.Array, *, dtype: str = "int8", scale_block: int = 128):
    """Blockwise symmetric quantization of stacked (C, D) f32 deltas.

    Each slot's flattened delta is split into ``scale_block``-wide blocks with
    one fp32 abs-max scale per (slot, block); D is zero-padded internally to a
    block multiple.  Zero blocks get scale 1.0 (any positive value dequantizes
    them exactly, and 1.0 keeps the scale tensor free of zeros/denormals).

    Returns (q (C, D_pad) int8|fp8, scales (C, nb) f32) with
    ``D_pad = nb * scale_block``.
    """
    c, d = flat.shape
    sb = int(scale_block)
    nb = -(-d // sb)
    d_pad = nb * sb
    flat = flat.astype(jnp.float32)
    if d_pad != d:
        flat = jnp.pad(flat, ((0, 0), (0, d_pad - d)))
    blocks = flat.reshape(c, nb, sb)
    absmax = jnp.max(jnp.abs(blocks), axis=2)
    qmax = _QMAX[dtype]
    scales = jnp.where(absmax > 0.0, absmax / qmax, 1.0).astype(jnp.float32)
    scaled = blocks / scales[:, :, None]
    if dtype == "int8":
        q = jnp.clip(jnp.round(scaled), -qmax, qmax).astype(jnp.int8)
    else:
        q = scaled.astype(quant_dtype(dtype))
    return q.reshape(c, d_pad), scales


def dequantize_stacked(q: jax.Array, scales: jax.Array) -> jax.Array:
    """Inverse of ``quantize_stacked``: (C, D_pad) quantized + (C, nb) scales
    -> (C, D_pad) f32.  Reference/CPU path — the fused kernel below performs
    the same widening per VMEM tile instead."""
    c, d_pad = q.shape
    nb = scales.shape[1]
    sb = d_pad // nb
    blocks = q.astype(jnp.float32).reshape(c, nb, sb) * scales[:, :, None]
    return blocks.reshape(c, d_pad)


def dequant_cohort_agg_reference(
    q: jax.Array, scales: jax.Array, w: jax.Array, lam_c: jax.Array
):
    """Pure-jnp oracle for ``fused_dequant_cohort_agg``: blockwise dequant +
    (2, C) x (C, D_pad) contraction + per-slot squared norms.

    Returns (d (D_pad,) f32, err_sq scalar f32, sq_norms (C,) f32).
    """
    c, d_pad = q.shape
    nb = scales.shape[1]
    sb = d_pad // nb
    blocks = q.astype(jnp.float32).reshape(c, nb, sb) * scales[:, :, None]
    w2 = jnp.stack(
        [w.astype(jnp.float32), w.astype(jnp.float32) - lam_c.astype(jnp.float32)]
    )
    out = jnp.einsum("mc,cbs->mbs", w2, blocks, precision=_HIGHEST).reshape(2, d_pad)
    sq_norms = jnp.sum(blocks * blocks, axis=(1, 2))
    return out[0], jnp.sum(out[1] ** 2), sq_norms


def _kernel(g_ref, w_ref, d_ref, sq_ref, acc_ref, *, n_chunks):
    ic = pl.program_id(0)

    @pl.when(ic == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    g = g_ref[...].astype(jnp.float32)  # (C, BD)
    w = w_ref[...].astype(jnp.float32)  # (C, 1)
    d_ref[0, ...] = jnp.sum(g * w, axis=0).astype(d_ref.dtype)
    acc_ref[:, 0] += jnp.sum(g * g, axis=1)

    @pl.when(ic == n_chunks - 1)
    def _done():
        sq_ref[...] = acc_ref[:, :1]


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def fused_weighted_agg(
    g: jax.Array, w: jax.Array, *, block_d: int = 2048, interpret: bool = False
):
    """g (C, D) stacked flattened client updates; w (C,) weights.

    Returns (d (D,) f32, sq_norms (C,) f32) in a single HBM pass over g.
    """
    c, d = g.shape
    bd = min(block_d, d)
    assert d % bd == 0, (d, bd)
    n_chunks = d // bd
    kernel = functools.partial(_kernel, n_chunks=n_chunks)
    d_out, sq = pl.pallas_call(
        kernel,
        grid=(n_chunks,),
        in_specs=[
            pl.BlockSpec((c, bd), lambda ic: (0, ic)),
            pl.BlockSpec((c, 1), lambda ic: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bd), lambda ic: (0, ic)),
            pl.BlockSpec((c, 1), lambda ic: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, d), jnp.float32),
            jax.ShapeDtypeStruct((c, 1), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((c, 128), jnp.float32)],
        interpret=interpret,
        name="fused_weighted_agg",
    )(g, w[:, None])
    return d_out[0], sq[:, 0]


def _multi_kernel(g_ref, w_ref, d_ref):
    g = g_ref[...].astype(jnp.float32)  # (C, BD)
    w = w_ref[...].astype(jnp.float32)  # (M, C)
    d_ref[...] = jnp.dot(
        w, g, precision=_HIGHEST, preferred_element_type=jnp.float32
    )


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def fused_multi_weighted_agg(
    g: jax.Array, w: jax.Array, *, block_d: int = 2048, interpret: bool = False
):
    """g (C, D) stacked flattened client updates; w (M, C) weight rows.

    Returns (M, D) f32 — M independent weighted aggregates sharing a single
    HBM pass over g.  The compiled server loop uses M=2 (estimator weights +
    estimator-minus-target weights) so the estimate and its squared-error
    diagnostic cost one read of the stacked deltas instead of three.
    """
    c, d = g.shape
    m = w.shape[0]
    bd = min(block_d, d)
    assert d % bd == 0, (d, bd)
    n_chunks = d // bd
    return pl.pallas_call(
        _multi_kernel,
        grid=(n_chunks,),
        in_specs=[
            pl.BlockSpec((c, bd), lambda ic: (0, ic)),
            pl.BlockSpec((m, c), lambda ic: (0, 0)),
        ],
        out_specs=pl.BlockSpec((m, bd), lambda ic: (0, ic)),
        out_shape=jax.ShapeDtypeStruct((m, d), jnp.float32),
        interpret=interpret,
        name="fused_multi_weighted_agg",
    )(g, w)


def _cohort_kernel(g_ref, w2_ref, d_ref, err_ref):
    # err_ref's block index never changes, so the (1, 128) output tile stays
    # resident across the sequential chunk grid and doubles as the
    # accumulator (every lane holds the same running sum; TPU VMEM takes
    # vector stores only, never a scalar).
    @pl.when(pl.program_id(0) == 0)
    def _init():
        err_ref[...] = jnp.zeros_like(err_ref)

    g = g_ref[...].astype(jnp.float32)  # (C, BD)
    w2 = w2_ref[...].astype(jnp.float32)  # (2, C)
    out = jnp.dot(
        w2, g, precision=_HIGHEST, preferred_element_type=jnp.float32
    )  # (2, BD)
    d_ref[...] = out[:1]
    err_ref[...] += jnp.sum(out[1:2] ** 2, axis=1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def fused_cohort_agg_and_error(
    g: jax.Array,
    w: jax.Array,
    lam_c: jax.Array,
    *,
    block_d: int = 2048,
    interpret: bool = False,
):
    """Cohort-width (C, D) entry point: estimate + squared-error in ONE pass.

    g (C, D) stacked flattened cohort deltas; w (C,) estimator weights from
    ``fed.cohort.select_cohort`` (zero on padding); lam_c (C,) the objective
    weights gathered at the cohort ids (zero on padding).

    Returns (d (D,) f32, err_sq scalar f32) where ``d = sum_c w_c g_c`` and
    ``err_sq = || sum_c (w_c - lam_c) g_c ||^2`` — the cohort-supported part
    of the estimator error.  Unlike ``fused_multi_weighted_agg`` driven at N
    width, nothing here is (N, D)-shaped: the error row is squared and
    accumulated across chunks in VMEM scratch, so only the (D,) estimate and
    one scalar ever leave the kernel.
    """
    c, d = g.shape
    bd = min(block_d, d)
    assert d % bd == 0, (d, bd)
    n_chunks = d // bd
    w2 = jnp.stack([w.astype(jnp.float32), w.astype(jnp.float32) - lam_c.astype(jnp.float32)])
    d_out, err = pl.pallas_call(
        _cohort_kernel,
        grid=(n_chunks,),
        in_specs=[
            pl.BlockSpec((c, bd), lambda ic: (0, ic)),
            pl.BlockSpec((2, c), lambda ic: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bd), lambda ic: (0, ic)),
            pl.BlockSpec((1, 128), lambda ic: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, d), jnp.float32),
            jax.ShapeDtypeStruct((1, 128), jnp.float32),
        ],
        interpret=interpret,
        name="fused_cohort_agg_and_error",
    )(g, w2)
    return d_out[0], err[0, 0]


def _dequant_cohort_kernel(q_ref, s_ref, w2_ref, d_ref, err_ref, sqn_ref, *, sb):
    # err_ref / sqn_ref keep one block index for the whole grid: resident
    # vector accumulators, as in ``_cohort_kernel``.
    @pl.when(pl.program_id(0) == 0)
    def _init():
        err_ref[...] = jnp.zeros_like(err_ref)
        sqn_ref[...] = jnp.zeros_like(sqn_ref)

    q = q_ref[...].astype(jnp.float32)  # (C, BD) widened in VMEM only
    s = s_ref[...].astype(jnp.float32)  # (C, BD // sb)
    c, bd = q.shape
    g = (q.reshape(c, bd // sb, sb) * s[:, :, None]).reshape(c, bd)
    w2 = w2_ref[...].astype(jnp.float32)  # (2, C)
    out = jnp.dot(
        w2, g, precision=_HIGHEST, preferred_element_type=jnp.float32
    )  # (2, BD)
    d_ref[...] = out[:1]
    err_ref[...] += jnp.sum(out[1:2] ** 2, axis=1, keepdims=True)
    sqn_ref[...] += jnp.sum(g * g, axis=1, keepdims=True)


def dequant_block_d(d_pad: int, scale_block: int) -> int:
    """Chunk width of ``fused_dequant_cohort_agg``: the whole row when it is
    short, else 128 scale blocks, so the (C, chunk / scale_block) scales tile
    is a whole 128-lane vector row (the TPU tiling rule for a block that is
    not the full array).  Callers pad D_pad to a multiple of it."""
    return min(d_pad, 128 * scale_block)


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def fused_dequant_cohort_agg(
    q: jax.Array,
    scales: jax.Array,
    w: jax.Array,
    lam_c: jax.Array,
    *,
    block_d: int | None = None,
    interpret: bool = False,
):
    """Compressed-width ``fused_cohort_agg_and_error``: the (C, D_pad) stacked
    cohort buffer stays int8/fp8 in HBM and is widened to f32 one VMEM tile at
    a time, fused with the weighted estimate, the squared-error diagnostic,
    and the per-slot dequantized squared norms — the sampler's feedback signal
    computed from exactly the values the estimator saw.  Nothing (C, D)-shaped
    at f32 ever reaches HBM.

    q (C, D_pad) int8|fp8 from ``quantize_stacked``; scales (C, nb) f32 with
    ``nb = D_pad / scale_block``; w / lam_c as in ``fused_cohort_agg_and_error``.
    The chunk width ``block_d`` defaults to ``dequant_block_d``, which
    D_pad must be a multiple of; a narrower one (a multiple of
    ``scale_block``) is legal in interpret mode only.

    Returns (d (D_pad,) f32, err_sq scalar f32, sq_norms (C,) f32).
    """
    c, d_pad = q.shape
    nb = scales.shape[1]
    assert d_pad % nb == 0, (d_pad, nb)
    sb = d_pad // nb
    bd = dequant_block_d(d_pad, sb) if block_d is None else min(block_d, d_pad)
    assert d_pad % bd == 0 and bd % sb == 0, (d_pad, bd, sb)
    n_chunks = d_pad // bd
    w2 = jnp.stack(
        [w.astype(jnp.float32), w.astype(jnp.float32) - lam_c.astype(jnp.float32)]
    )
    kernel = functools.partial(_dequant_cohort_kernel, sb=sb)
    d_out, err, sqn = pl.pallas_call(
        kernel,
        grid=(n_chunks,),
        in_specs=[
            pl.BlockSpec((c, bd), lambda ic: (0, ic)),
            pl.BlockSpec((c, bd // sb), lambda ic: (0, ic)),
            pl.BlockSpec((2, c), lambda ic: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bd), lambda ic: (0, ic)),
            pl.BlockSpec((1, 128), lambda ic: (0, 0)),
            pl.BlockSpec((c, 128), lambda ic: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, d_pad), jnp.float32),
            jax.ShapeDtypeStruct((1, 128), jnp.float32),
            jax.ShapeDtypeStruct((c, 128), jnp.float32),
        ],
        interpret=interpret,
        name="fused_dequant_cohort_agg",
    )(q, scales, w2)
    return d_out[0], err[0, 0], sqn[:, 0]
