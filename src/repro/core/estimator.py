"""Unbiased global estimation (Definition 2.1) and variance diagnostics.

The server-side estimate of the full-participation update

    d^t = sum_{i in S^t} lambda_i g_i^t / p_i^t          (ISP, mask form)
    d^t = (1/K) sum_{j=1..K} lambda_{i_j} g_{i_j} / q_{i_j}   (RSP-WR form)

operates on *pytrees* of client updates.  Two layouts are supported:

* stacked  — leaves carry a leading client axis (N, ...); used by the
  simulation substrate and the paper-scale experiments.
* weights-only — ``client_weights`` returns the scalar coefficient per client
  so the distributed runtime can pre-scale local shards before the collective
  reduce (DESIGN.md section 3: scale-then-psum, one pass).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.core.samplers import SampleResult

__all__ = [
    "client_weights",
    "aggregate_stacked",
    "full_aggregate_stacked",
    "aggregate_and_error",
    "aggregate_and_error_cohort",
    "aggregate_compressed",
    "isp_variance",
    "rsp_variance_bound",
    "empirical_sq_error",
]


def client_weights(
    draw: SampleResult, lam: jax.Array, procedure: str, budget: int
) -> jax.Array:
    """Scalar aggregation coefficient per client (zero for unsampled).

    The estimator is always ``d = sum_i w_i g_i`` with w from this function —
    the distributed round pre-scales each client's delta by ``w_i`` locally and
    reduces, so estimation costs one collective regardless of procedure.

    Composed-draw contract: the probabilities used here are ``draw.marginals``
    / ``draw.draw_probs`` verbatim, so a draw whose probabilities were
    composed upstream — e.g. ``core.stragglers.available_draw(draw, avail,
    q)``, which multiplies them by the availability probability ``q`` — makes
    this the corrected estimator (``lam / (q p)``) with no extra bookkeeping.
    The 1e-30 floors below are dead-code guards for the masked-out lanes
    only: a drawn client with a genuinely zero probability is a modeling
    error the composers reject (``stragglers.ZeroAvailabilityError`` on the
    host path, mask-to-zero in-trace) before the weight is formed.
    """
    lam = jnp.asarray(lam)
    if procedure == "isp":
        return jnp.where(
            draw.mask, lam / jnp.maximum(draw.marginals, 1e-30), 0.0
        )
    if procedure == "rsp_wr":
        q = jnp.maximum(draw.draw_probs, 1e-30)
        return draw.counts.astype(lam.dtype) * lam / (budget * q)
    if procedure == "rsp_wor":
        # Uniform without replacement: marginal p_i = K/N exactly.
        return jnp.where(
            draw.mask, lam / jnp.maximum(draw.marginals, 1e-30), 0.0
        )
    raise ValueError(f"unknown procedure {procedure!r}")


def aggregate_stacked(updates, weights: jax.Array):
    """d = sum_i w_i * g_i over a stacked pytree (leading client axis)."""

    def agg(leaf):
        w = weights.reshape((-1,) + (1,) * (leaf.ndim - 1)).astype(leaf.dtype)
        return jnp.sum(w * leaf, axis=0)

    return jax.tree_util.tree_map(agg, updates)


def full_aggregate_stacked(updates, lam: jax.Array):
    """Full-participation target sum_i lambda_i g_i."""

    def agg(leaf):
        w = lam.reshape((-1,) + (1,) * (leaf.ndim - 1)).astype(leaf.dtype)
        return jnp.sum(w * leaf, axis=0)

    return jax.tree_util.tree_map(agg, updates)


def _flatten_stacked(updates):
    """Stacked pytree (leading client axis N) -> (N, D) f32 + rebuild spec."""
    leaves, treedef = jax.tree_util.tree_flatten(updates)
    meta = tuple((leaf.shape[1:], leaf.dtype) for leaf in leaves)
    flat = jnp.concatenate(
        [leaf.reshape((leaf.shape[0], -1)).astype(jnp.float32) for leaf in leaves],
        axis=1,
    )
    return flat, (treedef, meta)


def _unflatten_vector(vec: jax.Array, spec):
    treedef, meta = spec
    out, off = [], 0
    for shape, dtype in meta:
        size = math.prod(shape) if shape else 1
        out.append(vec[off : off + size].reshape(shape).astype(dtype))
        off += size
    return jax.tree_util.tree_unflatten(treedef, out)


def aggregate_and_error(updates, weights: jax.Array, lam: jax.Array):
    """Estimate ``d = sum_i w_i g_i`` AND its squared error against the
    full-participation target ``sum_i lambda_i g_i`` in ONE pass over the
    stacked updates.

    The error vector ``sum_i (w_i - lam_i) g_i`` shares the pass: stacking the
    two weight rows turns both reductions into a single (2, N) x (N, D)
    contraction over the flattened deltas — the largest tensor the server
    touches — routed through ``kernels.fused_weighted_agg`` on TPU.

    Returns (estimate pytree, scalar squared error).
    """
    flat, spec = _flatten_stacked(updates)
    w2 = jnp.stack(
        [weights.astype(jnp.float32), weights.astype(jnp.float32) - lam.astype(jnp.float32)]
    )
    if _on_tpu():
        from repro.kernels.fused_weighted_agg import fused_multi_weighted_agg

        bd = _block_d(flat.shape[1])
        out = fused_multi_weighted_agg(_pad_cols(flat, bd), w2, block_d=bd)
    else:
        out = w2 @ flat
    return _unflatten_vector(out[0], spec), jnp.sum(out[1] ** 2)


def _on_tpu() -> bool:
    """The aggregation kernels run on every TPU call; the jnp contractions
    are the CPU path and the reference the kernel tests compare against."""
    return jax.default_backend() == "tpu"


_LANE = 128
_MAX_BLOCK_D = 2048


def _block_d(d_dim: int) -> int:
    """Kernel chunk width for a D-wide row: the whole row rounded up to a
    lane multiple when it is short, else ``_MAX_BLOCK_D``."""
    return min(-(-d_dim // _LANE) * _LANE, _MAX_BLOCK_D)


def _pad_cols(flat: jax.Array, multiple: int) -> jax.Array:
    """Zero-pad the trailing axis of (C, D) to a multiple of ``multiple``;
    zero columns add nothing to any weighted sum or squared norm."""
    pad = (-flat.shape[1]) % multiple
    return jnp.pad(flat, ((0, 0), (0, pad))) if pad else flat


def aggregate_and_error_cohort(updates, weights: jax.Array, lam_cohort: jax.Array):
    """Cohort-width ``aggregate_and_error``: (C, ...) stacked cohort deltas in,
    no (N, D) materialization anywhere.

    ``updates`` carries a leading *cohort-slot* axis C (not the client axis N);
    ``weights`` is ``sel.weights`` from ``fed.cohort.select_cohort`` (zero on
    padding) and ``lam_cohort`` is lambda gathered at ``sel.ids`` and zeroed on
    padding.  The returned estimate equals the scatter-to-N path's estimate in
    exact arithmetic — the off-cohort rows it sums are identically zero — but
    only to float tolerance on hardware (the reduction runs over C terms
    instead of N, so partial-sum order differs; see fed/cohort.py
    "Aggregation width").  The squared error is the cohort-supported error
    ``|| sum_c (w_c - lam_c) delta_c ||^2``, which is what the scatter path's
    diagnostic row also computes when the off-cohort deltas are zero.

    Returns (estimate pytree, scalar squared error).
    """
    flat, spec = _flatten_stacked(updates)
    if _on_tpu():
        from repro.kernels.fused_weighted_agg import fused_cohort_agg_and_error

        d_dim = flat.shape[1]
        bd = _block_d(d_dim)
        d_vec, sq = fused_cohort_agg_and_error(
            _pad_cols(flat, bd), weights, lam_cohort, block_d=bd
        )
        return _unflatten_vector(d_vec[:d_dim], spec), sq
    w2 = jnp.stack(
        [
            weights.astype(jnp.float32),
            weights.astype(jnp.float32) - lam_cohort.astype(jnp.float32),
        ]
    )
    out = w2 @ flat
    return _unflatten_vector(out[0], spec), jnp.sum(out[1] ** 2)


def aggregate_compressed(
    updates, weights: jax.Array, lam_cohort: jax.Array, compression, resid=None
):
    """Compressed-width ``aggregate_and_error_cohort``: quantize the stacked
    cohort deltas to ``compression.delta_dtype`` with per-(slot, block) fp32
    scales, then aggregate via the fused dequantize-in-VMEM kernel so the
    (C, D) buffer crosses HBM at quantized width exactly once.

    ``resid`` enables server-side error feedback: the applied estimate is
    ``d_hat + resid`` and the returned ``new_resid`` is the fresh
    quantization error ``d_true - d_hat`` (``d_true`` = the uncompressed
    aggregate of the transient f32 deltas — the value a per-client residual
    scheme would reconstruct; errors telescope instead of accumulating).
    With ``resid=None`` the raw ``d_hat`` is applied and ``new_resid`` is
    None — the ablation mode where quantization error random-walks.

    Returns (estimate pytree, err_sq scalar, dequantized norms (C,) f32,
    new_resid (D,) f32 | None).  ``err_sq`` and the norms are computed from
    the dequantized values, so the sampler's regret signal is what the
    estimator actually saw.
    """
    from repro.kernels.fused_weighted_agg import (
        dequant_block_d,
        dequant_cohort_agg_reference,
        fused_dequant_cohort_agg,
        quantize_stacked,
    )

    flat, spec = _flatten_stacked(updates)
    d_dim = flat.shape[1]
    sb = int(compression.scale_block)
    on_tpu = _on_tpu()
    # On TPU, pad D to whole kernel chunks up front: zero blocks quantize to
    # zero codes under scale 1.0 and contribute nothing.
    q, scales = quantize_stacked(
        _pad_cols(flat, dequant_block_d(d_dim, sb)) if on_tpu else flat,
        dtype=compression.delta_dtype,
        scale_block=sb,
    )
    if on_tpu:
        d_vec, sq, sqn = fused_dequant_cohort_agg(q, scales, weights, lam_cohort)
    else:
        d_vec, sq, sqn = dequant_cohort_agg_reference(q, scales, weights, lam_cohort)
    d_hat = d_vec[:d_dim]
    new_resid = None
    if resid is not None:
        d_true = jnp.matmul(
            weights.astype(jnp.float32), flat, precision=jax.lax.Precision.HIGHEST
        )
        new_resid = d_true - d_hat
        d_hat = d_hat + resid
    return _unflatten_vector(d_hat, spec), sq, jnp.sqrt(sqn), new_resid


def isp_variance(scores: jax.Array, p: jax.Array) -> jax.Array:
    """Exact ISP estimator variance (Lemma 2.1, equality case):

    V(S) = sum_i (1 - p_i) * a_i^2 / p_i,   a_i = lambda_i ||g_i||.
    """
    scores = jnp.asarray(scores)
    p = jnp.asarray(p)
    return jnp.sum((1.0 - p) * scores**2 / jnp.maximum(p, 1e-30))


def rsp_variance_bound(scores: jax.Array, p: jax.Array, budget: int) -> jax.Array:
    """RSP upper bound of Lemma 2.1: (N-K)/(N-1) * sum_i a_i^2 / p_i."""
    scores = jnp.asarray(scores)
    n = scores.shape[0]
    coef = (n - budget) / max(n - 1, 1)
    return coef * jnp.sum(scores**2 / jnp.maximum(p, 1e-30))


def empirical_sq_error(estimate, target) -> jax.Array:
    """|| d - sum lambda g ||^2 across a pytree."""
    sq = jax.tree_util.tree_map(
        lambda a, b: jnp.sum((a.astype(jnp.float32) - b.astype(jnp.float32)) ** 2),
        estimate,
        target,
    )
    return jax.tree_util.tree_reduce(jnp.add, sq)
