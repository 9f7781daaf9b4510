"""Mamba2 SSD scan as a Pallas TPU kernel (one head per grid row).

TPU adaptation of the CUDA selective-scan: within each chunk the recurrence
is evaluated as two MXU GEMMs (C·Bᵀ ∘ decay) @ X plus a rank-N state
contribution; across chunks a (hd, N) summary state is carried in VMEM
scratch along the sequential chunk grid dimension.  No token-level
recurrence ever touches HBM.

  grid = (B*H, n_chunks)            chunks sequential (state carry)
  x block   (1, Q, hd)   VMEM       dt-weighted head inputs
  da block  (1, Q, 128)  VMEM       per-step log-decay (lane-padded)
  b/c block (1, Q, N)    VMEM
  state     (hd, N) f32  scratch    carried across chunks

Oracle: ref.ssd_reference (sequential scan).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["ssd_scan"]


def _kernel(x_ref, da_ref, b_ref, c_ref, y_ref, state_ref, *, q_len, n_chunks):
    ic = pl.program_id(1)

    @pl.when(ic == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0].astype(jnp.float32)  # (Q, hd)
    da = da_ref[0, :, 0].astype(jnp.float32)  # (Q,)
    b = b_ref[0].astype(jnp.float32)  # (Q, N)
    c = c_ref[0].astype(jnp.float32)  # (Q, N)

    cum = jnp.cumsum(da)  # inclusive cumulative log decay
    # intra-chunk: y[t] = sum_{s<=t} (c_t . b_s) * exp(cum_t - cum_s) * x_s
    seg = cum[:, None] - cum[None, :]  # (Q, Q)
    causal = (
        jax.lax.broadcasted_iota(jnp.int32, (q_len, q_len), 0)
        >= jax.lax.broadcasted_iota(jnp.int32, (q_len, q_len), 1)
    )
    decay = jnp.where(causal, jnp.exp(seg), 0.0)
    cb = jax.lax.dot_general(
        c, b, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (Q, Q)
    y_intra = jax.lax.dot_general(
        cb * decay, x, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )

    # inter-chunk: y[t] += c_t @ (state^T) * exp(cum_t)
    state = state_ref[...]  # (hd, N)
    y_inter = jax.lax.dot_general(
        c * jnp.exp(cum)[:, None], state, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # (Q, hd)

    y_ref[0, ...] = (y_intra + y_inter).astype(y_ref.dtype)

    # state update: S <- exp(sum da) * S + sum_s exp(cum_last - cum_s) x_s b_s^T
    w = jnp.exp(cum[-1] - cum)  # (Q,)
    state_new = state * jnp.exp(cum[-1]) + jax.lax.dot_general(
        x * w[:, None], b, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )  # (hd, N)
    state_ref[...] = state_new


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(
    x: jax.Array,
    da: jax.Array,
    b: jax.Array,
    c: jax.Array,
    *,
    chunk: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """x (BH, S, hd); da (BH, S) log decays; b, c (BH, S, N). Returns y."""
    bh, s, hd = x.shape
    n = b.shape[-1]
    q = min(chunk, s)
    assert s % q == 0, (s, q)
    n_chunks = s // q
    grid = (bh, n_chunks)
    da_pad = jnp.broadcast_to(da[..., None], (bh, s, 128))

    kernel = functools.partial(_kernel, q_len=q, n_chunks=n_chunks)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, q, hd), lambda ih, ic: (ih, ic, 0)),
            pl.BlockSpec((1, q, 128), lambda ih, ic: (ih, ic, 0)),
            pl.BlockSpec((1, q, n), lambda ih, ic: (ih, ic, 0)),
            pl.BlockSpec((1, q, n), lambda ih, ic: (ih, ic, 0)),
        ],
        out_specs=pl.BlockSpec((1, q, hd), lambda ih, ic: (ih, ic, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, hd), x.dtype),
        scratch_shapes=[pltpu.VMEM((hd, n), jnp.float32)],
        interpret=interpret,
        name="ssd_scan",
    )(x, da_pad, b, c)
