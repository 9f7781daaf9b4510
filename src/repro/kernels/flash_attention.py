"""Flash attention (forward) as a Pallas TPU kernel.

Blockwise online-softmax over the KV sequence with explicit VMEM tiling:

  grid = (heads, n_q_blocks, n_kv_blocks)   — kv innermost, sequential
  q block    (1, BQ, hd)   VMEM
  k/v block  (1, BK, hd)   VMEM
  scratch    acc (BQ, hd) f32, m/l (BQ, 128) f32 persisted across kv steps

Tile sizes default to MXU-aligned 128 (BQ) x 128 (BK); hd is kept whole
(<=256 for every assigned arch).  Causal and sliding-window masks are applied
from absolute block offsets; soft-capping (gemma2) happens pre-mask.
The kernel is numerically exact w.r.t. `ref.mha_reference` up to dtype.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention"]

_NEG = -2.3819763e38


def _kernel(
    q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
    *, scale, causal, window, softcap, bq, bk, n_kv,
):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0].astype(jnp.float32)  # (BQ, hd)
    k = k_ref[0].astype(jnp.float32)  # (BK, hd)
    v = v_ref[0].astype(jnp.float32)

    logits = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # (BQ, BK)
    if softcap is not None:
        logits = softcap * jnp.tanh(logits / softcap)

    qpos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.ones((bq, bk), jnp.bool_)
    if causal:
        mask = kpos <= qpos
    if window is not None:
        mask = jnp.logical_and(mask, kpos > qpos - window)
    logits = jnp.where(mask, logits, _NEG)

    m_prev = m_ref[:, 0]  # (BQ,)
    l_prev = l_ref[:, 0]
    m_cur = jnp.max(logits, axis=-1)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(logits - m_new[:, None])  # (BQ, BK)
    correction = jnp.exp(m_prev - m_new)
    l_new = correction * l_prev + jnp.sum(p, axis=-1)
    acc_ref[...] = acc_ref[...] * correction[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    m_ref[...] = jnp.broadcast_to(m_new[:, None], m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_new[:, None], l_ref.shape)

    @pl.when(ik == n_kv - 1)
    def _done():
        # fully-masked rows (l == 0) produce 0, not NaN
        l = l_ref[:, 0]
        safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, ...] = (acc_ref[...] / safe[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "softcap", "block_q", "block_k", "interpret"),
)
def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int | None = None,
    softcap: float | None = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """q,k,v: (H, S, hd) — collapsed batch*heads leading dim. Returns (H,S,hd)."""
    h, s_q, hd = q.shape
    s_k = k.shape[1]
    bq = min(block_q, s_q)
    bk = min(block_k, s_k)
    assert s_q % bq == 0 and s_k % bk == 0, (s_q, s_k, bq, bk)
    n_q, n_kv = s_q // bq, s_k // bk
    grid = (h, n_q, n_kv)

    kernel = functools.partial(
        _kernel,
        scale=hd**-0.5,
        causal=causal,
        window=window,
        softcap=softcap,
        bq=bq,
        bk=bk,
        n_kv=n_kv,
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda h_, iq, ik: (h_, iq, 0)),
            pl.BlockSpec((1, bk, hd), lambda h_, iq, ik: (h_, ik, 0)),
            pl.BlockSpec((1, bk, hd), lambda h_, iq, ik: (h_, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda h_, iq, ik: (h_, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((h, s_q, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, hd), jnp.float32),  # acc
            pltpu.VMEM((bq, 128), jnp.float32),  # running max (lane-padded)
            pltpu.VMEM((bq, 128), jnp.float32),  # running denom
        ],
        interpret=interpret,
        name="flash_attention",
    )(q, k, v)
