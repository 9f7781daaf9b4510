"""RMSNorm as a Pallas kernel — bandwidth-bound normalization used everywhere.

  grid = (n_row_blocks,)
  x block (BR, D) VMEM -> y block (BR, D)

Oracle: ref.rmsnorm_reference.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["rmsnorm"]


def _kernel(x_ref, s_ref, y_ref, *, eps):
    x = x_ref[...].astype(jnp.float32)
    scale = s_ref[...].astype(jnp.float32)  # (1, D)
    norm = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    y_ref[...] = (norm * (1.0 + scale)).astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("eps", "block_rows", "interpret"))
def rmsnorm(
    x: jax.Array, scale: jax.Array, eps: float = 1e-6, block_rows: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """x (R, D); scale (D,)."""
    r, d = x.shape
    br = min(block_rows, r)
    assert r % br == 0, (r, br)
    kernel = functools.partial(_kernel, eps=eps)
    return pl.pallas_call(
        kernel,
        grid=(r // br,),
        in_specs=[
            pl.BlockSpec((br, d), lambda i: (i, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((br, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r, d), x.dtype),
        interpret=interpret,
        name="rmsnorm",
    )(x, scale[None, :])
