"""The precisions the plain references compute in, shared by every family.

``"f32"`` is float32 with every product at ``Precision.HIGHEST``; a lower
mode (``"bf16"``, ``"fp8"``) rounds each value to that type first, which is
how the control of the correctness check computes (the configuration's
``control_mode``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["HI", "cast"]

HI = jax.lax.Precision.HIGHEST

_LOWER = {"f32": None, "bf16": jnp.bfloat16, "fp8": jnp.float8_e4m3fn}


def cast(x, mode: str):
    """Round ``x`` to the precision ``mode`` computes in, back in float32."""
    low = _LOWER[mode]
    return x if low is None else x.astype(low).astype(jnp.float32)
