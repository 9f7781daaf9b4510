"""Plain multinomial logistic regression: ``softmax(x W + b)``, mean cross
entropy, float32 at ``Precision.HIGHEST`` (or every value rounded to the
lower ``mode`` of ``llama.cast`` for the control)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .llama import HI, cast

__all__ = ["loss", "grad"]


def loss(p, x, y, mode="f32"):
    lg = jnp.matmul(cast(x, mode), cast(p["w"], mode), precision=HI) + cast(p["b"], mode)
    lg = cast(lg, mode)
    lz = jax.nn.logsumexp(lg, axis=-1)
    gold = jnp.take_along_axis(lg, y[..., None], axis=-1)[..., 0]
    return jnp.mean(lz - gold)


@functools.partial(jax.jit, static_argnums=(3,))
def grad(p, x, y, mode):
    return jax.value_and_grad(loss)(p, x, y, mode)
