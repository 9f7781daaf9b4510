"""The multinomial logistic regression family: ``softmax(x W + b)``, mean
cross entropy, float32 at ``Precision.HIGHEST`` (or every value rounded to
the lower ``mode`` of ``precision.cast`` for the control)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .precision import HI, cast

__all__ = ["weights", "train_flops", "loss", "grad"]


@functools.partial(jax.jit, static_argnums=(1, 2))
def _weights(key, dim, n_classes):
    return {"w": 0.01 * jax.random.normal(key, (dim, n_classes), jnp.float32),
            "b": jnp.zeros((n_classes,), jnp.float32)}


def weights(cfg: dict, key):
    """W ~ N(0, 0.01^2), b = 0, float32."""
    return _weights(key, cfg["dim"], cfg["n_classes"])


def train_flops(m: dict, traffic: dict) -> float:
    """Forward and backward of ``x W + b`` over one round's samples (cohort
    x local steps x batch): 6 FLOPs per weight per row (the bias and the
    softmax are not counted)."""
    fed = traffic["federation"]
    samples = fed["cohort"] * fed["local_steps"] * fed["batch_size"]
    return 6.0 * samples * m["dim"] * m["n_classes"]


def loss(p, x, y, mode="f32"):
    lg = jnp.matmul(cast(x, mode), cast(p["w"], mode), precision=HI) + cast(p["b"], mode)
    lg = cast(lg, mode)
    lz = jax.nn.logsumexp(lg, axis=-1)
    gold = jnp.take_along_axis(lg, y[..., None], axis=-1)[..., 0]
    return jnp.mean(lz - gold)


@functools.partial(jax.jit, static_argnums=(3,))
def grad(p, x, y, mode):
    return jax.value_and_grad(loss)(p, x, y, mode)
