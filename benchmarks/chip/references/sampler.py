"""Plain K-Vib sampling (arXiv:2310.02698, Algorithm 2) and cohort selection.

* Probabilities: the FTRL water-filling solution on ``a = sqrt(stats +
  gamma)`` (Lemma 5.1 with no floor): ``p_i = min(1, a_i / s)`` with the
  level ``s`` found by sorting ``a`` in descending order and trying every
  count ``u`` of saturated clients, ``s_u = (sum of the rest) / (K - u)``;
  then mixed with the uniform ``K/N`` by ``theta = min(1, (N/(T K))^(1/3))``.
* Draw: independent Bernoulli(p_i) from ``uniform(key) < p``.
* Weights: ``lambda_i / p_i`` for drawn clients.
* Cohort of C slots: the C highest of i.i.d. uniform priorities among the
  drawn clients; on overflow the kept weights grow by ``|S| / C``.
* Update: ``stats += feedback^2 / p`` on drawn clients; after the first round
  ``gamma = G^2 N / (theta K)`` with G the mean feedback over drawn clients.

The keys are the run's keys, split as the round's conventions say, so the
same draw comes out of the same probabilities.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["theta", "isp", "probabilities", "draw", "select", "update"]


def theta(n: int, budget: int, horizon: int) -> float:
    return float(min(1.0, (n / (horizon * budget)) ** (1.0 / 3.0)))


@jax.jit
def isp(a, budget):
    """Water-filling ``p = min(1, a / s)`` with ``sum(p) = budget``."""
    n = a.shape[0]
    a = jnp.maximum(a, 1e-30)
    srt = jnp.sort(a)[::-1]
    rest = jnp.cumsum(srt[::-1])[::-1]  # rest[u] = sum of srt[u:]
    u = jnp.arange(n)
    s = rest / jnp.maximum(budget - u, 1e-30)
    ok = (u < budget) & (srt <= s)  # the (u+1)-th largest is not saturated
    level = s[jnp.argmax(ok)]
    return jnp.where(budget >= n, 1.0, jnp.minimum(1.0, a / level))


def probabilities(stats, gamma, budget: int, th: float):
    a = jnp.sqrt(stats + jnp.maximum(gamma, 1e-12))
    p = isp(a, jnp.float32(budget))
    return (1.0 - th) * p + th * budget / stats.shape[0]


def draw(key, p):
    """(mask, uniforms) of the Bernoulli draw."""
    u = jax.random.uniform(key, p.shape)
    return u < p, u


def select(mask, weights, cohort: int, key):
    """Cohort of ``cohort`` slots: (ids of kept clients, their weights, |S|)
    as numpy, ids in slot order."""
    n = mask.shape[0]
    pri = jnp.where(mask, jax.random.uniform(key, (n,)), -1.0)
    order = np.argsort(-np.asarray(pri), kind="stable")[:cohort]
    mask_np = np.asarray(mask)
    n_inc = int(mask_np.sum())
    keep = [int(i) for i in order if mask_np[i]]
    scale = n_inc / cohort if n_inc > cohort else 1.0
    w = np.asarray(weights, np.float64)[keep] * scale
    return keep, w.astype(np.float32), n_inc


def update(stats, gamma, t: int, mask, p, feedback, budget: int, th: float):
    stats = stats + jnp.where(mask, feedback ** 2 / jnp.maximum(p, 1e-30), 0.0)
    if t == 0:
        g = jnp.sum(jnp.where(mask, feedback, 0.0)) / jnp.maximum(jnp.sum(mask), 1)
        gamma = g ** 2 * stats.shape[0] / (th * budget)
    return stats, gamma
