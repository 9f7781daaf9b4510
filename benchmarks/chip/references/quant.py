"""Plain int8 delta compression with server error feedback.

Each kept client's update is flattened (leaves in pytree order) to float32
and cut into blocks of ``scale_block``; a block's scale is its largest
magnitude over 127 (1 for an all-zero block), its codes are
``clip(round(x / scale), -127, 127)``.  The server sums ``w_c * code *
scale`` into ``d_hat``, and with error feedback applies ``d_hat + resid``
and keeps ``resid = sum_c w_c x_c - d_hat`` for the next round.  The
feedback norm of a client is the norm of its dequantized update.  The
applied update is cast back to each leaf's type.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = ["aggregator", "init_state"]

QMAX = 127.0


def _flat(tree):
    return jnp.concatenate([x.reshape(-1).astype(jnp.float32)
                            for x in jax.tree_util.tree_leaves(tree)])


@functools.partial(jax.jit, static_argnums=(1,))
def _dequant(flat, sb):
    d = flat.shape[0]
    nb = -(-d // sb)
    x = jnp.pad(flat, (0, nb * sb - d)).reshape(nb, sb)
    amax = jnp.max(jnp.abs(x), axis=1)
    scale = jnp.where(amax > 0, amax / QMAX, 1.0)
    q = jnp.clip(jnp.round(x / scale[:, None]), -QMAX, QMAX)
    return (q * scale[:, None]).reshape(-1)[:d]


@jax.jit
def _acc(d_hat, d_true, deq, flat, w):
    return d_hat + w * deq, d_true + w * flat


def _unflat(vec, like):
    leaves, treedef = jax.tree_util.tree_flatten(like)
    out, off = [], 0
    for x in leaves:
        out.append(vec[off: off + x.size].reshape(x.shape).astype(x.dtype))
        off += x.size
    return jax.tree_util.tree_unflatten(treedef, out)


def init_state(_cfg, _seed):
    return None  # the residual starts at zero, sized on first use


def aggregator(comp: dict):
    if comp.get("delta_dtype") != "int8":
        raise ValueError(f"the plain reference covers int8 deltas, not {comp!r}")
    sb = int(comp.get("scale_block", 128))
    ef = bool(comp.get("error_feedback", True))

    def aggregate(deltas, w, resid, like):
        d = sum(x.size for x in jax.tree_util.tree_leaves(like))
        d_hat = d_true = jnp.zeros((d,), jnp.float32)
        norms = []
        for dl, wc in zip(deltas, w):
            flat = _flat(dl)
            deq = _dequant(flat, sb)
            norms.append(float(jnp.sqrt(jnp.sum(deq * deq))))
            d_hat, d_true = _acc(d_hat, d_true, deq, flat, jnp.float32(wc))
        if resid is None:
            resid = jnp.zeros_like(d_hat)
        applied = d_hat + resid if ef else d_hat
        new_resid = d_true - d_hat if ef else resid
        # The update takes the type of the clients' deltas, which is the
        # parameters' type.
        return _unflat(applied, like), norms, new_resid

    return aggregate
