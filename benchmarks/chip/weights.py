"""The benchmark's weights, made on the device from the seed in one jitted call.

``make(cfg, seed)`` dispatches on the configuration's ``model`` key to the
function of that name here.  Both the system under test and the references
start from these arrays; the program's own initialisers are not used.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import datasets

__all__ = ["make", "llama", "logreg"]


def _items(cfg: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, str, bool))))


@functools.partial(jax.jit, static_argnums=(1,))
def _llama(key, items):
    m = dict(items)
    d, f, L, V = m["hidden_size"], m["intermediate_size"], m["num_hidden_layers"], m["vocab_size"]
    hd, h, kv = m["head_dim"], m["num_attention_heads"], m["num_key_value_heads"]
    dt = jnp.dtype(m["torch_dtype"])
    ks = iter(jax.random.split(key, 16))

    def uni(shape, fan_in):
        bound = fan_in ** -0.5
        return jax.random.uniform(next(ks), shape, jnp.float32, -bound, bound).astype(dt)

    def norm_scale(shape):
        return (0.05 * jax.random.normal(next(ks), shape)).astype(dt)

    layers = {
        "ln1": norm_scale((L, d)),
        "attn": {"wq": uni((L, d, h * hd), d), "wk": uni((L, d, kv * hd), d),
                 "wv": uni((L, d, kv * hd), d), "wo": uni((L, h * hd, d), h * hd)},
        "ln2": norm_scale((L, d)),
        "mlp": {"up": uni((L, d, f), d), "down": uni((L, f, d), f), "gate": uni((L, d, f), d)},
    }
    return {
        "embed": (0.02 * jax.random.normal(next(ks), (V, d))).astype(dt),
        "final_norm": norm_scale((d,)),
        "stacks": [layers],
    }


def llama(cfg: dict, seed: int, stream: int = 2):
    return _llama(datasets.seed_key(seed, stream), _items(cfg))


@functools.partial(jax.jit, static_argnums=(1, 2))
def _logreg(key, dim, n_classes):
    return {"w": 0.01 * jax.random.normal(key, (dim, n_classes), jnp.float32),
            "b": jnp.zeros((n_classes,), jnp.float32)}


def logreg(cfg: dict, seed: int, stream: int = 2):
    return _logreg(datasets.seed_key(seed, stream), cfg["dim"], cfg["n_classes"])


def make(cfg: dict, seed: int, stream: int = 2):
    """The weights of ``cfg`` from ``seed``; another ``stream`` gives an
    independent set (the serving cells swap between two)."""
    return globals()[cfg["model"]](cfg, seed, stream)
