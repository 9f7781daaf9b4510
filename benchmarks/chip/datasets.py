"""Device-side federated datasets, made from the run's seed in one jitted call.

Two generators, registered with ``repro.api.register_dataset`` under the
names below so the program builds its experiment from them:

* ``bench_tokens`` — heterogeneous token streams: each client draws from one
  of ``n_styles`` unigram distributions (Dirichlet(0.1) over the vocabulary,
  sampled by inverse CDF), and every odd position is the previous token plus
  one, the pattern of ``repro.data.pipeline.synthetic_tokens``.  Every client
  holds ``seqs_per_client`` sequences, so client weights are equal.
* ``bench_synthetic`` — Synthetic(alpha, beta) of Li et al. (FedProx,
  arXiv:1812.06127, section 5): per client u ~ N(0, alpha), B ~ N(0, beta),
  W ~ N(u, 1) in R^{C x d}, b ~ N(u, 1), v ~ N(B, 1), x ~ N(v, diag(j^-1.2)),
  y = argmax(W x + b).  Equal client sizes (``samples_per_client``): a padded
  (N, S_max, d) layout cannot hold power-law sizes at 10^6 clients.  Clients
  are generated in blocks so that no (N, C, d) array of client models exists.

Both return ``repro.data.pipeline.FederatedDataset``, the program's input
format; nothing else of the program is used.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = ["seed_key", "tokens", "synthetic", "register"]


def seed_key(seed: int, stream: int = 0) -> jax.Array:
    """A PRNG key from any non-negative seed (more than 32 bits allowed),
    folded with a stream id so that weights, data and keys never share bits."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    key = jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)
    return jax.random.fold_in(key, stream)


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4, 5))
def _tokens(key, n_clients, seqs, seq_len, vocab, n_styles):
    k_style, k_tok = jax.random.split(key)
    probs = jax.random.dirichlet(k_style, jnp.full((vocab,), 0.1), (n_styles,))
    cdf = jnp.cumsum(probs, axis=-1)
    cdf = cdf / cdf[:, -1:]
    u = jax.random.uniform(k_tok, (n_clients, seqs, seq_len))
    style = jnp.arange(n_clients) % n_styles

    def one(c, uc):
        return jnp.minimum(jnp.searchsorted(c, uc.reshape(-1)), vocab - 1).reshape(uc.shape)

    toks = jax.vmap(one)(cdf[style], u).astype(jnp.int32)
    toks = toks.at[:, :, 1::2].set((toks[:, :, 0::2][:, :, : seq_len // 2] + 1) % vocab)
    return toks, jnp.roll(toks, -1, axis=-1)


def tokens(n_clients: int, seqs_per_client: int, seq_len: int, vocab: int,
           n_styles: int = 8, seed: int = 0):
    from repro.data.pipeline import FederatedDataset

    feats, labels = _tokens(seed_key(seed, 1), n_clients, seqs_per_client,
                            seq_len, vocab, n_styles)
    sizes = jnp.full((n_clients,), seqs_per_client, jnp.int32)
    return FederatedDataset(features=feats, labels=labels, sizes=sizes)


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4, 5, 6, 7))
def _synthetic(key, n_clients, samples, dim, n_classes, alpha, beta, block):
    n_blocks = -(-n_clients // block)
    sd = jnp.arange(1, dim + 1, dtype=jnp.float32) ** -0.6  # sqrt(j^-1.2)

    def one_block(i):
        ks = jax.random.split(jax.random.fold_in(key, i), 6)
        u = jax.random.normal(ks[0], (block, 1, 1)) * jnp.sqrt(alpha)
        b_mean = jax.random.normal(ks[1], (block, 1)) * jnp.sqrt(beta)
        w = u + jax.random.normal(ks[2], (block, n_classes, dim))
        b = u[:, :, 0] + jax.random.normal(ks[3], (block, n_classes))
        v = b_mean + jax.random.normal(ks[4], (block, dim))
        x = v[:, None, :] + sd * jax.random.normal(ks[5], (block, samples, dim))
        logits = jnp.einsum("bsd,bcd->bsc", x, w,
                            precision=jax.lax.Precision.HIGHEST) + b[:, None, :]
        return x, jnp.argmax(logits, axis=-1).astype(jnp.int32)

    x, y = jax.lax.map(one_block, jnp.arange(n_blocks))
    x = x.reshape(n_blocks * block, samples, dim)[:n_clients]
    y = y.reshape(n_blocks * block, samples)[:n_clients]
    return x, y


def synthetic(n_clients: int, samples_per_client: int, dim: int = 60,
              n_classes: int = 10, alpha: float = 1.0, beta: float = 1.0,
              seed: int = 0, block: int = 16384):
    from repro.data.pipeline import FederatedDataset

    x, y = _synthetic(seed_key(seed, 1), n_clients, samples_per_client, dim,
                      n_classes, float(alpha), float(beta), min(block, n_clients))
    sizes = jnp.full((n_clients,), samples_per_client, jnp.int32)
    return FederatedDataset(features=x, labels=y, sizes=sizes)


GENERATORS = {"bench_tokens": tokens, "bench_synthetic": synthetic}


def register() -> None:
    from repro import api

    for name, fn in GENERATORS.items():
        if name not in api.dataset_names():
            api.register_dataset(name, fn)
