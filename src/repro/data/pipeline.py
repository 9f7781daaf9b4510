"""Federated dataset container + batching.

Clients hold ragged datasets; for TPU-friendly vmapped simulation we pad all
clients to the max size and carry a validity mask.  Batch selection draws
uniformly from each client's valid region (with replacement across steps,
matching stochastic local SGD).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["FederatedDataset", "synthetic_classification", "synthetic_tokens"]


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class FederatedDataset:
    """Padded per-client data: features (N, S_max, ...), labels (N, S_max).

    A pytree, so round programs take it as a jit argument rather than
    compiling its arrays in as constants."""

    features: jax.Array
    labels: jax.Array
    sizes: jax.Array  # (N,) valid count per client

    @property
    def n_clients(self) -> int:
        return self.features.shape[0]

    @property
    def lam(self) -> jax.Array:
        """Client objective weights lambda_i proportional to dataset size
        (the FedAvg weighting of eq. 1)."""
        s = self.sizes.astype(jnp.float32)
        return s / jnp.sum(s)

    def client_batch(self, client: jax.Array, key: jax.Array, batch_size: int):
        """Uniform-with-replacement batch from one client's valid region."""
        idx = jax.random.randint(key, (batch_size,), 0, self.sizes[client])
        return self.features[client, idx], self.labels[client, idx]

    def rows(self, ids: jax.Array) -> "FederatedDataset":
        """The clients ``ids`` as a dataset of their own: whole rows gathered
        along the client axis, so row ``j`` is client ``ids[j]``.

        A round gathers its cohort's rows once and samples each slot's batches
        from them (``rows(ids).client_batch(j, ...)``, the same batches as
        ``client_batch(ids[j], ...)``).  Sampled straight from the full
        dataset under a per-slot ``vmap``, the batches are one point gather
        over the first two axes, for which the TPU compiler relayouts the
        whole (N, S_max, ...) array into a padded temporary on every call."""
        return FederatedDataset(
            features=self.features[ids], labels=self.labels[ids], sizes=self.sizes[ids]
        )

    def batch_all_clients(self, key: jax.Array, batch_size: int):
        """(N, B, ...) batches for vmapped full-cohort simulation."""
        keys = jax.random.split(key, self.n_clients)
        return jax.vmap(lambda client, k: self.client_batch(client, k, batch_size))(
            jnp.arange(self.n_clients), keys
        )


def synthetic_classification(
    n_clients: int = 100,
    alpha: float = 1.0,
    beta: float = 1.0,
    dim: int = 60,
    n_classes: int = 10,
    total: int = 20000,
    power: float = 1.5,
    seed: int = 0,
) -> FederatedDataset:
    """Synthetic(alpha, beta) of Li et al. 2020 — the paper's Section 6.1 task.

    Per client i: u_i ~ N(0, alpha); W_i ~ N(u_i, 1) in R^{C x d},
    b_i ~ N(u_i, 1); v_i ~ N(B_i, 1) with B_i ~ N(0, beta);
    x ~ N(v_i, diag(j^-1.2)); y = argmax(W_i x + b_i).  Sizes ~ power law.
    """
    from repro.data.partition import power_law_sizes

    rng = np.random.default_rng(seed)
    sizes = power_law_sizes(n_clients, total, alpha=power, seed=seed)
    s_max = int(sizes.max())
    feats = np.zeros((n_clients, s_max, dim), np.float32)
    labels = np.zeros((n_clients, s_max), np.int32)
    cov_diag = np.arange(1, dim + 1, dtype=np.float64) ** (-1.2)
    for i in range(n_clients):
        u = rng.normal(0, np.sqrt(alpha))
        b_mean = rng.normal(0, np.sqrt(beta))
        w = rng.normal(u, 1.0, size=(n_classes, dim))
        b = rng.normal(u, 1.0, size=(n_classes,))
        v = rng.normal(b_mean, 1.0, size=(dim,))
        x = rng.normal(v, np.sqrt(cov_diag), size=(int(sizes[i]), dim))
        logits = x @ w.T + b
        y = logits.argmax(axis=1)
        feats[i, : sizes[i]] = x.astype(np.float32)
        labels[i, : sizes[i]] = y.astype(np.int32)
        # pad region repeats the first sample (masked out by `sizes`)
        feats[i, sizes[i] :] = feats[i, 0]
        labels[i, sizes[i] :] = labels[i, 0]
    return FederatedDataset(
        features=jnp.asarray(feats), labels=jnp.asarray(labels), sizes=jnp.asarray(sizes)
    )


def synthetic_tokens(
    n_clients: int,
    seq_len: int,
    vocab: int,
    total_seqs: int,
    power: float = 1.5,
    n_styles: int = 8,
    seed: int = 0,
) -> FederatedDataset:
    """Heterogeneous federated token streams (Section 6.3 scaled down).

    Each client draws from one of ``n_styles`` Markov-ish token generators so
    client gradients genuinely differ (heterogeneity drives the sampler).
    """
    rng = np.random.default_rng(seed)
    from repro.data.partition import power_law_sizes

    sizes = power_law_sizes(n_clients, total_seqs, alpha=power, seed=seed)
    s_max = int(sizes.max())
    toks = np.zeros((n_clients, s_max, seq_len), np.int32)
    # style = a biased unigram distribution + shift pattern
    styles = rng.dirichlet(np.full(vocab, 0.1), size=n_styles)
    for i in range(n_clients):
        st = styles[i % n_styles]
        t = rng.choice(vocab, p=st, size=(int(sizes[i]), seq_len))
        # inject determinism: next token correlated with previous (shift+1 mod vocab)
        t[:, 1::2] = (t[:, 0::2][:, : t[:, 1::2].shape[1]] + 1) % vocab
        toks[i, : sizes[i]] = t
        toks[i, sizes[i] :] = toks[i, 0]
    labels = np.roll(toks, -1, axis=-1)
    return FederatedDataset(
        features=jnp.asarray(toks), labels=jnp.asarray(labels), sizes=jnp.asarray(sizes)
    )
