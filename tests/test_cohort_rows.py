"""A round gathers its cohort's data rows once, then samples each slot's
batches from its own row (``FederatedDataset.rows``).  The batches, and so
every delta, loss, norm and draw after them, are bit for bit those of the
per-slot point gather ``features[ids[j], idx]`` into the whole dataset that
the rows replace: on a ragged dataset, with padding slots in the cohort, in
both execution stacks."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import make_sampler
from repro.data import FederatedDataset, synthetic_classification, synthetic_tokens
from repro.fed import FedConfig, logistic_regression
from repro.fed import server as fed_server
from repro.fed.round import RoundSpec, build_fed_scan
from repro.models import transformer

COHORT = 6


class _PointGather:
    """The gather the rows replace: slot ``j`` indexes client ``ids[j]``
    straight out of the whole dataset."""

    built = 0

    def __init__(self, dataset, ids):
        self.dataset, self.ids = dataset, ids
        type(self).built += 1

    def client_batch(self, j, key, batch_size):
        return self.dataset.client_batch(self.ids[j], key, batch_size)


def _dataset(stack):
    if stack == "task":
        return synthetic_classification(n_clients=12, total=600, seed=7)
    return synthetic_tokens(n_clients=12, seq_len=16, vocab=128, total_seqs=256, seed=3)


def _one_round(stack, ds):
    """(state after one round's leaves, cohort size) of the stack's compiled
    deployable round: budget 2 in COHORT slots, so slots are padding."""
    sampler = make_sampler("kvib", n=ds.n_clients, budget=2, horizon=4)
    if stack == "task":
        cfg = FedConfig(rounds=2, budget=2, local_steps=2, batch_size=5, local_lr=0.05,
                        seed=11, oracle_metrics=False, cohort=COHORT)
        segment, state = fed_server.build_segment_runner(
            logistic_regression(), ds, sampler, cfg, donate=False)
        state = segment(state, 1)
        return jax.tree_util.tree_leaves(state), int(state.metrics["cohort_size"][0])
    cfg = get_config("smollm-360m").reduced(n_layers=2, d_model=64, d_ff=128, vocab=128)
    spec = RoundSpec(cohort=COHORT, local_steps=2, local_lr=0.05, local_batch=3)
    run = build_fed_scan(cfg, spec, sampler, ds)
    params = transformer.init_params(cfg, jax.random.PRNGKey(5))
    keys = jnp.stack([jnp.stack(list(jax.random.split(jax.random.PRNGKey(9), 2)))])
    params, s_state, metrics = run(params, sampler.init(), keys)
    return (jax.tree_util.tree_leaves((params, s_state, metrics)),
            int(metrics["cohort_size"][0]))


@pytest.mark.parametrize("stack", ["task", "zoo"])
def test_cohort_rows_are_bitwise_the_point_gather(monkeypatch, stack):
    ds = _dataset(stack)
    assert len(set(np.asarray(ds.sizes).tolist())) > 1  # ragged clients

    # The batches: duplicate ids, as a cohort's padding slots may repeat.
    ids = jnp.asarray([7, 0, 11, 3, 3, 5], jnp.int32)
    keys = jax.random.split(jax.random.PRNGKey(1), ids.shape[0])
    rows = ds.rows(ids)
    slots = jnp.arange(ids.shape[0])
    got = jax.vmap(lambda j, k: rows.client_batch(j, k, 9))(slots, keys)
    want = jax.vmap(lambda i, k: ds.client_batch(i, k, 9))(ids, keys)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    # One round of the stack, with the rows and with the point gather.
    leaves, cohort_size = _one_round(stack, ds)
    assert 0 < cohort_size < COHORT  # valid and padding slots both ran
    monkeypatch.setattr(FederatedDataset, "rows", lambda self, ids: _PointGather(self, ids))
    built = _PointGather.built
    leaves_point, _ = _one_round(stack, ds)
    assert _PointGather.built > built
    assert len(leaves) == len(leaves_point)
    for a, b in zip(leaves, leaves_point):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
