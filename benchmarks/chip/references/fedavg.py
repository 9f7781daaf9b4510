"""Plain federated rounds: K-Vib draw, cohort, local SGD, weighted average.

``follow`` runs the first rounds of a training cell from the seed, client by
client, and records what the harness also reads from the system under test
after each of those rounds (``observe``):

* ``loss``: the round's reported loss (``"mean"``: mean of the last local
  step's loss over the kept clients; ``"weighted"``: sum of weight x loss);
* ``cohort`` / ``n_incl``: the kept clients and the size of the draw;
* ``update``: per-leaf norms of the first round's applied update;
* ``change``: per-leaf norms of the parameters' change after all rounds;
* ``stats``: the sampler's accumulated statistics after all rounds.

A drawn client whose uniform lies within ``tol`` (relative) of its
probability could go either way under rounding of the probability, which
follows from the previous rounds' norms.  For such a client the reference
takes the side that the compared run took (its kept clients and draw size),
and counts every other disagreement in ``mismatch``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import sampler as ks

__all__ = ["leaf_norms", "weighted_sum", "follow", "compare"]


@jax.jit
def _leaf_norms(a, b):
    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32) - y.astype(jnp.float32))))
            for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b))]


def leaf_norms(a, b) -> dict:
    """{leaf path: ||a - b||} in float32."""
    paths = [jax.tree_util.keystr(p) for p, _ in jax.tree_util.tree_leaves_with_path(a)]
    return dict(zip(paths, (float(x) for x in _leaf_norms(a, b))))


def _resolve(mask, u, p, tol, seen_cohort, seen_n):
    """The draw, with the near-boundary clients set as the compared run set them."""
    mask = np.asarray(mask).copy()
    u, p = np.asarray(u), np.asarray(p)
    amb = np.abs(u - p) <= tol * p
    if seen_cohort is None or not amb.any():
        return mask
    amb_ids = np.flatnonzero(amb)
    kept = set(seen_cohort)
    for i in amb_ids:
        mask[i] = i in kept
    # Ambiguous clients drawn but dropped on overflow are not among the kept:
    # add the likeliest of them until the draw has the size the run had.
    need = int(seen_n) - int(mask.sum())
    spare = [i for i in amb_ids if i not in kept]
    spare.sort(key=lambda i: u[i] - p[i])
    for i in spare[:max(need, 0)]:
        mask[i] = True
    return mask


def weighted_sum(deltas, w, state, like):
    """``sum_c w_c delta_c`` in float32 (zeros shaped as ``like`` for an
    empty cohort), with each client's update norm."""
    norms = [float(jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                                for x in jax.tree_util.tree_leaves(dl))))
             for dl in deltas]
    d = jax.tree_util.tree_map(lambda x: jnp.zeros(x.shape, jnp.float32), like)
    for dl, wc in zip(deltas, w):
        d = jax.tree_util.tree_map(
            lambda a, x, wc=wc: a + jnp.float32(wc) * x.astype(jnp.float32), d, dl)
    return d, norms, state


def follow(*, rounds, key, params, lam, n, budget, cohort, horizon,
           key_order, client_update, apply_update, loss_kind, seen=None, tol=0.05,
           aggregate=weighted_sum, agg_state=None):
    """Run ``rounds`` rounds; see the module docstring.

    ``client_update(params, cid, k_data) -> (delta, last_loss)`` trains one
    client; ``aggregate(deltas, weights, state, params) -> (d, norms, state)`` forms
    the round's estimate and the clients' feedback norms (``weighted_sum``,
    or a compressed form with its carried state); ``apply_update(params, d)
    -> params`` is the server step.
    ``key_order`` is ``"draw_data"`` or ``"data_draw"``: the order of the two
    round keys split from the chain key after the carried one.
    ``seen`` is the compared run's record (for the near-boundary draws)."""
    th = ks.theta(n, budget, horizon)
    lam = jnp.asarray(lam, jnp.float32)
    stats = jnp.zeros((n,), jnp.float32)
    gamma = jnp.float32(0.0)
    p0 = params
    rec = {"loss": [], "cohort": [], "n_incl": [], "resid": [], "mismatch": 0}
    for t in range(rounds):
        key, k1, k2 = jax.random.split(key, 3)
        k_draw, k_data = (k1, k2) if key_order == "draw_data" else (k2, k1)
        p = ks.probabilities(stats, gamma, budget, th)
        mask, u = ks.draw(k_draw, p)
        if seen is not None:
            mask = _resolve(mask, u, p, tol, seen["cohort"][t], seen["n_incl"][t])
        mask = jnp.asarray(mask)
        w_full = jnp.where(mask, lam / jnp.maximum(p, 1e-30), 0.0)
        ids, w, n_inc = ks.select(mask, w_full, cohort, jax.random.fold_in(k_draw, 1))
        if seen is not None:
            rec["mismatch"] += len(set(ids) ^ set(seen["cohort"][t]))
            rec["mismatch"] += abs(n_inc - int(seen["n_incl"][t]))
        deltas, losses = [], []
        for cid in ids:
            delta, last = client_update(params, cid, k_data)
            deltas.append(delta)
            losses.append(float(last))
        d, norms, agg_state = aggregate(deltas, w, agg_state, params)
        del deltas
        new = apply_update(params, d)
        del d
        if t == 0:
            rec["update"] = leaf_norms(params, new)
        params = new
        if loss_kind == "mean":
            rec["loss"].append(float(np.mean(losses)) if losses else 0.0)
        else:
            rec["loss"].append(float(np.sum(np.asarray(w, np.float64) * np.asarray(losses))))
        fb = np.zeros((n,), np.float32)
        for cid, nm in zip(ids, norms):
            fb[cid] = float(lam[cid]) * nm
        stats, gamma = ks.update(stats, gamma, t, mask, p, jnp.asarray(fb), budget, th)
        rec["cohort"].append(sorted(ids))
        rec["n_incl"].append(n_inc)
        if agg_state is not None:
            rec["resid"].append(float(jnp.linalg.norm(agg_state)))
    rec["change"] = leaf_norms(p0, params)
    rec["stats"] = np.asarray(stats)
    return rec


def compare(run: dict, ref: dict) -> dict:
    """The compared numbers of a run against the reference.

    ``loss_gap``: largest relative gap of a round's loss; ``first_loss_gap``
    the same of the first round alone, before any update.  ``update_gap`` and
    ``change_gap``: the worst leaf's gap between the run's norm and the
    reference's, over the larger of the reference leaf's norm and the median
    leaf's; leaves whose reference update is under a thousandth of the
    median leaf's are left out (they move by round-off alone).
    ``stats_gap``: largest gap of the sampler's statistics over the largest
    reference statistic.  ``cohort_mismatch``: clients kept on one side only,
    plus the difference in draw size (exact).  ``resid_gap`` (compressed
    rounds): largest relative gap of the error-feedback residual's norm after
    a round."""
    ref_up = ref["update"]
    med = float(np.median(list(ref_up.values())))
    leaves = [k for k, v in ref_up.items() if v >= 1e-3 * med]

    def worst(name):
        r, g = ref[name], run[name]
        m = float(np.median([r[k] for k in leaves]))
        # An empty first cohort moves nothing on either side: compare the
        # norms themselves then.
        return max(abs(g[k] - r[k]) / (max(r[k], m) if m > 0 else 1.0) for k in leaves)

    rl, gl = np.asarray(ref["loss"]), np.asarray(run["loss"])
    rs, gs = np.asarray(ref["stats"], np.float64), np.asarray(run["stats"], np.float64)
    out = {
        "loss_gap": float(np.max(np.abs(gl - rl) / np.maximum(np.abs(rl), 1e-30))),
        "first_loss_gap": float(abs(gl[0] - rl[0]) / max(abs(rl[0]), 1e-30)),
        "update_gap": worst("update"),
        "change_gap": worst("change"),
        "stats_gap": float(np.max(np.abs(gs - rs)) / max(float(np.max(rs)), 1e-30)),
        "cohort_mismatch": float(ref["mismatch"]),
    }
    if ref.get("resid"):
        rr, gr = np.asarray(ref["resid"]), np.asarray(run["resid"])
        out["resid_gap"] = float(np.max(np.abs(gr - rr) / np.maximum(rr, 1e-30)))
    return out
