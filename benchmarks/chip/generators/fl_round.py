"""Training cells: federated rounds through ``repro.api``'s compiled segment.

One general generator for every ``fl_round.*`` traffic file.  The configuration
file says which stack runs the model (``stack``: ``"zoo"`` is the pod-scale
``fed.round`` segment that ``repro.api.run`` builds for zoo architectures,
``"task"`` the simulation stack's ``fed.server`` segment); the traffic file
holds the spec's sampler, federation, execution and compression sections, the
federated data's sizes, and the generator's own knobs:

``in_flight`` calls dispatched ahead of the host's wait, ``check_rounds`` the
rounds that set-up drives and the reference follows, ``faults`` planted for
the upper readings (``calibrate.py``), ``trace_seconds`` the length of the
traced window, and ``limits`` of the compared numbers.

Every call of the segment runs one round: the check reads the state after
each of the first ``check_rounds``.  Set-up builds one ``TrainState`` on the
benchmark's weights and key, drives it through those calls, and hands the
same state to the window.
"""
from __future__ import annotations

import collections
import dataclasses
import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from .. import counts, datasets, references, weights
from ..references import fedavg, precision

__all__ = ["Cell"]


def _items(cfg):
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, str, bool))))


class Cell:
    def __init__(self, cfg: dict, traffic: dict, seed: int):
        self.cfg, self.traffic, self.seed = cfg, traffic, int(seed)
        self.stack = cfg["stack"]
        self.family = references.family(cfg)
        self.loss_key = "loss" if self.stack == "zoo" else "train_loss"

    # -- the program --------------------------------------------------------

    def spec(self):
        from repro import api

        t, c = self.traffic, self.cfg
        data = dict(c.get("data", {}), **t.get("data", {}))
        data.setdefault("seed", self.seed)
        if self.stack == "zoo":
            task = {"kind": "zoo", "name": c["program_arch"], "dataset": "bench_tokens",
                    "dataset_kwargs": dict(data, vocab=c["vocab_size"])}
            if c.get("program_reduced"):
                task.update(reduced=True, kwargs=c["program_reduced"])
        else:
            task = {"kind": "task", "name": c["program_task"],
                    "kwargs": {"dim": c["dim"], "n_classes": c["n_classes"]},
                    "dataset": "bench_synthetic",
                    "dataset_kwargs": dict(data, dim=c["dim"], n_classes=c["n_classes"],
                                           alpha=c["alpha"], beta=c["beta"])}
        spec = {"task": task, "sampler": t["sampler"], "federation": t["federation"],
                "execution": dict(t.get("execution", {}), seed=self.seed & 0x7FFFFFFF)}
        if "compression" in t:
            spec["compression"] = t["compression"]
        return api.ExperimentSpec.from_dict(spec)

    def prepare(self):
        """The experiment as ``repro.api.build`` resolves it (its dataset is
        the benchmark's own generator's); no program is compiled."""
        from repro import api

        datasets.register()
        self.built = api.build(self.spec())
        return self.built

    def build(self):
        """(segment, fresh TrainState on the benchmark's weights and key)."""
        built = self.prepare()
        spec = built.spec
        params = weights.make(self.cfg, self.seed)
        key = datasets.seed_key(self.seed, 3)
        if self.stack == "zoo":
            from repro.fed.round import build_fed_scan_segment
            from repro.launch.mesh import make_host_mesh

            cfg = built.arch_config
            self.family.check_program(self.cfg, cfg)
            segment, make_state = build_fed_scan_segment(
                cfg, built.round_spec, built.sampler, built.dataset, mesh=make_host_mesh())
            state = make_state(params, built.sampler.init(), key, spec.federation.rounds)
        else:
            from repro.fed.server import build_segment_runner

            segment, state = build_segment_runner(
                built.task, built.dataset, built.sampler, built.fed_config)
            state = dataclasses.replace(state, params=params, key=key)
        return segment, state

    def _observe(self, state, t, stats_before, seen):
        m = state.metrics
        stats = np.asarray(state.sampler.stats)
        seen["loss"].append(float(m[self.loss_key][t]))
        seen["cohort"].append(np.flatnonzero(stats > stats_before).tolist())
        seen["n_incl"].append(int(m["cohort_size"][t]) + int(m["dropped"][t]))
        if state.compression:
            seen["resid"].append(float(jnp.linalg.norm(state.compression["resid"])))
        return stats

    def setup(self, span):
        self.segment, state = self.build()
        p0 = jax.tree_util.tree_map(jnp.copy, state.params)
        seen = {"loss": [], "cohort": [], "n_incl": [], "resid": []}
        stats = np.zeros(self.built.sampler.n, np.float32)
        for t in range(int(self.traffic["check_rounds"])):
            with span("bench.segment"):
                new = self.segment(state, 1)
            if t == 0:
                seen["update"] = fedavg.leaf_norms(p0, new.params)
            state = new
            stats = self._observe(state, t, stats, seen)
        seen["change"] = fedavg.leaf_norms(p0, state.params)
        seen["stats"] = stats
        del p0
        self.seen = seen
        self.state = state
        (state.round + 0).block_until_ready()  # the window's marker op, warmed
        self.compiles = self.segment._cache_size()

    # -- the window ---------------------------------------------------------

    def window(self, seconds, span):
        """Whole one-round segments, dispatched ``in_flight`` ahead of the
        host's wait, so the device never waits on the host between rounds."""
        state = self.state
        depth = int(self.traffic.get("in_flight", 2))
        start_round = int(state.round)
        calls, pending = 0, collections.deque()
        wall0, t0 = time.time(), time.perf_counter()
        with span("bench.window"):
            while True:
                pending.append(state.round + 0)  # ready when the previous call is
                with span("bench.segment"):
                    state = self.segment(state, 1)
                calls += 1
                if len(pending) >= depth:
                    with span("bench.sync"):
                        pending.popleft().block_until_ready()
                    if time.perf_counter() - t0 >= seconds:
                        break
            with span("bench.sync"):
                state.round.block_until_ready()
        t1 = time.perf_counter()
        self.state = state
        rounds = calls
        horizon = state.metrics[self.loss_key].shape[0]
        idx = (start_round + np.arange(rounds)) % horizon
        losses = np.asarray(state.metrics[self.loss_key])[idx[-min(rounds, horizon):]]
        failed = int(np.sum(~np.isfinite(losses)))
        if self.segment._cache_size() != self.compiles:
            raise RuntimeError("the segment compiled again inside the window")
        return {"wall0": wall0, "t0": t0, "t1": t1, "rounds": rounds, "attempted": rounds,
                "failed": failed, "round_ms": 1e3 * (t1 - t0) / rounds}

    def end_to_end(self, raw):
        return {"round_ms": raw["round_ms"]}

    def info(self, raw):
        """Counts that per-layer readers divide by device time."""
        t, c = self.traffic, self.cfg
        fed = t["federation"]
        out = {"rounds": raw["rounds"], "train_flops_per_round": self.family.train_flops(c, t)}
        comp = t.get("compression")
        if comp:
            d = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(self.state.params))
            sb = int(comp.get("scale_block", 128))
            from repro.kernels.fused_weighted_agg import dequant_block_d

            blk = dequant_block_d(-(-d // sb) * sb, sb)
            d_pad = -(-d // blk) * blk
            out["dequant_agg"] = counts.dequant_agg_cost(fed["cohort"], d_pad, sb)
        if t.get("execution", {}).get("sampler_axis"):
            out["waterfill"] = counts.waterfill_cost(self.built.sampler.n, 128)
        return out

    def release(self):
        del self.state, self.segment
        gc.collect()

    # -- the check ----------------------------------------------------------

    def reference(self, mode="f32", fault=None, seen="run"):
        """Follow the first rounds with the plain references; ``mode`` and
        ``fault`` make the control and the planted faults of the tests.
        ``seen`` is the record whose near-boundary draws the reference takes
        (the run's by default; ``None`` for none)."""
        t, c = self.traffic, self.cfg
        fed = t["federation"]
        ds = self.built.dataset
        feats, labels, sizes = ds.features, ds.labels, np.asarray(ds.sizes)
        n = ds.n_clients
        lam = sizes / sizes.sum()
        steps, batch, lr = fed["local_steps"], fed["batch_size"], fed["local_lr"]
        m_items = _items(c)
        half = fault == "half_batch"

        if self.stack == "zoo":
            dt = jnp.dtype(c["torch_dtype"])

            def client_update(params, cid, k_data):
                keys = jax.random.split(jax.random.fold_in(k_data, cid), steps)
                p = params
                for r in range(steps):
                    idx = jax.random.randint(keys[r], (batch,), 0, int(sizes[cid]))
                    tok, tgt = feats[cid, idx], labels[cid, idx]
                    if half:
                        tok, tgt = tok[: batch // 2], tgt[: batch // 2]
                    if fault == "token":
                        tok = tok.at[0, 0].set((tok[0, 0] + 1) % c["vocab_size"])
                    pf = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), p)
                    last, g = self.family.grad(pf, tok, tgt, m_items, mode)
                    del pf
                    p = jax.tree_util.tree_map(
                        lambda w, gr: (w.astype(jnp.float32)
                                       - lr * gr.astype(dt).astype(jnp.float32)).astype(dt),
                        p, g)
                    del g
                if fault == "unchanged":
                    p = params
                delta = jax.tree_util.tree_map(
                    lambda a, b: (a.astype(jnp.float32) - b.astype(jnp.float32)).astype(dt),
                    params, p)
                return delta, last

            server_lr = float(fed.get("server_opt_kwargs", {}).get("lr", 1.0))

            def apply_update(params, d):
                return jax.tree_util.tree_map(
                    lambda w, g: (w.astype(jnp.float32) - server_lr
                                  * g.astype(dt).astype(jnp.float32)).astype(dt), params, d)

            key_order, loss_kind = "draw_data", "mean"
        else:
            cache = {}

            def client_update(params, cid, k_data):
                if cache.get("k") is not k_data:
                    cache["k"] = k_data
                    cache["keys"] = jax.random.split(k_data, n * steps).reshape(n, steps, 2)
                keys = cache["keys"][cid]
                p = params
                for r in range(steps):
                    idx = jax.random.randint(keys[r], (batch,), 0, int(sizes[cid]))
                    x, y = feats[cid, idx], labels[cid, idx]
                    if half:
                        x, y = x[: batch // 2], y[: batch // 2]
                    if fault == "token":
                        y = y.at[0].set((y[0] + 1) % c["n_classes"])
                    last, g = self.family.grad(p, x, y, mode)
                    p = jax.tree_util.tree_map(lambda w, gr: precision.cast(w - lr * gr, mode),
                                               p, g)
                if fault == "unchanged":
                    p = params
                return jax.tree_util.tree_map(jnp.subtract, params, p), last

            def apply_update(params, d):
                return jax.tree_util.tree_map(lambda w, g: w - g, params, d)

            key_order, loss_kind = "data_draw", "weighted"

        aggregate, agg_state = fedavg.weighted_sum, None
        if t.get("compression"):
            from ..references import quant

            aggregate = quant.aggregator(t["compression"])
            agg_state = quant.init_state(self.cfg, self.seed)
        return fedavg.follow(
            rounds=int(t["check_rounds"]), key=datasets.seed_key(self.seed, 3),
            params=weights.make(c, self.seed), lam=lam, n=n, budget=fed["budget"],
            cohort=fed["cohort"], horizon=int(t["sampler"]["kwargs"]["horizon"]),
            key_order=key_order, client_update=client_update, apply_update=apply_update,
            loss_kind=loss_kind, seen=self.seen if isinstance(seen, str) else seen,
            aggregate=aggregate, agg_state=agg_state)

    def compare(self, run, ref):
        return fedavg.compare(run, ref)

    def upper(self, name):
        """Numbers of the control (``"control"``: the reference in the
        configuration's ``control_mode``) or of a planted fault, each in the
        program's place, against the plain reference."""
        kw = {"mode": self.cfg["control_mode"]} if name == "control" else {"fault": name}
        planted = self.reference(seen=None, **kw)
        return self.compare(planted, self.reference(seen=planted))

    def check(self):
        """The compared numbers of the run's first rounds."""
        return self.compare(self.seen, self.reference())
