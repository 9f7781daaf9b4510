"""Serving cells: ``repro.serve.ServeEngine`` over lockstep batches, with hot
swaps between two weight sets held on the device.

One general generator for every ``serve.*`` traffic file, whose keys are:
``batch``, ``prompt_len``, ``new_tokens`` (decode steps per batch; the
prefill gives one more token), ``page_size``, ``swap_every`` (decode steps
between ``swap_params`` calls, counted across batches), ``pool`` (prompt
batches made from the seed), ``check_sequences`` (served sequences the check
compares, drawn from the seed among those finished in the window),
``trace_seconds`` and ``limits``.

The window serves batches back to back, a closed loop: ``start`` (prefill
and the first token, waited for), then one ``step(1)`` per token, as a
streaming server syncs per token.  Every generated token counts, the first
ones too; every gap between consecutive tokens of a sequence is an
inter-token sample, the steps after a swap included.
"""
from __future__ import annotations

import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from .. import datasets, references, weights

__all__ = ["Cell"]

_STREAMS = (2, 4)  # the two weight sets


def _items(cfg):
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, str, bool))))


@jax.jit
def _token_fault(tokens):
    return tokens.at[0, -1].set((tokens[0, -1] + 1) % 7)


class Cell:
    def __init__(self, cfg: dict, traffic: dict, seed: int):
        self.cfg, self.traffic, self.seed = cfg, traffic, int(seed)
        self.max_seq = traffic["prompt_len"] + traffic["new_tokens"]
        self.family = references.family(cfg)

    def prepare(self):
        from repro.configs import get_config

        c = self.cfg
        arch = get_config(c["program_arch"])
        if c.get("program_reduced"):
            arch = arch.reduced(**c["program_reduced"])
        self.arch = arch
        t = self.traffic
        key = datasets.seed_key(self.seed, 5)
        pool = jax.jit(lambda k: jax.random.randint(
            k, (t["pool"], t["batch"], t["prompt_len"]), 0, c["vocab_size"], jnp.int32))(key)
        self.pool = list(pool)  # one device array per batch, sliced once here

    def setup(self, span):
        from repro.serve import ServeEngine

        self.prepare()
        t = self.traffic
        self.params = [weights.make(self.cfg, self.seed, s) for s in _STREAMS]
        self.engine = ServeEngine(self.arch, self.params[0], batch=t["batch"],
                                  max_seq=self.max_seq, page_size=t["page_size"],
                                  temperature=0.0, seed=self.seed & 0x7FFFFFFF)
        # Warm every program the window runs: one whole batch, swaps included.
        self.current, self.decoded = 0, 0
        self.compiles = None
        self.window(0.0, span)
        self.engine.swap_params(self.params[0])
        self.current, self.decoded = 0, 0
        self.compiles = (self.engine.prefill_cache_entries(), self.engine.decode_cache_entries())

    def window(self, seconds, span):
        t = self.traffic
        eng, n_new, every = self.engine, t["new_tokens"], t["swap_every"]
        gaps, prefill, served = [], [], []
        batches = 0
        wall0, t0 = time.time(), time.perf_counter()
        with span("bench.window"):
            while True:
                prompts = self.pool[batches % t["pool"]]
                sched = [self.current]
                tb = time.perf_counter()
                with span("bench.start"):
                    eng.start(prompts).block_until_ready()
                prev = time.perf_counter()
                prefill.append(prev - tb)
                for _ in range(n_new):
                    if self.decoded and self.decoded % every == 0:
                        self.current ^= 1
                        with span("bench.swap"):
                            eng.swap_params(self.params[self.current])
                    with span("bench.step"):
                        eng.step(1)
                    now = time.perf_counter()
                    gaps.append(now - prev)
                    prev = now
                    sched.append(self.current)
                    self.decoded += 1
                served.append((batches % t["pool"], eng.generated(), sched))
                batches += 1
                if time.perf_counter() - t0 >= seconds:
                    break
        t1 = time.perf_counter()
        if self.compiles and (eng.prefill_cache_entries(),
                              eng.decode_cache_entries()) != self.compiles:
            raise RuntimeError("the engine compiled again inside the window")
        self.served = served
        tokens = batches * t["batch"] * (n_new + 1)
        return {"wall0": wall0, "t0": t0, "t1": t1, "batches": batches, "tokens": tokens,
                "attempted": batches * t["batch"], "failed": 0,
                "serve_tokens_per_s": tokens / (t1 - t0),
                "itl_ms_p95": float(np.percentile(np.asarray(gaps) * 1e3, 95)),
                "itl_samples": len(gaps) * t["batch"], "prefill_ms": prefill}

    def end_to_end(self, raw):
        return {"serve_tokens_per_s": raw["serve_tokens_per_s"], "itl_ms_p95": raw["itl_ms_p95"]}

    def info(self, raw):
        t, c = self.traffic, self.cfg
        per_batch = self.family.serve_flops(c, t["batch"], t["prompt_len"], t["new_tokens"])
        return {"serve_flops": per_batch * raw["batches"], "batches": raw["batches"],
                "decode_steps": raw["batches"] * t["new_tokens"]}

    def release(self):
        del self.engine, self.params
        gc.collect()

    # -- the check ----------------------------------------------------------

    def _sample(self):
        """(prompt + served tokens (S, L + n + 1), weights index per position)
        of ``check_sequences`` sequences drawn from the seed."""
        t = self.traffic
        rng = np.random.default_rng(self.seed)
        flat = [(i, j) for i in range(len(self.served)) for j in range(t["batch"])]
        pick = sorted(rng.choice(len(flat), min(t["check_sequences"], len(flat)),
                                 replace=False))
        rows, scheds = [], []
        for k in pick:
            i, j = flat[k]
            pool_i, gen, sched = self.served[i]
            rows.append(jnp.concatenate([self.pool[pool_i][j], gen[j]]))
            # position p was processed with the weights of: the prefill for
            # the prompt, decode step s for position prompt_len - 1 + s.
            scheds.append(np.asarray([sched[0]] * t["prompt_len"] + sched[1:] + [sched[-1]]))
        return jnp.stack(rows), np.stack(scheds)

    def readings(self, mode="f32", control=None, fault=None):
        """Widest gap below the reference's best logit of the served tokens
        (or, with ``control`` set, of the tokens that the reference in that
        precision puts first at the same positions)."""
        t = self.traffic
        tokens, scheds = self._sample()
        if fault == "token":
            tokens = _token_fault(tokens)
        pf = [jax.tree_util.tree_map(lambda x: x.astype(jnp.float32),
                                     weights.make(self.cfg, self.seed, s)) for s in _STREAMS]
        m_items, L = _items(self.cfg), t["prompt_len"]
        gaps = self.family.served_gaps
        worst = 0.0
        for r in range(tokens.shape[0]):
            tok = tokens[r:r + 1]
            use_q = jnp.asarray(scheds[r] == 1)
            alt = tok
            if control:
                _, _, top = gaps(pf[0], pf[1], use_q, tok, tok, m_items, control)
                alt = jnp.concatenate([tok[:, :1], top], axis=1)
            g, ga, _ = gaps(pf[0], pf[1], use_q, tok, alt, m_items, mode)
            g = ga if control else g
            worst = max(worst, float(jnp.max(g[:, L - 1:])))
        return {"logit_gap": worst}

    def check(self):
        return self.readings()

    def upper(self, name):
        if name == "control":
            return self.readings(control=self.cfg["control_mode"])
        return self.readings(fault=name)
