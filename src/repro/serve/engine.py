"""The batched decode engine: prefill/decode split over the paged KV-cache.

``ServeEngine`` owns the two jitted entry points of the serving hot path —
``prefill + first-token sample`` and ``single-token decode + sample`` — over
a preallocated static-shape paged KV-cache (``models.attention``: a
``(B*P, page_size, KV, hd)`` pool indexed through a ``(B, P)`` page table).
Both entry points compile exactly once per engine and stay cached across
weight swaps:

* the cache pytree structure and every aval (shape/dtype) are pinned at
  construction — ``prefill`` allocates them, ``decode`` threads them
  unchanged, and ``swap_params`` validates a candidate against the pinned
  param treedef/avals before accepting it, so no call can ever present a
  new signature to the jit cache;
* sampling runs *inside* the jitted step with the temperature as a traced
  f32 scalar and a fresh per-call PRNG key, so greedy vs. stochastic
  decoding is a data change, not a recompile — and the first generated
  token (sampled from the prefill logits) respects the temperature exactly
  like every later one;
* ``swap_params`` happens between decode steps on the host: in-flight
  sequences keep their caches, positions, and last tokens, only the param
  arrays under the (structurally identical) pytree change.

``analysis.lint.audit_compile_once`` enforces the contract through
``compile_once_probe()``, which adapts the decode entry point to the
segment-runner probe interface (``_lint`` / ``_cache_size`` handles) and
cycles candidate params per call — i.e. the audited program IS the decode
step under continuous weight swaps.
"""
from __future__ import annotations

import time
from typing import Any

import jax
import jax.numpy as jnp

from repro import obs
from repro.models import transformer

__all__ = ["ServeEngine"]


def _leaf_avals(tree) -> list:
    """[(path, shape, dtype_name)] in flatten order — the pinned signature."""
    return [
        (jax.tree_util.keystr(path), tuple(x.shape), jnp.asarray(x).dtype.name)
        for path, x in jax.tree_util.tree_leaves_with_path(tree)
    ]


def _held_bytes(cfg, caches) -> tuple[int, int]:
    """(bytes in KV pages, bytes in recurrent state) of a paged cache list,
    one entry per pattern slot (``transformer.init_caches``' layout)."""
    kv = state = 0
    for kind, cache in zip(cfg.block_pattern, caches):
        for path, x in jax.tree_util.tree_leaves_with_path(cache):
            if kind in transformer.RECURRENT_KINDS:
                state += x.nbytes
            elif any(getattr(k, "key", None) in ("pool_k", "pool_v") for k in path):
                kv += x.nbytes
    return int(kv), int(state)


def _sample_token(logits: jax.Array, key: jax.Array, temperature: jax.Array):
    """(B, 1, V) logits -> (B, 1) int32 next tokens.

    Temperature is a *traced* scalar: ``temperature > 0`` selects stochastic
    sampling (logits scaled by ``1/temperature``), else argmax — one compiled
    program serves both, and the prefill's first token goes through the same
    path as every decode token (the old launcher's always-greedy-first bug)."""
    lg = logits[:, -1].astype(jnp.float32)
    greedy = jnp.argmax(lg, axis=-1)
    sampled = jax.random.categorical(key, lg / jnp.maximum(temperature, 1e-6), axis=-1)
    return jnp.where(temperature > 0, sampled, greedy).astype(jnp.int32)[:, None]


class ServeEngine:
    """Lockstep batched generation with hot-swappable weights.

    Parameters
    ----------
    cfg:
        ``repro.models.common.ArchConfig`` (LM archs; frontend/aux archs are
        rejected — serving traffic is token prompts).
    params:
        Initial weights; their treedef + avals become the pinned swap
        contract.
    batch / max_seq / page_size:
        Static decode geometry: ``batch`` lockstep sequences, each with a
        ``max_seq``-token paged cache of ``page_size``-token pages.
    temperature:
        Default sampling temperature (per-call override via ``start``/
        ``step`` is deliberately absent: it is traced data, set per engine).
    seed:
        Seeds the engine's *sampling* key stream only — prompt synthesis and
        param init are the caller's keys (split per use, never shared).
    """

    def __init__(
        self,
        cfg,
        params,
        *,
        batch: int,
        max_seq: int,
        page_size: int = 16,
        temperature: float = 0.0,
        seed: int = 0,
    ):
        if getattr(cfg, "frontend", None):
            raise ValueError(
                f"ServeEngine serves token-prompt LM archs; {cfg.name!r} has a "
                f"frontend ({cfg.frontend!r}) needing aux embeddings"
            )
        if max_seq < 2:
            raise ValueError(f"max_seq must be >= 2, got {max_seq}")
        self.cfg = cfg
        self.batch = int(batch)
        self.max_seq = int(max_seq)
        self.page_size = int(page_size)
        self.temperature = float(temperature)
        self._key = jax.random.PRNGKey(seed)

        self._params = jax.device_put(params)
        self._param_treedef = jax.tree_util.tree_structure(params)
        self._param_avals = _leaf_avals(params)

        # In-flight generation state (None until start()).
        self._tok = None
        self._caches = None
        self._index = 0
        self._out: list = []

        # Counters, plain host ints (``counters()`` returns them all):
        # batches prefilled, waits on the device, swaps installed and swaps
        # refused for treedef or aval drift.  Decode-side accounting
        # (prefill excluded: tokens/sec is the decode steady state the bench
        # gates) is assignable, so a caller can reset it.
        self.prefills = 0
        self.host_syncs = 0
        self.swaps = 0
        self.swaps_rejected = 0
        self.decode_tokens = 0
        self.decode_seconds = 0.0
        # Bytes the in-flight batch holds on the device: KV pages of the
        # attention slots, and the O(1) recurrent state of the Mamba2 / xLSTM
        # slots (set by start(); the shapes are fixed for the engine's life).
        self.kv_page_bytes = 0
        self.recurrent_state_bytes = 0

        def _prefill(p, prompts, key, temperature):
            logits, caches = transformer.prefill(
                p, cfg, prompts, max_seq=max_seq, page_size=page_size
            )
            return _sample_token(logits, key, temperature), logits, caches

        def _decode(p, tok, caches, index, key, temperature):
            logits, caches = transformer.decode_step(p, cfg, tok, caches, index)
            return _sample_token(logits, key, temperature), logits, caches

        self._prefill = jax.jit(_prefill)
        self._decode = jax.jit(_decode)

    # -- generation ----------------------------------------------------------
    @property
    def params(self):
        return self._params

    @property
    def index(self) -> int:
        """Tokens currently in the cache (= next write position)."""
        return self._index

    @property
    def capacity(self) -> int:
        """Decode steps possible before the paged cache is full."""
        return self.max_seq - self._index

    def _temp(self):
        return jnp.asarray(self.temperature, jnp.float32)

    def _next_key(self):
        self._key, sub = jax.random.split(self._key)
        return sub

    def start(self, prompts) -> jax.Array:
        """Prefill a fresh prompt batch; returns the first sampled tokens.

        Replaces any previous in-flight batch (the lockstep refill: serve
        traffic as back-to-back full batches)."""
        prompts = jnp.asarray(prompts, jnp.int32)
        if prompts.shape[0] != self.batch or prompts.ndim != 2:
            raise ValueError(
                f"prompts must be ({self.batch}, prompt_len), got {prompts.shape}"
            )
        if prompts.shape[1] >= self.max_seq:
            raise ValueError(
                f"prompt_len {prompts.shape[1]} must leave decode room under "
                f"max_seq={self.max_seq}"
            )
        self.prefills += 1
        with obs.span("serve.start", batch=self.prefills):
            tok, _, caches = self._prefill(
                self._params, prompts, self._next_key(), self._temp()
            )
        self._tok, self._caches = tok, caches
        self.kv_page_bytes, self.recurrent_state_bytes = _held_bytes(self.cfg, caches)
        self._index = int(prompts.shape[1])
        self._out = [tok]
        return tok

    def step(self, n: int = 1) -> int:
        """Run up to ``n`` decode steps (bounded by cache capacity).

        Returns the number of steps executed; accumulates decode-side
        wall-clock for ``tokens_per_sec``."""
        with obs.span("serve.step", batch=self.prefills, index=self._index):
            if self._tok is None:
                raise RuntimeError("no in-flight batch; call start(prompts) first")
            n = min(int(n), self.capacity)
            if n <= 0:
                return 0
            t0 = time.perf_counter()
            tok, caches = self._tok, self._caches
            for _ in range(n):
                with obs.span("serve.step.prep"):
                    index = jnp.asarray(self._index, jnp.int32)
                    key = self._next_key()
                    temperature = self._temp()
                with obs.span("serve.step.dispatch"):
                    tok, _, caches = self._decode(
                        self._params, tok, caches, index, key, temperature
                    )
                self._index += 1
                self._out.append(tok)
            with obs.span("serve.step.wait"):
                jax.block_until_ready(tok)
            self.host_syncs += 1
            self._tok, self._caches = tok, caches
            self.decode_seconds += time.perf_counter() - t0
            self.decode_tokens += n * self.batch
        return n

    def generated(self) -> jax.Array:
        """All tokens sampled for the current batch, (B, n_generated)."""
        if not self._out:
            return jnp.zeros((self.batch, 0), jnp.int32)
        return jnp.concatenate(self._out, axis=1)

    def tokens_per_sec(self) -> float:
        return self.decode_tokens / max(self.decode_seconds, 1e-9)

    def counters(self) -> dict:
        """The engine's counters, for logs and operators."""
        return {
            "prefills": self.prefills,
            "host_syncs": self.host_syncs,
            "swaps": self.swaps,
            "swaps_rejected": self.swaps_rejected,
            "decode_tokens": self.decode_tokens,
            "decode_seconds": self.decode_seconds,
            "kv_page_bytes": self.kv_page_bytes,
            "recurrent_state_bytes": self.recurrent_state_bytes,
        }

    # -- the hot swap --------------------------------------------------------
    def swap_params(self, new_params) -> None:
        """Install candidate weights between decode steps.

        Validates the candidate against the pinned treedef and avals FIRST:
        a structurally different pytree (or any shape/dtype drift) raises
        instead of poisoning the jit cache with a second entry.  In-flight
        sequences are untouched — caches, positions, and last tokens carry
        straight into the next decode step under the new weights."""
        with obs.span("serve.swap", swap=self.swaps):
            treedef = jax.tree_util.tree_structure(new_params)
            if treedef != self._param_treedef:
                self.swaps_rejected += 1
                raise ValueError(
                    f"swap_params: param treedef changed\n  pinned: "
                    f"{self._param_treedef}\n  candidate: {treedef}"
                )
            for (path, shape, dtype), (_, got_shape, got_dtype) in zip(
                self._param_avals, _leaf_avals(new_params)
            ):
                if (shape, dtype) != (got_shape, got_dtype):
                    self.swaps_rejected += 1
                    raise ValueError(
                        f"swap_params: param aval drift at {path}: pinned "
                        f"{shape}/{dtype}, candidate {got_shape}/{got_dtype} — "
                        "a swap must match the pinned signature exactly"
                    )
            self._params = jax.device_put(new_params)
        self.swaps += 1

    # -- lint handles --------------------------------------------------------
    def decode_cache_entries(self) -> int:
        """Jit cache entries of the decode entry point (compile-once: 1)."""
        return int(self._decode._cache_size())

    def prefill_cache_entries(self) -> int:
        return int(self._prefill._cache_size())

    def decode_jaxpr(self, prompt_len: int | None = None):
        """The decode step's jaxpr on this engine's pinned avals — the input
        ``analysis.lint.audit_dtypes`` audits in the serve lint cell."""
        plen = int(prompt_len) if prompt_len is not None else self.max_seq // 2
        caches = transformer.init_caches(
            self.cfg, self.batch, self.max_seq, page_size=self.page_size
        )
        tok = jnp.zeros((self.batch, 1), jnp.int32)
        return jax.make_jaxpr(
            lambda p, t, c, i, k, temp: self._decode(p, t, c, i, k, temp)
        )(
            self._params,
            tok,
            caches,
            jnp.asarray(plen, jnp.int32),
            jax.random.PRNGKey(0),
            jnp.asarray(self.temperature, jnp.float32),
        )

    def compile_once_probe(self, prompts, param_variants=None):
        """(probe_fn, init_state) for ``analysis.lint.audit_compile_once``.

        The probe adapts the decode entry point to the segment-runner probe
        interface: ``probe(state, n_rounds) -> state`` with ``state = (tok,
        caches, index, key)`` — every leaf an array, so the audit's numpy
        round trip (the checkpoint transport) applies cleanly.  Each *call*
        installs the next entry of ``param_variants`` (cycling), so the
        audit's ``n_segments + 1`` calls execute the decode step across >= 2
        weight swaps; the jit cache must still grow by exactly one.

        ``_lint`` declares ``donate=False`` (the engine never donates: the
        carried caches must survive a failed swap), ``_cache_size`` forwards
        the decode PjitFunction's counter."""
        variants = [jax.device_put(v) for v in (param_variants or [self._params])]
        for v in variants[1:]:
            if jax.tree_util.tree_structure(v) != self._param_treedef:
                raise ValueError("compile_once_probe: variant treedef mismatch")
        calls = {"n": 0}
        temp = jnp.asarray(self.temperature, jnp.float32)
        decode = self._decode

        tok, _, caches = self._prefill(
            variants[0], jnp.asarray(prompts, jnp.int32),
            jax.random.PRNGKey(1), temp,
        )
        init_state = (
            tok,
            caches,
            jnp.asarray(int(prompts.shape[1]), jnp.int32),
            jax.random.PRNGKey(2),
        )

        def probe(state, n_rounds: int):
            tok, caches, index, key = state
            p = variants[calls["n"] % len(variants)]
            calls["n"] += 1
            for _ in range(int(n_rounds)):
                key, sub = jax.random.split(key)
                tok, _, caches = decode(p, tok, caches, index, sub, temp)
                index = index + jnp.int32(1)
            return (tok, caches, index, key)

        probe._lint = {"donate": False, "donate_argnums": ()}
        probe._cache_size = decode._cache_size
        return probe, init_state
