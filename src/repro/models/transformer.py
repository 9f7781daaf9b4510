"""Model assembly: pattern-scanned block stacks for every assigned arch.

Parameters for each pattern *slot* are stacked along a leading repeat axis and
executed with ``jax.lax.scan`` over groups, so the traced graph size is
O(len(pattern)) regardless of depth (126-layer llama3-405b traces as one
layer group).  Three execution modes share the block implementations:

  train   — full-sequence forward, no caches              -> logits, aux
  prefill — full-sequence forward, caches returned        -> logits, caches
  decode  — one token, caches consumed/updated            -> logits, caches

Caches are pytrees mirroring the slot structure (stacked along repeats), so
they scan in lock-step with the parameters.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.models import attention as attn_mod
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models import xlstm as xlstm_mod
from repro.models.attention import (
    _causal_mask,
    _project_qkv,
    _sdpa,
    decode_attention,
    init_attention,
)
from repro.models.common import ArchConfig, rms_norm, softcap, uniform_init
from repro.models.mlp import init_mlp, mlp
from repro.models.sharding import shard

__all__ = [
    "init_params",
    "forward",
    "loss_fn",
    "prefill",
    "decode_step",
    "init_caches",
    "param_count",
]

MOE_AUX_COEF = 0.01

ATTN_KINDS = {"attn", "attn_local", "moe", "shared_attn", "dec"}
RECURRENT_KINDS = ("mamba2", "mamba2_mlp", "mlstm", "slstm")  # O(1) decode state


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def _init_block(kind: str, cfg: ArchConfig, key: jax.Array) -> dict:
    ks = jax.random.split(key, 4)
    d = cfg.d_model
    dt = cfg.param_dtype
    if kind in ("attn", "attn_local"):
        return {
            "ln1": jnp.zeros((d,), dt),
            "attn": init_attention(cfg, ks[0]),
            "ln2": jnp.zeros((d,), dt),
            "mlp": init_mlp(cfg, ks[1]),
        }
    if kind == "moe":
        return {
            "ln1": jnp.zeros((d,), dt),
            "attn": init_attention(cfg, ks[0]),
            "ln2": jnp.zeros((d,), dt),
            "moe": moe_mod.init_moe(cfg, ks[1]),
        }
    if kind == "mamba2":
        return {"ln1": jnp.zeros((d,), dt), "ssm": ssm_mod.init_mamba2(cfg, ks[0])}
    if kind == "mamba2_mlp":  # granite-4.0-h: a Mamba2 mixer, then an MLP
        return {
            "ln1": jnp.zeros((d,), dt),
            "ssm": ssm_mod.init_mamba2(cfg, ks[0]),
            "ln2": jnp.zeros((d,), dt),
            "mlp": init_mlp(cfg, ks[1]),
        }
    if kind == "mlstm":
        return {"ln1": jnp.zeros((d,), dt), "cell": xlstm_mod.init_mlstm(cfg, ks[0])}
    if kind == "slstm":
        return {"ln1": jnp.zeros((d,), dt), "cell": xlstm_mod.init_slstm(cfg, ks[0])}
    if kind == "cross_attn":
        return {
            "ln1": jnp.zeros((d,), dt),
            "attn": init_attention(cfg, ks[0], cross=True),
            "ln2": jnp.zeros((d,), dt),
            "mlp": init_mlp(cfg, ks[1]),
            "gate": jnp.zeros((), dt),  # llama-vision gated cross-attn
        }
    if kind == "enc":  # whisper encoder block (bidirectional)
        return {
            "ln1": jnp.zeros((d,), dt),
            "attn": init_attention(cfg, ks[0]),
            "ln2": jnp.zeros((d,), dt),
            "mlp": init_mlp(cfg, ks[1], gated=False),
        }
    if kind == "dec":  # whisper decoder block (self + cross)
        return {
            "ln1": jnp.zeros((d,), dt),
            "attn": init_attention(cfg, ks[0]),
            "lnx": jnp.zeros((d,), dt),
            "xattn": init_attention(cfg, ks[1], cross=True),
            "ln2": jnp.zeros((d,), dt),
            "mlp": init_mlp(cfg, ks[2], gated=False),
        }
    if kind == "shared_attn":
        return {}  # weights live in params["shared"], invoked by closure
    raise ValueError(f"unknown block kind {kind!r}")


def init_params(cfg: ArchConfig, key: jax.Array) -> dict:
    keys = jax.random.split(key, 8)
    reps = cfg.pattern_repeats()
    params: dict[str, Any] = {
        "embed": uniform_init(keys[0], (cfg.vocab, cfg.d_model), cfg.param_dtype, scale=0.02),
        "final_norm": jnp.zeros((cfg.d_model,), cfg.param_dtype),
    }
    # stacked per-slot parameters
    stacks = []
    for j, kind in enumerate(cfg.block_pattern):
        slot_keys = jax.random.split(jax.random.fold_in(keys[1], j), reps)
        stacked = jax.vmap(lambda k, kind=kind: _init_block(kind, cfg, k))(slot_keys)
        stacks.append(stacked)
    params["stacks"] = stacks

    if "shared_attn" in cfg.block_pattern:
        params["shared"] = _init_block("attn", cfg, keys[2])

    if cfg.encoder_layers:
        enc_keys = jax.random.split(keys[3], cfg.encoder_layers)
        params["encoder"] = {
            "pos": uniform_init(
                keys[4], (cfg.frontend_seq, cfg.d_model), cfg.param_dtype, scale=0.02
            ),
            "stack": jax.vmap(lambda k: _init_block("enc", cfg, k))(enc_keys),
            "final_norm": jnp.zeros((cfg.d_model,), cfg.param_dtype),
        }
    if cfg.frontend:
        fd = cfg.frontend_dim or cfg.d_model
        params["frontend_proj"] = uniform_init(keys[5], (fd, cfg.d_model), cfg.param_dtype)
    if not cfg.tie_embeddings:
        params["lm_head"] = uniform_init(keys[6], (cfg.d_model, cfg.vocab), cfg.param_dtype, scale=0.02)
    return params


def param_count(params) -> int:
    return sum(int(x.size) for x in jax.tree_util.tree_leaves(params))


# ---------------------------------------------------------------------------
# block application (shared across modes)
# ---------------------------------------------------------------------------


def _residual(cfg: ArchConfig, h, y):
    """h + y, or h + m * y under ``residual_multiplier`` m."""
    if cfg.residual_multiplier is not None:
        y = y * jnp.asarray(cfg.residual_multiplier, y.dtype)
    return h + y


def _apply_block(
    kind: str,
    p: dict,
    cfg: ArchConfig,
    h: jax.Array,
    *,
    mode: str,
    cache: Any = None,
    index: jax.Array | None = None,
    cross_src: jax.Array | None = None,
    shared: dict | None = None,
    max_seq: int | None = None,
):
    """Returns (h, new_cache, aux). cache semantics depend on mode."""
    aux = jnp.zeros((), jnp.float32)
    if kind == "shared_attn":
        # zamba2: weights shared across invocations; cache is per-invocation.
        return _apply_block(
            "attn", shared, cfg, h, mode=mode, cache=cache, index=index, max_seq=max_seq
        )

    window = cfg.sliding_window if kind == "attn_local" else None

    if kind in ("attn", "attn_local", "moe"):
        x = rms_norm(h, p["ln1"], cfg.norm_eps)
        if mode == "decode":
            y, cache = _decode_attn(p["attn"], cfg, x, cache, index, window=window)
        else:
            y, kv = _full_attention(
                p["attn"], cfg, x, window=window,
                want_cache=(mode == "prefill"), max_seq=max_seq,
            )
            cache = kv
        h = _residual(cfg, h, y)
        x = rms_norm(h, p["ln2"], cfg.norm_eps)
        if kind == "moe":
            y, aux = moe_mod.moe_ffn(p["moe"], cfg, x)
        else:
            y = mlp(p["mlp"], cfg, x)
        return _residual(cfg, h, y), cache, aux

    if kind in ("mamba2", "mamba2_mlp"):
        x = rms_norm(h, p["ln1"], cfg.norm_eps)
        if mode == "decode":
            y, cache = ssm_mod.mamba2_decode_step(p["ssm"], cfg, x, cache)
        elif mode == "prefill":
            # Final recurrent state falls out of the chunked scan — no O(S)
            # sequential replay (DESIGN.md perf note).
            y, cache = ssm_mod.mamba2_block(p["ssm"], cfg, x, return_state=True)
        else:
            y = ssm_mod.mamba2_block(p["ssm"], cfg, x)
        h = _residual(cfg, h, y)
        if kind == "mamba2_mlp":
            y = mlp(p["mlp"], cfg, rms_norm(h, p["ln2"], cfg.norm_eps))
            h = _residual(cfg, h, y)
        return h, cache, aux

    if kind in ("mlstm", "slstm"):
        mod_step = (
            xlstm_mod.mlstm_decode_step if kind == "mlstm" else xlstm_mod.slstm_decode_step
        )
        x = rms_norm(h, p["ln1"], cfg.norm_eps)
        if mode == "decode":
            y, cache = mod_step(p["cell"], cfg, x, cache)
        elif mode == "prefill":
            # One pass: scan the decode cell over the prompt, collecting both
            # the block outputs and the final state (identical math to decode).
            state0 = (
                xlstm_mod.init_mlstm_state(cfg, x.shape[0])
                if kind == "mlstm"
                else xlstm_mod.init_slstm_state(cfg, x.shape[0])
            )
            y, cache = _recurrent_prefill(
                lambda tok, st: mod_step(p["cell"], cfg, tok, st), state0, x
            )
        else:
            block = xlstm_mod.mlstm_block if kind == "mlstm" else xlstm_mod.slstm_block
            y = block(p["cell"], cfg, x)
        return h + y, cache, aux

    if kind == "cross_attn":
        x = rms_norm(h, p["ln1"], cfg.norm_eps)
        if mode == "decode":
            y = _cross_from_cache(p["attn"], cfg, x, cache)
        else:
            y, cache = _cross_attention(p["attn"], cfg, x, cross_src, want_cache=(mode == "prefill"))
        h = h + jnp.tanh(p["gate"]).astype(h.dtype) * y
        x = rms_norm(h, p["ln2"], cfg.norm_eps)
        return h + mlp(p["mlp"], cfg, x), cache, aux

    if kind == "enc":
        x = rms_norm(h, p["ln1"], cfg.norm_eps)
        y, _ = _full_attention(p["attn"], cfg, x, causal=False, want_cache=False)
        h = h + y
        x = rms_norm(h, p["ln2"], cfg.norm_eps)
        return h + mlp(p["mlp"], cfg, x), None, aux

    if kind == "dec":
        x = rms_norm(h, p["ln1"], cfg.norm_eps)
        if mode == "decode":
            y, self_cache = _decode_attn(p["attn"], cfg, x, cache["self"], index)
        else:
            y, self_cache = _full_attention(
                p["attn"], cfg, x, want_cache=(mode == "prefill"), max_seq=max_seq
            )
        h = h + y
        x = rms_norm(h, p["lnx"], cfg.norm_eps)
        if mode == "decode":
            y = _cross_from_cache(p["xattn"], cfg, x, cache["cross"])
            cross_cache = cache["cross"]
        else:
            y, cross_cache = _cross_attention(
                p["xattn"], cfg, x, cross_src, want_cache=(mode == "prefill")
            )
        h = h + y
        x = rms_norm(h, p["ln2"], cfg.norm_eps)
        new_cache = {"self": self_cache, "cross": cross_cache} if mode != "train" else None
        return h + mlp(p["mlp"], cfg, x), new_cache, aux

    raise ValueError(kind)


def _decode_attn(p, cfg, x, cache, index, *, window=None):
    """Decode-attention dispatch on the cache layout: a paged cache (the
    ``repro.serve`` engine's preallocated pool + page table) routes to
    ``paged_decode_attention``, the dense layout to ``decode_attention``.
    The layout is a property of the cache pytree, so the same jitted
    ``decode_step`` program serves both — treedef in, treedef out."""
    if isinstance(cache, dict) and "page_table" in cache:
        return attn_mod.paged_decode_attention(p, cfg, x, cache, index, window=window)
    return decode_attention(p, cfg, x, cache, index, window=window)


def _full_attention(p, cfg, x, *, causal=True, window=None, want_cache=False, max_seq=None):
    b, s, _ = x.shape
    q, k, v = _project_qkv(p, cfg, x, x)
    if causal:  # RoPE only on causal (decoder) attention; whisper enc uses abs pos
        q, k = attn_mod.position_qk(cfg, q, k, jnp.arange(s))
    q = shard(q, "batch", "seq", "kv_heads", None, None)
    k = shard(k, "batch", "seq", "kv_heads", None)
    v = shard(v, "batch", "seq", "kv_heads", None)
    mask = _causal_mask(s, s, window) if causal else None
    out = _sdpa(cfg, q, k, v, mask)
    out = out.reshape(b, s, cfg.n_heads * cfg.hd) @ p["wo"]
    cache = None
    if want_cache:
        if max_seq is not None and max_seq > s:
            pad = [(0, 0), (0, max_seq - s), (0, 0), (0, 0)]
            k, v = jnp.pad(k, pad), jnp.pad(v, pad)
        cache = {"k": k, "v": v}
    return out, cache


def _cross_attention(p, cfg, x, src, want_cache=False):
    b, s, _ = x.shape
    q, k, v = _project_qkv(p, cfg, x, src)
    out = _sdpa(cfg, q, k, v, mask=None)
    out = out.reshape(b, s, cfg.n_heads * cfg.hd) @ p["wo"]
    return out, ({"k": k, "v": v} if want_cache else None)


def _cross_from_cache(p, cfg, x, cache):
    b, s, _ = x.shape
    q = (x @ p["wq"]).reshape(b, s, cfg.n_kv_heads, cfg.q_groups, cfg.hd)
    out = _sdpa(cfg, q, cache["k"], cache["v"], mask=None)
    return out.reshape(b, s, cfg.n_heads * cfg.hd) @ p["wo"]


def _recurrent_prefill(step_fn, state0, x):
    """Fold the prompt into a recurrent state, emitting per-token outputs."""

    def step(st, tok):
        y, st = step_fn(tok[:, None, :], st)
        return st, y[:, 0]

    state, ys = jax.lax.scan(step, state0, jnp.moveaxis(x, 1, 0))
    return jnp.moveaxis(ys, 0, 1), state


# ---------------------------------------------------------------------------
# whisper encoder / frontends
# ---------------------------------------------------------------------------


def _encode(params, cfg: ArchConfig, frames: jax.Array) -> jax.Array:
    """frames: stubbed post-conv features (B, S_frames, frontend_dim)."""
    h = frames.astype(cfg.param_dtype) @ params["frontend_proj"]
    h = h + params["encoder"]["pos"][None]

    def body(h, blk):
        h, _, _ = _apply_block("enc", blk, cfg, h, mode="train")
        return h, ()

    h, _ = jax.lax.scan(body, h, params["encoder"]["stack"])
    return rms_norm(h, params["encoder"]["final_norm"], cfg.norm_eps)


def _cross_source(params, cfg: ArchConfig, aux_embeds):
    """Resolve the cross-attention source from stubbed frontend embeddings."""
    if aux_embeds is None:
        return None
    if cfg.encoder_layers:  # audio: run the encoder over the frames
        return _encode(params, cfg, aux_embeds)
    # vlm: project patch embeddings
    return aux_embeds.astype(cfg.param_dtype) @ params["frontend_proj"]


# ---------------------------------------------------------------------------
# forward / loss
# ---------------------------------------------------------------------------


def _run_stack(
    params, cfg: ArchConfig, h, *, mode, caches=None, index=None, cross_src=None, max_seq=None
):
    """Scan the pattern groups. caches: list per slot of stacked pytrees."""
    shared = params.get("shared")
    n_slots = len(cfg.block_pattern)
    xs = (params["stacks"], caches if caches is not None else [None] * n_slots)

    # scan wants a single pytree of xs with uniform leading dim
    reps = cfg.pattern_repeats()

    def block(kind, p, h, cache):
        return _apply_block(
            kind, p, cfg, h, mode=mode, cache=cache, index=index,
            cross_src=cross_src, shared=shared, max_seq=max_seq,
        )

    if mode == "train" and cfg.remat == "full":
        # Gradient checkpointing per block: backward recomputes one block's
        # forward at a time instead of saving O(S^2) attention (or SSD)
        # intermediates — mandatory at production sequence lengths.  Each
        # block of a many-slot group is its own checkpoint, so backward holds
        # one block's intermediates, not the whole group's.
        block = jax.checkpoint(block, static_argnums=(0,))

    def body(h, slot_inputs):
        blocks, slot_caches = slot_inputs
        aux_sum = jnp.zeros((), jnp.float32)
        new_caches = []
        for j, kind in enumerate(cfg.block_pattern):
            h, nc, aux = block(kind, blocks[j], h, slot_caches[j])
            aux_sum = aux_sum + aux
            new_caches.append(nc)
        return h, (aux_sum, new_caches)

    h, (aux_per_group, out_caches) = jax.lax.scan(body, h, xs)
    aux = jnp.sum(aux_per_group)
    if mode == "train":
        return h, aux, None
    return h, aux, out_caches


def _embed(params, cfg: ArchConfig, tokens):
    """Token embeddings, scaled as the configuration says."""
    h = params["embed"][tokens]
    if cfg.scale_embed:
        h = h * jnp.asarray(cfg.d_model**0.5, h.dtype)
    if cfg.embed_multiplier is not None:
        h = h * jnp.asarray(cfg.embed_multiplier, h.dtype)
    return h


def _head(params, cfg: ArchConfig, h):
    """Logits of hidden states ``h``: final norm, LM head, logit divisor and
    soft cap."""
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    logits = h @ params.get("lm_head", params["embed"].T)
    if cfg.logit_divisor is not None:
        logits = logits / jnp.asarray(cfg.logit_divisor, logits.dtype)
    return softcap(logits, cfg.final_softcap)


def forward(params, cfg: ArchConfig, tokens: jax.Array, aux_embeds=None):
    """Training forward: tokens (B, S) -> (logits (B,S,V), aux_loss)."""
    h = shard(_embed(params, cfg, tokens), "batch", "seq", None)
    cross_src = _cross_source(params, cfg, aux_embeds)
    h, aux, _ = _run_stack(params, cfg, h, mode="train", cross_src=cross_src)
    logits = shard(_head(params, cfg, h), "batch", "seq", "vocab")
    return logits, aux


def loss_fn(params, cfg: ArchConfig, batch) -> jax.Array:
    """batch: (tokens, targets) or (tokens, targets, aux_embeds)."""
    tokens, targets = batch[0], batch[1]
    aux_embeds = batch[2] if len(batch) > 2 else None
    logits, aux = forward(params, cfg, tokens, aux_embeds)
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None].astype(jnp.int32), axis=-1)[..., 0]
    xent = jnp.mean(logz - gold)
    return xent + MOE_AUX_COEF * aux


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------


def init_caches(cfg: ArchConfig, batch: int, max_seq: int, page_size: int | None = None):
    """Abstract cache structure (stacked over pattern repeats) for decode.

    ``page_size`` switches self-attention caches to the paged layout
    (``attention.init_paged_kv_cache`` — the serving substrate); recurrent
    states (mamba2/mlstm/slstm) and the fixed-width cross caches are O(1) in
    sequence length and have nothing to page."""
    reps = cfg.pattern_repeats()

    def kv_cache():
        if page_size is not None:
            return attn_mod.init_paged_kv_cache(cfg, batch, max_seq, page_size)
        return attn_mod.init_kv_cache(cfg, batch, max_seq)

    def one(kind):
        if kind in ("attn", "attn_local", "moe", "shared_attn"):
            return kv_cache()
        if kind in ("mamba2", "mamba2_mlp"):
            return ssm_mod.init_mamba2_state(cfg, batch)
        if kind == "mlstm":
            return xlstm_mod.init_mlstm_state(cfg, batch)
        if kind == "slstm":
            return xlstm_mod.init_slstm_state(cfg, batch)
        if kind == "cross_attn":
            return {
                "k": jnp.zeros((batch, cfg.frontend_seq, cfg.n_kv_heads, cfg.hd), cfg.param_dtype),
                "v": jnp.zeros((batch, cfg.frontend_seq, cfg.n_kv_heads, cfg.hd), cfg.param_dtype),
            }
        if kind == "dec":
            return {
                "self": kv_cache(),
                "cross": {
                    "k": jnp.zeros(
                        (batch, cfg.frontend_seq, cfg.n_kv_heads, cfg.hd), cfg.param_dtype
                    ),
                    "v": jnp.zeros(
                        (batch, cfg.frontend_seq, cfg.n_kv_heads, cfg.hd), cfg.param_dtype
                    ),
                },
            }
        raise ValueError(kind)

    return [
        jax.tree_util.tree_map(lambda x: jnp.broadcast_to(x, (reps,) + x.shape), one(kind))
        for kind in cfg.block_pattern
    ]


def prefill(
    params,
    cfg: ArchConfig,
    tokens: jax.Array,
    aux_embeds=None,
    max_seq=None,
    page_size: int | None = None,
):
    """Process the prompt, return (logits, caches).  Attention caches are
    padded to ``max_seq`` (defaults to the prompt length) so subsequent
    ``decode_step`` calls can append in place.

    ``page_size`` repacks the attention caches into the paged decode layout
    (``attention.pack_kv_to_pages``) before returning: prefill computes in
    the cheap contiguous layout, decode indexes through the page table — the
    prefill->decode hand-off of the serving engine."""
    h = shard(_embed(params, cfg, tokens), "batch", "seq", None)
    cross_src = _cross_source(params, cfg, aux_embeds)
    h, _, caches = _run_stack(
        params, cfg, h, mode="prefill", cross_src=cross_src, max_seq=max_seq
    )
    logits = _head(params, cfg, h[:, -1:])
    if page_size is not None:
        caches = _caches_to_pages(cfg, caches, page_size)
    return logits, caches


def _caches_to_pages(cfg: ArchConfig, caches, page_size: int):
    """Repack every self-attention slot cache (stacked over pattern repeats)
    into the paged layout; recurrent and cross caches pass through."""

    def pack(cache):  # vmapped over the leading repeats axis
        return jax.vmap(lambda c: attn_mod.pack_kv_to_pages(c, page_size))(cache)

    out = []
    for kind, cache in zip(cfg.block_pattern, caches):
        if kind in ("attn", "attn_local", "moe", "shared_attn"):
            out.append(pack(cache))
        elif kind == "dec":
            out.append({"self": pack(cache["self"]), "cross": cache["cross"]})
        else:
            out.append(cache)
    return out


def decode_step(params, cfg: ArchConfig, token: jax.Array, caches, index: jax.Array):
    """token (B, 1) int32; index = number of tokens already in cache."""
    h = _embed(params, cfg, token)
    h, _, caches = _run_stack(params, cfg, h, mode="decode", caches=caches, index=index)
    return _head(params, cfg, h), caches
