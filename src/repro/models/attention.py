"""Grouped-query attention with RoPE (or none), soft-capping, sliding windows,
cross-attention, and cached decode — the attention substrate for every
assigned architecture.

Layout conventions:
  activations    (B, S, d_model)
  q              (B, S, KV, G, hd)   G = n_heads / n_kv_heads
  k, v           (B, S, KV, hd)
  decode cache   {"k": (B, S_max, KV, hd), "v": ..., "idx": ()}
  paged cache    {"pool_k": (B*P, page, KV, hd), "pool_v": ...,
                  "page_table": (B, P) int32}

The paged layout is the serving substrate (``repro.serve``): the KV pool is
one preallocated static-shape buffer, sequences address it through an int32
page table, and a single-token decode writes exactly one (page, slot) line —
so the decode program's avals never depend on how long a sequence has grown
and the jit cache stays at one entry for the server's whole lifetime.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.common import ArchConfig, apply_rope, rms_norm, rope_angles, softcap, uniform_init
from repro.models.sharding import shard

__all__ = [
    "init_attention",
    "attention",
    "attn_scale",
    "position_qk",
    "cross_attention",
    "init_kv_cache",
    "decode_attention",
    "init_paged_kv_cache",
    "pack_kv_to_pages",
    "paged_decode_attention",
]

_NEG = -2.3819763e38  # bf16-safe -inf surrogate


def init_attention(cfg: ArchConfig, key: jax.Array, cross: bool = False) -> dict:
    hd = cfg.hd
    ks = jax.random.split(key, 6)
    p = {
        "wq": uniform_init(ks[0], (cfg.d_model, cfg.n_heads * hd), cfg.param_dtype),
        "wk": uniform_init(ks[1], (cfg.d_model, cfg.n_kv_heads * hd), cfg.param_dtype),
        "wv": uniform_init(ks[2], (cfg.d_model, cfg.n_kv_heads * hd), cfg.param_dtype),
        "wo": uniform_init(ks[3], (cfg.n_heads * hd, cfg.d_model), cfg.param_dtype),
    }
    if cfg.qk_norm and not cross:
        p["q_scale"] = jnp.zeros((hd,), cfg.param_dtype)
        p["k_scale"] = jnp.zeros((hd,), cfg.param_dtype)
    return p


def _project_qkv(params, cfg: ArchConfig, xq: jax.Array, xkv: jax.Array):
    b, s_q, _ = xq.shape
    s_kv = xkv.shape[1]
    hd = cfg.hd
    q = (xq @ params["wq"]).reshape(b, s_q, cfg.n_kv_heads, cfg.q_groups, hd)
    k = (xkv @ params["wk"]).reshape(b, s_kv, cfg.n_kv_heads, hd)
    v = (xkv @ params["wv"]).reshape(b, s_kv, cfg.n_kv_heads, hd)
    if "q_scale" in params:
        q = rms_norm(q, params["q_scale"], cfg.norm_eps)
        k = rms_norm(k, params["k_scale"], cfg.norm_eps)
    return q, k, v


def attn_scale(cfg: ArchConfig) -> float:
    """The softmax scale on q.k: the configured one, else hd^-1/2."""
    return cfg.attn_scale if cfg.attn_scale is not None else cfg.hd ** -0.5


def position_qk(cfg: ArchConfig, q, k, positions):
    """RoPE on q (B,S,KV,G,hd) and k (B,S,KV,hd) at ``positions`` (S,);
    under ``position_embedding="nope"`` both pass unchanged."""
    if cfg.position_embedding == "nope":
        return q, k
    cos, sin = rope_angles(positions, cfg.hd, cfg.rope_theta)  # (S, hd/2)
    q = apply_rope(q, cos[None, :, None, None, :], sin[None, :, None, None, :])
    k = apply_rope(k, cos[None, :, None, :], sin[None, :, None, :])
    return q, k


def _sdpa(cfg: ArchConfig, q, k, v, mask):
    """q (B,Sq,KV,G,hd); k,v (B,Skv,KV,hd); mask broadcastable (B,1,1,Sq,Skv)."""
    if cfg.attn_impl == "chunked" and k.shape[1] >= 512:
        return _sdpa_chunked(cfg, q, k, v, mask)
    scale = attn_scale(cfg)
    logits = jnp.einsum(
        "bqkgh,bskh->bkgqs", q.astype(jnp.float32), k.astype(jnp.float32)
    ) * scale
    logits = softcap(logits, cfg.attn_softcap)
    if mask is not None:
        logits = jnp.where(mask, logits, _NEG)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgqs,bskh->bqkgh", probs.astype(v.dtype), v)
    return out


def _sdpa_chunked(cfg: ArchConfig, q, k, v, mask, block: int = 512):
    """Online-softmax attention over KV blocks (flash-attention dataflow in
    pure jnp — the TPU Pallas kernel's fallback).  The (Sq, Skv) probability
    matrix is never materialized: HBM traffic drops from O(S^2) to O(S*hd).
    """
    b, s_q, kv, g, hd = q.shape
    s_k = k.shape[1]
    blk = block
    while s_k % blk:
        blk //= 2
    n_blocks = s_k // blk
    scale = attn_scale(cfg)
    qf = q.astype(jnp.float32) * scale

    def body(carry, i):
        acc, m_prev, l_prev = carry
        k_blk = jax.lax.dynamic_slice_in_dim(k, i * blk, blk, 1).astype(jnp.float32)
        v_blk = jax.lax.dynamic_slice_in_dim(v, i * blk, blk, 1).astype(jnp.float32)
        logits = jnp.einsum("bqkgh,bskh->bkgqs", qf, k_blk)
        logits = softcap(logits, cfg.attn_softcap)
        if mask is not None:
            m_blk = jax.lax.dynamic_slice_in_dim(
                jnp.broadcast_to(mask, mask.shape[:-1] + (s_k,)), i * blk, blk, -1
            )
            logits = jnp.where(m_blk, logits, _NEG)
        m_cur = jnp.max(logits, axis=-1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(logits - m_new[..., None])
        corr = jnp.exp(m_prev - m_new)
        l_new = corr * l_prev + jnp.sum(p, axis=-1)
        acc = acc * corr[..., None] + jnp.einsum("bkgqs,bskh->bkgqh", p, v_blk)
        return (acc, m_new, l_new), ()

    # Backward recomputes each block's probabilities from the carry instead
    # of saving them: the (Sq, Skv) matrix is not materialized in training
    # either.
    body = jax.checkpoint(body)
    acc0 = jnp.zeros((b, kv, g, s_q, hd), jnp.float32)
    m0 = jnp.full((b, kv, g, s_q), _NEG, jnp.float32)
    l0 = jnp.zeros((b, kv, g, s_q), jnp.float32)
    (acc, m_fin, l_fin), _ = jax.lax.scan(
        body, (acc0, m0, l0), jnp.arange(n_blocks)
    )
    safe = jnp.where(l_fin == 0.0, 1.0, l_fin)
    out = acc / safe[..., None]
    return jnp.moveaxis(out, 3, 1).astype(v.dtype)  # (B,Sq,KV,G,hd)


def _causal_mask(s_q: int, s_kv: int, window: int | None, offset: int = 0):
    """(1,1,1,Sq,Skv) bool; offset = absolute position of query 0."""
    qpos = jnp.arange(s_q)[:, None] + offset
    kpos = jnp.arange(s_kv)[None, :]
    m = kpos <= qpos
    if window is not None:
        m = jnp.logical_and(m, kpos > qpos - window)
    return m[None, None, None]


def attention(
    params,
    cfg: ArchConfig,
    x: jax.Array,
    *,
    causal: bool = True,
    window: int | None = None,
) -> jax.Array:
    """Full-sequence self-attention (train / prefill)."""
    b, s, _ = x.shape
    q, k, v = _project_qkv(params, cfg, x, x)
    q, k = position_qk(cfg, q, k, jnp.arange(s))
    q = shard(q, "batch", "seq", "kv_heads", None, None)
    k = shard(k, "batch", "seq", "kv_heads", None)
    v = shard(v, "batch", "seq", "kv_heads", None)
    mask = _causal_mask(s, s, window) if causal else None
    out = _sdpa(cfg, q, k, v, mask)
    out = out.reshape(b, s, cfg.n_heads * cfg.hd)
    return out @ params["wo"]


def cross_attention(params, cfg: ArchConfig, x: jax.Array, kv_source: jax.Array) -> jax.Array:
    """Cross-attention to encoder / image embeddings (no RoPE, full mask)."""
    b, s, _ = x.shape
    q, k, v = _project_qkv(params, cfg, x, kv_source)
    out = _sdpa(cfg, q, k, v, mask=None)
    return out.reshape(b, s, cfg.n_heads * cfg.hd) @ params["wo"]


# ---------------------------------------------------------------------------
# cached decode
# ---------------------------------------------------------------------------


def init_kv_cache(cfg: ArchConfig, batch: int, max_seq: int, dtype=None) -> dict:
    dtype = dtype or cfg.param_dtype
    shape = (batch, max_seq, cfg.n_kv_heads, cfg.hd)
    return {
        "k": jnp.zeros(shape, dtype),
        "v": jnp.zeros(shape, dtype),
    }


def decode_attention(
    params,
    cfg: ArchConfig,
    x: jax.Array,
    cache: dict,
    index: jax.Array,
    *,
    window: int | None = None,
) -> tuple[jax.Array, dict]:
    """Single-token decode: x (B,1,d); cache holds `index` valid tokens.

    The cache sequence axis carries the "kv_seq" logical sharding (mapped to
    the `model` mesh axis for long-context decode): the q@k contraction and
    the probs@v contraction then reduce over a sharded axis, which GSPMD
    lowers to per-shard partial attention + a small cross-shard combine —
    exactly the flash-decode communication pattern (DESIGN.md section 3).
    """
    b, one, _ = x.shape
    q, k_new, v_new = _project_qkv(params, cfg, x, x)
    q, k_new = position_qk(cfg, q, k_new, index[None])

    k = jax.lax.dynamic_update_slice(cache["k"], k_new.astype(cache["k"].dtype), (0, index, 0, 0))
    v = jax.lax.dynamic_update_slice(cache["v"], v_new.astype(cache["v"].dtype), (0, index, 0, 0))
    k = shard(k, "batch", "kv_seq", None, None)
    v = shard(v, "batch", "kv_seq", None, None)

    s_max = k.shape[1]
    kpos = jnp.arange(s_max)
    valid = kpos <= index
    if window is not None:
        valid = jnp.logical_and(valid, kpos > index - window)
    mask = valid[None, None, None, None, :]
    out = _sdpa(cfg, q, k, v, mask)
    out = out.reshape(b, 1, cfg.n_heads * cfg.hd)
    return out @ params["wo"], {"k": k, "v": v}


# ---------------------------------------------------------------------------
# paged decode cache (the serving layout)
# ---------------------------------------------------------------------------


def _pages_per_seq(max_seq: int, page_size: int) -> int:
    return -(-int(max_seq) // int(page_size))


def init_paged_kv_cache(
    cfg: ArchConfig, batch: int, max_seq: int, page_size: int, dtype=None
) -> dict:
    """Preallocated paged KV cache: a (B*P, page, KV, hd) pool plus a
    (B, P) int32 page table mapping each sequence's logical pages onto pool
    rows.  The identity table assigns every sequence a contiguous stripe;
    the indirection is what a production server remaps for prefix sharing /
    admission — the decode program below only ever sees the table."""
    dtype = dtype or cfg.param_dtype
    pages = _pages_per_seq(max_seq, page_size)
    pool = (batch * pages, int(page_size), cfg.n_kv_heads, cfg.hd)
    table = jnp.arange(batch * pages, dtype=jnp.int32).reshape(batch, pages)
    return {
        "pool_k": jnp.zeros(pool, dtype),
        "pool_v": jnp.zeros(pool, dtype),
        "page_table": table,
    }


def pack_kv_to_pages(cache: dict, page_size: int) -> dict:
    """Repack a dense prefill cache ``{"k","v"}: (B, S_max, KV, hd)`` into the
    paged layout (identity page table).  This is the prefill->decode hand-off:
    prefill writes the cheap contiguous layout, one reshape moves it into the
    pool the decode step indexes through the table."""
    k, v = cache["k"], cache["v"]
    b, s_max, kv, hd = k.shape
    pages = _pages_per_seq(s_max, page_size)
    pad = pages * int(page_size) - s_max
    if pad:
        k = jnp.pad(k, [(0, 0), (0, pad), (0, 0), (0, 0)])
        v = jnp.pad(v, [(0, 0), (0, pad), (0, 0), (0, 0)])
    table = jnp.arange(b * pages, dtype=jnp.int32).reshape(b, pages)
    return {
        "pool_k": k.reshape(b * pages, int(page_size), kv, hd),
        "pool_v": v.reshape(b * pages, int(page_size), kv, hd),
        "page_table": table,
    }


def paged_decode_attention(
    params,
    cfg: ArchConfig,
    x: jax.Array,
    cache: dict,
    index: jax.Array,
    *,
    window: int | None = None,
) -> tuple[jax.Array, dict]:
    """Single-token decode against the paged cache (lockstep batch: every
    sequence writes position ``index``).

    The new K/V line lands in exactly one (page, slot) per sequence: the
    physical page comes from one dynamic row of the page table, the write is
    a (B,)-scatter into the pool — O(B * KV * hd) bytes touched regardless of
    context length, versus the dense path's full-cache ``dynamic_update_slice``
    copy when the carry is not donated.  Attention then gathers the table's
    view of the pool back to (B, P*page, KV, hd) and reuses the masked SDPA
    (positions past ``index`` — including the padded tail of the last page —
    are masked, so pool garbage never contributes)."""
    b, _one, _ = x.shape
    pool_k, pool_v, table = cache["pool_k"], cache["pool_v"], cache["page_table"]
    page_size = pool_k.shape[1]

    q, k_new, v_new = _project_qkv(params, cfg, x, x)
    q, k_new = position_qk(cfg, q, k_new, index[None])

    # index is traced: page/slot stay inside the jitted program (no host sync,
    # no shape dependence on sequence length — the compile-once contract).
    page = index // page_size
    slot = index % page_size
    phys = jax.lax.dynamic_index_in_dim(table, page, axis=1, keepdims=False)  # (B,)
    pool_k = pool_k.at[phys, slot].set(k_new[:, 0].astype(pool_k.dtype))
    pool_v = pool_v.at[phys, slot].set(v_new[:, 0].astype(pool_v.dtype))

    # (B, P, page, KV, hd) -> (B, P*page, KV, hd): the table's sequence view.
    pages = table.shape[1]
    k = pool_k[table].reshape(b, pages * page_size, *pool_k.shape[2:])
    v = pool_v[table].reshape(b, pages * page_size, *pool_v.shape[2:])
    k = shard(k, "batch", "kv_seq", None, None)
    v = shard(v, "batch", "kv_seq", None, None)

    kpos = jnp.arange(pages * page_size)
    valid = kpos <= index
    if window is not None:
        valid = jnp.logical_and(valid, kpos > index - window)
    mask = valid[None, None, None, None, :]
    out = _sdpa(cfg, q, k, v, mask)
    out = out.reshape(b, 1, cfg.n_heads * cfg.hd)
    return out @ params["wo"], {
        "pool_k": pool_k,
        "pool_v": pool_v,
        "page_table": table,
    }
