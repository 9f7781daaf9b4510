"""Mamba2 (SSD) block — TPU-native chunked-scan formulation.

The CUDA selective-scan does not transfer to TPU; the SSD duality does
(Dao & Gu 2024): within a chunk the recurrence is a small quadratic attention
(MXU-shaped GEMMs), across chunks a cheap recurrence over per-chunk summary
states.  The chunked path below is what trains/lowers; a step recurrence
serves decode (O(1) state per token — this is why zamba2/xlstm run the
long_500k shape).  ``repro.kernels.ssd_scan`` carries the Pallas version of
the intra-chunk kernel with ``repro.kernels.ref`` as the oracle.

State-space shapes (n_groups = 1, B/C shared across heads):
  x   (B, S, H, hd)      dt (B, S, H)       A  (H,) negative scalars
  B,C (B, S, N)          chunk summary state (B, H, hd, N)
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.common import ArchConfig, rms_norm, uniform_init
from repro.models.sharding import shard

# The device scope around the scan (``repro.obs.MODEL_SCOPES``): the chunked
# SSD of training and prefill, and the decode step's state update.
SCAN_SCOPE = "ssm.scan"

__all__ = [
    "SCAN_SCOPE",
    "init_mamba2",
    "mamba2_block",
    "mamba2_decode_step",
    "init_mamba2_state",
    "ssd_chunked",
]


def ssd_chunked(x, dt, a_log, b, c, d_skip, chunk: int = 128, return_state: bool = False):
    """SSD scan. x (B,S,H,hd); dt (B,S,H); a_log (H,); b,c (B,S,N).

    Returns y (B,S,H,hd), and the final recurrent state (B,H,hd,N) when
    ``return_state`` (used by prefill — no O(S) sequential replay needed).
    """
    bsz, s, h, hd = x.shape
    n = b.shape[-1]
    assert s % chunk == 0, (s, chunk)
    nc = s // chunk
    af = -jnp.exp(a_log.astype(jnp.float32))  # (H,) negative
    dtf = jax.nn.softplus(dt.astype(jnp.float32))  # (B,S,H)
    xa = x.astype(jnp.float32) * dtf[..., None]  # dt-weighted input
    da = dtf * af  # (B,S,H) log-decay per step (negative)

    xa = xa.reshape(bsz, nc, chunk, h, hd)
    da = da.reshape(bsz, nc, chunk, h)
    bm = b.astype(jnp.float32).reshape(bsz, nc, chunk, n)
    cm = c.astype(jnp.float32).reshape(bsz, nc, chunk, n)

    cum = jnp.cumsum(da, axis=2)  # (B,nc,Q,H) inclusive cumulative log decay
    # intra-chunk quadratic term: M[t,s] = exp(cum_t - cum_s) for s <= t
    seg = cum[:, :, :, None, :] - cum[:, :, None, :, :]  # (B,nc,Q,Q,H)
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))
    # Mask before the exp: above the diagonal seg is positive and exp(seg)
    # can overflow, and inf x 0 in backward would make the gradient NaN.
    m = jnp.exp(jnp.where(causal[None, None, :, :, None], seg, -jnp.inf))
    cb = jnp.einsum("bcqn,bcsn->bcqs", cm, bm)  # (B,nc,Q,Q)
    y_intra = jnp.einsum("bcqs,bcqsh,bcshd->bcqhd", cb, m, xa)

    # chunk summary states: S_c = sum_s exp(cum_last - cum_s) * B_s x_s^T
    decay_to_end = jnp.exp(cum[:, :, -1:, :] - cum)  # (B,nc,Q,H)
    states = jnp.einsum("bcsn,bcsh,bcshd->bchdn", bm, decay_to_end, xa)

    # inter-chunk recurrence
    chunk_decay = jnp.exp(cum[:, :, -1, :])  # (B,nc,H)

    def step(h_prev, inp):
        s_c, dec = inp  # (B,H,hd,N), (B,H)
        h_new = h_prev * dec[:, :, None, None] + s_c
        return h_new, h_prev  # emit the *incoming* state for chunk c

    h0 = jnp.zeros((bsz, h, hd, n), jnp.float32)
    h_last, h_in = jax.lax.scan(
        step,
        h0,
        (jnp.moveaxis(states, 1, 0), jnp.moveaxis(chunk_decay, 1, 0)),
    )
    h_in = jnp.moveaxis(h_in, 0, 1)  # (B,nc,H,hd,N) state entering each chunk

    # inter-chunk contribution: y_t += C_t . (exp(cum_t) * h_in)
    y_inter = jnp.einsum("bcqn,bcqh,bchdn->bcqhd", cm, jnp.exp(cum), h_in)

    y = (y_intra + y_inter).reshape(bsz, s, h, hd)
    y = y + d_skip.astype(jnp.float32)[None, None, :, None] * x.astype(jnp.float32)
    if return_state:
        return y.astype(x.dtype), h_last
    return y.astype(x.dtype)


def init_mamba2(cfg: ArchConfig, key: jax.Array) -> dict:
    d = cfg.d_model
    d_in = cfg.ssm_expand * d
    n_heads = d_in // cfg.ssm_head_dim
    n = cfg.ssm_state
    conv_ch = d_in + 2 * n
    ks = jax.random.split(key, 5)
    return {
        # in_proj -> [z (d_in), x (d_in), B (n), C (n), dt (H)]
        "in_proj": uniform_init(ks[0], (d, 2 * d_in + 2 * n + n_heads), cfg.param_dtype),
        "conv_w": uniform_init(ks[1], (cfg.conv_width, conv_ch), cfg.param_dtype, scale=0.5),
        **({"conv_b": jnp.zeros((conv_ch,), cfg.param_dtype)} if cfg.conv_bias else {}),
        "a_log": jnp.zeros((n_heads,), jnp.float32),
        "d_skip": jnp.ones((n_heads,), jnp.float32),
        "dt_bias": jnp.zeros((n_heads,), jnp.float32),
        "norm_scale": jnp.zeros((d_in,), cfg.param_dtype),
        "out_proj": uniform_init(ks[2], (d_in, d), cfg.param_dtype),
    }


def _causal_conv(x, w, state=None, bias=None):
    """Depthwise causal conv. x (B,S,C); w (W,C); bias (C,) or None.
    state (B,W-1,C) for decode."""
    width = w.shape[0]
    if state is None:
        pad = jnp.zeros((x.shape[0], width - 1, x.shape[2]), x.dtype)
    else:
        pad = state.astype(x.dtype)
    xp = jnp.concatenate([pad, x], axis=1)
    out = sum(
        xp[:, i : i + x.shape[1], :] * w[i][None, None, :] for i in range(width)
    )
    if bias is not None:
        out = out + bias[None, None, :]
    # keep the carried dtype stable across scan iterations (prefill replay)
    new_state = xp[:, -(width - 1) :, :]
    if state is not None:
        new_state = new_state.astype(state.dtype)
    return out, new_state


def _split_proj(cfg: ArchConfig, proj):
    d_in = cfg.ssm_expand * cfg.d_model
    n = cfg.ssm_state
    n_heads = d_in // cfg.ssm_head_dim
    z = proj[..., :d_in]
    xbc = proj[..., d_in : 2 * d_in + 2 * n]
    dt = proj[..., 2 * d_in + 2 * n :]
    return z, xbc, dt, d_in, n, n_heads


def mamba2_block(
    params: dict, cfg: ArchConfig, x: jax.Array, chunk: int = 128, return_state: bool = False
):
    bsz, s, d = x.shape
    proj = x @ params["in_proj"]
    z, xbc_raw, dt, d_in, n, n_heads = _split_proj(cfg, proj)
    xbc, conv_tail = _causal_conv(xbc_raw, params["conv_w"], bias=params.get("conv_b"))
    xbc = jax.nn.silu(xbc)
    xs = xbc[..., :d_in].reshape(bsz, s, n_heads, cfg.ssm_head_dim)
    b = xbc[..., d_in : d_in + n]
    c = xbc[..., d_in + n :]
    xs = shard(xs, "batch", "seq", "state", None)
    dt = dt + params["dt_bias"][None, None, :]
    ch = min(chunk, s)
    while s % ch:
        ch //= 2
    with jax.named_scope(SCAN_SCOPE):
        out = ssd_chunked(
            xs, dt, params["a_log"], b, c, params["d_skip"], chunk=max(ch, 1),
            return_state=return_state,
        )
    y, ssm_state = out if return_state else (out, None)
    y = _gated_norm(params, cfg, y.reshape(bsz, s, d_in), z) @ params["out_proj"]
    if return_state:
        return y, {"conv": conv_tail, "ssm": ssm_state}
    return y


def _gated_norm(params: dict, cfg: ArchConfig, y, z):
    """Mamba2's RMSNormGated: the gate first, then the norm over all d_in
    channels (one group), in float32: ``rms_norm(y * silu(z))``."""
    g = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    return rms_norm(g, params["norm_scale"], cfg.norm_eps).astype(y.dtype)


def init_mamba2_state(cfg: ArchConfig, batch: int, dtype=jnp.float32) -> dict:
    d_in = cfg.ssm_expand * cfg.d_model
    n_heads = d_in // cfg.ssm_head_dim
    n = cfg.ssm_state
    conv_ch = d_in + 2 * n
    return {
        "conv": jnp.zeros((batch, cfg.conv_width - 1, conv_ch), dtype),
        "ssm": jnp.zeros((batch, n_heads, cfg.ssm_head_dim, n), jnp.float32),
    }


def mamba2_decode_step(params: dict, cfg: ArchConfig, x: jax.Array, state: dict):
    """x (B,1,d) -> (y (B,1,d), new_state). O(1) per token."""
    bsz = x.shape[0]
    proj = x @ params["in_proj"]
    z, xbc, dt, d_in, n, n_heads = _split_proj(cfg, proj)
    xbc, conv_state = _causal_conv(xbc, params["conv_w"], state["conv"], params.get("conv_b"))
    xbc = jax.nn.silu(xbc)
    xs = xbc[..., :d_in].reshape(bsz, n_heads, cfg.ssm_head_dim)
    b = xbc[:, 0, d_in : d_in + n]  # (B,N)
    c = xbc[:, 0, d_in + n :]
    with jax.named_scope(SCAN_SCOPE):
        dtf = jax.nn.softplus((dt[:, 0] + params["dt_bias"][None]).astype(jnp.float32))  # (B,H)
        af = -jnp.exp(params["a_log"].astype(jnp.float32))
        decay = jnp.exp(dtf * af[None])  # (B,H)
        h = state["ssm"] * decay[..., None, None] + jnp.einsum(
            "bhd,bn,bh->bhdn", xs.astype(jnp.float32), b.astype(jnp.float32), dtf
        )
        y = jnp.einsum("bhdn,bn->bhd", h, c.astype(jnp.float32))
        y = y + params["d_skip"][None, :, None] * xs.astype(jnp.float32)
    y = y.reshape(bsz, 1, d_in).astype(x.dtype)
    y = _gated_norm(params, cfg, y, z)
    return y @ params["out_proj"], {"conv": conv_state, "ssm": h}
