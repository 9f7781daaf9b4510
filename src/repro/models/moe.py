"""Mixture-of-Experts FFN: top-k router with capacity-based dense dispatch.

TPU adaptation: token->expert routing is expressed as one-hot dispatch/combine
einsums (GShard/Switch style) rather than host-side gathers — the dispatch
tensors become all-to-all-like reshards under GSPMD when experts are sharded
over the `model` mesh axis, and the expert GEMMs stay MXU-shaped.

Includes the auxiliary load-balance loss (Switch Transformer eq. 4) surfaced
to the trainer, and the optional *dense residual* branch of Arctic (a small
always-on MLP in parallel with the MoE output).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.common import ArchConfig, uniform_init
from repro.models.mlp import init_mlp, mlp
from repro.models.sharding import shard

__all__ = ["init_moe", "moe_ffn"]


def init_moe(cfg: ArchConfig, key: jax.Array) -> dict:
    e, d, f = cfg.n_experts, cfg.d_model, cfg.moe_d_ff
    ks = jax.random.split(key, 5)
    p = {
        "router": uniform_init(ks[0], (d, e), jnp.float32),
        "w_gate": uniform_init(ks[1], (e, d, f), cfg.param_dtype),
        "w_up": uniform_init(ks[2], (e, d, f), cfg.param_dtype),
        "w_down": uniform_init(ks[3], (e, f, d), cfg.param_dtype),
    }
    if cfg.dense_residual:
        p["dense"] = init_mlp(cfg, ks[4], d_ff=cfg.d_ff, gated=True)
    return p


def moe_ffn(params: dict, cfg: ArchConfig, x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Returns (output, aux_load_balance_loss). x: (B, S, d)."""
    if cfg.moe_impl == "a2a":
        from repro.models.sharding import current_mesh

        mesh = current_mesh()
        if mesh is not None and "model" in mesh.axis_names:
            return _moe_ffn_a2a(params, cfg, x, mesh)
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    n_tok = b * s
    xf = x.reshape(n_tok, d)

    gates = jax.nn.softmax(xf.astype(jnp.float32) @ params["router"], axis=-1)  # (T, E)
    top_w, top_idx = jax.lax.top_k(gates, k)  # (T, k)
    top_w = top_w / jnp.maximum(jnp.sum(top_w, -1, keepdims=True), 1e-9)

    # capacity per expert
    cap = int(max(1, round(cfg.capacity_factor * n_tok * k / e)))

    # Slot assignment without a (T, E, C) one-hot: a single (T, E) cumsum
    # gives each (token, expert) pair its position in the expert's buffer
    # (top-k experts are distinct per token, so the mask is 0/1).
    expert_mask = jnp.sum(jax.nn.one_hot(top_idx, e, dtype=jnp.float32), axis=1)  # (T, E)
    position = jnp.cumsum(expert_mask, axis=0) * expert_mask - 1.0  # (T, E)
    slot = jnp.take_along_axis(position, top_idx, axis=1).astype(jnp.int32)  # (T, k)
    keep = jnp.logical_and(slot >= 0, slot < cap)  # capacity drop
    slot_c = jnp.clip(slot, 0, cap - 1)

    # Scatter tokens into (E, C, d) expert buffers: k static scatter-adds.
    ex_in = jnp.zeros((e, cap, d), x.dtype)
    for kk in range(k):
        contrib = jnp.where(keep[:, kk : kk + 1], xf, 0).astype(x.dtype)
        ex_in = ex_in.at[top_idx[:, kk], slot_c[:, kk]].add(contrib)
    ex_in = shard(ex_in, "experts", None, None)

    h = jnp.einsum("ecd,edf->ecf", ex_in, params["w_up"])
    g = jnp.einsum("ecd,edf->ecf", ex_in, params["w_gate"])
    h = h * jax.nn.silu(g)
    h = shard(h, "experts", None, "ffn")
    ex_out = jnp.einsum("ecf,efd->ecd", h, params["w_down"])  # (E, C, d)

    # Combine: k gathers weighted by the renormalized router weights.
    out = jnp.zeros_like(xf)
    for kk in range(k):
        piece = ex_out[top_idx[:, kk], slot_c[:, kk]]  # (T, d)
        w = jnp.where(keep[:, kk], top_w[:, kk], 0.0)[:, None].astype(x.dtype)
        out = out + w * piece
    out = out.reshape(b, s, d)

    if "dense" in params:
        out = out + mlp(params["dense"], cfg, x)

    # Switch load-balance aux: E * sum_e (frac_tokens_e * mean_gate_e)
    frac = jnp.mean(expert_mask, axis=0)  # (E,)
    mean_gate = jnp.mean(gates, axis=0)
    aux = e * jnp.sum(frac * mean_gate)
    return out, aux


# ---------------------------------------------------------------------------
# shard_map all-to-all dispatch (EXPERIMENTS.md section Perf, qwen3 iteration)
# ---------------------------------------------------------------------------


def _pack_by_dest(xf, dest, n_dest: int, cap: int, valid=None):
    """Pack rows of xf (T, d) into (n_dest, cap, d) buffers by dest (T,).

    Returns (buffers, slot (T,), kept (T,)) — the cumsum slotting trick;
    overflow rows beyond `cap` are dropped; rows with ``valid=False`` (e.g.
    padding arriving from the wire) neither occupy slots nor contribute.
    """
    t = xf.shape[0]
    onehot = jax.nn.one_hot(dest, n_dest, dtype=jnp.float32)  # (T, n_dest)
    if valid is not None:
        onehot = onehot * valid[:, None].astype(jnp.float32)
    pos = jnp.cumsum(onehot, axis=0) * onehot - 1.0
    slot = jnp.max(pos, axis=1).astype(jnp.int32)  # position within dest
    kept = jnp.logical_and(slot >= 0, slot < cap)
    slot_c = jnp.clip(slot, 0, cap - 1)
    buf = jnp.zeros((n_dest, cap, xf.shape[1]), xf.dtype)
    buf = buf.at[dest, slot_c].add(jnp.where(kept[:, None], xf, 0))
    return buf, slot_c, kept


def _moe_ffn_a2a(params: dict, cfg: ArchConfig, x: jax.Array, mesh):
    """Expert-parallel MoE with explicit all-to-all dispatch.

    Token layout: batch sharded over the batch axes, sequence over `model`
    (sequence-parallel residual stream), so every (data, model) shard owns a
    disjoint token slice.  Each shard routes its tokens, exchanges them with
    the expert owners via all-to-all over `model`, runs its local experts,
    and all-to-alls the results back — the canonical TPU MoE schedule.
    Collective volume: O(3 * T_local * k * d) per layer instead of the
    O(E * cap * d) full-buffer all-reduces of the GSPMD scatter path.
    """
    from jax.sharding import PartitionSpec as P

    from repro.launch.mesh import batch_axes

    b_axes = batch_axes(mesh)
    n_model = mesh.shape["model"]
    e, k, d = cfg.n_experts, cfg.top_k, cfg.d_model
    e_local = e // n_model
    bsz, s, _ = x.shape
    t_local = (bsz // _axsize(mesh, b_axes)) * (s // n_model)
    # per-destination-shard capacity (pair capacity) and local expert capacity
    cap_pair = int(max(8, round(cfg.capacity_factor * t_local * k / n_model)))
    cap_local = int(max(8, round(cfg.capacity_factor * t_local * k * 1.0 / e_local)))

    def body(xb, router, w_gate, w_up, w_down):
        # xb (B_loc, S_loc, d); expert weights are this shard's slice (E_loc,..)
        t = xb.shape[0] * xb.shape[1]
        xf = xb.reshape(t, d)
        gates = jax.nn.softmax(xf.astype(jnp.float32) @ router, axis=-1)  # (t, E)
        top_w, top_idx = jax.lax.top_k(gates, k)
        top_w = top_w / jnp.maximum(jnp.sum(top_w, -1, keepdims=True), 1e-9)

        # flatten the k assignments; destination shard owns expert block
        flat_idx = top_idx.reshape(t * k)
        flat_w = top_w.reshape(t * k)
        dest = flat_idx // e_local
        x_rep = jnp.repeat(xf, k, axis=0)  # (t*k, d)
        send, slot, kept = _pack_by_dest(x_rep, dest, n_model, cap_pair)
        # ship expert-local ids alongside, +1 so 0 marks wire padding
        meta = (flat_idx % e_local + 1).astype(xf.dtype)[:, None]
        send_meta, _, _ = _pack_by_dest(meta, dest, n_model, cap_pair)

        recv = jax.lax.all_to_all(send, "model", split_axis=0, concat_axis=0, tiled=True)
        recv_meta = jax.lax.all_to_all(
            send_meta, "model", split_axis=0, concat_axis=0, tiled=True
        )

        # local expert compute: scatter received rows into per-expert buffers
        rows = recv.reshape(n_model * cap_pair, d)
        meta_rows = recv_meta.reshape(n_model * cap_pair)
        wire_valid = meta_rows > 0.5
        eid = jnp.clip(meta_rows.astype(jnp.int32) - 1, 0, e_local - 1)
        ebuf, eslot, ekept = _pack_by_dest(rows, eid, e_local, cap_local, valid=wire_valid)
        h = jnp.einsum("ecd,edf->ecf", ebuf, w_up)
        g = jnp.einsum("ecd,edf->ecf", ebuf, w_gate)
        h = h * jax.nn.silu(g)
        eout = jnp.einsum("ecf,efd->ecd", h, w_down)  # (E_loc, cap_local, d)
        # un-scatter back to the received-row order
        back_rows = jnp.where(
            ekept[:, None], eout[eid, eslot], 0
        )  # (n_model*cap_pair, d)
        back = back_rows.reshape(n_model, cap_pair, d)
        ret = jax.lax.all_to_all(back, "model", split_axis=0, concat_axis=0, tiled=True)

        # combine at the source: gather each assignment's row, weight, sum
        got = jnp.where(kept[:, None], ret[dest, slot], 0)  # (t*k, d)
        out = jnp.sum(
            (got * flat_w[:, None].astype(got.dtype)).reshape(t, k, d), axis=1
        )
        # load-balance aux (local estimate; averaged over shards by psum/size)
        frac = jnp.mean(
            jnp.sum(jax.nn.one_hot(top_idx, e, dtype=jnp.float32), axis=1), axis=0
        )
        mean_gate = jnp.mean(gates, axis=0)
        aux = e * jnp.sum(frac * mean_gate)
        aux = jax.lax.pmean(jax.lax.pmean(aux, "model"), b_axes)
        return out.reshape(xb.shape), aux

    bspec = b_axes if len(b_axes) > 1 else b_axes[0]
    out, aux = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(
            P(bspec, "model", None),  # x: batch over data(+pod), seq over model
            P(),  # router replicated
            P("model", None, None),  # experts over model
            P("model", None, None),
            P("model", None, None),
        ),
        out_specs=(P(bspec, "model", None), P()),
        check_vma=False,
    )(x, params["router"], params["w_gate"], params["w_up"], params["w_down"])

    if "dense" in params:
        out = out + mlp(params["dense"], cfg, x)
    return out, aux


def _axsize(mesh, axes) -> int:
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n
