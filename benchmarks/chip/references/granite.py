"""The granite-4.0-h family, a Mamba-2 + NoPE-attention hybrid: weights, FLOP
counts, the program check, and a plain forward, loss and gradient in float32.

Follows the parameter layout that the system under test is fed
(``weights``): ``embed`` (V, d) tied as the LM head, ``final_norm`` (d,),
and one dict per slot of the layer period in ``stacks``, each stacked over the
period's repeats.  A Mamba-2 slot holds ``ln1``/``ln2`` (d,), ``ssm``
{``in_proj`` (d, 2 d_in + 2 N + H), ``conv_w`` (W, d_in + 2 N), ``conv_b``,
``a_log``/``d_skip``/``dt_bias`` (H,), ``norm_scale`` (d_in,), ``out_proj``
(d_in, d)} and ``mlp`` {``up``/``gate`` (d, f), ``down`` (f, d)}; an
attention slot holds ``attn`` {``wq`` (d, Ha hd), ``wk``/``wv`` (d, KV hd),
``wo`` (Ha hd, d)} in place of ``ssm``.  A slot's kind is read from its keys.

The equations, from the published ``config.json`` (keys in brackets):

* ``h = e * E[tok]`` [embedding_multiplier]; each layer does
  ``h += r * mixer(RMSNorm(h))`` then ``h += r * MLP(RMSNorm(h))``
  [residual_multiplier, rms_norm_eps]; ``logits = RMSNorm(h) E^T / s``
  [logits_scaling].  MLP: ``down(up(x) * silu(gate(x)))``.
* Attention [layer_types "attention"]: GQA, query head ``h`` reads key/value
  head ``h // (Ha / KV)``, no positional encoding
  [position_embedding_type "nope"], causal softmax of ``q.k * a``
  [attention_multiplier], no bias.
* Mamba-2 [layer_types "mamba", mamba_*]: ``[z | xBC | dt] = u W_in``;
  ``xBC = silu(conv1d_causal(xBC) + b)``, split into ``x`` (H heads of P),
  ``B`` and ``C`` (N, one group); ``D = softplus(dt + dt_bias)``,
  ``A = -exp(A_log)``; token by token ``h_t = exp(D_t A) h_{t-1} +
  D_t x_t B_t^T`` and ``y_t = h_t C_t + D_skip x_t``; then
  ``W_out RMSNorm(y * silu(z))``: the gate first, the norm over all d_in
  channels.

Where it departs from the published model, as the program does:

* RMSNorm is ``x / sqrt(mean(x^2) + eps) * (1 + scale)``: every scale is
  stored as an offset from one.
* The recurrence is run token by token here; the published model, and the
  program, block it in chunks (``mamba_chunk_size``), a regrouping of the same
  sum.
* The gradient is returned rounded to the configuration's ``torch_dtype``,
  where the local step rounds it (``generators/fl_round.py``), so that no
  float32 copy of it sits on the chip beside the float32 weights.

Every product goes through ``_mm``/``_einsum``, which take the precision
(``precision.cast``): ``"f32"`` is float32 at ``Precision.HIGHEST``; a lower
mode rounds each operand to that type first (the control of the correctness
check).  The gradient fits on one chip at the published widths and 4k
positions: layers run under ``jax.checkpoint``, the recurrence in checkpointed
blocks of positions, attention in blocks of queries, and the LM head and loss
in blocks of positions.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from .precision import HI, cast

__all__ = ["weights", "train_flops", "serve_flops", "ssd_cost", "check_program",
           "matmul_params", "loss", "grad", "served_gaps"]

SCAN_BLOCK = 128  # positions per checkpointed block of the recurrence
QUERY_BLOCK = 512  # queries per block of attention
HEAD_BLOCK = 512  # positions per block of the LM head and loss


def _items(cfg: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, str, bool))))


def _sizes(m: dict) -> dict:
    d, ha = m["hidden_size"], m["num_attention_heads"]
    d_in = m["mamba_expand"] * d
    n = m["mamba_n_groups"] * m["mamba_d_state"]
    return {"d": d, "f": m["shared_intermediate_size"], "V": m["vocab_size"],
            "ha": ha, "kv": m["num_key_value_heads"], "hd": d // ha,
            "d_in": d_in, "H": m["mamba_n_heads"], "P": m["mamba_d_head"], "N": n,
            "W": m["mamba_d_conv"], "conv": d_in + 2 * n}


def period(layer_types) -> tuple:
    """The shortest prefix of ``layer_types`` whose repeats make the list:
    the slots of the program's ``block_pattern``."""
    types = tuple(layer_types)
    for k in range(1, len(types) + 1):
        if len(types) % k == 0 and types[:k] * (len(types) // k) == types:
            return types[:k]
    return types


@functools.partial(jax.jit, static_argnums=(1, 2))
def _weights(key, items, layer_types):
    m, z = dict(items), _sizes(dict(items))
    d, f, V = z["d"], z["f"], z["V"]
    dt = jnp.dtype(m["torch_dtype"])
    slots = period(layer_types)
    reps = len(layer_types) // len(slots)
    ks = iter(jax.random.split(key, 16 * len(slots) + 4))

    def uni(shape, fan_in):
        bound = fan_in ** -0.5
        return jax.random.uniform(next(ks), (reps,) + shape, jnp.float32, -bound, bound)

    def norm_scale(shape, lead=True):
        return 0.05 * jax.random.normal(next(ks), ((reps,) if lead else ()) + shape)

    def mlp():
        return {"up": uni((d, f), d), "down": uni((f, d), f), "gate": uni((d, f), d)}

    stacks = []
    for kind in slots:
        if kind == "attention":
            ha, kv, hd = z["ha"], z["kv"], z["hd"]
            mixer = {"attn": {"wq": uni((d, ha * hd), d), "wk": uni((d, kv * hd), d),
                              "wv": uni((d, kv * hd), d), "wo": uni((ha * hd, d), ha * hd)}}
        else:
            H, W = z["H"], z["W"]
            # Mamba-2's own initialisation: A in [1, 16], the step
            # softplus(dt_bias) log-uniform in [1e-3, 1e-1], D = 1.
            step = jnp.exp(jax.random.uniform(next(ks), (reps, H), jnp.float32,
                                              math.log(1e-3), math.log(1e-1)))
            mixer = {"ssm": {
                "in_proj": uni((d, 2 * z["d_in"] + 2 * z["N"] + H), d),
                "conv_w": uni((W, z["conv"]), W), "conv_b": uni((z["conv"],), W),
                "a_log": jnp.log(jax.random.uniform(next(ks), (reps, H), jnp.float32,
                                                    1.0, 16.0)),
                "d_skip": jnp.ones((reps, H), jnp.float32),
                "dt_bias": step + jnp.log(-jnp.expm1(-step)),
                "norm_scale": norm_scale((z["d_in"],)),
                "out_proj": uni((z["d_in"], d), z["d_in"])}}
        stacks.append(dict(mixer, ln1=norm_scale((d,)), ln2=norm_scale((d,)), mlp=mlp()))
    tree = {"embed": 0.02 * jax.random.normal(next(ks), (V, d)),
            "final_norm": norm_scale((d,), lead=False), "stacks": stacks}
    return jax.tree_util.tree_map(lambda x: x.astype(dt), tree)


def weights(cfg: dict, key):
    """Projections uniform in +-fan_in^-1/2 (the depthwise conv's fan-in is
    its width), embedding N(0, 0.02^2), norm scales N(0, 0.05^2), the SSM's
    A, step and D as Mamba-2 initialises them; every leaf in the
    configuration's ``torch_dtype``, as the published checkpoint stores it."""
    return _weights(key, _items(cfg), tuple(cfg["layer_types"]))


# -- FLOP counts, from the configuration's sizes ---------------------------


def _kinds(m: dict) -> tuple:
    """(Mamba-2 layers, attention layers) of the file's ``layer_types``."""
    types = m["layer_types"]
    return sum(t == "mamba" for t in types), sum(t == "attention" for t in types)


def matmul_params(m: dict) -> int:
    """Weights that take part in a matmul per token: every layer's mixer and
    MLP projections and the LM head (tied).  The embedding lookup, the conv,
    the norms and the SSM's per-head scalars are not matmuls."""
    z = _sizes(m)
    d, ha, kv, hd = z["d"], z["ha"], z["kv"], z["hd"]
    n_m, n_a = _kinds(m)
    mlp = 3 * d * z["f"]
    mamba = d * (2 * z["d_in"] + 2 * z["N"] + z["H"]) + z["d_in"] * d
    attn = d * ha * hd + 2 * d * kv * hd + ha * hd * d
    return n_m * (mamba + mlp) + n_a * (attn + mlp) + d * z["V"]


def _attn_flops_per_token(m: dict, ctx: int) -> int:
    """Forward score and value products of one token against ``ctx``
    positions, every attention layer: 2 matmuls x 2 FLOPs x heads x head
    size x ctx."""
    z = _sizes(m)
    return 4 * _kinds(m)[1] * z["ha"] * z["hd"] * ctx


def _ssd_flops_per_token(m: dict) -> float:
    """Forward FLOPs of the chunked SSD for one token, every Mamba-2 layer, at
    the published chunk Q (``mamba_chunk_size``): the chunk's C B^T scores
    (2 Q N), their product with the inputs (2 Q P H), the chunk states and
    the read-out of the incoming state (2 x 2 H P N) and the recurrence over
    chunk states (2 H P N / Q)."""
    z = _sizes(m)
    q, n, p, h = m["mamba_chunk_size"], z["N"], z["P"], z["H"]
    per_layer = 2 * q * n + 2 * q * p * h + 4 * h * p * n + 2 * h * p * n / q
    return _kinds(m)[0] * per_layer


def _round_tokens(traffic: dict) -> int:
    fed, seq = traffic["federation"], traffic["data"]["seq_len"]
    return fed["cohort"] * fed["local_steps"] * fed["batch_size"] * seq


def train_flops(m: dict, traffic: dict) -> float:
    """Forward and backward over one round's tokens (cohort x local steps x
    batch x ``seq_len``): 6 x matmul params per token, plus 3 x the forward
    attention products at the full sequence length (the PaLM appendix B
    count) and 3 x the forward SSD.  Recomputation under ``remat`` is not
    counted."""
    seq = traffic["data"]["seq_len"]
    per_token = (6 * matmul_params(m) + 3 * _attn_flops_per_token(m, seq)
                 + 3 * _ssd_flops_per_token(m))
    return float(_round_tokens(traffic)) * per_token


def ssd_cost(m: dict, traffic: dict) -> tuple[float, float]:
    """(FLOPs, bytes) of one round's SSD scans, forward and backward: the
    chunked SSD's FLOPs at the published chunk (3 x forward), and the scan's
    inputs ``x``, ``dt``, ``B``, ``C`` and its output ``y`` moved once in
    each of the two passes, in the program's type (the configuration's
    ``torch_dtype``).  Work that any implementation of the scan does."""
    z = _sizes(m)
    tokens = _round_tokens(traffic)
    flops = 3.0 * tokens * _ssd_flops_per_token(m)
    per_token = 2 * z["H"] * z["P"] + z["H"] + 2 * z["N"]  # x and y, dt, B and C
    nbytes = 2.0 * tokens * _kinds(m)[0] * per_token * jnp.dtype(m["torch_dtype"]).itemsize
    return flops, nbytes


def serve_flops(m: dict, batch: int, prompt_len: int, new_tokens: int) -> float:
    """One batch: the prefill of ``batch`` prompts (attention over the causal
    half, the chunked SSD), then ``new_tokens`` decode steps, step ``i``
    attending over ``prompt_len + i`` cached positions and updating each
    Mamba-2 layer's state (outer product and read-out, 4 H P N)."""
    z = _sizes(m)
    prefill = batch * prompt_len * (2 * matmul_params(m) + _ssd_flops_per_token(m)) \
        + batch * _attn_flops_per_token(m, prompt_len) * prompt_len / 2
    state = 4 * _kinds(m)[0] * z["H"] * z["P"] * z["N"]
    decode = sum(batch * (2 * matmul_params(m) + _attn_flops_per_token(m, prompt_len + i)
                          + state) for i in range(new_tokens))
    return float(prefill + decode)


def check_program(m: dict, arch) -> None:
    """Raise where the program's ``ArchConfig`` runs another model than the
    configuration file states: layer pattern, widths, SSM sizes, the four
    multipliers, positions, eps or vocabulary."""
    kinds = {"mamba": "mamba2_mlp", "attention": "attn"}
    pattern = arch.block_pattern * (arch.n_layers // len(arch.block_pattern))
    want = tuple(kinds.get(t, t) for t in m["layer_types"])
    checks = [
        ("layer_types", want, pattern),
        ("num_hidden_layers", m["num_hidden_layers"], arch.n_layers),
        ("hidden_size", m["hidden_size"], arch.d_model),
        ("num_attention_heads", m["num_attention_heads"], arch.n_heads),
        ("num_key_value_heads", m["num_key_value_heads"], arch.n_kv_heads),
        ("head size", m["hidden_size"] // m["num_attention_heads"], arch.hd),
        ("shared_intermediate_size", m["shared_intermediate_size"], arch.d_ff),
        ("intermediate_size", m["intermediate_size"], arch.d_ff),
        ("vocab_size", m["vocab_size"], arch.vocab),
        ("rms_norm_eps", m["rms_norm_eps"], arch.norm_eps),
        ("hidden_act", m["hidden_act"], arch.act),
        ("tie_word_embeddings", m["tie_word_embeddings"], arch.tie_embeddings),
        ("mamba_d_state", m["mamba_d_state"], arch.ssm_state),
        ("mamba_d_head", m["mamba_d_head"], arch.ssm_head_dim),
        ("mamba_expand", m["mamba_expand"], arch.ssm_expand),
        ("mamba_n_heads", m["mamba_n_heads"],
         arch.ssm_expand * arch.d_model // arch.ssm_head_dim),
        ("mamba_n_groups", m["mamba_n_groups"], 1),
        ("mamba_d_conv", m["mamba_d_conv"], arch.conv_width),
        ("mamba_conv_bias", m["mamba_conv_bias"], arch.conv_bias),
        ("mamba_proj_bias", m["mamba_proj_bias"], False),
        ("attention_bias", m["attention_bias"], False),
        ("embedding_multiplier", m["embedding_multiplier"], arch.embed_multiplier),
        ("residual_multiplier", m["residual_multiplier"], arch.residual_multiplier),
        ("attention_multiplier", m["attention_multiplier"], arch.attn_scale),
        ("logits_scaling", m["logits_scaling"], arch.logit_divisor),
        ("position_embedding_type", m["position_embedding_type"], arch.position_embedding),
    ]
    for name, mine, theirs in checks:
        if mine != theirs:
            raise ValueError(f"configuration file {name}={mine!r} but the "
                             f"program runs {theirs!r}")


# -- the plain reference ----------------------------------------------------


def _mm(a, b, mode):
    return jnp.matmul(cast(a, mode), cast(b, mode), precision=HI)


def _einsum(spec, a, b, mode):
    return jnp.einsum(spec, cast(a, mode), cast(b, mode), precision=HI)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (1.0 + scale)


def _blocks(x, size, axis=1):
    """Split ``axis`` into (n, size) blocks and move n to the front."""
    s = x.shape[axis]
    size = math.gcd(s, size)
    x = x.reshape(x.shape[:axis] + (s // size, size) + x.shape[axis + 1:])
    return jnp.moveaxis(x, axis, 0)


def _unblock(x, axis=1):
    x = jnp.moveaxis(x, 0, axis)
    return x.reshape(x.shape[:axis] + (x.shape[axis] * x.shape[axis + 1],) + x.shape[axis + 2:])


def _recurrence(xs, delta, a, b, c, mode):
    """The SSM token by token: ``xs`` (B, S, H, P), ``delta`` (B, S, H), ``a``
    (B, S, H) (A of each position's weights), ``b``/``c`` (B, S, N) ->
    ``y`` (B, S, H, P) without the skip term.  Blocks of ``SCAN_BLOCK``
    positions run under ``jax.checkpoint``: backward keeps the state at block
    edges and recomputes inside a block."""
    bsz, _, h, p = xs.shape
    state0 = jnp.zeros((bsz, h, p, b.shape[-1]), jnp.float32)

    def token(state, inp):
        x_t, d_t, a_t, b_t, c_t = inp
        state = jnp.exp(d_t * a_t)[..., None, None] * state + _einsum(
            "bhp,bn->bhpn", d_t[..., None] * x_t, b_t, mode)
        return state, _einsum("bhpn,bn->bhp", state, c_t, mode)

    @jax.checkpoint
    def block(state, inp):
        state, y = jax.lax.scan(token, state, tuple(jnp.moveaxis(v, 1, 0) for v in inp))
        return state, jnp.moveaxis(y, 0, 1)

    inp = tuple(_blocks(v, SCAN_BLOCK) for v in (xs, delta, a, b, c))
    _, y = jax.lax.scan(block, state0, inp)
    return _unblock(y)


def _attention(q, k, v, scale, mode):
    """Causal softmax attention, queries in blocks of ``QUERY_BLOCK`` under
    ``jax.checkpoint``; q, k, v (B, S, heads, hd)."""
    s = q.shape[1]
    kpos = jnp.arange(s)

    @jax.checkpoint
    def block(_, inp):
        qb, qpos = inp
        sc = _einsum("bqhd,bkhd->bhqk", qb, k, mode) * scale
        pr = jax.nn.softmax(jnp.where(kpos[None, :] <= qpos[:, None], sc, -jnp.inf), axis=-1)
        return None, _einsum("bhqk,bkhd->bqhd", pr, v, mode)

    _, o = jax.lax.scan(block, None, (_blocks(q, QUERY_BLOCK), _blocks(kpos, QUERY_BLOCK, 0)))
    return _unblock(o)


def _hidden(p, tokens, m, mode, q=None, use_q=None):
    """Final-norm hidden states (B, S, d) of float32 parameters ``p``.

    With a second parameter set ``q`` and a (S,) mask ``use_q``, position
    ``j`` is computed with ``q`` where ``use_q[j]`` and with ``p`` elsewhere,
    through every layer, while attention reads every earlier position's keys
    and values, the conv every earlier position's inputs and the recurrence
    the state, as those positions computed them: the semantics of weights
    swapped between decode steps over a kept cache and state."""
    z = _sizes(m)
    bsz, s = tokens.shape
    eps, r = m["rms_norm_eps"], m["residual_multiplier"]
    mixed = q is not None
    q = q if mixed else p
    sel = use_q[None, :, None] if mixed else None

    def pick(a, b_):
        return jnp.where(sel, b_, a) if mixed else a

    def per_pos(fa, wa, wb):
        """``fa(w)`` of one parameter set at each position, picked."""
        return pick(fa(wa), fa(wb)) if mixed else fa(wa)

    def lin(y, wa, wb):
        return per_pos(lambda w: _mm(y, w, mode), wa, wb)

    def norm(y, sa, sb):
        return per_pos(lambda sc: _rms(y, sc, eps), sa, sb)

    def mlp(x, fp, fq):
        y = norm(x, fp["ln2"], fq["ln2"])
        f, f2 = fp["mlp"], fq["mlp"]
        return lin(lin(y, f["up"], f2["up"]) * jax.nn.silu(lin(y, f["gate"], f2["gate"])),
                   f["down"], f2["down"])

    def attention(x, bp, bq):
        ha, kv, hd = z["ha"], z["kv"], z["hd"]
        y = norm(x, bp["ln1"], bq["ln1"])
        a, a2 = bp["attn"], bq["attn"]
        qh = lin(y, a["wq"], a2["wq"]).reshape(bsz, s, ha, hd)
        k = jnp.repeat(lin(y, a["wk"], a2["wk"]).reshape(bsz, s, kv, hd), ha // kv, axis=2)
        v = jnp.repeat(lin(y, a["wv"], a2["wv"]).reshape(bsz, s, kv, hd), ha // kv, axis=2)
        o = _attention(qh, k, v, m["attention_multiplier"], mode).reshape(bsz, s, ha * hd)
        return lin(o, a["wo"], a2["wo"])

    def mamba(x, bp, bq):
        d_in, n, h, pd, w = z["d_in"], z["N"], z["H"], z["P"], z["W"]
        sp, sq = bp["ssm"], bq["ssm"]
        u = norm(x, bp["ln1"], bq["ln1"])
        proj = lin(u, sp["in_proj"], sq["in_proj"])
        zg, xbc, dt = proj[..., :d_in], proj[..., d_in:2 * d_in + 2 * n], proj[..., 2 * d_in + 2 * n:]
        xpad = jnp.pad(xbc, [(0, 0), (w - 1, 0), (0, 0)])

        def conv(s_):
            return sum(cast(xpad[:, i:i + s], mode) * cast(s_["conv_w"][i], mode)
                       for i in range(w)) + s_["conv_b"]
        xbc = jax.nn.silu(per_pos(conv, sp, sq))
        xs = xbc[..., :d_in].reshape(bsz, s, h, pd)
        bm, cm = xbc[..., d_in:d_in + n], xbc[..., d_in + n:]
        delta = per_pos(lambda s_: jax.nn.softplus(dt + s_["dt_bias"]), sp, sq)
        a = per_pos(lambda s_: jnp.broadcast_to(-jnp.exp(s_["a_log"]), dt.shape), sp, sq)
        dsk = per_pos(lambda s_: jnp.broadcast_to(s_["d_skip"], dt.shape), sp, sq)
        y = _recurrence(xs, delta, a, bm, cm, mode) + dsk[..., None] * xs
        g = y.reshape(bsz, s, d_in) * jax.nn.silu(zg)
        g = per_pos(lambda sc: _rms(g, sc, eps), sp["norm_scale"], sq["norm_scale"])
        return lin(g, sp["out_proj"], sq["out_proj"])

    def layer(x, blks):
        bp, bq = blks
        mix = attention if "attn" in bp else mamba
        x = x + r * mix(x, bp, bq)
        return x + r * mlp(x, bp, bq)

    def group(x, slots):
        for blks in zip(*slots):
            x = jax.checkpoint(layer)(x, blks)
        return x, None

    x = m["embedding_multiplier"] * pick(p["embed"][tokens], q["embed"][tokens])
    x, _ = jax.lax.scan(group, x, (p["stacks"], q["stacks"]))
    return pick(_rms(x, p["final_norm"], eps), _rms(x, q["final_norm"], eps))


def _logits(x, embed, m, mode):
    return _mm(x, embed.T, mode) / m["logits_scaling"]


def loss(p, tokens, targets, m, mode="f32"):
    """Mean next-token cross entropy over (B, S); the head and the loss in
    blocks of ``HEAD_BLOCK`` positions under ``jax.checkpoint``."""
    x = _hidden(p, tokens, m, mode)

    @jax.checkpoint
    def block(total, inp):
        xb, tb = inp
        lg = _logits(xb, p["embed"], m, mode)
        gold = jnp.take_along_axis(lg, tb[..., None], axis=-1)[..., 0]
        return total + jnp.sum(jax.nn.logsumexp(lg, axis=-1) - gold), None

    total, _ = jax.lax.scan(block, jnp.float32(0.0),
                            (_blocks(x, HEAD_BLOCK), _blocks(targets, HEAD_BLOCK)))
    return total / targets.size


@functools.partial(jax.jit, static_argnums=(3, 4))
def grad(p, tokens, targets, m_items, mode):
    """(loss, gradient) at float32 parameters ``p``, the gradient rounded to
    the configuration's ``torch_dtype``; ``m_items`` is the configuration's
    scalars as a hashable tuple of (key, value) pairs."""
    m = dict(m_items)
    value, g = jax.value_and_grad(loss)(p, tokens, targets, m, mode)
    return value, jax.tree_util.tree_map(lambda x: x.astype(m["torch_dtype"]), g)


@functools.partial(jax.jit, static_argnums=(5, 6))
def served_gaps(p, q, use_q, tokens, alt, m_items, mode):
    """The reference's logits at each position ``j`` of ``tokens`` (B, S)
    (weights swapped as ``_hidden`` says), read for the token at ``j + 1``:
    (best logit - logit of ``tokens[:, j + 1]``, best - logit of
    ``alt[:, j + 1]``, the best token), each (B, S - 1)."""
    m = dict(m_items)
    x = _hidden(p, tokens[:, :-1], m, mode, q, use_q[:-1])
    lg = jnp.where(use_q[:-1][None, :, None],
                   _logits(x, q["embed"], m, mode), _logits(x, p["embed"], m, mode))
    best = jnp.max(lg, axis=-1)

    def gap(t):
        return best - jnp.take_along_axis(lg, t[:, 1:, None], axis=-1)[..., 0]

    return gap(tokens), gap(alt), jnp.argmax(lg, axis=-1).astype(tokens.dtype)
