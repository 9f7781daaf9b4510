"""The llama-style decoder family: weights, FLOP counts, the program check,
and a plain forward, loss and gradient in float32.

Follows the parameter layout that the system under test is fed
(``weights``): ``embed`` (V, d) tied as the LM head, ``final_norm`` (d,),
and one dict per layer kind stacked over layers in ``stacks[0]`` with
``ln1``/``ln2`` (d,), ``attn`` {``wq`` (d, H hd), ``wk``/``wv`` (d, KV hd),
``wo`` (H hd, d)} and ``mlp`` {``up``/``gate`` (d, f), ``down`` (f, d)}.

The equations, and where they depart from the published SmolLM (Llama):

* RMSNorm is ``x / sqrt(mean(x^2) + eps) * (1 + scale)``: the scale is stored
  as an offset from one (zero at initialisation means unit gain).
* RoPE rotates the two halves of each head (``[x1 cos - x2 sin,
  x2 cos + x1 sin]``), the Llama/NeoX layout, theta 10000.
* Grouped-query attention: query head ``h`` reads key/value head
  ``h // (H / KV)``; causal softmax in float32 with scale ``hd^-1/2``.
* MLP: ``down(up(x) * silu(gate(x)))``.

Every product goes through ``_mm``/``_einsum``, which take the precision
(``precision.cast``): ``"f32"`` is float32 at ``Precision.HIGHEST``; a lower
mode rounds each operand to that type first (the control of the correctness
check).  Layers run under ``jax.checkpoint`` in a ``lax.scan`` so a 32-layer
gradient fits beside the weights.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .precision import HI, cast

__all__ = ["weights", "train_flops", "serve_flops", "check_program", "matmul_params",
           "loss", "grad", "served_gaps"]


def _items(cfg: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, str, bool))))


@functools.partial(jax.jit, static_argnums=(1,))
def _weights(key, items):
    m = dict(items)
    d, f, L, V = m["hidden_size"], m["intermediate_size"], m["num_hidden_layers"], m["vocab_size"]
    hd, h, kv = m["head_dim"], m["num_attention_heads"], m["num_key_value_heads"]
    dt = jnp.dtype(m["torch_dtype"])
    ks = iter(jax.random.split(key, 16))

    def uni(shape, fan_in):
        bound = fan_in ** -0.5
        return jax.random.uniform(next(ks), shape, jnp.float32, -bound, bound).astype(dt)

    def norm_scale(shape):
        return (0.05 * jax.random.normal(next(ks), shape)).astype(dt)

    layers = {
        "ln1": norm_scale((L, d)),
        "attn": {"wq": uni((L, d, h * hd), d), "wk": uni((L, d, kv * hd), d),
                 "wv": uni((L, d, kv * hd), d), "wo": uni((L, h * hd, d), h * hd)},
        "ln2": norm_scale((L, d)),
        "mlp": {"up": uni((L, d, f), d), "down": uni((L, f, d), f), "gate": uni((L, d, f), d)},
    }
    return {
        "embed": (0.02 * jax.random.normal(next(ks), (V, d))).astype(dt),
        "final_norm": norm_scale((d,)),
        "stacks": [layers],
    }


def weights(cfg: dict, key):
    """Projections uniform in +-fan_in^-1/2, embedding N(0, 0.02^2), norm
    scales N(0, 0.05^2), in the configuration's ``torch_dtype``."""
    return _weights(key, _items(cfg))


# -- FLOP counts, from the configuration's sizes ---------------------------


def matmul_params(m: dict) -> int:
    """Weights that take part in a matmul per token: the attention and MLP
    projections of every layer and the LM head (tied or not).  The embedding
    lookup is a gather and costs no FLOPs."""
    d, f, L, V = m["hidden_size"], m["intermediate_size"], m["num_hidden_layers"], m["vocab_size"]
    hd = m["head_dim"]
    h, kv = m["num_attention_heads"], m["num_key_value_heads"]
    per_layer = d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * f
    return L * per_layer + d * V


def _attn_flops_per_token(m: dict, ctx: int) -> int:
    """Forward score and value products of one token against ``ctx``
    positions, every layer: 2 matmuls x 2 FLOPs x heads x head size x ctx."""
    return 4 * m["num_hidden_layers"] * m["num_attention_heads"] * m["head_dim"] * ctx


def train_flops(m: dict, traffic: dict) -> float:
    """Forward and backward over one round's tokens (cohort x local steps x
    batch x ``seq_len``): 6 x matmul params per token plus 3 x the forward
    attention products at the full sequence length (the PaLM appendix B
    count).  Recomputation under ``remat`` is not counted."""
    fed, seq = traffic["federation"], traffic["data"]["seq_len"]
    tokens = fed["cohort"] * fed["local_steps"] * fed["batch_size"] * seq
    return float(tokens) * (6 * matmul_params(m) + 3 * _attn_flops_per_token(m, seq))


def _forward_flops(m: dict, batch: int, seq: int) -> float:
    """Prefill: forward over ``batch`` prompts of ``seq`` tokens, attention
    over the causal half of the (seq, seq) products."""
    tokens = batch * seq
    return float(tokens) * 2 * matmul_params(m) + batch * _attn_flops_per_token(
        m, seq) * seq / 2


def _decode_flops(m: dict, batch: int, ctx: int) -> float:
    """One decode step of ``batch`` sequences with ``ctx`` cached positions."""
    return float(batch) * (2 * matmul_params(m) + _attn_flops_per_token(m, ctx))


def serve_flops(m: dict, batch: int, prompt_len: int, new_tokens: int) -> float:
    """One batch: the prefill of ``batch`` prompts, then ``new_tokens``
    decode steps, step ``i`` over ``prompt_len + i`` cached positions."""
    return _forward_flops(m, batch, prompt_len) + sum(
        _decode_flops(m, batch, prompt_len + i) for i in range(new_tokens))


def check_program(m: dict, arch) -> None:
    """Raise where the program's ``ArchConfig`` runs other sizes than the
    configuration file states."""
    for mine, theirs in (("hidden_size", arch.d_model), ("num_hidden_layers", arch.n_layers),
                         ("num_attention_heads", arch.n_heads), ("head_dim", arch.hd),
                         ("num_key_value_heads", arch.n_kv_heads),
                         ("intermediate_size", arch.d_ff), ("vocab_size", arch.vocab),
                         ("rms_norm_eps", arch.norm_eps), ("rope_theta", arch.rope_theta)):
        if m[mine] != theirs:
            raise ValueError(f"configuration file {mine}={m[mine]} but the "
                             f"program runs {theirs}")


# -- the plain reference ----------------------------------------------------


def _mm(a, b, mode):
    return jnp.matmul(cast(a, mode), cast(b, mode), precision=HI)


def _einsum(spec, a, b, mode):
    return jnp.einsum(spec, cast(a, mode), cast(b, mode), precision=HI)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (1.0 + scale)


def _rope(x, cos, sin):
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _hidden(p, tokens, m, mode, q=None, use_q=None):
    """Final-norm hidden states (B, S, d) of float32 parameters ``p``.

    With a second parameter set ``q`` and a (S,) mask ``use_q``, position
    ``j`` is computed with ``q`` where ``use_q[j]`` and with ``p`` elsewhere,
    through every layer, while attention reads every earlier position's keys
    and values as that position computed them: the semantics of weights
    swapped between decode steps over a kept cache."""
    b, s = tokens.shape
    mixed = q is not None
    q = q if mixed else p
    sel = use_q[None, :, None] if mixed else None

    def pick(a, b_):
        return jnp.where(sel, b_, a) if mixed else a
    h_n, kv_n, hd = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    eps = m["rms_norm_eps"]
    inv = 1.0 / (m["rope_theta"] ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    causal = jnp.tril(jnp.ones((s, s), bool))
    x = pick(p["embed"][tokens], q["embed"][tokens])

    def lin(y, wa, wb):
        return pick(_mm(y, wa, mode), _mm(y, wb, mode)) if mixed else _mm(y, wa, mode)

    def layer(x, blks):
        bp, bq = blks
        y = pick(_rms(x, bp["ln1"], eps), _rms(x, bq["ln1"], eps))
        a, a2 = bp["attn"], bq["attn"]
        qh = _rope(lin(y, a["wq"], a2["wq"]).reshape(b, s, h_n, hd), cos, sin)
        k = _rope(lin(y, a["wk"], a2["wk"]).reshape(b, s, kv_n, hd), cos, sin)
        v = lin(y, a["wv"], a2["wv"]).reshape(b, s, kv_n, hd)
        k = jnp.repeat(k, h_n // kv_n, axis=2)
        v = jnp.repeat(v, h_n // kv_n, axis=2)
        sc = _einsum("bqhd,bkhd->bhqk", qh, k, mode) * hd ** -0.5
        pr = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        o = _einsum("bhqk,bkhd->bqhd", pr, v, mode).reshape(b, s, h_n * hd)
        x = x + lin(o, a["wo"], a2["wo"])
        y = pick(_rms(x, bp["ln2"], eps), _rms(x, bq["ln2"], eps))
        f, f2 = bp["mlp"], bq["mlp"]
        x = x + lin(lin(y, f["up"], f2["up"]) * jax.nn.silu(lin(y, f["gate"], f2["gate"])),
                    f["down"], f2["down"])
        return x, None

    x, _ = jax.lax.scan(jax.checkpoint(layer), x, (p["stacks"][0], q["stacks"][0]))
    return pick(_rms(x, p["final_norm"], eps), _rms(x, q["final_norm"], eps))


def loss(p, tokens, targets, m, mode="f32"):
    """Mean next-token cross entropy over (B, S)."""
    x = _hidden(p, tokens, m, mode)
    lg = _mm(x, p["embed"].T, mode)
    lz = jax.nn.logsumexp(lg, axis=-1)
    gold = jnp.take_along_axis(lg, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(lz - gold)


@functools.partial(jax.jit, static_argnums=(3, 4))
def grad(p, tokens, targets, m_items, mode):
    """(loss, gradient) at float32 parameters ``p``; ``m_items`` is the
    configuration as a hashable tuple of (key, value) pairs."""
    return jax.value_and_grad(loss)(p, tokens, targets, dict(m_items), mode)


@functools.partial(jax.jit, static_argnums=(5, 6))
def served_gaps(p, q, use_q, tokens, alt, m_items, mode):
    """The reference's logits at each position ``j`` of ``tokens`` (B, S)
    (weights swapped as ``_hidden`` says), read for the token at ``j + 1``:
    (best logit - logit of ``tokens[:, j + 1]``, best - logit of
    ``alt[:, j + 1]``, the best token), each (B, S - 1)."""
    m = dict(m_items)
    x = _hidden(p, tokens[:, :-1], m, mode, q, use_q[:-1])
    lg = jnp.where(use_q[:-1][None, :, None],
                   _mm(x, q["embed"].T, mode), _mm(x, p["embed"].T, mode))
    best = jnp.max(lg, axis=-1)

    def gap(t):
        return best - jnp.take_along_axis(lg, t[:, 1:, None], axis=-1)[..., 0]

    return gap(tokens), gap(alt), jnp.argmax(lg, axis=-1).astype(tokens.dtype)
