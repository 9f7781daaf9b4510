"""Plain llama-style decoder: forward, loss and gradient in float32.

Follows the parameter layout that the system under test is fed (the
benchmark's own weights, ``weights.make``): ``embed`` (V, d) tied as the LM
head, ``final_norm`` (d,), and one dict per layer kind stacked over layers
in ``stacks[0]`` with ``ln1``/``ln2`` (d,), ``attn`` {``wq`` (d, H hd),
``wk``/``wv`` (d, KV hd), ``wo`` (H hd, d)} and ``mlp`` {``up``/``gate``
(d, f), ``down`` (f, d)}.

The equations, and where they depart from the published SmolLM (Llama):

* RMSNorm is ``x / sqrt(mean(x^2) + eps) * (1 + scale)``: the scale is stored
  as an offset from one (zero at initialisation means unit gain).
* RoPE rotates the two halves of each head (``[x1 cos - x2 sin,
  x2 cos + x1 sin]``), the Llama/NeoX layout, theta 10000.
* Grouped-query attention: query head ``h`` reads key/value head
  ``h // (H / KV)``; causal softmax in float32 with scale ``hd^-1/2``.
* MLP: ``down(up(x) * silu(gate(x)))``.

Every product goes through ``_mm``/``_einsum``, which take the precision:
``"f32"`` is float32 at ``Precision.HIGHEST``; a lower mode rounds each
operand to that type first (the control of the correctness check).  Layers
run under ``jax.checkpoint`` in a ``lax.scan`` so a 32-layer gradient fits
beside the weights.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = ["cast", "loss", "grad", "served_gaps"]

HI = jax.lax.Precision.HIGHEST

_LOWER = {"f32": None, "bf16": jnp.bfloat16, "fp8": jnp.float8_e4m3fn}


def cast(x, mode: str):
    """Round ``x`` to the precision ``mode`` computes in, back in float32."""
    low = _LOWER[mode]
    return x if low is None else x.astype(low).astype(jnp.float32)


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
