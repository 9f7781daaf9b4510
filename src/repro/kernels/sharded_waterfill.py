"""Per-shard water-filling threshold statistics as a Pallas segmented scan.

The sharded ISP solve (``repro.core.solver``) finds the scalar water level
``s`` with ``sum_i clip(a_i/s, p_min, 1) = K`` by a fixed-depth threshold
search: every refinement round evaluates the monotone counting function at a
whole ladder of L candidate levels, the per-shard partial statistics are
``psum``-merged across the mesh, and the bracket tightens to the pair of
adjacent levels enclosing the solution.  This kernel is the per-shard
workhorse of that search — one sequential pass over the shard's score chunks
accumulating, for all L levels at once:

  n_below[k] = #{ a_i <  levels[k] }          (searchsorted side='left')
  n_floor[k] = #{ a_i <= floors[k] }          (searchsorted side='right',
                                               floors[k] = levels[k] * p_min)
  mid_sum[k] = sum of a_i with floors[k] < a_i < levels[k]

Same block structure as ``ssd_scan.py``: a sequential chunk grid dimension
with the running (3, L, Q) per-lane accumulator carried in VMEM scratch,
initialized via ``pl.when`` on the first chunk and lane-reduced on the last.
No chunk's scores ever round-trip to HBM between grid steps.

  grid = (n_chunks,)                 chunks sequential (accumulator carry)
  scores block  (8, Q)    VMEM       8 rows of shard-local scores
  levels block  (L, 2)    VMEM       [levels | floors], resident every step
  acc        (3, L, Q) f32 scratch   per-lane partials carried across chunks

Padding contract: score entries equal to +inf are inert (they sit above any
finite level, so no count or sum includes them) — callers pad both the
shard-split remainder and the chunk remainder with +inf.  Counts are carried
as f32, exact for shards up to 2^24 scores.

Oracle: ref.waterfill_stats_reference (order-independent masked reductions).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["waterfill_level_stats"]

_LANE = 128
_SUBLANE = 8


def _kernel(s_ref, lv_ref, out_ref, acc_ref):
    ic = pl.program_id(0)

    @pl.when(ic == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Levels run down the sublanes and scores along the lanes, so each
    # comparison is a plain (L, 1) x (1, Q) broadcast; the (3, L, Q)
    # accumulator keeps per-lane partials and is lane-reduced once, at the
    # last chunk.
    levels = lv_ref[:, 0:1].astype(jnp.float32)  # (L, 1)
    floors = lv_ref[:, 1:2].astype(jnp.float32)  # (L, 1)
    for r in range(s_ref.shape[0]):
        a = s_ref[r : r + 1, :].astype(jnp.float32)  # (1, Q)
        below = a < levels  # (L, Q)
        at_floor = a <= floors
        in_mid = jnp.logical_and(~at_floor, below)
        acc_ref[0] += below.astype(jnp.float32)
        acc_ref[1] += at_floor.astype(jnp.float32)
        acc_ref[2] += jnp.where(in_mid, a, 0.0)

    @pl.when(ic == pl.num_programs(0) - 1)
    def _done():
        for k in range(3):
            out_ref[k] = jnp.sum(acc_ref[k], axis=1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def waterfill_level_stats(
    scores: jax.Array,
    levels: jax.Array,
    floors: jax.Array,
    *,
    chunk: int = 512,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """scores (M,) shard-local (+inf entries inert); levels/floors (L,).

    Returns ``(n_below, n_floor, mid_sum)``, each (L,) f32 — the shard-local
    threshold statistics defined in the module docstring, ready for a psum
    merge across the client-shard mesh axis."""
    (m,) = scores.shape
    (l,) = levels.shape
    q = max(_LANE, min(chunk, -(-m // _LANE) * _LANE))
    rows = -(-max(m, 1) // q)
    rows = -(-rows // _SUBLANE) * _SUBLANE
    s2 = jnp.full((rows * q,), jnp.inf, jnp.float32).at[:m].set(
        scores.astype(jnp.float32)
    ).reshape(rows, q)
    l_pad = -(-l // _SUBLANE) * _SUBLANE
    lv2 = jnp.stack(
        [
            jnp.ones((l_pad,), jnp.float32).at[:l].set(levels.astype(jnp.float32)),
            jnp.zeros((l_pad,), jnp.float32).at[:l].set(floors.astype(jnp.float32)),
        ],
        axis=1,
    )
    out = pl.pallas_call(
        _kernel,
        grid=(rows // _SUBLANE,),
        in_specs=[
            pl.BlockSpec((_SUBLANE, q), lambda ic: (ic, 0)),
            pl.BlockSpec((l_pad, 2), lambda ic: (0, 0)),
        ],
        out_specs=pl.BlockSpec((3, l_pad, 1), lambda ic: (0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((3, l_pad, 1), jnp.float32),
        scratch_shapes=[pltpu.VMEM((3, l_pad, q), jnp.float32)],
        interpret=interpret,
        name="waterfill_level_stats",
    )(s2, lv2)
    return out[0, :l, 0], out[1, :l, 0], out[2, :l, 0]
