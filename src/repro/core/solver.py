"""Budgeted water-filling solvers for independent-sampling probabilities.

This module implements the closed-form solutions of the paper:

* Lemma 2.2 (ISP): ``min_p sum_i a_i^2 / p_i`` subject to ``sum_i p_i = K``,
  ``0 < p_i <= 1`` — the optimal independent-sampling probabilities given
  scores ``a_i = lambda_i * ||g_i||``.
* Lemma 5.1 / Lemma B.8: the same program with an additional floor
  ``p_i >= p_min`` (the FTRL solution with regularizer gamma uses
  ``a_i = sqrt(pi^2_{1:t-1}(i) + gamma)``).
* Lemma 2.2 (RSP): ``p_i = K * a_i / sum_j a_j`` (probabilities for the
  random-sampling procedure; minimizes the *loose* RSP variance bound).

TPU adaptation note (DESIGN.md section 3): the paper's Appendix G maintains an
incrementally sorted list with binary-search insertion — a serial-CPU idiom.
Here the KKT system is solved *vectorized*: the stationarity condition gives
``p_i = clip(a_i / s, p_min, 1)`` for a single scalar water level ``s`` chosen
so that ``sum_i p_i = K``.  ``f(s) = sum_i clip(a_i/s, p_min, 1)`` is monotone
non-increasing in ``s``, so the level is found by breakpoint search over the
sorted scores and then *snapped* to the exact rational solution on the
identified middle segment, recovering the closed form of Lemma B.8 to machine
precision.

Two solve paths share that snap:

* **Single-device** (``_isp_solve``): evaluate f at all 2N breakpoints
  ``{a_i, a_i/p_min}`` via sorted prefix sums (O(N log N)) and bracket the
  budget crossing directly.
* **Sharded** (``shard=ShardSpec(...)``): nothing replicated scales O(N).
  Each mesh shard sorts and prefix-sums only its own (N/S,) slice; the
  crossing is bracketed by a fixed-depth threshold search in log-space
  (``lax.scan`` bisection, or on TPU the ``kernels/sharded_waterfill``
  Pallas segmented scan that scores a 128-level ladder per pass) whose
  per-shard counting statistics are merged with one ``psum`` per step.  The
  final level is snapped by recomputing the active sets from the *local
  sorted prefix sums* — the same searchsorted/prefix-difference expressions
  as the single-device path — so on one shard the result is **bitwise equal**
  to ``_isp_solve``, and across S>1 shards it differs only by the psum
  reassociation of the middle-set score sum (documented eps, ~1e-6 relative).
  Shard-count padding uses +inf scores, which sit above every finite
  threshold and therefore never enter a count or sum.

Host-path input validation (concrete arrays only): ``isp_probabilities``
raises ``ValueError`` for ``budget`` outside ``(0, N]``, ``p_min`` outside
``[0, budget/N]``, or negative / non-finite scores.  Under a trace these
checks are unreachable (values are abstract); the traced path instead clips —
scores through ``max(a, 1e-30)``, the floor through
``max(p_min, 1e-12)``, and ``budget >= N`` through full saturation — so a
compiled training step never faults, it degrades to the nearest feasible
program.  Zero scores are always legal: those clients sit at the floor.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = [
    "isp_probabilities",
    "rsp_probabilities",
    "mix_probabilities",
    "expected_cost",
    "optimal_cost",
]


def _validate_solver_inputs(scores, budget, p_min) -> None:
    """Host-path guard: raise on infeasible inputs instead of silently
    returning garbage.  No-op under tracing (abstract values can't be
    inspected — the traced path clips; see module docstring)."""
    if any(
        isinstance(x, jax.core.Tracer) for x in (scores, budget, p_min)
    ):
        return
    import numpy as np

    n = scores.shape[0]
    b = float(budget)
    pm = float(p_min)
    if not 0.0 < b <= n:
        raise ValueError(
            f"budget must satisfy 0 < budget <= N; got budget={b} with N={n}"
        )
    if pm < 0.0 or pm > b / n * (1.0 + 1e-6):
        raise ValueError(
            f"p_min must satisfy 0 <= p_min <= budget/N = {b / n:.6g}; "
            f"got p_min={pm} (the paper's regime is p_min <= K/(2N))"
        )
    s = np.asarray(scores)
    if not np.all(np.isfinite(s)):
        raise ValueError("scores must be finite (got NaN or inf)")
    if np.any(s < 0):
        raise ValueError(
            f"scores must be non-negative; min score = {float(s.min())} "
            "(zero scores are legal: those clients sit at the floor)"
        )

@functools.partial(jax.jit, static_argnames=())
def _isp_solve(a: jax.Array, budget: jax.Array, p_min: jax.Array) -> jax.Array:
    """Solve min sum a_i^2/p_i s.t. sum p = budget, p_min <= p <= 1.

    Exact breakpoint search: the KKT solution is p_i = clip(a_i/s, p_min, 1)
    for a scalar water level s.  f(s) = sum_i clip(a_i/s, p_min, 1) is
    monotone non-increasing and piecewise-hyperbolic with breakpoints at
    s = a_i (cap boundary) and s = a_i / p_min (floor boundary).  We evaluate
    f at all 2N breakpoints via sorted prefix sums (O(N log N)), locate the
    segment bracketing the budget, and solve the segment's closed form
    s* = c / z with c = sum of middle scores, z = budget - |U| - |L| p_min —
    exactly Lemma B.8.

    Requires a_i > 0 (callers add the gamma regularizer), 0 < p_min <= budget/N.
    """
    a = jnp.asarray(a)
    n = a.shape[0]

    a_sorted = jnp.sort(a)
    prefix = jnp.concatenate([jnp.zeros((1,), a.dtype), jnp.cumsum(a_sorted)])

    def f_and_sets(s):
        # |L| = #{a_i <= s*p_min}; |U| = #{a_i >= s}; middle sum via prefix.
        n_lower = jnp.searchsorted(a_sorted, s * p_min, side="right")
        n_not_upper = jnp.searchsorted(a_sorted, s, side="left")
        n_upper = n - n_not_upper
        c = prefix[n_not_upper] - prefix[n_lower]
        f = n_upper + n_lower * p_min + c / s
        return f, n_lower, n_upper, c

    # Candidate breakpoints (strictly positive).
    bps = jnp.sort(jnp.concatenate([a_sorted, a_sorted / p_min]))
    f_at_bps = jax.vmap(lambda s: f_and_sets(s)[0])(bps)
    # f_at_bps is non-increasing along bps.  Find the last breakpoint with
    # f >= budget: the solution lies in [bps[j], bps[j+1]].
    ge = f_at_bps >= budget
    j = jnp.maximum(jnp.sum(ge) - 1, 0)
    lo = bps[j]
    hi = bps[jnp.minimum(j + 1, 2 * n - 1)]
    s_probe = 0.5 * (lo + hi)
    # Within the open segment the active sets are fixed; recover them at the
    # midpoint and solve the closed form.
    _, n_lower, n_upper, c = f_and_sets(s_probe)
    z = budget - n_upper - n_lower * p_min
    s_star = jnp.where(z > 0, c / jnp.maximum(z, 1e-30), lo)
    # Degenerate: budget >= N -> everything saturates at 1.
    p = jnp.clip(a / jnp.maximum(s_star, 1e-30), p_min, 1.0)
    p = jnp.where(budget >= n, jnp.ones_like(p), p)
    return p


def _isp_solve_local(
    a_local: jax.Array,
    budget: jax.Array,
    p_min: jax.Array,
    *,
    n_global: int,
    axis_name: str | None = None,
    bisect_depth: int = 64,
    use_kernel: bool = False,
    kernel_rounds: int = 5,
    interpret: bool = True,
) -> jax.Array:
    """Shard-local body of the sharded water-filling solve.

    Runs under ``shard_map`` when ``axis_name`` is set (one psum/pmin/pmax
    per search step merges the per-shard statistics); with ``axis_name=None``
    it degenerates to a single-shard O(N) solve.  ``a_local`` may carry +inf
    padding (shard-count remainder): infs sort last, sit above every finite
    threshold, and clip to p=1 entries the caller slices off.

    The budget crossing of f(s) = sum clip(a_i/s, p_min, 1) is bracketed in
    log-space — ``bisect_depth`` scan steps of geometric bisection, or with
    ``use_kernel`` a ``kernel_rounds``-deep refinement that scores a
    128-level geometric ladder per pass with the Pallas segmented-scan
    kernel.  The bracket is then snapped to the exact Lemma B.8 rational
    solution via the same local sorted-prefix expressions as ``_isp_solve``,
    which is what makes the single-shard result bitwise-equal to it.
    """
    a_sorted = jnp.sort(a_local)
    prefix = jnp.concatenate(
        [jnp.zeros((1,), a_sorted.dtype), jnp.cumsum(a_sorted)]
    )

    def allsum(x):
        return jax.lax.psum(x, axis_name) if axis_name is not None else x

    finite = jnp.isfinite(a_sorted)
    a_min = jnp.min(jnp.where(finite, a_sorted, jnp.inf))
    a_max = jnp.max(jnp.where(finite, a_sorted, -jnp.inf))
    if axis_name is not None:
        a_min = jax.lax.pmin(a_min, axis_name)
        a_max = jax.lax.pmax(a_max, axis_name)

    def global_sets(s):
        # Same expressions as _isp_solve.f_and_sets, on the LOCAL sorted
        # prefix; psum merges the per-shard integer counts and middle sums.
        n_floor_l = jnp.searchsorted(a_sorted, s * p_min, side="right")
        n_below_l = jnp.searchsorted(a_sorted, s, side="left")
        c_l = prefix[n_below_l] - prefix[n_floor_l]
        return allsum(n_floor_l), n_global - allsum(n_below_l), allsum(c_l)

    # Bracket [lo0, hi0] strictly encloses every breakpoint {a_i, a_i/p_min}:
    # f(lo0) = N >= budget, f(hi0) = N*p_min <= budget.
    log_lo = jnp.log2(0.5 * a_min)
    log_hi = jnp.log2(2.0 * a_max / p_min)

    if use_kernel:
        from repro.kernels.sharded_waterfill import waterfill_level_stats

        n_levels = 128
        t = jnp.arange(n_levels, dtype=a_sorted.dtype) / (n_levels - 1)

        def ladder_round(carry, _):
            llo, lhi = carry
            logs = llo + t * (lhi - llo)
            levels = jnp.exp2(logs)
            n_below, n_floor, mid = waterfill_level_stats(
                a_sorted, levels, levels * p_min, interpret=interpret
            )
            f = (
                (n_global - allsum(n_below))
                + allsum(n_floor) * p_min
                + allsum(mid) / levels
            )
            j = jnp.maximum(jnp.sum(f >= budget) - 1, 0)
            return (logs[j], logs[jnp.minimum(j + 1, n_levels - 1)]), None

        (log_lo, log_hi), _ = jax.lax.scan(
            ladder_round, (log_lo, log_hi), None, length=kernel_rounds
        )
    else:

        def bisect(carry, _):
            llo, lhi = carry
            lmid = 0.5 * (llo + lhi)
            n_floor, n_upper, c = global_sets(jnp.exp2(lmid))
            ge = n_upper + n_floor * p_min + c / jnp.exp2(lmid) >= budget
            return (
                jnp.where(ge, lmid, llo),
                jnp.where(ge, lhi, lmid),
            ), None

        (log_lo, log_hi), _ = jax.lax.scan(
            bisect, (log_lo, log_hi), None, length=bisect_depth
        )

    # Snap: inside the bracketed open segment the active sets are fixed;
    # recover them at the (log-)midpoint and solve the Lemma B.8 closed form.
    s_probe = jnp.exp2(0.5 * (log_lo + log_hi))
    n_floor, n_upper, c = global_sets(s_probe)
    z = budget - n_upper - n_floor * p_min
    s_star = jnp.where(z > 0, c / jnp.maximum(z, 1e-30), jnp.exp2(log_lo))
    p = jnp.clip(a_local / jnp.maximum(s_star, 1e-30), p_min, 1.0)
    return jnp.where(budget >= n_global, jnp.ones_like(p), p)


@functools.partial(
    jax.jit, static_argnames=("shard", "use_kernel", "interpret")
)
def _isp_solve_sharded(
    a: jax.Array,
    budget: jax.Array,
    p_min: jax.Array,
    shard,
    *,
    use_kernel: bool = False,
    interpret: bool = True,
) -> jax.Array:
    """Solve over a (N,) score vector split across ``shard.axis`` of the
    ``shard`` (a launch.mesh.ShardSpec) mesh.  See _isp_solve_local."""
    from jax.sharding import PartitionSpec

    n = a.shape[0]
    pad = (-n) % shard.num_shards
    a_pad = (
        jnp.concatenate([a, jnp.full((pad,), jnp.inf, a.dtype)]) if pad else a
    )
    spec = PartitionSpec(shard.axis)
    fn = jax.shard_map(
        functools.partial(
            _isp_solve_local,
            n_global=n,
            axis_name=shard.axis,
            use_kernel=use_kernel,
            interpret=interpret,
        ),
        mesh=shard.mesh(),
        in_specs=(spec, PartitionSpec(), PartitionSpec()),
        out_specs=spec,
        check_vma=False,
    )
    p = fn(a_pad, budget, p_min)
    return p[:n] if pad else p


def isp_probabilities(
    scores: jax.Array,
    budget: float | jax.Array,
    p_min: float | jax.Array = 0.0,
    *,
    shard=None,
    use_kernel: bool | None = None,
) -> jax.Array:
    """Optimal independent-sampling probabilities (Lemma 2.2 / Lemma 5.1).

    Args:
      scores: non-negative per-client scores ``a_i`` (e.g. ``lambda_i*||g_i||``
        for Lemma 2.2, ``sqrt(pi^2_{1:t-1}(i) + gamma)`` for the FTRL solution).
      budget: expected cohort size ``K`` with ``0 < K <= N``.
      p_min: probability floor (0 recovers Lemma 2.2; the paper requires
        ``p_min <= K/(2N)`` in the analysis).
      shard: optional ``launch.mesh.ShardSpec`` — solve with the (N,) axis
        split over that mesh axis (nothing replicated scales O(N)).  Bitwise
        equal to the unsharded solve on one shard; documented-eps on more
        (see module docstring).
      use_kernel: route the sharded threshold search through the Pallas
        ``sharded_waterfill`` kernel.  Default (None): on for TPU backends,
        off elsewhere (interpret-mode Pallas unrolls the chunk grid at trace
        time, which is the wrong trade on CPU).

    Returns:
      p with ``p_min <= p_i <= 1`` and ``sum(p) == K`` (to float tolerance).

    Raises:
      ValueError: on the host path (concrete inputs) for budget outside
        (0, N], p_min > budget/N, or negative / non-finite scores.  The
        traced path clips instead (module docstring).
    """
    scores = jnp.asarray(scores)
    n = scores.shape[0]
    _validate_solver_inputs(scores, budget, p_min)
    budget = jnp.asarray(budget, dtype=scores.dtype)
    # A zero floor breaks the bisection bracket; use a tiny positive floor and
    # rely on snapping (clients with a_i == 0 get p = floor ~ 0, matching the
    # open-constraint solution p_i -> 0+).
    eps_floor = jnp.asarray(1e-12, scores.dtype)
    p_min_arr = jnp.maximum(jnp.asarray(p_min, dtype=scores.dtype), eps_floor)
    # Strictly positive scores for the solver; zero-score clients sit at floor.
    safe = jnp.maximum(scores, 1e-30)
    if shard is None:
        return _isp_solve(safe, budget, p_min_arr)
    on_tpu = jax.default_backend() == "tpu"
    if use_kernel is None:
        use_kernel = on_tpu
    return _isp_solve_sharded(
        safe, budget, p_min_arr, shard, use_kernel=use_kernel,
        interpret=not on_tpu,
    )


def rsp_probabilities(scores: jax.Array, budget: float | jax.Array) -> jax.Array:
    """Optimal marginals for the random sampling procedure: K * a / sum(a).

    Clipped to 1 with iterative mass redistribution so the result stays a
    valid marginal vector when K * max(a) > sum(a)  (the paper assumes the
    non-degenerate regime; production code must not produce p > 1).
    """
    scores = jnp.asarray(scores)
    budget = jnp.asarray(budget, dtype=scores.dtype)

    def body(_, p_and_free):
        # redistribute: clients at cap 1 keep it; remaining budget spread
        # proportionally over free clients.
        p, _ = p_and_free
        capped = p >= 1.0
        k_rem = budget - jnp.sum(capped)
        denom = jnp.sum(jnp.where(capped, 0.0, scores))
        p_new = jnp.where(
            capped, 1.0, k_rem * scores / jnp.maximum(denom, 1e-30)
        )
        return p_new, capped

    total = jnp.maximum(jnp.sum(scores), 1e-30)
    p0 = budget * scores / total
    # N iterations suffice in the worst case; a handful in practice.
    p, _ = jax.lax.fori_loop(
        0, 8, body, (p0, jnp.zeros_like(p0, dtype=bool))
    )
    return jnp.clip(p, 0.0, 1.0)


def mix_probabilities(p: jax.Array, theta: float | jax.Array, budget: float | jax.Array) -> jax.Array:
    """Mixing strategy, eq. (12): p~ = (1-theta) p + theta * K/N."""
    p = jnp.asarray(p)
    n = p.shape[0]
    theta = jnp.asarray(theta, p.dtype)
    budget = jnp.asarray(budget, p.dtype)
    return (1.0 - theta) * p + theta * budget / n


def expected_cost(scores: jax.Array, p: jax.Array) -> jax.Array:
    """Online cost l_t(p) = sum_i a_i^2 / p_i (Section 5.1)."""
    scores = jnp.asarray(scores)
    p = jnp.asarray(p)
    return jnp.sum(jnp.where(scores > 0, scores**2 / jnp.maximum(p, 1e-30), 0.0))


def optimal_cost(scores: jax.Array, budget: float | jax.Array) -> jax.Array:
    """min_p l_t(p) over the ISP polytope — used by regret metrics.

    Closed form when no p saturates: (sum a)^2 / K (eq. 39); in general we
    evaluate the cost at the exact solver output.
    """
    p_star = isp_probabilities(scores, budget, p_min=0.0)
    return expected_cost(scores, p_star)
