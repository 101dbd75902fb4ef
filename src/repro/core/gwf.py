"""General Water-Filling (GWF) — Algorithm 1 of the paper.

Solves the *Constrained Allocation Problem* (CAP): given a concave speedup
function ``s``, a budget ``b`` and derivative-ratio constants
``c_1 ≥ c_2 ≥ … ≥ c_k > 0``, find allocations ``θ_1 ≤ … ≤ θ_k`` with

    Σ θ_i = b,
    s'(θ_j)/s'(θ_i) = c_j/c_i          whenever θ_j ≥ θ_i > 0,      (9c)
    s'(θ_j)/s'(0)  ≥ c_j/c_i          whenever θ_j > θ_i = 0.      (9d)

Theorem 6: the solution exists and is unique; it is the water level ``h``
of the Water-Filling Problem (WFP)  β(h) = Σ θ_i(h) = b.

Two solver paths:

``solve_cap_regular``
    Closed form for the paper's *regular* class (Def. 1,
    ``s'(θ) = A (w + σθ)^γ``): with auxiliary function ``g(h) = A (σh)^γ``
    every bottle is a rectangle, ``θ_i(h) = u_i (h − h_i)^+`` with width
    ``u_i = c_i^{1/γ}`` and bottom ``h_i = σ w / u_i`` (paper §4.5.1).
    β is piecewise linear and is inverted *exactly* in O(k log k): one
    sort of the 2k breakpoints (bottle starts and caps), then prefix
    sums of the slope increments ``±u_i`` and offsets ``±u_i·h_i`` give
    β at every breakpoint in a single cumulative pass — no k×2k
    ``vmap(beta)`` evaluation matrix.  Memory is linear in k.

``solve_cap_regular_reference``
    The pre-overhaul O(k²) breakpoint search (β evaluated from scratch
    at each of the 2k breakpoints under ``vmap``).  Kept as the
    differential-test oracle for the prefix-sum solver.

``solve_cap_generic``
    For arbitrary concave ``s``: bisection on the *water pressure*
    ``λ = g(h)`` (strictly decreasing in h, so β is decreasing in λ),
    with the inner derivative inverse evaluated via the speedup's own
    ``ds_inv``.  Fully vectorized; jit/vmap-compatible.  Supports a
    warm-start ``bracket`` (validated against β before use, so a stale
    hint can only widen back to the safe bracket, never corrupt the
    answer), an adaptive ``rel_tol`` early exit that cuts iterations
    once the λ-bracket is relatively tight, and ``return_bracket`` so
    callers (SmartFill's scan) can carry the bracket across solves.

``solve_cap_hetero``
    The per-job generalization (paper §7): every job carries its own
    concave ``s_i`` via job-indexed speedup leaves (``core/speedup.py``).
    The λ-bisection is unchanged — θ_i(λ) = clip(s_i'⁻¹(c_i λ), 0, b) —
    with the safe bracket taken per job: λ ∈ [min_i s_i'(b)/c_i,
    max_i s_i'(0⁺)/c_i].  For regular-family members ``ds_inv_i`` is
    closed form, so every β probe is O(M); there is no rectangle-bottle
    closed form across heterogeneous (A_i, γ_i) — the bottles live on
    incompatible auxiliary curves — hence bisection is *the* hetero
    path, with the prefix-sum O(k log k) solver kept as the homogeneous
    fast case.  (``solve_cap_generic`` computes its bracket per job too,
    which for a shared speedup reduces bit-for-bit to the old scalar
    bracket — division by max/min commutes with min/max of quotients.)

All paths accept an ``active`` mask so they can live inside fixed-shape
``lax`` loops (SmartFill pads every CAP instance to M jobs).
``solve_cap_batched`` is the N-instance front door with size-aware
dispatch onto the fused Pallas waterfill kernels on TPU (including the
per-job-parameter ``hetero_waterfill`` variant).

All functions are pure and dtype-polymorphic; run under
``jax.config.update("jax_enable_x64", True)`` for reference precision.
"""
from __future__ import annotations

import typing

import jax
import jax.numpy as jnp

from . import fmath

from .speedup import RegularSpeedup, Speedup, StackedSpeedup, is_per_job

__all__ = [
    "solve_cap",
    "solve_cap_regular",
    "solve_cap_regular_reference",
    "solve_cap_generic",
    "solve_cap_hetero",
    "cap_bracket_probe",
    "solve_cap_hetero_sorted",
    "solve_cap_batched",
    "waterfill_prepare",
    "waterfill_solve",
    "waterfill_level",
    "HeteroPrep",
    "hetero_prepare",
    "hetero_breakpoints_init",
    "hetero_breakpoints_insert",
    "hetero_solve",
    "hetero_approx",
    "cap_residual",
]

_BIG = 1e30


def _masked(x, active, fill):
    return jnp.where(active, x, fill)


def waterfill_prepare(u, h0, active):
    """O(k log k) factorization of the WFP for fixed bottles (u, h0).

    The *uncapped* fill curve β(h) = Σᵢ uᵢ·(h − h0ᵢ)⁺ is piecewise
    linear with the bottle starts h0ᵢ as its only breakpoints (the
    per-bottle cap at the budget is inert at the crossing: Σθ = b with
    θ ≥ 0 already forces every θᵢ ≤ b, so capped and uncapped curves
    agree at and below it).  One sort of the starts plus prefix sums of
    the slope increments uᵢ and offsets uᵢ·h0ᵢ gives

        β(pos_j) = pos_j·Σu − Σ(u·h0)          (cumulative to j)

    at every breakpoint.  The factorization is *budget-independent*:
    ``waterfill_solve`` then inverts β(h) = b for any b in O(k) — one
    searchsorted and a linear interpolation — which is what lets
    SmartFill's μ-minimizer price ~70 budgets per iteration against a
    single sort.  Inactive bottles must arrive with u = 0.
    """
    u = jnp.asarray(u)
    if active is None:
        active = u > 0
    # Finite sentinel just past the largest active start: a huge constant
    # would multiply fp residue in the prefix sums and corrupt β's tail,
    # breaking the sortedness the crossing search relies on.
    h0_max = jnp.max(_masked(h0, active, -jnp.inf))
    sentinel = jnp.where(jnp.isfinite(h0_max), h0_max + 1.0, 1.0)
    pos = _masked(h0, active, sentinel)
    order = jnp.argsort(pos)
    pos = pos[order]
    slope = jnp.cumsum(u[order])                  # Σ u over started bottles
    offset = jnp.cumsum((u * jnp.where(active, h0, 0.0))[order])
    vals = pos * slope - offset                   # β at each breakpoint
    return pos, slope, vals


def _invert_fill_curve(prep, b):
    """Level h with β(h) = b on a prepared curve — O(k) per budget.

    Beyond the last breakpoint β is linear with the total slope, so the
    same interpolation extrapolates exactly; on a zero-slope segment
    (degenerate all-inactive curve) the segment's left edge is returned.
    """
    pos, slope, vals = prep
    k = pos.shape[0]
    b = jnp.asarray(b, pos.dtype)
    idx = jnp.clip(jnp.searchsorted(vals, b, side="left"), 1, k) - 1
    seg_slope = slope[idx]
    pos_slope = seg_slope > 0
    h = pos[idx] + (b - vals[idx]) / jnp.where(pos_slope, seg_slope, 1.0)
    return jnp.where(pos_slope, h, pos[idx])


def waterfill_solve(prep, u, h0, b, active):
    """Invert a prepared fill curve at budget ``b`` — O(k) per budget.

    Returns (k,) allocations θᵢ = clip(uᵢ·(h* − h0ᵢ), 0, b) with
    β(h*) = b.
    """
    b = jnp.asarray(b, prep[0].dtype)
    h = _invert_fill_curve(prep, b)
    theta = jnp.clip(u * (h - h0), 0.0, b)
    return jnp.where(active & (b > 0), theta, 0.0)


def waterfill_level(u, h0, b, active=None):
    """Exact water level h with β(h) = b, in O(k log k) (one-shot)."""
    u = jnp.asarray(u)
    if active is None:
        active = u > 0
    return _invert_fill_curve(waterfill_prepare(u, h0, active),
                              jnp.asarray(b, u.dtype))


def solve_cap_regular(sp: RegularSpeedup, b, c, active=None):
    """Closed-form CAP for regular speedup functions — O(k log k).

    Args:
      sp: RegularSpeedup with ``s'(θ) = A (w + σθ)^γ``.
      b: scalar budget, ``0 ≤ b ≤ B``.
      c: (k,) derivative-ratio constants, ``c_1 ≥ … ≥ c_k > 0``.
      active: optional (k,) bool mask; inactive jobs get θ=0 and are
        excluded from the budget.

    Returns:
      (k,) allocations θ with Σθ = b (exact up to fp).
    """
    c = jnp.asarray(c)
    k = c.shape[0]
    if active is None:
        active = jnp.ones((k,), dtype=bool)
    b = jnp.asarray(b, dtype=c.dtype)
    b_safe = jnp.maximum(b, jnp.asarray(1e-300, c.dtype))

    u = sp.bottle_width(c)            # u_i = c_i^{1/γ}
    h0 = sp.bottle_bottom(c)          # h_i = σ·w/u_i
    u = _masked(u, active, 0.0)
    theta = waterfill_solve(waterfill_prepare(u, h0, active),
                            u, h0, b_safe, active)
    return jnp.where(b > 0, theta, jnp.zeros_like(theta))


def solve_cap_regular_reference(sp: RegularSpeedup, b, c, active=None):
    """Pre-overhaul O(k²) closed-form CAP (β re-evaluated per breakpoint).

    The differential-test oracle for ``solve_cap_regular``: identical
    math, but β is recomputed from scratch at each of the 2k breakpoints
    under ``vmap`` — quadratic work and memory in k.
    """
    c = jnp.asarray(c)
    k = c.shape[0]
    if active is None:
        active = jnp.ones((k,), dtype=bool)
    b = jnp.asarray(b, dtype=c.dtype)
    b_safe = jnp.maximum(b, jnp.asarray(1e-300, c.dtype))

    u = sp.bottle_width(c)            # u_i = c_i^{1/γ}
    h0 = sp.bottle_bottom(c)          # h_i = σ·w/u_i
    u = _masked(u, active, 0.0)
    starts = _masked(h0, active, _BIG)
    caps = _masked(h0 + b_safe / jnp.maximum(u, 1e-300), active, 2.0 * _BIG)

    def beta(h):
        vol = jnp.clip(u * (h - h0), 0.0, b_safe)
        return jnp.sum(_masked(vol, active, 0.0))

    bp = jnp.sort(jnp.concatenate([starts, caps]))
    vals = jax.vmap(beta)(bp)                      # non-decreasing
    idx = jnp.clip(jnp.searchsorted(vals, b_safe, side="left"), 1, 2 * k - 1)
    h_lo = bp[idx - 1]
    h_hi = bp[idx]
    v_lo = vals[idx - 1]
    in_seg = active & (h_lo >= starts - 1e-300) & (h_lo < caps)
    slope = jnp.sum(jnp.where(in_seg, u, 0.0))
    h_interp = h_lo + (b_safe - v_lo) / jnp.where(slope > 0, slope, 1.0)
    h = jnp.where(slope > 0, jnp.minimum(h_interp, h_hi), h_lo)
    theta = jnp.clip(u * (h - h0), 0.0, b_safe)
    theta = _masked(theta, active, 0.0)
    return jnp.where(b > 0, theta, jnp.zeros_like(theta))


def solve_cap_generic(sp: Speedup, b, c, active=None, iters: int = 96,
                      bracket=None, rel_tol: float | None = None,
                      return_bracket: bool = False):
    """CAP for arbitrary concave speedups — bisection on water pressure λ.

    θ_i(λ) = clip(s'⁻¹(c_i λ), 0, b); β(λ) = Σ θ_i(λ) is strictly
    decreasing, so a scalar bisection on λ finds β(λ) = b.  The safe
    bracket is [s'(b)/max c, s'(0⁺)/min c] (paper (10b)/(10c)); when
    s'(0) = ∞ the upper end uses s'(ε) with ε = b/(8k), which already
    forces β < b.

    Args:
      bracket: optional (λ_lo, λ_hi) warm-start hint (e.g. the bracket
        returned by the previous solve of a nearby instance).  Each end
        is *validated* against β before use — a hint end that no longer
        brackets λ* falls back to the safe bracket, so a stale hint can
        cost two extra β evaluations but never a wrong answer.
      rel_tol: when set, the bisection exits early once
        ``hi ≤ lo·(1 + rel_tol)`` (a ``lax.while_loop`` bounded by
        ``iters``) — this is what makes warm-started solves cheap.
        Floored at a few ULP of the working dtype so the exit still
        fires in float32 (1 + 1e-13 rounds to 1.0f there).
      return_bracket: also return the final (λ_lo, λ_hi), for carrying
        across solves.
    """
    c = jnp.asarray(c)
    k = c.shape[0]
    if active is None:
        active = jnp.ones((k,), dtype=bool)
    b = jnp.asarray(b, dtype=c.dtype)
    b_safe = jnp.maximum(b, jnp.asarray(1e-300, c.dtype))

    # Per-job safe bracket (paper (10b)/(10c), §7 form): each job may
    # carry its own s_i via job-indexed speedup leaves, so the bracket
    # ends are reduced over jobs — λ_lo = min_i s_i'(b)/c_i makes the
    # binding job fill the whole budget (β ≥ b) and λ_hi = max_i
    # s_i'(0⁺)/c_i parks every job below ε (β ≤ k·ε < b).  For a shared
    # speedup this reduces bit-for-bit to ds(b)/max c and ds(0⁺)/min c.
    shape = c.shape
    ds_b = jnp.broadcast_to(sp.ds(b_safe), shape)
    ds0 = jnp.broadcast_to(sp.ds0(), shape)
    eps = b_safe / (8.0 * k)
    ds_top = jnp.where(jnp.isfinite(ds0), ds0,
                       jnp.broadcast_to(sp.ds(eps), shape))

    lam_lo = jnp.min(_masked(ds_b / c, active, jnp.inf))     # β(lam_lo) ≥ b
    lam_hi = (jnp.max(_masked(ds_top / c, active, -jnp.inf))
              * (1.0 + 1e-9))                 # β(lam_hi) ≤ k·ε < b (or 0)
    lam_hi = jnp.maximum(lam_hi, lam_lo * (1.0 + 1e-9))

    def theta_of(lam):
        y = c * lam
        th = jnp.clip(sp.ds_inv(y), 0.0, b_safe)
        # park jobs whose marginal value at zero is already below the level
        th = jnp.where(y >= ds0, 0.0, th)
        return _masked(th, active, 0.0)

    if bracket is not None:
        w_lo = jnp.maximum(jnp.asarray(bracket[0], c.dtype), 1e-300)
        w_hi = jnp.asarray(bracket[1], c.dtype)
        # β decreasing: β(w_lo) ≥ b ⇔ λ* ≥ w_lo (valid lower end);
        # β(w_hi) ≤ b ⇔ λ* ≤ w_hi (valid upper end).
        lam_lo = jnp.where(jnp.sum(theta_of(w_lo)) >= b_safe,
                           jnp.maximum(w_lo, lam_lo), lam_lo)
        lam_hi = jnp.where(jnp.sum(theta_of(w_hi)) <= b_safe,
                           jnp.minimum(w_hi, lam_hi), lam_hi)
        lam_hi = jnp.maximum(lam_hi, lam_lo * (1.0 + 1e-12))

    def shrink(carry):
        lo, hi = carry
        # bisect in log-space for relative precision across wide λ ranges
        mid = fmath.exp(0.5 * (fmath.log(lo) + fmath.log(hi)))
        beta = jnp.sum(theta_of(mid))
        # β decreasing in λ: β > b ⇒ λ* right of mid
        lo = jnp.where(beta > b_safe, mid, lo)
        hi = jnp.where(beta > b_safe, hi, mid)
        return lo, hi

    if rel_tol is None:
        lo, hi = jax.lax.fori_loop(
            0, iters, lambda _, carry: shrink(carry), (lam_lo, lam_hi))
    else:
        rel = jnp.maximum(jnp.asarray(rel_tol, c.dtype),
                          16.0 * jnp.finfo(c.dtype).eps)

        def cond(state):
            i, lo, hi = state
            return (i < iters) & (hi > lo * (1.0 + rel))

        def body(state):
            i, lo, hi = state
            lo, hi = shrink((lo, hi))
            return i + 1, lo, hi

        _, lo, hi = jax.lax.while_loop(cond, body, (0, lam_lo, lam_hi))

    lam = fmath.exp(0.5 * (fmath.log(lo) + fmath.log(hi)))
    theta = theta_of(lam)
    # exact budget: rescale the fp residual onto the positive allocations
    tot = jnp.sum(theta)
    theta = jnp.where(tot > 0, theta * (b_safe / tot), theta)
    theta = jnp.minimum(theta, b_safe)
    theta = jnp.where(b > 0, theta, jnp.zeros_like(theta))
    if return_bracket:
        return theta, (lo, hi)
    return theta


def cap_bracket_probe(sp: Speedup, b, c, bracket, active=None):
    """β-probe a carried λ-bracket against the *live* CAP instance.

    This is the validation ``solve_cap_generic`` applies internally to
    a warm ``bracket``, exposed for callers that must *decide* on the
    hint's health rather than silently absorb it — the streaming
    controller replans warm while the carried bracket still straddles
    λ* and falls back to a cold solve the moment it doesn't (budget
    collapse, bulk arrival).

    Returns ``(lo_ok, hi_ok)`` booleans: β decreasing in λ means the
    lower end is valid iff β(lo) ≥ b and the upper iff β(hi) ≤ b.  Two
    O(M) β evaluations; jit/vmap-safe.
    """
    c = jnp.asarray(c)
    k = c.shape[0]
    if active is None:
        active = jnp.ones((k,), dtype=bool)
    b_safe = jnp.maximum(jnp.asarray(b, c.dtype),
                         jnp.asarray(1e-300, c.dtype))
    ds0 = jnp.broadcast_to(sp.ds0(), c.shape)

    def beta(lam):
        y = c * lam
        th = jnp.clip(sp.ds_inv(y), 0.0, b_safe)
        th = jnp.where(y >= ds0, 0.0, th)
        return jnp.sum(_masked(th, active, 0.0))

    lo = jnp.maximum(jnp.asarray(bracket[0], c.dtype), 1e-300)
    hi = jnp.asarray(bracket[1], c.dtype)
    return beta(lo) >= b_safe, beta(hi) <= b_safe


def solve_cap_hetero(sp: Speedup, b, c, active=None, iters: int = 96,
                     **kwargs):
    """CAP with per-job speedup functions (paper §7) — O(M) per probe.

    ``sp`` carries job-indexed leaves (an ``(M,)``-leaved
    ``RegularSpeedup`` or a ``StackedSpeedup``); the solve is a
    λ-bisection over the per-job closed-form ``ds_inv_i(c_i λ)``.  This
    is ``solve_cap_generic`` — which is per-job aware throughout — under
    its §7 name; it exists so call sites can say what they mean and so
    the warm-bracket kwargs are documented for the hetero path too.
    """
    return solve_cap_generic(sp, b, c, active, iters=iters, **kwargs)


class HeteroPrep(typing.NamedTuple):
    """Budget-independent factorization of the per-job CAP (paper §7).

    For regular-family jobs the uncapped per-job allocation curve is
    closed form in the water pressure λ:

        θ̃_i(λ) = max(P_i λ^{E_i} − Q_i, 0),
        P_i = σ_i (c_i/A_i)^{E_i},  E_i = 1/γ_i,  Q_i = σ_i w_i,

    and each job switches off exactly at its *activation breakpoint*
    λ_act_i = s_i'(0)/c_i (∞ for the pure-power w = 0 family — the job
    never parks).  ``pos`` holds the breakpoints sorted descending and
    ``vals`` the uncapped fill curve β̃(λ) = Σ θ̃_i(λ) evaluated at
    them (ascending, since β̃ is decreasing): one ``searchsorted``
    then brackets λ* inside a single segment, replacing the blind
    λ-bisection's full-range probes.  The per-budget cap at b is inert
    at the crossing (Σθ̃ = b with θ̃ ≥ 0 forces every θ̃_i ≤ b —
    the same argument as ``waterfill_prepare``), so β̃ and the capped
    β share the root.

    ``P``/``E``/``Q``/``act`` are in job order; ``A``/``w``/``gamma``/
    ``sigma``/``c`` are kept for the budget-dependent safe bracket.
    """

    P: jnp.ndarray
    E: jnp.ndarray
    Q: jnp.ndarray
    A: jnp.ndarray
    w: jnp.ndarray
    gamma: jnp.ndarray
    sigma: jnp.ndarray
    c: jnp.ndarray
    act: jnp.ndarray
    pos: jnp.ndarray
    vals: jnp.ndarray


def _hetero_leaves(sp: Speedup, c):
    """Broadcast the regular-family leaves (A, w, γ, σ) to (M,)."""
    if not isinstance(sp, (RegularSpeedup, StackedSpeedup)):
        raise ValueError(
            "sorted-bracket hetero CAP needs a (possibly per-job) "
            "regular-family speedup (RegularSpeedup or StackedSpeedup)")
    shape = c.shape
    dt = c.dtype
    A = jnp.broadcast_to(jnp.asarray(sp.A, dt), shape)
    w = jnp.broadcast_to(jnp.asarray(sp.w, dt), shape)
    gamma = jnp.broadcast_to(jnp.asarray(sp.gamma, dt), shape)
    sigma = jnp.broadcast_to(jnp.asarray(sp.sigma, dt), shape)
    return A, w, gamma, sigma


def _hetero_coeffs(A, w, gamma, sigma, c, act):
    """(P, E, Q) of the uncapped curve plus λ_act per job (0 inactive)."""
    c_safe = jnp.where(act, c, 1.0)
    E = 1.0 / gamma
    P = sigma * fmath.power(c_safe / A, E)
    Q = sigma * w
    ds0 = jnp.where(w > 0, A * fmath.power(jnp.maximum(w, 1e-300), gamma),
                    jnp.inf)
    lam_act = jnp.where(act, ds0 / c_safe, 0.0)
    return P, E, Q, lam_act


def _beta_tilde(P, E, Q, act, lam):
    """Uncapped fill curve β̃(λ) = Σ_act max(P λ^E − Q, 0)."""
    term = P * fmath.power(lam, E) - Q
    return jnp.sum(jnp.where(act, jnp.maximum(term, 0.0), 0.0))


def hetero_breakpoints_init(M: int, dtype=jnp.float64):
    """Empty per-job breakpoint store: λ = 0, β̃-value = +∞ sentinels.

    Slot i belongs to job i (unsorted); ``hetero_breakpoints_insert``
    activates one job at a time in O(M), which is what lets SmartFill's
    scan maintain the exact sorted-breakpoint curve across iterations
    instead of re-evaluating the O(M²) breakpoint matrix per iteration
    (the c-constants of already-active jobs never change — only one new
    c_k arrives per iteration).
    """
    dtype = jnp.zeros((), dtype).dtype
    return (jnp.zeros((M,), dtype), jnp.full((M,), jnp.inf, dtype))


def hetero_breakpoints_insert(sp: Speedup, c, k, bp_lam, bp_val, live=True):
    """Activate job ``k`` (with its ratio constant ``c[k]``) in O(M).

    Adds job k's uncapped term max(P_k λ^{E_k} − Q_k, 0) to the stored
    β̃ value of every existing breakpoint (one shared exponent — a
    single vectorized power) and evaluates the *current* curve once at
    job k's own breakpoint λ_act_k (mixed exponents, one O(M) pass).
    ``live=False`` is a masked no-op so the call can sit inside a
    ``lax.scan`` step that also serves padded iterations.
    """
    c = jnp.asarray(c)
    M = c.shape[0]
    idx = jnp.arange(M)
    prev = idx < k                      # jobs already in the store
    A, w, gamma, sigma = _hetero_leaves(sp, c)
    P, E, Q, lam_act = _hetero_coeffs(A, w, gamma, sigma, c, prev)

    c_k = jnp.maximum(c[k], 1e-300)
    E_k, A_k, w_k, s_k = E[k], A[k], w[k], sigma[k]
    P_k = s_k * fmath.power(c_k / A_k, E_k)
    Q_k = s_k * w_k
    ds0_k = jnp.where(w_k > 0, A_k * fmath.power(jnp.maximum(w_k, 1e-300), gamma[k]),
                      jnp.inf)
    lam_k = ds0_k / c_k

    g = jnp.maximum(P_k * fmath.power(bp_lam, E_k) - Q_k, 0.0)
    val_k = _beta_tilde(P, E, Q, prev, lam_k)
    bp_lam2 = jnp.where(idx == k, lam_k, bp_lam)
    bp_val2 = jnp.where(idx == k, val_k, bp_val + g)
    live = jnp.asarray(live, bool)
    return (jnp.where(live, bp_lam2, bp_lam),
            jnp.where(live, bp_val2, bp_val))


def hetero_prepare(sp: Speedup, c, active=None, breakpoints=None):
    """Factorize the per-job CAP: sort the activation breakpoints once.

    Mirrors ``waterfill_prepare``: everything budget-independent — the
    term coefficients (P, E, Q), the breakpoints λ_act_i and the
    uncapped curve values β̃(λ_act_j) — is computed here, so
    ``hetero_solve`` prices any number of budgets against ONE sort.
    Without ``breakpoints`` the curve values are evaluated directly
    (an O(M²) vmapped pass — fine one-shot); SmartFill's scan passes
    the incrementally maintained ``(bp_lam, bp_val)`` store instead,
    keeping the per-iteration cost O(M log M).
    """
    c = jnp.asarray(c)
    M = c.shape[0]
    if active is None:
        active = jnp.ones((M,), dtype=bool)
    A, w, gamma, sigma = _hetero_leaves(sp, c)
    P, E, Q, lam_act = _hetero_coeffs(A, w, gamma, sigma, c, active)
    if breakpoints is None:
        bp_lam = lam_act
        bp_val = jnp.where(
            active,
            jax.vmap(lambda lam: _beta_tilde(P, E, Q, active, lam))(lam_act),
            jnp.inf)
    else:
        bp_lam, bp_val = breakpoints
    order = jnp.argsort(-bp_lam)
    return HeteroPrep(P=P, E=E, Q=Q, A=A, w=w, gamma=gamma, sigma=sigma,
                      c=c, act=active, pos=bp_lam[order],
                      vals=bp_val[order])


def hetero_solve(prep: HeteroPrep, b, iters: int = 48, lam_hint=None,
                 return_lam: bool = False, rtol: float = 1e-13,
                 unroll: int = 0):
    """Invert the prepared per-job fill curve at budget ``b``.

    ``searchsorted`` on the prepared curve values brackets λ* inside one
    breakpoint segment; the bracket is intersected with the safe bounds
    of ``solve_cap_generic`` (λ_lo = min_i s_i'(b)/c_i, λ_hi =
    max_i s_i'(0⁺)/c_i) and both ends are *validated* with a β̃
    evaluation — fp noise in the sorted values can cost two extra curve
    evaluations but never a wrong segment.  A safeguarded Newton
    iteration in t = log λ (the analytic dβ̃/dt = Σ P_i E_i λ^{E_i} is
    one fused pass) then converges quadratically from a secant estimate
    — or from ``lam_hint``, the warm start carried across SmartFill
    iterations and order-exchange candidates — exiting early once the
    step is below a few ULP.  A step that leaves the bracket falls back
    to *false position* through the carried bracket-end values (not
    midpoint bisection: at b → 0 the root sits within an ulp of the
    activation kink where every job is parked and dβ̃/dt = 0, and false
    position lands beside the kink in one step where bisection would
    need ~50 halvings — the b ≈ 0 probes of SmartFill's μ-grid hit this
    every iteration).  Typical cost: 4–8 O(M) passes against the blind
    bisection's ~50.

    ``lam_hint``: optional λ* guess; values ≤ 0 / outside the validated
    bracket are ignored (0 is the "no hint" sentinel).

    ``rtol``: relative budget-residual exit |β̃(λ) − b| ≤ rtol·b.  The
    default resolves θ to fp noise; SmartFill's coarse μ-localization
    grid passes a loose 1e-6 (cell placement only) to halve the Newton
    iterations of those throwaway probes.

    ``unroll`` > 0 replaces the while_loop with that many *unrolled*
    safeguarded steps (no early exit, no loop-carried launch overhead).
    Meant for warm-hinted descent probes, where 4 steps reach fp
    precision from a neighbouring λ* and the fixed cost of a while_loop
    launch would dominate the arithmetic; cold calls should keep the
    adaptive loop.
    """
    P, E, Q, act = prep.P, prep.E, prep.Q, prep.act
    c = prep.c
    dt = c.dtype
    M = c.shape[0]
    b = jnp.asarray(b, dt)
    b_safe = jnp.maximum(b, jnp.asarray(1e-300, dt))

    # safe bracket — identical bounds to solve_cap_generic
    c_safe = jnp.where(act, c, 1.0)
    ds_b = prep.A * fmath.power(
        jnp.maximum(prep.w + prep.sigma * b_safe, 1e-300), prep.gamma)
    eps = b_safe / (8.0 * M)
    ds0 = jnp.where(prep.w > 0,
                    prep.A * fmath.power(jnp.maximum(prep.w, 1e-300),
                                                     prep.gamma),
                    jnp.inf)
    ds_top = jnp.where(prep.w > 0, ds0,
                       prep.A * fmath.power(eps, prep.gamma))
    lam_lo_s = jnp.min(jnp.where(act, ds_b / c_safe, jnp.inf))
    lam_hi_s = jnp.max(jnp.where(act, ds_top / c_safe, -jnp.inf)) * (1 + 1e-9)
    good = (jnp.isfinite(lam_lo_s) & (lam_lo_s > 0) & jnp.isfinite(lam_hi_s)
            & (lam_hi_s > 0))
    lam_lo_s = jnp.where(good, lam_lo_s, 1.0)
    lam_hi_s = jnp.where(good, lam_hi_s, 2.0)
    lam_hi_s = jnp.maximum(lam_hi_s, lam_lo_s * (1 + 1e-9))

    # segment bracket: vals[idx−1] ≤ b ≤ vals[idx] ⇒ λ* ∈ [pos[idx],
    # pos[idx−1]] (pos descending, β̃ decreasing)
    idx = jnp.clip(jnp.searchsorted(prep.vals, b_safe, side="left"), 1, M - 1)
    lo = jnp.maximum(prep.pos[idx], lam_lo_s)
    hi = jnp.minimum(prep.pos[idx - 1], lam_hi_s)
    bad = ~(hi > lo)
    lo = jnp.where(bad, lam_lo_s, lo)
    hi = jnp.where(bad, lam_hi_s, hi)
    if unroll > 0:
        # lean probe: trust the stored segment-endpoint values for the
        # false-position residuals instead of re-evaluating β̃ at the
        # (possibly clamped) ends — four full curve passes saved.  When
        # the segment was degenerate (``bad``) the residuals are marked
        # non-finite, which disables the false-position branch and falls
        # back to the log-midpoint; the Newton steps never read them.
        okf = (~bad & jnp.isfinite(prep.vals[idx])
               & jnp.isfinite(prep.vals[idx - 1]))
        flo = jnp.where(okf, prep.vals[idx] - b_safe, jnp.inf)
        fhi = jnp.where(okf, prep.vals[idx - 1] - b_safe, -jnp.inf)
        hi = jnp.maximum(hi, lo * (1 + 1e-12))
    else:
        beta_lo_c = _beta_tilde(P, E, Q, act, lo)
        beta_hi_c = _beta_tilde(P, E, Q, act, hi)
        lo = jnp.where(beta_lo_c >= b_safe, lo, lam_lo_s)
        hi = jnp.where(beta_hi_c <= b_safe, hi, lam_hi_s)
        hi = jnp.maximum(hi, lo * (1 + 1e-12))
        # bracket-end residuals at the *final* ends — the false-position
        # fallback inside the loop steers by them, so they must belong to
        # the ends actually used (the candidate evaluations above are
        # stale whenever validation replaced an end with the safe bound)
        flo = _beta_tilde(P, E, Q, act, lo) - b_safe
        fhi = _beta_tilde(P, E, Q, act, hi) - b_safe

    tlo = fmath.log(lo)
    thi = fmath.log(hi)
    # init: secant in (t, log β̃) — on any fixed active set β̃ is a sum
    # of pure powers of λ, so log β̃ is near-linear in t = log λ and the
    # log-secant is exact for a one-family segment; fall back to the
    # plain secant (then the log-midpoint) when an end has β̃ = 0
    blo_v = flo + b_safe
    bhi_v = fhi + b_safe
    lg_b = fmath.log(b_safe)
    den_l = fmath.log(jnp.maximum(blo_v, 1e-300)) - fmath.log(
        jnp.maximum(bhi_v, 1e-300))
    frac_l = (fmath.log(jnp.maximum(blo_v, 1e-300)) - lg_b) / jnp.where(
        den_l > 0, den_l, 1.0)
    den0 = flo - fhi
    frac = jnp.where((bhi_v > 0) & (den_l > 0), frac_l,
                     jnp.where(den0 > 0,
                               flo / jnp.where(den0 > 0, den0, 1.0), 0.5))
    t_sec = tlo + frac * (thi - tlo)
    t0 = jnp.where(jnp.isfinite(t_sec),
                   jnp.clip(t_sec, tlo, thi), 0.5 * (tlo + thi))
    if lam_hint is not None:
        lam_hint = jnp.asarray(lam_hint, dt)
        use = jnp.isfinite(lam_hint) & (lam_hint > lo) & (lam_hint < hi)
        t0 = jnp.where(use, fmath.log(jnp.maximum(lam_hint, 1e-300)), t0)

    tol = 4.0 * jnp.asarray(jnp.finfo(dt).eps, dt)
    # residual exit: |β̃(λ) − b| ≤ rtol·b means the budget is met to
    # rounding (the final exact rescale absorbs the residue).  This must
    # gate the *step*, not just the loop: a converged iterate sits within
    # an ulp of a bracket end, where the strict in-bracket tests reject
    # every proposal and the midpoint fallback would fling the iterate
    # back to the middle of the stale bracket (observed: 4 Newton steps
    # to the root, then ~45 re-bisection steps).
    rtol = jnp.asarray(rtol, dt) * b_safe

    def cond(st):
        return (st[0] < iters) & (st[7] > tol)

    def body(st):
        i, t, tlo, thi, flo, fhi, side, _ = st
        u = P * fmath.exp(E * t)
        th = u - Q
        on = act & (th > 0)
        beta = jnp.sum(jnp.where(on, th, 0.0))
        phi = beta - b_safe
        dphi = jnp.sum(jnp.where(on, u * E, 0.0))     # dβ̃/dt < 0
        done = jnp.abs(phi) <= rtol
        up = phi > 0                                   # λ* above t
        tlo2 = jnp.where(up, t, tlo)
        flo2 = jnp.where(up, phi, flo)
        thi2 = jnp.where(up, thi, t)
        fhi2 = jnp.where(up, fhi, phi)
        # Illinois anti-stagnation: when the same end moves twice
        # running, halve the stale opposite end's residual so the false
        # position stops hugging it (β̃ spans orders of magnitude across
        # a wide segment, which otherwise pins the secant to one end)
        fhi2 = jnp.where(up & (side < 0), 0.5 * fhi2, fhi2)
        flo2 = jnp.where((~up) & (side > 0), 0.5 * flo2, flo2)
        side2 = jnp.where(up, -1, 1)
        # Newton on log β̃(t): β̃ is a sum of pure powers of λ on the
        # current active set, so log β̃ is near-linear in t and this
        # step is exact for a one-family segment — plain Newton on β̃
        # stalls in the flat tail where |φ/φ'| overshoots the bracket
        tn = t - fmath.log(jnp.maximum(beta, 1e-300) / b_safe) * beta / dphi
        den = flo2 - fhi2
        tf = tlo2 + (flo2 / jnp.where(den > 0, den, 1.0)) * (thi2 - tlo2)
        use_n = (beta > 0) & jnp.isfinite(tn) & (tn > tlo2) & (tn < thi2)
        use_f = (den > 0) & jnp.isfinite(tf) & (tf > tlo2) & (tf < thi2)
        t2 = jnp.where(use_n, tn,
                       jnp.where(use_f, tf, 0.5 * (tlo2 + thi2)))
        t2 = jnp.where(done, t, t2)
        step = jnp.where(done, jnp.zeros((), dt), jnp.abs(t2 - t))
        return i + 1, t2, tlo2, thi2, flo2, fhi2, side2, step

    # a non-positive budget has the trivial answer θ = 0 (applied after
    # the loop); start pre-converged instead of bisecting |φ| = b down
    # to the width tolerance
    st0 = (0, t0, tlo, thi, flo, fhi, 0,
           jnp.where(b > 0, jnp.asarray(jnp.inf, dt),
                     jnp.asarray(0.0, dt)))
    if unroll > 0:
        st = st0
        for _ in range(unroll):
            st = body(st)
        t = st[1]
    else:
        _, t, _, _, _, _, _, _ = jax.lax.while_loop(cond, body, st0)

    lam = fmath.exp(t)
    theta = jnp.clip(jnp.where(act, P * fmath.exp(E * t) - Q, 0.0),
                     0.0, b_safe)
    tot = jnp.sum(theta)
    theta = jnp.where(tot > 0, theta * (b_safe / tot), theta)
    theta = jnp.minimum(theta, b_safe)
    theta = jnp.where(b > 0, theta, jnp.zeros_like(theta))
    if return_lam:
        return theta, lam
    return theta


def hetero_approx(prep: HeteroPrep, b):
    """One fused pass of the prepared fill curve — no Newton iteration.

    ``searchsorted`` picks the breakpoint segment and a log-secant
    through the *stored* segment-endpoint values places λ̂ — exact when
    the segment's active set is a single regular family, a few percent
    otherwise.  The clipped allocation at λ̂ is rescaled to meet the
    budget exactly, so the result is always feasible (Σθ̂ = b).

    ``b`` may be a scalar or a (G,) vector of budgets — the vector form
    prices a whole localization grid in two fused (G, M) passes, with
    the safe λ bounds computed once at the largest budget (valid, if
    slightly wide, for every smaller one: every s_i' is monotone in its
    argument, so shrinking b can only move the true bounds inward).

    This is the localization probe of SmartFill's μ* minimizer: the
    coarse grid only needs to place the bracketing cell, and pricing a
    grid budget here costs one O(M) pass against the full solve's ~5
    validated Newton passes.  Never use it where the CAP itself is the
    answer — the parabolic descent and the final ``hetero_solve`` run
    at full precision.
    """
    P, E, Q, act = prep.P, prep.E, prep.Q, prep.act
    c = prep.c
    dt = c.dtype
    M = c.shape[0]
    b = jnp.asarray(b, dt)
    scalar = b.ndim == 0
    bv = jnp.atleast_1d(b)
    b_safe = jnp.maximum(bv, jnp.asarray(1e-300, dt))          # (G,)

    # safe λ bounds (same construction as hetero_solve), shared across
    # the batch by monotonicity: every s_i' is monotone in its argument,
    # so the low bound evaluated at the *largest* budget and the high
    # bound at the *smallest* enclose every lane's λ*(b) — two O(M)
    # passes for the whole batch instead of per-lane (G, M) pow passes.
    # (A single shared budget would not do: λ*(b) → ∞ as b → 0 for
    # power families, and a high bound taken at max(b) cuts those
    # small-b lanes off.)
    b_hi_ref = jnp.max(b_safe)
    b_lo_ref = jnp.min(b_safe)
    c_safe = jnp.where(act, c, 1.0)
    ds_b = prep.A * fmath.power(
        jnp.maximum(prep.w + prep.sigma * b_hi_ref, 1e-300), prep.gamma)
    eps = b_lo_ref / (8.0 * M)
    ds0 = jnp.where(prep.w > 0,
                    prep.A * fmath.power(jnp.maximum(prep.w, 1e-300),
                                                     prep.gamma),
                    jnp.inf)
    ds_top = jnp.where(prep.w > 0, ds0,
                       prep.A * fmath.power(eps, prep.gamma))
    lam_lo_s = jnp.min(jnp.where(act, ds_b / c_safe, jnp.inf))
    lam_hi_s = jnp.max(jnp.where(act, ds_top / c_safe, -jnp.inf)) * (1 + 1e-9)
    good = (jnp.isfinite(lam_lo_s) & (lam_lo_s > 0) & jnp.isfinite(lam_hi_s)
            & (lam_hi_s > 0))
    lam_lo_s = jnp.where(good, lam_lo_s, 1.0)
    lam_hi_s = jnp.where(good, lam_hi_s, 2.0)
    lam_hi_s = jnp.maximum(lam_hi_s, lam_lo_s * (1 + 1e-9))

    idx = jnp.clip(jnp.searchsorted(prep.vals, b_safe, side="left"),
                   1, M - 1)                                   # (G,)
    lo = jnp.clip(prep.pos[idx], lam_lo_s, lam_hi_s)
    hi = jnp.clip(prep.pos[idx - 1], lam_lo_s, lam_hi_s)
    hi = jnp.maximum(hi, lo * (1 + 1e-12))
    vlo = prep.vals[idx]          # β̃ at the segment's low-λ end (≥ b)
    vhi = prep.vals[idx - 1]      # β̃ at the high-λ end (≤ b)
    ok = (jnp.isfinite(vlo) & jnp.isfinite(vhi) & (vlo > 0) & (vhi > 0)
          & (vlo > vhi))
    num = fmath.log(jnp.maximum(vlo, 1e-300)) - fmath.log(b_safe)
    den = fmath.log(jnp.maximum(vlo, 1e-300)) - fmath.log(
        jnp.maximum(vhi, 1e-300))
    frac = jnp.where(ok, num / jnp.where(den > 0, den, 1.0), 0.5)
    t = fmath.log(lo) + jnp.clip(frac, 0.0, 1.0) * (fmath.log(hi) - fmath.log(lo))

    theta = jnp.clip(
        jnp.where(act[None, :],
                  P[None, :] * fmath.exp(E[None, :] * t[:, None]) - Q[None, :],
                  0.0),
        0.0, b_safe[:, None])                                  # (G, M)
    tot = jnp.sum(theta, axis=-1, keepdims=True)
    theta = jnp.where(tot > 0, theta * (b_safe[:, None] / tot), theta)
    theta = jnp.minimum(theta, b_safe[:, None])
    theta = jnp.where(bv[:, None] > 0, theta, jnp.zeros_like(theta))
    return theta[0] if scalar else theta


def solve_cap_hetero_sorted(sp: Speedup, b, c, active=None, iters: int = 48,
                            return_lam: bool = False):
    """One-shot sorted-bracket per-job CAP (prepare + solve).

    The fast §7 path for regular-family per-job speedups; differential-
    tested against the ``solve_cap_hetero`` λ-bisection oracle to
    ≤ 1e-10 (f64).  Non-regular speedups must keep using
    ``solve_cap_hetero``/``solve_cap_generic``.
    """
    c = jnp.asarray(c)
    if active is None:
        active = jnp.ones(c.shape, dtype=bool)
    prep = hetero_prepare(sp, c, active)
    return hetero_solve(prep, b, iters=iters, return_lam=return_lam)


def solve_cap(sp: Speedup, b, c, active=None, iters: int = 96):
    """Dispatch: closed form for a shared RegularSpeedup; λ-bisection for
    per-job (heterogeneous) or non-regular speedups.

    The rectangle-bottle closed form requires one shared auxiliary curve
    g(h) = A(σh)^γ — job-indexed (A_i, γ_i) leaves have none, so any
    per-job speedup routes to the bisection (where regular-family
    members still enjoy a closed-form ``ds_inv_i`` per probe).
    """
    if isinstance(sp, RegularSpeedup) and not is_per_job(sp):
        return solve_cap_regular(sp, b, c, active)
    return solve_cap_generic(sp, b, c, active, iters=iters)


def solve_cap_batched(sp: Speedup, b, c, active=None, iters: int = 64,
                      impl: str = "auto"):
    """CAP over N instances at once: (N, k) c-vectors, scalar or (N,) b.

    The batched front door for controllers that water-fill many tenants
    per tick.  Dispatch (``impl="auto"``):

      * shared RegularSpeedup on TPU with k ≥ the kernel threshold → the
        fused Pallas *generic waterfill* kernel (blocked θ(λ) +
        reduction per bisection step; sort-free, which is what the TPU
        wants — ``kernels/gwf_waterfill``);
      * shared RegularSpeedup elsewhere → ``vmap`` of the O(k log k)
        closed form;
      * per-job regular-family speedups (job-indexed RegularSpeedup
        leaves or a StackedSpeedup) on TPU at kernel size → the fused
        *hetero waterfill* kernel (per-job parameter blocks in VMEM);
        elsewhere → ``vmap`` of the sorted-bracket solver
        (``solve_cap_hetero_sorted``);
      * any other speedup → ``vmap`` of the λ-bisection.

    ``impl`` ∈ {"auto", "closed", "sorted", "bisect", "pallas"} forces a
    path ("pallas" resolves to the hetero kernel when ``sp`` is per-job;
    "bisect" remains the per-job differential oracle).
    Scalar speedup parameters are shared across instances; leaves with a
    leading N dimension are vmapped per instance; ``(N, k)`` leaves are
    per-instance *and* per-job.
    """
    c = jnp.asarray(c)
    if c.ndim != 2:
        raise ValueError("c must be (N, k)")
    N, k = c.shape
    if active is None:
        active = jnp.ones((N, k), dtype=bool)
    b_v = jnp.broadcast_to(jnp.asarray(b, c.dtype), (N,))
    from .batch import check_axes_unambiguous
    from .speedup import inner_per_job

    # With N == k a 1-D speedup leaf is per-instance or per-job with no
    # way to tell — every impl path must refuse, not just the kernel's
    # own broadcast (the vmapped paths would silently pick per-instance).
    check_axes_unambiguous(sp, N, k, "sp")
    per_job = inner_per_job(sp, N)
    regular = isinstance(sp, RegularSpeedup) and not per_job
    stackable = isinstance(sp, (RegularSpeedup, StackedSpeedup))
    if impl == "auto":
        from repro.kernels.gwf_waterfill.ops import use_pallas_for
        if stackable and per_job and use_pallas_for(k):
            impl = "pallas"
        elif regular and use_pallas_for(k):
            impl = "pallas"
        elif regular:
            impl = "closed"
        elif stackable and per_job:
            impl = "sorted"
        else:
            impl = "bisect"
    if impl == "pallas":
        if not stackable:
            raise ValueError("impl='pallas' needs a (possibly per-job) "
                             "regular-family speedup")
        cm = jnp.where(active, c, 0.0)
        if per_job:
            from repro.kernels.gwf_waterfill.ops import hetero_waterfill_op

            def bc(l):
                # (N,) per-instance leaves broadcast down the job axis;
                # (k,) shared-per-job leaves broadcast down the instance
                # axis; (N, k) pass through.
                l = jnp.asarray(l, c.dtype)
                if l.ndim == 1 and l.shape[0] == N:
                    if N == k:
                        raise ValueError(
                            "1-D speedup leaf of length N == k is "
                            "ambiguous (per-instance vs per-job); "
                            "reshape to (N, 1) or (1, k)")
                    l = l[:, None]
                return jnp.broadcast_to(l, (N, k))

            sigma = (sp.sigma if isinstance(sp, StackedSpeedup)
                     else float(sp.sigma))
            return hetero_waterfill_op(
                cm, bc(sp.A), bc(sp.w), bc(sp.gamma), bc(sigma),
                b_v, iters=iters)
        from repro.kernels.gwf_waterfill.ops import generic_waterfill_op
        return generic_waterfill_op(
            cm, jnp.broadcast_to(jnp.asarray(sp.A, c.dtype), (N,)),
            jnp.broadcast_to(jnp.asarray(sp.w, c.dtype), (N,)),
            jnp.broadcast_to(jnp.asarray(sp.gamma, c.dtype), (N,)),
            b_v, sigma=sp.sigma, iters=iters)
    sp_axes = jax.tree_util.tree_map(
        lambda l: 0 if (getattr(l, "ndim", 0) >= 1 and l.shape[0] == N)
        else None, sp)
    if impl == "closed":
        if not regular:
            raise ValueError("impl='closed' needs a RegularSpeedup")
        return jax.vmap(solve_cap_regular, in_axes=(sp_axes, 0, 0, 0))(
            sp, b_v, c, active)
    if impl == "sorted":
        if not stackable:
            raise ValueError("impl='sorted' needs a (possibly per-job) "
                             "regular-family speedup")
        return jax.vmap(
            lambda spv, bv, cv, av: solve_cap_hetero_sorted(spv, bv, cv, av),
            in_axes=(sp_axes, 0, 0, 0))(sp, b_v, c, active)
    if impl != "bisect":
        raise ValueError(f"unknown impl {impl!r}")
    return jax.vmap(
        lambda spv, bv, cv, av: solve_cap_generic(spv, bv, cv, av,
                                                  iters=iters),
        in_axes=(sp_axes, 0, 0, 0))(sp, b_v, c, active)


def cap_residual(sp: Speedup, b, c, theta, active=None, tol: float = 1e-6):
    """Max violation of the CAP constraints (9a)–(9d) by ``theta``.

    Returns a dict of violation magnitudes; used by tests and the CDR
    verifier.  Zero (≤ tol) everywhere ⟺ θ solves CAP.
    """
    c = jnp.asarray(c)
    theta = jnp.asarray(theta)
    k = c.shape[0]
    if active is None:
        active = jnp.ones((k,), dtype=bool)
    thm = jnp.where(active, theta, 0.0)

    budget = jnp.abs(jnp.sum(thm) - b)

    # (9b) ordering among active jobs (c sorted non-increasing).  A
    # shared-speedup property only: with per-job s_i, a job with a
    # steeper derivative can take less bandwidth at a larger c, so θ
    # ordering does not follow from c ordering and the check is skipped.
    if is_per_job(sp):
        order = jnp.zeros(())
    else:
        order = jnp.max(jnp.where(active[:-1] & active[1:],
                                  thm[:-1] - thm[1:], -jnp.inf))
        order = jnp.maximum(order, 0.0)

    iu = jnp.arange(k)
    upper = iu[:, None] < iu[None, :]           # pairs i < j only
    ds = sp.ds(thm)
    ds0 = jnp.broadcast_to(sp.ds0(), (k,))      # per-job under §7 leaves
    # (9c): s_j'(θ_j)·c_i − s_i'(θ_i)·c_j = 0 for active pairs with
    # θ_i, θ_j > 0 (per-job derivatives when sp carries (M,) leaves)
    pos = active & (thm > tol)
    num = ds[None, :] * c[:, None] - ds[:, None] * c[None, :]
    scale = jnp.maximum(ds[None, :] * c[:, None], 1e-30)
    ratio_viol = jnp.where(upper & pos[:, None] & pos[None, :],
                           jnp.abs(num) / scale, 0.0)
    # (9d): for i < j with θ_j > θ_i = 0: s_j'(θ_j)/s_i'(0) ≥ c_j/c_i —
    # the parking bound is against the *parked* job's own marginal rate
    # at zero (λ ≥ s_i'(0)/c_i ⟺ c_i/c_j · s_j'(θ_j) ≥ s_i'(0)); with a
    # shared speedup s_i'(0) = s_j'(0) and the two readings coincide.
    zero = active & (thm <= tol)
    ineq = (c[None, :] / c[:, None]) - (ds[None, :] / ds0[:, None])
    ineq_viol = jnp.where(upper & zero[:, None] & pos[None, :]
                          & jnp.isfinite(ds0)[:, None],
                          jnp.maximum(ineq, 0.0), 0.0)
    return {
        "budget": budget,
        "order": order,
        "ratio": jnp.max(ratio_viol),
        "park": jnp.max(ineq_viol),
    }
