"""SmartFill — Algorithm 2 of the paper: the complete solution to OPT.

OPT: minimize J = Σ w_i T_i over allocations θ_i(t), Σθ ≤ B, for M jobs
with sizes x_1 ≥ … ≥ x_M, weights w_1 ≤ … ≤ w_M, and a common concave
speedup function s(θ).

Structure (Props 7/8): allocations are piecewise-constant between
completions and jobs complete in SJF order M, M−1, …, 1, so the policy is
an upper-triangular matrix Θ where Θ[i, j] is the rate of job i+1 during
*phase* j+1 (the interval [T*_{j+2}, T*_{j+1}), with jobs 1..j+1 active).
Column M−1 is the first interval in time ([0, T*_M)); column 0 the last.

SmartFill builds Θ column by column from the last-completed job (job 1)
outward, carrying the CDR constants c_k (Cor. 2.1) and the value-function
coefficients a_k of Prop. 9 (J* = Σ a_i x_i):

  iteration 1:   θ¹₁ = B, c₁ = 1, a₁ = w₁ / s(B)
  iteration k+1: μ* = argmin_μ F(μ),
                 F(μ) = (Σ_{i≤k+1} w_i − Σ_{i≤k} a_i s(CAP_i(B−μ, c))) / s(μ)
                 θ^{k+1}_{k+1} = μ*;  θ^{k+1}_i = CAP_i(B−μ*, c)   (27)
                 c_{k+1} = c_k · s'(μ*) / s'(θ^{k+1}_k)            (28)
                 a_{k+1} = F(μ*)                                   (29)

NOTE on (26): the paper prints arg max_μ, but a_{k+1} is the marginal
*cost* of one unit of x_{k+1} (Prop. 9 proof sketch: J = Σ a_i x_i +
x_{k+1} F(μ)), so the correct operation is arg **min** (F(μ) → +∞ as
μ → 0⁺; no maximum exists).  Validated: with s = aθ^p SmartFill
reproduces heSRPT exactly (paper Figs. 4–5) and Figs. 6/8 gaps match.

Device-resident design
----------------------
The whole recursion is one jitted ``lax.scan`` over iterations k with
fixed shapes — no Python loop, no host round-trips per iteration:

  * the 1-D minimization runs fully on-device: a small mixed log+linear
    *localization* grid (``coarse`` points, to place the unimodal
    minimum's basin, resolving basins near μ→0) followed by a
    fixed-iteration **golden-section descent** inside the bracketing
    grid cell — ``descent_iters`` single-CAP evaluations shrink the
    bracket by φ⁻¹ per step (φ⁻¹⁴⁰ ≈ 4·10⁻⁹), replacing the old
    512-point grid + 4×64 grid-zoom (~768 CAP solves per iteration)
    with ~70;
  * for the pure-power subfamily of ``RegularSpeedup`` (s = aθ^p — the
    heSRPT family, where the paper's closed form applies) μ* is computed
    in closed form per iteration, skipping the search entirely:
    μ*/B = (W_{k+1}^m − W_k^m)/W_{k+1}^m with m = 1/(1−p) [Berg et al.];
    for the wider regular class the CAP inside F is closed form in
    O(k log k) (``solve_cap_regular``), only the scalar argmin is
    iterative;
  * on the generic (non-regular) path every F evaluation is a full
    λ-bisection; the scan carries the previous iteration's λ-bracket as
    a warm start (validated, so it can never corrupt the solve) and the
    bisection exits adaptively once the bracket is relatively tight —
    see ``solve_cap_generic(bracket=…, rel_tol=…)``;
  * the solver core takes a traced active-job count ``m`` so the same
    compiled program serves padded instances — ``jax.vmap`` over
    (x, w, B, m) is the batched planning API in ``core/batch.py``.

After warmup a call executes with zero per-iteration host syncs; the only
transfer is the final schedule read-back in the ``smartfill()`` wrapper.
``smartfill_reference`` preserves the original host-loop implementation
(including the original grid + grid-zoom minimizer) as the equivalence
oracle for tests.

Heterogeneous per-job speedups (paper §7)
-----------------------------------------
Every job may carry its own concave s_i via *job-indexed speedup leaves*
(``core/speedup.py``): the solver core detects per-job leaves statically
(leaf shape survives tracing) and switches the CAP to the per-job
λ-bisection (``solve_cap_hetero``) while every diagonal term — F's
denominator s_{k+1}(μ), the CDR update s'_{k+1}(μ)/s'_k(θ_k), the a₁
seed — indexes job k's own function through ``take_job`` (the identity
for shared speedups, so the homogeneous paths are bit-for-bit
unchanged; constant broadcast leaves are collapsed back to scalars for
the same reason).  Thm 10 keeps the CDR structure; the completion
*order* is open — ``smartfill_hetero`` searches it
(SJF-by-normalized-size + adjacent-exchange descent, with
``J == J_linear`` as the realized-order certificate) and
``smartfill_hetero_reference`` brute-forces it on small instances as
the test oracle.

Precision: run under ``jax.config.update("jax_enable_x64", True)`` for
reference accuracy.  In float32 the scalar minimizer loses ~1e-3
relative J on near-linear speedups (power p ≳ 0.9), where F's minimum
is shallow; the closed-form fast path is exact in either precision.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import fmath

from .gwf import (hetero_approx, hetero_breakpoints_init,
                  hetero_breakpoints_insert, hetero_prepare, hetero_solve,
                  solve_cap, solve_cap_generic, waterfill_prepare,
                  waterfill_solve)
from .speedup import (RegularSpeedup, Speedup, StackedSpeedup,
                      collapse_homogeneous, is_per_job, rowwise, take_job)

__all__ = [
    "SmartFillSchedule",
    "HeteroSmartFillSchedule",
    "smartfill",
    "smartfill_warm",
    "WarmStart",
    "smartfill_hetero",
    "smartfill_reference",
    "smartfill_hetero_reference",
    "smartfill_allocations",
    "completion_times",
    "normalized_order",
    "objective",
]


@dataclasses.dataclass(frozen=True)
class SmartFillSchedule:
    """Output of SmartFill.

    theta[i, j]: rate of job i during phase j (phase j has jobs 0..j
      active; phase M−1 is earliest in time).  Upper-triangular.
    c: (M,) CDR constants (Cor. 2.1), c[0] = 1, non-increasing.
    a: (M,) value-function coefficients (Prop. 9), non-decreasing.
    durations: (M,) phase lengths; durations[j] = |phase j|.
    T: (M,) completion times, T[0] > T[1] > … > T[M−1] (SJF order).
    J: optimal objective Σ w_i T_i.
    J_linear: Σ a_i x_i — must equal J (Prop. 9); kept for validation.
    """

    theta: jnp.ndarray
    c: jnp.ndarray
    a: jnp.ndarray
    durations: jnp.ndarray
    T: jnp.ndarray
    J: float
    J_linear: float


def _is_pure_power(sp: Speedup) -> bool:
    """True iff ``sp`` is s = aθ^p (closed-form μ* per iteration).

    Decidable only for concrete (non-traced) parameters; a traced ``sp``
    conservatively takes the generic path.  Batched parameters (leaves
    with a leading instance dimension, as produced by
    ``core/workloads.py``) qualify iff *every* instance is pure power —
    after vmap each lane sees its own scalar (w, γ).
    """
    if not isinstance(sp, RegularSpeedup) or sp.sigma != +1:
        return False
    try:
        w = np.asarray(sp.w)
        g = np.asarray(sp.gamma)
    except (TypeError, jax.errors.TracerArrayConversionError):
        return False
    return bool(np.all(w == 0.0) and np.all((-1.0 < g) & (g < 0.0)))


def _fast_ok(sp: Speedup, n_instances: int | None = None) -> bool:
    """True iff the closed-form μ* path is valid for ``sp`` as solved.

    The heSRPT closed form needs **one** exponent p per solved instance:
    pure power, and no job-indexed leaves.  A leading ``n_instances``
    axis (per-instance parameters, vmapped away by the batched planners)
    is fine — each lane then sees its own scalar p; any dimension beyond
    that is per-job heterogeneity and takes the descent minimizer.
    """
    from .speedup import inner_per_job

    return _is_pure_power(sp) and not inner_per_job(sp, n_instances)


# Golden-section constants: φ⁻¹ and φ⁻² (= 1 − φ⁻¹).
_INVPHI = 0.6180339887498949
_INVPHI2 = 0.3819660112501051
# Warm λ-bracket widening between SmartFill iterations (generic path):
# the previous iteration's λ* moves with c_{k+1} and the new budget, but
# rarely by more than this factor; a larger move is caught by the
# bracket validation inside solve_cap_generic and falls back to the
# safe bracket.
_WARM_WIDEN = 256.0
# Adaptive λ-bisection exit: stop once hi ≤ lo·(1 + rel_tol).
_CAP_REL_TOL = 1e-13
# Below this many jobs the μ-localization grid is priced with exact
# (λ-threaded) CAP solves instead of the one-pass approximation: few
# jobs means few β̃ breakpoints, and across a wide segment the
# log-secant's bias can misplace the grid argmin by several cells.
_APPROX_GRID_MIN_M = 33


def _mu_floor(B, dtype):
    """Dtype-aware positive lower edge of the μ-minimizer's domain.

    The historical floor ``B * 1e-9`` underflows to exactly 0 for small
    budgets (float32: B ≲ 1e-29), and μ = 0 puts s(0) = 0 on the
    phase-rate diagonal, NaN-ing the back-substituted durations.  Floor
    at ``tiny/eps`` of the working dtype (≈1e-31 in f32, ≈1e-292 in
    f64): far below any meaningful allocation, but positive and normal.
    """
    fi = jnp.finfo(dtype)
    floor = jnp.asarray(fi.tiny, dtype) / jnp.asarray(fi.eps, dtype)
    return jnp.maximum(B * 1e-9, floor)


def _f_grid(sp, mus, c, a, k, W, B):
    """Vectorized F(μ) over a grid. c/a are padded to M; first k entries live.

    ``k`` is a traced scalar so one compilation serves every SmartFill
    iteration (and every run with the same M / grid size).  With per-job
    speedup leaves the numerator prices each job under its own s_i and
    the denominator uses job k's own s_k (``take_job`` is the identity
    for a shared speedup, so the homogeneous path is unchanged).
    """
    M = c.shape[0]
    active = jnp.arange(M) < k
    sp_k = take_job(sp, k)

    def F(mu):
        th = solve_cap(sp, B - mu, c, active)
        served = jnp.where(active, a * sp.s(th), 0.0)
        return (W - jnp.sum(served)) / sp_k.s(mu)

    return jax.vmap(F)(mus)


def _argmin_bracket(mus, vals, n):
    """(best μ, best F, bracket, ok) of a grid; NaN-safe, on-device.

    ``ok`` is False when *every* grid value is non-finite (a degenerate
    instance) — the caller must then propagate a finite fallback instead
    of silently trusting index 0, which would poison the scan carry.
    """
    finite = jnp.isfinite(vals)
    i = jnp.argmin(jnp.where(finite, vals, jnp.inf))
    lo = mus[jnp.maximum(i - 1, 0)]
    hi = mus[jnp.minimum(i + 1, n - 1)]
    return mus[i], vals[i], lo, hi, jnp.any(finite)


def _uses_closed_cap(sp: Speedup) -> bool:
    """Static: can this iteration's CAP use the prefix-sum closed form?

    Only a *shared* RegularSpeedup has the common auxiliary curve the
    rectangle-bottle factorization needs; per-job leaves (paper §7) and
    non-regular speedups solve the CAP by λ-bisection (with warm-bracket
    carry across SmartFill iterations).  Leaf shape is static, so this
    decides the trace, not the data.
    """
    return isinstance(sp, RegularSpeedup) and not is_per_job(sp)


def _uses_sorted_cap(sp: Speedup) -> bool:
    """Static: can the per-job CAP use the sorted-breakpoint solver?

    Any stackable regular family — a job-indexed ``RegularSpeedup`` or a
    ``StackedSpeedup`` mix — has closed-form activation breakpoints
    ``s_i'(0⁺)/c_i``, so λ* can be bracketed by ``searchsorted`` on the
    sorted breakpoint curve and polished with safeguarded Newton instead
    of blind bisection (``hetero_prepare``/``hetero_solve``).  Per-job
    ``GenericSpeedup`` leaves stay on the λ-bisection path.
    """
    return isinstance(sp, (RegularSpeedup, StackedSpeedup)) and is_per_job(sp)


def _make_f(sp, c, a, k, W, B, warm, cap_iters, bp=None, lam_hint=None,
            precise=True):
    """Build (F, cap, chain) for one SmartFill iteration.

    ``chain`` is ``None`` except on the sorted per-job path, where it is
    the pair ``(F_grid, F_chain)`` consumed by ``_minimize_f_hinted``:
    a loose-tolerance probe for the localization grid and a λ-threading
    probe for the golden-section descent (there ``cap`` also accepts the
    descent's final λ* as a second argument).

    ``F(μ)`` is the single-point objective for the descent loop;
    ``cap(μ)`` returns ``(θ, λ-bracket, λ*)`` — the final CAP solve at
    the chosen μ*.  On the shared-regular path the CAP's water-filling
    curve is *factorized once* here (``waterfill_prepare`` — the sort
    and prefix sums depend only on c, not on the budget), and both F and
    cap invert it in O(k), so the per-iteration sort is paid exactly
    once.  On the per-job regular path (§7) the same factorization runs
    through ``hetero_prepare`` over the incrementally maintained
    breakpoint store ``bp`` — one O(M log M) sort per iteration shared
    by all ~74 budgets of the μ* descent — and each solve is a
    ``searchsorted`` bracket + safeguarded Newton seeded by ``lam_hint``
    (the previous iteration's λ*).  On the generic path each F
    evaluation is a warm-started, adaptively terminated λ-bisection
    (the warm bracket is this SmartFill iteration's, widened once here)
    and cap runs the full-precision bisection, returning the bracket to
    carry forward.  F's denominator is job k's own ``s_k(μ)`` —
    ``take_job`` is the identity for a shared speedup.
    """
    M = c.shape[0]
    active = jnp.arange(M) < k
    sp_k = take_job(sp, k)
    no_lam = jnp.zeros((), c.dtype)

    if _uses_closed_cap(sp):
        u = jnp.where(active, sp.bottle_width(c), 0.0)
        h0 = sp.bottle_bottom(c)
        prep = waterfill_prepare(u, h0, active)

        def F(mu):
            th = waterfill_solve(prep, u, h0, B - mu, active)
            served = jnp.where(active, a * sp.s(th), 0.0)
            return (W - jnp.sum(served)) / sp_k.s(mu)

        def cap(mu):
            return waterfill_solve(prep, u, h0, B - mu, active), warm, no_lam
    elif bp is not None and _uses_sorted_cap(sp):
        prep = hetero_prepare(sp, c, active, breakpoints=bp)

        def _price(th, mu):
            served = jnp.where(active, a * sp.s(th), 0.0)
            return (W - jnp.sum(served)) / sp_k.s(mu)

        def F(mu):
            th = hetero_solve(prep, B - mu, iters=cap_iters,
                              lam_hint=lam_hint)
            return _price(th, mu)

        small = c.shape[0] < _APPROX_GRID_MIN_M

        def F_chain(mu, hint):
            # bracket-selection probe: grid budgets arrive λ*-threaded
            # but a cell apart, so 4 unrolled safeguarded steps reach fp
            # precision without a while_loop launch per probe
            th, lam = hetero_solve(prep, B - mu, iters=cap_iters,
                                   lam_hint=hint, return_lam=True,
                                   unroll=4)
            return _price(th, mu), lam

        def F_desc(mu, hint):
            # descent probe: consecutive probes live inside one
            # contracting grid cell, so the warm λ* is near-exact and 2
            # steps square its error twice; small instances keep the
            # 4-step margin (they are oracle-pinned to 1e-6)
            th, lam = hetero_solve(prep, B - mu, iters=cap_iters,
                                   lam_hint=hint, return_lam=True,
                                   unroll=4 if (small and precise) else 2)
            return _price(th, mu), lam

        if small and precise:
            # few jobs ⇒ few breakpoints ⇒ wide segments, where the
            # one-pass log-secant approximation carries percent-level
            # bias — enough to misplace the grid argmin several cells
            # (seen on the m ≤ 6 oracle instances).  Price the grid
            # exactly instead, λ*-threaded left to right (grid μ
            # ascending ⇒ budget descending ⇒ λ* ascending, so every
            # eval is warm); small M keeps each pass cheap.
            def F_grid(mus, hint0):
                def stepg(h, mu):
                    v, h2 = F_chain(mu, h)
                    return h2, v
                _, vals = lax.scan(stepg, hint0, mus)
                return vals
        elif small:
            # relaxed (policy-grade) small-M grid: the approximation's
            # wide-segment bias is still too large here, but a *cold*
            # 6-step unrolled Newton per budget is already fp-accurate
            # (searchsorted gives the exact segment) and vmaps into one
            # fused (G, M) pass — ~20× less serial depth than the
            # λ-threaded exact scan the planner uses
            def F_grid(mus, hint0):
                th = jax.vmap(
                    lambda mu: hetero_solve(prep, B - mu, iters=cap_iters,
                                            unroll=6))(mus)       # (G, M)
                served = jnp.sum(
                    jnp.where(active[None, :], a[None, :] * sp.s(th), 0.0),
                    axis=-1)
                return (W - served) / sp_k.s(mus)
        else:
            def F_grid(mus, hint0):
                # localization probe: cell placement tolerates the
                # log-secant approximation's error at this breakpoint
                # density, so price the whole grid in two fused (G, M)
                # passes instead of running the Newton solve per point
                th = hetero_approx(prep, B - mus)              # (G, M)
                served = jnp.sum(
                    jnp.where(active[None, :], a[None, :] * sp.s(th), 0.0),
                    axis=-1)
                return (W - served) / sp_k.s(mus)

        def cap(mu, hint=None):
            # the descent hands over its final λ* (usually evaluated at
            # this very μ*), so 4 unrolled steps leave margin over the
            # ~2 a warm Newton needs; the cold no-hint call keeps the
            # adaptive loop
            th, lam = hetero_solve(
                prep, B - mu, iters=cap_iters,
                lam_hint=lam_hint if hint is None else hint,
                return_lam=True, unroll=0 if hint is None else 4)
            return th, warm, lam
        return F, cap, (F_grid, F_chain, F_desc)
    else:
        bracket = (warm[0] / _WARM_WIDEN, warm[1] * _WARM_WIDEN)

        def F(mu):
            th = solve_cap_generic(sp, B - mu, c, active, iters=cap_iters,
                                   bracket=bracket, rel_tol=_CAP_REL_TOL)
            served = jnp.where(active, a * sp.s(th), 0.0)
            return (W - jnp.sum(served)) / sp_k.s(mu)

        def cap(mu):
            th, br = solve_cap_generic(sp, B - mu, c, active, iters=96,
                                       bracket=bracket, return_bracket=True)
            return th, br, no_lam
    return F, cap, None


def _minimize_f(F, B, coarse, descent_iters):
    """argmin_μ F(μ) on (0, B]: coarse localization + golden-section.

    A mixed log+linear ``coarse``-point grid places the basin of the
    unimodal F (the log half resolves basins near μ→0); golden-section
    then contracts the bracketing cell by φ⁻¹ per iteration with one
    F evaluation each.  Entirely traced — zero host syncs.  If every
    probe is non-finite (degenerate instance) the minimizer returns the
    finite fallback μ = B.
    """
    B = jnp.asarray(B)
    dtype = B.dtype
    lo = _mu_floor(B, dtype)
    # The log half excludes its B endpoint: both halves ending exactly at
    # B would leave two coincident top grid points, and an argmin landing
    # on the second collapses the golden bracket to [B−ulp, B] — hiding
    # any interior minimum of the bracketing cell (seen on §7
    # mixed-family F whose minimum sits just under B).
    g1 = jnp.geomspace(lo, B, coarse // 2 + 1, dtype=dtype)[:-1]
    g2 = jnp.linspace(B / (coarse // 2), B, coarse // 2, dtype=dtype)
    mus = jnp.sort(jnp.concatenate([g1, g2]))
    vals = jax.vmap(F)(mus)
    mu0, val0, mu_lo, mu_hi, ok = _argmin_bracket(mus, vals, mus.shape[0])

    span = mu_hi - mu_lo
    x1 = mu_lo + _INVPHI2 * span
    x2 = mu_lo + _INVPHI * span
    f1 = F(x1)
    f2 = F(x2)

    def body(_, st):
        glo, ghi, x1, x2, f1, f2 = st
        left = (jnp.where(jnp.isnan(f1), jnp.inf, f1)
                <= jnp.where(jnp.isnan(f2), jnp.inf, f2))
        glo2 = jnp.where(left, glo, x1)
        ghi2 = jnp.where(left, x2, ghi)
        span = ghi2 - glo2
        p = jnp.where(left, glo2 + _INVPHI2 * span, glo2 + _INVPHI * span)
        fp = F(p)
        nx1 = jnp.where(left, p, x2)
        nf1 = jnp.where(left, fp, f2)
        nx2 = jnp.where(left, x1, p)
        nf2 = jnp.where(left, f1, fp)
        return glo2, ghi2, nx1, nx2, nf1, nf2

    _, _, x1, x2, f1, f2 = lax.fori_loop(
        0, descent_iters, body, (mu_lo, mu_hi, x1, x2, f1, f2))

    # best of the two interior points and the coarse argmin itself
    cand_mu = jnp.stack([mu0, x1, x2])
    cand_f = jnp.stack([val0, f1, f2])
    i = jnp.argmin(jnp.where(jnp.isfinite(cand_f), cand_f, jnp.inf))
    mu, val = cand_mu[i], cand_f[i]
    bad = ~(ok & jnp.isfinite(val))
    return jnp.where(bad, B, mu), jnp.where(bad, jnp.inf, val)


def _minimize_f_hinted(F_grid, F_chain, F_desc, B, coarse, descent_iters,
                       hint0, stol_rel=3e-7, window=5):
    """``_minimize_f`` specialized to the sorted per-job CAP path.

    Three per-eval/per-search accelerations the factorized solver makes
    possible: the localization grid prices one-pass approximate CAPs
    (``hetero_approx`` — cell placement only); the descent threads each
    probe's λ* into the next probe's warm start; and the descent itself
    is safeguarded successive-parabolic interpolation on the bracketing
    triple rather than golden section — superlinear, so it meets the
    golden-equivalent bracket tolerance in ~a third of the (serial,
    ~40 μs) F evaluations, with a convergence exit instead of a fixed
    trip count.  ``descent_iters`` remains the worst-case budget, and a
    non-contracting parabolic proposal falls back to the golden step of
    the larger sub-interval.  Returns ``(μ*, F(μ*), λ_last)``; the
    caller seeds the final CAP solve with ``λ_last``.
    """
    B = jnp.asarray(B)
    dtype = B.dtype
    lo = _mu_floor(B, dtype)
    g1 = jnp.geomspace(lo, B, coarse // 2 + 1, dtype=dtype)[:-1]
    g2 = jnp.linspace(B / (coarse // 2), B, coarse // 2, dtype=dtype)
    mus = jnp.sort(jnp.concatenate([g1, g2]))
    vals = F_grid(mus, hint0)
    finite = jnp.isfinite(vals)
    ok = jnp.any(finite)
    G = mus.shape[0]
    j0 = jnp.argmin(jnp.where(finite, vals, jnp.inf))

    # the approximate grid's percent-level bias can flip near-minimum
    # comparisons a cell either way, and converging the descent inside
    # the wrong cell costs ~1e-4 rel J at a cell edge — so re-price a
    # 5-point neighbourhood of the approximate argmin *exactly* (λ*
    # threaded through the chain) and re-select the bracketing triple
    # from those values
    ws = window if G >= window else G           # static window size
    half = ws // 2
    jc = jnp.clip(j0, half, G - ws + half)
    pts = lax.dynamic_slice(mus, (jc - half,), (ws,))
    fl, lam = [], hint0
    for t in range(ws):
        ft, lam = F_chain(pts[t], lam)
        fl.append(ft)
    inf = jnp.asarray(jnp.inf, dtype)
    fs = jnp.stack(fl)
    fs = jnp.where(jnp.isfinite(fs), fs, inf)
    # the window argmin may sit on a window *edge* (e.g. a boundary
    # minimum at μ = B, where the new job takes the whole budget); the
    # clipped triple below is then not a bracket (fm > edge value) and
    # the descent can walk into an interior basin and discard the edge —
    # keep the exactly-priced argmin as a final candidate, like
    # ``_minimize_f`` keeps its grid argmin
    kbest = jnp.argmin(fs)
    mu_w, f_w = pts[kbest], fs[kbest]
    kk = jnp.clip(kbest, 1, ws - 2)
    xa, xm, xb = pts[kk - 1], pts[kk], pts[kk + 1]
    fa, fm, fb = fs[kk - 1], fs[kk], fs[kk + 1]
    span0 = xb - xa
    # ≈ φ^-40 (the old default), except when the caller asks for a
    # tighter vertex exit than the width exit would allow — the classes
    # oracle pins J to 1e-8, which needs μ* located beyond 4e-9·span
    tol = jnp.minimum(jnp.asarray(4e-9, dtype),
                      jnp.asarray(stol_rel, dtype)) * span0
    # vertex-stability exit: F'(μ*) = 0, so at a smooth minimum a μ*
    # located to stol_rel·span leaves J within O((stol_rel·span)²·F'') —
    # negligible; at a segment-change *kink* the J error is linear in
    # the exit tolerance, which is why the caller passes a tight
    # stol_rel for small instances (oracle-pinned to 1e-6) and a
    # relaxed one for large ones (certified by J == J_linear only)
    stol = jnp.asarray(stol_rel, dtype) * span0

    def cond(st):
        i, xa, _, xb = st[0], st[1], st[2], st[3]
        return (i < descent_iters) & (xb - xa > tol) & (~st[8])

    def body(st):
        i, xa, xm, xb, fa, fm, fb, lam, _ = st
        # parabolic vertex through the triple
        d1 = (xm - xa) * (fm - fb)
        d2 = (xm - xb) * (fm - fa)
        den = 2.0 * (d1 - d2)
        u_p = xm - ((xm - xa) * d1 - (xm - xb) * d2) / jnp.where(
            den != 0.0, den, 1.0)
        # den < 0 ⟺ the fitted parabola is convex (vertex is a minimum);
        # a concave fit (possible while the triple is not yet a bracket,
        # fm above an edge value) puts u_p at the parabola's *maximum* —
        # accepting it stalls the loop shaving slivers off the wrong side
        ok_p = (den < 0.0) & jnp.isfinite(u_p) & (u_p > xa) & (u_p < xb)
        # a vertex that stopped moving IS convergence (for a quadratic
        # the vertex is exact at any bracket width — waiting for the
        # width tolerance would golden-step ~40 more times for nothing)
        done = ok_p & (jnp.abs(u_p - xm) < stol)
        # fallback: golden step into the larger sub-interval
        left_big = (xm - xa) >= (xb - xm)
        g = jnp.where(left_big, xm - _INVPHI2 * (xm - xa),
                      xm + _INVPHI2 * (xb - xm))
        u = jnp.where(ok_p & (jnp.abs(u_p - xm) >= stol), u_p, g)
        fu, lam = F_desc(u, lam)
        fu = jnp.where(jnp.isnan(fu), inf, fu)
        # bracket update keeping an interior minimum
        ul = u < xm                                    # u in (xa, xm)
        better = fu <= fm
        xa2 = jnp.where(ul, jnp.where(better, xa, u),
                        jnp.where(better, xm, xa))
        xb2 = jnp.where(ul, jnp.where(better, xm, xb),
                        jnp.where(better, xb, u))
        xm2 = jnp.where(better, u, xm)
        fa2 = jnp.where(ul, jnp.where(better, fa, fu),
                        jnp.where(better, fm, fa))
        fb2 = jnp.where(ul, jnp.where(better, fm, fb),
                        jnp.where(better, fb, fu))
        fm2 = jnp.where(better, fu, fm)
        return i + 1, xa2, xm2, xb2, fa2, fm2, fb2, lam, done

    st0 = (0, xa, xm, xb, fa, fm, fb, lam,
           jnp.zeros((), dtype=bool))
    _, xa, xm, xb, fa, fm, fb, lam, _ = lax.while_loop(cond, body, st0)

    cand_mu = jnp.stack([mu_w, xa, xm, xb])
    cand_f = jnp.stack([f_w, fa, fm, fb])
    i = jnp.argmin(jnp.where(jnp.isfinite(cand_f), cand_f, jnp.inf))
    mu, val = cand_mu[i], cand_f[i]
    bad = ~(ok & jnp.isfinite(val))
    return (jnp.where(bad, B, mu), jnp.where(bad, jnp.inf, val), lam)


@partial(jax.jit,
         static_argnames=("coarse", "descent_iters", "cap_iters", "fast",
                          "precise", "with_times", "stol_rel"))
def _solve(sp, x, w, B, m, coarse, descent_iters, cap_iters, fast,
           lam0=None, precise=True, with_times=True, stol_rel=None,
           bracket0=None):
    """Fixed-shape SmartFill core: lax.scan over iterations k = 1..M−1.

    Args:
      x, w: (M,) padded sizes/weights (padded entries must be 0).
      B: scalar budget (traced — per-instance under vmap).
      m: traced count of live jobs (prefix 0..m−1); iterations k ≥ m are
        masked no-ops so padded instances share the compiled program.
      coarse / descent_iters: static minimizer sizes (localization grid
        points / golden-section iterations).
      cap_iters: static λ-bisection budget per generic CAP solve (upper
        bound — the adaptive exit usually stops earlier).
      fast: static — closed-form μ* for the pure-power family.
      lam0: optional (M,) per-iteration λ* hints (a previous run's
        ``lam`` output — e.g. the pre-swap order during the exchange
        search, whose λ* barely moves under one swap).  Only consulted
        on the sorted per-job CAP path; a hint outside the solver's
        validated bracket is ignored, so stale hints cannot corrupt the
        solve.
      precise: static — False relaxes the small-instance μ* precision
        knobs to the large-instance (certificate-grade) settings and
        swaps the λ-threaded exact localization grid for one fused
        vmapped pass.  For per-event policy re-planning, where the
        allocations feed a simulator rather than an oracle-pinned J.
      with_times: static — False skips the back-substituted durations/
        T/J (returned as zeros); per-event policies only consume the
        allocation column.
      stol_rel: static — override for the hinted minimizer's vertex-
        stability exit (None ⇒ the size-tiered defaults below).  The
        class-aggregation oracle passes ~1e-10: its instances are tiny
        (C ≲ 64) and its differential contract (1e-8 rel J vs a host
        recursion) is linearly sensitive to μ* at clamped-duration
        kinks, so the extra descent iterations are worth buying.
      bracket0: optional (2,) generic-path λ-bracket (lo, hi) from a
        previous run's ``bracket`` output, seeding the carried warm
        bracket across *calls* the way the carry reuses it across
        iterations.  Every use is guarded by the β-probe validation
        inside ``solve_cap_generic`` (each end is kept only if its
        probe confirms it still brackets λ*), so a stale bracket —
        e.g. after the live budget collapsed between replanning
        events — degrades to the full-range "no hint" init instead of
        corrupting the solve.  Ignored on the closed-form path.

    Returns (theta, c, a, durations, T, J, J_linear, lam, bracket) as
    device arrays, where lam[k] is iteration k's CAP dual λ* on the
    sorted per-job path (0 on the closed-form and bisection paths —
    diagnostic and warm-start payload only) and bracket is the final
    carried (2,) λ-bracket, reusable as the next call's ``bracket0``.
    """
    M = x.shape[0]
    dtype = x.dtype
    B = jnp.asarray(B, dtype)
    idx = jnp.arange(M)
    zero = jnp.zeros((), dtype)
    live0 = m > 0
    closed_cap = _uses_closed_cap(sp)       # static per-job/generic dispatch
    sorted_cap = _uses_sorted_cap(sp)
    Wc = jnp.cumsum(w)                      # Wc[k] = Σ w[:k+1] (padded w = 0)

    c0 = jnp.zeros((M,), dtype).at[0].set(jnp.where(live0, 1.0, 0.0))
    a0 = jnp.zeros((M,), dtype).at[0].set(
        jnp.where(live0, w[0] / take_job(sp, 0).s(B), zero))
    col0 = jnp.where((idx == 0) & live0, B, zero)
    # generic-path λ-bracket warm start, carried across iterations; the
    # full-range init is rejected by the first solve's validation and
    # simply means "no hint yet"
    fi = jnp.finfo(dtype)
    warm0 = (jnp.asarray(fi.tiny, dtype) / jnp.asarray(fi.eps, dtype),
             jnp.asarray(fi.max, dtype) / 4.0)
    if bracket0 is not None:
        # cross-call warm start: clamp the caller's bracket into the
        # full range so a degenerate payload can at worst reproduce the
        # cold init; validity is re-proved per solve by the β-probes
        b0 = jnp.asarray(bracket0, dtype)
        warm0 = (jnp.clip(b0[0], warm0[0], warm0[1]),
                 jnp.clip(b0[1], warm0[0], warm0[1]))
    if sorted_cap:
        # per-job activation-breakpoint store (λ_i, β̃(λ_i)), maintained
        # incrementally: SmartFill only ever *appends* one CDR constant
        # c_k per iteration, so each update is O(M) instead of the
        # O(M²) one-shot prepare
        bp0 = hetero_breakpoints_init(M, dtype)
        bp0 = hetero_breakpoints_insert(sp, c0, 0, *bp0, live=live0)
    else:
        bp0 = None

    def step(carry, k):
        if sorted_cap:
            c, a, warm, bp = carry
        else:
            c, a, warm = carry
            bp = None
        live = k < m
        W = Wc[k]
        active = idx < k
        hint = None if lam0 is None else lam0[k]
        F, cap, chain = _make_f(sp, c, a, k, W, B, warm, cap_iters,
                                bp=bp, lam_hint=hint, precise=precise)
        if fast:
            # heSRPT closed form for s = aθ^p (p = γ+1, m = 1/(1−p) = −1/γ).
            # Clamped to the minimizer's domain [_mu_floor(B), B]: a
            # zero-weight live job gives μ = 0 exactly, which would put
            # s(0) = 0 on the phase-rate diagonal and NaN the durations.
            mexp = -1.0 / sp.gamma
            Wk = fmath.power(Wc[k], mexp)
            Wk1 = fmath.power(Wc[k - 1], mexp)
            mu = B * (Wk - Wk1) / jnp.maximum(Wk, 1e-300)
            mu = jnp.clip(mu, _mu_floor(B, dtype), B)
        elif chain is not None:
            hint0 = jnp.zeros((), dtype) if hint is None else hint
            # small instances are oracle-pinned to 1e-6 rel J: keep the
            # full 32-point grid and a tight descent exit there (both
            # are cheap at that size); large instances are certified by
            # J == J_linear, where the relaxed exit buys ~2× fewer evals
            small_m = precise and M < _APPROX_GRID_MIN_M
            stol_eff = ((3e-7 if small_m else 1e-4)
                        if stol_rel is None else stol_rel)
            coarse_eff = max(coarse, 32) if small_m else coarse
            # the small-M grid is exact, so its ±2-cell re-pricing
            # window guards only descent-entry quality; at large M the
            # breakpoints are dense enough that the approximate argmin
            # is reliable to ±1 cell
            window = 5 if small_m else 3
            mu, _, lam_mz = _minimize_f_hinted(
                chain[0], chain[1], chain[2], B, coarse_eff, descent_iters,
                hint0, stol_rel=stol_eff, window=window)
        else:
            mu, _ = _minimize_f(F, B, coarse, descent_iters)
        if chain is not None and not fast:
            th_rest, warm2, lam_k = cap(mu, lam_mz)     # (M,) padded
        else:
            th_rest, warm2, lam_k = cap(mu)             # (M,) padded
        if not closed_cap:
            # only a live iteration may move the carried warm bracket
            warm = (jnp.where(live, warm2[0], warm[0]),
                    jnp.where(live, warm2[1], warm[1]))
        # (29): a_{k+1} = F(μ*), evaluated on the one CAP solve above.
        # Per-job speedups (§7): each job is priced under its own s_i —
        # the (M,)-leaved sp.s is elementwise in the job axis — and the
        # new job's denominator/derivative use its own s_k.
        served = jnp.where(active, a * sp.s(th_rest), zero)
        a_next = (W - jnp.sum(served)) / take_job(sp, k).s(mu)
        col = jnp.where(active, th_rest, zero)
        col = jnp.where(idx == k, mu, col)
        # (28): c_{k+1} = c_k · s_{k}'(μ) / s_{k−1}'(θ_{k−1}^{k+1}) —
        # job-own derivatives under §7.  θ_{k−1} may be parked (=0);
        # s_{k−1}'(0) < ∞ is guaranteed for any parking speedup.
        ds_prev = take_job(sp, k - 1).ds(th_rest[k - 1])
        c_next = c[k - 1] * take_job(sp, k).ds(mu) / ds_prev
        c = c.at[k].set(jnp.where(live, jnp.maximum(c_next, 1e-300), zero))
        a = a.at[k].set(jnp.where(live, a_next, zero))
        col = jnp.where(live, col, zero)
        lam_k = jnp.where(live, lam_k, zero)
        if sorted_cap:
            bp = hetero_breakpoints_insert(sp, c, k, *bp, live=live)
            return (c, a, warm, bp), (col, lam_k)
        return (c, a, warm), (col, lam_k)

    carry0 = (c0, a0, warm0, bp0) if sorted_cap else (c0, a0, warm0)
    carry, (cols, lams) = lax.scan(step, carry0, jnp.arange(1, M))
    c, a = carry[0], carry[1]
    theta = jnp.concatenate([col0[:, None], cols.T], axis=1)
    lam = jnp.concatenate([jnp.zeros((1,), dtype), lams])

    active_jobs = idx < m
    if with_times:
        d, T = completion_times(sp, x, theta, active=active_jobs)
        J = jnp.sum(jnp.where(active_jobs, w * T, zero))
    else:
        d = T = jnp.zeros((M,), dtype)
        J = zero
    J_lin = jnp.sum(a * x)
    bracket = jnp.stack([carry[2][0], carry[2][1]])
    return theta, c, a, d, T, J, J_lin, lam, bracket


def completion_times(sp: Speedup, x, theta, active=None):
    """Back-substitute phase durations from Θ and sizes; return (d, T).

    x[j] = Σ_{m≥j} s(Θ[j,m])·d[m]  ⇒  solved from phase M−1 (earliest)
    down to phase 0.  With ``active`` (a prefix mask of live jobs),
    padded rows/columns are replaced by the identity so d = T = 0 there —
    this is what lets the solver run on padded batched instances.
    Per-job speedup leaves apply along *rows* of Θ (row i = job i), via
    the (M, 1) ``rowwise`` reshape.
    """
    x = jnp.asarray(x)
    M = x.shape[0]
    rate = (rowwise(sp) if is_per_job(sp) else sp).s(theta)  # (M, M)
    # x = R d with R upper-triangular (R[j, m] = s(Θ[j, m]), m ≥ j); the
    # diagonal is positive because each job runs in its own phase.
    R = jnp.triu(rate)
    if active is not None:
        active = jnp.asarray(active, bool)
        pair = active[:, None] & active[None, :]
        R = jnp.where(pair, R, jnp.eye(M, dtype=x.dtype))
        x = jnp.where(active, x, jnp.zeros((), x.dtype))
    d = jax.scipy.linalg.solve_triangular(R, x, lower=False)
    d = jnp.maximum(d, 0.0)
    # T[j] = Σ_{m ≥ j} d[m]  (phase M−1 is first in time)
    T = jnp.cumsum(d[::-1])[::-1]
    return d, T


def objective(w, T):
    return jnp.sum(jnp.asarray(w) * T)


def _validate_instance(x, w):
    xs, ws = np.asarray(x), np.asarray(w)
    if np.any(np.diff(xs) > 1e-12 * max(1.0, float(xs[0]))):
        raise ValueError("sizes must be non-increasing (x_1 ≥ … ≥ x_M)")
    if np.any(np.diff(ws) < -1e-12 * max(1.0, float(np.max(ws)))):
        raise ValueError("weights must be non-decreasing (w_1 ≤ … ≤ w_M)")


def smartfill(
    sp: Speedup,
    x,
    w,
    B: float | None = None,
    coarse: int = 32,
    descent_iters: int = 40,
    validate: bool = True,
    cap_iters: int = 64,
    fast_path: bool | None = None,
) -> SmartFillSchedule:
    """Run SmartFill (Algorithm 2) — single jitted device program.

    Args:
      sp: speedup function (shared RegularSpeedup → closed-form CAP;
        per-job leaves (§7) or non-regular → the λ-bisection path).  A
        per-job speedup must be indexed in the *given* job order — use
        ``smartfill_hetero`` to also search the completion order.
      x: (M,) job sizes, non-increasing.
      w: (M,) weights, non-decreasing.
      B: server bandwidth; defaults to sp.B.
      coarse: localization-grid points for the μ* minimizer.
      descent_iters: golden-section iterations inside the bracket.
      cap_iters: λ-bisection budget per generic-path F evaluation.
      fast_path: None (default) auto-enables the closed-form μ* path for
        shared pure-power speedups; False forces the bracketed-descent
        minimizer (used by equivalence tests).

    Returns a SmartFillSchedule.
    """
    x = jnp.asarray(x, dtype=jnp.result_type(float))
    w = jnp.asarray(w, dtype=x.dtype)
    M = int(x.shape[0])
    B = float(sp.B if B is None else B)
    if validate:
        _validate_instance(x, w)

    # constant job-indexed leaves describe a homogeneous instance: route
    # them through the shared fast paths bit-for-bit
    sp = collapse_homogeneous(sp)
    fast = _fast_ok(sp) and fast_path is not False
    theta, c, a, d, T, J, J_lin, _, _ = _solve(
        sp, x, w, B, M, coarse, descent_iters, cap_iters, fast)
    return SmartFillSchedule(
        theta=theta, c=c, a=a, durations=d, T=T,
        J=float(J), J_linear=float(J_lin),
    )


def smartfill_allocations(sp: Speedup, rem, w, B: float | None = None):
    """Current-instant optimal allocations for remaining sizes ``rem``.

    This is column M−1 of SmartFill run on the remaining workload — the
    re-planning form used by policy-driven simulation and the cluster
    scheduler.  rem must be sorted non-increasing with w non-decreasing.
    (For many instances at once use ``smartfill_allocations_batched``.)
    """
    sched = smartfill(sp, rem, w, B=B, validate=False)
    return sched.theta[:, -1]


@dataclasses.dataclass(frozen=True)
class WarmStart:
    """Cross-call warm-start payload for incremental re-planning.

    Produced by ``smartfill_warm`` and fed back to the next call on a
    *related* instance (the streaming controller's replanning events:
    one arrival/completion between solves, so λ* and the completion
    order barely move).  Both device payloads are validated on use —
    ``lam`` per iteration against the solver's bracket, ``bracket`` by
    the β-probes inside ``solve_cap_generic`` — so a stale payload
    costs a cold-priced solve, never a wrong one.

    lam: (M,) per-iteration CAP duals λ* (sorted per-job path; zeros on
      the closed-form/bisection paths).  Shape-tied to the producing
      call's padded M.
    bracket: (2,) final generic-path λ-bracket (lo, hi).
    order: optional host-side completion order the payload was produced
      under (row r of the solved instance held original job
      ``order[r]``); ``None`` when the caller manages ordering itself.
    """

    lam: jnp.ndarray
    bracket: jnp.ndarray
    order: np.ndarray | None = None


# WarmStart is a pytree so the streaming device path can carry it across
# events inside a lax.scan (the λ payload rides in the scan carry; the
# optional host-side order is a child too — ``None`` flattens to an
# empty subtree, and the device carry never populates it).
jax.tree_util.register_pytree_node(
    WarmStart,
    lambda ws: ((ws.lam, ws.bracket, ws.order), None),
    lambda _, ch: WarmStart(lam=ch[0], bracket=ch[1], order=ch[2]),
)


def smartfill_warm(
    sp: Speedup,
    x,
    w,
    B: float | None = None,
    warm: WarmStart | None = None,
    coarse: int = 32,
    descent_iters: int = 40,
    cap_iters: int = 64,
    fast_path: bool | None = None,
    stol_rel: float | None = None,
) -> tuple[SmartFillSchedule, WarmStart]:
    """``smartfill`` with cross-call warm starts, for replanning loops.

    Same contract as ``smartfill`` (x non-increasing, w non-decreasing —
    the caller owns the completion order), but the solve is seeded from
    ``warm`` (a previous call's payload: per-iteration λ* hints plus the
    generic-path λ-bracket) and returns a fresh payload alongside the
    schedule.  Hints only steer where the λ searches *start*; every use
    is bracket-validated, so the warm result matches the cold one to
    solver tolerance and a stale payload (budget jump, churned order)
    silently degrades to cold pricing.  The padded width M must match
    between the producing and consuming calls.
    """
    x = jnp.asarray(x, dtype=jnp.result_type(float))
    w = jnp.asarray(w, dtype=x.dtype)
    M = int(x.shape[0])
    B = float(sp.B if B is None else B)
    sp = collapse_homogeneous(sp)
    fast = _fast_ok(sp) and fast_path is not False
    lam0 = bracket0 = None
    if warm is not None:
        lam0 = jnp.asarray(warm.lam, x.dtype)
        bracket0 = jnp.asarray(warm.bracket, x.dtype)
        if lam0.shape != (M,):
            raise ValueError(
                f"warm.lam has shape {lam0.shape}, instance is padded "
                f"to M={M}")
    theta, c, a, d, T, J, J_lin, lam, bracket = _solve(
        sp, x, w, B, M, coarse, descent_iters, cap_iters, fast,
        lam0=lam0, stol_rel=stol_rel, bracket0=bracket0)
    sched = SmartFillSchedule(
        theta=theta, c=c, a=a, durations=d, T=T,
        J=float(J), J_linear=float(J_lin),
    )
    return sched, WarmStart(lam=lam, bracket=bracket)


# ---------------------------------------------------------------------------
# Host-loop reference (pre-refactor implementation) — the test oracle for
# the device-resident solver.  Kept verbatim in structure: a Python loop
# over iterations with host-synced argmins and the original 512-point
# grid + grid-zoom μ* minimizer (the oracle the bracketed descent is
# differential-tested against).
# ---------------------------------------------------------------------------

_f_grid_jit = jax.jit(_f_grid)


def _minimize_f_ref(sp, c, a, k, W, B, coarse=512, zoom_rounds=4, zoom_pts=64):
    dtype = c.dtype
    lo = _mu_floor(jnp.asarray(B, dtype), dtype)
    # same de-duplicated top grid point as _minimize_f (a coincident pair
    # at B collapses the zoom bracket to [B−ulp, B])
    g1 = jnp.geomspace(lo, B, coarse // 2 + 1, dtype=dtype)[:-1]
    g2 = jnp.linspace(B / (coarse // 2), B, coarse // 2, dtype=dtype)
    mus = jnp.sort(jnp.concatenate([g1, g2]))
    vals = _f_grid_jit(sp, mus, c, a, k, W, B)
    i = int(jnp.nanargmin(vals))
    mu_lo = mus[max(i - 1, 0)]
    mu_hi = mus[min(i + 1, mus.shape[0] - 1)]
    for _ in range(zoom_rounds):
        mus = jnp.linspace(mu_lo, mu_hi, zoom_pts, dtype=dtype)
        vals = _f_grid_jit(sp, mus, c, a, k, W, B)
        i = int(jnp.nanargmin(vals))
        mu_lo = mus[max(i - 1, 0)]
        mu_hi = mus[min(i + 1, zoom_pts - 1)]
    return mus[i], vals[i]


def smartfill_reference(
    sp: Speedup,
    x,
    w,
    B: float | None = None,
    coarse: int = 512,
    zoom_rounds: int = 4,
    validate: bool = True,
) -> SmartFillSchedule:
    """Original host-loop SmartFill (one host sync per zoom round).

    Slow but independently simple; used by tests to pin down the
    device-resident solver and the batched API.  Accepts per-job speedup
    leaves (§7) in the given job order — the diagonal terms use job k's
    own s_k/s_k' via ``take_job`` (the identity for a shared speedup),
    which is what makes this the fixed-order oracle behind
    ``smartfill_hetero_reference``.
    """
    x = jnp.asarray(x, dtype=jnp.result_type(float))
    w = jnp.asarray(w, dtype=x.dtype)
    M = int(x.shape[0])
    B = float(sp.B if B is None else B)
    if validate:
        _validate_instance(x, w)

    c = jnp.zeros((M,), x.dtype).at[0].set(1.0)
    a = jnp.zeros((M,), x.dtype).at[0].set(
        w[0] / take_job(sp, 0).s(jnp.asarray(B, x.dtype)))
    theta = jnp.zeros((M, M), x.dtype).at[0, 0].set(B)

    for k in range(1, M):
        W = jnp.sum(w[: k + 1])
        mu, a_next = _minimize_f_ref(sp, c, a, k, W, B, coarse, zoom_rounds)
        active = jnp.arange(M) < k
        th_rest = solve_cap(sp, B - mu, c, active)  # (M,) padded
        theta = theta.at[:, k].set(jnp.where(active, th_rest, 0.0))
        theta = theta.at[k, k].set(mu)
        ds_prev = take_job(sp, k - 1).ds(th_rest[k - 1])
        c_next = c[k - 1] * take_job(sp, k).ds(mu) / ds_prev
        c = c.at[k].set(jnp.maximum(c_next, 1e-300))
        a = a.at[k].set(a_next)

    d, T = completion_times(sp, x, theta)
    J = objective(w, T)
    J_lin = jnp.sum(a * x)
    return SmartFillSchedule(
        theta=theta, c=c, a=a, durations=d, T=T,
        J=float(J), J_linear=float(J_lin),
    )


# ---------------------------------------------------------------------------
# Heterogeneous per-job speedups (paper §7): SmartFill + completion-order
# search.  Thm 10 keeps the CDR Rule alive under per-job s_i; the optimal
# completion *order* is open — we plan with SJF-by-normalized-size and
# refine with adjacent exchanges, and the host reference oracle can brute
# force the order on small instances to pin the heuristic down in tests.
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class HeteroSmartFillSchedule(SmartFillSchedule):
    """A SmartFillSchedule whose rows are a *searched* completion order.

    ``order[r]`` is the original job index occupying schedule row r
    (rows follow the SmartFill convention: row 0 completes last, row
    M−1 first).  theta/c/a/durations/T are all in row order; map back
    with ``T[np.argsort(order)]`` etc.
    """

    order: np.ndarray


def normalized_order(sp: Speedup, x, w, B: float | None = None) -> np.ndarray:
    """SJF-by-normalized-size completion order for per-job speedups.

    Jobs are ranked by solo full-server completion time x_i / s_i(B) —
    descending, ties by weight ascending — so the job that would finish
    first alone completes first (row M−1).  For a shared speedup this
    reduces to the paper's size order.  Host-side (concrete inputs).
    """
    x = np.asarray(x, dtype=np.float64)
    w = np.asarray(w, dtype=np.float64)
    M = x.shape[0]
    B = float(sp.B if B is None else B)
    rate = np.broadcast_to(
        np.asarray(sp.s(jnp.full((M,), B, jnp.result_type(float)))), (M,))
    t_solo = x / np.maximum(rate, 1e-300)
    return np.lexsort((w, -t_solo))


def _permute_speedup(sp, perm):
    """Reorder job-indexed leaves; shared (scalar) leaves untouched."""
    return jax.tree_util.tree_map(
        lambda l: l[jnp.asarray(perm)] if getattr(l, "ndim", 0) >= 1 else l,
        sp)


def _exchange_candidates(order, window):
    """All single-swap neighbours of ``order`` within pair distance ≤ window.

    Returns an (n_cand, M) index array — ``window=1`` gives the M−1
    adjacent swaps; larger windows add the non-adjacent pairs the
    adjacent-only search cannot reach in one step (non-agreeable
    instances stall on those — see ``examples/hetero_fleet.py``).  The
    candidate count depends only on (M, window), so the batched scorer
    compiles exactly once.
    """
    order = np.asarray(order)
    n = int(order.shape[0])
    cands = []
    for i in range(n - 1):
        for j in range(i + 1, min(i + int(window), n - 1) + 1):
            cand = order.copy()
            cand[i], cand[j] = cand[j], cand[i]
            cands.append(cand)
    if not cands:
        return np.zeros((0, n), dtype=order.dtype)
    return np.stack(cands)


def _exchange_descent(run, order, passes, window=1):
    """Steepest-descent exchange search on the completion order.

    ``run(perm) → (result, J)``.  Each step scores *every* swap within
    ``window`` and takes the single best one iff it improves J beyond a
    1e-10 relative margin; the step budget is ``passes·(M−1)`` (the same
    number of accepted swaps the historical first-improvement passes
    allowed).  One shared procedure for the device planner and the host
    reference — the differential suite compares their *searches*
    against the batched scorer, so selection must be argmin-first in
    both (``np.argmin``/``jnp.argmin`` both break ties at the first
    occurrence).
    """
    order = np.asarray(order)
    best, best_J = run(order)
    steps = max(int(passes), 0) * max(int(order.shape[0]) - 1, 1)
    for _ in range(steps):
        cands = _exchange_candidates(order, window)
        if cands.shape[0] == 0:
            break
        outs = []
        Js = np.empty(cands.shape[0])
        for t in range(cands.shape[0]):
            out, J = run(cands[t])
            outs.append(out)
            Js[t] = J if np.isfinite(J) else np.inf
        j = int(np.argmin(Js))
        if Js[j] < best_J * (1.0 - 1e-10):
            order, best, best_J = cands[j], outs[j], float(Js[j])
        else:
            break
    return order, best, best_J


def _exchange_descent_batched(run_one, score, order, passes, window):
    """Device-batched steepest-descent exchange search.

    Same search as ``_exchange_descent`` but each step scores all
    candidates in ONE vmapped solve — ``score(perms, lam0) → (J, lam)``
    over an (n_cand, M) permutation array — and reduces with a device
    ``argmin``, so a step costs a single fused host sync (winning index
    + accept flag in one transfer) instead of n_cand full round-trips,
    and no per-candidate J is ever materialized on host: the incumbent
    J stays a device scalar until the search returns.  λ* hints from
    the incumbent order warm-start every candidate (one swap barely
    moves λ*).  The final order is re-solved un-hinted through
    ``run_one`` so the returned schedule is bitwise identical to the
    sequential search's.
    """
    order = np.asarray(order)
    out = run_one(order)
    best_J = out[5]                     # device scalar — never synced alone
    lam0 = out[7]
    steps = max(int(passes), 0) * max(int(order.shape[0]) - 1, 1)
    moved = False
    for _ in range(steps):
        cands = _exchange_candidates(order, window)
        if cands.shape[0] == 0:
            break
        Js, lams = score(jnp.asarray(cands), lam0)
        Js = jnp.where(jnp.isfinite(Js), Js, jnp.inf)
        j_dev = jnp.argmin(Js)
        J_cand = Js[j_dev]
        accept = jnp.isfinite(J_cand) & (J_cand < best_J * (1.0 - 1e-10))
        j, acc = jax.device_get((j_dev, accept))    # the step's one sync
        if acc:
            order, best_J, lam0, moved = (cands[int(j)], J_cand,
                                          lams[j_dev], True)
        else:
            break
    if moved:
        out = run_one(order)
    return order, out, float(out[5])


def smartfill_hetero(
    sp: Speedup,
    x,
    w,
    B: float | None = None,
    coarse: int = 24,
    descent_iters: int = 40,
    cap_iters: int = 64,
    exchange_passes: int = 2,
    exchange_window: int = 1,
    batched_exchange: bool = True,
    fast_path: bool | None = None,
    stol_rel: float | None = None,
) -> HeteroSmartFillSchedule:
    """SmartFill with per-job speedup functions (paper §7), device-resident.

    Args:
      sp: per-job speedup — an ``(M,)``-leaved ``RegularSpeedup``, a
        ``StackedSpeedup`` (mixing σ=±1 families), or a shared speedup
        (then this reduces to ``smartfill`` on sorted inputs).
      x, w: (M,) job sizes / weights in **any** order — the completion
        order is part of the decision here, so unlike ``smartfill`` no
        pre-sorting is required (or meaningful).
      exchange_passes: exchange-search step budget over the
        SJF-by-normalized-size initial order, as a multiple of M−1
        steepest-descent steps.  Each step scores every swap within
        ``exchange_window`` and takes the single best improvement;
        0 disables the search and plans the heuristic order directly.
        The §7 optimal order is open — the exchange check certifies a
        local optimum, and ``smartfill_hetero_reference(search="brute")``
        pins it globally on small instances.
      exchange_window: maximum pair distance of a candidate swap.  1
        (default) is the classical adjacent exchange; k > 1 also scores
        the ~k·M non-adjacent pairs within distance k in the *same*
        vmapped call, which escapes the stalls adjacent-only search
        hits on non-agreeable instances.
      batched_exchange: score all candidates of a step in one vmapped
        ``_solve`` (device argmin, λ* warm-started from the incumbent
        order, two host syncs per step).  False falls back to the
        sequential per-candidate loop — the differential reference.
      stol_rel: optional override for the μ* descent's vertex-stability
        exit (see ``_solve``); ``core/classes.py`` passes ~1e-10 to meet
        its 1e-8 differential contract on C ≲ 64 aggregates.

    Returns a HeteroSmartFillSchedule; ``.order`` maps schedule rows
    back to the caller's job indices.

    Feasibility: an order the recursion cannot realize shows up as
    negative raw phase durations, which back-substitution clamps to 0 —
    inflating J strictly above the value-function claim J_linear =
    Σ a_i x_i.  The search objective is that executed J, so infeasible
    orders are naturally dispreferred, and ``J == J_linear`` (to fp) is
    the certificate that the returned order is realized exactly
    (Prop. 9 carried into §7); the differential suite pins that the
    exchange passes repair every heuristic-order infeasibility it
    samples.
    """
    x = jnp.asarray(x, dtype=jnp.result_type(float))
    w = jnp.asarray(w, dtype=x.dtype)
    M = int(x.shape[0])
    B = float(sp.B if B is None else B)
    for leaf in jax.tree_util.tree_leaves(sp):
        if getattr(leaf, "ndim", 0) >= 1 and leaf.shape[0] != M:
            raise ValueError(
                f"per-job speedup leaf has {leaf.shape[0]} entries for "
                f"{M} jobs")
    sp = collapse_homogeneous(sp)
    fast = _fast_ok(sp) and fast_path is not False

    def run_one(perm):
        p = jnp.asarray(perm)
        return _solve(_permute_speedup(sp, p), x[p], w[p], B, M,
                      coarse, descent_iters, cap_iters, fast,
                      stol_rel=stol_rel)

    init = normalized_order(sp, x, w, B)
    if batched_exchange and exchange_passes > 0 and M > 1:
        sp_axes = jax.tree_util.tree_map(
            lambda l: 0 if getattr(l, "ndim", 0) >= 1 else None, sp)

        def score(perms, lam0):
            spn = jax.tree_util.tree_map(
                lambda l: l[perms] if getattr(l, "ndim", 0) >= 1 else l, sp)
            out = jax.vmap(
                lambda spv, xv, wv: _solve(spv, xv, wv, B, M, coarse,
                                           descent_iters, cap_iters, fast,
                                           lam0, stol_rel=stol_rel),
                in_axes=(sp_axes, 0, 0))(spn, x[perms], w[perms])
            return out[5], out[7]

        order, best, _ = _exchange_descent_batched(
            run_one, score, init, exchange_passes, exchange_window)
    else:
        def run(perm):
            out = run_one(perm)
            return out, float(out[5])

        order, best, _ = _exchange_descent(
            run, init, exchange_passes, exchange_window)

    theta, c, a, d, T, J, J_lin, *_ = best
    return HeteroSmartFillSchedule(
        theta=theta, c=c, a=a, durations=d, T=T,
        J=float(J), J_linear=float(J_lin), order=np.asarray(order),
    )


def smartfill_hetero_reference(
    sp: Speedup,
    x,
    w,
    B: float | None = None,
    search: str = "auto",
    max_brute: int = 5,
    coarse: int = 512,
    zoom_rounds: int = 4,
    exchange_passes: int = 2,
    exchange_window: int = 1,
) -> HeteroSmartFillSchedule:
    """Host-loop oracle for heterogeneous SmartFill.

    Runs the (per-job-generalized) original host recursion
    ``smartfill_reference`` over candidate completion orders and keeps
    the best J:

      * ``search="brute"`` (or "auto" with M ≤ ``max_brute``) tries
        **every** permutation — the order ground truth on small
        instances;
      * otherwise the same SJF-by-normalized-size + adjacent-exchange
        descent as the device planner, but driven by the independent
        host solver.

    The differential tests pin ``smartfill_hetero`` against this on
    mixed-family instances (tests/core/test_hetero.py).
    """
    import itertools

    x = np.asarray(x, dtype=np.float64)
    w = np.asarray(w, dtype=np.float64)
    M = x.shape[0]
    B = float(sp.B if B is None else B)
    sp = collapse_homogeneous(sp)

    def run(perm):
        perm = np.asarray(perm)
        sched = smartfill_reference(
            _permute_speedup(sp, perm), x[perm], w[perm], B=B,
            coarse=coarse, zoom_rounds=zoom_rounds, validate=False)
        return sched, float(sched.J)

    if search not in ("auto", "brute", "exchange"):
        raise ValueError("search must be 'auto', 'brute' or 'exchange'")
    brute = search == "brute" or (search == "auto" and M <= max_brute)
    if brute:
        best, best_J, order = None, np.inf, None
        for perm in itertools.permutations(range(M)):
            sched, J = run(perm)
            if np.isfinite(J) and J < best_J:
                best, best_J, order = sched, J, np.asarray(perm)
    else:
        order, best, _ = _exchange_descent(
            run, normalized_order(sp, x, w, B), exchange_passes,
            exchange_window)

    return HeteroSmartFillSchedule(
        theta=best.theta, c=best.c, a=best.a, durations=best.durations,
        T=best.T, J=best.J, J_linear=best.J_linear,
        order=np.asarray(order),
    )
