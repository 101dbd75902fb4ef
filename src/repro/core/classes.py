"""Class-aggregated planning: millions of jobs as dozens of classes.

Berg et al., *Asymptotically Optimal Scheduling of Multiple
Parallelizable Job Classes* (arXiv 2404.00346), show the optimal policy
concentrates on job **classes** in the many-jobs limit.  This module is
that limit made operational for the paper's SmartFill machinery: a
class is (job count n_c, representative remaining size x_c, per-job
weight w_c, a Table-1 speedup family), and planning happens over C ≲ 64
class aggregates instead of M = Σ n_c (up to 10⁶) per-job rows.

The whole layer rests on one exact identity.  Splitting a class's
bandwidth Θ_c equally over its n_c identical jobs (the symmetric
optimum — the jobs are exchangeable, s_c is concave) serves aggregate
work at

    S_c(Θ) = n_c · s_c(Θ / n_c),

and for the regular family s_c'(θ) = A (w + σθ)^γ the aggregate's
derivative is

    S_c'(Θ) = s_c'(Θ / n_c) = A (w + σΘ/n_c)^γ = A n_c^{−γ} (n_c w + σΘ)^γ

— the **same family** with A → A·n_c^{−γ} and w → n_c·w (γ, σ
unchanged; both sides vanish at Θ = 0, so the antiderivatives agree
too, including the γ = −1 log branch where A → A·n_c).  So a class
instance *is* a §7 heterogeneous instance over aggregates

    X_c = n_c x_c,   W_c = n_c w_c,   sp_agg = class_speedup(sp, n),

and ``plan_classes`` is ``smartfill_hetero`` verbatim — same sorted
per-job CAP (``hetero_prepare``/``hetero_solve``), same μ* descent,
same exchange order search — at C rows.  At n_c = 1 the transform is
the identity, which is what makes the convergence contract of
``tests/core/test_classes.py`` (class plan ≡ per-job plan at one job
per class) hold by construction rather than approximation.

All jobs of a class complete simultaneously at the class completion
time T_c, so the per-job objective is recovered exactly:

    J = Σ_c n_c w_c T_c = Σ_c W_c T_c  (the aggregate plan's own J).

``plan_classes_reference`` is the host-loop oracle — an independent
pure-numpy SmartFill recursion (λ-bisection CAP, grid + golden-section
μ*), no jax, no jit — that the differential suite pins the device
solver against.

Zero-count classes are inert: they are stripped before the solve and
scattered back as T = 0 / θ = 0 rows, so callers can keep a fixed
C-slot layout while classes drain to empty.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from . import fmath

from .smartfill import (HeteroSmartFillSchedule, _permute_speedup,
                        smartfill_hetero)
from .speedup import RegularSpeedup, Speedup, StackedSpeedup, is_per_job

__all__ = [
    "ClassState",
    "ClassPlan",
    "class_speedup",
    "aggregate_classes",
    "compact_aggregate_batch",
    "plan_classes",
    "plan_classes_batched",
    "expand_classes",
    "plan_classes_reference",
]


# ---------------------------------------------------------------------------
# State representation
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ClassState:
    """C job classes: counts, a size summary, per-job weights, families.

    counts[c] is the number of jobs in class c (0 ⇒ the class is inert;
    fractional counts are allowed — the fluid simulator drains counts
    continuously).  sizes[c] summarizes the class's remaining-size
    distribution by its per-job remaining work (jobs within a class are
    exchangeable, so under the symmetric allocation only the total
    n_c·x_c enters the plan).  ``sp`` holds one speedup family per class
    — (C,)-leaved ``RegularSpeedup``/``StackedSpeedup`` by the §7
    per-job-leaf convention — or a shared scalar-leaf family.
    """

    counts: np.ndarray       # (C,) jobs per class, ≥ 0
    sizes: np.ndarray        # (C,) per-job remaining size x_c > 0
    weights: np.ndarray      # (C,) per-job weight w_c ≥ 0
    sp: Speedup              # per-class (C,) leaves or shared
    B: float

    def __post_init__(self):
        counts = np.asarray(self.counts, dtype=np.float64)
        sizes = np.asarray(self.sizes, dtype=np.float64)
        weights = np.asarray(self.weights, dtype=np.float64)
        if not (counts.shape == sizes.shape == weights.shape):
            raise ValueError("counts, sizes and weights must all be (C,)")
        if counts.ndim != 1:
            raise ValueError("ClassState is single-instance: arrays are (C,)")
        if np.any(counts < 0):
            raise ValueError("class counts must be ≥ 0")
        if np.any(sizes[counts > 0] <= 0):
            raise ValueError("live classes need positive sizes")
        object.__setattr__(self, "counts", counts)
        object.__setattr__(self, "sizes", sizes)
        object.__setattr__(self, "weights", weights)
        object.__setattr__(self, "B", float(self.B))

    @property
    def C(self) -> int:
        return int(self.counts.shape[0])

    @property
    def jobs(self) -> float:
        """Total job count M = Σ n_c (float — fluid counts drain)."""
        return float(np.sum(self.counts))


@dataclasses.dataclass(frozen=True)
class ClassPlan:
    """Class-aggregated SmartFill plan, scattered back to C slots.

    T[c] is class c's completion time (all n_c jobs finish together;
    0 for empty classes); theta[c] the class's *aggregate* bandwidth in
    the earliest phase (t = 0, everything active) and theta_job[c] the
    per-job share theta[c] / n_c.  ``order[r]`` is the class index
    occupying schedule row r (live classes only; row 0 completes last).
    J = Σ_c n_c w_c T_c over all jobs; J_linear is the value-function
    certificate Σ a_c X_c (Prop. 9 over aggregates — equals J iff the
    order was realized exactly).  ``sched`` is the underlying
    live-class ``HeteroSmartFillSchedule`` (None for the host oracle).
    """

    counts: np.ndarray
    T: np.ndarray
    theta: np.ndarray
    theta_job: np.ndarray
    order: np.ndarray
    J: float
    J_linear: float
    sched: HeteroSmartFillSchedule | None = None


# ---------------------------------------------------------------------------
# The aggregation transform
# ---------------------------------------------------------------------------

def class_speedup(sp: Speedup, counts) -> Speedup:
    """Aggregate speedup S_c(Θ) = n_c·s_c(Θ/n_c), exactly in-family.

    Maps a per-class (or shared) regular-family speedup to the class
    aggregate via A → A·n^{−γ}, w → n·w (γ and σ unchanged) — see the
    module docstring for the two-line proof.  Zero counts substitute
    n = 1 (the identity transform) so inert classes keep valid family
    parameters; n = 1 classes are untouched bit-for-bit, which is the
    convergence anchor.  Broadcasts against ``counts``' shape, so (K, C)
    count arrays batch per instance.

    Only the closed-form families aggregate in-family; a
    ``GenericSpeedup`` has no parametrization to transform and raises.
    """
    counts = jnp.asarray(counts, jnp.result_type(float))
    n = jnp.where(counts > 0, counts, 1.0)
    if isinstance(sp, RegularSpeedup):
        gamma = jnp.broadcast_to(jnp.asarray(sp.gamma, n.dtype), n.shape)
        return RegularSpeedup(
            A=jnp.asarray(sp.A, n.dtype) * fmath.power(n, -gamma),
            w=jnp.asarray(sp.w, n.dtype) * n,
            gamma=gamma, sigma=sp.sigma, B=sp.B)
    if isinstance(sp, StackedSpeedup):
        gamma = jnp.broadcast_to(jnp.asarray(sp.gamma, n.dtype), n.shape)
        return StackedSpeedup(
            A=jnp.asarray(sp.A, n.dtype) * fmath.power(n, -gamma),
            w=jnp.asarray(sp.w, n.dtype) * n,
            gamma=gamma,
            sigma=jnp.broadcast_to(jnp.asarray(sp.sigma, n.dtype), n.shape),
            B=sp.B)
    raise TypeError(
        f"class aggregation needs a regular-family speedup "
        f"(RegularSpeedup/StackedSpeedup), got {type(sp).__name__}: the "
        f"n·s(Θ/n) aggregate of a GenericSpeedup has no parameters to "
        f"transform — wrap it per class via its own closure instead")


def aggregate_classes(state: ClassState):
    """(sp_agg, X, W): the §7 heterogeneous instance over aggregates.

    X_c = n_c·x_c and W_c = n_c·w_c are exact zeros for empty classes —
    the padding convention of the batched planners, so aggregates feed
    ``smartfill_batched``/fleet paths directly.
    """
    sp_agg = class_speedup(state.sp, state.counts)
    X = jnp.asarray(state.counts * state.sizes)
    W = jnp.asarray(state.counts * state.weights)
    return sp_agg, X, W


def expand_classes(state: ClassState):
    """Materialize the per-job instance: (x, w, sp_jobs, class_id).

    The differential harness's bridge: M = Σ n_c rows, class c
    contributing n_c identical jobs under its own family.  Counts must
    be integral (the fluid path has no per-job materialization).
    """
    counts = np.asarray(state.counts)
    if np.any(np.abs(counts - np.round(counts)) > 1e-9):
        raise ValueError("expand_classes needs integral counts")
    reps = np.round(counts).astype(int)
    class_id = np.repeat(np.arange(state.C), reps)
    x = np.repeat(state.sizes, reps)
    w = np.repeat(state.weights, reps)
    if is_per_job(state.sp):
        sp_jobs = jax.tree_util.tree_map(
            lambda l: jnp.asarray(np.repeat(np.asarray(l), reps, axis=0))
            if getattr(l, "ndim", 0) >= 1 else l,
            state.sp)
    else:
        sp_jobs = state.sp
    return x, w, sp_jobs, class_id


# ---------------------------------------------------------------------------
# Device planner
# ---------------------------------------------------------------------------

def plan_classes(
    state: ClassState,
    B: float | None = None,
    *,
    coarse: int = 64,
    descent_iters: int = 96,
    cap_iters: int = 64,
    exchange_passes: int = 2,
    exchange_window: int = 1,
    stol_rel: float | None = 1e-10,
) -> ClassPlan:
    """SmartFill over class aggregates — M = Σ n_c jobs as C rows.

    Strips empty classes, aggregates the rest (``class_speedup`` + X/W
    products) and runs the §7 heterogeneous planner
    (``smartfill_hetero`` — sorted per-job CAP, μ* descent, exchange
    order search) on the C_live-row instance.  The μ* precision knobs
    default tighter than the per-job planner's (``stol_rel=1e-10`` with
    the descent budget to use it, and a ``coarse=64`` localization grid
    matching the reference oracle's — F(μ) can be multimodal, and a
    coarser grid sometimes localizes a worse basin): C ≲ 64 rows make
    the extra work nearly free, and the 1e-8 differential contract
    against ``plan_classes_reference`` is linearly sensitive to μ*
    wherever durations clamp.  Results scatter back to
    the caller's C-slot layout; empty classes come back inert (T = 0,
    θ = 0).  All knobs pass through to ``smartfill_hetero``.
    """
    counts = np.asarray(state.counts, dtype=np.float64)
    C = counts.shape[0]
    B = float(state.B if B is None else B)
    live = np.flatnonzero(counts > 0)
    T = np.zeros(C)
    theta0 = np.zeros(C)
    if live.size == 0:
        return ClassPlan(counts=counts, T=T, theta=theta0,
                         theta_job=np.zeros(C),
                         order=np.zeros(0, dtype=int),
                         J=0.0, J_linear=0.0, sched=None)
    n_l = counts[live]
    sp_l = class_speedup(_permute_speedup(state.sp, live), n_l)
    X_l = n_l * state.sizes[live]
    W_l = n_l * state.weights[live]
    sched = smartfill_hetero(
        sp_l, X_l, W_l, B=B, coarse=coarse, descent_iters=descent_iters,
        cap_iters=cap_iters, exchange_passes=exchange_passes,
        exchange_window=exchange_window, stol_rel=stol_rel)
    order_cls = live[sched.order]           # schedule row r → class index
    T[order_cls] = np.asarray(sched.T)
    theta0[order_cls] = np.asarray(sched.theta[:, -1])
    n_safe = np.where(counts > 0, counts, 1.0)
    return ClassPlan(counts=counts, T=T, theta=theta0,
                     theta_job=theta0 / n_safe, order=order_cls,
                     J=float(sched.J), J_linear=float(sched.J_linear),
                     sched=sched)


def plan_classes_batched(counts, sizes, weights, sp, B=None, **kwargs):
    """K class instances planned in one batched device call.

    The fleet front door for class aggregates: per-instance, live
    classes are compacted to a prefix (the batched planners' padding
    convention — empty classes become exact-zero suffix rows), the
    aggregation transform is applied elementwise on the (K, C) leaves,
    and the whole batch goes through ``smartfill_hetero_batched`` (per
    -instance normalized-size order + one vmapped solve).

    Returns ``(orders, sched)`` exactly like ``smartfill_hetero_batched``
    — ``orders[k][r]`` is the original *class slot* of instance k in
    schedule row r (empty classes occupy the trailing rows), ``sched``
    the live-prefix ``BatchedSmartFillSchedule`` over aggregates (J is
    already the per-job objective Σ n_c w_c T_c).

    μ* precision defaults to ``plan_classes``'s tight knobs
    (``stol_rel=1e-10``, ``descent_iters=96``) rather than the batched
    planner's — same rationale, and it keeps the batched/sharded/single
    paths comparable at solver precision.
    """
    from .batch import smartfill_hetero_batched

    if B is None:
        B = sp.B
    kwargs.setdefault("coarse", 64)
    kwargs.setdefault("descent_iters", 96)
    kwargs.setdefault("stol_rel", 1e-10)
    perm, sp_agg, X, W = compact_aggregate_batch(counts, sizes, weights, sp)
    orders, sched = smartfill_hetero_batched(sp_agg, X, W, B=B, **kwargs)
    # compose: schedule row r → compacted slot orders[k, r] → class slot
    orders = np.take_along_axis(perm, orders, axis=1)
    return orders, sched


def compact_aggregate_batch(counts, sizes, weights, sp):
    """Host-side prep shared by the batched and fleet-sharded planners.

    Per instance, live classes are compacted to a prefix (the batched
    planners' padding convention — empty classes become exact-zero
    suffix rows) and the aggregation transform is applied elementwise
    on the (K, C) leaves.  Returns ``(perm, sp_agg, X, W)`` where
    ``perm[k]`` is the live-first compaction permutation of instance k
    and X/W are the aggregate sizes/weights with zero padding.
    """
    counts = np.asarray(counts, dtype=np.float64)
    sizes = np.asarray(sizes, dtype=np.float64)
    weights = np.asarray(weights, dtype=np.float64)
    if counts.ndim != 2:
        raise ValueError("class batches are (K, C) arrays")
    K, C = counts.shape
    # stable live-first compaction per instance (argsort of the "empty"
    # flag keeps relative order within both groups)
    perm = np.argsort(counts <= 0, axis=1, kind="stable")
    n_p = np.take_along_axis(counts, perm, axis=1)
    x_p = np.take_along_axis(sizes, perm, axis=1)
    w_p = np.take_along_axis(weights, perm, axis=1)

    def permute_leaf(l):
        arr = np.asarray(l)
        if arr.ndim >= 2 and arr.shape[:2] == (K, C):
            return jnp.asarray(np.take_along_axis(arr, perm, axis=1))
        if arr.ndim == 1 and arr.shape[0] == C:
            return jnp.asarray(np.asarray(l)[perm])  # shared → per-instance
        return l

    sp_p = jax.tree_util.tree_map(permute_leaf, sp)
    sp_agg = class_speedup(sp_p, jnp.asarray(n_p))
    live = n_p > 0
    X = np.where(live, n_p * x_p, 0.0)
    W = np.where(live, n_p * w_p, 0.0)
    return perm, sp_agg, X, W


# ---------------------------------------------------------------------------
# Host-loop oracle: pure numpy, no jax, no jit
# ---------------------------------------------------------------------------

def _np_family(sp: Speedup, C: int):
    """(A, w, γ, σ) as (C,) float64 numpy arrays; rejects non-regular."""
    if isinstance(sp, RegularSpeedup):
        sigma = np.full(C, float(sp.sigma))
    elif isinstance(sp, StackedSpeedup):
        sigma = np.broadcast_to(np.asarray(sp.sigma, np.float64), (C,))
    else:
        raise TypeError(
            f"plan_classes_reference needs a regular-family speedup, got "
            f"{type(sp).__name__}")
    A = np.broadcast_to(np.asarray(sp.A, np.float64), (C,)).copy()
    w = np.broadcast_to(np.asarray(sp.w, np.float64), (C,)).copy()
    g = np.broadcast_to(np.asarray(sp.gamma, np.float64), (C,)).copy()
    return A, w, g, np.asarray(sigma, np.float64).copy()


def _np_ds(A, w, g, sg, th):
    return A * (w + sg * th) ** g


def _np_s(A, w, g, sg, th):
    base = w + sg * th
    g1 = g + 1.0
    is_log = np.abs(g1) < 1e-12
    with np.errstate(divide="ignore", invalid="ignore", over="ignore"):
        w_safe = np.where(w > 0, w, 1.0)
        log_b = (A / sg) * (np.log(np.maximum(base, 1e-300))
                            - np.log(w_safe))
        g1s = np.where(is_log, 1.0, g1)
        pow_b = (A / (sg * g1s)) * (base ** g1s - w ** g1s)
    return np.where(is_log, log_b, pow_b)


def _np_ds_inv(A, w, g, sg, y):
    with np.errstate(over="ignore", invalid="ignore", divide="ignore"):
        out = sg * ((y / A) ** (1.0 / g) - w)
    # an overflowed (y/A)^{1/γ} means "θ beyond any budget", not "parked"
    # — keep the sign so the caller's [0, b] clip lands on the right edge
    return np.nan_to_num(out, nan=0.0, posinf=1e300, neginf=-1e300)


def _np_cap(A, w, g, sg, c, b, iters: int = 160):
    """CAP by λ-bisection: θ_i = (ds_inv_i(λ c_i))₊ with Σ θ = b.

    s_i'(θ_i)/c_i is one constant λ over the jobs with θ_i > 0 and
    every parked job has s_i'(0)/c_i ≤ λ (conditions (9a)–(9d)); the
    total allocation is strictly decreasing in λ, so log-space
    bisection over an astronomically wide bracket converges to f64
    exactness in ~160 halvings.  O(k) per probe — host-loop grade.
    """
    lo, hi = -690.0, 690.0              # ln λ: e^±690 spans all of f64
    for _ in range(iters):
        mid = 0.5 * (lo + hi)
        th = np.clip(_np_ds_inv(A, w, g, sg, np.exp(mid) * c), 0.0, b)
        if th.sum() > b:
            lo = mid
        else:
            hi = mid
    th = np.clip(_np_ds_inv(A, w, g, sg, np.exp(0.5 * (lo + hi)) * c),
                 0.0, b)
    total = th.sum()
    if total > 0:                       # exact budget on the live support
        th = th * (b / total)
    return th


def _np_minimize(F, B, coarse: int = 64, golden_iters: int = 120):
    """Grid-localized golden-section argmin of F on (0, B] (host mirror
    of ``smartfill._minimize_f``, run to f64 exactness)."""
    invphi, invphi2 = 0.6180339887498949, 0.3819660112501051
    fi = np.finfo(np.float64)
    lo_edge = max(B * 1e-9, fi.tiny / fi.eps)
    g1 = np.geomspace(lo_edge, B, coarse // 2 + 1)[:-1]
    g2 = np.linspace(B / (coarse // 2), B, coarse // 2)
    mus = np.sort(np.concatenate([g1, g2]))
    vals = np.array([F(mu) for mu in mus])
    finite = np.isfinite(vals)
    if not finite.any():
        return B, np.inf
    i = int(np.argmin(np.where(finite, vals, np.inf)))
    best_mu, best_val = mus[i], vals[i]
    lo, hi = mus[max(i - 1, 0)], mus[min(i + 1, len(mus) - 1)]
    x1 = lo + invphi2 * (hi - lo)
    x2 = lo + invphi * (hi - lo)
    f1, f2 = F(x1), F(x2)
    fin = lambda v: v if np.isfinite(v) else np.inf   # NaN → +inf
    for _ in range(golden_iters):
        if fin(f1) <= fin(f2):
            hi, x2, f2 = x2, x1, f1
            x1 = lo + invphi2 * (hi - lo)
            f1 = F(x1)
        else:
            lo, x1, f1 = x1, x2, f2
            x2 = lo + invphi * (hi - lo)
            f2 = F(x2)
    for mu, val in ((x1, f1), (x2, f2)):
        if np.isfinite(val) and val < best_val:
            best_mu, best_val = mu, val
    return float(best_mu), float(best_val)


def plan_classes_reference(
    state: ClassState,
    B: float | None = None,
    order=None,
    *,
    coarse: int = 64,
    golden_iters: int = 120,
) -> ClassPlan:
    """Host-loop class water-filler: the differential oracle.

    An independent pure-numpy implementation of the SmartFill recursion
    over class aggregates — python ``for`` over k, λ-bisection CAP,
    grid + golden-section μ* — sharing **no** code with the device
    solver (no jax, no jit).  Solves the given completion ``order``
    (class indices, schedule-row order, live classes only; default:
    SJF by normalized aggregate size, the device planner's initial
    heuristic — pass the device plan's ``.order`` to pin its searched
    order).  Empty classes are inert exactly as in ``plan_classes``.
    """
    counts = np.asarray(state.counts, dtype=np.float64)
    C = counts.shape[0]
    B = float(state.B if B is None else B)
    live = np.flatnonzero(counts > 0)
    if live.size == 0:
        return ClassPlan(counts=counts, T=np.zeros(C), theta=np.zeros(C),
                         theta_job=np.zeros(C), order=np.zeros(0, int),
                         J=0.0, J_linear=0.0, sched=None)
    n_l = counts[live]
    A, wsh, g, sg = _np_family(_permute_speedup(state.sp, live),
                               live.size)
    A = A * n_l ** (-g)                 # the aggregation transform
    wsh = wsh * n_l
    X = n_l * state.sizes[live]
    W = n_l * state.weights[live]
    if order is None:
        with np.errstate(divide="ignore"):
            t_solo = X / np.maximum(_np_s(A, wsh, g, sg, np.full(live.size, B)),
                                    1e-300)
        rows = np.lexsort((W, -t_solo))     # positions into `live`
        order_cls = live[rows]
    else:
        order_cls = np.asarray(order, dtype=int)
        pos = {int(cl): i for i, cl in enumerate(live)}
        rows = np.array([pos[int(cl)] for cl in order_cls], dtype=int)
    k_live = rows.size
    A, wsh, g, sg = A[rows], wsh[rows], g[rows], sg[rows]
    Xo, Wo = X[rows], W[rows]

    # SmartFill recursion k = 0..k_live−1 (host loop, eqs. (28)/(29))
    c = np.zeros(k_live)
    a = np.zeros(k_live)
    theta = np.zeros((k_live, k_live))
    c[0] = 1.0
    a[0] = Wo[0] / _np_s(A[:1], wsh[:1], g[:1], sg[:1],
                         np.array([B]))[0]
    theta[0, 0] = B
    for k in range(1, k_live):
        Ak, wk, gk, sk = A[:k], wsh[:k], g[:k], sg[:k]
        Wk = Wo[: k + 1].sum()

        def F(mu):
            th = _np_cap(Ak, wk, gk, sk, c[:k], B - mu)
            served = (a[:k] * _np_s(Ak, wk, gk, sk, th)).sum()
            s_new = _np_s(A[k : k + 1], wsh[k : k + 1], g[k : k + 1],
                          sg[k : k + 1], np.array([mu]))[0]
            return (Wk - served) / s_new

        mu, a_next = _np_minimize(F, B, coarse=coarse,
                                  golden_iters=golden_iters)
        th = _np_cap(Ak, wk, gk, sk, c[:k], B - mu)
        theta[:k, k] = th
        theta[k, k] = mu
        a[k] = a_next
        ds_prev = _np_ds(A[k - 1 : k], wsh[k - 1 : k], g[k - 1 : k],
                         sg[k - 1 : k], np.array([th[k - 1]]))[0]
        ds_new = _np_ds(A[k : k + 1], wsh[k : k + 1], g[k : k + 1],
                        sg[k : k + 1], np.array([mu]))[0]
        c[k] = max(c[k - 1] * ds_new / ds_prev, 1e-300)

    # back-substitute durations: X = R d, R[j, m] = S_j(Θ[j, m]), m ≥ j
    rate = _np_s(A[:, None], wsh[:, None], g[:, None], sg[:, None], theta)
    d = np.zeros(k_live)
    for j in range(k_live - 1, -1, -1):
        acc = Xo[j] - rate[j, j + 1 :] @ d[j + 1 :]
        d[j] = max(acc / rate[j, j], 0.0)
    T_rows = np.cumsum(d[::-1])[::-1]
    J = float(Wo @ T_rows)
    J_lin = float(a @ Xo)

    T = np.zeros(C)
    theta0 = np.zeros(C)
    T[order_cls] = T_rows
    theta0[order_cls] = theta[:, -1]
    n_safe = np.where(counts > 0, counts, 1.0)
    return ClassPlan(counts=counts, T=T, theta=theta0,
                     theta_job=theta0 / n_safe, order=order_cls,
                     J=J, J_linear=J_lin, sched=None)
