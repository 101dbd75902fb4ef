"""Policy zoo for the scenario engine — device-resident allocators.

Every policy is a frozen-dataclass pytree implementing one interface,

    policy(rem, w, active, B=None) → (M,) allocations θ with
    Σ over active ≤ B,

in pure jnp ops, so policies are swappable inside the engine's
``lax.scan`` (``core/simulator.py``) and batchable under ``jax.vmap``
(``simulate_ensemble``).  The optional 4th argument is the *current*
budget under dynamic-budget (fault-aware) execution: ``None`` (the
default, and the only form the legacy engine uses) means "spend your
own ``B``"; a traced value B(t) overrides it for this event, so
re-planning policies re-solve under the live budget and cached plans
(``HeteroSmartFillPolicy.pinned(cache_plan=True)``) invalidate and
re-solve instead of executing a stale table.  All numeric parameters — the speedup function,
B, heSRPT's exponent, static constants — are pytree *children*, so any
of them can carry a leading (K,) workload dimension and be vmapped per
instance by the ensemble runner (e.g. per-workload budgets or fitted
exponents); only structural knobs (grid sizes, the resolved fast-path
flag) are static aux data.  The budget a policy spends is **its own
``B``** — the engine executes whatever the policy allocates.

The zoo covers the paper's §6 comparison set:

  * ``SmartFillPolicy`` — re-plans the OPT solution (Algorithm 2) on the
    remaining sizes at every event; by Prop. 7 this reproduces the
    one-shot schedule exactly (time consistency).
  * ``HeSRPTPolicy``  — Berg et al.'s closed form for s = aθ^p, applied
    (exactly, or as the paper's approximation-based benchmark) under
    any true speedup.
  * ``EquiPolicy``    — EQUI: B/m to each active job.
  * ``SRPT1Policy``   — single-server SRPT: everything to the smallest
    remaining job (the p → 1 limit of heSRPT).
  * ``GWFStaticPolicy`` — water-fills with *static* derivative-ratio
    constants (default: proportional to weights) each event; the
    ablation showing the value of SmartFill's carried CDR constants.

Heterogeneous fleets (paper §7) add two members:

  * ``HeteroSmartFillPolicy`` — re-planning SmartFill for *per-job*
    speedup functions: active jobs are re-ranked by normalized remaining
    size (rem_i / s_i(B)) each event and solved with the job-indexed
    solver core.  The speedup's job-indexed leaves are aligned with the
    engine's job slots; (K, M) leaves batch per workload as usual.
  * ``WeightedMarginalRatePolicy`` — the *retired* pre-§7 heterogeneity
    heuristic, kept as a named baseline: equalize the weighted marginal
    rate (w_i/rem_i)·s_i'(θ_i) across active jobs by water-filling with
    static constants c_i ∝ rem_i/w_i under each job's own s_i.  It has
    no value-function recursion and no completion-order structure —
    exactly what hetero SmartFill adds — and the differential suite
    pins that SmartFill's J beats it on most mixed-family instances.

All policies tolerate padded jobs (``active`` False ⇒ θ = 0) and an
empty active set (θ ≡ 0), which the engine's halt steps rely on.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import fmath

from repro.core.gwf import (solve_cap, solve_cap_hetero,
                            solve_cap_hetero_sorted)
from repro.core.smartfill import (WarmStart, _fast_ok, _is_pure_power,
                                  _solve, _uses_sorted_cap)
from repro.core.speedup import Speedup, collapse_homogeneous, is_per_job

__all__ = [
    "Policy",
    "SmartFillPolicy",
    "HeteroSmartFillPolicy",
    "ClassSmartFillPolicy",
    "StreamingSmartFillPolicy",
    "StreamCascadePolicy",
    "StreamPlan",
    "stream_replan_core",
    "HeSRPTPolicy",
    "EquiPolicy",
    "SRPT1Policy",
    "GWFStaticPolicy",
    "WeightedMarginalRatePolicy",
    "default_zoo",
]

_TINY = 1e-300


def _active_order(rem, w, active):
    """Permutation putting active jobs first, sorted the SmartFill way:
    remaining size non-increasing, ties by weight non-decreasing."""
    key = jnp.where(active, -rem, jnp.inf)
    return jnp.lexsort((w, key))


class Policy:
    """Marker base: the engine dispatches on ``device_ready``."""

    device_ready = True
    name = "policy"

    def __call__(self, rem, w, active, B=None):
        raise NotImplementedError

    def _budget(self, B):
        """The budget to spend this event: the engine-supplied B(t)
        under fault-aware execution, else the policy's own B."""
        return self.B if B is None else B


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class EquiPolicy(Policy):
    """EQUI: split B evenly over the active jobs."""

    B: float
    name = "EQUI"

    def tree_flatten(self):
        return (self.B,), ()

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(B=children[0])

    def __call__(self, rem, w, active, B=None):
        m = jnp.sum(active)
        share = self._budget(B) / jnp.maximum(m, 1)
        return jnp.where(active, share, 0.0)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class SRPT1Policy(Policy):
    """SRPT-1: the whole budget to the smallest remaining active job."""

    B: float
    name = "SRPT-1"

    def tree_flatten(self):
        return (self.B,), ()

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(B=children[0])

    def __call__(self, rem, w, active, B=None):
        key = jnp.where(active, rem, jnp.inf)
        i = jnp.argmin(key)
        out = jnp.zeros_like(rem).at[i].set(
            jnp.asarray(self._budget(B), rem.dtype))
        return jnp.where(active, out, 0.0)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class HeSRPTPolicy(Policy):
    """Berg et al. closed form: θ_i/B = (W_i^m − W_{i−1}^m)/W_k^m,
    m = 1/(1−p), over active jobs ranked by remaining size (desc)."""

    p: float
    B: float
    name = "heSRPT"

    def tree_flatten(self):
        return (self.p, self.B), ()

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(p=children[0], B=children[1])

    def __call__(self, rem, w, active, B=None):
        M = rem.shape[0]
        order = _active_order(rem, w, active)
        ws = jnp.where(active, w, 0.0)[order]
        # shares depend only on weight *ratios* — normalize so the
        # cumsum powers cannot underflow (w ~ 1e-10 slowdown weights
        # raised to 1/(1−p) would flush to 0 in float32)
        ws = ws / jnp.maximum(jnp.max(ws), _TINY)
        m = jnp.sum(active)
        mexp = 1.0 / (1.0 - self.p)
        Wc = jnp.cumsum(ws)
        Wm = fmath.power(jnp.maximum(Wc, 0.0), mexp)
        Wm_prev = jnp.concatenate([jnp.zeros((1,), Wm.dtype), Wm[:-1]])
        Wk = Wm[jnp.maximum(m - 1, 0)]
        shares = self._budget(B) * (Wm - Wm_prev) / jnp.maximum(Wk, _TINY)
        shares = jnp.where(jnp.arange(M) < m, shares, 0.0)
        out = jnp.zeros_like(rem).at[order].set(shares)
        return jnp.where(active, out, 0.0)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class SmartFillPolicy(Policy):
    """Re-planning SmartFill: the optimal allocation for the current
    remaining sizes — column m−1 of Algorithm 2 run on (rem, w).

    ``fast`` is resolved at construction (host side, where the speedup's
    parameters are concrete) so the closed-form μ* path survives
    jit/vmap round-trips, where ``sp``'s leaves become tracers.
    """

    sp: Speedup
    B: float
    coarse: int = 32
    descent_iters: int = 40
    cap_iters: int = 64
    fast: bool | None = None
    name = "SmartFill"

    def __post_init__(self):
        if self.fast is None:
            object.__setattr__(self, "fast", _is_pure_power(self.sp))

    def tree_flatten(self):
        return (self.sp, self.B), (self.coarse, self.descent_iters,
                                   self.cap_iters, self.fast)

    @classmethod
    def tree_unflatten(cls, aux, children):
        coarse, descent_iters, cap_iters, fast = aux
        return cls(sp=children[0], B=children[1], coarse=coarse,
                   descent_iters=descent_iters, cap_iters=cap_iters,
                   fast=fast)

    def __call__(self, rem, w, active, B=None):
        from repro.core.speedup import is_per_job

        M = rem.shape[0]
        order = _active_order(rem, w, active)
        xs = jnp.where(active, rem, 0.0)[order]
        ws = jnp.where(active, w, 0.0)[order]
        m = jnp.sum(active)
        # ``fast`` was resolved at construction, where a 1-D leaf could
        # be per-workload (K,) — scalar per lane once the ensemble
        # runner vmaps, fast stays valid — or per-job (M,).  Here, past
        # any vmap, leaf shape tells them apart statically: job-indexed
        # leaves invalidate the shared-exponent closed form (use
        # HeteroSmartFillPolicy for those — this guard just makes the
        # mistake safe).
        fast = bool(self.fast) and not is_per_job(self.sp)
        theta, *_ = _solve(self.sp, xs, ws,
                           jnp.asarray(self._budget(B), xs.dtype),
                           m, self.coarse, self.descent_iters,
                           self.cap_iters, fast, with_times=False)
        col = jnp.take(theta, jnp.clip(m - 1, 0, M - 1), axis=1)
        col = jnp.where(jnp.arange(M) < m, col, 0.0)
        out = jnp.zeros_like(rem).at[order].set(col)
        return jnp.where(active, out, 0.0)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class GWFStaticPolicy(Policy):
    """Water-fill with static CDR constants (default c ∝ w) each event.

    Solves the CAP (Algorithm 1) for the active set with constants that
    never adapt — the baseline isolating what SmartFill's carried
    constants c_k (Cor. 2.1) buy over naive weighted water-filling.
    """

    sp: Speedup
    B: float
    c: jnp.ndarray | None = None    # per-job constants; None ⇒ w-derived
    name = "GWF-static"

    def tree_flatten(self):
        return (self.sp, self.c, self.B), ()

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(sp=children[0], c=children[1], B=children[2])

    def __call__(self, rem, w, active, B=None):
        if self.c is None:
            wmax = jnp.max(jnp.where(active, w, 0.0))
            c = jnp.where(active, w, 1.0) / jnp.maximum(wmax, _TINY)
        else:
            c = self.c
        c = jnp.clip(c, 1e-12, None)
        th = solve_cap(self.sp, jnp.asarray(self._budget(B), rem.dtype),
                       c, active)
        return jnp.where(active, th, 0.0)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class HeteroSmartFillPolicy(Policy):
    """Re-planning SmartFill for per-job speedup functions (paper §7).

    ``sp`` carries job-indexed leaves aligned with the engine's job
    slots (slot i ↔ leaf entry i).  With a **pinned completion order**
    (``rank`` set — see ``pinned``) the active jobs are ranked by their
    one-shot rank at every event and only the *allocations* are
    re-solved; by Prop. 7 carried into §7 this executes the one-shot
    plan exactly (time consistency).  With ``rank=None`` the policy
    re-ranks every event by normalized remaining size rem_i / s_i(B) —
    the PR 5 behavior, kept as an ablation: re-ranking can flip the
    order mid-run and execute strictly worse than the one-shot plan.
    With a shared (scalar-leaf) speedup this is exactly
    ``SmartFillPolicy``'s ranking and solve.  The closed-form μ* fast
    path never applies (per-job exponents), so ``fast`` is pinned False.

    ``pinned(..., cache_plan=True)`` goes one step further and stores
    the one-shot allocation table Θ, making each event an O(M) lookup
    (see ``pinned``).  Under dynamic budgets (the engine passes B(t))
    the cached table self-invalidates: it executes verbatim while
    B(t) == the construction budget and re-solves on the pinned order
    the moment a budget event moves it — never a stale table.  ``precise=False`` swaps the per-event re-solve
    onto the relaxed grid/descent path (~3× cheaper, ~1e−4-grade
    allocations) for streaming re-planning where events perturb the
    state anyway.
    """

    sp: Speedup
    B: float
    rank: jnp.ndarray | None = None     # per-job one-shot rank, or None
    theta: jnp.ndarray | None = None    # cached (M, M) plan in rank coords
    coarse: int = 32
    descent_iters: int = 40
    cap_iters: int = 64
    precise: bool = True
    name = "heteroSF"

    def tree_flatten(self):
        return (self.sp, self.B, self.rank, self.theta), (
            self.coarse, self.descent_iters, self.cap_iters, self.precise)

    @classmethod
    def tree_unflatten(cls, aux, children):
        coarse, descent_iters, cap_iters, precise = aux
        return cls(sp=children[0], B=children[1], rank=children[2],
                   theta=children[3], coarse=coarse,
                   descent_iters=descent_iters, cap_iters=cap_iters,
                   precise=precise)

    @classmethod
    def pinned(cls, sp: Speedup, x0, w0, B: float | None = None,
               order=None, exchange_passes: int = 2,
               cache_plan: bool = False, **kwargs):
        """Policy with the one-shot completion order fixed at construction.

        ``x0``/``w0`` are the *initial* sizes/weights — (M,) for one
        instance or (K, M) for an ensemble (rank then batches per
        workload like any other policy leaf).  For a single instance the
        order comes from the full planner (exchange search included);
        for a batch, from the per-instance normalized-size heuristic
        (the batched planner's order).  Pass ``order`` explicitly to pin
        a caller-chosen permutation instead (e.g. a brute-force optimum
        or a previously planned ``.order``).

        ``cache_plan=True`` additionally stores the one-shot allocation
        table Θ and executes it by active-count lookup instead of
        re-solving — the device analog of ``simulator.schedule_policy``.
        By Prop. 7 (carried into §7) the looked-up column equals the
        re-solved allocation at every state the pinned order can reach
        under pure completions, so this is the same policy with the
        per-event DP amortized into construction.  Only valid without
        arrivals (an arrival makes the active set a non-prefix of the
        pinned order — use rank-only pinning there).
        """
        from repro.core.batch import smartfill_hetero_batched
        from repro.core.smartfill import smartfill_hetero

        B = float(sp.B if B is None else B)
        x0 = np.asarray(x0, dtype=np.float64)
        w0 = np.asarray(w0, dtype=np.float64)
        if cache_plan and order is not None:
            raise ValueError("cache_plan plans its own order; pass one of "
                             "order / cache_plan")
        theta = None
        if x0.ndim == 1:
            if order is None:
                plan = smartfill_hetero(sp, x0, w0, B=B,
                                        exchange_passes=exchange_passes)
                order = plan.order
                if cache_plan:
                    theta = jnp.asarray(plan.theta)
            order2d = np.atleast_2d(np.asarray(order))
        else:
            if order is None:
                orders, sched = smartfill_hetero_batched(sp, x0, w0, B=B)
                order = orders
                if cache_plan:
                    theta = jnp.asarray(sched.theta)
            order2d = np.asarray(order)
        rank = np.empty_like(order2d)
        np.put_along_axis(rank, order2d,
                          np.broadcast_to(np.arange(order2d.shape[1]),
                                          order2d.shape), axis=1)
        rank = jnp.asarray(rank if x0.ndim > 1 else rank[0],
                           jnp.result_type(float))
        return cls(sp=sp, B=B, rank=rank, theta=theta, **kwargs)

    def __call__(self, rem, w, active, B=None):
        M = rem.shape[0]
        if self.rank is None:
            rate = jnp.broadcast_to(
                self.sp.s(jnp.full((M,), self.B, rem.dtype)), (M,))
            key = jnp.where(active, -(rem / jnp.maximum(rate, 1e-300)),
                            jnp.inf)
        else:
            key = jnp.where(active, jnp.asarray(self.rank, rem.dtype),
                            jnp.inf)
        order = jnp.lexsort((w, key))
        m = jnp.sum(active)

        def resolve(bv):
            xs = jnp.where(active, rem, 0.0)[order]
            ws = jnp.where(active, w, 0.0)[order]
            sp_o = jax.tree_util.tree_map(
                lambda l: l[order] if getattr(l, "ndim", 0) >= 1 else l,
                self.sp)
            th, *_ = _solve(sp_o, xs, ws, jnp.asarray(bv, xs.dtype),
                            m, self.coarse, self.descent_iters,
                            self.cap_iters, False, precise=self.precise,
                            with_times=False)
            return th

        if self.theta is not None:
            # cached-plan execution: position r < m holds the active job
            # of r-th smallest pinned rank, which under pure completions
            # is exactly rank r — row r, column m−1 of the stored table
            table = jnp.asarray(self.theta, rem.dtype)
            if B is None:
                theta = table
            else:
                # dynamic budget: the stored table was solved under
                # self.B — execute it verbatim while B(t) matches
                # (bit-identical to the undisturbed run), re-solve on
                # the pinned order the moment the budget moves
                theta = jax.lax.cond(
                    jnp.all(jnp.asarray(B) == jnp.asarray(self.B)),
                    lambda: table,
                    lambda: resolve(B))
        else:
            theta = resolve(self._budget(B))
        col = jnp.take(theta, jnp.clip(m - 1, 0, M - 1), axis=1)
        col = jnp.where(jnp.arange(M) < m, col, 0.0)
        out = jnp.zeros_like(rem).at[order].set(col)
        return jnp.where(active, out, 0.0)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class ClassSmartFillPolicy(HeteroSmartFillPolicy):
    """Re-planning SmartFill over *class aggregates* (core/classes.py).

    State is aggregate: rem_c = remaining class work R_c (initially
    n_c·x_c), w_c = aggregate weight n_c·w_c, under the class-aggregated
    speedup S_c(Θ) = n_c·s_c(Θ/n_c) — which stays inside the regular
    family (``class_speedup``), so the whole §7 per-job machinery applies
    verbatim with C rows instead of M.  Inherits ``HeteroSmartFillPolicy``
    unchanged; only construction differs: ``from_classes`` applies the
    aggregation transform host-side and (by default) pins the class
    completion order from the one-shot ``plan_classes`` plan, so running
    it through ``simulate_fluid_classes`` executes the plan exactly
    (time consistency, Prop. 7 over aggregates).  ``pin=False`` keeps
    the per-event re-ranking ablation.  Zero-count classes carry R = 0
    and are never active.
    """

    name = "classSF"

    @classmethod
    def from_classes(cls, state, B: float | None = None, pin: bool = True,
                     cache_plan: bool = False, **kwargs):
        """Build from a ``ClassState``.

        ``pin=True`` ranks classes by the one-shot plan's completion
        order (empty classes rank last — they are never active anyway);
        ``cache_plan=True`` additionally stores the plan's allocation
        table for O(C) per-event lookup instead of a re-solve.
        """
        from repro.core.classes import aggregate_classes, plan_classes

        B = float(state.B if B is None else B)
        sp_agg, _, _ = aggregate_classes(state)
        rank = None
        theta = None
        if pin or cache_plan:
            plan = plan_classes(state, B=B)
            C = state.C
            r = np.full(C, C, dtype=np.float64)
            r[np.asarray(plan.order)] = np.arange(plan.order.size)
            rank = jnp.asarray(r)
            if cache_plan:
                kl = plan.order.size
                th = np.zeros((C, C))
                if kl:
                    th[:kl, :kl] = np.asarray(plan.sched.theta)
                theta = jnp.asarray(th)
        return cls(sp=sp_agg, B=B, rank=rank, theta=theta, **kwargs)


@dataclasses.dataclass(frozen=True)
class StreamPlan:
    """One replanning event's output (host-materialized).

    order: (m,) controller-slot indices — schedule row r executes the
      job in slot ``order[r]`` (row coords: remaining size
      non-increasing, so row m−1 completes first).
    table: (M, M) allocation table in row coords (column j = the phase
      with rows 0..j active), executed by active-count lookup exactly
      like ``HeteroSmartFillPolicy.pinned(cache_plan=True)``.
    J / J_linear: the solve's executed objective and value-function
      claim Σ a_i x_i; ``certified`` is the J == J_linear realized-order
      certificate (Prop. 9 / §7).
    warm: True when the plan came from the warm-start path (carried
      completion order + validated λ hints) rather than a cold solve.
    """

    order: np.ndarray
    table: jnp.ndarray
    J: float
    J_linear: float
    m: int
    B: float
    warm: bool
    certified: bool

    def slot_allocations(self) -> np.ndarray:
        """(M,) current-phase allocations scattered back to slot coords."""
        M = int(self.table.shape[0])
        out = np.zeros(M)
        if self.m:
            col = np.asarray(self.table)[:, min(self.m - 1, M - 1)]
            out[self.order] = col[:self.m]
        return out


class StreamingSmartFillPolicy(Policy):
    """Host-side incremental re-planner for the streaming control plane.

    Carries warm-start state *across* replanning events (the open-arrival
    loop of ``serve/stream.py``): the previous plan's completion order
    and its λ payload (per-iteration CAP duals + the generic-path
    λ-bracket, ``core.smartfill.WarmStart``).  Between consecutive
    events the live set changes by one arrival or completion, so

      * the **order** is maintained incrementally — completed slots drop
        out, arrivals binary-insert by normalized remaining size
        rem_i / s_i(B).  This is sound between events because CAP
        allocations are non-decreasing along schedule rows (θ_1 ≤ … ≤
        θ_m), so remaining sizes never cross during execution; and

      * the **λ payload** seeds the next solve's searches.  Both halves
        are validated on use (β-probes, ``core.gwf.cap_bracket_probe``
        semantics), so a stale payload costs cold pricing, never a wrong
        answer.

    Every warm plan is accepted only under the ``J == J_linear``
    realized-order certificate; a failed certificate (or non-finite
    solve) falls back to a **cold** plan — a from-scratch re-rank, plus
    the full §7 exchange-order search for per-job speedups (what
    planning without carried state actually costs, and the baseline the
    warm path is benchmarked against).  A cold plan that *still* fails
    certification is returned uncertified; the streaming controller then
    falls down the robust degradation ladder instead of executing it.

    Not an engine pytree (``device_ready=False``): replanning is a
    host-side control-plane step between execution windows, with mutable
    warm state.  ``plan`` is the real interface; ``__call__`` adapts it
    to the host-policy signature for differential tests.
    """

    device_ready = False
    name = "streamingSF"

    def __init__(self, sp: Speedup, B: float | None = None, *,
                 certificate_rtol: float = 1e-8, coarse: int = 32,
                 descent_iters: int = 40, cap_iters: int = 64,
                 exchange_passes: int = 2, exchange_window: int = 1,
                 stol_rel: float | None = None):
        self.sp = collapse_homogeneous(sp)
        self.B = float(sp.B if B is None else B)
        self.certificate_rtol = float(certificate_rtol)
        self.coarse = int(coarse)
        self.descent_iters = int(descent_iters)
        self.cap_iters = int(cap_iters)
        self.exchange_passes = int(exchange_passes)
        self.exchange_window = int(exchange_window)
        self.stol_rel = stol_rel
        self._per_job = is_per_job(self.sp)
        self._fast = _fast_ok(self.sp)
        self.reset()

    def reset(self) -> None:
        """Drop all carried warm state (and the replan counters)."""
        self.warm: WarmStart | None = None
        self._order = np.zeros(0, np.int64)
        self.warm_replans = 0
        self.cold_replans = 0
        self.order_searches = 0

    # -- internals --------------------------------------------------------

    def _solo_key(self, rem: np.ndarray) -> np.ndarray:
        """Normalized remaining size rem_i / s_i(B) per slot (the §7
        SJF ranking key; shared speedups broadcast)."""
        M = rem.shape[0]
        rate = np.asarray(jnp.broadcast_to(
            self.sp.s(jnp.full((M,), self.B)), (M,)), float)
        return rem / np.maximum(rate, _TINY)

    def release(self, slots) -> None:
        """Forget carried state for recycled slots.

        The controller calls this when a job leaves its slot (completion
        or eviction).  Without it a new occupant of the same slot would
        inherit the old job's position in the carried order — the merged
        order silently stops being the SJF order and warm plans drift
        from cold ones (the slot-recycling latent bug this PR fixes).
        """
        slots = np.atleast_1d(np.asarray(slots, np.int64))
        if self._order.size:
            self._order = self._order[~np.isin(self._order, slots)]

    def _merge_order(self, rem, w, act) -> np.ndarray:
        """Warm order: drop completed slots from the carried order and
        binary-insert arrivals by normalized size (no re-sort of the
        survivors — that is the whole point)."""
        keep = self._order[act[self._order]]
        new = np.setdiff1d(np.where(act)[0], keep)
        if new.size:
            key = self._solo_key(rem)
            new = new[np.argsort(-key[new], kind="stable")]
            # survivor keys are non-increasing along the carried order
            # (allocations non-decreasing along rows ⇒ sizes never
            # cross); searchsorted wants ascending, hence the negation
            pos = np.searchsorted(-key[keep], -key[new], side="right")
            keep = np.insert(keep, pos, new)
        return keep

    def _fresh_order(self, rem, w, act) -> np.ndarray:
        slots = np.where(act)[0]
        key = self._solo_key(rem)
        return slots[np.lexsort((w[slots], -key[slots]))]

    def _run(self, order, rem, w, Bv, m, lam0=None, bracket0=None):
        """Padded ``_solve`` on the given slot order (row coords)."""
        M = rem.shape[0]
        rest = np.setdiff1d(np.arange(M), order)
        full = np.concatenate([order, rest]).astype(np.int64)
        live = np.arange(M) < m
        xs = jnp.asarray(np.where(live, rem[full], 0.0))
        ws = jnp.asarray(np.where(live, w[full], 0.0))
        sp_o = jax.tree_util.tree_map(
            lambda l: l[full] if getattr(l, "ndim", 0) >= 1 else l, self.sp)
        lam0 = None if lam0 is None else jnp.asarray(lam0, xs.dtype)
        bracket0 = (None if bracket0 is None
                    else jnp.asarray(bracket0, xs.dtype))
        return _solve(sp_o, xs, ws, jnp.asarray(Bv, xs.dtype), m,
                      self.coarse, self.descent_iters, self.cap_iters,
                      self._fast, lam0=lam0, stol_rel=self.stol_rel,
                      bracket0=bracket0)

    def _certified(self, J, J_lin) -> bool:
        # floor the tolerance at the solve dtype's precision: the 1e-8
        # default is meaningful under x64 but unreachable in float32
        eps = float(jnp.finfo(jnp.asarray(J).dtype).eps)
        rtol = max(self.certificate_rtol, 64.0 * eps)
        J = float(J)
        J_lin = float(J_lin)
        if not (np.isfinite(J) and np.isfinite(J_lin)):
            return False
        return abs(J - J_lin) <= rtol * max(1.0, abs(J_lin))

    def _search_order(self, rem, w, act, Bv) -> np.ndarray:
        """Full §7 exchange-order search on the dense active set."""
        from repro.core.smartfill import smartfill_hetero

        slots = np.where(act)[0]
        sp_sub = jax.tree_util.tree_map(
            lambda l: l[slots] if getattr(l, "ndim", 0) >= 1 else l, self.sp)
        plan = smartfill_hetero(
            sp_sub, rem[slots], w[slots], B=Bv,
            coarse=self.coarse, descent_iters=self.descent_iters,
            cap_iters=self.cap_iters,
            exchange_passes=self.exchange_passes,
            exchange_window=self.exchange_window, stol_rel=self.stol_rel)
        self.order_searches += 1
        return slots[np.asarray(plan.order)]

    # -- interface --------------------------------------------------------

    def plan(self, rem, w, active=None, B=None,
             warm: bool = True) -> StreamPlan:
        """Replan the live set; warm-start when possible.

        rem/w are (M,) slot-coordinate state (M = the controller's slot
        capacity); ``active`` masks the live slots (zero-remaining slots
        are dropped regardless).  ``B`` is the live budget.
        ``warm=False`` forces the cold from-scratch path (the benchmark
        baseline).  Updates the carried warm state either way.
        """
        rem = np.asarray(rem, float)
        w = np.asarray(w, float)
        M = rem.shape[0]
        act = (np.ones(M, bool) if active is None
               else np.asarray(active, bool)) & (rem > 0)
        Bv = float(self.B if B is None else B)
        m = int(act.sum())
        if m == 0:
            return StreamPlan(order=np.zeros(0, np.int64),
                              table=jnp.zeros((M, M)), J=0.0, J_linear=0.0,
                              m=0, B=Bv, warm=False, certified=True)

        picked = None
        if warm and self.warm is not None and self._order.size:
            order = self._merge_order(rem, w, act)
            out = self._run(order, rem, w, Bv, m,
                            lam0=self.warm.lam, bracket0=self.warm.bracket)
            if self._certified(out[5], out[6]):
                self.warm_replans += 1
                picked = (order, out, True)
        if picked is None:
            # cold: from scratch, no carried state — a fresh normalized-
            # size ranking, escalating to the §7 exchange-order search
            # when jobs carry their own speedups or the certificate
            # rejects the ranking (non-agreeable weights: the order is
            # a decision, and a cold replan must re-make it)
            if self._per_job and m > 1:
                order = self._search_order(rem, w, act, Bv)
                out = self._run(order, rem, w, Bv, m)
            else:
                order = self._fresh_order(rem, w, act)
                out = self._run(order, rem, w, Bv, m)
                if m > 1 and not self._certified(out[5], out[6]):
                    order = self._search_order(rem, w, act, Bv)
                    out = self._run(order, rem, w, Bv, m)
            self.cold_replans += 1
            picked = (order, out, False)

        order, out, was_warm = picked
        self.warm = WarmStart(lam=out[7], bracket=out[8])
        self._order = np.asarray(order, np.int64)
        return StreamPlan(order=self._order, table=out[0],
                          J=float(out[5]), J_linear=float(out[6]), m=m,
                          B=Bv, warm=was_warm,
                          certified=self._certified(out[5], out[6]))

    def __call__(self, rem, w, active, B=None):
        """Host-policy adapter: the current-phase allocation column."""
        return jnp.asarray(self.plan(rem, w, active, B=B).slot_allocations())


# ---------------------------------------------------------------------------
# Traced replanning cascade (shared speedups) — the device hot path's
# per-event planner, and the host oracle's via StreamCascadePolicy
# ---------------------------------------------------------------------------

def _stream_certified(J, J_lin, certificate_rtol, dtype):
    """Traced J == J_linear realized-order certificate (Prop. 9),
    floored at the dtype's precision like the host ``_certified``."""
    rt = jnp.maximum(jnp.asarray(certificate_rtol, dtype),
                     64.0 * jnp.finfo(dtype).eps)
    return (jnp.isfinite(J) & jnp.isfinite(J_lin)
            & (jnp.abs(J - J_lin) <= rt * jnp.maximum(1.0, jnp.abs(J_lin))))


def _exchange_search_shared(run_order, order0, out0, m, max_steps):
    """Traced steepest-descent adjacent-exchange order search.

    Starts from a failed fresh order, scores all M−1 adjacent swaps
    with one vmapped solve per step, and takes the best strictly-
    improving swap until none improves (or ``max_steps``).  The
    shared-speedup analogue of the §7 host search the streaming policy
    escalates to — on the day trace the fresh SJF ranking certifies
    ~98% of replans and this search rescues nearly all of the rest
    (non-agreeable live weights: rem shrinks while w stays 1/x₀, so
    the order is a decision the certificate audits).
    """
    M = order0.shape[0]
    ci = jnp.arange(M - 1)
    J0 = out0[5]
    bestJ0 = jnp.where(jnp.isfinite(J0), J0, jnp.inf)

    def swap1(order, i):
        a, b = order[i], order[i + 1]
        return order.at[i].set(b).at[i + 1].set(a)

    def sweep(state):
        order, out, bestJ, k, _ = state
        orders = jax.vmap(lambda i: swap1(order, i))(ci)
        outs = jax.vmap(run_order)(orders)
        # swaps reaching past the live prefix are no-ops, not candidates
        Js = jnp.where(((ci + 1) < m) & jnp.isfinite(outs[5]),
                       outs[5], jnp.inf)
        i = jnp.argmin(Js)
        better = Js[i] < bestJ - 1e-12 * jnp.maximum(1.0, jnp.abs(bestJ))
        pick = jax.tree_util.tree_map(lambda l: l[i], outs)
        out2 = jax.tree_util.tree_map(
            lambda nw, od: jnp.where(better, nw, od), pick, out)
        return (jnp.where(better, orders[i], order), out2,
                jnp.where(better, Js[i], bestJ), k + 1, better)

    def keep_going(state):
        return state[4] & (state[3] < max_steps)

    st = jax.lax.while_loop(
        keep_going, sweep,
        (order0, out0, bestJ0, jnp.zeros((), jnp.int32),
         jnp.ones((), bool)))
    return st[0], st[1]


def stream_replan_core(sp, ladder, rem, w, active, B_live, B_key, warm,
                       certificate_rtol, *, fast, coarse=32,
                       descent_iters=40, cap_iters=64, stol_rel=None,
                       search_steps=64):
    """One replanning event as a pure traced function (shared speedups).

    The decision cascade, every stage a real ``lax.cond`` branch so the
    common path pays one solve:

      1. **fresh solve** — rank the live set by normalized remaining
         size (SJF key under the *nominal* budget ``B_key``, weights
         break ties) and solve under the live budget, seeded with the
         carried ``WarmStart`` λ/bracket payload (validated on use, so
         a stale payload costs cold pricing, never a wrong answer);
      2. **exchange search** — if the J == J_linear certificate rejects
         the ranking (and m > 1), ``_exchange_search_shared``;
      3. **ladder** — still uncertified ⇒ the certificate-gated
         ``ladder_plan_table`` on the SJF ranking (the PR-8 contract:
         solver failures are absorbed, never executed).

    Returns ``(order, table, m, certified, searched, J, J_linear,
    warm2)`` with ``order`` a full (M,) slot permutation (live prefix
    first), ``table`` the (M, M) plan to execute, and ``warm2`` the
    carry for the next event.  ``StreamCascadePolicy`` (host) and
    ``serve.stream.StreamController.run_device`` call this *same*
    function, which is what makes the host loop a bit-comparable
    differential oracle for the device scan.
    """
    rem = jnp.asarray(rem)
    dtype = rem.dtype
    M = rem.shape[0]
    idx = jnp.arange(M)
    w = jnp.asarray(w, dtype)
    act = jnp.asarray(active, bool) & (rem > 0)
    m = jnp.sum(act)
    B_live = jnp.asarray(B_live, dtype)
    rate = sp.s(jnp.asarray(B_key, dtype))
    key = jnp.where(act, -(rem / jnp.maximum(rate, _TINY)), jnp.inf)
    order0 = jnp.lexsort((jnp.where(act, w, 0.0), key)).astype(jnp.int32)

    def run_order(order):
        xs = jnp.where(idx < m, rem[order], 0.0)
        ws = jnp.where(idx < m, w[order], 0.0)
        return _solve(sp, xs, ws, B_live, m, coarse, descent_iters,
                      cap_iters, fast, lam0=warm.lam, stol_rel=stol_rel,
                      bracket0=warm.bracket)

    out0 = run_order(order0)
    cert0 = _stream_certified(out0[5], out0[6], certificate_rtol, dtype)
    need_search = (~cert0) & (m > 1)

    def escalate(_):
        return _exchange_search_shared(run_order, order0, out0, m,
                                       search_steps)

    order1, out1 = jax.lax.cond(need_search, escalate,
                                lambda _: (order0, out0), None)
    certified = _stream_certified(out1[5], out1[6], certificate_rtol,
                                  dtype)

    def ladder_plan(_):
        from repro.robust.degrade import ladder_plan_table
        order_l = jnp.argsort(jnp.where(act, -rem, jnp.inf),
                              stable=True).astype(jnp.int32)
        rem_l = jnp.where(idx < m, rem[order_l], 0.0)
        w_l = jnp.where(idx < m, w[order_l], 0.0)
        return order_l, ladder_plan_table(ladder, rem_l, w_l, B=B_live)

    order_f, table_f = jax.lax.cond(
        certified, lambda _: (order1, out1[0]), ladder_plan, None)
    warm2 = WarmStart(lam=out1[7], bracket=out1[8])
    return (order_f, table_f, m.astype(jnp.int32), certified,
            need_search, out1[5], out1[6], warm2)


def stream_warm0(M: int, dtype=None) -> WarmStart:
    """The "no hint yet" WarmStart the cascade starts from: zero λ
    hints and the full-range cold bracket — ``_solve`` treats both
    exactly like absent hints, so the first replan prices cold."""
    dtype = jnp.result_type(float) if dtype is None else dtype
    fi = jnp.finfo(dtype)
    return WarmStart(
        lam=jnp.zeros((M,), dtype),
        bracket=jnp.stack([jnp.asarray(fi.tiny, dtype)
                           / jnp.asarray(fi.eps, dtype),
                           jnp.asarray(fi.max, dtype) / 4.0]))


_cascade_call = jax.jit(
    stream_replan_core,
    static_argnames=("fast", "coarse", "descent_iters", "cap_iters",
                     "stol_rel", "search_steps"))


class StreamCascadePolicy:
    """Host-side mirror of the device replanning cascade.

    Same ``plan``/``release``/``reset`` surface as
    ``StreamingSmartFillPolicy`` so it drops into ``StreamController``
    unchanged, but every decision — ranking, certificate, exchange
    search, warm-payload update — is made by the *same* jitted
    ``stream_replan_core`` the device scan inlines.  Running the host
    event loop with this policy is therefore the differential oracle
    for ``StreamController.run_device``: the two implementations share
    only the per-event planner and the window executor; event ordering,
    buffer promotion, queueing, backfill and metrics are independent
    code paths that must agree to float tolerance.

    Counter semantics (device-mirrored, coarser than the streaming
    policy's): ``warm_replans`` counts replans certified on the fresh
    hinted solve, ``cold_replans`` counts escalations (search or
    ladder), ``order_searches`` counts search entries.
    """

    device_ready = False
    name = "cascadeSF"

    def __init__(self, sp: Speedup, B: float | None = None, *,
                 certificate_rtol: float = 1e-8, coarse: int = 32,
                 descent_iters: int = 40, cap_iters: int = 64,
                 stol_rel: float | None = None,
                 search_steps: int | None = None, ladder=None):
        self.sp = collapse_homogeneous(sp)
        if is_per_job(self.sp):
            raise ValueError(
                "StreamCascadePolicy is the shared-speedup cascade; "
                "per-job streams replan through "
                "StreamingSmartFillPolicy")
        self.B = float(sp.B if B is None else B)
        self.certificate_rtol = float(certificate_rtol)
        self.coarse = int(coarse)
        self.descent_iters = int(descent_iters)
        self.cap_iters = int(cap_iters)
        self.stol_rel = stol_rel
        self.search_steps = search_steps
        self._fast = _fast_ok(self.sp)
        if ladder is None:
            from repro.robust.degrade import DegradingPolicy
            ladder = DegradingPolicy.ladder(self.sp, B=self.B)
        self.ladder = ladder
        self.reset()

    def reset(self) -> None:
        self.warm: WarmStart | None = None
        self.warm_replans = 0
        self.cold_replans = 0
        self.order_searches = 0

    def release(self, slots) -> None:
        """No carried order — nothing to forget on slot recycling."""

    def plan(self, rem, w, active=None, B=None) -> StreamPlan:
        rem = np.asarray(rem, float)
        w = np.asarray(w, float)
        M = rem.shape[0]
        act = (np.ones(M, bool) if active is None
               else np.asarray(active, bool))
        Bv = float(self.B if B is None else B)
        dtype = jnp.result_type(float)
        if self.warm is None or self.warm.lam.shape != (M,):
            self.warm = stream_warm0(M, dtype)
        steps = (4 * M if self.search_steps is None
                 else int(self.search_steps))
        order, table, m_, certified, searched, J, J_lin, warm2 = (
            _cascade_call(self.sp, self.ladder, jnp.asarray(rem, dtype),
                          jnp.asarray(w, dtype), jnp.asarray(act),
                          Bv, self.B, self.warm, self.certificate_rtol,
                          fast=self._fast, coarse=self.coarse,
                          descent_iters=self.descent_iters,
                          cap_iters=self.cap_iters,
                          stol_rel=self.stol_rel, search_steps=steps))
        self.warm = WarmStart(lam=warm2.lam, bracket=warm2.bracket)
        m = int(m_)
        cert = bool(certified)
        sd = bool(searched)
        self.warm_replans += int(cert and not sd)
        self.cold_replans += int(sd or not cert)
        self.order_searches += int(sd)
        return StreamPlan(order=np.asarray(order, np.int64)[:m],
                          table=table, J=float(J), J_linear=float(J_lin),
                          m=m, B=Bv, warm=cert and not sd,
                          certified=cert)

    def __call__(self, rem, w, active, B=None):
        """Host-policy adapter: the current-phase allocation column."""
        return jnp.asarray(self.plan(rem, w, active, B=B).slot_allocations())


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class WeightedMarginalRatePolicy(Policy):
    """Retired heterogeneity heuristic (named baseline, cf. §7).

    Before the per-job solver existed, ``sched/cluster.py`` documented
    heterogeneous fleets as "equalize w_i/x_i · s_i'(θ_i) via bisection".
    That is a GWF with static constants c_i ∝ rem_i/w_i evaluated under
    each job's own s_i — no carried CDR constants, no μ* recursion, no
    order search.  Kept as the ablation baseline the hetero SmartFill
    differential suite must beat.

    Per-event CAP dispatch is static on the speedup's type/leaf shapes:
    stackable regular-family per-job speedups take the sorted-bracket
    solver (``solve_cap_hetero_sorted`` — the §7 fast path), anything
    else the λ-bisection oracle.
    """

    sp: Speedup
    B: float
    name = "WMR"

    def tree_flatten(self):
        return (self.sp, self.B), ()

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(sp=children[0], B=children[1])

    def __call__(self, rem, w, active, B=None):
        b = jnp.asarray(self._budget(B), rem.dtype)
        c = jnp.where(active, rem / jnp.maximum(w, _TINY), 1.0)
        c = c / jnp.maximum(jnp.max(jnp.where(active, c, 0.0)), _TINY)
        c = jnp.clip(c, 1e-12, None)
        if _uses_sorted_cap(self.sp):
            th = solve_cap_hetero_sorted(self.sp, b, c, active)
        else:
            th = solve_cap_hetero(self.sp, b, c, active)
        return jnp.where(active, th, 0.0)


def default_zoo(sp: Speedup, B: float | None = None,
                p_fit: float = 0.5) -> tuple:
    """The paper's §6 comparison set for one server model.

    ``p_fit`` is the power-law exponent heSRPT plans with (for pure-power
    speedups pass the true p; otherwise a ``fit_power`` fit).
    """
    B = float(sp.B if B is None else B)
    return (
        SmartFillPolicy(sp, B=B),
        HeSRPTPolicy(p=p_fit, B=B),
        EquiPolicy(B=B),
        SRPT1Policy(B=B),
        GWFStaticPolicy(sp, B=B),
    )
