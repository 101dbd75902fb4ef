"""Speedup-function abstractions for SmartFill scheduling.

The paper assumes a speedup function ``s(θ)`` on ``[0, B]`` with

  * ``s(0) = 0``,
  * strictly increasing, continuous, differentiable,
  * strictly concave, with continuous derivative ``s'``.

Two concrete families are provided:

``RegularSpeedup``
    The paper's *regular* class (Definition 1): ``s'(θ) = α (θ + z)^γ``.
    We use the slightly more explicit parameterization

        ``s'(θ) = A · (w + σ θ)^γ``,   ``A > 0``, ``σ ∈ {+1, −1}``,

    with ``w + σθ > 0`` on ``[0, B]`` and ``σ·γ < 0`` (so ``s'`` is strictly
    decreasing).  This covers every row of the paper's Table 1:

      power          s = a θ^p            (A=ap,  w=0,   σ=+1, γ=p−1)
      shifted power  s = a(θ+z)^p − a z^p (A=ap,  w=z,   σ=+1, γ=p−1)
      logarithmic    s = a ln(pθ+1)       (A=a,   w=1/p, σ=+1, γ=−1)
      neg. power     s = a z^p − a(θ+z)^p (A=−ap, w=z,   σ=+1, γ=p−1), p<0
      saturating     s = a z^p − a(z−θ)^p (A=ap,  w=z,   σ=−1, γ=p−1), p>1

``GenericSpeedup``
    Arbitrary concave ``s`` given as callables ``(s, ds)``; the derivative
    inverse is computed with a fixed-iteration vectorized bisection (jit- and
    vmap-compatible).

Per-job heterogeneity (paper §7)
--------------------------------
Every job in one instance may carry its *own* concave speedup.  The
convention is **job-indexed leaves**: a speedup whose parameter leaves
are ``(M,)`` arrays assigns entry ``i`` to job ``i`` — all methods are
elementwise in the job axis, so ``sp.s(theta)`` with an ``(M,)`` θ
evaluates each job under its own function.  Two representations:

  * a ``RegularSpeedup`` with ``(M,)`` ``A/w/gamma`` leaves mixes every
    σ=+1 Table-1 family (power, shifted power, log, negative power) in
    one instance;
  * ``StackedSpeedup`` additionally makes σ a job-indexed leaf, so the
    saturating σ=−1 row can join the union — ``stack_speedups`` builds
    one from a list of per-job ``RegularSpeedup`` objects.

``is_per_job`` / ``take_job`` / ``rowwise`` / ``broadcast_speedup`` /
``collapse_homogeneous`` are the plumbing the solvers use: leaf *shape*
is static under tracing, so per-job dispatch costs nothing inside jit.
Batched planners extend the convention one axis up: ``(N, M)`` leaves
are per-instance-per-job (``core/batch.py``).

All methods are pure functions of jnp arrays, so every speedup object can be
closed over inside ``jax.jit`` / ``lax`` control flow.
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from . import fmath

__all__ = [
    "Speedup",
    "RegularSpeedup",
    "StackedSpeedup",
    "GenericSpeedup",
    "power",
    "shifted_power",
    "log_speedup",
    "neg_power",
    "saturating",
    "from_roofline",
    "stack_speedups",
    "stack_speedup_rows",
    "broadcast_speedup",
    "collapse_homogeneous",
    "is_per_job",
    "inner_per_job",
    "take_job",
    "rowwise",
]


class Speedup:
    """Common interface.  Subclasses implement s, ds and ds_inv."""

    B: float  # domain upper bound (server bandwidth)

    def s(self, theta):  # service rate
        raise NotImplementedError

    def ds(self, theta):  # derivative s'(θ)
        raise NotImplementedError

    def ds_inv(self, y):  # inverse of s' (s' is strictly decreasing)
        raise NotImplementedError

    def ds0(self):
        """s'(0); may be +inf (e.g. pure power laws)."""
        return self.ds(jnp.zeros(()))

    # -- convenience ---------------------------------------------------
    def check_concave(self, n: int = 1025, b: float | None = None) -> bool:
        """Numerical sanity check of the paper's assumptions on [0, B]."""
        b = self.B if b is None else b
        th = jnp.linspace(0.0, b, n)
        sv = self.s(th)
        dv = self.ds(th)
        ok = bool(jnp.all(dv > 0))  # strictly increasing
        ok &= bool(jnp.all(jnp.diff(dv) <= 1e-9 * jnp.maximum(1.0, dv[:-1])))
        ok &= bool(abs(float(self.s(jnp.zeros(())))) < 1e-12)
        ok &= bool(jnp.all(jnp.diff(sv) > 0))
        return ok


def _regular_ds(A, w, gamma, sigma, theta):
    """s'(θ) = A (w + σθ)^γ, elementwise in every parameter."""
    return A * fmath.power(w + sigma * theta, gamma)


def _regular_s(A, w, gamma, sigma, theta):
    """Antiderivative of ``_regular_ds`` with s(0) = 0, elementwise.

    γ == −1 (log family) takes the log branch, selected per entry with
    jnp.where so per-job parameter arrays can mix log and power families
    in one call.  The log argument is guarded against w == 0
    (construction validates it, but traced construction cannot;
    log(0)−log(0) would NaN the *selected* branch of an invalid
    log-family object instead of staying inert in the discarded one).
    """
    base = w + sigma * theta
    g1 = gamma + 1.0
    w_safe = jnp.where(w > 0, w, 1.0)
    # log1p/expm1 of the relative step σθ/w: the plain differences
    # log(w+σθ) − log(w) and (w+σθ)^{γ+1} − w^{γ+1} cancel away every
    # digit when θ ≪ w — the regime of class aggregates (w → n·w), where
    # float32 kept no correct digit of s.  w = 0 (pure power) has no
    # cancellation and keeps the plain power.
    rel = fmath.log1p(sigma * theta / w_safe)
    log_branch = (A / sigma) * rel
    safe_g1 = jnp.where(jnp.abs(g1) < 1e-12, 1.0, g1)
    shifted = fmath.power(w_safe, safe_g1) * fmath.expm1(safe_g1 * rel)
    diff = jnp.where(w > 0, shifted, fmath.power(base, safe_g1))
    pow_branch = (A / (sigma * safe_g1)) * diff
    return jnp.where(jnp.abs(g1) < 1e-12, log_branch, pow_branch)


def _regular_ds_inv(A, w, gamma, sigma, y):
    """Inverse of ``_regular_ds``: θ = σ((y/A)^{1/γ} − w), elementwise."""
    return sigma * (fmath.power(y / A, 1.0 / gamma) - w)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class RegularSpeedup(Speedup):
    """s'(θ) = A (w + σ θ)^γ  with  A>0, σ∈{±1}, σγ<0, w+σθ>0 on [0,B]."""

    A: jnp.ndarray
    w: jnp.ndarray
    gamma: jnp.ndarray
    sigma: int  # static: +1 or −1
    B: float    # static: domain bound

    def __post_init__(self):
        if self.sigma not in (+1, -1):
            raise ValueError("sigma must be ±1")
        _validate_log_family(self.w, self.gamma)

    # pytree plumbing (A, w, gamma dynamic; sigma/B static)
    def tree_flatten(self):
        return (self.A, self.w, self.gamma), (self.sigma, self.B)

    @classmethod
    def tree_unflatten(cls, aux, children):
        A, w, gamma = children
        sigma, B = aux
        return cls(A=A, w=w, gamma=gamma, sigma=sigma, B=B)

    # -- the three primitives (shared elementwise math above) ----------
    def _base(self, theta):
        return self.w + self.sigma * theta

    def ds(self, theta):
        return _regular_ds(self.A, self.w, self.gamma, self.sigma, theta)

    def s(self, theta):
        return _regular_s(self.A, self.w, self.gamma, self.sigma, theta)

    def ds_inv(self, y):
        return _regular_ds_inv(self.A, self.w, self.gamma, self.sigma, y)

    def ds0(self):
        w = jnp.asarray(self.w, dtype=jnp.result_type(float))
        if self.sigma == +1:
            # γ<0: s'(0) = A·w^γ = +inf when w == 0.
            return jnp.where(
                w > 0, self.A * fmath.power(jnp.maximum(w, 1e-300), self.gamma),
                jnp.inf)
        return self.A * fmath.power(w, self.gamma)

    # -- GWF rectangle-bottle geometry (paper §4.3/4.5.1) --------------
    def bottle_width(self, c):
        """u_i = c_i^{1/γ} (paper: auxiliary g(h)=A(σh)^γ ⇒ θ_i(h)=u_i(h−h_i)+)."""
        return fmath.power(c, 1.0 / self.gamma)

    def bottle_bottom(self, c):
        """h_i = σ·w / u_i."""
        return self.sigma * self.w / self.bottle_width(c)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class StackedSpeedup(Speedup):
    """Per-job family union (paper §7): s_i'(θ) = A_i (w_i + σ_i θ)^{γ_i}.

    The job-indexed generalization of ``RegularSpeedup`` with σ promoted
    to a dynamic ``(M,)`` leaf, so one object can mix *all five* Table-1
    rows — including the saturating σ=−1 family — across the jobs of a
    single instance.  Every method is elementwise in the job axis; there
    is no shared auxiliary function g(h), so the CAP over a stacked
    speedup has no rectangle-bottle closed form — ``core/gwf.py`` solves
    it by λ-bisection over the per-job closed-form ``ds_inv_i`` instead
    (O(M) per probe).

    Build one with ``stack_speedups([sp_1, …, sp_M])`` from per-job
    ``RegularSpeedup`` objects (e.g. the roofline-calibrated functions of
    ``sched/speedup_models.py``).  Batched planners use ``(N, M)``
    leaves — one row of job parameters per instance.
    """

    A: jnp.ndarray
    w: jnp.ndarray
    gamma: jnp.ndarray
    sigma: jnp.ndarray   # dynamic: ±1 per job
    B: float             # static: domain bound

    def __post_init__(self):
        try:
            sg = np.asarray(self.sigma)
        except (TypeError, jax.errors.TracerArrayConversionError):
            return
        if sg.size and not np.all(np.isin(sg, (1.0, -1.0))):
            raise ValueError("sigma entries must be ±1")
        _validate_log_family(self.w, self.gamma)

    # pytree plumbing (A, w, gamma, sigma dynamic; B static)
    def tree_flatten(self):
        return (self.A, self.w, self.gamma, self.sigma), (self.B,)

    @classmethod
    def tree_unflatten(cls, aux, children):
        # Raw construction: unflatten runs inside jax transforms where
        # children may be tracers or axis specs — __post_init__'s
        # concrete validation must not fire on those.
        obj = object.__new__(cls)
        for name, val in zip(("A", "w", "gamma", "sigma"), children):
            object.__setattr__(obj, name, val)
        object.__setattr__(obj, "B", aux[0])
        return obj

    # -- the three primitives (elementwise in the job axis; same shared
    # math as RegularSpeedup, σ just arrives as a ±1 leaf here) --------
    def _base(self, theta):
        return self.w + self.sigma * theta

    def ds(self, theta):
        return _regular_ds(self.A, self.w, self.gamma, self.sigma, theta)

    def s(self, theta):
        return _regular_s(self.A, self.w, self.gamma, self.sigma, theta)

    def ds_inv(self, y):
        return _regular_ds_inv(self.A, self.w, self.gamma, self.sigma, y)

    def ds0(self):
        w = jnp.asarray(self.w, dtype=jnp.result_type(float))
        # σ=+1, γ<0, w=0 (pure power): s'(0) = +∞; the σ=−1 saturating
        # family always has w = z ≥ B > 0, so the finite branch covers it.
        return jnp.where(w > 0,
                         self.A * fmath.power(jnp.maximum(w, 1e-300),
                                              self.gamma),
                         jnp.inf)


def _validate_log_family(w, gamma) -> None:
    """Concrete-parameter check: the log family (γ = −1) needs w > 0.

    ``s`` integrates through ``log(w + σθ) − log(w)``, which is NaN at
    w = 0 — validated at construction exactly like ``sigma`` is; traced
    parameters (shape-only) are skipped, the runtime guard in ``s``
    covers those.
    """
    try:
        wv = np.asarray(w)
        gv = np.asarray(gamma)
    except (TypeError, ValueError, jax.errors.TracerArrayConversionError):
        return
    if (wv.size == 0 or gv.size == 0
            or wv.dtype.kind not in "fiu" or gv.dtype.kind not in "fiu"):
        return      # axis specs / tracers / None: nothing concrete to check
    wb, gb = np.broadcast_arrays(wv, gv)
    if np.any((np.abs(gb + 1.0) < 1e-12) & (wb <= 0)):
        raise ValueError(
            "log-family speedup (γ = −1) requires a positive shift w "
            "(s integrates through log(w + σθ) − log(w), which is NaN "
            "at w = 0)")


# ---------------------------------------------------------------------------
# Per-job leaf plumbing (paper §7 heterogeneity)
# ---------------------------------------------------------------------------

def is_per_job(sp) -> bool:
    """True iff any dynamic leaf of ``sp`` is job-indexed (ndim ≥ 1).

    Leaf *shape* is static under jit/vmap, so this is a free static
    dispatch predicate inside traced code: after the batched planners
    vmap away a leading instance axis, shared parameters are scalars and
    per-job parameters are ``(M,)`` — exactly what this tests.
    """
    return any(getattr(l, "ndim", 0) >= 1
               for l in jax.tree_util.tree_leaves(sp))


def inner_per_job(sp, n_instances: int | None = None) -> bool:
    """``is_per_job`` as seen by one instance of a batched solve.

    Batched planners vmap away a leading ``n_instances`` axis; a leaf is
    job-indexed *inside* the vmap iff it still has a dimension left
    after stripping that axis — ``(N,)`` leaves are per-instance
    scalars, ``(N, M)`` leaves (and unmapped ``(M,)`` leaves) are
    per-job.  (The N == M ambiguity for 1-D leaves is rejected upstream
    by ``check_axes_unambiguous``.)
    """
    for l in jax.tree_util.tree_leaves(sp):
        nd = getattr(l, "ndim", 0)
        if (n_instances is not None and nd >= 1
                and l.shape[0] == n_instances):
            nd -= 1
        if nd >= 1:
            return True
    return False


def take_job(sp, i):
    """Job ``i``'s own speedup from a per-job one (identity when shared).

    ``i`` may be traced (a ``lax.scan`` iteration index); scalar leaves
    pass through untouched, so homogeneous code paths are bit-for-bit
    unchanged.
    """
    return jax.tree_util.tree_map(
        lambda l: l[i] if getattr(l, "ndim", 0) >= 1 else l, sp)


def rowwise(sp):
    """Per-job leaves reshaped ``(M,) → (M, 1)`` for row-wise broadcast.

    A schedule matrix Θ[i, j] indexes jobs along *rows*; plain ``(M,)``
    leaves would broadcast along columns instead.
    """
    return jax.tree_util.tree_map(
        lambda l: l[:, None] if getattr(l, "ndim", 0) >= 1 else l, sp)


def broadcast_speedup(sp: Speedup, M: int):
    """Job-indexed view of a shared speedup: scalar leaves broadcast to (M,).

    The homogeneous end of the per-job convention — useful to mix a
    shared-function fleet into per-job machinery.  Leaves that are
    already arrays are left untouched.  ``collapse_homogeneous`` is the
    inverse (and what the solvers apply so a broadcast object takes the
    shared fast paths bit-for-bit).
    """
    return jax.tree_util.tree_map(
        lambda l: (jnp.broadcast_to(jnp.asarray(l), (M,))
                   if getattr(jnp.asarray(l), "ndim", 0) == 0 else l), sp)


def collapse_homogeneous(sp):
    """Collapse constant job-indexed leaves back to scalars.

    When every array leaf is concrete and constant, the per-job object
    describes a homogeneous instance; collapsing routes it through the
    shared-function solver paths (closed-form CAP, pure-power μ*)
    **bit-for-bit** identically to a scalar-leaf object.  Traced,
    non-constant, or already-scalar speedups are returned unchanged.  A
    ``StackedSpeedup`` with uniform σ collapses all the way down to a
    ``RegularSpeedup``.
    """
    leaves = jax.tree_util.tree_leaves(sp)
    if not any(getattr(l, "ndim", 0) >= 1 for l in leaves):
        return sp
    try:
        arrs = [np.asarray(l) for l in leaves]
    except (TypeError, jax.errors.TracerArrayConversionError):
        return sp
    if not all(a.size > 0 and np.all(a == a.flat[0]) for a in arrs):
        return sp

    def scalarize(l):
        a = np.asarray(l)
        if a.ndim == 0:
            return l
        return jnp.asarray(a.flat[0], dtype=a.dtype)

    collapsed = jax.tree_util.tree_map(scalarize, sp)
    if isinstance(collapsed, StackedSpeedup):
        return RegularSpeedup(
            A=collapsed.A, w=collapsed.w, gamma=collapsed.gamma,
            sigma=int(np.asarray(collapsed.sigma)), B=collapsed.B)
    return collapsed


def stack_speedups(sps, B: float | None = None) -> StackedSpeedup:
    """Stack per-job ``RegularSpeedup`` objects into one ``StackedSpeedup``.

    Args:
      sps: one scalar-parameter ``RegularSpeedup`` per job (any mix of
        the five Table-1 families, σ=+1 and σ=−1 alike).
      B: domain bound of the stacked object; defaults to the common
        ``sp.B`` of the members (mixed bounds require an explicit B).

    Raises:
      TypeError: for members that cannot be stacked — ``GenericSpeedup``
        (no closed-form per-job derivative inverse) or other non-regular
        speedups.
      ValueError: for members that are already job-indexed, or mixed
        member bounds without an explicit ``B``.
    """
    sps = list(sps)
    if not sps:
        raise ValueError("stack_speedups needs at least one speedup")
    for i, s in enumerate(sps):
        if not isinstance(s, RegularSpeedup):
            raise TypeError(
                f"job {i}: {type(s).__name__} cannot be stacked into a "
                "per-job speedup — only RegularSpeedup members have the "
                "closed-form per-job derivative inverse the heterogeneous "
                "CAP solver needs (fit a regular family first, e.g. via "
                "core.hesrpt.fit_power)")
        if is_per_job(s):
            raise ValueError(f"job {i}: member is already job-indexed; "
                             "stack scalar-parameter speedups")
    if B is None:
        bounds = {float(s.B) for s in sps}
        if len(bounds) > 1:
            raise ValueError(
                f"members carry different bounds {sorted(bounds)}; pass an "
                "explicit B for the stacked speedup")
        B = bounds.pop()
    dt = jnp.result_type(float)
    return StackedSpeedup(
        A=jnp.asarray([float(s.A) for s in sps], dt),
        w=jnp.asarray([float(s.w) for s in sps], dt),
        gamma=jnp.asarray([float(s.gamma) for s in sps], dt),
        sigma=jnp.asarray([float(s.sigma) for s in sps], dt),
        B=float(B))


# A valid (shifted-power-like) family for slots no real job occupies:
# padded parameters must stay legal members so a masked solve cannot NaN.
_NEUTRAL_PARAMS = (1.0, 1.0, -0.5, 1.0)         # (A, w, γ, σ)


def stack_speedup_rows(rows, M: int, B: float) -> StackedSpeedup:
    """(N, M)-leaved ``StackedSpeedup`` from per-instance member lists.

    ``rows[n]`` lists instance n's per-job ``RegularSpeedup`` members in
    row (completion) order; rows shorter than ``M`` edge-replicate their
    last member into the padded slots, and empty rows hold neutral valid
    family parameters — the shared packing convention of the cluster
    scheduler, the admission controller and the fleet layer.  Members
    are validated exactly as in ``stack_speedups``.
    """
    N = len(rows)
    pars = np.empty((4, N, M))
    pars[0], pars[1], pars[2], pars[3] = (
        p for p in np.asarray(_NEUTRAL_PARAMS))
    for n, members in enumerate(rows):
        if len(members) > M:
            raise ValueError(f"row {n} has {len(members)} members for "
                             f"{M} slots")
        for r, s in enumerate(members):
            if not isinstance(s, RegularSpeedup) or is_per_job(s):
                # reuse stack_speedups' error text for the same contract
                stack_speedups([s], B=B)
            pars[0, n, r] = float(s.A)
            pars[1, n, r] = float(s.w)
            pars[2, n, r] = float(s.gamma)
            pars[3, n, r] = float(s.sigma)
        for r in range(len(members), M):
            if members:                 # edge-replicate the last member
                pars[:, n, r] = pars[:, n, len(members) - 1]
    return StackedSpeedup(A=pars[0], w=pars[1], gamma=pars[2],
                          sigma=pars[3], B=float(B))


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class GenericSpeedup(Speedup):
    """Arbitrary concave speedup from callables (s_fn, ds_fn).

    ``ds_inv`` runs a fixed-iteration bisection on [0, B] (s' strictly
    decreasing), fully vectorized — usable under jit/vmap.
    """

    s_fn: Callable = dataclasses.field(metadata=dict(static=True))
    ds_fn: Callable = dataclasses.field(metadata=dict(static=True))
    B: float = 1.0
    inv_iters: int = 80

    def tree_flatten(self):
        return (), (self.s_fn, self.ds_fn, self.B, self.inv_iters)

    @classmethod
    def tree_unflatten(cls, aux, children):
        s_fn, ds_fn, B, inv_iters = aux
        return cls(s_fn=s_fn, ds_fn=ds_fn, B=B, inv_iters=inv_iters)

    def s(self, theta):
        return self.s_fn(theta)

    def ds(self, theta):
        return self.ds_fn(theta)

    def ds_inv(self, y):
        y = jnp.asarray(y)
        lo = jnp.zeros_like(y)
        hi = jnp.full_like(y, self.B)

        def body(_, carry):
            lo, hi = carry
            mid = 0.5 * (lo + hi)
            v = self.ds_fn(mid)
            # s' decreasing: v > y ⇒ solution right of mid.
            lo = jnp.where(v > y, mid, lo)
            hi = jnp.where(v > y, hi, mid)
            return lo, hi

        lo, hi = jax.lax.fori_loop(0, self.inv_iters, body, (lo, hi))
        mid = 0.5 * (lo + hi)
        # Clamp outside the representable range of s' on [0, B].
        mid = jnp.where(y >= self.ds_fn(jnp.zeros_like(y)), 0.0, mid)
        mid = jnp.where(y <= self.ds_fn(jnp.full_like(y, self.B)), self.B, mid)
        return mid


# ---------------------------------------------------------------------------
# Named constructors (Table 1 of the paper)
# ---------------------------------------------------------------------------

def _f(x):
    return jnp.asarray(x, dtype=jnp.result_type(float))


def power(a: float, p: float, B: float) -> RegularSpeedup:
    """s(θ) = a θ^p, 0<p<1 — the heSRPT family [Berg et al. 2020]."""
    assert 0 < p < 1 and a > 0
    return RegularSpeedup(A=_f(a * p), w=_f(0.0), gamma=_f(p - 1.0), sigma=+1, B=B)


def shifted_power(a: float, z: float, p: float, B: float) -> RegularSpeedup:
    """s(θ) = a(θ+z)^p − a z^p, 0<p<1, z≥0.  (Fig. 8 uses a=1, z=4, p=.5.)"""
    assert 0 < p < 1 and a > 0 and z >= 0
    return RegularSpeedup(A=_f(a * p), w=_f(z), gamma=_f(p - 1.0), sigma=+1, B=B)


def log_speedup(a: float, p: float, B: float) -> RegularSpeedup:
    """s(θ) = a ln(pθ + 1).  (Fig. 6 uses a=1, p=1.)"""
    assert a > 0 and p > 0
    return RegularSpeedup(A=_f(a), w=_f(1.0 / p), gamma=_f(-1.0), sigma=+1, B=B)


def neg_power(a: float, z: float, p: float, B: float) -> RegularSpeedup:
    """s(θ) = a z^p − a(θ+z)^p, p<0, z>0.  Includes s=θ/(θ+1) (a=1,z=1,p=−1)."""
    assert p < 0 and a > 0 and z > 0
    return RegularSpeedup(A=_f(-a * p), w=_f(z), gamma=_f(p - 1.0), sigma=+1, B=B)


def saturating(a: float, z: float, p: float, B: float) -> RegularSpeedup:
    """s(θ) = a z^p − a(z−θ)^p, p>1, z≥B.  Includes s=2θ−θ² (a=1,z=1,p=2,B≤1)."""
    assert p > 1 and a > 0 and z >= B
    return RegularSpeedup(A=_f(a * p), w=_f(z), gamma=_f(p - 1.0), sigma=-1, B=B)


def from_roofline(
    tokens_per_step: float,
    step_flops: float,
    grad_bytes: float,
    B: float,
    peak_flops: float = 197e12,
    link_bw: float = 50e9,
    overlap: float = 0.0,
) -> RegularSpeedup:
    """Speedup function of a data-parallel training job on θ TPU chips.

    step_time(θ) = F/(θ·R) + (1−overlap)·2·P·(θ−1)/(θ·W)   (ring all-reduce)
    s(θ) = T / step_time(θ) = A·θ / (D + C·θ)

    which is the paper's Table-1 row 3 (neg_power, p = −1): the
    roofline-derived speedup of a DP TPU job is *regular*, so SmartFill has a
    closed form for real cluster workloads (DESIGN.md §2).
    """
    C = (1.0 - overlap) * 2.0 * grad_bytes / link_bw  # comm seconds (asymptotic)
    D = step_flops / peak_flops - C                   # F/R − C
    if D <= 0:
        # comm fully hidden or dominant from θ=1: fall back to a nearly
        # linear regular function (compute-bound all the way).
        return neg_power(a=tokens_per_step / C, z=1e6, p=-1.0, B=B)
    # s(θ) = (T/C)·(1 − (D/C)/(D/C+θ)) = a z^p − a (θ+z)^p, p=−1, z=D/C.
    z = D / C
    a = tokens_per_step / C * z  # so that a z^{−1} − a(θ+z)^{−1} = T θ/(D+Cθ)
    return neg_power(a=a, z=z, p=-1.0, B=B)
