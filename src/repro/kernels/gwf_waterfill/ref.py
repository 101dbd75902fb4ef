"""Pure-jnp oracles for the water-filling kernels.

``gwf_waterfill_ref``      — the exact piecewise-linear WFP solve from
                             ``core/gwf.py`` (O(k log k) sort + prefix
                             sums) specialized to (u, h0) inputs.
``generic_waterfill_ref``  — the batched λ-bisection (generic
                             waterfill) for the regular-family
                             parameterization s'(θ) = A(w + σθ)^γ; the
                             oracle for the fused Pallas kernel and the
                             CPU/GPU fallback of its ``impl="auto"``
                             dispatch.
``hetero_waterfill_ref``   — the per-job-parameter variant (paper §7):
                             A, w, γ and σ are (N, K) *job-indexed*
                             arrays, so every job solves under its own
                             regular family (the saturating σ=−1 row
                             included); oracle + fallback for the fused
                             ``hetero_waterfill`` kernel.

All are jit/vmap-friendly pure functions.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core import fmath

_BIG = 1e30


def gwf_waterfill_ref(u, h0, b):
    """Exact piecewise-linear WFP solve. u (M,), h0 (M,), scalar b."""
    from repro.core.gwf import waterfill_level

    u = u.astype(jnp.float64) if u.dtype == jnp.float64 else u.astype(jnp.float32)
    h0 = h0.astype(u.dtype)
    b = jnp.asarray(b, u.dtype)
    active = u > 0
    h = waterfill_level(u, h0, b, active)
    return jnp.where(active, jnp.clip(u * (h - h0), 0.0, b), 0.0)


def lam_bracket(c, A, w, gamma, b, sigma):
    """Safe λ-bisection bracket for one instance of the regular family.

    Mirrors ``core/gwf.py::solve_cap_generic``: λ ∈ [s'(b)/max c,
    s'(0⁺)/min c], with s'(ε), ε = b/(8k), standing in for an infinite
    s'(0) (the w = 0, σ = +1 power family).  Returns (lam_lo, lam_hi,
    ds0) with ds0 = s'(0) capped at 1e30 so it stays f32-representable.
    """
    k = c.shape[-1]
    active = c > 0
    c_hi = jnp.max(jnp.where(active, c, -jnp.inf), axis=-1)
    c_lo = jnp.min(jnp.where(active, c, jnp.inf), axis=-1)

    def ds(t):
        return A * fmath.power(w + sigma * t, gamma)

    ds_b = ds(b)
    eps = b / (8.0 * k)
    ds0 = jnp.where(w > 0, A * fmath.power(jnp.maximum(w, 1e-300), gamma),
                    jnp.asarray(_BIG, c.dtype))
    ds_top = jnp.where(w > 0, ds0, ds(eps))
    lam_lo = ds_b / c_hi
    lam_hi = ds_top / c_lo * (1.0 + 1e-6)
    lam_hi = jnp.maximum(lam_hi, lam_lo * (1.0 + 1e-6))
    # degenerate (no active jobs): any positive bracket keeps logs finite
    good = jnp.isfinite(lam_lo) & (lam_lo > 0) & jnp.isfinite(lam_hi)
    lam_lo = jnp.where(good, lam_lo, 1.0)
    lam_hi = jnp.where(good, lam_hi, 2.0)
    return lam_lo, lam_hi, ds0


def hetero_lam_bracket(c, A, w, gamma, sigma, b):
    """Per-job λ-bisection bracket for one instance (paper §7 bounds).

    All of c, A, w, gamma, sigma are (K,) job-indexed; b is scalar.
    λ_lo = min_i s_i'(b)/c_i (the binding job fills the whole budget,
    β ≥ b); λ_hi = max_i s_i'(0⁺)/c_i (every job parks below
    ε = b/(8k), β ≤ k·ε < b).  ds0 is per-job, capped at 1e30 so it
    stays f32-representable in-kernel.
    """
    k = c.shape[-1]
    active = c > 0

    def ds(t):
        base = jnp.maximum(w + sigma * t, 1e-30)
        return A * fmath.power(base, gamma)

    ds_b = ds(b)
    eps = b / (8.0 * k)
    ds0 = jnp.where(w > 0, A * fmath.power(jnp.maximum(w, 1e-300), gamma),
                    jnp.asarray(_BIG, c.dtype))
    ds_top = jnp.where(w > 0, ds0, ds(eps))
    lam_lo = jnp.min(jnp.where(active, ds_b / c, jnp.inf), axis=-1)
    lam_hi = (jnp.max(jnp.where(active, ds_top / c, -jnp.inf), axis=-1)
              * (1.0 + 1e-6))
    lam_hi = jnp.maximum(lam_hi, lam_lo * (1.0 + 1e-6))
    # degenerate (no active jobs): any positive bracket keeps logs finite
    good = jnp.isfinite(lam_lo) & (lam_lo > 0) & jnp.isfinite(lam_hi)
    lam_lo = jnp.where(good, lam_lo, 1.0)
    lam_hi = jnp.where(good, lam_hi, 2.0)
    return lam_lo, lam_hi, ds0


@partial(jax.jit, static_argnames=("iters",))
def hetero_waterfill_ref(c, A, w, gamma, sigma, b, iters=64):
    """Batched per-job waterfill, pure jnp: (N, K) job-indexed params.

    Every array is (N, K) except b (N,); σ entries are ±1 per job.
    Inactive slots are marked by c = 0 (their family params must still
    be valid — edge-replicate, don't zero).
    """
    c = jnp.asarray(c)
    dt = c.dtype
    shape = c.shape
    A = jnp.broadcast_to(jnp.asarray(A, dt), shape)
    w = jnp.broadcast_to(jnp.asarray(w, dt), shape)
    gamma = jnp.broadcast_to(jnp.asarray(gamma, dt), shape)
    sigma = jnp.broadcast_to(jnp.asarray(sigma, dt), shape)
    b = jnp.broadcast_to(jnp.asarray(b, dt), shape[:1])

    def one(c1, A1, w1, g1, s1, b1):
        lam_lo, lam_hi, ds0 = hetero_lam_bracket(c1, A1, w1, g1, s1, b1)
        active = c1 > 0

        def theta_of(lam):
            y = c1 * lam
            base = jnp.where(active, jnp.maximum(y / A1, 1e-30), 1.0)
            th = s1 * (fmath.power(base, 1.0 / g1) - w1)
            th = jnp.clip(th, 0.0, b1)
            th = jnp.where(y >= ds0, 0.0, th)
            return jnp.where(active, th, 0.0)

        def body(_, carry):
            lo, hi = carry
            mid = fmath.exp(0.5 * (fmath.log(lo) + fmath.log(hi)))
            below = jnp.sum(theta_of(mid)) > b1
            return jnp.where(below, mid, lo), jnp.where(below, hi, mid)

        lo, hi = jax.lax.fori_loop(0, iters, body, (lam_lo, lam_hi))
        th = theta_of(fmath.exp(0.5 * (fmath.log(lo) + fmath.log(hi))))
        tot = jnp.sum(th)
        th = jnp.where(tot > 0, th * (b1 / tot), th)
        return jnp.minimum(th, b1)

    return jax.vmap(one)(c, A, w, gamma, sigma, b)


@partial(jax.jit, static_argnames=("sigma", "iters"))
def generic_waterfill_ref(c, A, w, gamma, b, sigma=1, iters=64):
    """Batched generic waterfill, pure jnp: (N, K) c → (N, K) θ.

    A, w, gamma, b are (N,) per-instance scalars; ``sigma`` (static ±1)
    is shared.  Inactive slots are marked by c = 0.
    """
    c = jnp.asarray(c)
    dt = c.dtype
    A = jnp.broadcast_to(jnp.asarray(A, dt), c.shape[:1])
    w = jnp.broadcast_to(jnp.asarray(w, dt), c.shape[:1])
    gamma = jnp.broadcast_to(jnp.asarray(gamma, dt), c.shape[:1])
    b = jnp.broadcast_to(jnp.asarray(b, dt), c.shape[:1])

    def one(c1, A1, w1, g1, b1):
        lam_lo, lam_hi, ds0 = lam_bracket(c1, A1, w1, g1, b1, sigma)
        active = c1 > 0

        def theta_of(lam):
            y = c1 * lam
            base = jnp.where(active, y / A1, 1.0)
            th = sigma * (fmath.power(base, 1.0 / g1) - w1)
            th = jnp.clip(th, 0.0, b1)
            th = jnp.where(y >= ds0, 0.0, th)
            return jnp.where(active, th, 0.0)

        def body(_, carry):
            lo, hi = carry
            mid = fmath.exp(0.5 * (fmath.log(lo) + fmath.log(hi)))
            below = jnp.sum(theta_of(mid)) > b1
            return jnp.where(below, mid, lo), jnp.where(below, hi, mid)

        lo, hi = jax.lax.fori_loop(0, iters, body, (lam_lo, lam_hi))
        th = theta_of(fmath.exp(0.5 * (fmath.log(lo) + fmath.log(hi))))
        tot = jnp.sum(th)
        th = jnp.where(tot > 0, th * (b1 / tot), th)
        return jnp.minimum(th, b1)

    return jax.vmap(one)(c, A, w, gamma, b)
