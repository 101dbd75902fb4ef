"""GWF water-filling — Pallas TPU kernels for the paper's hot spot.

Two fused kernels share the same TPU-native shape: classical
water-filling is sort-based and sequential — hostile to the TPU's
vector units — so both recast the solve as a *fixed-iteration bisection*
whose every iteration is one fused VPU pass over the (8, 128)-tiled job
arrays resident in VMEM (elementwise map, clip, reduce) with the
[lo, hi] bracket carried in registers.  No sort, no data-dependent
control flow, deterministic latency — exactly what a cluster scheduler
embedded in a serving loop needs when managing thousands of jobs.

``gwf_waterfill`` (level bisection)
    The WFP for rectangle bottles (paper §4.5.1): find h with
    β(h) = Σᵢ clip(uᵢ·(h − h₀ᵢ), 0, b) = b, then θᵢ from h.  One
    instance per call; jobs padded to a multiple of 1024 and shaped
    (rows, 8, 128); inactive slots get u = 0.

``generic_waterfill`` (pressure bisection, batched)
    The *generic* CAP path fused end-to-end: bisection on the water
    pressure λ with the regular-family derivative inverse
    θᵢ(λ) = σ((cᵢλ/A)^{1/γ} − w) evaluated blockwise in-kernel, one
    grid step per instance — N independent (c, A, w, γ, b) instances
    solved in a single ``pallas_call``.  This is the TPU path even for
    regular speedups at scale: the closed form needs a sort, the
    bisection needs only maps and reductions.

``hetero_waterfill`` (pressure bisection, per-job parameters)
    The paper-§7 variant: A, w, γ and σ are *job-indexed* (N, K) arrays
    living in VMEM alongside c, so every job inverts its own regular
    family — mixed fleets (power + log + saturating in one instance)
    water-fill in a single fused kernel.  The λ-bracket and the per-job
    parking threshold s_i'(0) are computed in-kernel from the same
    blocks (one extra VPU pass), leaving only the budget in SMEM.
    Inactive lanes are c = 0 with *valid* family params (the fleet
    layer's edge-replication convention) — every transcendental is
    additionally guarded, so garbage lanes cannot NaN the reductions.

64 iterations bracket the answer to ~2⁻⁶⁴ of the initial interval —
beyond f32 resolution.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import fmath

from .ref import lam_bracket

_TILE = 1024  # 8 sublanes × 128 lanes
_NPAR = 8     # per-instance SMEM parameters of generic_waterfill


def _wf_kernel(u_ref, h0_ref, b_ref, theta_ref, *, iters):
    u = u_ref[...]                      # (rows, 8, 128)
    h0 = h0_ref[...]
    b = b_ref[0]

    # bracket: β(lo) ≤ b ≤ β(hi)
    big = jnp.where(u > 0, h0, -jnp.inf)
    lo0 = jnp.min(jnp.where(u > 0, h0, jnp.inf))
    hi0 = jnp.max(big + b / jnp.maximum(u, 1e-30))

    def beta(h):
        vol = jnp.clip(u * (h - h0), 0.0, b)
        return jnp.sum(vol)

    def body(i, carry):
        lo, hi = carry
        mid = 0.5 * (lo + hi)
        below = beta(mid) < b
        lo = jnp.where(below, mid, lo)
        hi = jnp.where(below, hi, mid)
        return lo, hi

    lo, hi = jax.lax.fori_loop(0, iters, body, (lo0, hi0))
    h = 0.5 * (lo + hi)
    theta_ref[...] = jnp.clip(u * (h - h0), 0.0, b)


def gwf_waterfill(u, h0, b, *, iters: int = 64, interpret: bool = False):
    """Solve WFP for rectangle bottles.

    u: (M,) widths (0 ⇒ inactive job); h0: (M,) bottoms; b: scalar budget.
    Returns θ: (M,) with Σθ = b (to bisection tolerance).
    """
    M = u.shape[0]
    Mp = -(-M // _TILE) * _TILE
    up = jnp.pad(u.astype(jnp.float32), (0, Mp - M))
    hp = jnp.pad(h0.astype(jnp.float32), (0, Mp - M))
    rows = Mp // _TILE
    up = up.reshape(rows, 8, 128)
    hp = hp.reshape(rows, 8, 128)
    b_arr = jnp.asarray([b], jnp.float32)

    theta = pl.pallas_call(
        functools.partial(_wf_kernel, iters=iters),
        grid=(),
        in_specs=[
            pl.BlockSpec(up.shape, lambda: (0, 0, 0)),
            pl.BlockSpec(hp.shape, lambda: (0, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec(up.shape, lambda: (0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(up.shape, jnp.float32),
        interpret=interpret,
    )(up, hp, b_arr)
    return theta.reshape(Mp)[:M]


def _bisect_mid(lo, hi):
    """A probe inside the scalar bracket 0 < lo < hi.

    Geometric while the bracket spans more than a factor of two, then
    arithmetic: the chip's scalar exp/log are too coarse (about 1e-6
    relative) to halve a bracket narrower than that, and a scalar has no
    bitcast in Mosaic, so fmath cannot stand in for them here.
    """
    geo = jnp.clip(jnp.exp(0.5 * (jnp.log(lo) + jnp.log(hi))), lo, hi)
    return jnp.where(hi > 2.0 * lo, geo, 0.5 * (lo + hi))


def _generic_wf_kernel(c_ref, par_ref, theta_ref, *, iters, sigma):
    c = c_ref[...]                      # (1, rows, 8, 128) — one instance
    # par is the whole flat (N·8,) SMEM table; this step reads its row
    base = pl.program_id(0) * _NPAR
    A = par_ref[base]
    w = par_ref[base + 1]
    ginv = par_ref[base + 2]            # 1/γ, precomputed host-side
    b = par_ref[base + 3]
    lam_lo = par_ref[base + 4]
    lam_hi = par_ref[base + 5]
    ds0 = par_ref[base + 6]
    active = c > 0.0

    def theta_of(lam):
        y = c * lam
        # (y/A)^{1/γ} via exp/log — the VPU has no generic power; the
        # base is 1 on inactive lanes so the log stays finite.
        base = jnp.where(active, y / A, 1.0)
        th = sigma * (fmath.power(base, ginv) - w)
        th = jnp.clip(th, 0.0, b)
        # park jobs whose marginal value at zero is below the pressure
        th = jnp.where(y >= ds0, 0.0, th)
        return jnp.where(active, th, 0.0)

    def body(i, carry):
        lo, hi = carry
        # bisect in log-space for relative precision across wide λ ranges
        mid = _bisect_mid(lo, hi)
        below = jnp.sum(theta_of(mid)) > b       # β > b ⇒ λ* right of mid
        lo = jnp.where(below, mid, lo)
        hi = jnp.where(below, hi, mid)
        return lo, hi

    lo, hi = jax.lax.fori_loop(0, iters, body, (lam_lo, lam_hi))
    th = theta_of(_bisect_mid(lo, hi))
    # exact budget: rescale the fp residual onto the positive allocations
    tot = jnp.sum(th)
    th = jnp.where(tot > 0, th * (b / tot), th)
    theta_ref[...] = jnp.minimum(th, b)


def generic_waterfill(c, A, w, gamma, b, *, sigma: int = 1, iters: int = 64,
                      interpret: bool = False):
    """Fused batched generic waterfill: (N, K) c-vectors → (N, K) θ.

    One grid step per instance; each step runs the whole λ-bisection
    over its VMEM-resident block.  A, w, gamma, b are (N,) per-instance
    scalars, passed with the bracket as one flat (N·8,) SMEM table that
    every step indexes by its instance (a per-step (1, 8) SMEM block is
    not a legal TPU tiling); ``sigma`` ∈ {+1, −1} is static.  Inactive
    slots are marked by c = 0.  Kernel math is float32.
    """
    c = jnp.asarray(c)
    if c.ndim != 2:
        raise ValueError("c must be (N, K)")
    N, K = c.shape
    dt = c.dtype
    A = jnp.broadcast_to(jnp.asarray(A, dt), (N,))
    w = jnp.broadcast_to(jnp.asarray(w, dt), (N,))
    gamma = jnp.broadcast_to(jnp.asarray(gamma, dt), (N,))
    b = jnp.broadcast_to(jnp.asarray(b, dt), (N,))
    lam_lo, lam_hi, ds0 = lam_bracket(c, A, w, gamma, b, sigma)

    Kp = -(-K // _TILE) * _TILE
    rows = Kp // _TILE
    cp = jnp.pad(c.astype(jnp.float32), ((0, 0), (0, Kp - K)))
    cp = cp.reshape(N, rows, 8, 128)
    par = jnp.stack(
        [A, w, 1.0 / gamma, b, lam_lo, lam_hi, ds0, jnp.zeros_like(A)],
        axis=1).astype(jnp.float32).reshape(N * _NPAR)

    # traced with x64 off: the kernel is f32 throughout, and under x64
    # the grid's index maps return int64, which Mosaic refuses
    with jax.enable_x64(False):
        theta = pl.pallas_call(
            functools.partial(_generic_wf_kernel, iters=iters, sigma=sigma),
            grid=(N,),
            in_specs=[
                pl.BlockSpec((1, rows, 8, 128), lambda n: (n, 0, 0, 0)),
                pl.BlockSpec(memory_space=pltpu.SMEM),
            ],
            out_specs=pl.BlockSpec((1, rows, 8, 128),
                                   lambda n: (n, 0, 0, 0)),
            out_shape=jax.ShapeDtypeStruct((N, rows, 8, 128), jnp.float32),
            interpret=interpret,
        )(cp, par)
    return theta.reshape(N, Kp)[:, :K]


_F32_BIG = 1e30      # f32-representable stand-in for an infinite s'(0)


def _hetero_wf_kernel(c_ref, A_ref, w_ref, g_ref, s_ref, b_ref, theta_ref,
                      *, iters):
    c = c_ref[...]                      # (1, rows, 8, 128) — one instance
    A = A_ref[...]
    w = w_ref[...]
    ginv = 1.0 / g_ref[...]             # γ ≠ 0 for every regular family
    sg = s_ref[...]                     # σ ∈ {±1} per job, as float
    b = b_ref[pl.program_id(0)]         # whole (N,) budget vector in SMEM
    active = c > 0.0

    def po(base, e):
        # the VPU has no generic power: fmath's exp/log reductions, which
        # round alike on every backend; base is clamped positive so
        # inactive/edge lanes stay finite.
        return fmath.power(jnp.maximum(base, 1e-30), e)

    # per-job bracket & parking threshold (mirrors ref.hetero_lam_bracket).
    # All literals are pinned f32: under jax_enable_x64 a bare python
    # float would promote the bisection carry to f64 mid-loop.
    one = jnp.float32(1.0)
    gam = g_ref[...]
    ds_b = A * po(w + sg * b, gam)              # s_i'(b)
    k_act = jnp.maximum(jnp.sum(jnp.where(active, one, 0).astype(c.dtype)),
                        one)
    eps = b / (jnp.float32(8.0) * k_act)
    ds0 = jnp.where(w > 0, A * po(w, gam), jnp.float32(_F32_BIG))
    ds_top = jnp.where(w > 0, ds0, A * po(w + sg * eps, gam))
    lam_lo = jnp.min(jnp.where(active, ds_b / c, jnp.inf))
    lam_hi = (jnp.max(jnp.where(active, ds_top / c, -jnp.inf))
              * jnp.float32(1.0 + 1e-6))
    lam_hi = jnp.maximum(lam_hi, lam_lo * jnp.float32(1.0 + 1e-6))
    good = jnp.isfinite(lam_lo) & (lam_lo > 0) & jnp.isfinite(lam_hi)
    lam_lo = jnp.where(good, lam_lo, one)
    lam_hi = jnp.where(good, lam_hi, jnp.float32(2.0))

    def theta_of(lam):
        y = c * lam
        base = jnp.where(active, jnp.maximum(y / A, 1e-30), 1.0)
        th = sg * (po(base, ginv) - w)
        th = jnp.clip(th, 0.0, b)
        # park jobs whose own marginal value at zero is below the pressure
        th = jnp.where(y >= ds0, 0.0, th)
        return jnp.where(active, th, 0.0)

    def body(i, carry):
        lo, hi = carry
        # bisect in log-space for relative precision across wide λ ranges
        mid = _bisect_mid(lo, hi)
        below = jnp.sum(theta_of(mid)) > b       # β > b ⇒ λ* right of mid
        lo = jnp.where(below, mid, lo)
        hi = jnp.where(below, hi, mid)
        return lo, hi

    lo, hi = jax.lax.fori_loop(0, iters, body, (lam_lo, lam_hi))
    th = theta_of(_bisect_mid(lo, hi))
    # exact budget: rescale the fp residual onto the positive allocations
    tot = jnp.sum(th)
    th = jnp.where(tot > 0, th * (b / tot), th)
    theta_ref[...] = jnp.minimum(th, b)


def hetero_waterfill(c, A, w, gamma, sigma, b, *, iters: int = 64,
                     interpret: bool = False):
    """Fused per-job-parameter waterfill: (N, K) job-indexed families.

    c, A, w, gamma, sigma: (N, K) arrays — job (n, i) inverts its own
    ``s'(θ) = A (w + σθ)^γ``; b: (N,) budgets.  One grid step per
    instance; each step runs the whole λ-bisection over six
    VMEM-resident blocks.  Inactive slots are marked by c = 0 and must
    carry valid family params (edge-replicated, never zeroed).  Kernel
    math is float32; padding lanes use σ=+1, A=w=γ=1.
    """
    c = jnp.asarray(c)
    if c.ndim != 2:
        raise ValueError("c must be (N, K)")
    N, K = c.shape
    dt = c.dtype
    shape = (N, K)
    A = jnp.broadcast_to(jnp.asarray(A, dt), shape)
    w = jnp.broadcast_to(jnp.asarray(w, dt), shape)
    gamma = jnp.broadcast_to(jnp.asarray(gamma, dt), shape)
    sigma = jnp.broadcast_to(jnp.asarray(sigma, dt), shape)
    b = jnp.broadcast_to(jnp.asarray(b, dt), (N,))

    Kp = -(-K // _TILE) * _TILE
    rows = Kp // _TILE

    def block(x, pad):
        xp = jnp.pad(x.astype(jnp.float32), ((0, 0), (0, Kp - K)),
                     constant_values=pad)
        return xp.reshape(N, rows, 8, 128)

    blocks = [block(c, 0.0), block(A, 1.0), block(w, 1.0),
              block(gamma, 1.0), block(sigma, 1.0)]
    spec = pl.BlockSpec((1, rows, 8, 128), lambda n: (n, 0, 0, 0))

    with jax.enable_x64(False):         # see generic_waterfill
        theta = pl.pallas_call(
            functools.partial(_hetero_wf_kernel, iters=iters),
            grid=(N,),
            in_specs=[spec] * 5 + [pl.BlockSpec(memory_space=pltpu.SMEM)],
            out_specs=spec,
            out_shape=jax.ShapeDtypeStruct((N, rows, 8, 128), jnp.float32),
            interpret=interpret,
        )(*blocks, b.astype(jnp.float32))
    return theta.reshape(N, Kp)[:, :K]
