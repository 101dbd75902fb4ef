"""exp, log, log1p, expm1 and power, accurate to a few ulp in float32.

The speedup families integrate and invert through these functions in
the regime where their results cancel (class aggregates, shifted
families with θ ≪ w), so every ulp of them shows in the plan.  On a TPU
v5e the default float32 ``log``, ``log1p`` and ``expm1`` are off by up
to about 4,000 ulp and ``exp`` and ``pow`` by up to about 100, where
the CPU's are within 2.5 (``tools/chip_diag.py`` measures both), and
Pallas kernels cannot ask for a more accurate variant.  So float32 inputs go through
the polynomial reductions below, built only from multiply, add, divide
and integer bit operations, which every backend rounds alike; the CPU
and the chip then agree to an ulp or two.  Any other dtype goes to
``jnp`` unchanged, so float64 results are bitwise what they were.

The reductions are musl's single-precision ``logf``/``expf`` (from
FreeBSD's ``e_logf.c``/``e_expf.c``).  ``power`` carries ``log x`` and
``y·log x`` as unevaluated hi + lo pairs (Dekker products), so its error
does not grow with ``|y·log x|``; ``log1p`` and ``expm1`` use
Goldberg's and Kahan's corrections on top of ``log``/``exp``.  Inputs
outside the normal, finite range (zero, negatives, inf, nan, values
whose ``exp`` overflows) fall through to ``jnp`` for its edge-case
semantics.  All of them lower inside Pallas TPU kernels on vector
operands (Mosaic has no scalar bitcast).
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np
from jax import lax

__all__ = ["exp", "expm1", "log", "log1p", "power"]

_F32 = np.float32               # numpy scalars: literals, not captured arrays
_TINY = 1.1754944e-38            # smallest normal float32
_HUGE = 3.4028235e38

# logf: ln 2 split so that k·_LN2_HI is exact for |k| < 2^8
_LN2_HI = 6.9314575195e-01       # 0x3f317200
_LN2_LO = 1.4286067653e-06       # 0x35bfbe8e
_INV_LN2 = 1.4426950216e+00
# |(log(1+s) − log(1−s))/s − Lg(s)| < 2^-34.24 on the reduced range
_LG1 = 0.66666662693
_LG2 = 0.40000972152
_LG3 = 0.28498786688
_LG4 = 0.24279078841
# expf: exp(r) on |r| ≤ ln2/2 through the Remez fit r − r²(P1 + r²P2)
_P1 = 1.6666625440e-1
_P2 = -2.7667332906e-3
_EXP_MAX = 88.0                  # |x| beyond this goes to jnp.exp
_I23 = np.int32(23)               # float32 mantissa bits


def _f32(*xs):
    return jnp.result_type(*xs) == _F32


def _c(v):
    return _F32(v)


def _split(a):
    """Veltkamp split: a = hi + lo, each with at most 12 significant bits."""
    t = _c(4097.0) * a
    hi = t - (t - a)
    return hi, a - hi


def _two_prod(a, b):
    """a·b = p + e exactly (Dekker)."""
    p = a * b
    ah, al = _split(a)
    bh, bl = _split(b)
    e = ((ah * bh - p) + ah * bl + al * bh) + al * bl
    return p, e


def _two_sum(a, b):
    """a + b = s + e exactly (Knuth)."""
    s = a + b
    bv = s - a
    e = (a - (s - bv)) + (b - bv)
    return s, e


def _reduce_log(x):
    """x = 2^k·m with m in [√2/2, √2); returns (k as float32, m − 1)."""
    ix = lax.bitcast_convert_type(x, jnp.int32)
    ix = ix + (0x3F800000 - 0x3F3504F3)
    k = lax.shift_right_arithmetic(ix, _I23) - 0x7F
    m = lax.bitcast_convert_type((ix & 0x007FFFFF) + 0x3F3504F3, _F32)
    return k.astype(_F32), m - _c(1.0)


def _log_poly(f):
    """(s, R) with log(1 + f) = f − f²/2 + s·(f²/2 + R), s = f/(2 + f)."""
    s = f / (_c(2.0) + f)
    z = s * s
    w = z * z
    return s, z * (_c(_LG1) + w * _c(_LG3)) + w * (_c(_LG2) + w * _c(_LG4))


def _normal(x):
    return (x >= _c(_TINY)) & (x <= _c(_HUGE))


def log(x):
    """Natural log; float32 to about 1 ulp."""
    if not _f32(x):
        return jnp.log(x)
    x = jnp.asarray(x, _F32)
    ok = _normal(x)
    xs = jnp.where(ok, x, _c(1.0))
    dk, f = _reduce_log(xs)
    s, R = _log_poly(f)
    hfsq = _c(0.5) * f * f
    out = s * (hfsq + R) + dk * _c(_LN2_LO) - hfsq + f + dk * _c(_LN2_HI)
    return jnp.where(ok, out, jnp.log(x))


def _log_hilo(x):
    """log x as hi + lo, for normal positive float32 x."""
    dk, f = _reduce_log(x)
    s, R = _log_poly(f)
    ff, ff_e = _two_prod(f, f)
    hfsq_hi, hfsq_lo = _c(0.5) * ff, _c(0.5) * ff_e
    d = s * ((hfsq_hi + hfsq_lo) + R)
    h1, e1 = _two_sum(dk * _c(_LN2_HI), f)            # k·ln2_hi is exact
    h2, e2 = _two_sum(h1, -hfsq_hi)
    lo = (e1 + e2) - hfsq_lo + d + dk * _c(_LN2_LO)
    hi = h2 + lo
    return hi, lo - (hi - h2)


def _exp_hilo(hi, lo):
    """exp(hi + lo) for |hi| ≤ _EXP_MAX, |lo| ≤ ulp(hi)."""
    k = jnp.floor(hi * _c(_INV_LN2) + _c(0.5))       # Mosaic has no round
    rhi = hi - k * _c(_LN2_HI)                        # exact (Sterbenz)
    rlo = k * _c(_LN2_LO) - lo
    r = rhi - rlo
    rr = r * r
    c = r - rr * (_c(_P1) + rr * _c(_P2))
    y = _c(1.0) + (r * c / (_c(2.0) - c) - rlo + rhi)
    ki = k.astype(jnp.int32)
    k1 = lax.shift_right_arithmetic(ki, np.int32(1))
    s1 = lax.bitcast_convert_type(lax.shift_left(k1 + 0x7F, _I23), _F32)
    s2 = lax.bitcast_convert_type(lax.shift_left(ki - k1 + 0x7F, _I23), _F32)
    return y * s1 * s2


def exp(x):
    """e^x; float32 to about 1 ulp."""
    if not _f32(x):
        return jnp.exp(x)
    x = jnp.asarray(x, _F32)
    ok = jnp.abs(x) <= _c(_EXP_MAX)
    xs = jnp.where(ok, x, _c(0.0))
    return jnp.where(ok, _exp_hilo(xs, jnp.zeros_like(xs)), jnp.exp(x))


def log1p(x):
    """log(1 + x), accurate for small x (Goldberg's correction)."""
    if not _f32(x):
        return jnp.log1p(x)
    x = jnp.asarray(x, _F32)
    u = _c(1.0) + x
    ok = _normal(u)
    us = jnp.where(ok, u, _c(2.0))
    d = us - _c(1.0)
    dsafe = jnp.where(d == 0, _c(1.0), d)
    out = jnp.where(d == 0, x, log(us) * (x / dsafe))
    return jnp.where(ok, out, jnp.log1p(x))


def expm1(x):
    """e^x − 1, accurate for small x (Kahan's correction)."""
    if not _f32(x):
        return jnp.expm1(x)
    x = jnp.asarray(x, _F32)
    ok = jnp.abs(x) <= _c(_EXP_MAX)
    xs = jnp.where(ok, x, _c(0.0))
    u = exp(xs)
    um1 = u - _c(1.0)
    lu = log(jnp.where(u == _c(1.0), _c(2.0), u))
    out = jnp.where(u == _c(1.0), xs,
                    jnp.where(um1 == _c(-1.0), _c(-1.0), um1 * xs / lu))
    # beyond ±88 expm1 is exp (or −1); Mosaic has no expm1 to fall to
    return jnp.where(ok, out, jnp.exp(x) - _c(1.0))


def power(x, y):
    """x ** y; float32 to a few ulp for normal x > 0 whatever |y·log x|."""
    if not _f32(x, y):
        return x ** y
    x, y = jnp.asarray(x, _F32), jnp.asarray(y, _F32)
    lhi, llo = _log_hilo(jnp.where(_normal(x), x, _c(1.0)))
    p, pe = _two_prod(y, lhi)
    plo = pe + y * llo
    zhi = p + plo
    zlo = plo - (zhi - p)
    ok = _normal(x) & (jnp.abs(zhi) <= _c(_EXP_MAX))
    out = _exp_hilo(jnp.where(ok, zhi, _c(0.0)), jnp.where(ok, zlo, _c(0.0)))
    return jnp.where(ok, out, x ** y)
