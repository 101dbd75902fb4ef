"""core/fmath.py: float32 exp/log family to a few ulp, float64 untouched."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl

from repro.core import fmath

_RNG = np.random.default_rng(7)


def _logu(a, b, n=1 << 14):
    return np.exp(_RNG.uniform(np.log(a), np.log(b), n)).astype(np.float32)


def _ulps(got, want):
    sp = np.spacing(np.abs(want).astype(np.float32)).astype(np.float64)
    return float(np.max(np.abs(np.asarray(got, np.float64) - want) / sp))


# (function, numpy float64 twin, inputs, max ulps): the bounds sit just
# above what the reductions reach (log/exp/power < 1 ulp; Goldberg's
# log1p and Kahan's expm1 < 2.5 ulp)
CASES = {
    "log_near_1": (fmath.log, np.log, _RNG.uniform(0.5, 2, 1 << 14), 1.0),
    "log_wide": (fmath.log, np.log, _logu(1e-30, 1e30), 1.0),
    "exp": (fmath.exp, np.exp, _RNG.uniform(-80, 80, 1 << 14), 1.0),
    "log1p_small": (fmath.log1p, np.log1p, _logu(1e-7, 0.1), 2.5),
    "log1p_large": (fmath.log1p, np.log1p, _logu(0.1, 1e4), 2.5),
    "expm1_small": (fmath.expm1, np.expm1, _logu(1e-7, 0.5), 2.5),
    "expm1_wide": (fmath.expm1, np.expm1, _RNG.uniform(-20, 20, 1 << 14),
                   2.5),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_unary_float32_within_ulps(case):
    fn, ref, x, bound = CASES[case]
    x = np.asarray(x, np.float32)
    got = jax.jit(fn)(jnp.asarray(x))
    assert got.dtype == jnp.float32
    assert _ulps(got, ref(x.astype(np.float64))) <= bound


@pytest.mark.parametrize("y", [0.5, -0.5, -1.0, 1 / 0.7, -1 / 0.3, 3.3])
def test_power_float32_within_one_ulp_for_any_exponent(y):
    """|y·log x| reaches 40 here; the hi/lo carry keeps the error < 1 ulp
    where a plain exp(y·log x) would lose ~|y·log x| ulps."""
    x = _logu(1e-3, 1e5)
    want = x.astype(np.float64) ** np.float64(np.float32(y))
    ok = (want > 1e-37) & (want < 3e38)
    got = np.asarray(jax.jit(fmath.power)(jnp.asarray(x), np.float32(y)))
    assert _ulps(got[ok], want[ok]) <= 1.0


def test_float64_passes_through_bitwise():
    x = jnp.asarray(_logu(1e-3, 1e3).astype(np.float64))
    assert x.dtype == jnp.float64
    for fn, ref in [(fmath.log, jnp.log), (fmath.exp, jnp.exp),
                    (fmath.log1p, jnp.log1p), (fmath.expm1, jnp.expm1)]:
        np.testing.assert_array_equal(fn(x), ref(x))
    np.testing.assert_array_equal(fmath.power(x, -0.5), x ** -0.5)


def test_edge_values_follow_jnp():
    x = jnp.asarray([0.0, -1.0, np.inf, np.nan, 1.0], jnp.float32)
    np.testing.assert_array_equal(fmath.log(x), jnp.log(x))
    np.testing.assert_array_equal(fmath.log1p(x - 1.0), jnp.log1p(x - 1.0))
    e = jnp.asarray([-200.0, 0.0, 89.0, 200.0, np.nan], jnp.float32)
    np.testing.assert_array_equal(fmath.exp(e), jnp.exp(e))
    np.testing.assert_array_equal(fmath.expm1(e), jnp.expm1(e))
    b = jnp.asarray([0.0, 0.0, np.inf, 4.0], jnp.float32)
    p = jnp.asarray([2.0, -0.5, 0.5, -0.5], jnp.float32)
    np.testing.assert_array_equal(fmath.power(b, p),
                                  jnp.asarray([0.0, np.inf, np.inf, 0.5],
                                              jnp.float32))


def test_python_float_exponent_keeps_float32():
    x = jnp.asarray([2.0, 3.0], jnp.float32)
    assert fmath.power(x, 0.5).dtype == jnp.float32


def test_same_values_inside_a_pallas_kernel():
    """The kernels call fmath on vector blocks; interpret mode must agree
    with the XLA path to an ulp (fusion may contract a multiply-add)."""
    x = jnp.asarray(_logu(1e-3, 1e3, 8 * 128).reshape(8, 128))

    def kern(x_ref, o_ref):
        v = x_ref[...]
        o_ref[...] = fmath.power(v, np.float32(-0.7)) + fmath.log1p(v)

    with jax.enable_x64(False):
        got = pl.pallas_call(kern, out_shape=jax.ShapeDtypeStruct(
            x.shape, jnp.float32), interpret=True)(x)
        want = fmath.power(x, np.float32(-0.7)) + fmath.log1p(x)
    np.testing.assert_allclose(got, want, rtol=2.4e-7, atol=0)
