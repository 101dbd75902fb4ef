"""Speedup-family unit tests: paper §2 assumptions + Table 1 rows."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    GenericSpeedup,
    from_roofline,
    log_speedup,
    neg_power,
    power,
    saturating,
    shifted_power,
)

B = 10.0

FAMILIES = {
    "power": power(1.0, 0.5, B),
    "power_08": power(10.0, 0.8, B),
    "shifted": shifted_power(1.0, 4.0, 0.5, B),
    "log": log_speedup(1.0, 1.0, B),
    "neg_power": neg_power(1.0, 1.0, -1.0, B),        # θ/(θ+1)
    "saturating": saturating(1.0, 1.0, 2.0, 0.9),      # 2θ−θ², B<1
}


@pytest.mark.parametrize("name", list(FAMILIES))
def test_paper_assumptions(name):
    sp = FAMILIES[name]
    assert sp.check_concave(), f"{name} violates paper §2 assumptions"


@pytest.mark.parametrize("name", list(FAMILIES))
def test_derivative_matches_fd(name):
    sp = FAMILIES[name]
    th = jnp.linspace(0.05, sp.B * 0.95, 101)
    eps = 1e-6
    fd = (sp.s(th + eps) - sp.s(th - eps)) / (2 * eps)
    np.testing.assert_allclose(np.array(sp.ds(th)), np.array(fd),
                               rtol=1e-5, atol=1e-7)


@pytest.mark.parametrize("name", list(FAMILIES))
def test_ds_inv_roundtrip(name):
    sp = FAMILIES[name]
    th = jnp.linspace(0.01, sp.B, 64)
    back = sp.ds_inv(sp.ds(th))
    np.testing.assert_allclose(np.array(back), np.array(th), rtol=1e-6, atol=1e-8)


def test_table1_examples():
    # row 1: s = (θ+1)^0.5 − 1
    sp = shifted_power(1.0, 1.0, 0.5, B)
    assert np.isclose(float(sp.s(jnp.float64(3.0))), 2.0 - 1.0)
    # row 2: s = ln(θ+1)
    sp = log_speedup(1.0, 1.0, B)
    assert np.isclose(float(sp.s(jnp.float64(np.e - 1))), 1.0)
    # row 3: s = θ/(θ+1) = 1·1^{−1} − 1·(θ+1)^{−1}
    sp = neg_power(1.0, 1.0, -1.0, B)
    assert np.isclose(float(sp.s(jnp.float64(1.0))), 0.5)
    # row 4: s = 2θ − θ² on B ≤ 1
    sp = saturating(1.0, 1.0, 2.0, 0.9)
    assert np.isclose(float(sp.s(jnp.float64(0.5))), 0.75)


def test_generic_matches_regular():
    reg = log_speedup(1.0, 1.0, B)
    gen = GenericSpeedup(s_fn=lambda t: jnp.log1p(t),
                         ds_fn=lambda t: 1.0 / (1.0 + t), B=B)
    th = jnp.linspace(0.0, B, 33)
    np.testing.assert_allclose(np.array(gen.s(th)), np.array(reg.s(th)), rtol=1e-12)
    y = jnp.linspace(float(reg.ds(jnp.float64(B))), float(reg.ds0()), 17)
    np.testing.assert_allclose(np.array(gen.ds_inv(y)), np.array(reg.ds_inv(y)),
                               rtol=1e-6, atol=1e-7)


def test_from_roofline_is_regular_and_concave():
    # llama-ish 1B training job: 6·N·D flops/step, 2 bytes/param grads
    sp = from_roofline(tokens_per_step=4096 * 256, step_flops=6 * 1.2e9 * 4096 * 256,
                       grad_bytes=2 * 1.2e9, B=256.0)
    assert sp.check_concave(n=513)
    # speedup must be increasing and sub-linear: s(2θ) < 2 s(θ)
    s1 = float(sp.s(jnp.float64(8.0)))
    s2 = float(sp.s(jnp.float64(16.0)))
    assert s1 < s2 < 2 * s1


@pytest.mark.parametrize("name", ["shifted", "log", "neg_power", "saturating"])
def test_s_keeps_f32_digits_when_theta_much_smaller_than_w(name):
    """Class aggregates (core/classes.py) scale the shift w by the class
    count n while each job's share θ stays small: s must not lose its
    float32 digits to the cancellation in (w+σθ)^{γ+1} − w^{γ+1}."""
    import jax

    from repro.core import class_speedup

    n = 31_250.0
    th = np.linspace(1e-3, 1.0, 64) * (0.9 if name == "saturating" else B)
    ref = np.asarray(class_speedup(FAMILIES[name], n).s(jnp.asarray(th)),
                     np.float64)
    with jax.enable_x64(False):
        sp32 = class_speedup(FAMILIES[name], jnp.float32(n))
        got = np.asarray(sp32.s(jnp.asarray(th, jnp.float32)), np.float64)
    np.testing.assert_allclose(got, ref, rtol=1e-5)
