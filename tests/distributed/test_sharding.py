"""Tests for the active-mesh helpers of ``distributed/sharding.py``.

``logical_to_spec``/``constrain`` resolve against the innermost
``with Mesh(...)`` context or the mesh ``set_mesh`` installed, and are
exact no-ops without one.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from repro.distributed.sharding import (active_mesh, constrain,
                                        logical_to_spec, param_sharding,
                                        set_mesh, with_logical_rules)


def _mesh_2d():
    """A (data, model) mesh over whatever devices exist (sizes ≥ 1)."""
    devs = np.asarray(jax.devices())
    return Mesh(devs.reshape(-1, 1), ("data", "model"))


def test_active_mesh_none_without_context():
    assert active_mesh() is None


def test_active_mesh_tracks_context():
    with _mesh_2d() as mesh:
        got = active_mesh()
        assert got is not None
        assert tuple(got.axis_names) == ("data", "model")
        assert got.devices.size == mesh.devices.size
    assert active_mesh() is None


def test_set_mesh_installs_and_clears():
    """set_mesh installs a process-wide mesh that logical_to_spec
    resolves against, and set_mesh(None) clears it again."""
    mesh = _mesh_2d()
    try:
        set_mesh(mesh)
        got = active_mesh()
        assert got is not None and tuple(got.axis_names) == ("data", "model")
        assert logical_to_spec("ff") == P("model")
    finally:
        set_mesh(None)
    assert active_mesh() is None
    assert logical_to_spec("ff") is None


def test_logical_to_spec_without_mesh_is_none():
    assert logical_to_spec("batch", "ff") is None
    assert logical_to_spec("heads", None, "fsdp", shape=(4, 8, 16)) is None


def test_logical_to_spec_with_mesh():
    with _mesh_2d():
        spec = logical_to_spec("batch", "ff")
        # batch → ("pod", "data"): only "data" is present; ff → "model"
        assert spec == P("data", "model")
        assert logical_to_spec(None, "heads") == P(None, "model")


def test_logical_to_spec_divisibility_fallback():
    with _mesh_2d() as mesh:
        d = mesh.shape["data"]
        # a dim not divisible by the mesh axis falls back to unsharded
        spec = logical_to_spec("fsdp", shape=(d + 1,))
        if d > 1:
            assert spec == P(None)
        else:
            assert spec == P("data")     # everything divides 1


def test_constrain_noop_without_mesh():
    x = jnp.ones((4, 8))
    y = constrain(x, "batch", "ff")
    assert y is x


def test_constrain_applies_inside_mesh():
    x = jnp.ones((4, 8))
    with _mesh_2d():
        y = jax.jit(lambda a: constrain(a, "batch", "ff"))(x)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(x))


def test_param_sharding_with_and_without_mesh():
    assert param_sharding("layer0/wq", (16, 4, 8)) is None
    with _mesh_2d():
        spec = param_sharding("layer0/wq", (16, 4, 8))
        assert isinstance(spec, P)


def test_with_logical_rules_override():
    with _mesh_2d():
        with with_logical_rules({"ff": ("data",)}):
            assert logical_to_spec("ff") == P("data")
        assert logical_to_spec("ff") == P("model")


def test_model_forward_smoke_under_mesh():
    """The seed failure mode end-to-end: a model forward inside a mesh
    context used to AttributeError at the first constrain() call."""
    pytest.importorskip("repro.models")
    from repro.configs import get_config
    from repro.models import init_params, model_apply

    cfg = get_config("llama3.2-1b", smoke=True)
    params = init_params(jax.random.PRNGKey(0), cfg)
    batch = {
        "tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                                     cfg.vocab),
        "labels": jax.random.randint(jax.random.PRNGKey(2), (2, 16), 0,
                                     cfg.vocab),
    }
    with _mesh_2d():
        loss, _, _ = jax.jit(
            lambda p, b: model_apply(p, b, cfg, return_logits=True))(
                params, batch)
    assert np.isfinite(float(loss))
