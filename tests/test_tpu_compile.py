"""The main path compiles for a TPU v5e that is described, not attached.

The TPU compiler is installed with jax; ``topologies.get_topology_desc``
describes a v5e host without a chip, and ``.lower(...).compile()``
against one of its devices raises whatever the chip's compiler would.
This catches what interpret mode cannot: tile-illegal block specs, int64
index maps, programs that do not fit.  Nothing runs and nothing is timed.

Everything here compiles in float32 (the chip path; conftest turns x64
on for the CPU references), with the persistent compile cache off: a
compile for a described device cannot be read back without one.  The
topology is described inside a module fixture only, never at import:
one process at a time may load the TPU library, and the test workers
must all collect the same tests.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

B = 10.0
K = 1024          # one (8, 128) tile per row: the kernels' dispatch floor
N_KERNEL = 8


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler, or its lock is held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def _compile(sharding, fn, *args, **static):
    """Lower ``fn`` for the described chip in f32 and compile it."""
    with jax.enable_x64(False):
        shapes = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(
                np.shape(a), jnp.asarray(a).dtype, sharding=sharding),
            args)
        return jax.jit(fn, static_argnames=tuple(static)).lower(
            *shapes, **static).compile()


def _kernel_case(name):
    from repro.kernels.gwf_waterfill import kernel

    f = np.float32
    row, col = np.zeros(K, f), np.zeros((N_KERNEL, K), f)
    vec = np.zeros(N_KERNEL, f)
    return {
        "gwf_waterfill": (kernel.gwf_waterfill, (row, row, f(1.0))),
        "generic_waterfill": (kernel.generic_waterfill,
                              (col, vec, vec, vec, vec)),
        "hetero_waterfill": (kernel.hetero_waterfill, (col,) * 5 + (vec,)),
    }[name]


@pytest.mark.parametrize("name", ["gwf_waterfill", "generic_waterfill",
                                  "hetero_waterfill"])
def test_waterfill_kernel_compiles(one_chip, name):
    fn, args = _kernel_case(name)
    compiled = _compile(one_chip, fn, *args)
    assert "tpu_custom_call" in compiled.as_text()


def test_stream_chunk_compiles(one_chip):
    from repro.core import power, sample_arrival_stream
    from repro.core.smartfill import _fast_ok
    from repro.serve import StreamCascadePolicy, StreamController
    from repro.serve.stream import _event_arrays, _stream_chunk, _stream_state0

    M = 16
    stream = sample_arrival_stream(
        17, horizon=1_800.0, rate=0.12, diurnal=0.75, period=1_800.0, B=B,
        n_budget_events=2, budget_frac=(0.3, 0.8), deadline_slack=50.0)
    with jax.enable_x64(False):
        sp = power(1.0, 0.5, B)
        ctl = StreamController(sp, B, max_live=M,
                               policy=StreamCascadePolicy(sp, B))
        state = _stream_state0(M, len(stream), B, jnp.float32)
    t_e, kind, pi, pf = _event_arrays(stream)
    f = np.float32
    events = (t_e.astype(f), kind, pi, pf.astype(f))
    x = np.asarray(stream.x, f)
    w = np.asarray(stream.w, f)
    scalars = [f(v) for v in (B, 0.0, 1e-12, 1e-8)]
    compiled = _compile(
        one_chip, _stream_chunk, ctl.sp, ctl.ladder, state, events, x, w,
        *scalars, fast=_fast_ok(ctl.sp), coarse=32, descent_iters=40,
        cap_iters=64, stol_rel=None, search_steps=4 * M)
    assert compiled.memory_analysis().temp_size_in_bytes > 0


def test_smartfill_batched_compiles(one_chip):
    from repro.core import log_speedup, sample_workloads, smartfill_batched

    wl = sample_workloads(0, K=256, M=32, B=B, m_range=(16, 32))
    with jax.enable_x64(False):
        sp = log_speedup(1.0, 1.0, B)

    def plan(X, W):
        out = smartfill_batched(sp, X, W, B=B)
        return out.theta, out.J, out.J_linear

    compiled = _compile(one_chip, plan, wl.X.astype(np.float32),
                        wl.W.astype(np.float32))
    assert compiled.memory_analysis().temp_size_in_bytes > 0


def test_fmath_lowers_in_a_kernel(one_chip):
    """Every fmath function lowers in Mosaic on vector operands (the
    kernels' power goes through it; Mosaic has no native expm1)."""
    from jax.experimental import pallas as pl

    from repro.core import fmath

    def kern(x_ref, o_ref):
        v = x_ref[...]
        o_ref[...] = (fmath.log(v) + fmath.exp(v) + fmath.log1p(v)
                      + fmath.expm1(v) + fmath.power(v, np.float32(-0.7)))

    def fn(x):
        return pl.pallas_call(kern, out_shape=jax.ShapeDtypeStruct(
            x.shape, jnp.float32))(x)

    compiled = _compile(one_chip, fn, np.ones((8, 128), np.float32))
    assert "tpu_custom_call" in compiled.as_text()
