"""Multi-device differential tests: sharded == single-device.

The fleet layer's contract is *parity*: ``plan_sharded`` must reproduce
``smartfill_batched`` and ``simulate_ensemble_sharded`` must reproduce
``simulate_ensemble`` instance by instance — sharding is a layout
decision, never a numerical one.  CI's devices=8 job runs this module
under ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` so the
mesh is a real 8-way partition; on a plain single-device run the same
assertions hold over a 1-device mesh (the shard_map/scan machinery is
exercised either way).

Tolerances: the objective J must match to ≤1e-6 (relative) in both
float64 and float32.  θ entries match to 1e-6 in float64; in float32
the bracketed-descent μ* minimizer amplifies one-ulp differences
between the differently-fused sharded/unsharded programs up to solver
tolerance, so θ is compared at a √eps-scaled bound instead (the
objective is flat at the optimum — θ wobble at that scale is exactly
what J ≤ 1e-6 permits).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from repro.core import (log_speedup, sample_workloads, shifted_power,
                        simulate_ensemble, smartfill_batched)
from repro.distributed import (active_fleet_mesh, fleet_mesh, plan_sharded,
                               simulate_ensemble_sharded)
from repro.sched.policies import EquiPolicy, HeSRPTPolicy, SmartFillPolicy

B = 10.0
K = 19          # deliberately not a multiple of any host device count
M = 6

_SPS = {
    "regular": lambda: shifted_power(1.0, 4.0, 0.5, B),
    "log": lambda: log_speedup(1.0, 1.0, B),
}


def _workloads(seed=0, k=K, m=M, **kw):
    wl = sample_workloads(seed, K=k, M=m, B=B, m_range=(1, m), **kw)
    X, W = wl.X.copy(), wl.W.copy()
    X[-1] = 0.0          # one all-padding instance (m = 0) in every batch
    W[-1] = 0.0
    return X, W, wl


def _theta_tol(dtype):
    eps = jnp.finfo(dtype).eps
    return 1e-6 if eps < 1e-10 else 64.0 * float(np.sqrt(eps))


def _assert_plan_parity(ref, sh, dtype):
    assert ref.theta.dtype == sh.theta.dtype == dtype
    J_ref, J_sh = np.asarray(ref.J), np.asarray(sh.J)
    np.testing.assert_allclose(J_sh, J_ref, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(sh.theta), np.asarray(ref.theta),
                               atol=_theta_tol(dtype))
    np.testing.assert_allclose(np.asarray(sh.T), np.asarray(ref.T),
                               rtol=1e-6, atol=_theta_tol(dtype))
    np.testing.assert_array_equal(np.asarray(sh.m), np.asarray(ref.m))


def _run_plan_parity(sp, X, W, dtype, **kw):
    ref = smartfill_batched(sp, X, W, B=B)
    sh = plan_sharded(sp, X, W, B=B, mesh=fleet_mesh(), **kw)
    _assert_plan_parity(ref, sh, dtype)


@pytest.mark.parametrize("family", sorted(_SPS))
def test_plan_parity_f64(family):
    X, W, _ = _workloads(0)
    _run_plan_parity(_SPS[family](), X, W, jnp.float64)


@pytest.mark.parametrize("family", sorted(_SPS))
def test_plan_parity_f32(family):
    X, W, _ = _workloads(1)
    with jax.enable_x64(False):
        _run_plan_parity(_SPS[family](), X, W, jnp.float32)


def test_plan_parity_chunked():
    """K≫memory driver: scanning bounded chunks changes nothing."""
    X, W, _ = _workloads(2)
    sp = _SPS["log"]()
    ref = smartfill_batched(sp, X, W, B=B)
    for chunk in (1, 4, 7, K):     # incl. chunk < devices and non-divisors
        sh = plan_sharded(sp, X, W, B=B, mesh=fleet_mesh(), chunk_size=chunk)
        _assert_plan_parity(ref, sh, jnp.float64)


def test_plan_parity_batched_speedups():
    """Per-instance RegularSpeedup leaves shard alongside their instance."""
    X, W, wl = _workloads(3, family=("power", "shifted", "log", "neg_power"))
    ref = smartfill_batched(wl.sp, X, W, B=B)
    sh = plan_sharded(wl.sp, X, W, B=B, mesh=fleet_mesh(), chunk_size=8)
    _assert_plan_parity(ref, sh, jnp.float64)


def test_plan_parity_per_instance_budgets():
    X, W, _ = _workloads(4)
    Bv = np.linspace(6.0, 14.0, K)
    sp = _SPS["regular"]()
    ref = smartfill_batched(sp, X, W, B=Bv)
    sh = plan_sharded(sp, X, W, B=Bv, mesh=fleet_mesh())
    _assert_plan_parity(ref, sh, jnp.float64)


def test_plan_padded_outputs_inert():
    """Mesh-padding instances must never leak: padded-out rows of the
    *returned* arrays are exactly the single-device zeros."""
    X, W, _ = _workloads(5)
    sp = _SPS["log"]()
    sh = plan_sharded(sp, X, W, B=B, mesh=fleet_mesh())
    assert sh.theta.shape[0] == K            # trimmed back to N
    assert float(jnp.abs(sh.theta[-1]).max()) == 0.0   # m = 0 instance
    assert float(sh.J[-1]) == 0.0


def _ensemble_policies(sp):
    return (SmartFillPolicy(sp, B=B), HeSRPTPolicy(0.5, B), EquiPolicy(B))


def _assert_ensemble_parity(ref, sh):
    np.testing.assert_array_equal(np.asarray(sh.finished),
                                  np.asarray(ref.finished))
    fin = np.asarray(ref.finished)
    J_ref, J_sh = np.asarray(ref.J), np.asarray(sh.J)
    np.testing.assert_allclose(np.where(fin, J_sh, 0.0),
                               np.where(fin, J_ref, 0.0),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(sh.T), np.asarray(ref.T),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(sh.n_events),
                                  np.asarray(ref.n_events))
    assert sh.policy_names == ref.policy_names


@pytest.mark.parametrize("dtype", ["f64", "f32"])
def test_ensemble_parity(dtype):
    X, W, wl = _workloads(6, arrival_rate=0.5)
    sp = _SPS["regular"]()

    def run():
        ref = simulate_ensemble(sp, _ensemble_policies(sp), X, W,
                                arrival=wl.arrival, B=B)
        sh = simulate_ensemble_sharded(sp, _ensemble_policies(sp), X, W,
                                       arrival=wl.arrival, B=B,
                                       mesh=fleet_mesh(), chunk_size=8)
        _assert_ensemble_parity(ref, sh)

    if dtype == "f32":
        with jax.enable_x64(False):
            run()
    else:
        run()


def test_ensemble_parity_batched_speedups():
    """Per-workload speedup params + per-workload policy budgets shard."""
    X, W, wl = _workloads(7, family=("power", "log"))
    Bv = np.linspace(8.0, 12.0, K)
    policies = (EquiPolicy(B=Bv), HeSRPTPolicy(0.5, B=Bv))
    ref = simulate_ensemble(wl.sp, policies, X, W)
    sh = simulate_ensemble_sharded(wl.sp, policies, X, W,
                                   mesh=fleet_mesh())
    _assert_ensemble_parity(ref, sh)


def test_small_K_pads_up_to_device_count():
    """K < device count: everything pads, results still exact."""
    X, W, _ = _workloads(8, k=3)
    sp = _SPS["log"]()
    ref = smartfill_batched(sp, X, W, B=B)
    sh = plan_sharded(sp, X, W, B=B, mesh=fleet_mesh())
    _assert_plan_parity(ref, sh, jnp.float64)


def test_mesh_context_dispatch():
    """active_fleet_mesh: 1-D contexts are ours, multi-axis are not."""
    assert active_fleet_mesh() is None
    devs = np.asarray(jax.devices())
    with Mesh(devs, ("fleet",)) as mesh:
        got = active_fleet_mesh()
        assert got is not None and tuple(got.axis_names) == ("fleet",)
        assert got.devices.size == mesh.devices.size
    with Mesh(devs.reshape(-1, 1), ("data", "model")):
        assert active_fleet_mesh() is None
    assert active_fleet_mesh() is None


def test_cluster_plan_fleets_dispatches_to_mesh():
    from repro.sched.cluster import ClusterScheduler, Job

    sp = _SPS["log"]()
    cs = ClusterScheduler(sp, B=B)
    fleets = [[Job("a", 5.0, 0.2), Job("b", 3.0, 1 / 3.0)],
              [Job("c", 7.0, 1 / 7.0), Job("d", 2.0, 0.5),
               Job("e", 1.0, 1.0)]]
    _, ref = cs.plan_fleets(fleets)
    alloc_ref = cs.current_allocations_fleets(fleets)
    with fleet_mesh():
        _, sh = cs.plan_fleets(fleets)
        alloc_sh = cs.current_allocations_fleets(fleets)
    np.testing.assert_allclose(np.asarray(sh.J), np.asarray(ref.J),
                               rtol=1e-6, atol=1e-6)
    for a, b in zip(alloc_sh, alloc_ref):
        np.testing.assert_allclose(a, b, atol=1e-6)


def test_admission_simulate_estimator_sharded():
    from repro.serve.admission import AdmissionController

    sp = _SPS["log"]()
    rs = np.array([8.0, 4.0])
    cs_ = np.array([6.0, 2.0, 1.0])
    ac = AdmissionController(sp, estimator="simulate")
    ref = ac.evaluate(rs, 1.0 / rs, cs_, 1.0 / cs_)
    with fleet_mesh():
        sh = ac.evaluate(rs, 1.0 / rs, cs_, 1.0 / cs_)
    np.testing.assert_allclose(sh.marginal_cost, ref.marginal_cost,
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(sh.admit, ref.admit)


def test_plan_parity_hetero_per_job_speedups():
    """§7 fleets shard: per-job (N, M) speedup leaves split along the
    instance axis, padded rows edge-replicate valid family params, and
    the sharded result equals the single-device heterogeneous solve."""
    X, W, wl = _workloads(
        11, family=("power", "shifted", "log", "neg_power", "saturating"),
        per_job=True)
    ref = smartfill_batched(wl.sp, X, W, B=B)
    sh = plan_sharded(wl.sp, X, W, B=B, mesh=fleet_mesh(), chunk_size=8)
    _assert_plan_parity(ref, sh, jnp.float64)


def test_plan_parity_class_aggregates():
    """Class-aggregated fleets shard: ``plan_classes_sharded`` must
    reproduce ``plan_classes_batched`` bit-for-bit — identical orders
    (the host-side compaction + normalized-size ordering is shared
    code) and identical J/θ/T (the solve is ``plan_sharded``'s, which
    is instance-by-instance the single-device program).  Zero-count
    classes ride along as inert padding."""
    from repro.core import plan_classes_batched, sample_class_workloads
    from repro.distributed import plan_classes_sharded

    wl = sample_class_workloads(31, K=K, C=5, B=B)
    counts = wl.counts.copy()
    counts[2] = 0.0
    counts[2, 3] = 4.0           # one nearly-empty instance in the batch
    ref_orders, ref = plan_classes_batched(counts, wl.sizes, wl.weights,
                                           wl.sp, B=B)
    sh_orders, sh = plan_classes_sharded(counts, wl.sizes, wl.weights,
                                         wl.sp, B=B, mesh=fleet_mesh(),
                                         chunk_size=8)
    np.testing.assert_array_equal(sh_orders, ref_orders)
    _assert_plan_parity(ref, sh, jnp.float64)


def test_ensemble_parity_hetero_policies():
    """HeteroSmartFillPolicy + the retired WMR baseline shard with their
    (K, M) per-job leaves through the ensemble runner."""
    from repro.sched.policies import (HeteroSmartFillPolicy,
                                      WeightedMarginalRatePolicy)

    X, W, wl = _workloads(12, k=9, m=4,
                          family=("power", "log", "saturating"),
                          per_job=True)
    pols = (HeteroSmartFillPolicy(wl.sp, B=B),
            WeightedMarginalRatePolicy(wl.sp, B=B))
    ref = simulate_ensemble(wl.sp, pols, X, W, B=B)
    sh = simulate_ensemble_sharded(wl.sp, pols, X, W, B=B,
                                   mesh=fleet_mesh())
    np.testing.assert_allclose(np.asarray(sh.J), np.asarray(ref.J),
                               rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(sh.finished),
                                  np.asarray(ref.finished))


def test_ensemble_parity_faulted():
    """Fault ensembles shard like workloads: per-instance chaos traces
    ride the mesh and the sharded faulted run equals the single-device
    faulted run exactly (including the all-padding instance, which must
    halt before consuming any fault)."""
    from repro.core.simulator import budget_trace
    from repro.core.workloads import sample_fault_traces

    X, W, wl = _workloads(21, k=9, m=4)
    sp = _SPS["regular"]()
    traces = sample_fault_traces(22, 9, 4, B=B, horizon=4.0,
                                 preempt_rate=0.8, fail_rate=0.5,
                                 straggle_rate=0.5)
    pols = (SmartFillPolicy(sp, B=B), EquiPolicy(B))
    ref = simulate_ensemble(sp, pols, X, W, faults=traces)
    sh = simulate_ensemble_sharded(sp, pols, X, W, faults=traces,
                                   mesh=fleet_mesh(), chunk_size=4)
    np.testing.assert_array_equal(np.asarray(sh.J), np.asarray(ref.J))
    np.testing.assert_array_equal(np.asarray(sh.T), np.asarray(ref.T))
    np.testing.assert_array_equal(np.asarray(sh.finished),
                                  np.asarray(ref.finished))

    # a shared 1-D trace broadcasts to every lane identically too
    bt = budget_trace([0.5, 1.5], [3.0, B])
    ref1 = simulate_ensemble(sp, pols, X, W, faults=bt)
    sh1 = simulate_ensemble_sharded(sp, pols, X, W, faults=bt,
                                    mesh=fleet_mesh())
    np.testing.assert_array_equal(np.asarray(sh1.J), np.asarray(ref1.J))


# ---------------------------------------------------------------------------
# Multi-tenant streaming service
# ---------------------------------------------------------------------------

def _tenant_streams(seeds, horizon=900.0, rate=0.2, **kw):
    from repro.core import sample_arrival_stream

    return [sample_arrival_stream(s, horizon=horizon, rate=rate,
                                  diurnal=0.75, period=horizon, B=B,
                                  n_budget_events=2,
                                  budget_frac=(0.3, 0.8), **kw)
            for s in seeds]


def test_serve_streams_sharded_matches_solo_run_device():
    """Tenant i through the sharded fleet == tenant i solo through
    ``run_device`` — bitwise, including replan counters, under
    per-tenant budgets and a nonzero plan latency.  T=3 deliberately
    does not divide the 8-way CI mesh, so padded inert tenants ride
    along (the kind-0 event encoding makes an all-zero row a no-op)."""
    from repro.core import power
    from repro.distributed import serve_streams_sharded
    from repro.serve import StreamCascadePolicy, StreamController

    sp = power(1.0, 0.5, B)
    streams = _tenant_streams((3, 7, 11), weights="random")
    budgets = [10.0, 8.0, 12.0]
    fleet = serve_streams_sharded(sp, streams, budgets=budgets,
                                  max_live=5, plan_latency=1.0,
                                  mesh=fleet_mesh())
    assert len(fleet) == 3
    for i, strm in enumerate(streams):
        ctl = StreamController(sp, budgets[i], max_live=5,
                               policy=StreamCascadePolicy(sp, budgets[i]),
                               plan_latency=1.0)
        solo = ctl.run_device(strm)
        got = fleet.results[i]
        np.testing.assert_array_equal(got.completion, solo.completion)
        assert got.replans == solo.replans
        assert got.warm_replans == solo.warm_replans
        assert got.cold_replans == solo.cold_replans
        assert got.degraded_windows == solo.degraded_windows
        assert got.metrics == solo.metrics


def test_serve_streams_sharded_admission_view():
    """The cross-tenant view: an overloaded starved tenant carries the
    backlog and is advised the larger share of the next budget round."""
    from repro.core import power
    from repro.distributed import serve_streams_sharded

    sp = power(1.0, 0.5, B)
    light, heavy = _tenant_streams((5, 6), horizon=600.0, rate=0.05), \
        _tenant_streams((8,), horizon=600.0, rate=1.5)
    fleet = serve_streams_sharded(sp, light + heavy,
                                  budgets=[B, B, 0.5], max_live=4,
                                  mesh=fleet_mesh())
    share = fleet.suggested_budget_share
    np.testing.assert_allclose(share.sum(), 1.0)
    assert fleet.backlog[2] > 0                 # starved tenant backed up
    assert share[2] == share.max()
    assert fleet.unfinished_work[2] > fleet.unfinished_work[:2].max()
    assert fleet.mean_slowdown.shape == (3,)
    assert fleet.deadline_misses.shape == (3,)


def test_serve_streams_sharded_validates():
    from repro.core import power, sample_workloads
    from repro.distributed import serve_streams_sharded

    sp = power(1.0, 0.5, B)
    streams = _tenant_streams((1,))
    with pytest.raises(ValueError, match="tenant"):
        serve_streams_sharded(sp, [], mesh=fleet_mesh())
    with pytest.raises(ValueError, match="budget"):
        serve_streams_sharded(sp, streams, budgets=[B, B],
                              mesh=fleet_mesh())
    wl = sample_workloads(0, K=2, M=4, B=B, per_job=True,
                          family=("power", "log"))
    sp_pj = jax.tree_util.tree_map(lambda l: jnp.asarray(l)[0], wl.sp)
    with pytest.raises(ValueError, match="shared scalar-leaf"):
        serve_streams_sharded(sp_pj, streams, mesh=fleet_mesh())
