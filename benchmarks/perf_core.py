"""Scheduler-core micro-benchmarks: µs/call for GWF and SmartFill.

These are the latencies a cluster controller pays per decision — the
numbers behind the "low complexity" claim of the paper's abstract.  The
headline comparison is single-instance µs/call (warm, jitted,
device-resident) versus batched planning throughput in instances/sec:
``smartfill_batched`` solves hundreds of padded (x, w, B) instances in
one vmap'd call, which is how a multi-tenant controller amortizes the
solver.

Run directly to write ``BENCH_core.json``:
    PYTHONPATH=src python -m benchmarks.perf_core [--quick] [--out PATH]
"""
from __future__ import annotations

import argparse
import json
import time

import jax

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core import (log_speedup, plan_classes, power,
                        sample_class_workloads, sample_workloads,
                        shifted_power, simulate_ensemble,
                        simulate_fluid_classes, simulate_policy_device,
                        smartfill, smartfill_batched, smartfill_hetero)
from repro.core.gwf import (solve_cap, solve_cap_regular_reference)
from repro.kernels.gwf_waterfill.ops import (generic_waterfill_op,
                                             gwf_waterfill_ref)
from repro.sched.policies import (ClassSmartFillPolicy, EquiPolicy,
                                  HeSRPTPolicy, HeteroSmartFillPolicy,
                                  SmartFillPolicy,
                                  WeightedMarginalRatePolicy)

B = 10.0


def _time(fn, *args, reps=100, warmup=3):
    """Best-of-reps warm latency in µs.

    The minimum is the standard robust statistic for micro-benchmarks:
    it estimates the cost of the work itself, while means absorb
    scheduler noise from shared runners — which is exactly what the
    >30% regression gate (benchmarks/check_regression.py) must not
    trip on.
    """
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best * 1e6  # µs


def bench_calibration():
    """Fixed-work machine-speed probe for the regression gate.

    A jitted dense matmul chain touches none of the scheduler code, so
    its time moves only with the runner's speed — the valid
    ``--calibrate`` row for ``check_regression.py`` (a row that shares
    the gated hot path would rescale a core regression into every other
    row and hide it).
    """
    x = jnp.ones((384, 384), jnp.float32)
    f = jax.jit(lambda x: (x @ x @ x).sum())
    return [{"name": "calibration_fixed_work", "us_per_call": _time(f, x)}]


def bench_gwf(quick: bool = False):
    """CAP/WFP solver latencies across job counts k.

    ``gwf_closed_form_k*``     — the O(k log k) prefix-sum closed form
                                 (the default ``solve_cap`` path);
    ``gwf_closed_form_ref_k*`` — the legacy O(k²) breakpoint search;
    ``gwf_waterfill_ref_k*``   — the (u, h0) WFP oracle;
    ``gwf_generic_waterfill_k*`` — the fused λ-bisection path behind
                                 ``impl="auto"`` (Pallas on TPU, jnp
                                 reference elsewhere).
    """
    rows = []
    sp = shifted_power(1.0, 4.0, 0.5, B)
    for k in (8, 64, 512, 4096):
        c = jnp.sort(jax.random.uniform(jax.random.PRNGKey(0), (k,),
                                        jnp.float32, 0.01, 1.0))[::-1]
        fn = jax.jit(lambda b, c: solve_cap(sp, b, c))
        us = _time(fn, 5.0, c)
        rows.append({"name": f"gwf_closed_form_k{k}", "us_per_call": us})
        if not (quick and k >= 4096):   # the O(k²) path is ~100× slower
            fn_ref = jax.jit(lambda b, c: solve_cap_regular_reference(sp, b, c))
            us_ref = _time(fn_ref, 5.0, c, reps=5 if k >= 4096 else 50)
            rows.append({"name": f"gwf_closed_form_ref_k{k}",
                         "us_per_call": us_ref})
        fn2 = jax.jit(lambda u, h0, b: gwf_waterfill_ref(u, h0, b))
        us2 = _time(fn2, sp.bottle_width(c).astype(jnp.float32),
                    sp.bottle_bottom(c).astype(jnp.float32), 5.0)
        rows.append({"name": f"gwf_waterfill_ref_k{k}", "us_per_call": us2})
        fn3 = jax.jit(lambda c, b: generic_waterfill_op(
            c, sp.A, sp.w, sp.gamma, b, sigma=sp.sigma))
        us3 = _time(fn3, c[None, :].astype(jnp.float32),
                    jnp.asarray([5.0], jnp.float32))
        rows.append({"name": f"gwf_generic_waterfill_k{k}",
                     "us_per_call": us3})
    return rows


_SPS = {
    "power": power(1.0, 0.5, B),         # closed-form μ* fast path
    "regular": shifted_power(1.0, 4.0, 0.5, B),
    "log": log_speedup(1.0, 1.0, B),
}


def bench_smartfill(ms=(10, 50, 100), reps=15):
    """Warm single-instance latency: one jitted device program per call."""
    rows = []
    for M in ms:
        x = np.arange(M, 0, -1.0)
        w = 1.0 / x
        for name, sp in _SPS.items():
            def run():
                # materializes J host-side, so the call blocks inherently
                return smartfill(sp, x, w, B=B, validate=False)
            out = run()                             # compile + warm
            rows.append({"name": f"smartfill_{name}_M{M}",
                         "family": name, "M": M,
                         "us_per_call": _time(run, reps=reps, warmup=1),
                         "J": out.J})
    return rows


def bench_smartfill_batched(n_instances=256, ms=(16, 32), reps=3):
    """Batched planning throughput: N padded instances per vmap'd call."""
    rows = []
    rng = np.random.default_rng(0)
    for M in ms:
        scale = rng.uniform(0.5, 2.0, (n_instances, 1))
        X = np.tile(np.arange(M, 0, -1.0), (n_instances, 1)) * scale
        W = 1.0 / X
        for name, sp in _SPS.items():
            def run():
                out = smartfill_batched(sp, X, W, B=B)
                jax.block_until_ready(out.J)
                return out
            dt = _time(run, reps=reps, warmup=1) / 1e6
            rows.append({
                "name": f"smartfill_batched_{name}_N{n_instances}_M{M}",
                "family": name, "M": M,
                "us_per_call": dt * 1e6,
                "instances_per_sec": n_instances / dt,
                "us_per_instance": dt / n_instances * 1e6,
            })
    return rows


def bench_simulator(K=256, M=16, reps=3):
    """Scenario-engine throughput: simulated events/sec, single vs ensemble.

    Single = one jitted ``lax.scan`` run of re-planning SmartFill on an
    M-job instance; ensemble = P policies × K random workloads in one
    compiled call (``simulate_ensemble``).  Events counted are executed
    (non-halt) engine events.
    """
    sp = power(1.0, 0.5, B)
    x = np.arange(M, 0, -1.0)
    w = 1.0 / x
    pol_sf = SmartFillPolicy(sp, B=B)

    def run_single():
        return simulate_policy_device(sp, x, w, pol_sf, B=B, trace=False)

    res = run_single()                              # compile + warm
    n_ev = res.n_events
    dt_single = _time(run_single, reps=reps, warmup=1) / 1e6
    rows = [{
        "name": f"sim_single_smartfill_M{M}",
        "us_per_call": dt_single * 1e6,
        "events_per_sec": n_ev / dt_single,
        "events": n_ev,
    }]

    wl = sample_workloads(0, K=K, M=M, B=B, m_range=(max(2, M // 2), M))
    policies = (pol_sf, HeSRPTPolicy(0.5, B), EquiPolicy(B))

    def run_ensemble():
        out = simulate_ensemble(sp, policies, wl.X, wl.W, B=B)
        jax.block_until_ready(out.J)
        return out

    out = run_ensemble()                            # compile + warm
    total_events = int(np.asarray(out.n_events).sum())
    dt_ens = _time(run_ensemble, reps=reps, warmup=1) / 1e6
    rows.append({
        "name": f"sim_ensemble_P{len(policies)}_K{K}_M{M}",
        "us_per_call": dt_ens * 1e6,
        "events_per_sec": total_events / dt_ens,
        "events": total_events,
        "instances_per_sec": len(policies) * K / dt_ens,
    })
    return rows


HETERO_FAMILIES = ("power", "shifted", "log", "neg_power", "saturating")


def bench_hetero(quick: bool = False, reps: int = 15):
    """Heterogeneous (§7) planning + ensemble rows.

    ``hetero_plan_M{32,256}``      — warm single-instance latency of the
        per-job SmartFill solve (fixed heuristic order, mixed families
        incl. the σ=−1 saturating row; every CAP probe is the per-job
        λ-bisection, so these rows gate the §7 hot path the shared
        closed form cannot cover);
    ``hetero_sim_ensemble_*``      — the scenario engine driving the
        pinned-order hetero SmartFill (one-shot plan cached at
        construction, executed by active-count lookup — the §7
        time-consistent policy) and the retired weighted-marginal-rate
        baseline (re-solved every event through the sorted-bracket CAP)
        over a per-job mixed-family ensemble, in simulated events/sec.
        Plan construction is one batched solve outside the timed region.
    """
    rows = []
    for M in (32, 256):
        wl = sample_workloads(7, K=1, M=M, B=B, family=HETERO_FAMILIES,
                              per_job=True)
        sp1 = jax.tree_util.tree_map(lambda l: jnp.asarray(l)[0], wl.sp)
        x, w = wl.X[0], wl.W[0]

        def run():
            return smartfill_hetero(sp1, x, w, B=B, exchange_passes=0)
        out = run()                                 # compile + warm
        # full reps even at M=256: the sorted-bracket rebuild brought it
        # from seconds/call to sub-second, so best-of-15 is affordable
        # and needed (host timer noise here is ±10-20% of the row)
        r = reps
        rows.append({"name": f"hetero_plan_M{M}", "M": M,
                     "us_per_call": _time(run, reps=r, warmup=1),
                     "J": out.J})

    K, M = (32, 12) if quick else (64, 16)
    wl = sample_workloads(8, K=K, M=M, B=B, family=HETERO_FAMILIES,
                          per_job=True, m_range=(max(2, M // 2), M))
    policies = (HeteroSmartFillPolicy.pinned(wl.sp, wl.X, wl.W, B=B,
                                             cache_plan=True),
                WeightedMarginalRatePolicy(wl.sp, B=B))

    def run_ens():
        out = simulate_ensemble(wl.sp, policies, wl.X, wl.W, B=B)
        jax.block_until_ready(out.J)
        return out

    out = run_ens()                                 # compile + warm
    events = int(np.asarray(out.n_events).sum())
    dt = _time(run_ens, reps=3, warmup=1) / 1e6
    rows.append({
        "name": f"hetero_sim_ensemble_P{len(policies)}_K{K}_M{M}",
        "us_per_call": dt * 1e6,
        "events_per_sec": events / dt,
        "events": events,
        "instances_per_sec": len(policies) * K / dt,
    })
    return rows


def bench_classes(quick: bool = False):
    """Class-aggregated (many-jobs limit) planning + fluid engine rows.

    ``class_plan_M1e6_C64`` — one full ``plan_classes`` call on 64
        classes of 15625 jobs each (M = 10⁶): host prep + aggregation
        transform + the §7 solve on 64 aggregate rows + exchange
        passes.  This is the ROADMAP "millions of users" headline —
        per-job planning at this M is off the chart (the per-job bench
        ceiling is M = 256), aggregation makes it a ~64-row solve.
    ``class_fluid_ensemble_*`` — the fluid class engine executing the
        cached one-shot plan over K mixed-family instances, in
        events/sec (each event completes at least one class).
    """
    C = 64
    per = 1_000_000 // C                    # 15625 jobs/class → M = 10⁶
    wb = sample_class_workloads(11, K=1, C=C, count_range=(per, per))
    st = wb.state(0)

    def run_plan():
        return plan_classes(st)

    out = run_plan()                        # compile + warm
    rows = [{
        "name": f"class_plan_M1e6_C{C}", "C": C, "jobs": int(out.counts.sum()),
        "us_per_call": _time(run_plan, reps=3 if quick else 5, warmup=1),
        "J": out.J,
    }]

    K, Cf = (8, 12) if quick else (32, 16)
    wb = sample_class_workloads(12, K=K, C=Cf)
    states = [wb.state(k) for k in range(K)]
    pols = [ClassSmartFillPolicy.from_classes(s, cache_plan=True)
            for s in states]                # plan construction not timed

    def run_fluid():
        total = 0
        for s, p in zip(states, pols):
            total += simulate_fluid_classes(s, p, trace=False).n_events
        return total

    events = run_fluid()                    # compile + warm
    dt = _time(run_fluid, reps=3, warmup=1) / 1e6
    rows.append({
        "name": f"class_fluid_ensemble_K{K}_C{Cf}",
        "us_per_call": dt * 1e6,
        "events_per_sec": events / dt,
        "events": events,
        "instances_per_sec": K / dt,
    })
    return rows


def bench_robust(quick: bool = False):
    """Robustness-layer rows: what the control plane pays to be safe.

    ``robust_sf_ensemble_*``     — plain SmartFill ensemble (the
        baseline the certificate overhead is measured against);
    ``robust_cert_ensemble_*``   — the same ensemble behind the full
        ``DegradingPolicy`` ladder (per-event certificates on every
        rung + the GWF-static and EQUI fallbacks evaluated eagerly) —
        the "certificates are nearly free next to the per-event DP"
        claim, quoted as ``robust_certificate_overhead_x``;
    ``robust_faulted_ensemble_*`` — the fault-aware engine under a
        seeded chaos ensemble (budget preemptions + failures +
        stragglers, one trace per workload), in events/sec;
    ``robust_degraded_ensemble_*`` — a sabotaged primary forcing every
        event onto the GWF-static rung; its J against the healthy
        re-planning run is ``robust_degraded_vs_replan_J_gap_pct`` —
        the scheduling cost of running degraded instead of re-solving.
    """
    from repro.core.workloads import sample_fault_traces
    from repro.robust import DegradingPolicy, SaboteurPolicy
    from repro.sched.policies import GWFStaticPolicy

    K, M = (32, 12) if quick else (64, 16)
    sp = _SPS["regular"]
    wl = sample_workloads(21, K=K, M=M, B=B, m_range=(max(2, M // 2), M))
    rows = []

    def ens(policies, faults=None):
        def run():
            out = simulate_ensemble(sp, policies, wl.X, wl.W, B=B,
                                    faults=faults)
            jax.block_until_ready(out.J)
            return out

        out = run()                             # compile + warm
        dt = _time(run, reps=3, warmup=1) / 1e6
        events = int(np.asarray(out.n_events).sum())
        return out, dt, events

    plain = (SmartFillPolicy(sp, B=B),)
    out_p, dt_p, ev_p = ens(plain)
    rows.append({"name": f"robust_sf_ensemble_K{K}_M{M}",
                 "us_per_call": dt_p * 1e6, "events_per_sec": ev_p / dt_p,
                 "events": ev_p, "instances_per_sec": K / dt_p})

    certified = (DegradingPolicy.ladder(sp, B=B),)
    out_c, dt_c, ev_c = ens(certified)
    rows.append({"name": f"robust_cert_ensemble_K{K}_M{M}",
                 "us_per_call": dt_c * 1e6, "events_per_sec": ev_c / dt_c,
                 "events": ev_c, "instances_per_sec": K / dt_c})

    traces = sample_fault_traces(22, K, M, B=B, horizon=6.0,
                                 preempt_rate=0.5, fail_rate=0.3,
                                 straggle_rate=0.3)
    out_f, dt_f, ev_f = ens(plain, faults=traces)
    rows.append({"name": f"robust_faulted_ensemble_K{K}_M{M}",
                 "us_per_call": dt_f * 1e6, "events_per_sec": ev_f / dt_f,
                 "events": ev_f, "instances_per_sec": K / dt_f})

    degraded = (DegradingPolicy(rungs=(
        SaboteurPolicy(SmartFillPolicy(sp, B=B), mode="nan"),
        GWFStaticPolicy(sp, B=B),
        EquiPolicy(B))),)
    out_d, dt_d, ev_d = ens(degraded)
    J_p = np.asarray(out_p.J)[0]
    J_d = np.asarray(out_d.J)[0]
    ok = np.isfinite(J_p) & np.isfinite(J_d) & (J_p > 0)
    gap_pct = float(np.median((J_d[ok] - J_p[ok]) / J_p[ok]) * 100.0)
    rows.append({"name": f"robust_degraded_ensemble_K{K}_M{M}",
                 "us_per_call": dt_d * 1e6, "events_per_sec": ev_d / dt_d,
                 "events": ev_d, "instances_per_sec": K / dt_d,
                 "J_gap_pct": gap_pct})
    return rows


FLEET_DEVICE_COUNTS = (1, 2, 4, 8)


def bench_fleet_at(devices: int, base_n: int, quick: bool) -> list:
    """Sharded planning/simulation over the first ``devices`` devices.

    Weak scaling: the per-device load is fixed at ``base_n`` instances,
    so N = base_n · D and ideal instances/sec grows linearly with D.
    """
    from repro.distributed import (fleet_mesh, plan_sharded,
                                   simulate_ensemble_sharded)

    mesh = fleet_mesh(devices)
    N, M = base_n * devices, 16
    sp = _SPS["regular"]
    wl = sample_workloads(0, K=N, M=M, B=B, m_range=(max(2, M // 2), M))

    def run_plan():
        out = plan_sharded(sp, wl.X, wl.W, B=B, mesh=mesh)
        jax.block_until_ready(out.J)
        return out

    run_plan()                                   # compile + warm
    dt = _time(run_plan, reps=3, warmup=1) / 1e6
    rows = [{
        "name": f"fleet_plan_weak_D{devices}",
        "devices": devices, "instances": N,
        "us_per_call": dt * 1e6,
        "instances_per_sec": N / dt,
        "us_per_instance": dt / N * 1e6,
    }]
    if not quick:
        policies = (HeSRPTPolicy(0.5, B), EquiPolicy(B))

        def run_sim():
            out = simulate_ensemble_sharded(sp, policies, wl.X, wl.W, B=B,
                                            mesh=mesh)
            jax.block_until_ready(out.J)
            return out

        out = run_sim()
        events = int(np.asarray(out.n_events).sum())
        dt = _time(run_sim, reps=3, warmup=1) / 1e6
        rows.append({
            "name": f"fleet_sim_weak_D{devices}",
            "devices": devices, "instances": len(policies) * N,
            "us_per_call": dt * 1e6,
            "instances_per_sec": len(policies) * N / dt,
            "events_per_sec": events / dt,
        })
    return rows


def bench_fleet(quick: bool = False):
    """Weak-scaling rows at 1/2/4/8 devices, in this process.

    Each count D runs over the first D devices this process sees.  On
    the CPU set ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
    before starting; a count above what the process sees raises, so a
    regression gate never loses a row without a word.
    """
    require_devices(max(FLEET_DEVICE_COUNTS), "the fleet sweep")
    base_n = 32 if quick else 64
    rows = []
    for D in FLEET_DEVICE_COUNTS:
        rows.extend(bench_fleet_at(D, base_n, quick))
    return rows


def require_devices(n: int, what: str) -> None:
    """Raise unless this process sees at least ``n`` devices."""
    have = len(jax.devices())
    if have < n:
        raise RuntimeError(
            f"{what} needs {n} devices and this process sees {have}; on "
            f"the CPU start it with XLA_FLAGS="
            f"--xla_force_host_platform_device_count={n}")


def collect(quick: bool = False):
    """All rows + the single-vs-batched amortization summary.

    The amortization factor compares a batched call's per-instance cost
    against a warm single-instance call of the *same* family and M.
    """
    n = 64 if quick else 256
    batched_ms = (16,) if quick else (16, 32)
    # hetero's single-instance latency rows run FIRST: every other
    # section leaves allocator/compile-cache pressure behind that
    # inflates a warm ~200 ms row by 10-15% (measured: 186 ms in a
    # clean process vs 215+ ms after the gwf/batched sections)
    hetero = bench_hetero(quick=quick)
    gwf = bench_gwf(quick=quick)
    single = bench_smartfill(ms=(10, 50) if quick else (10, 50, 100))
    single += bench_smartfill(ms=batched_ms)        # same-M baselines
    batched = bench_smartfill_batched(n_instances=n, ms=batched_ms)
    simulator = bench_simulator(K=64 if quick else 256, M=16)
    classes = bench_classes(quick=quick)
    robust = bench_robust(quick=quick)
    fleet = bench_fleet(quick=quick)
    summary = {}
    for r in batched:
        base = next((s for s in single
                     if s["family"] == r["family"] and s["M"] == r["M"]),
                    None)
        if base is not None:
            summary[r["name"] + "_amortization_x"] = (
                base["us_per_call"] / r["us_per_instance"])
    gwf_by_name = {r["name"]: r["us_per_call"] for r in gwf}
    for k in (8, 64, 512, 4096):
        ref = gwf_by_name.get(f"gwf_closed_form_ref_k{k}")
        new = gwf_by_name.get(f"gwf_closed_form_k{k}")
        if ref and new:
            summary[f"gwf_closed_form_k{k}_speedup_x"] = ref / new
    sim_single = simulator[0]
    sim_ens = simulator[1]
    summary["sim_ensemble_events_per_sec"] = sim_ens["events_per_sec"]
    summary["sim_ensemble_amortization_x"] = (
        sim_ens["events_per_sec"] / sim_single["events_per_sec"])
    het_by_name = {r["name"]: r for r in hetero}
    # §7 overhead: per-job λ-bisection CAP vs the shared closed form at
    # the same M (hetero pays bisection per probe; this ratio is the
    # price of heterogeneity the README quotes)
    base50 = next((r for r in single
                   if r["family"] == "regular" and r["M"] == 50), None)
    h32 = het_by_name.get("hetero_plan_M32")
    if base50 and h32:
        summary["hetero_plan_M32_vs_regular_M50_x"] = (
            h32["us_per_call"] / base50["us_per_call"])
    for r in hetero:
        if "events_per_sec" in r:
            summary["hetero_ensemble_events_per_sec"] = r["events_per_sec"]
    cls_by_name = {r["name"]: r for r in classes}
    plan_1e6 = cls_by_name.get("class_plan_M1e6_C64")
    if plan_1e6:
        summary["class_plan_M1e6_ms"] = plan_1e6["us_per_call"] / 1e3
        # per-job jobs/sec through the aggregate planner — the headline
        # the ROADMAP item asks for
        summary["class_plan_M1e6_jobs_per_sec"] = (
            plan_1e6["jobs"] / (plan_1e6["us_per_call"] / 1e6))
    for r in classes:
        if "events_per_sec" in r:
            summary["class_fluid_events_per_sec"] = r["events_per_sec"]
    rob_plain = next((r for r in robust
                      if r["name"].startswith("robust_sf_ensemble")), None)
    rob_cert = next((r for r in robust
                     if r["name"].startswith("robust_cert_ensemble")), None)
    if rob_plain and rob_cert:
        # the certificate-overhead headline: wrapped / unwrapped wall time
        summary["robust_certificate_overhead_x"] = (
            rob_cert["us_per_call"] / rob_plain["us_per_call"])
    rob_faulted = next((r for r in robust
                        if r["name"].startswith("robust_faulted")), None)
    if rob_faulted:
        summary["robust_faulted_events_per_sec"] = (
            rob_faulted["events_per_sec"])
    rob_deg = next((r for r in robust
                    if r["name"].startswith("robust_degraded")), None)
    if rob_deg:
        summary["robust_degraded_vs_replan_J_gap_pct"] = rob_deg["J_gap_pct"]
    # weak-scaling efficiency: throughput relative to D=1 (1.0 = ideal;
    # on an oversubscribed CPU host the curve flattens at the physical
    # core count — the rows pin the mechanism, not the silicon)
    fleet_by_d = {r["devices"]: r for r in fleet
                  if r["name"].startswith("fleet_plan_")}
    base = fleet_by_d.get(1)
    if base:
        for d, r in sorted(fleet_by_d.items()):
            summary[f"fleet_plan_weak_scaling_D{d}_x"] = (
                r["instances_per_sec"] / base["instances_per_sec"])
    return {
        "calibration": bench_calibration(),
        "gwf": gwf,
        "smartfill_single": single,
        "smartfill_batched": batched,
        "simulator": simulator,
        "hetero": hetero,
        "classes": classes,
        "robust": robust,
        "fleet": fleet,
        "summary": summary,
        "config": {"B": B, "n_instances": n, "x64": jax.config.jax_enable_x64,
                   "fleet_devices": list(FLEET_DEVICE_COUNTS)},
    }


def bench_rows(quick: bool = False):
    """Flat row list for CSV harnesses — same sweep as ``collect()``.

    ``benchmarks/run.py`` prints these so the CSV and BENCH_core.json
    always come from one sweep definition.
    """
    report = collect(quick=quick)
    return (report["gwf"] + report["smartfill_single"]
            + report["smartfill_batched"] + report["simulator"]
            + report["hetero"] + report["classes"] + report["robust"]
            + report["fleet"])


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--out", default="BENCH_core.json")
    args = ap.parse_args()
    enable_compile_cache()
    report = collect(quick=args.quick)
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
    for sec in ("smartfill_single", "smartfill_batched", "simulator",
                "hetero", "classes", "robust", "fleet"):
        for r in report[sec]:
            extra = (f"  {r['instances_per_sec']:.0f} inst/s"
                     if "instances_per_sec" in r else "")
            if "events_per_sec" in r:
                extra += f"  {r['events_per_sec']:.0f} events/s"
            print(f"{r['name']:48s} {r['us_per_call']:12.1f} µs/call{extra}")
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
