"""Streaming control-plane benchmarks: the online service loop.

Five questions, one JSON:

  * **Sustained service throughput** — ``serve_stream_day`` runs the
    device-resident event scan (``StreamController.run_device``: the
    whole day is one compiled ``lax.scan`` over control-plane events)
    over a day-long diurnal arrival trace (arrivals + budget dips +
    recoveries) and reports control-plane events/sec sustained end to
    end, plus the SLO tail and its bit-parity against the host oracle
    (``run`` with the same ``StreamCascadePolicy``), which is timed as
    ``serve_stream_day_host``.  This is the number a capacity planner
    quotes: how much open-arrival load one controller absorbs.

  * **Multi-tenant sharding** — ``serve_multitenant_*_T{1,2,4,8}``
    runs T independent tenant streams through
    ``serve_streams_sharded`` over the first T devices of this
    process with fixed per-tenant load — ideal weak scaling is T× the
    T=1 events/sec.

  * **Trace replay** — ``serve_trace_replay`` replays the recorded
    arrival log under ``benchmarks/traces/`` through the controller
    via ``load_arrival_log`` (the production-trace path, vs the
    synthetic sampler every other row uses).

  * **Warm vs cold replanning** — ``serve_warm_replan_M*`` times one
    incremental replan (carried completion order + λ-bracket hints)
    against ``serve_cold_replan_M*``, the from-scratch solve on the
    same live state (fresh ranking plus, for per-job speedups, the full
    §7 exchange-order search a cold planner cannot skip).  The ratio is
    the ``serve_warm_vs_cold_replan_x`` headline — the reason the
    streaming controller replans every event without falling behind.

  * **Admission scoring** — ``serve_admission_score`` times one
    watchdog-wrapped marginal-ΔJ admission decision against a live set
    (``agreeable="rank"`` streaming mode).

Run directly to write ``BENCH_serve.json``:
    PYTHONPATH=src python -m benchmarks.perf_serve [--quick] [--out PATH]
"""
from __future__ import annotations

import argparse
import json
import pathlib
import time

import jax

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp
import numpy as np

from benchmarks.perf_core import require_devices
from repro.compile_cache import enable_compile_cache
from repro.core import power, sample_arrival_stream, sample_workloads
from repro.core.workloads import load_arrival_log
from repro.sched.policies import StreamingSmartFillPolicy
from repro.serve import StreamCascadePolicy, StreamController
from repro.serve.admission import AdmissionController

B = 10.0
SP = power(1.0, 0.5, B)
HETERO_FAMILIES = ("power", "shifted", "log", "neg_power", "saturating")


def _time(fn, *args, reps=100, warmup=3):
    """Best-of-reps warm latency in µs (see perf_core._time)."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best * 1e6  # µs


def bench_calibration():
    """Fixed-work machine-speed probe for the regression gate (identical
    in spirit to perf_core's: touches none of the serving code)."""
    x = jnp.ones((384, 384), jnp.float32)
    f = jax.jit(lambda x: (x @ x @ x).sum())
    return [{"name": "calibration_fixed_work", "us_per_call": _time(f, x)}]


def _stream_row(name, res, best, horizon):
    m = res.metrics
    return {
        "name": name,
        "us_per_call": best * 1e6,
        "horizon_s": horizon,
        "arrivals": m.n_arrivals,
        "completed": m.n_completed,
        "events": res.n_events,
        "replans": res.replans,
        "warm_replans": res.warm_replans,
        "cold_replans": res.cold_replans,
        "degraded_windows": res.degraded_windows,
        "events_per_sec": res.n_events / best,
        "arrivals_per_sec": m.n_arrivals / best,
        "weighted_J": m.weighted_J,
        "mean_slowdown": m.mean_slowdown,
        "p50_latency_s": m.p50_latency,
        "p99_latency_s": m.p99_latency,
        "deadline_misses": m.deadline_misses,
    }


def bench_stream(quick: bool = False):
    """The day-long open-arrival run: sustained events/s + SLO tail.

    Load is ~0.6 of service capacity at the diurnal peak, so the live
    set breathes between empty and full — the regime where warm starts,
    slot recycling, and budget-dip replans all fire.  quick mode runs
    two hours of trace instead of 24 (same mechanics, tier-1 friendly).

    ``serve_stream_day`` is the device-resident scan; the host loop
    with the same ``StreamCascadePolicy`` is its differential oracle
    and is timed alongside as ``serve_stream_day_host`` — the row pair
    is the hot-path speedup, and the device row carries the measured
    completion-array parity against the oracle (must be ~0).
    """
    horizon = 7_200.0 if quick else 86_400.0
    M = 8 if quick else 16
    stream = sample_arrival_stream(
        17, horizon=horizon, rate=0.12, diurnal=0.75, period=horizon,
        B=B, n_budget_events=2 if quick else 12,
        budget_frac=(0.3, 0.8), deadline_slack=50.0)
    ctl = StreamController(SP, B, max_live=M,
                           policy=StreamCascadePolicy(SP, B))

    def run_host():
        return ctl.run(stream)

    def run_dev():
        return ctl.run_device(stream)

    host = run_host()                             # warm the exec jit
    reps = 2 if quick else 1
    best_h = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        host = run_host()
        best_h = min(best_h, time.perf_counter() - t0)
    dev = run_dev()                               # compile + warm
    best_d = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        dev = run_dev()
        best_d = min(best_d, time.perf_counter() - t0)
    parity = float(np.max(np.abs(
        np.where(np.isfinite(host.completion), host.completion, 0.0)
        - np.where(np.isfinite(dev.completion), dev.completion, 0.0))))
    q = "_quick" if quick else ""
    day = _stream_row(f"serve_stream_day{q}", dev, best_d, horizon)
    day["parity_max_completion_diff"] = parity
    day["parity_dJ"] = abs(host.metrics.weighted_J
                           - dev.metrics.weighted_J)
    host_row = _stream_row(f"serve_stream_day_host{q}", host, best_h,
                           horizon)
    return [day, host_row]


MULTITENANT_COUNTS = (1, 2, 4, 8)


def bench_multitenant_at(tenants: int, quick: bool) -> list:
    """The sharded multi-tenant serve over the first ``tenants`` devices,
    one tenant per device.  Weak scaling: per-tenant load is fixed, so
    ideal total events/sec grows linearly with T."""
    from repro.distributed import fleet_mesh, serve_streams_sharded

    horizon = 1_800.0 if quick else 7_200.0
    streams = [sample_arrival_stream(
        17 + i, horizon=horizon, rate=0.12, diurnal=0.75, period=horizon,
        B=B, n_budget_events=2, budget_frac=(0.3, 0.8),
        deadline_slack=50.0) for i in range(tenants)]
    mesh = fleet_mesh(tenants)

    def run():
        return serve_streams_sharded(SP, streams, max_live=8, mesh=mesh)

    fleet = run()                                 # compile + warm
    best = float("inf")
    for _ in range(3 if quick else 2):
        t0 = time.perf_counter()
        fleet = run()
        best = min(best, time.perf_counter() - t0)
    events = sum(r.n_events for r in fleet.results)
    q = "_quick" if quick else "_day"
    return [{
        "name": f"serve_multitenant{q}_T{tenants}",
        "tenants": tenants,
        "us_per_call": best * 1e6,
        "horizon_s": horizon,
        "events": events,
        "events_per_sec": events / best,
        "arrivals": sum(r.metrics.n_arrivals for r in fleet.results),
        "completed": sum(r.metrics.n_completed for r in fleet.results),
        "mean_slowdown": float(np.mean(fleet.mean_slowdown)),
        "suggested_budget_share": fleet.suggested_budget_share.tolist(),
    }]


def bench_multitenant(quick: bool = False):
    """Weak-scaling rows at 1/2/4/8 tenants, in this process.

    Each count T runs over the first T devices this process sees (see
    perf_core.bench_fleet: on the CPU set
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` before
    starting; a count above what the process sees raises)."""
    require_devices(max(MULTITENANT_COUNTS), "the multi-tenant sweep")
    rows = []
    for T in MULTITENANT_COUNTS:
        rows.extend(bench_multitenant_at(T, quick))
    return rows


def bench_replay(quick: bool = False):
    """Replay the recorded trace under benchmarks/traces/ — the
    production-log ingestion path (``load_arrival_log`` →
    ``arrival_stream_from_log`` → controller), host loop."""
    path = pathlib.Path(__file__).parent / "traces" / "arrivals_sample.csv"
    stream = load_arrival_log(path)
    ctl = StreamController(SP, B, max_live=8,
                           policy=StreamCascadePolicy(SP, B))

    def run():
        return ctl.run(stream)

    res = run()                                   # warm
    best = float("inf")
    for _ in range(2 if quick else 3):
        t0 = time.perf_counter()
        res = run()
        best = min(best, time.perf_counter() - t0)
    row = _stream_row("serve_trace_replay", res, best, stream.horizon)
    row["trace"] = path.name
    return [row]


def bench_replan(quick: bool = False):
    """Warm vs cold replanning latency on the same live state.

    Per-job speedups are the honest comparison: a cold replan must
    re-make the §7 completion-order decision (exchange search over the
    live set), while the warm replan reuses the carried order and the
    validated λ payload — one hinted fixed-shape solve.  The shared-
    speedup pair is reported too (there the cold path is only a fresh
    ranking + unhinted solve, so the gap is the λ iterations alone).
    """
    rows = []
    reps = 10 if quick else 20
    for M in ((8,) if quick else (8, 16)):
        wl = sample_workloads(5, K=1, M=M, B=B, family=HETERO_FAMILIES,
                              per_job=True)
        sp1 = jax.tree_util.tree_map(lambda l: jnp.asarray(l)[0], wl.sp)
        x, w = np.asarray(wl.X[0]), np.asarray(wl.W[0])
        act = x > 0

        warm_pol = StreamingSmartFillPolicy(sp1, B)
        warm_pol.plan(x, w, act)                  # prime carried state

        def run_warm():
            return warm_pol.plan(x, w, act)

        def run_cold():
            return warm_pol.plan(x, w, act, warm=False)

        run_warm(); run_cold()                    # compile both paths
        us_w = _time(run_warm, reps=reps, warmup=1)
        us_c = _time(run_cold, reps=max(3, reps // 2), warmup=1)
        pw = run_warm()
        assert pw.warm and pw.certified
        rows.append({"name": f"serve_warm_replan_M{M}", "M": M,
                     "us_per_call": us_w, "J": pw.J})
        rows.append({"name": f"serve_cold_replan_M{M}", "M": M,
                     "us_per_call": us_c, "J": run_cold().J})

    # shared-speedup pair at M=16: the λ-hint-only gap
    M = 16
    x = np.arange(M, 0, -1.0)
    w = 1.0 / x
    act = np.ones(M, bool)
    pol = StreamingSmartFillPolicy(SP, B)
    pol.plan(x, w, act)

    def run_warm_sh():
        return pol.plan(x, w, act)

    def run_cold_sh():
        return pol.plan(x, w, act, warm=False)

    run_warm_sh(); run_cold_sh()
    rows.append({"name": f"serve_warm_replan_shared_M{M}", "M": M,
                 "us_per_call": _time(run_warm_sh, reps=reps, warmup=1)})
    rows.append({"name": f"serve_cold_replan_shared_M{M}", "M": M,
                 "us_per_call": _time(run_cold_sh, reps=reps, warmup=1)})
    return rows


def bench_admission(quick: bool = False):
    """One watchdog-wrapped admission decision against a live set."""
    M = 8 if quick else 15
    rng = np.random.default_rng(2)
    run_x = np.sort(rng.uniform(0.5, 20.0, M))[::-1].copy()
    run_w = 1.0 / run_x
    cand_x = np.asarray([rng.uniform(0.5, 20.0)])
    cand_w = 1.0 / cand_x
    adm = AdmissionController(SP, B=B, agreeable="rank")

    def run():
        return adm.evaluate(run_x, run_w, cand_x, cand_w)

    run()                                         # compile + warm
    return [{"name": f"serve_admission_score_M{M}", "M": M,
             "us_per_call": _time(run, reps=10 if quick else 30,
                                  warmup=1)}]


def collect(quick: bool = False):
    stream = bench_stream(quick=quick)
    multitenant = bench_multitenant(quick=quick)
    replay = bench_replay(quick=quick)
    replan = bench_replan(quick=quick)
    admission = bench_admission(quick=quick)
    serve = stream + multitenant + replay + replan + admission

    by_name = {r["name"]: r for r in serve}
    summary = {}
    day, host = stream[0], stream[1]
    summary["serve_stream_events_per_sec"] = day["events_per_sec"]
    summary["serve_stream_p99_latency_s"] = day["p99_latency_s"]
    summary["serve_stream_mean_slowdown"] = day["mean_slowdown"]
    summary["serve_stream_warm_fraction"] = (
        day["warm_replans"] / max(1, day["replans"]))
    summary["serve_stream_device_vs_host_x"] = (
        host["us_per_call"] / day["us_per_call"])
    summary["serve_stream_parity_max_diff"] = (
        day["parity_max_completion_diff"])
    mt = {r["tenants"]: r for r in multitenant}
    if 1 in mt and 8 in mt:
        summary["serve_multitenant_T8_vs_T1_x"] = (
            mt[8]["events_per_sec"] / mt[1]["events_per_sec"])
    summary["serve_trace_replay_events_per_sec"] = (
        replay[0]["events_per_sec"])
    for M in (8, 16):
        wr = by_name.get(f"serve_warm_replan_M{M}")
        cr = by_name.get(f"serve_cold_replan_M{M}")
        if wr and cr:
            summary[f"serve_warm_vs_cold_replan_M{M}_x"] = (
                cr["us_per_call"] / wr["us_per_call"])
    wr = by_name.get("serve_warm_replan_shared_M16")
    cr = by_name.get("serve_cold_replan_shared_M16")
    if wr and cr:
        summary["serve_warm_vs_cold_replan_shared_x"] = (
            cr["us_per_call"] / wr["us_per_call"])
    # the acceptance headline: incremental replanning must be at least
    # 2x cheaper than planning from scratch on the same live state
    summary["serve_warm_vs_cold_replan_x"] = max(
        v for k, v in summary.items()
        if k.startswith("serve_warm_vs_cold_replan_M"))
    return {
        "calibration": bench_calibration(),
        "serve": serve,
        "summary": summary,
        "config": {"B": B, "quick": quick,
                   "x64": jax.config.jax_enable_x64},
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--out", default="BENCH_serve.json")
    args = ap.parse_args()
    enable_compile_cache()
    report = collect(quick=args.quick)
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
    for r in report["serve"]:
        extra = ""
        if "events_per_sec" in r:
            extra = f"  {r['events_per_sec']:.0f} events/s"
        if "p99_latency_s" in r:
            extra += (f"  p99 {r['p99_latency_s']:.2f}s"
                      f"  warm {r['warm_replans']}/{r['replans']}")
        print(f"{r['name']:40s} {r['us_per_call']:12.1f} µs/call{extra}")
    for k, v in report["summary"].items():
        print(f"  {k:42s} {v:.3f}")
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
