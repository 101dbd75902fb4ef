#!/usr/bin/env python3
"""Drive the scheduler's main path once on a TPU and check it against CPU references.

    python chip_smoke.py              # one chip: stream, plan, simulate, kernels
    python chip_smoke.py --chips 4    # four chips: the sharded paths only

One process.  The chip runs in float32 (x64 stays off, as JAX starts);
every reference runs on the CPU backend of the same process in float64
(``jax.enable_x64(True)`` under ``jax.default_device(cpu)``), so the
chip is never compared with itself.  Each tolerance in ``TOL`` was
derived from a CPU float32-vs-float64 run of the same inputs; see
CHANGES.md.

Phases (one chip):
  stream    the day-long arrival trace through ``StreamController.run_device``
            against the host oracle ``StreamController.run``;
  plan      ``smartfill_batched`` at N=256, M=32 (log family) and
            ``plan_classes`` for 10^6 jobs as C=32 classes, each against
            the CPU run and the J == J_linear certificate;
  simulate  ``simulate_ensemble``, 3 policies x K=256, against the CPU run;
  kernels   the three Pallas waterfill kernels (``impl="pallas"``) at
            K in {1024, 4096}, N=8, against their jnp references: in f64
            on the CPU, and in f32 on the chip as a second witness.

Phases (``--chips 4``):
  sharded   ``serve_streams_sharded`` for T=4 tenants on ``fleet_mesh(4)``
            against each tenant's solo ``run_device`` on one chip, and
            ``plan_sharded`` at N=1024 against ``smartfill_batched`` on one
            chip; the sharded work must span all four chips (the plan's
            output sharding, and each chip's peak memory for the streams).

Compile-plus-first-run and warm wall times are printed as set-up lines.
The last line of standard output is the JSON verdict, printed only when
every phase passed; any failure exits non-zero.  Without a TPU, or
without the repository's sources next to it, the script exits non-zero
before running anything.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import pathlib
import sys
import time
import traceback

ROOT = pathlib.Path(__file__).resolve().parent
B = 10.0

# Upper bounds on chip-f32 vs CPU-f64 differences: ten times the
# largest difference in a CPU float32-vs-float64 run of the same inputs
# (the comment gives it; CHANGES.md has the run).  The chip itself never
# set a bound.
TOL = {
    "stream.weighted_J_rel": 4.6e-5,        # 4.57e-6
    "stream.mean_slowdown_rel": 4.6e-5,     # 4.56e-6
    "stream.p99_latency_rel": 2.7e-4,       # 2.64e-5
    "stream.latency_max_abs_s": 1.7,        # 0.169 s
    "plan.J_rel": 2.3e-6,                   # 2.23e-7
    "plan.certificate_rel": 3e-6,           # 2.99e-7
    "classes.J_rel_on_order": 4.6e-5,       # 4.51e-6
    "classes.certificate_rel": 6.2e-7,      # 6.18e-8
    # how far the chip's searched order may fall behind the CPU f64
    # search's local optimum (the search is not continuous in rounding)
    "classes.search_J_excess": 4.1e-2,      # 4.00e-3
    "simulate.J_rel": 2e-6,                 # 1.91e-7
    # θ relative to θ for jobs above 1e-6 of an average share (the
    # shifted family's θ = (λc/A)^{1/γ} − w cancels in float32 as θ ≪ w,
    # so generic_waterfill's bound is the widest); |θ|/b for the rest
    "kernels.gwf_waterfill.theta_rel": 7.6e-5,       # 7.56e-6
    "kernels.generic_waterfill.theta_rel": 1.1e-2,   # 1.09e-3
    "kernels.hetero_waterfill.theta_rel": 4.6e-4,    # 4.53e-5
    "kernels.theta_rest_over_b": 9.7e-9,    # 9.62e-10
    "kernels.budget_rel": 1.5e-6,           # 1.50e-7
    # sharded vs one chip: the same f32 arithmetic in two programs the
    # compiler may fuse differently, so bounded like f32 against f64
    # (on forced CPU host devices the two are bitwise equal, and the
    # run prints whether they still are)
    "sharded.stream_weighted_J_rel": 4.6e-5,
    "sharded.stream_latency_max_abs_s": 1.7,
    "sharded.plan_J_rel": 2.3e-6,
}


class CheckFailed(Exception):
    pass


def _fail(msg: str) -> None:
    print(f"chip_smoke: {msg}", file=sys.stderr)
    sys.exit(1)


def _rel(a, b):
    """Largest elementwise |a - b| / |b| (|b| floored at 1e-30)."""
    import numpy as np
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b) / np.maximum(np.abs(b), 1e-30)))


def _timed(fn):
    """Run twice: (result of the warm run, first-run s, warm-run s)."""
    import jax
    t0 = time.perf_counter()
    jax.block_until_ready(fn())
    t1 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, t1 - t0, time.perf_counter() - t1


def _setup_line(phase, what, first, warm):
    print(f"setup {phase}.{what}: compile+first_run_s={first!r} "
          f"warm_run_s={warm!r}", flush=True)


@contextlib.contextmanager
def _reference(cpu):
    """The CPU float64 context every reference runs in."""
    import jax
    if cpu is None:
        raise CheckFailed("no independent reference: the CPU backend is "
                          "not visible to this process")
    with jax.enable_x64(True), jax.default_device(cpu):
        yield


def _checks(phase, rows):
    """Print every (name, value, limit) row; raise if any is out of bound."""
    import numpy as np
    bad = []
    for name, value, limit in rows:
        ok = bool(np.isfinite(value)) and value <= limit
        print(f"check {phase}.{name} = {value!r} (limit {limit!r}) "
              f"{'ok' if ok else 'FAIL'}", flush=True)
        if not ok:
            bad.append(name)
    if bad:
        raise CheckFailed(f"{phase}: out of bound: {', '.join(bad)}")


# ---------------------------------------------------------------------------
# One-chip phases
# ---------------------------------------------------------------------------

def _day_stream():
    from repro.core import sample_arrival_stream
    return sample_arrival_stream(
        17, horizon=86_400.0, rate=0.12, diurnal=0.75, period=86_400.0,
        B=B, n_budget_events=12, budget_frac=(0.3, 0.8),
        deadline_slack=50.0)


def _stream_controller(max_live):
    from repro.core import power
    from repro.serve import StreamCascadePolicy, StreamController
    sp = power(1.0, 0.5, B)
    return StreamController(sp, B, max_live=max_live,
                            policy=StreamCascadePolicy(sp, B))


def _nonfinite(res):
    import numpy as np
    m = res.metrics
    vals = [m.weighted_J, m.mean_flow, m.mean_slowdown, m.p50_latency,
            m.p99_latency, res.replans, res.warm_replans, res.cold_replans,
            res.degraded_windows, res.n_events]
    return int(np.sum(~np.isfinite(np.asarray(vals, np.float64))))


def phase_stream(chip, cpu):
    import jax
    import numpy as np
    stream = _day_stream()
    n = len(stream)
    with jax.default_device(chip):
        ctl = _stream_controller(16)
        dev, first, warm = _timed(lambda: ctl.run_device(stream))
    _setup_line("stream", f"run_device_E{dev.n_events}", first, warm)
    t0 = time.perf_counter()
    with _reference(cpu):
        ref = _stream_controller(16).run(stream)
    print(f"setup stream.cpu_f64_host_oracle_s={time.perf_counter() - t0!r}",
          flush=True)
    dm, rm = dev.metrics, ref.metrics
    print(f"info stream: arrivals={n} events={dev.n_events} "
          f"replans={dev.replans} warm={dev.warm_replans} "
          f"cold={dev.cold_replans} degraded={dev.degraded_windows} "
          f"(cpu f64: replans={ref.replans} warm={ref.warm_replans} "
          f"cold={ref.cold_replans} degraded={ref.degraded_windows})",
          flush=True)
    done = np.isfinite(dev.latency) & np.isfinite(ref.latency)
    _checks("stream", [
        ("chip_jobs_not_completed", n - dm.n_completed, 0),
        ("ref_jobs_not_completed", n - rm.n_completed, 0),
        ("chip_nonfinite_counters", _nonfinite(dev), 0),
        ("weighted_J_rel", _rel(dm.weighted_J, rm.weighted_J),
         TOL["stream.weighted_J_rel"]),
        ("mean_slowdown_rel", _rel(dm.mean_slowdown, rm.mean_slowdown),
         TOL["stream.mean_slowdown_rel"]),
        ("p99_latency_rel", _rel(dm.p99_latency, rm.p99_latency),
         TOL["stream.p99_latency_rel"]),
        ("latency_max_abs_s",
         float(np.max(np.abs(dev.latency[done] - ref.latency[done]))),
         TOL["stream.latency_max_abs_s"]),
    ])


def _plan_inputs():
    from repro.core import sample_class_workloads, sample_workloads
    wl = sample_workloads(0, K=256, M=32, B=B, m_range=(16, 32))
    cw = sample_class_workloads(1, K=1, C=32, B=B,
                                count_range=(31_250, 31_250))
    return wl, cw


def _certificate(J, J_lin):
    import numpy as np
    J, J_lin = np.asarray(J, np.float64), np.asarray(J_lin, np.float64)
    return float(np.max(np.abs(J - J_lin) / np.maximum(np.abs(J), 1e-30)))


def phase_plan(chip, cpu):
    import jax
    import numpy as np
    from repro.core import (log_speedup, plan_classes,
                            plan_classes_reference, smartfill_batched)
    wl, cw = _plan_inputs()

    def batched():
        out = smartfill_batched(log_speedup(1.0, 1.0, B), wl.X, wl.W, B=B)
        return out.J, out.J_linear

    with jax.default_device(chip):
        (J, J_lin), first, warm = _timed(batched)
        _setup_line("plan", "smartfill_batched_N256_M32", first, warm)
        t0 = time.perf_counter()
        cls = plan_classes(cw.state(0))
        t1 = time.perf_counter()
        cls = plan_classes(cw.state(0))
        _setup_line("plan", "plan_classes_M1e6_C32", t1 - t0,
                    time.perf_counter() - t1)
    # The exchange-order search ends in a local optimum that moves with
    # rounding: f32 runs whose arithmetic is a few ulp off stop up to
    # 0.5% above the f64 search (CHANGES.md).  So the chip's class plan
    # is held to the numpy oracle on the chip's own order, and its
    # search only to a band above the f64 search.
    with _reference(cpu):
        J_ref, _ = batched()
        cls_ref = plan_classes(cw.state(0))
        on_order = plan_classes_reference(cw.state(0), order=cls.order)
    print(f"info classes: chip order == cpu f64 order: "
          f"{np.array_equal(cls.order, cls_ref.order)}; oracle on the "
          f"chip's order: J={on_order.J!r} J_linear={on_order.J_linear!r}",
          flush=True)
    _checks("plan", [
        ("nonfinite_J", int(np.sum(~np.isfinite(np.asarray(J)))), 0),
        ("J_rel", _rel(J, J_ref), TOL["plan.J_rel"]),
        ("certificate_rel", _certificate(J, J_lin),
         TOL["plan.certificate_rel"]),
        ("classes_jobs", abs(float(np.sum(cls.counts)) - 1e6), 0),
        ("classes_J_rel_on_order", _rel(cls.J, on_order.J),
         TOL["classes.J_rel_on_order"]),
        ("classes_certificate_rel", _certificate(cls.J, cls.J_linear),
         TOL["classes.certificate_rel"]),
        ("classes_search_J_excess",
         (cls.J - cls_ref.J) / cls_ref.J, TOL["classes.search_J_excess"]),
    ])


def phase_simulate(chip, cpu):
    import jax
    import numpy as np
    from repro.core import power, sample_workloads, simulate_ensemble
    from repro.sched.policies import EquiPolicy, HeSRPTPolicy, SmartFillPolicy
    wl = sample_workloads(2, K=256, M=16, B=B, m_range=(8, 16))

    def run():
        sp = power(1.0, 0.5, B)
        pols = (SmartFillPolicy(sp, B=B), HeSRPTPolicy(0.5, B), EquiPolicy(B))
        out = simulate_ensemble(sp, pols, wl.X, wl.W, B=B)
        return out.J, out.finished

    with jax.default_device(chip):
        (J, fin), first, warm = _timed(run)
    _setup_line("simulate", "ensemble_P3_K256_M16", first, warm)
    with _reference(cpu):
        J_ref, fin_ref = run()
    _checks("simulate", [
        ("chip_unfinished", int(np.sum(~np.asarray(fin))), 0),
        ("ref_unfinished", int(np.sum(~np.asarray(fin_ref))), 0),
        ("J_rel", _rel(J, J_ref), TOL["simulate.J_rel"]),
    ])


def _kernel_cases(K, N=8):
    """Seeded inputs for the three kernels at K jobs (numpy, float64)."""
    import numpy as np
    from repro.core import sample_workloads, shifted_power
    rng = np.random.default_rng(K)
    u = rng.uniform(0.1, 1.0, K)
    h0 = rng.uniform(0.0, 1.0, K)
    C = np.zeros((N, K))
    for n in range(N):
        k = int(rng.integers(K // 2, K + 1))
        C[n, :k] = np.sort(rng.uniform(0.05, 1.0, k))[::-1]
    bs = rng.uniform(1.0, 9.0, N)
    fam = shifted_power(1.0, 4.0, 0.5, B)
    hw = sample_workloads(K, K=N, M=K, B=B, per_job=True,
                          family=("power", "shifted", "log", "neg_power",
                                  "saturating"))
    hsp = [np.asarray(hw.sp.A), np.asarray(hw.sp.w), np.asarray(hw.sp.gamma),
           np.broadcast_to(np.asarray(hw.sp.sigma, np.float64), C.shape)]
    Ch = np.where(hw.X > 0, C, 0.0)
    return {        # positional op arguments; the budget comes last
        "gwf_waterfill": (u, h0, 5.0),
        "generic_waterfill": (C, fam.A, fam.w, fam.gamma, bs),
        "hetero_waterfill": (Ch, *hsp, bs),
    }


def _theta_rel(th, ref, b):
    """Largest |θ − θ_ref| / θ_ref over jobs the reference gives more than
    1e-6 of an average share, and the largest |θ| / b over the rest."""
    import numpy as np
    bcol = np.broadcast_to(np.asarray(b, np.float64)[..., None], ref.shape)
    big = ref > 1e-6 * bcol / ref.shape[-1]
    rel = np.abs(th - ref) / np.where(big, ref, 1.0)
    return (float(np.max(np.where(big, rel, 0.0))),
            float(np.max(np.where(big, 0.0, np.abs(th) / bcol))))


def phase_kernels(chip, cpu, impl="pallas"):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.gwf_waterfill import ops

    rows = []
    for K in (1024, 4096):
        for name, vals in _kernel_cases(K).items():
            op = getattr(ops, f"{name}_op")
            b = np.asarray(vals[-1], np.float64)
            with jax.default_device(chip):
                f32 = [jnp.asarray(v, jnp.float32) for v in vals]
                th, first, warm = _timed(lambda: op(*f32, impl=impl))
                # second witness: the jnp reference in f32 on the same
                # chip, which tells the chip's arithmetic from the kernel's
                th32 = op(*f32, impl="ref")
            _setup_line("kernels", f"{name}_K{K}", first, warm)
            with _reference(cpu):
                ref = op(*[jnp.asarray(v, jnp.float64) for v in vals],
                         impl="ref")
            th, th32, ref = (np.asarray(a, np.float64)
                             for a in (th, th32, ref))
            rel, rest = _theta_rel(th, ref, b)
            rel32, rest32 = _theta_rel(th, th32, b)
            tag = f"{name}_K{K}"
            rows += [
                (f"{tag}_theta_rel", rel, TOL[f"kernels.{name}.theta_rel"]),
                (f"{tag}_theta_rest_over_b", rest,
                 TOL["kernels.theta_rest_over_b"]),
                (f"{tag}_vs_chip_ref_theta_rel", rel32,
                 TOL[f"kernels.{name}.theta_rel"]),
                (f"{tag}_vs_chip_ref_rest_over_b", rest32,
                 TOL["kernels.theta_rest_over_b"]),
                (f"{tag}_budget_rel", _rel(th.sum(axis=-1), b),
                 TOL["kernels.budget_rel"]),
            ]
    _checks("kernels", rows)


# ---------------------------------------------------------------------------
# Four-chip phase
# ---------------------------------------------------------------------------

def _peak_bytes(devs):
    """Peak device memory of each chip, from the runtime's own counters."""
    stats = [d.memory_stats() for d in devs]
    if any(not st or "peak_bytes_in_use" not in st for st in stats):
        raise CheckFailed("the runtime reports no peak memory per chip")
    return [int(st["peak_bytes_in_use"]) for st in stats]


def phase_sharded(chip, cpu):
    import jax
    import numpy as np
    from repro.core import (log_speedup, power, sample_arrival_stream,
                            sample_workloads, smartfill_batched)
    from repro.distributed import fleet_mesh, plan_sharded, serve_streams_sharded

    mesh = fleet_mesh(4)
    streams = [sample_arrival_stream(
        17 + i, horizon=7_200.0, rate=0.12, diurnal=0.75, period=7_200.0,
        B=B, n_budget_events=2, budget_frac=(0.3, 0.8), deadline_slack=50.0)
        for i in range(4)]
    sp = power(1.0, 0.5, B)
    devs = list(mesh.devices.flat)
    peak0 = _peak_bytes(devs)
    fleet, first, warm = _timed(
        lambda: serve_streams_sharded(sp, streams, max_live=8, mesh=mesh))
    _setup_line("sharded", "serve_streams_T4", first, warm)
    peak1 = _peak_bytes(devs)
    print(f"info sharded: peak bytes per chip before {peak0}, after {peak1}",
          flush=True)
    # the tenants' state lives on every chip of the mesh, not chip 0 alone
    rows = [("mesh_distinct_chips_missing", 4 - len(set(devs)), 0),
            ("stream_chips_unused",
             sum(a <= b for a, b in zip(peak1, peak0)), 0)]
    with jax.default_device(chip):
        for i, strm in enumerate(streams):
            solo = _stream_controller(8).run_device(strm)
            got = fleet.results[i]
            print(f"info sharded tenant{i}: completions bitwise equal: "
                  f"{np.array_equal(got.completion, solo.completion)}; "
                  f"replans {got.replans} (solo {solo.replans})", flush=True)
            rows += [
                (f"tenant{i}_jobs_not_completed",
                 len(strm) - got.metrics.n_completed, 0),
                (f"tenant{i}_solo_jobs_not_completed",
                 len(strm) - solo.metrics.n_completed, 0),
                (f"tenant{i}_weighted_J_rel",
                 _rel(got.metrics.weighted_J, solo.metrics.weighted_J),
                 TOL["sharded.stream_weighted_J_rel"]),
                (f"tenant{i}_latency_max_abs_s",
                 float(np.max(np.abs(got.latency - solo.latency))),
                 TOL["sharded.stream_latency_max_abs_s"]),
            ]

    wl = sample_workloads(0, K=1024, M=32, B=B, m_range=(16, 32))
    lsp = log_speedup(1.0, 1.0, B)
    sh, first, warm = _timed(
        lambda: plan_sharded(lsp, wl.X, wl.W, B=B, mesh=mesh))
    _setup_line("sharded", "plan_sharded_N1024_M32", first, warm)
    with jax.default_device(chip):
        one = smartfill_batched(lsp, wl.X, wl.W, B=B)
    rows += [
        ("plan_devices_missing", 4 - len(sh.J.sharding.device_set), 0),
        ("plan_J_rel", _rel(sh.J, one.J), TOL["sharded.plan_J_rel"]),
    ]
    _checks("sharded", rows)


# ---------------------------------------------------------------------------

def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded paths across four chips")
    args = ap.parse_args()

    if not (ROOT / "src" / "repro").is_dir():
        _fail(f"the repository's sources (src/repro) are not next to "
              f"{pathlib.Path(__file__).name}; run it from a checkout")
    sys.path.insert(0, str(ROOT / "src"))
    # the references need the CPU backend next to the chip
    plats = os.environ.get("JAX_PLATFORMS", "")
    if plats and "cpu" not in plats.split(","):
        os.environ["JAX_PLATFORMS"] = plats + ",cpu"

    import jax

    from repro.compile_cache import enable_compile_cache

    print(f"setup compile_cache_dir={enable_compile_cache()}", flush=True)
    devs = jax.devices()
    d0 = devs[0]
    print(f"device platform={d0.platform} kind={d0.device_kind} "
          f"count={len(devs)}", flush=True)
    if d0.platform != "tpu":
        _fail(f"no TPU: JAX's default backend is {d0.platform!r}")
    if len(devs) < args.chips:
        _fail(f"--chips {args.chips} needs {args.chips} chips, "
              f"found {len(devs)}")
    try:
        cpu = jax.devices("cpu")[0]
    except RuntimeError:
        cpu = None

    phases = ([phase_sharded] if args.chips == 4 else
              [phase_stream, phase_plan, phase_simulate, phase_kernels])
    failed = []
    for phase in phases:
        name = phase.__name__.removeprefix("phase_")
        t0 = time.perf_counter()
        try:
            phase(d0, cpu)
        except Exception:
            traceback.print_exc()
            failed.append(name)
        print(f"setup {name}.phase_wall_s={time.perf_counter() - t0!r}",
              flush=True)
    if failed:
        _fail(f"failed phases: {', '.join(failed)}")
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": args.chips}}))


if __name__ == "__main__":
    main()
