"""Device-resident scenario engine: event-driven execution of policies.

Between events allocations are constant, so the next event is the
earliest of (a) a completion min_i rem_i / s(θ_i) and (b) a pending
arrival; at each event the policy is re-invoked on the updated remaining
sizes.  Exact for piecewise-constant policies (which SmartFill, heSRPT
and every policy in ``sched/policies.py`` are, Prop. 7) — no time
discretization error.

Two executors share these semantics:

``simulate_policy`` (device engine)
    One jitted ``lax.scan`` over a **fixed** event count 4M+16 — enough
    for M completions plus M arrival events with a 2×+16 safety margin.
    Jobs are padded (size 0 ⇒ never active), arrivals are folded in as
    events (the step advances to exactly ``min(t + dt_completion,
    next_arrival)``), and halting is a masked no-op so the program shape
    is static.  Policies must be jax-traceable ``(rem, w, active) → θ``
    pytrees (see ``sched/policies.py``); legacy host callables are
    transparently routed to the reference loop.

``simulate_policy_reference`` (host oracle)
    The original numpy event loop, kept as the differential-test oracle
    for the device engine, extended with the same arrival-event
    semantics.

``simulate_ensemble`` evaluates P policies × K workloads in **one**
compiled call: a Python-unrolled loop over policies (each a distinct
pytree) around a ``jax.vmap`` over workloads, inside a single
``jax.jit``.  Speedup parameters may themselves be batched per workload:
any pytree leaf of ``sp`` (or of a policy) with leading dimension K is
vmapped alongside the workload arrays.

**Fault schedules** (``faults=`` on every executor): a ``FaultTrace``
holds a sorted sequence of timed control-plane events folded into the
event horizon exactly like arrivals — the step advances to exactly
``min(t + dt_completion, next_arrival, next_fault)``:

  * ``KIND_BUDGET``    — the server budget becomes ``value`` (preemption
    shrinks B(t), recovery restores it).  Policies are invoked with the
    *current* budget (the optional 4th argument of the policy
    interface), so re-planning policies re-solve under B(t) and cached
    plans invalidate instead of executing a stale table.
  * ``KIND_FAILURE``   — job ``job`` crashes and restarts, losing the
    fraction ``value`` of its *completed* work (rem += value·(x − rem)).
    Completions are resolved first: a failure coincident with (or after)
    a job's completion is a no-op.
  * ``KIND_STRAGGLER`` — job ``job``'s effective service rate is scaled
    by ``value`` from now on (degraded speedup the planner cannot see);
    ``value = 1`` is recovery.

Both executors implement identical fault semantics, so the host oracle
remains the differential pin for the faulted device engine
(tests/robust/test_faults.py).

Engine throughput is dominated by the per-event policy call — for
``SmartFillPolicy`` that is a full re-plan, so the events/sec reported
by ``benchmarks/perf_core.py`` scale directly with the solver hot path
(the O(k log k) factorized water-filling and the bracketed-descent μ*
minimizer of ``core/gwf.py`` / ``core/smartfill.py``).

Used for
  * cross-checking SmartFill's predicted J (= Σ a_i x_i) against an
    independent execution of its schedule,
  * evaluating baseline policies (heSRPT, EQUI, …) under a true concave
    s over large randomized ensembles (paper §6), and
  * the cluster-scheduler event loop (sched/cluster.py) and the serving
    tier's simulated admission scoring (serve/admission.py).
"""
from __future__ import annotations

import dataclasses
import logging
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

_log = logging.getLogger(__name__)

__all__ = [
    "SimResult",
    "EnsembleResult",
    "FluidClassResult",
    "FaultTrace",
    "KIND_BUDGET",
    "KIND_FAILURE",
    "KIND_STRAGGLER",
    "budget_trace",
    "n_events_for",
    "simulate_policy",
    "simulate_policy_device",
    "simulate_policy_reference",
    "simulate_ensemble",
    "simulate_fluid_classes",
    "schedule_policy",
    "smartfill_sim_policy",
]


@dataclasses.dataclass(frozen=True)
class SimResult:
    T: np.ndarray          # completion time per job
    J: float               # Σ w_i T_i (inf if any job failed to finish)
    events: list           # (t, allocations) trace
    n_events: int


@dataclasses.dataclass(frozen=True)
class EnsembleResult:
    """Stacked outcomes of P policies × K workloads (device arrays).

    J[p, k] = Σ_i w_i T_i of policy p on workload k (+inf where the
    policy failed to complete every job within the event budget);
    T: (P, K, M) completion times; finished: (P, K) all-jobs-done flags;
    n_events: (P, K) executed (non-halt) event counts;
    exhausted: (P, K) — True where the row is unfinished *because* the
    fixed device event budget saturated (n_events hit the horizon), as
    opposed to e.g. a zero-allocation policy stalling.  Such a J=inf is
    an artifact of the horizon, not a verdict on the policy — raise
    ``n_events`` to resolve it; the runner also warns once per process
    (mirroring the cluster scheduler's loud device fallback).
    """

    J: jnp.ndarray
    T: jnp.ndarray
    finished: jnp.ndarray
    n_events: jnp.ndarray
    exhausted: jnp.ndarray
    policy_names: tuple

    def __len__(self) -> int:
        return int(self.J.shape[0])


def n_events_for(M: int) -> int:
    """Fixed event budget of the device engine: 4M + 16."""
    return 4 * int(M) + 16


# Loud-once flag for event-budget exhaustion (module-level so the warning
# fires once per process across every ensemble/sharded runner, mirroring
# sched/cluster.py's _warned_device_fallback).
_warned_event_budget = False


def _warn_event_budget(exhausted, n_events: int, where: str) -> None:
    """Warn (once per process) when rows returned J=inf only because the
    fixed device event horizon saturated mid-run.  Before this existed
    the artifact was indistinguishable from a genuinely stalling policy."""
    global _warned_event_budget
    if _warned_event_budget:
        return
    n_bad = int(np.sum(np.asarray(exhausted)))
    if n_bad:
        _warned_event_budget = True
        _log.warning(
            "%s: %d row(s) hit the fixed device event budget "
            "(n_events=%d) before finishing — their J=inf is a horizon "
            "artifact, not a policy verdict; raise n_events (see "
            "EnsembleResult.exhausted; further occurrences are silent)",
            where, n_bad, n_events)


# ---------------------------------------------------------------------------
# Fault traces (dynamic budgets, failures, stragglers)
# ---------------------------------------------------------------------------

KIND_BUDGET = 0      # value = new server budget B(t)
KIND_FAILURE = 1     # job restarts, losing fraction `value` of done work
KIND_STRAGGLER = 2   # job's effective rate is scaled by `value` from now on


@dataclasses.dataclass(frozen=True)
class FaultTrace:
    """Seeded, replayable control-plane fault schedule.

    times:  (S,) or (K, S) non-decreasing event times (+inf = padding).
    kinds:  int array, same shape — KIND_BUDGET / KIND_FAILURE /
            KIND_STRAGGLER per event (ignored on +inf padding slots).
    jobs:   int array, same shape — target job for FAILURE / STRAGGLER
            (ignored for BUDGET; use 0).
    values: float array, same shape — payload: the new budget (> 0), the
            lost fraction of completed work in [0, 1], or the new rate
            multiplier (> 0; a hard-zero stall would deadlock the host
            oracle while the device engine pads J to +inf, so full stops
            are rejected by ``validate``).

    The 2-D form carries one trace per workload for ensemble runs;
    ``instance(k)`` extracts a single row.  Build via
    ``core.workloads.sample_fault_traces`` (seeded chaos) or
    ``budget_trace`` (pure B(t) steps).
    """

    times: np.ndarray
    kinds: np.ndarray
    jobs: np.ndarray
    values: np.ndarray

    def __post_init__(self):
        object.__setattr__(self, "times", np.asarray(self.times, np.float64))
        object.__setattr__(self, "kinds", np.asarray(self.kinds, np.int32))
        object.__setattr__(self, "jobs", np.asarray(self.jobs, np.int32))
        object.__setattr__(self, "values", np.asarray(self.values, np.float64))

    @property
    def S(self) -> int:
        return int(self.times.shape[-1])

    @property
    def batched(self) -> bool:
        return self.times.ndim == 2

    def instance(self, k: int) -> "FaultTrace":
        if not self.batched:
            return self
        return FaultTrace(self.times[k], self.kinds[k], self.jobs[k],
                          self.values[k])

    def validate(self, M: int) -> None:
        """Host-side shape/semantics checks; raises ValueError."""
        t, k, j, v = self.times, self.kinds, self.jobs, self.values
        if t.ndim not in (1, 2):
            raise ValueError(f"FaultTrace.times must be 1-D or 2-D, got "
                             f"shape {t.shape}")
        if not (k.shape == t.shape == j.shape == v.shape):
            raise ValueError("FaultTrace arrays must share one shape, got "
                             f"times{t.shape} kinds{k.shape} jobs{j.shape} "
                             f"values{v.shape}")
        if np.isnan(t).any() or (t < 0).any():
            raise ValueError("FaultTrace.times must be ≥ 0 (NaN forbidden; "
                             "+inf = padding)")
        if not np.all(t[..., :-1] <= t[..., 1:]):
            raise ValueError("FaultTrace.times must be non-decreasing "
                             "per trace (inf-padded at the end)")
        live = np.isfinite(t)
        if not np.isin(k[live], (KIND_BUDGET, KIND_FAILURE,
                                 KIND_STRAGGLER)).all():
            raise ValueError("FaultTrace.kinds must be KIND_BUDGET/"
                             "KIND_FAILURE/KIND_STRAGGLER")
        targeted = live & np.isin(k, (KIND_FAILURE, KIND_STRAGGLER))
        if ((j[targeted] < 0) | (j[targeted] >= M)).any():
            raise ValueError(f"FaultTrace.jobs must lie in [0, {M}) for "
                             "failure/straggler events")
        vb = v[live & (k == KIND_BUDGET)]
        if (~np.isfinite(vb) | (vb <= 0)).any():
            raise ValueError("budget events need a finite value > 0")
        vf = v[live & (k == KIND_FAILURE)]
        if (~np.isfinite(vf) | (vf < 0) | (vf > 1)).any():
            raise ValueError("failure events need a loss fraction in [0, 1]")
        vs = v[live & (k == KIND_STRAGGLER)]
        if (~np.isfinite(vs) | (vs <= 0)).any():
            raise ValueError("straggler events need a finite rate "
                             "multiplier > 0")


def budget_trace(times, values) -> FaultTrace:
    """Pure budget schedule B(t): step to ``values[i]`` at ``times[i]``."""
    times = np.asarray(times, np.float64)
    values = np.asarray(values, np.float64)
    return FaultTrace(times=times, kinds=np.zeros(times.shape, np.int32),
                      jobs=np.zeros(times.shape, np.int32), values=values)


def _prepared_faults(faults: FaultTrace, M: int, dtype, K: int | None = None):
    """Validate and lower a FaultTrace to device arrays.

    Appends one +inf sentinel event so the scan can index ``times[fi]``
    with ``fi`` up to S without out-of-bounds clamping surprises; with
    ``K`` given, 1-D traces are broadcast so every fault leaf is
    unambiguously (K, S+1)-batched for vmap/shard_map.
    """
    faults.validate(M)
    t = faults.times
    pad = np.full(t.shape[:-1] + (1,), np.inf)
    t = np.concatenate([t, pad], axis=-1)
    k = np.concatenate([faults.kinds,
                        np.full(pad.shape, -1, np.int32)], axis=-1)
    j = np.concatenate([faults.jobs, np.zeros(pad.shape, np.int32)], axis=-1)
    v = np.concatenate([faults.values, np.zeros(pad.shape)], axis=-1)
    if K is not None:
        if t.ndim == 1:
            t, k, j, v = (np.broadcast_to(a, (K,) + a.shape).copy()
                          for a in (t, k, j, v))
        elif t.shape[0] != K:
            raise ValueError(f"batched FaultTrace has {t.shape[0]} traces "
                             f"for K={K} workloads")
    elif t.ndim != 1:
        raise ValueError("single-instance executors need a 1-D FaultTrace "
                         "(use .instance(k) to pick one row)")
    return (jnp.asarray(t, dtype), jnp.asarray(k), jnp.asarray(j),
            jnp.asarray(v, dtype))


def _fault_n_events(M: int, S: int) -> int:
    """Default event budget with faults: each fault consumes one event
    and each failure can force one extra completion."""
    return n_events_for(M) + 2 * int(S)


# ---------------------------------------------------------------------------
# Input validation (front-door satellite): negative / non-finite sizes,
# weights or budgets used to flow into the scan and surface as NaN J.
# ---------------------------------------------------------------------------

def _concrete(a):
    """Host view of ``a``, or None if it is a tracer/abstract value."""
    try:
        return np.asarray(a)
    except (TypeError, ValueError):     # tracers, ragged input
        return None


def _validate_workload(x, w, arrival=None, what: str = "simulate_policy"):
    for name, a in (("x (sizes)", x), ("w (weights)", w)):
        arr = _concrete(a)
        if arr is None:
            continue
        if not np.all(np.isfinite(arr)):
            raise ValueError(f"{what}: {name} must be finite; got "
                             f"min={np.min(arr)!r} max={np.max(arr)!r}")
        if np.any(arr < 0):
            raise ValueError(f"{what}: {name} must be ≥ 0 "
                             f"(size 0 = padding); got min={np.min(arr)!r}")
    if arrival is not None:
        arr = _concrete(arrival)
        if arr is not None and np.isnan(arr).any():
            raise ValueError(f"{what}: arrival times must not be NaN")


def _validate_budget(B, what: str, source: str = "B"):
    if B is None:
        return
    arr = _concrete(B)
    if arr is None:
        return
    if not np.all(np.isfinite(arr)) or np.any(arr <= 0):
        raise ValueError(f"{what}: {source} must be finite and > 0, "
                         f"got {arr!r}")


# ---------------------------------------------------------------------------
# Device engine
# ---------------------------------------------------------------------------

def _sim_core(sp, policy, x, w, arrival, rtol, n_events, faults=None,
              B0=None):
    """Traced single-instance event loop — the body shared by jit/vmap.

    Jobs with x == 0 are padding: never arrive, never run, T = 0.
    Returns (T, finished, ts, thetas, valid) where ts/thetas/valid are
    the (n_events,)-padded event trace (valid=False ⇒ halt no-op).

    ``faults`` (prepared sentinel-terminated arrays, see
    ``_prepared_faults``) switches to the fault-aware step: the carry
    additionally tracks the current budget B(t) (initialized from
    ``B0``), per-job rate multipliers, and a fault cursor.  The step
    advances to ``min(t + dt_completion, next_arrival, next_fault)``,
    resolves completions first, then applies at most one fault event
    (coincident faults drain through successive dt = 0 steps).  With
    ``faults=None`` the legacy step runs unchanged — byte-identical
    program, policies invoked with the 3-argument form.
    """
    dtype = x.dtype
    M = x.shape[0]
    real = x > 0
    rem0 = jnp.where(real, x, 0.0)
    # completion tolerance: relative to the largest job, floored at a few
    # ulps of the working dtype so float32 runs still detect completions
    eps = jnp.finfo(dtype).eps
    tol = jnp.maximum(rtol, 8.0 * eps) * jnp.maximum(1.0, jnp.max(x, initial=0.0))
    zero = jnp.zeros((), dtype)

    if faults is None:
        def step(carry, _):
            t, rem, T = carry
            arrived = real & (arrival <= t)
            active = arrived & (rem > 0)
            theta = jnp.where(active, policy(rem, w, active), zero)
            rates = jnp.where(active, sp.s(theta), zero)
            runnable = active & (rates > 0)
            dt_c = jnp.min(jnp.where(runnable,
                                     rem / jnp.where(runnable, rates, 1.0),
                                     jnp.inf))
            pending = real & ~arrived
            t_arr = jnp.min(jnp.where(pending, arrival, jnp.inf))
            t_next = jnp.minimum(t + dt_c, t_arr)  # == t_arr on arrivals
            live = jnp.isfinite(t_next)
            t_new = jnp.where(live, t_next, t)
            dt = t_new - t
            rem2 = jnp.where(active, rem - rates * dt, rem)
            done_now = active & (rem2 <= tol)
            T = jnp.where(done_now, t_new, T)
            rem2 = jnp.where(done_now, zero, jnp.maximum(rem2, 0.0))
            return (t_new, rem2, T), (t, theta, live)

        carry0 = (zero, rem0, jnp.zeros((M,), dtype))
        (_, rem_end, T), (ts, thetas, valid) = lax.scan(
            step, carry0, None, length=n_events)
        finished = jnp.all(~real | (rem_end <= 0))
        return T, finished, ts, thetas, valid

    ftimes, fkinds, fjobs, fvalues = faults     # (S+1,) sentinel-terminated
    S = ftimes.shape[0] - 1
    lane = jnp.arange(M)

    def step(carry, _):
        t, rem, T, Bc, mult, fi = carry
        arrived = real & (arrival <= t)
        active = arrived & (rem > 0)
        theta = jnp.where(active, policy(rem, w, active, Bc), zero)
        rates = jnp.where(active, sp.s(theta) * mult, zero)
        runnable = active & (rates > 0)
        dt_c = jnp.min(jnp.where(runnable,
                                 rem / jnp.where(runnable, rates, 1.0),
                                 jnp.inf))
        pending = real & ~arrived
        t_arr = jnp.min(jnp.where(pending, arrival, jnp.inf))
        idx = jnp.minimum(fi, S)                # sentinel keeps this in-range
        t_fault = ftimes[idx]
        t_next = jnp.minimum(jnp.minimum(t + dt_c, t_arr), t_fault)
        # faults alone are not work: once every real job is done (or can
        # never arrive) the engine halts even if fault events remain —
        # mirrored by the reference oracle's early return.
        live = jnp.isfinite(t_next) & (active.any() | pending.any())
        t_new = jnp.where(live, t_next, t)
        dt = t_new - t
        rem2 = jnp.where(active, rem - rates * dt, rem)
        done_now = active & (rem2 <= tol)
        T = jnp.where(done_now, t_new, T)
        rem2 = jnp.where(done_now, zero, jnp.maximum(rem2, 0.0))
        # completions above are resolved first; now at most one fault
        hit = live & (t_fault <= t_new)
        kind = fkinds[idx]
        sel = lane == fjobs[idx]
        val = fvalues[idx]
        Bc = jnp.where(hit & (kind == KIND_BUDGET), val, Bc)
        # a failure only bites jobs that have arrived and still run —
        # crashing a job at (or after) its completion instant is a no-op
        failable = real & (arrival <= t_new) & (rem2 > 0)
        lose = hit & (kind == KIND_FAILURE)
        rem2 = jnp.where(lose & sel & failable,
                         jnp.minimum(rem2 + val * (x - rem2), x), rem2)
        mult = jnp.where(hit & (kind == KIND_STRAGGLER) & sel, val, mult)
        fi = fi + hit.astype(fi.dtype)
        return (t_new, rem2, T, Bc, mult, fi), (t, theta, live)

    carry0 = (zero, rem0, jnp.zeros((M,), dtype),
              jnp.asarray(B0, dtype), jnp.ones((M,), dtype),
              jnp.zeros((), jnp.int32))
    (_, rem_end, T, _, _, _), (ts, thetas, valid) = lax.scan(
        step, carry0, None, length=n_events)
    finished = jnp.all(~real | (rem_end <= 0))
    return T, finished, ts, thetas, valid


@partial(jax.jit, static_argnames=("n_events",))
def _simulate_faulted_jit(sp, policy, x, w, arrival, rtol, n_events,
                          faults, B0):
    T, finished, ts, thetas, valid = _sim_core(
        sp, policy, x, w, arrival, rtol, n_events, faults=faults, B0=B0)
    J = jnp.where(finished, jnp.sum(w * T), jnp.inf)
    return T, J, finished, ts, thetas, valid


@partial(jax.jit, static_argnames=("n_events",))
def _simulate_jit(sp, policy, x, w, arrival, rtol, n_events):
    T, finished, ts, thetas, valid = _sim_core(
        sp, policy, x, w, arrival, rtol, n_events)
    J = jnp.where(finished, jnp.sum(w * T), jnp.inf)
    return T, J, finished, ts, thetas, valid


def _check_policy_budget(policy, B):
    """The engine spends the *policy's* budget; a caller-supplied B is a
    cross-check only.  Raise loudly on a concrete mismatch instead of
    silently simulating a different budget than the caller asked for."""
    if B is None:
        return
    pB = getattr(policy, "B", None)
    if pB is None:
        return
    try:
        ok = np.allclose(np.asarray(B, dtype=np.float64),
                         np.asarray(pB, dtype=np.float64))
    except (TypeError, ValueError, jax.errors.TracerArrayConversionError):
        return                      # traced / non-broadcastable: trust caller
    if not ok:
        raise ValueError(
            f"B={B} disagrees with {getattr(policy, 'name', policy)!r}'s "
            f"own budget {pB}; the engine executes the policy's B — "
            "construct the policy with the budget you want (per-workload "
            "budgets: give the policy a (K,)-shaped B leaf)")


def _fault_B0(policy, B, what: str):
    """Initial budget B(0) for a faulted run: the caller's B, else the
    policy's own; faulted runs need one (the carry tracks it)."""
    B0 = B if B is not None else getattr(policy, "B", None)
    if B0 is None:
        raise ValueError(
            f"{what}: faulted runs need an initial budget — pass B= or use "
            "a policy with a B leaf")
    return B0


def simulate_policy_device(sp, x, w, policy, B=None, arrival=None,
                           rtol: float = 1e-12, max_events: int | None = None,
                           trace: bool = True,
                           faults: FaultTrace | None = None) -> SimResult:
    """Run a jax-traceable policy through the ``lax.scan`` engine.

    policy(rem, w, active) → (M,) allocations with Σ over active ≤ B;
    must be a pytree of traceable ops (see ``sched/policies.py``).  The
    bandwidth budget is the **policy's own B** — the ``B`` kwarg is only
    cross-checked against it (mismatch raises).  ``arrival`` (optional)
    holds per-job release times; jobs are folded in as events.  Returns
    a host-materialized SimResult; jobs that did not complete within the
    4M+16 event budget leave J = +inf.

    ``faults`` (a 1-D ``FaultTrace``) enables the fault-aware engine:
    the policy is then invoked as ``policy(rem, w, active, B_t)`` with
    the current budget, so it must accept the optional 4th argument
    (every policy in ``sched/policies.py`` does).
    """
    _check_policy_budget(policy, B)
    _validate_workload(x, w, arrival, what="simulate_policy")
    _validate_budget(B, "simulate_policy")
    _validate_budget(getattr(policy, "B", None), "simulate_policy",
                     source=f"policy {getattr(policy, 'name', policy)!r}.B")
    x = jnp.asarray(x, dtype=jnp.result_type(float))
    w = jnp.asarray(w, dtype=x.dtype)
    M = x.shape[0]
    if M == 0:                          # match the reference: nothing to do
        return SimResult(T=np.zeros(0), J=0.0, events=[], n_events=0)
    arr = (jnp.zeros((M,), x.dtype) if arrival is None
           else jnp.asarray(arrival, x.dtype))
    if faults is not None:
        ft = _prepared_faults(faults, M, x.dtype)
        n_events = int(max_events or _fault_n_events(M, faults.S))
        B0 = jnp.asarray(_fault_B0(policy, B, "simulate_policy"), x.dtype)
        T, J, finished, ts, thetas, valid = _simulate_faulted_jit(
            sp, policy, x, w, arr, jnp.asarray(rtol, x.dtype), n_events,
            ft, B0)
    else:
        n_events = int(max_events or n_events_for(M))
        T, J, finished, ts, thetas, valid = _simulate_jit(
            sp, policy, x, w, arr, jnp.asarray(rtol, x.dtype), n_events)
    if not trace:
        return SimResult(T=np.asarray(T), J=float(J), events=[],
                         n_events=int(np.asarray(valid).sum()))
    ts = np.asarray(ts)
    thetas = np.asarray(thetas)
    mask = np.asarray(valid)
    events = [(float(ts[i]), thetas[i].copy())
              for i in np.flatnonzero(mask)]
    return SimResult(T=np.asarray(T), J=float(J), events=events,
                     n_events=len(events))


def simulate_policy(sp, x, w, policy, B=None, arrival=None,
                    rtol: float = 1e-12, max_events: int | None = None,
                    faults: FaultTrace | None = None):
    """Run ``policy`` to completion under true speedup ``sp``.

    Dispatch: pytree policies from ``sched/policies.py`` (marked
    ``device_ready``) run on the ``lax.scan`` device engine; plain host
    callables run on the numpy reference loop (the pre-engine behavior).
    """
    if getattr(policy, "device_ready", False):
        return simulate_policy_device(sp, x, w, policy, B=B,
                                      arrival=arrival, rtol=rtol,
                                      max_events=max_events, faults=faults)
    return simulate_policy_reference(sp, x, w, policy, B=B, arrival=arrival,
                                     rtol=rtol, max_events=max_events,
                                     faults=faults)


# ---------------------------------------------------------------------------
# Ensemble runner: P policies × K workloads, one compiled call
# ---------------------------------------------------------------------------

def _batch_axes(tree, K: int):
    """vmap in_axes for ``tree``: leaves with leading dim K map on 0."""
    from .batch import batch_axes

    return batch_axes(tree, K)


@partial(jax.jit, static_argnames=("n_events",))
def _ensemble_jit(sp, policies, X, W, ARR, rtol, n_events, faults=None):
    K = X.shape[0]
    sp_axes = _batch_axes(sp, K)
    Ts, Js, fins, nev = [], [], [], []
    for pol in policies:                 # static unroll — one program
        pol_axes = _batch_axes(pol, K)

        if faults is None:
            def one(spv, pv, xk, wk, ak):
                T, finished, _, _, valid = _sim_core(
                    spv, pv, xk, wk, ak, rtol, n_events)
                J = jnp.where(finished, jnp.sum(wk * T), jnp.inf)
                return T, J, finished, jnp.sum(valid)

            T, J, finished, ne = jax.vmap(
                one, in_axes=(sp_axes, pol_axes, 0, 0, 0))(
                    sp, pol, X, W, ARR)
        else:
            def one(spv, pv, xk, wk, ak, fk):
                T, finished, _, _, valid = _sim_core(
                    spv, pv, xk, wk, ak, rtol, n_events,
                    faults=fk, B0=pv.B)
                J = jnp.where(finished, jnp.sum(wk * T), jnp.inf)
                return T, J, finished, jnp.sum(valid)

            # axes derived from the fault pytree itself: every prepared
            # fault leaf is (K, S+1)-batched, and a structure-matched
            # spec can never silently desynchronize when FaultTrace
            # grows a field (a literal 4-tuple would)
            fault_axes = jax.tree_util.tree_map(lambda _: 0, faults)
            T, J, finished, ne = jax.vmap(
                one, in_axes=(sp_axes, pol_axes, 0, 0, 0, fault_axes))(
                    sp, pol, X, W, ARR, faults)
        Ts.append(T)
        Js.append(J)
        fins.append(finished)
        nev.append(ne)
    return (jnp.stack(Js), jnp.stack(Ts), jnp.stack(fins), jnp.stack(nev))


def _check_axes_unambiguous(tree, K: int, M: int, what: str):
    """With K == M a 1-D (K,) leaf could equally be per-job data; refuse
    to guess (a wrong guess silently corrupts every instance).  One
    shared implementation with the batched planner (core/batch.py)."""
    from .batch import check_axes_unambiguous

    check_axes_unambiguous(tree, K, M, what)


def simulate_ensemble(sp, policies, X, W, arrival=None, B=None,
                      rtol: float = 1e-12,
                      n_events: int | None = None,
                      faults: FaultTrace | None = None) -> EnsembleResult:
    """Evaluate P policies × K workloads in one compiled device call.

    Args:
      sp: true speedup driving the dynamics.  Pytree leaves with leading
        dimension K (e.g. per-workload ``RegularSpeedup`` parameters from
        ``core/workloads.py``) are vmapped per workload; scalar leaves
        are shared.  (When K == M this is ambiguous for 1-D leaves and
        the call raises — reshape per-workload leaves to (K, 1).)
      policies: sequence of device-ready policy pytrees
        (``sched/policies.py``).  Per-workload policy parameters batch
        the same way as ``sp`` — e.g. a (K,)-shaped ``B`` leaf gives
        each workload its own budget.
      X, W: (K, M) padded sizes / weights (size 0 ⇒ padding).
      arrival: optional (K, M) release times (0 = present at start).
      B: cross-check only — each policy spends its *own* B; a concrete
        mismatch with a policy's budget raises.
      n_events: event budget per instance; defaults to 4M+16
        (+2 per fault event when ``faults`` is given).
      faults: optional ``FaultTrace`` — 1-D (same trace for every
        workload) or (K, S)-batched (one trace per workload, sharded
        like workload ensembles).  Every policy then needs a B leaf
        (the initial budget of its fault carry).

    Returns an EnsembleResult with all arrays still on device.
    """
    X = jnp.asarray(X, dtype=jnp.result_type(float))
    W = jnp.asarray(W, dtype=X.dtype)
    if X.ndim != 2 or W.shape != X.shape:
        raise ValueError("X and W must both be (K, M)")
    K, M = X.shape
    _validate_workload(X, W, arrival, what="simulate_ensemble")
    _validate_budget(B, "simulate_ensemble")
    ARR = (jnp.zeros_like(X) if arrival is None
           else jnp.asarray(arrival, X.dtype))
    if ARR.shape != X.shape:
        raise ValueError("arrival must be (K, M)")
    policies = tuple(policies)
    if not policies:
        raise ValueError("need at least one policy")
    if M == 0:                          # K empty instances: all-zero result
        P = len(policies)
        return EnsembleResult(
            J=jnp.zeros((P, K), X.dtype), T=jnp.zeros((P, K, 0), X.dtype),
            finished=jnp.ones((P, K), bool),
            n_events=jnp.zeros((P, K), jnp.int32),
            exhausted=jnp.zeros((P, K), bool),
            policy_names=tuple(getattr(p, "name", type(p).__name__)
                               for p in policies))
    _check_axes_unambiguous(sp, K, M, "sp")
    for p in policies:
        if not getattr(p, "device_ready", False):
            raise ValueError(
                f"policy {p!r} is not device-ready; use sched/policies.py")
        _check_policy_budget(p, B)
        _validate_budget(getattr(p, "B", None), "simulate_ensemble",
                         source=f"policy {getattr(p, 'name', p)!r}.B")
        _check_axes_unambiguous(p, K, M, f"policy {getattr(p, 'name', p)!r}")
    ft = None
    if faults is not None:
        for p in policies:
            # the ensemble fault carry starts from each policy's own B
            _fault_B0(p, None, "simulate_ensemble")
        # broadcast to (K, S+1) so fault leaves always batch unambiguously
        ft = _prepared_faults(faults, M, X.dtype, K=K)
        n_events = int(n_events or _fault_n_events(M, faults.S))
    else:
        n_events = int(n_events or n_events_for(M))
    J, T, finished, ne = _ensemble_jit(
        sp, policies, X, W, ARR, jnp.asarray(rtol, X.dtype), n_events,
        faults=ft)
    # unfinished AND the executed-event count saturated the horizon ⇒ the
    # run was cut off, not stalled; surface it instead of a bare J=inf
    exhausted = (~finished) & (ne >= n_events)
    _warn_event_budget(exhausted, n_events, "simulate_ensemble")
    names = tuple(getattr(p, "name", type(p).__name__) for p in policies)
    return EnsembleResult(J=J, T=T, finished=finished, n_events=ne,
                          exhausted=exhausted, policy_names=names)


# ---------------------------------------------------------------------------
# Fluid class-aggregate executor (many-jobs limit, core/classes.py)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class FluidClassResult:
    """Outcome of the fluid class executor (host-materialized).

    T[c] is the exhaustion time of class c (0 for empty classes);
    J_jobs = Σ_c n⁰_c w_c T_c is the discrete objective under the
    all-jobs-finish-at-exhaustion convention — the quantity
    ``plan_classes`` optimizes; J_fluid = ∫ Σ_c w_c n_c(t) dt is the
    fluid-limit objective with the continuously draining count
    n_c(t) = R_c(t)/x_c (≤ J_jobs, since mass that drains early stops
    accruing weight).  events is the (t, Θ) trace of aggregate
    allocations per inter-event interval.
    """

    T: np.ndarray
    J_fluid: float
    J_jobs: float
    finished: bool
    events: list
    n_events: int


def _fluid_core(sp_agg, policy, R0, wx_ratio, W_agg, rtol, n_events):
    """Traced fluid event loop over class aggregates.

    Classes drain continuously: aggregate work R_c decreases at the
    aggregate rate S_c(Θ_c) with S_c frozen at the initial counts (the
    fluid limit holds the per-class speedup family fixed over a planning
    horizon; completions shrink the *mass*, not the family).  Between
    events allocations are constant, so the next event is the earliest
    class exhaustion — at most C non-trivial events.  The weighted-count
    integral over one interval is closed-form (n_c is affine in t):

        ∫ w_c n_c dt = (w_c/x_c) ∫ R_c(t) dt
                     = (w_c/x_c) (R_c·dt − S_c(Θ_c)·dt²/2).
    """
    dtype = R0.dtype
    C = R0.shape[0]
    real = R0 > 0
    eps = jnp.finfo(dtype).eps
    tol = jnp.maximum(rtol, 8.0 * eps) * jnp.maximum(
        1.0, jnp.max(R0, initial=0.0))
    zero = jnp.zeros((), dtype)

    def step(carry, _):
        t, R, T, Jf = carry
        active = real & (R > 0)
        theta = jnp.where(active, policy(R, W_agg, active), zero)
        rates = jnp.where(active, sp_agg.s(theta), zero)
        runnable = active & (rates > 0)
        dt_c = jnp.min(jnp.where(runnable,
                                 R / jnp.where(runnable, rates, 1.0),
                                 jnp.inf))
        live = jnp.isfinite(dt_c)
        dt = jnp.where(live, dt_c, 0.0)
        t_new = t + dt
        dJ = jnp.sum(jnp.where(active,
                               wx_ratio * (R * dt - rates * dt * dt / 2.0),
                               0.0))
        R2 = jnp.where(active, jnp.maximum(R - rates * dt, 0.0), R)
        done_now = active & (R2 <= tol)
        T = jnp.where(done_now, t_new, T)
        R2 = jnp.where(done_now, zero, R2)
        return (t_new, R2, T, Jf + dJ), (t, theta, live & active.any())

    carry0 = (zero, jnp.where(real, R0, 0.0), jnp.zeros((C,), dtype), zero)
    (_, R_end, T, Jf), (ts, thetas, valid) = lax.scan(
        step, carry0, None, length=n_events)
    finished = jnp.all(~real | (R_end <= 0))
    return T, Jf, finished, ts, thetas, valid


@partial(jax.jit, static_argnames=("n_events",))
def _fluid_jit(sp_agg, policy, R0, wx_ratio, W_agg, rtol, n_events):
    return _fluid_core(sp_agg, policy, R0, wx_ratio, W_agg, rtol, n_events)


def simulate_fluid_classes(state, policy, rtol: float = 1e-12,
                           max_events: int | None = None,
                           trace: bool = True) -> FluidClassResult:
    """Run a device-ready policy over class aggregates in the fluid limit.

    ``state`` is a ``core.classes.ClassState``; ``policy`` must be a
    jax-traceable ``(rem, w, active) → Θ`` pytree (``sched/policies.py``)
    invoked with *aggregate* remaining work and *aggregate* weights
    n_c·w_c — e.g. ``ClassSmartFillPolicy.from_classes(state)``.  Each
    event completes at least one class, so the default budget 2C+8 is
    ample.  Zero-count classes are inert (T = 0, never allocated).
    """
    from .classes import class_speedup

    counts = np.asarray(state.counts, dtype=np.float64)
    x = np.asarray(state.sizes, dtype=np.float64)
    w = np.asarray(state.weights, dtype=np.float64)
    C = counts.shape[0]
    if C == 0:
        return FluidClassResult(T=np.zeros(0), J_fluid=0.0, J_jobs=0.0,
                                finished=True, events=[], n_events=0)
    sp_agg = class_speedup(state.sp, jnp.asarray(counts))
    live = counts > 0
    R0 = jnp.asarray(np.where(live, counts * x, 0.0))
    W_agg = jnp.asarray(np.where(live, counts * w, 0.0))
    # guard the x=0 padding slots: R0 is 0 there, the ratio never used
    wx = jnp.asarray(np.where(live, w / np.where(x > 0, x, 1.0), 0.0))
    n_events = int(max_events or (2 * C + 8))
    T, Jf, finished, ts, thetas, valid = _fluid_jit(
        sp_agg, policy, R0, wx, W_agg, jnp.asarray(rtol, R0.dtype), n_events)
    T = np.asarray(T)
    J_jobs = float(np.sum(counts * w * T)) if bool(finished) else float("inf")
    mask = np.asarray(valid)
    events = []
    if trace:
        ts = np.asarray(ts)
        thetas = np.asarray(thetas)
        events = [(float(ts[i]), thetas[i].copy())
                  for i in np.flatnonzero(mask)]
    return FluidClassResult(
        T=T, J_fluid=float(Jf) if bool(finished) else float("inf"),
        J_jobs=J_jobs, finished=bool(finished), events=events,
        n_events=int(mask.sum()))


# ---------------------------------------------------------------------------
# Host reference loop (the pre-engine implementation) — the differential
# oracle for the device engine.  Arrival events use the same semantics.
# ---------------------------------------------------------------------------

def simulate_policy_reference(sp, x, w, policy, B: float | None = None,
                              arrival=None, rtol: float = 1e-12,
                              max_events: int | None = None,
                              faults: FaultTrace | None = None):
    """Numpy event loop oracle; exact same event semantics as the engine.

    policy(rem, w, active) → (M,) allocations with Σ over active ≤ B.
    Raises on budget violations, deadlock and event-budget exhaustion —
    host-side checks the device engine cannot afford.

    With ``faults`` the oracle mirrors the fault-aware device step
    exactly — current-budget policy invocation (4-argument form),
    completion-before-fault ordering, one fault per event, faults alone
    are not work — so it stays the differential pin for the faulted
    engine.  The runtime budget check then tracks B(t).
    """
    x = np.asarray(x, dtype=np.float64)
    w = np.asarray(w, dtype=np.float64)
    _validate_workload(x, w, arrival, what="simulate_policy_reference")
    _validate_budget(B, "simulate_policy_reference")
    M = x.shape[0]
    if faults is None:
        Bcur = float(getattr(sp, "B", 0.0) if B is None else B)
    else:
        Bcur = float(_fault_B0(policy, B, "simulate_policy_reference"))
    real = x > 0
    arr = (np.zeros(M) if arrival is None
           else np.asarray(arrival, dtype=np.float64))
    rem = np.where(real, x, 0.0)
    T = np.zeros(M)
    mult = np.ones(M)
    t = 0.0
    events = []
    if faults is not None:
        faults.validate(M)
        if faults.batched:
            raise ValueError("the reference oracle runs one instance — "
                             "pass faults.instance(k)")
        ftimes, fkinds, fjobs, fvalues = (faults.times, faults.kinds,
                                          faults.jobs, faults.values)
        fi, S = 0, faults.S
        limit = max_events or _fault_n_events(M, S)
    else:
        fi, S = 0, 0
        limit = max_events or n_events_for(M)
    # same tolerance formula as the device engine (float64 host side)
    tol = max(rtol, 8.0 * np.finfo(np.float64).eps) * max(
        1.0, float(x.max()) if M else 1.0)

    for _ in range(limit):
        arrived = real & (arr <= t)
        active = arrived & (rem > 0)
        pending = real & ~arrived
        if not active.any() and not pending.any():
            return SimResult(T=T, J=float(np.sum(w * T)), events=events,
                             n_events=len(events))
        if faults is None:
            raw = policy(rem, w, active)
        else:
            raw = policy(rem, w, active, Bcur)
        theta = np.where(active, np.asarray(raw, dtype=np.float64), 0.0)
        if theta[active].sum() > Bcur * (1 + 1e-9):
            raise ValueError("policy exceeded bandwidth budget")
        rates = np.where(active,
                         np.array(sp.s(theta), dtype=np.float64) * mult, 0.0)
        runnable = active & (rates > 0)
        t_fault = float(ftimes[fi]) if fi < S else np.inf
        if not runnable.any() and not pending.any() \
                and not np.isfinite(t_fault):
            raise RuntimeError("deadlock: no active job has positive rate")
        dt_c = (float(np.min(rem[runnable] / rates[runnable]))
                if runnable.any() else np.inf)
        t_arr = float(np.min(arr[pending])) if pending.any() else np.inf
        t_next = min(t + dt_c, t_arr, t_fault)
        events.append((t, theta.copy()))
        dt = t_next - t
        t = t_next
        rem = np.where(active, rem - rates * dt, rem)
        done = active & (rem <= tol)
        T[done] = t
        rem[done] = 0.0
        if faults is not None and t_fault <= t:
            k, j, v = int(fkinds[fi]), int(fjobs[fi]), float(fvalues[fi])
            if k == KIND_BUDGET:
                Bcur = v
            elif k == KIND_FAILURE:
                # completions above resolved first: rem[j] == 0 ⇒ no-op
                if real[j] and arr[j] <= t and rem[j] > 0:
                    rem[j] = min(rem[j] + v * (x[j] - rem[j]), x[j])
            elif k == KIND_STRAGGLER:
                mult[j] = v
            fi += 1
    raise RuntimeError(f"exceeded {limit} events — policy may not complete jobs")


# ---------------------------------------------------------------------------
# Host policy wrappers (legacy; dispatched to the reference loop)
# ---------------------------------------------------------------------------

def schedule_policy(schedule):
    """Wrap a precomputed SmartFillSchedule as a re-planning policy.

    Looks up the phase by the number of remaining jobs (Prop. 7: the
    allocation depends only on the active set) — executing it through the
    simulator independently validates durations/T/J.
    """
    theta = np.asarray(schedule.theta, dtype=np.float64)

    def policy(rem, w, active):
        k = int(np.sum(active))         # phase k−1 has jobs 0..k−1 active
        out = np.zeros_like(np.asarray(rem, dtype=np.float64))
        idx = np.flatnonzero(active)
        # jobs complete in SJF order ⇒ active set is the k largest = 0..k−1
        out[idx] = theta[: k, k - 1][: idx.size]
        return out

    return policy


def smartfill_sim_policy(sp, B: float | None = None):
    """Re-planning SmartFill policy (time-consistency check).

    At every event, re-run SmartFill on the remaining sizes.  For the
    OPT setting this must reproduce the one-shot schedule's J.
    (Host-side; the device-resident equivalent is
    ``sched.policies.SmartFillPolicy``.)
    """
    from .smartfill import smartfill_allocations

    def policy(rem, w, active):
        rem = np.asarray(rem, dtype=np.float64)
        w = np.asarray(w, dtype=np.float64)
        out = np.zeros_like(rem)
        idx = np.flatnonzero(active)
        if idx.size == 0:
            return out
        order = idx[np.lexsort((w[idx], -rem[idx]))]
        th = smartfill_allocations(sp, rem[order], w[order], B=B)
        out[order] = np.asarray(th)
        return out

    return policy
