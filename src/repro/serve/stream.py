"""Streaming control plane: open-arrival online service.

The scenario engine simulates *closed* instances on a fixed event
horizon (4M+16); a production service faces an unbounded arrival
stream.  ``StreamController`` services one (``core.workloads.
ArrivalStream``) as a host-driven loop over **arrival windows** — the
spans between consecutive control-plane events (arrivals, budget steps,
end of trace) — with carried state: remaining sizes, the live slot
mask, the live budget B(t), and the planner's warm-start payload
(completion order + λ-bracket).

Inside a window nothing changes that the plan did not anticipate, so
execution is one jitted fixed-shape ``lax.scan`` (``_exec_window``):
each step looks up the active-count column of the current plan table,
advances to the earlier of the next completion and the window end, and
retires completed rows — at most M completions plus a final advance,
so M+1 steps regardless of the window length.  The host loop between
windows is the control plane proper:

  * **Warm-started replanning** — every event hands the live state to a
    ``StreamingSmartFillPolicy``, which reuses the previous plan's
    completion order and λ payload and falls back to a cold solve when
    the bracket-validation probe or the J == J_linear certificate
    fails (see ``sched.policies``).

  * **Double-buffered plans** (``PlanBuffer``) — the executor always
    reads the *front* plan; a freshly solved plan is published to the
    back buffer with the solve's latency and promoted at the first
    window boundary past its ready time.  Admission therefore never
    blocks on an in-flight solve: the stream keeps executing the stale
    front plan (allocations stay feasible — the table is
    active-count-indexed), and jobs admitted meanwhile simply idle
    until the next plan covers them.

  * **Certified degradation** — a replan that fails certification (or
    raises) does not reach the executor: the controller counts a
    degraded window and swaps in a ``robust.ladder_plan_table`` built
    from the degradation ladder (SmartFill → GWF-static → EQUI, each
    column certificate-gated), exactly the PR-8 contract that solver
    failures are absorbed, never executed.

  * **Watchdog-wrapped admission** — an optional ``AdmissionController``
    (which must run in ``agreeable="rank"`` mode: live half-served
    state is non-agreeable by construction) scores each arrival's
    marginal ΔJ against the live set; its watchdog degrades to
    deny-all rather than stalling the loop.

SLO metrics follow the heSRPT-slowdown line of work (Berg et al.,
arXiv:1903.09346; slowdown variant arXiv:2011.09676): alongside the
paper's weighted J (= weighted flow time here) the result reports mean
slowdown (flow time over the job's hypothetical solo service time
x/s(B)), p50/p99 latency, and deadline misses.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.smartfill import _fast_ok
from repro.core.speedup import Speedup, collapse_homogeneous, is_per_job
from repro.core.workloads import ArrivalStream
from repro.robust.degrade import DegradingPolicy, ladder_plan_table
from repro.sched.policies import (StreamCascadePolicy,
                                  StreamingSmartFillPolicy, StreamPlan,
                                  stream_replan_core, stream_warm0)

__all__ = ["StreamMetrics", "StreamResult", "PlanBuffer",
           "StreamController", "StreamCascadePolicy"]


# ---------------------------------------------------------------------------
# Window executor: one jitted scan per arrival window
# ---------------------------------------------------------------------------

def _rate_floor(dtype):
    """Smallest admissible completion-rate denominator for ``dtype``.

    The old literal floor ``1e-300`` is fine under f64 but *flushes to
    zero* when cast to f32 (``np.float32(1e-300) == 0.0``), leaving the
    division unprotected exactly where it matters: a live row whose
    rate lands in the f32 denormal range (or is flushed to 0 on
    flush-to-zero accelerator hardware) divides by a denormal/zero and
    the step width goes inf.  Same shape as the PR-3 ``_mu_floor`` fix:
    tiny/eps is the smallest *normal*-scaled floor (≈9.9e-32 f32,
    ≈1e-292 f64), far below any physical rate, so dt stays finite
    without perturbing healthy windows.
    """
    fi = jnp.finfo(dtype)
    return jnp.asarray(fi.tiny, dtype) / jnp.asarray(fi.eps, dtype)


@jax.jit
def _exec_window(sp, table, rem0, live0, span, rtol):
    """Advance the live rows ``span`` time under ``table`` (row coords).

    Fixed-shape ``lax.scan`` over M+1 steps (at most M completions plus
    one final advance; exhausted windows step with h = 0).  Each step:

      * the live count m selects column m−1 of the plan table, whose
        first m entries are assigned to the live rows *by rank* — for a
        prefix live set (the normal case: completions retire the last
        row first) this is the identity, and for the non-prefix sets a
        stale double-buffered plan can produce it degrades gracefully
        (rank r reads the allocation planned for rank r);
      * rates are s(θ) under the (shared) server speedup, the step
        advances to min(next completion, window end), and rows whose
        remaining size falls below the completion tolerance retire.

    Returns ``(rem_end, live_end, comp)`` with ``comp[i]`` the
    completion offset from the window start (+inf where row i survived).
    """
    M = rem0.shape[0]
    dtype = rem0.dtype
    idx = jnp.arange(M)
    tol = (jnp.maximum(jnp.asarray(rtol, dtype),
                       8.0 * jnp.finfo(dtype).eps)
           * jnp.maximum(1.0, jnp.max(rem0)))
    inf = jnp.asarray(jnp.inf, dtype)

    def step(carry, _):
        rem, live, left, elapsed, comp = carry
        m = jnp.sum(live)
        colm = jnp.take(table, jnp.clip(m - 1, 0, M - 1), axis=1)
        rank = jnp.clip(jnp.cumsum(live) - 1, 0, M - 1)
        th = jnp.where(live, jnp.take(colm, rank), 0.0)
        rate = jnp.where(live, sp.s(th), 0.0)
        dt = jnp.where(live & (rate > 0),
                       rem / jnp.maximum(rate, _rate_floor(dtype)), inf)
        h = jnp.minimum(jnp.min(dt), left)
        h = jnp.maximum(h, 0.0)
        rem2 = jnp.where(live, jnp.maximum(rem - rate * h, 0.0), rem)
        done = live & (rem2 <= tol)
        comp = jnp.where(done, elapsed + h, comp)
        return (jnp.where(done, 0.0, rem2), live & ~done, left - h,
                elapsed + h, comp), None

    carry0 = (rem0, live0, jnp.asarray(span, dtype),
              jnp.zeros((), dtype), jnp.full((M,), jnp.inf, dtype))
    (rem, live, _, _, comp), _ = jax.lax.scan(
        step, carry0, None, length=M + 1)
    return rem, live, comp


# ---------------------------------------------------------------------------
# Results
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class StreamMetrics:
    """SLO summary of one stream run (completed jobs only, except the
    deadline counters, which charge unfinished past-deadline jobs too)."""

    n_arrivals: int
    n_admitted: int
    n_rejected: int
    n_completed: int
    weighted_J: float          # Σ w_i (C_i − a_i): weighted flow time
    mean_flow: float
    mean_slowdown: float       # (C_i − a_i) / (x_i / s(B)), averaged
    p50_latency: float
    p99_latency: float
    deadline_misses: int
    deadline_total: int


@dataclasses.dataclass(frozen=True)
class StreamResult:
    """Full outcome of ``StreamController.run`` (host-materialized).

    Per-job arrays are stream-indexed (length N = len(stream));
    ``completion`` is +inf for jobs still live (or rejected) at the
    horizon.  ``replans``/``warm_replans``/``cold_replans`` count
    planner invocations; ``degraded_windows`` counts windows executed
    on the ladder fallback table; ``n_events`` counts control-plane
    events (windows), not engine steps.
    """

    metrics: StreamMetrics
    completion: np.ndarray
    latency: np.ndarray
    slowdown: np.ndarray
    admitted: np.ndarray
    replans: int
    warm_replans: int
    cold_replans: int
    degraded_windows: int
    n_events: int


# ---------------------------------------------------------------------------
# Double-buffered plans
# ---------------------------------------------------------------------------

class PlanBuffer:
    """Front/back plan pair: the executor reads ``front``; ``publish``
    stages a new plan behind a ready time, ``poll`` promotes it once the
    stream clock passes that time.  This models the in-flight solve of
    a real control plane in a single-threaded loop: admission and
    execution proceed against the stale front plan while the "solver"
    (ready-time delay) runs — they never block on it.  Promotion
    happens at window boundaries (the executor holds one table per
    window by construction)."""

    def __init__(self):
        self.front: StreamPlan | None = None
        self.back: tuple[float, StreamPlan] | None = None
        self.swaps = 0

    def publish(self, plan: StreamPlan, ready_at: float = -np.inf) -> None:
        self.back = (float(ready_at), plan)

    def poll(self, now: float) -> StreamPlan | None:
        if self.back is not None and now >= self.back[0]:
            self.front = self.back[1]
            self.back = None
            self.swaps += 1
        return self.front


# ---------------------------------------------------------------------------
# The controller
# ---------------------------------------------------------------------------

class StreamController:
    """Online service loop over an ``ArrivalStream`` (module docstring).

    Args:
      sp: *shared* server speedup (job-indexed leaves are rejected —
        slots are reused across jobs, so per-slot leaves would silently
        reassign speedups; per-job heterogeneous replanning is
        ``StreamingSmartFillPolicy``'s direct API).
      B: nominal budget (defaults to sp.B); budget events in the trace
        override it live.
      max_live: slot capacity M — the padded width every replanning
        solve and window execution runs at (no recompilation as the
        live count breathes).  Arrivals beyond capacity queue FIFO.
      policy: the incremental re-planner; defaults to a
        ``StreamingSmartFillPolicy(sp, B)``.
      admission: optional ``AdmissionController`` in ``agreeable="rank"``
        mode; scores every arrival against the live set, deny ⇒ the job
        is rejected (never queued).  Its watchdog semantics apply.
      ladder: certificate-gated fallback for failed replans; defaults to
        the canonical ``DegradingPolicy.ladder(sp, B)``.
      plan_latency: simulated solve latency — a replanned table becomes
        visible to the executor only ``plan_latency`` after its event
        (double buffering; 0 ⇒ plans land instantly).
      rtol: completion tolerance of the window executor.
    """

    def __init__(self, sp: Speedup, B: float | None = None, *,
                 max_live: int = 16,
                 policy: StreamingSmartFillPolicy | None = None,
                 admission=None, ladder: DegradingPolicy | None = None,
                 plan_latency: float = 0.0, rtol: float = 1e-12):
        sp = collapse_homogeneous(sp)
        if is_per_job(sp):
            raise ValueError(
                "StreamController needs a shared speedup; per-job "
                "streams replan through StreamingSmartFillPolicy "
                "directly")
        self.sp = sp
        self.B = float(sp.B if B is None else B)
        self.M = int(max_live)
        if self.M < 1:
            raise ValueError("max_live must be >= 1")
        self.policy = (StreamingSmartFillPolicy(sp, self.B)
                       if policy is None else policy)
        if admission is not None and admission.agreeable != "rank":
            raise ValueError(
                "stream admission must use agreeable='rank': live "
                "half-served state is non-agreeable by construction")
        self.admission = admission
        self.ladder = (DegradingPolicy.ladder(sp, B=self.B)
                       if ladder is None else ladder)
        self.plan_latency = float(plan_latency)
        self.rtol = float(rtol)

    # -- internals --------------------------------------------------------

    def _admit(self, xj, wj, rem, wslot, active) -> bool:
        """Score one arrival against the live set (deny ⇒ reject)."""
        if self.admission is None:
            return True
        dec = self.admission.evaluate(
            rem[active], wslot[active], np.asarray([xj]), np.asarray([wj]))
        # watchdog exhaustion fails closed (deny-all, status degraded)
        return bool(dec.admit[0])

    def _replan(self, t, rem, w, active, B_live, buffer) -> tuple[int, int]:
        """Solve on the live state; publish certified plans, fall down
        the ladder otherwise.  Returns (degraded, replanned) counts."""
        try:
            plan = self.policy.plan(rem, w, active, B=B_live)
            failed = not plan.certified
        except jax.errors.JaxRuntimeError:
            raise           # a device or compile fault is not a bad plan
        except (FloatingPointError, ValueError, RuntimeError):
            plan, failed = None, True
        if not failed:
            buffer.publish(plan, ready_at=t + self.plan_latency)
            return 0, 1
        # ladder fallback: certificate-gated columns on the *current*
        # SJF ranking — published instantly (the emergency plan must
        # not sit behind a solve latency)
        order = np.where(active)[0][np.argsort(-rem[active], kind="stable")]
        m = order.size
        rem_rows = np.zeros(self.M)
        w_rows = np.zeros(self.M)
        rem_rows[:m] = rem[order]
        w_rows[:m] = w[order]
        table = ladder_plan_table(self.ladder, rem_rows, w_rows, B=B_live)
        buffer.publish(StreamPlan(
            order=order, table=table, J=float("nan"), J_linear=float("nan"),
            m=m, B=B_live, warm=False, certified=False))
        return 1, 1

    def _execute(self, plan, t0, t1, rem, w, active, job_of_slot,
                 completion, cut_after_completion=False) -> float:
        """Run [t0, t1) under ``plan``; mutate slot state in place.

        With ``cut_after_completion`` the segment stops at the first
        completion instead of running to t1 (the controller uses this
        when jobs are queued: a freed slot must be backfilled and
        replanned *at the completion time*, not at the next event).
        Returns the time actually reached (t1, or the cut time).
        """
        M = self.M
        order = np.asarray(plan.order, np.int64)
        k = order.size
        rows = np.full(M, -1, np.int64)
        rows[:k] = order
        live = np.zeros(M, bool)
        live[:k] = active[order] & (rem[order] > 0)
        rem_rows = np.zeros(M)
        rem_rows[:k] = rem[order]
        table = jnp.asarray(plan.table, jnp.result_type(float))
        rem_j = jnp.asarray(rem_rows)
        live_j = jnp.asarray(live)
        rem_end, live_end, comp = _exec_window(
            self.sp, table, rem_j, live_j, t1 - t0, self.rtol)
        comp = np.asarray(comp)
        t_end = t1
        if cut_after_completion and np.isfinite(comp).any():
            c0 = float(np.min(comp[np.isfinite(comp)]))
            if t0 + c0 < t1:
                t_end = t0 + c0
                rem_end, live_end, comp = _exec_window(
                    self.sp, table, rem_j, live_j, c0, self.rtol)
                comp = np.asarray(comp)
        rem_end = np.asarray(rem_end)
        freed = []
        for r in range(k):
            s = rows[r]
            if not live[r]:
                continue
            rem[s] = rem_end[r]
            if np.isfinite(comp[r]):
                completion[job_of_slot[s]] = t0 + comp[r]
                active[s] = False
                job_of_slot[s] = -1
                rem[s] = 0.0
                freed.append(s)
        if freed:
            # drop the freed slots from the planner's carried order NOW:
            # a queued job may recycle the slot before the next replan,
            # and it must enter the order as an arrival, not inherit the
            # completed job's position
            self.policy.release(np.asarray(freed))
        return t_end

    # -- interface --------------------------------------------------------

    def run(self, stream: ArrivalStream) -> StreamResult:
        """Service the whole trace; see the module docstring."""
        N = len(stream)
        M = self.M
        x_all = np.asarray(stream.x, float)
        w_all = np.asarray(stream.w, float)
        t_all = np.asarray(stream.t, float)

        # merged control-plane events: (time, kind, payload), stable in
        # time with arrivals before budget steps at ties
        events = [(t_all[j], 0, j) for j in range(N)]
        events += [(float(bt), 1, float(bv)) for bt, bv in
                   zip(stream.budget_times, stream.budget_values)]
        events.sort(key=lambda e: (e[0], e[1]))
        events.append((float(stream.horizon), 2, 0.0))

        rem = np.zeros(M)
        wslot = np.zeros(M)
        active = np.zeros(M, bool)
        job_of_slot = np.full(M, -1, np.int64)
        completion = np.full(N, np.inf)
        admitted = np.zeros(N, bool)
        queue: list[int] = []

        buffer = PlanBuffer()
        self.policy.reset()
        B_live = self.B
        t_prev = 0.0
        degraded = 0
        replans = 0
        n_windows = 0

        def fill_free_slots() -> bool:
            """Queued jobs into free slots (FIFO); True if any landed."""
            landed = False
            while queue and not active.all():
                j = queue.pop(0)
                s = int(np.flatnonzero(~active)[0])
                rem[s] = x_all[j]
                wslot[s] = w_all[j]
                active[s] = True
                job_of_slot[s] = j
                landed = True
            return landed

        for t_ev, kind, payload in events:
            # 1. execute up to this event on the front plan, splitting
            # the window (a) where a back-buffered plan comes ready, so
            # an in-flight solve lands mid-window instead of waiting for
            # the next control-plane event, and (b) at completions while
            # jobs are queued, so freed slots backfill at the completion
            # time rather than idling until the next arrival
            t_cur = t_prev
            while t_cur < t_ev:
                plan = buffer.poll(t_cur)
                t_stop = t_ev
                if buffer.back is not None and buffer.back[0] < t_ev:
                    t_stop = buffer.back[0]   # > t_cur: poll() promoted
                if plan is None or not active.any():
                    t_cur = t_stop
                    continue
                t_end = self._execute(plan, t_cur, t_stop, rem, wslot,
                                      active, job_of_slot, completion,
                                      cut_after_completion=bool(queue))
                n_windows += 1
                if t_end < t_stop and fill_free_slots():
                    d, r = self._replan(t_end, rem, wslot, active,
                                        B_live, buffer)
                    degraded += d
                    replans += r
                t_cur = t_end
            buffer.poll(t_ev)
            changed = fill_free_slots()
            # 2. apply the event
            if kind == 0:
                j = int(payload)
                if self._admit(x_all[j], w_all[j], rem, wslot, active):
                    admitted[j] = True
                    queue.append(j)
                    changed = fill_free_slots() or True
            elif kind == 1:
                changed = True
                B_live = float(payload)
            else:                                   # end of trace
                break
            # 3. replan on the new state (double-buffered)
            if changed or buffer.front is None:
                d, r = self._replan(t_ev, rem, wslot, active, B_live,
                                    buffer)
                degraded += d
                replans += r
            t_prev = t_ev

        return self._finalize(stream, completion, admitted,
                              replans=replans,
                              warm_replans=self.policy.warm_replans,
                              cold_replans=self.policy.cold_replans,
                              degraded=degraded, n_windows=n_windows)

    def _finalize(self, stream, completion, admitted, *, replans,
                  warm_replans, cold_replans, degraded,
                  n_windows) -> StreamResult:
        """SLO metrics from a completion array — shared verbatim by the
        host loop and the device scan so the two paths are compared on
        identical formulas."""
        N = len(stream)
        x_all = np.asarray(stream.x, float)
        w_all = np.asarray(stream.w, float)
        t_all = np.asarray(stream.t, float)
        lat = completion - t_all
        solo = x_all / max(float(self.sp.s(jnp.asarray(self.B))), 1e-300)
        slow = lat / np.maximum(solo, 1e-300)
        done = np.isfinite(completion)
        fin = lat[done]
        dl = np.asarray(stream.deadline, float)
        has_dl = np.isfinite(dl) & admitted
        misses = int(np.sum(has_dl & (completion > dl)))
        metrics = StreamMetrics(
            n_arrivals=N,
            n_admitted=int(admitted.sum()),
            n_rejected=int(N - admitted.sum()),
            n_completed=int(done.sum()),
            weighted_J=float(np.sum(w_all[done] * fin)),
            mean_flow=float(fin.mean()) if fin.size else 0.0,
            mean_slowdown=float(slow[done].mean()) if fin.size else 0.0,
            p50_latency=float(np.percentile(fin, 50)) if fin.size else 0.0,
            p99_latency=float(np.percentile(fin, 99)) if fin.size else 0.0,
            deadline_misses=misses,
            deadline_total=int(has_dl.sum()),
        )
        return StreamResult(
            metrics=metrics, completion=completion, latency=lat,
            slowdown=slow, admitted=admitted, replans=replans,
            warm_replans=warm_replans, cold_replans=cold_replans,
            degraded_windows=degraded, n_events=n_windows)

    def run_device(self, stream: ArrivalStream, *,
                   chunk_events: int | None = None) -> StreamResult:
        """Service the whole trace on device: one ``lax.scan`` over
        control-plane events instead of one host round-trip per window.

        Same contract as ``run`` modulo the replanning policy: the
        device path replans through the traced ``stream_replan_core``
        cascade (fresh hinted solve → certificate → exchange search →
        ladder, all real ``lax.cond`` branches), with the ``WarmStart``
        λ/bracket payload, the ``PlanBuffer`` front/back pair, the FIFO
        queue and the slot state all living in the scan carry — the
        host syncs once per ``chunk_events`` chunk (default: once for
        the whole trace).  ``StreamController.run`` with a
        ``StreamCascadePolicy`` makes the *same* decisions through the
        host loop and is this path's differential oracle.

        Admission must be None (device arrivals are all admitted) —
        scoring arrivals against the live set is host control-plane
        logic that has no traced form here.  Cascade knobs (certificate
        rtol, solver sizes, search budget) are read off ``self.policy``
        when present so an oracle/device pair is configured once.
        """
        if self.admission is not None:
            raise ValueError(
                "run_device supports admission=None only; scored "
                "admission stays on the host loop")
        N = len(stream)
        M = self.M
        dtype = jnp.result_type(float)
        p = self.policy
        knobs = dict(
            cert_rtol=float(getattr(p, "certificate_rtol", 1e-8)),
            coarse=int(getattr(p, "coarse", 32)),
            descent_iters=int(getattr(p, "descent_iters", 40)),
            cap_iters=int(getattr(p, "cap_iters", 64)),
            stol_rel=getattr(p, "stol_rel", None),
            search_steps=(4 * M
                          if getattr(p, "search_steps", None) is None
                          else int(p.search_steps)),
            fast=_fast_ok(self.sp),
        )
        t_e, kind, pi, pf = _event_arrays(stream)
        E = t_e.size
        W = E if chunk_events is None else max(int(chunk_events), 1)
        n_chunks = -(-E // W)
        pad = n_chunks * W - E
        if pad:
            t_e = np.concatenate([t_e, np.zeros(pad)])
            kind = np.concatenate([kind, np.zeros(pad, np.int32)])
            pi = np.concatenate([pi, np.zeros(pad, np.int32)])
            pf = np.concatenate([pf, np.zeros(pad)])
        x_all = jnp.asarray(np.asarray(stream.x, float), dtype)
        w_all = jnp.asarray(np.asarray(stream.w, float), dtype)
        state = _stream_state0(M, N, self.B, dtype)
        for c in range(n_chunks):
            ev = tuple(jnp.asarray(a[c * W:(c + 1) * W])
                       for a in (t_e, kind, pi, pf))
            state = _stream_chunk(
                self.sp, self.ladder, state, ev, x_all, w_all,
                jnp.asarray(self.B, dtype),
                jnp.asarray(self.plan_latency, dtype),
                jnp.asarray(self.rtol, dtype),
                jnp.asarray(knobs["cert_rtol"], dtype),
                fast=knobs["fast"], coarse=knobs["coarse"],
                descent_iters=knobs["descent_iters"],
                cap_iters=knobs["cap_iters"],
                stol_rel=knobs["stol_rel"],
                search_steps=knobs["search_steps"])
        completion = np.asarray(state["completion"][:N], float)
        admitted = np.ones(N, bool)
        return self._finalize(
            stream, completion, admitted,
            replans=int(state["replans"]),
            warm_replans=int(state["warm_ct"]),
            cold_replans=int(state["cold_ct"]),
            degraded=int(state["degraded"]),
            n_windows=int(state["n_windows"]))


# ---------------------------------------------------------------------------
# Device-resident event scan
# ---------------------------------------------------------------------------
#
# The host loop above is the differential oracle; everything below is
# the same control plane as pure traced code.  Event kinds are encoded
# so an all-zero row is *inert* — the fleet driver's padding contract
# (distributed/fleet.py) then works unchanged for padded tenants:
#
#   0 = pad (no-op), 1 = arrival (pi = job index), 2 = budget step
#   (pf = new budget), 3 = end of trace.
#
# One scan step = one control-plane event: execute up to the event on
# the front plan (splitting windows where a back-buffered plan comes
# ready and at completions while jobs are queued — the
# cut_at_first_completion backfill, lowered into the scan as a
# lax.cond around a re-run of the same `_exec_window` scan the host
# calls), then apply the event and replan through the traced cascade.

def _event_arrays(stream: ArrivalStream):
    """Merged device event arrays, ordered exactly like the host loop
    (time-stable, arrivals before budget steps at ties, end last)."""
    N = len(stream)
    t_all = np.asarray(stream.t, float)
    ev = [(float(t_all[j]), 0, j, 0.0) for j in range(N)]
    ev += [(float(bt), 1, 0, float(bv)) for bt, bv in
           zip(stream.budget_times, stream.budget_values)]
    ev.sort(key=lambda e: (e[0], e[1]))
    t_e = np.array([e[0] for e in ev] + [float(stream.horizon)], float)
    kind = np.array([1 if e[1] == 0 else 2 for e in ev] + [3], np.int32)
    pi = np.array([e[2] for e in ev] + [0], np.int32)
    pf = np.array([e[3] for e in ev] + [0.0], float)
    return t_e, kind, pi, pf


def _stream_state0(M: int, N: int, B: float, dtype) -> dict:
    """Initial scan carry: empty slots, no plans, cold warm payload."""
    n = max(N, 1)
    i32 = jnp.int32
    return {
        "t": jnp.zeros((), dtype),
        "rem": jnp.zeros((M,), dtype),
        "wslot": jnp.zeros((M,), dtype),
        "active": jnp.zeros((M,), bool),
        "jos": jnp.full((M,), -1, i32),
        "B_live": jnp.asarray(B, dtype),
        "order": jnp.arange(M, dtype=i32),
        "table": jnp.zeros((M, M), dtype),
        "m_front": jnp.zeros((), i32),
        "has_front": jnp.zeros((), bool),
        "border": jnp.arange(M, dtype=i32),
        "btable": jnp.zeros((M, M), dtype),
        "m_back": jnp.zeros((), i32),
        "bready": jnp.asarray(-jnp.inf, dtype),
        "has_back": jnp.zeros((), bool),
        "qbuf": jnp.zeros((n,), i32),
        "qhead": jnp.zeros((), i32),
        "qtail": jnp.zeros((), i32),
        "completion": jnp.full((n,), jnp.inf, dtype),
        "warm": stream_warm0(M, dtype),
        "n_windows": jnp.zeros((), i32),
        "replans": jnp.zeros((), i32),
        "degraded": jnp.zeros((), i32),
        "warm_ct": jnp.zeros((), i32),
        "cold_ct": jnp.zeros((), i32),
        "searches": jnp.zeros((), i32),
    }


def _promote(s: dict, now) -> dict:
    """PlanBuffer.poll as traced state: back → front once ready."""
    s = dict(s)
    go = s["has_back"] & (now >= s["bready"])
    s["order"] = jnp.where(go, s["border"], s["order"])
    s["table"] = jnp.where(go, s["btable"], s["table"])
    s["m_front"] = jnp.where(go, s["m_back"], s["m_front"])
    s["has_front"] = s["has_front"] | go
    s["has_back"] = s["has_back"] & ~go
    return s


def _fill_slots(s: dict, x_all, w_all) -> dict:
    """Queued jobs into free slots, FIFO, lowest slot first — the
    host loop's fill_free_slots as a while_loop."""
    def pending(st):
        return (st["qtail"] > st["qhead"]) & ~jnp.all(st["active"])

    def land(st):
        st = dict(st)
        j = st["qbuf"][st["qhead"]]
        slot = jnp.argmin(st["active"])        # first free slot
        st["rem"] = st["rem"].at[slot].set(x_all[j])
        st["wslot"] = st["wslot"].at[slot].set(w_all[j])
        st["active"] = st["active"].at[slot].set(True)
        st["jos"] = st["jos"].at[slot].set(j)
        st["qhead"] = st["qhead"] + 1
        return st

    return jax.lax.while_loop(pending, land, s)


def _replan_dev(s: dict, t_now, sp, ladder, B_key, plan_latency,
                cert_rtol, knobs) -> dict:
    """Traced _replan: cascade solve, publish to the back buffer
    (certified plans behind the solve latency, the ladder instantly)."""
    s = dict(s)
    order, table, m, certified, searched, _, _, warm2 = (
        stream_replan_core(sp, ladder, s["rem"], s["wslot"], s["active"],
                           s["B_live"], B_key, s["warm"], cert_rtol,
                           **knobs))
    s["border"] = order
    s["btable"] = table
    s["m_back"] = m
    s["bready"] = jnp.where(certified, t_now + plan_latency,
                            -jnp.inf).astype(s["bready"].dtype)
    s["has_back"] = jnp.ones((), bool)
    s["warm"] = warm2
    one = jnp.ones((), s["replans"].dtype)
    zero = jnp.zeros((), s["replans"].dtype)
    s["replans"] = s["replans"] + one
    s["degraded"] = s["degraded"] + jnp.where(certified, zero, one)
    s["warm_ct"] = s["warm_ct"] + jnp.where(certified & ~searched,
                                            one, zero)
    s["cold_ct"] = s["cold_ct"] + jnp.where(searched | ~certified,
                                            one, zero)
    s["searches"] = s["searches"] + jnp.where(searched, one, zero)
    return s


def _exec_until(s: dict, t_ev, sp, ladder, x_all, w_all, B_key,
                plan_latency, rtol, cert_rtol, knobs) -> dict:
    """Execute up to ``t_ev`` on the front plan — the host loop's inner
    ``while t_cur < t_ev`` with its two window splits: (a) where a
    back-buffered plan comes ready, (b) at the first completion while
    jobs are queued (backfill + replan at the completion time)."""
    M = s["rem"].shape[0]
    N = s["completion"].shape[0]
    idx = jnp.arange(M)

    def behind(st):
        return st["t"] < t_ev

    def window(st):
        st = _promote(st, st["t"])
        t0 = st["t"]
        t_stop = jnp.where(st["has_back"] & (st["bready"] < t_ev),
                           st["bready"], t_ev)
        run = st["has_front"] & jnp.any(st["active"])
        rows = st["order"]
        cov = idx < st["m_front"]
        rem_rows = jnp.where(cov, st["rem"][rows], 0.0)
        live0 = cov & st["active"][rows] & (rem_rows > 0) & run
        queued = st["qtail"] > st["qhead"]
        rem_e, live_e, comp = _exec_window(
            sp, st["table"], rem_rows, live0, t_stop - t0, rtol)
        # cut_at_first_completion, exactly the host algorithm: if jobs
        # are queued and the first completion lands strictly inside the
        # window, re-run the same scan on the shorter span (bitwise the
        # host's second _exec_window call, inlined instead of
        # re-dispatched)
        c0 = jnp.min(jnp.where(jnp.isfinite(comp), comp, jnp.inf))
        do_cut = queued & jnp.isfinite(c0) & (t0 + c0 < t_stop)
        rem_e, live_e, comp = jax.lax.cond(
            do_cut,
            lambda _: _exec_window(sp, st["table"], rem_rows, live0,
                                   c0, rtol),
            lambda _: (rem_e, live_e, comp), None)
        t_end = jnp.where(do_cut, t0 + c0, t_stop)
        # scatter the window result back to slot coords and retire
        newly = live0 & ~live_e
        jobs_r = st["jos"][rows]
        st = dict(st)
        st["rem"] = st["rem"].at[rows].set(
            jnp.where(live0, rem_e, st["rem"][rows]))
        cjob = jnp.where(newly, jobs_r, N)     # sentinel → dropped
        st["completion"] = st["completion"].at[cjob].set(
            t0 + comp, mode="drop")
        st["active"] = st["active"].at[rows].set(
            jnp.where(newly, False, st["active"][rows]))
        st["jos"] = st["jos"].at[rows].set(
            jnp.where(newly, -1, jobs_r))
        st["n_windows"] = st["n_windows"] + run.astype(
            st["n_windows"].dtype)
        st["t"] = t_end
        # backfill freed slots and replan at the cut time (the host's
        # "if t_end < t_stop and fill_free_slots()" branch)
        refill = do_cut & jnp.any(newly) & queued
        st = jax.lax.cond(
            refill,
            lambda u: _replan_dev(_fill_slots(u, x_all, w_all), t_end,
                                  sp, ladder, B_key, plan_latency,
                                  cert_rtol, knobs),
            lambda u: u, st)
        return st

    return jax.lax.while_loop(behind, window, s)


def _stream_event(s: dict, ev, sp, ladder, x_all, w_all, B_key,
                  plan_latency, rtol, cert_rtol, knobs) -> dict:
    """One control-plane event: execute-up-to, apply, replan."""
    t_ev, kind, pi, pf = ev
    live_ev = kind > 0
    s = _exec_until(s, jnp.where(live_ev, t_ev, s["t"]), sp, ladder,
                    x_all, w_all, B_key, plan_latency, rtol, cert_rtol,
                    knobs)
    s = jax.lax.cond(live_ev, lambda u: _promote(u, t_ev),
                     lambda u: dict(u), s)
    s = jax.lax.cond(live_ev, lambda u: _fill_slots(u, x_all, w_all),
                     lambda u: u, s)

    def arrive(u):
        u = dict(u)
        u["qbuf"] = u["qbuf"].at[u["qtail"]].set(pi)
        u["qtail"] = u["qtail"] + 1
        return _fill_slots(u, x_all, w_all)

    s = jax.lax.cond(kind == 1, arrive, lambda u: u, s)
    s = dict(s)
    s["B_live"] = jnp.where(kind == 2, pf, s["B_live"])
    s = jax.lax.cond(
        (kind == 1) | (kind == 2),
        lambda u: _replan_dev(u, t_ev, sp, ladder, B_key, plan_latency,
                              cert_rtol, knobs),
        lambda u: u, s)
    return s


@partial(jax.jit, static_argnames=("fast", "coarse", "descent_iters",
                                   "cap_iters", "stol_rel",
                                   "search_steps"))
def _stream_chunk(sp, ladder, state, events, x_all, w_all, B_key,
                  plan_latency, rtol, cert_rtol, *, fast, coarse,
                  descent_iters, cap_iters, stol_rel, search_steps):
    """One compiled dispatch servicing a chunk of events via lax.scan."""
    knobs = dict(fast=fast, coarse=coarse, descent_iters=descent_iters,
                 cap_iters=cap_iters, stol_rel=stol_rel,
                 search_steps=search_steps)

    def step(s, ev):
        return _stream_event(s, ev, sp, ladder, x_all, w_all, B_key,
                             plan_latency, rtol, cert_rtol, knobs), None

    state, _ = jax.lax.scan(step, state, events)
    return state
