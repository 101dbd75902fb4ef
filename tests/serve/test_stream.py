"""Streaming control plane: warm-started replanning + the online loop.

The headline acceptance test is warm == cold J parity ≤ 1e-10 over a
seeded arrival trace that includes a budget-collapse event (the
λ-bracket invalidation case): the warm path's reused completion order
and λ hints must be pure accelerators — the certified plan they produce
is the same one a from-scratch solve finds, state by state, and the
whole-stream metrics agree to reference precision.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import (
    WarmStart,
    cap_bracket_probe,
    power,
    sample_arrival_stream,
    saturating,
    smartfill,
    smartfill_hetero,
    smartfill_warm,
    stack_speedups,
)
from repro.robust import DegradingPolicy, ladder_plan_table
from repro.sched.policies import (
    EquiPolicy,
    StreamingSmartFillPolicy,
    StreamPlan,
)
from repro.serve import PlanBuffer, StreamController
from repro.serve.admission import AdmissionController
from repro.serve.stream import _exec_window

B = 10.0
SP = power(1.0, 0.5, B)


class ColdOnlyPolicy(StreamingSmartFillPolicy):
    """Force the from-scratch path on every replan (parity baseline)."""

    def plan(self, rem, w, active=None, B=None, warm=True):
        return super().plan(rem, w, active=active, B=B, warm=False)


# ---------------------------------------------------------------------------
# Acceptance: warm == cold parity over a trace with a budget collapse
# ---------------------------------------------------------------------------

def _parity_trace():
    # load ~0.65 of service capacity, so live sets genuinely overlap
    # (warm starts do real work) while every job still completes; the
    # budget events include deep dips — the bracket-invalidation case
    return sample_arrival_stream(3, horizon=1000.0, rate=0.2, B=B,
                                 n_budget_events=3,
                                 budget_frac=(0.15, 0.35),
                                 deadline_slack=50.0)


def test_warm_equals_cold_over_arrival_trace():
    stream = _parity_trace()
    assert len(stream) >= 10
    assert stream.budget_times.shape[0] >= 3
    warm_ctl = StreamController(SP, B, max_live=8)
    cold_ctl = StreamController(SP, B, max_live=8,
                                policy=ColdOnlyPolicy(SP, B))
    rw = warm_ctl.run(stream)
    rc = cold_ctl.run(stream)
    # the warm path must actually have fired (else this tests nothing)
    assert rw.warm_replans > 0
    assert rc.warm_replans == 0
    assert rw.degraded_windows == rc.degraded_windows == 0
    Jw, Jc = rw.metrics.weighted_J, rc.metrics.weighted_J
    assert abs(Jw - Jc) <= 1e-10 * max(1.0, abs(Jc))
    np.testing.assert_allclose(rw.completion, rc.completion,
                               rtol=1e-9, atol=1e-9)
    assert rw.metrics.n_completed == rc.metrics.n_completed


def test_warm_equals_cold_per_state_parity():
    # state-by-state: evolve live state by *executing* the warm plan
    # between replans (the dynamics the carried-order invariant is
    # stated for — allocations non-decreasing along rows, so remaining
    # sizes never cross), and compare each warm plan against a fresh
    # cold solver at <= 1e-10.  Step 10 collapses the budget: the warm
    # λ-bracket goes stale and must be probed away, not executed.
    rng = np.random.default_rng(0)
    M = 8
    warm_pol = StreamingSmartFillPolicy(SP, B)
    rem = np.zeros(M)
    act = np.zeros(M, bool)
    w = np.ones(M)
    live_B = B
    for step in range(25):
        if step == 10:
            live_B = 0.2 * B      # budget collapse: stale bracket invalid
        free = np.flatnonzero(~act)
        if free.size and rng.random() < 0.8:
            s = free[0]
            act[s] = True
            rem[s] = rng.uniform(0.5, 20.0)
            w[s] = 1.0 / rem[s]   # slowdown weights (streaming default)
        if not act.any():
            continue
        pw = warm_pol.plan(rem, w, act, B=live_B)
        pc = ColdOnlyPolicy(SP, B).plan(rem, w, act, B=live_B)
        assert pw.certified and pc.certified, step
        assert abs(pw.J - pc.J) <= 1e-10 * max(1.0, abs(pc.J)), step
        # execute the plan for a random span (completions allowed)
        theta = np.asarray(pw.slot_allocations())
        rate = np.where(act, np.asarray(SP.s(jnp.asarray(theta))), 0.0)
        dt = rng.uniform(0.2, 1.5) * float(
            np.min(rem[act] / np.maximum(rate[act], 1e-300)))
        rem = np.maximum(rem - rate * dt, 0.0)
        done = act & (rem <= 1e-12)
        act &= ~done
        if done.any():
            warm_pol.release(np.flatnonzero(done))
    assert warm_pol.warm_replans > 5


def test_release_prevents_slot_recycling_corruption():
    # complete a job, reuse its slot for a *larger* job: without
    # release() the new occupant inherits the old job's position in the
    # carried order and the warm plan drifts from the cold one
    pol = StreamingSmartFillPolicy(SP, B)
    rem = np.array([16.0, 5.0, 4.0])
    w = 1.0 / rem
    act = np.ones(3, bool)
    pol.plan(rem, w, act)
    # job in slot 2 completes; a bigger job takes the slot
    pol.release([2])
    rem2 = np.array([15.0, 3.5, 6.3])
    w2 = np.array([w[0], w[1], 1.0 / 6.3])
    pw = pol.plan(rem2, w2, act)
    pc = ColdOnlyPolicy(SP, B).plan(rem2, w2, act)
    assert pw.warm and pw.certified and pc.certified
    np.testing.assert_array_equal(pw.order, pc.order)
    assert abs(pw.J - pc.J) <= 1e-10 * max(1.0, abs(pc.J))


def test_warm_hint_survives_budget_collapse():
    # a solve at B, then the same instance at B/20 with the stale hints:
    # the probe must reject the stale bracket and the solve still land
    # on the cold answer
    x = np.array([8.0, 5.0, 2.0, 1.0])
    w = np.array([0.5, 1.0, 1.0, 2.0])
    _, warm = smartfill_warm(SP, x, w, B=B)
    cold = smartfill(SP, x, w, B=B / 20)
    warm_sched, _ = smartfill_warm(SP, x, w, B=B / 20, warm=warm)
    assert abs(warm_sched.J - cold.J) <= 1e-10 * max(1.0, cold.J)


# ---------------------------------------------------------------------------
# Warm-start plumbing: smartfill_warm + cap_bracket_probe
# ---------------------------------------------------------------------------

def test_smartfill_warm_matches_smartfill():
    x = np.array([5.0, 3.0, 1.0])
    w = np.array([1.0, 1.0, 2.0])
    base = smartfill(SP, x, w, B=B)
    sched, warm = smartfill_warm(SP, x, w, B=B)
    assert abs(sched.J - base.J) <= 1e-12 * max(1.0, base.J)
    assert warm.lam.shape == (3,)
    assert warm.bracket.shape == (2,)
    resched, warm2 = smartfill_warm(SP, x, w, B=B, warm=warm)
    assert abs(resched.J - base.J) <= 1e-12 * max(1.0, base.J)
    assert np.all(np.isfinite(np.asarray(warm2.bracket)))


def test_smartfill_warm_rejects_bad_lam_shape():
    x = np.ones(3)
    with pytest.raises(ValueError):
        smartfill_warm(SP, x, np.ones(3), B=B,
                       warm=WarmStart(lam=jnp.ones(5),
                                      bracket=jnp.array([1e-6, 1.0])))


def test_cap_bracket_probe_flags_stale_bracket():
    c = jnp.array([2.0, 1.0, 0.5])
    lo_ok, hi_ok = cap_bracket_probe(SP, B, c, jnp.array([1e-12, 1e3]))
    assert bool(lo_ok) and bool(hi_ok)
    # collapse the budget 50x: the stale *upper* end (sized for the old
    # budget's much smaller multiplier) keeps covering, but a bracket
    # pinned near the old root no longer straddles the new one
    lo_ok2, hi_ok2 = cap_bracket_probe(SP, B / 50, c,
                                       jnp.array([1e-12, 1e-9]))
    assert not bool(hi_ok2)


# ---------------------------------------------------------------------------
# The window executor
# ---------------------------------------------------------------------------

def test_exec_window_single_job_rate():
    # one live row at θ = B runs at s(B); completion offset = rem/s(B)
    M = 4
    table = jnp.zeros((M, M)).at[0, 0].set(B)
    rem0 = jnp.zeros(M).at[0].set(4.0)
    live0 = jnp.zeros(M, bool).at[0].set(True)
    srate = float(SP.s(jnp.asarray(B)))
    rem, live, comp = _exec_window(SP, table, rem0, live0, 100.0, 1e-12)
    assert not bool(live[0])
    np.testing.assert_allclose(float(comp[0]), 4.0 / srate, rtol=1e-9)
    # a window shorter than the completion leaves the job live
    rem2, live2, comp2 = _exec_window(SP, table, rem0, live0,
                                      1.0, 1e-12)
    assert bool(live2[0]) and not np.isfinite(float(comp2[0]))
    np.testing.assert_allclose(float(rem2[0]), 4.0 - srate, rtol=1e-9)


def test_exec_window_matches_smartfill_completions():
    # full SmartFill table on a 3-job instance: the scan must reproduce
    # the planned completion times T exactly
    x = np.array([6.0, 3.0, 1.5])
    w = np.ones(3)
    sched = smartfill(SP, x, w, B=B)
    order = np.argsort(-x)     # already sorted
    M = 3
    table = jnp.asarray(sched.theta)
    rem0 = jnp.asarray(x[order])
    live0 = jnp.ones(M, bool)
    rem, live, comp = _exec_window(SP, table, rem0, live0, 1e4, 1e-12)
    assert not bool(live.any())
    T = np.sort(np.asarray(sched.T))[::-1]   # row 0 = largest, last done
    np.testing.assert_allclose(np.asarray(comp), T, rtol=1e-8)


def test_exec_window_non_prefix_live_rank_compression():
    # stale-plan case: live rows {0, 2} of a 3-row table must read
    # column 1 (two active) at ranks 0 and 1
    M = 3
    table = jnp.asarray([[4.0, 6.0, 5.0],
                         [0.0, 4.0, 3.0],
                         [0.0, 0.0, 2.0]])
    rem0 = jnp.asarray([5.0, 0.0, 4.0])
    live0 = jnp.asarray([True, False, True])
    rem, live, comp = _exec_window(SP, table, rem0, live0, 0.5, 1e-12)
    s = lambda th: float(SP.s(jnp.asarray(th)))
    np.testing.assert_allclose(float(rem[0]), 5.0 - 0.5 * s(6.0), rtol=1e-9)
    np.testing.assert_allclose(float(rem[2]), 4.0 - 0.5 * s(4.0), rtol=1e-9)


# ---------------------------------------------------------------------------
# PlanBuffer / double buffering
# ---------------------------------------------------------------------------

def _dummy_plan(m=1):
    return StreamPlan(order=np.arange(m), table=jnp.zeros((4, 4)),
                      J=0.0, J_linear=0.0, m=m, B=B, warm=False,
                      certified=True)


def test_plan_buffer_promotes_at_ready_time():
    buf = PlanBuffer()
    assert buf.poll(0.0) is None
    p1, p2 = _dummy_plan(1), _dummy_plan(2)
    buf.publish(p1, ready_at=5.0)
    assert buf.poll(4.9) is None          # still in flight
    assert buf.poll(5.0) is p1            # promoted
    buf.publish(p2, ready_at=7.0)
    assert buf.poll(6.0) is p1            # front stays while back solves
    assert buf.poll(7.5) is p2
    assert buf.swaps == 2


def test_plan_latency_jobs_idle_until_promotion():
    # one job, solve latency L: nothing executes before the plan lands,
    # so completion = L + service — and the mid-window promotion split
    # must pick the plan up without any further control-plane event
    x = 4.0
    stream_t = np.array([0.0])
    from repro.core.workloads import ArrivalStream
    stream = ArrivalStream(t=stream_t, x=np.array([x]), w=np.ones(1),
                           deadline=np.full(1, np.inf), horizon=1000.0,
                           budget_times=np.zeros(0),
                           budget_values=np.zeros(0))
    L = 3.0
    ctl = StreamController(SP, B, max_live=4, plan_latency=L)
    res = ctl.run(stream)
    srate = float(SP.s(jnp.asarray(B)))
    np.testing.assert_allclose(res.completion[0], L + x / srate,
                               rtol=1e-8)
    ctl0 = StreamController(SP, B, max_live=4)
    np.testing.assert_allclose(ctl0.run(stream).completion[0], x / srate,
                               rtol=1e-8)


# ---------------------------------------------------------------------------
# Controller semantics
# ---------------------------------------------------------------------------

def test_stream_all_jobs_complete_and_metrics_consistent():
    stream = sample_arrival_stream(7, horizon=6000.0, rate=0.015, B=B,
                                   n_budget_events=2, deadline_slack=30.0)
    ctl = StreamController(SP, B, max_live=8)
    res = ctl.run(stream)
    m = res.metrics
    assert m.n_arrivals == len(stream)
    assert m.n_admitted == m.n_arrivals          # no admission controller
    assert m.n_completed == m.n_admitted          # horizon is generous
    done = np.isfinite(res.completion)
    assert done.sum() == m.n_completed
    # completions never precede arrivals; latency/slowdown consistent
    assert np.all(res.completion[done] >= np.asarray(stream.t)[done])
    np.testing.assert_allclose(
        m.weighted_J,
        float(np.sum(np.asarray(stream.w)[done] * res.latency[done])))
    assert m.mean_slowdown >= 1.0 - 1e-9          # can't beat solo service
    assert m.p99_latency >= m.p50_latency >= 0.0
    assert res.replans >= res.warm_replans + res.cold_replans


def test_stream_capacity_queues_fifo():
    from repro.core.workloads import ArrivalStream
    # three identical jobs at t=0 into one slot: strictly serial FIFO
    stream = ArrivalStream(t=np.zeros(3), x=np.full(3, 2.0),
                           w=np.ones(3), deadline=np.full(3, np.inf),
                           horizon=1000.0, budget_times=np.zeros(0),
                           budget_values=np.zeros(0))
    ctl = StreamController(SP, B, max_live=1)
    res = ctl.run(stream)
    srate = float(SP.s(jnp.asarray(B)))
    expect = 2.0 / srate * np.arange(1, 4)
    np.testing.assert_allclose(np.sort(res.completion), expect, rtol=1e-6)


def test_stream_budget_event_slows_service():
    from repro.core.workloads import ArrivalStream
    mk = lambda bt, bv: ArrivalStream(
        t=np.zeros(1), x=np.array([8.0]), w=np.ones(1),
        deadline=np.full(1, np.inf), horizon=1000.0,
        budget_times=np.asarray(bt), budget_values=np.asarray(bv))
    full = StreamController(SP, B, max_live=2).run(mk([], []))
    dipped = StreamController(SP, B, max_live=2).run(
        mk([0.5], [B / 10]))
    assert dipped.completion[0] > full.completion[0] + 0.1


def test_stream_uncertified_replan_falls_to_ladder():
    class Broken(StreamingSmartFillPolicy):
        def plan(self, rem, w, active=None, B=None, warm=True):
            raise FloatingPointError("poisoned solve")

    stream = sample_arrival_stream(5, horizon=4000.0, rate=0.01, B=B)
    ctl = StreamController(SP, B, max_live=4, policy=Broken(SP, B))
    res = ctl.run(stream)
    assert res.degraded_windows == res.replans > 0
    # the ladder's SmartFill rung is healthy, so jobs still finish
    assert res.metrics.n_completed == res.metrics.n_admitted


@pytest.mark.parametrize("exc, absorbed", [
    (ValueError("infeasible order"), True),
    (jax.errors.JaxRuntimeError("INTERNAL: device fault"), False),
])
def test_stream_replan_fault_routing(exc, absorbed):
    """Solver errors fall to the ladder; a device or compile fault
    (``JaxRuntimeError``) propagates instead of passing for a degraded
    window."""
    class Faulty(StreamingSmartFillPolicy):
        def plan(self, rem, w, active=None, B=None, warm=True):
            raise exc

    stream = sample_arrival_stream(5, horizon=4000.0, rate=0.01, B=B)
    ctl = StreamController(SP, B, max_live=4, policy=Faulty(SP, B))
    if absorbed:
        res = ctl.run(stream)
        assert res.degraded_windows == res.replans > 0
    else:
        with pytest.raises(jax.errors.JaxRuntimeError, match="device"):
            ctl.run(stream)


def test_stream_rejects_per_job_speedup():
    sp_pj = stack_speedups([power(1.0, 0.4, B), power(1.0, 0.6, B)])
    with pytest.raises(ValueError, match="shared"):
        StreamController(sp_pj, B)
    # the per-job path lives in the policy directly
    pol = StreamingSmartFillPolicy(sp_pj, B)
    p = pol.plan(np.array([4.0, 2.0]), np.ones(2))
    assert p.certified and p.m == 2


def test_streaming_policy_per_job_warm_parity():
    sps = [power(1.0, 0.4, B), saturating(0.5, 12.0, 2.0, B),
           power(1.0, 0.7, B)]
    sp_pj = stack_speedups(sps)
    x = np.array([6.0, 4.0, 2.0])
    w = np.array([1.0, 0.5, 2.0])
    pol = StreamingSmartFillPolicy(sp_pj, B)
    p_cold = pol.plan(x, w)
    assert not p_cold.warm and p_cold.certified
    ref = smartfill_hetero(sp_pj, x, w, B=B)
    assert abs(p_cold.J - ref.J) <= 1e-9 * max(1.0, ref.J)
    # shrink and replan warm: certified, and equal to a fresh solve
    x2 = x * 0.8
    p_warm = pol.plan(x2, w)
    assert p_warm.warm and p_warm.certified
    ref2 = smartfill_hetero(sp_pj, x2, w, B=B)
    assert abs(p_warm.J - ref2.J) <= 1e-9 * max(1.0, ref2.J)


# ---------------------------------------------------------------------------
# Ladder plan tables
# ---------------------------------------------------------------------------

def test_ladder_plan_table_columns_match_policy():
    ladder = DegradingPolicy.ladder(SP, B=B)
    rem = np.array([5.0, 3.0, 1.0, 0.0])
    w = np.ones(4)
    table = ladder_plan_table(ladder, rem, w, B=B)
    assert table.shape == (4, 4)
    idx = np.arange(4)
    for m in range(1, 5):
        act = idx < m
        col = np.where(act, np.asarray(ladder(rem, w, act, B)), 0.0)
        np.testing.assert_allclose(np.asarray(table[:, m - 1]), col,
                                   rtol=1e-12)
        assert float(np.asarray(table[:, m - 1]).sum()) <= B + 1e-9


def test_ladder_plan_table_equi_feasible():
    table = ladder_plan_table(EquiPolicy(B), np.ones(3), np.ones(3), B=B)
    for m in range(1, 4):
        np.testing.assert_allclose(np.asarray(table[:m, m - 1]), B / m,
                                   rtol=1e-12)


# ---------------------------------------------------------------------------
# Admission in rank mode + stream integration
# ---------------------------------------------------------------------------

def test_admission_rank_mode_accepts_live_state():
    # half-served live state is non-agreeable: sizes shrank, weights
    # didn't.  "require" must reject it, "rank" must score it.
    run_x = np.array([5.0, 0.3])      # biggest remnant carries the
    run_w = np.array([5.0, 0.1])      # biggest weight: non-agreeable
    cand_x, cand_w = np.array([1.0]), np.array([1.0])
    strict = AdmissionController(SP, B=B, agreeable="require")
    with pytest.raises(ValueError):
        strict.evaluate(run_x, run_w, cand_x, cand_w)
    ranked = AdmissionController(SP, B=B, agreeable="rank")
    dec = ranked.evaluate(run_x, run_w, cand_x, cand_w)
    assert dec.admit.shape == (1,)
    assert np.isfinite(dec.marginal_cost).all()


def test_admission_rejects_unknown_agreeable_mode():
    with pytest.raises(ValueError):
        AdmissionController(SP, B=B, agreeable="maybe")


def test_stream_with_admission_threshold_rejects():
    stream = sample_arrival_stream(11, horizon=4000.0, rate=0.02, B=B)
    assert len(stream) >= 5
    deny_all = AdmissionController(SP, B=B, cost_threshold=-1.0,
                                   agreeable="rank")
    ctl = StreamController(SP, B, max_live=8, admission=deny_all)
    res = ctl.run(stream)
    assert res.metrics.n_rejected == len(stream)
    assert res.metrics.n_completed == 0
    admit_all = AdmissionController(SP, B=B, agreeable="rank")
    res2 = StreamController(SP, B, max_live=8,
                            admission=admit_all).run(stream)
    assert res2.metrics.n_admitted == len(stream)


def test_stream_requires_rank_mode_admission():
    strict = AdmissionController(SP, B=B, agreeable="require")
    with pytest.raises(ValueError, match="rank"):
        StreamController(SP, B, admission=strict)


# ---------------------------------------------------------------------------
# Arrival stream sampling
# ---------------------------------------------------------------------------

def test_arrival_stream_reproducible_and_sorted():
    a = sample_arrival_stream(42, horizon=10_000.0, rate=0.01, B=B,
                              n_budget_events=3, deadline_slack=10.0)
    b = sample_arrival_stream(42, horizon=10_000.0, rate=0.01, B=B,
                              n_budget_events=3, deadline_slack=10.0)
    np.testing.assert_array_equal(a.t, b.t)
    np.testing.assert_array_equal(a.x, b.x)
    np.testing.assert_array_equal(a.budget_times, b.budget_times)
    t = np.asarray(a.t)
    assert np.all(np.diff(t) >= 0)
    assert t.size == len(a)
    assert np.all((t >= 0) & (t <= a.horizon))
    bt = np.asarray(a.budget_times)
    assert np.all(np.diff(bt) >= 0)
    assert np.all(np.asarray(a.budget_values) <= B + 1e-12)
    # slowdown weights are 1/x; deadlines sit slack×solo past arrival
    np.testing.assert_allclose(np.asarray(a.w), 1.0 / np.asarray(a.x))
    np.testing.assert_allclose(np.asarray(a.deadline),
                               t + 10.0 * np.asarray(a.x))


def test_arrival_stream_diurnal_intensity():
    # λ(t) peaks mid-period and troughs at the start: a one-period trace
    # must put well over half its arrivals in the middle half
    s = sample_arrival_stream(0, horizon=86_400.0, rate=0.05,
                              diurnal=0.9, B=B)
    t = np.asarray(s.t)
    mid = (t > 86_400 * 0.25) & (t < 86_400 * 0.75)
    assert mid.mean() > 0.6
    flat = sample_arrival_stream(0, horizon=86_400.0, rate=0.05,
                                 diurnal=0.0, B=B)
    tf = np.asarray(flat.t)
    midf = (tf > 86_400 * 0.25) & (tf < 86_400 * 0.75)
    assert abs(midf.mean() - 0.5) < 0.1
