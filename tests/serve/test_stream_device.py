"""Device-resident streaming hot path: device scan == host oracle.

The contract of ``StreamController.run_device`` is *bit-parity* with
the host loop running the same ``StreamCascadePolicy`` — the host loop
is kept precisely to be this differential oracle.  Every stage of the
traced replan cascade (fresh hinted solve → certificate → adjacent-
exchange search → ladder) and every window mechanic (double-buffer
promotion mid-window, cut-at-first-completion backfill, FIFO queueing,
budget events) must make the same decision and produce the same floats
through ``lax.scan`` as through the Python loop.

Also here: the dtype-aware ``_rate_floor`` regression (the f32 hazard
of the old ``1e-300`` literal), ``PlanBuffer.poll`` at exactly
``ready_at``, ``StreamingSmartFillPolicy.release`` with slots absent
from the carried order, and the arrival-log replay constructors.
"""
import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import power, sample_arrival_stream
from repro.core.workloads import (ArrivalStream, arrival_stream_from_log,
                                  load_arrival_log)
from repro.sched.policies import (StreamingSmartFillPolicy, _cascade_packed,
                                  _read_decision, stream_replan_core,
                                  stream_warm0)
from repro.serve import PlanBuffer, StreamCascadePolicy, StreamController
from repro.serve.stream import _exec_window, _rate_floor, _window_packed

B = 10.0
SP = power(1.0, 0.5, B)


def _pair(seed, horizon, M, *, rate=0.1, weights="slowdown",
          plan_latency=0.0, n_budget_events=2, B_t=B):
    stream = sample_arrival_stream(
        seed, horizon=horizon, rate=rate, diurnal=0.75, period=horizon,
        weights=weights, B=B_t, n_budget_events=n_budget_events,
        budget_frac=(0.3, 0.8))
    ctl = StreamController(SP, B_t, max_live=M,
                           policy=StreamCascadePolicy(SP, B_t),
                           plan_latency=plan_latency)
    return stream, ctl


def _assert_parity(host, dev):
    np.testing.assert_array_equal(np.isfinite(host.completion),
                                  np.isfinite(dev.completion))
    fin = np.isfinite(host.completion)
    # bitwise: the device scan runs the same jitted kernels on the same
    # floats in the same sequence — any drift means a decision diverged
    np.testing.assert_array_equal(host.completion[fin],
                                  dev.completion[fin])
    assert host.replans == dev.replans
    assert host.warm_replans == dev.warm_replans
    assert host.cold_replans == dev.cold_replans
    assert host.degraded_windows == dev.degraded_windows
    assert host.n_windows == dev.n_windows
    assert host.search_solves == dev.search_solves
    assert host.cut_reruns == dev.cut_reruns
    assert host.budget_excess == pytest.approx(dev.budget_excess,
                                               abs=1e-9 * B)
    assert host.metrics == dev.metrics


@pytest.mark.parametrize("seed,M,latency,weights,rate", [
    (3, 6, 0.0, "slowdown", 0.15),     # warm cascade only
    (11, 5, 2.0, "slowdown", 0.12),    # double-buffered mid-window splits
    (5, 6, 0.0, "random", 0.25),       # non-agreeable: search branch fires
])
def test_device_matches_host_oracle(seed, M, latency, weights, rate):
    stream, ctl = _pair(seed, 1200.0, M, rate=rate, weights=weights,
                        plan_latency=latency)
    host = ctl.run(stream)
    dev = ctl.run_device(stream)
    _assert_parity(host, dev)


def test_device_search_branch_exercised_and_identical():
    # random weights break the agreeable structure, so the fresh SJF
    # order fails the certificate and the traced exchange search must
    # rescue it — on both paths, identically
    stream, ctl = _pair(9, 2400.0, 8, rate=0.35, weights="random")
    host = ctl.run(stream)
    dev = ctl.run_device(stream)
    assert host.cold_replans > 0          # the branch actually fired
    assert ctl.policy.order_searches > 0
    _assert_parity(host, dev)


@pytest.mark.parametrize("seed,M,rate,weights", [
    (5, 6, 0.25, "random"),      # non-agreeable: many searches
    (3, 6, 0.15, "slowdown"),    # the day's weights: a few
])
def test_device_work_counters_match_host(seed, M, rate, weights):
    # both tapes escalate to the exchange search and backfill at cut
    # completions: the wasted-work counters must agree path for path
    stream, ctl = _pair(seed, 1200.0, M, rate=rate, weights=weights)
    host = ctl.run(stream)
    dev = ctl.run_device(stream)
    assert host.search_solves == dev.search_solves > 0
    assert host.search_solves % (M - 1) == 0        # whole sweeps
    assert host.cut_reruns == dev.cut_reruns > 0
    assert host.cut_reruns <= host.n_windows
    # plans spend the live budget and no more, to rounding
    tol = 64 * np.finfo(float).eps * B
    assert 0.0 <= host.budget_excess <= tol
    assert 0.0 <= dev.budget_excess <= tol


class _Inflated:
    """``StreamCascadePolicy`` whose every table asks for 10% too much."""

    def __init__(self, inner):
        self.inner = inner

    def plan(self, rem, w, active=None, B=None):
        import dataclasses
        p = self.inner.plan(rem, w, active, B)
        return dataclasses.replace(p, table=p.table * 1.1)

    def release(self, slots):
        self.inner.release(slots)

    def reset(self):
        self.inner.reset()

    @property
    def warm_replans(self):
        return self.inner.warm_replans

    @property
    def cold_replans(self):
        return self.inner.cold_replans


def test_budget_excess_flags_an_inflated_table():
    from repro.serve.stream import _plan_excess
    stream, ctl = _pair(3, 600.0, 6, rate=0.15)
    ok = ctl.run(stream)
    ctl.policy = _Inflated(ctl.policy)
    bad = ctl.run(stream)
    assert ok.budget_excess <= 64 * np.finfo(float).eps * B
    # a full live column sums to B(t) <= B, so 10% over reads up to 0.1 B
    assert 0.0 < bad.budget_excess <= 0.1 * B * (1 + 1e-9)
    # the scan's traced form on one such table
    plan = StreamCascadePolicy(SP, B).plan(np.array([3.0, 2.0, 1.0, 0.0]),
                                           np.ones(4), B=B)
    assert float(_plan_excess(plan.table, plan.m, B)) == pytest.approx(
        0.0, abs=1e-9)
    assert float(_plan_excess(plan.table * 1.1, plan.m, B)) == (
        pytest.approx(0.1 * B))
    assert float(_plan_excess(plan.table * 1.1, 0, B)) == 0.0


def test_device_chunked_equals_single_dispatch():
    # chunk_events splits the trace into several compiled dispatches
    # with the carry handed across — the seam must be invisible
    stream, ctl = _pair(7, 1500.0, 4, rate=0.2)
    whole = ctl.run_device(stream)
    chunked = ctl.run_device(stream, chunk_events=17)
    np.testing.assert_array_equal(whole.completion, chunked.completion)
    assert whole.replans == chunked.replans
    assert whole.n_windows == chunked.n_windows


def test_device_rejects_scored_admission():
    from repro.serve.admission import AdmissionController
    stream, _ = _pair(3, 600.0, 4)
    ctl = StreamController(SP, B, max_live=4,
                           admission=AdmissionController(
                               SP, B=B, agreeable="rank"))
    with pytest.raises(ValueError, match="admission"):
        ctl.run_device(stream)


@pytest.mark.slow
def test_device_day_trace_parity():
    # the acceptance trace: a full diurnal day with budget dips — all
    # four cascade stages fire (warm, search-rescued, ladder) and the
    # device scan must still be bit-identical to the oracle
    stream, ctl = _pair(17, 86_400.0, 16, rate=0.12,
                        n_budget_events=12)
    host = ctl.run(stream)
    dev = ctl.run_device(stream)
    assert host.cold_replans > 0 and host.degraded_windows > 0
    _assert_parity(host, dev)


# ---------------------------------------------------------------------------
# The host's packed entries: one upload and one read, the same bits
# ---------------------------------------------------------------------------

_STATIC = ("fast", "coarse", "descent_iters", "cap_iters", "stol_rel",
           "search_steps")
_cascade_call = jax.jit(stream_replan_core, static_argnames=_STATIC)


def _live_state(stage):
    """Six slots, one idle, under a dipped budget: agreeable weights
    certify the fresh solve; seed 1's random weights escalate to the
    exchange search, and with no search step allowed reach the ladder."""
    r = np.random.default_rng(0 if stage == "fresh" else 1)
    rem = r.uniform(0.5, 20.0, 6)
    w = 1.0 / rem if stage == "fresh" else r.uniform(0.1, 5.0, 6)
    active = np.ones(6, bool)
    active[5] = stage == "fresh"
    active[4] = False
    return rem, w, active, 7.0, (0 if stage == "ladder" else 24)


def _assert_same_bits(a, b):
    a, b = np.asarray(a), np.asarray(b)
    assert a.dtype == b.dtype and a.shape == b.shape
    np.testing.assert_array_equal(a.view(np.uint8), b.view(np.uint8))


@pytest.mark.parametrize("x64", [True, False], ids=["f64", "f32"])
@pytest.mark.parametrize("stage", ["fresh", "search", "ladder"])
def test_packed_cascade_matches_cascade_call_bit_for_bit(stage, x64):
    rem, w, active, B_live, steps = _live_state(stage)
    with jax.enable_x64(x64):
        sp = power(1.0, 0.5, B)
        pol = StreamCascadePolicy(sp, B, search_steps=steps)
        dtype = jnp.result_type(float)
        static = dict(fast=pol._fast, coarse=pol.coarse,
                      descent_iters=pol.descent_iters,
                      cap_iters=pol.cap_iters, stol_rel=pol.stol_rel,
                      search_steps=steps)
        sp_d, ladder_d, B_key, cert_rtol = pol._fixed
        warm = stream_warm0(6, dtype)
        # a fresh payload, then the payload the first call left
        for shrink in (1.0, 0.9):
            x = rem * shrink
            ref = _cascade_call(
                pol.sp, pol.ladder, jnp.asarray(x, dtype),
                jnp.asarray(w, dtype), jnp.asarray(active), B_live, B,
                warm, pol.certificate_rtol, **static)
            packed = np.concatenate([x, w, active, [B_live]]).astype(dtype)
            out, table, warm2 = _cascade_packed(
                sp_d, ladder_d, jax.device_put(packed), B_key, warm,
                cert_rtol, **static)
            order, m, cert, searched, sweeps, J, J_lin = _read_decision(
                jax.device_get(out), 6, dtype)
            np.testing.assert_array_equal(order, np.asarray(ref[0]))
            assert (m, cert, searched, sweeps) == (
                int(ref[2]), bool(ref[3]), bool(ref[4]), int(ref[8]))
            _assert_same_bits(np.array([J, J_lin], dtype),
                              np.array([ref[5], ref[6]]))
            _assert_same_bits(table, ref[1])
            _assert_same_bits(warm2.lam, ref[7].lam)
            _assert_same_bits(warm2.bracket, ref[7].bracket)
            warm = ref[7]
    # each state reaches the stage it stands for
    assert cert == (stage != "ladder")
    assert searched == (stage != "fresh")
    assert (sweeps > 0) == (stage == "search")


@pytest.mark.parametrize("x64", [True, False], ids=["f64", "f32"])
def test_packed_window_matches_exec_window_bit_for_bit(x64):
    M = 5
    with jax.enable_x64(x64):
        sp = power(1.0, 0.5, B)
        dtype = jnp.result_type(float)
        pol = StreamCascadePolicy(sp, B)
        rem = np.array([4.0, 1.5, 7.0, 0.0, 0.0])
        live = np.array([True, True, True, False, False])
        table = pol.plan(rem, 1.0 / np.maximum(rem, 1.0), live).table
        ctl = StreamController(sp, B, max_live=M)
        sp_d, rtol = ctl._window_fixed
        packed = np.concatenate([rem, live, [30.0]]).astype(dtype)
        got = jax.device_get(_window_packed(
            sp_d, table, jax.device_put(packed), rtol))
        rem_end, _, comp = _exec_window(ctl.sp, table, jnp.asarray(rem),
                                        jnp.asarray(live), 30.0, ctl.rtol)
        _assert_same_bits(got[:M], comp)
        _assert_same_bits(got[M:], rem_end)
        # the cut re-run: the same window stopped at its first completion
        c0 = float(np.min(got[:M][np.isfinite(got[:M])]))
        assert 0.0 < c0 < 30.0
        packed[2 * M] = c0
        got = jax.device_get(_window_packed(
            sp_d, table, jax.device_put(packed), rtol))
        rem_end, _, comp = _exec_window(ctl.sp, table, jnp.asarray(rem),
                                        jnp.asarray(live), c0, ctl.rtol)
        _assert_same_bits(got[:M], comp)
        _assert_same_bits(got[M:], rem_end)
        assert np.isfinite(got[:M]).sum() == 1


# ---------------------------------------------------------------------------
# dtype-aware rate floor (the f32 1e-300 flush-to-zero regression)
# ---------------------------------------------------------------------------

def test_rate_floor_is_normal_in_both_dtypes():
    # the old literal floor is *zero* in f32 — exactly the unprotected
    # division the floor exists to prevent
    assert np.float32(1e-300) == 0.0
    for dt in (jnp.float32, jnp.float64):
        floor = float(_rate_floor(dt))
        assert floor > 0.0
        assert floor >= float(jnp.finfo(dt).tiny)   # normal, not denormal
    assert float(_rate_floor(jnp.float64)) < 1e-290


def test_f32_denormal_rate_division_is_protected():
    # the division guard itself: a denormal f32 rate (> 0, so the
    # rate-is-zero mask does not catch it) divides UNprotected under
    # the old literal floor — 1e-300 flushes to 0.0 in f32 and
    # maximum(rate, 0) is a no-op — and rem/rate overflows to inf;
    # the dtype-aware floor keeps the step width finite
    one = jnp.asarray(1.0, jnp.float32)
    rate = jnp.asarray(1e-40, jnp.float32)            # denormal, > 0
    assert float(rate) > 0.0
    old_floor = jnp.asarray(1e-300, jnp.float32)      # == 0.0: no guard
    assert float(old_floor) == 0.0
    assert not np.isfinite(float(one / jnp.maximum(rate, old_floor)))
    guarded = one / jnp.maximum(rate, _rate_floor(jnp.float32))
    assert np.isfinite(float(guarded))


def test_exec_window_f32_stays_in_dtype_and_completes():
    # end-to-end f32 window: the floored division must not promote the
    # carry to f64 (a dtype mismatch aborts the scan) and a healthy
    # window completes with finite f32 outputs
    import jax
    dt = jnp.float32
    sp32 = jax.tree_util.tree_map(lambda l: jnp.asarray(l, dt), SP)
    table = jnp.asarray([[4.0, 4.0],
                         [0.0, 4.0]], dt)
    rem0 = jnp.asarray([1.0, 2.0], dt)
    live0 = jnp.asarray([True, True])
    rem, live, comp = _exec_window(sp32, table, rem0, live0,
                                   jnp.asarray(100.0, dt),
                                   jnp.asarray(1e-6, dt))
    assert rem.dtype == dt and comp.dtype == dt
    assert np.all(np.isfinite(np.asarray(rem)))
    assert np.isfinite(float(comp[0])) and np.isfinite(float(comp[1]))
    assert not np.any(np.asarray(live))


# ---------------------------------------------------------------------------
# PlanBuffer.poll at exactly ready_at
# ---------------------------------------------------------------------------

def _plan(tag):
    from repro.sched.policies import StreamPlan
    return StreamPlan(order=np.arange(2), table=np.full((2, 2), float(tag)),
                      J=float(tag), J_linear=float(tag), m=2, B=B,
                      warm=False, certified=True)


def test_plan_buffer_promotes_at_exact_ready_time():
    # now == ready_at must promote (the device scan's `now >= bready`
    # and the host's `now >= back[0]` agree on the closed boundary);
    # the instant-publish ladder case (-inf) promotes at any clock
    buf = PlanBuffer()
    p = _plan(1)
    buf.publish(p, ready_at=5.0)
    assert buf.poll(np.nextafter(5.0, -np.inf)) is None
    assert buf.poll(5.0) is p                    # closed boundary
    assert buf.swaps == 1
    q = _plan(2)
    buf.publish(q)                               # default -inf: instant
    assert buf.poll(-1e30) is q
    # re-publish before promotion: latest wins, the stale back plan is
    # never promoted
    r, s = _plan(3), _plan(4)
    buf.publish(r, ready_at=8.0)
    buf.publish(s, ready_at=9.0)
    assert buf.poll(8.5) is q                    # r was overwritten
    assert buf.poll(9.0) is s


# ---------------------------------------------------------------------------
# StreamingSmartFillPolicy.release with slots absent from the order
# ---------------------------------------------------------------------------

def test_release_with_absent_slots_is_harmless():
    pol = StreamingSmartFillPolicy(SP, B)
    rem = np.array([9.0, 4.0, 2.0])
    w = 1.0 / rem
    act = np.ones(3, bool)
    pol.plan(rem, w, act)
    carried = pol._order.copy()
    # slots the carried order has never seen (beyond M, or already
    # released twice) must be ignored, not corrupt the order
    pol.release([7, 12])
    np.testing.assert_array_equal(pol._order, carried)
    pol.release([1])
    pol.release([1, 5])                          # double release: no-op
    np.testing.assert_array_equal(pol._order,
                                  carried[carried != 1])
    # and the next plan still certifies warm from the pruned order
    rem2 = np.array([8.0, 3.0, 1.5])
    p2 = pol.plan(rem2, w, act)
    assert p2.warm and p2.certified


def test_release_on_empty_order_is_noop():
    pol = StreamingSmartFillPolicy(SP, B)
    pol.release([0, 1])                          # before any plan
    assert pol._order.size == 0


# ---------------------------------------------------------------------------
# Arrival-log replay (from_log + load_arrival_log)
# ---------------------------------------------------------------------------

def test_from_log_sorts_and_defaults():
    st = arrival_stream_from_log([3.0, 1.0, 2.0], [2.0, 4.0, 1.0])
    np.testing.assert_array_equal(st.t, [1.0, 2.0, 3.0])
    np.testing.assert_array_equal(st.x, [4.0, 1.0, 2.0])
    np.testing.assert_allclose(st.w, 1.0 / st.x)     # slowdown default
    assert np.all(np.isinf(st.deadline))
    assert st.horizon > 3.0                          # last event inside
    assert len(st) == 3
    # the sampler advertises the replay entry point
    assert sample_arrival_stream.from_log is arrival_stream_from_log


def test_from_log_validates():
    with pytest.raises(ValueError, match="positive"):
        arrival_stream_from_log([0.0], [0.0])
    with pytest.raises(ValueError, match="length"):
        arrival_stream_from_log([0.0, 1.0], [1.0])
    with pytest.raises(ValueError, match="strictly before"):
        arrival_stream_from_log([5.0], [1.0], horizon=5.0)
    with pytest.raises(ValueError, match="budget"):
        arrival_stream_from_log([0.0], [1.0], budget_times=[1.0],
                                budget_values=[])


def test_load_arrival_log_csv_roundtrip(tmp_path):
    path = tmp_path / "trace.csv"
    path.write_text("# budget 4.0 6.5\n"
                    "t,x,w,deadline\n"
                    "0.5,2.0,0.5,inf\n"
                    "1.5,1.0,1.0,9.0\n")
    st = load_arrival_log(path)
    np.testing.assert_array_equal(st.t, [0.5, 1.5])
    np.testing.assert_array_equal(st.w, [0.5, 1.0])
    np.testing.assert_array_equal(st.deadline, [np.inf, 9.0])
    np.testing.assert_array_equal(st.budget_times, [4.0])
    np.testing.assert_array_equal(st.budget_values, [6.5])


def test_load_arrival_log_json(tmp_path):
    path = tmp_path / "trace.json"
    path.write_text(json.dumps({
        "t": [0.0, 2.0], "x": [3.0, 1.0], "horizon": 100.0,
        "budget_times": [1.0], "budget_values": [5.0]}))
    st = load_arrival_log(path)
    assert st.horizon == 100.0
    np.testing.assert_array_equal(st.budget_times, [1.0])
    np.testing.assert_allclose(st.w, [1.0 / 3.0, 1.0])


def test_committed_trace_replays_through_both_paths():
    # the shipped benchmark trace must replay through the controller,
    # and the device path must agree with the host oracle on it
    import pathlib
    trace = (pathlib.Path(__file__).resolve().parents[2]
             / "benchmarks" / "traces" / "arrivals_sample.csv")
    stream = load_arrival_log(trace)
    assert len(stream) > 50 and stream.budget_times.size >= 2
    ctl = StreamController(SP, B, max_live=8,
                           policy=StreamCascadePolicy(SP, B))
    host = ctl.run(stream)
    dev = ctl.run_device(stream)
    _assert_parity(host, dev)


def test_replayed_stream_equals_original_run():
    # record a sampled stream to the log format, replay it: the
    # controller must produce the identical outcome
    src = sample_arrival_stream(31, horizon=400.0, rate=0.2, B=B,
                                n_budget_events=2, budget_frac=(0.4, 0.9))
    replay = arrival_stream_from_log(
        src.t, src.x, src.w, deadlines=src.deadline, horizon=src.horizon,
        budget_times=src.budget_times, budget_values=src.budget_values)
    ctl = StreamController(SP, B, max_live=6)
    a, b = ctl.run(src), ctl.run(replay)
    np.testing.assert_array_equal(a.completion, b.completion)
    assert a.metrics == b.metrics
