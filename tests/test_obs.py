"""The in-process tracer (``repro.obs``): spans nest and give self time,
nothing is recorded (or built) without a profiler session, and under one
the control plane's spans and counters appear."""
import jax
import numpy as np
import pytest

from repro import obs
from repro.core import power, sample_arrival_stream
from repro.serve import StreamCascadePolicy, StreamController
from repro.serve.stream import _EXCESS_BATCH

B = 10.0
SP = power(1.0, 0.5, B)


class _Null:
    def __init__(self, name):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


@pytest.fixture
def recording(monkeypatch):
    """Recording on without a profiler session, on a clean registry."""
    monkeypatch.setattr(obs, "_enabled", lambda: True)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _Null)
    obs.reset()
    yield
    obs.reset()


def test_nesting_and_self_time(recording, monkeypatch):
    # a [0, 100) holds b [10, 30) and c [40, 70); c holds d [50, 60)
    ticks = iter([0, 10, 30, 40, 50, 60, 70, 100])
    monkeypatch.setattr(obs.time, "perf_counter_ns", lambda: next(ticks))
    with obs.span("a"):
        with obs.span("b"):
            pass
        with obs.span("c"):
            with obs.span("d"):
                pass
    obs.count("n")
    obs.count("n", 4)
    t = obs.totals()
    ns = 1e-9
    want = {"a": (100, 50), "b": (20, 20), "c": (30, 20), "d": (10, 10)}
    for name, (total, self_) in want.items():
        row = t["spans"][name]
        assert row["count"] == 1
        assert row["total_s"] == pytest.approx(total * ns)
        assert row["self_s"] == pytest.approx(self_ * ns)
    assert t["counters"] == {"n": 5}
    parents = {name: parent for name, parent, *_ in obs._spans}
    assert parents == {"a": None, "b": "a", "c": "a", "d": "c"}


def test_spans_repeat_and_sum(recording):
    for _ in range(3):
        with obs.span("repro.x"):
            with obs.span("repro.x.y"):
                pass
    t = obs.totals()["spans"]
    assert t["repro.x"]["count"] == 3 and t["repro.x.y"]["count"] == 3
    assert t["repro.x"]["self_s"] == pytest.approx(
        t["repro.x"]["total_s"] - t["repro.x.y"]["total_s"])
    obs.reset()
    assert obs.totals() == {"spans": {}, "counters": {}}


def test_off_without_a_profiler_session(monkeypatch):
    built = []

    class Counting(jax.profiler.TraceAnnotation):
        def __init__(self, name, **kw):
            built.append(name)
            super().__init__(name, **kw)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Counting)
    obs.reset()
    assert not jax.profiler.TraceAnnotation.is_enabled()
    with obs.span("repro.loop"):
        obs.count("repro.h2d", 3)
    assert built == []
    assert obs.totals() == {"spans": {}, "counters": {}}


def test_host_leaves_counts_what_a_call_uploads():
    import jax.numpy as jnp
    assert obs.host_leaves(jnp.ones(3), np.ones(2), 1.0,
                           (2, jnp.zeros(1))) == 3
    assert obs.host_leaves(SP) == 0


def _stream(seed=5, M=4):
    stream = sample_arrival_stream(seed, horizon=300.0, rate=0.2, B=B,
                                   n_budget_events=1)
    ctl = StreamController(SP, B, max_live=M,
                           policy=StreamCascadePolicy(SP, B))
    return stream, ctl


def test_served_loop_spans_under_a_profiler_session(tmp_path):
    stream, ctl = _stream()
    ctl.run(stream)                        # compile outside the session
    obs.reset()
    with jax.profiler.trace(str(tmp_path)):
        res = ctl.run(stream)
    t = obs.totals()
    obs.reset()
    spans, counters = t["spans"], t["counters"]
    assert spans["repro.loop"]["count"] == 1
    assert spans["repro.plan"]["count"] == res.replans > 0
    assert spans["repro.window"]["count"] == res.n_windows > 0
    plan = spans["repro.plan"]
    parts = (spans["repro.plan.dispatch"]["total_s"]
             + spans["repro.plan.readback"]["total_s"])
    assert parts <= plan["total_s"]
    assert plan["self_s"] == pytest.approx(plan["total_s"] - parts)
    assert spans["repro.loop"]["self_s"] < spans["repro.loop"]["total_s"]
    _assert_exact_transfers(counters, res)


def _assert_exact_transfers(counters, res):
    """One upload and one read per decision and per window, one more
    each per cut; the budget check uploads two arrays and reads one per
    batch of plans."""
    assert res.degraded_windows == 0         # no host-built ladder table
    batches = -(-res.replans // _EXCESS_BATCH)
    calls = res.replans + res.n_windows + res.cut_reruns
    assert counters["repro.d2h"] == calls + batches
    assert counters["repro.h2d"] == calls + 2 * batches


def _queued_stream(M):
    """A busy tape: jobs queue, so windows are cut at completions, and
    random weights send plans to the exchange search."""
    stream = sample_arrival_stream(5, horizon=300.0, rate=1.0, B=B,
                                   weights="random", n_budget_events=2)
    ctl = StreamController(SP, B, max_live=M,
                           policy=StreamCascadePolicy(SP, B))
    return stream, ctl


def test_served_loop_makes_no_implicit_upload(recording, monkeypatch):
    stream, ctl = _queued_stream(6)
    ctl.run(stream)                        # compile; the warm payload
    obs.reset()
    # after the loop, the per-tape summary evaluates s(B) eagerly, one
    # implicit upload per tape and no part of an event's work
    finalize = ctl._finalize

    def finalize_allowed(*args, **kw):
        with jax.transfer_guard_host_to_device("allow"):
            return finalize(*args, **kw)

    monkeypatch.setattr(ctl, "_finalize", finalize_allowed)
    with jax.transfer_guard_host_to_device("disallow"):
        res = ctl.run(stream)
    assert res.cut_reruns > 0 and res.search_solves > 0
    _assert_exact_transfers(obs.totals()["counters"], res)


def test_a_cut_window_traces_the_window_entry_once(recording):
    from repro.sched.policies import _cascade_packed
    from repro.serve.stream import _exec_window, _window_packed
    for fn in (_cascade_packed, _window_packed, _exec_window):
        fn.clear_cache()                   # the traces counted are ours
    stream, ctl = _queued_stream(3)
    res = ctl.run(stream)
    assert res.cut_reruns > 0
    counters = obs.totals()["counters"]
    assert counters["repro.retrace._window_packed"] == 1
    assert counters["repro.retrace._exec_window"] == 1
    assert counters["repro.retrace._cascade_packed"] == 1


def test_scan_counters_under_a_profiler_session(tmp_path):
    stream, ctl = _stream()
    ctl.run_device(stream)
    obs.reset()
    with jax.profiler.trace(str(tmp_path)):
        res = ctl.run_device(stream)
    t = obs.totals()
    obs.reset()
    spans, counters = t["spans"], t["counters"]
    for name in ("repro.scan", "repro.scan.prepare", "repro.scan.wait",
                 "repro.scan.finish", "repro.scan.finish.read"):
        assert spans[name]["count"] == 1
    assert counters["repro.scan.replans"] == res.replans > 0
    assert counters["repro.scan.n_windows"] == res.n_windows
    assert counters["repro.scan.search_solves"] == res.search_solves
    assert counters["repro.scan.cut_reruns"] == res.cut_reruns
    # the results and every counter come back in one read
    assert counters["repro.d2h"] == 9


def test_a_new_tape_length_retraces_the_scan_once(tmp_path):
    short, ctl = _stream(seed=5)
    longer = sample_arrival_stream(6, horizon=600.0, rate=0.2, B=B,
                                   n_budget_events=1)
    assert len(longer) != len(short)
    ctl.run_device(short)
    obs.reset()
    with jax.profiler.trace(str(tmp_path)):
        ctl.run_device(short)
        before = dict(obs.totals()["counters"])
        ctl.run_device(longer)
        after = obs.totals()["counters"]
    obs.reset()
    assert before.get("repro.retrace._stream_chunk", 0) == 0
    assert after["repro.retrace._stream_chunk"] == 1
    # the cascade is traced inside the scan's program
    assert after["repro.retrace.stream_replan_core"] >= 1
