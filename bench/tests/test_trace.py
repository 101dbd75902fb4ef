"""The trace reduction, on a hand-made trace and on a small recorded one
(``data/trace_small.json``, written by ``record_trace.py`` on a TPU v5e)."""
import pathlib

import pytest

from bench.trace import Trace, load_json, reduce_trace

DATA = pathlib.Path(__file__).resolve().parent / "data" / "trace_small.json"


def _hand_made():
    # window [0, 100); chip 0 busy [10, 30) u [20, 40) u [60, 70);
    # chip 1 busy [0, 50) and an op outside the window
    ops = {"/device:TPU:0": [("a", 10, 30), ("b", 20, 40), ("a", 60, 70)],
           "/device:TPU:1": [("a", 0, 50), ("c", 150, 200)]}
    progs = {"/device:TPU:0": [("jit_f", 10, 40), ("jit_f", 60, 70)],
             "/device:TPU:1": [("jit_f", 0, 50)]}
    spans = [("bench.window", 0, 100), ("bench.tape", 0, 55),
             ("bench.decision", 40, 52), ("bench.tape", 55, 100)]
    return Trace(ops=ops, programs=progs, spans=spans)


def test_busy_union_idle_and_ops():
    s = reduce_trace(_hand_made())
    assert s.n_chips == 2
    assert s.window_s == pytest.approx(100e-9)
    # chip 0: [10, 40) u [60, 70) = 40 busy, chip 1: 50 busy -> mean 45
    assert s.busy_s == pytest.approx(45e-9)
    assert s.idle_share == pytest.approx(0.55)
    ops = dict(s.device_ops)
    assert ops["a"] == pytest.approx((20 + 10 + 50) / 2 * 1e-9)
    assert ops["b"] == pytest.approx(20 / 2 * 1e-9)
    assert "c" not in ops
    assert dict(s.programs)["jit_f"] == pytest.approx((40 + 50) / 2 * 1e-9)


def test_idle_gaps_go_to_the_innermost_span():
    s = reduce_trace(_hand_made(), chips=["/device:TPU:0"])
    gaps = dict(s.idle_gaps)
    # chip 0 idles [0,10) tape, [40,60) mid 50 decision, [70,100) tape
    assert gaps["bench.tape"] == pytest.approx(40e-9)
    assert gaps["bench.decision"] == pytest.approx(20e-9)
    assert sum(gaps.values()) == pytest.approx(s.window_s - s.busy_s)


def test_a_gap_outside_every_span_goes_to_the_window():
    t = Trace(ops={"d": [("x", 0, 10)]}, programs={},
              spans=[("bench.window", 0, 20)])
    assert dict(reduce_trace(t).idle_gaps) == {"bench.window":
                                               pytest.approx(10e-9)}


def test_one_window_span_is_required():
    t = _hand_made()
    t.spans.append(("bench.window", 0, 5))
    with pytest.raises(ValueError):
        reduce_trace(t)


def _sweep_busy(events, lo, hi):
    """Busy time by an event sweep: a second way to take the union."""
    edges = sorted([(max(s, lo), 1) for _, s, e in events if e > lo and s < hi]
                   + [(min(e, hi), -1) for _, s, e in events
                      if e > lo and s < hi])
    busy, depth, last = 0, 0, None
    for t, d in edges:
        if depth > 0:
            busy += t - last
        depth += d
        last = t
    return busy


def test_recorded_trace():
    tr = load_json(str(DATA))
    assert tr.ops, "the recorded trace holds device operations"
    s = reduce_trace(tr)
    lo, hi = [(a, b) for n, a, b in tr.spans if n == "bench.window"][0]
    want = sum(_sweep_busy(v, lo, hi) for v in tr.ops.values()) / len(tr.ops)
    assert s.busy_s == pytest.approx(want * 1e-9)
    assert 0.0 < s.busy_s <= s.window_s
    assert 0.0 <= s.idle_share < 1.0
    assert sum(v for _, v in s.idle_gaps) == pytest.approx(
        s.window_s - s.busy_s)
    assert all(n.startswith("bench.") for n, _ in s.idle_gaps)
    assert len(s.device_ops) <= 10 and s.device_ops[0][1] > 0
