"""``correct`` comes out false when it should.

Each test drives the rest of a run through ``harness.run_cell`` on the
CPU (the look for a chip skipped) at a size a test run can hold, with
the timed path replaced: by the control (the plain reference computed
in bfloat16, the precision below the configuration's float32), or by
the program with one fault planted where it produces its answer.  A
sound run of the same size comes out true.
"""
import types

import ml_dtypes
import numpy as np
import pytest

from bench import harness
from bench.calibrate import control_runner
from bench.runners import _stream, stream_replay, stream_served

SEED = 2**31 + 4242


def _spec(cell, n_arrivals):
    spec = harness.load_cell(cell)
    spec.mix["tape"]["n_arrivals"] = n_arrivals
    spec.mix["pool"] = 4
    return spec


def _run(cell, runner_cls, n_arrivals=192, seconds=0.2, trace=False):
    spec = _spec(cell, n_arrivals)
    mod = types.SimpleNamespace(Runner=runner_cls)
    return harness.run_cell(cell, SEED, seconds, trace,
                            require_accelerator=False, spec=spec,
                            runner_module=mod)


class _Res:
    def __init__(self, completion, replans=1, cold=0):
        self.completion = completion
        self.replans = replans
        self.cold_replans = cold


def _patched(base, serve):
    """``base`` with its timed path ``serve_tapes`` replaced."""
    class D(base):
        def serve_tapes(self, tapes):
            return serve(self, base.serve_tapes, tapes)
    return D


# -- the faults ------------------------------------------------------------

def _unchanged(self, inner, tapes):
    """A step that returns its state unchanged: nothing is served."""
    return [_Res(np.full(t.t.size, np.inf)) for t in tapes]


def _half_left_out(self, inner, tapes):
    """Half of each tape's arrivals left out of the batch served."""
    out = []
    for t in tapes:
        h = t.t.size // 2
        half = type(t)(t=t.t[:h], x=t.x[:h], w=t.w[:h],
                       deadline=t.deadline[:h], horizon=t.horizon,
                       budget_times=t.budget_times,
                       budget_values=t.budget_values)
        self.stream_of[id(half)] = _stream.arrival_stream(half)
        res, = inner(self, [half])
        c = np.full(t.t.size, np.inf)
        c[:h] = res.completion
        out.append(_Res(c))
    return out


def _answer_altered(self, inner, tapes):
    """Completions altered where the program produces them: one job in
    every sixteen finishes 1 s late, more jobs than the ``_trim`` numbers
    leave out (a job or two 1 s late are among those, PERF.md 4.1)."""
    out = []
    for r in inner(self, tapes):
        c = np.array(r.completion)
        c[::16] += 1.0
        out.append(_Res(c))
    return out


def _completed_at_arrival(self, inner, tapes):
    """One job reported complete at its arrival, before it could run."""
    out = []
    for t, r in zip(tapes, inner(self, tapes)):
        c = np.array(r.completion)
        c[len(c) // 3] = t.t[len(c) // 3]
        out.append(_Res(c))
    return out


def _exchange_left_out(self, inner, tapes):
    """Every tenant gets the first chip's answer: the results of the
    other chips never reach the host."""
    out = inner(self, tapes)
    return [_Res(out[0].completion[:t.t.size]) for t in tapes]


# -- the tests -------------------------------------------------------------

@pytest.mark.parametrize("cell,base,trace", [
    ("day_stream.replay", stream_replay.Runner, False),
    ("day_stream.served", stream_served.Runner, False),
    ("day_stream.served", stream_served.Runner, True),
])
def test_sound_run_is_correct(cell, base, trace):
    res = _run(cell, base, trace=trace)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert list(res["checks"])[-1] == "compiles_in_window"
    spec = harness.load_cell(cell)
    want = spec.per_layer if trace else spec.end_to_end
    assert set(res["metrics"]) == {m["name"] for m in want}
    if trace:
        assert 0 < res["device"]["busy_s"] <= res["device"]["window_s"]
        assert res["breakdown"]["device_ops"]


@pytest.mark.parametrize("cell,base,n_arrivals", [
    ("day_stream.replay", stream_replay.Runner, 192),
    ("day_stream.served", stream_served.Runner, 192),
    ("tenant_pool_t4.replay", stream_replay.Runner, 96),
])
def test_control_fails(cell, base, n_arrivals):
    class Control(base):
        def __init__(self, *a):
            super().__init__(*a)
            control_runner(self)

    res = _run(cell, Control, n_arrivals=n_arrivals)
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("fault", [_unchanged, _half_left_out,
                                   _answer_altered, _completed_at_arrival])
@pytest.mark.parametrize("cell,base", [
    ("day_stream.replay", stream_replay.Runner),
    ("day_stream.served", stream_served.Runner),
])
def test_fault_fails(cell, base, fault):
    res = _run(cell, _patched(base, fault))
    assert not res["correct"], (fault.__name__, res["checks"])
    assert res["failed"] >= 1


def test_four_tenants_sound_and_faults():
    cell = "tenant_pool_t4.replay"
    res = _run(cell, stream_replay.Runner, n_arrivals=96)
    assert res["correct"], res["checks"]
    assert res["device"]["count"] == 4
    for fault in (_exchange_left_out, _answer_altered,
                  _completed_at_arrival, _unchanged):
        res = _run(cell, _patched(stream_replay.Runner, fault),
                   n_arrivals=96)
        assert not res["correct"], (fault.__name__, res["checks"])


def test_bfloat16_reference_departs_from_float64():
    spec = _spec("day_stream.replay", 192)
    tape = _stream.pool(spec, SEED)[0][0]
    ref = _stream.reference(spec.config, tape)
    low = _stream.reference(spec.config, tape, ml_dtypes.bfloat16)
    d = _stream.compare_one(tape, low.completion, ref.completion,
                            _stream.full_rate(spec.config))
    assert any(d[k] > lim for k, lim in spec.limits.items()), d
