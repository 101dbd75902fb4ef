"""The readers of the program's own spans and counters (``repro.obs``):
their formulas on a filled registry, ``None`` where the program has no
tracer or recorded nothing, the trace reduction's gaps under a program
span, and a traced run of each cell on the CPU reporting them."""
import importlib
import sys
import types

import jax
import pytest

from bench import harness
from bench.runners import stream_replay, stream_served
from bench.trace import Trace, reduce_trace

SEED = 2**31 + 4243

SERVED = ("plan_dispatch_ms.served", "plan_readback_ms.served",
          "window_ms.served", "loop_self_ms.served",
          "transfers_per_decision.served")
REPLAY = ("scan_host_ms.replay", "search_solve_share.replay",
          "cut_rerun_share.replay")
NEW = SERVED + REPLAY + ("scan_host_ms.tenants4",)


class _Null:
    def __init__(self, name):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


@pytest.fixture
def obs(monkeypatch):
    """``repro.obs`` recording without a profiler session, on a fake
    clock that ticks 1 ms per reading."""
    from repro import obs
    ticks = iter(range(0, 10**12, 10**6))
    monkeypatch.setattr(obs, "_enabled", lambda: True)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _Null)
    monkeypatch.setattr(obs.time, "perf_counter_ns", lambda: next(ticks))
    obs.reset()
    yield obs
    obs.reset()


def _read(name):
    return harness._reader(name)(None)


def test_readers_on_a_filled_registry(obs):
    # one loop holding two decisions and one window; each span's
    # enter and exit read the clock once, 1 ms apart
    with obs.span("repro.loop"):                     # 0 .. 17 ms
        for _ in range(2):
            with obs.span("repro.plan"):             # 5 ms each
                with obs.span("repro.plan.dispatch"):    # 1 ms
                    obs.count("repro.h2d", 9)
                with obs.span("repro.plan.readback"):    # 1 ms
                    obs.count("repro.d2h", 6)
        with obs.span("repro.window"):               # 3 ms
            with obs.span("repro.window.dispatch"):
                obs.count("repro.h2d", 4)
    assert _read("plan_dispatch_ms.served") == pytest.approx(1.0)
    assert _read("plan_readback_ms.served") == pytest.approx(1.0)
    assert _read("window_ms.served") == pytest.approx(1.5)
    # loop 17 ms, less 2 × 5 ms of plans and 3 ms of window
    assert _read("loop_self_ms.served") == pytest.approx(2.0)
    assert _read("transfers_per_decision.served") == pytest.approx(
        (18 + 12 + 4) / 2)

    obs.reset()
    for _ in range(2):
        with obs.span("repro.scan"):
            with obs.span("repro.scan.prepare"):         # 1 ms
                pass
            with obs.span("repro.scan.wait"):
                pass
            with obs.span("repro.scan.finish"):          # 1 ms
                pass
    obs.count("repro.scan.replans", 300)
    obs.count("repro.scan.search_solves", 100)
    obs.count("repro.scan.n_windows", 400)
    obs.count("repro.scan.cut_reruns", 40)
    assert _read("scan_host_ms.replay") == pytest.approx(2.0)
    assert _read("scan_host_ms.tenants4") == pytest.approx(2.0)
    assert _read("search_solve_share.replay") == pytest.approx(25.0)
    assert _read("cut_rerun_share.replay") == pytest.approx(10.0)


def test_readers_without_records(obs):
    for name in NEW:
        assert _read(name) is None, name


def test_readers_without_the_tracer(monkeypatch):
    # a checkout whose program has no repro.obs
    import repro
    monkeypatch.delattr(repro, "obs", raising=False)
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    with pytest.raises(ImportError):
        importlib.import_module("repro.obs")
    for name in NEW:
        assert _read(name) is None, name


def test_a_gap_under_a_program_span_goes_to_it():
    # chip idle [0, 10), [30, 60), [70, 75); a program span inside the
    # item holds the last two gaps, its child the middle one
    t = Trace(ops={"/device:TPU:0": [("a", 10, 30), ("b", 60, 70),
                                     ("c", 75, 100)]},
              programs={},
              spans=[("bench.window", 0, 100), ("bench.tape", 0, 100),
                     ("repro.scan", 15, 85), ("repro.scan.prepare", 30, 60)])
    gaps = dict(reduce_trace(t).idle_gaps)
    assert gaps == {"repro.scan.prepare": pytest.approx(30e-9),
                    "repro.scan": pytest.approx(5e-9),
                    "bench.tape": pytest.approx(10e-9)}


def _traced(cell, runner, n_arrivals):
    spec = harness.load_cell(cell)
    spec.mix["tape"]["n_arrivals"] = n_arrivals
    spec.mix["pool"] = 2
    return harness.run_cell(cell, SEED, 0.2, True,
                            require_accelerator=False, spec=spec,
                            runner_module=types.SimpleNamespace(
                                Runner=runner.Runner))


@pytest.mark.parametrize("cell,runner,names", [
    ("day_stream.served", stream_served, SERVED),
    ("day_stream.replay", stream_replay, REPLAY),
    ("tenant_pool_t4.replay", stream_replay, ("scan_host_ms.tenants4",)),
])
def test_a_traced_run_reports_them(cell, runner, names):
    from repro import obs
    obs.reset()
    result = _traced(cell, runner, 96)
    obs.reset()
    assert result["correct"], result["checks"]
    got = result["metrics"]
    for name in names:
        assert name in got and got[name]["value"] >= 0.0, name
    if cell == "day_stream.served":
        parts = (got["plan_dispatch_ms.served"]["value"]
                 + got["plan_readback_ms.served"]["value"])
        assert parts <= got["decision_p95_ms.served"]["value"]
