"""The plain reference serves a tape as the program does.

In float64 on the CPU the program's host loop and its device scan
(``StreamController.run`` / ``run_device`` with the cascade policy)
and the reference agree to rounding: the same decisions, the same
completions.  The reference imports nothing of the program; this test
is where the two meet.
"""
import json
import pathlib

import jax
import numpy as np
import pytest

from bench import generate
from bench.runners import _stream

BENCH = pathlib.Path(__file__).resolve().parents[1]
CFG = json.loads((BENCH / "configs" / "day_stream.json").read_text())


def _tape(seed, n):
    mix = json.loads((BENCH / "traffic" / "replay.json").read_text())["tape"]
    mix = dict(mix, n_arrivals=n)
    return generate.draw_tape(generate.rng_for(seed, 0), mix, CFG["B"])


@pytest.mark.parametrize("seed,burst", [(5, 0), (2**31 + 77, 0), (9, 48)])
def test_reference_equals_program_in_float64(seed, burst):
    tape = _tape(seed, 160)
    if burst:
        tape = generate.burst(tape, burst)
    with jax.enable_x64(True):
        cfg = dict(CFG)
        ctl = _stream.controller(cfg)
        dev = ctl.run_device(_stream.arrival_stream(tape))
        host = _stream.controller(cfg).run(_stream.arrival_stream(tape))
    # in float64 the certificate's floor is float64's, as in the program
    ref = _stream.simulate_tape(
        tape, a=1.0, p=0.5, B=CFG["B"], max_live=CFG["max_live"],
        cert_rtol=CFG["certificate_rtol"], rtol=CFG["window_rtol"])
    assert np.isfinite(ref.completion).all()
    for got in (dev.completion, host.completion):
        np.testing.assert_allclose(got, ref.completion, rtol=1e-9,
                                   atol=1e-9)
    assert dev.replans == ref.replans


def test_compare_one_reads_zero_on_equal_and_sees_one_altered_job():
    tape = _tape(3, 64)
    rate = _stream.full_rate(CFG)
    ref = _stream.reference(CFG, tape).completion
    same = _stream.compare_one(tape, ref.copy(), ref, rate)
    assert same["weighted_J_rel"] == 0.0
    assert same["latency_max_abs_s"] == 0.0
    assert same["jobs_not_completed"] == 0.0
    # the reference never finishes a job before it could
    assert same["early_completion_s"] <= 1e-9
    assert same["latency_gap_trim_s"] == 0.0
    assert same["weighted_J_rel_trim"] == 0.0
    bad = ref.copy()
    bad[10] += 1.0
    d = _stream.compare_one(tape, bad, ref, rate)
    assert d["latency_max_abs_s"] == pytest.approx(1.0)
    # one job is among the few the _trim numbers leave out; many are not
    assert d["latency_gap_trim_s"] == 0.0
    bad[20:20 + 2 * _stream.trim_count(tape.t.size)] += 1.0
    d = _stream.compare_one(tape, bad, ref, rate)
    assert d["latency_gap_trim_s"] == pytest.approx(1.0)
    early = ref.copy()
    early[10] = tape.t[10]
    d = _stream.compare_one(tape, early, ref, rate)
    assert d["early_completion_s"] == pytest.approx(tape.x[10] / rate)
    lost = ref.copy()
    lost[:5] = np.inf
    assert _stream.compare_one(tape, lost, ref, rate)[
        "jobs_not_completed"] == 5
