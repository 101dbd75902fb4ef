"""Every seed gives the program inputs of the same shapes."""
import json
import pathlib

import numpy as np
import pytest

from bench import generate

TRAFFIC = pathlib.Path(__file__).resolve().parents[1] / "traffic"
SEEDS = [0, 1, 977, 2**31 - 1, 2**31 + 12345, 2**40 + 3, -8]


def _mix(name):
    return json.loads((TRAFFIC / f"{name}.json").read_text())["tape"]


@pytest.mark.parametrize("traffic", ["replay", "served"])
def test_tapes_have_one_shape_for_every_seed(traffic):
    mix = _mix(traffic)
    shapes = set()
    for seed in SEEDS:
        for purpose in (generate.STREAM_POOL, generate.STREAM_WARM):
            tape = generate.draw_tape(generate.rng_for(seed, purpose), mix,
                                      10.0)
            shapes.add((tape.t.shape, tape.x.shape, tape.w.shape,
                        tape.deadline.shape, tape.budget_times.shape,
                        tape.budget_values.shape, tape.n_events))
    n = mix["n_arrivals"]
    k = 2 * mix["n_budget_dips"]
    assert shapes == {((n,), (n,), (n,), (n,), (k,), (k,), n + k + 1)}


@pytest.mark.parametrize("seed", SEEDS)
def test_tape_is_a_valid_trace(seed):
    mix = _mix("replay")
    tape = generate.draw_tape(generate.rng_for(seed, 0), mix, 10.0)
    assert np.all(np.diff(tape.t) >= 0) and tape.t[0] >= 0
    lo, hi = mix["size_range"]
    assert np.all((tape.x >= lo) & (tape.x <= hi))
    np.testing.assert_allclose(tape.w, 1.0 / tape.x)
    assert np.all(np.diff(tape.budget_times) >= 0)
    assert np.all(tape.budget_times <= tape.t[-1])
    assert tape.horizon == pytest.approx(tape.t[-1] + mix["drain_s"])
    dips = tape.budget_values[tape.budget_values < 10.0]
    assert dips.size == mix["n_budget_dips"]
    assert np.all((dips >= 3.0) & (dips <= 8.0))


def test_same_seed_same_tape_other_seed_other_tape():
    mix = _mix("replay")
    a = generate.draw_tape(generate.rng_for(2**31 + 5, 0, 3), mix, 10.0)
    b = generate.draw_tape(generate.rng_for(2**31 + 5, 0, 3), mix, 10.0)
    c = generate.draw_tape(generate.rng_for(2**31 + 6, 0, 3), mix, 10.0)
    np.testing.assert_array_equal(a.t, b.t)
    np.testing.assert_array_equal(a.x, b.x)
    assert not np.array_equal(a.x, c.x)


def test_diurnal_phase_moves_the_rate():
    """Arrivals follow the day: the mean rate over a tape stays near the
    mix's rate, and the phase of the day differs between seeds."""
    mix = _mix("replay")
    rates = [mix["n_arrivals"] / generate.draw_tape(
        generate.rng_for(s, 0), mix, 10.0).t[-1] for s in range(16)]
    assert 0.3 * mix["rate"] < np.median(rates) < 2.0 * mix["rate"]
    assert np.std(rates) > 0


def test_burst_queues_the_first_arrivals():
    mix = _mix("served")
    tape = generate.draw_tape(generate.rng_for(3, 1), mix, 10.0)
    b = generate.burst(tape, 48)
    assert b.t[47] - b.t[0] <= 1.0 + 1e-9
    np.testing.assert_array_equal(b.t[48:], tape.t[48:])
    assert np.all(np.diff(b.t) >= 0)
