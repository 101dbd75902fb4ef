"""The benchmark's one traffic generator: inputs from a seed and a mix file.

Every draw is conditioned on its count, so every seed gives the program
inputs of the same shapes and each compiled program is found again in
the compile cache.  The arrival process follows the repository's
day-long sampler (a diurnal nonhomogeneous Poisson process, thinned
against its peak rate, slowdown weights w = 1/x, paired budget dips
and recoveries), with two changes: a tape holds exactly ``n_arrivals``
arrivals, read from a seed-chosen phase of the day, and exactly
``n_budget_dips`` dips, each with its recovery inside the tape.
"""
from __future__ import annotations

import dataclasses

import numpy as np

__all__ = ["Tape", "rng_for", "draw_tape", "draw_tapes", "burst"]

# separate random streams of one seed, by purpose
STREAM_POOL, STREAM_WARM, STREAM_CHECK = 0, 1, 2


def rng_for(seed: int, purpose: int, index: int = 0) -> np.random.Generator:
    """A generator for one purpose of one seed (any whole number)."""
    seed = int(seed)
    return np.random.default_rng([abs(seed), int(seed < 0), purpose, index])


@dataclasses.dataclass(frozen=True)
class Tape:
    """One controller's input: N arrivals, budget steps, an end mark."""

    t: np.ndarray              # (N,) arrival times, non-decreasing
    x: np.ndarray              # (N,) job sizes
    w: np.ndarray              # (N,) weights
    deadline: np.ndarray       # (N,) absolute deadlines
    horizon: float             # the end mark
    budget_times: np.ndarray   # (S,) sorted
    budget_values: np.ndarray  # (S,) absolute budget after each step

    @property
    def n_events(self) -> int:
        """Control-plane events: arrivals, budget steps and the end mark."""
        return int(self.t.size + self.budget_times.size + 1)


def draw_tape(rng: np.random.Generator, mix: dict, B: float) -> Tape:
    """One tape of the mix ``mix`` (a traffic file's ``tape`` group)."""
    n = int(mix["n_arrivals"])
    rate = float(mix["rate"])
    diurnal = float(mix["diurnal"])
    period = float(mix["period_s"])
    lam_max = rate * (1.0 + diurnal)
    phase = rng.uniform(0.0, period)
    times = []
    t_now, kept = 0.0, 0
    while kept < n:
        chunk = max(2 * (n - kept), 64)
        cand = t_now + np.cumsum(rng.exponential(1.0 / lam_max, chunk))
        lam = rate * (1.0 + diurnal * np.sin(
            2.0 * np.pi * (cand + phase) / period - 0.5 * np.pi))
        keep = cand[rng.uniform(0.0, lam_max, chunk) < lam]
        times.append(keep[:n - kept])
        kept += min(keep.size, n - kept)
        t_now = float(cand[-1])
    t = np.concatenate(times)
    lo, hi = mix["size_range"]
    x = rng.uniform(float(lo), float(hi), n)
    if mix["weights"] != "slowdown":
        raise ValueError(f"unknown weights {mix['weights']!r}")
    w = 1.0 / x
    deadline = t + float(mix["deadline_slack"]) * x
    span = float(t[-1])
    k = int(mix["n_budget_dips"])
    dips = np.sort(rng.uniform(0.0, span, k))
    recov = np.minimum(dips + rng.exponential(
        float(mix["dip_mean_frac"]) * span, k), span)
    flo, fhi = mix["budget_frac"]
    bt = np.concatenate([dips, recov])
    bv = np.concatenate([B * rng.uniform(float(flo), float(fhi), k),
                         np.full(k, B)])
    order = np.argsort(bt, kind="stable")
    return Tape(t=t, x=x, w=w, deadline=deadline,
                horizon=span + float(mix["drain_s"]),
                budget_times=bt[order], budget_values=bv[order])


def burst(tape: Tape, n: int) -> Tape:
    """The tape with its first ``n`` arrivals squeezed into one second,
    so that jobs queue for slots (a warm-up that reaches the backfill
    path); the shapes stay those of the tape."""
    t = tape.t.copy()
    n = min(n, t.size)
    t[:n] = t[0] + (t[:n] - t[0]) * (1.0 / max(t[n - 1] - t[0], 1.0))
    return dataclasses.replace(tape, t=t, deadline=tape.deadline
                               - tape.t + t)


def draw_tapes(seed: int, purpose: int, count: int, mix: dict,
               B: float) -> list[Tape]:
    return [draw_tape(rng_for(seed, purpose, i), mix, B)
            for i in range(count)]
