"""What the stream runners share: the controller a configuration states,
its tapes, and the comparison of served tapes with the reference."""
from __future__ import annotations

import numpy as np

from bench import generate
from bench.reference.stream import simulate_tape

# dtype of the program's timed path, by the configuration's word
PRECISION = {"float32": np.float32}


def full_rate(cfg) -> float:
    """s(B): the rate of one job served by the whole pool, the fastest
    any job can run (the live budget B(t) never exceeds B)."""
    sp = cfg["speedup"]
    return float(sp["a"]) * float(cfg["B"]) ** float(sp["p"])


def speedup(cfg):
    from repro.core import power
    sp = cfg["speedup"]
    if sp["family"] != "power":
        raise ValueError(f"unknown speedup family {sp['family']!r}")
    return power(float(sp["a"]), float(sp["p"]), float(cfg["B"]))


def check_precision(cfg):
    """The program runs as the configuration states: x64 stays off."""
    import jax.numpy as jnp
    want = PRECISION[cfg["precision"]]
    got = jnp.result_type(float)
    if np.dtype(got) != np.dtype(want):
        raise RuntimeError(f"the configuration states {cfg['precision']}, "
                           f"JAX computes in {np.dtype(got).name}")


def controller(cfg, policy_wrap=None):
    from repro.serve import StreamCascadePolicy, StreamController
    sp = speedup(cfg)
    B = float(cfg["B"])
    policy = StreamCascadePolicy(sp, B,
                                 certificate_rtol=float(cfg["certificate_rtol"]))
    if policy_wrap is not None:
        policy = policy_wrap(policy)
    return StreamController(sp, B, max_live=int(cfg["max_live"]),
                            policy=policy, rtol=float(cfg["window_rtol"]))


def arrival_stream(tape):
    from repro.core.workloads import ArrivalStream
    return ArrivalStream(t=tape.t, x=tape.x, w=tape.w,
                         deadline=tape.deadline, horizon=tape.horizon,
                         budget_times=tape.budget_times,
                         budget_values=tape.budget_values)


def pool(spec, seed):
    """The cell's input pool and warm-up tapes, all of one shape."""
    mix, B = spec.mix, float(spec.config["B"])
    tapes = generate.draw_tapes(seed, generate.STREAM_POOL,
                                int(mix["pool"]), mix["tape"], B)
    # warm-up tapes open with a burst that fills every slot and queues,
    # so the backfill path's programs are loaded before the window
    warm = [generate.burst(t, 3 * int(spec.config["max_live"]))
            for t in generate.draw_tapes(seed, generate.STREAM_WARM,
                                         int(mix["warm"]), mix["tape"], B)]
    return tapes, warm


def reference(cfg, tape, dtype=np.float64):
    sp = cfg["speedup"]
    eff = max(float(cfg["certificate_rtol"]),
              64.0 * float(np.finfo(PRECISION[cfg["precision"]]).eps))
    return simulate_tape(tape, a=float(sp["a"]), p=float(sp["p"]),
                         B=float(cfg["B"]), max_live=int(cfg["max_live"]),
                         cert_rtol=eff, rtol=float(cfg["window_rtol"]),
                         dtype=dtype)


# share of a tape's jobs left out of the ``_trim`` numbers: those whose
# latency departs most from the reference's.  The float32 event scan and
# the float64 reference can take different, equally certified orders for
# a few jobs of one busy period (PERF.md 4.1); every other job must still
# agree.
TRIM_SHARE = 0.01


def trim_count(n: int) -> int:
    """Jobs left out of the ``_trim`` numbers of a tape of ``n`` jobs."""
    return int(np.ceil(TRIM_SHARE * n))


ROWS = ("jobs_not_completed", "reference_jobs_not_completed",
        "early_completion_s", "weighted_J_rel", "p99_latency_rel",
        "latency_max_abs_s", "weighted_J_rel_trim", "p99_latency_rel_trim",
        "latency_gap_trim_s")


def _quality(w, lat, rlat, suffix=""):
    J = float(np.sum(w * lat))
    rJ = float(np.sum(w * rlat))
    p99 = float(np.percentile(lat, 99))
    rp99 = float(np.percentile(rlat, 99))
    return {"weighted_J_rel" + suffix: abs(J - rJ) / max(abs(rJ), 1e-300),
            "p99_latency_rel" + suffix: abs(p99 - rp99) / max(abs(rp99),
                                                             1e-300)}


def compare_one(tape, completion, ref_completion, rate) -> dict:
    """The numbers compared for one served tape; ``rate`` is s(B)."""
    n = tape.t.size
    done = np.isfinite(completion)
    rdone = np.isfinite(ref_completion)
    lat = completion - tape.t
    rlat = ref_completion - tape.t
    both = done & rdone
    out = {"jobs_not_completed": float(n - done.sum()),
           "reference_jobs_not_completed": float(n - rdone.sum())}
    k = trim_count(n)
    if both.sum() <= k:
        out.update(dict.fromkeys(ROWS[2:], np.inf))
        return out
    # how far a job finished before it could have: its arrival plus its
    # size served alone by the whole pool
    out["early_completion_s"] = float(np.max(tape.x[done] / rate
                                             - lat[done]))
    out.update(_quality(tape.w, lat, rlat) if both.all() else
               {"weighted_J_rel": np.inf, "p99_latency_rel": np.inf})
    gap = np.abs(lat[both] - rlat[both])
    out["latency_max_abs_s"] = float(np.max(gap))
    keep = np.ones(gap.size, bool)
    keep[np.argsort(gap)[gap.size - k:]] = False
    out["latency_gap_trim_s"] = float(np.max(gap[keep]))
    out.update(_quality(tape.w[both][keep], lat[both][keep],
                        rlat[both][keep], "_trim"))
    return out


class Checked:
    """Per-tape comparisons of a run, folded to one row per number."""

    def __init__(self, limits, rate):
        self.limits, self.rate = limits, rate
        self.per_tape: list[dict] = []

    def add(self, tape, completion, ref_completion):
        self.per_tape.append(compare_one(tape, completion, ref_completion,
                                         self.rate))

    def rows(self):
        """(number, worst reading, limit) for each number the cell's
        limits file names."""
        if not self.per_tape:
            return [("tapes_compared", 0.0, -1.0)]
        return [(k, max(d[k] for d in self.per_tape), float(self.limits[k]))
                for k in ROWS if k in self.limits]

    def failed_tapes(self) -> int:
        def bad(d):
            return any(not (np.isfinite(d[k]) and d[k] <= self.limits[k])
                       for k in ROWS if k in self.limits)
        return sum(bad(d) for d in self.per_tape)


def sample(seed, n_items, n_check):
    """Which items of the window are compared: drawn from the seed."""
    rng = generate.rng_for(seed, generate.STREAM_CHECK)
    k = min(n_items, n_check)
    return sorted(rng.choice(n_items, size=k, replace=False).tolist())
