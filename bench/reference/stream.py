"""Plain reference of the streaming control plane, in numpy.

It states what one controller does with one tape, written from the
published algorithms and not from the program under test (it imports
nothing of it):

* events: arrivals, budget steps and the end mark, ordered by time,
  arrivals before budget steps at equal times;
* slots: ``max_live`` of them; arrivals beyond capacity wait in a FIFO
  queue and take the lowest free slot;
* execution: between events the front plan runs; a plan is a completion
  order over the live slots (row ``m-1`` completes first) and a table
  whose column ``j`` holds the allocations of the phase in which rows
  ``0..j`` are live.  A window executor of ``M + 1`` steps reads the
  column of the live count and gives the live rows their allocation by
  rank.  While jobs queue, a window stops at its first completion, the
  freed slot is filled and the controller replans at that time;
* plans land at once (no solve latency);
* the replanning cascade: rank the live jobs by remaining size
  (largest first, smaller weight first at ties) and plan on that order
  with heSRPT's closed form for s(θ) = a θ^p (Berg, Vesilo and
  Harchol-Balter, arXiv:1903.09346; it is SmartFill's Algorithm 2 on the
  power family, arXiv:2509.01811).  The plan is accepted when its
  realized objective J equals its value-function claim J_linear = Σ a_i x_i
  (Prop. 9) to a relative ``cert_rtol``.  Otherwise a steepest-descent
  search over adjacent swaps of the order lowers J, at most ``4 M``
  steps, and its result is accepted under the same test.  Still
  unaccepted, the plan falls back to heSRPT's shares on the
  remaining-size order (the degradation ladder's first rung).

``Precision`` rounds the arithmetic of plans and windows to a narrower
type after every operation; event times stay in float64.  float64 is
the reference; bfloat16 is the control that has to fail the comparison.
"""
from __future__ import annotations

import dataclasses

import ml_dtypes
import numpy as np

__all__ = ["Precision", "StreamOutcome", "simulate_tape"]


class Precision:
    """Round-after-every-operation arithmetic in ``dtype``."""

    def __init__(self, dtype):
        self.dtype = np.dtype(dtype)
        self.exact = self.dtype == np.float64
        fi = ml_dtypes.finfo(self.dtype)
        self.eps = float(fi.eps)
        self.tiny = float(fi.tiny)

    def __call__(self, v):
        if self.exact:
            return np.asarray(v, np.float64)
        return np.asarray(v, np.float64).astype(self.dtype).astype(np.float64)


@dataclasses.dataclass(frozen=True)
class StreamOutcome:
    completion: np.ndarray     # (N,) absolute completion times, inf if none
    replans: int
    searched: int
    fallbacks: int


class _Plan:
    __slots__ = ("order", "table")

    def __init__(self, order, table):
        self.order = order
        self.table = table


class _Cascade:
    def __init__(self, a, p, B_nominal, M, cert_rtol, r: Precision):
        self.a, self.p, self.M, self.r = a, p, M, r
        self.q = 1.0 / (1.0 - p)
        self.B_nominal = B_nominal
        self.cert_rtol = max(cert_rtol, 64.0 * r.eps)
        self.idx = np.arange(M)

    def s(self, th):
        r = self.r
        return r(self.a * r(np.power(np.maximum(th, 0.0), self.p)))

    def table(self, ws, B):
        """heSRPT shares: column k gives row i <= k the share
        B (W_i^q - W_{i-1}^q) / W_k^q, W the cumulative weight from row 0."""
        r, M = self.r, self.M
        W = r(np.cumsum(ws)) if r.exact else self._cumsum(ws)
        Wq = r(np.power(W, self.q))
        prev = np.concatenate([[0.0], Wq[:-1]])
        diff = r(Wq - prev)
        tab = np.zeros((M, M))
        for k in range(M):
            den = max(Wq[k], 1e-300)
            tab[:k + 1, k] = r(r(B * diff[:k + 1]) / den)
        return tab, W

    def _cumsum(self, v):
        out = np.empty_like(v)
        acc = 0.0
        for i, x in enumerate(v):
            acc = float(self.r(acc + x))
            out[i] = acc
        return out

    def solve(self, xs, ws, m, B):
        """(table, J, J_linear) on the row order of ``xs``/``ws``."""
        r, M = self.r, self.M
        tab, W = self.table(ws, B)
        floor = max(B * 1e-9, r.tiny / r.eps)
        for k in range(1, M):
            tab[k, k] = min(max(tab[k, k], floor), B)
        # value coefficients (Prop. 9): a_0 = w_0 / s(B), then
        # a_k = (W_k - sum_{i<k} a_i s(theta_i^k)) / s(theta_k^k)
        a = np.zeros(M)
        if m > 0:
            a[0] = r(ws[0] / self.s(B))
        for k in range(1, m):
            served = r(a[:k] * self.s(tab[:k, k]))
            tot = float(r(np.sum(served))) if r.exact else self._sum(served)
            a[k] = r(r(W[k] - tot) / self.s(tab[k, k]))
        J_lin = float(r(np.sum(r(a * xs)))) if r.exact else self._sum(
            r(a * xs))
        # durations: x_j = sum_{l >= j} s(theta_j^l) d_l, solved from the
        # earliest phase (l = m-1) down, then clamped at zero
        d = np.zeros(M)
        rate = self.s(tab)
        for j in range(m - 1, -1, -1):
            acc = 0.0
            for l in range(j + 1, m):
                acc = float(r(acc + r(rate[j, l] * d[l])))
            d[j] = float(r(r(xs[j] - acc) / rate[j, j]))
        d = np.maximum(d, 0.0)
        T = np.zeros(M)
        acc = 0.0
        for j in range(M - 1, -1, -1):
            acc = float(r(acc + d[j]))
            T[j] = acc
        J = float(r(np.sum(r(ws[:m] * T[:m])))) if r.exact else self._sum(
            r(ws[:m] * T[:m]))
        return tab, J, J_lin

    def _sum(self, v):
        acc = 0.0
        for x in np.asarray(v).ravel():
            acc = float(self.r(acc + x))
        return acc

    def certified(self, J, J_lin):
        if not (np.isfinite(J) and np.isfinite(J_lin)):
            return False
        return abs(J - J_lin) <= self.cert_rtol * max(1.0, abs(J_lin))

    def run_order(self, order, rem, w, m, B):
        live = self.idx < m
        xs = np.where(live, rem[order], 0.0)
        ws = np.where(live, w[order], 0.0)
        return self.solve(xs, ws, m, B)

    def plan(self, rem, w, act, B_live):
        """The accepted plan for the live state; (plan, searched, fell)."""
        M = self.M
        act = act & (rem > 0)
        m = int(act.sum())
        key = np.where(act, -(rem / self.s(self.B_nominal)), np.inf)
        order0 = np.lexsort((np.where(act, w, 0.0), key))
        tab0, J0, L0 = self.run_order(order0, rem, w, m, B_live)
        if self.certified(J0, L0):
            return _Plan(order0[:m], tab0), False, False
        if m <= 1:
            return self._fallback(rem, w, act, m, B_live), False, True
        order, tab, J, L = order0, tab0, J0, L0
        best = J0 if np.isfinite(J0) else np.inf
        for _ in range(4 * M):
            cands = []
            for i in range(m - 1):
                o = order.copy()
                o[i], o[i + 1] = o[i + 1], o[i]
                t_i, J_i, L_i = self.run_order(o, rem, w, m, B_live)
                cands.append((J_i if np.isfinite(J_i) else np.inf, i, o,
                              t_i, L_i))
            Jc, _, o, t_i, L_i = min(cands, key=lambda c: (c[0], c[1]))
            if not Jc < best - 1e-12 * max(1.0, abs(best)):
                break
            order, tab, J, L, best = o, t_i, Jc, L_i, Jc
        if self.certified(J, L):
            return _Plan(order[:m], tab), True, False
        return self._fallback(rem, w, act, m, B_live), True, True

    def _fallback(self, rem, w, act, m, B_live):
        order = np.lexsort((np.where(act, w, 0.0),
                            np.where(act, -rem, np.inf)))
        ws = np.where(self.idx < m, w[order], 0.0)
        tab, _ = self.table(ws, B_live)
        return _Plan(order[:m], tab)


def _exec_window(cascade, table, rem0, live0, span, rtol, r: Precision):
    """M + 1 steps: advance to the earlier of the next completion and the
    window end; returns (rem, live, completion offsets)."""
    M = rem0.shape[0]
    tol = max(rtol, 8.0 * r.eps) * max(1.0, float(np.max(rem0)))
    rem, live = rem0.copy(), live0.copy()
    left, elapsed = float(r(span)), 0.0
    comp = np.full(M, np.inf)
    for _ in range(M + 1):
        m = int(live.sum())
        col = table[:, min(max(m - 1, 0), M - 1)]
        rank = np.clip(np.cumsum(live) - 1, 0, M - 1)
        th = np.where(live, col[rank], 0.0)
        rate = np.where(live, cascade.s(th), 0.0)
        with np.errstate(divide="ignore", invalid="ignore"):
            dt = np.where(live & (rate > 0),
                          r(rem / np.maximum(rate, r.tiny / r.eps)), np.inf)
        h = max(min(float(np.min(dt)), left), 0.0)
        rem2 = np.where(live, np.maximum(r(rem - r(rate * h)), 0.0), rem)
        done = live & (rem2 <= tol)
        comp = np.where(done, float(r(elapsed + h)), comp)
        rem = np.where(done, 0.0, rem2)
        live = live & ~done
        left = float(r(left - h))
        elapsed = float(r(elapsed + h))
        if not live.any() or (h == 0.0 and not done.any()):
            break           # every further step would change nothing
    return rem, live, comp


def simulate_tape(tape, *, a, p, B, max_live, cert_rtol, rtol=1e-12,
                  dtype=np.float64) -> StreamOutcome:
    """Serve one tape (``bench.traffic.Tape``) the way the controller's
    semantics say; returns every job's completion time."""
    r = Precision(dtype)
    M = int(max_live)
    cas = _Cascade(a, p, B, M, cert_rtol, r)
    N = tape.t.size
    x_all, w_all = r(tape.x), r(tape.w)
    events = [(float(tape.t[j]), 0, j) for j in range(N)]
    events += [(float(bt), 1, float(bv))
               for bt, bv in zip(tape.budget_times, tape.budget_values)]
    events.sort(key=lambda e: (e[0], e[1]))
    events.append((float(tape.horizon), 2, 0.0))

    rem = np.zeros(M)
    wslot = np.zeros(M)
    active = np.zeros(M, bool)
    job = np.full(M, -1)
    completion = np.full(N, np.inf)
    queue: list[int] = []
    front = None
    B_live = float(B)
    counts = {"replans": 0, "searched": 0, "fallbacks": 0}

    def fill():
        landed = False
        while queue and not active.all():
            j = queue.pop(0)
            s = int(np.flatnonzero(~active)[0])
            rem[s], wslot[s], active[s], job[s] = x_all[j], w_all[j], True, j
            landed = True
        return landed

    def replan():
        plan, searched, fell = cas.plan(rem, wslot, active, float(r(B_live)))
        counts["replans"] += 1
        counts["searched"] += int(searched)
        counts["fallbacks"] += int(fell)
        return plan

    def execute(plan, t0, t1, cut):
        order = plan.order
        k = order.size
        rows_live = np.zeros(M, bool)
        rows_live[:k] = active[order] & (rem[order] > 0)
        rem_rows = np.zeros(M)
        rem_rows[:k] = rem[order]
        rem_e, _, comp = _exec_window(cas, plan.table, rem_rows, rows_live,
                                      t1 - t0, rtol, r)
        t_end = t1
        fin = np.isfinite(comp)
        if cut and fin.any():
            c0 = float(np.min(comp[fin]))
            if t0 + c0 < t1:
                t_end = t0 + c0
                rem_e, _, comp = _exec_window(cas, plan.table, rem_rows,
                                              rows_live, c0, rtol, r)
        for i in range(k):
            if not rows_live[i]:
                continue
            s = order[i]
            rem[s] = rem_e[i]
            if np.isfinite(comp[i]):
                completion[job[s]] = t0 + comp[i]
                active[s], job[s], rem[s] = False, -1, 0.0
        return t_end

    t_prev = 0.0
    for t_ev, kind, payload in events:
        t_cur = t_prev
        while t_cur < t_ev:
            if front is None or not active.any():
                t_cur = t_ev
                continue
            t_end = execute(front, t_cur, t_ev, bool(queue))
            if t_end < t_ev and fill():
                front = replan()
            t_cur = t_end
        changed = fill()
        if kind == 0:
            queue.append(int(payload))
            changed = fill() or True
        elif kind == 1:
            changed = True
            B_live = float(payload)
        else:
            break
        if changed or front is None:
            front = replan()
        t_prev = t_ev
    return StreamOutcome(completion=completion, **counts)
