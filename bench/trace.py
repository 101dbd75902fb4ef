"""Reduce a profiler trace to the benchmark's device numbers.

The harness traces its measured window with ``jax.profiler`` and names
its host spans ``bench.*`` (``jax.profiler.TraceAnnotation``).  This
module reads the written ``.xplane.pb`` into a ``Trace`` (device
operations per chip, programs per chip, host spans) and reduces it:

* busy time: the union of the intervals in which an operation ran on a
  chip, clipped to the window span, averaged over the chips;
* idle share: 1 - busy / window;
* device time per operation and per program, averaged over the chips;
* idle gaps: the complement of the busy union inside the window, each
  attributed to the innermost host span that covers its midpoint (the
  window span itself when no narrower one does).

Only this file turns a trace into numbers, and ``tests/test_trace.py``
checks it on a small recorded trace.
"""
from __future__ import annotations

import dataclasses
import glob
import json
import os

__all__ = ["Trace", "Summary", "load_xspace", "load_json", "reduce_trace",
           "WINDOW_SPAN"]

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
_OPS_LINES = ("XLA Ops",)
_PROGRAM_LINES = ("XLA Modules",)
_CPU_CLIENT_LINE = "tf_XLAPjRtCpuClient"
_CPU_NOT_OPS = ("ThreadpoolListener", "end: ")


@dataclasses.dataclass
class Trace:
    """Events in nanoseconds on the profiler's one clock."""

    ops: dict          # chip plane name -> [(op name, start, end)]
    programs: dict     # chip plane name -> [(program name, start, end)]
    spans: list        # [(span name, start, end)] of the harness


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                 # mean over chips
    idle_share: float
    device_ops: list              # [(op name, s per chip)], most first
    programs: list                # [(program name, s per chip)], most first
    idle_gaps: list               # [(host span, s per chip)], most first
    n_chips: int


def _short(name: str) -> str:
    """``%while.691 = (s32[], ...) while(...)`` -> ``while.691``;
    ``jit__stream_chunk(5688...)`` -> ``jit__stream_chunk``."""
    name = name.split(" = ", 1)[0].lstrip("%")
    return name.split("(", 1)[0] if name.endswith(")") else name


def load_json(path: str) -> Trace:
    with open(path) as f:
        d = json.load(f)
    return Trace(ops={k: [tuple(e) for e in v] for k, v in d["ops"].items()},
                 programs={k: [tuple(e) for e in v]
                           for k, v in d["programs"].items()},
                 spans=[tuple(e) for e in d["spans"]])


def load_xspace(log_dir: str, host_ops: bool = False) -> Trace:
    """The newest ``.xplane.pb`` under ``log_dir``, as a ``Trace``.

    ``host_ops`` (a rehearsal on the CPU backend, which has no device
    planes) takes the CPU client's executions as the one chip's
    operations, so the reduction runs end to end without a chip."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = ProfileData.from_file(paths[-1])
    ops, programs, spans = {}, {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                dest = (ops if line.name in _OPS_LINES else
                        programs if line.name in _PROGRAM_LINES else None)
                if dest is None:
                    continue
                dest.setdefault(plane.name, []).extend(
                    (_short(ev.name), int(ev.start_ns), int(ev.end_ns))
                    for ev in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((ev.name, int(ev.start_ns), int(ev.end_ns))
                             for ev in line.events
                             if ev.name.startswith(SPAN_PREFIX))
                if host_ops and line.name.startswith(_CPU_CLIENT_LINE):
                    ops.setdefault(plane.name, []).extend(
                        (ev.name, int(ev.start_ns), int(ev.end_ns))
                        for ev in line.events
                        if ev.duration_ns > 0
                        and not ev.name.startswith(_CPU_NOT_OPS))
    if host_ops and any(p.startswith("/device:") for p in ops):
        ops = {p: v for p, v in ops.items() if p.startswith("/device:")}
    return Trace(ops=ops, programs=programs, spans=spans)


def _union(intervals, lo, hi):
    """Merged [start, end) intervals clipped to [lo, hi)."""
    out = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _totals(events, lo, hi):
    tot = {}
    for name, s, e in events:
        d = min(e, hi) - max(s, lo)
        if d > 0:
            tot[name] = tot.get(name, 0) + d
    return tot


def _attribute(spans, t):
    """The innermost span that covers ``t`` (the window when none)."""
    best = None
    for name, s, e in spans:
        if s <= t < e and name != WINDOW_SPAN:
            if best is None or e - s < best[1]:
                best = (name, e - s)
    return WINDOW_SPAN if best is None else best[0]


def reduce_trace(trace: Trace, chips=None, top: int = 10) -> Summary:
    """Reduce over the window span; ``chips`` limits it to the named
    device planes (default: every plane with an operation)."""
    wins = [(s, e) for name, s, e in trace.spans if name == WINDOW_SPAN]
    if len(wins) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN} span, found "
                         f"{len(wins)}")
    lo, hi = wins[0]
    names = sorted(trace.ops) if chips is None else list(chips)
    if not names:
        raise ValueError("the trace holds no device operations")
    n = len(names)
    busy = 0
    op_tot, prog_tot, gap_tot = {}, {}, {}
    for chip in names:
        evs = trace.ops.get(chip, [])
        merged = _union([(s, e) for _, s, e in evs], lo, hi)
        busy += sum(e - s for s, e in merged)
        for k, v in _totals(evs, lo, hi).items():
            op_tot[k] = op_tot.get(k, 0) + v
        for k, v in _totals(trace.programs.get(chip, []), lo, hi).items():
            prog_tot[k] = prog_tot.get(k, 0) + v
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e > s:
                who = _attribute(trace.spans, (s + e) // 2)
                gap_tot[who] = gap_tot.get(who, 0) + (e - s)

    def ranked(tot):
        return [[k, v / n * 1e-9] for k, v in
                sorted(tot.items(), key=lambda kv: -kv[1])[:top]]

    window_s = (hi - lo) * 1e-9
    busy_s = busy / n * 1e-9
    return Summary(window_s=window_s, busy_s=busy_s,
                   idle_share=1.0 - busy_s / window_s if window_s else 1.0,
                   device_ops=ranked(op_tot), programs=ranked(prog_tot),
                   idle_gaps=ranked(gap_tot), n_chips=n)
