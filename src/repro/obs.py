"""Spans and counters of the control plane, recorded while profiling.

One in-process tracer for the whole program.  ``span(name)`` times a
block on the host clock (``time.perf_counter_ns``), remembers the span
that encloses it on the same thread, and enters a
``jax.profiler.TraceAnnotation`` of the same name, so the span also lands
in the profiler's host plane on the clock of the device trace.
``count(name, n)`` adds to a counter.  ``totals()`` folds what was
recorded into, per span name, its count, total seconds and self seconds
(its duration less the part its child spans cover), beside the counters;
``reset()`` clears everything.

Recording is on exactly while a profiler session is active
(``jax.profiler.trace``/``start_trace``): an operator's trace, or a
benchmark's traced window, holds the records of that session and no
others.  With no session, ``span`` and ``count`` check that one flag and
return; they build no annotation and record nothing.

Span names start with ``repro.``; the control plane's are

  ``repro.loop``    one ``StreamController.run`` (self: event sorting,
                    queue and slot bookkeeping, ``_finalize``);
  ``repro.plan``    one ``StreamCascadePolicy.plan``, with children
                    ``.dispatch`` (the live state's one packed upload and
                    the cascade call) and ``.readback`` (the decision's
                    one packed read);
  ``repro.window``  one ``StreamController._execute``, with children
                    ``.dispatch`` (one packed upload and the call),
                    ``.readback`` (one packed read) and ``.cut`` (the
                    re-run at a first completion: one upload, one read);
  ``repro.scan``    one ``run_device`` or ``serve_streams_sharded``, with
                    children ``.prepare`` (host work before the device
                    runs, the dispatch included), ``.wait`` (blocked on
                    the device) and ``.finish`` (the one read,
                    ``.finish.read``, then the metrics).

Counters: ``repro.h2d`` and ``repro.d2h`` (arrays moved each way: on
the served loop one and one per plan, per window and per cut, the fixed
arguments having gone up once when the policy and controller were built),
``repro.scan.<counter>`` (the event scan's own work counts) and
``repro.retrace.<function>`` (traces of a jitted function).
"""
from __future__ import annotations

import threading
import time

import jax

__all__ = ["span", "count", "totals", "reset", "host_leaves"]

_enabled = jax.profiler.TraceAnnotation.is_enabled

_lock = threading.Lock()
_local = threading.local()
# (name, parent name or None, start ns, end ns, ns covered by children)
_spans: list[tuple] = []
_counters: dict[str, int] = {}


class _Off:
    """The span of an untraced block: does nothing."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_OFF = _Off()


class _Span:
    __slots__ = ("name", "ann", "t0", "child_ns", "parent")

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        stack = getattr(_local, "stack", None)
        if stack is None:
            stack = _local.stack = []
        self.parent = stack[-1] if stack else None
        self.child_ns = 0
        stack.append(self)
        self.ann = jax.profiler.TraceAnnotation(self.name)
        self.ann.__enter__()
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter_ns()
        self.ann.__exit__(*exc)
        _local.stack.pop()
        parent = self.parent
        if parent is not None:
            parent.child_ns += t1 - self.t0
        with _lock:
            _spans.append((self.name, None if parent is None
                           else parent.name, self.t0, t1, self.child_ns))
        return False


def span(name: str):
    """Context manager timing one block under ``name`` while profiling."""
    return _Span(name) if _enabled() else _OFF


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to counter ``name`` while profiling."""
    if _enabled():
        with _lock:
            _counters[name] = _counters.get(name, 0) + int(n)


def totals() -> dict:
    """``{"spans": {name: {"count", "total_s", "self_s"}}, "counters":
    {name: n}}`` over everything recorded since the last ``reset``."""
    with _lock:
        spans = list(_spans)
        counters = dict(_counters)
    out: dict[str, dict] = {}
    for name, _, t0, t1, child in spans:
        row = out.setdefault(name, {"count": 0, "total_s": 0.0,
                                    "self_s": 0.0})
        row["count"] += 1
        row["total_s"] += (t1 - t0) * 1e-9
        row["self_s"] += (t1 - t0 - child) * 1e-9
    return {"spans": out, "counters": counters}


def reset() -> None:
    """Forget every recorded span and counter."""
    with _lock:
        _spans.clear()
        _counters.clear()


def host_leaves(*trees) -> int:
    """Leaves of ``trees`` that live on the host (NumPy arrays, Python
    scalars): what a device call given them must upload."""
    return sum(not isinstance(leaf, jax.Array)
               for leaf in jax.tree_util.tree_leaves(trees))
