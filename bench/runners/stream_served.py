"""Tapes back to back through the host-served loop.

``StreamController.run`` makes one replanning decision per event
through the jitted cascade and executes each window with a device call.
The controller takes any policy with ``plan``, ``release`` and
``reset``, so a proxy around ``StreamCascadePolicy`` times every
decision, from entry to a ``StreamPlan`` on the host, under the span
``bench.decision``.
"""
from __future__ import annotations

from bench.runners import _stream


class TimedPolicy:
    """``StreamCascadePolicy`` with every ``plan`` call timed."""

    def __init__(self, inner, spans):
        self.inner, self.spans = inner, spans
        self.count = 0

    def plan(self, rem, w, active=None, B=None):
        with self.spans("bench.decision"):
            out = self.inner.plan(rem, w, active, B)
        self.count += 1
        return out

    def release(self, slots):
        self.inner.release(slots)

    def reset(self):
        self.inner.reset()

    @property
    def warm_replans(self):
        return self.inner.warm_replans

    @property
    def cold_replans(self):
        return self.inner.cold_replans


class Runner:
    def __init__(self, spec, seed, devs, spans):
        self.spec, self.seed, self.devs, self.spans = spec, seed, devs, spans
        self.cfg = spec.config
        self.results = []          # per item: (tape, completion)
        self.replans = 0
        self.cold = 0
        self.events = 0

    def setup(self):
        _stream.check_precision(self.cfg)
        with self.spans("bench.setup.inputs"):
            self.tapes, self.warm_tapes = _stream.pool(self.spec, self.seed)
            self.stream_of = {id(t): _stream.arrival_stream(t)
                              for t in self.tapes + self.warm_tapes}
        self.ctl = _stream.controller(
            self.cfg, lambda p: TimedPolicy(p, self.spans))

    def warm(self):
        with self.spans("bench.setup.warm"):
            self.serve_tapes(self.warm_tapes)
        # the window's decisions only
        self.spans.durations.pop("bench.decision", None)
        self.ctl.policy.count = 0

    def serve_tapes(self, tapes):
        """The timed path: tapes one after another through the loop."""
        return [self.ctl.run(self.stream_of[id(t)]) for t in tapes]

    def run_item(self, i):
        tape = self.tapes[i % len(self.tapes)]
        with self.spans("bench.tape"):
            res, = self.serve_tapes([tape])
        self.results.append((tape, res.completion))
        self.events += tape.n_events
        self.replans += res.replans
        self.cold += res.cold_replans

    def work(self):
        return {"events": self.events, "decisions": self.ctl.policy.count}

    def counters(self):
        return {"replans": self.replans, "cold_replans": self.cold}

    def release(self):
        self.ctl = self.stream_of = None

    def compare(self):
        self.checked = _stream.Checked(self.spec.limits,
                                       _stream.full_rate(self.cfg))
        for i in _stream.sample(self.seed, len(self.results),
                                int(self.spec.mix["check_tapes"])):
            tape, completion = self.results[i]
            ref = _stream.reference(self.cfg, tape)
            self.checked.add(tape, completion, ref.completion)
        return len(self.results), self.checked.rows()

    def failed_items(self):
        return self.checked.failed_tapes()
