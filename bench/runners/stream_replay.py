"""Tapes back to back through the device event scan.

One tenant: ``StreamController.run_device``, one tape per item.  More
tenants (``tenants`` in the configuration): ``serve_streams_sharded``
on a mesh of that many chips, one tape per tenant per item.  Each item
ends in a host sync (the result is read back), so the window holds all
the work of the tapes it ran.
"""
from __future__ import annotations

from bench.runners import _stream


class Runner:
    def __init__(self, spec, seed, devs, spans):
        self.spec, self.seed, self.devs, self.spans = spec, seed, devs, spans
        self.cfg = spec.config
        self.T = int(self.cfg.get("tenants", 1))
        self.results = []          # per item: [(tape, completion)]
        self.replans = 0
        self.cold = 0
        self.events = 0

    def setup(self):
        _stream.check_precision(self.cfg)
        with self.spans("bench.setup.inputs"):
            self.tapes, self.warm_tapes = _stream.pool(self.spec, self.seed)
            self.stream_of = {id(t): _stream.arrival_stream(t)
                              for t in self.tapes + self.warm_tapes}
        if self.T == 1:
            self.ctl = _stream.controller(self.cfg)
        else:
            from repro.distributed import fleet_mesh
            self.sp = _stream.speedup(self.cfg)
            self.mesh = fleet_mesh(self.T)

    def serve_tapes(self, tapes):
        """The timed path: one item's tapes through the program."""
        streams = [self.stream_of[id(t)] for t in tapes]
        if self.T == 1:
            return [self.ctl.run_device(streams[0])]
        from repro.distributed import serve_streams_sharded
        fleet = serve_streams_sharded(
            self.sp, streams, max_live=int(self.cfg["max_live"]),
            mesh=self.mesh, rtol=float(self.cfg["window_rtol"]),
            certificate_rtol=float(self.cfg["certificate_rtol"]))
        return list(fleet.results)

    def _pick(self, i, pool):
        n = len(pool)
        return [pool[(i * self.T + k) % n] for k in range(self.T)]

    def warm(self):
        with self.spans("bench.setup.warm"):
            for i in range(max(1, len(self.warm_tapes) // self.T)):
                self.serve_tapes(self._pick(i, self.warm_tapes))

    def run_item(self, i):
        tapes = self._pick(i, self.tapes)
        with self.spans("bench.tape"):
            res = self.serve_tapes(tapes)
        self.results.append([(t, r.completion) for t, r in zip(tapes, res)])
        for t, r in zip(tapes, res):
            self.events += t.n_events
            self.replans += r.replans
            self.cold += r.cold_replans

    def work(self):
        return {"events": self.events}

    def counters(self):
        return {"replans": self.replans, "cold_replans": self.cold}

    def release(self):
        self.ctl = self.mesh = self.stream_of = None

    def compare(self):
        """Tapes of the window, drawn from the seed, against the
        reference: every tenant of each item drawn."""
        n_check = -(-int(self.spec.mix["check_tapes"]) // self.T)
        self.checked = _stream.Checked(self.spec.limits,
                                       _stream.full_rate(self.cfg))
        for i in _stream.sample(self.seed, len(self.results), n_check):
            for tape, completion in self.results[i]:
                ref = _stream.reference(self.cfg, tape)
                self.checked.add(tape, completion, ref.completion)
        return len(self.results) * self.T, self.checked.rows()

    def failed_items(self):
        return self.checked.failed_tapes()
