#!/usr/bin/env python3
"""Record the small trace that ``test_trace.py`` checks the reduction on.

    python bench/tests/record_trace.py [out.json]

Runs two short tapes of the replay runner, traced, inside a
``bench.window`` span, and writes the chip operations, programs and
host spans of the window as JSON (default
``bench/tests/data/trace_small.json``).  Run it on the chip; on the CPU
backend it records the CPU client's executions instead.
"""
import pathlib
import sys
import tempfile

ROOT = pathlib.Path(__file__).resolve().parents[2]


def main():
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    out = pathlib.Path(sys.argv[1] if len(sys.argv) > 1 else
                       ROOT / "bench/tests/data/trace_small.json")
    import json

    import jax

    from bench import harness
    from bench.runners import stream_replay
    from bench.trace import load_xspace

    spec = harness.load_cell("day_stream.replay")
    spec.mix["tape"]["n_arrivals"] = 6
    spec.mix["pool"] = 2
    devs = jax.devices()[:1]
    spans = harness.Spans()
    runner = stream_replay.Runner(spec, 11, devs, spans)
    runner.setup()
    runner.warm()
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        with spans("bench.window"):
            for i in range(2):
                runner.run_item(i)
        jax.profiler.stop_trace()
        tr = load_xspace(d, host_ops=devs[0].platform == "cpu")
    lo, hi = [(s, e) for n, s, e in tr.spans if n == "bench.window"][0]
    keep = {p: [e for e in v if e[2] > lo and e[1] < hi]
            for p, v in tr.ops.items()}
    progs = {p: [e for e in v if e[2] > lo and e[1] < hi]
             for p, v in tr.programs.items()}
    spans_w = [s for s in tr.spans if s[2] > lo and s[1] < hi]
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps({"platform": devs[0].platform,
                               "kind": devs[0].device_kind,
                               "ops": keep, "programs": progs,
                               "spans": spans_w}))
    print(f"wrote {out}: {sum(map(len, keep.values()))} ops, "
          f"{len(spans_w)} spans")


if __name__ == "__main__":
    main()
