#!/usr/bin/env python3
"""Read the two sides of every limit of a cell: the program's sound runs
and the control.

    python bench/calibrate.py --workload <cell> --seeds 1,2,... \
        [--control-seeds 1,2,3] [--items N] [--out readings.json]

For each seed, in one process: one run of the cell through
``harness.run_cell``, the path that decides ``correct``, with a window
of ``--items`` items (at least the mix's ``check_tapes``).  The control
is the plain reference put in the program's place and computed in
bfloat16, the precision below the configuration's float32.  Prints
every number compared, per seed, then the largest sound reading and the
smallest control reading of each; the limits in ``limits/<cell>.json``
are set between the two (PERF.md gives the readings).  Needs the chip,
as a run does.
"""
from __future__ import annotations

import argparse
import importlib
import json
import pathlib
import sys
import time
import types

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _readings(cell, seed, spec, items, control):
    """The numbers compared in one run of the cell through the harness,
    with ``items`` items in its window."""
    from bench import harness
    sound = importlib.import_module(f"bench.runners.{spec.mix['runner']}")
    mod = sound if not control else types.SimpleNamespace(
        Runner=lambda *a: control_runner(sound.Runner(*a)))
    res = harness.run_cell(cell, seed, 0.0, False, spec=spec,
                           runner_module=mod, min_items=items)
    return {n: c["value"] for n, c in res["checks"].items()}


def control_runner(runner):
    """The runner with the program replaced by the bfloat16 reference."""
    import ml_dtypes

    from bench.runners import _stream

    class _Res:
        def __init__(self, out):
            self.completion = out.completion
            self.replans = out.replans
            self.cold_replans = out.searched + out.fallbacks

    def serve_tapes(tapes):
        return [_Res(_stream.reference(runner.cfg, t, ml_dtypes.bfloat16))
                for t in tapes]

    runner.serve_tapes = serve_tapes
    return runner


def main():
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--items", type=int, default=0)
    ap.add_argument("--out", default="")
    args = ap.parse_args()
    from bench import harness
    spec = harness.load_cell(args.workload)
    T = int(spec.config.get("tenants", 1))
    items = max(args.items, -(-int(spec.mix["check_tapes"]) // T))
    out = {"workload": args.workload, "sound": {}, "control": {}}
    for kind, seeds in (("sound", args.seeds),
                        ("control", args.control_seeds)):
        for s in filter(None, seeds.split(",")):
            t0 = time.perf_counter()
            r = _readings(args.workload, int(s), spec, items,
                          kind == "control")
            out[kind][s] = r
            print(f"{kind} seed={s} ({time.perf_counter() - t0:.1f}s) "
                  + " ".join(f"{k}={v!r}" for k, v in r.items()),
                  flush=True)
    names = sorted({k for d in out["sound"].values() for k in d})
    for n in names:
        lo = max(d[n] for d in out["sound"].values())
        hi = (min(d[n] for d in out["control"].values())
              if out["control"] else float("nan"))
        print(f"reading {n}: sound max={lo!r} control min={hi!r} "
              f"limit={spec.limits.get(n)!r}")
    if args.out:
        pathlib.Path(args.out).write_text(json.dumps(out, indent=1))


if __name__ == "__main__":
    main()
