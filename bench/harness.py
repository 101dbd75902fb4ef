"""Run one benchmark cell once: set up, measure a window, check, report.

Everything that belongs to one cell is found by name from
``BENCHMARK.json``: the configuration file, the traffic mix
``traffic/<traffic>.json`` (which names its runner,
``runners/<runner>.py``), the limits of its comparison
``limits/<cell>.json`` and one reader per metric,
``metrics/<metric>.py`` (or one for the name's stem before its first
dot).  A new cell, mix or metric is new files and
new entries, never an edit here.

The last line of standard output is the result as JSON; the numbers
compared, each beside its limit, are the last lines of standard error
and the last key of the result.
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import math
import pathlib
import shutil
import sys
import tempfile
import time
import traceback

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent

__all__ = ["CellSpec", "load_cell", "run_cell", "NoAccelerator", "Spans"]

# a program compiled, or loaded from the persistent cache
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# a Python function traced again (its compiled program is found again)
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"


class NoAccelerator(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class CellSpec:
    name: str
    chips: int
    config: dict
    mix: dict
    limits: dict
    end_to_end: list       # metric entries of BENCHMARK.json
    per_layer: list


def _load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, root: pathlib.Path = ROOT) -> CellSpec:
    spec = _load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have: {', '.join(sorted(cells))})")
    cell = cells[name]
    cfgs = {c["name"]: c for c in spec["configs"]}
    config = _load_json(root / cfgs[cell["config"]]["file"])
    mix = _load_json(BENCH / "traffic" / f"{cell['traffic']}.json")
    limits = _load_json(BENCH / "limits" / f"{name}.json")
    e2e = [m for m in spec["end_to_end"]
           if name in m.get("workloads", [name])]
    e2e_names = {m["name"] for m in e2e}
    layer = [m for m in spec["per_layer"]
             if name in m.get("workloads", [name]) and m["moves"] in e2e_names]
    return CellSpec(name=name, chips=int(cell["chips"]), config=config,
                    mix=mix, limits=limits, end_to_end=e2e, per_layer=layer)


def _reader(metric: str):
    """``metrics/<metric>.py``, else the reader of the name's stem before
    its first dot (``device_idle.py`` reads every ``device_idle.<cell>``)."""
    path = BENCH / "metrics" / f"{metric}.py"
    if not path.exists():
        path = BENCH / "metrics" / f"{metric.split('.', 1)[0]}.py"
    mod_name = "bench_metric_" + path.stem.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class Spans:
    """The harness's host spans: ``jax.profiler.TraceAnnotation`` in the
    trace, and each span's durations on the host clock."""

    def __init__(self):
        import jax
        self._annotation = jax.profiler.TraceAnnotation
        self.durations: dict[str, list[float]] = {}

    def __call__(self, name: str):
        return _Span(self, name)


class _Span:
    __slots__ = ("owner", "name", "ann", "t0")

    def __init__(self, owner, name):
        self.owner, self.name = owner, name

    def __enter__(self):
        self.ann = self.owner._annotation(self.name)
        self.ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter() - self.t0
        self.ann.__exit__(*exc)
        self.owner.durations.setdefault(self.name, []).append(dt)
        return False


@dataclasses.dataclass
class Context:
    """What a metric reader reads."""

    window_s: float
    setup_s: float
    work: dict             # counts of the window's work, by the runner
    counters: dict         # the program's own counters, by the runner
    spans: dict            # host span name -> [seconds]
    trace: object          # trace.Summary, or None without --trace 1


def _devices(chips: int, require_accelerator: bool):
    import jax
    devs = jax.devices()
    print(f"device platform={devs[0].platform} kind={devs[0].device_kind} "
          f"count={len(devs)}", file=sys.stderr, flush=True)
    if require_accelerator and devs[0].platform == "cpu":
        raise NoAccelerator("JAX found no accelerator, only the CPU")
    if len(devs) < chips:
        raise NoAccelerator(f"the cell asks for {chips} chips, JAX found "
                            f"{len(devs)}")
    return devs[:chips]


def _peak_bytes(devs):
    peaks = []
    for d in devs:
        st = d.memory_stats() or {}
        if "peak_bytes_in_use" in st:
            peaks.append(int(st["peak_bytes_in_use"]))
    return max(peaks) if peaks else 0


def _chip_planes(trace, devs):
    ids = {str(d.id) for d in devs}
    named = [p for p in trace.ops if p.rsplit(":", 1)[-1] in ids]
    return named or sorted(trace.ops)


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             require_accelerator: bool = True, t_start: float | None = None,
             spec: CellSpec | None = None, runner_module=None,
             min_items: int = 1) -> dict:
    """One run of cell ``name``; returns the result printed last.

    ``spec`` and ``runner_module`` let a test give a cell of its own
    size or a broken timed path, and ``min_items`` lets calibration run
    as many items as it compares; the command line never does.
    """
    t_start = time.perf_counter() if t_start is None else t_start
    spec = load_cell(name) if spec is None else spec
    src = ROOT / "src"
    if not (src / "repro").is_dir():
        raise FileNotFoundError(f"the program's sources ({src}/repro) are "
                                "not in this checkout")
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))
    import jax

    from repro.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    print(f"setup compile_cache_dir={cache_dir}", file=sys.stderr,
          flush=True)
    devs = _devices(spec.chips, require_accelerator)

    if runner_module is None:
        runner_module = importlib.import_module(
            f"bench.runners.{spec.mix['runner']}")
    spans = Spans()
    runner = runner_module.Runner(spec, seed, devs, spans)
    runner.setup()
    runner.warm()
    print("setup " + " ".join(
        f"{k}={sum(v)!r}" for k, v in spans.durations.items()
        if k.startswith("bench.setup.")) +
        f" before_window={time.perf_counter() - t_start!r}",
        file=sys.stderr, flush=True)

    counts = {COMPILE_EVENT: 0, TRACE_EVENT: 0}

    def on_event(event, duration, **_):
        if event in counts:
            counts[event] += 1

    jax.monitoring.register_event_duration_secs_listener(on_event)
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    try:
        if trace:
            jax.profiler.start_trace(trace_dir)
        setup_s = time.perf_counter() - t_start
        # a traced window stops after the mix's ``trace_items`` items:
        # the profiler's device buffer holds only a few seconds of ops
        limit = int(spec.mix.get("trace_items", 0)) if trace else 0
        items = 0
        with spans("bench.window"):
            t0 = time.perf_counter()
            deadline = t0 + seconds
            while True:
                runner.run_item(items)
                items += 1
                if items == limit or (time.perf_counter() >= deadline
                                      and items >= min_items):
                    break
            window_s = time.perf_counter() - t0
        in_window = counts[COMPILE_EVENT]
        t_stop = time.perf_counter()
        if trace:
            jax.profiler.stop_trace()
        jax.monitoring.unregister_event_duration_listener(on_event)
        print(f"window items={items} seconds={window_s!r} "
              f"compiles_in_window={in_window} "
              f"retraces_in_window={counts[TRACE_EVENT]}", file=sys.stderr,
              flush=True)
        memory_peak = _peak_bytes(devs)
        summary = None
        if trace:
            from bench.trace import load_xspace, reduce_trace
            t1 = time.perf_counter()
            tr = load_xspace(trace_dir,
                             host_ops=devs[0].platform == "cpu")
            t2 = time.perf_counter()
            summary = reduce_trace(tr, chips=_chip_planes(tr, devs))
            print(f"trace stop_s={t1 - t_stop!r} load_s={t2 - t1!r} "
                  f"reduce_s={time.perf_counter() - t2!r} "
                  f"ops={sum(map(len, tr.ops.values()))}", file=sys.stderr,
                  flush=True)
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)

    ctx = Context(window_s=window_s, setup_s=setup_s, work=runner.work(),
                  counters=runner.counters(), spans=spans.durations,
                  trace=summary)
    wanted = spec.per_layer if trace else spec.end_to_end
    metrics = {}
    for m in wanted:
        v = _reader(m["name"])(ctx)
        if v is None:
            continue
        metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}

    # the program's state is freed before the reference runs
    runner.release()
    attempted, rows = runner.compare()
    rows.append(("compiles_in_window", float(in_window), 0.0))
    failed = [n for n, v, lim in rows
              if not (math.isfinite(v) and v <= lim)]
    d0 = devs[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devs), "memory_peak_bytes": memory_peak}
    result = {"correct": not failed, "attempted": int(attempted),
              "failed": int(runner.failed_items()), "metrics": metrics,
              "device": device}
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        result["breakdown"] = {"device_ops": summary.device_ops,
                               "idle_gaps": summary.idle_gaps}
    result["checks"] = {n: {"value": v, "limit": lim} for n, v, lim in rows}
    for n, v, lim in rows:
        print(f"check {n} = {v!r} (limit {lim!r}) "
              f"{'FAIL' if n in failed else 'ok'}", file=sys.stderr)
    return result


def main(argv=None, t_start: float | None = None) -> int:
    import argparse
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(
        description="Run one benchmark cell once (see BENCHMARK.json).")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        spec = load_cell(args.workload)
    except (FileNotFoundError, KeyError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace), t_start=t_start, spec=spec)
    except NoAccelerator as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    except Exception:
        traceback.print_exc()
        return 1
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0
