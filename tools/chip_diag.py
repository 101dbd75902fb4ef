#!/usr/bin/env python3
"""Measure float32 accuracy on the default JAX backend, against float64 numpy.

    python tools/chip_diag.py                          # all sections
    python tools/chip_diag.py --only classes --src DIR # class plan of another tree

Sections:
  primitives  log, log1p, exp, expm1, power and divide in float32: the
              backend's own ops (XLA default and, where the compiler
              offers one, ``AccuracyMode.HIGHEST``), ``repro.core.fmath``,
              and both inside a Pallas kernel.  Max and median error in
              ulps of the float64 result rounded to float32.
  classes     ``plan_classes`` for 10^6 jobs as C=32 classes (the inputs
              of chip_smoke's plan phase) in float32, against the numpy
              float64 oracle ``plan_classes_reference`` on the order the
              float32 run chose, plus its J == J_linear certificate.

``--src`` points at the ``src`` directory of the tree to import (for
example an unpacked parent commit), so one call can compare two trees.
Runs on any backend; on the CPU it gives the baseline the chip is read
against.  Prints one line per measurement.
"""
from __future__ import annotations

import argparse
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _ulps(got, want64):
    import numpy as np
    got = np.asarray(got, np.float64)
    sp = np.spacing(np.abs(want64).astype(np.float32)).astype(np.float64)
    fin = np.isfinite(want64) & (np.abs(want64) < 3e38) & (np.abs(want64) > 1e-37)
    e = np.abs(got - want64)[fin] / sp[fin]
    return float(np.max(e)), float(np.median(e))


def _cases():
    import numpy as np
    rng = np.random.default_rng(0)

    def logu(a, b, n=1 << 16):
        return np.exp(rng.uniform(np.log(a), np.log(b), n)).astype(np.float32)

    def uni(a, b, n=1 << 16):
        return rng.uniform(a, b, n).astype(np.float32)

    out = [
        ("log", "[0.5,2]", uni(0.5, 2.0), None),
        ("log", "[1e-30,1e30]", logu(1e-30, 1e30), None),
        ("log1p", "[1e-7,1e-1]", logu(1e-7, 0.1), None),
        ("log1p", "[0.1,1e4]", logu(0.1, 1e4), None),
        ("exp", "[-80,80]", uni(-80.0, 80.0), None),
        ("exp", "[-1,1]", uni(-1.0, 1.0), None),
        ("expm1", "[1e-7,0.5]", logu(1e-7, 0.5), None),
        ("expm1", "[-20,20]", uni(-20.0, 20.0), None),
        ("divide", "[1e-3,1e3]", logu(1e-3, 1e3), logu(1e-3, 1e3)),
    ]
    for y in (0.5, -0.5, -1.0, 1 / 0.7, -2.0):
        out.append(("power", f"x in [1e-3,1e5], y={y:.4g}", logu(1e-3, 1e5),
                    np.full(1 << 16, y, np.float32)))
    return out


def primitives():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax
    from jax.experimental import pallas as pl

    from repro.core import fmath

    f64 = {"log": np.log, "log1p": np.log1p, "exp": np.exp,
           "expm1": np.expm1, "divide": np.divide, "power": np.power}
    native = {"log": jnp.log, "log1p": jnp.log1p, "exp": jnp.exp,
              "expm1": jnp.expm1, "divide": jnp.divide, "power": jnp.power}
    mine = {"log": fmath.log, "log1p": fmath.log1p, "exp": fmath.exp,
            "expm1": fmath.expm1, "power": fmath.power}
    highest = {n: (lambda x, n=n: getattr(lax, n)(
        x, accuracy=lax.AccuracyMode.HIGHEST))
        for n in ("log", "log1p", "exp", "expm1")}

    def in_kernel(fn, *xs):
        def kern(*refs):
            refs[-1][...] = fn(*(r[...] for r in refs[:-1]))
        shaped = [jnp.asarray(x).reshape(-1, 128) for x in xs]
        out = pl.pallas_call(kern, out_shape=jax.ShapeDtypeStruct(
            shaped[0].shape, jnp.float32))(*shaped)
        return out.reshape(-1)

    for name, rng, x, y in _cases():
        args = (x,) if y is None else (x, y)
        want = f64[name](*(a.astype(np.float64) for a in args))
        impls = [("xla", native[name])]
        if name in highest:
            impls.append(("xla_highest", highest[name]))
        if name in mine:
            impls.append(("fmath", mine[name]))
        impls.append(("pallas", lambda *a, f=native[name]: in_kernel(f, *a)))
        if name in mine:
            impls.append(("pallas_fmath",
                          lambda *a, f=mine[name]: in_kernel(f, *a)))
        for label, fn in impls:
            try:
                got = np.asarray(jax.jit(fn)(*args))
                mx, med = _ulps(got, want)
                print(f"primitive {name} {rng} {label}: max_ulp={mx!r} "
                      f"median_ulp={med!r}", flush=True)
            except Exception as e:          # a lowering the backend lacks
                print(f"primitive {name} {rng} {label}: unavailable "
                      f"({type(e).__name__}: {str(e).splitlines()[0][:120]})",
                      flush=True)


def classes():
    import time

    import numpy as np

    from repro.core import (plan_classes, plan_classes_reference,
                            sample_class_workloads)
    cw = sample_class_workloads(1, K=1, C=32, B=10.0,
                                count_range=(31_250, 31_250))
    t0 = time.perf_counter()
    cls = plan_classes(cw.state(0))
    J = float(np.asarray(cls.J))
    print(f"classes: float32 plan_classes wall_s="
          f"{time.perf_counter() - t0!r} J={J!r} "
          f"J_linear={float(np.asarray(cls.J_linear))!r}", flush=True)
    ref = plan_classes_reference(cw.state(0), order=np.asarray(cls.order))
    print(f"classes: oracle on that order J={ref.J!r} "
          f"J_linear={ref.J_linear!r} J_rel_on_order={abs(J - ref.J) / ref.J!r} "
          f"oracle_certificate_rel="
          f"{abs(ref.J - ref.J_linear) / ref.J!r}", flush=True)


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--src", default=str(ROOT / "src"),
                    help="the tree's src directory to import repro from")
    ap.add_argument("--only", choices=("primitives", "classes"))
    args = ap.parse_args()
    sys.path.insert(0, args.src)

    import jax
    d = jax.devices()[0]
    print(f"device platform={d.platform} kind={d.device_kind} "
          f"src={args.src} x64={jax.config.jax_enable_x64}", flush=True)
    if args.only in (None, "primitives"):
        primitives()
    if args.only in (None, "classes"):
        classes()


if __name__ == "__main__":
    main()
