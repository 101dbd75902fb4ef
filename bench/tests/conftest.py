"""The benchmark's own tests: run them on the CPU, from the repo root,

    JAX_PLATFORMS=cpu python -m pytest -q -p no:cacheprovider bench/tests

Four host devices are forced before JAX starts, for the four-tenant
cell's rehearsal; the program runs in float32, as on the chip.
"""
import os
import pathlib
import sys

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)
