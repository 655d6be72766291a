"""The chip benchmark's own tests: small cells on the CPU.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/chip/tests

They skip the look for a chip and drive the rest of a run.
"""
import os
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parent.parent
for p in (str(HERE), str(HERE.parent.parent / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
# four host devices for the four-chip cell, set before JAX starts
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS",
                                                                  ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=4")
