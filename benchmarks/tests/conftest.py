"""These tests run on the CPU (``python -m pytest benchmarks/tests -q``):
the rehearsal backend is forced before jax is imported."""

import os
import pathlib
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=4")
HERE = pathlib.Path(__file__).resolve().parent.parent
for p in (str(HERE.parent), str(HERE)):
    if p not in sys.path:
        sys.path.insert(0, p)
