"""The yardstick's own tests: outside tier-1, run by hand with

    JAX_PLATFORMS=cpu python -m pytest yardstick/tests -q

on four virtual CPU devices. They check the manifest, the arithmetic, the
trace reduction and the generators' correctness; they measure nothing."""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=4").strip()

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
