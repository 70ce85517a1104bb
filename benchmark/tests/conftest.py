"""The benchmark's own tests run on the host's CPU, at small sizes."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# Four virtual CPU devices, so that a four-chip cell's dp mesh can be
# built here; set before JAX loads. One-chip cells use the first alone.
_FOUR = "--xla_force_host_platform_device_count=4"
if _FOUR not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + " " +
                               _FOUR).strip()
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
