"""CPU-only tests of the benchmark harness.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests

Four host devices stand in for the four-chip mesh; nothing here needs a
chip, and the harness's own look for one is skipped by calling
``bench.run.run_cell`` directly.
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
