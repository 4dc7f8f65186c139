"""Multi-pod split pipeline correctness (runs in a subprocess because the
device-count flag must be set before jax initialises).

CPU only: the parent has imported JAX before it starts the child, and on a
TPU host the parent would hold the chip the child needs.  On the chip the
same path runs in one process: ``python chip_smoke.py --chips 4``."""
import os
import subprocess
import sys

import pytest


@pytest.mark.slow
def test_multipod_pipeline_example():
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..", "src"))
    script = os.path.join(os.path.dirname(__file__), "..", "examples",
                          "multipod_pipeline.py")
    out = subprocess.run([sys.executable, script], env=env, timeout=600,
                         capture_output=True, text=True)
    assert out.returncode == 0, out.stderr[-2000:]
    # bitwise-identical on some jax versions; reassociation across
    # shard_map/scan can differ in the last float32 bits on others
    import re
    m = re.search(r"max err ([0-9.e+-]+)", out.stdout)
    assert m, out.stdout
    assert float(m.group(1)) <= 1e-4, out.stdout
