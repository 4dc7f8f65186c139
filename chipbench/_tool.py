"""Shared start of the benchmark's tools (``sweep.py``, ``control.py``,
``record_trace.py``): the import path, the chip check, the compile cache
and one JSON line per reading."""
from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def start():
    """The Spec of this checkout, once a TPU is found and the compile cache
    is on; exits non-zero without a TPU."""
    import jax

    from chipbench.spec import Spec
    from repro.launch.cache import enable_compile_cache

    if jax.devices()[0].platform != "tpu":
        sys.exit("no TPU: the benchmark's tools run on the chip only")
    enable_compile_cache()
    return Spec.load(ROOT)


def emit(**fields):
    print(json.dumps(fields), flush=True)
