"""Production mesh builders.

Functions, not module-level constants: importing this module must never
touch jax device state (the dry-run sets XLA_FLAGS before first init).
"""
from __future__ import annotations

import jax


def make_mesh(shape: tuple, axes: tuple, *, devices=None):
    """``jax.make_mesh`` with Auto axis types (its default is Explicit),
    which the sharding rules and ``shard_map`` steps are written for."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False, model_axis: int = 16):
    """Single pod: (data, model) with data*model = 256 chips (v5e pod).
    Multi-pod prepends pod=2 (512 chips).

    ``model_axis`` is a per-architecture profile knob: the default 16 suits
    128-head-multiple models; archs whose head count is 8-divisible but not
    16-divisible (llama3.2-3b: 24 heads, whisper-tiny: 6) want
    ``model_axis=8`` — on llama3.2-3b x train_4k this cuts per-device peak
    HBM 8.2x and the memory term 13x (EXPERIMENTS.md §Perf iter 6)."""
    assert 256 % model_axis == 0
    data = 256 // model_axis
    shape = (2, data, model_axis) if multi_pod else (data, model_axis)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh():
    """Degenerate 1-device mesh for CPU smoke tests."""
    return make_mesh((1, 1), ("data", "model"))
