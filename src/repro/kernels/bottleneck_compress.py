"""Pallas TPU kernel for the split-point wire compression (DESIGN.md §3).

This is Split-Et-Impera's hot op: at the head/tail boundary the bottleneck
encoder projects the activation to the undercomplete latent and the result
is quantised to int8 for the wire (edge->server network, or the cross-pod
``ppermute`` in the multi-pod mapping).  Fusing projection + ReLU +
per-row amax + quantisation in one kernel means the f32 latent never
round-trips through HBM — only the int8 payload and one scale per row
leave VMEM.

Grid: (n_tiles, c_tiles); the contraction over input channels C is the
innermost ("arbitrary") dimension accumulating into a VMEM f32 scratch;
the final contraction step applies ReLU, computes the row-wise amax and
writes the int8 block.  Tiles are MXU-aligned (128).

Validated against ``ref.bottleneck_compress_ref`` in interpret mode.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def tpu_available() -> bool:
    """True when the default backend is a real TPU (not interpret mode).

    A TPU that fails to initialise raises here: it must never quietly
    route the codec to the CPU reference.
    """
    return jax.devices()[0].platform == "tpu"


def resolve_backend(backend: str | None = None) -> str:
    """Pick the execution path for the compress op.

    ``backend``: 'auto' | 'kernel' | 'interpret' | 'ref' (or None = env
    ``REPRO_BOTTLENECK_BACKEND``, default 'auto').  'auto' compiles the
    Pallas kernel on TPU and uses the pure-JAX reference everywhere else,
    so the runtime/CI can call this op on any host; 'interpret' forces the
    Pallas interpreter (kernel-logic validation on CPU).
    """
    backend = backend or os.environ.get("REPRO_BOTTLENECK_BACKEND", "auto")
    if backend not in ("auto", "kernel", "interpret", "ref"):
        raise ValueError(f"unknown bottleneck backend {backend!r}")
    if backend == "auto":
        return "kernel" if tpu_available() else "ref"
    return backend


# Mosaic gives a kernel 16 MiB of scoped VMEM unless asked for more; a
# v5e core has 128 MiB.  Codec blocks are sized to stay under the budget,
# and the limit is raised (never past the cap) only for blocks that need it.
_VMEM_DEFAULT = 16 * 2**20
_VMEM_BUDGET = 32 * 2**20
_VMEM_CAP = 96 * 2**20
_LANE = 128


def _fit_lane_block(bc: int, vmem_bytes) -> int:
    """Halve the lane block ``bc`` (keeping it a multiple of 128 that
    divides the old one) until ``vmem_bytes(bc)`` fits the VMEM budget."""
    while bc % (2 * _LANE) == 0 and vmem_bytes(bc) > _VMEM_BUDGET:
        bc //= 2
    return bc


def _compiler_params(semantics: tuple = ("parallel", "arbitrary"),
                     vmem_bytes: int = 0):
    limit = None
    if vmem_bytes > _VMEM_DEFAULT * 3 // 4:
        limit = min(2 * vmem_bytes, _VMEM_CAP)
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=limit)


def _kernel(f_ref, w_ref, b_ref, q_ref, s_ref, acc, *, nc: int, scale: float):
    ic = pl.program_id(1)

    @pl.when(ic == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    f = f_ref[...].astype(jnp.float32)          # (bn, bc)
    w = w_ref[...].astype(jnp.float32)          # (bc, L)
    acc[...] += jax.lax.dot(f, w)

    @pl.when(ic == nc - 1)
    def _finish():
        z = jax.nn.relu(acc[...] + b_ref[...].astype(jnp.float32))
        amax = jnp.max(jnp.abs(z), axis=1, keepdims=True)
        s = jnp.where(amax > 0, amax / scale, 1.0)
        q_ref[...] = jnp.clip(jnp.round(z / s), -127, 127).astype(jnp.int8)
        s_ref[...] = s.astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("bn", "bc", "interpret"))
def bottleneck_compress(f: jax.Array, w: jax.Array, b: jax.Array, *,
                        bn: int = 128, bc: int = 512,
                        interpret: bool = False):
    """f: (N, C) activations; w: (C, L); b: (L,).

    Returns (q int8 (N, L), row scales f32 (N, 1)) — the wire payload.
    The contraction block ``bc`` shrinks for wide latents so the
    double-buffered ``(bc, L)`` weight block fits VMEM.
    """
    n, c = f.shape
    l = w.shape[1]
    lp = _pad_to(l, _LANE)

    def vmem_bytes(bc_):
        return (2 * bn_ * bc_ * 4 + 2 * bc_ * lp * 4 + 2 * 8 * lp * 4
                + 2 * bn_ * lp + 2 * bn_ * _LANE * 4 + bn_ * lp * 4)

    bn_ = min(bn, n)
    bc_ = _fit_lane_block(min(bc, c), vmem_bytes)
    assert n % bn_ == 0 and c % bc_ == 0
    nn, nc = n // bn_, c // bc_

    kernel = functools.partial(_kernel, nc=nc, scale=127.0)
    q, s = pl.pallas_call(
        kernel,
        grid=(nn, nc),
        in_specs=[
            pl.BlockSpec((bn_, bc_), lambda i, j: (i, j)),
            pl.BlockSpec((bc_, l), lambda i, j: (j, 0)),
            pl.BlockSpec((1, l), lambda i, j: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bn_, l), lambda i, j: (i, 0)),
            pl.BlockSpec((bn_, 1), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, l), jnp.int8),
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bn_, l), jnp.float32)],
        compiler_params=_compiler_params(vmem_bytes=vmem_bytes(bc_)),
        interpret=interpret,
    )(f, w, b.reshape(1, l))
    return q, s


def _pad_to(n: int, mult: int) -> int:
    return ((n + mult - 1) // mult) * mult


def bottleneck_compress_any(f: jax.Array, w: jax.Array, b: jax.Array, *,
                            backend: str | None = None,
                            bn: int = 128, bc: int = 512):
    """Shape-flexible, backend-routed compress: the runtime's entry point.

    Accepts activations with any leading dims ``(..., C)``; pads N/C up to
    the kernel's tile multiples (zero rows quantise to zero and are
    dropped), and routes per :func:`resolve_backend` — the Pallas kernel on
    TPU, the jnp reference otherwise — so the exact same int8 wire payload
    is produced on every host.

    Returns ``(q int8 (..., L), scales f32 (..., 1))``.
    """
    from . import ref as _ref

    lead = f.shape[:-1]
    c = f.shape[-1]
    l = w.shape[1]
    f2 = f.reshape(-1, c)
    n = f2.shape[0]
    mode = resolve_backend(backend)
    if mode == "ref":
        q, s = _ref.bottleneck_compress_ref(f2, w, b)
    else:
        np_, cp = n, c
        if n > bn and n % bn:
            np_ = _pad_to(n, bn)
        if c > bc and c % bc:
            cp = _pad_to(c, bc)
        fp = jnp.zeros((np_, cp), f2.dtype).at[:n, :c].set(f2)
        wp = jnp.zeros((cp, l), w.dtype).at[:c].set(w)
        q, s = bottleneck_compress(fp, wp, b, bn=bn, bc=bc,
                                   interpret=(mode == "interpret"))
        q, s = q[:n], s[:n]
    return q.reshape(lead + (l,)), s.reshape(lead + (1,))
