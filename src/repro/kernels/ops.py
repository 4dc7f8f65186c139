"""Jit'd dispatch wrappers: Pallas kernel on TPU, reference elsewhere.

The models call these ops; on the CPU container the reference (pure-jnp)
path runs and the Pallas bodies are exercised via ``interpret=True`` in
tests.  ``force`` overrides for testing ('pallas-interpret' runs the real
kernel body emulated on CPU).
"""
from __future__ import annotations

from typing import Optional

from . import ref
from .bottleneck_compress import bottleneck_compress, tpu_available
from .flash_attention import flash_attention
from .mamba_scan import mamba_scan
from .rwkv6_scan import rwkv6_scan


def attention_op(q, k, v, *, causal: bool = True, window: Optional[int] = None,
                 force: Optional[str] = None):
    mode = force or ("pallas" if tpu_available() else "ref")
    if mode == "pallas":
        return flash_attention(q, k, v, causal=causal, window=window)
    if mode == "pallas-interpret":
        return flash_attention(q, k, v, causal=causal, window=window,
                               interpret=True)
    return ref.flash_attention_ref(q, k, v, causal=causal, window=window)


def compress_op(f, w, b, *, force: Optional[str] = None):
    mode = force or ("pallas" if tpu_available() else "ref")
    if mode == "pallas":
        return bottleneck_compress(f, w, b)
    if mode == "pallas-interpret":
        return bottleneck_compress(f, w, b, interpret=True)
    return ref.bottleneck_compress_ref(f, w, b)


def decompress_op(q, s):
    return ref.bottleneck_decompress_ref(q, s)


def wkv_op(r, k, v, w, u, *, chunk: int = 64, force: Optional[str] = None):
    mode = force or ("pallas" if tpu_available() else "ref")
    if mode == "pallas":
        return rwkv6_scan(r, k, v, w, u, chunk=chunk)
    if mode == "pallas-interpret":
        return rwkv6_scan(r, k, v, w, u, chunk=chunk, interpret=True)
    import jax.numpy as jnp
    b, _, h, d = r.shape
    return ref.rwkv6_scan_ref(r, k, v, w, u, jnp.zeros((b, h, d, d), jnp.float32))


def mamba_scan_op(dt, b, c, x, a, *, force=None):
    mode = force or ("pallas" if tpu_available() else "ref")
    if mode == "pallas":
        return mamba_scan(dt, b, c, x, a)
    if mode == "pallas-interpret":
        return mamba_scan(dt, b, c, x, a, interpret=True)
    return ref.mamba_scan_ref(dt, b, c, x, a)
