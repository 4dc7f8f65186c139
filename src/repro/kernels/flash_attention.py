"""Pallas TPU flash-attention kernel (causal / sliding-window, GQA).

Grid: (batch*heads, q_blocks, kv_blocks) with the kv dimension innermost
("arbitrary" semantics) so the online-softmax state (m, l, acc) lives in
VMEM scratch across kv steps.  Blocks are MXU-aligned (multiples of 128 in
the seq dims, head_dim 64/128).  Fully-masked kv blocks are skipped with
``pl.when`` — on TPU this converts causal masking into a real 2x FLOP
saving, which the pure-jnp flash path in ``repro.models.layers`` does not
get (see EXPERIMENTS.md §Perf).

Validated in interpret mode against ``ref.flash_attention_ref`` over shape,
dtype, GQA-ratio and window sweeps (tests/test_kernels_flash.py).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _compiler_params():
    return pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "arbitrary"))


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, causal: bool, window: Optional[int],
            bq: int, bk: int, sq: int, sk: int, nk: int):
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    iq = pl.program_id(1)
    # absolute positions; queries occupy the LAST sq slots of the sk range
    q_pos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + (sk - sq)
    k_pos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)

    # block-level skip: is any (q,k) pair in this tile live?
    lo_q, hi_q = iq * bq + (sk - sq), iq * bq + bq - 1 + (sk - sq)
    lo_k = ik * bk
    live = True
    if causal:
        live = jnp.asarray(lo_k <= hi_q)
    if window is not None:
        live = jnp.logical_and(live, jnp.asarray(lo_k + bk - 1 > lo_q - window))

    @pl.when(live)
    def _body():
        q = q_ref[0].astype(jnp.float32) * scale            # (bq, d)
        k = k_ref[0].astype(jnp.float32)                    # (bk, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (bq, bk)
        mask = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            mask &= k_pos <= q_pos
        if window is not None:
            mask &= k_pos > q_pos - window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)
        l_scr[...] = l_scr[...] * alpha + p.sum(axis=1, keepdims=True)
        v = v_ref[0].astype(jnp.float32)                    # (bk, d)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot(p, v)
        m_scr[...] = m_new

    @pl.when(ik == nk - 1)
    def _finish():
        o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "bq", "bk",
                                             "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: Optional[int] = None,
                    bq: int = 128, bk: int = 128,
                    interpret: bool = False) -> jax.Array:
    """q: (B,Sq,H,D); k,v: (B,Sk,K,D) with H % K == 0. Returns (B,Sq,H,D)."""
    b, sq, h, d = q.shape
    sk, kh = k.shape[1], k.shape[2]
    g = h // kh
    bq_, bk_ = min(bq, sq), min(bk, sk)
    assert sq % bq_ == 0 and sk % bk_ == 0
    nq, nk = sq // bq_, sk // bk_
    scale = 1.0 / math.sqrt(d)

    qt = q.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    kt = k.transpose(0, 2, 1, 3).reshape(b * kh, sk, d)
    vt = v.transpose(0, 2, 1, 3).reshape(b * kh, sk, d)

    kernel = functools.partial(_kernel, scale=scale, causal=causal,
                               window=window, bq=bq_, bk=bk_, sq=sq, sk=sk,
                               nk=nk)
    out = pl.pallas_call(
        kernel,
        grid=(b * h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq_, d), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, bk_, d), lambda bh, iq, ik: (bh // g, ik, 0)),
            pl.BlockSpec((1, bk_, d), lambda bh, iq, ik: (bh // g, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq_, d), lambda bh, iq, ik: (bh, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq_, 1), jnp.float32),
            pltpu.VMEM((bq_, 1), jnp.float32),
            pltpu.VMEM((bq_, d), jnp.float32),
        ],
        compiler_params=_compiler_params(),
        interpret=interpret,
    )(qt, kt, vt)
    return out.reshape(b, h, sq, d).transpose(0, 2, 1, 3)
