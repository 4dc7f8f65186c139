"""Pallas TPU kernel for the RWKV-6 (Finch) WKV recurrence.

Grid: (B*H, n_chunks) with the chunk dimension innermost ("arbitrary"):
the (D_k x D_v) decay state lives in VMEM scratch across chunks, and the
per-timestep recurrence runs as a ``fori_loop`` over the chunk.  Memory
traffic is therefore one read of r/k/v/w and one write of out per token —
the state never visits HBM (the lax.scan reference spills it every step
on the XLA side unless fused).

Head dims are VPU-lane-aligned (64).  Validated against
``ref.rwkv6_scan_ref`` in interpret mode over shape and chunk sweeps.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _compiler_params():
    return pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary"))


def _kernel(r_ref, k_ref, v_ref, w_ref, u_ref, o_ref, sf_ref, state, *,
            chunk: int, n_chunks: int):
    ic = pl.program_id(1)

    @pl.when(ic == 0)
    def _init():
        state[...] = jnp.zeros_like(state)

    u = u_ref[0]                                 # (D,)

    def step(t, s):
        rt = r_ref[0, t].astype(jnp.float32)     # (D,)
        kt = k_ref[0, t].astype(jnp.float32)
        vt = v_ref[0, t].astype(jnp.float32)
        wt = w_ref[0, t].astype(jnp.float32)
        kv = kt[:, None] * vt[None, :]           # (Dk, Dv)
        out = jnp.sum(rt[:, None] * (s + u[:, None] * kv), axis=0)
        o_ref[0, t] = out.astype(o_ref.dtype)
        return wt[:, None] * s + kv

    state[...] = jax.lax.fori_loop(0, chunk, step, state[...])

    @pl.when(ic == n_chunks - 1)
    def _emit_state():
        sf_ref[0] = state[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def rwkv6_scan(r: jax.Array, k: jax.Array, v: jax.Array, w: jax.Array,
               u: jax.Array, *, chunk: int = 64, interpret: bool = False):
    """r,k,v,w: (B,S,H,D) f32; u: (H,D). Returns (out (B,S,H,D), state (B,H,D,D))."""
    b, s, h, d = r.shape
    chunk_ = min(chunk, s)
    assert s % chunk_ == 0
    nc = s // chunk_

    def bh(x):  # (B,S,H,D) -> (B*H, S, D)
        return x.transpose(0, 2, 1, 3).reshape(b * h, s, d)

    uu = jnp.broadcast_to(u[None], (b, h, d)).reshape(b * h, d)
    kernel = functools.partial(_kernel, chunk=chunk_, n_chunks=nc)
    out, state = pl.pallas_call(
        kernel,
        grid=(b * h, nc),
        in_specs=[
            pl.BlockSpec((1, chunk_, d), lambda i, c: (i, c, 0)),
            pl.BlockSpec((1, chunk_, d), lambda i, c: (i, c, 0)),
            pl.BlockSpec((1, chunk_, d), lambda i, c: (i, c, 0)),
            pl.BlockSpec((1, chunk_, d), lambda i, c: (i, c, 0)),
            pl.BlockSpec((1, d), lambda i, c: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk_, d), lambda i, c: (i, c, 0)),
            pl.BlockSpec((1, d, d), lambda i, c: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, s, d), r.dtype),
            jax.ShapeDtypeStruct((b * h, d, d), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((d, d), jnp.float32)],
        compiler_params=_compiler_params(),
        interpret=interpret,
    )(bh(r), bh(k), bh(v), bh(w), uu)
    return (out.reshape(b, h, s, d).transpose(0, 2, 1, 3),
            state.reshape(b, h, d, d))
