"""Pallas TPU kernel for the Mamba (S6) selective-state-space scan.

The XLA fallback materialises the discretised operands dA = exp(dt*A) and
dBx = dt*B*x as (B, chunk, d_inner, d_state) tensors in HBM per chunk —
the dominant memory term of jamba-v0.1-52b in the roofline table.  This
kernel fuses discretisation + recurrence: it reads only dt (B,S,di),
B/C (B,S,ds), x (B,S,di) and A (di,ds) from HBM, keeps the (bd, ds) state
and all discretised quantities in VMEM, and writes y (B,S,di) — HBM
traffic drops from O(S·di·ds) to O(S·(di+ds)), a ~d_state (16x) cut.

Grid: (batch, di_blocks, chunks) with chunks innermost ("arbitrary") so
the state scratch persists; di is blocked to keep (bd, ds) + operand
tiles inside VMEM (bd=512 -> ~0.6 MB scratch at ds=16).

Validated against ``ref.mamba_scan_ref`` in interpret mode.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _compiler_params():
    return pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "arbitrary"))


def _kernel(dt_ref, b_ref, c_ref, x_ref, a_ref, y_ref, state, *, chunk: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        state[...] = jnp.zeros_like(state)

    a = a_ref[...]                                   # (bd, ds)

    def step(t, h):
        dt = dt_ref[0, t].astype(jnp.float32)        # (bd,)
        bt = b_ref[0, t].astype(jnp.float32)         # (ds,)
        ct = c_ref[0, t].astype(jnp.float32)         # (ds,)
        xt = x_ref[0, t].astype(jnp.float32)         # (bd,)
        dA = jnp.exp(dt[:, None] * a)                # (bd, ds) — in VMEM only
        h = dA * h + (dt * xt)[:, None] * bt[None, :]
        y_ref[0, t] = jnp.sum(h * ct[None, :], axis=1).astype(y_ref.dtype)
        return h

    state[...] = jax.lax.fori_loop(0, chunk, step, state[...])


@functools.partial(jax.jit, static_argnames=("chunk", "bd", "interpret"))
def mamba_scan(dt: jax.Array, b: jax.Array, c: jax.Array, x: jax.Array,
               a: jax.Array, *, chunk: int = 128, bd: int = 512,
               interpret: bool = False) -> jax.Array:
    """Selective scan: y[t] = C[t]·h[t],  h[t] = exp(dt[t]A)h[t-1] + dt[t]B[t]x[t].

    dt, x: (B,S,di) f32;  b, c: (B,S,ds) f32;  a: (di,ds) f32 (negative).
    Returns y (B,S,di) f32.  (The D-skip and gating stay outside — they are
    elementwise and fuse on their own.)
    """
    bsz, s, di = dt.shape
    ds = b.shape[-1]
    bd_ = min(bd, di)
    assert di % bd_ == 0
    chunk_ = min(chunk, s)
    assert s % chunk_ == 0
    nd, nc = di // bd_, s // chunk_

    kernel = functools.partial(_kernel, chunk=chunk_)
    y = pl.pallas_call(
        kernel,
        grid=(bsz, nd, nc),
        in_specs=[
            pl.BlockSpec((1, chunk_, bd_), lambda i, j, k: (i, k, j)),   # dt
            pl.BlockSpec((1, chunk_, ds), lambda i, j, k: (i, k, 0)),    # B
            pl.BlockSpec((1, chunk_, ds), lambda i, j, k: (i, k, 0)),    # C
            pl.BlockSpec((1, chunk_, bd_), lambda i, j, k: (i, k, j)),   # x
            pl.BlockSpec((bd_, ds), lambda i, j, k: (j, 0)),             # A
        ],
        out_specs=pl.BlockSpec((1, chunk_, bd_), lambda i, j, k: (i, k, j)),
        out_shape=jax.ShapeDtypeStruct((bsz, s, di), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bd_, ds), jnp.float32)],
        compiler_params=_compiler_params(),
        interpret=interpret,
    )(dt, b, c, x, a)
    return y
