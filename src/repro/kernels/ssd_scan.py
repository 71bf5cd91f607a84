"""Mamba-2 SSD intra-chunk kernel (Pallas TPU).

The quadratic-in-chunk part of the SSD algorithm (arXiv:2405.21060 §6) is the
compute hot-spot of every mamba layer (mamba2-1.3b, jamba): per (batch,
chunk, head) it builds the causal decay matrix L, the C·Bᵀ Gram matrix, and
contracts against x — all MXU matmuls once tiled. The inter-chunk recurrence
(linear) and the carried-state output term stay in jnp (ops.py composes).

The wrapper moves the head axis in front of the chunk axis, so every block's
last two dimensions are a whole (chunk, feature) tile, as Mosaic requires.
Block layout per grid step (b, z=chunk, h):
  x      (1, 1, 1, C, P)  VMEM     y_diag (1, 1, 1, C, P)
  dt_col (1, 1, 1, C, 1)           states (1, 1, 1, P, N)
  dt_row (1, 1, 1, 1, C)
  B, C   (1, 1, 1, C, N)
  A      (H,)             SMEM (whole array, read at the head index)
dt comes in both orientations so the kernel needs no in-register transpose
of a vector: the column feeds row-wise scales, the row column-wise ones.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_chunk_kernel(x_ref, dtc_ref, dtr_ref, a_ref, b_ref, c_ref,
                      y_ref, st_ref):
    x = x_ref[0, 0, 0].astype(jnp.float32)          # (C, P)
    bm = b_ref[0, 0, 0].astype(jnp.float32)         # (C, N)
    cm = c_ref[0, 0, 0].astype(jnp.float32)         # (C, N)
    a = a_ref[pl.program_id(2)]                     # scalar (per head)
    dt_col = dtc_ref[0, 0, 0].astype(jnp.float32)   # (C, 1)
    dt_row = dtr_ref[0, 0, 0].astype(jnp.float32)   # (1, C)

    c_len = x.shape[0]
    rows = jax.lax.broadcasted_iota(jnp.int32, (c_len, c_len), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (c_len, c_len), 1)
    causal = rows >= cols
    # inclusive prefix sums of dt*a in both orientations, as masked sums
    cs_col = jnp.sum(jnp.where(causal, dt_row * a, 0.0), axis=1,
                     keepdims=True)                 # (C, 1)
    cs_row = jnp.sum(jnp.where(rows <= cols, dt_col * a, 0.0), axis=0,
                     keepdims=True)                 # (1, C)
    ell = jnp.where(causal, jnp.exp(cs_col - cs_row), 0.0)   # sum_{j+1..i}

    cb = jnp.dot(cm, bm.T, preferred_element_type=jnp.float32)   # (C, C)
    w = cb * ell * dt_row
    y_ref[0, 0, 0] = jnp.dot(w, x, preferred_element_type=jnp.float32)

    total = jnp.sum(dt_row * a)
    decay = jnp.exp(total - cs_col)                 # (C, 1)
    st_ref[0, 0, 0] = jax.lax.dot_general(
        x, bm * (dt_col * decay), (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)                      # (P, N)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssd_chunk_pallas(x, dt, A, B, C, interpret=True):
    """x (b, nc, c, h, p); dt (b, nc, c, h); A (h,); B, C (b, nc, c, h, n).

    Returns (y_diag (b,nc,c,h,p), states (b,nc,h,p,n)).
    """
    b, nc, c, h, p = x.shape
    n = B.shape[-1]
    grid = (b, nc, h)

    def tile(last):
        return pl.BlockSpec((1, 1, 1) + last,
                            lambda bi, zi, hi: (bi, zi, hi, 0, 0))

    dth = dt.transpose(0, 1, 3, 2)                  # (b, nc, h, c)
    y, st = pl.pallas_call(
        _ssd_chunk_kernel,
        grid=grid,
        in_specs=[
            tile((c, p)),
            tile((c, 1)),
            tile((1, c)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            tile((c, n)),
            tile((c, n)),
        ],
        out_specs=[tile((c, p)), tile((p, n))],
        out_shape=[
            jax.ShapeDtypeStruct((b, nc, h, c, p), jnp.float32),
            jax.ShapeDtypeStruct((b, nc, h, p, n), jnp.float32),
        ],
        interpret=interpret,
    )(
        x.transpose(0, 1, 3, 2, 4),
        dth[..., None],
        dth[..., None, :],
        A.astype(jnp.float32),
        B.transpose(0, 1, 3, 2, 4),
        C.transpose(0, 1, 3, 2, 4),
    )
    return y.transpose(0, 1, 3, 2, 4), st
