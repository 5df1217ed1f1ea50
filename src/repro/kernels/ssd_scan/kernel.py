"""Pallas TPU kernel: Mamba2 SSD chunked scan (one head per grid row).

Grid = (B*H, n_chunks); the chunk dimension is sequential ("arbitrary")
so a VMEM scratch carries the running SSM state [P, N] across chunks —
the HBM-resident inter-chunk state tensor of the XLA path (models/mamba2)
never exists.  Per chunk the kernel computes, entirely in VMEM:

    intra  = (C B^T  .*  L) dt x        (cs x cs dual form, MXU)
    inter  = C S_in  .*  exp(cumsum dA)
    S_out  = exp(sum dA) S_in + (B dt-decay)^T x

Chunk size cs = 128..256 keeps the [cs, cs] score tile and the [P, N]
state tile (64*128 f32 = 32 KiB) VMEM-resident.

This is the TPU-native blocking of the Mamba2 CUDA kernel (DESIGN.md §5):
the warp-level parallel prefix of the GPU implementation becomes a
grid-sequential VMEM-carried state, which matches the TPU's
software-pipelined sequential grid model.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, dskip_ref,
            y_ref, state_scr, *, n_chunks):
    c_idx = pl.program_id(1)

    @pl.when(c_idx == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    x = x_ref[0].astype(jnp.float32)        # [cs, P]
    dt = dt_ref[0].astype(jnp.float32)      # [cs, 1] -> [cs]
    dt = dt[:, 0]
    a = a_ref[0, 0]                         # scalar (per-head A)
    b = b_ref[0].astype(jnp.float32)        # [cs, N]
    c = c_ref[0].astype(jnp.float32)        # [cs, N]
    d_skip = dskip_ref[0, 0]

    cs = x.shape[0]
    da = dt * a                              # [cs]
    da_cum = jnp.cumsum(da)                  # inclusive
    da_total = da_cum[-1]

    # intra-chunk dual form
    seg = da_cum[:, None] - da_cum[None, :]  # seg[l,s] = sum_{s<k<=l}
    tri = jnp.tril(jnp.ones((cs, cs), jnp.float32))
    l_mat = jnp.exp(jnp.where(tri > 0, seg, -jnp.inf))
    scores = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    m = scores * l_mat                       # [cs(l), cs(s)]
    y_intra = jax.lax.dot_general(m * dt[None, :], x,
                                  (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)

    # inter-chunk contribution from the carried state
    s_in = state_scr[...]                    # [P, N]
    y_inter = jax.lax.dot_general(c, s_in, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32)
    y_inter = y_inter * jnp.exp(da_cum)[:, None]

    y_ref[...] = (y_intra + y_inter + d_skip * x)[None].astype(y_ref.dtype)

    # state update: S_out = exp(da_total) S_in + x^T (B * decay * dt)
    decay = jnp.exp(da_total - da_cum) * dt  # [cs]
    state_new = jnp.exp(da_total) * s_in + jax.lax.dot_general(
        x, b * decay[:, None], (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    state_scr[...] = state_new


def ssd_scan_tiled(x, dt, a, b_mat, c_mat, d_skip, *, chunk: int,
                   interpret: bool = False):
    """x [BH, S, P]; dt [BH, S]; a [BH]; b/c [BH, S, N]; d_skip [BH]
    -> y [BH, S, P].  (ops.py folds batch*heads and broadcasts B/C over
    heads.)  S % chunk == 0."""
    bh, s, p = x.shape
    n = b_mat.shape[-1]
    assert s % chunk == 0
    nc = s // chunk
    grid = (bh, nc)
    kernel = functools.partial(_kernel, n_chunks=nc)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, p), lambda i, c: (i, c, 0)),
            pl.BlockSpec((1, chunk, 1), lambda i, c: (i, c, 0)),
            pl.BlockSpec((1, 1), lambda i, c: (i, 0)),
            pl.BlockSpec((1, chunk, n), lambda i, c: (i, c, 0)),
            pl.BlockSpec((1, chunk, n), lambda i, c: (i, c, 0)),
            pl.BlockSpec((1, 1), lambda i, c: (i, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, p), lambda i, c: (i, c, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, p), x.dtype),
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(x, dt[..., None], a[:, None], b_mat, c_mat, d_skip[:, None])
