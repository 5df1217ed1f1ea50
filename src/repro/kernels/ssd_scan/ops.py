"""Jit'd wrapper: Mamba2-shaped SSD via the Pallas kernel — drop-in for
models.mamba2.ssd_chunked (head-grouped B/C broadcast + batch*head fold)."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import resolve_interpret
from repro.kernels.ssd_scan.kernel import ssd_scan_tiled


@partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_apply(x, dt, a, b_mat, c_mat, d_skip, *, chunk: int = 128,
              interpret: bool | None = None):
    """Same signature as models.mamba2.ssd_chunked (minus init_state):
    x [B,S,H,P]; dt [B,S,H]; a [H]; b/c [B,S,N]; d_skip [H] -> y [B,S,H,P]."""
    bsz, s, h, p = x.shape
    n = b_mat.shape[-1]
    xf = x.transpose(0, 2, 1, 3).reshape(bsz * h, s, p)
    dtf = dt.transpose(0, 2, 1).reshape(bsz * h, s)
    af = jnp.tile(a, bsz)
    bf = jnp.repeat(b_mat, h, axis=0).reshape(bsz, h, s, n).reshape(bsz * h, s, n)
    cf = jnp.repeat(c_mat, h, axis=0).reshape(bsz, h, s, n).reshape(bsz * h, s, n)
    df = jnp.tile(d_skip, bsz)
    y = ssd_scan_tiled(xf, dtf, af, bf, cf, df, chunk=chunk,
                       interpret=resolve_interpret(interpret))
    return y.reshape(bsz, h, s, p).transpose(0, 2, 1, 3)
