"""Jit'd wrapper for the decode-attention kernel, shaped to drop into
layers.attn_decode_step (q [B,1,H,dh] + KVCache)."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import resolve_interpret
from repro.kernels.swa_decode.kernel import swa_decode_tiled


@partial(jax.jit, static_argnames=("window", "n_heads", "interpret"))
def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     pos_buf: jax.Array, qpos: jax.Array,
                     *, window: int | None, n_heads: int,
                     interpret: bool | None = None) -> jax.Array:
    """q [B,1,H,dh]; k/v [B,W,Hkv,dh]; returns [B,1,H,dh]."""
    bsz, _, h, dh = q.shape
    hkv = k.shape[2]
    g = h // hkv
    w = k.shape[1]
    kv_blk = 512 if w % 512 == 0 else (256 if w % 256 == 0 else
                                       (128 if w % 128 == 0 else w))
    qg = (q[:, 0] * dh ** -0.5).reshape(bsz, hkv, g, dh)
    out = swa_decode_tiled(qg, k, v, pos_buf.astype(jnp.int32),
                           qpos.astype(jnp.int32), window=window,
                           kv_blk=kv_blk,
                           interpret=resolve_interpret(interpret))
    return out.reshape(bsz, 1, h, dh)
