"""Jit'd wrapper: aggregate a pytree of stacked client gradients with a
coefficient vector — the FL engine's ``aggregate_fn`` plug-in
(engine.run_fl(aggregate_fn=masked_aggregate_pytree))."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import resolve_interpret
from repro.kernels.masked_aggregate.kernel import (
    CLIENT_BLK, LANE_BLK, masked_aggregate_tiled,
    quantized_masked_aggregate_tiled)
from repro.kernels.masked_aggregate.ref import quantizer_levels


@partial(jax.jit, static_argnames=("interpret",))
def masked_aggregate(gstack: jax.Array, coef: jax.Array,
                     interpret: bool | None = None) -> jax.Array:
    """gstack [N, ...] -> [...] (leading client axis reduced).

    ``interpret=None`` compiles the kernel on TPU and interprets it
    elsewhere (``repro.kernels.resolve_interpret``).
    """
    interpret = resolve_interpret(interpret)
    n = gstack.shape[0]
    lead_shape = gstack.shape[1:]
    d = int(np.prod(lead_shape))
    flat = gstack.reshape(n, d)
    n_pad = -(-n // CLIENT_BLK) * CLIENT_BLK - n
    d_pad = -(-d // LANE_BLK) * LANE_BLK - d
    flat = jnp.pad(flat, ((0, n_pad), (0, d_pad)))
    coef_p = jnp.pad(coef, (0, n_pad))
    out = masked_aggregate_tiled(flat, coef_p, interpret=interpret)
    return out[:d].reshape(lead_shape)


def masked_aggregate_pytree(gstack_tree, coef, interpret: bool | None = None):
    return jax.tree_util.tree_map(
        lambda g: masked_aggregate(g, coef, interpret=interpret).astype(g.dtype),
        gstack_tree)


@partial(jax.jit, static_argnames=("interpret",))
def quantized_masked_aggregate(gstack: jax.Array, coef: jax.Array,
                               noise: jax.Array, bits,
                               interpret: bool | None = None) -> jax.Array:
    """gstack/noise [N, ...] -> [...]: per-client b_i-bit stochastic-rounding
    quantisation fused into the masked sum.  ``bits`` is a scalar or [N]
    array; ``noise`` is uniform(0,1) of gstack's shape (precomputed so the
    kernel matches the unfused quantise-then-sum path exactly)."""
    interpret = resolve_interpret(interpret)
    n = gstack.shape[0]
    lead_shape = gstack.shape[1:]
    d = int(np.prod(lead_shape))
    flat = gstack.reshape(n, d).astype(jnp.float32)
    noise_f = noise.reshape(n, d).astype(jnp.float32)
    levels = jnp.broadcast_to(quantizer_levels(bits), (n,))
    scale = jnp.maximum(jnp.max(jnp.abs(flat), axis=1), 1e-12) / levels
    n_pad = -(-n // CLIENT_BLK) * CLIENT_BLK - n
    d_pad = -(-d // LANE_BLK) * LANE_BLK - d
    flat = jnp.pad(flat, ((0, n_pad), (0, d_pad)))
    noise_f = jnp.pad(noise_f, ((0, n_pad), (0, d_pad)), constant_values=1.0)
    coef_p = jnp.pad(coef, (0, n_pad))
    scale_p = jnp.pad(scale, (0, n_pad), constant_values=1.0)
    levels_p = jnp.pad(levels, (0, n_pad), constant_values=1.0)
    out = quantized_masked_aggregate_tiled(flat, coef_p, noise_f, scale_p,
                                           levels_p, interpret=interpret)
    return out[:d].reshape(lead_shape)


def quantized_aggregate_pytree(gstack_tree, coef, key, bits,
                               interpret: bool | None = None):
    """Key-streamed pytree front-end: splits ``key`` exactly like
    ``engine._quantize_tree`` (per leaf, then per client) so the fused
    kernel reproduces the unfused engines' noise bit-for-bit."""
    leaves, treedef = jax.tree_util.tree_flatten(gstack_tree)
    keys = jax.random.split(key, len(leaves))
    out = []
    for leaf, k in zip(leaves, keys):
        ks = jax.random.split(k, leaf.shape[0])
        noise = jax.vmap(
            lambda kk, shp=leaf.shape[1:]: jax.random.uniform(kk, shp))(ks)
        out.append(quantized_masked_aggregate(
            leaf, coef, noise, bits, interpret=interpret).astype(leaf.dtype))
    return jax.tree_util.tree_unflatten(treedef, out)
