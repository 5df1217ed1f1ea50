"""Pure-jnp oracles for the selection_solve kernels (same math as
core/optimal.py and core/alternating.py, restated on the kernels'
flattened operands)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.problem import accurate_expm1
from repro.kernels.selection_solve.kernel import (
    LN2,
    N_ALT,
    N_BISECT,
    _fused_solve_tile,
)


def _feasible(a, pg, bw, emax, ec, s_bits, tau, p_max):
    expo = jnp.minimum(a * s_bits / (bw * tau), 120.0)
    p_min = accurate_expm1(expo * LN2) / pg
    return (p_min <= p_max) & (tau * p_min + a * ec <= emax)


def selection_solve_ref(pg, bw, emax, ec, *, s_bits: float, tau: float,
                        p_max: float):
    ones = jnp.ones_like(pg)
    feas1 = _feasible(ones, pg, bw, emax, ec, s_bits, tau, p_max)
    lo, hi = jnp.zeros_like(pg), ones

    def body(_, lohi):
        lo, hi = lohi
        mid = 0.5 * (lo + hi)
        ok = _feasible(mid, pg, bw, emax, ec, s_bits, tau, p_max)
        return jnp.where(ok, mid, lo), jnp.where(ok, hi, mid)

    lo, hi = jax.lax.fori_loop(0, N_BISECT, body, (lo, hi))
    a = jnp.where(feas1, 1.0, lo)
    expo = jnp.minimum(a * s_bits / (bw * tau), 120.0)
    p = jnp.clip(accurate_expm1(expo * LN2) / pg, 0.0, p_max)
    return a, p


def fused_solve_ref(pg, bw, emax, ec, *, s_bits: float, tau: float,
                    p_max: float, n_iters: int = N_ALT,
                    faithful_eq13_typo: bool = False):
    """XLA reference for ``fused_solve_tiled``: the identical tile math
    run outside ``pallas_call`` (every iterate materialised in HBM)."""
    return _fused_solve_tile(pg, bw, emax, ec, s_bits=float(s_bits),
                             tau=float(tau), p_max=float(p_max),
                             n_iters=int(n_iters),
                             faithful_eq13_typo=bool(faithful_eq13_typo))
