"""Pallas TPU kernels: fleet-scale joint selection/power solve.

Two solvers over the same pre-flattened element tiles:

* ``selection_solve_tiled`` — the per-device *global* optimum of problem
  (7) (the monotone bisection of core/optimal.py), 60 fixed bisection
  iterations.
* ``fused_solve_tiled``     — the paper's Algorithm 2 as the fused
  single-level alternating fixed point (core/alternating.py
  ``fused_fixed_point``): closed-form power update, eq.-10 energy gate
  and eq.-13 selection update per iteration, a fixed ``n_iters`` trip
  count on the VPU with each element frozen once converged.  Same local
  optimum as ``solve_joint`` (<= 1e-5 elementwise).

Device state (path gain, bandwidth, budgets, compute energy) is streamed
HBM -> VMEM in (ROWS, 128) blocks and every iterate stays VMEM-resident —
branch-free elementwise ops, no host loop, no re-materialisation of
intermediates in HBM.  For planetary-scale FL fleets (10^5-10^7 devices x
rounds) this is the compute hot-spot of the paper's technique; the pure
XLA paths materialise each iterate in HBM.

Inputs are pre-flattened [M, 128] tiles (ops.py handles padding/reshape):
    path_gain   g / (d^2 sigma^2)           [M,128] f32
    bandwidth   B_i                         [M,128] f32
    e_max       per-round energy budget     [M,128] f32
    e_comp      E^c_i                       [M,128] f32
scalars (compiled in): S (bits), tau, p_max.
Outputs: a* and P*, both [M,128] f32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.alternating import FleetElements, _fused_step, fused_init

LN2 = 0.6931471805599453

DEFAULT_ROWS = 256      # (256, 128) f32 tile = 128 KiB/operand in VMEM
N_BISECT = 60
N_ALT = 50              # fused alternating iterations (solve_joint max_iters)
EPS = 1e-7              # per-element stopping step (solve_joint_fused eps)


# Mosaic lowers neither ``expm1`` nor an accuracy request on ``exp`` and
# ``log``, and the TPU's own f32 exp and log are approximations (on a
# v5e, exp up to 57 ulp off and log up to 2206 ulp) that break the closed
# forms' round trip (core/problem.py).  The kernels therefore build their
# transcendentals from f32 arithmetic, bit shifts and short polynomials,
# each within 2 ulp on the v5e.
_LN2_HI = 0.693145751953125      # ln 2 to 15 bits: n * _LN2_HI is exact
_LN2_LO = 1.4286068202862268e-06  # ln 2 - _LN2_HI
_SQRT2 = 1.4142135623730951


def _exp(x):
    """exp(x) for 0 <= x <= 88: x = n ln 2 + r with |r| <= ln 2 / 2
    (Cody-Waite, so r is exact), exp(r) from its degree-8 Taylor series
    (truncation < 1e-10 relative) and 2^n written into the exponent."""
    n = jnp.floor(x * (1.0 / LN2) + 0.5)
    r = (x - n * _LN2_HI) - n * _LN2_LO
    t = jnp.ones_like(x)
    for k in range(8, 0, -1):
        t = 1.0 + r * (1.0 / k) * t
    scale = jax.lax.bitcast_convert_type(
        jax.lax.shift_left(n.astype(jnp.int32) + 127, 23), jnp.float32)
    return t * scale


def _expm1(x):
    """exp(x) - 1 for 0 <= x <= 88.  Below ln 2 the degree-10 Taylor
    series in Horner form is exact to f32 rounding (truncation < 1e-9
    relative), so the result keeps its relative accuracy near 0, where
    ``exp(x) - 1`` cancels; from ln 2 up, ``exp(x) - 1`` loses at most one
    bit.  (Kahan's ``(u - 1) x / log(u)`` is not used: XLA rewrites
    ``log(exp(x))`` to ``x``, which undoes it in interpret mode.)"""
    small = x < LN2
    xs = jnp.where(small, x, 0.0)
    t = jnp.ones_like(x)
    for k in range(10, 1, -1):
        t = 1.0 + xs * (1.0 / k) * t
    return jnp.where(small, xs * t, _exp(jnp.where(small, 0.0, x)) - 1.0)


def _log2(y):
    """log2(y) for normal y > 0 (inf and NaN pass through): y = 2^e m with
    m in [sqrt(1/2), sqrt(2)) read from the bits, and ln m = 2 atanh(s),
    s = (m - 1) / (m + 1), |s| < 0.172, from its series to s^13
    (truncation < 1e-11 relative)."""
    bits = jax.lax.bitcast_convert_type(y, jnp.int32)
    e = jax.lax.shift_right_arithmetic(bits, 23) - 127
    m = jax.lax.bitcast_convert_type(
        jax.lax.bitwise_or(jax.lax.bitwise_and(bits, 0x007FFFFF), 0x3F800000),
        jnp.float32)
    big = m > _SQRT2
    m = jnp.where(big, 0.5 * m, m)
    e = jnp.where(big, e + 1, e)
    s = (m - 1.0) / (m + 1.0)
    s2 = s * s
    t = jnp.full_like(y, 1.0 / 13)
    for k in (11, 9, 7, 5, 3, 1):
        t = 1.0 / k + s2 * t
    out = e.astype(jnp.float32) + (2.0 / LN2) * s * t
    return jnp.where(y < jnp.inf, out, y)


def _feasible(a, pg, bw, emax, ec, s_bits, tau, p_max):
    """F(a): P^min(a) <= P^max  and  tau P^min(a) + a E^c <= E^max."""
    expo = jnp.minimum(a * s_bits / (bw * tau), 120.0)
    p_min = _expm1(expo * LN2) / pg
    power_ok = p_min <= p_max
    energy_ok = tau * p_min + a * ec <= emax
    return power_ok & energy_ok


def _solve_tile(pg, bw, emax, ec, *, s_bits, tau, p_max):
    ones = jnp.ones_like(pg)
    feas1 = _feasible(ones, pg, bw, emax, ec, s_bits, tau, p_max)
    lo = jnp.zeros_like(pg)
    hi = ones

    def body(_, lohi):
        lo, hi = lohi
        mid = 0.5 * (lo + hi)
        ok = _feasible(mid, pg, bw, emax, ec, s_bits, tau, p_max)
        return jnp.where(ok, mid, lo), jnp.where(ok, hi, mid)

    lo, hi = jax.lax.fori_loop(0, N_BISECT, body, (lo, hi))
    a = jnp.where(feas1, 1.0, lo)
    expo = jnp.minimum(a * s_bits / (bw * tau), 120.0)
    p = jnp.clip(_expm1(expo * LN2) / pg, 0.0, p_max)
    return a, p


def _kernel(pg_ref, bw_ref, emax_ref, ec_ref, a_ref, p_ref,
            *, s_bits, tau, p_max):
    a, p = _solve_tile(pg_ref[...], bw_ref[...], emax_ref[...], ec_ref[...],
                       s_bits=s_bits, tau=tau, p_max=p_max)
    a_ref[...] = a
    p_ref[...] = p


def selection_solve_tiled(pg, bw, emax, ec, *, s_bits: float, tau: float,
                          p_max: float, rows: int = DEFAULT_ROWS,
                          interpret: bool = False):
    """pg/bw/emax/ec: [M, 128] f32 with M % rows == 0."""
    kernel = functools.partial(_kernel, s_bits=float(s_bits), tau=float(tau),
                               p_max=float(p_max))
    return _launch_tiled(kernel, pg, bw, emax, ec, rows=rows,
                         interpret=interpret)


def _launch_tiled(kernel, pg, bw, emax, ec, *, rows: int, interpret: bool):
    m, lanes = pg.shape
    assert lanes == 128 and m % rows == 0, (m, lanes, rows)
    grid = (m // rows,)
    blk = pl.BlockSpec((rows, 128), lambda i: (i, 0))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[blk] * 4,
        out_specs=[blk, blk],
        out_shape=[jax.ShapeDtypeStruct((m, 128), jnp.float32)] * 2,
        interpret=interpret,
    )(pg, bw, emax, ec)


# ----------------------------------------- fused alternating fixed point

def _fused_solve_tile(pg, bw, emax, ec, *, s_bits, tau, p_max, n_iters,
                      faithful_eq13_typo):
    """The fused alternation on one tile, reusing the *same* step and
    init as the XLA solver (``core/alternating.py`` — plain elementwise
    jnp, legal inside a Pallas body), so the kernel can never drift from
    ``solve_joint_fused``; only the loop shape differs: a fixed trip
    count, with each element frozen at the first step that moves it by
    less than ``EPS`` — the XLA solve's stopping rule, applied per
    element.  Without the freeze, f32 rounding keeps an element whose
    time constraint binds moving by a few ulp per step, and the fixed
    trip count sums those steps."""
    el = FleetElements(pg=pg, bw=bw, emax=emax, ec=ec)
    step = functools.partial(_fused_step, el=el, s_bits=s_bits, tau=tau,
                             p_max=p_max, power_solver="analytic",
                             faithful_eq13_typo=faithful_eq13_typo,
                             expm1=_expm1, log2=_log2)
    a0, _ = fused_init(el, s_bits=s_bits, tau=tau, p_max=p_max,
                       faithful_eq13_typo=faithful_eq13_typo, log2=_log2)

    def advance(a, p, moving):
        a_new, p_new = step(a)[:2]
        keep = moving > 0
        return (jnp.where(keep, a_new, a), jnp.where(keep, p_new, p),
                jnp.where(keep & (jnp.abs(a_new - a) >= EPS), 1.0, 0.0))

    # the seeding step(a0) is iteration 1, as in fused_fixed_point /
    # solve_joint — n_iters total steps, not n_iters + 1 (the step's third
    # output, the inner Dinkelbach count, is always 0 in analytic mode)
    first = advance(a0, jnp.zeros_like(a0), jnp.ones_like(a0))
    a, p, _ = jax.lax.fori_loop(1, n_iters, lambda _, s: advance(*s), first)
    return a, p


def _fused_kernel(pg_ref, bw_ref, emax_ref, ec_ref, a_ref, p_ref,
                  *, s_bits, tau, p_max, n_iters, faithful_eq13_typo):
    a, p = _fused_solve_tile(pg_ref[...], bw_ref[...], emax_ref[...],
                             ec_ref[...], s_bits=s_bits, tau=tau,
                             p_max=p_max, n_iters=n_iters,
                             faithful_eq13_typo=faithful_eq13_typo)
    a_ref[...] = a
    p_ref[...] = p


def fused_solve_tiled(pg, bw, emax, ec, *, s_bits: float, tau: float,
                      p_max: float, n_iters: int = N_ALT,
                      faithful_eq13_typo: bool = False,
                      rows: int = DEFAULT_ROWS, interpret: bool = False):
    """Fused alternating fixed point over [M, 128] f32 tiles.

    ``n_iters`` is a fixed trip count (fori, fully VMEM-resident); each
    element freezes at its first step below ``EPS``, so running the
    ``solve_joint`` iteration budget unconditionally trades a negligible
    amount of VPU work for branch-free tiles.
    """
    kernel = functools.partial(_fused_kernel, s_bits=float(s_bits),
                               tau=float(tau), p_max=float(p_max),
                               n_iters=int(n_iters),
                               faithful_eq13_typo=bool(faithful_eq13_typo))
    return _launch_tiled(kernel, pg, bw, emax, ec, rows=rows,
                         interpret=interpret)
