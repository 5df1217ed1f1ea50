"""Power allocation: Dinkelbach's method (Algorithm 1), vectorised.

The per-(i, k) fractional program (9)

    min_{P^min <= P <= P^max}   a S P / (B log2(1 + P * pg))

is solved for the *whole fleet at once*: the paper iterates devices one by
one on a CPU; on TPU we batch every (i, k) subproblem into element-wise
vector ops inside a single ``lax.while_loop`` with per-element convergence
masking.  This is the hardware adaptation described in DESIGN.md §5.

Closed-form inner step (setting d/dP of (11) to zero):

    P*(lambda) = lambda * B / (a S ln 2) - 1 / pg        (then clipped)

lambda update:  lambda_j = a S P* / (B log2(1 + P* pg)) = a P* T(P*) objective.

Because the ratio P / log(1+cP) is strictly increasing on P > 0, the true
minimiser is the *lower boundary* P = clip(P^min(a), 0, P^max); Dinkelbach
converges there through the clipping.  ``analytic_power`` exposes that
shortcut (bit-identical solution, ~30x fewer flops) as a beyond-paper
solver optimisation; tests assert both agree.

Every update is available in two layers:

* **element level** (``*_elements``): raw ``(a, pg, bw, ...)`` arrays of
  any common shape — the separable (instance, device, round) element set.
  These are the single source of truth for the closed forms; the fused
  flat solver (``core/alternating.py``), the batched engine
  (``core/batch.py``) and the Pallas kernel oracle all build on them.
* **problem level** (``dinkelbach_power`` / ``analytic_power``): the
  original :class:`WirelessFLProblem` API, now thin broadcast shims over
  the element level (bit-identical to the pre-refactor implementations).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.problem import (
    LN2,
    WirelessFLProblem,
    _bcast_like,
    accurate_expm1,
    accurate_log2,
)

_A_FLOOR = 1e-12   # guards the a -> 0 division in P*(lambda)


class PowerSolution(NamedTuple):
    power: jax.Array        # P*_ik
    lam: jax.Array          # converged Dinkelbach lambda (= min energy E^u at a)
    n_iters: jax.Array      # scalar int32, iterations to fleet-wide convergence
    feasible: jax.Array     # bool, P^min(a) <= P^max elementwise


# -------------------------------------------------------- element level

def element_p_min(a, pg, bw, *, s_bits: float, tau: float,
                  expm1=accurate_expm1) -> jax.Array:
    """P^min_ik = (2^{a S / (B tau)} - 1) / pg, exponent-clamped (eq. 7c).

    Mirrors ``WirelessFLProblem.p_min`` on raw element arrays.  ``expm1``
    lets a Pallas body substitute a form its compiler lowers.
    """
    exponent = jnp.minimum(a * s_bits / (bw * tau), 120.0)
    num = expm1(exponent * LN2)
    # zero/NaN gain (deep fade to zero, corrupted channel): P^min = inf is
    # the infeasible-device gate — the raw division emits 0 / 0 = NaN at
    # a = 0 and poisons the fused while-loop (docs/robustness.md)
    return jnp.where(pg > 0, num / jnp.where(pg > 0, pg, 1.0), jnp.inf)


def element_tx_time(power, pg, bw, *, s_bits: float,
                    log2=accurate_log2) -> jax.Array:
    """T_ik(P) = S / r_ik(P) with r = B log2(1 + P pg)  (eq. 1).  ``log2``
    lets a Pallas body substitute a form its compiler lowers."""
    return s_bits / jnp.maximum(bw * log2(1.0 + power * pg), 1e-30)


def _element_lam(a, power, pg, bw, *, s_bits: float,
                 log2=accurate_log2) -> jax.Array:
    """Objective (9a): a P T(P), defined 0 where a = 0 (rate(0) = 0)."""
    t = element_tx_time(power, pg, bw, s_bits=s_bits, log2=log2)
    return jnp.where(a > 0, jnp.maximum(a, _A_FLOOR) * power * t, 0.0)


def analytic_power_elements(a, pg, bw, *, s_bits: float, tau: float,
                            p_max: float, expm1=accurate_expm1,
                            log2=accurate_log2
                            ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Closed-form optimum of (9) per element: P* = clip(P^min(a), 0, P^max).

    Returns ``(power, lam, feasible)`` with ``lam`` the objective (9a) at
    the optimum — exactly what Dinkelbach's lambda converges to.
    """
    p_min = jnp.clip(element_p_min(a, pg, bw, s_bits=s_bits, tau=tau,
                                   expm1=expm1), 0.0, None)
    feasible = p_min <= p_max * (1 + 1e-6)
    p = jnp.minimum(p_min, p_max)
    return p, _element_lam(a, p, pg, bw, s_bits=s_bits, log2=log2), feasible


def dinkelbach_power_elements(a, pg, bw, *, s_bits: float, tau: float,
                              p_max: float,
                              lam0: float | jax.Array = 1e-3,
                              eps: float = 1e-6, max_iters: int = 64
                              ) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Vectorised Algorithm 1 over raw element arrays.

    Returns ``(power, lam, n_iters, feasible)``.  Retained as the faithful
    reference for ``analytic_power_elements`` (which is its fixed point in
    closed form); the while-loop makes this a *nested* iteration when used
    inside the fused solver, so it is a reference mode there.

    ``lam0`` seeds the lambda iteration and may be a per-element array —
    the warm-start hook: Dinkelbach converges to the same fixed point from
    any start (Newton on a concave F(lambda)), so a ``lam0`` taken from a
    nearby problem's converged lambda (see :func:`element_warm_lambda`)
    changes nothing but the iteration count.
    """
    a_safe = jnp.maximum(a, _A_FLOOR)
    p_min = jnp.clip(element_p_min(a, pg, bw, s_bits=s_bits, tau=tau),
                     0.0, None)
    p_lo = jnp.minimum(p_min, p_max)   # clip box; feasibility reported separately
    feasible = p_min <= p_max * (1 + 1e-6)

    def p_star(lam):
        # pg <= 0 (gated-out element): drop the -1/pg offset instead of
        # producing -inf/NaN; the clip to [p_lo, p_max] dominates anyway
        inv_pg = jnp.where(pg > 0, 1.0 / jnp.where(pg > 0, pg, 1.0), 0.0)
        p = lam * bw / (a_safe * s_bits * LN2) - inv_pg
        return jnp.clip(p, p_lo, p_max)

    def lam_of(p):
        # guard P=0 (a=0 rows): rate(0)=0 -> T=inf, but a*P=0; define energy 0.
        return _element_lam(a, p, pg, bw, s_bits=s_bits)

    def cond(state):
        _, lam, lam_prev, it, done = state
        return (~jnp.all(done)) & (it < max_iters)

    def body(state):
        p, lam, lam_prev, it, done = state
        p_new = p_star(lam)
        lam_new = lam_of(p_new)
        # relative criterion: energies span ~1e-12..1e2 J across the fleet,
        # so an absolute epsilon would freeze small-energy elements early.
        done_new = jnp.abs(lam_new - lam) <= eps * jnp.maximum(jnp.abs(lam_new), 1e-30)
        # frozen elements keep their converged values
        p_out = jnp.where(done, p, p_new)
        lam_out = jnp.where(done, lam, lam_new)
        return p_out, lam_out, lam, it + 1, done | done_new

    lam_init = jnp.full_like(a, lam0)
    p_init = p_star(lam_init)
    state = (p_init, lam_of(p_init), lam_init, jnp.int32(0), jnp.zeros_like(a, bool))
    p, lam, _, iters, _ = jax.lax.while_loop(cond, body, state)
    return p, lam, iters, feasible


def energy_gate_elements(a, lam, emax, ec) -> jax.Array:
    """Algorithm 2 line 4: objective (9a) <= H_ik = E^max - a E^c (eq. 10)."""
    h = emax - a * ec
    return lam <= h + 1e-9


def element_warm_lambda(a0, p0, pg, bw, *, s_bits: float,
                        lam_floor: float = 1e-3) -> jax.Array:
    """Per-element Dinkelbach seed from a previous solution ``(a0, p0)``.

    Evaluates the objective (9a) at the previous powers on the *current*
    channel: lam0 = a0 P0 T(P0).  On a drifting channel this lands within
    the drift of the new converged lambda, so Algorithm 1 terminates in
    1-3 iterations instead of its cold ~10-60 (see docs/serving.md).
    Elements with no usable previous state (a0 = 0 or P0 = 0, e.g. padded
    slots or newly admitted devices) fall back to the cold-start constant
    ``lam_floor`` — the same 1e-3 the cold path uses.
    """
    lam = _element_lam(a0, p0, pg, bw, s_bits=s_bits)
    return jnp.where((a0 > 0) & (p0 > 0) & (lam > 0), lam, lam_floor)


# -------------------------------------------------------- problem level

def _element_operands(problem: WirelessFLProblem, a: jax.Array):
    """``(a, pg, bw, s)`` broadcast to a common element rank.

    A 1-d ``a`` on a fading problem is materialised to the path gain's
    ``[N, K]`` shape ("same probability, each round's channel" — the
    ``problem.py`` broadcasting contract) so the element-level while
    loops carry shape-stable state; ``bw`` gains a trailing round axis
    whenever any operand is per-round.  ``s`` is the effective payload
    :meth:`WirelessFLProblem.payload_bits` at that rank — the static
    python float when the problem has no ``bits`` leaf (the element
    closed forms are pure elementwise jnp math, so float and array
    payloads trace identically apart from the extra broadcast).
    """
    pg = problem._pg(a)
    bw = problem.bandwidth_hz
    rank = max(a.ndim, pg.ndim)
    if rank > bw.ndim:
        bw = bw[:, None]
    if a.ndim < pg.ndim:
        a = jnp.broadcast_to(a[:, None], pg.shape)
    return a, pg, bw, problem.payload_bits(rank)


def dinkelbach_power(problem: WirelessFLProblem,
                     a: jax.Array,
                     *,
                     lam0: float | jax.Array = 1e-3,
                     eps: float = 1e-6,
                     max_iters: int = 64) -> PowerSolution:
    """Vectorised Algorithm 1 over every (i, k) subproblem simultaneously."""
    a, pg, bw, s = _element_operands(problem, a)
    p, lam, iters, feasible = dinkelbach_power_elements(
        a, pg, bw, s_bits=s, tau=problem.tau_th,
        p_max=problem.p_max, lam0=lam0, eps=eps, max_iters=max_iters)
    return PowerSolution(power=p, lam=lam, n_iters=iters, feasible=feasible)


def analytic_power(problem: WirelessFLProblem, a: jax.Array) -> PowerSolution:
    """Closed-form optimum of (9): the ratio is increasing in P, so
    P* = clip(P^min(a), 0, P^max).  Beyond-paper solver fast path."""
    a, pg, bw, s = _element_operands(problem, a)
    p, lam, feasible = analytic_power_elements(
        a, pg, bw, s_bits=s, tau=problem.tau_th,
        p_max=problem.p_max)
    return PowerSolution(power=p, lam=lam, n_iters=jnp.int32(0), feasible=feasible)


def energy_bound_ok(problem: WirelessFLProblem, a: jax.Array, sol: PowerSolution) -> jax.Array:
    """Algorithm 2 line 4: is objective (9a) <= H_ik = E^max - a E^c (eq. 10)?

    Ranks follow the ``problem.py`` contract: a 1-d ``a`` against a
    per-round ``sol.lam`` (fading problem) broadcasts across rounds.
    """
    rank = max(a.ndim, jnp.ndim(sol.lam))
    ec = _bcast_like(problem.compute_energy(), rank)
    emax = _bcast_like(problem.energy_budget_j, rank)
    return energy_gate_elements(_bcast_like(a, rank), sol.lam, emax, ec)


# Two f32 programs that form x = a S ln2 / (B tau) through a handful of
# roundings (a S, B tau, the quotient, the ln 2 product) may differ by a
# few f32 epsilons of x; powers at the floor (a = 0) differ by at most
# an absolute 1e-6 W.
_AGREEMENT_ULPS = 4.0
_AGREEMENT_ATOL = 1e-6


def power_agreement_tol(problem: WirelessFLProblem, a, power, *,
                        a_other=None) -> np.ndarray:
    """Per-element bound on ``|P_1 - P_2|`` between two f32 solves that
    reach the same fixed point ``(a, power)`` through different programs
    (chunked vs unchunked, XLA vs Pallas, CPU vs TPU).

    ``P^min = expm1(x) / pg`` with ``x = a S_i ln2 / (B_i tau)``.  A
    relative error ``r`` in ``x`` moves ``P^min`` by ``kappa(x) r``, where
    ``kappa(x) = x e^x / expm1(x)`` is the condition number of expm1: 1
    at ``x = 0`` and about ``x`` for large ``x``.  Rounding gives
    ``r = _AGREEMENT_ULPS`` f32 epsilons.  Where the two solves'
    selections differ by more than rounding (a fixed iteration count
    against a converged loop, or an element on the ``P^max`` feasibility
    edge whose gate tips the other way), pass the other solve's ``a`` as
    ``a_other``: its relative distance to ``a`` is a relative error in
    ``x`` too.  Returns ``_AGREEMENT_ATOL + kappa(x) * (r + |a - a_other|
    / a) * |power|`` in float64, shaped like ``a``.
    """
    a = np.asarray(a, np.float64)
    rank = a.ndim
    s = problem.payload_bits(rank)
    bw = _bcast_like(problem.bandwidth_hz, rank)
    x = np.minimum(a * np.asarray(s, np.float64)
                   / (np.asarray(bw, np.float64) * problem.tau_th),
                   120.0) * LN2
    safe = np.where(x > 0, x, 1.0)
    kappa = np.where(x > 0, safe * np.exp(safe) / np.expm1(safe), 1.0)
    rel = _AGREEMENT_ULPS * float(np.finfo(np.float32).eps)
    if a_other is not None:
        a_other = np.asarray(a_other, np.float64)
        top = np.maximum(a, a_other)
        rel = rel + np.abs(a - a_other) / np.where(top > 0, top, 1.0)
    return _AGREEMENT_ATOL + kappa * rel * np.abs(np.asarray(power, np.float64))
