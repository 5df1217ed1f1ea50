"""Algorithm 2: alternating optimisation of (7).

repeat:
    P^{n+1}  <- power update (Algorithm 1 / closed form) at a^n
    if objective (9a) bounded by H (eq. 10):      (feasibility gate, line 4)
        a^{n+1} <- closed form (13)
until converged

The objective is monotone non-decreasing and bounded by sum(w) = 1, so the
loop converges to a local optimum (paper, Sec. IV-B).  Elements whose
energy gate fails keep their previous a (the paper "breaks"; per-element
freezing is the batched equivalent and can only do better).

Three implementations:

* ``solve_joint``       — the paper-shaped solve: a ``lax.while_loop``
                          whose stopping rule is the *global* objective
                          delta, with the power subproblem solved by
                          Dinkelbach's inner ``while_loop`` by default.
* ``solve_joint_trace`` — python loop recording the objective path.  It
                          runs exactly the same ``_alternating_step`` and
                          the same f32 stopping predicate ``_converged``
                          as ``solve_joint``, so both count iterations
                          identically (no off-by-one: both perform at most
                          ``max_iters`` steps and ``n_iters`` is the
                          number of steps actually taken).
* ``solve_joint_fused`` — the fused single-level solver: one flat,
                          convergence-masked fixed-point iteration over
                          the separable (instance, device, round) element
                          set.  The closed-form ``analytic_power`` update
                          (the Dinkelbach fixed point, see power.py), the
                          eq.-10 energy gate and the eq.-13 selection
                          update run in a single ``lax.while_loop`` body;
                          there is **no nested loop**, so vmapped/stacked
                          ensembles never wait on the slowest inner solve.
                          Stopping is per element (max |Δa| < eps), which
                          implies the global rule: sum(w) = 1 means
                          |Δobj| <= max|Δa| < eps.  Supports a
                          ``chunk_elements`` memory bound and an
                          element-axis ``NamedSharding`` for mega-fleet
                          (10^5..10^6 device) solves — see
                          ``fused_fixed_point_flat``.

Warm starts (the online / serving path)
---------------------------------------

``solve_joint`` and ``solve_joint_fused`` accept an optional
``init=(a0, p0)`` resumable state — typically ``previous.resume`` from an
earlier :class:`JointSolution` on a nearby problem (a drifted channel,
a perturbed energy budget).  Semantics, chosen so warm starts can never
change the answer:

* The selection iterate still starts from the canonical feasible point
  (eq. 13 at P^max).  Algorithm 2's alternation is monotone
  non-increasing in ``a`` — the eq.-13 time term at P = P^min(a) is
  exactly ``a`` — so seeding ``a`` from a stale solution would ratchet
  the objective down over a stream of drifting solves instead of
  tracking the true optimum.  The canonical start is a closed form, so
  there is nothing to save there anyway.
* What the warm start *does* seed is the iterative machinery: with
  ``power_solver="dinkelbach"`` the inner Algorithm-1 lambda iteration
  starts from the init state's energy ``lam0 = a0 P0 T(P0)`` (evaluated
  on the current channel) instead of the cold constant.  Dinkelbach is
  globally convergent, so the solution is unchanged (bit-for-bit in
  practice) while the inner iteration count collapses ~10x on a
  coherent channel — ``JointSolution.inner_iters`` reports it, and the
  ``fleet_service_throughput`` benchmark gates it.  The closed-form
  ``"analytic"`` mode has no inner iterations to save; it accepts
  ``init`` as a no-op so callers can thread state unconditionally.

When ``init`` is omitted every solver is bit-identical to the cold path.
"""
from __future__ import annotations

import functools
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.power import (
    PowerSolution,
    analytic_power,
    analytic_power_elements,
    dinkelbach_power,
    dinkelbach_power_elements,
    element_tx_time,
    element_warm_lambda,
    energy_bound_ok,
    energy_gate_elements,
)
from repro.core.problem import WirelessFLProblem, accurate_expm1, accurate_log2
from repro.core.selection import optimal_selection, selection_update_elements


class WarmStart(NamedTuple):
    """Resumable solver state: a previous solution's ``(a, power)``.

    Feed it back as ``solve_joint(..., init=state)`` (or the fused/batch
    equivalents) to warm-start the next solve on a nearby problem.  Any
    ``(a0, p0)`` pair of the right shape works — the NamedTuple is just
    the canonical carrier, obtained from ``JointSolution.resume``.
    """

    a: jax.Array
    power: jax.Array


class JointSolution(NamedTuple):
    a: jax.Array           # selection probabilities a*_ik
    power: jax.Array       # transmit powers P*_ik
    objective: jax.Array   # scalar, sum_i w_i a_i (per round)
    n_iters: jax.Array     # outer iterations used
    converged: jax.Array   # bool
    # total inner power-solver (Algorithm 1) iterations summed over the
    # outer steps; 0 for the closed-form analytic mode.  The figure warm
    # starts collapse — see the module docstring.
    inner_iters: jax.Array | int = 0
    # per-element uplink bit widths chosen by the bit-allocation step —
    # only set when solving with a ``bit_menu`` (docs/compression.md);
    # None otherwise.
    bits: Optional[jax.Array] = None

    @property
    def resume(self) -> WarmStart:
        """The resumable warm-start state for a subsequent nearby solve."""
        return WarmStart(a=self.a, power=self.power)


def _init_state(problem: WirelessFLProblem, shape) -> tuple[jax.Array, jax.Array]:
    """Feasible (a^0, P^0): transmit at P^max, then a^0 from (13)."""
    p0 = jnp.full(shape, problem.p_max)
    a0 = optimal_selection(problem, p0)
    return a0, p0


def _solution_shape(problem: WirelessFLProblem, per_round: bool):
    n = problem.n_devices
    if problem.fading is not None:
        if not per_round:
            # a 1-d iterate against the [N, K] path gain only "works"
            # when K == N, and is then silently wrong — refuse instead
            raise ValueError(
                "per_round=False is meaningless on a fading problem: the "
                "closed forms are separable per (i, k), so solve with "
                "per_round=True (solution shape [N, K])")
        return (n, problem.n_rounds)
    return (n,)


# ------------------------------------------------- shared Algorithm-2 step

def _alternating_step(problem: WirelessFLProblem, a: jax.Array,
                      solver: Callable[..., PowerSolution],
                      faithful_eq13_typo: bool
                      ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One Algorithm-2 alternation: power update, eq.-10 gate, eq.-13.

    Returns ``(a_new, power, inner_iters)`` — the last is the power
    subproblem's iteration count (0 for the closed-form solvers).
    """
    sol = solver(problem, a)
    ok = energy_bound_ok(problem, a, sol) & sol.feasible
    a_new = optimal_selection(problem, sol.power,
                              faithful_eq13_typo=faithful_eq13_typo)
    # freeze elements whose power subproblem is infeasible / unbounded
    a_new = jnp.where(ok, a_new, a)
    return a_new, sol.power, sol.n_iters


def _converged(obj: jax.Array, obj_prev: jax.Array, eps: float) -> jax.Array:
    """The single stopping predicate both solve_joint paths share.

    Evaluated on-device in the objective's dtype (f32): the python trace
    loop must not compare float64-upcast copies, or its iteration count
    can differ from the ``while_loop``'s by one near the threshold.
    """
    return jnp.abs(obj - obj_prev) < eps


def _warm_solver(problem: WirelessFLProblem, power_solver: str,
                 init: Optional[tuple[jax.Array, jax.Array]],
                 shape) -> Callable[..., PowerSolution]:
    """Resolve the power solver, seeding Dinkelbach's lambda from ``init``.

    The warm seed only touches the inner iteration's starting point —
    the converged power/lambda are init-independent (module docstring).
    """
    if power_solver == "analytic":
        return analytic_power          # closed form: init is a no-op
    if init is None:
        return dinkelbach_power
    a0, p0 = init
    a0 = jnp.broadcast_to(jnp.asarray(a0, jnp.float32), shape)
    p0 = jnp.broadcast_to(jnp.asarray(p0, jnp.float32), shape)
    pg = problem._pg(a0)
    bw = problem.bandwidth_hz if a0.ndim == 1 else problem.bandwidth_hz[:, None]
    lam0 = element_warm_lambda(a0, p0, pg, bw,
                               s_bits=problem.payload_bits(a0.ndim))
    return functools.partial(dinkelbach_power, lam0=lam0)


def solve_joint(problem: WirelessFLProblem,
                *,
                eps: float = 1e-7,
                max_iters: int = 50,
                power_solver: str = "dinkelbach",
                faithful_eq13_typo: bool = False,
                per_round: bool = True,
                init: Optional[tuple[jax.Array, jax.Array]] = None
                ) -> JointSolution:
    """Run Algorithm 2 to convergence for the whole fleet (jit-compatible).

    ``init=(a0, p0)`` warm-starts the solve from a previous solution's
    resumable state (``JointSolution.resume``); omitted, the solve is
    bit-identical to the cold path.  See the module docstring for the
    warm-start semantics.
    """
    shape = _solution_shape(problem, per_round)
    a0, p0 = _init_state(problem, shape)
    solver = _warm_solver(problem, power_solver, init, shape)
    step = functools.partial(_alternating_step, solver=solver,
                             faithful_eq13_typo=faithful_eq13_typo)

    def cond(state):
        _, _, obj, obj_prev, it, _ = state
        return ~_converged(obj, obj_prev, eps) & (it < max_iters)

    def body(state):
        a, p, obj, _, it, inner = state
        a_new, p_new, k = step(problem, a)
        return (a_new, p_new, problem.objective(a_new), obj, it + 1,
                inner + k)

    a1, p1, k1 = step(problem, a0)
    state = (a1, p1, problem.objective(a1), problem.objective(a0),
             jnp.int32(1), jnp.int32(0) + k1)
    a, p, obj, obj_prev, iters, inner = jax.lax.while_loop(cond, body, state)
    return JointSolution(a=a, power=p, objective=obj, n_iters=iters,
                         converged=_converged(obj, obj_prev, eps),
                         inner_iters=inner)


def solve_joint_trace(problem: WirelessFLProblem,
                      *,
                      eps: float = 1e-7,
                      max_iters: int = 50,
                      power_solver: str = "dinkelbach",
                      faithful_eq13_typo: bool = False,
                      init: Optional[tuple[jax.Array, jax.Array]] = None
                      ) -> tuple[JointSolution, list[float]]:
    """Python-loop variant of Algorithm 2 recording the objective trace.

    Shares ``_alternating_step`` and ``_converged`` with ``solve_joint``,
    so the recorded trace length and ``n_iters`` match the jitted path
    step for step (the convergence benchmark counts on this); ``init``
    has the same warm-start semantics too.
    """
    shape = _solution_shape(problem, per_round=True)
    a, p = _init_state(problem, shape)
    solver = _warm_solver(problem, power_solver, init, shape)
    step = functools.partial(_alternating_step, solver=solver,
                             faithful_eq13_typo=faithful_eq13_typo)
    obj_prev = problem.objective(a)
    trace = [float(obj_prev)]
    converged = False
    it = 0
    inner = jnp.int32(0)
    for it in range(1, max_iters + 1):  # noqa: B007 - read after the loop (n_iters)
        a, p, k = step(problem, a)
        inner = inner + k
        obj = problem.objective(a)
        trace.append(float(obj))
        if bool(_converged(obj, obj_prev, eps)):
            converged = True
            break
        obj_prev = obj
    res = JointSolution(a=a, power=p, objective=jnp.asarray(trace[-1]),
                        n_iters=jnp.int32(it), converged=jnp.asarray(converged),
                        inner_iters=inner)
    return res, trace


# --------------------------------------------- fused single-level solver

class FleetElements(NamedTuple):
    """Constraint data of the separable (instance, device, round) elements.

    All leaves share one common shape — flat ``[E]``, per-device ``[N]``,
    per-(device, round) ``[N, K]``, stacked ``[B, N]``/``[B, N, K]``; the
    solver never looks at the structure, only at elements.
    """

    pg: jax.Array      # path gain g / (d^2 sigma^2)
    bw: jax.Array      # bandwidth B_i
    emax: jax.Array    # per-round energy budget E^max_i
    ec: jax.Array      # computation energy E^c_i
    # effective uplink payload S_i = S b_i / 32 in bits (already scaled);
    # None => every element uses the solver's static ``s_bits`` payload —
    # the byte-identity idiom of the problem's optional leaves.
    sbits: Optional[jax.Array] = None


# padding for chunk/shard alignment: zero energy budget self-deselects
# (a* = 0, P* = 0) without producing NaN/inf in any update — the element
# analogue of core/batch.py's ``_PAD_VALUES``.
_ELEMENT_PAD = dict(pg=1.0, bw=1.0, emax=0.0, ec=1.0)

# below this element count, auto-sharding (shard=True without an explicit
# mesh) stays local: splitting a few thousand f32 elements over devices
# costs more in per-iteration collectives (the while-loop convergence
# reduce) than the sharded compute saves.  Element sharding exists for
# the 10^5..10^6-element mega-fleet regime.
_MIN_SHARD_ELEMENTS = 32_768


def problem_elements(problem: WirelessFLProblem,
                     per_round: bool = True) -> FleetElements:
    """Broadcast one problem's constraint data to the element set."""
    shape = _solution_shape(problem, per_round)

    def b(x):
        return jnp.broadcast_to(x[:, None] if x.ndim < len(shape) else x,
                                shape)

    return FleetElements(pg=b(problem.path_gain()),
                         bw=b(problem.bandwidth_hz),
                         emax=b(problem.energy_budget_j),
                         ec=b(problem.compute_energy()),
                         sbits=None if problem.bits is None
                         else b(problem.payload_bits(len(shape))))


def _fused_step(a: jax.Array, el: FleetElements, *, s_bits: float,
                tau: float, p_max: float, power_solver: str,
                faithful_eq13_typo: bool,
                lam0: float | jax.Array = 1e-3,
                expm1=accurate_expm1, log2=accurate_log2
                ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One fused alternation on raw elements: power + gate + eq. 13.

    With ``power_solver="analytic"`` (default) this is straight-line
    element-wise code — the whole Algorithm-2 body with no inner loop.
    ``"dinkelbach"`` is the faithful reference mode and re-introduces the
    inner Algorithm-1 iteration (slow; for agreement checks, and the mode
    whose ``lam0`` seed the warm-start path collapses).

    Returns ``(a_new, power, inner_iters)``; ``inner_iters`` is 0 in
    analytic mode.  ``expm1`` and ``log2`` replace the closed forms'
    transcendentals in the analytic power update and the rate — the
    Pallas kernel passes forms Mosaic lowers.
    """
    if el.sbits is not None:
        s_bits = el.sbits        # per-element bit-scaled payload
    if power_solver == "analytic":
        p, lam, feasible = analytic_power_elements(
            a, el.pg, el.bw, s_bits=s_bits, tau=tau, p_max=p_max,
            expm1=expm1, log2=log2)
        inner = jnp.int32(0)
    elif power_solver == "dinkelbach":
        p, lam, inner, feasible = dinkelbach_power_elements(
            a, el.pg, el.bw, s_bits=s_bits, tau=tau, p_max=p_max, lam0=lam0)
    else:
        raise ValueError(f"unknown power_solver {power_solver!r}")
    ok = energy_gate_elements(a, lam, el.emax, el.ec) & feasible
    t = element_tx_time(p, el.pg, el.bw, s_bits=s_bits, log2=log2)
    a_new = selection_update_elements(p, t, el.emax, el.ec, tau=tau,
                                      s_bits=s_bits,
                                      faithful_eq13_typo=faithful_eq13_typo)
    return jnp.where(ok, a_new, a), p, inner


def fused_init(el: FleetElements, *, s_bits: float, tau: float,
               p_max: float, faithful_eq13_typo: bool = False,
               log2=accurate_log2) -> tuple[jax.Array, jax.Array]:
    """Feasible (a^0, P^0) on raw elements: transmit at P^max, a^0 from
    eq. (13) — the element form of ``_init_state``.  Shared with the
    Pallas kernel so the two paths cannot drift (``log2`` as in
    ``_fused_step``)."""
    if el.sbits is not None:
        s_bits = el.sbits
    p0 = jnp.full(el.pg.shape, p_max)
    t0 = element_tx_time(p0, el.pg, el.bw, s_bits=s_bits, log2=log2)
    a0 = selection_update_elements(p0, t0, el.emax, el.ec, tau=tau,
                                   s_bits=s_bits,
                                   faithful_eq13_typo=faithful_eq13_typo)
    return a0, p0


def _menu_payloads(el: FleetElements, *, s_bits: float, bit_menu):
    """Candidate effective payloads for each menu entry, descending width.

    Entry ``b`` maps to ``S b / 32``; a problem-level ``bits`` cap
    (``el.sbits``) composes by elementwise minimum — the device can never
    transmit more precision than its own leaf allows.  Descending order is
    load-bearing: ``jnp.argmax`` returns the *first* maximum, so exact
    ties in the candidate objective resolve to the largest bit width
    (devices with slack keep full precision; see docs/compression.md).
    """
    menu = tuple(sorted({float(b) for b in bit_menu}, reverse=True))
    if not menu or menu[0] > 32.0 or menu[-1] <= 0.0:
        raise ValueError(f"bit_menu entries must lie in (0, 32], got {bit_menu!r}")
    payloads = []
    for b in menu:
        s_b = s_bits * (b / 32.0)
        if el.sbits is not None:
            s_b = jnp.minimum(el.sbits, s_b)
        payloads.append(s_b)
    return menu, payloads


def select_best_bits(a_m: jax.Array, p_m: jax.Array, sbits_m: jax.Array,
                     *, s_bits: float, atol: float = 1e-6
                     ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Closed-form bit-allocation: argmax over per-element candidates.

    ``a_m``/``p_m``/``sbits_m`` stack one converged candidate solution per
    menu entry along a leading axis, **ordered by descending bit width**.
    Per element the chosen entry is the first (widest) whose selection
    probability is within ``atol`` of the best — participation is the
    paper objective (7a), so any real gain justifies dropping bits, while
    near-ties (a = 1 capped, deselected a = 0, upload energy negligible
    against E^c) resolve to full precision rather than to float noise.

    Returns ``(a, power, bits)`` with ``bits = 32 * sbits / S``, the
    effective chosen width.  This is the step the golden N=3 oracle in
    ``tests/test_bit_allocation.py`` pins.
    """
    amax = jnp.max(a_m, axis=0)
    idx = jnp.argmax(a_m >= amax[None] - atol, axis=0)[None]

    def take(x):
        return jnp.take_along_axis(x, idx, axis=0)[0]

    return take(a_m), take(p_m), take(sbits_m) * (32.0 / s_bits)


def fused_fixed_point(el: FleetElements, *, s_bits: float, tau: float,
                      p_max: float, eps: float = 1e-7, max_iters: int = 50,
                      power_solver: str = "analytic",
                      faithful_eq13_typo: bool = False,
                      init: Optional[tuple[jax.Array, jax.Array]] = None,
                      bit_menu: Optional[tuple] = None
                      ) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array,
                                 jax.Array]:
    """The flat convergence-masked alternating solve.

    One ``lax.while_loop`` over the whole element set; iteration ``n``
    applies ``_fused_step`` to every element simultaneously and the loop
    exits when every element's update moved less than ``eps`` (or at
    ``max_iters`` total steps, counted like ``solve_joint``).  Per-element
    trajectories are identical to ``solve_joint``'s — the problem is
    separable, so each element's update depends only on its own ``a`` —
    only the stopping rule differs (elementwise vs global objective), and
    the elementwise rule is the stricter of the two.

    ``init=(a0, p0)`` element arrays warm-start the solve (module
    docstring): the selection iterate still starts canonically, but the
    Dinkelbach mode's inner lambda is seeded from the init state's
    energy.  Omitted, the solve is bit-identical to the cold path.

    Returns ``(a, power, n_iters, converged, inner_iters)`` with
    ``converged`` a per-element bool and ``inner_iters`` the summed inner
    power-solver iterations (0 in analytic mode).

    ``bit_menu`` (a tuple of widths in (0, 32], e.g. ``(4, 6, 8, 16, 32)``)
    enables the joint bit/power/selection solve and extends the return
    value to the 6-tuple ``(a, power, n_iters, converged, inner_iters,
    bits)``.  The menu is evaluated *vectorized inside the same
    convergence-masked single-level while loop*: the element set is
    expanded with a leading candidate axis (one slice per menu width,
    descending), every candidate's alternation runs to its own fixed
    point in the one ``lax.while_loop``, and :func:`select_best_bits`
    reduces the axis per element (argmax of the converged selection
    probability, exact-tie towards full precision).  This is exact for
    the separable per-element problem — comparing candidates only after
    one step from a shared iterate would always tie, because the eq.-13
    time term at P = P^min(a) equals ``a`` for *every* payload.  The
    ``None`` default keeps the historical 5-tuple and traces the exact
    pre-menu program.
    """
    if bit_menu is not None:
        _, payloads = _menu_payloads(el, s_bits=s_bits, bit_menu=bit_menu)
        m, shape = len(payloads), el.pg.shape

        def expand(x):
            return jnp.broadcast_to(x[None], (m,) + shape)

        sb = jnp.stack([jnp.broadcast_to(
            jnp.asarray(s_b, jnp.float32), shape) for s_b in payloads])
        el_m = FleetElements(pg=expand(el.pg), bw=expand(el.bw),
                             emax=expand(el.emax), ec=expand(el.ec),
                             sbits=sb)
        init_m = None if init is None else tuple(expand(x) for x in init)
        a_m, p_m, iters, conv_m, inner = fused_fixed_point(
            el_m, s_bits=s_bits, tau=tau, p_max=p_max, eps=eps,
            max_iters=max_iters, power_solver=power_solver,
            faithful_eq13_typo=faithful_eq13_typo, init=init_m)
        a, p, bits = select_best_bits(a_m, p_m, sb, s_bits=s_bits)
        return a, p, iters, jnp.all(conv_m, axis=0), inner, bits

    lam0 = 1e-3
    if init is not None and power_solver == "dinkelbach":
        lam0 = element_warm_lambda(init[0], init[1], el.pg, el.bw,
                                   s_bits=s_bits if el.sbits is None
                                   else el.sbits)
    a0, _ = fused_init(el, s_bits=s_bits, tau=tau, p_max=p_max,
                       faithful_eq13_typo=faithful_eq13_typo)

    step = functools.partial(_fused_step, el=el, s_bits=s_bits, tau=tau,
                             p_max=p_max, power_solver=power_solver,
                             faithful_eq13_typo=faithful_eq13_typo,
                             lam0=lam0)

    def cond(state):
        _, _, delta, it, _ = state
        return jnp.any(delta >= eps) & (it < max_iters)

    def body(state):
        a, _, _, it, inner = state
        a_new, p_new, k = step(a)
        return a_new, p_new, jnp.abs(a_new - a), it + 1, inner + k

    a1, p1, k1 = step(a0)
    state = (a1, p1, jnp.abs(a1 - a0), jnp.int32(1), jnp.int32(0) + k1)
    a, p, delta, iters, inner = jax.lax.while_loop(cond, body, state)
    return a, p, iters, delta < eps, inner


def element_mesh(mesh: Optional[jax.sharding.Mesh] = None
                 ) -> Optional[jax.sharding.Mesh]:
    """Resolve the mesh used to shard the element axis over local devices.

    Returns None when sharding is a no-op (single device).  A
    user-supplied mesh may use any axis naming; the element axis is split
    along its *first* axis (matching ``core.batch.batch_sharding``).
    """
    if mesh is None:
        devices = jax.devices()
        if len(devices) <= 1:
            return None
        mesh = jax.sharding.Mesh(np.array(devices), ("elements",))
    return mesh if mesh.shape[mesh.axis_names[0]] > 1 else None


def _pad_flat(x: jax.Array, multiple: int, fill: float) -> jax.Array:
    pad = (-x.shape[0]) % multiple
    return x if pad == 0 else jnp.pad(x, (0, pad), constant_values=fill)


def _pad_elements(el: FleetElements, multiple: int) -> FleetElements:
    padded = {f: _pad_flat(getattr(el, f), multiple, _ELEMENT_PAD[f])
              for f in _ELEMENT_PAD}
    if el.sbits is not None:
        # any positive payload works: padded slots self-deselect via
        # emax = 0, the fill only needs to keep the closed forms finite
        padded["sbits"] = _pad_flat(el.sbits, multiple, 1.0)
    return FleetElements(**padded)


def fused_fixed_point_flat(el: FleetElements, *, s_bits: float, tau: float,
                           p_max: float, eps: float = 1e-7,
                           max_iters: int = 50,
                           power_solver: str = "analytic",
                           faithful_eq13_typo: bool = False,
                           chunk_elements: Optional[int] = None,
                           mesh: Optional[jax.sharding.Mesh] = None,
                           shard: bool = True,
                           init: Optional[tuple[jax.Array, jax.Array]] = None,
                           bit_menu: Optional[tuple] = None
                           ) -> tuple[jax.Array, jax.Array, jax.Array,
                                      jax.Array, jax.Array]:
    """Chunked, device-sharded driver over a flat ``[E]`` element set.

    * ``chunk_elements`` bounds the working set: the element axis is padded
      to a whole number of chunks and solved chunk-by-chunk under
      ``lax.map`` (sequential, compiled once), so peak memory is
      O(chunk_elements) regardless of fleet size.  ``None`` solves all E
      elements in one call.
    * ``shard=True`` lays the element axis (the within-chunk axis when
      chunking) out across the local device mesh with a ``NamedSharding``
      — a *device-axis* sharding: a single 100k-device instance spreads
      over the mesh even at batch size 1.  Chunk sizes are rounded up to
      the device count so every shard is equal.  Auto-sharding only
      engages when the per-solve working set — min(E, chunk_elements) —
      reaches ``_MIN_SHARD_ELEMENTS`` (below that the per-iteration
      convergence all-reduce costs more than the sharded compute saves);
      passing an explicit ``mesh`` always shards, regardless of ``shard``
      and the threshold.

    Returns flat ``(a, power, n_iters, converged, inner_iters)`` of the
    original length E; padding elements are solved (to a = P = 0) and
    stripped.  ``init=(a0, p0)`` flat element arrays warm-start the solve
    (padded/chunked/sharded alongside the elements); on the chunked path
    ``inner_iters`` sums over chunks (total inner work) while ``n_iters``
    is the max.

    ``bit_menu`` forwards to :func:`fused_fixed_point` and, when set,
    extends the return value with a trailing flat ``bits`` array (the
    6-tuple contract described there).
    """
    assert el.pg.ndim == 1, "fused_fixed_point_flat takes flat [E] elements"
    e = el.pg.shape[0]

    def solve(operand):
        el_c, init_c = operand
        return fused_fixed_point(el_c, s_bits=s_bits, tau=tau,
                                 p_max=p_max, eps=eps, max_iters=max_iters,
                                 power_solver=power_solver,
                                 faithful_eq13_typo=faithful_eq13_typo,
                                 init=init_c, bit_menu=bit_menu)

    if mesh is not None:
        shard = True                       # an explicit mesh always shards
    else:
        # the while-loop all-reduce is paid per *solve*, so the auto
        # threshold looks at the per-chunk working set, not the total E
        working_set = e if chunk_elements is None else min(e, chunk_elements)
        if working_set < _MIN_SHARD_ELEMENTS:
            shard = False                  # auto-sharding: stay local
    mesh = element_mesh(mesh) if shard else None
    n_shards = 1 if mesh is None else mesh.shape[mesh.axis_names[0]]

    def pad(multiple):
        el_p = _pad_elements(el, multiple)
        init_p = None if init is None else tuple(
            _pad_flat(jnp.asarray(x).reshape(-1), multiple, 0.0)
            for x in init)
        return el_p, init_p

    def constrain(arrs, spec):
        if mesh is None:
            return arrs
        ns = jax.sharding.NamedSharding(mesh, spec)
        return jax.tree_util.tree_map(
            lambda x: jax.lax.with_sharding_constraint(x, ns), arrs)

    if chunk_elements is None:
        operand = constrain(pad(n_shards),
                            jax.sharding.PartitionSpec(mesh.axis_names[0])
                            if mesh else None)
        out = solve(operand)
        if bit_menu is None:
            a, p, iters, conv, inner = out
            return a[:e], p[:e], iters, conv[:e], inner
        a, p, iters, conv, inner, bits = out
        return a[:e], p[:e], iters, conv[:e], inner, bits[:e]

    chunk = -(-chunk_elements // n_shards) * n_shards
    operand = pad(chunk)
    n_chunks = operand[0].pg.shape[0] // chunk
    operand = jax.tree_util.tree_map(
        lambda x: x.reshape(n_chunks, chunk), operand)
    operand = constrain(operand,
                        jax.sharding.PartitionSpec(None, mesh.axis_names[0])
                        if mesh else None)
    out = jax.lax.map(solve, operand)

    def unflat(x):
        return x.reshape(-1)[:e]

    if bit_menu is None:
        a, p, iters, conv, inner = out
        return (unflat(a), unflat(p), jnp.max(iters), unflat(conv),
                jnp.sum(inner))
    a, p, iters, conv, inner, bits = out
    return (unflat(a), unflat(p), jnp.max(iters), unflat(conv),
            jnp.sum(inner), unflat(bits))


def solve_joint_fused(problem: WirelessFLProblem,
                      *,
                      eps: float = 1e-7,
                      max_iters: int = 50,
                      power_solver: str = "analytic",
                      faithful_eq13_typo: bool = False,
                      per_round: bool = True,
                      chunk_elements: Optional[int] = None,
                      mesh: Optional[jax.sharding.Mesh] = None,
                      shard: bool = False,
                      sanitize: bool = False,
                      init: Optional[tuple[jax.Array, jax.Array]] = None,
                      bit_menu: Optional[tuple] = None
                      ) -> JointSolution:
    """Fused single-level Algorithm 2 for one problem (jit-compatible).

    ``sanitize=True`` maps devices with non-finite / out-of-domain
    constraint data to self-deselecting no-ops (a* = P* = 0) via
    ``WirelessFLProblem.sanitize`` before solving — the boundary
    hardening used by the serving path (docs/robustness.md); on healthy
    input it is bit-identical to ``sanitize=False``.

    Matches ``solve_joint`` to solver tolerance (tests assert <= 1e-5 on
    a*, P* and the objective) while running the whole alternation as one
    flat masked iteration — the mega-fleet path for 10^5+ device
    instances.  ``chunk_elements``/``mesh``/``shard`` are forwarded to
    :func:`fused_fixed_point_flat` (they are jit-static arguments).
    ``init=(a0, p0)`` (shaped like the solution) warm-starts the solve —
    see the module docstring; omitted, the solve is bit-identical to the
    cold path, and the returned ``JointSolution.resume`` is the state to
    feed the next solve on a drifted problem.

    Caveat: with ``faithful_eq13_typo=True`` the verbatim formula has no
    interior fixed point (each sweep contracts a by 1/S), so the
    per-element rule iterates to the collapsed solution while
    ``solve_joint``'s global-objective rule stops a couple of sweeps
    above it; the <= 1e-5 agreement guarantee covers the corrected
    formula only.

    ``bit_menu`` (e.g. ``(4, 6, 8, 16, 32)``) enables the joint
    bit/power/selection alternation: each sweep additionally picks, per
    element, the menu width maximising the eq.-13 update (ties towards
    full precision), and the returned ``JointSolution.bits`` carries the
    chosen widths.  ``None`` (the default) traces the exact historical
    program — byte-identical solutions, ``bits=None``.
    """
    if sanitize:
        problem, _ = problem.sanitize()
    # per_round=False on a fading problem is rejected by _solution_shape
    # (via problem_elements), one message for every solver entry point
    el = problem_elements(problem, per_round)
    shape = el.pg.shape
    if init is not None:
        init = tuple(jnp.broadcast_to(jnp.asarray(x, jnp.float32), shape)
                     for x in init)
    kw = dict(s_bits=problem.grad_size_bits, tau=problem.tau_th,
              p_max=problem.p_max, eps=eps, max_iters=max_iters,
              power_solver=power_solver,
              faithful_eq13_typo=faithful_eq13_typo, init=init,
              bit_menu=bit_menu)
    bits = None
    if chunk_elements is None and not shard and mesh is None:
        out = fused_fixed_point(el, **kw)
        if bit_menu is None:
            a, p, iters, conv, inner = out
        else:
            a, p, iters, conv, inner, bits = out
    else:
        kw["init"] = None if init is None else tuple(
            x.reshape(-1) for x in init)
        flat = jax.tree_util.tree_map(lambda x: x.reshape(-1), el)
        out = fused_fixed_point_flat(
            flat, chunk_elements=chunk_elements, mesh=mesh, shard=shard, **kw)
        if bit_menu is None:
            a, p, iters, conv, inner = out
        else:
            a, p, iters, conv, inner, bits = out
            bits = bits.reshape(shape)
        a, p, conv = a.reshape(shape), p.reshape(shape), conv.reshape(shape)
    return JointSolution(a=a, power=p, objective=problem.objective(a),
                         n_iters=iters, converged=jnp.all(conv),
                         inner_iters=inner, bits=bits)
