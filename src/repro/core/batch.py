"""Batched multi-scenario fleet solver: many problem (7) instances at once.

The paper's Algorithm 2 solves one 100-device instance.  Ensemble studies
(fading draws, bandwidth mixes, fleet-size sweeps — cf. Perazzone et al.,
arXiv:2201.07912 and Guo et al., arXiv:2205.09306, which both evaluate
over large ensembles of channel realisations) need *thousands* of
heterogeneous instances.  This module stacks them into one device-sharded
batch:

* ``ProblemBatch`` — a pytree of ``WirelessFLProblem`` leaves stacked to
  ``[B, N_max]`` (``[B, N_max, K]`` for fading), with ragged fleet sizes
  handled by padding plus a ``[B, N_max]`` validity ``mask``.  Padded
  device slots are constructed so every solver *self-deselects* them
  (zero energy budget => a* = 0) — no solver change needed.
* ``stack_problems`` / ``ProblemBatch.unstack`` — build/split the batch.
* ``solve_joint_batch`` — ``jax.vmap`` of Algorithm 2 (or the fused
  single-level solver, the exact bisection optimum, or the Pallas
  ``selection_solve``/``fused_solve`` kernel fast paths) across the
  batch, jitted once, optionally sharded over the local device mesh with
  ``jax.sharding.NamedSharding`` along the batch axis — or, for
  ``method="fused"``, along the flattened *element* axis with an optional
  ``chunk_elements`` memory bound (the mega-fleet path: a single 100k- or
  1M-device instance spreads over the mesh and solves in fixed memory).

Static metadata (``p_max``, ``tau_th``, ``grad_size_bits``, ...) is shared
batch-wide — ``stack_problems`` raises if instances disagree, since those
fields are compiled into the kernel as constants.

See ``docs/scenarios.md`` for the scenario generators that feed this API
and ``tests/test_batch_solver.py`` for the agreement guarantees.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.alternating import (
    FleetElements,
    JointSolution,
    WarmStart,
    fused_fixed_point_flat,
    solve_joint,
)
from repro.core.optimal import solve_joint_optimal
from repro.core.problem import NEUTRAL_FILLS, WirelessFLProblem

# static (non-leaf) fields that must be uniform across a batch
_STATIC_FIELDS = ("grad_size_bits", "noise_power", "p_max", "tau_th",
                  "kappa", "n_rounds")
# array leaves stacked along the new batch axis, with the value used to
# fill padded device slots.  Padding is chosen so padded slots are
# *infeasible at any a > 0* (zero energy budget) yet produce no NaN/inf in
# any solver: distance 1 m keeps path gain finite, weight 0 removes the
# slot from every objective.  The same fills sanitize unhealthy devices
# (``WirelessFLProblem.sanitize``) — one idiom, one source of truth.
_PAD_VALUES = NEUTRAL_FILLS


class BatchSolution(NamedTuple):
    """Stacked per-instance solutions. All arrays lead with the batch axis."""

    a: jax.Array           # [B, N_max] (or [B, N_max, K])
    power: jax.Array       # same shape as a
    objective: jax.Array   # [B]
    n_iters: jax.Array     # [B] or scalar
    converged: jax.Array   # [B] bool
    mask: jax.Array        # [B, N_max] bool — valid device slots
    # summed inner power-solver iterations ([B] or scalar; 0 for the
    # closed-form analytic modes) — what warm starts collapse
    inner_iters: jax.Array | int = 0
    # chosen uplink bit widths (method="fused" with a bit_menu); None
    # otherwise — mirrors JointSolution.bits
    bits: Optional[jax.Array] = None

    def instance(self, b: int) -> JointSolution:
        """Per-instance JointSolution with padding stripped."""
        n = int(np.sum(np.asarray(self.mask[b])))
        return JointSolution(a=self.a[b, :n], power=self.power[b, :n],
                             objective=self.objective[b],
                             n_iters=jnp.asarray(self.n_iters)[b]
                             if jnp.ndim(self.n_iters) else self.n_iters,
                             converged=self.converged[b],
                             inner_iters=jnp.asarray(self.inner_iters)[b]
                             if jnp.ndim(self.inner_iters) else self.inner_iters,
                             bits=None if self.bits is None
                             else self.bits[b, :n])

    @property
    def resume(self) -> WarmStart:
        """Batch warm-start state for a subsequent nearby batched solve."""
        return WarmStart(a=self.a, power=self.power)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class ProblemBatch:
    """B stacked ``WirelessFLProblem`` instances, padded to a common N_max.

    ``problem`` holds the stacked leaves (``[B, N_max]``; fading
    ``[B, N_max, K]``); its static metadata is the batch-wide shared
    configuration.  ``mask[b, i]`` is True iff slot ``i`` of instance ``b``
    is a real device; ``fleet_sizes[b]`` is the true (unpadded) N.
    """

    problem: WirelessFLProblem
    mask: jax.Array          # [B, N_max] bool
    fleet_sizes: jax.Array   # [B] int32

    @property
    def batch_size(self) -> int:
        return int(self.mask.shape[0])

    @property
    def n_max(self) -> int:
        return int(self.mask.shape[1])

    def unstack(self) -> list[WirelessFLProblem]:
        """Split back into per-instance problems (padding stripped)."""
        sizes = np.asarray(self.fleet_sizes)
        out = []
        for b in range(self.batch_size):
            n = int(sizes[b])
            kw = {}
            for f in dataclasses.fields(WirelessFLProblem):
                v = getattr(self.problem, f.name)
                if f.name in _PAD_VALUES:
                    v = v[b, :n]
                elif f.name in ("fading", "interference", "bits"):
                    v = None if v is None else v[b, :n]
                kw[f.name] = v
            out.append(WirelessFLProblem(**kw))
        return out


def _slot_array(leaves: list[np.ndarray], shape: tuple[int, int],
                fill: float, name: str) -> np.ndarray:
    # one host array at the final [B, N_max, ...] slot shape, pre-filled
    # with the leaf's neutral fill, each instance written into its rows;
    # the dtype is what jnp.asarray(np.stack(leaves)) would give
    trail = leaves[0].shape[1:]
    if any(x.shape[1:] != trail for x in leaves):
        raise ValueError(f"{name} trailing shape differs across the batch "
                         f"({sorted({x.shape[1:] for x in leaves})})")
    dtype = jax.dtypes.canonicalize_dtype(np.result_type(*leaves))
    out = np.full(shape + trail, fill, dtype)
    for b, x in enumerate(leaves):
        out[b, :x.shape[0]] = x
    return out


def stack_problems(problems: Sequence[WirelessFLProblem], *,
                   batch_size: Optional[int] = None,
                   n_max: Optional[int] = None) -> ProblemBatch:
    """Stack instances into a ProblemBatch, padding ragged fleet sizes.

    All instances must share the static metadata (``p_max``, ``tau_th``,
    ``grad_size_bits``, ``noise_power``, ``kappa``, ``n_rounds``) — those
    are jit-compile-time constants.  Instances may freely differ in fleet
    size and in every per-device array.  Fading must be all-or-none: a
    non-fading instance solves one [N] round while a fading one solves
    [N, K] rounds, so mixing them in one batch would silently change the
    non-fading instances' objective (summed over K synthetic rounds).
    Pass explicit unit fading to opt a static-channel instance into a
    fading batch.

    ``batch_size`` / ``n_max`` pad to fixed slot shapes exactly as
    :func:`pad_batch` does (default: the natural ``(len(problems),
    max N)``), but in one host pass: every leaf is written once into a
    numpy array of the final shape and the whole batch goes to the
    device in a single ``jax.device_put``.  Host (numpy) leaves are
    never read back from the device.
    """
    if not problems:
        raise ValueError("stack_problems needs at least one problem")
    ref = problems[0]
    for p in problems[1:]:
        for f in _STATIC_FIELDS:
            if getattr(p, f) != getattr(ref, f):
                raise ValueError(
                    f"static field {f!r} differs across the batch "
                    f"({getattr(p, f)} vs {getattr(ref, f)}); solve instances "
                    "with differing statics in separate batches")

    b0 = len(problems)
    n0 = max(p.n_devices for p in problems)
    n_fading = sum(p.fading is not None for p in problems)
    if 0 < n_fading < len(problems):
        raise ValueError(
            f"{n_fading}/{len(problems)} instances carry fading; fading must "
            "be all-or-none per batch (give static-channel instances "
            "explicit unit fading to mix them in)")
    n_interf = sum(p.interference is not None for p in problems)
    if 0 < n_interf < len(problems):
        raise ValueError(
            f"{n_interf}/{len(problems)} instances carry interference; "
            "interference must be all-or-none per batch (give quiet cells "
            "explicit zero interference to mix them in)")
    if n_interf and len({p.interference.ndim for p in problems}) > 1:
        raise ValueError("interference rank ([N] vs [N, K]) must be uniform "
                         "across the batch")
    n_bits = sum(p.bits is not None for p in problems)
    if 0 < n_bits < len(problems):
        raise ValueError(
            f"{n_bits}/{len(problems)} instances carry a bits leaf; bits "
            "must be all-or-none per batch (give full-precision instances "
            "explicit bits=32 to mix them in)")
    if n_bits and len({p.bits.ndim for p in problems}) > 1:
        raise ValueError("bits rank ([N] vs [N, K]) must be uniform "
                         "across the batch")

    bsz = b0 if batch_size is None else batch_size
    nmx = n0 if n_max is None else n_max
    if bsz < b0 or nmx < n0:
        raise ValueError(f"stack_problems cannot shrink ({b0}, {n0}) -> "
                         f"({bsz}, {nmx})")

    def slots(name: str, fill: float) -> np.ndarray:
        return _slot_array([np.asarray(getattr(p, name)) for p in problems],
                           (bsz, nmx), fill, name)

    leaves = {name: slots(name, fill) for name, fill in _PAD_VALUES.items()}
    for name, fill, present in (("fading", 1.0, n_fading),
                                ("interference", 0.0, n_interf),
                                ("bits", 32.0, n_bits)):
        leaves[name] = slots(name, fill) if present else None
    sizes = np.zeros(bsz, np.int32)
    sizes[:b0] = [p.n_devices for p in problems]
    mask = np.arange(nmx)[None, :] < sizes[:, None]
    prob = WirelessFLProblem(
        **leaves, **{f: getattr(ref, f) for f in _STATIC_FIELDS})
    return jax.device_put(ProblemBatch(problem=prob, mask=mask,
                                       fleet_sizes=sizes))


def pad_batch(batch: ProblemBatch, *, batch_size: Optional[int] = None,
              n_max: Optional[int] = None) -> ProblemBatch:
    """Pad a batch to fixed ``(batch_size, n_max)`` slot shapes.

    The serving path packs variable request micro-batches into quantised
    slot shapes so jit compiles once per bucket instead of once per
    (B, N) combination.  Padded instance rows reuse ``_PAD_VALUES`` (zero
    energy budget => every solver self-deselects them) with an all-False
    mask row and fleet size 0; ``BatchSolution.instance`` never exposes
    them.  Shrinking is not supported.
    """
    b0, n0 = batch.batch_size, batch.n_max
    bsz = b0 if batch_size is None else batch_size
    nmx = n0 if n_max is None else n_max
    if bsz < b0 or nmx < n0:
        raise ValueError(f"pad_batch cannot shrink ({b0}, {n0}) -> "
                         f"({bsz}, {nmx})")
    if (bsz, nmx) == (b0, n0):
        return batch
    db, dn = bsz - b0, nmx - n0
    kw = {}
    for f in dataclasses.fields(WirelessFLProblem):
        v = getattr(batch.problem, f.name)
        if f.name in _PAD_VALUES:
            v = jnp.asarray(np.pad(np.asarray(v), [(0, db), (0, dn)],
                                   constant_values=_PAD_VALUES[f.name]))
        elif f.name == "fading" and v is not None:
            v = jnp.asarray(np.pad(np.asarray(v), [(0, db), (0, dn), (0, 0)],
                                   constant_values=1.0))
        elif f.name == "interference" and v is not None:
            pad = [(0, db), (0, dn)] + [(0, 0)] * (np.ndim(v) - 2)
            v = jnp.asarray(np.pad(np.asarray(v), pad, constant_values=0.0))
        elif f.name == "bits" and v is not None:
            pad = [(0, db), (0, dn)] + [(0, 0)] * (np.ndim(v) - 2)
            v = jnp.asarray(np.pad(np.asarray(v), pad, constant_values=32.0))
        kw[f.name] = v
    mask = jnp.asarray(np.pad(np.asarray(batch.mask), [(0, db), (0, dn)],
                              constant_values=False))
    sizes = jnp.asarray(np.pad(np.asarray(batch.fleet_sizes), (0, db)))
    return ProblemBatch(problem=WirelessFLProblem(**kw), mask=mask,
                        fleet_sizes=sizes)


# --------------------------------------------------------------- sharding

def batch_sharding(batch_size: int,
                   mesh: Optional[jax.sharding.Mesh] = None
                   ) -> Optional[jax.sharding.NamedSharding]:
    """NamedSharding that splits the batch axis over the local devices.

    A user-supplied ``mesh`` may use any axis naming; the batch axis is
    split along the mesh's *first* axis.  Returns None when sharding is a
    no-op (single device) or impossible (batch not divisible by the device
    count — jax requires equal shards).
    """
    if mesh is None:
        devices = jax.devices()
        if len(devices) <= 1:
            return None
        mesh = jax.sharding.Mesh(np.array(devices), ("batch",))
    axis = mesh.axis_names[0]
    n_shards = mesh.shape[axis]
    if n_shards <= 1 or batch_size % n_shards != 0:
        return None
    return jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec(axis))


def shard_batch(batch: ProblemBatch,
                mesh: Optional[jax.sharding.Mesh] = None) -> ProblemBatch:
    """Place every leaf of the batch with its batch axis split over devices."""
    sharding = batch_sharding(batch.batch_size, mesh)
    if sharding is None:
        return batch
    return jax.tree_util.tree_map(lambda x: jax.device_put(x, sharding), batch)


# ----------------------------------------------------------------- solver

def _mask_solution(sol: JointSolution, mask: jax.Array) -> BatchSolution:
    m = mask if sol.a.ndim == mask.ndim else mask[..., None]
    return BatchSolution(a=jnp.where(m, sol.a, 0.0),
                         power=jnp.where(m, sol.power, 0.0),
                         objective=sol.objective, n_iters=sol.n_iters,
                         converged=sol.converged, mask=mask,
                         inner_iters=sol.inner_iters,
                         bits=None if sol.bits is None
                         else jnp.where(m, sol.bits, 32.0))


@partial(jax.jit, static_argnames=("method", "power_solver",
                                   "faithful_eq13_typo", "max_iters"))
def _solve_batch_vmapped(batch: ProblemBatch, method: str, power_solver: str,
                         faithful_eq13_typo: bool, eps: float,
                         max_iters: int,
                         init: Optional[WarmStart]) -> BatchSolution:
    if method == "optimal":
        sol = jax.vmap(solve_joint_optimal)(batch.problem)
    else:
        solve = partial(solve_joint, eps=eps, max_iters=max_iters,
                        power_solver=power_solver,
                        faithful_eq13_typo=faithful_eq13_typo)
        if init is None:
            sol = jax.vmap(solve)(batch.problem)
        else:
            sol = jax.vmap(lambda p, a0, p0: solve(p, init=(a0, p0)))(
                batch.problem, init[0], init[1])
    return _mask_solution(sol, batch.mask)


def batch_elements(batch: ProblemBatch) -> FleetElements:
    """Stacked per-element constraint data, shape [B, N_max] or [B, N_max, K]."""
    problem = batch.problem
    # per-instance rank-sensitive broadcasting lives in path_gain(); vmap it
    # rather than reimplementing the [B, N, K] case here.
    pg = jax.vmap(WirelessFLProblem.path_gain)(problem)

    def b(x):
        return jnp.broadcast_to(x[..., None] if x.ndim < pg.ndim else x,
                                pg.shape)

    return FleetElements(pg=pg, bw=b(problem.bandwidth_hz),
                         emax=b(problem.energy_budget_j),
                         ec=b(jax.vmap(WirelessFLProblem.compute_energy)(problem)),
                         sbits=None if problem.bits is None
                         else b(problem.grad_size_bits * problem.bits / 32.0))


@partial(jax.jit, static_argnames=("power_solver", "faithful_eq13_typo",
                                   "max_iters", "chunk_elements", "mesh",
                                   "shard", "bit_menu"))
def _solve_batch_fused(batch: ProblemBatch, power_solver: str,
                       faithful_eq13_typo: bool, eps: float, max_iters: int,
                       chunk_elements: Optional[int],
                       mesh: Optional[jax.sharding.Mesh],
                       shard: bool,
                       init: Optional[WarmStart],
                       bit_menu: Optional[tuple] = None) -> BatchSolution:
    """The fused flat path: one convergence-masked iteration over the whole
    [B * N_max (* K)] element set — no per-instance lockstep, optionally
    chunked (fixed memory) and sharded along the *element* axis (a single
    mega-fleet instance spreads over the mesh even at B = 1)."""
    el = batch_elements(batch)
    shape = el.pg.shape
    flat = jax.tree_util.tree_map(lambda x: x.reshape(-1), el)
    flat_init = None
    if init is not None:
        flat_init = tuple(
            jnp.broadcast_to(jnp.asarray(x, jnp.float32),
                             shape).reshape(-1) for x in init)
    out = fused_fixed_point_flat(
        flat, s_bits=batch.problem.grad_size_bits, tau=batch.problem.tau_th,
        p_max=batch.problem.p_max, eps=eps, max_iters=max_iters,
        power_solver=power_solver, faithful_eq13_typo=faithful_eq13_typo,
        chunk_elements=chunk_elements, mesh=mesh, shard=shard,
        init=flat_init, bit_menu=bit_menu)
    bits = None
    if bit_menu is None:
        a, p, iters, conv, inner = out
    else:
        a, p, iters, conv, inner, bits = out
        bits = bits.reshape(shape)
    a, p, conv = a.reshape(shape), p.reshape(shape), conv.reshape(shape)
    b = shape[0]
    sol = JointSolution(
        a=a, power=p,
        objective=jax.vmap(WirelessFLProblem.objective)(batch.problem, a),
        n_iters=jnp.broadcast_to(iters, (b,)),
        converged=conv.reshape(b, -1).all(axis=1),
        inner_iters=inner, bits=bits)
    return _mask_solution(sol, batch.mask)


def solve_joint_batch(batch: ProblemBatch,
                      *,
                      method: str = "alternating",
                      power_solver: Optional[str] = None,
                      faithful_eq13_typo: bool = False,
                      eps: float = 1e-7,
                      max_iters: int = 50,
                      shard: bool = True,
                      mesh: Optional[jax.sharding.Mesh] = None,
                      chunk_elements: Optional[int] = None,
                      interpret: Optional[bool] = None,
                      sanitize: bool = False,
                      init: Optional[WarmStart] = None,
                      bit_menu: Optional[tuple] = None) -> BatchSolution:
    """Solve every instance of ``batch`` in one jitted, device-sharded call.

    ``sanitize=True`` runs ``WirelessFLProblem.sanitize`` over the
    stacked leaves first: devices with non-finite / out-of-domain data
    self-deselect (a* = P* = 0, the padded-slot idiom) instead of
    poisoning the solve; healthy batches are bit-identical to
    ``sanitize=False`` (docs/robustness.md).

    method:
      * ``"alternating"``  — vmap of Algorithm 2 (``solve_joint``); matches
        a python loop of per-instance solves to solver tolerance.
      * ``"fused"``        — the fused single-level solver
        (``core.alternating.fused_fixed_point_flat``) over the flattened
        element set: same fixed point as ``"alternating"`` (agreement
        <= 1e-5 elementwise) but one flat convergence-masked loop — no
        nested while-loops, so the batch never waits on the slowest inner
        solve.  The mega-fleet path: honours ``chunk_elements`` and
        shards the *element* axis (not just the batch axis).
      * ``"optimal"``      — vmap of the exact bisection optimum
        (``solve_joint_optimal``).
      * ``"kernel"``       — the Pallas ``selection_solve`` kernel over the
        flattened ``[B * N_max]`` element set (solves the same bisection
        problem as ``"optimal"``).
      * ``"fused_kernel"`` — the Pallas ``fused_solve`` kernel: the fused
        alternating fixed point, whole tiles VMEM-resident.

    The kernel methods compile on a TPU and run in the Pallas interpreter
    elsewhere; ``interpret=True`` forces the interpreter
    (``repro.kernels.resolve_interpret``).

    ``power_solver`` (default: ``"dinkelbach"`` for ``"alternating"``,
    ``"analytic"`` — the bit-identical closed form — for the fused
    methods), ``faithful_eq13_typo``, ``eps``, and ``max_iters`` are
    Algorithm-2 knobs and apply only to the alternating/fused methods
    (the other methods compute the exact per-element optimum directly);
    requesting the eq.-13 typo with them is an error rather than a
    silent mismatch.  ``"fused_kernel"`` runs ``max_iters`` fixed
    iterations (no ``eps`` early-exit — each element freezes at its first
    step below the default ``eps``, 1e-7) and rejects
    ``power_solver="dinkelbach"``.

    ``shard=True`` splits the batch axis (the element axis for
    ``"fused"``) over the local devices with a ``NamedSharding`` before
    solving (no-op on a single device).  ``chunk_elements`` bounds the
    fused solve's working set to a fixed number of elements regardless of
    fleet size (only valid with ``method="fused"``).  Padded device slots
    come back with ``a = power = 0``; per-instance objectives never
    include them (their objective weight is 0).

    ``init`` (a :class:`WarmStart` or ``(a0, p0)`` pair shaped like the
    batch solution, typically a previous ``BatchSolution.resume``)
    warm-starts the iterative methods; all-zero rows mean "no previous
    state" and behave exactly cold, so mixed warm/cold micro-batches need
    no special casing.  Solutions are init-independent — see
    ``core.alternating``'s warm-start notes; only iteration counts
    (``inner_iters``) change.  The direct methods ("optimal"/"kernel")
    and the fixed-trip "fused_kernel" have no iteration to warm-start
    and reject ``init``.

    ``bit_menu`` (method="fused" only) runs the joint bit/power/selection
    solve — see ``solve_joint_fused`` — and fills ``BatchSolution.bits``.
    """
    if method not in ("alternating", "fused", "optimal", "kernel",
                      "fused_kernel"):
        raise ValueError(f"unknown method {method!r}")
    if bit_menu is not None and method != "fused":
        raise ValueError(
            f"bit_menu is implemented by the fused single-level solver "
            f"only; method={method!r} would silently ignore it")
    if method in ("kernel", "fused_kernel") and batch.problem.bits is not None:
        raise ValueError(
            "the Pallas kernel methods compile a single static payload and "
            "would silently ignore the per-device bits leaf; use "
            "method='fused' (or 'alternating'/'optimal') for bit-scaled "
            "problems")
    if sanitize:
        prob, _ = batch.problem.sanitize()
        batch = dataclasses.replace(batch, problem=prob)
    if init is not None:
        if method not in ("alternating", "fused"):
            raise ValueError(
                f"init warm-starts the iterative methods only; "
                f"method={method!r} computes its solution in a fixed "
                "number of steps and would silently ignore it")
        init = WarmStart(a=jnp.asarray(init[0], jnp.float32),
                         power=jnp.asarray(init[1], jnp.float32))
    alg2 = method in ("alternating", "fused", "fused_kernel")
    if not alg2 and faithful_eq13_typo:
        raise ValueError(
            f"faithful_eq13_typo only applies to the Algorithm-2 methods "
            f"('alternating'/'fused'/'fused_kernel'); method={method!r} "
            "computes the exact per-element optimum and has no eq. (13) step")
    if chunk_elements is not None and method != "fused":
        raise ValueError(
            f"chunk_elements is a method='fused' memory bound; "
            f"method={method!r} would silently ignore it")
    if power_solver is None:
        power_solver = ("analytic" if method in ("fused", "fused_kernel")
                        else "dinkelbach")
    if method == "fused_kernel" and power_solver != "analytic":
        raise ValueError(
            f"method='fused_kernel' only implements the analytic "
            f"(closed-form) power update; power_solver={power_solver!r} "
            "would be silently ignored — use method='fused' for the "
            "Dinkelbach reference mode")
    if method == "fused":
        menu = None if bit_menu is None else tuple(
            sorted({float(b) for b in bit_menu}, reverse=True))
        return _solve_batch_fused(batch, power_solver, faithful_eq13_typo,
                                  eps, max_iters, chunk_elements, mesh, shard,
                                  init, menu)
    if shard:
        batch = shard_batch(batch, mesh)
    if method == "kernel":
        from repro.kernels.selection_solve.ops import solve_joint_kernel_batch
        return solve_joint_kernel_batch(batch, interpret=interpret)
    if method == "fused_kernel":
        from repro.kernels.selection_solve.ops import solve_joint_fused_kernel_batch
        # the kernel runs its full iteration budget unconditionally (fixed
        # trip count, each element frozen at its first step below the
        # default eps), so ``eps`` is not passed on; ``max_iters`` maps
        # onto that budget.
        return solve_joint_fused_kernel_batch(
            batch, n_iters=max_iters, faithful_eq13_typo=faithful_eq13_typo,
            interpret=interpret)
    return _solve_batch_vmapped(batch, method, power_solver,
                                faithful_eq13_typo, eps, max_iters, init)
