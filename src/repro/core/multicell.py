"""Multi-cell metro control plane: C coupled cells, one fused solve per tick.

The paper solves a single cell.  A metro deployment runs *C* cells whose
per-device problems are coupled two ways (docs/multicell.md):

* **inter-cell interference** — a device transmitting in cell c' raises
  the noise floor at cell c's base station.  With an aggregate coupling
  gain ``G[c, c']`` (path loss x spectral-overlap factor between the two
  cells, zero diagonal), the interference power received at BS c is::

      I_c = sum_{c' != c} G[c, c'] * sum_i a_{c'i} P_{c'i}

  (``a P`` is the *expected* transmit power of a probabilistically
  selected device).  ``I_c`` enters every SINR in cell c through the
  ``WirelessFLProblem.interference`` leaf: sigma^2 -> sigma^2 + I_c.
* **a shared backhaul budget** — all C cells upload through one metro
  aggregation link of ``backhaul_bits`` capacity per round, constraining
  the expected traffic ``sum_{c,i} a_{ci} S <= B``.

Both couplings are resolved by a **dual-decomposition outer loop**
(:func:`solve_coupled`): fix the interference estimate ``I`` and the
backhaul price ``mu``, run the existing fused flat solver
(``solve_joint_batch(method="fused")``) over the *union* (cell, device)
element set — one convergence-masked while-loop reusing its chunking and
element-axis ``NamedSharding`` — then update ``(I, mu)`` from the new
solution and repeat until the coupled-KKT residual converges.  The inner
solve is the only accelerator work; the outer updates are O(C N) numpy.

The backhaul price step is *exact* (a continuous knapsack, not a
subgradient step): given the per-element caps ``a*`` from the inner
solve, the budget-constrained selection maximising ``sum w a`` fills
devices in decreasing weight order with one fractional marginal device,
whose weight density is the optimal price ``mu``.  Complementary
slackness therefore holds exactly at every outer iteration (pinned by
``tests/test_multicell.py``).

Identity guarantee: with an all-zero coupling matrix and no backhaul
budget, the zero interference estimate is *elided* (the problem keeps
``interference=None``), so the one outer iteration runs byte-for-byte
the same compiled program as the uncoupled
``solve_joint_batch(cells, method="fused")`` — bitwise-identical
solutions, converged after a single outer step.

Serving: ``FleetControlService.solve_coupled`` batches a whole metro
tick through this loop and warm-starts ``(I, mu)`` (and the element warm
start) from the previous tick via :class:`CoupledDuals` /
``MultiCellSolution.resume`` — on a coherent channel the outer loop then
collapses to one or two iterations (``multicell_solver`` benchmarks).

Layouts: :func:`grid_coupling` (a square grid, ``gain / dist^alpha``)
and :func:`hex_coupling` (3GPP TR 38.901 UMa: a hexagonal lattice of
three-sector sites, NLOS path loss and the sector antenna pattern).

Profiler spans: while a ``jax.profiler`` trace is being taken, each
outer iteration writes ``multicell.solve`` (the union solve, to
completion), ``multicell.readback`` (``a``/``P`` to the host),
``multicell.price`` (the knapsack) and ``multicell.interference``
(``I`` from the answers, the residual, and the next iteration's
interference leaf built and uploaded; the first iteration's leaf is
built in one more such span before the loop).  With no trace running
the loop pays one ``is_enabled`` check and builds no annotation.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core.alternating import WarmStart, solve_joint_fused
from repro.core.batch import (
    BatchSolution,
    ProblemBatch,
    pad_batch,
    solve_joint_batch,
    stack_problems,
)
from repro.core.problem import WirelessFLProblem

# profiler spans, written only while a jax.profiler trace is being taken
SPAN_SOLVE = "multicell.solve"                # union solve, to completion
SPAN_READBACK = "multicell.readback"          # a / P device -> host
SPAN_PRICE = "multicell.price"                # backhaul knapsack
SPAN_INTERFERENCE = "multicell.interference"  # I update, next leaf upload

_NO_SPAN = contextlib.nullcontext()


def _span(on: bool, name: str):
    """Profiler span ``name`` when ``on`` (a trace is running), else a
    shared no-op: with tracing off no annotation is built."""
    return TraceAnnotation(name) if on else _NO_SPAN


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class MultiCellProblem:
    """C per-cell problem (7) instances plus their metro-level coupling.

    ``cells`` stacks the per-cell :class:`WirelessFLProblem` leaves
    (``[C, N_max]``, fading ``[C, N_max, K]``); ``coupling[c, c']`` is
    the aggregate interference gain from cell c' transmissions into cell
    c's base-station receiver (zero diagonal — own-cell traffic is
    orthogonal OFDMA, not interference); ``backhaul_bits`` is the shared
    per-round metro uplink budget in bits (``None`` = unconstrained).
    """

    cells: ProblemBatch
    coupling: jax.Array      # [C, C], >= 0, zero diagonal
    backhaul_bits: Optional[float] = dataclasses.field(
        default=None, metadata=dict(static=True))

    @property
    def n_cells(self) -> int:
        return self.cells.batch_size


def make_multicell(problems: Sequence[WirelessFLProblem] | ProblemBatch,
                   coupling: np.ndarray | jax.Array,
                   *, backhaul_bits: Optional[float] = None
                   ) -> MultiCellProblem:
    """Validate and assemble a :class:`MultiCellProblem`.

    ``problems`` is either per-cell instances (stacked here) or an
    already-stacked :class:`ProblemBatch`; ``coupling`` must be a
    ``[C, C]`` non-negative matrix with a zero diagonal.
    """
    cells = problems if isinstance(problems, ProblemBatch) \
        else stack_problems(list(problems))
    g = np.asarray(coupling, np.float64)
    c = cells.batch_size
    if g.shape != (c, c):
        raise ValueError(f"coupling must be [{c}, {c}] for {c} cells, "
                         f"got {g.shape}")
    if not np.isfinite(g).all():
        raise ValueError("coupling gains must be finite — a NaN/Inf entry "
                         "would poison every cell's interference estimate")
    if np.any(g < 0):
        raise ValueError("coupling gains must be non-negative")
    if np.any(np.diag(g) != 0):
        raise ValueError(
            "coupling must have a zero diagonal — own-cell OFDMA traffic "
            "is orthogonal, not interference (model extra in-cell noise "
            "through noise_power instead)")
    if backhaul_bits is not None and backhaul_bits <= 0:
        raise ValueError(f"backhaul_bits must be positive, "
                         f"got {backhaul_bits}")
    return MultiCellProblem(cells=cells, coupling=jnp.asarray(g, jnp.float32),
                            backhaul_bits=None if backhaul_bits is None
                            else float(backhaul_bits))


def grid_coupling(n_cells: int, *, gain: float, alpha: float = 2.0,
                  spacing: float = 1.0) -> np.ndarray:
    """Square-grid coupling matrix: cells on a ceil(sqrt(C)) grid, gain
    ``gain / dist^alpha`` between distinct cells (``dist`` in units of
    ``spacing``), zero diagonal.  ``gain`` is the nearest-neighbour
    coupling; diagonal neighbours get ``gain / 2^(alpha/2)`` and so on.
    """
    side = int(np.ceil(np.sqrt(n_cells)))
    xy = np.stack(np.divmod(np.arange(n_cells), side), axis=1) * spacing
    d = np.linalg.norm(xy[:, None, :] - xy[None, :, :], axis=-1)
    with np.errstate(divide="ignore"):
        g = gain * spacing ** alpha / np.maximum(d, 1e-30) ** alpha
    np.fill_diagonal(g, 0.0)
    return g


# 3GPP TR 38.901 V17, urban macro (UMa).  Table 7.2-1: BS and UT
# heights, the least BS-UT distance on the ground.
UMA_BS_HEIGHT_M = 25.0
UMA_UT_HEIGHT_M = 1.5
UMA_MIN_DISTANCE_2D_M = 35.0
# Table 7.4.1-1, UMa NLOS PL'_UMa-NLOS (dB; d3D in m, fc in GHz):
# a + b log10(d3D) + c log10(fc) - e (h_UT - 1.5)
UMA_NLOS_PL_DB = (13.54, 39.08, 20.0, 0.6)
# Table 7.3-1, horizontal pattern A(phi) = -min(12 (phi / phi_3dB)^2, A_m)
SECTOR_PHI_3DB_DEG = 65.0
SECTOR_A_M_DB = 30.0
# boresights of a site's three sectors (TR 38.901 Sec. 7.8, ITU-R M.2412)
SECTOR_BORESIGHTS_DEG = (30.0, 150.0, 270.0)


def uma_nlos_path_loss_db(d3d_m, fc_ghz: float) -> np.ndarray:
    """UMa NLOS path loss PL' (dB) at 3-D distance ``d3d_m``."""
    a, b, c, e = UMA_NLOS_PL_DB
    return (a + b * np.log10(d3d_m) + c * np.log10(fc_ghz)
            - e * (UMA_UT_HEIGHT_M - 1.5))


def sector_pattern_db(phi_deg) -> np.ndarray:
    """Horizontal sector antenna gain (dB) at ``phi_deg`` off boresight,
    any angle (wrapped to [-180, 180))."""
    phi = (np.asarray(phi_deg, np.float64) + 180.0) % 360.0 - 180.0
    return -np.minimum(12.0 * (phi / SECTOR_PHI_3DB_DEG) ** 2, SECTOR_A_M_DB)


def hex_sites(rings: int, isd_m: float) -> np.ndarray:
    """``[1 + 3 R (R + 1), 2]`` site positions (m) of a hexagonal lattice
    of ``R = rings`` rings around a centre site, ``isd_m`` apart."""
    q, r = np.meshgrid(np.arange(-rings, rings + 1),
                       np.arange(-rings, rings + 1), indexing="ij")
    keep = np.abs(q + r) <= rings
    q, r = q[keep], r[keep]
    return isd_m * np.stack([q + 0.5 * r, np.sqrt(3.0) / 2.0 * r], axis=1)


def sector_points(rings: int, isd_m: float, n_points: int,
                  seed: int) -> np.ndarray:
    """``[C, n_points, 2]`` points drawn uniformly in each sector.

    Cell ``c = 3 s + k`` is sector ``k`` of site ``s``: the third of the
    site's hexagon (circumradius ``isd_m / sqrt(3)``) that is the rhombus
    spanned by the two hexagon corners 60 degrees either side of its
    boresight."""
    sites = hex_sites(rings, isd_m)
    rng = np.random.default_rng(seed)
    radius = isd_m / np.sqrt(3.0)
    bore = np.radians(SECTOR_BORESIGHTS_DEG)
    corners = radius * np.stack(
        [np.stack([np.cos(bore - np.pi / 3), np.sin(bore - np.pi / 3)], -1),
         np.stack([np.cos(bore + np.pi / 3), np.sin(bore + np.pi / 3)], -1)],
        axis=1)                                            # [3, 2, 2]
    st = rng.uniform(size=(len(sites), 3, n_points, 2))
    pts = sites[:, None, None, :] + st @ corners[None]     # [S, 3, P, 2]
    return pts.reshape(3 * len(sites), n_points, 2)


def hex_coupling(rings: int, isd_m: float = 500.0, *, n_points: int = 64,
                 seed: int = 0, fc_ghz: float = 3.5) -> np.ndarray:
    """Coupling matrix of a 3GPP TR 38.901 UMa metro (W/W, zero diagonal).

    ``1 + 3 R (R + 1)`` three-sector sites on a hexagonal lattice of
    ``R = rings`` rings, ``isd_m`` apart, no wrap-around; cell ``c = 3 s +
    k`` is sector ``k`` of site ``s`` (:func:`sector_points`).
    ``G[c, c']`` is the mean, over ``n_points`` points drawn from ``seed``
    uniformly in sector ``c'``, of ``10^((A_c(phi) - PL(d3D)) / 10)``:
    the UMa NLOS path loss (:func:`uma_nlos_path_loss_db`) from the point
    to cell c's site, with the ground distance held to at least
    ``UMA_MIN_DISTANCE_2D_M``, and cell c's sector pattern
    (:func:`sector_pattern_db`) at the point's bearing from its site.
    """
    sites = hex_sites(rings, isd_m)
    pts = sector_points(rings, isd_m, n_points, seed)      # [C, P, 2]
    n_cells = len(pts)
    site_of = np.repeat(np.arange(len(sites)), 3)
    bore = np.tile(SECTOR_BORESIGHTS_DEG, len(sites))
    dh = UMA_BS_HEIGHT_M - UMA_UT_HEIGHT_M
    g = np.empty((n_cells, n_cells))
    for c in range(n_cells):          # one receiving cell's row at a time
        rel = pts - sites[site_of[c]]                      # [C, P, 2]
        d2d = np.maximum(np.hypot(rel[..., 0], rel[..., 1]),
                         UMA_MIN_DISTANCE_2D_M)
        loss = uma_nlos_path_loss_db(np.sqrt(d2d ** 2 + dh ** 2), fc_ghz)
        phi = np.degrees(np.arctan2(rel[..., 1], rel[..., 0])) - bore[c]
        g[c] = np.mean(10.0 ** ((sector_pattern_db(phi) - loss) / 10.0),
                       axis=1)
    np.fill_diagonal(g, 0.0)
    return g


def pad_metro(mc: MultiCellProblem, *, n_cells: Optional[int] = None,
              n_max: Optional[int] = None) -> MultiCellProblem:
    """Pad a metro to fixed ``(n_cells, n_max)`` slot shapes.

    The serving path quantises metro shapes into buckets so jit compiles
    once per bucket (exactly like :func:`repro.core.batch.pad_batch`,
    which this wraps).  Padded cells get zero coupling rows/columns and
    the standard padded-device leaves (zero weights and energy budgets),
    so they select nothing, radiate nothing, and add no backhaul load.
    """
    cells = pad_batch(mc.cells, batch_size=n_cells, n_max=n_max)
    c0, c1 = mc.n_cells, cells.batch_size
    if c1 == c0 and cells is mc.cells:
        return mc
    g = np.zeros((c1, c1), np.float32)
    g[:c0, :c0] = np.asarray(mc.coupling)
    return MultiCellProblem(cells=cells, coupling=jnp.asarray(g),
                            backhaul_bits=mc.backhaul_bits)


class CoupledDuals(NamedTuple):
    """Warm-start state carried across metro ticks (``.resume``)."""

    interference: np.ndarray          # [C] (or [C, K]) last I estimate, W
    mu: np.ndarray                    # scalar (or [K]) backhaul price
    warm: Optional[WarmStart] = None  # element warm start for the inner solve


class MultiCellSolution(NamedTuple):
    """Converged coupled solve: the union solution plus the dual state."""

    batch: BatchSolution      # per-cell (a*, P*), padded [C, N_max(, K)]
    interference: np.ndarray  # [C] or [C, K] consistent with batch
    mu: np.ndarray            # scalar or [K] backhaul price (weight / unit a)
    backhaul_load: np.ndarray  # scalar or [K] expected metro uplink bits
    outer_iters: int          # dual-decomposition iterations run
    residual: float           # final coupled-KKT residual
    converged: bool           # residual <= outer_tol within the budget
    # the interference estimate ``batch`` was solved at ([C] or [C, K]);
    # ``interference`` is the next estimate, recomputed from ``batch``
    solved_at: np.ndarray
    # True when the outer loop ran out of iterations: the returned state
    # is then the *best-residual* iterate seen (best-feasible-so-far),
    # not the last step's — see docs/robustness.md
    hit_iter_cap: bool = False

    @property
    def resume(self) -> CoupledDuals:
        """Dual/warm state seeding the next tick's :func:`solve_coupled`."""
        return CoupledDuals(interference=self.interference, mu=self.mu,
                            warm=WarmStart(a=self.batch.a,
                                           power=self.batch.power))


def cell_interference(coupling: np.ndarray, a: np.ndarray,
                      power: np.ndarray) -> np.ndarray:
    """I_c = sum_{c'} G[c, c'] sum_i a_{c'i} P_{c'i} — the interference
    power each BS receives given the fleet's expected transmit powers.

    ``a``/``power`` are ``[C, N]`` or ``[C, N, K]`` (padded slots carry
    ``a = 0`` and drop out); returns ``[C]`` or ``[C, K]``.
    """
    tx = np.asarray(a, np.float64) * np.asarray(power, np.float64)
    per_cell = tx.sum(axis=1)                  # [C] or [C, K]
    return np.asarray(coupling, np.float64) @ per_cell


def _knapsack_round(caps: np.ndarray, w: np.ndarray, s_bits: float,
                    budget: float) -> tuple[np.ndarray, float, float]:
    """Exact budget projection for one round: maximise ``sum w a`` over
    ``0 <= a <= caps`` s.t. ``sum a * s_bits <= budget``.

    Continuous knapsack with uniform per-unit cost: fill by decreasing
    weight, one fractional marginal element.  Returns ``(a, mu, load)``
    where ``mu`` is the marginal element's weight — the exact dual price
    of the budget constraint (0 when it does not bind), so
    ``mu * (load - budget) == 0`` holds by construction.
    """
    caps = np.asarray(caps, np.float64).ravel()
    w = np.asarray(w, np.float64).ravel()
    total = caps.sum() * s_bits
    if total <= budget:
        return caps, 0.0, total
    order = np.argsort(-w, kind="stable")
    bits = caps[order] * s_bits
    csum = np.cumsum(bits)
    j = int(np.searchsorted(csum, budget, side="left"))
    a = np.zeros_like(caps)
    a[order[:j]] = caps[order[:j]]
    spent = csum[j - 1] if j > 0 else 0.0
    a[order[j]] = (budget - spent) / s_bits
    return a, float(w[order[j]]), float(budget)


def _backhaul_project(a_cap: np.ndarray, w: np.ndarray, s_bits: float,
                      budget: Optional[float]
                      ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Apply the knapsack per round.  ``a_cap`` is ``[C, N]`` or
    ``[C, N, K]``; the budget applies to each round independently.
    Returns ``(a, mu, load)`` with ``mu``/``load`` scalar or ``[K]``.
    """
    a_cap = np.asarray(a_cap, np.float64)
    if budget is None:
        load = a_cap.sum(axis=(0, 1)) * s_bits    # scalar-0d or [K]
        return a_cap, np.zeros_like(load), load
    if a_cap.ndim == 2:
        a, mu, load = _knapsack_round(a_cap, w, s_bits, budget)
        return a.reshape(a_cap.shape), np.float64(mu), np.float64(load)
    k_rounds = a_cap.shape[-1]
    a = np.empty_like(a_cap)
    mu = np.zeros(k_rounds)
    load = np.zeros(k_rounds)
    for k in range(k_rounds):
        ak, mu[k], load[k] = _knapsack_round(a_cap[:, :, k], w, s_bits,
                                             budget)
        a[:, :, k] = ak.reshape(a_cap.shape[:2])
    return a, mu, load


def _with_interference(cells: ProblemBatch,
                       interference: np.ndarray) -> ProblemBatch:
    """``cells`` with per-cell interference ``[C]``/``[C, K]`` broadcast
    to every device slot.  An all-zero estimate is *elided* (the problem
    keeps its original ``interference`` leaf — ``None`` for a plain
    metro), so the zero-coupling path compiles and runs exactly the
    uncoupled program (the bitwise-identity guarantee)."""
    interference = np.asarray(interference)
    if not interference.any():
        return cells
    c, n_max = cells.batch_size, cells.n_max
    if interference.ndim == 1:
        arr = np.broadcast_to(interference[:, None], (c, n_max))
    else:
        arr = np.broadcast_to(interference[:, None, :],
                              (c, n_max, interference.shape[-1]))
    base = cells.problem.interference
    if base is not None:                       # exogenous interference adds
        arr = arr + np.asarray(base)
    prob = dataclasses.replace(cells.problem,
                               interference=jnp.asarray(arr, jnp.float32))
    return dataclasses.replace(cells, problem=prob)


def _relative_delta(old: np.ndarray, new: np.ndarray) -> float:
    scale = max(float(np.max(np.abs(old), initial=0.0)),
                float(np.max(np.abs(new), initial=0.0)), 1e-30)
    return float(np.max(np.abs(new - old), initial=0.0)) / scale


def _masked_weights(cells: ProblemBatch) -> np.ndarray:
    w = np.asarray(cells.problem.weights, np.float64)
    return np.where(np.asarray(cells.mask), w, 0.0)


def solve_coupled(mc: MultiCellProblem,
                  *,
                  outer_iters: int = 25,
                  outer_tol: float = 1e-3,
                  damping: float = 0.5,
                  method: str = "fused",
                  power_solver: Optional[str] = None,
                  eps: float = 1e-7,
                  max_iters: int = 50,
                  chunk_elements: Optional[int] = None,
                  mesh: Optional[jax.sharding.Mesh] = None,
                  shard: bool = True,
                  warm_start: bool = True,
                  sanitize: bool = False,
                  init: Optional[CoupledDuals] = None) -> MultiCellSolution:
    """Dual-decomposition solve of a coupled metro tick.

    Each outer iteration (host python; the module docstring derives it):

    1. **inner solve** — fix the interference estimate ``I``; solve the
       union (cell, device) element set in ONE fused flat call,
       ``solve_joint_batch(cells + I, method="fused")``, inheriting its
       ``chunk_elements`` bound and element-axis sharding.  ``I`` enters
       through the ``interference`` leaf only — no solver change.
    2. **backhaul price** — project the per-element caps ``a*`` onto the
       shared budget with the exact knapsack dual (`mu` = marginal
       weight; complementary slackness exact).
    3. **interference update** — recompute ``I`` from the projected
       solution and relax with ``damping`` (1.0 = undamped fixed point;
       smaller values damp the power <-> interference feedback on
       strongly coupled grids).

    Stops when the coupled-KKT residual — the max of the relative
    interference-fixed-point error and the relative price change — drops
    to ``outer_tol``, or after ``outer_iters``.  ``init`` (a
    :class:`CoupledDuals`, typically ``prev.resume``) warm-starts
    ``(I, mu)`` and the element iterates; shape-mismatched state is
    ignored (cold start) so fleet reconfigurations need no special
    casing.  Solutions are init-independent to solver tolerance; only
    outer/inner iteration counts change (the serving claim the
    ``multicell_solver`` bench gates).

    ``sanitize=True`` forwards to ``solve_joint_batch`` (unhealthy
    devices self-deselect).  If the loop exhausts ``outer_iters`` the
    returned solution is the **best-residual iterate seen** with
    ``hit_iter_cap=True`` — degraded but usable, never the last
    (possibly oscillating) step by accident; converged solves are
    bit-identical to the pre-flag behaviour.  On either path
    ``solved_at`` is the estimate ``I`` that the returned ``batch`` was
    solved at, and ``interference`` the one recomputed from it.
    """
    cells = mc.cells
    if damping <= 0.0 or damping > 1.0:
        raise ValueError(f"damping must be in (0, 1], got {damping}")
    if outer_iters < 1:
        raise ValueError(f"outer_iters must be >= 1, got {outer_iters}")
    coupling = np.asarray(mc.coupling, np.float64)
    per_round = cells.problem.fading is not None
    k_rounds = cells.problem.fading.shape[-1] if per_round else None
    i_shape = (mc.n_cells, k_rounds) if per_round else (mc.n_cells,)
    s_bits = cells.problem.grad_size_bits
    w = _masked_weights(cells)

    interference = np.zeros(i_shape)
    mu = np.zeros(k_rounds) if per_round else np.float64(0.0)
    warm = None
    if init is not None:
        if np.shape(init.interference) == i_shape:
            interference = np.asarray(init.interference, np.float64)
        if np.shape(init.mu) == np.shape(mu):
            mu = np.asarray(init.mu, np.float64)
        if warm_start and init.warm is not None:
            sol_shape = i_shape[:1] + (cells.n_max,) + i_shape[1:]
            if tuple(init.warm.a.shape) == sol_shape:
                warm = init.warm

    bs = None
    a_proj: np.ndarray | jax.Array = jnp.zeros(0)
    load = np.zeros(k_rounds) if per_round else np.float64(0.0)
    residual, converged, t = float("inf"), False, 0
    # best-residual iterate: (residual, bs, a_proj, mu, load, I, solved_at)
    best = None
    trace = TraceAnnotation.is_enabled()
    with _span(trace, SPAN_INTERFERENCE):
        problem = _with_interference(cells, interference)
    for t in range(1, outer_iters + 1):  # noqa: B007 - read after the loop
        solved_at = interference
        with _span(trace, SPAN_SOLVE):
            bs = jax.block_until_ready(solve_joint_batch(
                problem, method=method,
                power_solver=power_solver, eps=eps, max_iters=max_iters,
                chunk_elements=chunk_elements, mesh=mesh, shard=shard,
                sanitize=sanitize,
                init=warm if warm_start else None))
        with _span(trace, SPAN_READBACK):
            a_host = np.asarray(bs.a)
            p_host = np.asarray(bs.power)
        with _span(trace, SPAN_PRICE):
            if mc.backhaul_bits is None:
                # no projection: keep the solver's arrays untouched so
                # the zero-coupling path stays bitwise identical to the
                # uncoupled solve
                a_proj = bs.a
                mu_new = np.zeros_like(mu)
                i_src = np.asarray(a_host, np.float64)
                load = i_src.sum(axis=(0, 1)) * s_bits
            else:
                a_proj, mu_new, load = _backhaul_project(
                    a_host, w, s_bits, mc.backhaul_bits)
                i_src = a_proj
        with _span(trace, SPAN_INTERFERENCE):
            i_new = cell_interference(coupling, i_src, p_host)
            residual = max(_relative_delta(interference, i_new),
                           _relative_delta(np.atleast_1d(mu),
                                           np.atleast_1d(mu_new)))
            converged = residual <= outer_tol
            if best is None or residual < best[0]:
                best = (residual, bs, a_proj, mu_new, load, i_new, solved_at)
            interference = i_new if converged or damping >= 1.0 \
                else interference + damping * (i_new - interference)
            mu = mu_new
            if warm_start:
                warm = bs.resume
            if not converged and t < outer_iters:
                problem = _with_interference(cells, interference)
        if converged:
            break

    hit_iter_cap = not converged
    if hit_iter_cap:
        # iteration cap: hand back the best-residual iterate seen, not
        # whatever the last (possibly oscillating) step produced
        residual, bs, a_proj, mu, load, interference, solved_at = best

    if mc.backhaul_bits is None:
        final = bs
    else:
        a_arr = jnp.asarray(a_proj, jnp.float32)
        w_b = w if a_arr.ndim == 2 else w[:, :, None]
        objective = jnp.asarray(
            np.sum(np.asarray(a_proj, np.float64) * w_b, axis=tuple(
                range(1, np.ndim(a_proj)))), jnp.float32)
        final = bs._replace(a=a_arr, objective=objective)
    return MultiCellSolution(batch=final, interference=interference, mu=mu,
                             backhaul_load=load, outer_iters=t,
                             residual=residual, converged=converged,
                             hit_iter_cap=hit_iter_cap, solved_at=solved_at)


@functools.lru_cache(maxsize=32)
def _loop_cell_solve(power_solver: str, eps: float, max_iters: int):
    """Jitted per-cell solve for :func:`solve_coupled_loop`, cached per
    solver configuration so repeated calls reuse one executable per
    problem structure."""
    return jax.jit(functools.partial(
        solve_joint_fused, power_solver=power_solver, eps=eps,
        max_iters=max_iters, shard=False))


def solve_coupled_loop(mc: MultiCellProblem,
                       *,
                       outer_iters: int = 25,
                       outer_tol: float = 1e-3,
                       damping: float = 0.5,
                       power_solver: Optional[str] = None,
                       eps: float = 1e-7,
                       max_iters: int = 50) -> MultiCellSolution:
    """Reference implementation: the same dual decomposition with a
    *python loop of per-cell* ``solve_joint_fused`` calls per outer
    iteration instead of one union solve — C jit dispatches per step.

    Agreement oracle for the tests and the baseline the
    ``multicell_solver`` benchmark's compare.py floor measures
    :func:`solve_coupled` against (the issue's "per-cell loop with the
    fixed point in python").
    """
    cells = mc.cells
    if outer_iters < 1:
        raise ValueError(f"outer_iters must be >= 1, got {outer_iters}")
    power_solver = power_solver or "analytic"
    # jit the per-cell solve: bare ``solve_joint_fused`` dispatches its
    # while_loop eagerly, which recompiles per call — C x outer_iters
    # fresh LLVM modules per solve would exhaust the process map budget
    cell_solve = _loop_cell_solve(power_solver, eps, max_iters)
    problems = cells.unstack()
    coupling = np.asarray(mc.coupling, np.float64)
    per_round = cells.problem.fading is not None
    k_rounds = cells.problem.fading.shape[-1] if per_round else None
    i_shape = (mc.n_cells, k_rounds) if per_round else (mc.n_cells,)
    s_bits = cells.problem.grad_size_bits
    w = _masked_weights(cells)
    n_max = cells.n_max

    def pad(x, n):
        pad_width = [(0, n_max - n)] + [(0, 0)] * (x.ndim - 1)
        return np.pad(np.asarray(x, np.float64), pad_width)

    interference = np.zeros(i_shape)
    mu = np.zeros(k_rounds) if per_round else np.float64(0.0)
    a_pad = np.zeros(i_shape[:1] + (n_max,) + i_shape[1:])
    p_pad = np.zeros_like(a_pad)
    residual, converged, t = float("inf"), False, 0
    conv_all = True
    for t in range(1, outer_iters + 1):  # noqa: B007 - read after the loop
        solved_at = interference
        sols = []
        for c, prob in enumerate(problems):
            i_c = interference[c]
            if np.any(i_c):
                shape = (prob.n_devices,) if not per_round \
                    else (prob.n_devices, k_rounds)
                prob = dataclasses.replace(
                    prob, interference=jnp.asarray(
                        np.broadcast_to(np.reshape(i_c, (1,) + i_c.shape),
                                        shape), jnp.float32))
            sols.append(cell_solve(prob))
        a_pad = np.stack([pad(s.a, p.n_devices)
                          for s, p in zip(sols, problems)])
        p_pad = np.stack([pad(s.power, p.n_devices)
                          for s, p in zip(sols, problems)])
        conv_all = all(bool(np.all(np.asarray(s.converged))) for s in sols)
        a_proj, mu_new, load = _backhaul_project(a_pad, w, s_bits,
                                                 mc.backhaul_bits)
        i_new = cell_interference(coupling, a_proj, p_pad)
        residual = max(_relative_delta(interference, i_new),
                       _relative_delta(np.atleast_1d(mu),
                                       np.atleast_1d(mu_new)))
        converged = residual <= outer_tol
        interference = i_new if converged or damping >= 1.0 \
            else interference + damping * (i_new - interference)
        mu = mu_new
        a_pad = a_proj
        if converged:
            break

    w_b = w if a_pad.ndim == 2 else w[:, :, None]
    batch = BatchSolution(
        a=jnp.asarray(a_pad, jnp.float32),
        power=jnp.asarray(p_pad, jnp.float32),
        objective=jnp.asarray(np.sum(a_pad * w_b, axis=tuple(
            range(1, a_pad.ndim))), jnp.float32),
        n_iters=jnp.asarray(t), converged=jnp.asarray(
            np.full(mc.n_cells, conv_all)),
        mask=cells.mask)
    return MultiCellSolution(batch=batch, interference=interference, mu=mu,
                             backhaul_load=load, outer_iters=t,
                             residual=residual, converged=converged,
                             hit_iter_cap=not converged, solved_at=solved_at)
