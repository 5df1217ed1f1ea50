"""Jit'd public wrappers for the selection_solve kernels.

``solve_joint_kernel`` takes one WirelessFLProblem and returns a
JointSolution (drop-in for ``core.optimal.solve_joint_optimal``);
``solve_joint_fused_kernel`` is the same wrapper around the fused
alternating fixed point (drop-in for ``core.alternating.solve_joint`` /
``solve_joint_fused``).  The ``*_batch`` variants take a
``core.batch.ProblemBatch`` and return a ``BatchSolution`` — the problem
(7) element set is separable per ``(instance, device, round)``, so the
whole batch flattens into one tiled kernel launch.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core.alternating import JointSolution
from repro.core.problem import WirelessFLProblem
from repro.kernels import resolve_interpret

_ROWS_BLK = 256 * 128   # elements per kernel tile: (256, 128) f32


def _pack(x, n_pad):
    x = jnp.pad(x.astype(jnp.float32).reshape(-1), (0, n_pad),
                constant_values=1.0)
    return x.reshape(-1, 128)


def _bcast_rounds(x: jax.Array, like: jax.Array) -> jax.Array:
    """Broadcast per-device x to per-(device, round) rank of ``like``."""
    return x if x.ndim == like.ndim else jnp.broadcast_to(
        x[..., None], like.shape)


def _solve_elements(problem: WirelessFLProblem, pg: jax.Array,
                    interpret: bool | None, tiled_fn=None,
                    **tiled_kw) -> tuple[jax.Array, jax.Array]:
    """Run a tiled kernel over every element of ``pg`` (any shape),
    returning (a*, P*) with ``pg``'s shape.  Scalar constraint data is
    broadcast from the problem; per-device vectors are broadcast across
    rounds."""
    if tiled_fn is None:
        from repro.kernels.selection_solve.kernel import selection_solve_tiled
        tiled_fn = selection_solve_tiled

    bw = _bcast_rounds(problem.bandwidth_hz, pg)
    emax = _bcast_rounds(problem.energy_budget_j, pg)
    ec = _bcast_rounds(problem.compute_energy(), pg)

    n = pg.size
    m_pad = -(-n // _ROWS_BLK) * _ROWS_BLK
    n_pad = m_pad - n
    args = [_pack(v, n_pad) for v in (pg, bw, emax, ec)]
    a, p = tiled_fn(
        *args, s_bits=problem.grad_size_bits, tau=problem.tau_th,
        p_max=problem.p_max, interpret=resolve_interpret(interpret),
        **tiled_kw)
    return (a.reshape(-1)[:n].reshape(pg.shape),
            p.reshape(-1)[:n].reshape(pg.shape))


@partial(jax.jit, static_argnames=("interpret",))
def solve_joint_kernel(problem: WirelessFLProblem,
                       interpret: bool | None = None) -> JointSolution:
    a, p = _solve_elements(problem, problem.path_gain(), interpret)
    return JointSolution(a=a, power=p, objective=problem.objective(a),
                         n_iters=jnp.int32(60), converged=jnp.asarray(True))


@partial(jax.jit, static_argnames=("interpret",))
def solve_joint_kernel_batch(batch, interpret: bool | None = None):
    """Pallas fast path for ``core.batch.solve_joint_batch``.

    Flattens the [B, N_max] (or [B, N_max, K]) element set into one tiled
    ``selection_solve`` launch.  Solves the same per-element bisection
    problem as ``solve_joint_optimal`` (the paper's Algorithm 2 is a local
    method; the kernel computes the exact per-element optimum).
    """
    from repro.core.batch import _mask_solution

    problem = batch.problem
    # per-instance rank-sensitive broadcasting lives in path_gain(); vmap it
    # rather than reimplementing the [B, N, K] case here.
    pg = jax.vmap(WirelessFLProblem.path_gain)(problem)
    a, p = _solve_elements(problem, pg, interpret)
    b = batch.mask.shape[0]
    sol = JointSolution(a=a, power=p,
                        objective=jax.vmap(WirelessFLProblem.objective)(problem, a),
                        n_iters=jnp.full((b,), 60, jnp.int32),
                        converged=jnp.ones((b,), bool))
    return _mask_solution(sol, batch.mask)


# ------------------------------------------- fused alternating fixed point

@partial(jax.jit, static_argnames=("n_iters", "faithful_eq13_typo",
                                   "interpret"))
def solve_joint_fused_kernel(problem: WirelessFLProblem,
                             n_iters: int = 50,
                             faithful_eq13_typo: bool = False,
                             interpret: bool | None = None) -> JointSolution:
    """Pallas fused Algorithm-2 solve for one problem (drop-in for
    ``core.alternating.solve_joint_fused``; agreement <= 1e-5)."""
    from repro.kernels.selection_solve.kernel import fused_solve_tiled

    a, p = _solve_elements(problem, problem.path_gain(), interpret,
                           tiled_fn=fused_solve_tiled, n_iters=n_iters,
                           faithful_eq13_typo=faithful_eq13_typo)
    return JointSolution(a=a, power=p, objective=problem.objective(a),
                         n_iters=jnp.int32(n_iters),
                         converged=jnp.asarray(True))


@partial(jax.jit, static_argnames=("n_iters", "faithful_eq13_typo",
                                   "interpret"))
def solve_joint_fused_kernel_batch(batch, n_iters: int = 50,
                                   faithful_eq13_typo: bool = False,
                                   interpret: bool | None = None):
    """Pallas fused path for ``core.batch.solve_joint_batch``: the whole
    [B * N_max (* K)] element set runs the alternating fixed point in one
    tiled launch, every iterate VMEM-resident."""
    from repro.core.batch import _mask_solution
    from repro.kernels.selection_solve.kernel import fused_solve_tiled

    problem = batch.problem
    pg = jax.vmap(WirelessFLProblem.path_gain)(problem)
    a, p = _solve_elements(problem, pg, interpret,
                           tiled_fn=fused_solve_tiled, n_iters=n_iters,
                           faithful_eq13_typo=faithful_eq13_typo)
    b = batch.mask.shape[0]
    sol = JointSolution(a=a, power=p,
                        objective=jax.vmap(WirelessFLProblem.objective)(problem, a),
                        n_iters=jnp.full((b,), n_iters, jnp.int32),
                        converged=jnp.ones((b,), bool))
    return _mask_solution(sol, batch.mask)
