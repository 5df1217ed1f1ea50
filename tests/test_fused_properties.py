"""Property-based tests (hypothesis): the fused single-level solver is
``solve_joint`` — same a*, P* and objective to <= 1e-5 — across random
feasible problems including fading, ragged stacked batches with padded
slots self-deselecting, and chunked == unchunked solves."""
import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.core import (
    sample_problem,
    solve_joint,
    solve_joint_batch,
    solve_joint_fused,
    stack_problems,
)
from repro.core.power import power_agreement_tol

TOL = 1e-5


def assert_agrees(fused, ref, *, tol=TOL):
    np.testing.assert_allclose(np.asarray(fused.a), np.asarray(ref.a),
                               atol=tol, rtol=0)
    np.testing.assert_allclose(np.asarray(fused.power), np.asarray(ref.power),
                               atol=tol, rtol=tol)
    np.testing.assert_allclose(float(fused.objective), float(ref.objective),
                               atol=tol, rtol=0)


def _problem(seed, n, tau, pmax, fading):
    return sample_problem(seed, n, tau_th=tau, p_max=pmax,
                          with_fading=fading, n_rounds=3 if fading else 1)


# n is drawn from a tiny set so jax's shape-keyed compilation cache is
# reused across hypothesis examples (arbitrary n => a recompile per example).
problem_strategy = st.builds(
    _problem,
    seed=st.integers(0, 2**31 - 1),
    n=st.sampled_from([8, 32]),
    tau=st.floats(0.01, 2.0),
    pmax=st.floats(0.05, 10.0),
    fading=st.booleans(),
)


@settings(max_examples=25, deadline=None)
@given(problem_strategy)
def test_fused_matches_solve_joint(problem):
    assert_agrees(solve_joint_fused(problem), solve_joint(problem))


@settings(max_examples=10, deadline=None)
@given(st.lists(st.tuples(st.integers(0, 2**31 - 1),
                          st.sampled_from([8, 16, 24])),
                min_size=2, max_size=5))
def test_fused_batch_ragged_property(specs):
    probs = [sample_problem(seed, n) for seed, n in specs]
    batch = stack_problems(probs)
    sol = solve_joint_batch(batch, method="fused")
    for b, prob in enumerate(probs):
        assert_agrees(sol.instance(b), solve_joint(prob))
    # padded slots self-deselect
    pad = ~np.asarray(batch.mask)
    assert np.all(np.asarray(sol.a)[pad] == 0.0)
    assert np.all(np.asarray(sol.power)[pad] == 0.0)


@settings(max_examples=15, deadline=None)
@given(problem_strategy, st.sampled_from([32, 100, 4096]))
def test_fused_chunked_matches_unchunked(problem, chunk):
    ref = solve_joint_fused(problem)
    sol = solve_joint_fused(problem, chunk_elements=chunk)
    np.testing.assert_allclose(np.asarray(sol.a), np.asarray(ref.a),
                               atol=1e-6, rtol=0)
    # two XLA programs of one fixed point: P^min = expm1(x)/pg amplifies
    # f32 rounding of x about x-fold (power_agreement_tol derives it)
    tol = power_agreement_tol(problem, ref.a, ref.power)
    assert np.all(np.abs(np.asarray(sol.power, np.float64)
                         - np.asarray(ref.power, np.float64)) <= tol)
