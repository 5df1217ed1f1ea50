"""Batched multi-scenario engine: solve_joint_batch must agree with a
python loop of per-instance solves, through ragged padding, fading, the
kernel fast path, and the scenario registry."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    SCENARIOS,
    ProbabilisticScheduler,
    make_batch,
    make_mixed_batch,
    make_problem,
    pad_batch,
    sample_problem,
    solve_joint,
    solve_joint_batch,
    solve_joint_optimal,
    stack_problems,
)

OBJ_TOL = 1e-5


def _assert_matches_loop(batch, problems, *, method="alternating"):
    sol = solve_joint_batch(batch, method=method)
    ref_solver = solve_joint_optimal if method != "alternating" else solve_joint
    for b, prob in enumerate(problems):
        ref = ref_solver(prob)
        assert abs(float(sol.objective[b]) - float(ref.objective)) <= OBJ_TOL, \
            f"instance {b}: batched {float(sol.objective[b])} " \
            f"vs loop {float(ref.objective)}"
        inst = sol.instance(b)
        assert inst.a.shape == ref.a.shape
        assert bool(prob.constraints_satisfied(inst.a, inst.power,
                                               rtol=1e-3).all()), \
            f"instance {b}: batched solution infeasible"
    return sol


class TestStacking:
    def test_ragged_roundtrip(self):
        probs = [sample_problem(i, n) for i, n in enumerate([8, 16, 12])]
        batch = stack_problems(probs)
        assert batch.batch_size == 3 and batch.n_max == 16
        assert np.array_equal(np.asarray(batch.fleet_sizes), [8, 16, 12])
        assert int(batch.mask.sum()) == 8 + 16 + 12
        for orig, back in zip(probs, batch.unstack()):
            assert back.n_devices == orig.n_devices
            for f in ("distance_m", "bandwidth_hz", "energy_budget_j",
                      "weights"):
                np.testing.assert_allclose(np.asarray(getattr(back, f)),
                                           np.asarray(getattr(orig, f)))

    def test_static_mismatch_rejected(self):
        a = sample_problem(0, 8)
        b = dataclasses.replace(a, tau_th=0.5)
        with pytest.raises(ValueError, match="tau_th"):
            stack_problems([a, b])

    def test_mixed_fading_rejected(self):
        # a non-fading instance solves one [N] round, a fading one [N, K];
        # mixing would silently K-multiply the former's objective
        a = sample_problem(0, 8, with_fading=True, n_rounds=3)
        b = sample_problem(1, 8, n_rounds=3)
        with pytest.raises(ValueError, match="all-or-none"):
            stack_problems([a, b])
        # explicit unit fading opts a static-channel instance in
        c = dataclasses.replace(b, fading=jnp.ones((8, 3), jnp.float32))
        batch = stack_problems([a, c])
        assert batch.problem.fading.shape == (2, 8, 3)
        np.testing.assert_allclose(np.asarray(batch.problem.fading[1]), 1.0)

    @pytest.mark.parametrize("case, slots", [
        ("static", (4, 16)),          # ragged, padding on both axes
        ("static", (3, 16)),          # padding on the device axis only
        ("static", (5, 12)),          # padding on the batch axis only
        ("static", (None, None)),     # no padding at all
        ("fading", (4, 16)),
        ("interference_n", (4, 16)),
        ("interference_nk", (4, 16)),
        ("bits", (4, 16)),
        ("host_f64", (4, 16)),        # numpy float64 leaves, as clients send
    ])
    def test_slot_shape_matches_pad_batch(self, case, slots):
        """Packing at slot shape in one pass is ``pad_batch`` of the
        natural stack, bit for bit, in dtype, shape and weak type."""
        sizes = [5, 12, 9]
        fading = case in ("fading", "interference_nk")
        probs = [sample_problem(i, n, with_fading=fading, n_rounds=3)
                 for i, n in enumerate(sizes)]
        rng = np.random.default_rng(0)
        if case == "interference_n":
            probs = [dataclasses.replace(p, interference=jnp.asarray(
                rng.uniform(0, 1e-12, n), jnp.float32))
                for p, n in zip(probs, sizes)]
        elif case == "interference_nk":
            probs = [dataclasses.replace(p, interference=jnp.asarray(
                rng.uniform(0, 1e-12, (n, 3)), jnp.float32))
                for p, n in zip(probs, sizes)]
        elif case == "bits":
            probs = [dataclasses.replace(p, bits=jnp.asarray(
                rng.choice([8.0, 16.0, 32.0], n), jnp.float32))
                for p, n in zip(probs, sizes)]
        elif case == "host_f64":
            probs = [dataclasses.replace(p, **{
                f: np.asarray(getattr(p, f), np.float64)
                for f in ("distance_m", "bandwidth_hz", "energy_budget_j",
                          "weights")}, fading=rng.uniform(0.1, 2.0, (n, 1)),
                n_rounds=1) for p, n in zip(probs, sizes)]
        bsz, nmx = slots
        got = stack_problems(probs, batch_size=bsz, n_max=nmx)
        want = stack_problems(probs)
        if slots != (None, None):
            want = pad_batch(want, batch_size=bsz, n_max=nmx)
        assert jax.tree.structure(got) == jax.tree.structure(want)
        for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            assert isinstance(g, jax.Array)
            assert (g.dtype, g.shape, g.weak_type) == \
                (w.dtype, w.shape, w.weak_type)
            assert np.asarray(g).tobytes() == np.asarray(w).tobytes()
        assert got.batch_size == (bsz or 3) and got.n_max == (nmx or 12)

    def test_slot_shape_cannot_shrink(self):
        probs = [sample_problem(i, n) for i, n in enumerate([5, 12])]
        with pytest.raises(ValueError, match="cannot shrink"):
            stack_problems(probs, batch_size=1)
        with pytest.raises(ValueError, match="cannot shrink"):
            stack_problems(probs, n_max=8)


class TestBatchAgreement:
    def test_ragged_alternating(self):
        probs = [sample_problem(i, n) for i, n in enumerate([8, 24, 16, 24])]
        _assert_matches_loop(stack_problems(probs), probs)

    def test_ragged_optimal(self):
        probs = [sample_problem(i, n) for i, n in enumerate([8, 24, 16])]
        _assert_matches_loop(stack_problems(probs), probs, method="optimal")

    def test_kernel_fast_path(self):
        probs = [sample_problem(i, n) for i, n in enumerate([8, 24, 16])]
        _assert_matches_loop(stack_problems(probs), probs, method="kernel")

    def test_64_instances(self):
        # the acceptance-scale check: >= 64 stacked scenarios, |dobj| <= 1e-5
        probs = [sample_problem(i, 16) for i in range(64)]
        sol = _assert_matches_loop(stack_problems(probs), probs)
        assert sol.a.shape == (64, 16)
        assert bool(sol.converged.all())

    def test_fading_batch(self):
        probs = [sample_problem(i, 10, with_fading=True, n_rounds=4)
                 for i in range(4)]
        sol = _assert_matches_loop(stack_problems(probs), probs)
        assert sol.a.shape == (4, 10, 4)

    def test_padding_inert(self):
        # padded slots must come back a = power = 0 and never participate
        probs = [sample_problem(i, n) for i, n in enumerate([4, 32])]
        batch = stack_problems(probs)
        sol = solve_joint_batch(batch)
        pad = ~np.asarray(batch.mask)
        assert np.all(np.asarray(sol.a)[pad] == 0.0)
        assert np.all(np.asarray(sol.power)[pad] == 0.0)


class TestScenarios:
    @pytest.mark.parametrize("name", sorted(SCENARIOS))
    def test_registry_builds_and_solves(self, name):
        from repro.core.multicell import MultiCellProblem, solve_coupled

        # small fleets keep CI fast; every scenario accepts n_devices
        prob = make_problem(name, seed=0, n_devices=16)
        if isinstance(prob, MultiCellProblem):
            # multi-cell entries solve through the coupled loop
            # (tests/test_multicell.py has the full contract)
            sol = solve_coupled(make_problem(name, seed=0, n_cells=2,
                                             n_devices=16))
            assert sol.converged
            assert float(jnp.sum(sol.batch.objective)) >= 0.0
            return
        sol = solve_joint(prob)
        assert bool(prob.constraints_satisfied(sol.a, sol.power,
                                               rtol=1e-3).all())
        assert float(sol.objective) >= 0.0

    def test_make_batch(self):
        batch = make_batch("sparse_energy_starved", 6, seed=0, n_devices=12)
        assert batch.batch_size == 6 and batch.n_max == 12
        sol = solve_joint_batch(batch)
        assert sol.objective.shape == (6,)

    def test_mixed_batch_ragged(self):
        batch = make_mixed_batch(
            ["paper_static", "sparse_energy_starved"], seed=0)
        assert batch.n_max == 100
        sol = solve_joint_batch(batch)
        assert bool(jnp.all(sol.objective > 0))

    def test_unknown_scenario(self):
        with pytest.raises(KeyError, match="unknown scenario"):
            make_problem("nope")


class TestSchedulerBatch:
    def test_precompute_and_sample_batch(self):
        batch = make_batch("paper_static", 4, seed=0, n_devices=16)
        sched = ProbabilisticScheduler()
        state = sched.precompute_batch(batch)
        assert state.a.shape == (4, 16)
        np.testing.assert_allclose(np.asarray(state.agg_weights.sum(1)),
                                   1.0, rtol=1e-5)
        draw = sched.sample_batch(state, jax.random.PRNGKey(0))
        assert draw.mask.shape == (4, 16)
        assert draw.mask.dtype == jnp.bool_
        # each instance matches the per-problem precompute
        for b, prob in enumerate(batch.unstack()):
            ref = sched.precompute(prob)
            np.testing.assert_allclose(np.asarray(state.a[b]),
                                       np.asarray(ref.a), atol=1e-5)
