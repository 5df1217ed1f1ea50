"""Static-analysis gate (``repro.analysis``): every pass proves it
*catches* a planted defect (positive fixtures) and stays quiet on
clean/production code (negative fixtures).

Tier-1 keeps the fixtures tiny; the production-scale sweeps (all hot
paths, all PRNG programs, the full rank sweep) run in the CI
``analysis`` job via ``tools/run_analysis.py --gate`` and in the slow
tier here."""
import textwrap

import pytest

import jax
import jax.numpy as jnp

from repro.analysis import (
    HOT_PATHS,
    PRNG_PROGRAMS,
    CompileBudget,
    CompileBudgetExceeded,
    broadcastable_leaves,
    check_key_reuse,
    load_budgets,
    measure,
    sweep_rank_contract,
    weak_scalar_findings,
)
from repro.analysis.hygiene import WAIVER, check_donation, scan_host_syncs
from repro.core.problem import WirelessFLProblem


# ------------------------------------------------------------ recompile

class TestCompileBudget:
    def test_counts_fresh_compile(self):
        """Positive: a jit signature never seen before must be counted.
        Inputs are built *outside* the scope (eager ``jnp.ones`` itself
        compiles tiny programs); an odd prime size keeps the signature
        unique to this test."""
        fn = jax.jit(lambda x: x * 2.0 + 1.0)
        x = jnp.ones((173,))
        with CompileBudget(budget=None, strict=False) as cb:
            fn(x).block_until_ready()
        assert cb.count == 1

    def test_budget_zero_raises_and_names_program(self):
        def distinctly_named_program(x):
            return x - 3.0

        fn = jax.jit(distinctly_named_program)
        x = jnp.ones((179,))
        with pytest.raises(CompileBudgetExceeded) as ei, \
                CompileBudget(budget=0, name="steady"):
            fn(x).block_until_ready()
        assert "steady" in str(ei.value)
        # program names are best-effort (parsed from jax debug logs)
        assert "distinctly_named_program" in str(ei.value)

    def test_cache_hit_is_zero(self):
        """Negative: re-running a compiled signature on fresh same-shaped
        inputs is free — the steady-state contract."""
        fn = jax.jit(lambda x: jnp.sum(x * x))
        # explicit dtype: jnp.full with a bare python fill value is
        # weak-typed, which would fork the signature vs jnp.ones — the
        # very hazard the hygiene pass audits
        a, b = jnp.ones((181,)), jnp.full((181,), 2.0, dtype=jnp.float32)
        fn(a).block_until_ready()
        with CompileBudget(budget=0, name="cache hit"):
            fn(b).block_until_ready()

    def test_does_not_swallow_body_exception(self):
        x = jnp.ones((191,))
        with pytest.raises(ValueError, match="from body"), \
                CompileBudget(budget=0):
            jax.jit(lambda x: x @ x)(x).block_until_ready()
            raise ValueError("from body")

    def test_global_log_is_monotonic(self):
        """Nested budgets both see a compile in the inner block, and a
        budget stops counting once its block has exited."""
        x = jnp.ones((193,))
        with CompileBudget(budget=None) as outer:
            with CompileBudget(budget=None) as inner:
                jax.jit(lambda x: x + 5)(x).block_until_ready()
        jax.jit(lambda x: x + 7)(x).block_until_ready()
        assert outer.count >= inner.count >= 1
        assert inner.count == 1

    def test_budgets_file_covers_every_hot_path(self):
        budgets = load_budgets()
        assert set(budgets) == set(HOT_PATHS)
        assert all(v == 0 for v in budgets.values()), \
            "non-zero steady-state budgets need a justification comment"

    @pytest.mark.slow
    @pytest.mark.parametrize("name", sorted(HOT_PATHS))
    def test_hot_path_steady_state(self, name):
        """Every registered production hot path meets its committed
        budget (the same check the CI analysis job gates)."""
        result = measure(name)
        assert result["steady_compiles"] <= load_budgets()[name], result


# ------------------------------------------------------------------ prng

def _consume(key, shape=()):
    return jax.random.uniform(key, shape)


class TestKeyReuse:
    def test_flags_double_consumption(self):
        """Positive: the same key drawn twice."""
        def bad(key):
            return _consume(key) + _consume(key)

        findings = check_key_reuse(bad, jax.random.PRNGKey(0))
        assert len(findings) == 1
        assert findings[0].n_consumed == 2
        assert findings[0].kind == "reuse"

    def test_split_is_clean(self):
        def good(key):
            k1, k2 = jax.random.split(key)
            return _consume(k1) + _consume(k2)

        assert check_key_reuse(good, jax.random.PRNGKey(0)) == []

    def test_fold_in_collision_flagged_distinct_clean(self):
        def collide(key):
            return (_consume(jax.random.fold_in(key, 7))
                    + _consume(jax.random.fold_in(key, 7)))

        def distinct(key):
            return (_consume(jax.random.fold_in(key, 7))
                    + _consume(jax.random.fold_in(key, 8)))

        assert len(check_key_reuse(collide, jax.random.PRNGKey(0))) == 1
        assert check_key_reuse(distinct, jax.random.PRNGKey(0)) == []

    def test_scan_carry_reuse_flagged(self):
        """Positive: a scan body that consumes its key carry but threads
        it through unchanged reuses it every iteration."""
        def bad_scan(key):
            def body(k, _):
                return k, _consume(k)
            return jax.lax.scan(body, key, jnp.arange(4.0))

        findings = check_key_reuse(bad_scan, jax.random.PRNGKey(0))
        assert any(f.kind == "carry-reuse" for f in findings)

    def test_scan_split_carry_clean(self):
        def good_scan(key):
            def body(k, _):
                k, sub = jax.random.split(k)
                return k, _consume(sub)
            return jax.lax.scan(body, key, jnp.arange(4.0))

        assert check_key_reuse(good_scan, jax.random.PRNGKey(0)) == []

    def test_exclusive_branches_clean(self):
        """cond branches are exclusive: one key consumed in both arms is
        still consumed once per execution."""
        def branchy(key, flag):
            return jax.lax.cond(flag, _consume, lambda k: _consume(k) * 2.0,
                                key)

        assert check_key_reuse(branchy, jax.random.PRNGKey(0),
                               jnp.bool_(True)) == []

    def test_vmapped_split_children_distinct(self):
        """Regression: under vmap the split axis is not axis 0; children
        must still get distinct classes."""
        def vm(keys):
            def one(key):
                k1, k2 = jax.random.split(key)
                return _consume(k1) + _consume(k2)
            return jax.vmap(one)(keys)

        keys = jax.random.split(jax.random.PRNGKey(0), 3)
        assert check_key_reuse(vm, keys) == []

    def test_mask_stream_program_clean(self):
        """Negative (production): the planner's mask preview."""
        assert PRNG_PROGRAMS["mask_stream"]() == []

    @pytest.mark.slow
    @pytest.mark.parametrize("name", sorted(PRNG_PROGRAMS))
    def test_production_programs_clean(self, name):
        assert PRNG_PROGRAMS[name]() == []


# ------------------------------------------------------------------ rank

class _OldPathGainBug(WirelessFLProblem):
    """The pre-fix ``path_gain``: base lifted to ``[:, None]`` whenever
    fading is present, so a rank-1 fading silently builds [N, N]."""

    def path_gain(self):
        if self.fading is None or self.interference is not None:
            return super().path_gain()
        base = 1.0 / (jnp.square(self.distance_m) * self.noise_power)
        return jnp.where(self.fading > 0, self.fading * base[:, None], 0.0)


class _DropsRoundAxisBug(WirelessFLProblem):
    """A method that collapses the round axis of a rank-2 result."""

    def rate(self, power):
        r = super().rate(power)
        return r[:, 0] if r.ndim == 2 else r


class _WrongColumnBug(WirelessFLProblem):
    """Right shape, wrong values: every round repeats column 0 — only
    the bitwise per-column check can see this."""

    def rate(self, power):
        r = super().rate(power)
        return jnp.broadcast_to(r[:, :1], r.shape) if r.ndim == 2 else r


class TestRankContract:
    def test_discovers_all_leaves(self):
        assert set(broadcastable_leaves()) >= {"fading", "interference",
                                               "bits"}

    def test_requires_n_neq_k(self):
        with pytest.raises(ValueError, match="n != k"):
            sweep_rank_contract(n=3, k=3)

    def test_flags_rank1_fading_shape_bug(self):
        """Positive: the exact defect this pass surfaced on its first
        run against the real ``problem.py`` (fixed in this PR)."""
        findings, _ = sweep_rank_contract(
            _OldPathGainBug, methods={"path_gain": ((), "elementwise")})
        assert any(f.kind == "shape" and "(3, 3)" in f.detail
                   for f in findings)

    def test_flags_collapsed_round_axis(self):
        findings, _ = sweep_rank_contract(
            _DropsRoundAxisBug, methods={"rate": (("power",), "elementwise")})
        assert any(f.kind == "shape" for f in findings)

    def test_flags_wrong_column_values(self):
        findings, _ = sweep_rank_contract(
            _WrongColumnBug, methods={"rate": (("power",), "elementwise")})
        assert any(f.kind == "columns" for f in findings)

    def test_clean_on_fixed_library_subset(self):
        """Negative (tier-1 sized): the methods the PR fixed."""
        findings, stats = sweep_rank_contract(methods={
            "path_gain": ((), "elementwise"),
            "tx_time": (("power",), "elementwise"),
            "p_min": (("a",), "elementwise"),
        })
        assert findings == []
        assert stats["n_combos"] > 100

    @pytest.mark.slow
    def test_full_sweep_clean(self):
        findings, stats = sweep_rank_contract()
        assert findings == [], "\n".join(str(f) for f in findings)
        assert stats["n_combos"] == 486


# --------------------------------------------------------------- hygiene

_BAD_MODULE = textwrap.dedent(f"""
    import jax
    import numpy as np

    @jax.jit
    def jitted(x):
        y = float(x)
        z = np.asarray(x)
        waived = x.sum().item()  # {WAIVER}
        return y + z + waived

    def scan_body(c, x):
        return c + x.item(), None

    def run(xs):
        return jax.lax.scan(scan_body, 0.0, xs)

    def untraced(x):
        return float(x)
""")


class TestHostSyncScan:
    @pytest.fixture()
    def bad_tree(self, tmp_path):
        (tmp_path / "mod.py").write_text(_BAD_MODULE)
        return tmp_path

    def test_flags_syncs_in_traced_contexts(self, bad_tree):
        findings, stats = scan_host_syncs(bad_tree)
        details = [f.detail for f in findings]
        assert stats["traced_functions"] == 2  # jitted + scan_body
        assert sum("float()" in d for d in details) == 1
        assert sum("np.asarray" in d for d in details) == 1
        assert sum(".item()" in d for d in details) == 1  # scan_body only

    def test_waiver_and_untraced_are_quiet(self, bad_tree):
        findings, _ = scan_host_syncs(bad_tree)
        src_lines = _BAD_MODULE.splitlines()
        flagged = [src_lines[int(f.site.rsplit(":", 1)[1]) - 1]
                   for f in findings]
        assert not any(WAIVER in line for line in flagged)
        assert not any("untraced" in line for line in flagged)

    def test_production_tree_clean(self):
        findings, stats = scan_host_syncs()
        assert findings == [], "\n".join(str(f) for f in findings)
        assert stats["traced_functions"] > 20


class TestWeakTypeAudit:
    def test_flags_strong_scalar_leaf(self):
        findings = weak_scalar_findings(
            {"lr": jnp.float32(0.1)}, program="fixture")
        assert len(findings) == 1
        assert findings[0].kind == "weak-type"

    def test_quiet_on_weak_and_nonscalar(self):
        clean = {"lr": 0.1, "n": 7, "arr": jnp.ones((3,)),
                 "key": jax.random.PRNGKey(0)}
        assert weak_scalar_findings(clean, program="fixture") == []


class TestDonationAudit:
    @pytest.mark.slow
    def test_sweep_donation_round_trips(self):
        findings, stats = check_donation()
        assert findings == [], "\n".join(str(f) for f in findings)
        assert stats["aliased_outputs"] == stats["params_leaves"] > 0
        assert stats["aliased_outputs_undonated"] == 0
