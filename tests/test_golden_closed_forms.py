"""Golden-value regression tests for the paper's closed forms.

The eq.-10 power update (``analytic_power_elements`` — the closed-form
optimum Algorithm 1 converges to), the eq.-13 selection update
(``selection_update_elements``), and the helpers they share are pinned
against *hand-computed* oracle numbers for a tiny N=3 element set, so a
future refactor cannot silently drift the formulas.  Every expected value
below is derived in the comment next to it from the paper equations with
calculator-friendly constants — none is a recorded output of the code
under test.

Constants used throughout: S = 100 bits, tau = 1 s, P^max = 1 W.
"""
import numpy as np
import pytest

from repro.core.power import (
    analytic_power_elements,
    dinkelbach_power_elements,
    element_p_min,
    element_tx_time,
    element_warm_lambda,
    energy_gate_elements,
)
from repro.core.selection import selection_update_elements

S_BITS, TAU, P_MAX = 100.0, 1.0, 1.0

# three regimes of the power subproblem (9):
#   el0 interior:  a=0.5, pg=3, B=100
#       exponent  = a S / (B tau) = 0.5
#       P^min     = (2^0.5 - 1) / 3          = 0.13807118745769837
#       P*        = P^min  (< P^max, feasible)
#       rate(P*)  = B log2(1 + P* pg) = 100 * 0.5 = 50 bit/s
#       T(P*)     = S / rate = 2 s  (= tau / a, by construction of P^min)
#       lam       = a P* T = 0.5 * 0.13807... * 2 = 0.13807118745769837 J
#   el1 clipped:   a=1, pg=1, B=10
#       exponent  = 10,  P^min = 2^10 - 1 = 1023  > P^max  -> infeasible
#       P*        = P^max = 1
#       T(P*)     = 100 / (10 * log2 2) = 10 s
#       lam       = 1 * 1 * 10 = 10 J
#   el2 deselected: a=0 -> P^min = 0, P* = 0, lam = 0 (rate(0) = 0)
A = np.array([0.5, 1.0, 0.0], np.float32)
PG = np.array([3.0, 1.0, 2.0], np.float32)
BW = np.array([100.0, 10.0, 50.0], np.float32)

P_MIN_GOLD = [0.13807118745769837, 1023.0, 0.0]
P_GOLD = [0.13807118745769837, 1.0, 0.0]
LAM_GOLD = [0.13807118745769837, 10.0, 0.0]
FEAS_GOLD = [True, False, True]


class TestPowerClosedForm:
    def test_element_p_min(self):
        got = element_p_min(A, PG, BW, s_bits=S_BITS, tau=TAU)
        np.testing.assert_allclose(np.asarray(got), P_MIN_GOLD, rtol=1e-5)

    def test_p_min_exponent_clamp(self):
        # a S / (B tau) = 200 clamps to 120: finite, astronomically
        # infeasible rather than NaN/inf
        got = np.asarray(element_p_min(
            np.float32(2.0), np.float32(1.0), np.float32(1.0),
            s_bits=S_BITS, tau=TAU))
        assert np.isfinite(got)
        np.testing.assert_allclose(got, 2.0 ** 120, rtol=1e-5)

    def test_element_tx_time(self):
        # P=3, pg=1: rate = 25 * log2(4) = 50 bit/s, T = 100/50 = 2 s
        got = element_tx_time(np.float32(3.0), np.float32(1.0),
                              np.float32(25.0), s_bits=S_BITS)
        np.testing.assert_allclose(np.asarray(got), 2.0, rtol=1e-6)

    def test_analytic_power_elements(self):
        p, lam, feas = analytic_power_elements(
            A, PG, BW, s_bits=S_BITS, tau=TAU, p_max=P_MAX)
        np.testing.assert_allclose(np.asarray(p), P_GOLD, rtol=1e-5)
        np.testing.assert_allclose(np.asarray(lam), LAM_GOLD, rtol=1e-5)
        np.testing.assert_array_equal(np.asarray(feas), FEAS_GOLD)

    def test_dinkelbach_converges_to_golden(self):
        """Algorithm 1 must land on the same closed-form numbers."""
        p, lam, iters, feas = dinkelbach_power_elements(
            A, PG, BW, s_bits=S_BITS, tau=TAU, p_max=P_MAX)
        np.testing.assert_allclose(np.asarray(p), P_GOLD, rtol=1e-4)
        np.testing.assert_allclose(np.asarray(lam), LAM_GOLD, rtol=1e-4)
        np.testing.assert_array_equal(np.asarray(feas), FEAS_GOLD)
        assert 0 < int(iters) <= 64

    def test_warm_lambda_seed(self):
        # seed = a P T at the given state; invalid states fall back to
        # the cold constant 1e-3
        lam0 = element_warm_lambda(A, np.asarray(P_GOLD, np.float32),
                                   PG, BW, s_bits=S_BITS)
        np.testing.assert_allclose(np.asarray(lam0)[:2], LAM_GOLD[:2],
                                   rtol=1e-5)
        assert float(np.asarray(lam0)[2]) == pytest.approx(1e-3)


class TestSelectionClosedForm:
    # a* = min(1, tau / T, E^max / (P T + E^c)) per eq. (13), corrected
    #   time-binding:   P=0.5, T=4,   E^max=10,  E^c=1
    #                   -> min(1, 0.25, 10/3)         = 0.25
    #   energy-binding: P=1,   T=0.5, E^max=0.3, E^c=0.1
    #                   -> min(1, 2, 0.3/0.6)         = 0.5
    #   capped:         P=0.1, T=0.1, E^max=100, E^c=1
    #                   -> min(1, 10, 100/1.01)       = 1.0
    #   zero power:     P=0 transmits nothing         -> 0.0
    P = np.array([0.5, 1.0, 0.1, 0.0], np.float32)
    T = np.array([4.0, 0.5, 0.1, 1.0], np.float32)
    EMAX = np.array([10.0, 0.3, 100.0, 1.0], np.float32)
    EC = np.array([1.0, 0.1, 1.0, 0.1], np.float32)
    A_GOLD = [0.25, 0.5, 1.0, 0.0]

    def test_selection_update_elements(self):
        got = selection_update_elements(self.P, self.T, self.EMAX, self.EC,
                                        tau=TAU, s_bits=S_BITS)
        np.testing.assert_allclose(np.asarray(got), self.A_GOLD, rtol=1e-6)

    def test_faithful_typo_divides_time_term_by_s(self):
        # the verbatim paper formula prints tau / (S T): the time-binding
        # element drops to 0.25/100 = 0.0025; the energy-bound and capped
        # elements re-bind accordingly: min(1, 2/100, 0.5) = 0.02,
        # min(1, 10/100, 99.0099) = 0.1
        got = selection_update_elements(self.P, self.T, self.EMAX, self.EC,
                                        tau=TAU, s_bits=S_BITS,
                                        faithful_eq13_typo=True)
        np.testing.assert_allclose(np.asarray(got),
                                   [0.0025, 0.02, 0.1, 0.0], rtol=1e-6)


class TestEnergyGate:
    def test_eq10_gate(self):
        # H = E^max - a E^c; gate is lam <= H (+1e-9 tolerance):
        #   (a=0.5, E^max=1, E^c=1) -> H = 0.5
        a = np.full(3, 0.5, np.float32)
        emax = np.ones(3, np.float32)
        ec = np.ones(3, np.float32)
        lam = np.array([0.2, 0.6, 0.5], np.float32)
        np.testing.assert_array_equal(
            np.asarray(energy_gate_elements(a, lam, emax, ec)),
            [True, False, True])


class TestPowerAgreementTol:
    """``power_agreement_tol`` on the three hand-computed elements above:
    x = a S ln2 / (B tau) and kappa(x) = x e^x / expm1(x).
      el0  x = 0.5 ln2 = 0.34657359,  kappa = 0.34657359 * 1.41421356
                                              / 0.41421356 = 1.18328...
      el1  x = 10 ln2 = 6.93147181,   kappa = 6.93147181 * 1024 / 1023
                                            = 6.93824743...
      el2  a = 0: kappa = 1 and P = 0, so only atol remains."""

    KAPPA = np.array([1.1832840, 6.9382474, 1.0])

    def _problem(self):
        import dataclasses

        import jax.numpy as jnp

        from repro.core.problem import sample_problem

        return dataclasses.replace(sample_problem(0, 3),
                                   bandwidth_hz=jnp.asarray(BW),
                                   grad_size_bits=S_BITS, tau_th=TAU)

    def test_rounding_term(self):
        from repro.core.power import power_agreement_tol

        got = power_agreement_tol(self._problem(), A, P_GOLD)
        eps = np.finfo(np.float32).eps
        want = 1e-6 + 4 * eps * self.KAPPA * np.asarray(P_GOLD)
        np.testing.assert_allclose(got, want, rtol=1e-6)

    def test_selection_gap_term(self):
        from repro.core.power import power_agreement_tol

        a_other = A * np.float32(1.0 + 1e-3)
        base = power_agreement_tol(self._problem(), A, P_GOLD)
        got = power_agreement_tol(self._problem(), A, P_GOLD,
                                  a_other=a_other)
        rel = np.where(A > 0, (a_other - A) / np.maximum(a_other, 1e-30), 0)
        np.testing.assert_allclose(got - base,
                                   self.KAPPA * rel * np.asarray(P_GOLD),
                                   rtol=1e-5, atol=1e-12)
