"""``WirelessFLProblem.sanitize`` as one compiled program.

``sanitize()`` (with its default ``health_mask``) runs as a single
``jax.jit`` program over the problem's array leaves.  Each case compares
it with a plain NumPy reference written here — ``np.where`` over
``health_mask(xp=np)`` with the neutral fills — bit for bit, on single
``[N]`` and batched ``[B, N]`` / ``[B, N, K]`` problems, with and without
the optional ``fading`` / ``interference`` / ``bits`` leaves, on healthy
input and on input with NaN, negative and zero entries.
"""
import jax
import numpy as np
import pytest

from repro.core.problem import NEUTRAL_FILLS, WirelessFLProblem

N, B, K = 12, 3, 4
#: the optional leaves' fills, as docs/robustness.md states them
OPTIONAL_FILLS = {"fading": 1.0, "interference": 0.0, "bits": 32.0}
LAYOUTS = {
    # (per-device shape, optional-leaf shape)
    "single": ((N,), (N, K)),
    "batched": ((B, N), (B, N)),
    "batched_rounds": ((B, N), (B, N, K)),
}
OPTIONALS = {
    "none": (),
    "fading": ("fading",),
    "fading_interference": ("fading", "interference"),
    "fading_interference_bits": ("fading", "interference", "bits"),
}


def _problem(layout: str, optional: tuple, poisoned: bool, seed: int = 0):
    rng = np.random.default_rng(seed)
    dev_shape, opt_shape = LAYOUTS[layout]
    leaves = {name: rng.uniform(0.5, 2.0, dev_shape).astype(np.float32)
              for name in NEUTRAL_FILLS}
    opt = {
        "fading": rng.uniform(0.1, 2.0, opt_shape).astype(np.float32),
        "interference": rng.uniform(0.0, 1e-12, opt_shape).astype(np.float32),
        "bits": rng.choice([4.0, 8.0, 16.0, 32.0],
                           opt_shape).astype(np.float32),
    }
    leaves.update({name: opt[name] for name in optional})
    if poisoned:
        row = (1,) if layout == "single" else (1, 2)
        leaves["distance_m"][row] = np.nan
        leaves["energy_budget_j"][row[:-1] + (4,)] = -1.0
        leaves["bandwidth_hz"][row[:-1] + (7,)] = 0.0
        for name, bad in (("fading", 0.0), ("interference", -1e-13),
                          ("bits", np.nan)):
            if name in optional:
                x = leaves[name]
                idx = row[:-1] + (9,) + (0,) * (x.ndim - len(dev_shape))
                x[idx] = bad
    return WirelessFLProblem(**leaves)


def _reference(prob: WirelessFLProblem):
    health = np.asarray(prob.health_mask(xp=np))
    out = {}
    for name, fill in NEUTRAL_FILLS.items():
        x = np.asarray(getattr(prob, name))
        out[name] = np.where(health, x, np.asarray(fill, x.dtype))
    rank = np.asarray(prob.distance_m).ndim
    for name, fill in OPTIONAL_FILLS.items():
        x = getattr(prob, name)
        if x is not None:
            x = np.asarray(x)
            h = health[..., None] if x.ndim > rank else health
            out[name] = np.where(h, x, np.asarray(fill, x.dtype))
    return out, health


def _assert_bitwise(got, want, name):
    got = np.asarray(got)
    assert got.dtype == want.dtype and got.shape == want.shape, name
    assert got.tobytes() == want.tobytes(), name


@pytest.mark.parametrize("poisoned", [False, True],
                         ids=["healthy", "poisoned"])
@pytest.mark.parametrize("optional", list(OPTIONALS))
@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_compiled_sanitize_matches_numpy_reference(layout, optional,
                                                   poisoned):
    prob = _problem(layout, OPTIONALS[optional], poisoned)
    want, want_health = _reference(prob)
    clean, health = prob.sanitize()
    np.testing.assert_array_equal(np.asarray(health), want_health)
    assert bool(want_health.all()) != poisoned
    for name, x in want.items():
        _assert_bitwise(getattr(clean, name), x, name)
    for name in set(OPTIONAL_FILLS) - set(want):
        assert getattr(clean, name) is None
    # the static constants pass through untouched
    assert clean.tau_th == prob.tau_th and clean.p_max == prob.p_max


@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_explicit_host_health_gives_the_same_problem(layout):
    prob = _problem(layout, OPTIONALS["fading_interference_bits"], True)
    health = prob.health_mask(xp=np)
    by_default, _ = prob.sanitize()
    given, returned = prob.sanitize(health=health)
    np.testing.assert_array_equal(np.asarray(returned), health)
    for name in list(NEUTRAL_FILLS) + list(OPTIONAL_FILLS):
        _assert_bitwise(getattr(given, name),
                        np.asarray(getattr(by_default, name)), name)


def test_sanitize_is_one_program():
    prob = _problem("batched_rounds", OPTIONALS["fading_interference_bits"],
                    False)
    eqns = jax.make_jaxpr(lambda p: p.sanitize())(prob).jaxpr.eqns
    assert len(eqns) == 1 and eqns[0].primitive.name in ("pjit", "jit")
