"""The 3GPP hexagonal metro (``hex_coupling``) and the solved-at estimate.

* **layout** — ``hex_coupling`` on ``R`` rings has ``3 (1 + 3R(R+1))``
  cells, a zero diagonal and finite, non-negative gains; the UMa NLOS
  path loss and the sector pattern give the TR 38.901 numbers worked
  out by hand, and at ``R = 1`` a back-lobe entry (every point 30 dB
  down) and a main-lobe entry (every point within 65 degrees of
  boresight) equal the formula evaluated by hand on the same points; the
  whole matrix equals the one the benchmark's plain reference builds
  from the configuration file's constants, and another sector's points,
  a transposed matrix or a bfloat16 one do not.
* **reference** — ``FleetControlService.solve_coupled`` on a seeded
  ``R = 1`` metro (21 cells x 16 devices) agrees with the benchmark's
  plain float64 reference (``coupled_reference``) at the estimate each
  tick was solved at.
* **solved at** — ``MultiCellSolution.solved_at`` is the estimate the
  returned ``batch`` was solved at, on the converged path and on the
  iteration-cap path: a fresh solve there gives ``batch`` bit for bit.
"""
import dataclasses
import importlib.util
import json
from pathlib import Path

import numpy as np
import pytest

from repro.core import make_problem
from repro.core.batch import solve_joint_batch, stack_problems
from repro.core.multicell import (
    _backhaul_project,
    _masked_weights,
    _with_interference,
    hex_coupling,
    hex_sites,
    make_multicell,
    sector_pattern_db,
    sector_points,
    solve_coupled,
    uma_nlos_path_loss_db,
)
from repro.core.problem import WirelessFLProblem
from repro.serve import FleetControlService, ServiceConfig

CHIP = Path(__file__).resolve().parents[1] / "benchmarks" / "chip"


def _load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _load(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------- layout

@pytest.mark.parametrize("rings", [0, 1, 2, 3])
def test_site_count(rings):
    sites = hex_sites(rings, 500.0)
    assert len(sites) == 1 + 3 * rings * (rings + 1)
    # distinct, and every site but the centre has a neighbour 500 m away
    d = np.hypot(*(sites[:, None] - sites[None]).transpose(2, 0, 1))
    np.fill_diagonal(d, np.inf)
    if rings:
        np.testing.assert_allclose(d.min(axis=1), 500.0)


@pytest.mark.parametrize("rings", [0, 1, 2])
def test_coupling_shape_diagonal_and_sign(rings):
    g = hex_coupling(rings, 500.0, n_points=16, seed=3)
    c = 3 * (1 + 3 * rings * (rings + 1))
    assert g.shape == (c, c)
    assert np.all(np.diag(g) == 0.0)
    off = g[~np.eye(c, dtype=bool)]
    assert np.all(np.isfinite(off)) and np.all(off > 0.0)
    # accepted by the metro's own validation
    cells = [make_problem("paper_static", seed=s, n_devices=4)
             for s in range(c)]
    assert make_multicell(cells, g).n_cells == c


def test_path_loss_and_pattern_by_hand():
    # PL' = 13.54 + 39.08 log10(500) + 20 log10(3.5) - 0.6 (1.5 - 1.5)
    #     = 13.54 + 105.4757478 + 10.8813609 = 129.8971087 dB
    assert uma_nlos_path_loss_db(500.0, 3.5) == pytest.approx(129.8971087,
                                                               abs=1e-6)
    # about 1e-13 W/W at 500 m
    assert 10 ** (-uma_nlos_path_loss_db(500.0, 3.5) / 10) == \
        pytest.approx(1.02397e-13, rel=1e-4)
    # on boresight 0 dB; the 3 dB point at half of phi_3dB = 65 deg;
    # -12 dB at 65 deg; the back lobe held at A_m = 30 dB
    assert sector_pattern_db(0.0) == 0.0
    assert sector_pattern_db(32.5) == pytest.approx(-3.0)
    assert sector_pattern_db(-65.0) == pytest.approx(-12.0)
    assert sector_pattern_db(180.0) == -30.0
    assert sector_pattern_db(-150.0) == -30.0
    # bearings wrap: a point at -90 deg lies on the 270 deg boresight
    assert sector_pattern_db(-90.0 - 270.0) == 0.0


def _by_hand(pts, site, bore_deg, fc_ghz=3.5):
    """G entry of one receiving sector from the drawn points, written
    out from TR 38.901: d2D >= 35 m, d3D with 25 - 1.5 m of height,
    PL' of Table 7.4.1-1, A(phi) of Table 7.3-1."""
    rel = pts - site
    d2d = np.maximum(np.hypot(rel[:, 0], rel[:, 1]), 35.0)
    d3d = np.sqrt(d2d ** 2 + 23.5 ** 2)
    pl = 13.54 + 39.08 * np.log10(d3d) + 20.0 * np.log10(fc_ghz)
    phi = (np.degrees(np.arctan2(rel[:, 1], rel[:, 0])) - bore_deg
           + 180.0) % 360.0 - 180.0
    a = -np.minimum(12.0 * (phi / 65.0) ** 2, 30.0)
    return float(np.mean(10.0 ** ((a - pl) / 10.0))), a


def test_back_lobe_and_boresight_entries_by_hand():
    seed, n_points = 11, 64
    g = hex_coupling(1, 500.0, n_points=n_points, seed=seed)
    pts = sector_points(1, 500.0, n_points, seed)
    sites = hex_sites(1, 500.0)
    centre = int(np.argmin(np.hypot(sites[:, 0], sites[:, 1])))
    bores = (30.0, 150.0, 270.0)
    back = front = None
    for k, bore in enumerate(bores):
        c = 3 * centre + k
        for c2 in range(len(pts)):
            if c2 // 3 == centre:
                continue
            value, a = _by_hand(pts[c2], sites[centre], bore)
            if back is None and np.all(a == -30.0):
                back = (c, c2, value)
            if front is None and np.all(a > -12.0):
                front = (c, c2, value)
    assert back is not None and front is not None
    for c, c2, value in (back, front):
        assert g[c, c2] == pytest.approx(value, rel=1e-12)
    # a sector facing away hears the same points 30 dB down
    c, c2, _ = back
    rel = pts[c2] - sites[centre]
    d3d = np.sqrt(np.maximum(np.hypot(rel[:, 0], rel[:, 1]), 35.0) ** 2
                  + 23.5 ** 2)
    iso = np.mean(10.0 ** (-uma_nlos_path_loss_db(d3d, 3.5) / 10.0))
    assert g[c, c2] == pytest.approx(1e-3 * iso, rel=1e-12)
    # same seed, same matrix; another seed, other points
    np.testing.assert_array_equal(
        g, hex_coupling(1, 500.0, n_points=n_points, seed=seed))
    assert not np.array_equal(
        g, hex_coupling(1, 500.0, n_points=n_points, seed=seed + 1))


@pytest.mark.parametrize("rings, seed", [(0, 0), (1, 11), (2, 2**40 + 3)])
def test_coupling_matches_the_benchmark_reference(rings, seed):
    ref = _load(CHIP / "configs" / "coupled_reference.py",
                "hex_test_coupled_reference")
    cfg = dict(_load_json(CHIP / "configs" / "metro_uma_hex.json"),
               rings=rings)
    want = ref.layout_coupling(cfg, seed)
    got = hex_coupling(rings, cfg["isd_m"], n_points=cfg["coupling_points"],
                       seed=seed, fc_ghz=cfg["fc_ghz"])
    assert ref.coupling_gap(got, want) <= 1e-12
    np.testing.assert_array_equal(ref.sites(rings, 500.0),
                                  hex_sites(rings, 500.0))
    limit = cfg["limits"]["coupling_rel_gap"]
    assert ref.coupling_gap(got.T, want) > limit
    assert ref.coupling_gap(
        hex_coupling(rings, cfg["isd_m"], n_points=cfg["coupling_points"],
                     seed=seed + 1, fc_ghz=cfg["fc_ghz"]), want) > limit
    assert ref.coupling_gap(ref.layout_coupling(cfg, seed, dtype="bfloat16"),
                            want) > 100 * limit


# ------------------------------------------------------------- reference

@pytest.fixture(scope="module")
def seeded_metro():
    """A seeded R = 1 metro: 21 cells x 16 devices, the paper's cell, two
    rounds of drifting gains, the benchmark's budget rule."""
    gen = _load(CHIP / "gen.py", "metro_test_gen")
    ref = _load(CHIP / "configs" / "coupled_reference.py",
                "metro_test_coupled_reference")
    cfg = dict(
        area_m=1000.0, total_bandwidth_hz=1e7, devices_per_cell=16,
        energy_budget_range_j=[1e-3, 100.0], dataset_total=21 * 16 * 600,
        cycles_per_sample_range=[1e4, 5e4], cpu_hz_range=[5e8, 2e9],
        grad_size_bits=6374720.0, noise_power_w=1e-12, p_max_w=1.0,
        tau_th_s=0.08, kappa=1e-28)
    c, n = 21, 16
    rng = gen.rng_for(2**31 + 5, 2)
    dev = {k: v.reshape(c, n) for k, v in gen.devices(cfg, rng,
                                                      c * n).items()}
    gains = gen.gauss_markov_gains(rng, (c, n), 2, 0.9)
    statics = gen.statics(cfg)
    coupling = hex_coupling(1, 500.0, n_points=64, seed=7)
    budget = 0.6 * 2.1 * c * statics["grad_size_bits"]
    return gen, ref, dev, gains, statics, coupling, budget


def test_service_tick_matches_coupled_reference(seeded_metro):
    _, ref, dev, gains, statics, coupling, budget = seeded_metro
    c, n = dev["weights"].shape
    cells = stack_problems([
        WirelessFLProblem(**{k: v[i] for k, v in dev.items()}, fading=None,
                          n_rounds=1, **statics) for i in range(c)])
    metro = make_multicell(cells, coupling, backhaul_bits=budget)
    svc = FleetControlService(ServiceConfig())
    for k in range(gains.shape[-1]):            # a cold tick, a warm one
        problem = dataclasses.replace(cells.problem,
                                      fading=gains[:, :, k:k + 1])
        resp = svc.solve_coupled("m", dataclasses.replace(
            metro, cells=dataclasses.replace(cells, problem=problem)))
        sol = resp.solution
        assert resp.warm_started == (k > 0)
        assert sol.converged and not sol.hit_iter_cap
        a = np.asarray(sol.batch.a, np.float64)[:c, :n, 0]
        p = np.asarray(sol.batch.power, np.float64)[:c, :n, 0]
        at = np.asarray(sol.solved_at)[:c, 0]
        ra, rp, rmu, rload, _ = ref.solve(dev, gains[:, :, k], coupling,
                                          statics, budget, at)
        # the budget binds: one device is the knapsack's marginal one, and
        # its share carries the float32 rounding of every cap above it
        assert rmu > 0 and rload == pytest.approx(budget)
        gap = np.abs(a - ra)
        assert np.sort(gap.ravel())[-2] <= 1e-6 and gap.max() <= 1e-5
        np.testing.assert_allclose(p, rp, rtol=1e-5, atol=1e-12)
        assert float(np.ravel(sol.mu)[0]) == pytest.approx(rmu, rel=1e-6)
        assert a.sum() * statics["grad_size_bits"] <= budget * (1 + 1e-6)
        # the answers make an interference within the loop's tolerance
        # of the estimate they were solved at
        assert ref.relative_gap(at, ref.interference(coupling, a, p)) \
            <= 1.01e-3


def test_reference_in_bfloat16_is_far_off(seeded_metro):
    _, ref, dev, gains, statics, coupling, budget = seeded_metro
    at = ref.equilibrium(dev, gains[:, :, 0], coupling, statics, budget,
                         tol=1e-3, damping=0.5)
    ra, rp, _, _, nxt = ref.solve(dev, gains[:, :, 0], coupling, statics,
                                  budget, at)
    assert ref.relative_gap(at, nxt) <= 1e-3
    ba, bp, _, _, _ = ref.solve(dev, gains[:, :, 0], coupling, statics,
                                budget, at, dtype="bfloat16")
    assert np.max(np.abs(np.asarray(ba, np.float64) - ra)) > 1e-3


# --------------------------------------------------------------- sanitize

def _seeded_metro_tick(seeded_metro, k=0):
    _, _, dev, gains, statics, coupling, budget = seeded_metro
    c, n = dev["weights"].shape
    cells = stack_problems([
        WirelessFLProblem(**{key: v[i] for key, v in dev.items()},
                          fading=None, n_rounds=1, **statics)
        for i in range(c)])
    cells = dataclasses.replace(cells, problem=dataclasses.replace(
        cells.problem, fading=gains[:, :, k:k + 1]))
    return make_multicell(cells, coupling, backhaul_bits=budget)


def test_sanitize_is_bitwise_identity_on_a_healthy_metro(seeded_metro):
    metro = _seeded_metro_tick(seeded_metro)
    plain = solve_coupled(metro, sanitize=False)
    hardened = solve_coupled(metro, sanitize=True)
    for name in ("a", "power"):
        got = np.asarray(getattr(hardened.batch, name))
        want = np.asarray(getattr(plain.batch, name))
        assert got.tobytes() == want.tobytes(), name
    assert np.asarray(hardened.mu).tobytes() == np.asarray(plain.mu).tobytes()
    assert hardened.solved_at.tobytes() == plain.solved_at.tobytes()
    assert hardened.outer_iters == plain.outer_iters


def test_sanitize_deselects_a_poisoned_device(seeded_metro):
    metro = _seeded_metro_tick(seeded_metro)
    fading = np.array(metro.cells.problem.fading)
    fading[3, 5, 0] = np.nan
    cells = dataclasses.replace(metro.cells, problem=dataclasses.replace(
        metro.cells.problem, fading=fading))
    sol = solve_coupled(dataclasses.replace(metro, cells=cells),
                        sanitize=True)
    a = np.asarray(sol.batch.a)
    p = np.asarray(sol.batch.power)
    assert a[3, 5, 0] == 0.0 and p[3, 5, 0] == 0.0
    assert np.all(np.isfinite(a)) and np.all(np.isfinite(p))
    assert np.all(np.isfinite(sol.solved_at))
    assert np.all(np.isfinite(sol.interference))
    assert np.all(np.isfinite(np.asarray(sol.mu)))
    # the other devices are still served
    assert np.count_nonzero(a) > 0


def test_second_service_tick_compiles_nothing(seeded_metro):
    from repro.analysis.recompile import CompileBudget

    svc = FleetControlService(ServiceConfig())
    svc.solve_coupled("m", _seeded_metro_tick(seeded_metro, 0))
    with CompileBudget(None) as budget:
        resp = svc.solve_coupled("m", _seeded_metro_tick(seeded_metro, 1))
    assert resp.warm_started
    assert budget.count == 0, budget.names


# -------------------------------------------------------------- solved at

@pytest.fixture(scope="module")
def budget_metro():
    return make_problem("metro_coupled", seed=0, n_cells=4, n_devices=24)


@pytest.mark.parametrize("outer_iters", [40, 2], ids=["converged", "cap"])
def test_solved_at_is_the_estimate_batch_was_solved_at(budget_metro,
                                                       outer_iters):
    mc = budget_metro
    sol = solve_coupled(mc, outer_iters=outer_iters)
    assert sol.converged == (outer_iters == 40)
    assert sol.hit_iter_cap == (outer_iters == 2)
    assert sol.solved_at.shape == sol.interference.shape
    bs = solve_joint_batch(_with_interference(mc.cells, sol.solved_at),
                           method="fused")
    a, mu, _ = _backhaul_project(np.asarray(bs.a), _masked_weights(mc.cells),
                                 mc.cells.problem.grad_size_bits,
                                 mc.backhaul_bits)
    np.testing.assert_array_equal(np.asarray(sol.batch.a),
                                  a.astype(np.float32))
    np.testing.assert_array_equal(np.asarray(sol.batch.power),
                                  np.asarray(bs.power))
    assert float(mu) == float(sol.mu)
    # ``interference`` is the estimate recomputed from ``batch``: within
    # the tolerance of ``solved_at`` once converged, far from it at a cap
    gap = (np.max(np.abs(sol.interference - sol.solved_at))
           / np.max(np.abs(sol.interference)))
    assert (gap <= 1e-3) == sol.converged


def test_solved_at_of_the_loop_reference(budget_metro):
    from repro.core.multicell import solve_coupled_loop

    sol = solve_coupled_loop(budget_metro)
    fast = solve_coupled(budget_metro)
    np.testing.assert_allclose(sol.solved_at, fast.solved_at, rtol=2e-3)
