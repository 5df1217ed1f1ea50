"""The comparison that decides ``correct``, shown to fail where it must.

* The control — the plain reference computed one precision down
  (``bfloat16`` for the configurations' ``float32``) and put in the
  program's place — is called not correct, on three seeds, at sizes a
  test run holds.  ``control.py`` runs the same at the cells' own sizes.
* A run driven end to end on the CPU (the look for a chip skipped) with
  the timed path sound is correct; with the timed path broken
  underneath — half of a batch's answers left out, or one answer altered
  where the solve produces it — it is not.
"""
from __future__ import annotations

import sys
from pathlib import Path

import jax
import pytest

CHIP = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(CHIP))
sys.path.insert(0, str(CHIP.parents[1] / "src"))

import bench  # noqa: E402
import control  # noqa: E402

SEEDS = (2**31 + 11, 7, 2**40 + 3)
# sizes a test run holds; the widths (devices per cell, leaves) are kept
SMALL = {
    "metro_serve_bursty": ({"n_cells": 6}, {"burst_rate_hz": 80.0,
                                             "burst_len": 10, "idle_s": 0.1,
                                             "static_cells": 2}),
    "fleet_solve_1m": ({"n_cells": 200}, {"sample_below": 4}),
}


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("cell", sorted(SMALL))
def test_control_is_not_correct(cell, seed):
    cfg, traffic = SMALL[cell]
    correct, compared = control.control_run(
        cell, seed, 1.0, "bfloat16", config_overrides=cfg,
        traffic_overrides=traffic)
    assert not correct, compared
    # the reference in its own precision, in the same place, passes
    correct, compared = control.control_run(
        cell, seed, 1.0, "float64", config_overrides=cfg,
        traffic_overrides=traffic)
    assert correct, compared
    assert compared["a_gap"]["value"] == 0.0


def _run(cell, seed=SEEDS[0]):
    cfg, traffic = SMALL[cell]
    result, _ = bench.run(cell, seed, 1.0, False, chip=False,
                          config_overrides=cfg, traffic_overrides=traffic)
    return result


def _alter_one(a):
    """One answer changed where it is produced."""
    idx = (0,) * a.ndim
    return a.at[idx].add(1e-3)


def _drop_half(a):
    """Half of the answers left out (zeroed)."""
    return a.at[: a.shape[0] // 2].set(0.0)


@pytest.mark.parametrize("cell", sorted(SMALL))
def test_sound_run_is_correct(cell):
    result = _run(cell)
    assert result["correct"], result["compared"]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert list(result)[-1] == "compared"


def test_serve_half_the_batch_left_out(monkeypatch):
    from repro.serve import fleet_service

    serve = fleet_service.FleetControlService._serve

    def half(self, reqs, *a, **kw):
        return serve(self, reqs, *a, **kw)[: max(1, len(reqs) // 2)]

    monkeypatch.setattr(fleet_service.FleetControlService, "_serve", half)
    result = _run("metro_serve_bursty")
    assert not result["correct"]
    assert result["compared"]["unanswered"]["value"] > 0


@pytest.mark.parametrize("fault", [_alter_one, _drop_half])
def test_serve_answer_broken_where_produced(monkeypatch, fault):
    from repro.serve import fleet_service

    solve = fleet_service.solve_joint_batch

    def broken(*a, **kw):
        sol = solve(*a, **kw)
        return sol._replace(a=fault(sol.a))

    monkeypatch.setattr(fleet_service, "solve_joint_batch", broken)
    result = _run("metro_serve_bursty")
    assert not result["correct"]
    assert result["compared"]["a_gap"]["value"] > \
        result["compared"]["a_gap"]["limit"]


@pytest.mark.parametrize("fault", [_alter_one, _drop_half])
def test_fleet_answer_broken_where_produced(monkeypatch, fault):
    import repro.core

    solve = repro.core.solve_joint_fused

    def broken(*a, **kw):
        sol = solve(*a, **kw)
        return sol._replace(a=fault(sol.a))

    monkeypatch.setattr(repro.core, "solve_joint_fused", broken)
    jax.clear_caches()
    result = _run("fleet_solve_1m")
    assert not result["correct"]
    assert result["compared"]["a_gap"]["value"] > \
        result["compared"]["a_gap"]["limit"]

