"""The coupled metro cell (``metro_coupled_tick``), driven on the CPU.

At a size a test run holds (one ring of sites, 21 cells of 16 devices;
the layout, the budget rule and the loop settings as configured):

* a run with the program sound is correct, and a traced run reports each
  of the cell's eight per-layer metrics;
* the control, the plain reference in ``bfloat16`` in the program's
  place, is not correct on three seeds, and the reference in float64 in
  the same place is;
* a run whose timed path is broken underneath — half the cells' answers
  dropped, or one power altered where the solve produces it — is not
  correct;
* a program that builds another metro's ``G`` — transposed, without the
  sector pattern, with the boresights mirrored, or from other points —
  is not correct: the reference builds ``G`` itself from the
  configuration file;
* the readers give nothing on a program without the spans or counters.
"""
from __future__ import annotations

import math
import sys
from pathlib import Path
from types import SimpleNamespace as NS

import jax
import numpy as np
import pytest

CHIP = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(CHIP))
sys.path.insert(0, str(CHIP.parents[1] / "src"))

import bench  # noqa: E402
import control  # noqa: E402
import reduce  # noqa: E402

CELL = "metro_coupled_tick"
SEEDS = (2**31 + 11, 7, 2**40 + 3)
SMALL = ({"rings": 1, "devices_per_cell": 16}, {"sample_below": 4})
READERS = [m["name"] for m in bench.load_json(bench.SPEC_PATH)["per_layer"]
           if m["name"].endswith(".metro")]


def _run(seed=SEEDS[0], trace=False):
    cfg, traffic = SMALL
    result, facts = bench.run(CELL, seed, 1.0, trace, chip=False,
                              config_overrides=cfg,
                              traffic_overrides=traffic)
    return result, facts


def test_eight_readers():
    assert len(READERS) == 8


def test_sound_run_is_correct():
    result, facts = _run()
    assert result["correct"], result["compared"]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert facts["ticks_checked"][0] == 0 and len(facts["ticks_checked"]) >= 2
    assert facts["counters"]["metro_ticks"] == result["attempted"]
    # every tick was warm: the duals came from the service's cache
    assert facts["counters"]["metro_warm"] == result["attempted"]


def test_traced_run_reports_every_reader():
    result, _ = _run(trace=True)
    assert result["correct"], result["compared"]
    assert set(result["metrics"]) == set(READERS)
    for metric in READERS:
        value = result["metrics"][metric]["value"]
        assert math.isfinite(value) and value > 0, metric


@pytest.mark.parametrize("seed", SEEDS)
def test_control_is_not_correct(seed):
    cfg, traffic = SMALL
    correct, compared = control.control_run(
        CELL, seed, 1.0, "bfloat16", config_overrides=cfg,
        traffic_overrides=traffic)
    assert not correct, compared
    correct, compared = control.control_run(
        CELL, seed, 1.0, "float64", config_overrides=cfg,
        traffic_overrides=traffic)
    assert correct, compared
    assert compared["a_gap"]["value"] == 0.0
    assert compared["coupling_rel_gap"]["value"] == 0.0


@pytest.mark.parametrize("seed", SEEDS)
def test_control_coupling_is_not_correct(seed):
    """The reference's own ``G`` in bfloat16 fails the coupling check
    alone, by far."""
    cfg, traffic = SMALL
    _, compared = control.control_run(
        CELL, seed, 1.0, "bfloat16", config_overrides=cfg,
        traffic_overrides=traffic)
    gap = compared["coupling_rel_gap"]
    assert gap["value"] > 100 * gap["limit"], compared


def _drop_half(sol):
    """Half of the cells' caps left out (zeroed)."""
    return sol._replace(a=sol.a.at[: sol.a.shape[0] // 2].set(0.0))


def _alter_power(sol):
    """The largest power 1% high, where the solve produces it."""
    p = sol.power
    return sol._replace(power=jax.numpy.where(p == p.max(), p * 1.01, p))


@pytest.mark.parametrize("fault", [_drop_half, _alter_power])
def test_answer_broken_where_produced(monkeypatch, fault):
    from repro.core import multicell

    solve = multicell.solve_joint_batch

    def broken(*a, **kw):
        return fault(solve(*a, **kw))

    monkeypatch.setattr(multicell, "solve_joint_batch", broken)
    result, _ = _run()
    assert not result["correct"], result["compared"]


def _transposed(multicell, monkeypatch):
    build = multicell.hex_coupling
    monkeypatch.setattr(multicell, "hex_coupling",
                        lambda *a, **kw: build(*a, **kw).T)


def _no_pattern(multicell, monkeypatch):
    monkeypatch.setattr(multicell, "sector_pattern_db",
                        lambda phi: 0.0 * np.asarray(phi, np.float64))


def _mirrored_boresights(multicell, monkeypatch):
    monkeypatch.setattr(multicell, "SECTOR_BORESIGHTS_DEG",
                        (-30.0, -150.0, -270.0))


def _other_points(multicell, monkeypatch):
    build = multicell.hex_coupling
    monkeypatch.setattr(multicell, "hex_coupling",
                        lambda *a, seed, **kw: build(*a, seed=seed + 1, **kw))


@pytest.mark.parametrize("fault", [_transposed, _no_pattern,
                                   _mirrored_boresights, _other_points])
def test_another_metro_is_not_correct(monkeypatch, fault):
    from repro.core import multicell

    fault(multicell, monkeypatch)
    result, _ = _run()
    assert not result["correct"], result["compared"]
    gap = result["compared"]["coupling_rel_gap"]
    assert gap["value"] > gap["limit"], result["compared"]


def test_nothing_to_read_without_the_spans_or_the_counters():
    view = reduce.RunView(NS(config={}, traffic={}), {"counters": {}},
                          reduce.Reduction(), bench.device_info(1))
    for metric in READERS:
        if metric.startswith("device_idle_share"):
            continue               # the device trace is always there
        value = bench.load_module(CHIP / "metrics" / f"{metric}.py",
                                  f"metric_{metric}").read(view)
        assert value is None, metric
