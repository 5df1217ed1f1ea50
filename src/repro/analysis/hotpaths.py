"""Registered hot paths and their compile budgets.

Each entry names a production entry point and knows how to build a
self-contained workload for it: a ``warmup()`` thunk that pays every
expected trace/compile once, and a ``steady()`` thunk that re-runs the
path on *fresh same-shaped inputs* — the state a serving process lives
in.  ``measure()`` wraps both in :class:`~repro.analysis.recompile.
CompileBudget` scopes; the steady-state counts are compared against the
committed ``analysis/budgets.json`` by ``tools/run_analysis.py --gate``
(and by the slow-tier service test).

Registering a new hot path::

    @register_hot_path("my_path", doc="one-line contract")
    def _build_my_path() -> HotPathRun:
        ...build inputs eagerly here (outside the measured scopes)...
        return HotPathRun(warmup=..., steady=...)

The builder runs eagerly *before* either measured scope, so input
construction (device puts, tiny eager ops) never pollutes the counts.
Budgets are steady-state only: warmup compile counts vary with jax
version and backend and are reported, not gated.
"""
from __future__ import annotations

import dataclasses
import functools
import json
import pathlib
from typing import Callable, Optional

import numpy as np

import jax

from repro.analysis.recompile import CompileBudget

__all__ = [
    "HOT_PATHS",
    "HotPath",
    "HotPathRun",
    "default_budgets_path",
    "load_budgets",
    "measure",
    "measure_all",
    "register_hot_path",
]


@dataclasses.dataclass
class HotPathRun:
    """Built workload: warmup pays the compiles, steady must not."""

    warmup: Callable[[], None]
    steady: Callable[[], None]


@dataclasses.dataclass(frozen=True)
class HotPath:
    name: str
    doc: str
    build: Callable[[], HotPathRun]


HOT_PATHS: dict[str, HotPath] = {}


def register_hot_path(name: str, *, doc: str):
    """Decorator registering a hot-path builder under ``name``."""
    def wrap(build: Callable[[], HotPathRun]):
        HOT_PATHS[name] = HotPath(name=name, doc=doc, build=build)
        return build
    return wrap


def measure(name: str) -> dict:
    """Build + run one hot path; returns warmup/steady compile counts."""
    hp = HOT_PATHS[name]
    run = hp.build()
    with CompileBudget(budget=None, strict=False,
                       name=f"{name}:warmup") as warm:
        run.warmup()
    with CompileBudget(budget=None, strict=False,
                       name=f"{name}:steady") as steady:
        run.steady()
    return {
        "doc": hp.doc,
        "warmup_compiles": warm.count,
        "steady_compiles": steady.count,
        "steady_programs": steady.names,
    }


def measure_all(names: Optional[list[str]] = None) -> dict[str, dict]:
    return {name: measure(name) for name in (names or sorted(HOT_PATHS))}


def default_budgets_path() -> pathlib.Path:
    """``analysis/budgets.json`` at the repo root (three levels up from
    this file: src/repro/analysis -> repo)."""
    return (pathlib.Path(__file__).resolve().parents[3]
            / "analysis" / "budgets.json")


def load_budgets(path: Optional[pathlib.Path] = None) -> dict[str, int]:
    with open(path or default_budgets_path()) as fh:
        data = json.load(fh)
    return {k: int(v) for k, v in data["steady_state_compiles"].items()}


# --------------------------------------------------------------------------
# the registered production hot paths
# --------------------------------------------------------------------------

def _two_problems(n: int):
    from repro.core.problem import sample_problem
    return sample_problem(0, n), sample_problem(1, n)


@register_hot_path(
    "solve_joint_fused",
    doc="jitted fused Algorithm-2 solve; zero recompiles across fresh "
        "same-shaped problems (the PR-7 eager-while_loop regression)")
def _build_solve_joint_fused() -> HotPathRun:
    from repro.core.alternating import solve_joint_fused

    prob_a, prob_b = _two_problems(32)
    fn = jax.jit(functools.partial(solve_joint_fused, eps=1e-6,
                                   max_iters=40))

    def warmup():
        jax.block_until_ready(fn(prob_a).a)

    def steady():
        jax.block_until_ready(fn(prob_b).a)

    return HotPathRun(warmup=warmup, steady=steady)


@register_hot_path(
    "solve_joint_batch",
    doc="batched fused solve (the service's _solve payload); zero "
        "recompiles for a fixed (batch, bucket) signature")
def _build_solve_joint_batch() -> HotPathRun:
    from repro.core.batch import solve_joint_batch, stack_problems
    from repro.core.problem import sample_problem

    def batch(seed0: int):
        probs = [sample_problem(seed0 + i, 16 + 4 * i) for i in range(3)]
        return stack_problems(probs, batch_size=4, n_max=32)

    batch_a, batch_b = batch(0), batch(10)

    def warmup():
        jax.block_until_ready(solve_joint_batch(batch_a, method="fused").a)

    def steady():
        jax.block_until_ready(solve_joint_batch(batch_b, method="fused").a)

    return HotPathRun(warmup=warmup, steady=steady)


@register_hot_path(
    "fleet_service_step",
    doc="FleetControlService.step after warmup(): the first live request "
        "and every later one must hit precompiled programs only")
def _build_fleet_service_step() -> HotPathRun:
    from repro.core.problem import sample_problem
    from repro.serve.fleet_service import FleetControlService, ServiceConfig

    service = FleetControlService(ServiceConfig(cost_smoothing=0.0))
    template = sample_problem(0, 24)
    # fresh per-cell problems for two steady rounds: round 2 exercises the
    # warm-start (cached-seed) jit signature on the live path
    rounds = [[sample_problem(100 * r + c, 24) for c in range(3)]
              for r in range(2)]

    def warmup():
        service.warmup(template, max_devices=24)

    def steady():
        now = 0.0
        for round_problems in rounds:
            for c, prob in enumerate(round_problems):
                now += 1e-4
                service.submit(f"cell-{c}", prob, now=now)
            service.step(now=now)

    return HotPathRun(warmup=warmup, steady=steady)


def _build_sweep_inputs(*, uplink_bits: Optional[int], seeds: list[int],
                        aggregate: str):
    """Stacked plans + datasets + params for a tiny scan-engine sweep."""
    from repro.core.problem import sample_problem
    from repro.core.schedulers import UniformScheduler
    from repro.data.synthetic import make_dataset
    from repro.fl.engine import FLConfig
    from repro.fl.scan_engine import (init_sweep_params, plan_trajectory,
                                      stack_plans)

    n, n_rounds = 6, 3
    problem = sample_problem(0, n)
    scheduler = UniformScheduler(m=2)
    train = make_dataset(48, seed=0)
    test = make_dataset(16, seed=1)
    parts = np.array_split(np.arange(48), n)
    configs = [FLConfig(n_rounds=n_rounds, batch_per_client=2, eval_every=2,
                        aggregate=aggregate, uplink_bits=uplink_bits,
                        seed=s) for s in seeds]
    plans = stack_plans([plan_trajectory(problem, scheduler, parts, c)
                         for c in configs])
    params = init_sweep_params(configs)
    return plans, train, test, configs[0], params


@register_hot_path(
    "scan_engine_sweep",
    doc="stacked-trajectory FL sweep: one program per static config; "
        "fresh same-shaped plans reuse it with zero recompiles")
def _build_scan_engine_sweep() -> HotPathRun:
    from repro.fl.scan_engine import run_fl_sweep

    plans_a, train, test, config, params = _build_sweep_inputs(
        uplink_bits=None, seeds=[0, 1], aggregate="fused")
    plans_b, _, _, _, params_b = _build_sweep_inputs(
        uplink_bits=None, seeds=[2, 3], aggregate="fused")

    def warmup():
        run_fl_sweep(plans_a, train, test, config, params, shard=False)

    def steady():
        run_fl_sweep(plans_b, train, test, config, params_b, shard=False)

    return HotPathRun(warmup=warmup, steady=steady)


@register_hot_path(
    "scan_engine_strategies",
    doc="scheduler strategy is plan *data*, not a jit-static: bernoulli/"
        "fixed/uniform trajectories share one program per bucket")
def _build_scan_engine_strategies() -> HotPathRun:
    from repro.core.problem import sample_problem
    from repro.core.schedulers import (DeterministicScheduler,
                                       ProbabilisticScheduler,
                                       UniformScheduler)
    from repro.data.synthetic import make_dataset
    from repro.fl.engine import FLConfig
    from repro.fl.scan_engine import (init_sweep_params, plan_trajectory,
                                      run_fl_sweep, stack_plans)

    n, n_rounds = 6, 3
    problem = sample_problem(0, n)
    train = make_dataset(48, seed=0)
    test = make_dataset(16, seed=1)
    parts = np.array_split(np.arange(48), n)
    config = FLConfig(n_rounds=n_rounds, batch_per_client=2, eval_every=2)

    def stacked(scheduler):
        plan = plan_trajectory(problem, scheduler, parts, config)
        return stack_plans([plan]), init_sweep_params([config])

    warm_inputs = stacked(UniformScheduler(m=2))
    steady_inputs = [stacked(s) for s in (ProbabilisticScheduler(),
                                          DeterministicScheduler())]

    def warmup():
        plans, params = warm_inputs
        run_fl_sweep(plans, train, test, config, params, shard=False)

    def steady():
        for plans, params in steady_inputs:
            run_fl_sweep(plans, train, test, config, params, shard=False)

    return HotPathRun(warmup=warmup, steady=steady)
