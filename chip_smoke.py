"""Bring the control plane, the fleet solve and the FL loop up on a TPU.

    python chip_smoke.py             # one chip: every phase below
    python chip_smoke.py --chips 4   # four chips: the sharded paths only

Everything runs in this one process (a chip belongs to one process at a
time).  The one-chip phases drive the system through the entry points a
user calls, at full size, and check what comes out against the repo's
own references:

1. device      — JAX must find a TPU; prints its kind and count.
2. fleet solve — ``mega_fleet_100k`` (100 000 devices): the chunked XLA
                 fused solve and the compiled Pallas ``fused_kernel``
                 batch solve, both feasible and elementwise in agreement.
3. service     — ``FleetControlService`` warmed on a 100-device
                 ``drifting_metro`` cell, driven by a seeded Poisson trace
                 of 64 requests over 8 cells on the wall clock: every
                 request answered once, none shed, retried or unconverged,
                 and sampled answers equal to ``solve_joint``.
4. metro tick  — ``metro_coupled`` (16 cells x 64 devices) through
                 ``solve_coupled``, against ``solve_coupled_loop``.
5. training    — the closed loop with the paper's CNN, the compiled
                 ``masked_aggregate`` sweep against the jnp aggregate, and
                 a scan trajectory against ``run_fl``.

``--chips 4`` runs only the multi-chip paths and what they are compared
with: the element-sharded ``metro_1m_users`` solve against the one-chip
solve, and a 4-trajectory ``run_grid(engine="scan")`` sharded against
unsharded.

Any failed check raises, so the exit code is non-zero.  The last line of
standard output is ``{"ok": true, "device": {...}}``; earlier lines give
set-up and compile seconds as set-up facts, not as rates.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import functools
import json
import sys
import time
from pathlib import Path

import numpy as np

SRC = Path(__file__).resolve().parent / "src"

# f32 epsilon of a TPU matmul at default precision: operands are rounded
# to bf16 (8 significant bits) for one MXU pass.
BF16_EPS = 2.0 ** -8
# |a_1 - a_2| between two solves of one fixed point: the repo's
# fused-vs-reference and kernel-vs-XLA agreement contract
A_ATOL = 1e-5
# |acc_1 - acc_2| for two trainings that differ by rounding only: the
# repo's scan-vs-loop bound (tests/test_fl_scan.py) — a few borderline
# test images may flip
ACC_ATOL = 0.02


class SmokeFailure(RuntimeError):
    """A check of what the chip computed did not hold."""


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(msg, flush=True)


@contextlib.contextmanager
def phase(name: str):
    """Prints a phase's outcome and its wall seconds (set-up, compile and
    run together).  An exception propagates: a phase never passes by
    being caught."""
    t0 = time.perf_counter()
    log(f"[{name}] start")
    yield
    log(f"[{name}] ok  ({time.perf_counter() - t0:.1f} s set-up + compile "
        "+ run)")


# ------------------------------------------------------------ phase 1

def require_devices(n_chips: int) -> dict:
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU; JAX found platform "
                         f"{platform!r} ({len(devices)} device(s))")
    if len(devices) < n_chips:
        raise SystemExit(f"chip_smoke: --chips {n_chips} needs {n_chips} "
                         f"TPU devices; JAX found {len(devices)}")
    info = {"platform": platform, "kind": devices[0].device_kind,
            "count": len(devices)}
    log(f"device: platform={platform} kind={info['kind']} "
        f"count={info['count']}")
    return info


# ---------------------------------------------------------- helpers

def _np(x) -> np.ndarray:
    return np.asarray(x, np.float64)


def check_solution_agrees(problem, sol, ref, what: str) -> dict:
    """a within ``A_ATOL``; powers within ``power_agreement_tol`` — f32
    rounding and the two solves' difference in a, both amplified x-fold
    by P^min = expm1(x)/pg (core/power.py)."""
    from repro.core.power import power_agreement_tol

    da = np.abs(_np(sol.a) - _np(ref.a))
    dp = np.abs(_np(sol.power) - _np(ref.power))
    tol = power_agreement_tol(problem, _np(ref.a), _np(ref.power),
                              a_other=_np(sol.a))
    check(da.shape == dp.shape == tol.shape, f"{what}: shapes differ")
    facts = {"max_da": float(da.max()), "max_dp_over_tol": float(
        (dp / tol).max())}
    check(np.all(da <= A_ATOL), f"{what}: |da| {facts['max_da']:.3g} > "
          f"{A_ATOL}")
    check(np.all(dp <= tol), f"{what}: |dP| exceeds power_agreement_tol "
          f"(max ratio {facts['max_dp_over_tol']:.3g})")
    return facts


def params_rel_diff(params, ref, init) -> float:
    """||params - ref|| / ||ref - init||: the gap between two trainings
    relative to the training update itself."""
    import jax

    def norm2(tree):
        return sum(float(np.sum(np.square(_np(x))))
                   for x in jax.tree_util.tree_leaves(tree))

    diff = jax.tree_util.tree_map(lambda x, y: _np(x) - _np(y), params, ref)
    upd = jax.tree_util.tree_map(lambda x, y: _np(x) - _np(y), ref, init)
    return (norm2(diff) / max(norm2(upd), 1e-30)) ** 0.5


def check_params_finite(params, what: str) -> None:
    import jax

    check(all(np.all(np.isfinite(_np(x)))
              for x in jax.tree_util.tree_leaves(params)),
          f"{what}: non-finite parameters")


def check_training_agrees(params, ref, init, n_rounds: int, what: str,
                          acc=None, acc_ref=None) -> dict:
    """Two trainings that differ only in rounding: each round's update may
    move by one bf16 epsilon of its size (TPU matmuls at default
    precision), and ``n_rounds`` such rounds add up to first order —
    ``n_rounds * BF16_EPS`` of the update norm.  Precision is left at the
    default the system runs with."""
    check_params_finite(params, what)
    check_params_finite(ref, what)
    rel = params_rel_diff(params, ref, init)
    tol = n_rounds * BF16_EPS
    facts = {"params_rel_diff": rel, "params_rel_tol": tol}
    check(rel <= tol, f"{what}: params differ by {rel:.3g} of the update "
          f"(tolerance {tol:.3g})")
    if acc is not None:
        acc, acc_ref = _np(acc), _np(acc_ref)
        check(np.all(np.isfinite(acc)), f"{what}: non-finite accuracy")
        dacc = float(np.max(np.abs(acc - acc_ref)))
        facts["max_dacc"] = dacc
        check(dacc <= ACC_ATOL, f"{what}: accuracy differs by {dacc:.3g}")
    return facts


# ------------------------------------------------------------ phase 2

def phase_fleet_solve(n_devices: int = 100_000,
                      chunk_elements: int = 16_384) -> dict:
    import jax

    from repro.core import solve_joint_batch, solve_joint_fused, stack_problems
    from repro.core.scenarios import make_problem
    from repro.kernels.selection_solve.ops import (
        solve_joint_fused_kernel_batch)

    prob = make_problem("mega_fleet_100k", seed=0, n_devices=n_devices)
    t0 = time.perf_counter()
    xla = jax.jit(functools.partial(solve_joint_fused,
                                    chunk_elements=chunk_elements))(prob)
    jax.block_until_ready(xla.a)
    log(f"  fused XLA solve (chunk {chunk_elements}): compile + first run "
        f"{time.perf_counter() - t0:.2f} s")

    batch = stack_problems([prob])
    if jax.default_backend() == "tpu":
        # compiled, not interpreted: the Mosaic kernel is in the program
        hlo = solve_joint_fused_kernel_batch.lower(batch).as_text()
        check("tpu_custom_call" in hlo,
              "method='fused_kernel' did not lower to a compiled kernel")
    t0 = time.perf_counter()
    ker = solve_joint_batch(batch, method="fused_kernel").instance(0)
    jax.block_until_ready(ker.a)
    log(f"  fused Pallas kernel solve: compile + first run "
        f"{time.perf_counter() - t0:.2f} s")

    check(bool(np.all(np.asarray(xla.converged))),
          "fused XLA solve did not converge")
    facts = {}
    for name, sol in (("xla", xla), ("kernel", ker)):
        check(bool(np.all(np.asarray(prob.constraints_satisfied(
            sol.a, sol.power, rtol=1e-3)))), f"{name} solve infeasible")
        facts[f"E_participants_{name}"] = float(np.sum(_np(sol.a)))
    facts.update(check_solution_agrees(prob, ker, xla, "kernel vs XLA"))
    log(f"  E[participants]: XLA {facts['E_participants_xla']:.6f}  "
        f"kernel {facts['E_participants_kernel']:.6f}")
    return facts


# ------------------------------------------------------------ phase 3

def phase_service(n_devices: int = 100, n_cells: int = 8,
                  n_requests: int = 64, rate_hz: float = 200.0,
                  n_reference: int = 4) -> dict:
    import jax

    from repro.core import solve_joint
    from repro.core.scenarios import make_problem, slice_round
    from repro.serve import FleetControlService, ServiceConfig
    from repro.serve.load_gen import drive, make_cells, poisson_trace

    svc = FleetControlService(ServiceConfig())
    template = slice_round(make_problem("drifting_metro", seed=0,
                                        n_devices=n_devices), 0)
    t0 = time.perf_counter()
    buckets = svc.warmup(template)
    log(f"  warmup: {len(buckets)} buckets {sorted(buckets)} compiled in "
        f"{time.perf_counter() - t0:.2f} s")

    cells = make_cells(n_cells, n_devices=n_devices,
                       scenario="drifting_metro", seed=1)
    trace = poisson_trace(cells, rate_hz=rate_hz, n_requests=n_requests,
                          seed=0)
    report = drive(svc, trace, clock="wall")
    resp = report.responses
    seqs = sorted(r.seq for r in resp)
    # a fresh service numbers its submissions 1, 2, ... in trace order
    check(seqs == list(range(1, len(trace) + 1)),
          f"{len(resp)} responses for {len(trace)} requests, or a request "
          "answered twice")
    for r in resp:
        check(not r.shed, f"request {r.seq} was shed")
        check(not r.retried, f"request {r.seq} was retried")
        check(r.converged, f"request {r.seq} did not converge")
        check(r.n_unhealthy == 0, f"request {r.seq} had unhealthy devices")
        check(np.all(np.isfinite(_np(r.solution.a))), "non-finite answer")

    by_seq = {r.seq: r for r in resp}
    ref_solve = jax.jit(solve_joint)
    picks = np.linspace(1, len(trace), n_reference).round().astype(int)
    facts = {"n_responses": len(resp)}
    for seq in picks:
        arrival = trace[seq - 1]
        ref = ref_solve(arrival.problem)
        got = by_seq[seq].solution
        facts[f"ref_{seq}"] = check_solution_agrees(
            arrival.problem, got, ref, f"request {seq} vs solve_joint")
        check(abs(float(got.objective) - float(ref.objective)) <= A_ATOL,
              f"request {seq}: objective differs from solve_joint")
    s = svc.stats.summary()
    for counter in ("unconverged", "retries", "shed", "unhealthy_devices",
                    "breaker_opens"):
        check(s[counter] == 0, f"service counted {s[counter]} {counter}")
    log(f"  {len(resp)} responses in {s['batches']} batches (closed by "
        f"{s['closes']}), {len(picks)} checked against solve_joint")
    return facts


# ------------------------------------------------------------ phase 4

def phase_metro_tick(n_cells: int = 16, n_devices: int = 64) -> dict:
    from repro.core.multicell import solve_coupled_loop
    from repro.core.scenarios import make_problem
    from repro.serve import FleetControlService, ServiceConfig

    metro = make_problem("metro_coupled", seed=0, n_cells=n_cells,
                         n_devices=n_devices)
    svc = FleetControlService(ServiceConfig())
    t0 = time.perf_counter()
    resp = svc.solve_coupled("metro-0", metro)
    log(f"  coupled tick: {resp.solution.outer_iters} outer iterations, "
        f"compile + run {time.perf_counter() - t0:.2f} s")
    sol = resp.solution
    check(not sol.hit_iter_cap, "coupled tick hit its iteration cap")
    check(sol.converged, "coupled tick did not converge")
    ref = solve_coupled_loop(metro)
    check(ref.converged, "solve_coupled_loop did not converge")
    obj = _np(sol.batch.objective)[:n_cells]
    obj_ref = _np(ref.batch.objective)
    # per-cell weights sum to 1, so |d objective| <= max |da|
    dobj = float(np.max(np.abs(obj - obj_ref)))
    check(dobj <= A_ATOL, f"coupled objective differs by {dobj:.3g}")
    log(f"  metro objective {obj.sum():.6f} vs loop {obj_ref.sum():.6f}")
    return {"outer_iters": sol.outer_iters, "max_dobj": dobj}


# ------------------------------------------------------------ phase 5

def phase_training(n_devices: int = 100, n_rounds: int = 4,
                   n_train: int = 2048, n_test: int = 512) -> dict:
    import jax

    from repro.core import make_scheduler
    from repro.core.scenarios import make_problem
    from repro.data.partition import dirichlet_partition
    from repro.data.synthetic import make_mnist_like
    from repro.fl.closed_loop import ClosedLoopConfig, run_closed_loop_grid
    from repro.fl.engine import FLConfig, run_fl
    from repro.fl.scan_engine import (init_sweep_params, plan_trajectory,
                                      run_fl_scan, run_fl_sweep, stack_plans)

    facts: dict = {}
    t0 = time.perf_counter()
    grid = run_closed_loop_grid(
        ClosedLoopConfig(n_devices=n_devices, n_rounds=n_rounds,
                         eval_every=2, n_train=n_train, n_test=n_test),
        strategies=("probabilistic", "uniform", "joint_bits"))
    for name, row in grid["strategies"].items():
        check(all(np.isfinite(v) for v in row.values()),
              f"closed loop {name}: non-finite summary {row}")
        facts[f"closed_loop_{name}_acc"] = row["final_acc"]
    log(f"  closed loop ({n_rounds} rounds): "
        + " ".join(f"{k}={v['final_acc']:.3f}"
                   for k, v in grid["strategies"].items())
        + f"  ({time.perf_counter() - t0:.2f} s)")

    problem = make_problem("drifting_metro", seed=0, n_devices=n_devices,
                           n_rounds=n_rounds, tau_th=0.5)
    train, test = make_mnist_like(n_train, n_test, seed=0)
    parts = dirichlet_partition(train, n_devices, 0.3, seed=1)
    sch = make_scheduler("probabilistic")

    # compiled masked_aggregate kernel vs the jnp aggregate, same plan;
    # every donated call gets init params of its own
    cfg = FLConfig(n_rounds=n_rounds, eval_every=2, batch_per_client=8,
                   lr=0.1, aggregate="stacked", seed=3)
    plans = stack_plans([plan_trajectory(problem, sch, parts, cfg)])
    runs = {}
    for use_kernel in (True, False):
        t0 = time.perf_counter()
        runs[use_kernel] = run_fl_sweep(plans, train, test, cfg,
                                        init_sweep_params([cfg]),
                                        use_kernel=use_kernel)
        log(f"  sweep use_kernel={use_kernel}: "
            f"{time.perf_counter() - t0:.2f} s")
    init = init_sweep_params([cfg])
    facts["kernel_vs_jnp"] = check_training_agrees(
        runs[True].params, runs[False].params, init, n_rounds,
        "masked_aggregate kernel vs jnp",
        acc=runs[True].histories[0].eval_acc,
        acc_ref=runs[False].histories[0].eval_acc)

    # scan-fused trajectory vs the reference python-loop engine
    cfg = FLConfig(n_rounds=n_rounds, eval_every=2, batch_per_client=8,
                   lr=0.1, seed=5)
    t0 = time.perf_counter()
    scan = run_fl_scan(problem, sch, train, parts, test, cfg)
    ref = run_fl(problem, sch, train, parts, test, cfg)
    log(f"  scan vs run_fl: {time.perf_counter() - t0:.2f} s")
    hs, hr = scan.history, ref.history
    check(np.array_equal(hs.participants, hr.participants),
          "scan and run_fl drew different participants")
    check(np.allclose(hs.energy, hr.energy, rtol=1e-5, atol=1e-6),
          "scan and run_fl energy accounting differ")
    init = jax.tree_util.tree_map(lambda x: x[0], init_sweep_params([cfg]))
    facts["scan_vs_run_fl"] = check_training_agrees(
        scan.params, ref.params, init, n_rounds, "scan vs run_fl",
        acc=hs.eval_acc, acc_ref=hr.eval_acc)
    return facts


# ------------------------------------------------------- four chips

def _shard_devices(x) -> set:
    return {s.device for s in x.addressable_shards}


def phase_sharded_solve(n_chips: int, n_devices: int = 1_000_000,
                        chunk_elements: int = 131_072) -> dict:
    import jax

    from repro.core import solve_joint_fused
    from repro.core.scenarios import make_problem

    prob = make_problem("metro_1m_users", seed=0, n_devices=n_devices)
    sols, texts = {}, {}
    for shard in (True, False):
        fn = jax.jit(functools.partial(solve_joint_fused,
                                       chunk_elements=chunk_elements,
                                       shard=shard))
        t0 = time.perf_counter()
        texts[shard] = fn.lower(prob).compile().as_text()
        sols[shard] = fn(prob)
        jax.block_until_ready(sols[shard].a)
        log(f"  metro_1m_users shard={shard}: compile + first run "
            f"{time.perf_counter() - t0:.2f} s")
    # the sharded program splits every chunk across the chips: its
    # per-iteration convergence test is a cross-chip all-reduce
    check("all-reduce" in texts[True],
          "shard=True compiled no cross-chip collective")
    check("all-reduce" not in texts[False],
          "shard=False compiled a cross-chip collective")
    for shard, sol in sols.items():
        check(bool(np.all(np.asarray(sol.converged))),
              f"shard={shard} solve did not converge")
    devs = _shard_devices(sols[True].a)
    check(len(devs) == n_chips,
          f"sharded solve output on {len(devs)} device(s), not {n_chips}")
    facts = check_solution_agrees(prob, sols[True], sols[False],
                                  "sharded vs one-chip solve")
    facts["E_participants"] = float(np.sum(_np(sols[True].a)))
    log(f"  E[participants] sharded {facts['E_participants']:.3f}, "
        f"one chip {float(np.sum(_np(sols[False].a))):.3f}; "
        f"output shards on {len(devs)} devices")
    return facts


def phase_sharded_grid(n_chips: int, n_rounds: int = 4) -> dict:
    import jax

    from repro.fl.experiments import (MILD_BIAS, STRATEGIES,
                                      build_scenario_plans, run_grid)
    from repro.fl.scan_engine import (init_sweep_params, run_fl_sweep,
                                      stack_plans)

    # one run per strategy: len(STRATEGIES) == 4 trajectories
    spec = dataclasses.replace(MILD_BIAS, n_rounds=n_rounds, n_runs=1,
                               eval_every=2)
    t0 = time.perf_counter()
    grid = run_grid([spec], strategies=STRATEGIES, verbose=False,
                    engine="scan")
    log(f"  run_grid(engine='scan'), {len(STRATEGIES)} trajectories: "
        f"{time.perf_counter() - t0:.2f} s")

    plans, labels, configs, train, test = build_scenario_plans(
        spec, 0, STRATEGIES)
    stacked = stack_plans(plans)
    runs = {shard: run_fl_sweep(stacked, train, test, configs[0],
                                init_sweep_params(configs), shard=shard)
            for shard in (True, False)}
    leaf = jax.tree_util.tree_leaves(runs[True].params)[0]
    devs = _shard_devices(leaf)
    check(len(devs) == n_chips,
          f"sharded sweep params on {len(devs)} device(s), not {n_chips}")
    check(len({str(s.index) for s in leaf.addressable_shards}) == n_chips,
          "sharded sweep params are replicated, not split by trajectory")
    facts = check_training_agrees(
        runs[True].params, runs[False].params, init_sweep_params(configs),
        n_rounds, "sharded vs unsharded sweep",
        acc=[h.eval_acc for h in runs[True].histories],
        acc_ref=[h.eval_acc for h in runs[False].histories])
    got = grid[spec.name]["strategies"]
    for strat in STRATEGIES:
        want = [h.eval_acc[-1] for h, s in zip(runs[False].histories,
                                               labels) if s == strat]
        dacc = abs(got[strat]["final_acc"] - float(np.mean(want)))
        check(dacc <= ACC_ATOL,
              f"run_grid {strat}: final accuracy differs by {dacc:.3g}")
    log(f"  sweep params split over {len(devs)} devices; run_grid agrees "
        "with the unsharded sweep")
    return facts


# ------------------------------------------------------------- main

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: every one-chip phase; 4: the sharded paths")
    args = ap.parse_args(argv)

    device = require_devices(args.chips)
    sys.path.insert(0, str(SRC))
    from repro.compile_cache import enable_compile_cache

    cache = Path(enable_compile_cache())
    n_cached = len(list(cache.glob("*"))) if cache.is_dir() else 0
    log(f"compile cache: {cache} ({n_cached} entries at start)")

    facts: dict = {}
    if args.chips == 1:
        phases = (("fleet_solve", phase_fleet_solve),
                  ("service", phase_service),
                  ("metro_tick", phase_metro_tick),
                  ("training", phase_training))
    else:
        phases = (("sharded_solve", functools.partial(
                      phase_sharded_solve, args.chips)),
                  ("sharded_grid", functools.partial(
                      phase_sharded_grid, args.chips)))
    for name, fn in phases:
        with phase(name):
            facts[name] = fn()
    log("facts: " + json.dumps(facts, default=float, sort_keys=True))
    n_cached = len(list(cache.glob("*"))) if cache.is_dir() else 0
    log(f"compile cache: {n_cached} entries at end")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
