"""Fleet-scale solve on the scenario engine — a smoke benchmark.

Two axes of scale, both far beyond the paper's single 100-device instance,
both driven by the fused single-level solver (``method="fused"`` /
``solve_joint_fused``) with its chunked, element-sharded mega-fleet path:

1. **One huge fleet** (``--n``): the fused chunked driver against
   Algorithm 2 (nested loops), the exact bisection optimum, and the
   Pallas kernels on a single N-device scenario drawn from the registry
   (interpret mode on CPU; compiled on TPU).  Prints solved-devices/sec.
2. **Many scenarios at once** (``--batch``): a ``ProblemBatch`` of i.i.d.
   scenario draws solved by ``solve_joint_batch(method="fused")`` in one
   flat, device-sharded call, versus the PR-1 vmapped path and the naive
   per-instance python loop.

    PYTHONPATH=src python examples/fleet_scale.py --n 1000000
    PYTHONPATH=src python examples/fleet_scale.py --scenario mega_fleet_100k --n 100000
    PYTHONPATH=src python examples/fleet_scale.py --scenario rayleigh_fading --batch 64
"""
import argparse
import time

import jax

from repro.core import (
    solve_joint,
    solve_joint_batch,
    solve_joint_fused,
    solve_joint_optimal,
)
from repro.core.scenarios import SCENARIOS, make_batch, make_problem
from repro.kernels.selection_solve.ops import solve_joint_kernel


def _bench(fn):
    """Compile (warmup call), then time one blocked solve."""
    sol = fn()
    jax.block_until_ready(sol.a)
    t0 = time.perf_counter()
    sol = fn()
    jax.block_until_ready(sol.a)
    return sol, time.perf_counter() - t0


def bench_single_fleet(scenario: str, n: int, chunk: int) -> None:
    prob = make_problem(scenario, seed=0, n_devices=n)
    # fading solves n_rounds elements per device; report the honest unit
    n_elements = n * (prob.n_rounds if prob.fading is not None else 1)
    unit = "elements/sec" if prob.fading is not None else "devices/sec"
    print(f"--- one {n}-device '{scenario}' fleet "
          f"({len(jax.devices())} device(s)) ---")
    solvers = [
        ("fused chunked (mega-fleet)",
         jax.jit(lambda p: solve_joint_fused(p, chunk_elements=chunk,
                                             shard=True))),
        ("fused flat (single launch)", jax.jit(solve_joint_fused)),
        ("alternating (paper Alg 2)", jax.jit(solve_joint)),
        ("bisection optimum (ours)", jax.jit(solve_joint_optimal)),
        ("pallas kernel", solve_joint_kernel),
    ]
    for name, fn in solvers:
        sol, dt = _bench(lambda fn=fn: fn(prob))
        feas = bool(prob.constraints_satisfied(sol.a, sol.power, rtol=1e-3).all())
        print(f"{name:28s}: objective={float(sol.objective):.6f} "
              f"E[participants]={float(sol.a.sum()):9.1f} "
              f"{dt * 1e3:8.1f} ms/solve "
              f"{n_elements / dt:12.0f} {unit} feasible={feas}")


def bench_scenario_batch(scenario: str, batch_size: int) -> None:
    n = SCENARIOS[scenario].n_devices
    batch = make_batch(scenario, batch_size, seed=0)
    n_devices_total = int(batch.fleet_sizes.sum())
    print(f"--- {batch_size} x {n}-device '{scenario}' instances, "
          f"{len(jax.devices())} device(s) ---")

    def run(label, fn):
        sol, dt = _bench(fn)
        print(f"{label:28s}: {batch_size / dt:10.1f} instances/sec "
              f"{n_devices_total / dt:12.0f} devices/sec "
              f"({dt * 1e3:.1f} ms total)")
        return sol, dt

    sol, dt_fused = run("fused (flat element set)",
                        lambda: solve_joint_batch(batch, method="fused"))
    _, dt_vmap = run("vmapped Alg 2 (PR-1 path)",
                     lambda: solve_joint_batch(batch))

    single = jax.jit(solve_joint)
    problems = batch.unstack()
    jax.block_until_ready(single(problems[0]).a)        # compile
    t0 = time.perf_counter()
    for p in problems:
        ref = single(p)
    jax.block_until_ready(ref.a)
    dt_loop = time.perf_counter() - t0
    print(f"{'per-instance python loop':28s}: {batch_size / dt_loop:10.1f} "
          f"instances/sec {n_devices_total / dt_loop:12.0f} devices/sec "
          f"({dt_loop * 1e3:.1f} ms total)")
    print(f"fused speedup: {dt_vmap / dt_fused:.1f}x vs vmapped, "
          f"{dt_loop / dt_fused:.1f}x vs loop")

    obj = sol.objective
    print(f"objective over the ensemble: mean={float(obj.mean()):.5f} "
          f"min={float(obj.min()):.5f} max={float(obj.max()):.5f}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=200_000,
                    help="fleet size for the single-fleet comparison")
    ap.add_argument("--scenario", default="paper_static",
                    choices=sorted(SCENARIOS))
    ap.add_argument("--batch", type=int, default=32,
                    help="number of stacked scenario instances")
    ap.add_argument("--chunk-elements", type=int, default=16_384,
                    help="fused mega-fleet memory bound (elements per chunk)")
    args = ap.parse_args()

    bench_single_fleet(args.scenario, args.n, args.chunk_elements)
    bench_scenario_batch(args.scenario, args.batch)


if __name__ == "__main__":
    main()
