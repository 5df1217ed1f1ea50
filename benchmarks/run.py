"""Benchmark harness — one entry per paper table/figure plus framework
benchmarks.  Prints ``name,us_per_call,derived`` CSV rows (plus human
summaries as comment lines prefixed with '#').

    PYTHONPATH=src python -m benchmarks.run                 # fast set
    PYTHONPATH=src python -m benchmarks.run --full          # + FL tables
    PYTHONPATH=src python -m benchmarks.run --only solver_scaling
    PYTHONPATH=src python -m benchmarks.run \
        --only fl_sweep_scaling,batch_solver_scaling --json BENCH_pr.json

``--json`` records the rows (plus environment metadata) for the CI
benchmark-regression gate — see ``benchmarks/compare.py``.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

ROWS: list[tuple[str, float, str]] = []


def emit(name: str, us_per_call: float, derived: str = ""):
    ROWS.append((name, us_per_call, derived))
    print(f"{name},{us_per_call:.3f},{derived}", flush=True)


def _timeit(fn, *args, n=20, warmup=3) -> float:
    """Best-of-n wall time per call in us.  Every call — warmup and timed —
    is ``block_until_ready``'d so jax's async dispatch can't understate
    the cost (returning an unrealised array is near-free).  The minimum,
    as in stdlib ``timeit``, is the noise-robust statistic: anything above
    it measures scheduler interference, not the program."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(n):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best * 1e6


# ----------------------------------------------------------- paper tables

def bench_paper_tables(full: bool):
    """Tables I-IV: time/energy-to-accuracy for the four strategies in both
    scenarios (fig 1-2 curves saved to experiments/)."""
    from repro.fl.experiments import HIGH_BIAS, MILD_BIAS, format_tables, run_scenario
    specs = [HIGH_BIAS, MILD_BIAS]
    if not full:
        # reduced rounds can't reach the paper-scale targets; scale them
        # down so the time/energy-to-accuracy columns stay meaningful
        specs = [dataclasses.replace(s, n_rounds=100, n_runs=1, n_train=3000,
                                     n_test=600, n_devices=50,
                                     targets=(0.25, 0.45))
                 for s in specs]
    out_dir = Path("experiments/bench_tables")
    out_dir.mkdir(parents=True, exist_ok=True)
    for spec in specs:
        t0 = time.perf_counter()
        res = run_scenario(spec, verbose=False)
        dt = time.perf_counter() - t0
        (out_dir / f"{spec.name}.json").write_text(json.dumps(res, indent=1))
        print("#" + format_tables(res, spec).replace("\n", "\n#"))
        for strat, r in res["strategies"].items():
            t = r["table"]
            emit(f"table_{spec.name}_{strat}_time_to_low",
                 (t["time_to_low"] or float("nan")) * 1e6,
                 f"sim_seconds_to_{spec.targets[0]:.0%}")
            emit(f"table_{spec.name}_{strat}_energy_to_low",
                 (t["energy_to_low"] or float("nan")),
                 f"joules_to_{spec.targets[0]:.0%}")
        emit(f"table_{spec.name}_wall", dt * 1e6, "bench wall time")


# --------------------------------------------------------- solver scaling

def bench_solver_scaling(full: bool):
    """Fleet-solve latency vs N (the paper solves 100 devices; the
    framework's vectorised/bisection paths scale to millions)."""
    from repro.core import sample_problem, solve_joint, solve_joint_optimal
    sizes = [100, 10_000, 1_000_000] if full else [100, 10_000, 200_000]
    for n in sizes:
        prob = sample_problem(0, n)
        alt = jax.jit(solve_joint)
        opt = jax.jit(solve_joint_optimal)
        us_alt = _timeit(lambda: alt(prob), n=5)
        us_opt = _timeit(lambda: opt(prob), n=5)
        obj_a = float(solve_joint(prob).objective)
        obj_o = float(solve_joint_optimal(prob).objective)
        emit(f"solver_alternating_n{n}", us_alt, f"objective={obj_a:.5f}")
        emit(f"solver_optimal_n{n}", us_opt,
             f"objective={obj_o:.5f} (+{(obj_o / max(obj_a, 1e-12) - 1):.2%})")


def bench_batch_solver_scaling(full: bool):
    """Batched multi-scenario engine (``solve_joint_batch``) vs the naive
    per-problem python loop: instances/sec at growing batch sizes."""
    from repro.core import solve_joint, solve_joint_batch, stack_problems
    from repro.core.scenarios import make_problem

    n = 64                      # devices per instance
    batch_sizes = [8, 64, 256] if full else [8, 64]
    probs = [make_problem("paper_static", seed=i, n_devices=n)
             for i in range(max(batch_sizes))]

    single = jax.jit(solve_joint)
    jax.block_until_ready(single(probs[0]).a)   # one compile, shared shapes

    def naive_loop(ps):
        out = [single(p) for p in ps]
        jax.block_until_ready(out[-1].a)
        return out

    for bsz in batch_sizes:
        batch = stack_problems(probs[:bsz])
        us_batch = _timeit(lambda batch=batch: solve_joint_batch(batch).a,
                           n=5)
        us_loop = _timeit(lambda chunk=probs[:bsz]: naive_loop(chunk),
                          n=3, warmup=1)
        ips_batch = bsz / (us_batch / 1e6)
        ips_loop = bsz / (us_loop / 1e6)
        emit(f"batch_solver_batched_b{bsz}", us_batch,
             f"instances_per_sec={ips_batch:.1f}")
        emit(f"batch_solver_loop_b{bsz}", us_loop,
             f"instances_per_sec={ips_loop:.1f} "
             f"batched_speedup={ips_batch / ips_loop:.1f}x")


def bench_fused_solver_scaling(full: bool):
    """Fused single-level solver vs the PR-1 ``solve_joint_batch`` path
    (vmapped nested-while Algorithm 2) — the tentpole speedup claim.

    Two regimes:
      * B=64 ensemble of 64-device instances: the vmapped nested loops run
        every instance to the slowest inner solve; the fused flat loop
        masks per element.
      * N=100k single instance (``mega_fleet_100k``): the chunked,
        element-sharded mega-fleet path on a fixed ``chunk_elements``
        memory bound.
    """
    from repro.core import solve_joint_batch, stack_problems
    from repro.core.scenarios import make_problem

    n, bsz = 64, 64
    probs = [make_problem("paper_static", seed=i, n_devices=n)
             for i in range(bsz)]
    batch = stack_problems(probs)

    us_base = _timeit(lambda: solve_joint_batch(batch).a, n=5)
    us_fused = _timeit(lambda: solve_joint_batch(batch, method="fused").a,
                       n=5)
    ips_base = bsz / (us_base / 1e6)
    ips_fused = bsz / (us_fused / 1e6)
    emit(f"fused_solver_base_b{bsz}", us_base,
         f"instances_per_sec={ips_base:.1f}")
    emit(f"fused_solver_fused_b{bsz}", us_fused,
         f"instances_per_sec={ips_fused:.1f} "
         f"speedup={us_base / us_fused:.1f}x")

    n_mega = 100_000
    chunk = 16_384
    mega = make_problem("mega_fleet_100k", seed=0, n_devices=n_mega)
    mega_batch = stack_problems([mega])
    # best-of-5: the 100k vmapped solve is ~20 ms and scheduler-noise on a
    # busy runner is easily +50%, which would flake the 25% absolute gate
    us_base_m = _timeit(lambda: solve_joint_batch(mega_batch).a, n=5)
    us_fused_m = _timeit(
        lambda: solve_joint_batch(mega_batch, method="fused",
                                  chunk_elements=chunk).a, n=5)
    emit(f"fused_solver_base_n{n_mega}", us_base_m,
         f"devices_per_sec={n_mega / (us_base_m / 1e6):.0f}")
    emit(f"fused_solver_fused_n{n_mega}", us_fused_m,
         f"devices_per_sec={n_mega / (us_fused_m / 1e6):.0f} "
         f"chunk_elements={chunk} speedup={us_base_m / us_fused_m:.1f}x")


def bench_dinkelbach(full: bool):
    """Algorithm 1 iterations to convergence + agreement with the
    closed-form fast path."""
    from repro.core import sample_problem
    from repro.core.power import analytic_power, dinkelbach_power
    prob = sample_problem(1, 10_000)
    a = jnp.full((10_000,), 0.05)
    d = jax.jit(lambda: dinkelbach_power(prob, a))
    an = jax.jit(lambda: analytic_power(prob, a))
    us_d = _timeit(d, n=10)
    us_a = _timeit(an, n=10)
    iters = int(dinkelbach_power(prob, a).n_iters)
    gap = float(jnp.max(jnp.abs(d().power - an().power)))
    emit("dinkelbach_10k", us_d, f"iters={iters}")
    emit("analytic_power_10k", us_a, f"max_power_gap={gap:.2e}")


# --------------------------------------------------------------- kernels

def bench_kernels(full: bool):
    """Pallas kernels (interpret=True: functional check; real perf target
    is TPU) vs their jnp oracles (the XLA path actually timed)."""
    from repro.kernels.masked_aggregate.kernel import masked_aggregate_tiled
    from repro.kernels.masked_aggregate.ref import masked_aggregate_ref
    rng = np.random.default_rng(0)
    n, d = (256, 131_072) if full else (128, 16_384)
    g = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    coef = jnp.asarray(rng.uniform(0, 1, n), jnp.float32)
    ref = jax.jit(masked_aggregate_ref)
    us_ref = _timeit(ref, g, coef, n=10)
    err = float(jnp.max(jnp.abs(
        masked_aggregate_tiled(g, coef, interpret=True)
        - masked_aggregate_ref(g, coef))))
    emit("masked_aggregate_ref_xla", us_ref, f"N={n} D={d}")
    emit("masked_aggregate_kernel_check", 0.0, f"interpret_max_err={err:.2e}")

    from repro.kernels.ssd_scan.ops import ssd_apply
    from repro.models.mamba2 import ssd_chunked
    b, s, h, p, nstate = 2, 512, 4, 64, 64
    x = jnp.asarray(rng.normal(size=(b, s, h, p)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.001, 0.1, (b, s, h)), jnp.float32)
    a_ = jnp.asarray(-rng.uniform(0.5, 4, h), jnp.float32)
    bm = jnp.asarray(rng.normal(size=(b, s, nstate)) * 0.3, jnp.float32)
    cm = jnp.asarray(rng.normal(size=(b, s, nstate)) * 0.3, jnp.float32)
    dskip = jnp.asarray(rng.normal(size=h), jnp.float32)
    xla = jax.jit(lambda *t: ssd_chunked(*t, chunk=128)[0])
    us_x = _timeit(xla, x, dt, a_, bm, cm, dskip, n=5)
    err = float(jnp.max(jnp.abs(
        ssd_apply(x, dt, a_, bm, cm, dskip, chunk=128, interpret=True)
        - xla(x, dt, a_, bm, cm, dskip))))
    emit("ssd_chunked_xla", us_x, f"B{b}xS{s}xH{h}")
    emit("ssd_kernel_check", 0.0, f"interpret_max_err={err:.2e}")

    from repro.kernels.swa_decode.ref import swa_decode_ref
    bsz, hkv, grp, dh, w = 2, 4, 4, 128, 2048
    q = jnp.asarray(rng.normal(size=(bsz, hkv, grp, dh)), jnp.float32) * dh ** -0.5
    k = jnp.asarray(rng.normal(size=(bsz, w, hkv, dh)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(bsz, w, hkv, dh)), jnp.float32)
    pos = jnp.arange(w, dtype=jnp.int32)
    qpos = jnp.int32(w - 1)
    refd = jax.jit(lambda *t: swa_decode_ref(*t, window=1024))
    us_ref = _timeit(refd, q, k, v, pos, qpos, n=10)
    emit("swa_decode_ref_xla", us_ref, f"W={w} Hkv={hkv} G={grp}")


# ----------------------------------------------------------- FL step perf

def bench_fl_round(full: bool):
    """One FL communication round (CNN, 50 clients) — fused vs stacked
    aggregation paths."""
    from repro.core import ProbabilisticScheduler, sample_problem
    from repro.data.partition import dirichlet_partition
    from repro.data.synthetic import make_mnist_like
    from repro.fl.engine import FLConfig, run_fl
    train, test = make_mnist_like(2000, 200, seed=0)
    parts = dirichlet_partition(train, 50, 0.3, seed=1)
    prob = sample_problem(0, 50, tau_th=0.5,
                          dirichlet_sizes=np.array([len(p) for p in parts]))
    for mode in ("fused", "stacked"):
        cfg = FLConfig(n_rounds=12, eval_every=1000, batch_per_client=8,
                       aggregate=mode, seed=0)
        t0 = time.perf_counter()
        res = run_fl(prob, ProbabilisticScheduler(), train, parts, test, cfg)
        # the final update is still in flight when run_fl returns — block so
        # the per-round figure includes it
        jax.block_until_ready(res.params)
        us = (time.perf_counter() - t0) / 12 * 1e6
        emit(f"fl_round_{mode}", us, "50 clients x 8 samples")


def bench_fl_sweep_scaling(full: bool):
    """Whole-trajectory throughput: the scan-fused vmapped sweep engine
    (``repro.fl.scan_engine``) vs the per-run python-loop reference
    (``run_fl``) on a seed-averaging grid, probabilistic strategy.

    Both sides pay their full cost per iteration: the loop re-solves the
    joint problem every run (as ``run_scenario`` does today); the sweep
    solves once, plans every trajectory, and runs one jitted call.
    """
    from repro.core import ProbabilisticScheduler, sample_problem
    from repro.data.partition import dirichlet_partition
    from repro.data.synthetic import make_mnist_like
    from repro.fl.engine import FLConfig, run_fl
    from repro.fl.scan_engine import (init_sweep_params, plan_trajectory,
                                      run_fl_sweep, stack_plans)

    n_dev, rounds, b = 8, 12, 1
    train, test = make_mnist_like(1024, 64, seed=0)
    parts = dirichlet_partition(train, n_dev, 0.3, seed=1)
    prob = sample_problem(0, n_dev, tau_th=0.5,
                          dirichlet_sizes=np.array([len(p) for p in parts]))
    sch = ProbabilisticScheduler()

    def loop_grid(cfgs):
        out = [run_fl(prob, sch, train, parts, test, c) for c in cfgs]
        return out[-1].params

    def scan_grid(cfgs):
        state = sch.precompute(prob)
        plans = [plan_trajectory(prob, sch, parts, c, state=state)
                 for c in cfgs]
        sweep = run_fl_sweep(stack_plans(plans), train, test, cfgs[0],
                             init_sweep_params(cfgs), donate_params=False)
        return sweep.params

    for n_traj in (4, 8, 16) if full else (4, 8):
        cfgs = [FLConfig(n_rounds=rounds, eval_every=rounds,
                         batch_per_client=b, seed=s) for s in range(n_traj)]
        us_loop = _timeit(loop_grid, cfgs, n=3, warmup=1)
        us_scan = _timeit(scan_grid, cfgs, n=4, warmup=1)
        tps_loop = n_traj / (us_loop / 1e6)
        tps_scan = n_traj / (us_scan / 1e6)
        emit(f"fl_sweep_loop_t{n_traj}", us_loop,
             f"trajectories_per_sec={tps_loop:.2f}")
        emit(f"fl_sweep_scan_t{n_traj}", us_scan,
             f"trajectories_per_sec={tps_scan:.2f} "
             f"speedup={us_loop / us_scan:.1f}x")


# ------------------------------------------------------- fleet service

def bench_fleet_service_throughput(full: bool):
    """The online fleet control plane (``repro.serve``) on a drifting
    channel.  Three claims, three measurements:

    * micro-batching: the service at ``max_batch=C`` vs the same service
      draining one request per step — isolates what packing requests
      into padded slots amortises (per-step pack + dispatch overhead);
    * warm starts: inner Algorithm-1 (Dinkelbach) iterations per
      micro-batch, warm vs cold, in the paper-faithful mode.  The counts
      are deterministic (same seeds => same counts), so the ``speedup=``
      ratio is gated machine-independently by ``benchmarks/compare.py``;
    * context: a bare jitted per-request ``solve_joint_fused`` loop.  At
      paper scale on CPU the closed-form solve is so cheap that no
      serving machinery beats it (docs/serving.md discusses when the
      service earns its keep); the row keeps that trade-off visible
      rather than hiding it.

    Wall-clock rows feed the same-runner absolute gate.
    """
    from repro.core import make_problem, slice_round, solve_joint_fused
    from repro.serve import FleetControlService, ServiceConfig

    n_cells, n_dev, n_rounds = (16, 64, 10) if full else (8, 64, 8)
    cells = [make_problem("drifting_metro", seed=s, n_devices=n_dev,
                          n_rounds=n_rounds) for s in range(n_cells)]
    requests = [[(c, slice_round(prob, k)) for c, prob in enumerate(cells)]
                for k in range(n_rounds)]
    n_req = n_cells * n_rounds

    def run_service(max_batch=n_cells, **cfg_kw):
        svc = FleetControlService(ServiceConfig(max_batch=max_batch,
                                                **cfg_kw))
        for k, batch in enumerate(requests):
            svc.run(batch)
            if k == 0:
                # round 0 is all-cold and (on the first call of a config)
                # carries jit compiles; drop it from the steady-state
                # stats — the caches keep their state
                svc.stats.reset()
        return svc

    # steady state: throwaway passes warm every jit signature (batched
    # and per-request slot shapes); each timed pass then starts from
    # fresh caches, so it re-measures the same cold->warm request stream
    run_service()
    run_service(max_batch=1)
    us_svc = _timeit(lambda: run_service(), n=5, warmup=1)
    us_one = _timeit(lambda: run_service(max_batch=1), n=3, warmup=1)

    solve = jax.jit(solve_joint_fused)

    def naive_loop():
        out = None
        for batch in requests:
            for _, prob in batch:
                out = solve(prob)
        jax.block_until_ready(out.a)

    us_loop = _timeit(naive_loop, n=3, warmup=1)
    emit(f"fleet_service_batched_c{n_cells}", us_svc,
         f"solves_per_sec={n_req / (us_svc / 1e6):.1f} "
         f"speedup={us_one / us_svc:.1f}x")
    emit(f"fleet_service_unbatched_c{n_cells}", us_one,
         f"solves_per_sec={n_req / (us_one / 1e6):.1f}")
    emit(f"fleet_service_bare_loop_c{n_cells}", us_loop,
         f"solves_per_sec={n_req / (us_loop / 1e6):.1f}")

    # warm-start iteration drop, paper-faithful Dinkelbach mode: the
    # counts are deterministic, so the ratio transfers across machines
    run_service(power_solver="dinkelbach")   # compile both init signatures
    warm = run_service(power_solver="dinkelbach", warm_start=True)
    cold = run_service(power_solver="dinkelbach", warm_start=False)
    wi, ci = warm.stats.mean_inner_iters, cold.stats.mean_inner_iters
    s = warm.stats.summary()
    emit("fleet_service_warm_inner_iters", wi,
         f"p50_ms={s['p50_latency_s'] * 1e3:.2f} "
         f"p99_ms={s['p99_latency_s'] * 1e3:.2f} "
         f"warm_fraction={s['warm_fraction']:.2f}")
    emit("fleet_service_cold_inner_iters", ci,
         f"speedup={ci / max(wi, 1e-9):.1f}x")


def bench_fleet_service_openloop(full: bool):
    """The open-loop control plane under seeded arrival traffic — the
    serving claims of ``docs/serving.md`` measured end-to-end:

    * ``_sustained``: Poisson arrivals at 0.7x the *measured* full-batch
      capacity; ``throughput_ratio`` (sustained/offered) is dimensionless
      and gated machine-independently by ``compare.py``;
    * ``_latency``: p50/p99 request latency and the deadline-miss rate,
      with deadlines expressed in units of the measured batch cost
      (``p99_over_deadline`` therefore transfers across machines — the
      gated p99 ceiling);
    * ``_warmup``: AOT warmup cost per bucket (compiled with the
      persistent cache off, so a cache left by an earlier run does not
      serve it) and ``first_over_p50``,
      the no-trace-spike acceptance figure (first post-warmup request vs
      steady-state p50);
    * ``_bursty``: ON/OFF bursts over drifted + stale-tolerant cells;
      ``preemptions`` counts the priority lane actually firing.

    Wall-clock rows feed the same-runner absolute gate as usual.
    """
    from repro.compile_cache import compile_cache_off
    from repro.core import slice_round
    from repro.serve import (FleetControlService, ServiceConfig,
                             bursty_trace, drive, make_cells,
                             measure_capacity, poisson_trace)

    n_cells, n_dev, n_rounds = (8, 64, 12) if full else (6, 48, 8)
    n_req = 240 if full else 120
    cells = make_cells(n_cells, n_devices=n_dev, n_rounds=n_rounds, seed=0)
    probe = [slice_round(c, 0) for c in cells]

    svc = FleetControlService(ServiceConfig(max_batch=8))
    with compile_cache_off():
        wtimes = svc.warmup(probe[0], max_devices=n_dev)
    cap = measure_capacity(svc, probe)
    svc.stats.reset()

    # deadline budget in units of the measured full-batch cost: the
    # miss-rate / p99 figures then mean the same thing on any machine
    deadline = 8.0 * svc.config.max_batch / cap
    trace = poisson_trace(cells, rate_hz=0.7 * cap, n_requests=n_req,
                          seed=1, deadline_s=deadline)
    rep = drive(svc, trace, reset_stats_after=n_req // 4)
    s = svc.stats
    p50, p99 = s.latency_percentile(50), s.latency_percentile(99)
    first = rep.responses[0].latency_s   # first post-warmup request
    emit("fleet_service_openloop_sustained", rep.wall_s / n_req * 1e6,
         f"solves_per_sec={rep.sustained_rate_hz:.1f} "
         f"offered_hz={rep.offered_rate_hz:.1f} "
         f"throughput_ratio={rep.sustained_rate_hz / rep.offered_rate_hz:.3f}")
    emit("fleet_service_openloop_latency", p99 * 1e6,
         f"p50_ms={p50 * 1e3:.2f} p99_ms={p99 * 1e3:.2f} "
         f"deadline_ms={deadline * 1e3:.2f} "
         f"miss_rate={s.deadline_miss_rate:.4f} "
         f"p99_over_deadline={p99 / deadline:.3f}")
    emit("fleet_service_openloop_warmup", sum(wtimes.values()) * 1e6,
         f"buckets={len(wtimes)} first_ms={first * 1e3:.2f} "
         f"first_over_p50={first / max(p50, 1e-9):.2f}")

    # bursty: stale-tolerant (1-round) cells mixed with the drifting
    # ones; drifted cells ride the priority lane through each burst
    svc2 = FleetControlService(ServiceConfig(max_batch=8))
    svc2.warmup(probe[0], max_devices=n_dev)
    static = make_cells(2, n_devices=n_dev, n_rounds=1, seed=100)
    btrace = bursty_trace(static + cells, burst_rate_hz=2.0 * cap,
                          burst_len=3 * n_cells, n_bursts=4,
                          idle_s=4.0 * svc2.config.max_batch / cap, seed=2)
    rep2 = drive(svc2, btrace)
    s2 = svc2.stats.summary()
    emit("fleet_service_openloop_bursty",
         rep2.wall_s / len(btrace) * 1e6,
         f"preemptions={svc2.stats.n_preemptions} "
         f"priority_fraction={s2['priority_fraction']:.3f} "
         f"mean_batch={s2['solved'] / max(s2['batches'], 1):.2f}")


def bench_fleet_service_faulted(full: bool):
    """Degraded-mode serving under the seeded chaos harness
    (``docs/robustness.md``): the same Poisson load driven twice through
    identically-warmed services — once clean, once with 10% of arrivals
    corrupted (``FaultPlan``) — so the cost of sanitize + retry +
    degraded cache locality shows up as one dimensionless ratio:

    * ``_clean``: the fault-free reference drive;
    * ``_chaos``: the corrupted drive; ``degraded_throughput_ratio``
      (faulted sustained rate / clean sustained rate) is gated >= 0.5
      by ``compare.py``, and ``nan_escapes`` — non-finite values in any
      response — is gated == 0.  Both transfer across machines.

    Wall-clock per-request figures are queue-dependent tail statistics
    (``ABSOLUTE_EXEMPT``, like the open-loop rows).
    """
    from repro.core import slice_round
    from repro.serve import (FaultPlan, FleetControlService, ServiceConfig,
                             chaos_drive, drive, make_cells,
                             measure_capacity, poisson_trace)

    n_cells, n_dev, n_rounds = (8, 64, 12) if full else (6, 48, 8)
    n_req = 240 if full else 120

    cells = make_cells(n_cells, n_devices=n_dev, n_rounds=n_rounds, seed=0)
    probe = [slice_round(c, 0) for c in cells]

    def fresh():
        svc = FleetControlService(ServiceConfig(max_batch=8))
        svc.warmup(probe[0], max_devices=n_dev)
        return svc

    cap = measure_capacity(fresh(), probe)
    trace = poisson_trace(cells, rate_hz=0.6 * cap, n_requests=n_req, seed=1)

    svc = fresh()
    svc.stats.reset()
    clean = drive(svc, trace, reset_stats_after=n_req // 4)

    svc2 = fresh()
    svc2.stats.reset()
    plan = FaultPlan(seed=3, fault_rate=0.1)   # 10% of arrivals corrupted
    chaos = chaos_drive(svc2, trace, plan, clock="wall",
                        reset_stats_after=n_req // 4)

    ratio = chaos.report.sustained_rate_hz / clean.sustained_rate_hz
    emit("fleet_service_faulted_clean", clean.wall_s / n_req * 1e6,
         f"solves_per_sec={clean.sustained_rate_hz:.1f} "
         f"offered_hz={clean.offered_rate_hz:.1f}")
    emit("fleet_service_faulted_chaos",
         chaos.report.wall_s / n_req * 1e6,
         f"degraded_throughput_ratio={ratio:.3f} "
         f"nan_escapes={chaos.nan_escapes} "
         f"n_faulted={chaos.n_faulted} "
         f"unhealthy_devices={chaos.n_unhealthy_devices} "
         f"retries={chaos.counters['retries']} "
         f"shed={chaos.counters['shed']}")


# ------------------------------------------------------- multi-cell

def bench_multicell_solver(full: bool):
    """The coupled metro solver (``core.multicell``): dual decomposition
    with ONE element-sharded fused union solve per outer iteration vs the
    reference python loop of per-cell ``solve_joint_fused`` calls running
    the same fixed point (``solve_coupled_loop``).

    * the wall-clock pair carries the tentpole ``speedup=`` claim at
      C=64 cells (gated machine-independently by ``compare.py``);
    * ``multicell_warm_outer_iters`` pins the warm-dual claim: outer
      iterations on a tick seeded with the previous tick's duals vs a
      cold solve.  The counts are deterministic (same scenario seed =>
      same counts), so the ratio transfers across machines.
    """
    from repro.core import solve_coupled, solve_coupled_loop
    from repro.core.scenarios import make_problem

    c, n = 64, 64
    mc = make_problem("metro_coupled", seed=0, n_cells=c, n_devices=n)

    cold = solve_coupled(mc)            # compiles, and pins the iter count
    solve_coupled_loop(mc)              # compiles the per-cell program
    us_coupled = _timeit(lambda: solve_coupled(mc).batch.a, n=5, warmup=1)
    us_loop = _timeit(lambda: solve_coupled_loop(mc).batch.a, n=3, warmup=1)
    emit(f"multicell_coupled_c{c}", us_coupled,
         f"outer_iters={cold.outer_iters} "
         f"cells_per_sec={c / (us_coupled / 1e6):.0f} "
         f"speedup={us_loop / us_coupled:.1f}x")
    emit(f"multicell_loop_c{c}", us_loop,
         f"cells_per_sec={c / (us_loop / 1e6):.0f}")

    # deterministic warm-dual claim: outer iterations with/without the
    # previous tick's duals on the same metro
    warm = solve_coupled(mc, init=cold.resume)
    emit("multicell_warm_outer_iters", float(warm.outer_iters),
         f"residual={warm.residual:.2e} "
         f"speedup={cold.outer_iters / max(warm.outer_iters, 1):.1f}x")
    emit("multicell_cold_outer_iters", float(cold.outer_iters),
         f"mu={float(np.max(np.atleast_1d(np.asarray(cold.mu)))):.3e} "
         f"load_over_budget="
         f"{float(np.max(np.atleast_1d(np.asarray(cold.backhaul_load)))) / mc.backhaul_bits:.4f}")


# ------------------------------------------------------- closed loop

def bench_closed_loop_throughput(full: bool):
    """The drift-aware closed loop (``repro.fl.closed_loop``): per-round
    control-plane solves on a Gauss-Markov channel, warm-started service
    vs a per-round cold ``solve_joint`` loop.

    * wall-clock rows (``closed_loop_control_*``) feed the same-runner
      absolute gate;
    * the inner-iteration pair (``closed_loop_{warm,cold}_inner_iters``)
      is deterministic (same seeds => same counts), so its ``speedup=``
      ratio is gated machine-independently — the closed loop's
      drift-tracking claim;
    * ``closed_loop_pipeline`` times the whole loop (control plane +
      strategy suite + scan-fused training) end-to-end.
    """
    import functools

    from repro.core import make_problem, slice_round, solve_joint
    from repro.fl.closed_loop import (CLOSED_LOOP_STRATEGIES,
                                      ClosedLoopConfig, run_closed_loop_grid,
                                      solve_rounds)
    from repro.serve import FleetControlService, ServiceConfig

    n_dev, k_rounds = (48, 12) if full else (32, 8)
    prob = make_problem("drifting_metro", seed=0, n_devices=n_dev,
                        n_rounds=k_rounds)

    def control_warm():
        svc = FleetControlService(ServiceConfig(method="alternating",
                                                power_solver="dinkelbach"))
        return solve_rounds(prob, svc)

    solve = jax.jit(functools.partial(solve_joint,
                                      power_solver="dinkelbach"))

    def control_cold_loop():
        inner, out = 0, None
        for k in range(k_rounds):
            out = solve(slice_round(prob, k))
            inner += int(out.inner_iters)
        jax.block_until_ready(out.a)
        return inner

    control_warm()          # compile cold + warm init signatures
    control_cold_loop()
    us_warm = _timeit(control_warm, n=3, warmup=1)
    us_cold = _timeit(control_cold_loop, n=3, warmup=1)
    emit(f"closed_loop_control_warm_k{k_rounds}", us_warm,
         f"rounds_per_sec={k_rounds / (us_warm / 1e6):.1f}")
    emit(f"closed_loop_control_cold_k{k_rounds}", us_cold,
         f"rounds_per_sec={k_rounds / (us_cold / 1e6):.1f}")

    # deterministic drift-tracking claim: inner Algorithm-1 iterations
    # per round, warm-started stream vs per-round cold solves
    trace = control_warm()
    wi = trace.inner_iters / k_rounds
    ci = control_cold_loop() / k_rounds
    emit("closed_loop_warm_inner_iters", wi,
         f"warm_rounds={trace.warm_rounds}/{k_rounds}")
    emit("closed_loop_cold_inner_iters", ci,
         f"speedup={ci / max(wi, 1e-9):.1f}x")

    # end-to-end: control plane + classic strategy suite + scan-fused
    # training.  Pinned to the pre-compression five strategies so the
    # committed baseline stays comparable; the quantized joint_bits
    # strategy is benched separately (bench_bit_allocation).
    classic = tuple(s for s in CLOSED_LOOP_STRATEGIES if s != "joint_bits")
    n_strat = len(classic)
    cfg = ClosedLoopConfig(n_devices=16, n_rounds=6, n_train=512,
                           n_test=128, eval_every=3)
    us_pipe = _timeit(lambda: run_closed_loop_grid(cfg, classic),
                      n=3, warmup=1)
    emit("closed_loop_pipeline", us_pipe,
         f"strategies={n_strat} rounds={cfg.n_rounds} "
         f"trajectories_per_sec={n_strat / (us_pipe / 1e6):.2f}")


# -------------------------------------------------------- bit allocation

def bench_bit_allocation(full: bool):
    """Joint bit/power/selection (docs/compression.md): participation and
    per-participant energy vs fixed fp32 on the bandwidth-starved
    scenario, plus the quantized masked-aggregate kernel vs its jnp
    oracle.  ``participants_ratio`` is deterministic (same scenario seed
    => same solve) and gated machine-independently in compare.py."""
    import dataclasses as _dc

    from repro.core import make_problem, solve_joint_fused
    from repro.kernels.masked_aggregate.ops import quantized_masked_aggregate
    from repro.kernels.masked_aggregate.ref import (
        quantized_masked_aggregate_ref)

    n_dev = 64 if full else 32
    menu = (8, 16, 32)
    prob = make_problem("bandwidth_starved", seed=1, n_devices=n_dev)

    sol32 = solve_joint_fused(prob)
    solm = solve_joint_fused(prob, bit_menu=menu)
    us32 = _timeit(lambda: solve_joint_fused(prob), n=5, warmup=1)
    usm = _timeit(lambda: solve_joint_fused(prob, bit_menu=menu),
                  n=5, warmup=1)

    def per_round(sol, p):
        a = np.asarray(sol.a)
        e_dev = np.asarray(p.upload_energy(sol.power)
                           + p.compute_energy())
        return float(a.sum()), float((a * e_dev).sum())

    parts32, energy32 = per_round(sol32, prob)
    prob_b = _dc.replace(prob, bits=solm.bits)
    parts_m, energy_m = per_round(solm, prob_b)
    epp32 = energy32 / max(parts32, 1e-12)
    epp_m = energy_m / max(parts_m, 1e-12)
    emit(f"bit_allocation_solve_fp32_n{n_dev}", us32,
         f"expected_participants={parts32:.2f}")
    emit("bit_allocation_participation", usm,
         f"participants_ratio={parts_m / max(parts32, 1e-12):.2f} "
         f"energy_per_participant_ratio={epp_m / max(epp32, 1e-12):.2f} "
         f"menu={'/'.join(str(b) for b in menu)} N={n_dev}")

    rng = np.random.default_rng(0)
    n, d = (256, 131_072) if full else (128, 16_384)
    g = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    coef = jnp.asarray(rng.uniform(0, 1, n), jnp.float32)
    noise = jnp.asarray(rng.uniform(0, 1, (n, d)), jnp.float32)
    bits = jnp.asarray(rng.choice([4.0, 8.0, 16.0, 32.0], n), jnp.float32)
    ref = jax.jit(quantized_masked_aggregate_ref)
    us_ref = _timeit(ref, g, coef, noise, bits, n=10)
    err = float(jnp.max(jnp.abs(
        quantized_masked_aggregate(g, coef, noise, bits, interpret=True)
        - ref(g, coef, noise, bits))))
    emit("bit_allocation_quantized_aggregate_ref_xla", us_ref,
         f"N={n} D={d}")
    emit("bit_allocation_kernel_check", 0.0,
         f"interpret_max_err={err:.2e}")


# ------------------------------------------------------------- roofline

def bench_roofline(full: bool):
    """Summarise dry-run artifacts into the §Roofline table."""
    art = Path("experiments/artifacts")
    rows = 0
    for f in sorted(art.glob("*.json")):
        rec = json.loads(f.read_text())
        if rec.get("status") != "ok":
            continue
        r = rec["roofline"]
        rows += 1
        emit(f"roofline_{rec['arch']}_{rec['shape']}_{rec['mesh']}",
             max(r["compute_s"], r["memory_s"], r["collective_s"]) * 1e6,
             f"dominant={r['dominant']} useful={r['useful_ratio']:.2f}")
    if not rows:
        print("# no dry-run artifacts found; run repro.launch.dryrun first")


BENCHES = {
    "paper_tables": bench_paper_tables,
    "solver_scaling": bench_solver_scaling,
    "batch_solver_scaling": bench_batch_solver_scaling,
    "fused_solver_scaling": bench_fused_solver_scaling,
    "dinkelbach": bench_dinkelbach,
    "kernels": bench_kernels,
    "fl_round": bench_fl_round,
    "fl_sweep_scaling": bench_fl_sweep_scaling,
    "fleet_service_throughput": bench_fleet_service_throughput,
    "fleet_service_openloop": bench_fleet_service_openloop,
    "fleet_service_faulted": bench_fleet_service_faulted,
    "multicell_solver": bench_multicell_solver,
    "closed_loop_throughput": bench_closed_loop_throughput,
    "bit_allocation": bench_bit_allocation,
    "roofline": bench_roofline,
}


def _write_json(path: str, args) -> None:
    rec = {
        "meta": {
            "argv": sys.argv[1:],
            "jax": jax.__version__,
            "backend": jax.default_backend(),
            "device_count": jax.device_count(),
            "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"),
        },
        "benches": {name: {"us_per_call": us, "derived": derived}
                    for name, us, derived in ROWS},
    }
    Path(path).write_text(json.dumps(rec, indent=1))
    print(f"# wrote {path} ({len(ROWS)} rows)")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma-separated bench names "
                         f"(choices: {', '.join(sorted(BENCHES))})")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write rows + metadata as JSON (CI gate input)")
    ap.add_argument("--profile", default=None, metavar="DIR",
                    help="wrap the timed region in jax.profiler.trace(DIR) "
                         "(TensorBoard/Perfetto trace of every bench run)")
    ap.add_argument("--host-devices", type=int, default=0, metavar="N",
                    help="force N virtual host (CPU) devices so the sharded "
                         "paths exercise a multi-device mesh; must be set "
                         "before any jax computation runs")
    args = ap.parse_args(argv)
    if args.host_devices > 0:
        # effective only because the backend has not been initialised yet:
        # nothing above touches a jax array before benches run
        flags = os.environ.get("XLA_FLAGS", "")
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count="
            f"{args.host_devices}").strip()
    from repro.compile_cache import enable_compile_cache

    print(f"# compile cache: {enable_compile_cache()}")
    names = args.only.split(",") if args.only else list(BENCHES)
    unknown = [n for n in names if n not in BENCHES]
    if unknown:
        ap.error(f"unknown bench(es) {unknown}; choices: {sorted(BENCHES)}")
    print("name,us_per_call,derived")
    profile = (jax.profiler.trace(args.profile) if args.profile
               else contextlib.nullcontext())
    with profile:
        for name in names:
            print(f"# --- {name} ---", flush=True)
            BENCHES[name](args.full)
    if args.profile:
        print(f"# profiler trace written to {args.profile}")
    if args.json:
        _write_json(args.json, args)


if __name__ == "__main__":
    main()
