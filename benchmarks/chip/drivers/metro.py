"""Coupled metro ticks: every cell of a city re-solved under one budget.

Set-up draws the metro from the seed: ``devices_per_cell`` devices in
each of the ``3 (1 + 3 R (R + 1))`` cells of ``R = rings`` rings of
three-sector sites, the shared backhaul budget, and
``traffic["rounds"]`` rounds of a drifting channel into host memory,
and the seed of the layout's points, from which the program's
``hex_coupling`` builds the coupling ``G``.  Set-up puts the metro's
static leaves on the device once and serves three ticks (one cold, two
warm), so that every program the window runs is compiled and the
service holds warm duals.

Each step of the window replaces the metro's ``fading`` with the next
round's ``[C, N, 1]`` gains from host memory, calls
``FleetControlService.solve_coupled`` with the configuration's loop
settings, and copies ``a*`` and ``P*`` of the real cells back to host
memory.  Ticks run back to back; nothing is pipelined.

The answers of the first tick, two ticks drawn from the seed and the
last tick are kept with the estimate ``I`` each was solved at, its price
and its loop counts, and compared with the plain reference at that
``I``: the answers and the price, the interference the program's
answers make against ``I``, and the load against the budget.  The
reference builds ``G`` itself from the configuration file's constants,
and the program's ``G`` is compared with it entry by entry.
"""
from __future__ import annotations

import dataclasses
import sys
import time
from typing import NamedTuple

import numpy as np

import gen

#: the numbers :func:`check` compares, each with a limit in the config file
COMPARED = ("coupling_rel_gap", "a_gap", "p_rel_gap", "mu_rel_gap",
            "kkt_residual", "over_budget")
#: ticks whose answers are kept for the check, besides the first and last
N_SAMPLED = 2
#: the service's name for the metro
METRO_ID = "metro"


class Tick(NamedTuple):
    """What a tick handed back, on the host, for the real cells."""

    round_k: int
    a: np.ndarray           # [C, N]
    p: np.ndarray           # [C, N]
    mu: float               # the backhaul price
    solved_at: np.ndarray   # [C] the estimate I the answers were solved at
    outer_iters: int
    hit_cap: bool


def n_cells(rings: int) -> int:
    return 3 * (1 + 3 * rings * (rings + 1))


class Setup:
    """What the window needs; built by :func:`setup`.  With
    ``program=False`` only the cell's data is drawn, and ``coupling``
    (the program's ``G``) is left for :meth:`control` to fill."""

    def __init__(self, ctx, program: bool = True):
        cfg, traffic = ctx.config, ctx.traffic
        self.cfg = cfg
        self.n_cells = c = n_cells(int(cfg["rings"]))
        self.n_dev = n = int(cfg["devices_per_cell"])
        rng = gen.rng_for(ctx.seed, 2)
        self.dev = {k: v.reshape(c, n)
                    for k, v in gen.devices(cfg, rng, c * n).items()}
        gains = gen.gauss_markov_gains(rng, (c, n), int(traffic["rounds"]),
                                       float(cfg["coherence"]))
        # one contiguous [C, N, 1] host block per round
        self.gains = np.ascontiguousarray(np.moveaxis(gains, -1, 0)[..., None])
        self.statics = gen.statics(cfg)
        self.budget = (float(cfg["backhaul_fraction"])
                       * float(cfg["uploads_per_cell"]) * c
                       * float(cfg["grad_size_bits"]))
        self.layout_seed = int(gen.rng_for(ctx.seed, 4).integers(1 << 62))
        self.sample = set(gen.rng_for(ctx.seed, 3).integers(
            1, int(traffic["sample_below"]), size=N_SAMPLED).tolist()) | {0}
        self.kept: dict[int, Tick] = {}
        self.service = self.metro = self.coupling = None
        if not program:
            return
        import jax
        import jax.numpy as jnp

        from repro.core.batch import ProblemBatch
        from repro.core.multicell import hex_coupling, make_multicell
        from repro.core.problem import WirelessFLProblem
        from repro.serve import FleetControlService, ServiceConfig

        leaves = jax.device_put(dict(self.dev))
        cells = ProblemBatch(
            problem=WirelessFLProblem(**leaves, fading=None, n_rounds=1,
                                      **self.statics),
            mask=jnp.ones((c, n), bool),
            fleet_sizes=jnp.full((c,), n, jnp.int32))
        self.coupling = hex_coupling(
            int(cfg["rings"]), float(cfg["isd_m"]),
            n_points=int(cfg["coupling_points"]),
            seed=self.layout_seed, fc_ghz=float(cfg["fc_ghz"]))
        self.metro = make_multicell(cells, self.coupling,
                                    backhaul_bits=self.budget)
        self.service = FleetControlService(ServiceConfig())
        last = len(self.gains)
        for k in (last - 3, last - 2, last - 1):     # cold, then warm
            self.step(k)

    def control(self, reference, dtype: str) -> dict:
        """The reference in ``dtype`` put in the program's place: its
        ``G``, and its answers for the sampled ticks, each at the float64
        reference's own equilibrium ``I``; and the facts a window
        reports."""
        cfg = self.cfg
        exact = reference.layout_coupling(cfg, self.layout_seed)
        self.coupling = reference.layout_coupling(cfg, self.layout_seed,
                                                  dtype=dtype)
        for k in sorted(self.sample):
            g = self.gains[k % len(self.gains), :, :, 0]
            at = reference.equilibrium(
                self.dev, g, exact, self.statics, self.budget,
                tol=float(cfg["outer_tol"]), damping=float(cfg["damping"]))
            a, p, mu, _, _ = reference.solve(
                self.dev, g, exact, self.statics, self.budget, at,
                dtype=dtype)
            self.kept[k] = Tick(k % len(self.gains), a, p, mu, at, 0, False)
        return {}

    def release(self) -> None:
        """Let go of the service, the device-resident metro and its duals."""
        self.service = self.metro = None

    def step(self, k: int, spans=None) -> Tick:
        cfg, c, n = self.cfg, self.n_cells, self.n_dev
        r = k % len(self.gains)
        cells = self.metro.cells
        metro = dataclasses.replace(self.metro, cells=dataclasses.replace(
            cells, problem=dataclasses.replace(cells.problem,
                                               fading=self.gains[r])))
        t0 = time.perf_counter()
        sol = self.service.solve_coupled(
            METRO_ID, metro, outer_iters=int(cfg["outer_iters"]),
            outer_tol=float(cfg["outer_tol"]),
            damping=float(cfg["damping"])).solution
        t1 = time.perf_counter()
        a = np.asarray(sol.batch.a)[:c, :n, 0]
        p = np.asarray(sol.batch.power)[:c, :n, 0]
        if spans is not None:
            spans.add("tick", t0, t1)
            spans.add("extract", t1, time.perf_counter())
        return Tick(r, a, p, float(np.ravel(sol.mu)[0]),
                    np.asarray(sol.solved_at)[:c, 0], int(sol.outer_iters),
                    bool(sol.hit_iter_cap))


def setup(ctx) -> Setup:
    return Setup(ctx)


def window(st: Setup, ctx) -> dict:
    before = st.service.stats.counter_summary()
    t0 = ctx.open_window()
    t_stop = t0 + ctx.seconds
    k = failed = 0
    tick = None
    while k == 0 or time.perf_counter() < t_stop:
        tick = st.step(k, ctx.spans)
        failed += not (np.all(np.isfinite(tick.a))
                       and np.all(np.isfinite(tick.p)))
        if k in st.sample:
            st.kept[k] = tick
        k += 1
    t_end = ctx.close_window()
    st.kept[k - 1] = tick
    after = st.service.stats.counter_summary()
    n = st.n_cells * st.n_dev
    return {
        "end_to_end": {"solve_devices_per_s": n * k / (t_end - t0)},
        "attempted": k,
        "failed": int(failed),
        "window_s": t_end - t0,
        "steps": k,
        "n_devices": n,
        "counters": {key: after[key] - before[key] for key in after
                     if isinstance(after[key], int)},
    }


def check(st: Setup, ctx, facts: dict) -> dict:
    """The program's ``G`` against the reference's, and the kept ticks
    (``facts["ticks_checked"]``) against the reference at the estimate
    each was solved at: ``{name: widest value}``."""
    ref = ctx.reference
    s_bits = st.statics["grad_size_bits"]
    coupling = ref.layout_coupling(st.cfg, st.layout_seed)
    facts["ticks_checked"] = sorted(st.kept)
    worst = {"coupling_rel_gap": ref.coupling_gap(st.coupling, coupling)}
    print(f"coupling_rel_gap {worst['coupling_rel_gap']!r}",
          file=sys.stderr, flush=True)
    for k, t in sorted(st.kept.items()):
        g = st.gains[t.round_k, :, :, 0]
        ra, rp, rmu, _, _ = ref.solve(st.dev, g, coupling, st.statics,
                                      st.budget, t.solved_at)
        a = np.asarray(t.a, np.float64)
        p = np.asarray(t.p, np.float64)
        load = float(np.sum(a)) * s_bits
        got = {
            "a_gap": float(np.max(np.abs(a - ra), initial=0.0)),
            "p_rel_gap": float(np.max(np.abs(p - rp)
                                      / np.maximum(np.abs(rp), 1e-30),
                                      initial=0.0)),
            "mu_rel_gap": abs(t.mu - rmu) / max(abs(rmu), 1e-30),
            "kkt_residual": ref.relative_gap(
                t.solved_at, ref.interference(coupling, a, p)),
            "over_budget": (load - st.budget) / st.budget,
        }
        print(f"tick {k}: round {t.round_k}  outer_iters {t.outer_iters}  "
              f"hit_cap {t.hit_cap}  mu {t.mu!r}  load/budget "
              f"{load / st.budget!r}  " + "  ".join(
                  f"{name} {v!r}" for name, v in got.items()),
              file=sys.stderr, flush=True)
        for name, v in got.items():
            worst[name] = max(worst.get(name, -np.inf), v)
    return worst
