"""Bulk fleet solve: back-to-back round solves of one large deployment.

Set-up draws the deployment's per-device leaves from the seed and puts
them on the device once, and draws ``traffic["rounds"]`` rounds of a
drifting channel into host memory.  Each step of the window takes the
next round's ``[N]`` gains from host memory, solves the round with
``solve_joint_fused`` under its own defaults (the code's path choice),
and copies ``a*`` and ``P*`` back to host memory, as a controller that
hands the decisions on would.  Nothing is pipelined across steps.

The answers of a few steps, drawn from the seed, and of the last step
are kept and compared with the plain reference, device block by device
block.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np

import gen

#: the numbers :func:`check` compares, each with a limit in the config file
COMPARED = ("a_gap", "p_rel_gap")
#: steps whose answers are kept for the check, besides the first and last
N_SAMPLED = 2
#: devices per block of the reference (host memory stays small)
REF_BLOCK = 1 << 18


class Setup:
    """What the window needs; built by :func:`setup`.  With
    ``program=False`` only the cell's data is drawn."""

    def __init__(self, ctx, program: bool = True):
        cfg, traffic = ctx.config, ctx.traffic
        n = int(cfg["n_cells"]) * int(cfg["devices_per_cell"])
        rng = gen.rng_for(ctx.seed, 2)
        self.dev = gen.devices(cfg, rng, n)
        n_rounds = int(traffic["rounds"])
        gains = gen.gauss_markov_gains(rng, (n,), n_rounds,
                                       float(cfg["coherence"]))
        # one contiguous [N, 1] host block per round
        self.gains = np.ascontiguousarray(np.moveaxis(gains, 1, 0)[..., None])
        self.statics = gen.statics(cfg)
        self.sample = set(gen.rng_for(ctx.seed, 3).integers(
            1, int(traffic["sample_below"]), size=N_SAMPLED).tolist()) | {0}
        self.kept: dict[int, tuple] = {}
        self.problem = self.solve = None
        if not program:
            return
        import jax
        import jax.numpy as jnp

        from repro.core import solve_joint_fused
        from repro.core.problem import WirelessFLProblem

        self.problem = WirelessFLProblem(
            **{k: jnp.asarray(v) for k, v in self.dev.items()},
            fading=None, n_rounds=1, **self.statics)
        self.solve = jax.jit(solve_joint_fused)
        for k in range(2):                 # compile and warm both buffers
            self.step(k)

    def control(self, reference, dtype: str) -> dict:
        """Answers of the reference in ``dtype`` put in the program's
        place for the sampled steps, and the facts a window reports."""
        for k in sorted(self.sample):
            a, p = reference.solve(self.dev, self.gains[k % len(self.gains),
                                                        :, 0],
                                   self.statics, dtype=dtype)
            self.kept[k] = (a[:, None], p[:, None])
        return {}

    def release(self) -> None:
        """Let go of the device-resident problem and the compiled solve."""
        self.problem = self.solve = None

    def step(self, k: int, spans=None):
        import jax

        t0 = time.perf_counter()
        fading = jax.device_put(self.gains[k % len(self.gains)])
        t1 = time.perf_counter()
        sol = self.solve(dataclasses.replace(self.problem, fading=fading))
        t2 = time.perf_counter()
        a = np.asarray(sol.a)
        p = np.asarray(sol.power)
        if spans is not None:
            t3 = time.perf_counter()
            spans.add("upload", t0, t1)
            spans.add("solve", t1, t2)
            spans.add("extract", t2, t3)
        return a, p


def setup(ctx) -> Setup:
    return Setup(ctx)


def window(st: Setup, ctx) -> dict:
    t0 = ctx.open_window()
    t_stop = t0 + ctx.seconds
    k = 0
    a = p = None
    while k == 0 or time.perf_counter() < t_stop:
        a, p = st.step(k, ctx.spans)
        if k in st.sample:
            st.kept[k] = (a.copy(), p.copy())
        k += 1
    t_end = ctx.close_window()
    st.kept[k - 1] = (a, p)
    n = len(st.gains[0])
    return {
        "end_to_end": {"solve_devices_per_s": n * k / (t_end - t0)},
        "attempted": k,
        "failed": int(sum(not (np.all(np.isfinite(x[0]))
                               and np.all(np.isfinite(x[1])))
                          for x in st.kept.values())),
        "window_s": t_end - t0,
        "steps": k,
        "n_devices": n,
        "counters": {},
    }


def check(st: Setup, ctx, facts: dict) -> dict:
    """The kept steps (``facts["steps_checked"]``) against the reference,
    block by block."""
    facts["steps_checked"] = sorted(st.kept)
    worst: dict[str, float] = {}
    for k, (a, p) in sorted(st.kept.items()):
        g = st.gains[k % len(st.gains), :, 0]
        for lo in range(0, len(g), REF_BLOCK):
            sl = slice(lo, lo + REF_BLOCK)
            got = ctx.compare({f: v[sl] for f, v in st.dev.items()}, g[sl],
                              st.statics, a[sl, 0], p[sl, 0])
            for name, v in got.items():
                worst[name] = max(worst.get(name, 0.0), v)
    return worst
