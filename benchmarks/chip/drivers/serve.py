"""Open-loop serving: per-cell solve requests through ``FleetControlService``.

Set-up builds a metro of ``n_cells`` drifting cells from the seed (the
mix may hold the channel of its first ``static_cells`` still), starts
the service with its defaults, runs its ahead-of-time ``warmup`` and
serves round 0 of every cell, so that every cell has cached state when
the window opens, as it would in a deployment that has been running.

The window is an open loop, as in the program's ``serve/load_gen.drive``:
each request is submitted when it is due whatever the service is doing,
and ``poll`` is pumped in between.  A request's latency runs from the
time it was due to the moment ``poll`` handed its answer back, so a
stalled driver counts against the requests behind it.  After the last
arrival the queue is drained, for at most ``DRAIN_S``.

Every answer is then compared with the plain reference on the same
cell-round problem.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np

import gen

#: the numbers :func:`check` compares, each with a limit in the config file
COMPARED = ("unanswered", "repeated", "shed", "a_gap", "p_rel_gap")
DRAIN_S = 60.0      # how long an answer may come after the window closes
INF_MS = 1e9        # the stand-in for an unanswered request's latency


class Setup:
    """What the window needs; built by :func:`setup`.  With
    ``program=False`` only the cell's data is drawn (the control's runs
    need no service)."""

    def __init__(self, ctx, program: bool = True):
        cfg, traffic, seed = ctx.config, ctx.traffic, ctx.seed
        self.n_cells = int(cfg["n_cells"])
        n = int(cfg["devices_per_cell"])
        self.arrivals = gen.arrivals(traffic, self.n_cells, ctx.seconds,
                                     gen.rng_for(seed, 1))
        per_cell = np.bincount([a.cell for a in self.arrivals],
                               minlength=self.n_cells)
        n_rounds = 1 + int(per_cell.max(initial=0))
        rng = gen.rng_for(seed, 2)
        self.dev = [gen.devices(cfg, rng, n) for _ in range(self.n_cells)]
        self.gains = gen.gauss_markov_gains(rng, (self.n_cells, n), n_rounds,
                                            float(cfg["coherence"]))
        # the mix's first ``static_cells`` cells see a channel that holds
        # still, so their cached answers stay valid (stale-tolerant cells)
        n_static = int(traffic.get("static_cells", 0))
        self.gains[:n_static] = self.gains[:n_static, :, :1]
        self.statics = gen.statics(cfg)
        self.answers: dict[int, tuple] = {}
        self.service = None
        if not program:
            return
        from repro.core.problem import WirelessFLProblem
        from repro.serve import FleetControlService, ServiceConfig

        base = [WirelessFLProblem(**d, fading=None, n_rounds=1,
                                  **self.statics) for d in self.dev]

        def request(cell, k):
            # host arrays, as a base station would send them
            return dataclasses.replace(
                base[cell], fading=self.gains[cell, :, k:k + 1])

        self.requests = [request(a.cell, a.round_k) for a in self.arrivals]
        self.service = FleetControlService(ServiceConfig())
        self.service.warmup(request(0, 0))
        self.service.run([(c, request(c, 0)) for c in range(self.n_cells)])

    def release(self) -> None:
        """Let go of the service and what it holds on the device."""
        self.service = None

    def stacked(self, idx) -> tuple[dict, np.ndarray]:
        """Per-device leaves ``[M, N]`` and gains ``[M, N]`` of requests
        ``idx``."""
        cells = np.array([self.arrivals[i].cell for i in idx], dtype=int)
        rounds = np.array([self.arrivals[i].round_k for i in idx], dtype=int)
        dev = {f: np.stack([d[f] for d in self.dev])[cells]
               for f in gen.DEVICE_FIELDS}
        return dev, self.gains[cells, :, rounds]

    def control(self, reference, dtype: str) -> dict:
        """Answers of the reference in ``dtype`` put in the program's
        place, and the facts a window would report with them."""
        idx = list(range(len(self.arrivals)))
        dev, gain = self.stacked(idx)
        a, p = reference.solve(dev, gain, self.statics, dtype=dtype)
        self.answers = {i: (a[i], p[i], False) for i in idx}
        return {"unanswered": 0, "repeated": 0, "shed": 0}


def setup(ctx) -> Setup:
    return Setup(ctx)


def window(st: Setup, ctx) -> dict:
    """Drive the open loop for ``ctx.seconds``; returns the run's facts."""
    svc, arr, reqs = st.service, st.arrivals, st.requests
    n = len(arr)
    sched = np.array([a.t for a in arr])
    submitted = np.full(n, np.nan)
    answered = np.full(n, np.nan)
    repeats = 0
    answers = st.answers
    seq_index: dict[int, int] = {}
    spans = ctx.spans
    before = svc.stats.counter_summary()

    def pump():
        nonlocal repeats
        t_in = time.perf_counter()
        out = svc.poll()
        if not out:
            return
        t_out = time.perf_counter()
        spans.add("serve_batch", t_in, t_out)
        for r in out:
            i = seq_index.get(r.seq)
            if i is None:
                continue
            if not np.isnan(answered[i]):
                repeats += 1
                continue
            answered[i] = t_out
            answers[i] = (r.solution.a, r.solution.power, bool(r.shed))

    t0 = ctx.open_window()
    i = 0
    while i < n:
        due = t0 + sched[i]
        while time.perf_counter() < due:
            pump()
        now = time.perf_counter()
        while i < n and t0 + sched[i] <= now:
            t_in = time.perf_counter()
            req = svc.submit(arr[i].cell, reqs[i], now=t0 + sched[i])
            submitted[i] = time.perf_counter()
            spans.add("submit", t_in, submitted[i])
            seq_index[req.seq] = i
            i += 1
        pump()
    t_last = t0 + ctx.seconds
    while svc.pending and time.perf_counter() < t_last + DRAIN_S:
        pump()
    t_end = ctx.close_window()

    after = svc.stats.counter_summary()
    counters = {k: after[k] - before[k] for k in after
                if isinstance(after[k], int)}
    latency_ms = (answered - (t0 + sched)) * 1e3
    batch_ms = [(b - a) * 1e3 for name, a, b in spans.items
                if name == "serve_batch"]
    shed = sum(1 for v in answers.values() if v[2])
    unanswered = int(np.isnan(answered).sum())
    lat = np.where(np.isnan(latency_ms), np.inf, latency_ms)
    with np.errstate(invalid="ignore"):      # inf - inf between two misses
        p99 = float(np.percentile(lat, 99)) if n else float("nan")
    return {
        "end_to_end": {"serve_p99_ms": p99 if np.isfinite(p99) else INF_MS},
        "attempted": n,
        "failed": unanswered + shed,
        "window_s": t_end - t0,
        "counters": counters,
        # the host's longest batches, to tell one stall from a slow stretch
        "host": {"batch_ms_p50": float(np.median(batch_ms)) if batch_ms
                 else None,
                 "batch_ms_max": max(batch_ms, default=None),
                 "batches_over_100ms": sum(b > 100.0 for b in batch_ms)},
        "max_batch": svc.config.max_batch,
        "lateness_ms": (submitted - (t0 + sched)) * 1e3,
        "latency_ms": latency_ms,
        "unanswered": unanswered,
        "repeated": repeats,
        "shed": shed,
    }


def check(st: Setup, ctx, facts: dict) -> dict:
    """Every answer against the reference: ``{name: value}``."""
    idx = sorted(st.answers)
    out = {"unanswered": facts["unanswered"], "repeated": facts["repeated"],
           "shed": facts["shed"]}
    if not idx:
        out["a_gap"] = out["p_rel_gap"] = float("inf")
        return out
    dev, gain = st.stacked(idx)
    a = np.stack([np.asarray(st.answers[i][0]).reshape(-1) for i in idx])
    p = np.stack([np.asarray(st.answers[i][1]).reshape(-1) for i in idx])
    out.update(ctx.compare(dev, gain, st.statics, a, p))
    return out
