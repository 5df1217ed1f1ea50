"""The readers of the service's own spans and queue-wait counter.

A small ``FleetControlService`` serves a few rounds on the CPU under the
JAX profiler; its ``fleet_service.*`` annotations land on the trace's
host plane, where ``reduce.reduce_trace`` totals them by name.  Each
reader, run through a ``RunView`` of that reduction, gives a finite
positive number, and a batch's five phases fit inside the batch.  A run
of a program without the spans or the counter gives nothing to read,
and a traced run of the served cell, driven end to end, reports every
one of them.
"""
from __future__ import annotations

import math
import sys
from pathlib import Path
from types import SimpleNamespace as NS

import jax
import pytest

CHIP = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(CHIP))
sys.path.insert(0, str(CHIP.parents[1] / "src"))

import bench  # noqa: E402
import reduce  # noqa: E402

CELL = "metro_serve_bursty"
PHASES = ("pack_ms.serve", "seed_ms.serve", "solve_call_ms.serve",
          "readback_ms.serve", "respond_ms.serve")
READERS = ("queue_wait_ms.serve", "submit_us.serve", "key_us.serve",
           "batch_host_ms.serve") + PHASES
# sizes a test run holds (as in test_chip_bench_correct.py)
SMALL = ({"n_cells": 6}, {"burst_rate_hz": 80.0, "burst_len": 10,
                          "idle_s": 0.1, "static_cells": 2})


def _read(metric: str, view):
    return bench.load_module(CHIP / "metrics" / f"{metric}.py",
                             f"metric_{metric}").read(view)


@pytest.fixture(scope="module")
def view(tmp_path_factory):
    from jax.profiler import ProfileData

    from repro.core import make_problem, slice_round
    from repro.serve import FleetControlService, ServiceConfig

    cells = [make_problem("drifting_metro", seed=s, n_devices=16,
                          n_rounds=4) for s in range(5)]
    svc = FleetControlService(ServiceConfig(max_batch=4))
    svc.run([(c, slice_round(p, 0)) for c, p in enumerate(cells)])
    before = svc.stats.counter_summary()
    trace_dir = tmp_path_factory.mktemp("trace")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=options)
    try:
        for k in (1, 2, 3):
            svc.run([(c, slice_round(p, k)) for c, p in enumerate(cells)])
    finally:
        jax.profiler.stop_trace()
    after = svc.stats.counter_summary()
    counters = {k: after[k] - before[k] for k in after
                if isinstance(after[k], int)}
    data = ProfileData.from_file(
        str(sorted(trace_dir.rglob("*.xplane.pb"))[-1]))
    ends = [(ev.start_ns, ev.start_ns + ev.duration_ns)
            for plane in data.planes for line in plane.lines
            for ev in line.events]
    window = (min(s for s, _ in ends), max(e for _, e in ends) + 1)
    red = reduce.reduce_trace(data, window, [], chips=1)
    ctx = NS(config={}, traffic={})
    facts = {"counters": counters, "max_batch": svc.config.max_batch}
    return reduce.RunView(ctx, facts, red, bench.device_info(1))


@pytest.mark.parametrize("metric", READERS)
def test_reader_gives_a_finite_positive_value(view, metric):
    value = _read(metric, view)
    assert value is not None
    assert math.isfinite(value) and value > 0


def test_phases_fit_inside_the_batch(view):
    phases = sum(_read(m, view) for m in PHASES)
    assert phases <= _read("batch_host_ms.serve", view)


def test_nothing_to_read_without_the_spans_or_the_counter():
    # what a program without the spans and the counter leaves behind
    view = reduce.RunView(NS(config={}, traffic={}),
                          {"counters": {"solved": 5, "batches": 2}},
                          reduce.Reduction(), bench.device_info(1))
    for metric in READERS:
        assert _read(metric, view) is None, metric


def test_traced_serve_run_reports_every_reader():
    cfg, traffic = SMALL
    result, _ = bench.run(CELL, 2**31 + 11, 1.0, True, chip=False,
                          config_overrides=cfg, traffic_overrides=traffic)
    assert result["correct"], result["compared"]
    for metric in READERS:
        value = result["metrics"][metric]["value"]
        assert math.isfinite(value) and value > 0, metric
