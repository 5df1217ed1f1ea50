"""Profiler spans of a coupled metro tick.

While a ``jax.profiler`` trace is being taken,
``FleetControlService.solve_coupled`` writes ``fleet_service.metro``
around the tick and ``fleet_service.metro_pad`` around its padding, and
the outer loop of ``core.multicell.solve_coupled`` writes, per outer
iteration, ``multicell.solve``, ``multicell.readback``,
``multicell.price`` and ``multicell.interference`` (one more
``multicell.interference`` before the loop builds the first leaf).  The
children nest inside their tick and cover at least 90% of it.  With no
trace running no annotation is built, and the answers are the same.
"""
import dataclasses

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData, TraceAnnotation

from repro.core import multicell as mcm
from repro.core.multicell import hex_coupling, make_multicell
from repro.core.problem import sample_problem
from repro.serve import FleetControlService, ServiceConfig
from repro.serve import fleet_service as fs

LOOP = (mcm.SPAN_SOLVE, mcm.SPAN_READBACK, mcm.SPAN_PRICE,
        mcm.SPAN_INTERFERENCE)
CHILDREN = (fs.SPAN_METRO_PAD,) + LOOP
NAMES = (fs.SPAN_METRO,) + CHILDREN
N_DEVICES = 16


@pytest.fixture(scope="module")
def metro():
    """An R = 1 metro (21 cells) under a binding backhaul budget."""
    cells = [sample_problem(7_001 * c, N_DEVICES) for c in range(21)]
    s_bits = cells[0].grad_size_bits
    return make_multicell(cells, hex_coupling(1, 500.0, n_points=16, seed=5),
                          backhaul_bits=0.6 * 2.1 * 21 * s_bits)


def _drifted(mc, k):
    """``mc`` with round ``k``'s seeded fading on every device."""
    rng = np.random.default_rng(k)
    problem = dataclasses.replace(
        mc.cells.problem, fading=rng.exponential(
            size=mc.cells.mask.shape + (1,)).astype(np.float32))
    return dataclasses.replace(
        mc, cells=dataclasses.replace(mc.cells, problem=problem))


def _warm_service(mc):
    """A service that has served one tick outside any trace, so the loop's
    programs are compiled and the metro's duals are cached."""
    svc = FleetControlService(ServiceConfig())
    svc.solve_coupled("m", _drifted(mc, 0))
    return svc


def _traced(fn, trace_dir):
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=options)
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    path = sorted(trace_dir.rglob("*.xplane.pb"))[-1]
    data = ProfileData.from_file(str(path))
    events = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
              for plane in data.planes for line in plane.lines
              for ev in line.events if ev.name in NAMES]
    return out, sorted(events, key=lambda e: e[1])


def _inside(ev, outer) -> bool:
    return outer[1] <= ev[1] and ev[2] <= outer[2]


@pytest.fixture(scope="module")
def ticks(metro, tmp_path_factory):
    """Three warm ticks on drifting channels, traced."""
    svc = _warm_service(metro)
    before = svc.stats.counter_summary()
    out, events = _traced(
        lambda: [svc.solve_coupled("m", _drifted(metro, k))
                 for k in (1, 2, 3)],
        tmp_path_factory.mktemp("trace"))
    after = svc.stats.counter_summary()
    iters = after["metro_outer_iters"] - before["metro_outer_iters"]
    return out, events, iters


def test_every_span_name_is_in_the_trace(ticks):
    _, events, _ = ticks
    assert {e[0] for e in events} == set(NAMES)


def test_span_counts_per_tick_and_per_iteration(ticks):
    out, events, iters = ticks
    count = {n: sum(e[0] == n for e in events) for n in NAMES}
    assert iters == sum(r.solution.outer_iters for r in out) >= 3
    assert count[fs.SPAN_METRO] == count[fs.SPAN_METRO_PAD] == 3
    for name in (mcm.SPAN_SOLVE, mcm.SPAN_READBACK, mcm.SPAN_PRICE):
        assert count[name] == iters, name
    # one leaf per iteration: the first before the loop
    assert count[mcm.SPAN_INTERFERENCE] == iters + 3


def test_children_nest_and_cover_the_tick(ticks):
    _, events, _ = ticks
    metros = [e for e in events if e[0] == fs.SPAN_METRO]
    for ev in events:
        if ev[0] in CHILDREN:
            assert sum(_inside(ev, m) for m in metros) == 1, ev
    for m in metros:
        inner = [e for e in events if e[0] in CHILDREN and _inside(e, m)]
        assert inner[0][0] == fs.SPAN_METRO_PAD
        # the children tile the tick without overlapping
        for a, b in zip(inner, inner[1:]):
            assert a[2] <= b[1]
        covered = sum(e[2] - e[1] for e in inner)
        assert covered >= 0.9 * (m[2] - m[1]), (covered, m[2] - m[1])


class _Counting(TraceAnnotation):
    made = 0

    def __init__(self, *args, **kwargs):
        _Counting.made += 1
        super().__init__(*args, **kwargs)


def test_no_annotation_is_built_without_a_trace(metro, monkeypatch,
                                                 tmp_path):
    svc = _warm_service(metro)
    monkeypatch.setattr(fs, "TraceAnnotation", _Counting)
    monkeypatch.setattr(mcm, "TraceAnnotation", _Counting)
    _Counting.made = 0
    plain = svc.solve_coupled("m", _drifted(metro, 1)).solution
    assert _Counting.made == 0
    # the guard is not vacuous: under a trace the same tick builds one
    # annotation per span, and hands back the same answers
    traced_svc = _warm_service(metro)
    traced, _ = _traced(
        lambda: traced_svc.solve_coupled("m", _drifted(metro, 1)).solution,
        tmp_path)
    assert _Counting.made == 2 + 4 * traced.outer_iters + 1
    assert traced.outer_iters == plain.outer_iters
    for x, y in ((traced.batch.a, plain.batch.a),
                 (traced.batch.power, plain.batch.power),
                 (traced.solved_at, plain.solved_at), (traced.mu, plain.mu)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
