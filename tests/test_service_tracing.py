"""Profiler spans and the queue-wait counter of the fleet control plane.

While a ``jax.profiler`` trace is being taken, ``FleetControlService``
writes ``fleet_service.*`` annotations: a ``submit`` span per request
(with its ``key`` inside), a ``serve`` span per answered batch and, on a
solved batch, five children that tile it (``pack``, ``seed``,
``solve``, ``readback`` twice, ``respond``).  With no trace running it
builds no annotation at all.  ``ServiceStats.queue_wait_us`` sums each
answered request's wait from submit to its batch's close stamp.
"""
import jax
import pytest
from jax.profiler import ProfileData, TraceAnnotation

from repro.core import make_problem, slice_round
from repro.serve import CLOSE_FORCED, FleetControlService, ServiceConfig
from repro.serve import fleet_service as fs

CHILDREN = (fs.SPAN_PACK, fs.SPAN_SEED, fs.SPAN_SOLVE, fs.SPAN_READBACK,
            fs.SPAN_RESPOND)
NAMES = (fs.SPAN_SUBMIT, fs.SPAN_KEY, fs.SPAN_SERVE) + CHILDREN
N_DEVICES = 8       # one device bucket (min_device_bucket)


def _cells(n_cells=3, n_rounds=3):
    return [make_problem("drifting_metro", seed=s, n_devices=N_DEVICES,
                         n_rounds=n_rounds) for s in range(n_cells)]


def _warm_service(cells, **cfg):
    """A service that has served round 0 of every cell (outside any
    trace), so its programs are compiled and its caches hold state."""
    svc = FleetControlService(ServiceConfig(max_batch=4, **cfg))
    svc.run([(c, slice_round(p, 0)) for c, p in enumerate(cells)])
    return svc


def _traced(fn, trace_dir):
    """``fn()`` under a profiler trace: its result and the trace's
    ``fleet_service.*`` events as ``(name, start_ns, end_ns, stats)``."""
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=options)
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    path = sorted(trace_dir.rglob("*.xplane.pb"))[-1]
    data = ProfileData.from_file(str(path))
    events = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
               dict(ev.stats))
              for plane in data.planes for line in plane.lines
              for ev in line.events if ev.name.startswith("fleet_service.")]
    return out, sorted(events, key=lambda e: e[1])


def _seqs(stats) -> set:
    # one seq is read back as an int, several as a space-separated string
    return {int(s) for s in str(stats["seqs"]).split()}


def _inside(ev, outer) -> bool:
    return outer[1] <= ev[1] and ev[2] <= outer[2]


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """Two rounds of three cells, drained by forced closes, traced."""
    cells = _cells()
    svc = _warm_service(cells)
    before = svc.stats.counter_summary()
    requests = [(c, slice_round(p, k)) for k in (1, 2)
                for c, p in enumerate(cells)]
    responses, events = _traced(lambda: svc.run(requests),
                                tmp_path_factory.mktemp("trace"))
    after = svc.stats.counter_summary()
    n_batches = after["batches"] - before["batches"]
    assert after["retries"] == before["retries"]
    return responses, events, n_batches, len(requests)


def test_every_span_name_is_in_the_trace(served):
    _, events, _, _ = served
    assert {e[0] for e in events} == set(NAMES)


def test_children_nest_inside_their_batch(served):
    _, events, _, _ = served
    batches = [e for e in events if e[0] == fs.SPAN_SERVE]
    submits = [e for e in events if e[0] == fs.SPAN_SUBMIT]
    for ev in events:
        if ev[0] in CHILDREN:
            assert sum(_inside(ev, b) for b in batches) == 1, ev
        elif ev[0] == fs.SPAN_KEY:
            assert sum(_inside(ev, s) for s in submits) == 1, ev
    # each batch holds its own five children, two of them read backs
    for b in batches:
        inner = [e[0] for e in events if e[0] in CHILDREN and _inside(e, b)]
        assert sorted(inner) == sorted(CHILDREN + (fs.SPAN_READBACK,))
        # in order: pack, seed, solve, readback, ..., readback, respond
        assert inner[:3] == list(CHILDREN[:3])
        assert inner[-1] == fs.SPAN_RESPOND


def test_span_counts_per_request_and_per_batch(served):
    _, events, n_batches, n_requests = served
    count = {n: sum(e[0] == n for e in events) for n in NAMES}
    assert n_batches >= 2
    for name in (fs.SPAN_SERVE, fs.SPAN_PACK, fs.SPAN_SEED, fs.SPAN_SOLVE,
                 fs.SPAN_RESPOND):
        assert count[name] == n_batches, name
    assert count[fs.SPAN_READBACK] == 2 * n_batches
    assert count[fs.SPAN_SUBMIT] == count[fs.SPAN_KEY] == n_requests


def test_batch_seqs_join_the_submit_spans(served):
    responses, events, _, n_requests = served
    by_seq = {r.seq: r for r in responses}
    submits = {e[3]["seq"]: e[3] for e in events if e[0] == fs.SPAN_SUBMIT}
    assert set(submits) == set(by_seq) and len(submits) == n_requests
    seen: set = set()
    for _, _, _, meta in (e for e in events if e[0] == fs.SPAN_SERVE):
        seqs = _seqs(meta)
        assert seqs and not seqs & seen
        seen |= seqs
        assert meta["size"] == len(seqs)
        assert meta["reason"] == CLOSE_FORCED
        assert meta["bucket"] == N_DEVICES
        for seq in seqs:
            # the request's intake names its cell and the batch's lane
            assert submits[seq]["cell"] == by_seq[seq].cell_id
            assert submits[seq]["lane"] == meta["lane"]
    assert seen == set(submits)
    # batch indices follow the stats' batch count, one apart
    index = sorted(e[3]["batch"] for e in events if e[0] == fs.SPAN_SERVE)
    assert index == list(range(index[0], index[0] + len(index)))


def test_shed_batch_has_a_serve_span_and_no_solve(tmp_path):
    cells = _cells(n_cells=1)
    svc = _warm_service(cells)
    svc._breaker_open[N_DEVICES] = 1          # force the breaker open
    responses, events = _traced(
        lambda: svc.run([(0, slice_round(cells[0], 1))]), tmp_path)
    assert [r.shed for r in responses] == [True]
    names = [e[0] for e in events]
    assert names.count(fs.SPAN_SERVE) == 1
    assert not set(names) & set(CHILDREN)
    serve, = (e for e in events if e[0] == fs.SPAN_SERVE)
    assert _seqs(serve[3]) == {responses[0].seq}


class _Counting(TraceAnnotation):
    made = 0

    def __init__(self, *args, **kwargs):
        _Counting.made += 1
        super().__init__(*args, **kwargs)


def test_no_annotation_is_built_without_a_trace(monkeypatch, tmp_path):
    cells = _cells()
    svc = _warm_service(cells)
    monkeypatch.setattr(fs, "TraceAnnotation", _Counting)
    _Counting.made = 0
    svc.run([(c, slice_round(p, 1)) for c, p in enumerate(cells)])
    svc._breaker_open[N_DEVICES] = 1
    svc.run([(0, slice_round(cells[0], 2))])           # a shed batch too
    assert _Counting.made == 0
    # the guard is not vacuous: under a trace the same calls build one
    # annotation per span
    batches = svc.stats.n_batches
    _traced(lambda: svc.run([(c, slice_round(p, 2))
                             for c, p in enumerate(cells)]), tmp_path)
    n_batches = svc.stats.n_batches - batches
    assert _Counting.made == 2 * len(cells) + 7 * n_batches


def _virtual_service(**cfg):
    return FleetControlService(ServiceConfig(max_batch=4, cost_smoothing=0.0,
                                             **cfg))


def test_queue_wait_us_of_a_forced_close():
    cell, = _cells(n_cells=1)
    svc = _virtual_service()
    for t_ms in (0, 1, 2):
        svc.submit(t_ms, slice_round(cell, 0), now=t_ms * 1e-3)
    assert len(svc.step(now=10e-3)) == 3
    # (10 - 0) + (10 - 1) + (10 - 2) ms
    assert svc.stats.counter_summary()["queue_wait_us"] == 27_000
    svc.stats.reset()
    assert svc.stats.counter_summary()["queue_wait_us"] == 0


def test_queue_wait_us_counts_shed_responses():
    cell, = _cells(n_cells=1)
    svc = _virtual_service()
    svc._breaker_open[N_DEVICES] = 1
    for t_ms in (0, 1, 2):
        svc.submit(t_ms, slice_round(cell, 0), now=t_ms * 1e-3)
    responses = svc.step(now=10e-3)
    assert all(r.shed for r in responses)
    counters = svc.stats.counter_summary()
    assert counters["shed"] == 3
    assert counters["queue_wait_us"] == 27_000


def test_queue_wait_us_uses_the_poll_close_stamp():
    cell, = _cells(n_cells=1)
    svc = _virtual_service()
    svc.submit(0, slice_round(cell, 0), now=0.0)
    assert svc.poll(now=1e-3) == []                  # lingering
    # the oldest request has waited max_linger_s (5 ms): closed at 6 ms
    assert len(svc.poll(now=6e-3)) == 1
    assert svc.stats.counter_summary()["queue_wait_us"] == 6_000
