"""The trace reduction, on hand-built events and on a recorded trace.

``tests/data/fixture.xplane.pb`` was recorded on one TPU v5e by
``record_fixture.py``: three rounds of an upload, two named programs
(``fixture_elementwise``, ``fixture_matmul``), a read back and a host
pause, each inside a host span.
"""
from __future__ import annotations

import sys
from pathlib import Path
from types import SimpleNamespace as NS

import numpy as np
import pytest

CHIP = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(CHIP))

import reduce  # noqa: E402
import roofline  # noqa: E402

FIXTURE = CHIP / "tests" / "data" / "fixture.xplane.pb"


def _ev(name, start, dur):
    return NS(name=name, start_ns=float(start), duration_ns=float(dur))


def _profile(*planes):
    return NS(planes=[NS(name=n, lines=[NS(name=ln, events=evs)
                                        for ln, evs in lines])
                      for n, lines in planes])


def test_union_counts_overlap_once():
    iv = np.array([[0, 10], [5, 15], [20, 30], [21, 22], [30, 31]], float)
    secs, merged = reduce.union_seconds(iv)
    assert secs == pytest.approx(26e-9)
    assert merged.tolist() == [[0, 15], [20, 31]]
    assert reduce.union_seconds(np.zeros((0, 2)))[0] == 0.0


def test_busy_programs_and_gaps_on_built_events():
    data = _profile(
        ("/device:TPU:0", [
            ("XLA Modules", [_ev("jit_solve(3)", 100, 50),
                             _ev("jit_solve(3)", 300, 50),
                             _ev("jit_other.1", 500, 100)]),
            # ops nest inside their module: busy counts them once
            ("XLA Ops", [_ev("fusion.1", 110, 20), _ev("fusion.1", 310, 30),
                         _ev("copy.2", 560, 60)]),
            # step markers span idle time too: not work
            ("Steps", [_ev("0", 0, 1000)]),
        ]),
        ("/host:CPU", [("main/1", [_ev("bench.anchor", 0, 1),
                                   _ev("XlaLinearize", 10, 30),
                                   _ev("XlaLinearize", 400, 30),
                                   _ev("XlaLinearize", 2000, 30)])]),
        # a second chip is left out of a one-chip cell
        ("/device:TPU:1", [("XLA Modules", [_ev("jit_solve(3)", 0, 900)])]),
    )
    spans = [("solve", 90, 160), ("extract", 160, 290), ("upload", 380, 480)]
    red = reduce.reduce_trace(data, (0, 1000), spans, chips=1)
    assert red.window_s == pytest.approx(1e-6)
    # [100,150) [300,350) [500,620): 220 ns
    assert red.busy_s == pytest.approx(220e-9)
    assert red.idle_pct() == pytest.approx(78.0)
    assert red.program_seconds("jit_solve") == (pytest.approx(100e-9), 2)
    assert red.program_seconds("jit_other") == (pytest.approx(100e-9), 1)
    assert red.ops["fusion.1"] == pytest.approx(50e-9)
    # host events by name, the one outside the window left out
    assert red.host_seconds(["XlaLinearize", "absent"]) == \
        (pytest.approx(60e-9), 2)
    # gaps [0,100) [150,300) [350,500) [620,1000), cut by the spans:
    # [90,160) solve, [160,290) extract, [380,480) upload
    assert [(lbl, round(s * 1e9)) for lbl, s in red.gaps] == [
        ("none", 90), ("solve", 10),
        ("solve", 10), ("extract", 130), ("none", 10),
        ("none", 30), ("upload", 100), ("none", 20),
        ("none", 380)]
    bd = red.breakdown()
    assert bd["idle_gaps"][0] == ["none", pytest.approx(530e-9)]
    assert len(bd["device_ops"]) <= 10


def test_window_clips_events():
    data = _profile(("/device:TPU:0", [("XLA Modules", [
        _ev("jit_a", -50, 100), _ev("jit_a", 90, 40)])]))
    red = reduce.reduce_trace(data, (0, 100), [], chips=1)
    assert red.busy_s == pytest.approx(60e-9)     # [0,50) and [90,100)


def test_nested_spans_give_way_to_the_inner_one():
    segs = reduce.flatten_spans([("outer", 0, 100), ("inner", 20, 30),
                                 ("next", 90, 120)])
    assert segs == [("outer", 0, 20), ("inner", 20, 30), ("outer", 30, 90),
                    ("next", 90, 120)]


def test_op_name_is_the_instruction_name():
    assert reduce.op_name("%subtract_abs_fusion.1 = (f32[8]{0}) fusion("
                          "f32[8]{0} %p), kind=kLoop") == \
        "%subtract_abs_fusion.1"
    assert reduce.op_name("fusion.1") == "fusion.1"


def test_program_name_strips_ids():
    assert reduce.program_name("jit_solve_joint_fused(140)") == \
        "jit_solve_joint_fused"
    assert reduce.program_name("jit__solve_batch_fused.3") == \
        "jit__solve_batch_fused"


def test_roofline_bytes_of_a_known_shape():
    # 10^6 devices: 8 f32 leaves read, a* and P* written
    assert roofline.fused_solve_bytes(1_000_000) == 40_000_000
    # 40 MB at 819 GB/s is 48.84 us; in 97.68 us that is half the roofline
    t_min = 40e6 / 819e9
    assert roofline.roofline_share(40e6, 2 * t_min, 819e9) == \
        pytest.approx(50.0)


@pytest.fixture(scope="module")
def fixture_trace():
    from jax.profiler import ProfileData

    return ProfileData.from_file(str(FIXTURE))


def _annotations(data):
    return [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
            for plane in data.planes for line in plane.lines
            for ev in line.events if ev.name.startswith("bench.")]


def test_recorded_trace_programs_and_busy(fixture_trace):
    spans = _annotations(fixture_trace)
    lo = min(s for _, s, _ in spans)
    hi = max(e for _, _, e in spans)
    red = reduce.reduce_trace(fixture_trace, (lo, hi), spans, chips=1)
    elem, n_elem = red.program_seconds("jit_fixture_elementwise")
    mat, n_mat = red.program_seconds("jit_fixture_matmul")
    assert (n_elem, n_mat) == (3, 3)
    assert set(red.programs) == {"jit_fixture_elementwise",
                                 "jit_fixture_matmul"}
    # the two programs never overlap and their ops lie inside them
    assert red.busy_s == pytest.approx(elem + mat, rel=1e-4)
    assert 10e-6 < elem / 3 < 20e-6 and 10e-6 < mat / 3 < 20e-6
    # the copies of the three rounds, one each way, on the host planes
    up = "tpu::System::TransferToDevice=>IssueEvent=>Done"
    down = "tpu::System::TransferFromDevice=>IssueEvent=>Done"
    assert red.host_seconds([up])[1] == 3
    assert red.host_seconds([down])[1] == 3
    assert red.host_seconds(["XlaLinearize"])[1] == 3


def test_recorded_trace_gaps_follow_host_spans(fixture_trace):
    spans = _annotations(fixture_trace)
    lo = min(s for _, s, _ in spans)
    hi = max(e for _, _, e in spans)
    red = reduce.reduce_trace(fixture_trace, (lo, hi), spans, chips=1)
    idle = dict(red.breakdown()["idle_gaps"])
    assert red.window_s - red.busy_s == pytest.approx(sum(idle.values()))
    pause = sum(e - s for n, s, e in spans if n == "bench.host_pause")
    # the device does nothing during the host pauses, so all of them
    # is idle and named for the pause
    assert idle["bench.host_pause"] == pytest.approx(pause * 1e-9, rel=1e-3)
    assert max(idle, key=idle.get) == "bench.host_pause"
    assert reduce.find_anchor(_profile(("/host:CPU", [("main/1", [
        _ev("x", 1, 1), _ev("bench.anchor", 42, 1)])]))) == 42.0
