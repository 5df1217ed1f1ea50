"""From a profiler trace and the benchmark's host spans to per-layer numbers.

The JAX profiler writes an ``.xplane.pb``; ``jax.profiler.ProfileData``
reads it.  On a TPU (see ``tests/data/fixture.xplane.pb``) each chip's
work lies on a plane ``/device:TPU:<i>``: its line ``XLA Modules`` holds
one event per execution of a compiled program, named after the jitted
function with a ``(<fingerprint>)`` suffix, and ``XLA Ops`` one event per
operation inside it.  The plane's other lines are not work: ``Steps``
spans whole steps, idle time included, and ``Async XLA Ops`` repeats
copies that run under the ops.  The host's runtime writes its own events
(dispatch, layout conversion, copies) on the lines of ``/host:CPU``.

* **busy** — the union of the intervals of the ``XLA Modules`` and
  ``XLA Ops`` events, clipped to the traced window; **idle** is the
  window less busy.  Overlapping events count once.
* **programs** — device seconds and executions per program, keyed by
  the module name without its suffix; **ops** — device seconds per HLO
  instruction name (``%fusion.3``).
* **host events** — seconds and count per event name on the host planes
  (``XlaLinearize``, ``tpu::System::TransferToDevice=>IssueEvent=>Done``
  and the like), for metrics of the host's runtime.
* **gaps** — the idle stretches of the window, cut where the
  benchmark's host spans open and close, each piece labelled by the span
  open at the time (``"none"`` where none was: the driver was waiting or
  polling).

The benchmark's spans are taken on ``time.perf_counter``; the profiler
has a clock of its own.  One annotation (``bench.anchor``) is written
into the trace while the host clock is read, and its start maps the one
clock onto the other.

Peaks come from ``peaks.json``, keyed by ``device_kind``; a kind that is
not in the table is an error, never a default.
"""
from __future__ import annotations

import json
import re
from collections import defaultdict
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
MODULE_LINE = "XLA Modules"
OP_LINE = "XLA Ops"
BUSY_LINES = (MODULE_LINE, OP_LINE)
ANCHOR = "bench.anchor"
_SUFFIX = re.compile(r"(\(\d+\)|\.\d+)+$")


def load_peaks(kind: str, path: Path = HERE / "peaks.json") -> dict:
    """The chip's published peaks; an unknown ``device_kind`` raises."""
    with open(path) as f:
        table = json.load(f)
    if kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {kind!r} in {path.name}; "
                       f"known: {sorted(table['devices'])}")
    return table["devices"][kind]


def program_name(event_name: str) -> str:
    """``jit_solve(12)`` -> ``jit_solve``."""
    return _SUFFIX.sub("", event_name)


def op_name(event_name: str) -> str:
    """``%fusion.3 = f32[...] fusion(...)`` -> ``%fusion.3``: an op event
    is named by its whole HLO instruction; the name is its first word."""
    return event_name.split(" = ", 1)[0]


def union_seconds(intervals: np.ndarray) -> tuple[float, np.ndarray]:
    """Total length of the union of ``[start, end)`` rows (ns), and the
    merged intervals, sorted."""
    if len(intervals) == 0:
        return 0.0, np.zeros((0, 2))
    iv = intervals[np.argsort(intervals[:, 0], kind="stable")]
    merged = [list(iv[0])]
    for s, e in iv[1:]:
        if s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    m = np.asarray(merged, np.float64)
    return float(np.sum(m[:, 1] - m[:, 0]) * 1e-9), m


class Reduction:
    """What one traced window reduces to (seconds unless named)."""

    def __init__(self):
        self.window_s = 0.0
        self.busy_s = 0.0           # mean over the chips
        self.programs: dict[str, list] = defaultdict(lambda: [0.0, 0])
        self.ops: dict[str, float] = defaultdict(float)
        self.host: dict[str, list] = defaultdict(lambda: [0.0, 0])
        self.gaps: list[tuple[str, float]] = []

    def program_seconds(self, word: str) -> tuple[float, int]:
        """Device seconds and executions of the programs whose name holds
        ``word``."""
        secs = sum(v[0] for k, v in self.programs.items() if word in k)
        n = sum(v[1] for k, v in self.programs.items() if word in k)
        return secs, n

    def idle_pct(self) -> float | None:
        """Percent of the window in which the device ran nothing."""
        if self.window_s <= 0:
            return None
        return 100.0 * (1.0 - self.busy_s / self.window_s)

    def host_seconds(self, names) -> tuple[float, int]:
        """Seconds and count of the host events called one of ``names``."""
        recs = [self.host[n] for n in names if n in self.host]
        return sum(r[0] for r in recs), sum(r[1] for r in recs)

    def breakdown(self) -> dict:
        """The ten costliest device ops, and idle time by host span."""
        ops = sorted(self.ops.items(), key=lambda kv: -kv[1])[:10]
        by_label: dict[str, float] = defaultdict(float)
        for label, secs in self.gaps:
            by_label[label] += secs
        gaps = sorted(by_label.items(), key=lambda kv: -kv[1])[:10]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in gaps]}


def reduce_trace(data, window_ns: tuple[float, float],
                 spans_ns: list[tuple[str, float, float]] = (),
                 chips: int = 1) -> Reduction:
    """Reduce a ``ProfileData`` over ``window_ns`` (trace clock)."""
    lo, hi = window_ns
    red = Reduction()
    red.window_s = (hi - lo) * 1e-9
    busy, n_dev = 0.0, 0
    for plane in data.planes:
        if plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if lo <= ev.start_ns < hi:
                        rec = red.host[ev.name]
                        rec[0] += ev.duration_ns * 1e-9
                        rec[1] += 1
            continue
        m = DEVICE_PLANE.match(plane.name)
        if not m or int(m.group(1)) >= chips:
            continue
        n_dev += 1
        iv = []
        for line in plane.lines:
            if line.name not in BUSY_LINES:
                continue
            for ev in line.events:
                s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                if e <= lo or s >= hi:
                    continue
                iv.append((max(s, lo), min(e, hi)))
                dur = ev.duration_ns * 1e-9
                if line.name == MODULE_LINE:
                    rec = red.programs[program_name(ev.name)]
                    rec[0] += dur
                    rec[1] += 1
                else:
                    red.ops[op_name(ev.name)] += dur
        secs, merged = union_seconds(np.asarray(iv, np.float64).reshape(-1, 2))
        busy += secs
        if n_dev == 1:
            red.gaps = label_gaps(merged, lo, hi, spans_ns)
    red.busy_s = busy / max(n_dev, 1)
    return red


def flatten_spans(spans_ns) -> list[tuple[str, float, float]]:
    """Host spans as sorted, non-overlapping labelled segments; where one
    span opens inside another, the inner one holds its stretch."""
    out: list[list] = []
    for name, s, e in sorted(spans_ns, key=lambda x: x[1]):
        if e <= s:
            continue
        if out and out[-1][2] > s:
            last = out.pop()
            if last[1] < s:
                out.append([last[0], last[1], s])
            out.append([name, s, e])
            if last[2] > e:
                out.append([last[0], e, last[2]])
        else:
            out.append([name, s, e])
    return [tuple(x) for x in out]


def label_gaps(merged: np.ndarray, lo: float, hi: float,
               spans_ns) -> list[tuple[str, float]]:
    """The idle stretches of ``[lo, hi)`` between the busy ``merged``
    intervals, cut where the host spans open and close: ``(label,
    seconds)`` pieces, labelled by the span open at the time (``"none"``
    where none was)."""
    edges = np.concatenate([[lo], merged.reshape(-1), [hi]]).reshape(-1, 2)
    segs = flatten_spans(spans_ns)
    out, j = [], 0
    for s, e in edges:
        if e <= s:
            continue
        while j < len(segs) and segs[j][2] <= s:
            j += 1
        t, k = s, j
        while k < len(segs) and segs[k][1] < e:
            name, a, b = segs[k]
            if a > t:
                out.append(("none", (a - t) * 1e-9))
            a, b = max(a, t), min(b, e)
            if b > a:
                out.append((name, (b - a) * 1e-9))
                t = b
            k += 1
        if e > t:
            out.append(("none", (e - t) * 1e-9))
    return out


def find_anchor(data) -> float:
    """Start (trace clock, ns) of the ``bench.anchor`` annotation."""
    for plane in data.planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name == ANCHOR:
                    return float(ev.start_ns)
    raise ValueError(f"no {ANCHOR!r} event in the trace")


def reduce_run(ctx, device: dict) -> Reduction:
    """Reduce the trace that ``ctx``'s window wrote."""
    from jax.profiler import ProfileData

    found = sorted(Path(ctx.trace_dir).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no trace under {ctx.trace_dir}")
    data = ProfileData.from_file(str(found[-1]))
    # host clock (s) -> trace clock (ns), through the anchor
    offset = find_anchor(data) - ctx.anchor[0] * 1e9

    def to_ns(t):
        return t * 1e9 + offset

    spans = [(n, to_ns(a), to_ns(b)) for n, a, b in ctx.spans.items]
    return reduce_trace(data, (to_ns(ctx.t_open), to_ns(ctx.t_close)),
                        spans, chips=int(device["count"]))


class RunView:
    """What a metric reader sees of one traced run."""

    def __init__(self, ctx, facts: dict, red: Reduction, device: dict):
        self.ctx, self.facts, self.trace, self.device = ctx, facts, red, device
        self.config, self.traffic = ctx.config, ctx.traffic

    def peaks(self) -> dict:
        return load_peaks(self.device["kind"])
