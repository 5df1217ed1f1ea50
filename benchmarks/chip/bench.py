"""Run one benchmark cell once on the chip and print one JSON result line.

    python3 benchmarks/chip/bench.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

Everything is found by name from ``BENCHMARK.json``: the cell names its
configuration (``configs/<config>.json``, with the plain reference that
the file names beside it) and its traffic mix (``traffic/<mix>.json``),
and the mix names the driver (``drivers/<driver>.py``) whose window it
runs.  Each per-layer metric is read by ``metrics/<metric>.py``.  A
later cell or metric adds files and entries; it edits none of these.

A run: check that JAX sees a TPU with the chips the cell asks for (and
fail, printing no result, when it does not); turn on the persistent
compilation cache inside the checkout; build the cell's data from the
seed and warm up its programs (``setup_s`` is the process's age when the
window opens); measure for ``--seconds``, counting XLA compilations in
the window; read the device's peak memory; then compare every answer
that the window produced, or a sample of them drawn from the seed, with
the plain reference.  ``--trace 1`` runs the same window under the JAX
profiler and reports the per-layer metrics instead of the end-to-end
ones.  The last lines of standard error give each compared number beside
its limit; the last line of standard output is the result.
"""
from __future__ import annotations

import argparse
import gc
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
SPEC_PATH = ROOT / "BENCHMARK.json"
sys.path.insert(0, str(HERE))

#: JAX's event for one real backend compilation (as in the program's
#: ``analysis/recompile.CompileBudget``); cache hits emit nothing
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
#: the stand-in printed for a compared number that is not finite
NOT_FINITE = 1e300


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def process_age_s() -> float:
    """Seconds since this process started (Linux), else since import."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = float(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return uptime - start_ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return time.perf_counter() - _IMPORTED


_IMPORTED = time.perf_counter()


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


class Spans:
    """Host spans recorded by the benchmark around calls into each layer,
    on the host clock (``time.perf_counter`` seconds)."""

    def __init__(self):
        self.items: list[tuple[str, float, float]] = []

    def add(self, name: str, t0: float, t1: float) -> None:
        self.items.append((name, t0, t1))


class Context:
    """What a driver sees: the cell's data files, the seed and the window."""

    def __init__(self, cell: dict, config: dict, traffic: dict, seed: int,
                 seconds: float, trace: bool, reference):
        self.cell, self.config, self.traffic = cell, config, traffic
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.reference = reference
        self.spans = Spans()
        self.compiles = 0
        self.t_open = self.t_close = None
        self.setup_s = None
        self.trace_dir = None
        self.anchor = None

    def _on_event(self, event: str, duration: float, **_kw) -> None:
        if event == COMPILE_EVENT:
            self.compiles += 1

    def open_window(self) -> float:
        """Ends set-up and opens the measured window; returns its start."""
        import jax

        self.setup_s = process_age_s()
        # as a latency-bound Python server does once it has started: what
        # set-up left behind (the drawn data, the compiled programs) is
        # moved out of the collector's reach, so that no full collection
        # inside the window walks it (one took 46 ms on 124k objects)
        gc.freeze()
        if self.trace:
            self.trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
            # the Python tracer would log every call of the host loop
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(self.trace_dir, profiler_options=options)
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.anchor"):
                t1 = time.perf_counter()
            self.anchor = (t0, t1)
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        self.t_open = time.perf_counter()
        return self.t_open

    def close_window(self) -> float:
        import jax

        self.t_close = time.perf_counter()
        gc.unfreeze()
        jax.monitoring.unregister_event_duration_listener(self._on_event)
        if self.trace:
            jax.profiler.stop_trace()
        return self.t_close

    def compare(self, dev, gain, statics, a, p) -> dict:
        """Widest gaps of the answers ``(a, p)`` from the reference's."""
        import numpy as np

        ra, rp = self.reference.solve(dev, gain, statics, dtype="float64")
        a = np.asarray(a, np.float64).reshape(ra.shape)
        p = np.asarray(p, np.float64).reshape(rp.shape)
        return {
            "a_gap": float(np.max(np.abs(a - ra), initial=0.0)),
            "p_rel_gap": float(np.max(np.abs(p - rp)
                                      / np.maximum(np.abs(rp), 1e-30),
                                      initial=0.0)),
        }


def require_chip(chips: int) -> dict:
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"needs a TPU; JAX found {devices[0].platform!r}")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips; JAX found "
                     f"{len(devices)}")
    return device_info(chips)


def device_info(chips: int) -> dict:
    import jax

    devices = jax.devices()[:chips]
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def enable_compile_cache() -> str:
    """The persistent cache: ``JAX_COMPILATION_CACHE_DIR`` where set, else
    a fixed directory in the checkout.  Every program is cached, however
    fast it compiled, so a second run compiles nothing."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def peak_memory_bytes(chips: int) -> int:
    import jax

    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


def cell_spec(spec: dict, name: str) -> tuple[dict, dict]:
    cells = {c["name"]: c for c in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; cells: {sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    return cell, configs[cell["config"]]


def cell_metrics(spec: dict, cell: dict, kind: str) -> list[dict]:
    """The end-to-end or per-layer metrics that ``cell`` reports."""
    e2e = [m for m in spec["end_to_end"]
           if "workloads" not in m or cell["name"] in m["workloads"]]
    if kind == "end_to_end":
        return e2e
    reported = {m["name"] for m in e2e}
    return [m for m in spec["per_layer"]
            if (cell["name"] in m["workloads"] if "workloads" in m
                else m["moves"] in reported)]


def load_cell(cell: dict, cfg_entry: dict, config_overrides=None,
              traffic_overrides=None) -> tuple:
    """``(config, traffic, reference, driver)`` of a cell, found by name."""
    config = load_json(ROOT / cfg_entry["file"])
    config.update(config_overrides or {})
    traffic = load_json(HERE / "traffic" / f"{cell['traffic']}.json")
    traffic.update(traffic_overrides or {})
    reference = load_module(HERE / "configs" / f"{config['reference']}.py",
                            f"reference_{config['reference']}")
    driver = load_module(HERE / "drivers" / f"{traffic['driver']}.py",
                         f"driver_{traffic['driver']}")
    return config, traffic, reference, driver


def judge(config: dict, values: dict) -> tuple[bool, dict]:
    """Each compared number beside its limit; correct iff none is over."""
    compared = {}
    for name, value in values.items():
        value = float(value)
        compared[name] = {
            "value": value if math.isfinite(value) else NOT_FINITE,
            "limit": float(config["limits"][name])}
    return all(c["value"] <= c["limit"] for c in compared.values()), compared


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        chip: bool = True, config_overrides: dict | None = None,
        traffic_overrides: dict | None = None) -> tuple[dict, dict]:
    """One run of one cell: ``(result, facts)``, the result line's object
    and the driver's facts of the window.

    ``chip=False`` skips the look for a TPU and leaves the compilation
    cache alone (the CPU tests drive the rest of a run that way, at
    sizes the overrides shrink)."""
    spec = load_json(SPEC_PATH)
    cell, cfg_entry = cell_spec(spec, workload)
    if chip:
        device = require_chip(int(cell["chips"]))
        enable_compile_cache()
    else:
        device = device_info(int(cell["chips"]))
    sys.path.insert(0, str(ROOT / "src"))

    config, traffic, reference, driver = load_cell(
        cell, cfg_entry, config_overrides, traffic_overrides)
    ctx = Context(cell, config, traffic, seed, seconds, trace, reference)

    state = driver.setup(ctx)
    facts = driver.window(state, ctx)
    device["memory_peak_bytes"] = peak_memory_bytes(int(cell["chips"]))
    log(f"compiles_in_window: {ctx.compiles}")
    log(f"setup_s: {ctx.setup_s}  window_s: {facts['window_s']}  "
        f"attempted: {facts['attempted']}  failed: {facts['failed']}")
    if facts.get("counters"):
        log("counters: " + json.dumps(facts["counters"], sort_keys=True))
    if facts.get("host"):
        log("host: " + json.dumps(facts["host"], sort_keys=True))

    result: dict = {"correct": False, "attempted": int(facts["attempted"]),
                    "failed": int(facts["failed"]), "metrics": {},
                    "device": device}
    if trace:
        import reduce

        red = reduce.reduce_run(ctx, device)
        shutil.rmtree(ctx.trace_dir, ignore_errors=True)
        device["busy_s"] = red.busy_s
        device["window_s"] = red.window_s
        view = reduce.RunView(ctx, facts, red, device)
        for m in cell_metrics(spec, cell, "per_layer"):
            value = load_module(HERE / "metrics" / f"{m['name']}.py",
                                f"metric_{m['name']}").read(view)
            if value is not None:
                result["metrics"][m["name"]] = {"value": float(value),
                                                "unit": m["unit"]}
        result["breakdown"] = red.breakdown()
    else:
        values = dict(facts["end_to_end"], setup_s=ctx.setup_s)
        for m in cell_metrics(spec, cell, "end_to_end"):
            result["metrics"][m["name"]] = {"value": float(values[m["name"]]),
                                            "unit": m["unit"]}

    # the reference runs once the window has closed and the peak memory
    # is read, with the program's device state let go
    state.release()
    gc.collect()
    t_check = time.perf_counter()
    values = driver.check(state, ctx, facts)
    log(f"check_s: {time.perf_counter() - t_check}")
    result["correct"], compared = judge(config, values)
    result["compared"] = compared
    for name, c in compared.items():
        verdict = "ok" if c["value"] <= c["limit"] else "FAIL"
        log(f"compared {name}: {c['value']!r} limit {c['limit']!r} {verdict}")
    return result, facts


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result, _ = run(args.workload, args.seed, args.seconds,
                        bool(args.trace))
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
