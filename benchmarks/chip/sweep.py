"""Find a serving cell's knee: the highest offered rate it sustains.

    python3 benchmarks/chip/sweep.py --workload metro_serve_bursty \\
        --rates 60,120,250,500 --seeds 3 [--seconds 51] [--seed 7]

Runs the cell's window with its configuration and mix, but with Poisson
arrivals at each offered rate, ``--seeds`` times per rate on different
seeds, all in this one process (a chip belongs to one process).  It
prints a row per run (latency percentiles, answers per second, how long
the queue took to drain after the last arrival, batch fill) and then a
row per rate.  A rate is sustained when every seed drained its queue
within ``DRAIN_OK_S`` and no seed's 99th percentile exceeded
``STABLE_FACTOR`` times the lowest rate's median one; the knee is the
highest rate up to which every rate was sustained.  The window defaults
to ``run_seconds``, the length the cells are measured at.  The knee is
read off once, when a cell is defined, and written into ``PERF.md``;
the benchmark's own runs never sweep.
"""
from __future__ import annotations

import argparse
import json
import statistics
import sys
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent))
import bench  # noqa: E402

DRAIN_OK_S = 0.5     # a sustained rate leaves at most this much queue
STABLE_FACTOR = 2.0  # and a tail within this factor of the lowest rate's


def sweep_row(result: dict, facts: dict, rate: float, seed: int,
              seconds: float) -> dict:
    lat = np.asarray(facts["latency_ms"], np.float64)
    done = lat[np.isfinite(lat)]
    c = facts["counters"]
    return {"rate_hz": rate, "seed": seed, "correct": result["correct"],
            "requests": int(facts["attempted"]),
            "answered_per_s": float(done.size / facts["window_s"]),
            "p50_ms": float(np.percentile(done, 50)) if done.size else None,
            "p99_ms": float(np.percentile(lat, 99)) if lat.size else None,
            "drain_s": float(facts["window_s"] - seconds),
            "batch_fill": c.get("solved", 0) / max(1, c.get("batches", 0))
            / facts["max_batch"], **facts["host"]}


def knee(rows: list[dict]) -> tuple[list[dict], float | None]:
    """Per-rate summaries and the highest rate up to which all held."""
    rates = sorted({r["rate_hz"] for r in rows})
    by_rate = {q: [r for r in rows if r["rate_hz"] == q] for q in rates}
    base = statistics.median(r["p99_ms"] for r in by_rate[rates[0]])
    out, best, held = [], None, True
    for q in rates:
        p99 = [r["p99_ms"] for r in by_rate[q]]
        ok = (max(r["drain_s"] for r in by_rate[q]) <= DRAIN_OK_S
              and max(p99) <= STABLE_FACTOR * base
              and all(r["correct"] for r in by_rate[q]))
        held = held and ok
        if held:
            best = q
        out.append({"rate_hz": q, "sustained": ok,
                    "p99_ms_median": statistics.median(p99),
                    "p99_ms_min": min(p99), "p99_ms_max": max(p99),
                    "drain_s_max": max(r["drain_s"] for r in by_rate[q])})
    return out, best


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True,
                    help="comma-separated offered rates, requests/s")
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)
    seconds = args.seconds or bench.load_json(bench.SPEC_PATH)["run_seconds"]
    rows = []
    rates = [float(r) for r in args.rates.split(",")]
    for i, rate in enumerate(rates):
        for j in range(args.seeds):
            seed = args.seed + i * args.seeds + j
            result, facts = bench.run(
                args.workload, seed, seconds, False,
                traffic_overrides={"arrivals": "poisson", "rate_hz": rate})
            row = sweep_row(result, facts, rate, seed, seconds)
            rows.append(row)
            print("sweep " + json.dumps(row), flush=True)
    summary, best = knee(rows)
    for s in summary:
        print("rate " + json.dumps(s), flush=True)
    print("knee " + json.dumps({"knee_hz": best}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
