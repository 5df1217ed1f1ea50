"""The comparison's control: the reference, one precision down, in the
program's place.

    python3 benchmarks/chip/control.py --workload fleet_solve_1m \\
        --seeds 11,12,13 [--seconds 51] [--dtype bfloat16]

For each seed it draws the cell's data at the cell's own size (the
requests a window of ``--seconds`` offers, or the fleet's sampled
steps), answers every one with the plain reference computed in
``--dtype`` (the precision below the configuration's ``float32``), and
compares those answers with the reference in ``float64`` exactly as a
run compares the program's.  It prints each compared number beside its
limit: a sound comparison calls the control not correct.  No program
runs, so it needs no chip; the benchmark's own runs never run it.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))
import bench  # noqa: E402


def control_run(workload: str, seed: int, seconds: float, dtype: str, *,
                config_overrides=None, traffic_overrides=None
                ) -> tuple[bool, dict]:
    """``(correct, compared)`` with the control's answers in place."""
    spec = bench.load_json(bench.SPEC_PATH)
    cell, cfg_entry = bench.cell_spec(spec, workload)
    config, traffic, reference, driver = bench.load_cell(
        cell, cfg_entry, config_overrides, traffic_overrides)
    ctx = bench.Context(cell, config, traffic, seed, seconds, False,
                        reference)
    st = driver.Setup(ctx, program=False)
    facts = st.control(reference, dtype)
    return bench.judge(config, driver.check(st, ctx, facts))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--dtype", default="bfloat16")
    args = ap.parse_args(argv)
    seconds = args.seconds or bench.load_json(bench.SPEC_PATH)["run_seconds"]
    for seed in (int(s) for s in args.seeds.split(",")):
        correct, compared = control_run(args.workload, seed, seconds,
                                        args.dtype)
        print("control " + json.dumps({"workload": args.workload,
                                       "seed": seed, "dtype": args.dtype,
                                       "correct": correct,
                                       "compared": compared}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
