"""Record the small profiler trace that the reduction's tests read.

    python benchmarks/chip/record_fixture.py [--out DIR]

Runs two named jitted programs, one host-to-device upload and one
device-to-host read inside host spans, with a host-only pause between
them, under the JAX profiler.  It copies the ``.xplane.pb`` to
``benchmarks/chip/tests/data/fixture.xplane.pb`` and prints the trace's
planes and lines, so a reader can see how a TPU trace names things.
Run it on the chip; on the CPU it records a CPU trace.
"""
from __future__ import annotations

import argparse
import shutil
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
FIXTURE = HERE / "tests" / "data" / "fixture.xplane.pb"


def record(out: Path) -> Path:
    import jax
    import jax.numpy as jnp
    import numpy as np

    @jax.jit
    def fixture_elementwise(x):
        return jnp.expm1(x) * 0.5 + jnp.log1p(x)

    @jax.jit
    def fixture_matmul(a):
        return a @ a

    host = np.linspace(0.0, 1.0, 1 << 20, dtype=np.float32)
    mat = jnp.ones((1024, 1024), jnp.bfloat16)
    # compile outside the trace
    fixture_elementwise(jnp.asarray(host)).block_until_ready()
    fixture_matmul(mat).block_until_ready()

    with jax.profiler.trace(str(out)):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.upload"):
                x = jax.device_put(host)
                x.block_until_ready()
            with jax.profiler.TraceAnnotation("bench.solve"):
                y = fixture_elementwise(x)
                z = fixture_matmul(mat)
                jax.block_until_ready((y, z))
            with jax.profiler.TraceAnnotation("bench.extract"):
                np.asarray(y)
            with jax.profiler.TraceAnnotation("bench.host_pause"):
                time.sleep(0.005)
    found = sorted(out.rglob("*.xplane.pb"))
    if not found:
        raise SystemExit(f"no .xplane.pb under {out}")
    return found[-1]


def describe(path: Path) -> None:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    for plane in data.planes:
        lines = list(plane.lines)
        print(f"plane {plane.name!r}: {len(lines)} lines")
        for line in lines:
            events = list(line.events)
            print(f"  line {line.name!r}: {len(events)} events")
            for ev in events[:6]:
                stats = {k: v for k, v in ev.stats}
                print(f"    {ev.name[:80]!r} start={ev.start_ns} "
                      f"dur={ev.duration_ns} stats={sorted(stats)[:8]}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--out", default=str(HERE.parents[1] / "chiprun_out"
                                         / "fixture_trace"))
    args = ap.parse_args(argv)
    out = Path(args.out)
    shutil.rmtree(out, ignore_errors=True)
    path = record(out)
    describe(path)
    FIXTURE.parent.mkdir(parents=True, exist_ok=True)
    shutil.copyfile(path, FIXTURE)
    print(f"fixture: {FIXTURE} ({FIXTURE.stat().st_size} bytes)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
