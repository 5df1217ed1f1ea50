"""Device time of one of the service's batch solves, in ms: the device
seconds of the compiled ``_solve_batch_fused`` program in the trace,
over its executions (one per micro-batch)."""

PROGRAM = "_solve_batch_fused"


def read(run):
    secs, n = run.trace.program_seconds(PROGRAM)
    if n == 0:
        return None
    return 1e3 * secs / n
