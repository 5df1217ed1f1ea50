"""Host time per outer iteration of a coupled metro tick, in ms: the
seconds of the program's ``multicell.price`` spans in the trace (the
exact backhaul knapsack on the host) over the count of
``multicell.solve`` spans (one per outer iteration)."""

SPAN = "multicell.price"
ITERATION = "multicell.solve"


def read(run):
    iters = run.trace.host_seconds([ITERATION])[1]
    if not iters:
        return None
    return 1e3 * run.trace.host_seconds([SPAN])[0] / iters
