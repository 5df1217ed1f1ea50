"""Host time per outer iteration of a coupled metro tick, in ms: the
seconds of the program's ``multicell.solve`` spans in the trace (the
union solve, from its call to block_until_ready) over the count of
``multicell.solve`` spans (one per outer iteration)."""

SPAN = "multicell.solve"
ITERATION = "multicell.solve"


def read(run):
    iters = run.trace.host_seconds([ITERATION])[1]
    if not iters:
        return None
    return 1e3 * run.trace.host_seconds([SPAN])[0] / iters
