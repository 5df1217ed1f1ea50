"""Host time per outer iteration of a coupled metro tick, in ms: the
seconds of the program's ``multicell.interference`` spans in the trace
(the interference update and the next interference leaf's build and
upload, with the first leaf of each tick) over the count of
``multicell.solve`` spans (one per outer iteration)."""

SPAN = "multicell.interference"
ITERATION = "multicell.solve"


def read(run):
    iters = run.trace.host_seconds([ITERATION])[1]
    if not iters:
        return None
    return 1e3 * run.trace.host_seconds([SPAN])[0] / iters
