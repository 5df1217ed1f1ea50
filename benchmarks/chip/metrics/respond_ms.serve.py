"""Host time of building the responses per answered batch, in ms: the
seconds of the program's ``fleet_service.respond`` spans in the
trace (per-request answers, cache writes, accounting) over the
count of ``fleet_service.serve`` spans."""

SPAN = "fleet_service.respond"
BATCH = "fleet_service.serve"


def read(run):
    batches = run.trace.host_seconds([BATCH])[1]
    if not batches:
        return None
    return 1e3 * run.trace.host_seconds([SPAN])[0] / batches
