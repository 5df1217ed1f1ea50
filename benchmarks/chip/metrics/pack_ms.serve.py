"""Host time of packing per answered batch, in ms: the seconds of the
program's ``fleet_service.pack`` spans in the trace (stacking the
requests and padding them to the bucket) over the count of
``fleet_service.serve`` spans (one per answered batch)."""

SPAN = "fleet_service.pack"
BATCH = "fleet_service.serve"


def read(run):
    batches = run.trace.host_seconds([BATCH])[1]
    if not batches:
        return None
    return 1e3 * run.trace.host_seconds([SPAN])[0] / batches
