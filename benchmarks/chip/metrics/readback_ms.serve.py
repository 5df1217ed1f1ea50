"""Host time of the reads from the device per answered batch, in ms:
the seconds of the program's ``fleet_service.readback`` spans in
the trace (the convergence flags, then the six answer fields) over
the count of ``fleet_service.serve`` spans."""

SPAN = "fleet_service.readback"
BATCH = "fleet_service.serve"


def read(run):
    batches = run.trace.host_seconds([BATCH])[1]
    if not batches:
        return None
    return 1e3 * run.trace.host_seconds([SPAN])[0] / batches
