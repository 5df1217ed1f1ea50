"""Host time of the solve call per answered batch, in ms: the seconds
of the program's ``fleet_service.solve`` spans in the trace (the
batched solve's dispatch through ``block_until_ready``, a retry's
too) over the count of ``fleet_service.serve`` spans."""

SPAN = "fleet_service.solve"
BATCH = "fleet_service.serve"


def read(run):
    batches = run.trace.host_seconds([BATCH])[1]
    if not batches:
        return None
    return 1e3 * run.trace.host_seconds([SPAN])[0] / batches
