"""Host time of one request's warm-start key, in us: the seconds of
the program's ``fleet_service.key`` spans in the trace (the
log-quantised SHA-1 of ``quantized_problem_key``) over their count."""

SPAN = "fleet_service.key"


def read(run):
    secs, n = run.trace.host_seconds([SPAN])
    if not n:
        return None
    return 1e6 * secs / n
