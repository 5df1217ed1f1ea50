"""Host time of one answered batch of the service, in ms: the seconds
of the program's ``fleet_service.serve`` spans in the trace (all of
``_serve``, or ``_shed`` while a breaker is open) over their count."""

SPAN = "fleet_service.serve"


def read(run):
    secs, n = run.trace.host_seconds([SPAN])
    if not n:
        return None
    return 1e3 * secs / n
