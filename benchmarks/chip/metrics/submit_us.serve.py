"""Host time of one request's intake, in us: the seconds of the
program's ``fleet_service.submit`` spans in the trace (health
check, feature key, lane, queueing) over their count."""

SPAN = "fleet_service.submit"


def read(run):
    secs, n = run.trace.host_seconds([SPAN])
    if not n:
        return None
    return 1e6 * secs / n
