"""Host time of one coupled metro tick, in ms: the seconds of the
program's ``fleet_service.metro`` spans in the trace (the whole of
``FleetControlService.solve_coupled``) over their count."""

SPAN = "fleet_service.metro"


def read(run):
    secs, n = run.trace.host_seconds([SPAN])
    if not n:
        return None
    return 1e3 * secs / n
