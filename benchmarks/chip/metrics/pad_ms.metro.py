"""Host time of padding per coupled metro tick, in ms: the seconds of
the program's ``fleet_service.metro_pad`` spans in the trace
(``pad_metro`` to the tick's cell and device bucket) over the count of
``fleet_service.metro`` spans (one per tick)."""

SPAN = "fleet_service.metro_pad"
TICK = "fleet_service.metro"


def read(run):
    ticks = run.trace.host_seconds([TICK])[1]
    if not ticks:
        return None
    return 1e3 * run.trace.host_seconds([SPAN])[0] / ticks
