"""Share of the traced window in which no operation ran on the device:
one minus the union of the device's event intervals over the window."""


def read(run):
    return run.trace.idle_pct()
