"""Mean time a request waited in the service's queue, in ms: from its
submit stamp to the close stamp of the batch that answered it, summed by
the service's ``queue_wait_us`` counter (``ServiceStats``) over the
window, over the requests answered in it."""


def read(run):
    c = run.facts.get("counters", {})
    if "queue_wait_us" not in c or not c.get("solved"):
        return None
    return c["queue_wait_us"] / c["solved"] / 1e3
