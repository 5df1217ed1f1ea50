"""Share of the service's micro-batch slots that carried a request in the
window: solved requests over batches times ``max_batch``, from the
service's own counters (``ServiceStats``)."""


def read(run):
    c = run.facts.get("counters", {})
    batches = c.get("batches", 0)
    if not batches:
        return None
    return 100.0 * c["solved"] / (batches * run.facts["max_batch"])
