"""Outer iterations of the dual-decomposition loop per coupled metro
tick: the service's ``metro_outer_iters`` counter over its
``metro_ticks`` counter (``ServiceStats``), over the window."""


def read(run):
    c = run.facts.get("counters", {})
    if not c.get("metro_ticks") or "metro_outer_iters" not in c:
        return None
    return c["metro_outer_iters"] / c["metro_ticks"]
