"""How late the open-loop driver submitted: the 99th percentile, over the
window's requests, of the submit time minus the time the request was due
(host clock).  A starved driver shows here, not as a fast server."""
import numpy as np


def read(run):
    late = np.asarray(run.facts.get("lateness_ms", []), np.float64)
    late = late[np.isfinite(late)]
    if late.size == 0:
        return None
    return float(np.percentile(late, 99))
