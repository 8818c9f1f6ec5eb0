"""95th percentile of ``ServedWalk.wait`` (submit to admission into a
slot, on the service's clock) over the window's completed requests, in
ms: WalkService admission and DRR."""
import numpy as np


def read(record):
    w = np.asarray(record.get("queue_wait_s", []), np.float64)
    w = w[np.isfinite(w)]
    return 1e3 * float(np.percentile(w, 95)) if w.size else None
