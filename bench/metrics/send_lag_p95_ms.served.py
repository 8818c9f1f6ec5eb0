"""95th percentile of how late the load generator sent each request of
the window against its due time, in ms (a starved generator would read
as a fast server)."""
import numpy as np


def read(record):
    t = np.asarray(record.get("send_lag_s", []), np.float64)
    t = t[np.isfinite(t)]
    return 1e3 * float(np.percentile(t, 95)) if t.size else None
