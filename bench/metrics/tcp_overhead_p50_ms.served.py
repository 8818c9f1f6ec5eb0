"""Median over the window's completed requests of the client's latency
(send to path received) minus the service's own (``ServedWalk.latency``,
submit to finish), in ms: the TCP front-end and the client's polling."""
import numpy as np


def read(record):
    t = np.asarray(record.get("tcp_overhead_s", []), np.float64)
    t = t[np.isfinite(t)]
    return 1e3 * float(np.median(t)) if t.size else None
