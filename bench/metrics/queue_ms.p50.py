"""Median time a request waited in the runtime's queue before its batch was
dispatched, in ms (the program's ``serve_queue_latency_seconds``)."""

import numpy as np


def read(r):
    q = r.layer.get("queue_s")
    return None if q is None or len(q) == 0 else 1e3 * float(np.median(q))
