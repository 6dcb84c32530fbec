"""Median time of one batch through the search engine, in ms (the
program's ``serve_engine_latency_seconds``, taken around work that ends in
``block_until_ready``)."""

import numpy as np


def read(r):
    d = r.layer.get("dispatch_s")
    return None if d is None or len(d) == 0 else 1e3 * float(np.median(d))
