"""Device idle share over one traced build: 1 - busy / window, in %.

Busy is the union of the intervals in which an op ran on the device."""

from harness import trace


def read(r):
    share = trace.busy_share(r.record)
    return None if share is None else 100.0 * (1.0 - share)
