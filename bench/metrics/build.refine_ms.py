"""Device milliseconds of the bulk refinement-round programs in one traced
build, found by their stable program name."""

from harness import trace

PROGRAM = "bulk_refine"


def read(r):
    s = trace.module_seconds(r.record, PROGRAM)
    return None if s is None else 1e3 * s
