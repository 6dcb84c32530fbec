"""Device idle milliseconds of one traced build's window that no phase span
below ``build`` covers: before and after the build, and between phases.

Read from the program's spans (``harness/spans.py``); None where the
program has none on the profiler's clock."""

from harness import spans


def read(r):
    return spans.idle_ms(r.record, "unattributed")
