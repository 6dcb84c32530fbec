"""Device idle milliseconds of one traced build while the host was inside
``build/coder``: the coder's fit (PCA, k-means) and encode.

Read from the program's spans (``harness/spans.py``); None where the
program has none on the profiler's clock."""

from harness import spans


def read(r):
    return spans.idle_ms(r.record, "coder")
