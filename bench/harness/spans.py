"""Device idle time of one traced build, split by the program phase the
host was in, read from the program's own ``repro.obs`` spans.

The program stamps its spans on the profiler's clock, but the compact
record (``harness/trace.py``) keeps only the benchmark's own host events.
So the last finished root ``build`` span is placed on the record by its
start, which is taken to be the start of the ``bench/build`` event around
it: the two open within microseconds of each other. A root span longer
than ``bench/build`` (placed so, it would end after it) is not the traced
build, and nothing is read.

Each idle gap of the first device in the record's window goes to the
phase below ``build`` whose span is open at the gap's middle (the union
and the midpoint rule of ``trace.idle_gaps``): ``coder`` (``build/coder``),
``repair`` (``build/repair``), ``graph`` (``build/bulk_refine`` and
``build/bulk_commit``), or else ``unattributed``. The four partition the
window's idle time. A program without such spans (one whose spans carry no
``t0_ns``) reads nothing.
"""

from __future__ import annotations

import bisect

from harness import trace

BUILD = "bench/build"
PHASES = ("coder", "repair", "graph", "unattributed")
PHASE_OF = {
    "build/coder": "coder",
    "build/repair": "repair",
    "build/bulk_refine": "graph",
    "build/bulk_commit": "graph",
}


def last_build():
    """The program's last finished root ``build`` span, or None."""
    try:
        from repro import obs
    except ImportError:
        return None
    roots = obs.spans("build")
    return roots[-1] if roots else None


def _host_event(rec: dict, name: str):
    return next((h for h in rec.get("host", []) if h[0] == name), None)


def attribute(rec: dict, root) -> dict | None:
    """Idle milliseconds of the record's window by phase, or None where the
    record or the span tree gives nothing to read."""
    w = trace.window_ns(rec)
    build = _host_event(rec, BUILD)
    t0, t1 = getattr(root, "t0_ns", None), getattr(root, "t1_ns", None)
    if w is None or not rec.get("devices") or build is None or not t0 or not t1:
        return None
    _, b0, b_dur = build
    if t1 - t0 > b_dur:
        return None
    shift = b0 - t0
    phases = sorted(
        (c.t0_ns + shift, c.t1_ns + shift, PHASE_OF[c.name])
        for c in root.children if c.name in PHASE_OF
    )
    starts = [p[0] for p in phases]

    def phase_at(t: int) -> str:
        i = bisect.bisect_right(starts, t) - 1
        return phases[i][2] if i >= 0 and t < phases[i][1] else "unattributed"

    total = dict.fromkeys(PHASES, 0)
    busy = trace.clip(trace.union((o[1], o[2]) for o in rec["devices"][0]["ops"]), *w)
    t = w[0]
    for s, e in busy + [(w[1], w[1])]:
        if s > t:
            total[phase_at((s + t) // 2)] += s - t
        t = max(t, e)
    return {k: v / 1e6 for k, v in total.items()}


_cache: list = [None, None, None]  # record, root span, attribution


def idle_ms(rec: dict, phase: str) -> float | None:
    """One phase's idle milliseconds; the attribution is made once for a
    record and its span tree, and shared by the readers."""
    root = last_build()
    if _cache[0] is not rec or _cache[1] is not root:
        _cache[:] = [rec, root, attribute(rec, root)]
    got = _cache[2]
    return None if got is None else got[phase]
