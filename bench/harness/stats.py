"""Arithmetic of the end-to-end metrics."""

from __future__ import annotations

import math

import numpy as np


def rate(items_per_unit: int, units: int, seconds: float) -> float:
    """Items of all whole units over all the time they took."""
    if seconds <= 0:
        raise ValueError(f"rate over {seconds} s")
    return items_per_unit * units / seconds


def percentile_from_due(due, done, q: float) -> float:
    """The ``q``-th percentile of latency over all requests, each timed from
    when it was due; one that never finished (``done`` NaN) counts as
    missing every limit, so it lies above every finished one."""
    due = np.asarray(due, np.float64)
    done = np.asarray(done, np.float64)
    lat = np.where(np.isnan(done), np.inf, done - due)
    if lat.size == 0:
        raise ValueError("no requests")
    lat = np.sort(lat)
    # nearest rank: the smallest latency that at least q% of requests meet
    idx = max(0, math.ceil(q / 100.0 * lat.size) - 1)
    return float(lat[idx])
