"""``repro.obs`` — the unified observability layer (DESIGN.md §14).

One process-wide metrics registry (labeled counters / gauges / bounded-
reservoir histograms, :mod:`repro.obs.registry`), nestable host-boundary
spans with CostAccount fold-ins (:mod:`repro.obs.trace`), and report
rendering (Prometheus text exposition + JSON dump + the
``python -m repro.obs.report`` CLI, :mod:`repro.obs.report`).

Two tiers of instrumentation:

  * **Always-on metric primitives** back the serving ``stats()`` surfaces
    (engine latency window, admission counters, queue depth, cold
    dispatches). They are as cheap as the ad-hoc counters they replaced —
    one locked increment or deque append per event, references held
    directly so the hot path never formats a label.
  * **Gated extras** — spans (on the profiler's clock, and annotated into
    any ``jax.profiler`` capture), trace export, kernel-dispatch, compile
    and build-phase counters — cost nothing unless the module-level
    enable flag is set (``REPRO_OBS=1`` env, or :func:`enable` at
    runtime): :func:`tick` and :func:`span` check it before touching
    labels, the clock or the profiler, and never run inside jitted code
    (counters fold in at the same host boundaries ``CostAccount`` already
    crosses).

This package imports nothing from ``repro.graph`` / ``repro.kernels`` /
``repro.serve`` (they all import it), except lazily inside the report CLI.
"""

from __future__ import annotations

from repro.obs.registry import (  # noqa: F401
    REGISTRY,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    pcts_ms,
)
from repro.obs.trace import (  # noqa: F401
    NULL_SPAN,
    Span,
    clear_spans,
    disable,
    enable,
    enabled,
    export_jsonl,
    iter_spans,
    now,
    span,
    spans,
    trace_clock_ns,
)

__all__ = [
    "REGISTRY",
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "NULL_SPAN",
    "Span",
    "clear_spans",
    "counter",
    "disable",
    "enable",
    "enabled",
    "export_jsonl",
    "gauge",
    "histogram",
    "iter_spans",
    "now",
    "pcts_ms",
    "snapshot",
    "span",
    "spans",
    "tick",
    "trace_clock_ns",
]


def counter(name: str, **labels) -> Counter:
    """Get-or-create a counter in the process registry."""
    return REGISTRY.counter(name, **labels)


def gauge(name: str, **labels) -> Gauge:
    return REGISTRY.gauge(name, **labels)


def histogram(name: str, *, window: int = 4096, **labels) -> Histogram:
    return REGISTRY.histogram(name, window=window, **labels)


def snapshot() -> dict:
    """Consistent point-in-time dump of every registered metric."""
    return REGISTRY.snapshot()


def tick(name: str, n=1, **labels) -> None:
    """Gated counter bump: a no-op (before any label formatting) unless
    obs is enabled. The idiom for trace-time kernel/dispatch counters and
    host-boundary build counters."""
    if not enabled():
        return
    REGISTRY.counter(name, **labels).inc(n)
