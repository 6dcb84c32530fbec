"""Nestable spans + the process clocks (DESIGN.md §14).

A :func:`span` is a host interval with a name, free-form attributes,
optional :class:`~repro.graph.engine.CostAccount`-style cost fold-ins
(``add_cost``), and children (spans opened while it is active on the same
thread). Spans live strictly at **host boundaries** — around jit calls and
the host floats that force them, never inside traced code — so the build
profiler can attribute wall time and distance evaluations per phase
without touching the compiled programs.

Spans run on the profiler's clock (:func:`trace_clock_ns`, wall-clock
nanoseconds since the epoch): an exported span's ``t0_ns`` minus a
``jax.profiler`` capture's ``profile_start_time`` is its offset on that
capture's timeline. An enabled span also enters a
``jax.profiler.TraceAnnotation`` of its name, so any profile shows the
program's phases on the host timeline beside the device ops. A span never
waits for the device: its length is host time, and device time comes from
the device trace.

While obs is enabled, every executable JAX makes (compiled, or loaded from
the persistent cache) is recorded as a finished ``jit/compile`` span
(attribute ``program``) under the span open on the compiling thread, if
any, and counted in ``jit_executables_total{program}`` and
``jit_compile_seconds_total{program}``.

Zero-cost-when-disabled: the module-level enable flag (``REPRO_OBS=1`` at
import, or :func:`enable`/:func:`disable` at runtime) is checked before
any label formatting or clock read; disabled ``span()`` yields a shared
null singleton whose ``add_cost``/``set`` are no-ops — crucially,
``add_cost`` receives raw (possibly still-device) values and only the
*real* span converts them with ``float()``, so a disabled span never
forces a device sync.

:data:`now` is the one sanctioned monotonic clock for every stats path in
``serve/`` and ``graph/engine.py`` — ``benchmarks/check_obs_guard.py``
fails CI if a raw stdlib monotonic-clock call reappears there, which keeps
all timestamps (deadlines included) on a single comparable timebase.
"""

from __future__ import annotations

import collections
import contextlib
import json
import os
import threading
import time

import jax.monitoring
from jax.profiler import TraceAnnotation

from repro.obs import registry as _registry

__all__ = [
    "NULL_SPAN",
    "Span",
    "clear_spans",
    "disable",
    "enable",
    "enabled",
    "export_jsonl",
    "iter_spans",
    "now",
    "span",
    "spans",
    "trace_clock_ns",
]

#: The process-wide monotonic clock (seconds, arbitrary epoch): deadlines
#: and latency histograms.
now = time.perf_counter

#: The span clock, the profiler's (integer ns since the epoch).
trace_clock_ns = time.time_ns

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_ENABLED = os.environ.get("REPRO_OBS", "") not in ("", "0", "false", "False")


def enabled() -> bool:
    """Whether spans/traces/gated counters are being recorded."""
    return _ENABLED


def enable() -> None:
    global _ENABLED
    _ENABLED = True
    _listen_for_compiles()


def disable() -> None:
    global _ENABLED
    _ENABLED = False


class Span:
    """One recorded interval; build via :func:`span`, not directly."""

    __slots__ = (
        "name", "attrs", "t0_ns", "t1_ns", "n_dists", "n_hops", "children",
    )

    def __init__(self, name: str, attrs: dict):
        self.name = name
        self.attrs = dict(attrs)
        self.t0_ns = 0  # trace_clock_ns() at open and at close
        self.t1_ns = 0
        self.n_dists = 0.0
        self.n_hops = 0.0
        self.children: list = []

    @property
    def dur_s(self) -> float:
        return (self.t1_ns - self.t0_ns) / 1e9

    def add_cost(self, n_dists=0, n_hops=0) -> "Span":
        """Fold a CostAccount-style delta in. ``float()`` happens HERE (on
        the enabled path only), so callers may pass device scalars without
        paying a sync when tracing is off."""
        self.n_dists += float(n_dists)
        self.n_hops += float(n_hops)
        return self

    def set(self, **attrs) -> "Span":
        self.attrs.update(attrs)
        return self

    def to_dict(self) -> dict:
        d = {
            "name": self.name,
            "t0_ns": self.t0_ns,
            "t1_ns": self.t1_ns,
            "dur_s": self.dur_s,
            "n_dists": self.n_dists,
            "n_hops": self.n_hops,
        }
        if self.attrs:
            d["attrs"] = dict(self.attrs)
        if self.children:
            d["children"] = [c.to_dict() for c in self.children]
        return d

    def __repr__(self) -> str:
        return (
            f"Span({self.name!r}, dur_s={self.dur_s:.6f}, "
            f"n_dists={self.n_dists:g}, children={len(self.children)})"
        )


class _NullSpan:
    """The disabled-path singleton: every method is a no-argument-touching
    no-op (``add_cost`` never calls ``float()`` on its inputs)."""

    __slots__ = ()

    def add_cost(self, n_dists=0, n_hops=0) -> "_NullSpan":
        return self

    def set(self, **attrs) -> "_NullSpan":
        return self


NULL_SPAN = _NullSpan()

_tls = threading.local()
_lock = threading.Lock()
#: finished ROOT spans (children hang off their parents), bounded.
_finished: collections.deque = collections.deque(maxlen=1024)


@contextlib.contextmanager
def span(name: str, **attrs):
    """Open a span; nests under the innermost active span of this thread.

    Disabled mode yields :data:`NULL_SPAN` without reading the clock,
    touching the attrs or the profiler."""
    if not _ENABLED:
        yield NULL_SPAN
        return
    sp = Span(name, attrs)
    stack = _stack()
    parent = stack[-1] if stack else None
    stack.append(sp)
    sp.t0_ns = trace_clock_ns()
    try:
        with TraceAnnotation(name):
            yield sp
    finally:
        sp.t1_ns = trace_clock_ns()
        stack.pop()
        if parent is not None:
            parent.children.append(sp)
        else:
            with _lock:
                _finished.append(sp)


def _stack() -> list:
    stack = getattr(_tls, "stack", None)
    if stack is None:
        stack = _tls.stack = []
    return stack


def _on_compile(event: str, start_s: float, end_s: float, **kw) -> None:
    """JAX time-span listener: one executable made, on this thread."""
    if not _ENABLED or event != COMPILE_EVENT:
        return
    program = str(kw.get("fun_name", "?"))
    stack = _stack()
    if stack:
        sp = Span("jit/compile", {"program": program})
        sp.t0_ns, sp.t1_ns = int(start_s * 1e9), int(end_s * 1e9)
        stack[-1].children.append(sp)
    _registry.REGISTRY.counter("jit_executables_total", program=program).inc()
    _registry.REGISTRY.counter(
        "jit_compile_seconds_total", program=program
    ).inc(end_s - start_s)


_listening = False


def _listen_for_compiles() -> None:
    """Register :func:`_on_compile` with ``jax.monitoring``, once."""
    global _listening
    with _lock:
        if _listening:
            return
        _listening = True
    jax.monitoring.register_event_time_span_listener(_on_compile)


def spans(name: str | None = None) -> list:
    """Finished root spans (most recent last), optionally filtered by name."""
    with _lock:
        out = list(_finished)
    if name is not None:
        out = [s for s in out if s.name == name]
    return out


def iter_spans(name: str | None = None):
    """Every finished span, roots and descendants (depth-first)."""
    todo = spans()
    while todo:
        sp = todo.pop(0)
        if name is None or sp.name == name:
            yield sp
        todo[:0] = sp.children


def clear_spans() -> None:
    with _lock:
        _finished.clear()


def export_jsonl(path_or_file) -> int:
    """Write finished root spans as JSON lines; returns the line count."""
    roots = spans()
    if hasattr(path_or_file, "write"):
        for sp in roots:
            path_or_file.write(json.dumps(sp.to_dict()) + "\n")
    else:
        with open(path_or_file, "w") as f:
            for sp in roots:
                f.write(json.dumps(sp.to_dict()) + "\n")
    return len(roots)


if _ENABLED:
    _listen_for_compiles()
