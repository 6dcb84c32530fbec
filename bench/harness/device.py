"""The chip, its peaks, the compile cache and the compile clock."""

from __future__ import annotations

import json
import os

import jax


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def require_chips(n: int) -> list:
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX platform is {devs[0].platform!r}")
    if len(devs) < n:
        raise NoChip(f"the cell asks for {n} chips, JAX sees {len(devs)}")
    return devs[:n]


def describe(devs: list) -> dict:
    d = devs[0]
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}


def peaks(path: str, device_kind: str) -> dict:
    """The published peaks of ``device_kind``; an unknown device is an error."""
    with open(path) as f:
        table = json.load(f)
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {path}")
    return table["devices"][device_kind]


def memory_peak(devs: list) -> int | None:
    """Peak bytes in use on the fullest chip, where the backend reports it."""
    peaks_ = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devs]
    peaks_ = [p for p in peaks_ if p is not None]
    return max(peaks_) if peaks_ else None


def use_compile_cache(default_dir: str) -> str:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR``
    where it is set (JAX reads it itself), else ``default_dir``, a fixed
    path inside the checkout. Every program is kept, however quick its
    compile, so that a run after the first compiles nothing."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or default_dir
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileClock:
    """Executables made and their seconds, from JAX's monitoring events.

    JAX times each executable it makes, whether compiled or read from the
    persistent cache: ``compiles`` counts both, ``cache_hits`` the reads."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == self.EVENT:
            self.seconds += secs
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def kernel_dispatch() -> dict:
    """The program's ``kernel_traces_total`` series as {kernel/impl: count}."""
    from repro import obs

    out = {}
    for m in obs.REGISTRY.metrics():
        if m.name == "kernel_traces_total":
            labels = dict(m.labels)
            out[f"{labels['kernel']}/{labels['impl']}"] = m.value
    return out
