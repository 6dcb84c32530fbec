"""Device traces: capture, a compact record, and the reduction to numbers.

``capture`` runs a block under the JAX profiler (the Python tracer off, so
the host runs at its own speed) and turns the ``.xplane.pb`` into a
compact record: each device plane's op and program events, and the host
spans the benchmark opened with ``TraceAnnotation`` (names starting with
``bench``). Everything after that works on the record alone, so the
reduction is tested on a small recorded trace without a chip.

On a TPU the profiler names each op event by its HLO instruction text
(``%fusion.163 = s32[...] fusion(...)``) and each program event
``jit_<name>(<fingerprint>)``. The record keeps an op's instruction name
(``fusion.163``), and the whole text only for a Pallas kernel
(``tpu_custom_call``), whose output shape the roofline readers need.

Record layout::

    {"window": [start_ns, end_ns],
     "devices": [{"name": "/device:TPU:0",
                  "ops": [[name, start_ns, dur_ns, kernel_text], ...],
                  "modules": [[name, start_ns, dur_ns], ...]}],
     "host": [[name, start_ns, dur_ns], ...]}
"""

from __future__ import annotations

import bisect
import contextlib
import glob
import os
import re
import shutil

DEVICE_PREFIX = "/device:TPU:"  # not "/device:CUSTOM:...", which holds no ops
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PREFIX = "bench"
WINDOW = "bench/window"


KERNEL_MARK = 'custom_call_target="tpu_custom_call"'


def _op(name: str, start: int, dur: int) -> list:
    short = name.split(" = ", 1)[0].lstrip("%")
    return [short, start, dur, name if KERNEL_MARK in name else ""]


def from_xplane(path: str) -> dict:
    """Compact record of one ``.xplane.pb``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, host = [], []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            ops, mods = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend(
                        _op(ev.name, int(ev.start_ns), int(ev.duration_ns))
                        for ev in line.events
                    )
                elif line.name == MODULES_LINE:
                    mods.extend(
                        [ev.name, int(ev.start_ns), int(ev.duration_ns)]
                        for ev in line.events
                    )
            devices.append({"name": plane.name, "ops": ops, "modules": mods})
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(
                    [ev.name, int(ev.start_ns), int(ev.duration_ns)]
                    for ev in line.events
                    if ev.name.startswith(HOST_PREFIX)
                )
    win = [h for h in host if h[0] == WINDOW]
    window = [win[0][1], win[0][1] + win[0][2]] if win else None
    return {"window": window, "devices": devices, "host": host}


class Capture:
    """A profiled window, opened and closed at points the caller picks."""

    def __init__(self, log_dir: str):
        self.log_dir = log_dir
        self._window = None

    def start(self) -> None:
        import jax

        shutil.rmtree(self.log_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.log_dir, profiler_options=opts)
        self._window = jax.profiler.TraceAnnotation(WINDOW)
        self._window.__enter__()

    def stop(self) -> dict:
        """Close the window; its compact record."""
        import jax

        self._window.__exit__(None, None, None)
        jax.profiler.stop_trace()
        paths = glob.glob(os.path.join(self.log_dir, "**", "*.xplane.pb"),
                          recursive=True)
        rec = from_xplane(max(paths, key=os.path.getmtime)) if paths else {}
        shutil.rmtree(self.log_dir, ignore_errors=True)
        return rec


@contextlib.contextmanager
def capture(log_dir: str, out: dict):
    """Profile the block; fill ``out`` with its compact record."""
    cap = Capture(log_dir)
    cap.start()
    try:
        yield
    finally:
        out.update(cap.stop())


# ---------------------------------------------------------------------------
# reduction


def union(intervals) -> list[tuple[int, int]]:
    """Merged [start, end) intervals, sorted."""
    out: list[list[int]] = []
    for s, e in sorted((s, s + d) for s, d in intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(spans, lo: int, hi: int) -> list[tuple[int, int]]:
    return [(max(s, lo), min(e, hi)) for s, e in spans if e > lo and s < hi]


def window_ns(rec: dict) -> tuple[int, int] | None:
    w = rec.get("window")
    return (int(w[0]), int(w[1])) if w else None


def busy_ns(rec: dict) -> list[int]:
    """Per device: nanoseconds inside the window in which an op ran."""
    w = window_ns(rec)
    if w is None:
        return []
    out = []
    for dev in rec["devices"]:
        spans = clip(union((o[1], o[2]) for o in dev["ops"]), *w)
        out.append(sum(e - s for s, e in spans))
    return out


def busy_share(rec: dict) -> float | None:
    """Busy time over the window, averaged over the devices."""
    w = window_ns(rec)
    b = busy_ns(rec)
    if w is None or not b or w[1] <= w[0]:
        return None
    return sum(b) / len(b) / (w[1] - w[0])


def _host_at(rec: dict, t: int) -> str:
    """The innermost benchmark span open at time ``t``."""
    best, best_len = "host", None
    for name, s, d in rec["host"]:
        if name != WINDOW and s <= t < s + d and (best_len is None or d < best_len):
            best, best_len = name, d
    return best


def _program_at(mods: list, starts: list, t: int) -> str | None:
    """The device program running at time ``t``, if any."""
    i = bisect.bisect_right(starts, t) - 1
    return _program(mods[i][0]) if i >= 0 and t < mods[i][1] + mods[i][2] else None


def idle_gaps(rec: dict, top: int = 10) -> list[list]:
    """Idle time of the first device in the window, summed by what the host
    was doing in each gap (the innermost benchmark span open at the gap's
    middle) and by the device program that ends the gap (``<span> >
    <program>``; ``<span> > end`` for a gap that lasts to the window's
    close): [[name, seconds], ...], most first."""
    w = window_ns(rec)
    if w is None or not rec["devices"]:
        return []
    dev = rec["devices"][0]
    mods = sorted(dev["modules"], key=lambda m: m[1])
    starts = [m[1] for m in mods]
    spans = clip(union((o[1], o[2]) for o in dev["ops"]), *w)
    tot: dict[str, int] = {}
    t = w[0]
    for s, e in spans + [(w[1], w[1])]:
        if s > t:
            nxt = _program_at(mods, starts, s) if s < w[1] else "end"
            name = f"{_host_at(rec, (s + t) // 2)} > {nxt or '?'}"
            tot[name] = tot.get(name, 0) + (s - t)
        t = max(t, e)
    return [[k, v / 1e9] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:top]]


def _program(name: str) -> str:
    """``jit_bulk_commit(1234)`` -> ``bulk_commit``."""
    name = name.split("(", 1)[0]
    return name[4:] if name.startswith("jit_") else name


def top_ops(rec: dict, top: int = 10) -> list[list]:
    """Device seconds of the outermost ops in the window (an op inside a
    loop op counts in the loop), by program and op name, averaged over
    devices: [["bulk_refine_jit/while.79", seconds], ...], most first."""
    w = window_ns(rec)
    if w is None or not rec["devices"]:
        return []
    tot: dict[str, float] = {}
    for dev in rec["devices"]:
        mods = sorted(dev["modules"], key=lambda m: m[1])
        starts = [m[1] for m in mods]
        end = -1
        for name, s, d, _ in sorted(dev["ops"], key=lambda o: (o[1], -o[2])):
            if s < end or not w[0] <= s < w[1]:
                continue  # nested in the op before it, or outside the window
            end = s + d
            key = f"{_program_at(mods, starts, s) or '?'}/{name}"
            tot[key] = tot.get(key, 0.0) + d / 1e9
    n = len(rec["devices"])
    return [[k, v / n] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:top]]


def kernel_calls(rec: dict, kernel: str) -> list[tuple[int, str]]:
    """(duration ns, instruction text) of each call of Pallas kernel
    ``kernel`` in the window, over all devices."""
    w = window_ns(rec)
    if w is None:
        return []
    out = []
    for dev in rec["devices"]:
        for name, s, d, text in dev["ops"]:
            if text and kernel in name.split(".", 1)[0] and w[0] <= s < w[1]:
                out.append((d, text))
    return out


def kernel_names(rec: dict) -> dict[str, int]:
    """Calls of each Pallas kernel in the record, by instruction name."""
    out: dict[str, int] = {}
    for dev in rec.get("devices", []):
        for name, _, _, text in dev["ops"]:
            if text:
                key = name.split(".", 1)[0]
                out[key] = out.get(key, 0) + 1
    return out


SHAPE = re.compile(r"[a-z]+\d*\[([\d,]*)\]")


def out_dims(text: str) -> tuple[int, ...]:
    """The first output shape of an instruction text; () where none."""
    m = SHAPE.search(text.split(" = ", 1)[-1])
    return tuple(int(x) for x in m.group(1).split(",") if x) if m else ()


def module_seconds(rec: dict, fragment: str) -> float | None:
    """Device seconds of programs whose name holds ``fragment`` in the
    window, averaged over devices; None where no such program ran."""
    w = window_ns(rec)
    if w is None or not rec["devices"]:
        return None
    tot, seen = 0.0, False
    for dev in rec["devices"]:
        for name, s, d in dev["modules"]:
            if fragment in name and w[0] <= s < w[1]:
                tot += d / 1e9
                seen = True
    return tot / len(rec["devices"]) if seen else None
