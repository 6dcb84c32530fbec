"""Device idle time split by the program's build phases."""

import json
import os
from types import SimpleNamespace

import pytest

from bench_checkout import BENCH  # also puts the harness on the path
from harness import spans, trace

READERS = {p: f"build.{p}_idle_ms" for p in ("coder", "repair", "graph", "unattributed")}

# window 0..100; ops cover [10,30) and [50,60); bench/build is [0,100)
SMALL = {
    "window": [0, 100],
    "devices": [{
        "name": "/device:TPU:0",
        "ops": [["a", 10, 20, ""], ["b", 50, 10, ""], ["late", 120, 5, ""]],
        "modules": [["jit_x(1)", 10, 20], ["jit_bulk_commit(2)", 50, 10]],
    }],
    "host": [["bench/window", 0, 100], ["bench/build", 0, 100]],
}


def span(name, t0, t1, *children):
    return SimpleNamespace(name=name, t0_ns=t0, t1_ns=t1, children=list(children))


def tree(*children, t0=5_000, t1=5_100):
    """A root build span on another clock: 5000 on it is 0 on the record."""
    return span("build", t0, t1, *children)


def load_reader(name):
    import importlib.util

    path = os.path.join(BENCH, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("reader_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def read_all(monkeypatch, record, root):
    monkeypatch.setattr(spans, "last_build", lambda: root)
    r = SimpleNamespace(record=record, config={}, peaks=None, layer={})
    return {p: load_reader(name)(r) for p, name in READERS.items()}


def test_gaps_go_to_the_phase_open_at_their_middle(monkeypatch):
    # gaps: [0,10) mid 5, [30,50) mid 40, [60,100) mid 80
    root = tree(
        span("build/coder", 5_000, 5_010, span("build/coder/pca", 5_000, 5_008)),
        span("jit/compile", 5_012, 5_014),
        span("build/bulk_refine", 5_020, 5_035),
        span("build/repair", 5_035, 5_050, span("build/repair/bfs", 5_036, 5_040)),
        span("build/bulk_commit", 5_060, 5_070),
    )
    got = read_all(monkeypatch, SMALL, root)
    # [30,50)'s middle is in the repair; [60,100)'s middle, 80, in no phase
    assert got == {"coder": pytest.approx(10e-6), "repair": pytest.approx(20e-6),
                   "graph": 0.0, "unattributed": pytest.approx(40e-6)}
    root = tree(span("build/bulk_commit", 5_060, 5_095))
    got = read_all(monkeypatch, SMALL, root)
    assert got["graph"] == pytest.approx(40e-6)
    assert got["unattributed"] == pytest.approx(30e-6)


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(BENCH, "data", "trace_small.json")) as f:
        return json.load(f)


def test_the_four_partition_the_windows_idle_time(monkeypatch, recorded):
    (_, b0, b_dur), = [h for h in recorded["host"] if h[0] == "bench/build"]
    (_, c0, c_dur), = [h for h in recorded["host"] if h[0] == "bench/build/coder"]
    t0 = 1_760_000_000_000_000_000  # the profiler's clock: ns since the epoch
    at = lambda t: t0 + t - b0  # noqa: E731
    root = tree(
        span("build/coder", at(c0), at(c0 + c_dur)),
        span("build/bulk_refine", at(c0 + c_dur + 1_000), at(b0 + b_dur - 1_000)),
        t0=t0, t1=at(b0 + b_dur - 10),
    )
    got = read_all(monkeypatch, recorded, root)
    lo, hi = recorded["window"]
    idle_ms = (hi - lo - trace.busy_ns(recorded)[0]) / 1e6
    assert sum(got.values()) == pytest.approx(idle_ms, rel=1e-9)
    assert 100 * (1 - trace.busy_share(recorded)) == pytest.approx(100 * idle_ms * 1e6 / (hi - lo))
    # the recorded slice's longest gap is in the coder (see test_bench_trace)
    gaps = trace.idle_gaps(recorded, top=1000)
    coder = sum(s for name, s in gaps if name.startswith("bench/build/coder > "))
    assert got["coder"] == pytest.approx(1e3 * coder, rel=1e-6)
    assert got["coder"] > got["graph"] > 0
    assert got["repair"] == 0.0


def test_nothing_read_without_a_root_span_that_fits(monkeypatch):
    fits = tree(span("build/coder", 5_000, 5_010))
    assert read_all(monkeypatch, SMALL, fits)["coder"] == pytest.approx(10e-6)
    for root in (
        None,  # no build span
        tree(t0=5_000, t1=5_101),  # longer than bench/build
        SimpleNamespace(name="build", t0=1.5, children=[]),  # spans off the profiler's clock
    ):
        assert set(read_all(monkeypatch, SMALL, root).values()) == {None}
    assert set(read_all(monkeypatch, dict(SMALL, devices=[]), fits).values()) == {None}
    assert set(read_all(monkeypatch, {}, fits).values()) == {None}


def test_a_root_as_long_as_bench_build_fits(monkeypatch):
    assert read_all(monkeypatch, SMALL, tree(t0=5_000, t1=5_100))["unattributed"] == pytest.approx(70e-6)
    rec = dict(SMALL, window=[0, 3_000_000], host=[["bench/build", 0, 1_000_000]])
    for dur in (1_000_001, 2_000_001):  # ends past bench/build; more than 1 ms past
        assert read_all(monkeypatch, rec, tree(t0=5_000, t1=5_000 + dur))["coder"] is None


def test_attribution_made_once_per_record(monkeypatch):
    calls = []
    real = spans.attribute

    def counted(rec, root):
        calls.append(1)
        return real(rec, root)

    monkeypatch.setattr(spans, "attribute", counted)
    root = tree(span("build/coder", 5_000, 5_010))
    read_all(monkeypatch, SMALL, root)
    assert len(calls) == 1
    read_all(monkeypatch, dict(SMALL), root)  # another record
    assert len(calls) == 2
