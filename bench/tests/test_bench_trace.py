"""The reduction from a device trace to numbers."""

import json
import os

import pytest

from bench_checkout import BENCH  # also puts the harness on the path
from harness import trace

# window 0..100; ops cover [10,30) (two overlapping) and [50,60); the host
# was in bench/build throughout and in bench/build/repair over [30,50)
SMALL = {
    "window": [0, 100],
    "devices": [{
        "name": "/device:TPU:0",
        "ops": [["while.1", 10, 20, ""], ["a", 10, 15, ""], ["b", 20, 10, ""],
                ["c", 50, 10, ""], ["late", 120, 5, ""]],
        "modules": [["jit_x(1)", 10, 20], ["jit_bulk_commit(2)", 50, 10]],
    }],
    "host": [["bench/window", 0, 100], ["bench/build", 0, 100],
             ["bench/build/repair", 30, 20]],
}


def test_union_and_busy_share():
    assert trace.union([(10, 15), (20, 10), (50, 10)]) == [(10, 30), (50, 60)]
    assert trace.busy_ns(SMALL) == [30]
    assert trace.busy_share(SMALL) == pytest.approx(0.3)


def test_idle_time_is_summed_by_the_innermost_host_span():
    # idle [0,10) and [60,100) in bench/build; [30,50) in bench/build/repair;
    # each gap also named by the program that ends it
    assert trace.idle_gaps(SMALL) == [
        ["bench/build > end", pytest.approx(40e-9)],
        ["bench/build/repair > bulk_commit", pytest.approx(20e-9)],
        ["bench/build > x", pytest.approx(10e-9)],
    ]


def test_outermost_ops_and_program_seconds():
    # a and b run inside while.1 and count there; "late" is past the window
    assert trace.top_ops(SMALL) == [["x/while.1", pytest.approx(20e-9)],
                                    ["bulk_commit/c", pytest.approx(10e-9)]]
    assert trace.module_seconds(SMALL, "bulk_commit") == pytest.approx(10e-9)
    assert trace.module_seconds(SMALL, "bulk_refine") is None


def test_no_window_reads_nothing():
    rec = dict(SMALL, window=None)
    assert trace.busy_share(rec) is None
    assert trace.idle_gaps(rec) == []


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(BENCH, "data", "trace_small.json")) as f:
        return json.load(f)


class Reading:
    def __init__(self, record, config, peaks):
        self.record, self.config, self.peaks, self.layer = record, config, peaks, {}


def load_reader(name):
    import importlib.util

    path = os.path.join(BENCH, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("reader_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_recorded_busy_and_idle_add_up_to_the_window(recorded):
    lo, hi = recorded["window"]
    # busy by a sweep over every nanosecond boundary, independent of union()
    edges = sorted({t for o in recorded["devices"][0]["ops"] for t in (o[1], o[1] + o[2])})
    busy = sum(b - a for a, b in zip(edges, edges[1:])
               if any(o[1] <= a and b <= o[1] + o[2] for o in recorded["devices"][0]["ops"]))
    assert trace.busy_ns(recorded) == [busy]
    idle = sum(s for _, s in trace.idle_gaps(recorded, top=100))
    assert idle * 1e9 + busy == pytest.approx(hi - lo, abs=100)
    assert trace.idle_gaps(recorded)[0][0].startswith("bench/build/coder > ")


def test_recorded_top_ops_are_outermost_and_named_by_program(recorded):
    top = trace.top_ops(recorded, top=1000)
    assert all("/" in name for name, _ in top)
    assert not any(name.split("/")[1].startswith("flash_round") for name, _ in top)
    assert sum(s for _, s in top) * 1e9 == pytest.approx(trace.busy_ns(recorded)[0], rel=1e-6)


def test_recorded_flash_round_roofline(recorded):
    with open(os.path.join(BENCH, "configs", "deep-96.json")) as f:
        config = json.load(f)
    with open(os.path.join(BENCH, "peaks.json")) as f:
        peaks = json.load(f)["devices"]["TPU v5 lite"]
    calls = trace.kernel_calls(recorded, "flash_round")
    assert [trace.out_dims(t) for _, t in calls] == [(256, 64)] * len(calls)
    # 256 vertices x 64 candidates; 48 codes of 4 bits, 48 x 16 one-byte
    # table entries a vertex, 4-byte sums: HBM-bound
    least = (256 * 64 * 48 / 2 + 256 * 48 * 16 + 256 * 64 * 4) / peaks["hbm_bytes_per_s"]
    want = 100 * len(calls) * least / (sum(d for d, _ in calls) / 1e9)
    got = load_reader("flash_round_roofline")(Reading(recorded, config, peaks))
    assert got == pytest.approx(want)
    assert 0 < got < 100
    assert load_reader("flash_round_roofline")(Reading(recorded, config, None)) is None


def test_recorded_program_time(recorded):
    got = load_reader("build.refine_ms")(Reading(recorded, {}, None))
    want = sum(d for n, s, d in recorded["devices"][0]["modules"]
               if "bulk_refine" in n and recorded["window"][0] <= s < recorded["window"][1])
    assert got == pytest.approx(want / 1e6)
    assert load_reader("build.commit_ms")(Reading(recorded, {}, None)) is None
