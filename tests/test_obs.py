"""Tests for the unified observability layer (DESIGN.md §14).

Contracts:
  1. Registry primitives are exact under contention: counters hammered by
     many threads lose no increments, snapshots taken mid-update are
     consistent views (never errors), and get-or-create returns the same
     object for the same (name, labels) identity.
  2. ``Histogram.pcts_ms`` is bit-identical with the ``np.percentile``
     form the two deleted ``_pcts`` helpers computed — the serve
     ``stats()`` surfaces must not move.
  3. Spans nest per thread, fold CostAccount-style costs in, export as
     JSON lines, and cost nothing when disabled (NULL_SPAN; nothing
     recorded, inputs never ``float()``-ed).
  4. A build's per-phase distance split partitions ``n_dists`` exactly,
     for both incremental and bulk strategies.
  5. Spans run on the profiler's clock (``time.time_ns``), show on a
     ``jax.profiler`` capture's host plane, touch neither clock nor
     profiler when disabled, record each executable JAX makes as a
     ``jit/compile`` child, and cover a bulk build's host phases.
  6. The serve stats surfaces stay registry-backed and API-compatible:
     ``latency_window`` is a ctor knob, ``reset()`` exists on every
     stats() provider, and live Runtime counters agree with the registry
     series under concurrent submit threads + the scheduler thread.
"""

from __future__ import annotations

import glob
import io
import json
import threading
import time

import jax
import numpy as np
import pytest

from repro import obs, serve
from repro.graph.hnsw import HNSWParams
from repro.index import AnnIndex
from repro.obs import report
from tests.conftest import make_clustered

PARAMS = HNSWParams(r_upper=4, r_base=8, ef=16, batch=32, max_layers=2)
FLASH_KW = dict(d_f=32, m_f=16, l_f=4, h=8, kmeans_iters=5)


@pytest.fixture()
def obs_on():
    """Enable obs for one test, restoring the prior global state."""
    was = obs.enabled()
    obs.enable()
    obs.clear_spans()
    yield
    obs.clear_spans()
    obs.enable() if was else obs.disable()


@pytest.fixture()
def obs_off():
    was = obs.enabled()
    obs.disable()
    yield
    obs.enable() if was else obs.disable()


@pytest.fixture(scope="module")
def small_index():
    data = make_clustered(300, 32, seed=3)
    return AnnIndex.build(data, algo="hnsw", backend="fp32", params=PARAMS)


class TestRegistry:
    def test_counter_identity_and_inc(self):
        reg = obs.MetricsRegistry()
        c1 = reg.counter("x_total", route="a")
        c2 = reg.counter("x_total", route="a")
        assert c1 is c2  # get-or-create is idempotent
        c1.inc().inc(4)
        assert c2.value == 5
        c1.reset()
        assert c1.value == 0

    def test_label_order_is_identity_free(self):
        reg = obs.MetricsRegistry()
        assert reg.counter("y", a="1", b="2") is reg.counter("y", b="2", a="1")
        assert reg.counter("y", a="1", b="2").key == 'y{a="1",b="2"}'

    def test_kind_mismatch_raises(self):
        reg = obs.MetricsRegistry()
        reg.counter("z_total")
        with pytest.raises(TypeError, match="already registered"):
            reg.gauge("z_total")

    def test_gauge_set_and_inc(self):
        g = obs.MetricsRegistry().gauge("depth")
        g.set(7)
        assert g.value == 7
        g.inc(-2)
        assert g.value == 5

    def test_histogram_window_bound_and_alltime(self):
        h = obs.MetricsRegistry().histogram("lat", window=8)
        for i in range(20):
            h.observe(i * 1e-3)
        assert len(h) == 8  # bounded reservoir
        assert h.count == 20  # all-time count survives eviction
        assert h.sum == pytest.approx(sum(i * 1e-3 for i in range(20)))
        np.testing.assert_allclose(
            h.values(), [i * 1e-3 for i in range(12, 20)]
        )

    def test_pcts_bit_identical_with_np_percentile(self):
        # the deleted serve/_pcts helpers were exactly this expression;
        # stats() surfaces must not move by a single ulp
        rng = np.random.default_rng(0)
        vals = rng.exponential(0.01, size=137)
        h = obs.MetricsRegistry().histogram("lat", window=4096)
        for v in vals:
            h.observe(v)
        lat = np.asarray(vals, np.float64)
        expect = (
            float(np.percentile(lat, 50) * 1e3),
            float(np.percentile(lat, 99) * 1e3),
        )
        assert h.pcts_ms() == expect
        assert obs.pcts_ms(vals) == expect
        assert obs.pcts_ms([]) == (0.0, 0.0)

    def test_snapshot_shape_and_reset_keeps_identity(self):
        reg = obs.MetricsRegistry()
        c = reg.counter("a_total", k="v").inc(3)
        reg.gauge("b").set(2)
        reg.histogram("c", window=4).observe(0.5)
        snap = reg.snapshot()
        assert snap["counters"]['a_total{k="v"}'] == 3
        assert snap["gauges"]["b"] == 2
        hsnap = snap["histograms"]["c"]
        assert hsnap["count"] == 1 and hsnap["window"] == 4
        assert set(hsnap) == {
            "count", "sum", "window_len", "window", "p50_ms", "p99_ms"
        }
        reg.reset()
        assert c is reg.counter("a_total", k="v")  # identity survives reset
        assert c.value == 0
        reg.clear()
        assert reg.snapshot() == {
            "counters": {}, "gauges": {}, "histograms": {}
        }

    def test_next_instance_is_unique(self):
        reg = obs.MetricsRegistry()
        ids = [reg.next_instance() for _ in range(10)]
        assert len(set(ids)) == 10


class TestConcurrency:
    def test_counter_exact_under_thread_contention(self):
        c = obs.MetricsRegistry().counter("hammer_total")
        n_threads, n_incs = 8, 2000

        def hammer():
            for _ in range(n_incs):
                c.inc()

        threads = [threading.Thread(target=hammer) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert c.value == n_threads * n_incs  # no lost increments

    def test_snapshot_during_updates_is_consistent(self):
        reg = obs.MetricsRegistry()
        h = reg.histogram("lat", window=64)
        c = reg.counter("events_total")
        stop = threading.Event()
        errors: list = []

        def writer():
            i = 0
            while not stop.is_set():
                h.observe(i * 1e-4)
                c.inc()
                i += 1

        def reader():
            try:
                while not stop.is_set():
                    snap = reg.snapshot()
                    hs = snap["histograms"]["lat"]
                    # the windowed copy is internally consistent
                    assert hs["window_len"] <= 64
                    assert hs["count"] >= hs["window_len"]
                    h.pcts_ms()
            except Exception as e:  # noqa: BLE001
                errors.append(e)

        ts = [threading.Thread(target=writer) for _ in range(2)]
        ts += [threading.Thread(target=reader) for _ in range(2)]
        for t in ts:
            t.start()
        stop.wait(0.5)
        stop.set()
        for t in ts:
            t.join()
        assert not errors

    def test_runtime_scheduler_thread_agrees_with_registry(self, small_index):
        """Client threads + the Runtime scheduler thread hammer the same
        admission counters; stats() and the registry series must agree."""
        queries = make_clustered(32, 32, seed=4)
        with serve.Runtime(
            small_index, k=5, ef=16, q_buckets=(1, 8), max_wait_ms=2.0
        ) as rt:
            rt.warmup()
            futs: list = []
            futs_lock = threading.Lock()

            def client(chunk):
                for q in chunk:
                    f = rt.submit(q)
                    with futs_lock:
                        futs.append(f)

            threads = [
                threading.Thread(target=client, args=(queries[i::4],))
                for i in range(4)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            for f in futs:
                f.result(60)
            stats = rt.stats()
        assert stats["admitted"] == len(queries)
        assert stats["served"] == len(queries)
        assert stats["shed"] == 0
        # the stats() view is the registry series, not a parallel book
        ctl = rt.admission
        assert int(ctl._counters["admitted"].value) == stats["admitted"]
        assert int(ctl._counters["served"].value) == stats["served"]
        assert len(ctl._e2e_lat) == stats["served"]


class TestSpans:
    def test_nesting_and_cost_folding(self, obs_on):
        with obs.span("outer", algo="hnsw") as sp_out:
            with obs.span("inner") as sp_in:
                sp_in.add_cost(np.float32(10.0), 2)  # device-ish scalar ok
            sp_out.set(extra=1)
        roots = obs.spans("outer")
        assert len(roots) == 1
        out = roots[0]
        assert out.attrs == {"algo": "hnsw", "extra": 1}
        assert [c.name for c in out.children] == ["inner"]
        assert out.children[0].n_dists == 10.0
        assert out.children[0].n_hops == 2.0
        assert out.dur_s >= out.children[0].dur_s >= 0.0
        # iter_spans walks descendants too
        assert [s.name for s in obs.iter_spans()] == ["outer", "inner"]

    def test_disabled_records_nothing(self, obs_off):
        class Unfloatable:
            def __float__(self):
                raise AssertionError("disabled add_cost must not float()")

        with obs.span("ghost") as sp:
            assert sp is obs.NULL_SPAN
            sp.add_cost(Unfloatable())  # no sync / no conversion
            sp.set(x=1)
        assert obs.spans("ghost") == []

    def test_export_jsonl(self, obs_on):
        with obs.span("a"):
            with obs.span("b"):
                pass
        with obs.span("c"):
            pass
        buf = io.StringIO()
        assert obs.export_jsonl(buf) == 2  # root spans only
        lines = [json.loads(ln) for ln in buf.getvalue().splitlines()]
        assert [ln["name"] for ln in lines] == ["a", "c"]
        assert [c["name"] for c in lines[0]["children"]] == ["b"]
        obs.clear_spans()
        assert obs.spans() == []

    def test_tick_is_gated(self, obs_on):
        obs.tick("gated_total", n=2, route="x")
        assert obs.REGISTRY.counter("gated_total", route="x").value == 2
        obs.disable()
        obs.tick("gated_total", n=100, route="x")
        obs.enable()
        assert obs.REGISTRY.counter("gated_total", route="x").value == 2


class TestProfilerClock:
    def test_span_clock_is_time_ns(self, obs_on):
        before = time.time_ns()
        with obs.span("clocked"):
            pass
        after = time.time_ns()
        sp = obs.spans("clocked")[-1]
        assert before <= sp.t0_ns <= sp.t1_ns <= after
        assert sp.dur_s == (sp.t1_ns - sp.t0_ns) / 1e9
        d = sp.to_dict()
        assert (d["t0_ns"], d["t1_ns"]) == (sp.t0_ns, sp.t1_ns)

    def test_span_on_the_profilers_host_plane(self, obs_on, tmp_path):
        from jax.profiler import ProfileData

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            with obs.span("obs-test/phase"):
                time.sleep(0.002)
        finally:
            jax.profiler.stop_trace()
        sp = obs.spans("obs-test/phase")[-1]
        (path,) = glob.glob(
            str(tmp_path / "**" / "*.xplane.pb"), recursive=True
        )
        pd = ProfileData.from_file(path)
        (env,) = [p for p in pd.planes if p.name == "Task Environment"]
        start = dict(env.stats)["profile_start_time"]
        events = [
            ev for p in pd.planes if p.name.startswith("/host:")
            for line in p.lines for ev in line.events
            if ev.name == "obs-test/phase"
        ]
        assert len(events) == 1
        assert abs(start + events[0].start_ns - sp.t0_ns) < 1e6
        assert abs(events[0].duration_ns - (sp.t1_ns - sp.t0_ns)) < 1e6

    def test_disabled_span_reads_no_clock_and_emits_nothing(
        self, obs_off, monkeypatch
    ):
        from repro.obs import trace

        def boom(*_a, **_k):
            raise AssertionError("a disabled span touched a clock or the profiler")

        monkeypatch.setattr(trace, "trace_clock_ns", boom)
        monkeypatch.setattr(trace, "now", boom)
        monkeypatch.setattr(trace, "TraceAnnotation", boom)
        with obs.span("ghost", attr=1) as sp:
            sp.set(x=2)
        assert sp is obs.NULL_SPAN

    def test_compile_is_a_child_span_and_counted(self, obs_on):
        def obs_test_program(x):
            return x * 3 + 1

        counter = obs.REGISTRY.counter(
            "jit_executables_total", program="jit(obs_test_program)"
        )
        before = counter.value
        with obs.span("compiling"):
            t0 = time.time_ns()
            jax.jit(obs_test_program)(np.ones(7, np.float32))
            t1 = time.time_ns()
        sp = obs.spans("compiling")[-1]
        kids = [
            c for c in sp.children
            if c.name == "jit/compile"
            and c.attrs["program"] == "jit(obs_test_program)"
        ]
        assert len(kids) == 1
        # time.time() seconds converted to the span clock
        assert t0 - 1e6 <= kids[0].t0_ns <= kids[0].t1_ns <= t1 + 1e6
        assert counter.value == before + 1
        assert obs.REGISTRY.counter(
            "jit_compile_seconds_total", program="jit(obs_test_program)"
        ).value > 0

    def test_bulk_build_span_tree(self, obs_on):
        data = make_clustered(400, 32, seed=5)
        AnnIndex.build(
            data, algo="hnsw", strategy="bulk", params=PARAMS,
            backend_kwargs=FLASH_KW,
        )
        root = obs.spans("build")[-1]

        def names(sp):
            return [c.name for c in sp.children if c.name != "jit/compile"]

        top = names(root)
        assert top[0] == "build/coder" and top[-1] == "build/repair"
        assert set(top[1:-1]) == {"build/bulk_refine", "build/bulk_commit"}
        (coder,) = [c for c in root.children if c.name == "build/coder"]
        assert names(coder) == [
            "build/coder/pca", "build/coder/kmeans", "build/coder/encode"
        ]
        refine = [c for c in root.children if c.name == "build/bulk_refine"]
        assert all(c.attrs["rounds"] >= 1 for c in refine)
        (repair,) = [c for c in root.children if c.name == "build/repair"]
        assert names(repair)[0] == "build/repair/bfs"
        assert "unreachable" in repair.children[0].attrs
        assert repair.attrs["passes"] >= 0
        for c in repair.children:
            if c.name == "build/repair/reinsert":
                assert c.attrs["schedule"] <= c.attrs["schedule_padded"]
        # children lie inside their parents on one clock
        for parent in [root, coder, repair]:
            for c in parent.children:
                if c.name != "jit/compile":
                    assert parent.t0_ns <= c.t0_ns <= c.t1_ns <= parent.t1_ns


class TestBuildPhases:
    @pytest.mark.parametrize("strategy", ["incremental", "bulk"])
    def test_phase_split_partitions_n_dists_exactly(self, strategy, obs_on):
        data = make_clustered(400, 32, seed=5)
        idx = AnnIndex.build(
            data, algo="hnsw", strategy=strategy, params=PARAMS,
            backend_kwargs=FLASH_KW,
        )
        stats = idx.last_stats
        assert stats.phases is not None
        phases = np.asarray(stats.phases, np.float64)
        assert float(phases.sum()) == float(stats.n_dists)  # exact, not ≈
        if strategy == "bulk":
            assert phases[3] > 0  # bulk phase did the work
        else:
            assert phases[2] > 0  # base-layer beam did the work
        # the build span recorded the same totals
        roots = obs.spans("build")
        assert roots and roots[-1].n_dists == float(stats.n_dists)
        assert roots[-1].attrs["strategy"] == strategy


class TestStatsSurfaces:
    def test_engine_latency_window_is_ctor_knob(self, small_index):
        engine = serve.SearchEngine(
            small_index, k=5, ef=16, q_buckets=(1,), latency_window=16
        )
        assert engine.latency_window == 16
        assert engine._lat.window == 16
        q = make_clustered(1, 32, seed=6)[0]
        for _ in range(20):
            engine.search(q)
        assert len(engine._lat) == 16  # bounded by the ctor knob
        assert engine.stats()["calls"] == 20

    def test_reset_on_every_stats_provider(self, small_index):
        engine = serve.SearchEngine(
            small_index, k=5, ef=16, q_buckets=(1,), latency_window=8
        )
        q = make_clustered(1, 32, seed=7)[0]
        engine.search(q)
        n_compiles = engine.n_compiles
        engine.reset()
        stats = engine.stats()
        assert stats["calls"] == 0 and stats["p50_ms"] == 0.0
        assert engine.n_compiles == n_compiles  # compiles survive reset

        ctl = serve.AdmissionController()
        ctl.admit(0)
        ctl.record_served(1e-3, 2e-3, missed=False)
        ctl.reset()
        stats = ctl.stats()
        assert stats["admitted"] == 0 and stats["served"] == 0
        assert stats["p50_ms"] == 0.0

        with serve.Runtime(
            small_index, k=5, ef=16, q_buckets=(1,), max_wait_ms=2.0
        ) as rt:
            rt.warmup()
            rt.search(q, 60)
            assert rt.stats()["served"] == 1
            rt.reset()
            assert rt.stats()["served"] == 0
            assert rt.stats()["cold_dispatches"] == 0

    def test_flip_spans_and_counter(self, small_index, obs_on):
        handle = serve.IndexHandle(small_index.clone())
        flips_before = obs.REGISTRY.counter("serve_flips_total").value
        gen = handle.add(make_clustered(4, 32, seed=8))
        assert gen.gen == 1
        assert obs.REGISTRY.counter("serve_flips_total").value == (
            flips_before + 1
        )
        sp = obs.spans("serve/flip")[-1]
        assert sp.attrs["base_gen"] == 0 and sp.attrs["gen"] == 1
        names = [c.name for c in sp.children]
        assert names == [
            "serve/flip/clone", "serve/flip/apply", "serve/flip/prepare"
        ]


class TestReport:
    def test_prometheus_text_rendering(self):
        snap = {
            "counters": {'req_total{route="a"}': 3, "plain_total": 1},
            "gauges": {"depth": 2},
            "histograms": {
                'lat_seconds{inst="0"}': {
                    "count": 5, "sum": 0.5, "window_len": 5,
                    "window": 4096, "p50_ms": 10.0, "p99_ms": 90.0,
                },
            },
        }
        text = report.prometheus_text(snap)
        assert 'req_total{route="a"} 3' in text
        assert "plain_total 1" in text
        assert "depth 2" in text
        assert 'lat_seconds_count{inst="0"} 5' in text
        assert 'lat_seconds_ms{inst="0",quantile="0.5"} 10.0' in text
        assert text.endswith("\n")

    def test_phase_table_exactness_line(self):
        class Stub:
            n_dists = 100.0
            phases = np.asarray([10.0, 20.0, 70.0, 0.0, 0.0])

        table = report.phase_table(Stub())
        assert "exact partition: True" in table
        assert "beam_base" in table

        class Bad:
            n_dists = 100.0
            phases = np.asarray([10.0, 20.0, 60.0, 0.0, 0.0])

        assert "exact partition: False" in report.phase_table(Bad())

    def test_json_dump_structure(self, obs_on):
        obs.counter("dump_total", k="v").inc()
        with obs.span("dump_span"):
            pass
        out = report.json_dump()
        assert 'dump_total{k="v"}' in out["metrics"]["counters"]
        assert any(sp["name"] == "dump_span" for sp in out["spans"])
