"""Sharded parallel build + serve (graph/sharded.py, DESIGN.md §16).

What is being asserted:

  1. Streaming assignment: chunked-callable and array sources produce
     byte-identical spill plans; global ids partition [0, N); balanced
     routing respects the per-segment capacity; the one-shot-iterator
     misuse fails loudly; ``ops.nearest_centroid`` matches the argmin
     oracle (with banned-segment masking).
  2. The parity grid: a sharded build (inline and process-pool) is
     BIT-EXACT with a sequential ``SegmentedAnnIndex.build`` over the same
     assignment, across algo × backend — every exported segment array is
     equal, and fan-out searches agree after mapping global ids through
     each side's locator.
  3. Parallel query fan-out (``SegmentedAnnIndex.search`` /
     ``SegmentRouter``) returns results identical to the sequential loop.
  4. Lifecycle decoupling: the published manifest + per-segment snapshots
     load in a FRESH process (the attach-on-another-host step) and search
     identically; ``serve.init_from_manifest`` adopts the manifest as a
     durable recovery root.
  5. Graceful fallback: no mesh + no workers builds inline through the
     same code path; a 1-device mesh degrades the same way.
  6. The coordinator's assignment memory stays O(chunk + segments) — peak
     RSS growth while streaming a ~100 MB virtual dataset is a small
     fraction of materializing it (subprocess, getrusage).
  7. A sharded build emits one obs profile: a ``shard/build`` root span
     with one ``shard/segment`` child per segment carrying worker, phase
     split, and cost labels that sum to the workers' reported n_dists.
"""

from __future__ import annotations

import os
import subprocess
import sys
import textwrap

import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs, serve
from repro.graph import AnnIndex, BuildParams
from repro.graph.segmented import SegmentedAnnIndex
from repro.graph.sharded import (
    ShardConfig,
    ShardedBuilder,
    ShardPlan,
    bootstrap_centroids,
    fanout_map,
    iter_chunks,
    model_parallel_wall,
    reservoir_sample,
    stream_assign,
)
from repro.kernels import ops

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PARAMS = BuildParams(r_upper=8, r_base=16, ef=32, batch=32, max_layers=2)
N, D, S = 1200, 32, 3


def clustered(n: int = N, d: int = D, seed: int = 0) -> np.ndarray:
    rng = np.random.default_rng(seed)
    centers = rng.normal(size=(8, d)).astype(np.float32) * 1.5
    x = centers[rng.integers(0, 8, n)]
    return (x + rng.normal(size=(n, d)).astype(np.float32) * 0.3).astype(
        np.float32
    )


@pytest.fixture(scope="module")
def data():
    return clustered()


@pytest.fixture(scope="module")
def queries():
    return clustered(24, D, seed=99)


def _config(tmpdir, **over) -> ShardConfig:
    kw = dict(
        n_segments=S, chunk_size=256, algo="hnsw", backend="fp32",
        params=PARAMS, sample_size=512, seed=0,
    )
    kw.update(over)
    return ShardConfig(**kw)


@pytest.fixture(scope="module")
def inline_result(data, tmp_path_factory):
    """One inline sharded build with a published manifest, shared below."""
    wd = tmp_path_factory.mktemp("inline")
    builder = ShardedBuilder(_config(wd), workdir=str(wd))
    return builder.build(data, snapshot_path=str(wd / "index"))


@pytest.fixture(scope="module")
def pool_result(data, tmp_path_factory):
    """One 2-worker process-pool build (spawn; disk is the transport)."""
    wd = tmp_path_factory.mktemp("pool")
    builder = ShardedBuilder(_config(wd), workers=2, workdir=str(wd))
    return builder.build(data, snapshot_path=str(wd / "index"))


def _map_local(seg_index, gids: np.ndarray) -> np.ndarray:
    """Global ids -> (segment, local) pairs via the index's locator
    (padding −1 stays −1), so id schemes with different global numbering
    compare on physical identity."""
    gids = np.asarray(gids)
    out = np.full(gids.shape + (2,), -1, np.int64)
    valid = gids >= 0
    out[valid] = seg_index._locate[gids[valid]]
    return out


# ---------------------------------------------------------------------------
# 1. Streaming assignment
# ---------------------------------------------------------------------------


class TestAssign:
    def test_chunked_callable_matches_array_source(self, data, tmp_path):
        cents = bootstrap_centroids(data, S, sample_size=512, seed=0)

        # balanced routing is a greedy pass over chunks, so equality holds
        # per chunk-partition: the callable must yield the same boundaries
        def chunks():
            for i in range(0, N, 256):
                yield data[i : i + 256]

        p1 = stream_assign(data, cents, str(tmp_path / "a"), chunk_size=256)
        p2 = stream_assign(
            chunks, cents, str(tmp_path / "b"), chunk_size=256, n_total=N
        )
        assert p1.seg_sizes == p2.seg_sizes
        for s in range(S):
            v1, g1 = p1.load_segment(s)
            v2, g2 = p2.load_segment(s)
            np.testing.assert_array_equal(g1, g2)
            np.testing.assert_array_equal(v1, v2)

    def test_gids_partition_and_locate(self, inline_result):
        plan = inline_result.plan
        allg = np.concatenate(plan.global_of())
        np.testing.assert_array_equal(np.sort(allg), np.arange(plan.n))
        loc = plan.locate()
        for s, gids in enumerate(plan.global_of()):
            assert (loc[gids, 0] == s).all()
            np.testing.assert_array_equal(loc[gids, 1], np.arange(len(gids)))

    def test_balanced_respects_capacity(self, data, tmp_path):
        cents = bootstrap_centroids(data, S, sample_size=512, seed=0)
        cap = -(-N // S)
        plan = stream_assign(data, cents, str(tmp_path / "c"), chunk_size=256)
        assert max(plan.seg_sizes) <= cap
        assert sum(plan.seg_sizes) == N

    def test_unbalanced_is_pure_nearest(self, data, tmp_path):
        cents = bootstrap_centroids(data, S, sample_size=512, seed=0)
        plan = stream_assign(
            data, cents, str(tmp_path / "u"), chunk_size=256, balanced=False
        )
        want = np.asarray(
            jnp.argmin(ops.l2_batch(jnp.asarray(data), jnp.asarray(cents)), axis=1)
        )
        loc = plan.locate()
        np.testing.assert_array_equal(loc[:, 0], want)

    def test_one_shot_iterator_rejected(self, data, tmp_path):
        builder = ShardedBuilder(_config(tmp_path), workdir=str(tmp_path))
        with pytest.raises(TypeError, match="re-creates"):
            builder.assign(iter([data]))

    def test_plan_round_trips(self, inline_result):
        plan = inline_result.plan
        again = ShardPlan.load(plan.spill_dir)
        assert again.seg_sizes == plan.seg_sizes
        assert (again.n, again.d) == (plan.n, plan.d)
        np.testing.assert_array_equal(again.centroids, plan.centroids)

    def test_reservoir_sample_shape_and_determinism(self, data):
        s1 = reservoir_sample(data, 300, seed=7)
        s2 = reservoir_sample(
            lambda: iter_chunks(data, 128), 300, seed=7
        )
        assert s1.shape == (300, D)
        np.testing.assert_array_equal(s1, s2)

    def test_nearest_centroid_matches_oracle(self, data):
        cents = jnp.asarray(data[:5])
        route, d2 = ops.nearest_centroid(jnp.asarray(data), cents)
        full = np.asarray(ops.l2_batch(jnp.asarray(data), cents))
        np.testing.assert_array_equal(np.asarray(route), full.argmin(axis=1))
        np.testing.assert_allclose(np.asarray(d2), full.min(axis=1), rtol=1e-6)
        banned = jnp.asarray(np.eye(5, dtype=bool)[0])
        route_b, _ = ops.nearest_centroid(jnp.asarray(data), cents, banned=banned)
        assert (np.asarray(route_b) != 0).all()


# ---------------------------------------------------------------------------
# 2. The parity grid: sharded ≡ sequential segmented, bit-exact
# ---------------------------------------------------------------------------


GRID = [
    ("hnsw", "fp32"),
    ("hnsw", "flash_blocked"),
    ("vamana", "fp32"),
    ("nsg", "flash_blocked"),
]


class TestParityGrid:
    @pytest.mark.parametrize("algo,backend", GRID)
    def test_sharded_equals_sequential_on_same_assignment(
        self, data, queries, tmp_path, algo, backend
    ):
        bk = (
            dict(d_f=16, m_f=8, kmeans_iters=5)
            if backend.startswith("flash") else None
        )
        cfg = _config(
            tmp_path, algo=algo, backend=backend, n_segments=2,
            backend_kwargs=bk,
        )
        builder = ShardedBuilder(cfg, workdir=str(tmp_path))
        res = builder.build(data[:800])
        assert res.mode == "inline"
        plan = res.plan
        seq = SegmentedAnnIndex.build(
            (plan.load_segment(s)[0] for s in range(2)),
            algo=algo, backend=backend, params=PARAMS, seed=0,
            backend_kwargs=bk,
        )
        # bit-exact per-segment state: every exported array equal
        for s in range(2):
            _, a = res.index.segments[s].export_state()
            _, b = seq.segments[s].export_state()
            assert set(a) == set(b)
            for name in a:
                np.testing.assert_array_equal(
                    a[name], b[name], err_msg=f"{algo}/{backend} seg{s} {name}"
                )
        # fan-out search parity on physical (segment, local) identity —
        # global numbering differs (stream order vs contiguous ranges)
        r1 = res.index.search(queries, k=5)
        r2 = seq.search(queries, k=5)
        np.testing.assert_array_equal(
            np.asarray(r1.dists), np.asarray(r2.dists)
        )
        np.testing.assert_array_equal(
            _map_local(res.index, np.asarray(r1.ids)),
            _map_local(seq, np.asarray(r2.ids)),
        )

    def test_pool_build_is_bit_exact_with_inline(
        self, inline_result, pool_result
    ):
        """Same assignment + same per-segment program in another process
        must produce the same bits (jax CPU determinism) — the claim that
        lets a fleet build segments anywhere."""
        assert pool_result.mode == "pool"
        assert all(m["pid"] != os.getpid() for m in pool_result.segments)
        for s in range(S):
            _, a = inline_result.index.segments[s].export_state()
            _, b = pool_result.index.segments[s].export_state()
            for name in a:
                np.testing.assert_array_equal(a[name], b[name])

    def test_worker_metrics_reported(self, pool_result):
        for m in pool_result.segments:
            assert m["n_vectors"] > 0
            assert m["wall_s"] > 0
            assert m["n_dists"] > 0
            assert m["max_rss_mb"] > 0
            assert m["phases"] is not None and sum(m["phases"].values()) > 0
            # the worker wrote into the staging dir; after the atomic
            # publish the segment lives under the final snapshot path
            assert os.path.isdir(
                serve.segment_dir(pool_result.snapshot_path, m["seg"])
            )


# ---------------------------------------------------------------------------
# 3. Parallel fan-out search ≡ sequential loop
# ---------------------------------------------------------------------------


class TestFanout:
    def test_segmented_search_fanout_parity(self, inline_result, queries):
        idx = inline_result.index
        par = idx.search(queries, k=5)
        seq = idx.search(queries, k=5, fanout=False)
        np.testing.assert_array_equal(np.asarray(par.ids), np.asarray(seq.ids))
        np.testing.assert_array_equal(
            np.asarray(par.dists), np.asarray(seq.dists)
        )
        assert float(par.n_scan) == float(seq.n_scan)

    def test_router_fanout_parity(self, inline_result, queries):
        idx = inline_result.index
        router = serve.SegmentRouter(
            idx, n_probe=S, k=5, ef=32, q_buckets=(8, 32)
        ).warmup()
        par = router.search(queries)
        router.fanout = False
        seq = router.search(queries)
        np.testing.assert_array_equal(np.asarray(par.ids), np.asarray(seq.ids))
        np.testing.assert_array_equal(
            np.asarray(par.dists), np.asarray(seq.dists)
        )
        assert router.stats()["fanout"] is False

    def test_fanout_map_order_and_fallback(self):
        items = list(range(17))
        assert fanout_map(lambda x: x * x, items) == [x * x for x in items]
        assert fanout_map(lambda x: -x, items, parallel=False) == [
            -x for x in items
        ]

    def test_model_parallel_wall(self):
        assert model_parallel_wall([3, 3, 3, 3], 1) == pytest.approx(12.0)
        assert model_parallel_wall([3, 3, 3, 3], 4) == pytest.approx(3.0)
        assert model_parallel_wall([4, 3, 2, 1], 2) == pytest.approx(5.0)


# ---------------------------------------------------------------------------
# 4. Manifest lifecycle: fresh-process attach + durable adoption
# ---------------------------------------------------------------------------


class TestManifest:
    def test_manifest_loads_and_matches(self, pool_result, queries):
        loaded = serve.load_index(pool_result.snapshot_path)
        r1 = pool_result.index.search(queries, k=5)
        r2 = loaded.search(queries, k=5)
        np.testing.assert_array_equal(np.asarray(r1.ids), np.asarray(r2.ids))

    def test_attach_in_fresh_process(self, pool_result, queries, tmp_path):
        """The other-host story end-to-end: a process that took no part in
        the build loads the published manifest, serves it, and adopts it
        as a durable recovery root."""
        want = np.asarray(pool_result.index.search(queries, k=5).ids)
        np.save(tmp_path / "queries.npy", queries)
        np.save(tmp_path / "want.npy", want)
        script = textwrap.dedent(f"""
            import numpy as np
            from repro import serve
            q = np.load({str(tmp_path / 'queries.npy')!r})
            want = np.load({str(tmp_path / 'want.npy')!r})
            idx = serve.load_index({pool_result.snapshot_path!r})
            got = np.asarray(idx.search(q, k=5).ids)
            assert np.array_equal(got, want), "fresh-process search diverged"
            root, live = serve.init_from_manifest(
                {str(tmp_path / 'root')!r}, {pool_result.snapshot_path!r}
            )
            rec = serve.recover(root)
            got2 = np.asarray(rec.index.search(q, k=5).ids)
            assert np.array_equal(got2, want)
            assert rec.replayed == 0 and not rec.degraded
            print("FRESH-ATTACH-OK")
        """)
        env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
        proc = subprocess.run(
            [sys.executable, "-c", script], env=env, capture_output=True,
            text=True, timeout=600,
        )
        assert proc.returncode == 0, proc.stderr[-2000:]
        assert "FRESH-ATTACH-OK" in proc.stdout

    def test_quarantine_on_segment_corruption(self, pool_result, tmp_path):
        import shutil

        root = str(tmp_path / "corrupt")
        shutil.copytree(pool_result.snapshot_path, root)
        with open(os.path.join(serve.segment_dir(root, 1), "arrays.npz"), "r+b") as f:
            f.seek(60)
            b = f.read(1)
            f.seek(60)
            f.write(bytes([b[0] ^ 0xFF]))
        idx = serve.load_index(root, quarantine=True)
        assert idx.quarantined == {1}
        assert idx.health()["degraded"]


# ---------------------------------------------------------------------------
# 5. Graceful single-device fallback + facade entry point
# ---------------------------------------------------------------------------


class TestFallback:
    def test_no_mesh_no_workers_runs_inline(self, inline_result):
        assert inline_result.mode == "inline"
        assert inline_result.n_workers == 1

    def test_one_device_mesh_degrades_to_inline(self, data, tmp_path):
        from repro.launch.mesh import make_segment_mesh

        mesh = make_segment_mesh(1)
        builder = ShardedBuilder(
            _config(tmp_path, n_segments=2), mesh=mesh, workdir=str(tmp_path)
        )
        res = builder.build(data[:400])
        assert res.mode == "inline"
        assert res.index.n == 400

    def test_pool_refused_on_tpu(self, data, tmp_path, monkeypatch):
        """A spawned worker cannot reach the chip its parent holds: on a
        TPU backend ``workers>1`` raises before any process starts."""
        import repro.graph.sharded as sharded_mod

        monkeypatch.setattr(sharded_mod.jax, "default_backend", lambda: "tpu")
        builder = ShardedBuilder(
            _config(tmp_path, n_segments=2), workers=2, workdir=str(tmp_path)
        )
        with pytest.raises(RuntimeError, match="mesh="):
            builder.build(data[:400])

    def test_build_streaming_facade(self, data, queries, tmp_path):
        idx = SegmentedAnnIndex.build_streaming(
            data, n_segments=S, chunk_size=256, algo="hnsw", backend="fp32",
            params=PARAMS, seed=0, workdir=str(tmp_path / "a"),
        )
        ref = ShardedBuilder(
            ShardConfig(n_segments=S, chunk_size=256, algo="hnsw",
                        backend="fp32", params=PARAMS, seed=0),
            workdir=str(tmp_path / "b"),
        ).build(data)
        r1 = idx.search(queries, k=5)
        r2 = ref.index.search(queries, k=5)
        np.testing.assert_array_equal(np.asarray(r1.ids), np.asarray(r2.ids))

    def test_segmented_build_accepts_generator(self, data, queries):
        segs = [data[i * 400 : (i + 1) * 400] for i in range(3)]
        from_gen = SegmentedAnnIndex.build(
            (s for s in segs), algo="hnsw", backend="fp32", params=PARAMS
        )
        from_list = SegmentedAnnIndex.build(
            segs, algo="hnsw", backend="fp32", params=PARAMS
        )
        r1 = from_gen.search(queries, k=5)
        r2 = from_list.search(queries, k=5)
        np.testing.assert_array_equal(np.asarray(r1.ids), np.asarray(r2.ids))


# ---------------------------------------------------------------------------
# 6. Coordinator memory: assignment is O(chunk + segments)
# ---------------------------------------------------------------------------


MEMORY_SCRIPT = """
import resource, numpy as np
from repro.graph.sharded import bootstrap_centroids, stream_assign

N, D, CHUNK = 262144, 96, 16384          # ~96 MB of f32 if materialized

def chunks():
    for i in range(N // CHUNK):
        rng = np.random.default_rng(i)   # regenerable: nothing retained
        yield rng.normal(size=(CHUNK, D)).astype(np.float32)

cents = bootstrap_centroids(chunks, 8, sample_size=4096, seed=0,
                            chunk_size=CHUNK)
base = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0
plan = stream_assign(chunks, cents, "@SPILL@", chunk_size=CHUNK, n_total=N)
peak = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0
assert sum(plan.seg_sizes) == N
grown = peak - base
full_mb = N * D * 4 / 1e6
assert grown < 0.5 * full_mb, (
    f"assignment grew RSS by {grown:.0f} MB streaming a {full_mb:.0f} MB "
    "dataset - not O(chunk + segments)")
print(f"MEM-OK grew {grown:.1f} MB for {full_mb:.0f} MB dataset")
"""


class TestMemory:
    def test_streaming_assignment_memory_bound(self, tmp_path):
        script = MEMORY_SCRIPT.replace("@SPILL@", str(tmp_path / "spill"))
        env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
        proc = subprocess.run(
            [sys.executable, "-c", script], env=env, capture_output=True,
            text=True, timeout=600,
        )
        assert proc.returncode == 0, proc.stderr[-2000:]
        assert "MEM-OK" in proc.stdout


# ---------------------------------------------------------------------------
# 7. Mesh mode (multi-device shard_map) in a subprocess
# ---------------------------------------------------------------------------


MESH_SCRIPT = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import numpy as np, jax, jax.numpy as jnp
from repro.graph import BuildParams
from repro.graph.sharded import ShardConfig, ShardedBuilder
from repro.graph.segmented import build_segments_vmapped, fit_shared_coder
from repro.graph.engine import sample_levels, prefix_entries
from repro.launch.mesh import make_segment_mesh

assert len(jax.devices()) == 2
rng = np.random.default_rng(0)
data = rng.normal(size=(600, 32)).astype(np.float32)
P = BuildParams(r_upper=8, r_base=16, ef=32, batch=32, max_layers=2)
cfg = ShardConfig(n_segments=2, chunk_size=256, params=P, sample_size=512,
                  seed=0, backend_kwargs=dict(d_f=16, m_f=8, kmeans_iters=5))
res = ShardedBuilder(cfg, mesh=make_segment_mesh()).build(data)
assert res.mode == "mesh", res.mode
r = res.index.search(rng.normal(size=(4, 32)).astype(np.float32), k=5)
assert (np.asarray(r.ids) >= 0).all()
plan = res.plan
n_s = plan.seg_sizes[0]
stacked = np.stack([plan.load_segment(s)[0] for s in range(2)])
coder = fit_shared_coder(jax.random.PRNGKey(0),
                         jnp.asarray(stacked.reshape(-1, 32)[:512]),
                         d_f=16, m_f=8, kmeans_iters=5)
levels = np.stack([sample_levels(s, n_s, r_upper=8, max_layers=2)
                   for s in range(2)])
entries = np.stack([prefix_entries(levels[s], 32) for s in range(2)])
ref = build_segments_vmapped(jnp.asarray(stacked), coder, jnp.asarray(levels),
                             jnp.asarray(entries), params=P)
for s in range(2):
    got = np.asarray(res.index.segments[s].graph.adj0)
    want = np.asarray(ref.index.adj0[s])
    assert np.array_equal(got, want), f"seg {s}: shard_map != vmapped"
print("MESH-OK")
"""


class TestMesh:
    def test_mesh_build_matches_vmapped_reference(self):
        """shard_map over forced host devices ≡ the vmapped single-device
        reference program — the mesh deployment changes placement, never
        results."""
        env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
        proc = subprocess.run(
            [sys.executable, "-c", MESH_SCRIPT], env=env,
            capture_output=True, text=True, timeout=600,
        )
        assert proc.returncode == 0, proc.stderr[-2000:]
        assert "MESH-OK" in proc.stdout


# ---------------------------------------------------------------------------
# 8. Observability: one profile per sharded build
# ---------------------------------------------------------------------------


class TestObsProfile:
    def test_build_emits_span_tree_and_counters(self, data, tmp_path):
        before = obs.snapshot().get("counters", {})
        obs.enable()
        obs.clear_spans()
        try:
            cfg = _config(tmp_path, n_segments=2, sample_size=256)
            res = ShardedBuilder(cfg, workdir=str(tmp_path)).build(data[:400])
        finally:
            obs.disable()
        roots = obs.spans("shard/build")
        assert len(roots) == 1
        root = roots[0]
        assert root.attrs["segments"] == 2
        segs = [c for c in root.children if c.name == "shard/segment"]
        assert len(segs) == 2
        total = sum(m["n_dists"] for m in res.segments)
        assert total > 0
        assert root.n_dists == pytest.approx(total)
        for sp, m in zip(segs, res.segments):
            assert sp.attrs["segment"] == m["seg"]
            assert sp.attrs["worker"] == m["pid"]
            assert sp.attrs["n"] == m["n_vectors"]
            assert sp.n_dists == pytest.approx(m["n_dists"])
            assert sp.attrs["phases"] == m["phases"]
        assert len(obs.spans("shard/assign")) == 1
        after = obs.snapshot().get("counters", {})

        def delta(name):
            return sum(
                v for k, v in after.items() if k.startswith(name)
            ) - sum(v for k, v in before.items() if k.startswith(name))

        assert delta("shard_segments_built_total") == 2
        assert delta("shard_segment_vectors_total") == 400
        # the dists counter ticks once per (segment, phase) bucket
        ptotal = sum(
            sum(m["phases"].values()) for m in res.segments if m["phases"]
        )
        assert ptotal > 0
        assert delta("shard_build_dists_total") == pytest.approx(ptotal)
