"""Smoke test of the main path on a TPU: build → snapshot → serve.

    python chip_smoke.py                 # one chip: the served path
    python chip_smoke.py --n 200000      # a smaller collection
    python chip_smoke.py --chips 4       # four chips: the segment mesh only

One chip (default): a Gaussian-mixture collection of ``--n`` vectors at
d = 96 (the DEEP shape of the repo's million-vector tier), generated from
``--seed``, is built with ``AnnIndex.build(algo="hnsw",
backend="flash_blocked")`` (bulk strategy), saved and loaded back as a
snapshot, and served through ``serve.Runtime`` (k=10, ef=2048, exact rerank
of the whole beam): 256 single-query submits, every future resolved. Recall@10 is
checked against a plain jnp brute-force top-k that does not touch the
repo's kernels.

``--chips 4``: a balanced 4-segment assignment built by ``ShardedBuilder``
on a 4-device mesh (one segment per device), the same stacked program run
through ``build_segments_vmapped`` on one device (adjacency must be equal),
and the mesh fan-out search checked against brute force.

The script fails (non-zero exit, no result line) when JAX finds no TPU,
when any kernel dispatched through anything but compiled Pallas, when a
request failed or the runtime restarted a thread, or when recall is short.
Its last stdout line is ``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

D = 96
N_QUERIES = 256
K = 10
MIN_RECALL = 0.90
#: Flash coder: all 96 dims, M=48 subspaces of K=16 codewords (24 B per
#: vector). M=16 cannot reach the recall bar: at n=30,000 its best 64 by
#: quantized distance hold under half of the true top-10.
CODER = dict(d_f=96, m_f=48, kmeans_iters=12)
#: beam width of the served search; its whole beam is reranked exactly.
#: Recall of the quantized beam falls with n. On one v5e at n=10⁶:
#: ef=512 0.819, 1024 0.917, 2048 0.964 (more bulk rounds did not help:
#: 8 rounds at ef=1024 gave 0.907). The best 2048 by quantized distance
#: hold 99.96% of the true top-10, the best 512 97.9% (counted on the CPU).
EF = 2048
#: beam width of the four-segment mesh search (16,384 vectors a segment)
EF_MESH = 512


class SmokeFailure(Exception):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def log(**fields) -> None:
    print(" ".join(f"{k}={v}" for k, v in fields.items()), flush=True)


class CompileClock:
    """Sums XLA backend-compile seconds and persistent-cache hits, from
    JAX's own monitoring events."""

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


def mixture(seed: int, n: int, q: int) -> tuple[np.ndarray, np.ndarray]:
    """Base vectors and held-out queries from one Gaussian mixture
    (``benchmarks/common.bench_data``'s generator)."""
    from repro.data.synthetic import vector_dataset

    x = vector_dataset(seed, n=n + q, d=D, n_clusters=48, sep=1.0)
    return x[:n], x[n:]


@jax.jit
def _chunk_topk(x, q, best_d, best_i, offset):
    d = (
        jnp.sum(q * q, axis=1, keepdims=True)
        + jnp.sum(x * x, axis=1)[None, :]
        - 2.0 * jnp.matmul(q, x.T, precision=jax.lax.Precision.HIGHEST)
    )
    ids = offset + jnp.arange(x.shape[0], dtype=jnp.int32)
    all_d = jnp.concatenate([best_d, d], axis=1)
    all_i = jnp.concatenate([best_i, jnp.broadcast_to(ids, d.shape)], axis=1)
    neg, pos = jax.lax.top_k(-all_d, best_d.shape[1])
    return -neg, jnp.take_along_axis(all_i, pos, axis=1)


def brute_force_topk(data: np.ndarray, queries: np.ndarray, k: int) -> np.ndarray:
    """Exact squared-L2 top-k by plain jnp over fixed-size chunks."""
    n = data.shape[0]
    chunk = min(n, 1 << 16)
    pad = -n % chunk
    x = jnp.asarray(np.concatenate([data, np.full((pad, D), 1e6, np.float32)]))
    q = jnp.asarray(queries)
    best_d = jnp.full((q.shape[0], k), jnp.inf, jnp.float32)
    best_i = jnp.full((q.shape[0], k), -1, jnp.int32)
    for off in range(0, n + pad, chunk):
        best_d, best_i = _chunk_topk(x[off:off + chunk], q, best_d, best_i, off)
    return np.asarray(best_i)


def recall_at_k(got: np.ndarray, want: np.ndarray) -> float:
    hits = sum(len(set(g[g >= 0]) & set(w)) for g, w in zip(got, want))
    return hits / want.size


def device_bytes() -> dict:
    stats = jax.devices()[0].memory_stats() or {}
    return {k: stats.get(k) for k in ("bytes_in_use", "peak_bytes_in_use")}


def kernel_dispatch() -> dict:
    """``kernel_traces_total`` series as {(kernel, impl): count}."""
    from repro import obs

    out = {}
    for m in obs.REGISTRY.metrics():
        if m.name == "kernel_traces_total":
            labels = dict(m.labels)
            out[(labels["kernel"], labels["impl"])] = m.value
    return out


def check_kernels(*required: str) -> None:
    traces = kernel_dispatch()
    log(kernel_traces_total=json.dumps(
        {f"{kern}/{impl}": v for (kern, impl), v in sorted(traces.items())}
    ))
    off = sorted({impl for (_, impl) in traces} - {"pallas"})
    check(not off, f"kernels dispatched through {off}, not compiled Pallas")
    seen = {kern for (kern, _) in traces}
    missing = [kern for kern in required if kern not in seen]
    check(not missing, f"kernels never dispatched: {missing}")


def one_chip(args, clock: CompileClock) -> None:
    from repro import serve
    from repro.index import AnnIndex, SearchSpec

    data, queries = mixture(args.seed, args.n, N_QUERIES)
    log(n=args.n, d=D, queries=N_QUERIES, seed=args.seed)

    c0, t0 = clock.seconds, time.perf_counter()
    index = AnnIndex.build(
        data, algo="hnsw", backend="flash_blocked",
        backend_kwargs=dict(CODER, keep_raw=True), seed=args.seed,
    )
    jax.block_until_ready(index.graph.adj0)
    log(phase="build", wall_s=time.perf_counter() - t0,
        compile_s=clock.seconds - c0, strategy=index.build_strategy,
        **device_bytes())

    with tempfile.TemporaryDirectory() as tmp:
        t0 = time.perf_counter()
        path = serve.save_index(os.path.join(tmp, "index"), index)
        loaded = serve.load_index(path)
        log(phase="snapshot", wall_s=time.perf_counter() - t0)
    del index

    # exact rerank of the whole beam: with 4-bit codes a 4·k cut loses true
    # neighbours before the rerank sees them
    spec = SearchSpec(k=K, ef=EF, rerank="exact", rerank_mult=None)
    c0, t0 = clock.seconds, time.perf_counter()
    with serve.Runtime(loaded, spec=spec) as rt:
        rt.warmup()
        log(phase="warmup", wall_s=time.perf_counter() - t0,
            compile_s=clock.seconds - c0)
        t0 = time.perf_counter()
        futures = [rt.submit(q) for q in queries]
        results, failed = [], []
        for i, f in enumerate(futures):
            try:
                results.append(np.asarray(f.result(timeout=600).ids))
            except Exception as e:  # noqa: BLE001 - every failure is reported
                failed.append((i, repr(e)))
        stats = rt.stats()
    log(phase="serve", wall_s=time.perf_counter() - t0,
        requests=len(futures), failed=len(failed),
        batches=stats["batches"], mean_batch=stats["mean_batch"],
        thread_restarts_total=stats["thread_restarts"])
    check(not failed, f"{len(failed)} requests failed, first: {failed[:1]}")
    check(stats["thread_restarts"] == 0,
          f"runtime restarted a thread {stats['thread_restarts']} times")

    got = np.stack([r.reshape(-1)[:K] for r in results])
    recall = recall_at_k(got, brute_force_topk(data, queries, K))
    log(recall_at_10=recall, min_recall=MIN_RECALL,
        compile_s_total=clock.seconds, compiles=clock.compiles,
        cache_hits=clock.cache_hits, **device_bytes())
    check_kernels("flash_round", "flash_expand")
    check(recall >= MIN_RECALL, f"recall@10 {recall} < {MIN_RECALL}")


def four_chips(args, clock: CompileClock) -> None:
    from repro.graph import BuildParams
    from repro.graph.engine import prefix_entries, sample_levels
    from repro.graph.segmented import (
        build_segments_vmapped,
        fit_shared_coder,
        make_segmented_build_fn,
        make_segmented_search_fn,
    )
    from repro.graph.sharded import ShardConfig, ShardedBuilder
    from repro.launch.mesh import make_segment_mesh

    check(len(jax.devices()) >= 4, f"--chips 4 needs 4 devices, "
          f"JAX sees {len(jax.devices())}")
    s_total = 4
    n = args.n - args.n % s_total
    data, queries = mixture(args.seed, n, N_QUERIES)
    log(n=n, d=D, segments=s_total, queries=N_QUERIES, seed=args.seed)
    params = BuildParams()
    mesh = make_segment_mesh(s_total)
    cfg = ShardConfig(
        n_segments=s_total, params=params, seed=args.seed,
        backend_kwargs=CODER, balanced=True,
    )
    with tempfile.TemporaryDirectory() as tmp:
        t0 = time.perf_counter()
        res = ShardedBuilder(cfg, mesh=mesh, workdir=tmp).build(data)
        plan = res.plan
        log(phase="sharded_build", mode=res.mode, wall_s=time.perf_counter() - t0,
            seg_sizes=list(map(int, plan.seg_sizes)))
        check(res.mode == "mesh", f"ShardedBuilder ran {res.mode!r}, not 'mesh'")
        stacked = np.stack([plan.load_segment(s)[0] for s in range(s_total)])
        global_of = np.concatenate(plan.global_of())
    n_s = stacked.shape[1]

    # ShardedBuilder's mesh inputs, rebuilt the way it makes them
    sample = stacked.reshape(-1, D)[: cfg.sample_size]
    coder = fit_shared_coder(
        jax.random.PRNGKey(cfg.seed), jnp.asarray(sample), **CODER
    )
    levels = np.stack([
        sample_levels(cfg.seed + s, n_s, r_upper=params.r_upper,
                      max_layers=params.max_layers)
        for s in range(s_total)
    ])
    entries = jnp.asarray(np.stack([
        prefix_entries(levels[s], params.batch) for s in range(s_total)
    ]))
    levels = jnp.asarray(levels)
    t0 = time.perf_counter()
    built = make_segmented_build_fn(mesh, params=params, seg_axes=("data",))(
        jnp.asarray(stacked), coder, levels, entries
    )
    adj_mesh = jax.block_until_ready(built.adj0)
    log(phase="mesh_build", wall_s=time.perf_counter() - t0)
    shards = adj_mesh.addressable_shards
    per_dev = sorted((sh.device.id, sh.data.shape[0]) for sh in shards)
    log(segments_per_device=per_dev)
    check(len({d for d, _ in per_dev}) == s_total
          and all(c == 1 for _, c in per_dev),
          f"expected one segment on each of {s_total} devices, got {per_dev}")

    t0 = time.perf_counter()
    ref = build_segments_vmapped(
        jax.device_put(jnp.asarray(stacked), jax.devices()[0]), coder,
        levels, entries, params=params,
    )
    adj_ref = np.asarray(ref.index.adj0)
    log(phase="vmapped_reference", wall_s=time.perf_counter() - t0)
    check(np.array_equal(np.asarray(adj_mesh), adj_ref),
          "mesh build adjacency != build_segments_vmapped adjacency")
    for s in range(s_total):
        check(np.array_equal(
            np.asarray(res.index.segments[s].graph.adj0), adj_ref[s]),
            f"ShardedBuilder segment {s} adjacency != vmapped reference")
    log(adjacency_equal=True)

    search = make_segmented_search_fn(
        mesh, k=K, ef_search=EF_MESH, seg_axes=("data",)
    )
    offsets = jnp.arange(s_total, dtype=jnp.int32) * n_s
    t0 = time.perf_counter()
    pos, _ = search(built, jnp.asarray(queries), offsets, jnp.asarray(stacked))
    pos = np.asarray(pos)
    got = np.where(pos >= 0, global_of[np.maximum(pos, 0)], -1)
    recall = recall_at_k(got, brute_force_topk(data, queries, K))
    log(phase="mesh_search", wall_s=time.perf_counter() - t0,
        recall_at_10=recall, min_recall=MIN_RECALL,
        compile_s_total=clock.seconds, compiles=clock.compiles,
        cache_hits=clock.cache_hits)
    check_kernels()
    check(recall >= MIN_RECALL, f"recall@10 {recall} < {MIN_RECALL}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--n", type=int, default=None,
                    help="collection size (default 1,000,000; 65,536 with "
                    "--chips 4)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    if args.n is None:
        args.n = 1_000_000 if args.chips == 1 else 65_536

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU present (JAX platform {dev.platform!r})",
              file=sys.stderr)
        return 2

    from repro import obs
    from repro.kernels import ops
    from repro.utils import use_compile_cache

    log(compile_cache=use_compile_cache(ROOT))
    check(ops._DEFAULT_IMPL is None,
          f"ops.set_default_impl({ops._DEFAULT_IMPL!r}) is set")
    obs.enable()
    clock = CompileClock()
    try:
        (one_chip if args.chips == 1 else four_chips)(args, clock)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
