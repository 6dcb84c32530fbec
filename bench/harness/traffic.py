"""The general traffic driver. A traffic mix is a data file,
``bench/traffic/<mix>.json``, whose ``kind`` picks one of the drivers below
and whose other keys are that driver's parameters.

Each driver makes its inputs from the seed, sets up and warms the program
on exactly the shapes it will use, measures for the window, and then
checks what the timed path produced against the reference. It returns a
:class:`Outcome`; ``bench/run.py`` turns that into the result line.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable

import jax
import numpy as np

from harness import checks, data, device, reference, stats, trace


@dataclasses.dataclass
class Context:
    cell: dict
    config: dict
    traffic: dict
    seed: int
    seconds: float
    tracing: bool
    control: bool
    devices: list
    clock: Any
    t_process: float  # perf_counter at process start
    trace_dir: str


@dataclasses.dataclass
class Outcome:
    setup_s: float
    e2e: dict  # end-to-end metric name -> value
    attempted: int
    failed: int
    checks: list
    notes: dict  # printed on an earlier line: window facts
    record: dict | None = None  # compact device trace (--trace 1)
    layer: dict = dataclasses.field(default_factory=dict)  # counters for readers
    memory_peak: int | None = None


def _block(index) -> None:
    jax.block_until_ready(jax.tree_util.tree_leaves(index.graph))


def build_fn(cfg: dict) -> Callable:
    """``AnnIndex.build`` with the configuration's index settings.

    The build's own seed (levels, coder initialisation) is the
    configuration's ``build_seed``, so every collection is built through
    programs of the same shapes; the collection itself comes from the
    run's seed."""
    from repro.graph import BuildParams
    from repro.index import AnnIndex

    ix = cfg["index"]
    params = BuildParams(**ix.get("params", {}))
    coder = dict(ix["coder"], keep_raw=True)

    def build(vectors):
        return AnnIndex.build(
            vectors, algo=ix["algo"], backend=ix["backend"], params=params,
            backend_kwargs=coder, seed=int(ix["build_seed"]),
            strategy=ix.get("strategy", "bulk"),
        )

    return build


def search_answers(index, queries, cfg: dict, control: bool, base):
    """Ids and distances the searched graph returns; under ``control`` the
    reference in bfloat16, in the configuration's distance, takes the
    program's place."""
    from repro.index import SearchSpec

    spec = SearchSpec(**cfg["search"])
    if control:
        return reference.topk(base, queries, spec.k, distance=reference.distance_of(cfg),
                              precision="bfloat16")
    res = index.search(queries, spec=spec)
    return np.asarray(res.ids), np.asarray(res.dists)


def build_stream(ctx: Context) -> Outcome:
    """Whole builds back to back, each from raw host vectors to a
    searchable graph, of the collection that set-up built once to warm
    every program. Checked afterwards: the last graph, and a search of it
    by held-out queries."""
    cfg, tr = ctx.config, ctx.traffic
    n = int(cfg["n"])
    distance = reference.distance_of(cfg)
    # a configuration whose searches are slow may check fewer queries
    n_check = int(cfg.get("check_queries", tr["check_queries"]))
    base, queries = data.inputs(cfg, ctx.seed, n_check)
    build = build_fn(cfg)
    index = build(base)
    _block(index)
    setup_s = time.perf_counter() - ctx.t_process
    setup_compiles, setup_compile_s = ctx.clock.compiles, ctx.clock.seconds
    setup_hits = ctx.clock.cache_hits

    c0 = ctx.clock.compiles
    record = None
    if ctx.tracing:
        record = {}
        with trace.capture(ctx.trace_dir, record):
            index = None
            with jax.profiler.TraceAnnotation("bench/build"):
                index = build(base)
                _block(index)
        builds, window = 1, None
    else:
        t0 = time.perf_counter()
        deadline = t0 + ctx.seconds
        builds = 0
        while builds == 0 or time.perf_counter() < deadline:
            index = None  # the previous graph is freed before the next build
            index = build(base)
            _block(index)
            builds += 1
        window = time.perf_counter() - t0
    compiles = ctx.clock.compiles - c0

    peak = device.memory_peak(ctx.devices)
    g = index.graph
    found = checks.graph_checks(
        np.asarray(g.adj0), np.asarray(g.adj_up), int(g.entry)
    )
    ids, dists = search_answers(index, queries, cfg, ctx.control, base)
    del index, g
    truth, _ = reference.topk(base, queries, int(cfg["search"]["k"]), distance=distance)
    found += checks.answer_checks(ids, dists, base, queries, truth, cfg["limits"],
                                  distance=distance)
    recall = next(c.value for c in found if c.name == "recall_at_10")
    e2e = {"setup_s": setup_s, "recall_at_10": recall}
    if window is not None:
        e2e["build_vps"] = stats.rate(n, builds, window)
    return Outcome(
        setup_s=setup_s, e2e=e2e, attempted=builds, failed=0, checks=found,
        notes={"builds": builds, "window_s": window, "vectors_per_build": n,
               "check_queries": n_check,
               "compiles_in_window": compiles, "setup_compiles": setup_compiles,
               "setup_cache_hits": setup_hits, "setup_compile_s": setup_compile_s},
        record=record, memory_peak=peak,
    )


def arrival_offsets(seed: int, rate: float, seconds: float) -> np.ndarray:
    """Send times, in seconds from the window's start, of an open loop at
    ``rate`` per second: Poisson gaps, but the same set of gaps for every
    seed (the exponential's quantiles) in an order drawn from the seed, so
    every seed offers the same work in the same time."""
    count = max(1, int(round(rate * seconds)))
    u = (np.arange(count) + 0.5) / count
    gaps = -np.log1p(-u)
    gaps *= seconds / gaps.sum()
    rng = np.random.default_rng(seed)
    return np.cumsum(rng.permutation(gaps))


def _registry_values(name: str) -> np.ndarray:
    from repro import obs

    vals = [m.values() for m in obs.REGISTRY.metrics()
            if m.name == name and hasattr(m, "values")]
    return np.concatenate(vals) if vals else np.zeros(0)


def open_loop(ctx: Context) -> Outcome:
    """Single-query requests through ``serve.Runtime``, sent on a schedule
    whatever the server does (an open loop at the traffic's ``rate``), each
    timed from when it was due. The index is built in set-up from the
    run's collection, and the runtime warmed on its batch buckets. Every
    answer is checked against the reference once the window has closed."""
    from repro import serve
    from repro.index import SearchSpec

    cfg, tr = ctx.config, ctx.traffic
    n = int(cfg["n"])
    distance = reference.distance_of(cfg)
    seconds = float(tr["trace_seconds"]) if ctx.tracing else ctx.seconds
    due = arrival_offsets(ctx.seed, float(tr["rate"]), seconds)
    base, queries = data.inputs(cfg, ctx.seed, int(due.size))
    index = build_fn(cfg)(base)
    _block(index)
    spec = SearchSpec(**cfg["search"])
    rt = serve.Runtime(index, spec=spec, max_wait_ms=float(tr["max_wait_ms"]))
    try:
        rt.warmup()
        # every block size the scheduler can pack, so that the engine's
        # padding of a partial bucket compiles here and not in the window
        for q in range(1, rt.max_batch + 1):
            rt.engine.search(queries[:q], record=False)
        rt.reset_stats()
        setup_s = time.perf_counter() - ctx.t_process
        setup_compiles, setup_compile_s = ctx.clock.compiles, ctx.clock.seconds
        setup_hits = ctx.clock.cache_hits

        done = np.full(due.size, np.nan)
        sent = np.full(due.size, np.nan)
        futures = []

        def finish(i):
            def cb(fut):
                if fut.exception() is None:
                    done[i] = time.perf_counter()
            return cb

        c0 = ctx.clock.compiles
        record = None
        # a traced run profiles the part of its window after trace_from_s,
        # once the queue has reached its steady state
        cap = trace.Capture(ctx.trace_dir) if ctx.tracing else None
        trace_from = float(tr.get("trace_from_s", 0.0))
        t0 = time.perf_counter()
        for i, off in enumerate(due):
            if cap is not None and record is None and off >= trace_from:
                cap.start()
                record = {}
            wait = t0 + off - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            sent[i] = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench/serve/submit"):
                fut = rt.submit(queries[i])
            fut.add_done_callback(finish(i))
            futures.append(fut)
        if record is not None:
            wait = t0 + seconds - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            record = cap.stop()
        answers, failed = [], 0
        for fut in futures:
            try:
                answers.append(fut.result(timeout=max(
                    1.0, t0 + seconds + float(tr["drain_seconds"]) - time.perf_counter())))
            except Exception:  # noqa: BLE001 — every failed request is counted
                answers.append(None)
                failed += 1
        compiles = ctx.clock.compiles - c0
        stats_ = rt.stats()
    finally:
        rt.close()

    peak = device.memory_peak(ctx.devices)
    due_abs = t0 + due
    p90 = stats.percentile_from_due(due_abs, done, 90.0)
    late = sent - due_abs
    ok = [i for i, a in enumerate(answers) if a is not None]
    k = int(spec.k)
    ids = np.stack([np.asarray(answers[i].ids).reshape(-1)[:k] for i in ok]) if ok else np.zeros((0, k), np.int32)
    dists = np.stack([np.asarray(answers[i].dists).reshape(-1)[:k] for i in ok]) if ok else np.zeros((0, k), np.float32)
    del index, rt
    if ctx.control:
        ids, dists = reference.topk(base, queries[ok], k, distance=distance,
                                    precision="bfloat16")
    truth, _ = reference.topk(base, queries[ok], k, distance=distance)
    found = checks.answer_checks(ids, dists, base, queries[ok], truth, cfg["limits"],
                                 distance=distance)
    recall = next(c.value for c in found if c.name == "recall_at_10")
    buckets = serve.DEFAULT_BUCKETS
    return Outcome(
        setup_s=setup_s,
        e2e={"setup_s": setup_s, "recall_at_10": recall, "query_p90_ms": 1e3 * p90},
        attempted=int(due.size), failed=failed, checks=found,
        notes={"requests": int(due.size), "rate_per_s": float(tr["rate"]),
               "window_s": seconds, "sender_late_ms_p50": 1e3 * float(np.median(late)),
               "sender_late_ms_max": 1e3 * float(np.max(late)),
               "compiles_in_window": compiles, "setup_compiles": setup_compiles,
               "setup_cache_hits": setup_hits, "setup_compile_s": setup_compile_s,
               "batches": stats_["batches"],
               "mean_batch": stats_["mean_batch"]},
        record=record, memory_peak=peak,
        layer={"queue_s": _registry_values("serve_queue_latency_seconds"),
               "dispatch_s": _registry_values("serve_engine_latency_seconds"),
               "mean_batch": stats_["mean_batch"], "max_bucket": max(buckets),
               "dists_per_query": stats_["engine"]["n_dists_per_query"], "batches": stats_["batches"]},
    )


DRIVERS: dict[str, Callable[[Context], Outcome]] = {
    "build_stream": build_stream,
    "open_loop": open_loop,
}
