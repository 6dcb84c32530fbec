"""HNSW index construction in JAX (paper Algorithm 1, batch-synchronous).

Faithful structure:
  * exponentially-decaying random levels (mL = 1/ln R_upper), layer-0 degree
    R_base = 2·R_upper (paper footnote 3),
  * per inserted vector: descend layers from the entry point, beam-search the
    top-C candidates (CA), heuristic-select ≤R neighbors (NS), add reverse
    edges, prune overflowing lists with the same heuristic (Alg. 1 lines 4–7).

TPU-native deviation (DESIGN.md §2, A1): hnswlib inserts concurrently from 24
threads under fine-grained locks; here a *batch* of P vectors is inserted
synchronously against the frozen prefix graph (vmapped CA/NS), then forward +
reverse edges are committed. For P ≪ n this matches a legal thread
interleaving, and recall parity is asserted in tests/benchmarks.

All of the batched CA+NS machinery lives in :mod:`repro.graph.engine`
(DESIGN.md §3); this module owns only the HNSW-specific parts — the layered
index type, level sampling glue, and the layered search. Vamana/NSG and the
segment-parallel layer build on the same engine, not on this module's
internals.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.graph.beam import beam_search, greedy_descent
from repro.graph.rerank import SearchSpec, rerank_topk, resolve_search_args
from repro.graph.engine import (  # noqa: F401 — re-exported public API
    INF,
    BuildEngine,
    BuildParams,
    BuildStats,
    CostAccount,
    bulk_commit,
    bulk_refine,
    prefix_entries,
    repair_reachability,
    sample_levels,
)

# Canonical name for the paper's Algorithm-1 hyper-parameters; kept as the
# HNSW-flavoured alias everywhere downstream (benchmarks, examples, tests).
HNSWParams = BuildParams


class HNSWIndex(NamedTuple):
    """Built index (pytree). adjacency rows: −1 = empty slot."""

    adj0: jax.Array  # (n, r_base) int32
    adj0_d: jax.Array  # (n, r_base) f32 — backend-scale dist to each neighbor
    adj_up: jax.Array  # (L−1, n, r_upper) int32
    adj_up_d: jax.Array  # (L−1, n, r_upper) f32
    levels: jax.Array  # (n,) int32
    entry: jax.Array  # () int32 — vertex with the max level
    backend: object  # distance backend (registered pytree)


@functools.partial(jax.jit, static_argnames=("params",))
def build_hnsw_jit(data, backend, levels, entries, *, params: HNSWParams):
    """Jitted device build (public: the segment-parallel layer traces this).

    ``levels``/``entries`` are precomputed on the host (see
    :func:`sample_levels` / :func:`prefix_entries`); everything else is one
    engine-driven ``fori_loop`` program.
    """
    engine = BuildEngine(params)
    adj0, adj0_d, adj_up, adj_up_d, backend, acct = engine.build_layered(
        data, backend, levels, entries
    )
    entry = jnp.argmax(levels).astype(jnp.int32)
    index = HNSWIndex(
        adj0=adj0, adj0_d=adj0_d, adj_up=adj_up, adj_up_d=adj_up_d,
        levels=levels, entry=entry, backend=backend,
    )
    return index, BuildStats(
        n_dists=acct.n_dists.astype(jnp.float32), n_hops=acct.n_hops,
        phases=acct.phases,
    )


def _build_hnsw_bulk(
    data, backend, levels: np.ndarray, *, params: HNSWParams, seed: int
) -> tuple[HNSWIndex, BuildStats]:
    """Bulk-construction fast path (``strategy="bulk"``, DESIGN.md §12).

    Each layer's k-NN pools are bootstrapped by whole-dataset RNN-Descent
    refinement rounds (``engine.bulk_refine`` — dense batched scans, no
    serial beam dependency on the graph prefix), then committed through the
    SAME MRNG selection / forward / reverse machinery as the incremental
    path (``engine.bulk_commit``), so edge semantics are unchanged. Upper
    layers refine only their member subsets (levels ≥ l). A final BFS +
    re-insert pass guarantees base-layer reachability from the entry
    (incremental insertion gets this for free; random pools do not).
    """
    data = jnp.asarray(data, jnp.float32)
    n = data.shape[0]
    levels_np = np.asarray(levels)
    engine = BuildEngine(params)
    l_up = params.max_layers - 1
    adj0 = jnp.full((n, params.r_base), -1, jnp.int32)
    adj0_d = jnp.full((n, params.r_base), INF)
    adj_up = jnp.full((l_up, n, params.r_upper), -1, jnp.int32)
    adj_up_d = jnp.full((l_up, n, params.r_upper), INF)
    n_d = n_h = 0.0

    if n >= 2:
        members = np.arange(n, dtype=np.int32)
        with obs.span("build/bulk_refine", layer=0) as sp:
            pool_ids, pool_d, nd, nh, rounds = bulk_refine(
                data, backend, members, r=params.r_base, params=params,
                seed=seed, layer=0,
            )
            sp.add_cost(nd, nh)
            sp.set(rounds=rounds)
        with obs.span("build/bulk_commit", layer=0):
            adj0, adj0_d, backend = bulk_commit(
                engine, adj0, adj0_d, backend, jnp.asarray(members),
                pool_ids, pool_d, r=params.r_base,
            )
        n_d += nd
        n_h += nh

    for l in range(1, params.max_layers):
        members = np.nonzero(levels_np >= l)[0].astype(np.int32)
        if members.size < 2:
            continue  # nothing to link at this layer
        with obs.span("build/bulk_refine", layer=l) as sp:
            pool_ids, pool_d, nd, nh, rounds = bulk_refine(
                data, backend, members, r=params.r_upper, params=params,
                seed=seed, layer=l,
            )
            sp.add_cost(nd, nh)
            sp.set(rounds=rounds)
        with obs.span("build/bulk_commit", layer=l):
            a, ad, backend = bulk_commit(
                engine, adj_up[l - 1], adj_up_d[l - 1], backend,
                jnp.asarray(members), pool_ids, pool_d, r=params.r_upper,
            )
        adj_up = adj_up.at[l - 1].set(a)
        adj_up_d = adj_up_d.at[l - 1].set(ad)
        n_d += nd
        n_h += nh

    entry = int(np.argmax(levels_np)) if n else 0
    lv = jnp.asarray(levels_np)
    adj0, adj0_d, adj_up, adj_up_d, backend, rd, rh = repair_reachability(
        data, adj0, adj0_d, adj_up, adj_up_d, backend, lv, entry,
        params=params,
    )
    bulk_nd = n_d
    n_d += rd
    n_h += rh

    index = HNSWIndex(
        adj0=adj0, adj0_d=adj0_d, adj_up=adj_up, adj_up_d=adj_up_d,
        levels=lv, entry=jnp.int32(entry), backend=backend,
    )
    return index, BuildStats(
        n_dists=jnp.float32(n_d), n_hops=jnp.float32(n_h),
        phases=jnp.asarray([0.0, 0.0, 0.0, bulk_nd, rd], jnp.float32),
    )


def build_hnsw(
    data,
    backend,
    *,
    params: HNSWParams = HNSWParams(),
    seed: int = 0,
    levels: np.ndarray | None = None,
    strategy: str = "incremental",
) -> tuple[HNSWIndex, BuildStats]:
    """Public entry: build an HNSW index over ``data`` with ``backend``.

    ``data`` is only consumed through ``backend.prepare_query`` (the inserted
    vector's own context — for Flash that is its ADT, built once per insert,
    paper Remark 2); all candidate/neighbor comparisons go through the
    backend's compact representation.

    ``strategy`` picks candidate acquisition: ``"incremental"`` is the
    paper's batch-synchronous insertion loop; ``"bulk"`` bootstraps each
    layer with RNN-Descent refinement rounds (DESIGN.md §12 — much higher
    build throughput, same selection/commit semantics). The facade
    (``repro.index.AnnIndex.build``) defaults from-scratch builds to bulk.
    """
    data = jnp.asarray(data, jnp.float32)
    n = data.shape[0]
    if levels is None:
        levels = sample_levels(
            seed, n, r_upper=params.r_upper, max_layers=params.max_layers
        )
    if strategy == "bulk":
        return _build_hnsw_bulk(data, backend, levels, params=params, seed=seed)
    if strategy != "incremental":
        raise ValueError(f"unknown build strategy {strategy!r}")
    entries = prefix_entries(levels, params.batch)
    return build_hnsw_jit(
        data, backend, jnp.asarray(levels), jnp.asarray(entries), params=params
    )


# ---------------------------------------------------------------------------
# Search (query side — the two-stage pipeline of DESIGN.md §11:
# quantized candidate scan + Reranker second stage)
# ---------------------------------------------------------------------------


class SearchResult(NamedTuple):
    """One result shape for every read path, with the scan/rerank cost split.

    ``n_dists`` stays a scalar total, now ``n_scan + n_rerank`` — for
    reranked searches that is larger than the pre-pipeline value, which
    silently dropped the second stage's evaluations from the bill. The
    split tells you how much of the work ran on compact codes (scan:
    descent + base-layer beam, backend scale) versus at full precision
    (rerank: the second stage, 0 when ``rerank="none"``).
    """

    ids: jax.Array  # (Q, k)
    dists: jax.Array  # (Q, k) — reranker scale (exact L2) or backend scale
    n_dists: jax.Array  # () total distance evaluations (scan + rerank)
    n_scan: jax.Array | None = None  # () compact-code evaluations
    n_rerank: jax.Array | None = None  # () second-stage evaluations


@functools.partial(jax.jit, static_argnames=("spec", "max_layers"))
def _search_hnsw_spec(
    index: HNSWIndex, queries, banned, reranker, *, spec: SearchSpec,
    max_layers: int | None,
) -> SearchResult:
    """The jitted layered pipeline: greedy descent → quantized beam over the
    best ``spec.n_keep`` candidates → ``reranker`` second stage (skipped
    when None). One trace per (spec, shapes) — the serving engine keys its
    compiled-bucket table on exactly this pair."""
    backend = index.backend
    n_layers = index.adj_up.shape[0] + 1 if max_layers is None else max_layers

    def one(q):
        qctx = backend.prepare_query(q)
        ep = index.entry
        nd = jnp.int32(0)
        for l in range(n_layers - 1, 0, -1):
            desc = greedy_descent(backend, qctx, index.adj_up[l - 1], ep)
            ep = desc.node
            nd = nd + desc.n_dists
        res = beam_search(
            backend, qctx, index.adj0, ep[None], ef=spec.ef, width=spec.width,
            banned=banned, n_keep=spec.n_keep,
        )
        n_scan = nd + res.n_dists
        if reranker is None:
            return res.ids[: spec.k], res.dists[: spec.k], n_scan, jnp.int32(0)
        ids, dists, n_rr = rerank_topk(reranker, q, res.ids, res.dists, spec.k)
        return ids, dists, n_scan, n_rr

    ids, dists, ns, nr = jax.vmap(one)(queries)
    ns, nr = jnp.sum(ns), jnp.sum(nr)
    return SearchResult(
        ids=ids, dists=dists, n_dists=ns + nr, n_scan=ns, n_rerank=nr
    )


def search_hnsw(
    index: HNSWIndex,
    queries: jax.Array,
    *,
    k: int | None = None,
    ef_search: int = 64,
    max_layers: int | None = None,
    width: int = 1,
    rerank_vectors: jax.Array | None = None,
    banned: jax.Array | None = None,
    spec: SearchSpec | None = None,
    reranker=None,
) -> SearchResult:
    """Layered two-stage search (DESIGN.md §11).

    Canonical form: pass a frozen ``spec=``:class:`SearchSpec` (+ a
    ``reranker=`` for specs with a second stage — see
    ``graph.rerank.make_reranker``). The legacy keyword form maps onto it
    bit-exactly: ``rerank_vectors=`` is exact rerank over the whole beam,
    omitting it is ``rerank="none"``.

    ``max_layers`` defaults to the layer count the index was actually built
    with (``adj_up.shape[0] + 1``) — passing it is only needed to search a
    shallower prefix of the hierarchy. ``n_dists`` counts every distance
    evaluation (descent + beam + rerank; see ``SearchResult`` for the
    split). ``banned`` is the (n,) tombstone mask of DESIGN.md §8:
    tombstoned vertices stay traversable but are never returned.
    """
    spec, reranker = resolve_search_args(
        spec, reranker, k=k, ef=ef_search, width=width,
        rerank_vectors=rerank_vectors,
    )
    return _search_hnsw_spec(
        index, queries, banned, reranker, spec=spec, max_layers=max_layers
    )
