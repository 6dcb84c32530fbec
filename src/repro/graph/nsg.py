"""NSG-style build (paper §4.5.3 generality target).

NSG (Fu et al., VLDB'19) differs from HNSW in how candidates are acquired:
it searches a prebuilt approximate k-NN graph from the medoid and applies the
MRNG edge rule. The CA + NS decomposition is identical — which is exactly the
paper's generality argument: Flash plugs into the distance layer unchanged,
and the build composes the shared :class:`repro.graph.engine.BuildEngine`
stages (acquire → select → commit_forward → reverse_pass, DESIGN.md §3).

Pipeline here: (1) exact k-NN graph (the oracle substitute for NN-descent at
the scales this container runs), (2) for every vertex, beam-search the k-NN
graph from the medoid through the compact-code backend, (3) MRNG-select ≤ R
neighbors from beam ∪ kNN candidates, (4) reverse edges + prune.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.graph.engine import (
    INF,
    BuildEngine,
    BuildParams,
    bulk_commit,
    bulk_refine,
    repair_reachability,
)
from repro.graph.hnsw import HNSWParams  # noqa: F401 — canonical param alias
from repro.graph.knn import exact_knn
from repro.graph.vamana import FlatIndex, medoid_id


@functools.partial(jax.jit, static_argnames=("params",))
def _build_nsg_jit(data, backend, knn_adj, entry, *, params: BuildParams):
    engine = BuildEngine(params)
    n = data.shape[0]
    p = params.batch
    r = params.r_base
    adj = jnp.full((n, r), -1, jnp.int32)
    adj_d = jnp.full((n, r), INF)
    nb = -(-n // p)

    def body(b, carry):
        adj, adj_d, backend = carry
        ids = b * p + jnp.arange(p, dtype=jnp.int32)
        mask = ids < n
        ids = jnp.minimum(ids, n - 1)
        qctx = jax.vmap(backend.prepare_query)(data[ids])
        # CA on the kNN graph from the medoid (shared entry for the batch).
        res = engine.acquire(
            backend, qctx, knn_adj, jnp.full((p,), entry, jnp.int32)
        )
        # Candidates = beam ∪ own kNN row (NSG uses the search's visited set;
        # the beam is its top slice, the kNN row guarantees local candidates).
        own = knn_adj[ids]  # (P, k)
        own_d = jax.vmap(backend.query_dists)(qctx, jnp.maximum(own, 0))
        own_d = jnp.where(own >= 0, own_d, INF)
        # Drop self edges.
        own = jnp.where(own == ids[:, None], -1, own)
        own_d = jnp.where(own == -1, INF, own_d)
        cand_ids = jnp.concatenate([res.ids, own], axis=1)
        cand_d = jnp.concatenate([res.dists, own_d], axis=1)
        order = jnp.argsort(cand_d, axis=1)
        cand_ids = jnp.take_along_axis(cand_ids, order, axis=1)
        cand_d = jnp.take_along_axis(cand_d, order, axis=1)
        # Dedup: mask repeats (sorted by distance; equal ids are adjacent
        # only if equal distance — mask any id seen earlier).
        eq = cand_ids[:, :, None] == cand_ids[:, None, :]
        tri = jnp.tril(jnp.ones((cand_ids.shape[1],) * 2, bool), k=-1)
        dup = jnp.any(eq & tri[None], axis=2)
        cand_ids = jnp.where(dup | (cand_ids < 0), -1, cand_ids)
        cand_d = jnp.where(cand_ids < 0, INF, cand_d)
        sel = engine.select(backend, cand_ids, cand_d, r=r)
        sel_ids = jnp.where(mask[:, None], sel.ids, -1)
        sel_d = jnp.where(mask[:, None], sel.dists, INF)
        adj, adj_d, backend = engine.commit_forward(
            adj, adj_d, backend, ids, sel_ids, sel_d, mask
        )
        adj, adj_d, backend = engine.reverse_pass(
            adj, adj_d, backend, ids, sel_ids, sel_d, mask
        )
        return adj, adj_d, backend

    adj, adj_d, backend = jax.lax.fori_loop(0, nb, body, (adj, adj_d, backend))
    return FlatIndex(adj=adj, adj_d=adj_d, entry=entry, backend=backend)


def _build_nsg_bulk(data, backend, entry, *, params: BuildParams,
                    knn_k: int, seed: int):
    """Bulk NSG (DESIGN.md §12): the refinement rounds ARE the k-NN stage.

    NSG's pipeline starts from an approximate k-NN graph; the bulk path
    produces exactly that as its refined pools — so the exact-k-NN oracle
    pass of the incremental path is skipped entirely (an extra win on top
    of the batched acquisition) and the returned ``knn_adj`` is the pools'
    top-k slice. Selection/commit/reverse and medoid-reachability repair
    are shared with the other bulk builders.
    """
    n = data.shape[0]
    flat = dataclasses.replace(params, max_layers=1)
    engine = BuildEngine(flat)
    r = flat.r_base
    adj = jnp.full((n, r), -1, jnp.int32)
    adj_d = jnp.full((n, r), INF)
    n_d = n_h = 0.0
    knn_adj = jnp.full((n, knn_k), -1, jnp.int32)

    if n >= 2:
        members = np.arange(n, dtype=np.int32)
        with obs.span("build/bulk_refine", layer=0) as sp:
            pool_ids, pool_d, n_d, n_h, rounds = bulk_refine(
                data, backend, members, r=r, params=flat, seed=seed, layer=0
            )
            sp.add_cost(n_d, n_h)
            sp.set(rounds=rounds)
        with obs.span("build/bulk_commit", layer=0):
            adj, adj_d, backend = bulk_commit(
                engine, adj, adj_d, backend, jnp.asarray(members),
                pool_ids, pool_d, r=r,
            )
        pool_p = pool_ids.shape[1]
        if pool_p >= knn_k:
            knn_adj = pool_ids[:, :knn_k]
        else:
            knn_adj = knn_adj.at[:, :pool_p].set(pool_ids)

    adj_up = jnp.full((0, n, flat.r_upper), -1, jnp.int32)
    adj_up_d = jnp.full((0, n, flat.r_upper), INF)
    levels = jnp.zeros((n,), jnp.int32)
    adj, adj_d, adj_up, adj_up_d, backend, rd, rh = repair_reachability(
        data, adj, adj_d, adj_up, adj_up_d, backend, levels, int(entry),
        params=flat,
    )
    del rd, rh  # FlatIndex carries no stats; counters kept for symmetry
    return FlatIndex(adj=adj, adj_d=adj_d, entry=entry, backend=backend), knn_adj


def build_nsg(
    data,
    backend,
    *,
    params: BuildParams = BuildParams(),
    knn_k: int = 16,
    strategy: str = "incremental",
    seed: int = 0,
):
    """Build an NSG-style index. Returns (FlatIndex, knn_adj).

    ``strategy="bulk"`` replaces BOTH the exact k-NN oracle pass and the
    per-batch beam acquisition with RNN-Descent refinement rounds
    (DESIGN.md §12); ``knn_adj`` then comes from the refined pools.
    """
    data = jnp.asarray(data, jnp.float32)
    entry = medoid_id(data)
    if strategy == "bulk":
        return _build_nsg_bulk(
            data, backend, entry, params=params, knn_k=knn_k, seed=seed
        )
    if strategy != "incremental":
        raise ValueError(f"unknown build strategy {strategy!r}")
    ids, _ = exact_knn(data, data, k=knn_k + 1)
    # Strip self-matches (first column is the point itself).
    knn_adj = ids[:, 1:]
    return _build_nsg_jit(data, backend, knn_adj, entry, params=params), knn_adj
