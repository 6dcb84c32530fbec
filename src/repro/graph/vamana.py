"""Vamana / τ-MG-style flat graph build (paper §4.5.3 generality target).

Same CA + NS skeleton as HNSW (which is the paper's point — Flash accelerates
any graph algorithm built from those two stages), differing in:

  * single layer, entry point = medoid (closest vector to the data mean),
  * robust prune with slack α ≥ 1 (α = 1 first pass, α > 1 second pass),
  * a refinement pass that re-runs CA+NS for every vertex against the built
    graph (DiskANN's two-pass schedule).

Built on the shared :class:`repro.graph.engine.BuildEngine` (DESIGN.md §3):
each pass is the engine's batch-synchronous insert loop with that pass's α.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.graph.beam import INF, beam_search
from repro.graph.engine import (
    BuildEngine,
    BuildParams,
    CostAccount,
    bulk_commit,
    bulk_refine,
    repair_reachability,
)
from repro.graph.hnsw import HNSWParams  # noqa: F401 — canonical param alias
from repro.graph.hnsw import SearchResult
from repro.graph.rerank import SearchSpec, rerank_topk, resolve_search_args


class FlatIndex(NamedTuple):
    adj: jax.Array  # (n, R) int32
    adj_d: jax.Array  # (n, R) f32
    entry: jax.Array  # () int32 — medoid
    backend: object


def medoid_id(data: jax.Array) -> jax.Array:
    """Vector closest to the dataset mean (the Vamana/NSG navigating start)."""
    mean = jnp.mean(data, axis=0)
    d = jnp.sum((data - mean[None, :]) ** 2, axis=-1)
    return jnp.argmin(d).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("params", "two_pass"))
def _build_flat_jit(data, backend, entry, *, params: BuildParams, two_pass: bool):
    n = data.shape[0]
    p = params.batch
    flat = dataclasses.replace(params, max_layers=1)
    levels = jnp.zeros((n,), jnp.int32)
    adj0 = jnp.full((n, flat.r_base), -1, jnp.int32)
    adj0_d = jnp.full((n, flat.r_base), INF)
    adj_up = jnp.full((1, n, flat.r_upper), -1, jnp.int32)
    adj_up_d = jnp.full((1, n, flat.r_upper), INF)

    adj0, adj0_d, adj_up, adj_up_d, backend, acct0 = BuildEngine(flat).bootstrap(
        data, adj0, adj0_d, adj_up, adj_up_d, backend, levels
    )
    nb = -(-n // p)

    def pass_body(alpha_pass, adj0, adj0_d, backend, start_batch, acct0):
        engine = BuildEngine(dataclasses.replace(flat, alpha=alpha_pass))

        def body(b, carry):
            adj0, adj0_d, backend, acct = carry
            ids = b * p + jnp.arange(p, dtype=jnp.int32)
            mask = ids < n
            ids = jnp.minimum(ids, n - 1)
            a0, a0d, au, aud, backend, acct = engine.insert_batch(
                data, adj0, adj0_d, adj_up, adj_up_d, backend,
                levels, ids, entry, mask, acct=acct,
            )
            return a0, a0d, backend, acct

        adj0, adj0_d, backend, acct = jax.lax.fori_loop(
            start_batch, nb, body, (adj0, adj0_d, backend, acct0)
        )
        return adj0, adj0_d, backend, acct

    adj0, adj0_d, backend, s1 = pass_body(1.0, adj0, adj0_d, backend, 1, acct0)
    if two_pass:
        # Refinement: re-insert every vertex with the relaxed α against the
        # built graph (candidates come from a fresh beam search, which
        # dominates the visited set V of the original algorithm).
        adj0, adj0_d, backend, s2 = pass_body(
            params.alpha, adj0, adj0_d, backend, 0, CostAccount.zero()
        )
    index = FlatIndex(adj=adj0, adj_d=adj0_d, entry=entry, backend=backend)
    return index, s1


def _build_vamana_bulk(data, backend, entry, *, params: BuildParams, seed: int):
    """Bulk Vamana (DESIGN.md §12): RNN-Descent pools + one α-relaxed commit.

    The refinement rounds subsume DiskANN's two-pass schedule — every
    vertex's pool is already refined against the whole dataset when the
    robust prune (α = ``params.alpha``) runs, so there is no second
    insertion sweep. Reachability from the medoid is repaired the same way
    as bulk HNSW.
    """
    n = data.shape[0]
    flat = dataclasses.replace(params, max_layers=1)
    engine = BuildEngine(flat)
    adj0 = jnp.full((n, flat.r_base), -1, jnp.int32)
    adj0_d = jnp.full((n, flat.r_base), INF)
    adj_up = jnp.full((0, n, flat.r_upper), -1, jnp.int32)
    adj_up_d = jnp.full((0, n, flat.r_upper), INF)
    levels = jnp.zeros((n,), jnp.int32)
    n_d = n_h = 0.0

    if n >= 2:
        members = np.arange(n, dtype=np.int32)
        with obs.span("build/bulk_refine", layer=0) as sp:
            pool_ids, pool_d, n_d, n_h, rounds = bulk_refine(
                data, backend, members, r=flat.r_base, params=flat,
                seed=seed, layer=0,
            )
            sp.add_cost(n_d, n_h)
            sp.set(rounds=rounds)
        with obs.span("build/bulk_commit", layer=0):
            adj0, adj0_d, backend = bulk_commit(
                engine, adj0, adj0_d, backend, jnp.asarray(members),
                pool_ids, pool_d, r=flat.r_base,
            )

    adj0, adj0_d, adj_up, adj_up_d, backend, rd, rh = repair_reachability(
        data, adj0, adj0_d, adj_up, adj_up_d, backend, levels, int(entry),
        params=flat,
    )
    index = FlatIndex(adj=adj0, adj_d=adj0_d, entry=entry, backend=backend)
    return index, CostAccount(
        n_dists=jnp.float32(n_d + rd), n_hops=jnp.float32(n_h + rh),
        phases=jnp.asarray([0.0, 0.0, 0.0, n_d, rd], jnp.float32),
    )


def build_vamana(
    data,
    backend,
    *,
    params: BuildParams = BuildParams(alpha=1.2),
    two_pass: bool = True,
    strategy: str = "incremental",
    seed: int = 0,
):
    data = jnp.asarray(data, jnp.float32)
    entry = medoid_id(data)
    if strategy == "bulk":
        # ``two_pass`` is an incremental-schedule knob; the bulk rounds
        # replace both passes, so it is accepted and ignored here.
        return _build_vamana_bulk(data, backend, entry, params=params, seed=seed)
    if strategy != "incremental":
        raise ValueError(f"unknown build strategy {strategy!r}")
    return _build_flat_jit(data, backend, entry, params=params, two_pass=two_pass)


@functools.partial(jax.jit, static_argnames=("spec",))
def _search_flat_spec(
    index: FlatIndex, queries, banned, reranker, *, spec: SearchSpec
) -> SearchResult:
    """The jitted flat pipeline: quantized beam from the medoid over the
    best ``spec.n_keep`` candidates → ``reranker`` second stage (skipped
    when None) — the flat-graph twin of ``hnsw._search_hnsw_spec``."""
    backend = index.backend

    def one(q):
        qctx = backend.prepare_query(q)
        res = beam_search(
            backend, qctx, index.adj, index.entry[None], ef=spec.ef,
            width=spec.width, banned=banned, n_keep=spec.n_keep,
        )
        if reranker is None:
            return (
                res.ids[: spec.k], res.dists[: spec.k], res.n_dists,
                jnp.int32(0),
            )
        ids, dists, n_rr = rerank_topk(reranker, q, res.ids, res.dists, spec.k)
        return ids, dists, res.n_dists, n_rr

    ids, dists, ns, nr = jax.vmap(one)(queries)
    ns, nr = jnp.sum(ns), jnp.sum(nr)
    return SearchResult(
        ids=ids, dists=dists, n_dists=ns + nr, n_scan=ns, n_rerank=nr
    )


def search_flat_result(
    index: FlatIndex,
    queries: jax.Array,
    *,
    k: int | None = None,
    ef_search: int = 64,
    width: int = 1,
    rerank_vectors: jax.Array | None = None,
    banned: jax.Array | None = None,
    spec: SearchSpec | None = None,
    reranker=None,
) -> SearchResult:
    """Flat two-stage search (DESIGN.md §11): beam from the medoid +
    Reranker second stage.

    The flat-graph counterpart of ``search_hnsw`` — same canonical
    ``spec=``/``reranker=`` interface with the same bit-exact legacy
    keyword mapping, same ``SearchResult`` shape (the ``repro.index``
    facade relies on that), same ``banned`` tombstone semantics
    (traversable, never returned), and the same split cost accounting.
    """
    spec, reranker = resolve_search_args(
        spec, reranker, k=k, ef=ef_search, width=width,
        rerank_vectors=rerank_vectors,
    )
    return _search_flat_spec(index, queries, banned, reranker, spec=spec)
