"""Neighbor Selection: the heuristic edge-selection stage (paper §2.2, line 6).

Given candidates sorted ascending by distance to the inserted vector x, the
MRNG-style heuristic keeps candidate v iff no already-selected u is closer to
v than x is (δ(u, v) < δ(v, x) excludes v). Vamana/τ-MG generalize with a
slack α ≥ 1 (exclude iff α·δ(u, v) < δ(v, x)); α = 1 is exactly HNSW.

The scan is sequential in the candidate order but each step is vectorized:
we precompute the (C, C) candidate pair-distance matrix through the backend
(``pair_table``; for Flash SDT sums — the table of §3.3.3, *zero* vector
fetches, contracted on the MXU) and run a ``lax.scan`` of C O(C) steps.

The same routine prunes overflowing reverse-edge lists (line 7): candidates
are then "existing neighbors ∪ {new vertex}".
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

INF = jnp.float32(jnp.inf)


class Selection(NamedTuple):
    ids: jax.Array  # (R,) int32, −1 padded, ascending by distance
    dists: jax.Array  # (R,) f32, +inf padded
    count: jax.Array  # () int32


def select_neighbors(
    backend,
    cand_ids: jax.Array,
    cand_dists: jax.Array,
    *,
    r: int,
    alpha: float = 1.0,
) -> Selection:
    """Greedy heuristic selection of ≤ r neighbors from sorted candidates.

    cand_ids   (C,) int32, −1 = invalid (must sort ascending by cand_dists,
               invalid entries at +inf — exactly a BeamResult).
    cand_dists (C,) f32 distances to the inserted vector (backend scale).
    """
    c = cand_ids.shape[0]
    valid = cand_ids >= 0
    safe = jnp.where(valid, cand_ids, 0)
    # (C, C) pair distances via the backend (Flash: SDT lookups).
    pair = backend.pair_table(safe)
    pair = jnp.where(valid[:, None] & valid[None, :], pair, INF)

    def step(carry, i):
        sel_mask, count = carry
        # v = candidate i. Selected u's all have δ(u,x) <= δ(v,x) (sorted), so
        # the paper's rule reduces to: exclude iff ∃ selected u with
        # α·δ(u,v) < δ(v,x).  (Squared distances — order-equivalent.)
        conflict = jnp.any(sel_mask & (alpha * pair[i] < cand_dists[i]))
        ok = valid[i] & ~conflict & (count < r)
        return (sel_mask.at[i].set(ok), count + ok.astype(jnp.int32)), ok

    (sel_mask, count), _ = jax.lax.scan(
        step, (jnp.zeros((c,), bool), jnp.int32(0)), jnp.arange(c)
    )
    # Extract ≤ r selected, keep ascending order (scan went in sorted order).
    # The stable sort carries the ids with their keys: no index gathers.
    key = jnp.where(sel_mask, cand_dists, INF)
    kk = min(r, c)  # candidate list may be shorter than r (bootstrap batches)
    dists, ids = jax.lax.sort(
        (key, jnp.where(sel_mask, cand_ids, -1)), num_keys=1, is_stable=True
    )
    ids, dists = ids[:kk], dists[:kk]
    if kk < r:
        ids = jnp.concatenate([ids, jnp.full((r - kk,), -1, ids.dtype)])
        dists = jnp.concatenate([dists, jnp.full((r - kk,), INF)])
    return Selection(ids=ids, dists=dists, count=count)


def prune_list(
    backend,
    cand_ids: jax.Array,
    cand_dists: jax.Array,
    *,
    r: int,
    alpha: float = 1.0,
    mode: str = "heuristic",
) -> Selection:
    """Prune an (unsorted) candidate list down to ≤ r entries.

    mode="heuristic" — sort then :func:`select_neighbors` (hnswlib's overflow
    behaviour, paper line 7).
    mode="farthest"  — keep the r closest (the cheap NSW-style variant; used
    as an ablation in the benchmarks).
    """
    d = jnp.where(cand_ids >= 0, cand_dists, INF)
    d_s, ids_s = jax.lax.sort((d, cand_ids), num_keys=1, is_stable=True)
    if mode == "farthest":
        ids = jnp.where(jnp.isfinite(d_s[:r]), ids_s[:r], -1)
        return Selection(
            ids=ids, dists=d_s[:r], count=jnp.sum((ids >= 0).astype(jnp.int32))
        )
    if mode != "heuristic":
        raise ValueError(f"unknown prune mode {mode!r}")
    return select_neighbors(backend, ids_s, d_s, r=r, alpha=alpha)
