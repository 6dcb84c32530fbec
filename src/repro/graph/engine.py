"""Shared batched CA+NS build engine (DESIGN.md §3).

Every graph index in this repo — HNSW, Vamana, NSG, and the segment-parallel
deployment — is the same two-stage loop the paper decomposes construction
into: **candidate acquisition** (CA: beam-search the frozen prefix graph
through a compact-code distance backend) and **neighbor selection** (NS: the
MRNG-style heuristic over the candidates), followed by a forward commit of the
selected lists and a reverse pass that adds y→x edges and prunes overflow.
This module is that loop, extracted once behind a public API so the algorithm
modules compose it instead of cross-importing each other's private helpers:

    engine = BuildEngine(BuildParams(r_base=32, ef=64, width=4))
    res    = engine.acquire(backend, qctx, adjacency, entries)   # CA
    sel    = engine.select(backend, res.ids, res.dists, r=r)     # NS
    ...    = engine.commit_forward(...); engine.reverse_pass(...)

or, for the full batch-synchronous layered build (HNSW and the flat builds):

    state  = engine.bootstrap(data, *state, levels)
    *state, acct = engine.insert_batch(data, *state, levels, ids, entry, mask,
                                       acct=acct)

Pluggable axes:
  * distance backend — anything satisfying the ``graph.backends`` protocol,
  * selection policy — ``BuildParams.select_mode`` ("heuristic" = MRNG rule
    with slack α; "closest" = plain top-R, the NSW-style ablation),
  * beam width — ``BuildParams.width`` (W): the multi-expansion beam feeds
    the distance backend W·R-wide candidate blocks per iteration (DESIGN.md
    §3.2), which is what keeps the Flash Pallas kernel dense,
  * cost accounting — a :class:`CostAccount` threaded through every CA call,
    so build benchmarks report distance evaluations, not just wall-clock.

Everything here is pure and shape-static: jit/vmap/shard_map-safe, with the
backend riding along in the carry (the Flash blocked neighbor-code mirror
stays in sync through ``with_updated_edges``).
"""

from __future__ import annotations

import functools
import os
from dataclasses import dataclass
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.graph.beam import INF, BeamResult, beam_search
from repro.graph.select import Selection, prune_list, select_neighbors

#: Build phases for per-phase distance attribution (DESIGN.md §14). The
#: CostAccount ``phases`` vector partitions ``n_dists`` over exactly these
#: buckets — bootstrap (seed-batch scoring), upper/base-layer beam
#: acquisition, bulk refinement rounds, and reachability repair — so the
#: paper's "where does indexing time go" table falls out of one build.
PHASE_NAMES = ("bootstrap", "beam_upper", "beam_base", "bulk", "repair")
N_PHASES = len(PHASE_NAMES)
PH_BOOTSTRAP, PH_BEAM_UPPER, PH_BEAM_BASE, PH_BULK, PH_REPAIR = range(N_PHASES)


@dataclass(frozen=True)
class BuildParams:
    """Static build hyper-parameters (hashable => jit static arg).

    r_upper:  R on layers ≥ 1 (paper's R).
    r_base:   R on layer 0 (2·R by default, per paper footnote 3).
    ef:       C — construction beam width (efConstruction).
    batch:    P — concurrent inserts per synchronous step.
    max_layers: total layers L (levels 0..L−1).
    alpha:    RNG-slack for selection (1.0 = HNSW; >1 = Vamana/τ-MG style).
    prune_mode: overflow pruning ("heuristic" per paper, "farthest" ablation).
    max_iters: beam expansion cap (defaults inside beam, scaled by width).
    width:    W — beam expansions per iteration (1 = classic HNSW beam;
              >1 = multi-expansion, denser distance blocks per iteration).
    select_mode: NS policy ("heuristic" = MRNG rule, "closest" = top-R).
    bulk_rounds: refinement-round cap for ``strategy="bulk"`` builds
              (DESIGN.md §12); rounds stop early on convergence.
    bulk_pool: candidate-pool width P kept per vertex between bulk rounds
              (0 = auto: 2·R of the layer being built — wide enough that
              the MRNG selection sees the same candidate diversity an
              ef-beam gives the incremental path).
    bulk_eps: convergence threshold — stop when the fraction of vertices
              whose pool changed in a round drops below this.
    bulk_alpha: selection slack used by bulk commits only (effective
              alpha = max(alpha, bulk_alpha)). Bulk pools are NN-balls
              plus random long-range candidates, not beam paths; without
              extra slack MRNG occlusion strips the long edges and the
              graph degenerates into per-cluster islands. 1.2 matches
              Vamana's recommended robust-prune slack.
    """

    r_upper: int = 16
    r_base: int = 32
    ef: int = 64
    batch: int = 32
    max_layers: int = 3
    alpha: float = 1.0
    prune_mode: str = "heuristic"
    max_iters: int | None = None
    width: int = 1
    select_mode: str = "heuristic"
    bulk_rounds: int = 3
    bulk_pool: int = 0
    bulk_eps: float = 0.02
    bulk_alpha: float = 1.2

    def bulk_select_alpha(self) -> float:
        """Effective RNG slack for bulk selection/reverse pruning."""
        return max(self.alpha, self.bulk_alpha)


class CostAccount(NamedTuple):
    """Build cost counters, threaded through every CA stage.

    n_dists: distance evaluations (the paper's dominant cost term).
    n_hops:  expanded vertices (≈ adjacency-row fetches).
    phases:  (N_PHASES,) f32 per-phase split of ``n_dists`` in
             :data:`PHASE_NAMES` order, or None for accounts built before
             the profiler existed. Both sides are exact integer-valued
             f32 accumulations, so ``phases.sum() == n_dists`` holds
             exactly for any build below 2**24 evaluations per bucket.
    """

    n_dists: jax.Array
    n_hops: jax.Array
    phases: jax.Array | None = None

    @classmethod
    def zero(cls) -> "CostAccount":
        return cls(
            n_dists=jnp.float32(0), n_hops=jnp.float32(0),
            phases=jnp.zeros((N_PHASES,), jnp.float32),
        )

    def add_beam(self, res: BeamResult, *, phase: int = PH_BEAM_BASE) -> "CostAccount":
        """Fold a (possibly vmapped) beam result into the account."""
        nd = jnp.sum(res.n_dists)
        return CostAccount(
            n_dists=self.n_dists + nd,
            n_hops=self.n_hops + jnp.sum(res.n_hops),
            phases=(
                None if self.phases is None
                else self.phases.at[phase].add(nd.astype(jnp.float32))
            ),
        )

    def add_dists(self, n, *, phase: int, n_hops=0) -> "CostAccount":
        """Fold raw evaluation counts in (non-beam scoring: bootstrap,
        bulk rounds, repair) with their phase attribution."""
        nd = jnp.float32(n)
        return CostAccount(
            n_dists=self.n_dists + nd,
            n_hops=self.n_hops + jnp.float32(n_hops),
            phases=(
                None if self.phases is None else self.phases.at[phase].add(nd)
            ),
        )


class BuildStats(NamedTuple):
    """Public build-cost summary (the CostAccount, frozen at return).

    ``phases`` carries the per-phase ``n_dists`` split when the builder
    tracked one (None otherwise — e.g. NSG, whose adapter reports no
    stats); :data:`PHASE_NAMES` gives the bucket order.
    """

    n_dists: jax.Array
    n_hops: jax.Array
    phases: jax.Array | None = None

    def phase_dict(self) -> dict | None:
        """Host-side ``{phase_name: n_dists}`` view of :attr:`phases`
        (None when the builder tracked no split). Cross-process build
        observability (graph/sharded.py workers) ships this dict — not
        the device array — from worker back to the coordinator."""
        if self.phases is None:
            return None
        vals = np.asarray(self.phases, np.float64)
        return {name: float(v) for name, v in zip(PHASE_NAMES, vals)}


def sample_levels(
    seed: int, n: int, *, r_upper: int, max_layers: int
) -> np.ndarray:
    """Exponentially decaying level assignment, mL = 1/ln(R_upper)."""
    rng = np.random.default_rng(seed)
    m_l = 1.0 / np.log(max(r_upper, 2))
    lv = np.floor(-np.log(rng.uniform(1e-12, 1.0, size=n)) * m_l).astype(np.int32)
    return np.minimum(lv, max_layers - 1)


def prefix_entries(
    levels: np.ndarray, batch: int, *, start: int = 0, entry0: int = -1
) -> np.ndarray:
    """Host-side: entry point (argmax level over the inserted prefix) per batch.

    Batch b inserts ids [start + b·P, start + (b+1)·P); its searches start
    from the highest-level vertex among all earlier ids — exactly hnswlib's
    enter-point maintenance, precomputed because insertion order is known up
    front. A fresh build uses the defaults (start=0, no prior entry);
    dynamic growth (``repro.index.AnnIndex.add``, DESIGN.md §8) passes the
    old size as ``start`` and the live graph's entry as ``entry0`` so the
    plan continues from the built prefix instead of rescanning it.
    """
    n = len(levels)
    nb = -(-(n - start) // batch)
    ent = np.full((nb,), -1, np.int64)
    best = int(entry0)
    best_lv = int(levels[best]) if best >= 0 else -1
    idx = start if best >= 0 else 0
    for b in range(nb):
        bstart = start + b * batch
        while idx < bstart:
            if levels[idx] > best_lv:
                best_lv, best = int(levels[idx]), idx
            idx += 1
        ent[b] = best
    return ent.astype(np.int32)


# ---------------------------------------------------------------------------
# Edge commit (module-level pure helpers; BuildEngine methods wrap them)
# ---------------------------------------------------------------------------


def commit_forward(adj, adj_d, backend, new_ids, sel_ids, sel_d, mask):
    """Write the selected neighbor lists of a batch of new vertices.

    Masked-out rows scatter to an out-of-bounds index with mode="drop" —
    masked ids may be clamped duplicates of real ids, and duplicate scatter
    order is undefined.
    """
    n = adj.shape[0]
    ids_s = jnp.where(mask, new_ids, n)  # n = out of bounds -> dropped
    adj = adj.at[ids_s].set(sel_ids, mode="drop")
    adj_d = adj_d.at[ids_s].set(sel_d, mode="drop")
    backend = backend.with_updated_edges(ids_s, sel_ids)
    return adj, adj_d, backend


def reverse_pass(
    adj, adj_d, backend, new_ids, sel_ids, sel_d, mask, *, params: BuildParams
):
    """Add reverse edges y → x for each x in the batch, pruning overflow.

    Sequential over the P inserts (they may touch the same destination y);
    vectorized over each insert's ≤R destinations (distinct within one list).
    Destinations that already list x are skipped — a no-op for fresh builds
    (x has no incoming edges yet) that makes *re*-insertion of an existing
    vertex (``repro.index`` compaction, DESIGN.md §8) duplicate-free.
    """
    p, r = sel_ids.shape

    def body(i, carry):
        adj, adj_d, backend = carry
        x = new_ids[i]
        nbrs, nd = sel_ids[i], sel_d[i]  # (r,)
        ok = (nbrs >= 0) & mask[i]
        safe = jnp.where(ok, nbrs, 0)
        ex_ids = adj[safe]  # (r, r)
        ex_d = adj_d[safe]
        ok &= ~jnp.any(ex_ids == x, axis=1)  # y already lists x -> skip
        counts = jnp.sum(ex_ids >= 0, axis=1)  # (r,)
        # Room left → plain append at the first free slot (hnswlib line 7).
        slot = jnp.arange(r)[None, :] == counts[:, None]
        app_ids = jnp.where(slot, x, ex_ids)
        app_d = jnp.where(slot, nd[:, None], ex_d)
        # Full → heuristic prune over existing ∪ {x} (r+1 candidates).
        cand_ids = jnp.concatenate([ex_ids, jnp.full((r, 1), x, jnp.int32)], 1)
        cand_d = jnp.concatenate([ex_d, nd[:, None]], 1)
        pruned = jax.vmap(
            lambda ci, cd: prune_list(
                backend, ci, cd, r=r, alpha=params.alpha, mode=params.prune_mode
            )
        )(cand_ids, cand_d)
        full = counts >= r
        rows = jnp.where(full[:, None], pruned.ids, app_ids)
        rows_d = jnp.where(full[:, None], pruned.dists, app_d)
        n = adj.shape[0]
        dst = jnp.where(ok, safe, n)  # masked dsts dropped (see commit_forward)
        adj = adj.at[dst].set(rows, mode="drop")
        adj_d = adj_d.at[dst].set(rows_d, mode="drop")
        backend = backend.with_updated_edges(dst, rows)
        return adj, adj_d, backend

    return jax.lax.fori_loop(0, p, body, (adj, adj_d, backend))


def _drop_self(cand_ids, cand_d, new_ids):
    """Strike each inserted vertex from its own candidate list.

    A fresh build can never acquire the vertex being inserted (it has no
    incoming edges yet), so this is bit-exact no-op there — the stable
    sort of an already-sorted list is the identity. Re-inserting an
    EXISTING vertex (``repro.index`` compaction, DESIGN.md §8) does find
    itself at distance ~0, and without this mask would select itself as its
    own closest neighbor.
    """
    self_hit = cand_ids == new_ids[:, None]
    d, ids = jax.lax.sort(
        (jnp.where(self_hit, INF, cand_d), jnp.where(self_hit, -1, cand_ids)),
        dimension=1, num_keys=1, is_stable=True,
    )
    return ids, d


# ---------------------------------------------------------------------------
# The engine
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class BuildEngine:
    """Composable CA → NS → commit pipeline over one static param set.

    Hashable (frozen dataclass of a frozen dataclass), so an engine is a
    valid jit static argument; all methods are pure functions of traced
    array state.
    """

    params: BuildParams

    # ---- CA: candidate acquisition ------------------------------------

    def acquire(self, backend, qctx, adjacency, entries) -> BeamResult:
        """Batched beam search: qctx pytree with leading (P,), entries (P,)."""
        p = self.params
        return jax.vmap(
            lambda qc, e: beam_search(
                backend, qc, adjacency, e[None],
                ef=p.ef, width=p.width, max_iters=p.max_iters,
            )
        )(qctx, entries)

    # ---- NS: neighbor selection (pluggable policy) --------------------

    def select_one(self, backend, cand_ids, cand_d, *, r: int) -> Selection:
        """Select ≤ r neighbors from one sorted candidate list."""
        mode = self.params.select_mode
        if mode == "heuristic":
            return select_neighbors(
                backend, cand_ids, cand_d, r=r, alpha=self.params.alpha
            )
        if mode == "closest":
            # NSW-style ablation: keep the r nearest, no occlusion rule.
            c = cand_ids.shape[0]
            kk = min(r, c)
            ids = jnp.where(jnp.isfinite(cand_d[:kk]), cand_ids[:kk], -1)
            dists = jnp.where(ids >= 0, cand_d[:kk], INF)
            if kk < r:
                ids = jnp.concatenate([ids, jnp.full((r - kk,), -1, ids.dtype)])
                dists = jnp.concatenate([dists, jnp.full((r - kk,), INF)])
            return Selection(
                ids=ids, dists=dists, count=jnp.sum((ids >= 0).astype(jnp.int32))
            )
        raise ValueError(f"unknown select_mode {mode!r}")

    def select(self, backend, cand_ids, cand_d, *, r: int) -> Selection:
        """Batched selection over (P, C) candidate lists."""
        return jax.vmap(
            lambda ci, cd: self.select_one(backend, ci, cd, r=r)
        )(cand_ids, cand_d)

    # ---- commit --------------------------------------------------------

    def commit_forward(self, adj, adj_d, backend, new_ids, sel_ids, sel_d, mask):
        return commit_forward(adj, adj_d, backend, new_ids, sel_ids, sel_d, mask)

    def reverse_pass(self, adj, adj_d, backend, new_ids, sel_ids, sel_d, mask):
        return reverse_pass(
            adj, adj_d, backend, new_ids, sel_ids, sel_d, mask, params=self.params
        )

    # ---- composed: one batch-synchronous layered insert ----------------

    def insert_batch(
        self, data, adj0, adj0_d, adj_up, adj_up_d, backend, levels,
        new_ids, entry, mask, *, acct: CostAccount,
    ):
        """Insert one batch of P vectors against the frozen current graph."""
        p = new_ids.shape[0]
        params = self.params
        l_top = params.max_layers - 1
        qctx = jax.vmap(backend.prepare_query)(data[new_ids])  # pytree (P, …)
        lv = levels[new_ids]

        eps = jnp.full((p,), entry, jnp.int32)  # current per-query entry point

        # ---- upper layers: descend + (maybe) insert ----------------------
        for l in range(l_top, 0, -1):
            adj_l, adj_ld = adj_up[l - 1], adj_up_d[l - 1]
            res = self.acquire(backend, qctx, adj_l, eps)
            acct = acct.add_beam(res, phase=PH_BEAM_UPPER)
            do = (lv >= l) & mask
            cand_ids, cand_d = _drop_self(res.ids, res.dists, new_ids)
            sel = self.select(backend, cand_ids, cand_d, r=params.r_upper)
            sel_ids = jnp.where(do[:, None], sel.ids, -1)
            sel_d = jnp.where(do[:, None], sel.dists, INF)
            adj_l, adj_ld, backend = self.commit_forward(
                adj_l, adj_ld, backend, new_ids, sel_ids, sel_d, do
            )
            adj_l, adj_ld, backend = self.reverse_pass(
                adj_l, adj_ld, backend, new_ids, sel_ids, sel_d, do
            )
            adj_up = adj_up.at[l - 1].set(adj_l)
            adj_up_d = adj_up_d.at[l - 1].set(adj_ld)
            # next-layer entry: the closest vertex found at this layer (if any).
            eps = jnp.where(res.ids[:, 0] >= 0, res.ids[:, 0], eps)

        # ---- base layer --------------------------------------------------
        res = self.acquire(backend, qctx, adj0, eps)
        acct = acct.add_beam(res, phase=PH_BEAM_BASE)
        cand_ids, cand_d = _drop_self(res.ids, res.dists, new_ids)
        sel = self.select(backend, cand_ids, cand_d, r=params.r_base)
        sel_ids = jnp.where(mask[:, None], sel.ids, -1)
        sel_d = jnp.where(mask[:, None], sel.dists, INF)
        adj0, adj0_d, backend = self.commit_forward(
            adj0, adj0_d, backend, new_ids, sel_ids, sel_d, mask
        )
        adj0, adj0_d, backend = self.reverse_pass(
            adj0, adj0_d, backend, new_ids, sel_ids, sel_d, mask
        )
        return adj0, adj0_d, adj_up, adj_up_d, backend, acct

    # ---- composed: exact sequential seed batch --------------------------

    def bootstrap(
        self, data, adj0, adj0_d, adj_up, adj_up_d, backend, levels,
        *, acct: CostAccount | None = None,
    ):
        """Exact sequential insertion of the first batch (connected seed).

        Returns the graph carry plus a :class:`CostAccount` whose
        ``query_dists`` evaluations (p per insert, p inserts — the seed
        batch's p² scoring) are attributed to the ``bootstrap`` phase;
        pre-profiler callers that ignored bootstrap cost can pass and
        discard it, but the build loops thread it so build totals now
        cover every evaluation the engine issues.
        """
        params = self.params
        p = min(params.batch, data.shape[0])
        cand_pool = jnp.arange(p, dtype=jnp.int32)
        if acct is None:
            acct = CostAccount.zero()

        def body(i, carry):
            adj0, adj0_d, adj_up, adj_up_d, backend, acct = carry
            qctx = backend.prepare_query(data[i])
            d_all = backend.query_dists(qctx, cand_pool)  # (p,)
            acct = acct.add_dists(p, phase=PH_BOOTSTRAP)
            for l in range(params.max_layers - 1, -1, -1):
                r_l = params.r_base if l == 0 else params.r_upper
                elig = (cand_pool < i) & (levels[:p] >= l) & (levels[i] >= l)
                d = jnp.where(elig, d_all, INF)
                d_s, ids_s = jax.lax.sort(
                    (d, cand_pool), num_keys=1, is_stable=True
                )
                ids_s = jnp.where(jnp.isfinite(d_s), ids_s, -1)
                sel = self.select_one(backend, ids_s, d_s, r=r_l)
                new_ids = jnp.full((1,), i, jnp.int32)
                m1 = jnp.array([levels[i] >= l])
                if l == 0:
                    adj0, adj0_d, backend = self.commit_forward(
                        adj0, adj0_d, backend, new_ids,
                        sel.ids[None], sel.dists[None], m1,
                    )
                    adj0, adj0_d, backend = self.reverse_pass(
                        adj0, adj0_d, backend, new_ids,
                        sel.ids[None], sel.dists[None], m1,
                    )
                else:
                    a, ad = adj_up[l - 1], adj_up_d[l - 1]
                    a, ad, backend = self.commit_forward(
                        a, ad, backend, new_ids, sel.ids[None], sel.dists[None], m1
                    )
                    a, ad, backend = self.reverse_pass(
                        a, ad, backend, new_ids, sel.ids[None], sel.dists[None], m1
                    )
                    adj_up = adj_up.at[l - 1].set(a)
                    adj_up_d = adj_up_d.at[l - 1].set(ad)
            return adj0, adj0_d, adj_up, adj_up_d, backend, acct

        return jax.lax.fori_loop(
            0, p, body, (adj0, adj0_d, adj_up, adj_up_d, backend, acct)
        )

    # ---- composed: the whole layered build (HNSW and flat graphs) -------

    def build_layered(self, data, backend, levels, entries):
        """Batch-synchronous build loop over all of ``data`` (DESIGN.md §2).

        Returns (adj0, adj0_d, adj_up, adj_up_d, backend, CostAccount);
        callers wrap the arrays into their index type. Not jitted here —
        algorithm modules jit their wrappers with the engine static.
        """
        params = self.params
        n = data.shape[0]
        p = params.batch
        # A 1-layer build allocates a 0-length upper stack, so search-side
        # layer derivation (adj_up.shape[0] + 1) reports the true depth.
        l_up = params.max_layers - 1
        adj0 = jnp.full((n, params.r_base), -1, jnp.int32)
        adj0_d = jnp.full((n, params.r_base), INF)
        adj_up = jnp.full((l_up, n, params.r_upper), -1, jnp.int32)
        adj_up_d = jnp.full((l_up, n, params.r_upper), INF)

        adj0, adj0_d, adj_up, adj_up_d, backend, acct = self.bootstrap(
            data, adj0, adj0_d, adj_up, adj_up_d, backend, levels
        )

        nb = -(-n // p)

        def body(b, carry):
            adj0, adj0_d, adj_up, adj_up_d, backend, acct = carry
            start = b * p
            ids = start + jnp.arange(p, dtype=jnp.int32)
            mask = ids < n
            ids = jnp.minimum(ids, n - 1)
            return self.insert_batch(
                data, adj0, adj0_d, adj_up, adj_up_d, backend, levels,
                ids, entries[b], mask, acct=acct,
            )

        adj0, adj0_d, adj_up, adj_up_d, backend, acct = jax.lax.fori_loop(
            1, nb, body,
            (adj0, adj0_d, adj_up, adj_up_d, backend, acct),
        )
        return adj0, adj0_d, adj_up, adj_up_d, backend, acct


# ---------------------------------------------------------------------------
# Insert scheduling (shared by dynamic maintenance and bulk repair)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("engine",))
def run_insert_schedule(
    engine: BuildEngine, data, adj0, adj0_d, adj_up, adj_up_d, backend,
    levels, ids, entries, mask,
):
    """Run ``engine.insert_batch`` over a (nb, P) id schedule against an
    existing graph — the device program behind every post-build insertion:
    dynamic growth and compaction (``repro.index.grow_index`` delegates
    here, DESIGN.md §8) and the bulk build's reachability repair (§12).

    ids/mask (nb, P): padded id batches; entries (nb,): per-batch entry
    point. Returns the updated graph arrays, backend, and a CostAccount of
    the insertions' distance evaluations.
    """

    def body(b, carry):
        adj0, adj0_d, adj_up, adj_up_d, backend, acct = carry
        return engine.insert_batch(
            data, adj0, adj0_d, adj_up, adj_up_d, backend, levels,
            ids[b], entries[b], mask[b], acct=acct,
        )

    return jax.lax.fori_loop(
        0, ids.shape[0], body,
        (adj0, adj0_d, adj_up, adj_up_d, backend, CostAccount.zero()),
    )


def batch_schedule(ids: np.ndarray, batch: int):
    """Host-side: pad a flat id list to full (nb, P) batches + validity mask."""
    n = len(ids)
    nb = -(-n // batch)
    pad = nb * batch - n
    ids_p = np.concatenate([ids, np.full(pad, ids[-1] if n else 0, np.int32)])
    mask = np.concatenate([np.ones(n, bool), np.zeros(pad, bool)])
    return ids_p.reshape(nb, batch).astype(np.int32), mask.reshape(nb, batch)


# ---------------------------------------------------------------------------
# Bulk construction (strategy="bulk"): RNN-Descent refinement rounds
# (DESIGN.md §12)
# ---------------------------------------------------------------------------
#
# The incremental path above is serial in the graph prefix: batch b's beam
# searches need batch b−1's edges. The bulk path removes that dependency by
# bootstrapping the k-NN pool with whole-dataset refinement rounds à la
# Relative NN-Descent: every vertex keeps a pool of its P best candidates,
# and each round scores pool ∪ neighbor-of-neighbor expansion for ALL
# vertices in one dense batched pass (``backend.round_dists`` — for Flash
# one blocked Pallas launch per chunk, kernels.ops.flash_round). The refined
# pools then feed the SAME neighbor selection, forward commit, and reverse
# pass as the incremental path (``BuildEngine.select``, ``commit_forward``,
# ``reverse_pass``), so graph semantics are unchanged — only candidate
# acquisition is replaced.

#: vertices scored per round_dists launch — bounds the (chunk, C) gather and
#: the (chunk, M, K) query-context block resident at once.
_BULK_CHUNK = 256

#: pool prefix expanded per round (NN-Descent's sampled join): candidates
#: per round are P + E² — E=8 keeps the block dense but bounded, trading a
#: round or two of convergence for a ~3× smaller scoring block per round.
_BULK_EXPAND = 8

#: rows per vmapped block of a whole-layer selection or prune: each row
#: holds a (C, C) pair table, so a whole layer at once is n·C² entries —
#: 9.2·10⁹ at n = 10⁶, past what the TPU compiler can even index.
_COMMIT_ROWS = 256

#: random extra candidates appended to each final pool before selection —
#: MRNG keeps the un-occluded ones, which is where the graph gets its
#: long-range (cross-cluster) edges; pure refined pools converge to local
#: k-NN islands that no beam can enter. 32 per vertex (with the extra
#: occlusion slack of ``bulk_alpha``) is enough for the clustered
#: benchmark distributions; the cost is one extra scoring pass, no merge.
_BULK_RANDOM = 32


def _bulk_score(backend, data, members, cand, chunk: int):
    """Chunked ``round_dists`` scoring of a (m, C) candidate block.

    Each chunk's query contexts are built inside the chunk: for a whole
    layer at once they would be (m, M, K) tables, GBs at n = 10⁶ with a
    wide coder. Masks self/invalid entries to +inf. ``m`` must be a
    multiple of ``chunk`` (the caller pads once). Returns (dists (m, C),
    bad (m, C) mask).
    """
    m, c = cand.shape
    n_chunks = m // chunk

    def score(args):
        mem, cd = args  # (chunk,), (chunk, C)
        qctx = jax.vmap(backend.prepare_query)(data[mem])
        return backend.round_dists(qctx, jnp.maximum(cd, 0))

    d = jax.lax.map(
        score, (members.reshape(n_chunks, chunk), cand.reshape(n_chunks, chunk, c))
    ).reshape(m, c)

    bad = (cand < 0) | (cand == members[:, None])
    return jnp.where(bad, INF, d), bad


def _bulk_score_topk(backend, data, members, cand, pool_p: int, chunk: int):
    """Score a (m, C) candidate block and keep the best P per row — NO
    dedup. A repeated id occupies repeated pool slots for a round, which
    wastes a little pool width but skips the per-row id-sort (the single
    most expensive op in a refinement round); the loop exit runs one
    exact dedup merge (:func:`_bulk_score_merge`) so downstream consumers
    never see duplicates. Returns (ids, dists, n_scored) like the merge.
    """
    m, c = cand.shape
    d, bad = _bulk_score(backend, data, members, cand, chunk)
    neg, idx = jax.lax.top_k(-d, pool_p)
    new_d = -neg
    new_ids = jnp.take_along_axis(cand, idx, axis=1)
    fin = jnp.isfinite(new_d)
    return (
        jnp.where(fin, new_ids, -1),
        jnp.where(fin, new_d, INF),
        jnp.sum(~bad),
    )


def _bulk_score_merge(backend, data, members, cand, pool_p: int, chunk: int):
    """Score a (m, C) candidate block and merge to the best P per row.

    Traced helper shared by pool init and the loop-exit cleanup: chunked
    scoring (:func:`_bulk_score`), per-row dedup (sort by id, strike
    adjacent repeats), then a top-P merge. Returns (ids (m, P) ascending
    by distance −1-padded, dists (m, P) +inf-padded, n_scored).
    """
    m, c = cand.shape
    d, bad = _bulk_score(backend, data, members, cand, chunk)
    n_scored = jnp.sum(~bad)
    # Dedup: stable-sort each row by id (invalids to a sentinel past any
    # real id), strike adjacent repeats; merging then works directly on the
    # id-sorted row — top_k tie-breaks by position, so results are
    # deterministic.
    idkey = jnp.where(bad, jnp.int32(2**30), cand)
    order = jnp.argsort(idkey, axis=1, stable=True)
    ids_s = jnp.take_along_axis(cand, order, axis=1)
    d_s = jnp.take_along_axis(d, order, axis=1)
    dup = jnp.concatenate(
        [jnp.zeros((m, 1), bool), ids_s[:, 1:] == ids_s[:, :-1]], axis=1
    )
    d_s = jnp.where(dup, INF, d_s)
    neg, idx = jax.lax.top_k(-d_s, pool_p)
    new_d = -neg
    new_ids = jnp.take_along_axis(ids_s, idx, axis=1)
    fin = jnp.isfinite(new_d)
    return (
        jnp.where(fin, new_ids, -1),
        jnp.where(fin, new_d, INF),
        n_scored,
    )


@functools.partial(
    jax.jit, static_argnames=("r_exp", "chunk", "max_rounds", "pool_p")
)
def _bulk_refine_jit(
    data, backend, members, valid, cand0, rnd_aug, inv, eps_count,
    *, pool_p: int, r_exp: int, chunk: int, max_rounds: int,
):
    """The whole refinement schedule as ONE compiled program.

    Seeds pools from ``cand0``, then a ``while_loop`` of refinement rounds
    (candidates = pool ∪ neighbor-of-neighbor prefix block, one batched
    scoring pass each) until fewer than ``eps_count`` valid rows change or
    ``max_rounds`` is hit — no host round-trips between rounds. A final
    pass scores ``rnd_aug`` random candidates (see ``_BULK_RANDOM``) and
    appends them to the pool tail for selection to occlusion-filter.

    ``members``/``cand0``/``rnd_aug`` come in padded to a multiple of
    ``chunk`` with ``valid`` marking real rows; ``inv`` maps global id →
    member row. Returns (pool_ids (m_pad, P+S), pool_d, n_rounds,
    n_scored).
    """
    pool_ids, pool_d, nsc0 = _bulk_score_merge(
        backend, data, members, cand0, pool_p, chunk
    )

    def cond(carry):
        _, _, rounds, changed, _ = carry
        return (rounds < max_rounds) & (changed > eps_count)

    def body(carry):
        pool_ids, pool_d, rounds, _, n_scored = carry
        m = pool_ids.shape[0]
        top = pool_ids[:, :r_exp]  # (m, E) global ids
        ok = top >= 0
        rows = top[inv[jnp.maximum(top, 0)]]  # (m, E, E): E-prefix rows
        non = jnp.where(ok[:, :, None], rows, -1).reshape(m, r_exp * r_exp)
        cand = jnp.concatenate([pool_ids, non], axis=1)  # (m, P + E²)
        new_ids, new_d, nsc = _bulk_score_topk(
            backend, data, members, cand, pool_p, chunk
        )
        changed = jnp.sum(jnp.any(new_ids != pool_ids, axis=1) & valid)
        return new_ids, new_d, rounds + 1, changed, n_scored + nsc

    pool_ids, pool_d, rounds, _, n_scored = jax.lax.while_loop(
        cond, body,
        (pool_ids, pool_d, jnp.int32(0), jnp.int32(2**30), nsc0),
    )
    # Rounds merge duplicate-tolerant (_bulk_score_topk); one exact merge
    # of the pool against itself strikes the accumulated repeats before
    # anything downstream consumes it.
    pool_ids, pool_d, nsc_c = _bulk_score_merge(
        backend, data, members, pool_ids, pool_p, chunk
    )
    n_scored = n_scored + nsc_c
    # Random augmentation: append S scored random members to each pool so
    # MRNG selection sees long-range candidates. No merge pass is needed —
    # ``prune_list`` sorts its candidates and the occlusion rule strikes
    # any duplicate of a pool entry (pair distance 0), so the tail only
    # has to be scored. The refined NN prefix stays intact (NSG's knn
    # slice is safe).
    aug_d, aug_bad = _bulk_score(backend, data, members, rnd_aug, chunk)
    pool_ids = jnp.concatenate(
        [pool_ids, jnp.where(aug_bad, -1, rnd_aug)], axis=1
    )
    pool_d = jnp.concatenate([pool_d, aug_d], axis=1)
    return pool_ids, pool_d, rounds, n_scored + jnp.sum(~aug_bad)


def bulk_pool_width(params: BuildParams, r: int, m: int) -> int:
    """Resolved candidate-pool width P for a layer of degree ``r`` over
    ``m`` members (``bulk_pool`` knob, 0 = auto 2·R, clamped to m−1)."""
    p = params.bulk_pool if params.bulk_pool > 0 else 2 * r
    return max(1, min(p, m - 1))


def bulk_refine(
    data, backend, member_ids: np.ndarray, *, r: int, params: BuildParams,
    seed: int, layer: int = 0,
):
    """Refine a k-NN candidate pool over ``member_ids`` by batched rounds.

    Host wrapper around the single compiled refinement program
    (:func:`_bulk_refine_jit`): pads the member set to the scoring chunk,
    seeds each pool with random members, draws the random-augmentation
    block, and unpads the result. Convergence (``bulk_eps``/``bulk_rounds``)
    runs entirely on-device.

    Returns (pool_ids (m, P+S), pool_d, n_dists, n_hops, n_rounds): the
    first P columns are the refined pool ascending by distance, the S-wide
    tail the scored random augmentation (unsorted); n_hops counts
    adjacency-pool row fetches (m·E per round), the bulk analogue of beam
    hops.
    """
    m = int(len(member_ids))
    if m < 2:
        raise ValueError(f"bulk_refine needs ≥ 2 members, got {m}")
    n = data.shape[0]
    pool_p = bulk_pool_width(params, r, m)
    r_exp = min(r, pool_p, _BULK_EXPAND)
    s_aug = min(_BULK_RANDOM, m - 1)
    chunk = min(_BULK_CHUNK, m)
    m_pad = -(-m // chunk) * chunk
    mem_np = np.asarray(member_ids, np.int32)
    rng = np.random.default_rng([seed, 0xB07B, layer])
    rnd = rng.integers(0, m - 1, size=(m, pool_p))
    rnd += rnd >= np.arange(m)[:, None]  # shift past self: uniform on m−1
    cand0 = mem_np[rnd]
    aug = mem_np[rng.integers(0, m, size=(m, s_aug))]

    pad = m_pad - m
    mem_p = np.concatenate([mem_np, np.full(pad, mem_np[0], np.int32)])
    cand0 = np.concatenate([cand0, np.full((pad, pool_p), -1, np.int32)])
    aug = np.concatenate([aug, np.full((pad, s_aug), -1, np.int32)])
    valid = np.concatenate([np.ones(m, bool), np.zeros(pad, bool)])
    inv = (
        jnp.zeros((n,), jnp.int32)
        .at[jnp.asarray(mem_np)].set(jnp.arange(m, dtype=jnp.int32))
    )

    pool_ids, pool_d, rounds, n_scored = _bulk_refine_jit(
        data, backend, jnp.asarray(mem_p), jnp.asarray(valid),
        jnp.asarray(cand0), jnp.asarray(aug), inv,
        jnp.int32(int(params.bulk_eps * m)),
        pool_p=pool_p, r_exp=r_exp, chunk=chunk,
        max_rounds=params.bulk_rounds,
    )
    rounds = int(rounds)
    obs.tick("bulk_rounds_total", n=rounds, layer=str(layer))
    return (
        pool_ids[:m], pool_d[:m],
        float(n_scored), float(m * r_exp * rounds), rounds,
    )


def _f32_sort_key(x):
    """int32 keys in ``jax.lax.sort``'s order of the float32 ``x`` (−0
    equal to +0, NaN last): the bits of the canonical float, with the
    magnitude bits flipped below zero."""
    x = jnp.where(x == 0, 0.0, x)
    x = jnp.where(jnp.isnan(x), jnp.nan, x)
    i = jax.lax.bitcast_convert_type(x, jnp.int32)
    return i ^ ((i >> 31) & 0x7FFFFFFF)


@functools.partial(jax.jit, static_argnames=("params",))
def bulk_reverse(adj, adj_d, backend, members, sel_ids, sel_d,
                 *, params: BuildParams):
    """Reverse pass for a whole-membership commit — batched, not serial.

    The incremental ``reverse_pass`` walks inserts one by one because
    concurrent inserts may touch the same destination row. A bulk commit
    has ALL forward lists at once, so the reverse direction becomes a
    grouping problem: flatten every forward edge x→y into a proposal
    y←x, bucket proposals by destination (sort by (y, d), rank within
    group, keep the best K=2R per destination), and prune each touched
    row's existing ∪ proposed candidates with the SAME MRNG heuristic the
    serial pass applies (``prune_list``) — one vmapped prune over n rows
    instead of an m-step ``fori_loop``.
    """
    m, r = sel_ids.shape
    n = adj.shape[0]
    k_cap = 2 * r
    src = jnp.repeat(members, r)  # (m·r,)
    dst = sel_ids.reshape(-1)
    dd = sel_d.reshape(-1)
    dstk = jnp.where(dst >= 0, dst, n)  # invalid edges bucket to sentinel n
    # group by destination, ascending distance within each group: a stable
    # sort by distance, then a stable sort by destination, each carrying the
    # other arrays. All int32 (distances by their bits): with float operands
    # the chip's compiler takes about twice as long on these m·r elements.
    ddb = jax.lax.bitcast_convert_type(dd, jnp.int32)
    _, dst_s, src_s, ddb = jax.lax.sort(
        (_f32_sort_key(dd), dstk, src, ddb), num_keys=1, is_stable=True
    )
    dst_s, src_s, ddb = jax.lax.sort(
        (dst_s, src_s, ddb), num_keys=1, is_stable=True
    )
    dd_s = jax.lax.bitcast_convert_type(ddb, jnp.float32)
    idx = jnp.arange(m * r)
    first = jnp.concatenate(
        [jnp.ones((1,), bool), dst_s[1:] != dst_s[:-1]]
    )
    start = jax.lax.cummax(jnp.where(first, idx, 0))
    rank = idx - start
    ok = (dst_s < n) & (rank < k_cap)
    row = jnp.where(ok, dst_s, n)  # OOB rows dropped by the scatter
    col = jnp.where(ok, rank, 0)
    prop_ids = jnp.full((n, k_cap), -1, jnp.int32).at[row, col].set(
        src_s, mode="drop"
    )
    prop_d = jnp.full((n, k_cap), INF).at[row, col].set(dd_s, mode="drop")
    touched = prop_ids[:, 0] >= 0

    cand_ids = jnp.concatenate([adj, prop_ids], axis=1)  # (n, r + K)
    cand_d = jnp.concatenate([adj_d, prop_d], axis=1)
    # dedup (x may already sit in y's row): sort by id, strike repeats
    badc = cand_ids < 0
    idkey = jnp.where(badc, jnp.int32(2**30), cand_ids)
    _, ids_s, d_s = jax.lax.sort(
        (idkey, cand_ids, jnp.where(badc, INF, cand_d)),
        dimension=1, num_keys=1, is_stable=True,
    )
    dup = jnp.concatenate(
        [jnp.zeros((n, 1), bool), ids_s[:, 1:] == ids_s[:, :-1]], axis=1
    )
    ids_s = jnp.where(dup, -1, ids_s)
    d_s = jnp.where(dup, INF, d_s)

    pruned = jax.lax.map(
        lambda a: prune_list(
            backend, *a, r=r,
            alpha=params.bulk_select_alpha(), mode=params.prune_mode,
        ),
        (ids_s, d_s), batch_size=_COMMIT_ROWS,
    )
    new_adj = jnp.where(touched[:, None], pruned.ids, adj)
    new_adj_d = jnp.where(touched[:, None], pruned.dists, adj_d)
    backend = backend.with_updated_edges(
        jnp.arange(n, dtype=jnp.int32), new_adj
    )
    return new_adj, new_adj_d, backend


@functools.partial(jax.jit, static_argnames=("engine", "r"))
def bulk_commit(engine: BuildEngine, adj, adj_d, backend, members,
                pool_ids, pool_d, *, r: int):
    """Commit refined pools through the engine's NS machinery: MRNG
    selection over each pool, forward commit, then the batched reverse
    pass (:func:`bulk_reverse`) — the same occlusion rule as an
    incremental insert, with the serial destination walk replaced by
    grouped reverse proposals (DESIGN.md §12). Selection runs with the
    widened ``bulk_select_alpha()`` slack so the random long-range
    candidates in the pool tail survive occlusion."""
    p = engine.params
    # The random tail is appended unsorted — selection's greedy occlusion
    # walk needs candidates ascending by distance.
    pool_d, pool_ids = jax.lax.sort(
        (jnp.where(pool_ids >= 0, pool_d, INF), pool_ids),
        dimension=1, num_keys=1, is_stable=True,
    )
    if p.select_mode == "heuristic":
        sel = jax.lax.map(
            lambda a: select_neighbors(
                backend, *a, r=r, alpha=p.bulk_select_alpha()
            ),
            (pool_ids, pool_d), batch_size=_COMMIT_ROWS,
        )
    else:
        sel = engine.select(backend, pool_ids, pool_d, r=r)
    mask = jnp.ones(members.shape, bool)
    adj, adj_d, backend = commit_forward(
        adj, adj_d, backend, members, sel.ids, sel.dists, mask
    )
    adj, adj_d, backend = bulk_reverse(
        adj, adj_d, backend, members, sel.ids, sel.dists,
        params=engine.params,
    )
    return adj, adj_d, backend


def bfs_reachable(adj: np.ndarray, entry: int) -> np.ndarray:
    """Host-side BFS over an adjacency table: (n,) bool reachability from
    ``entry`` (the bulk build's connectivity check; vectorized frontier)."""
    n = adj.shape[0]
    seen = np.zeros(n, bool)
    if n == 0:
        return seen
    seen[entry] = True
    frontier = np.asarray([entry])
    while frontier.size:
        nxt = adj[frontier].reshape(-1)
        nxt = np.unique(nxt[nxt >= 0])
        nxt = nxt[~seen[nxt]]
        seen[nxt] = True
        frontier = nxt
    return seen


def repair_reachability(
    data, adj0, adj0_d, adj_up, adj_up_d, backend, levels, entry: int,
    *, params: BuildParams, max_passes: int = 2,
):
    """Make every vertex reachable from ``entry`` on the base layer.

    Randomly-seeded refinement can leave islands (a cluster whose pools
    never sample outside itself); incremental insertion cannot, because
    every vertex is acquired via a beam from the entry. The repair is that
    same machinery: BFS the base layer, re-insert unreachable vertices
    through ``run_insert_schedule`` (safe for re-insertion via the engine's
    self-exclusion and already-present reverse-edge guards), repeat up to
    ``max_passes``. Any pathological leftovers (every reverse edge pruned)
    are force-linked to their nearest reachable vertex.

    Runs in a ``build/repair`` span (attribute ``passes``) whose children
    are each host BFS (``build/repair/bfs``, attribute ``unreachable``),
    each re-insertion pass (``build/repair/reinsert``: ``unreachable``, and
    the schedule length before and after padding, ``schedule`` and
    ``schedule_padded``) and the forced-link loop (``build/repair/graft``).

    Returns (adj0, adj0_d, adj_up, adj_up_d, backend, n_dists, n_hops).
    """
    with obs.span("build/repair") as sp:
        out = _repair_reachability(
            data, adj0, adj0_d, adj_up, adj_up_d, backend, levels, entry,
            params=params, max_passes=max_passes, sp=sp,
        )
        sp.add_cost(out[5], out[6])
    return out


def _bfs_unreachable(adj0, entry: int):
    """Host BFS of the base layer from ``entry``, with the copy of the
    adjacency to the host, in a ``build/repair/bfs`` span: (host adjacency,
    seen mask, unreachable ids)."""
    with obs.span("build/repair/bfs") as sp:
        adj_np = np.asarray(adj0)
        seen = bfs_reachable(adj_np, entry)
        unreach = np.nonzero(~seen)[0].astype(np.int32)
        sp.set(unreachable=int(unreach.size))
    return adj_np, seen, unreach


def _repair_reachability(
    data, adj0, adj0_d, adj_up, adj_up_d, backend, levels, entry: int,
    *, params: BuildParams, max_passes: int, sp,
):
    engine = BuildEngine(params)
    n = int(adj0.shape[0])
    n_d = n_h = 0.0
    sp.set(passes=0)
    for p in range(max_passes):
        _, _, unreach = _bfs_unreachable(adj0, int(entry))
        if unreach.size == 0:
            return adj0, adj0_d, adj_up, adj_up_d, backend, n_d, n_h
        if unreach.size > n // 4:
            break  # mostly islands: beams from the tiny reachable core
            # cannot acquire island-local neighbors — go structural
        with obs.span(
            "build/repair/reinsert", unreachable=int(unreach.size)
        ) as rsp:
            ids, mask = batch_schedule(unreach, params.batch)
            # pad the schedule length to a power of two so repair passes of
            # similar size share one run_insert_schedule compile
            nb = ids.shape[0]
            nb_p = 1 << (nb - 1).bit_length()
            rsp.set(schedule=nb, schedule_padded=nb_p)
            pad = np.zeros((nb_p - nb, params.batch), np.int32)
            ids = np.concatenate([ids, pad])
            mask = np.concatenate([mask, pad.astype(bool)])
            ent = np.full((nb_p,), int(entry), np.int32)
            adj0, adj0_d, adj_up, adj_up_d, backend, acct = run_insert_schedule(
                engine, data, adj0, adj0_d, adj_up, adj_up_d, backend,
                jnp.asarray(levels), jnp.asarray(ids), jnp.asarray(ent),
                jnp.asarray(mask),
            )
            n_d += float(acct.n_dists)
            n_h += float(acct.n_hops)
        sp.set(passes=p + 1)
    adj_np, seen, unreach = _bfs_unreachable(adj0, int(entry))
    if unreach.size:
        with obs.span("build/repair/graft", unreachable=int(unreach.size)):
            adj0, adj0_d, backend, n_d = _graft(
                adj_np.copy(), np.array(adj0_d), backend, seen, unreach,
                int(entry), n_d,
            )
    return adj0, adj0_d, adj_up, adj_up_d, backend, n_d, n_h


def _graft(adj_np, adj_d_np, backend, seen, unreach, entry: int, n_d):
    """Force-link every vertex in ``unreach`` to its nearest vertex already
    ``seen``, editing the host copies ``adj_np``/``adj_d_np`` of the base
    layer. Returns (adj0, adj0_d, backend, n_d), ``n_d`` grown by the
    distances computed."""
    n = adj_np.shape[0]
    all_ids = jnp.arange(n, dtype=jnp.int32)
    # Batched distance rows (unreachable × everyone), tiled at a fixed
    # row-block shape: per-u calls would recompile per shape as the
    # reachable set grows, and one monolithic (U, n) call materializes
    # an (U, n, ·) workspace in the backend — at mostly-island scale
    # (U ≈ n) that is O(n²·d) bytes. Fixed blocks compile once and cap
    # the workspace; padding rows are discarded (values unchanged).
    u_sz = int(unreach.size)
    budget = int(os.environ.get("REPRO_REPAIR_TILE", 1 << 19))
    blk = max(1, min(u_sz, budget // max(1, n)))
    pad = (-u_sz) % blk
    u_pad = np.concatenate([unreach, np.zeros(pad, np.int32)])
    d_all = np.concatenate([
        np.asarray(backend.pair_dists(
            jnp.asarray(u_pad[i:i + blk, None]), all_ids[None, :],
        ))
        for i in range(0, u_sz + pad, blk)
    ])[:u_sz]
    n_d += float(d_all.size)
    row_of = {int(u): i for i, u in enumerate(unreach)}

    def dists_from(v: int) -> np.ndarray:
        i = row_of.get(v)
        if i is not None:
            return d_all[i]
        return np.asarray(backend.pair_dists(
            jnp.full((1, 1), v, jnp.int32), all_ids[None, :],
        ))[0]

    grafted = np.zeros(adj_np.shape, bool)  # graft slots are permanent

    def link(u: int, y: int, d: float) -> bool:
        row = adj_np[y]
        free = np.nonzero(row < 0)[0]
        if free.size:
            slot = int(free[0])
        else:
            evictable = np.nonzero(~grafted[y])[0]
            if evictable.size == 0:
                return False  # row is all grafts — caller picks another y
            # evict the smallest-distance edge: its target sits in the
            # dense local neighborhood with many alternative in-edges
            slot = int(evictable[np.argmin(adj_d_np[y, evictable])])
        adj_np[y, slot] = u
        adj_d_np[y, slot] = d
        grafted[y, slot] = True
        return True

    # Per island (forward-closure component): graft the best border
    # pair (u*, y*) — min distance from any island member to any
    # reachable vertex — then flood the island's closure as seen.
    # Grafts never evict each other (no ping-pong), so every pass
    # makes permanent progress; the outer BFS re-run heals nodes cut
    # loose when a graft evicted their only in-edge.
    for _ in range(64):
        todo = np.nonzero(~seen)[0]
        if todo.size == 0:
            break
        for u in todo:
            while not seen[u]:
                comp = bfs_reachable(adj_np, int(u)) & ~seen
                members = np.nonzero(comp)[0]
                d_sub = np.stack([dists_from(int(v)) for v in members])
                d_sub = np.where(seen[None, :], d_sub, np.inf)
                while True:
                    flat = int(np.argmin(d_sub))
                    ui, y = divmod(flat, n)
                    if link(int(members[ui]), y, float(d_sub[ui, y])):
                        break
                    d_sub[:, y] = np.inf  # row saturated with grafts
                seen |= bfs_reachable(adj_np, int(members[ui]))
        seen = bfs_reachable(adj_np, entry)
    adj0 = jnp.asarray(adj_np)
    adj0_d = jnp.asarray(adj_d_np)
    backend = backend.with_updated_edges(all_ids, adj0)
    return adj0, adj0_d, backend, n_d
