"""Candidate Acquisition: fixed-shape multi-expansion beam search (§2.2, line 5).

This is HNSW's ``SEARCH-LAYER`` written against XLA's static-shape rules:

  * the candidate set C(x) is a fixed-width beam of ``ef`` slots kept sorted
    ascending by distance (pad: id = −1, dist = +inf),
  * the visited set is a dense (n,) bool bitmap (marked at evaluation time, so
    a vertex's distance is computed exactly once),
  * the loop is a ``lax.while_loop``: expand the ``width`` best unexpanded beam
    entries, gather + score their ``width·R`` candidate block in ONE call —
    the fused ``backend.expand()`` kernel step when the backend advertises it
    (DESIGN.md §10: in-kernel gather of adjacency + packed code rows, MXU
    one-hot ADT contraction), else the gather + ``neighbor_dists_batch``
    fallback, bit-exact either way — and merge by top-ef once per iteration.

``width`` is the TPU restatement of the paper's "maximize SIMD utilization"
claim: the per-iteration distance stage sees a dense (W·R,) code block instead
of a ≤R sliver, so the Flash blocked kernel (kernels.ops.flash_scan_batch)
amortizes its HBM→VMEM DMA and VPU lookup over W rows. ``width=1`` is
bit-exact with the classic single-expansion beam (asserted in
tests/test_engine.py) — same expansion order, same merge ties, same counters.

Stopping rule: stop when the best unexpanded candidate is farther than the
current worst beam member (T in the paper's Example 1) — the classic HNSW
termination — with a hard ``max_iters`` cap for jit safety. With width > 1 the
trailing picks of an iteration may lie beyond T; expanding them is the classic
beam-width trade (a few extra distance evaluations for W× fewer, denser loop
iterations).

Batched insertion vmaps this over P queries; the backend is shared state.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro import obs

INF = jnp.float32(jnp.inf)


class BeamResult(NamedTuple):
    ids: jax.Array  # (ef,) int32, −1 padded, ascending by dist
    dists: jax.Array  # (ef,) f32, +inf padded
    n_hops: jax.Array  # () int32 — expanded-vertex count (cost accounting)
    n_dists: jax.Array  # () int32 — distance evaluations (cost accounting)


class DescentResult(NamedTuple):
    node: jax.Array  # () int32 — closest vertex reached
    dist: jax.Array  # () f32
    n_dists: jax.Array  # () int32 — distance evaluations (cost accounting)


def _merge(ids_a, d_a, exp_a, ids_b, d_b, exp_b, ef):
    """Merge two candidate lists, keep ef smallest (ties broken by index).

    A single masked top-k: one ``top_k`` over the negated concatenated
    distances (masked slots ride in as +inf and sink), whose *returned
    values* are the merged distances — the former implementation re-gathered
    the distances through the index vector, paying a redundant (ef+W·R)→ef
    gather every beam iteration. Bit-identical (asserted in
    tests/test_expand.py): ``-(-d) == d`` exactly for every finite float and
    +inf, and ``top_k`` breaks ties by lowest index, the same order a stable
    ascending sort yields.

    (A variadic stable ``lax.sort`` carrying (d, ids, exp) was measured
    ~5× slower than the ``top_k`` custom call on XLA CPU — see DESIGN.md
    §10 — so the masked top-k formulation wins on both op count and
    backend-specific lowering.)
    """
    d = jnp.concatenate([d_a, d_b])
    ids = jnp.concatenate([ids_a, ids_b])
    exp = jnp.concatenate([exp_a, exp_b])
    neg_d, idx = jax.lax.top_k(-d, ef)
    return ids[idx], -neg_d, exp[idx]


def uses_fused_expand(backend, r: int) -> bool:
    """The static decision ``beam_search`` makes at trace time: does this
    backend serve the fused single-kernel expansion step (DESIGN.md §10)
    for adjacency rows of width ``r``?

    Single source of truth for dispatch — benchmarks and the CI capability
    guard assert against this instead of re-deriving the rule."""
    return bool(getattr(backend, "supports_expand", lambda _r: False)(r))


def beam_search(
    backend,
    qctx,
    adjacency: jax.Array,
    entry_ids: jax.Array,
    *,
    ef: int,
    width: int = 1,
    max_iters: int | None = None,
    visited0: jax.Array | None = None,
    banned: jax.Array | None = None,
    fused: bool | None = None,
    n_keep: int | None = None,
) -> BeamResult:
    """Greedy multi-expansion beam search over one adjacency (one layer).

    backend    distance backend (see graph.backends).
    qctx       backend.prepare_query(q) output.
    adjacency  (n, R) int32, −1 = empty slot.
    entry_ids  (E,) int32 entry points (−1 padded).
    ef         beam width (C in the paper during construction).
    width      W — vertices expanded per iteration (1 = classic beam).
    max_iters  iteration cap; defaults to ⌈(4·ef+8)/W⌉ so the total
               expansion budget is width-independent.
    banned     optional (n,) bool tombstone mask (DESIGN.md §8): banned
               vertices participate in traversal exactly as before (they are
               expanded, their adjacency rows are followed, their distances
               are evaluated and counted) but are struck from the returned
               beam — deleted vertices stay navigable without ever being
               results.
    fused      fused-expansion dispatch (DESIGN.md §10). None (default):
               use ``backend.expand()`` iff the backend advertises the
               capability for this adjacency width (:func:`uses_fused_expand`).
               False: force the gather+scan fallback (parity tests).
               True: require the fused path — raises for backends without
               the capability hook instead of silently degrading.
    n_keep     how many beam slots to return (DESIGN.md §11): the search
               pipeline's candidate superset is the best ``n_keep =
               min(ef, k·rerank_mult)`` scan candidates; the beam itself
               always runs at full ``ef``. None (default) returns the whole
               beam.
    """
    n, r = adjacency.shape
    e = entry_ids.shape[0]
    if e > ef:
        raise ValueError(f"entries ({e}) must fit the beam (ef={ef})")
    if width < 1:
        raise ValueError(f"width must be >= 1, got {width}")
    keep = ef if n_keep is None else min(max(int(n_keep), 1), ef)
    w = min(width, ef)
    max_iters = max_iters if max_iters is not None else -(-(4 * ef + 8) // w)
    use_fused = uses_fused_expand(backend, r) if fused is None else fused
    if use_fused and not uses_fused_expand(backend, r):
        raise ValueError(
            f"fused=True but {type(backend).__name__} does not support the "
            f"fused expand() path for adjacency width R={r}"
        )
    # Trace-time dispatch counter (this Python body runs once per compile).
    obs.tick("beam_dispatch_total", route="fused" if use_fused else "gather")

    valid_e = entry_ids >= 0
    safe_e = jnp.where(valid_e, entry_ids, 0)
    d_e = jnp.where(valid_e, backend.query_dists(qctx, safe_e), INF)
    visited = jnp.zeros((n,), bool) if visited0 is None else visited0
    visited = visited.at[safe_e].max(valid_e)

    pad = ef - e
    beam_ids = jnp.concatenate([entry_ids, jnp.full((pad,), -1, jnp.int32)])
    beam_d = jnp.concatenate([d_e, jnp.full((pad,), INF)])
    beam_exp = jnp.concatenate(
        [~valid_e, jnp.ones((pad,), bool)]
    )  # padding counts as expanded
    # keep sorted ascending
    order = jnp.argsort(beam_d)
    beam_ids, beam_d, beam_exp = beam_ids[order], beam_d[order], beam_exp[order]

    def cond(state):
        beam_ids, beam_d, beam_exp, visited, it, nd, nh = state
        best_unexp = jnp.min(jnp.where(beam_exp, INF, beam_d))
        worst = beam_d[ef - 1]
        return (best_unexp <= worst) & (best_unexp < INF) & (it < max_iters)

    def body(state):
        beam_ids, beam_d, beam_exp, visited, it, nd, nh = state
        # W best unexpanded beam entries (top_k is stable: lowest index on
        # ties, so W=1 picks exactly argmin — the classic expansion order).
        key = jnp.where(beam_exp, INF, beam_d)
        _, bi = jax.lax.top_k(-key, w)  # (W,) distinct beam positions
        sel_ok = key[bi] < INF  # un-expandable picks are pads/expanded
        beam_exp = beam_exp.at[bi].set(True)
        nodes = jnp.where(sel_ok, beam_ids[bi], -1)  # (W,)
        if use_fused:
            # One fused kernel: in-kernel adjacency + packed-code-row gather
            # (scalar-prefetched frontier ids) and the packed ADT
            # lookup — the per-iteration HBM round trip for the
            # (W·R, M) code block disappears (DESIGN.md §10).
            rows, d_block = backend.expand(qctx, nodes, adjacency)  # (W, R) ×2
        else:
            rows = adjacency[jnp.maximum(nodes, 0)]  # (W, R)
            # One dense (W, R) distance block — the whole point of width > 1.
            # (the blocked backend reads its mirror by ``nodes``; ``safe``
            # below is only the gather-path id clamp, so scoring first on
            # the raw rows is equivalent — ids are re-masked after)
            d_block = backend.neighbor_dists_batch(
                qctx, nodes, jnp.maximum(rows, 0)
            )
        pre_ok = (rows >= 0) & (nodes >= 0)[:, None]
        safe = jnp.where(pre_ok, rows, 0)  # (W, R)
        ok = pre_ok
        if w == 1:
            ok &= ~visited[safe]
            visited = visited.at[safe].max(ok)
        else:
            # Visited-check + mark one row at a time: row i sees the bitmap
            # already marked by rows < i, so a neighbor shared by two
            # expanded vertices survives only in its first row — the classic
            # "marked at evaluation time" dedup, w tiny scatter/gather pairs
            # instead of a sort or an (n,) scratch buffer in the hot loop.
            # (A closed-form (W·R)² first-occurrence mask was measured ~2×
            # slower than this loop on XLA CPU — see DESIGN.md §10.)
            def mark(i, carry):
                visited, okc = carry
                row_ok = okc[i] & ~visited[safe[i]]
                visited = visited.at[safe[i]].max(row_ok)
                okc = okc.at[i].set(row_ok)
                return visited, okc

            visited, ok = jax.lax.fori_loop(0, w, mark, (visited, ok))
        flat = safe.reshape(w * r)
        flat_ok = ok.reshape(w * r)
        d_new = jnp.where(flat_ok, d_block.reshape(w * r), INF)
        ids_new = jnp.where(flat_ok, flat, -1)
        beam_ids, beam_d, beam_exp = _merge(
            beam_ids, beam_d, beam_exp, ids_new, d_new, ~flat_ok, ef
        )
        return (
            beam_ids, beam_d, beam_exp, visited, it + 1,
            nd + jnp.sum(flat_ok), nh + jnp.sum(sel_ok),
        )

    state = (
        beam_ids, beam_d, beam_exp, visited,
        jnp.int32(0), jnp.sum(valid_e), jnp.int32(0),
    )
    beam_ids, beam_d, beam_exp, visited, it, nd, nh = jax.lax.while_loop(
        cond, body, state
    )
    del visited, beam_exp, it
    if banned is not None:
        # Strike tombstoned vertices from the results (traversal above was
        # oblivious to the mask, so counters and expansion order are the
        # same as an unmasked search).
        dead = (beam_ids >= 0) & banned[jnp.maximum(beam_ids, 0)]
        beam_d = jnp.where(dead, INF, beam_d)
        beam_ids = jnp.where(dead, -1, beam_ids)
        order = jnp.argsort(beam_d)
        beam_ids, beam_d = beam_ids[order], beam_d[order]
    return BeamResult(
        ids=beam_ids[:keep], dists=beam_d[:keep], n_hops=nh, n_dists=nd
    )


def greedy_descent(
    backend, qctx, adjacency: jax.Array, entry_id: jax.Array, *, max_iters: int = 64
) -> DescentResult:
    """ef=1 greedy walk (upper-layer descent).

    Matches HNSW's inter-layer hop: repeatedly move to the closest neighbor
    while it improves; a beam of 1 without a visited set. Distance
    evaluations are counted (``n_dists``) so callers can fold the descent
    cost into their accounting — previously these were silently dropped.

    Tombstones (DESIGN.md §8) need no mask here: the descent's output only
    seeds the next layer's search and is never user-visible, and tombstoned
    vertices are by design fully traversable — result filtering happens in
    :func:`beam_search` via ``banned``.
    """

    def cond(state):
        node, d, moved, it, nd = state
        return moved & (it < max_iters)

    def body(state):
        node, d, _, it, nd = state
        nbrs = adjacency[jnp.maximum(node, 0)]
        ok = (nbrs >= 0) & (node >= 0)
        safe = jnp.where(ok, nbrs, 0)
        d_n = jnp.where(ok, backend.query_dists(qctx, safe), INF)
        j = jnp.argmin(d_n)
        better = d_n[j] < d
        node2 = jnp.where(better, safe[j], node)
        d2 = jnp.where(better, d_n[j], d)
        return node2, d2, better, it + 1, nd + jnp.sum(ok)

    valid = entry_id >= 0
    d0 = jnp.where(
        valid, backend.query_dists(qctx, jnp.maximum(entry_id, 0)[None])[0], INF
    )
    node, d, _, _, nd = jax.lax.while_loop(
        cond, body, (entry_id, d0, valid, jnp.int32(0), valid.astype(jnp.int32))
    )
    return DescentResult(node=node, dist=d, n_dists=nd)
