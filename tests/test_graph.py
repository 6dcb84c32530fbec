"""Integration + property tests for the graph-index substrate.

Covers: beam search invariants, neighbor-selection (MRNG rule), HNSW build +
search recall per backend, reverse-edge integrity, Vamana/NSG generality,
segmented build/search parity.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import graph
from repro.graph import segmented as seg
from repro.graph.beam import beam_search
from repro.graph.hnsw import (
    HNSWParams,
    build_hnsw,
    prefix_entries,
    sample_levels,
    search_hnsw,
)
from repro.graph.knn import average_distance_ratio, exact_knn, recall_at_k
from repro.graph.nsg import build_nsg
from repro.graph import engine as eng
from repro.graph.select import Selection, prune_list, select_neighbors
from repro.graph.vamana import build_vamana, search_flat_result
from tests.conftest import make_clustered

PARAMS = HNSWParams(r_upper=8, r_base=16, ef=32, batch=16, max_layers=3)


@pytest.fixture(scope="module")
def truth(small_data):
    data, queries = small_data
    ids, d = exact_knn(queries, data, k=10)
    return ids, d


@pytest.fixture(scope="module")
def fp32_index(small_data):
    data, _ = small_data
    be = graph.make_backend("fp32", data)
    index, stats = build_hnsw(data, be, params=PARAMS)
    return index, stats


@pytest.fixture(scope="module")
def flash_index(small_data, key):
    data, _ = small_data
    be = graph.make_backend(
        "flash", data, key, d_f=32, m_f=16, l_f=4, h=8, kmeans_iters=10
    )
    index, stats = build_hnsw(data, be, params=PARAMS)
    return index, stats


class TestLevels:
    def test_levels_distribution(self):
        lv = sample_levels(0, 100000, r_upper=16, max_layers=6)
        assert lv.min() == 0 and lv.max() <= 5
        # exponential decay: each layer ~1/R_upper of the previous
        frac1 = (lv >= 1).mean()
        assert 0.02 < frac1 < 0.12  # 1/16 ≈ 0.0625

    def test_prefix_entries(self):
        lv = np.array([0, 2, 0, 1, 3, 0, 0, 0], np.int32)
        ent = prefix_entries(lv, 2)
        np.testing.assert_array_equal(ent, [-1, 1, 1, 4])


class TestBeam:
    def test_beam_sorted_and_visits_once(self, small_data):
        data, _ = small_data
        be = graph.make_backend("fp32", data)
        # ring adjacency: node i -> i±1 … a path graph
        n = data.shape[0]
        adj = jnp.stack(
            [jnp.arange(1, n + 1) % n, jnp.arange(-1, n - 1) % n], axis=1
        ).astype(jnp.int32)
        qctx = be.prepare_query(data[5])
        res = beam_search(be, qctx, adj, jnp.asarray([0]), ef=8)
        d = np.asarray(res.dists)
        assert np.all(np.diff(d[np.isfinite(d)]) >= 0)  # ascending
        ids = np.asarray(res.ids)
        valid = ids[ids >= 0]
        assert len(np.unique(valid)) == len(valid)  # no duplicates

    def test_beam_finds_true_nn_on_full_graph(self, small_data):
        """On a graph where the entry connects to everything, beam == brute."""
        data, _ = small_data
        n = data.shape[0]
        be = graph.make_backend("fp32", data[:257])
        adj = jnp.full((257, 256), -1, jnp.int32)
        adj = adj.at[0].set(jnp.arange(1, 257))
        q = data[300]
        res = beam_search(be, be.prepare_query(q), adj, jnp.asarray([0]), ef=8)
        true = np.argsort(np.asarray(jnp.sum((data[:257] - q) ** 2, -1)))[:1]
        assert int(res.ids[0]) == int(true[0])


class TestSelect:
    def test_respects_r(self, small_data):
        data, _ = small_data
        be = graph.make_backend("fp32", data)
        q = data[0]
        d = be.query_dists(be.prepare_query(q), jnp.arange(64))
        order = jnp.argsort(d)
        sel = select_neighbors(be, order.astype(jnp.int32), d[order], r=8)
        assert int(sel.count) <= 8
        assert int(jnp.sum(sel.ids >= 0)) == int(sel.count)

    def test_mrng_rule_holds(self, small_data):
        """For every selected pair (u later than v): δ(u,v) ≥ δ(u,x)."""
        data, _ = small_data
        be = graph.make_backend("fp32", data)
        q = data[0]
        ids = jnp.arange(1, 129, dtype=jnp.int32)
        d = be.query_dists(be.prepare_query(q), ids)
        order = jnp.argsort(d)
        sel = select_neighbors(be, ids[order], d[order], r=16)
        sids = np.asarray(sel.ids)
        sd = np.asarray(sel.dists)
        chosen = sids[sids >= 0]
        cd = sd[sids >= 0]
        for i in range(len(chosen)):
            for j in range(i):
                pd = float(
                    be.pair_dists(jnp.asarray(chosen[i]), jnp.asarray(chosen[j]))
                )
                assert pd >= cd[i] - 1e-5  # no selected u dominates v

    def test_selected_sorted_ascending(self, small_data):
        data, _ = small_data
        be = graph.make_backend("fp32", data)
        d = be.query_dists(be.prepare_query(data[0]), jnp.arange(64))
        order = jnp.argsort(d)
        sel = select_neighbors(be, order.astype(jnp.int32), d[order], r=8)
        sd = np.asarray(sel.dists)
        assert np.all(np.diff(sd[np.isfinite(sd)]) >= 0)


class TestHNSWBuild:
    def test_fp32_recall(self, small_data, fp32_index, truth):
        data, queries = small_data
        index, _ = fp32_index
        res = search_hnsw(index, queries, k=10, ef_search=64, max_layers=3)
        assert recall_at_k(res.ids, truth[0], 10) >= 0.9

    def test_flash_recall_with_rerank(self, small_data, flash_index, truth):
        data, queries = small_data
        index, _ = flash_index
        res = search_hnsw(
            index, queries, k=10, ef_search=128, max_layers=3, rerank_vectors=data
        )
        assert recall_at_k(res.ids, truth[0], 10) >= 0.85

    def test_flash_build_quality_matches_fp32_graph(
        self, small_data, flash_index, fp32_index, truth
    ):
        """Graph built with Flash codes, searched in fp32: recall stays high —
        the paper's core claim (compressed comparisons build a good graph)."""
        data, queries = small_data
        index, _ = flash_index
        fp_be = graph.make_backend("fp32", data)
        mixed = index._replace(backend=fp_be)
        res = search_hnsw(mixed, queries, k=10, ef_search=64, max_layers=3)
        assert recall_at_k(res.ids, truth[0], 10) >= 0.85

    def test_adjacency_wellformed(self, fp32_index, small_data):
        data, _ = small_data
        index, _ = fp32_index
        adj = np.asarray(index.adj0)
        n = data.shape[0]
        assert adj.shape == (n, PARAMS.r_base)
        assert adj.min() >= -1 and adj.max() < n
        # no self loops
        self_loop = adj == np.arange(n)[:, None]
        assert not self_loop.any()
        # mean degree is healthy (connected-ish graph)
        deg = (adj >= 0).sum(1)
        assert deg.mean() > 4

    def test_no_duplicate_neighbors(self, fp32_index):
        index, _ = fp32_index
        adj = np.asarray(index.adj0)
        for row in adj[:200]:
            v = row[row >= 0]
            assert len(np.unique(v)) == len(v)

    def test_upper_layers_sparse(self, fp32_index, small_data):
        data, _ = small_data
        index, _ = fp32_index
        lv = np.asarray(index.levels)
        up = np.asarray(index.adj_up[0])
        # only vertices with level >= 1 may have layer-1 edges
        has_edges = (up >= 0).any(1)
        assert not has_edges[lv < 1].any()

    def test_build_stats_positive(self, fp32_index):
        _, stats = fp32_index
        assert float(stats.n_dists) > 0 and float(stats.n_hops) > 0

    def test_adr_close_to_one(self, small_data, flash_index, truth):
        data, queries = small_data
        index, _ = flash_index
        res = search_hnsw(
            index, queries, k=10, ef_search=128, max_layers=3, rerank_vectors=data
        )
        adr = average_distance_ratio(res.dists, truth[1], 10)
        assert adr < 1.15


class TestBackendsBuild:
    @pytest.mark.parametrize(
        "kind,kw,min_recall",
        [
            ("sq", dict(bits=8), 0.85),
            ("pca", dict(alpha=0.9), 0.6),
            ("pq", dict(m=8, l_pq=6, kmeans_iters=6), 0.5),
        ],
    )
    def test_backend_recall(self, small_data, key, truth, kind, kw, min_recall):
        data, queries = small_data
        be = graph.make_backend(kind, data, key, **kw)
        index, _ = build_hnsw(data, be, params=PARAMS)
        res = search_hnsw(
            index, queries, k=10, ef_search=96, max_layers=3, rerank_vectors=data
        )
        assert recall_at_k(res.ids, truth[0], 10) >= min_recall

    def test_flash_blocked_equals_flash(self, small_data, key, truth):
        """The access-aware layout changes memory traffic, not results."""
        data, queries = small_data
        be_b = graph.make_backend(
            "flash_blocked", data, key, d_f=32, m_f=16, l_f=4, h=8,
            kmeans_iters=10, r_for_blocked=PARAMS.r_base,
        )
        index_b, _ = build_hnsw(data, be_b, params=PARAMS)
        be_f = graph.FlashBackend(be_b.coder, be_b.codes)
        index_f, _ = build_hnsw(data, be_f, params=PARAMS)
        np.testing.assert_array_equal(
            np.asarray(index_b.adj0), np.asarray(index_f.adj0)
        )
        # and the (4-bit packed) mirror is consistent with the adjacency
        from repro.core import unpack_codes

        adj = np.asarray(index_b.adj0)
        m_f = index_b.backend.coder.m_f
        nbrc = np.asarray(unpack_codes(index_b.backend.nbr_codes, m_f))
        codes = np.asarray(index_b.backend.codes)
        for v in range(0, 200, 17):
            for slot, u in enumerate(adj[v]):
                if u >= 0:
                    np.testing.assert_array_equal(nbrc[v, slot], codes[u])


class TestGenerality:
    def test_vamana_fp32(self, small_data, truth):
        data, queries = small_data
        be = graph.make_backend("fp32", data)
        idx, _ = build_vamana(data, be, params=HNSWParams(
            r_upper=8, r_base=24, ef=96, batch=16, alpha=1.2))
        res = search_flat_result(idx, queries, k=10, ef_search=96)
        assert recall_at_k(res.ids, truth[0], 10) >= 0.9

    def test_vamana_flash(self, small_data, key, truth):
        data, queries = small_data
        be = graph.make_backend("flash", data, key, d_f=32, m_f=16, kmeans_iters=10)
        idx, _ = build_vamana(data, be, params=HNSWParams(
            r_upper=8, r_base=24, ef=96, batch=16, alpha=1.2))
        res = search_flat_result(idx, queries, k=10, ef_search=128, rerank_vectors=data)
        assert recall_at_k(res.ids, truth[0], 10) >= 0.9

    def test_nsg_flash(self, small_data, key, truth):
        data, queries = small_data
        be = graph.make_backend("flash", data, key, d_f=32, m_f=16, kmeans_iters=10)
        (idx, _knn) = build_nsg(
            data, be, params=HNSWParams(r_base=24, ef=96, batch=16), knn_k=24
        )
        res = search_flat_result(idx, queries, k=10, ef_search=128, rerank_vectors=data)
        assert recall_at_k(res.ids, truth[0], 10) >= 0.8


class TestSegmented:
    def test_build_and_merge(self, small_data, key, truth):
        data, queries = small_data
        S, ns = 4, 500
        segs = data[: S * ns].reshape(S, ns, -1)
        coder = seg.fit_shared_coder(key, data, d_f=32, m_f=16, kmeans_iters=10)
        levels = np.stack(
            [sample_levels(s, ns, r_upper=8, max_layers=3) for s in range(S)]
        )
        entries = np.stack([prefix_entries(levels[s], 16) for s in range(S)])
        built = seg.build_segments_vmapped(
            segs, coder, jnp.asarray(levels), jnp.asarray(entries), params=PARAMS
        )
        gids, gd = seg.search_segments_local(
            built, queries, np.full(S, ns), k=10, ef_search=64, max_layers=3,
            seg_vectors=segs,
        )
        assert recall_at_k(gids, truth[0], 10) >= 0.9

    def test_shard_map_matches_vmap(self, small_data, key):
        """shard_map deployment ≡ vmap reference on a 1-device mesh."""
        data, _ = small_data
        S, ns = 2, 500
        segs = data[: S * ns].reshape(S, ns, -1)
        coder = seg.fit_shared_coder(key, data, d_f=16, m_f=8, kmeans_iters=6)
        levels = np.stack(
            [sample_levels(s, ns, r_upper=8, max_layers=3) for s in range(S)]
        )
        entries = np.stack([prefix_entries(levels[s], 16) for s in range(S)])
        ref = seg.build_segments_vmapped(
            segs, coder, jnp.asarray(levels), jnp.asarray(entries), params=PARAMS
        )
        mesh = jax.make_mesh((1,), ("data",))
        f = seg.make_segmented_build_fn(mesh, params=PARAMS, seg_axes=("data",))
        got = f(segs, coder, jnp.asarray(levels), jnp.asarray(entries))
        np.testing.assert_array_equal(
            np.asarray(got.adj0), np.asarray(ref.index.adj0)
        )


# ---------------------------------------------------------------------------
# The build's sorts carry their ids and distances. The oracles below are the
# formulation they replaced: a sort or ``top_k`` computes a permutation, and
# indexing applies it.
# ---------------------------------------------------------------------------

INF = jnp.float32(jnp.inf)


def _old_select_neighbors(backend, cand_ids, cand_dists, *, r, alpha=1.0):
    c = cand_ids.shape[0]
    valid = cand_ids >= 0
    pair = backend.pair_table(jnp.where(valid, cand_ids, 0))
    pair = jnp.where(valid[:, None] & valid[None, :], pair, INF)

    def step(carry, i):
        sel_mask, count = carry
        conflict = jnp.any(sel_mask & (alpha * pair[i] < cand_dists[i]))
        ok = valid[i] & ~conflict & (count < r)
        return (sel_mask.at[i].set(ok), count + ok.astype(jnp.int32)), ok

    (sel_mask, count), _ = jax.lax.scan(
        step, (jnp.zeros((c,), bool), jnp.int32(0)), jnp.arange(c)
    )
    key = jnp.where(sel_mask, cand_dists, INF)
    kk = min(r, c)
    _, idx = jax.lax.top_k(-key, kk)
    ids = jnp.where(sel_mask[idx], cand_ids[idx], -1)
    dists = jnp.where(sel_mask[idx], cand_dists[idx], INF)
    ids = jnp.concatenate([ids, jnp.full((r - kk,), -1, ids.dtype)])
    dists = jnp.concatenate([dists, jnp.full((r - kk,), INF)])
    return Selection(ids=ids, dists=dists, count=count)


def _old_prune_list(backend, cand_ids, cand_dists, *, r, alpha=1.0,
                    mode="heuristic"):
    d = jnp.where(cand_ids >= 0, cand_dists, INF)
    order = jnp.argsort(d)
    ids_s, d_s = cand_ids[order], d[order]
    if mode == "farthest":
        ids = jnp.where(jnp.isfinite(d_s[:r]), ids_s[:r], -1)
        return Selection(
            ids=ids, dists=d_s[:r], count=jnp.sum((ids >= 0).astype(jnp.int32))
        )
    return _old_select_neighbors(backend, ids_s, d_s, r=r, alpha=alpha)


def _old_drop_self(cand_ids, cand_d, new_ids):
    self_hit = cand_ids == new_ids[:, None]
    d = jnp.where(self_hit, INF, cand_d)
    ids = jnp.where(self_hit, -1, cand_ids)
    order = jnp.argsort(d, axis=1)
    return (
        jnp.take_along_axis(ids, order, axis=1),
        jnp.take_along_axis(d, order, axis=1),
    )


def _old_bootstrap(engine, data, adj0, adj0_d, adj_up, adj_up_d, backend,
                   levels):
    params = engine.params
    p = min(params.batch, data.shape[0])
    cand_pool = jnp.arange(p, dtype=jnp.int32)

    def body(i, carry):
        adj0, adj0_d, adj_up, adj_up_d, backend = carry
        d_all = backend.query_dists(backend.prepare_query(data[i]), cand_pool)
        for l in range(params.max_layers - 1, -1, -1):
            r_l = params.r_base if l == 0 else params.r_upper
            elig = (cand_pool < i) & (levels[:p] >= l) & (levels[i] >= l)
            d = jnp.where(elig, d_all, INF)
            order = jnp.argsort(d)
            ids_s = jnp.where(jnp.isfinite(d[order]), cand_pool[order], -1)
            sel = engine.select_one(backend, ids_s, d[order], r=r_l)
            new_ids = jnp.full((1,), i, jnp.int32)
            m1 = jnp.array([levels[i] >= l])
            a, ad = (adj0, adj0_d) if l == 0 else (adj_up[l - 1], adj_up_d[l - 1])
            a, ad, backend = engine.commit_forward(
                a, ad, backend, new_ids, sel.ids[None], sel.dists[None], m1
            )
            a, ad, backend = engine.reverse_pass(
                a, ad, backend, new_ids, sel.ids[None], sel.dists[None], m1
            )
            if l == 0:
                adj0, adj0_d = a, ad
            else:
                adj_up = adj_up.at[l - 1].set(a)
                adj_up_d = adj_up_d.at[l - 1].set(ad)
        return adj0, adj0_d, adj_up, adj_up_d, backend

    return jax.lax.fori_loop(
        0, p, body, (adj0, adj0_d, adj_up, adj_up_d, backend)
    )


def _old_bulk_reverse(adj, adj_d, backend, members, sel_ids, sel_d, *,
                      params):
    m, r = sel_ids.shape
    n = adj.shape[0]
    k_cap = 2 * r
    src = jnp.repeat(members, r)
    dst = sel_ids.reshape(-1)
    dd = sel_d.reshape(-1)
    dstk = jnp.where(dst >= 0, dst, n)
    o1 = jnp.argsort(dd, stable=True)
    o2 = jnp.argsort(dstk[o1], stable=True)
    o = o1[o2]
    dst_s, src_s, dd_s = dstk[o], src[o], dd[o]
    idx = jnp.arange(m * r)
    first = jnp.concatenate([jnp.ones((1,), bool), dst_s[1:] != dst_s[:-1]])
    rank = idx - jax.lax.cummax(jnp.where(first, idx, 0))
    ok = (dst_s < n) & (rank < k_cap)
    row = jnp.where(ok, dst_s, n)
    col = jnp.where(ok, rank, 0)
    prop_ids = jnp.full((n, k_cap), -1, jnp.int32).at[row, col].set(
        src_s, mode="drop"
    )
    prop_d = jnp.full((n, k_cap), INF).at[row, col].set(dd_s, mode="drop")
    touched = prop_ids[:, 0] >= 0
    cand_ids = jnp.concatenate([adj, prop_ids], axis=1)
    cand_d = jnp.concatenate([adj_d, prop_d], axis=1)
    badc = cand_ids < 0
    idkey = jnp.where(badc, jnp.int32(2**30), cand_ids)
    order = jnp.argsort(idkey, axis=1, stable=True)
    ids_s = jnp.take_along_axis(cand_ids, order, axis=1)
    d_s = jnp.take_along_axis(jnp.where(badc, INF, cand_d), order, axis=1)
    dup = jnp.concatenate(
        [jnp.zeros((n, 1), bool), ids_s[:, 1:] == ids_s[:, :-1]], axis=1
    )
    ids_s = jnp.where(dup, -1, ids_s)
    d_s = jnp.where(dup, INF, d_s)
    pruned = jax.lax.map(
        lambda a: _old_prune_list(
            backend, *a, r=r, alpha=params.bulk_select_alpha(),
            mode=params.prune_mode,
        ),
        (ids_s, d_s), batch_size=eng._COMMIT_ROWS,
    )
    new_adj = jnp.where(touched[:, None], pruned.ids, adj)
    new_adj_d = jnp.where(touched[:, None], pruned.dists, adj_d)
    backend = backend.with_updated_edges(jnp.arange(n, dtype=jnp.int32), new_adj)
    return new_adj, new_adj_d, backend


def _old_bulk_commit(engine, adj, adj_d, backend, members, pool_ids, pool_d,
                     *, r):
    p = engine.params
    pool_d = jnp.where(pool_ids >= 0, pool_d, INF)
    order = jnp.argsort(pool_d, axis=1)
    pool_ids = jnp.take_along_axis(pool_ids, order, axis=1)
    pool_d = jnp.take_along_axis(pool_d, order, axis=1)
    sel = jax.lax.map(
        lambda a: _old_select_neighbors(
            backend, *a, r=r, alpha=p.bulk_select_alpha()
        ),
        (pool_ids, pool_d), batch_size=eng._COMMIT_ROWS,
    )
    mask = jnp.ones(members.shape, bool)
    adj, adj_d, backend = eng.commit_forward(
        adj, adj_d, backend, members, sel.ids, sel.dists, mask
    )
    return _old_bulk_reverse(
        adj, adj_d, backend, members, sel.ids, sel.dists, params=p
    )


SORT_N, SORT_R = 480, 8
SORT_PARAMS = HNSWParams(r_upper=4, r_base=SORT_R, ef=16, batch=24, max_layers=3)


@pytest.fixture(scope="module")
def tie_backend(key):
    """flash_blocked over 4 subspaces: integer distances, many of them tied."""
    data = jnp.asarray(make_clustered(SORT_N, 16, n_clusters=6, seed=3))
    be = graph.make_backend(
        "flash_blocked", data, key, d_f=16, m_f=4, l_f=4, h=8,
        kmeans_iters=4, r_for_blocked=SORT_R,
    )
    return data, be


def _candidates(be, data, rng, rows, c, *, sort):
    """Candidate rows drawn with repeats (exact ties), −1 holes at +inf."""
    ids = rng.integers(0, SORT_N, size=(rows, c)).astype(np.int32)
    ids[rng.random((rows, c)) < 0.2] = -1
    q = rng.integers(0, SORT_N, size=rows)
    d = np.asarray(jax.vmap(
        lambda qi, ci: be.query_dists(be.prepare_query(data[qi]), ci)
    )(jnp.asarray(q), jnp.asarray(np.maximum(ids, 0))))
    d = np.where(ids >= 0, d, np.inf).astype(np.float32)
    if sort:
        o = np.argsort(d, axis=1, kind="stable")
        ids, d = np.take_along_axis(ids, o, 1), np.take_along_axis(d, o, 1)
    return jnp.asarray(ids), jnp.asarray(d), q


def _case_select_neighbors(be, data, rng):
    out = []
    for c, r in [(24, 8), (12, 16)]:  # r > c: the padded tail
        ids, d, _ = _candidates(be, data, rng, 64, c, sort=True)
        for f in (select_neighbors, _old_select_neighbors):
            out.append(jax.vmap(lambda i, x: f(be, i, x, r=r, alpha=1.2))(ids, d))
    return out[0::2], out[1::2]


def _case_prune_list(be, data, rng):
    ids, d, _ = _candidates(be, data, rng, 64, 17, sort=False)
    new, old = [], []
    for mode in ("heuristic", "farthest"):
        for f, acc in ((prune_list, new), (_old_prune_list, old)):
            acc.append(jax.vmap(
                lambda i, x: f(be, i, x, r=SORT_R, alpha=1.0, mode=mode)
            )(ids, d))
    return new, old


def _case_drop_self(be, data, rng):
    ids, d, q = _candidates(be, data, rng, 64, 24, sort=True)
    new_ids = jnp.asarray(q, jnp.int32)
    ids = ids.at[:, 3].set(new_ids).at[:, 7].set(new_ids)  # self hits
    return eng._drop_self(ids, d, new_ids), _old_drop_self(ids, d, new_ids)


def _graph_state(params, n):
    l_up = params.max_layers - 1
    return (
        jnp.full((n, params.r_base), -1, jnp.int32),
        jnp.full((n, params.r_base), INF),
        jnp.full((l_up, n, params.r_upper), -1, jnp.int32),
        jnp.full((l_up, n, params.r_upper), INF),
    )


def _case_bootstrap(be, data, rng):
    engine = eng.BuildEngine(SORT_PARAMS)
    levels = jnp.asarray(
        sample_levels(5, SORT_N, r_upper=4, max_layers=3)
    )
    state = _graph_state(SORT_PARAMS, SORT_N)
    new = jax.jit(lambda *a: engine.bootstrap(*a)[:5])(data, *state, be, levels)
    old = jax.jit(lambda *a: _old_bootstrap(engine, *a))(data, *state, be, levels)
    return new, old


def _reverse_inputs(be, rng, *, hot):
    """Forward lists of 320 members; ``hot`` of them draw from 12 destinations
    (groups longer than 2R, cut among tied distances), and each member's
    destination row already lists it at a random slot (the dedup)."""
    m, n, r = 320, SORT_N, SORT_R
    members = np.sort(rng.choice(n, m, replace=False)).astype(np.int32)
    sel = np.stack([rng.choice(n, r, replace=False) for _ in range(m)])
    for i in range(hot):
        sel[i] = rng.choice(12, r, replace=False)
    sel[rng.random((m, r)) < 0.15] = -1
    sel_d = np.where(sel >= 0, rng.integers(0, 4, (m, r)), np.inf)
    adj = np.full((n, r), -1, np.int32)
    adj_d = np.full((n, r), np.inf, np.float32)
    for x, row in zip(members[::3], sel[::3]):
        y = row[row >= 0][:1]
        if y.size:
            slot = rng.integers(0, r)
            adj[y[0], slot], adj_d[y[0], slot] = x, rng.integers(0, 4)
    return (jnp.asarray(adj), jnp.asarray(adj_d), be, jnp.asarray(members),
            jnp.asarray(sel, jnp.int32), jnp.asarray(sel_d, jnp.float32))


def _case_bulk_reverse_grouping(be, data, rng):
    args = _reverse_inputs(be, rng, hot=160)
    new = eng.bulk_reverse(*args, params=SORT_PARAMS)
    old = jax.jit(
        lambda *a: _old_bulk_reverse(*a, params=SORT_PARAMS)
    )(*args)
    return new, old


def _case_bulk_reverse_dedup(be, data, rng):
    args = _reverse_inputs(be, rng, hot=0)
    new = eng.bulk_reverse(*args, params=SORT_PARAMS)
    old = jax.jit(
        lambda *a: _old_bulk_reverse(*a, params=SORT_PARAMS)
    )(*args)
    return new, old


def _case_f32_sort_key(be, data, rng):
    """The grouping's integer key orders float32 as the float sort does:
    −0 tied with +0, subnormals, negatives, ±inf, NaN last."""
    x = np.float32([0.0, -0.0, 1e-45, -1e-45, 1.0, -1.5, 2.0, np.inf, -np.inf,
                    np.nan, -np.nan, 3.4e38, -3.4e38])
    x = jnp.asarray(x[rng.integers(0, x.size, 512)])
    ids = jnp.arange(x.size, dtype=jnp.int32)
    _, new_ids, new_x = jax.lax.sort(
        (eng._f32_sort_key(x), ids, x), num_keys=1, is_stable=True
    )
    order = jnp.argsort(x, stable=True)
    return (new_ids, new_x), (ids[order], x[order])


def _commit_both(be, members, pool_ids, pool_d, r):
    engine = eng.BuildEngine(SORT_PARAMS)
    adj = jnp.full((SORT_N, r), -1, jnp.int32)
    adj_d = jnp.full((SORT_N, r), INF)
    args = (adj, adj_d, be, members, pool_ids, pool_d)
    new = eng.bulk_commit(engine, *args, r=r)
    old = jax.jit(
        lambda *a: _old_bulk_commit(engine, *a, r=r)
    )(*args)
    return new, old


def _case_bulk_commit_presort(be, data, rng):
    """An unsorted pool over every fourth vertex (an upper layer's commit)."""
    members = jnp.arange(0, SORT_N, 4, dtype=jnp.int32)
    ids, d, _ = _candidates(be, data, rng, members.shape[0], 20, sort=False)
    return _commit_both(be, members, ids, d, SORT_PARAMS.r_upper)


def _case_bulk_commit_build(be, data, rng):
    """Layer 0 of a bulk build: refined pools, then the commit, mirror too."""
    members = np.arange(SORT_N, dtype=np.int32)
    pool_ids, pool_d, *_ = eng.bulk_refine(
        data, be, members, r=SORT_R, params=SORT_PARAMS, seed=7
    )
    return _commit_both(be, jnp.asarray(members), pool_ids, pool_d, SORT_R)


SORT_CASES = {
    "select_neighbors": _case_select_neighbors,
    "prune_list": _case_prune_list,
    "drop_self": _case_drop_self,
    "bootstrap": _case_bootstrap,
    "bulk_reverse_grouping": _case_bulk_reverse_grouping,
    "bulk_reverse_dedup": _case_bulk_reverse_dedup,
    "f32_sort_key": _case_f32_sort_key,
    "bulk_commit_presort": _case_bulk_commit_presort,
    "bulk_commit_build": _case_bulk_commit_build,
}


def _bits(x):
    """Floats by their bits: −0 is not +0, and NaN equals itself."""
    x = np.asarray(x)
    return x.view(np.int32) if x.dtype == np.float32 else x


@pytest.mark.parametrize("site", list(SORT_CASES))
def test_payload_sort_bit_exact(tie_backend, site):
    """Each sort that carries ids and distances gives, bit for bit, what the
    argsort / top_k and gathers it replaced gave — ties, −1 ids and +inf
    padding included; for the commit, adjacency, distances and mirror."""
    data, be = tie_backend
    new, old = SORT_CASES[site](be, data, np.random.default_rng(11))
    leaves_new = jax.tree.leaves(new)
    leaves_old = jax.tree.leaves(old)
    assert len(leaves_new) == len(leaves_old) > 0
    for a, b in zip(leaves_new, leaves_old):
        np.testing.assert_array_equal(_bits(a), _bits(b))
