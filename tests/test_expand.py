"""Fused beam-expansion path (DESIGN.md §10).

Contracts:
  1. Kernel parity: ``ops.flash_expand`` (interpret-mode Pallas) == the
     pure-jnp oracle, over packed and legacy mirrors, with inactive (−1)
     frontier slots.
  2. Beam parity grid: ``beam_search`` with the fused ``expand()`` hook is
     bit-exact with the gather+scan fallback — ids, dists, and both cost
     counters — across width ∈ {1, 4, 8}, ef ∈ {8, 48}, with/without a
     tombstone mask and a warm visited bitmap, on the ref and
     interpret-mode Pallas dispatch paths.
  3. Packed 4-bit mirror: pack→unpack is the identity, the mirror's HBM
     footprint is halved vs unpacked bytes, snapshots round-trip (format
     v2) and legacy unpacked (v1) state migrates bit-exactly.
  4. Capability hook: only the Flash blocked layout advertises ``expand``
     (the CI guard), and forcing ``fused=True`` elsewhere raises.
  5. The single-sort ``_merge`` is bit-identical to the former
     concatenate + ``top_k`` + gather merge.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import graph
from repro.core import flash as flash_mod
from repro.graph import beam as beam_mod
from repro.graph.beam import beam_search, uses_fused_expand
from repro.graph.hnsw import HNSWParams, build_hnsw
from repro.kernels import ops, ref

PARAMS = HNSWParams(r_upper=8, r_base=16, ef=32, batch=16, max_layers=2)
FLASH_KW = dict(d_f=32, m_f=16, l_f=4, h=8, kmeans_iters=8)


def _rng(seed=0):
    return np.random.default_rng(seed)


@pytest.fixture(scope="module")
def blocked_index(small_data, key):
    data, _ = small_data
    be = graph.make_backend(
        "flash_blocked", data, key, r_for_blocked=PARAMS.r_base, **FLASH_KW
    )
    index, _ = build_hnsw(data, be, params=PARAMS)
    return index


# ---------------------------------------------------------------------------
# 1) kernel parity: interpret-mode Pallas vs oracle
# ---------------------------------------------------------------------------


class TestFlashExpandKernel:
    @pytest.mark.parametrize("w", [1, 4, 8])
    @pytest.mark.parametrize("r", [8, 32])
    def test_packed_parity(self, w, r):
        rng = _rng(w * 131 + r)
        n, m, k = 120, 16, 16
        nodes = jnp.asarray(rng.integers(-1, n, (w,)), jnp.int32)
        adj = jnp.asarray(rng.integers(-1, n, (n, r)), jnp.int32)
        mirror = jnp.asarray(rng.integers(0, 256, (n, r, m // 2)), jnp.uint8)
        adt = jnp.asarray(rng.integers(0, 255, (m, k)), jnp.int32)
        rows_i, sums_i = ops.flash_expand(nodes, adj, mirror, adt, impl="interpret")
        rows_r, sums_r = ref.flash_expand_ref(nodes, adj, mirror, adt)
        np.testing.assert_array_equal(np.asarray(rows_i), np.asarray(rows_r))
        np.testing.assert_array_equal(np.asarray(sums_i), np.asarray(sums_r))

    @pytest.mark.parametrize("m", [7, 16])
    def test_matches_unfused_scan_pipeline(self, m):
        """Fused kernel == gather + unpack + flash_scan_batch, end to end."""
        rng = _rng(m)
        n, w, r, k = 90, 4, 16, 16
        codes = jnp.asarray(rng.integers(0, 16, (n, r, m)), jnp.int32)
        mirror = flash_mod.pack_codes(codes)
        nodes = jnp.asarray(rng.integers(0, n, (w,)), jnp.int32)
        adj = jnp.asarray(rng.integers(-1, n, (n, r)), jnp.int32)
        adt = jnp.asarray(rng.integers(0, 255, (m, k)), jnp.int32)
        rows, sums = ops.flash_expand(nodes, adj, mirror, adt, impl="interpret")
        expect = ops.flash_scan_batch(codes[nodes], adt, impl="ref")
        np.testing.assert_array_equal(np.asarray(sums), np.asarray(expect))
        np.testing.assert_array_equal(np.asarray(rows), np.asarray(adj[nodes]))

    def test_legacy_unpacked_mirror(self):
        """K > 16 coders keep the (n, R, M) int32 mirror; same kernel."""
        rng = _rng(5)
        n, w, r, m, k = 60, 4, 8, 8, 64
        nodes = jnp.asarray(rng.integers(-1, n, (w,)), jnp.int32)
        adj = jnp.asarray(rng.integers(-1, n, (n, r)), jnp.int32)
        mirror = jnp.asarray(rng.integers(0, k, (n, r, m)), jnp.int32)
        adt = jnp.asarray(rng.integers(0, 255, (m, k)), jnp.int32)
        rows_i, sums_i = ops.flash_expand(nodes, adj, mirror, adt, impl="interpret")
        rows_r, sums_r = ref.flash_expand_ref(nodes, adj, mirror, adt)
        np.testing.assert_array_equal(np.asarray(rows_i), np.asarray(rows_r))
        np.testing.assert_array_equal(np.asarray(sums_i), np.asarray(sums_r))

    def test_float_adt(self):
        """float32 tables (rerank-ordering ADTs) go through the same path."""
        rng = _rng(7)
        n, w, r, m, k = 50, 2, 8, 16, 16
        nodes = jnp.asarray(rng.integers(0, n, (w,)), jnp.int32)
        adj = jnp.asarray(rng.integers(-1, n, (n, r)), jnp.int32)
        mirror = jnp.asarray(rng.integers(0, 256, (n, r, m // 2)), jnp.uint8)
        adt = jnp.asarray(rng.uniform(0, 100, (m, k)), jnp.float32)
        _, sums_i = ops.flash_expand(nodes, adj, mirror, adt, impl="interpret")
        _, sums_r = ref.flash_expand_ref(nodes, adj, mirror, adt)
        assert sums_i.dtype == sums_r.dtype == jnp.float32
        np.testing.assert_allclose(
            np.asarray(sums_i), np.asarray(sums_r), rtol=1e-6, atol=1e-4
        )

    def test_mirror_shape_mismatch_raises(self):
        from repro.kernels.flash_expand import flash_expand_pallas

        nodes = jnp.zeros((2,), jnp.int32)
        adj = jnp.zeros((10, 4), jnp.int32)
        adt = jnp.zeros((16, 16), jnp.int32)
        bad = jnp.zeros((10, 4, 5), jnp.uint8)  # expect ceil(16/2) = 8
        with pytest.raises(ValueError, match="mirror"):
            flash_expand_pallas(nodes, adj, bad, adt, interpret=True)


# ---------------------------------------------------------------------------
# 2) beam parity grid: fused expand() vs gather+scan, bit-exact
# ---------------------------------------------------------------------------


def _assert_beams_equal(a, b):
    np.testing.assert_array_equal(np.asarray(a.ids), np.asarray(b.ids))
    np.testing.assert_array_equal(np.asarray(a.dists), np.asarray(b.dists))
    np.testing.assert_array_equal(np.asarray(a.n_dists), np.asarray(b.n_dists))
    np.testing.assert_array_equal(np.asarray(a.n_hops), np.asarray(b.n_hops))


class TestBeamParityGrid:
    def _grid_point(self, index, queries, *, width, ef, banned, warm, n_q=4):
        be = index.backend
        n = be.n
        banned_mask = (
            jnp.asarray(np.arange(n) % 7 == 0) if banned else None
        )
        visited0 = jnp.asarray(np.arange(n) % 5 == 0) if warm else None
        for qi in range(n_q):
            qctx = be.prepare_query(queries[qi])
            kw = dict(
                ef=ef, width=width, banned=banned_mask, visited0=visited0
            )
            fused = beam_search(
                be, qctx, index.adj0, jnp.asarray([0]), fused=True, **kw
            )
            fallback = beam_search(
                be, qctx, index.adj0, jnp.asarray([0]), fused=False, **kw
            )
            _assert_beams_equal(fused, fallback)

    @pytest.mark.parametrize("width", [1, 4, 8])
    @pytest.mark.parametrize("ef", [8, 48])
    def test_ref_grid(self, small_data, blocked_index, width, ef):
        _, queries = small_data
        self._grid_point(
            blocked_index, queries, width=width, ef=ef, banned=False, warm=False
        )

    @pytest.mark.parametrize("width", [1, 4, 8])
    def test_ref_grid_masked(self, small_data, blocked_index, width):
        """Tombstone mask + warm visited bitmap together."""
        _, queries = small_data
        self._grid_point(
            blocked_index, queries, width=width, ef=48, banned=True, warm=True
        )

    @pytest.mark.parametrize("width,ef", [(1, 8), (4, 8), (8, 48)])
    def test_interpret_grid(self, small_data, blocked_index, width, ef):
        """Same contract with every kernel forced through interpret-mode
        Pallas (fused expand AND the fallback's blocked scan)."""
        _, queries = small_data
        ops.set_default_impl("interpret")
        try:
            self._grid_point(
                blocked_index, queries,
                width=width, ef=ef, banned=(width == 4), warm=(width == 8),
                n_q=2,
            )
        finally:
            ops.set_default_impl(None)

    def test_vmapped_fused_matches_fallback(self, small_data, blocked_index):
        """The engine's vmapped acquire path (P queries at once)."""
        _, queries = small_data
        be = blocked_index.backend
        qctx = jax.vmap(be.prepare_query)(queries[:8])

        def run(fused):
            return jax.vmap(
                lambda qc: beam_search(
                    be, qc, blocked_index.adj0, jnp.asarray([0]),
                    ef=32, width=4, fused=fused,
                )
            )(qctx)

        _assert_beams_equal(run(True), run(False))


# ---------------------------------------------------------------------------
# 3) packed codes: round-trip, halved bytes, snapshot v2 + v1 migration
# ---------------------------------------------------------------------------


class TestPackedCodes:
    @pytest.mark.parametrize("m", [2, 7, 16])
    def test_pack_unpack_identity(self, m):
        rng = _rng(m)
        codes = jnp.asarray(rng.integers(0, 16, (40, 6, m)), jnp.int32)
        packed = flash_mod.pack_codes(codes)
        assert packed.dtype == jnp.uint8
        assert packed.shape == (40, 6, (m + 1) // 2)
        np.testing.assert_array_equal(
            np.asarray(flash_mod.unpack_codes(packed, m)), np.asarray(codes)
        )

    def test_mirror_bytes_halved(self, blocked_index):
        be = blocked_index.backend
        n, r = be.nbr_codes.shape[:2]
        m = be.coder.m_f
        assert be.nbr_codes.dtype == jnp.uint8
        # two codewords per byte: half the bytes of one-byte-per-code storage
        assert be.nbr_codes.nbytes == n * r * ((m + 1) // 2)
        assert be.nbr_codes.nbytes * 2 == n * r * m

    def test_snapshot_roundtrip_packed(self, small_data, key, tmp_path):
        from repro.index import AnnIndex
        from repro.serve import load_index, save_index

        data, queries = small_data
        idx = AnnIndex.build(
            data[:600], algo="hnsw", backend="flash_blocked",
            params=PARAMS, backend_kwargs=dict(FLASH_KW),
        )
        save_index(str(tmp_path / "snap"), idx)
        back = load_index(str(tmp_path / "snap"))
        assert back.backend.nbr_codes.dtype == jnp.uint8
        a = idx.search(queries, k=5, ef=32)
        b = back.search(queries, k=5, ef=32)
        np.testing.assert_array_equal(np.asarray(a.ids), np.asarray(b.ids))
        np.testing.assert_array_equal(np.asarray(a.dists), np.asarray(b.dists))

    def test_v1_unpacked_state_migrates(self, blocked_index, small_data):
        """A format-v1 state dict (int32 (n, R, M) mirror) restores to the
        packed layout and serves identical distances."""
        _, queries = small_data
        be = blocked_index.backend
        state = be.state_dict()
        state["nbr_codes"] = np.asarray(
            flash_mod.unpack_codes(jnp.asarray(state["nbr_codes"]), be.coder.m_f),
            dtype=np.int32,
        )
        migrated = type(be).from_state(state)
        assert migrated.nbr_codes.dtype == jnp.uint8
        np.testing.assert_array_equal(
            np.asarray(migrated.nbr_codes), np.asarray(be.nbr_codes)
        )
        qctx = be.prepare_query(queries[0])
        nodes = jnp.asarray([3, 11], jnp.int32)
        a = be.neighbor_dists_batch(qctx, nodes, blocked_index.adj0[nodes])
        b = migrated.neighbor_dists_batch(qctx, nodes, blocked_index.adj0[nodes])
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# 4) capability hook (the CI guard asserts through uses_fused_expand)
# ---------------------------------------------------------------------------


class TestCapabilityHook:
    def test_only_blocked_backend_advertises(self, small_data, key):
        data, _ = small_data
        sample = data[:300]
        for kind in graph.kinds():
            kw = {}
            if kind in ("flash", "flash_blocked"):
                kw = dict(FLASH_KW)
            if kind == "flash_blocked":
                kw["r_for_blocked"] = 16
            if kind == "pq":
                kw = dict(m=8, l_pq=4, kmeans_iters=4)
            if kind == "sq":
                kw = dict(bits=8)
            if kind == "pca":
                kw = dict(alpha=0.9)
            be = graph.make_backend(kind, sample, key, **kw)
            expect = kind == "flash_blocked"
            assert uses_fused_expand(be, 16) is expect, kind
            assert uses_fused_expand(be, 8) is False, kind  # mirror mismatch

    def test_fused_true_raises_without_capability(self, small_data):
        data, queries = small_data
        be = graph.make_backend("fp32", data[:200])
        qctx = be.prepare_query(queries[0])
        adj = jnp.full((200, 8), -1, jnp.int32)
        with pytest.raises(ValueError, match="fused"):
            beam_search(be, qctx, adj, jnp.asarray([0]), ef=8, fused=True)

    def test_base_expand_not_implemented(self, small_data):
        data, queries = small_data
        be = graph.make_backend("fp32", data[:200])
        qctx = be.prepare_query(queries[0])
        with pytest.raises(NotImplementedError, match="expand"):
            be.expand(qctx, jnp.asarray([0]), jnp.full((200, 8), -1, jnp.int32))


# ---------------------------------------------------------------------------
# 5) the single-sort merge is bit-identical to the former top_k merge
# ---------------------------------------------------------------------------


class TestMergeEquivalence:
    @staticmethod
    def _merge_topk(ids_a, d_a, exp_a, ids_b, d_b, exp_b, ef):
        """The pre-refactor merge, kept verbatim as the oracle."""
        ids = jnp.concatenate([ids_a, ids_b])
        d = jnp.concatenate([d_a, d_b])
        exp = jnp.concatenate([exp_a, exp_b])
        _, idx = jax.lax.top_k(-d, ef)
        return ids[idx], d[idx], exp[idx]

    @pytest.mark.parametrize("seed", range(8))
    def test_bit_identical_with_ties(self, seed):
        rng = _rng(seed)
        ef, nb = 16, 24
        # coarse-quantized distances force plenty of exact ties (+inf pads)
        d_a = np.sort(rng.integers(0, 6, ef).astype(np.float32))
        d_a[rng.random(ef) < 0.2] = np.inf
        d_a = np.sort(d_a)
        d_b = rng.integers(0, 6, nb).astype(np.float32)
        d_b[rng.random(nb) < 0.3] = np.inf
        args = (
            jnp.asarray(rng.integers(-1, 40, ef), jnp.int32), jnp.asarray(d_a),
            jnp.asarray(rng.random(ef) < 0.5),
            jnp.asarray(rng.integers(-1, 40, nb), jnp.int32), jnp.asarray(d_b),
            jnp.asarray(rng.random(nb) < 0.5),
        )
        got = beam_mod._merge(*args, ef)
        want = self._merge_topk(*args, ef)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
