"""Unit + property tests for repro.core (the paper's §3.1–§3.3 machinery)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro import core
from repro.core import kmeans as km
from repro.core import pca as pca_mod
from repro.core import quantize as qz


# ---------------------------------------------------------------------------
# PCA
# ---------------------------------------------------------------------------


class TestPCA:
    def test_orthonormal_components(self, small_data):
        data, _ = small_data
        model = pca_mod.fit_pca(data)
        c = np.asarray(model.components)
        np.testing.assert_allclose(c.T @ c, np.eye(c.shape[1]), atol=1e-4)

    def test_eigenvalues_descending(self, small_data):
        data, _ = small_data
        model = pca_mod.fit_pca(data)
        ev = np.asarray(model.eigenvalues)
        assert np.all(np.diff(ev) <= 1e-5)

    def test_norm_preserved_full_rank(self, small_data):
        data, _ = small_data
        model = pca_mod.fit_pca(data)
        z = pca_mod.transform(model, data[:50])
        orig = jnp.linalg.norm(data[:50] - model.mean, axis=-1)
        np.testing.assert_allclose(
            np.asarray(jnp.linalg.norm(z, axis=-1)), np.asarray(orig), rtol=1e-4
        )

    def test_variance_dim_monotone(self, small_data):
        data, _ = small_data
        model = pca_mod.fit_pca(data)
        d50 = pca_mod.variance_dim(model, 0.5)
        d90 = pca_mod.variance_dim(model, 0.9)
        d99 = pca_mod.variance_dim(model, 0.99)
        assert 1 <= d50 <= d90 <= d99 <= model.dim

    def test_reconstruction_error_decreases_with_d(self, small_data):
        data, _ = small_data
        model = pca_mod.fit_pca(data)
        errs = [
            float(jnp.mean(pca_mod.reconstruction_error(model, data[:100], d)))
            for d in (8, 24, 48)
        ]
        assert errs[0] >= errs[1] >= errs[2]
        assert errs[2] < 1e-3  # full rank ⇒ exact


# ---------------------------------------------------------------------------
# k-means
# ---------------------------------------------------------------------------


class TestKMeans:
    def test_fit_reduces_inertia(self, key):
        x = jax.random.normal(key, (512, 8))
        c0, inertia0 = km.kmeans_fit(key, x, k=16, iters=0)
        c1, inertia1 = km.kmeans_fit(key, x, k=16, iters=20)
        assert float(inertia1) <= float(inertia0) + 1e-3

    def test_batched_matches_single(self, key):
        xs = jax.random.normal(key, (4, 256, 6))
        cb, _ = km.kmeans_fit_batched(key, xs, k=8, iters=10)
        assert cb.shape == (4, 8, 6)
        # each subspace's codebook explains its own data better than another's
        a0 = km.assign_codes(xs[0], cb[0])
        assert a0.shape == (256,) and int(a0.max()) < 8

    def test_no_empty_clusters_on_clustered_data(self, key):
        centers = jax.random.normal(key, (8, 4)) * 5
        idx = jax.random.randint(key, (400,), 0, 8)
        x = centers[idx] + 0.1 * jax.random.normal(key, (400, 4))
        cb, _ = km.kmeans_fit(key, x, k=8, iters=25)
        assign = km.assign_codes(x, cb)
        # all 8 clusters should be used
        assert len(np.unique(np.asarray(assign))) == 8


# ---------------------------------------------------------------------------
# Scalar quantization + table quantization (Eq. 9)
# ---------------------------------------------------------------------------


class TestQuantize:
    def test_sq_roundtrip_bound(self, small_data):
        data, _ = small_data
        params = qz.sq_fit(data, bits=8)
        dec = qz.sq_decode(params, qz.sq_encode(params, data[:100]))
        # max error ≤ one quantization step per dim
        step = np.asarray(params.scale) / 255.0
        err = np.abs(np.asarray(dec - data[:100]))
        assert np.all(err <= step[None, :] + 1e-6)

    @pytest.mark.parametrize("bits", [2, 4, 8])
    def test_sq_bits_levels(self, small_data, bits):
        data, _ = small_data
        params = qz.sq_fit(data, bits=bits)
        codes = qz.sq_encode(params, data[:64])
        assert int(codes.max()) <= 2**bits - 1 and int(codes.min()) >= 0

    def test_table_quant_monotone_affine(self):
        """Eq. 9 preserves comparisons of subspace *sums* (paper §3.3.3)."""
        rng = np.random.default_rng(0)
        tq = qz.fit_table_quant(
            jnp.zeros((4,)), jnp.asarray([1.0, 1.0, 1.0, 1.0]), h=8
        )
        t = jnp.asarray(rng.uniform(0, 1, (4, 16)).astype(np.float32))
        q = qz.quantize_table(tq, t)
        assert int(q.max()) <= 255 and int(q.min()) >= 0
        # sums of quantized entries track sums of true entries within M levels
        sums_t = np.asarray(t.sum(0))
        sums_q = np.asarray(q.sum(0), dtype=np.float64)
        scale = 255.0 / float(tq.delta)
        # |q_sum − scale·(t_sum − 4·dist_min)| ≤ M rounding steps
        recon = sums_q / scale
        assert np.all(np.abs(recon - sums_t) <= 4.5 / scale * 1.0 + 4 * float(tq.delta) / 255.0)

    def test_pack4_roundtrip(self, key):
        codes = jax.random.randint(key, (33, 16), 0, 16)
        packed = qz.pack4(codes)
        assert packed.shape == (33, 8) and packed.dtype == jnp.uint8
        np.testing.assert_array_equal(np.asarray(qz.unpack4(packed)), np.asarray(codes))

    def test_pack4_odd_raises(self):
        with pytest.raises(ValueError):
            qz.pack4(jnp.zeros((4, 3), jnp.int32))


# ---------------------------------------------------------------------------
# Lemma 1 / Theorem 1 (§3.1)
# ---------------------------------------------------------------------------


class TestMargin:
    @given(
        st.integers(min_value=0, max_value=2**31 - 1),
        st.integers(min_value=2, max_value=16),
    )
    @settings(max_examples=30, deadline=None)
    def test_lemma1_sign_equivalence(self, seed, dim):
        """sign(δ(u,v) − δ(u,w)) == sign(e·u − b) for random real vectors."""
        rng = np.random.default_rng(seed)
        u, v, w = rng.normal(size=(3, dim)).astype(np.float32)
        margin = float(core.hyperplane_margin(jnp.asarray(u), jnp.asarray(v), jnp.asarray(w)))
        direct = float(np.sum((u - v) ** 2) - np.sum((u - w) ** 2))
        # e·u − b has the sign of δ²(u,v) − δ²(u,w) ... times −2? Check both.
        assert np.sign(margin) == np.sign(direct) or abs(direct) < 1e-4

    @given(st.integers(min_value=0, max_value=2**31 - 1))
    @settings(max_examples=20, deadline=None)
    def test_theorem1_margin_bound_sufficient(self, seed):
        """When |e·u − b| ≥ |E|, compressed and true comparisons agree."""
        rng = np.random.default_rng(seed)
        u, v, w = rng.normal(size=(3, 12)).astype(np.float32)
        noise = rng.normal(size=(3, 12)).astype(np.float32) * 0.05
        up, vp, wp = u - noise[0], v - noise[1], w - noise[2]
        margin = core.hyperplane_margin(jnp.asarray(u), jnp.asarray(v), jnp.asarray(w))
        err = core.error_term(
            *(jnp.asarray(x) for x in (u, v, w)),
            *(jnp.asarray(x) for x in noise),
        )
        if abs(float(margin)) >= abs(float(err)):
            s_true = core.comparison_sign(
                jnp.asarray(u), jnp.asarray(v), jnp.asarray(w)
            )
            s_comp = core.comparison_sign(
                jnp.asarray(up), jnp.asarray(vp), jnp.asarray(wp)
            )
            assert float(s_true) == float(s_comp) or float(s_true) == 0.0

    def test_error_term_zero_for_exact_codes(self, key):
        u, v, w = jax.random.normal(key, (3, 8))
        z = jnp.zeros((8,))
        assert float(core.error_term(u, v, w, z, z, z)) == 0.0

    def test_margin_rate_improves_with_subspaces(self, small_data, key):
        """More subspaces at fixed d_F ⇒ finer codes ⇒ better sign agreement.

        (Note the paper's Finding 2: increasing d_F at fixed M_F can *hurt* —
        fewer dims per bit budget beats more dims; the monotone axis is M_F.)
        """
        data, _ = small_data
        triples = core.sample_triples(key, data, n_triples=128, pool=1024)
        rates = []
        for m_f in (4, 16):
            coder = core.fit_flash(key, data, d_f=32, m_f=m_f, kmeans_iters=6)
            _, sign = core.margin_satisfaction_rate(
                triples, lambda x, c=coder: core.reconstruct(c, x)
            )
            rates.append(float(sign))
        assert rates[1] >= rates[0]

    def test_calibrate_selects_feasible(self, small_data, key):
        data, _ = small_data

        def factory(d_f):
            coder = core.fit_flash(key, data, d_f=d_f, m_f=8, kmeans_iters=4)
            return (lambda x: core.reconstruct(coder, x)), d_f * 0.5

        best = core.calibrate(
            key, data, factory, [{"d_f": 8}, {"d_f": 32}],
            target_rate=0.0, n_triples=64,
        )
        assert best["code_bytes"] == 4.0  # smallest feasible at target 0


# ---------------------------------------------------------------------------
# Flash coder (§3.3)
# ---------------------------------------------------------------------------


class TestFlashCoder:
    @pytest.fixture(scope="class")
    def coder(self, small_data, key):
        data, _ = small_data
        return core.fit_flash(key, data, d_f=32, m_f=16, l_f=4, h=8, kmeans_iters=10)

    def test_shapes_and_ranges(self, coder, small_data):
        data, _ = small_data
        assert coder.m_f == 16 and coder.k == 16 and coder.ds == 2
        codes = core.encode(coder, data[:64])
        assert codes.shape == (64, 16)
        assert int(codes.min()) >= 0 and int(codes.max()) < 16
        assert int(coder.sdt_q.min()) >= 0 and int(coder.sdt_q.max()) <= 255

    def test_adt_fits_simd_register(self, coder):
        """K·H = 16·8 = 128 bits per subspace table (paper's register budget)."""
        assert coder.k * int(coder.h_bits) == 128

    def test_query_ctx_codes_match_encode(self, coder, small_data):
        data, _ = small_data
        ctx = core.query_ctx(coder, data[7])
        codes = core.encode(coder, data[7:8])[0]
        np.testing.assert_array_equal(np.asarray(ctx.codes), np.asarray(codes))

    def test_sdc_self_distance_near_zero(self, coder, small_data):
        data, _ = small_data
        codes = core.encode(coder, data[:16])
        self_d = core.sdc_lookup(coder, codes, codes)
        assert int(jnp.max(self_d)) <= coder.m_f  # ≤ 1 rounding level per subspace

    @pytest.mark.parametrize("top", [255, 2**16 - 1])
    def test_sdc_table_bit_exact_with_lookup(self, coder, small_data, key, top):
        """The one-hot MXU form gives the gather's integer sums exactly,
        also for levels wider than bf16's 8-bit mantissa."""
        data, _ = small_data
        sdt = jax.random.randint(key, coder.sdt_q.shape, 0, top + 1)
        wide = coder._replace(sdt_q=sdt)
        codes = core.encode(coder, data[:96])
        want = core.sdc_lookup(wide, codes[:, None], codes[None, :])
        got = core.sdc_table(wide, codes)
        assert got.dtype == jnp.float32
        np.testing.assert_array_equal(
            np.asarray(got), np.asarray(want).astype(np.float32)
        )

    def test_adc_ordering_tracks_true_ordering(self, coder, small_data):
        data, _ = small_data
        q = data[0]
        ctx = core.query_ctx(coder, q)
        codes = core.encode(coder, data[:256])
        est = np.asarray(core.adc_lookup(ctx.adt_q, codes))
        true = np.asarray(jnp.sum((data[:256] - q) ** 2, axis=-1))
        top_est = set(np.argsort(est)[:20].tolist())
        top_true = set(np.argsort(true)[:20].tolist())
        assert len(top_est & top_true) >= 10  # coarse codes, generous bound

    def test_adt_sdt_share_scale(self, coder, small_data):
        """CA (ADT) and NS (SDT) values must be mutually comparable (§3.3.3)."""
        data, _ = small_data
        q = data[3]
        ctx = core.query_ctx(coder, q)
        codes = core.encode(coder, data[:128])
        adc = np.asarray(core.adc_lookup(ctx.adt_q, codes), np.float64)
        sdc = np.asarray(core.sdc_lookup(coder, ctx.codes[None], codes), np.float64)
        # both approximate δ²(q, x) on the same quantized scale
        mask = adc > np.percentile(adc, 20)  # skip tiny distances
        rel = np.abs(adc[mask] - sdc[mask]) / np.maximum(adc[mask], 1)
        assert np.median(rel) < 0.5

    def test_neighbor_block_layout_roundtrip(self, key):
        codes = jax.random.randint(key, (32, 16), 0, 16)
        blocks = core.to_neighbor_blocks(codes, 16)
        assert blocks.shape == (2, 16, 16)
        np.testing.assert_array_equal(
            np.asarray(core.from_neighbor_blocks(blocks)), np.asarray(codes)
        )

    def test_estimate_distance_monotone(self, coder):
        sums = jnp.asarray([0, 100, 200], jnp.int32)
        est = np.asarray(core.estimate_distance(coder, sums))
        assert est[0] < est[1] < est[2]


# ---------------------------------------------------------------------------
# Baselines (§3.2)
# ---------------------------------------------------------------------------


class TestBaselines:
    def test_pq_reconstruct_better_with_more_subspaces(self, small_data, key):
        data, _ = small_data
        errs = []
        for m in (4, 16):
            pq = core.fit_pq(key, data, m=m, l_pq=6, kmeans_iters=6)
            rec = core.pq_reconstruct(pq, data[:64])
            errs.append(float(jnp.mean(jnp.sum((rec - data[:64]) ** 2, -1))))
        assert errs[1] <= errs[0]

    def test_pq_sdc_approximates_adc(self, small_data, key):
        data, _ = small_data
        pq = core.fit_pq(key, data, m=8, l_pq=6, kmeans_iters=6)
        codes = core.pq_encode(pq, data[:64])
        tab = core.pq_adc_table(pq, data[0])
        adc = np.asarray(core.adc_lookup(tab, codes))
        sdc = np.asarray(core.pq_sdc_lookup(pq, codes[0:1], codes))
        assert np.corrcoef(adc, sdc)[0, 1] > 0.8

    def test_sq_dist_matches_decoded(self, small_data):
        data, _ = small_data
        sq = core.fit_sq(data, bits=8)
        qa = core.sq_encode(sq, data[:8])
        qb = core.sq_encode(sq, data[8:16])
        d_int = np.asarray(core.sq_dist(sq, qa, qb))
        da = core.sq_reconstruct(sq, data[:8])
        db = core.sq_reconstruct(sq, data[8:16])
        d_dec = np.asarray(jnp.sum((da - db) ** 2, -1))
        np.testing.assert_allclose(d_int, d_dec, rtol=1e-4, atol=1e-4)

    def test_pca_coder_variance_selection(self, small_data):
        data, _ = small_data
        c = core.fit_pca_coder(data, alpha=0.9)
        assert 1 <= c.d <= data.shape[1]
        z = core.pca_encode(c, data[:32])
        assert z.shape == (32, c.d)
