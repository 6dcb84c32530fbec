"""Generators: the same seed gives the same inputs, for any seed."""

import numpy as np
import pytest

import bench_checkout  # noqa: F401 — puts the harness on the path
from harness import data

GEN = {"kind": "gmm", "n_clusters": 8, "sep": 1.0, "spectrum": "linear",
       "scale_hi": 1.0, "scale_lo": 0.2}


@pytest.mark.parametrize("seed", [0, 12345, 2**31 + 11, 2**40 + 3])
def test_same_seed_same_collection(seed):
    a = np.asarray(data.collection(seed, 500, GEN, 16))
    b = np.asarray(data.collection(seed, 500, GEN, 16))
    np.testing.assert_array_equal(a, b)
    c = np.asarray(data.collection(seed + 1, 500, GEN, 16))
    assert not np.array_equal(a, c)


def test_seeds_past_32_bits_are_distinct():
    lo = np.asarray(data.collection(7, 100, GEN, 8))
    hi = np.asarray(data.collection(7 + 2**32, 100, GEN, 8))
    assert not np.array_equal(lo, hi)


def test_normalised_power_spectrum():
    gen = dict(GEN, spectrum="power", scale_lo=0.05, normalize=True,
               center_scaled=True)
    x = np.asarray(data.collection(1, 400, gen, 64))
    np.testing.assert_allclose(np.linalg.norm(x, axis=1), 1.0, rtol=1e-5)
    s = data.noise_scales(64, gen)
    assert s[0] == pytest.approx(1.0) and s[-1] == pytest.approx(0.05, rel=1e-4)
    assert np.all(np.diff(s) < 0)


def test_every_seed_gets_the_same_work_in_another_order():
    """The same sizes and shape for every seed; the seed draws the rows."""
    cfg = {"n": 300, "dim": 16, "data_seed": 4, "generator": GEN}
    base_a, q_a = data.inputs(cfg, 11, 50)
    base_b, q_b = data.inputs(cfg, 2**33 + 1, 50)
    assert base_a.shape == base_b.shape == (300, 16) and q_a.shape == (50, 16)
    assert not np.array_equal(base_a, base_b)
    assert not np.array_equal(q_a, q_b)
    again = data.inputs(cfg, 11, 50)
    np.testing.assert_array_equal(again[0], base_a)
    np.testing.assert_array_equal(again[1], q_a)
    # another configuration's data_seed draws apart on the same run seed
    other, _ = data.inputs(dict(cfg, data_seed=5), 11, 50)
    assert not np.array_equal(other, base_a)


#: text queries over image rows, in miniature: another noise spectrum, and
#: a shift common to every query
SHIFTED = {"spectrum": "linear", "scale_hi": 0.2, "scale_lo": 1.0, "offset": 4.0}
OOD = {"n": 4000, "dim": 16, "data_seed": 4, "generator": GEN, "queries": SHIFTED}


def mahalanobis2(base, rows):
    """Mean squared Mahalanobis distance of ``rows`` to the mean of
    ``base``, under the sample covariance of ``base``."""
    diff = rows.astype(np.float64) - base.mean(0)
    z = np.linalg.solve(np.cov(base, rowvar=False), diff.T).T
    return float(np.mean(np.sum(diff * z, axis=1)))


@pytest.mark.parametrize("seed", [11, 2**31 + 11])
def test_same_seed_same_shifted_queries(seed):
    base, q = data.inputs(OOD, seed, 50)
    again = data.inputs(OOD, seed, 50)
    np.testing.assert_array_equal(again[0], base)
    np.testing.assert_array_equal(again[1], q)
    assert q.shape == (50, 16) and q.dtype == np.float32
    other = data.inputs(OOD, seed + 1, 50)
    assert not np.array_equal(other[1], q)
    # normalize applies to the queries alone
    _, unit = data.inputs(dict(OOD, queries=dict(SHIFTED, normalize=True)), seed, 50)
    np.testing.assert_allclose(np.linalg.norm(unit, axis=1), 1.0, rtol=1e-5)


def test_base_rows_do_not_depend_on_the_query_count():
    a, _ = data.inputs(OOD, 11, 50)
    b, _ = data.inputs(OOD, 11, 333)
    np.testing.assert_array_equal(a, b)
    # nor on the queries' own parameters
    c, _ = data.inputs(dict(OOD, queries={"offset": 1.0}), 11, 50)
    np.testing.assert_array_equal(a, c)


@pytest.mark.parametrize("seed", [11, 12, 2**33 + 1])
def test_shifted_queries_lie_off_the_base_distribution(seed):
    base, q = data.inputs(OOD, seed, 500)
    _, held = data.inputs({k: v for k, v in OOD.items() if k != "queries"}, seed, 500)
    assert mahalanobis2(base, q) >= 2.0 * mahalanobis2(base, held)


def test_queries_share_the_base_centres():
    """With the base's own spectrum and no shift, the queries are drawn as
    held-out base rows are: around the same centres (queries around
    centres of their own would read about 3.6 times as far)."""
    same = dict(OOD, queries={k: GEN[k] for k in ("spectrum", "scale_hi", "scale_lo")})
    base, q = data.inputs(same, 11, 500)
    _, held = data.inputs({k: v for k, v in OOD.items() if k != "queries"}, 11, 500)
    assert 0.8 <= mahalanobis2(base, q) / mahalanobis2(base, held) <= 1.25


def test_unknown_query_key_is_refused():
    with pytest.raises(ValueError, match="unknown keys"):
        data.inputs(dict(OOD, queries={"ofset": 4.0}), 11, 5)
