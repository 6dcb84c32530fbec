"""The plain reference against numpy, in squared L2 and by inner product,
and its bfloat16 control."""

import numpy as np
import pytest

import bench_checkout  # noqa: F401 — puts the harness on the path
from harness import checks, reference


def numpy_dists(x, q, distance):
    """(Q, N) distances in float64: squared L2, or -<q, x>."""
    q = q.astype(np.float64)
    if distance == "ip":
        return -q @ x.astype(np.float64).T
    return ((q[:, None, :] - x[None, :, :]) ** 2).sum(-1)


def numpy_topk(x, q, k, distance="l2"):
    d = numpy_dists(x, q, distance)
    ids = np.argsort(d, axis=1, kind="stable")[:, :k]
    return ids, np.take_along_axis(d, ids, axis=1)


@pytest.fixture(scope="module")
def xq():
    rng = np.random.default_rng(3)
    return (rng.normal(size=(700, 24)).astype(np.float32),
            rng.normal(size=(9, 24)).astype(np.float32))


@pytest.mark.parametrize("distance", ["l2", "ip"])
@pytest.mark.parametrize("block", [256, 1 << 16])
def test_topk_matches_numpy_across_blocks(xq, block, distance, monkeypatch):
    monkeypatch.setattr(reference, "BLOCK", block)
    x, q = xq
    ids, d = reference.topk(x, q, 10, distance=distance)
    want_ids, want_d = numpy_topk(x, q, 10, distance)
    np.testing.assert_array_equal(ids, want_ids)
    np.testing.assert_allclose(d, want_d, rtol=1e-4, atol=1e-4)
    assert np.all(np.diff(d, axis=1) >= 0)  # ascending, as `malformed` asks


@pytest.mark.parametrize("distance", ["l2", "ip"])
def test_distances_match_numpy(xq, distance):
    x, q = xq
    ids = np.tile(np.arange(5, dtype=np.int32), (q.shape[0], 1))
    ids[0, 0] = -1
    got = reference.distances(x, q, ids, distance=distance)
    want = np.take_along_axis(numpy_dists(x, q, distance), np.maximum(ids, 0), axis=1)
    assert np.isinf(got[0, 0])
    # an inner product can sit near 0, so it alone needs an absolute tolerance
    atol = 1e-5 if distance == "ip" else 0
    np.testing.assert_allclose(got[:, 1:], want[:, 1:], rtol=1e-6, atol=atol)


def test_unknown_distance_is_refused(xq):
    x, q = xq
    with pytest.raises(ValueError, match="unknown distance"):
        reference.topk(x, q, 10, distance="cosine")
    with pytest.raises(ValueError, match="unknown distance"):
        reference.distance_of({"distance": "cos"})
    assert reference.distance_of({}) == "l2"


@pytest.mark.parametrize("metric, distance", [
    ("inner product", None), ("ip", "l2"), ("MIPS over image rows", None),
    ("l2", "ip"), ("cosine (unit rows, served through the index's L2)", "ip"),
])
def test_metric_and_distance_that_disagree_are_refused(metric, distance):
    """The prose ``metric`` and the ``distance`` the harness ranks by state
    one fact twice; where they disagree the configuration is refused, not
    ranked by the default."""
    cfg = {"metric": metric} if distance is None else {"metric": metric, "distance": distance}
    with pytest.raises(ValueError, match="disagree"):
        reference.distance_of(cfg)


@pytest.mark.parametrize("metric, distance, want", [
    ("l2", None, "l2"), ("cosine (unit rows, served through the index's L2)", None, "l2"),
    ("inner product", "ip", "ip"), ("ip", "ip", "ip"), (None, "ip", "ip"),
])
def test_metric_and_distance_that_agree_are_taken(metric, distance, want):
    cfg = {k: v for k, v in (("metric", metric), ("distance", distance)) if v is not None}
    assert reference.distance_of(cfg) == want


def test_recall_counts_shared_ids():
    want = np.array([[1, 2, 3, 4], [5, 6, 7, 8]])
    got = np.array([[4, 3, 9, -1], [5, 6, 7, 8]])
    assert reference.recall_at_k(got, want) == 6 / 8


def test_bfloat16_control_fails_the_distance_gap(xq):
    x, q = xq
    ids, d = reference.topk(x, q, 10)
    assert checks.dist_gap(ids, reference.distances(x, q, ids), x, q) < 1e-6
    c_ids, c_d = reference.topk(x, q, 10, precision="bfloat16")
    assert checks.dist_gap(c_ids, c_d, x, q) > 1e-3


def test_bfloat16_control_fails_the_inner_product_gap():
    """Rows that are not unit-normalised, at norms from 0.5 to 4, and
    queries shifted off the rows: the float32 reference reads within
    10^-5 of ||q||·||x||, the bfloat16 control above 2·10^-4."""
    rng = np.random.default_rng(5)
    x = rng.normal(size=(2000, 96)) * rng.uniform(0.5, 4.0, size=(2000, 1)) / np.sqrt(96)
    q = rng.normal(size=(64, 96)) / np.sqrt(96) + 0.3 * rng.normal(size=96) / np.sqrt(96)
    x, q = x.astype(np.float32), q.astype(np.float32)
    ids, d = reference.topk(x, q, 10, distance="ip")
    assert checks.dist_gap(ids, d, x, q, distance="ip") <= 1e-5
    exact = reference.distances(x, q, ids, distance="ip")
    assert checks.dist_gap(ids, exact, x, q, distance="ip") == 0.0
    c_ids, c_d = reference.topk(x, q, 10, distance="ip", precision="bfloat16")
    assert checks.dist_gap(c_ids, c_d, x, q, distance="ip") > 2e-4


def test_inner_product_gap_scales_with_the_norms():
    """A product near 0 is no reason for a wide gap: one float32 rounding
    of a 2.0·2.0 product reads about 10^-8 of ||q||·||x||, not of the
    product; the L2 formula on the same numbers would divide by the
    floor."""
    q = np.array([[2.0, 0.0]], np.float32)
    x = np.array([[1e-6, 2.0], [2.0, 0.0]], np.float32)
    ids = np.array([[0, 1]], np.int32)
    exact = reference.distances(x, q, ids, distance="ip")
    np.testing.assert_allclose(exact, [[-2e-6, -4.0]], rtol=1e-6)
    got = exact + np.array([[1e-7, 0.0]], np.float32)
    assert checks.dist_gap(ids, got, x, q, distance="ip") == pytest.approx(
        1e-7 / 4.0, rel=1e-3)
    # the sign is part of the answer: +<q, x> in place of -<q, x>
    assert checks.dist_gap(ids, -exact, x, q, distance="ip") == pytest.approx(2.0, rel=1e-6)


def test_graph_checks_see_islands_and_bad_edges():
    adj = np.array([[1, -1], [0, 2], [1, -1], [4, -1], [3, 3]], np.int32)
    found = {c.name: c.value for c in checks.graph_checks(adj, np.zeros((0, 5, 2), np.int32), 0)}
    assert found == {"graph_bad_edges": 0.0, "unreachable": 2.0}
    adj[4, 1] = 4
    adj[2, 1] = 9
    found = {c.name: c.value for c in checks.graph_checks(adj, np.zeros((0, 5, 2), np.int32), 0)}
    assert found["graph_bad_edges"] == 2.0
