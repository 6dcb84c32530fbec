"""The plain reference against numpy, and its bfloat16 control."""

import numpy as np
import pytest

import bench_checkout  # noqa: F401 — puts the harness on the path
from harness import checks, reference


def numpy_topk(x, q, k):
    d = ((q[:, None, :].astype(np.float64) - x[None, :, :]) ** 2).sum(-1)
    ids = np.argsort(d, axis=1, kind="stable")[:, :k]
    return ids, np.take_along_axis(d, ids, axis=1)


@pytest.fixture(scope="module")
def xq():
    rng = np.random.default_rng(3)
    return (rng.normal(size=(700, 24)).astype(np.float32),
            rng.normal(size=(9, 24)).astype(np.float32))


@pytest.mark.parametrize("block", [256, 1 << 16])
def test_topk_matches_numpy_across_blocks(xq, block, monkeypatch):
    monkeypatch.setattr(reference, "BLOCK", block)
    x, q = xq
    ids, d = reference.topk(x, q, 10)
    want_ids, want_d = numpy_topk(x, q, 10)
    np.testing.assert_array_equal(ids, want_ids)
    np.testing.assert_allclose(d, want_d, rtol=1e-4, atol=1e-4)


def test_distances_match_numpy(xq):
    x, q = xq
    ids = np.tile(np.arange(5, dtype=np.int32), (q.shape[0], 1))
    ids[0, 0] = -1
    got = reference.distances(x, q, ids)
    want = ((q[:, None, :].astype(np.float64) - x[ids]) ** 2).sum(-1)
    assert np.isinf(got[0, 0])
    np.testing.assert_allclose(got[:, 1:], want[:, 1:], rtol=1e-6)


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


def test_graph_checks_see_islands_and_bad_edges():
    adj = np.array([[1, -1], [0, 2], [1, -1], [4, -1], [3, 3]], np.int32)
    found = {c.name: c.value for c in checks.graph_checks(adj, np.zeros((0, 5, 2), np.int32), 0)}
    assert found == {"graph_bad_edges": 0.0, "unreachable": 2.0}
    adj[4, 1] = 4
    adj[2, 1] = 9
    found = {c.name: c.value for c in checks.graph_checks(adj, np.zeros((0, 5, 2), np.int32), 0)}
    assert found["graph_bad_edges"] == 2.0
